"""operators: device ms a step spends in attention (``attn`` scope of
``models/attention.attn_decode``: projections, RoPE, scores, softmax, PV,
output projection), the cache write (``attn/kv_write``) left out."""
from chipbench import scopes


def read(w):
    return scopes.ms_per_step(w, "attn")
