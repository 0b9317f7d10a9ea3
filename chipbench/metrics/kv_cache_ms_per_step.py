"""step: device ms a step spends writing and moving the K/V cache: the
cache write (``attn/kv_write``) and the layer loop's own work (``layers``:
slicing each layer's parameters and cache, restacking the new cache)."""
from chipbench import scopes


def read(w):
    return scopes.ms_per_step(w, "attn/kv_write", "layers")
