"""step: device ms a step spends writing and moving the K/V cache: the
cache write (``attn/kv_write``, each layer's new rows written into the
donated cache after the loop) and the layer loop's own work (``layers``:
the loop and the slicing of each layer's parameters out of the stack)."""
from chipbench import scopes


def read(w):
    return scopes.ms_per_step(w, "attn/kv_write", "layers")
