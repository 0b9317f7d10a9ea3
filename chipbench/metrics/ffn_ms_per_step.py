"""operators: device ms a step spends in the feed-forward layers (``mlp``
scope of ``models/mlp``, ``moe`` of ``models/moe``)."""
from chipbench import scopes


def read(w):
    return scopes.ms_per_step(w, "mlp", "moe")
