"""scheduler: programs the device runs a step: ``XLA Modules`` events
started in the traced window over the ``serve.step`` spans in it (count)."""
from chipbench import scopes


def read(w):
    r = scopes.for_window(w)
    if r is None:
        return None
    return r["programs"] / r["steps"]
