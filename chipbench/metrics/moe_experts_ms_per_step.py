"""operators: device ms a step spends in the expert FFN (``moe/experts``,
inside ``models/moe``'s ``moe`` scope: the grouped expert matmuls and the
gating between them)."""
from chipbench import scopes


def read(w):
    return scopes.ms_under(w, "moe/experts")
