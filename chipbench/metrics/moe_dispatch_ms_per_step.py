"""operators: device ms a step spends in the expert layer around its
experts: under ``moe`` but outside ``moe/experts`` (the router and its top-k
in ``moe/route``, sorting and gathering rows in ``moe/dispatch``, putting
them back and weighting them in ``moe/combine``).  Nothing where the step
names no ``moe/experts``."""
from chipbench import scopes


def read(w):
    experts = scopes.ms_under(w, "moe/experts")
    if experts is None:
        return None
    return scopes.ms_under(w, "moe") - experts
