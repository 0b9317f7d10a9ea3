"""operators: device ms a step spends in the head (``head`` scope: final
norm and ``models/common.lm_logits``) and in the engine's argmax programs."""
from chipbench import scopes


def read(w):
    return scopes.ms_per_step(w, "head")
