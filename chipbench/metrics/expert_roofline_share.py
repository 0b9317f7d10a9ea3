"""kernels: the grouped expert matmul's share of its roofline (%).  Its
least time on the chip, the larger of its FLOPs at the peak bf16 rate and
its HBM bytes at the peak bandwidth (``expert_flops`` and ``expert_bytes``
of the configuration's reference module), over its device time a step
(``moe/experts``).  Counted for what the step gives it: every slot of the
traffic, occupied or not, sends its k rows through each layer."""
from chipbench import reference, scopes


def read(w):
    ms = scopes.ms_under(w, "moe/experts")
    ref = reference.for_config(w.sizes)
    if ms is None or not hasattr(ref, "expert_flops"):
        return None
    s, slots = w.sizes, w.traffic["slots"]
    layer_s = max(
        ref.expert_flops(s, slots * s["num_experts_per_tok"]) / w.peaks["bf16_flops_per_s"],
        ref.expert_bytes(s, slots) / w.peaks["hbm_bytes_per_s"])
    return 100.0 * s["num_hidden_layers"] * layer_s / (ms / 1e3)
