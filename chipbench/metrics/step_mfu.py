"""step: FLOPs the window's live tokens need (``counts.step_flops``) over
the window's time at the chip's peak bf16 rate (%)."""
from chipbench import counts
from chipbench.driver import occupancy


def read(w):
    flops = 0
    for x in w.waves:
        occ, _ = occupancy(x.prompt_len, x.gen_len, len(x.inflight))
        flops += sum(counts.step_flops(w.sizes, int(n), s) for s, n in enumerate(occ))
    return 100.0 * flops / (w.seconds * w.peaks["bf16_flops_per_s"])
