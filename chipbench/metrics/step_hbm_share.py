"""step: HBM bytes the window's steps need (``counts.step_bytes``) over the
window's time at the chip's peak HBM bandwidth (%)."""
from chipbench import counts
from chipbench.driver import occupancy


def read(w):
    nbytes = 0.0
    for x in w.waves:
        occ, _ = occupancy(x.prompt_len, x.gen_len, len(x.inflight))
        nbytes += sum(counts.step_bytes(w.sizes, int(n), s) for s, n in enumerate(occ))
    return 100.0 * nbytes / (w.seconds * w.peaks["hbm_bytes_per_s"])
