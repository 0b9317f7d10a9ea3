"""scheduler: occupied slot-steps that fed a prompt token, over all
occupied slot-steps (%)."""
from chipbench.driver import occupancy


def read(w):
    occ = pre = 0
    for x in w.waves:
        o, p = occupancy(x.prompt_len, x.gen_len, len(x.inflight))
        occ, pre = occ + int(o.sum()), pre + int(p.sum())
    return 100.0 * pre / occ
