"""scheduler: occupied slots over slots, averaged over the window's steps,
as the engine's own ``inflight`` counted them (%)."""
import numpy as np


def read(w):
    steps = sum(len(x.inflight) for x in w.waves)
    occ = sum(int(np.sum(x.inflight)) for x in w.waves)
    return 100.0 * occ / (steps * w.traffic["slots"])
