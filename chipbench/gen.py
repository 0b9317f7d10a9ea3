"""Traffic generator: waves of requests from a traffic file and a seed.

A traffic file fixes the wave: ``slots`` requests, their prompt and
generation lengths drawn once under the file's ``sizes_seed``.  So every wave
of every seed does the same work; the run's seed only assigns the lengths to
slots and draws the prompt ids.

A length is given by the mean that the file's public source publishes.  The
sources give a mean and no more of the shape, so a length is drawn from the
distribution of most entropy with that mean, the exponential, rounded to
whole tokens and clipped to ``[min, max]``: each clip is a cut that the file
names.
"""
import numpy as np


def _lengths(rng, spec, n):
    x = np.rint(rng.exponential(spec["mean"], n))
    return np.clip(x, spec["min"], spec["max"]).astype(np.int64)


def wave_sizes(traffic):
    """(prompt lengths, generation lengths) of one wave, fixed by the file."""
    rng = np.random.default_rng(traffic["sizes_seed"])
    n = traffic["slots"]
    p = _lengths(rng, traffic["prompt"], n)
    g = _lengths(rng, traffic["gen"], n)
    if np.any(p + g > traffic["max_len"] - 1):
        raise ValueError("a request of this traffic would not fit the cache: "
                         f"max P + G = {int((p + g).max())} > max_len - 1")
    return p, g


def wave(traffic, rng, vocab_size):
    """One wave: a list of (prompt ids int32, generation length), in slot
    order.  Prompt ids are uniform in [1, vocab_size)."""
    p, g = wave_sizes(traffic)
    order = rng.permutation(len(p))
    return [(rng.integers(1, vocab_size, int(p[i])).astype(np.int32), int(g[i]))
            for i in order]
