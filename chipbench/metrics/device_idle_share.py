"""device: 1 - (union of device-op intervals) / traced window, from the
profiler's trace (%)."""


def read(w):
    if w.trace is None:
        return None
    return 100.0 * w.trace["idle_share"]
