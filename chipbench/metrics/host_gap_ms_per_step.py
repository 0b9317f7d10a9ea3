"""scheduler: ms a step the device idles while the engine's host code runs:
idle time of the trace while the host is in ``serve.feed``,
``serve.bookkeep`` or ``serve.admit``, or between ``serve.step`` spans."""
from chipbench import scopes


def read(w):
    r = scopes.for_window(w)
    if r is None:
        return None
    return 1e3 * sum(r["idle_s"].get(k, 0.0) for k in scopes.HOST_GAPS) / r["steps"]
