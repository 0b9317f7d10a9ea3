"""Reduction of a profiler trace (``.xplane.pb``) to device busy time, idle
share and a breakdown.

The window is the host span ``traced_window`` that the benchmark opens
around the traced steps.  Busy time is the union of the intervals of the
operations on each device's ``XLA Ops`` line inside the window, averaged over
the devices that ran any.  Each idle gap between busy intervals is named by
what the host was doing at its midpoint: the benchmark's own span that
covers it and the innermost host event there.
"""
import collections
import glob
import os
import re

WINDOW = "traced_window"
OPS_LINE = "XLA Ops"
OWN_SPANS = ("wave.build", "engine.admit", "engine.step", "serve_step.call")


def op_name(long_name):
    """'%fusion.3 = bf16[...] fusion(...), kind=kLoop, ...' -> '%fusion.3 fusion'."""
    name, _, rest = long_name.partition(" = ")
    m = re.search(r" ([a-z][a-z0-9_-]*)\(", " " + rest)
    return f"{name} {m.group(1)}" if m else name


def find_xplane(trace_dir):
    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def merge(intervals):
    """Union of (start, end) intervals, sorted and disjoint."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _host_events(planes):
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    yield ev.name, ev.start_ns, ev.end_ns


def reduce(planes, top=10):
    """{'window_s', 'busy_s', 'idle_share', 'device_ops', 'idle_gaps'} of the
    planes of one trace (``ProfileData(...).planes``)."""
    planes = list(planes)
    host = list(_host_events(planes))
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if len(windows) != 1:
        raise ValueError(f"expected one {WINDOW!r} host span, found {len(windows)}")
    w0, w1 = windows[0]
    per_device, op_time = [], collections.Counter()
    for plane in planes:
        if not plane.name.startswith("/device:TPU"):
            continue
        spans = []
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                if e > s:
                    spans.append((s, e))
                    op_time[op_name(ev.name)] += (e - s) / 1e9
        if spans:
            per_device.append(merge(spans))
    if not per_device:
        raise ValueError("no device operation inside the traced window")
    busy = sum(sum(e - s for s, e in m) for m in per_device) / len(per_device) / 1e9
    window = (w1 - w0) / 1e9
    # gaps of the first device, each put down to what the host was doing
    edges = [w0] + [x for se in per_device[0] for x in se] + [w1]
    gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
            if edges[i + 1] > edges[i]]
    named = collections.Counter()
    for s, e in gaps:
        named[_what_host_did(host, (s + e) // 2)] += (e - s) / 1e9
    return {"window_s": window, "busy_s": busy, "idle_share": 1.0 - busy / window,
            "device_ops": [[n, t] for n, t in op_time.most_common(top)],
            "idle_gaps": [[n, t] for n, t in named.most_common(top)]}


def _what_host_did(host, t):
    own, inner = None, None
    for name, s, e in host:
        if s <= t < e and name != WINDOW:
            if name in OWN_SPANS and (own is None or s > own[1]):
                own = (name, s)
            elif name not in OWN_SPANS and (inner is None or s > inner[1]):
                inner = (name, s)
    parts = [x[0] for x in (own, inner) if x is not None]
    return " > ".join(parts) if parts else "outside any host event"


def reduce_dir(trace_dir, top=10):
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)).planes, top)
