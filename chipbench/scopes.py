"""Per-operator device time and per-step host gaps of a profiler trace.

The served step names its parts with ``jax.named_scope`` (``repro.models``:
``embed``, ``layers``, ``attn``, ``attn/kv_write``, ``mlp``, ``moe``,
``head``) and the engine its work with host spans (``repro.launch.serve``:
``serve.step`` and its phases ``serve.feed``, ``serve.dispatch``,
``serve.sample``, ``serve.sync``, ``serve.bookkeep``; ``serve.admit``).
The reduction of a trace:

- steps: the ``serve.step`` spans entirely inside ``traced_window``;
- device time: each operation on a device's ``XLA Ops`` line inside the
  window counts its self time (its interval less what operations nested in
  it cover, so a ``while`` and its body are not counted twice) to one scope.
  An operation of the program ``jit_serve_step`` goes to the innermost
  scope of its ``tf_op`` (the ``op_name`` metadata of its HLO instruction,
  kept in the trace); with no scope, to ``layers`` where it is the layer
  loop's ``while`` (which the TPU profiler leaves without ``tf_op``) or runs
  inside an operation of ``layers``, else to ``other``.  The
  engine's argmax programs go to ``head`` whole; any other program to
  ``other``;
- scope paths: the same self time also goes to the operation's path, its
  scope followed by the scopes the program opened inside it (``moe/experts``
  for a ``tf_op`` '.../moe/experts/dot_general:'; the parts JAX writes
  itself, such as ``while``, ``body`` or an einsum's subscripts, left
  out).  A metric file reads a nested scope by its path (``ms_under``)
  with no entry in the tables below; the scope it is nested in keeps
  counting its time;
- idle time: each instant the first device runs no operation goes to the
  innermost ``serve.*`` phase span the host is in then, to ``serve.step``
  where it is in a step but in none of its phases, or else to "between
  serve.step spans" (a gap is split where host spans begin and end, not put
  down whole to the span at its midpoint: that flips from run to run).

    python3 -m chipbench.scopes <trace dir or .xplane.pb>

prints the whole attribution (``--trace 1`` leaves its trace in
``.chipbench/trace``).  A trace of a program without these names (no
``serve.step`` span in the window) reduces to zero steps, and the readers
of ``chipbench/metrics/`` then report nothing.
"""
import bisect
import collections
import functools
import heapq
import pathlib
import re
import sys

from chipbench import xtrace

TRACE_DIR = pathlib.Path(__file__).resolve().parent.parent / ".chipbench" / "trace"
SCOPES = ("embed", "layers", "attn", "attn/kv_write", "mlp", "moe", "head")
OTHER = "other"
STEP_PROGRAM = "jit_serve_step"
HEAD_PROGRAMS = ("jit_sample_argmax", "jit_sample_guarded_argmax")
STEP = "serve.step"
BETWEEN = "between serve.step spans"
HOST_GAPS = ("serve.feed", "serve.bookkeep", "serve.admit", BETWEEN)
MODULES_LINE = "XLA Modules"
_SCOPE_OF = {"embed": "embed", "layers": "layers", "attn": "attn",
             "kv_write": "attn/kv_write", "mlp": "mlp", "moe": "moe",
             "head": "head"}


# -- the tf_op of each device operation, read from the serialized XSpace ----
# (ProfileData gives events but not their metadata's stats; the XPlane wire
# format is read here by hand, so no package beyond JAX is needed)

def _varint(b, i):
    out = shift = 0
    while True:
        c = b[i]
        i += 1
        out |= (c & 0x7F) << shift
        shift += 7
        if c < 0x80:
            return out, i


def _fields(b):
    """(field number, value) of one protobuf message: ints for varints,
    memoryviews for length-delimited fields; fixed-width fields skipped."""
    i, end = 0, len(b)
    while i < end:
        key, i = _varint(b, i)
        kind = key & 7
        if kind == 0:
            v, i = _varint(b, i)
        elif kind == 2:
            n, i = _varint(b, i)
            v, i = b[i:i + n], i + n
        elif kind in (1, 5):
            i += 8 if kind == 1 else 4
            continue
        else:
            raise ValueError(f"wire type {kind} in an XSpace")
        yield key >> 3, v


def tf_ops(raw):
    """{device plane name: {operation name: tf_op}} of a serialized XSpace.
    XSpace.planes = 1; XPlane: name = 2, event_metadata = 4 (map of
    XEventMetadata: name = 2, stats = 5), stat_metadata = 5 (map of
    XStatMetadata: id = 1, name = 2); XStat: metadata_id = 1,
    str_value = 5, ref_value = 7."""
    out = {}
    for f, plane in _fields(memoryview(raw)):
        if f != 1:
            continue
        name, events, stat_names = "", [], {}
        for g, v in _fields(plane):
            if g == 2:
                name = bytes(v).decode()
            elif g == 4:
                events.append(v)
            elif g == 5:
                meta = dict(_fields(dict(_fields(v)).get(2, b"")))
                stat_names[meta.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if not name.startswith("/device:"):
            continue
        tf_id = {n: i for i, n in stat_names.items()}.get("tf_op")
        ops = out[name] = {}
        for entry in events:
            op, tf = "", None
            for g, v in _fields(dict(_fields(entry)).get(2, b"")):
                if g == 2:
                    op = bytes(v).decode()
                elif g == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == tf_id:
                        tf = bytes(stat[5]).decode() if 5 in stat \
                            else stat_names.get(stat.get(7))
            if tf:
                ops[op] = tf
    return out


def scope_of(tf_op):
    """Innermost named scope of a ``tf_op`` ('<op_name>:<type>'), or None."""
    for part in reversed((tf_op.rpartition(":")[0] or tf_op).split("/")):
        if part in _SCOPE_OF:
            return _SCOPE_OF[part]
    return None


def _by_jax(part):
    """Whether a part of an ``op_name`` is one JAX writes itself: a loop or
    call of its own, a transformation ('jvp(...)'), an einsum's subscripts."""
    return (part in ("while", "body", "cond", "closed_call", "checkpoint", "remat")
            or "(" in part or "->" in part or re.fullmatch(r"branch_\d+_fun", part))


def scope_path(tf_op):
    """The innermost named scope of a ``tf_op`` (as ``scope_of`` gives it)
    followed by the scopes opened inside it, '/'-joined; None where it has
    no named scope.  The ``op_name``'s last part, the operation, is left
    out."""
    parts = (tf_op.rpartition(":")[0] or tf_op).split("/")
    for i in range(len(parts) - 1, -1, -1):
        if parts[i] in _SCOPE_OF:
            inner = [p for p in parts[i + 1:-1] if not _by_jax(p)]
            return "/".join([_SCOPE_OF[parts[i]]] + inner)
    return None


# -- reduction ---------------------------------------------------------------

def _self_times(ops):
    """Self time of each (start, end) interval, each instant going to the
    innermost (latest started) interval running then, and each interval's
    enclosing interval (index, or -1)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][0], -ops[i][1]))
    cuts = sorted({t for se in ops for t in se})
    own = [0] * len(ops)
    parent = [-1] * len(ops)
    running = []                     # heap of (-start, end, index)
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(order) and ops[order[k]][0] <= a:
            i = order[k]
            while running and running[0][1] <= a:
                heapq.heappop(running)
            if running and running[0][1] >= ops[i][1]:
                parent[i] = running[0][2]
            heapq.heappush(running, (-ops[i][0], ops[i][1], i))
            k += 1
        while running and running[0][1] <= a:
            heapq.heappop(running)
        if running:
            own[running[0][2]] += b - a
    return own, parent


def _is_loop(name):
    """Whether an operation is a ``while``: the TPU profiler gives the
    layer loop no ``tf_op``, and the step's only loops are the layer scan."""
    return xtrace.op_name(name).endswith(" while")


def _program_of(modules, t):
    """Name of the program running at ``t`` (modules: sorted (start, end,
    name)), or None."""
    j = bisect.bisect_right(modules, (t, float("inf"), "")) - 1
    if j >= 0 and modules[j][0] <= t < modules[j][1]:
        return modules[j][2]
    return None


def reduce(planes, ops_tf):
    """Attribution of one trace: {'window_s', 'steps', 'busy_s', 'scope_s'
    (seconds per scope and 'other', device mean), 'path_s' (the same
    seconds per scope path, ``scope_path``), 'other_ops' (the five
    operations 'other' holds most of), 'idle_s' (seconds per host span,
    first device), 'programs' (module runs started in the window, device
    mean), 'step_program' (whether ``jit_serve_step`` ran)}.
    ``planes``: ``ProfileData(...).planes``; ``ops_tf``: ``tf_ops(...)``."""
    planes = list(planes)
    window, steps, phases = [], [], []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xtrace.WINDOW:
                    window.append((ev.start_ns, ev.end_ns))
                elif ev.name == STEP:
                    steps.append((ev.start_ns, ev.end_ns))
                elif ev.name.startswith("serve."):
                    phases.append((ev.start_ns, ev.end_ns, ev.name))
    if len(window) != 1:
        raise ValueError(f"expected one {xtrace.WINDOW!r} host span, found {len(window)}")
    w0, w1 = window[0]
    whole = [(s, e) for s, e in steps if w0 <= s and e <= w1]
    scope_s, programs, busy, first = collections.Counter(), 0, [], None
    path_s = collections.Counter()
    other_ops = collections.Counter()
    step_program = False
    devices = [p for p in planes if p.name.startswith("/device:TPU")]
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        modules = sorted((ev.start_ns, ev.end_ns, ev.name.split("(")[0])
                         for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines else ()))
        programs += sum(1 for s, _, _ in modules if w0 <= s < w1)
        clipped = [(max(ev.start_ns, w0), min(ev.end_ns, w1), ev.name) for ev in
                   (lines[xtrace.OPS_LINE].events if xtrace.OPS_LINE in lines else ())]
        clipped = sorted((x for x in clipped if x[1] > x[0]), key=lambda x: (x[0], -x[1]))
        if not clipped:
            continue
        ops = [(s, e) for s, e, _ in clipped]
        names = [n for _, _, n in clipped]
        tf = ops_tf.get(plane.name, {})
        own, parent = _self_times(ops)
        scope = [None] * len(ops)
        for i in range(len(ops)):          # an enclosing operation comes first
            prog, path = _program_of(modules, ops[i][0]), None
            if prog in HEAD_PROGRAMS:
                scope[i] = "head"
            elif prog == STEP_PROGRAM:
                step_program = True
                scope[i] = scope_of(tf.get(names[i], ""))
                path = scope_path(tf.get(names[i], ""))
                if scope[i] is None:
                    inner = parent[i] >= 0 and scope[parent[i]] == "layers"
                    scope[i] = "layers" if inner or _is_loop(names[i]) else OTHER
            else:
                scope[i] = OTHER
            scope_s[scope[i]] += own[i] / 1e9
            path_s[path or scope[i]] += own[i] / 1e9
            if scope[i] == OTHER:
                other_ops[xtrace.op_name(names[i])] += own[i] / 1e9
        merged = xtrace.merge(ops)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if first is None:
            first = merged
    if not busy:
        raise ValueError("no device operation inside the traced window")
    n = len(busy)
    idle = collections.Counter()
    edges = [w0] + [t for se in first for t in se] + [w1]
    bounds = sorted({t for s, e, _ in phases for t in (s, e)}
                    | {t for s, e in steps for t in (s, e)})
    for a, b in zip(edges[::2], edges[1::2]):
        cuts = [a] + bounds[bisect.bisect_right(bounds, a):bisect.bisect_left(bounds, b)] + [b]
        for x, y in zip(cuts, cuts[1:]):
            if y > x:
                idle[_host_span(steps, phases, (x + y) / 2)] += (y - x) / 1e9
    return {"window_s": (w1 - w0) / 1e9, "steps": len(whole),
            "busy_s": sum(busy) / n,
            "scope_s": {k: v / n for k, v in scope_s.items()},
            "path_s": {k: v / n for k, v in path_s.items()},
            "other_ops": [[k, v / n] for k, v in other_ops.most_common(5)],
            "idle_s": dict(idle), "programs": programs / n,
            "step_program": step_program}


def _host_span(steps, phases, t):
    inner = None
    for s, e, name in phases:
        if s <= t < e and (inner is None or s > inner[0]):
            inner = (s, name)
    if inner is not None:
        return inner[1]
    if any(s <= t < e for s, e in steps):
        return STEP
    return BETWEEN


@functools.lru_cache(maxsize=4)
def reduce_file(path):
    """``reduce`` of one ``.xplane.pb`` file (parsed once per path)."""
    from jax.profiler import ProfileData
    raw = pathlib.Path(path).read_bytes()
    return reduce(ProfileData.from_serialized_xspace(raw).planes, tf_ops(raw))


def for_window(w):
    """The reduction of a traced run's trace (``TRACE_DIR``), or None where
    the run was not traced or the trace holds no whole ``serve.step`` span."""
    if w.trace is None:
        return None
    r = reduce_file(xtrace.find_xplane(str(TRACE_DIR)))
    return r if r["steps"] else None


def ms_per_step(w, *scopes):
    """Device ms a step spends in ``scopes``, or None where the trace has no
    step or no ``jit_serve_step`` program (a program without named scopes)."""
    r = for_window(w)
    if r is None or not r["step_program"]:
        return None
    return 1e3 * sum(r["scope_s"].get(s, 0.0) for s in scopes) / r["steps"]


def ms_under(w, path):
    """Device ms a step spends in the scope at ``path`` ('moe/experts') and
    the scopes inside it, by the paths of ``scope_path``; None as for
    ``ms_per_step``, and where no operation of the window lies under
    ``path``.  Unlike ``ms_per_step``, 'attn' here holds 'attn/kv_write'."""
    r = for_window(w)
    if r is None or not r["step_program"]:
        return None
    under = [t for p, t in r["path_s"].items() if p == path or p.startswith(path + "/")]
    return 1e3 * sum(under) / r["steps"] if under else None


def report(r):
    """Lines of text: each scope's and each host span's ms per step."""
    n = r["steps"]
    per = lambda s: 1e3 * s / max(n, 1)     # ms in the window with no step
    scoped = sum(r["scope_s"].values())
    out = [f"steps {n}  window {r['window_s']:.6f} s  busy {r['busy_s']:.6f} s  "
           f"programs per step {r['programs'] / n if n else float('nan'):.3f}",
           f"device ms {'per step' if n else 'in the window'} "
           f"({STEP_PROGRAM} ran: {r['step_program']}; "
           f"scoped + other = {100 * scoped / r['busy_s']:.2f}% of busy)"]
    for s in SCOPES + (OTHER,):
        t = r["scope_s"].get(s, 0.0)
        out.append(f"  {s:<16} {per(t):10.4f}  {100 * t / r['busy_s']:6.2f}% of busy")
    for name, t in r["other_ops"]:
        out.append(f"    other: {name:<40} {per(t):10.4f}")
    for p, t in sorted(r["path_s"].items()):
        if p not in SCOPES + (OTHER,):
            out.append(f"    path {p:<41} {per(t):10.4f}")
    idle = sum(r["idle_s"].values())
    out.append(f"idle ms {'per step' if n else 'in the window'} "
               f"(idle {idle:.6f} s of the window)")
    for name, t in sorted(r["idle_s"].items(), key=lambda kv: -kv[1]):
        out.append(f"  {name:<26} {per(t):10.4f}  {100 * t / idle:6.2f}% of idle")
    return out


def main(argv):
    path = pathlib.Path(argv[0]) if argv else TRACE_DIR
    f = str(path) if path.is_file() else xtrace.find_xplane(str(path))
    print("\n".join(report(reduce_file(f))))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
