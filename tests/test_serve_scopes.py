"""The served step and the engine describe themselves in a profiler trace:
named scopes on every operation of the compiled step, engine spans on the
profiler's clock, and programs named by their functions."""
import glob
import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.launch.serve import Engine, Request
from repro.models import decode, get_config
from repro.models import params as MP
from repro.obs import SpanTracer, spans as SP

SCOPE_PARTS = {part for s in decode.SERVE_SCOPES for part in s.split("/")}
# operations that move no data and run nothing on a device
NO_OPS = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
# the Pallas kernels of ``kernels/ops.py`` run on the CPU through their
# interpreter, as control flow of their own; on the chip each is one custom
# call, and counts here as one operation
KERNELS = {"jit(decode_attention)", "jit(grouped_matmul)"}
_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = .*?[\s)}]([a-z][a-z0-9\-_]*)\(")


def executed_instructions(hlo):
    """(name, opcode, op_name or None) of each instruction of a compiled
    module that runs as an operation of its own: those of the entry
    computation and of the control-flow computations it reaches (while
    bodies and conditions, calls, branches), not those inside fusions,
    reducers or a kernel's interpreter (:data:`KERNELS`)."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), [])
            entry = m.group(2) if m.group(1) else entry
        elif cur is not None and " = " in line:
            cur.append(line)
    todo, seen, out = [entry], set(), []
    while todo:
        comp = todo.pop()
        if comp in seen:
            continue
        seen.add(comp)
        for line in comps[comp]:
            name, op = _INSTRUCTION.match(line).groups()
            op_name = re.search(r'op_name="([^"]*)"', line)
            kernel = op_name and KERNELS & set(op_name.group(1).split("/"))
            if op in ("while", "call", "conditional") and not kernel:
                todo += re.findall(r"(?:condition|body|to_apply)=%([\w.\-]+)", line)
                for group in re.findall(r"branch_computations=\{([^}]*)\}", line):
                    todo += [c.strip().lstrip("%") for c in group.split(",")]
            out.append((name, op, op_name.group(1) if op_name else None))
    return out


@pytest.mark.parametrize("arch", ["qwen2-0.5b", "olmoe-1b-7b"])
def test_every_operation_of_the_step_carries_a_scope(arch):
    cfg = get_config(arch).reduced()
    compiled = decode.make_serve_step(cfg).lower(
        MP.param_specs(cfg), decode.cache_specs(cfg, 4, 32),
        jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    hlo = compiled.as_text()
    assert hlo.startswith("HloModule jit_serve_step,")
    ops = [i for i in executed_instructions(hlo) if i[1] not in NO_OPS]
    assert len(ops) > 50
    unscoped = [(n, op, o) for n, op, o in ops
                if o is not None and not SCOPE_PARTS & set(o.split("/"))]
    assert unscoped == []
    # what XLA inserted (no metadata): copies, buffer set-up, a few
    # rewritten dots and reductions
    inserted = [(n, op) for n, op, o in ops if o is None]
    assert len(inserted) <= 12, inserted
    found = {p for _, _, o in ops if o for p in o.split("/") if p in SCOPE_PARTS}
    want = {"embed", "layers", "attn", "kv_write", "head"}
    want |= {"moe", "route", "dispatch", "experts"} if cfg.family == "moe" else {"mlp"}
    # the weighting of ``combine`` may fuse into the residual add after it
    assert found - {"combine"} == want


def _xplane(trace_dir):
    return ProfileData.from_file(
        glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")[-1])


def _start_time(profile):
    env = [dict(p.stats) for p in profile.planes if p.name == "Task Environment"]
    return env[0]["profile_start_time"]


def _host_events(profile):
    return sorted((ev.start_ns, ev.end_ns, ev.name) for p in profile.planes
                  if p.name.startswith("/host:") for line in p.lines
                  for ev in line.events)


def test_span_events_land_beside_profiler_annotations(tmp_path):
    tr = SpanTracer()
    jax.profiler.start_trace(str(tmp_path))
    try:
        tr.emit(SP.STEP, step=0)
        with jax.profiler.TraceAnnotation("probe"):
            pass
    finally:
        jax.profiler.stop_trace()
    profile = _xplane(tmp_path)
    probe = [s for s, _, n in _host_events(profile) if n == "probe"]
    at = tr.epoch_ns + 1000 * tr.events[0].ts_us - _start_time(profile)
    assert len(probe) == 1 and abs(at - probe[0]) < 1e6


def test_exported_stream_carries_the_epoch():
    tr = SpanTracer()
    tr.emit(SP.STEP, step=0)
    text = SP.to_jsonl(tr.events, epoch_ns=tr.epoch_ns)
    assert json.loads(text.splitlines()[0]) == {"epoch_ns": tr.epoch_ns}
    assert SP.from_jsonl(text) == tr.events
    stable = SP.to_jsonl(tr.events, stable=True, epoch_ns=tr.epoch_ns)
    assert stable.splitlines()[0] == '{"epoch_ns": 0}'
    assert SP.to_jsonl(tr.events) == text.split("\n", 1)[1]


def test_engine_step_is_a_serve_step_span_with_its_phases(tmp_path):
    cfg = get_config("qwen2-0.5b").reduced()
    eng = Engine(cfg, MP.init_params(cfg, seed=0), 2, 16)
    eng.submit(Request(0, np.array([3, 5, 7], np.int32), 4))
    eng.admit()
    eng.step()                      # compiles outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        eng.step()
    finally:
        jax.profiler.stop_trace()
    events = _host_events(_xplane(tmp_path))
    steps = [(s, e) for s, e, n in events if n == "serve.step"]
    assert len(steps) == 1
    s0, e0 = steps[0]
    inside = [(s, n) for s, e, n in events if s0 <= s and e <= e0]
    phases = [n for _, n in inside if n.startswith("serve.") and n != "serve.step"]
    assert phases == ["serve.feed", "serve.dispatch", "serve.sample",
                      "serve.sync", "serve.bookkeep"]
    # the step's programs, each under its own name; nothing else is run
    programs = {n for _, n in inside if n.startswith("PjitFunction(")}
    assert programs == {"PjitFunction(serve_step)", "PjitFunction(sample_argmax)"}
