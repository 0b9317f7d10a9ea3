"""Scopes the program opens inside a named one, read by their path
(``scopes.scope_path``, ``scopes.ms_under``) with no entry in the scope
tables: the hand-made trace of ``test_chipbench_scopes.py`` with its MLP
made an expert layer that opens ``route`` and ``experts`` inside ``moe``."""
import importlib
from types import SimpleNamespace as NS

import pytest

import test_chipbench_scopes as T
from chipbench import scopes

BODY = f"{T.STEP}/layers/while/body/closed_call"
# the MLP's 400 ns of a step, split: 100 in moe itself, 100 in moe/route,
# 200 in moe/experts (under a loop of its own, which JAX names)
MOE = [
    ("%fusion.5 = moe", 3600, 3700, f"{BODY}/moe/add:"),
    ("%fusion.6 = route", 3700, 3800, f"{BODY}/moe/route/btd,de->bte/dot_general:"),
    ("%fusion.7 = experts", 3800, 4000,
     f"{BODY}/moe/experts/while/body/closed_call/ebd,edf->ebf/dot_general:"),
]
ONE_STEP = [op for op in T.ONE_STEP if op[0] != "%fusion.2 = mlp"] + MOE


def _xspace(one_step):
    """``T.handmade_xspace`` over another step's operations."""
    from jax.profiler import ProfileData
    ops = one_step + T._shift(one_step, 3000) + [("%other = x", 8200, 8300, None)]
    modules = T.MODULES + T._shift(T.MODULES, 3000) + [("jit_other(3)", 8200, 8300)]
    host = T.HOST + T.PHASES + T._shift(T.PHASES, 3000)
    text = " ".join([
        T._plane(1, "/host:CPU", {"python": host}),
        T._plane(2, "/device:TPU:0", {"XLA Modules": modules,
                                      "XLA Ops": [o[:3] for o in ops]},
                 {o[0]: o[3] for o in ops})])
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture
def nested(tmp_path, monkeypatch):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(_xspace(ONE_STEP))
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    return scopes.reduce_file(str(d / "t.xplane.pb"))


@pytest.mark.parametrize("tf_op,path", [
    (f"{BODY}/moe/experts/while/body/closed_call/ebd,edf->ebf/dot_general:", "moe/experts"),
    (f"{BODY}/attn/kv_write/dynamic_update_slice:", "attn/kv_write"),
    (f"{T.STEP}/layers/kv_write/dynamic_update_slice:", "attn/kv_write"),
    (f"{BODY}/attn/bhgqd,bhkd->bhgqk/dot_general:", "attn"),
    (f"{T.STEP}/layers/while:", "layers"),
    (f"{T.STEP}/head/jvp(norm)/rsqrt:", "head"),
    (f"{BODY}/moe/cond/branch_1_fun/top/sort:", "moe/top"),
    ("jit(sample_argmax)/argmax:", None),
])
def test_scope_path(tf_op, path):
    assert scopes.scope_path(tf_op) == path
    if path is not None:
        assert path.startswith(scopes.scope_of(tf_op))


def test_nested_scopes_keep_their_parents_time(nested):
    got = {k: round(v * 1e9) for k, v in nested["scope_s"].items()}
    assert got == {"embed": 200, "layers": 1200, "attn": 800, "attn/kv_write": 200,
                   "moe": 800, "head": 500, "other": 200}
    paths = {k: round(v * 1e9) for k, v in nested["path_s"].items()}
    assert paths == {"embed": 200, "layers": 1200, "attn": 800, "attn/kv_write": 200,
                     "moe": 200, "moe/route": 200, "moe/experts": 400,
                     "head": 500, "other": 200}


@pytest.mark.parametrize("path,want", [
    ("moe/experts", 200e-6), ("moe/route", 100e-6), ("moe", 400e-6),
    ("attn", 500e-6), ("attn/kv_write", 100e-6), ("moe/gate", None), ("mo", None),
])
def test_ms_under_reads_a_scope_by_its_path(nested, path, want):
    w = NS(trace={})
    assert scopes.ms_under(w, path) == (None if want is None else pytest.approx(want))
    assert scopes.ms_under(NS(trace=None), path) is None


@pytest.mark.parametrize("name,want", [
    ("ffn_ms_per_step", 400e-6),            # all of moe, as the MLP was
    ("attn_ms_per_step", 400e-6),
    ("kv_cache_ms_per_step", 700e-6),
    ("head_ms_per_step", 250e-6),
])
def test_operator_readers_are_unchanged_by_nesting(nested, name, want):
    read = importlib.import_module(f"chipbench.metrics.{name}").read
    assert read(NS(trace={})) == pytest.approx(want)


def test_report_lists_the_nested_paths(nested):
    lines = scopes.report(nested)
    assert any(ln.split()[:2] == ["path", "moe/experts"] for ln in lines)
    assert not any(ln.split()[:2] == ["path", "attn"] for ln in lines)
