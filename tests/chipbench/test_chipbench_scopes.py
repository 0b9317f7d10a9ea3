"""Attribution of a trace to named scopes and engine spans
(``chipbench/scopes.py``) and the readers of its per-layer metrics: a
trace made by hand (two steps of a served program, written as an XSpace),
and the traces recorded on a TPU v5e (``data/``)."""
import importlib
import pathlib
import random
from types import SimpleNamespace as NS

import pytest

import chipbench_small  # noqa: F401  (puts the repo on sys.path)
from chipbench import scopes, xtrace

DATA = pathlib.Path(__file__).parent / "data"
STEP = "jit(serve_step)"

# host spans (ns): the window holds steps 1 and 2 whole; step 0 ends before
# it opens and step 3 runs past its end
HOST = [("traced_window", 100, 10100), ("serve.step", 0, 1000),
        ("serve.step", 8100, 10200)]
# device operations of one step: (name, start, end, tf_op or None)
ONE_STEP = [
    ("%gather = embed", 2700, 2800, f"{STEP}/embed/gather:"),
    ("%while.2 = while", 2800, 4300, f"{STEP}/layers/while:"),
    ("%fusion.1 = attn", 2900, 3300, f"{STEP}/layers/while/body/closed_call/attn/dot_general:"),
    ("%dus.1 = kv", 3300, 3400,
     f"{STEP}/layers/while/body/closed_call/attn/kv_write/dynamic_update_slice:"),
    ("%copy.1 = copy in the loop", 3400, 3600, None),
    ("%fusion.2 = mlp", 3600, 4000, f"{STEP}/layers/while/body/closed_call/mlp/dot_general:"),
    ("%fusion.3 = head", 4300, 4450, f"{STEP}/head/bsd,vd->bsv/dot_general:"),
    ("%copy.2 = copy after the loop", 4450, 4500, None),
    ("%reduce = argmax", 4600, 4700, "jit(sample_argmax)/argmax:"),
]
MODULES = [("jit_serve_step(1)", 2700, 4500), ("jit_sample_argmax(2)", 4600, 4700)]
PHASES = [("serve.step", 2100, 5000), ("serve.feed", 2100, 2600),
          ("serve.dispatch", 2600, 2700), ("serve.sample", 2700, 4800),
          ("serve.sync", 4800, 4850), ("serve.bookkeep", 4850, 5000)]


def _shift(rows, dt):
    return [(r[0], r[1] + dt, r[2] + dt) + tuple(r[3:]) for r in rows]


def _line(name, events, meta_id):
    out = []
    for ev_name, s, e in events:
        out.append(f"events {{ metadata_id: {meta_id[ev_name]} offset_ps: {s * 1000} "
                   f"duration_ps: {(e - s) * 1000} }}")
    return f'lines {{ name: "{name}" timestamp_ns: 0 {" ".join(out)} }}'


def _plane(pid, name, lines, tf=None):
    """One XPlane in text form; ``lines``: {line name: [(event, start, end)]};
    ``tf``: {event name: tf_op}, kept as the metadata's ``tf_op`` stat."""
    names = sorted({n for evs in lines.values() for n, _, _ in evs})
    meta_id = {n: i + 1 for i, n in enumerate(names)}
    metas = []
    for n in names:
        stat = f' stats {{ metadata_id: 1 str_value: "{tf[n]}" }}' if tf and tf.get(n) else ""
        metas.append(f'event_metadata {{ key: {meta_id[n]} value {{ id: {meta_id[n]} '
                     f'name: "{n}"{stat} }} }}')
    body = " ".join([_line(k, v, meta_id) for k, v in lines.items()] + metas)
    stat_meta = 'stat_metadata { key: 1 value { id: 1 name: "tf_op" } }' if tf else ""
    return f'planes {{ id: {pid} name: "{name}" {body} {stat_meta} }}'


def handmade_xspace():
    """Serialized XSpace of ``HOST``, two steps of ``ONE_STEP`` 3000 ns apart,
    and a program that is neither the step nor an argmax."""
    from jax.profiler import ProfileData
    ops = ONE_STEP + _shift(ONE_STEP, 3000) + [("%other = x", 8200, 8300, None)]
    modules = MODULES + _shift(MODULES, 3000) + [("jit_other(3)", 8200, 8300)]
    host = HOST + PHASES + _shift(PHASES, 3000)
    text = " ".join([
        _plane(1, "/host:CPU", {"python": host}),
        _plane(2, "/device:TPU:0", {"XLA Modules": modules,
                                    "XLA Ops": [o[:3] for o in ops]},
               {o[0]: o[3] for o in ops})])
    return ProfileData.text_proto_to_serialized_xspace(text)


@pytest.fixture
def handmade(tmp_path, monkeypatch):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes(handmade_xspace())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)
    return scopes.reduce_file(str(d / "t.xplane.pb"))


def test_tf_ops_read_from_the_serialized_trace():
    ops = scopes.tf_ops(handmade_xspace())
    assert set(ops) == {"/device:TPU:0"}
    assert ops["/device:TPU:0"]["%while.2 = while"] == f"{STEP}/layers/while:"
    assert "%copy.1 = copy in the loop" not in ops["/device:TPU:0"]
    assert scopes.scope_of(f"{STEP}/layers/while/body/attn/kv_write/dus:") == "attn/kv_write"
    assert scopes.scope_of("jit(sample_argmax)/argmax:") is None


def test_self_time_nesting_and_scopes(handmade):
    r = handmade
    assert r["steps"] == 2                      # steps 0 and 3 are cut by the window
    assert r["window_s"] == pytest.approx(10000e-9)
    assert r["busy_s"] == pytest.approx(3900e-9)
    got = {k: round(v * 1e9) for k, v in r["scope_s"].items()}
    # the while counts only what its body leaves (100 + 300 a step), the
    # copy inside the loop goes to layers, the one after it to other; the
    # argmax program counts to head whole
    assert got == {"embed": 200, "layers": 1200, "attn": 800, "attn/kv_write": 200,
                   "mlp": 800, "head": 500, "other": 200}
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"])
    assert r["programs"] == 5 and r["step_program"]


def test_self_times_add_up_to_the_busy_union():
    """Any intervals, nested or overlapping: each instant counts once, and
    an interval's parent encloses it."""
    rng = random.Random(0)
    for _ in range(300):
        ops = []
        for _ in range(rng.randint(1, 25)):
            s = rng.randint(0, 100)
            ops.append((s, s + rng.randint(1, 30)))
        own, parent = scopes._self_times(ops)
        assert sum(own) == sum(e - s for s, e in xtrace.merge(ops))
        assert all(ops[p][0] <= ops[i][0] and ops[i][1] <= ops[p][1]
                   for i, p in enumerate(parent) if p >= 0)


def test_idle_time_goes_to_the_engine_span_the_host_is_in(handmade):
    got = {k: round(v * 1e9) for k, v in handmade["idle_s"].items()}
    # gaps 100..2700, 4500..4600, 4700..5700, 7500..7600, 7700..8200 and
    # 8300..10100, each split where the host's spans begin and end
    assert got == {"serve.step": 2800,         # in steps 0 and 3 (no phases)
                   scopes.BETWEEN: 1300,       # 1000..2100, 5000..5100, 8000..8100
                   "serve.feed": 1000, "serve.dispatch": 200,
                   "serve.sample": 400, "serve.sync": 100, "serve.bookkeep": 300}
    assert sum(got.values()) == 10000 - 3900


def _window(trace=True):
    return NS(trace={} if trace else None)


@pytest.mark.parametrize("name,want", [
    ("attn_ms_per_step", 400e-6),
    ("kv_cache_ms_per_step", 700e-6),
    ("ffn_ms_per_step", 400e-6),
    ("head_ms_per_step", 250e-6),
    ("host_gap_ms_per_step", 1300e-6),
    ("programs_per_step", 2.5),
])
def test_reader(handmade, name, want):
    read = importlib.import_module(f"chipbench.metrics.{name}").read
    assert read(_window()) == pytest.approx(want)
    assert read(_window(trace=False)) is None


@pytest.fixture
def unscoped(tmp_path, monkeypatch):
    """The recorded trace of a program with no named scopes or engine spans."""
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "t.xplane.pb").write_bytes((DATA / "tiny.xplane.pb").read_bytes())
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)


@pytest.mark.parametrize("name", ["attn_ms_per_step", "kv_cache_ms_per_step",
                                  "ffn_ms_per_step", "head_ms_per_step",
                                  "host_gap_ms_per_step", "programs_per_step"])
def test_readers_report_nothing_for_a_program_without_names(unscoped, name):
    read = importlib.import_module(f"chipbench.metrics.{name}").read
    assert read(_window()) is None


def test_recorded_scoped_trace(capsys):
    """Four engine steps of the small qwen2 (two layers of width 64)
    recorded on a TPU v5e by ``record_scoped_fixture.py``; the source paths
    the trace carried were made relative to the checkout."""
    r = scopes.reduce_file(str(DATA / "tiny_scoped.xplane.pb"))
    assert r["steps"] == 4 and r["step_program"]
    present = {k for k, v in r["scope_s"].items() if v > 0}
    assert set(scopes.SCOPES) - {"moe"} <= present        # a dense model
    assert sum(r["scope_s"].values()) == pytest.approx(r["busy_s"], rel=0.01)
    # the engine dispatches two programs a step: the step and the argmax
    assert r["programs"] / r["steps"] == 2
    assert set(r["idle_s"]) <= {"serve.feed", "serve.dispatch", "serve.sample",
                                "serve.sync", "serve.bookkeep", scopes.STEP,
                                scopes.BETWEEN}
    assert scopes.main([str(DATA / "tiny_scoped.xplane.pb")]) == 0
    assert "attn/kv_write" in capsys.readouterr().out
