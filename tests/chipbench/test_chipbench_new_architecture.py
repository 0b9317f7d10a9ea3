"""An architecture enters the benchmark as files of its own.  Every part of a
toy one is written under a temporary directory, none into the checkout: its
configuration file (with ``reference``, ``counters`` and ``repo.checked``),
its reference module with its own step counts, its traffic, its limits, a
per-layer metric that reads an engine counter, and the manifest that names
them.  The harness finds each part by the names the files give."""
import importlib
import json
import sys

import numpy as np
import pytest

import chipbench_small as S
import chipbench.metrics
import chipbench.reference
from chipbench import check, counts, driver, gen, run, weights

ARCH = "toy_arch"
METRIC = "toy_tokens_generated"
CELL = "toy.small_chat"
TOKENS = "serve_tokens_generated_total"
STEPS = "serve_engine_steps_total"

REFERENCE = '''"""Toy architecture: the dense decoder, through a module of its own that
counts its step in its own way."""
from chipbench.reference import dense

CALLS = []


def layer(s, p, x, mm):
    CALLS.append("layer")
    return dense.layer(s, p, x, mm)


def head(s, params, x, mm):
    CALLS.append("head")
    return dense.head(s, params, x, mm)


def step_flops(s, n_occ, pos):
    return 1000 * n_occ + pos


def step_bytes(s, n_occ, pos):
    return 2000 * n_occ + pos
'''

READER = f'''"""Toy: tokens the engine's counter says it generated, over all waves."""


def read(w):
    got = [x.counters["{TOKENS}"]["value"] for x in w.waves if "{TOKENS}" in x.counters]
    return float(sum(got)) if got else None
'''
LIMITS ={"max_logit_gap": {"limit": 0.1}}


def _toy_sizes():
    s = S.sizes("qwen2-0.5b")
    s.update(name="toy", reference=ARCH, counters=[TOKENS, STEPS, "no_such_instrument"])
    s["repo"]["checked"] = {"act": "hidden_act"}
    return s


@pytest.fixture
def toy(tmp_path, monkeypatch):
    """A checkout root of the toy's files only, its reference and metric
    packages put on the harness's package paths."""
    root = tmp_path / "checkout"
    bench = json.loads((S.ROOT / "BENCHMARK.json").read_text())
    files = {
        "BENCHMARK.json": dict(
            bench,
            configs=[{"name": "toy", "source": "a test", "file": "chipbench/configs/toy.json",
                      "reduced": [], "why": "a toy architecture"}],
            workloads=[{"name": CELL, "config": "toy", "traffic": "toy.small",
                        "chips": 1, "why": "a short wave"}],
            per_layer=[{"name": METRIC, "unit": "tokens", "better": "higher",
                        "source": "program_counter", "layer": "scheduler",
                        "moves": "tokens_per_s", "workloads": [CELL]}]),
        "chipbench/configs/toy.json": _toy_sizes(),
        "chipbench/traffic/toy.small.json": S.TRAFFIC,
        f"chipbench/limits/{CELL}.json": LIMITS,
    }
    for name, obj in files.items():
        (root / name).parent.mkdir(parents=True, exist_ok=True)
        (root / name).write_text(json.dumps(obj))
    for pkg, name, text in ((chipbench.reference, ARCH, REFERENCE),
                            (chipbench.metrics, METRIC, READER)):
        d = root / "chipbench" / pkg.__name__.rpartition(".")[2]
        d.mkdir(exist_ok=True)
        (d / f"{name}.py").write_text(text)
        monkeypatch.setattr(pkg, "__path__", list(pkg.__path__) + [str(d)])
    importlib.invalidate_caches()
    yield root
    for m in (f"chipbench.reference.{ARCH}", f"chipbench.metrics.{METRIC}"):
        sys.modules.pop(m, None)
    assert not (S.ROOT / "chipbench" / "reference" / f"{ARCH}.py").exists()


def test_load_cell_finds_the_toys_files(toy):
    bench, cell, sizes, traffic, limits = run.load_cell(toy, CELL)
    assert cell["config"] == "toy" and sizes == _toy_sizes()
    assert traffic == S.TRAFFIC and limits == LIMITS
    assert [m["name"] for m in run.wanted(bench["per_layer"], CELL)] == [METRIC]


def test_repo_config_checks_the_files_own_pairs(toy):
    s = run.load_cell(toy, CELL)[2]
    assert run.repo_config(s).act == "silu"
    with pytest.raises(ValueError, match="'act'"):
        run.repo_config(dict(s, hidden_act="gelu"))
    # a flag the file states and the program has no field for
    no_field = dict(s, qk_norm=True, repo=dict(s["repo"], checked={"qk_norm": "qk_norm"}))
    with pytest.raises(ValueError, match="qk_norm"):
        run.repo_config(no_field)
    # a file without ``checked`` is held to the fixed fields alone
    plain = dict(s, hidden_act="gelu", repo={k: v for k, v in s["repo"].items()
                                             if k != "checked"})
    assert run.repo_config(plain).act == "silu"


def test_counts_come_from_the_toys_reference(toy):
    s = run.load_cell(toy, CELL)[2]
    assert counts.step_flops(s, 3, 4) == 3004
    assert counts.step_bytes(s, 3, 4) == 6004
    # the same file without ``reference`` is counted by the decoder formulas
    dense = {k: v for k, v in s.items() if k != "reference"}
    assert counts.step_flops(dense, 3, 4) == counts.decoder_step_flops(s, 3, 4) != 3004
    assert counts.step_bytes(dense, 3, 4) == counts.decoder_step_bytes(s, 3, 4) != 6004


def test_check_uses_the_toys_reference(toy):
    s = run.load_cell(toy, CELL)[2]
    cfg = run.repo_config(s)
    params = weights.make(cfg, 4)
    prompt, out = np.arange(1, 6, dtype=np.int32), [7, 8, 9]
    tokens, rows, cols, _ = check._batch([(prompt, out)], 16)
    arch = importlib.import_module(f"chipbench.reference.{ARCH}")
    arch.CALLS.clear()
    got = np.asarray(check.logits(s, params, tokens, rows, cols))
    assert arch.CALLS == ["layer", "head"]
    dense = {k: v for k, v in s.items() if k != "reference"}
    want = np.asarray(check.logits(dense, params, tokens, rows, cols))
    assert arch.CALLS == ["layer", "head"]          # the family's module, not the toy's
    np.testing.assert_array_equal(got, want)


def test_wave_keeps_the_named_counters():
    from repro.launch import serve
    s = S.sizes("qwen2-0.5b")
    cfg = run.repo_config(s)
    params = weights.make(cfg, 6)
    rng = np.random.default_rng(6)
    names = [TOKENS, STEPS, "serve_step_latency_us", "no_such_instrument"]
    w = driver.run(serve, cfg, params, gen.wave(S.TRAFFIC, rng, s["vocab_size"]),
                   S.TRAFFIC["max_len"], counters=names)
    assert set(w.counters) == set(names[:3])
    assert w.counters[TOKENS]["value"] == w.gen_len.sum()
    assert w.counters[STEPS]["value"] == len(w.t_end)
    assert w.counters["serve_step_latency_us"]["count"] == len(w.t_end)
    plain = driver.run(serve, cfg, params, gen.wave(S.TRAFFIC, rng, s["vocab_size"]),
                       S.TRAFFIC["max_len"])
    assert plain.counters == {} and plain.failed == 0


@pytest.fixture
def engines(monkeypatch, tmp_path):
    """The ``metrics`` argument of every engine built; a traced run's
    profiler and trace reading replaced by stand-ins the CPU can give."""
    from repro.launch import serve
    built = []

    class Recording(serve.Engine):
        def __init__(self, *args, metrics=None, **kw):
            built.append(metrics)
            super().__init__(*args, metrics=metrics, **kw)

    monkeypatch.setattr(serve, "Engine", Recording)
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    monkeypatch.setattr(run, "TRACE_FIRST_STEP", 10**9)
    monkeypatch.setattr(run.xtrace, "reduce_dir", lambda d: {
        "busy_s": 1.0, "window_s": 2.0, "device_ops": [], "idle_gaps": []})
    monkeypatch.setattr(run.counts, "peaks", lambda kind: {})
    return built


def test_a_counters_cell_reads_its_counter_in_traced_runs_only(toy, engines):
    bench, cell, sizes, traffic, limits = run.load_cell(toy, CELL)
    r = run.run_cell(bench, cell, sizes, traffic, limits, 2**33 + 5, 0.0, 1)
    assert r["correct"] and r["failed"] == 0
    assert engines and all(m is not None for m in engines)
    generated = int(gen.wave_sizes(S.TRAFFIC)[1].sum())     # one wave in a 0 s window
    assert r["metrics"] == {METRIC: {"value": float(generated), "unit": "tokens"}}
    engines.clear()
    r = run.run_cell(bench, cell, sizes, traffic, limits, 2**33 + 5, 0.0, 0)
    assert r["correct"] and engines and all(m is None for m in engines)


def test_a_cell_without_counters_builds_no_registry(toy, engines):
    bench, cell, sizes, traffic, limits = run.load_cell(toy, CELL)
    sizes = {k: v for k, v in sizes.items() if k != "counters"}
    r = run.run_cell(bench, cell, sizes, traffic, limits, 2**33 + 6, 0.0, 1)
    assert r["correct"] and engines and all(m is None for m in engines)
    assert r["metrics"] == {}           # the reader finds nothing to read
