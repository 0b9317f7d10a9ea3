"""The ``olmoe-1b-7b-0924`` configuration and its cell: the file read into
the program's config (and refused by a program without QK-norm), the engine
against the file's reference at every served position, a run of the cell at
a small size, the grouped expert matmul's counts, and the cell's three
per-layer readers on a hand-made trace whose expert layer opens ``route``,
``dispatch``, ``experts`` and ``combine``."""
import dataclasses
import importlib
import json
from types import SimpleNamespace as NS

import numpy as np
import pytest

import chipbench_small as S
import test_chipbench_scopes as T
from chipbench import check, counts, gen, run, scopes, weights

CONFIG = "olmoe-1b-7b-0924"
CELL = "olmoe-1b-7b-0924.chat_decode"
READERS = ["moe_experts_ms_per_step", "moe_dispatch_ms_per_step", "expert_roofline_share"]


def _file():
    return json.loads((S.ROOT / "chipbench" / "configs" / f"{CONFIG}.json").read_text())


# -- (f) the configuration file and the program ------------------------------

def test_the_cell_and_its_files_load():
    bench, cell, sizes, traffic, limits = run.load_cell(S.ROOT, CELL)
    assert cell["chips"] == 1 and sizes == _file()
    assert (traffic["slots"], traffic["max_len"]) == (64, 1024)
    p, g = gen.wave_sizes(traffic)
    assert len(p) == 64 and int((p + g).max()) <= 1023
    assert set(limits) <= {"max_logit_gap", "mean_logit_gap"}
    per_layer = [m["name"] for m in run.wanted(bench["per_layer"], CELL)]
    assert set(READERS) <= set(per_layer) and "prefill_slot_share" not in per_layer
    end_to_end = [m["name"] for m in run.wanted(bench["end_to_end"], CELL)]
    assert end_to_end == ["tokens_per_s", "itl_p95_ms", "setup_s"]
    for name in READERS:            # the new readers list this cell alone
        entry = next(m for m in bench["per_layer"] if m["name"] == name)
        assert entry["workloads"] == [CELL]


def test_repo_config_is_the_published_architecture():
    cfg = run.repo_config(_file())
    assert (cfg.num_layers, cfg.d_model, cfg.num_heads, cfg.num_kv_heads) == (8, 2048, 16, 16)
    assert (cfg.num_experts, cfg.experts_per_token, cfg.norm_eps) == (64, 8, 1e-5)
    assert cfg.qk_norm and not cfg.moe_norm_topk_prob and cfg.moe_dispatch == "grouped"
    with pytest.raises(ValueError, match="qk_norm"):
        run.repo_config(dict(_file(), qk_norm=False))


def test_a_program_without_qk_norm_is_refused_at_once(monkeypatch):
    """The program before these flags existed: its ModelConfig has no
    ``qk_norm`` and no ``moe_norm_topk_prob``, so the file's overrides fail."""
    import repro.models
    from repro.models.config import ModelConfig
    old = [f for f in dataclasses.fields(ModelConfig)
           if f.name not in ("qk_norm", "moe_norm_topk_prob")]
    Older = dataclasses.make_dataclass(
        "ModelConfig", [(f.name, f.type) if f.default is dataclasses.MISSING
                        else (f.name, f.type, dataclasses.field(default=f.default))
                        for f in old], frozen=True)
    registered = repro.models.get_config("olmoe-1b-7b")
    monkeypatch.setattr(repro.models, "get_config", lambda name: Older(
        **{f.name: getattr(registered, f.name) for f in old}))
    with pytest.raises(TypeError, match="qk_norm"):
        run.repo_config(_file())


# -- (a) the engine against the reference -----------------------------------

def test_engine_matches_the_reference_at_every_position():
    """A wave of four requests served token by token from position 0, in
    fp32: every slot's logits at every prompt and answer position against
    the reference's full forward over the same tokens."""
    from repro.launch import serve
    s = S.sizes(CONFIG)
    cfg = dataclasses.replace(run.repo_config(s), dtype="float32")
    params = weights.make(cfg, 11)
    reqs = gen.wave(S.TRAFFIC, np.random.default_rng(11), s["vocab_size"])
    steps = []
    real = serve.decode.make_serve_step(cfg)

    def recording(*args):
        logits, cache = real(*args)
        steps.append(np.asarray(logits[:, -1], np.float32))
        return logits, cache

    eng = serve.Engine(cfg, params, len(reqs), S.TRAFFIC["max_len"])
    eng._step = recording
    served = [serve.Request(i, p, g) for i, (p, g) in enumerate(reqs)]
    for r in served:
        eng.submit(r)
    eng.admit()
    while eng.inflight:
        eng.step()
    worst = 0.0
    for slot, r in enumerate(served):
        assert len(r.out) == r.gen
        n = len(r.prompt) + r.gen - 1            # positions that have a logits row
        got = np.stack([steps[j][slot] for j in range(n)])
        seq = np.concatenate([r.prompt, np.asarray(r.out[:-1], np.int32)])
        tokens = np.zeros((check.ROWS, S.TRAFFIC["max_len"]), np.int32)
        tokens[0, :n] = seq
        ref = np.asarray(check.logits(s, params, tokens, np.zeros(n, int), np.arange(n)))
        assert (got[len(r.prompt) - 1:].argmax(-1) == np.asarray(r.out)).all()
        worst = max(worst, float(np.abs(got - ref).max() / np.abs(ref).max()))
    # fp32 against fp32: the program and the reference differ only in the
    # order of their sums, a few ulp a layer; 1e-4 of the largest logit
    # leaves two orders of room, while one expert swapped by a wrong gate or
    # a norm left out moves a logit by a tenth of the largest or more
    assert worst < 1e-4


# -- a run of the cell at a small size ---------------------------------------

def test_a_small_run_of_the_cell_reports_its_metrics(monkeypatch, tmp_path):
    bench, cell, _, _, _ = run.load_cell(S.ROOT, CELL)
    monkeypatch.setattr(run, "OUT_DIR", tmp_path)
    limits = {"mean_logit_gap": {"limit": 0.05}, "max_logit_gap": {"limit": 1.0}}
    r = run.run_cell(bench, cell, S.sizes(CONFIG), S.TRAFFIC, limits, 2**33 + 7, 0.0, 0)
    assert r["correct"] and r["failed"] == 0
    assert set(r["metrics"]) == {"tokens_per_s", "itl_p95_ms", "setup_s"}
    assert r["compared"]["served_tokens"]["value"] > 0


# -- the grouped expert matmul's counts --------------------------------------

def test_expert_counts_match_hand_sums():
    arch = importlib.import_module("chipbench.reference.olmoe")
    s = _file()
    assert arch.expert_flops(s, 512) == 2 * 512 * 3 * 2048 * 1024
    touched = counts.experts_touched(s, 64)
    assert 63.98 < touched < 63.99
    assert arch.expert_bytes(s, 64) == pytest.approx(
        2 * (touched * 3 * 2048 * 1024 + 2 * 64 * 8 * 2048), rel=1e-12)
    # the step is counted by the decoder formulas, with the file's experts
    assert not hasattr(arch, "step_flops") and not hasattr(arch, "step_bytes")


# -- (g) the three readers ---------------------------------------------------

BODY = f"{T.STEP}/layers/while/body/closed_call"
# the MLP's 400 ns of a step, made an expert layer: 40 in moe itself, 60 in
# route, 50 in dispatch, 200 in experts (the kernel under a jit of its own),
# 50 in combine
MOE = [
    ("%fusion.5 = moe", 3600, 3640, f"{BODY}/moe/add:"),
    ("%fusion.6 = route", 3640, 3700, f"{BODY}/moe/route/top_k:"),
    ("%sort.1 = dispatch", 3700, 3750, f"{BODY}/moe/dispatch/sort:"),
    ("%gmm.1 = experts", 3750, 3950,
     f"{BODY}/moe/experts/jit(grouped_matmul)/jit(gmm)/pallas_call:"),
    ("%gather.3 = combine", 3950, 4000, f"{BODY}/moe/combine/gather:"),
]


def _trace(tmp_path, monkeypatch, one_step):
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    from jax.profiler import ProfileData
    ops = one_step + T._shift(one_step, 3000)
    text = " ".join([
        T._plane(1, "/host:CPU", {"python": T.HOST + T.PHASES + T._shift(T.PHASES, 3000)}),
        T._plane(2, "/device:TPU:0", {"XLA Modules": T.MODULES + T._shift(T.MODULES, 3000),
                                      "XLA Ops": [o[:3] for o in ops]},
                 {o[0]: o[3] for o in ops})])
    (d / "t.xplane.pb").write_bytes(ProfileData.text_proto_to_serialized_xspace(text))
    monkeypatch.setattr(scopes, "TRACE_DIR", tmp_path)


def _window():
    return NS(trace={}, sizes=_file(), traffic={"slots": 64},
              peaks=counts.peaks("TPU v5 lite"))


def _read(name, w):
    return importlib.import_module(f"chipbench.metrics.{name}").read(w)


def test_readers_on_a_nested_expert_layer(tmp_path, monkeypatch):
    one_step = [op for op in T.ONE_STEP if op[0] != "%fusion.2 = mlp"] + MOE
    _trace(tmp_path, monkeypatch, one_step)
    w = _window()
    assert _read("moe_experts_ms_per_step", w) == pytest.approx(200e-6)
    assert _read("moe_dispatch_ms_per_step", w) == pytest.approx(200e-6)
    arch = importlib.import_module("chipbench.reference.olmoe")
    s = w.sizes
    least = max(arch.expert_flops(s, 512) / 197e12, arch.expert_bytes(s, 64) / 819e9)
    assert _read("expert_roofline_share", w) == pytest.approx(100 * 8 * least / 200e-9)
    # the operator reader still counts the whole expert layer
    assert _read("ffn_ms_per_step", w) == pytest.approx(400e-6)


def test_readers_find_nothing_without_nested_scopes(tmp_path, monkeypatch):
    _trace(tmp_path, monkeypatch, T.ONE_STEP)
    for name in READERS:
        assert _read(name, _window()) is None
        assert _read(name, NS(**dict(vars(_window()), trace=None))) is None
