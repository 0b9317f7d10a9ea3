"""The fp32 references against the engine at a small size on the CPU, the
fp8 control, a served token altered where it is produced, and the entry
point's refusal to run without a TPU."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench_small as S
from chipbench import check, driver, gen, run, weights

FAMILIES = ["qwen2-0.5b", "olmoe-1b-7b"]
LIMITS = {"max_logit_gap": {"limit": 0.1}}


def _wave(name, seed, slots=4):
    from repro.launch import serve
    s = S.sizes(name)
    cfg = run.repo_config(s)
    params = weights.make(cfg, seed)
    t = dict(S.TRAFFIC, slots=slots)
    reqs = gen.wave(t, np.random.default_rng(seed), s["vocab_size"])
    return s, cfg, params, t, reqs


@pytest.mark.parametrize("name", FAMILIES)
def test_reference_agrees_with_engine_logits(name):
    """A wave started at position 0: the logits the engine produced for slot
    0 at each generated position against the reference's full forward."""
    from repro.launch import serve
    s, cfg, params, t, reqs = _wave(name, 7)
    rows = []
    real = serve.decode.make_serve_step(cfg)

    def recording(*args):
        logits, cache = real(*args)
        rows.append(np.asarray(logits[0, -1], np.float32))
        return logits, cache

    eng = serve.Engine(cfg, params, t["slots"], t["max_len"])
    eng._step = recording
    r0 = serve.Request(0, reqs[0][0], reqs[0][1])
    eng.submit(r0)
    for i, (p, g) in enumerate(reqs[1:], 1):
        eng.submit(serve.Request(i, p, g))
    eng.admit()
    while eng.inflight:
        eng.step()
    p = len(r0.prompt)
    chip = np.stack(rows[p - 1:p - 1 + r0.gen])
    assert (chip.argmax(-1) == np.asarray(r0.out)).all()
    tokens, rr, cc, _ = check._batch([(r0.prompt, r0.out)], t["max_len"])
    ref = np.asarray(check.logits(s, params, tokens, rr, cc))
    rel = np.linalg.norm(chip - ref, axis=-1) / np.linalg.norm(ref, axis=-1)
    assert rel.max() < 0.05        # bf16 serving path against fp32


def _committed_rule(name):
    """The numbers that the limits files of configuration ``name``'s cells
    compare."""
    files = sorted((S.ROOT / "chipbench" / "limits").glob(f"{name}.*.json"))
    names = {n for f in files for n in json.loads(f.read_text())}
    assert names
    return names


@pytest.mark.parametrize("name", FAMILIES)
def test_run_cell_is_correct_and_the_control_is_not(name):
    """The program passes and the fp8 control fails, both judged by the
    decision a run makes (``check.compare`` and ``check.within``) on the
    numbers the family's committed limits files compare.  At this width the
    gaps are smaller than at the published one, so each limit is placed as
    the chip's are: between the program's largest reading and the
    control's smallest."""
    b, cell = S.bench()
    r = run.run_cell(b, cell, S.sizes(name), S.TRAFFIC, LIMITS, 2**33 + 3, 0.0, 0)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] >= 4
    assert list(r)[-1] == "compared"
    assert {"tokens_per_s", "itl_p95_ms", "setup_s"} <= set(r["metrics"])
    from repro.launch import serve
    got = []
    for seed in (1, 2, 3):
        s, cfg, params, t, reqs = _wave(name, seed, slots=8)
        w = driver.run(serve, cfg, params, reqs, t["max_len"])
        got.append(check.gaps(s, params, w.done, t["max_len"], control=True))
    for number in _committed_rule(name):
        program = max(g[number] for g in got)
        ctl = min(check.control_numbers(g)[number] for g in got)
        assert ctl > 3 * program
        limits = {number: {"limit": (program * ctl) ** 0.5 if program else ctl / 3}}
        for g in got:
            assert check.within(check.compare(g, limits))
            assert not check.within(check.compare(check.control_numbers(g), limits))


@pytest.mark.parametrize("name", FAMILIES)
def test_altered_token_is_not_correct(name, monkeypatch):
    """The step's logits for slot 0 are pushed to token 7 where they are
    produced: that request's served tokens change, and the run says so."""
    from repro.models import decode
    real = decode.make_serve_step

    def broken(cfg):
        step = real(cfg)

        def altered(*args):
            logits, cache = step(*args)
            return logits.at[0, :, 7].add(100.0), cache
        return altered

    monkeypatch.setattr(decode, "make_serve_step", broken)
    b, cell = S.bench()
    r = run.run_cell(b, cell, S.sizes(name), S.TRAFFIC, LIMITS, 11, 0.0, 0)
    assert r["failed"] == 0 and not r["correct"]
    assert r["compared"]["max_logit_gap"]["value"] > LIMITS["max_logit_gap"]["limit"]


def test_run_exits_nonzero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2-0.5b.batch_prefill",
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=S.ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "no TPU" in p.stderr
