"""Traffic generation, the manifest's files, and the harness arithmetic:
exact percentiles, whole-wave rates and per-request latencies."""
import importlib
import json
import time

import numpy as np
import pytest

import chipbench_small as S
from chipbench import driver, gen, run, stats, weights

BENCH = json.loads((S.ROOT / "BENCHMARK.json").read_text())
TRAFFIC = sorted((S.ROOT / "chipbench" / "traffic").glob("*.json"))


@pytest.mark.parametrize("path", TRAFFIC, ids=lambda p: p.stem)
def test_traffic_fits_the_cache_and_repeats_by_seed(path):
    t = json.loads(path.read_text())
    p, g = gen.wave_sizes(t)
    assert len(p) == t["slots"] and (p + g <= t["max_len"] - 1).all()
    assert p.min() >= t["prompt"]["min"] and p.max() <= t["prompt"]["max"]
    assert g.min() >= t["gen"]["min"] and g.max() <= t["gen"]["max"]
    assert "arXiv:" in t["source"] and t["cuts"]
    big = 2**33 + 12345
    a = gen.wave(t, np.random.default_rng(big), 1000)
    b = gen.wave(t, np.random.default_rng(big), 1000)
    c = gen.wave(t, np.random.default_rng(big + 1), 1000)
    assert all(np.array_equal(x[0], y[0]) and x[1] == y[1] for x, y in zip(a, b))
    assert not all(np.array_equal(x[0], y[0]) for x, y in zip(a, c))
    # every seed serves the same set of sizes, in another order
    assert sorted((len(x[0]), x[1]) for x in a) == sorted((len(x[0]), x[1]) for x in c)
    assert all(x[0].min() >= 1 and x[0].max() < 1000 for x in a)


def test_manifest_files_are_found_by_name():
    for cell in BENCH["workloads"]:
        _, _, sizes, traffic, limits = run.load_cell(S.ROOT, cell["name"])
        assert limits and all(
            name in ("max_logit_gap", "mean_logit_gap") and lim["limit"] > 0
            for name, lim in limits.items())
        assert sizes["name"] == cell["config"]
    for m in BENCH["per_layer"]:
        assert callable(importlib.import_module(f"chipbench.metrics.{m['name']}").read)


def test_nearest_rank_percentile():
    x = list(range(1, 101))                     # 1..100
    assert stats.percentile(x, 95) == 95
    assert stats.percentile(x[::-1], 50) == 50
    assert stats.percentile([3.0], 95) == 3.0
    assert stats.percentile(list(range(1, 21)), 95) == 19   # ceil(0.95 * 20) = 19th
    with pytest.raises(ValueError):
        stats.percentile([], 95)


def _wave(p, g, t_start, step_s):
    p, g = np.array(p), np.array(g)
    steps = int((p + g).max()) - 1
    occ, _ = driver.occupancy(p, g, steps)
    t_end = t_start + 0.5 + step_s * np.arange(1, steps + 1)
    done = [(np.ones(a, np.int32), [0] * b) for a, b in zip(p, g)]
    return driver.Wave(p, g, t_start, t_start + 0.5, t_end, occ, done, 0)


def test_exponential_lengths_keep_the_published_mean():
    t = {"slots": 20000, "max_len": 10**6, "sizes_seed": 3,
         "prompt": {"mean": 69.5, "min": 1, "max": 10**5},
         "gen": {"mean": 214.5, "min": 1, "max": 10**5}}
    p, g = gen.wave_sizes(t)
    assert p.mean() == pytest.approx(69.5, rel=0.03)
    assert g.mean() == pytest.approx(214.5, rel=0.03)
    t["gen"]["max"] = 120
    assert gen.wave_sizes(t)[1].max() == 120


def test_whole_wave_rate_and_latencies():
    w1 = _wave([3, 5], [4, 2], 0.0, 0.1)        # 6 steps: 0.5 s to submit + 0.6 s
    w2 = _wave([3, 5], [4, 2], 10.0, 0.2)       # 6 steps: 0.5 s + 1.2 s
    e = run.end_to_end([w1, w2], setup_s=1.0)
    # the whole window, the 8.9 s between the waves included
    assert e["tokens_per_s"] == pytest.approx(12 / 11.7)
    ttft, itl = run.latencies([w1, w2])
    # request (P=3): first token after step 2 -> 3 steps after submit
    assert ttft == pytest.approx([0.3, 0.5, 0.6, 1.0])
    assert sorted(itl) == pytest.approx([0.1] * 4 + [0.2] * 4)
    assert e["itl_p95_ms"] == pytest.approx(200.0)
    assert e["ttft_p95_ms"] == pytest.approx(1000.0)


def test_time_between_waves_lowers_the_rate():
    """Host work between waves (collecting the last engine, drawing the next
    wave) is in the window: a pause there lowers the rate."""
    from repro.launch import serve
    s = S.sizes("qwen2-0.5b")
    cfg = run.repo_config(s)
    params = weights.make(cfg, 5)
    rng = np.random.default_rng(5)
    waves = [driver.run(serve, cfg, params, gen.wave(S.TRAFFIC, rng, s["vocab_size"]),
                        S.TRAFFIC["max_len"]) for _ in range(2)]
    time.sleep(0.5)
    waves.append(driver.run(serve, cfg, params,
                            gen.wave(S.TRAFFIC, rng, s["vocab_size"]),
                            S.TRAFFIC["max_len"]))
    busy = sum(w.seconds for w in waves)
    tokens = sum(len(o) for w in waves for _, o in w.done)
    rate = run.end_to_end(waves, 0.0)["tokens_per_s"]
    assert rate < tokens / (busy + 0.5)
    assert rate == pytest.approx(tokens / run.window_seconds(waves))


def test_occupancy_schedule():
    occ, pre = driver.occupancy(np.array([3, 5]), np.array([4, 2]), 6)
    assert occ.tolist() == [2, 2, 2, 2, 2, 2] and pre.tolist() == [2, 2, 2, 1, 1, 0]
