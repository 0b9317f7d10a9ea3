"""Run one benchmark cell once, on the machine it is started on.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

from the root of a checkout.  One process: it fails without a TPU (never
falling back to the CPU), loads the cell named in ``BENCHMARK.json`` with its
configuration, traffic and limit files, makes the weights on the device from
the seed, warms up the cell's one step shape (compiles, through the
persistent compile cache in the checkout), then serves whole waves through
``repro.launch.serve.Engine`` for at most ``--seconds``.  After the window it
compares a sample of the served tokens with the fp32 reference that the
configuration file names.
The last line of standard output is one JSON object; the numbers compared
and their limits are also the last lines of standard error.

With ``--trace 1`` the run reports the per-layer metrics instead of the
end-to-end ones, and records a profiler trace of about three seconds of the
first wave.  A configuration file that lists ``counters`` (instruments of
the engine's ``MetricsRegistry``) has them kept for each wave of its
``--trace 1`` runs, for the per-layer readers; its other runs, and those of
every other configuration, build the engine without a registry.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = pathlib.Path(__file__).resolve().parent
ROOT = HERE.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from chipbench import check, counts, driver, gen, stats, xtrace  # noqa: E402

OUT_DIR = ROOT / ".chipbench"      # traces of --trace 1 runs (not committed)
TRACE_FIRST_STEP = 20              # the trace starts after this step of wave 0
TRACE_SECONDS = 3.0
# ModelConfig field <- configuration-file key, checked against each other;
# a file's ``repo.checked`` adds pairs of its own
FIELDS = {"family": "family", "num_layers": "num_hidden_layers",
          "d_model": "hidden_size", "num_heads": "num_attention_heads",
          "num_kv_heads": "num_key_value_heads", "head_dim": "head_dim",
          "d_ff": "intermediate_size", "vocab_size": "vocab_size",
          "rope_theta": "rope_theta", "norm_eps": "rms_norm_eps",
          "tie_embeddings": "tie_word_embeddings", "qkv_bias": "qkv_bias",
          "dtype": "torch_dtype"}
MOE_FIELDS = {"num_experts": "num_experts",
              "experts_per_token": "num_experts_per_tok"}


@dataclasses.dataclass
class Window:
    """What the per-layer readers see of one run."""
    sizes: dict
    traffic: dict
    waves: list
    seconds: float              # the window's time, profiler calls left out
    peaks: dict
    trace: dict


def load_cell(root, name):
    """(manifest, cell entry, configuration, traffic, limits) of cell ``name``."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    entry = next(c for c in bench["configs"] if c["name"] == cell["config"])
    sizes = json.loads((root / entry["file"]).read_text())
    traffic = json.loads((root / "chipbench" / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
    limits = json.loads((root / "chipbench" / "limits" / f"{name}.json").read_text())
    return bench, cell, sizes, traffic, limits


def repo_config(sizes):
    """The program's ModelConfig as the configuration file states it; raises
    where the program's config disagrees with the file, or has no field that
    the file's ``repo.checked`` names."""
    from repro.models import get_config
    cfg = dataclasses.replace(get_config(sizes["repo"]["config"]),
                              **sizes["repo"]["overrides"])
    fields = dict(FIELDS, **(MOE_FIELDS if sizes.get("num_experts") else {}),
                  **sizes["repo"].get("checked", {}))
    missing = "<no such field>"
    wrong = {f: (getattr(cfg, f, missing), sizes[k]) for f, k in fields.items()
             if getattr(cfg, f, missing) != sizes[k]}
    if wrong:
        raise ValueError(f"program config differs from the file: {wrong}")
    return cfg


def wanted(metrics, cell):
    return [m for m in metrics if cell in m.get("workloads", [cell])]


def latencies(waves):
    """TTFT of every request and every gap between consecutive tokens of one
    request (seconds).  Request r's k-th token comes from step P_r - 1 + k."""
    ttft, itl = [], []
    for w in waves:
        ttft.append(w.t_end[w.prompt_len - 1] - w.t_submit)
        for p, g in zip(w.prompt_len, w.gen_len):
            itl.append(np.diff(w.t_end[p - 1:p + g - 1]))
    return np.concatenate(ttft), np.concatenate(itl)


def window_seconds(waves):
    """The whole window: the first wave's start to the last wave's last
    step, so whatever the host does between waves is in it."""
    return float(waves[-1].t_end[-1] - waves[0].t_start)


def end_to_end(waves, setup_s):
    ttft, itl = latencies(waves)
    tokens = sum(len(o) for w in waves for _, o in w.done)
    return {"tokens_per_s": tokens / window_seconds(waves),
            "ttft_p95_ms": 1e3 * stats.percentile(ttft, 95),
            "itl_p95_ms": 1e3 * stats.percentile(itl, 95),
            "setup_s": setup_s}


class Tracer:
    """Starts the profiler after step ``TRACE_FIRST_STEP`` of the first wave
    and stops it ``TRACE_SECONDS`` later or at the wave's end; the time spent
    in the profiler's own calls is kept apart."""

    def __init__(self, out):
        self.out, self.t0, self.span, self.overhead = out, None, None, 0.0

    def on_step(self, n):
        import jax
        if self.t0 is None and n == TRACE_FIRST_STEP:
            t = time.perf_counter()
            jax.profiler.start_trace(str(self.out))
            self.span = jax.profiler.TraceAnnotation(xtrace.WINDOW)
            self.span.__enter__()
            self.t0 = time.perf_counter()
            self.overhead += self.t0 - t
        elif self.span is not None and time.perf_counter() - self.t0 >= TRACE_SECONDS:
            self.stop()

    def stop(self):
        import jax
        if self.span is None:
            return
        t = time.perf_counter()
        self.span.__exit__(None, None, None)
        self.span = None
        jax.profiler.stop_trace()
        self.overhead += time.perf_counter() - t


def run_cell(bench, cell, sizes, traffic, limits, seed, seconds, trace,
             t_start=T_START):
    """Serve the cell's window and check it; returns the result line's
    object, with 'diagnostics' (printed apart) before 'compared'."""
    import jax
    from repro.launch import serve

    cfg = repo_config(sizes)
    max_len, vocab = traffic["max_len"], sizes["vocab_size"]
    dev = jax.devices()[0]
    counters = sizes.get("counters") if trace else None
    from chipbench import weights
    t_ready = time.perf_counter()
    params = jax.block_until_ready(weights.make(cfg, seed))
    t_weights = time.perf_counter()
    # warm-up: the cell's one step shape, on requests the window never sends
    driver.run(serve, cfg, params,
               gen.wave(traffic, np.random.default_rng([seed, 1]), vocab),
               max_len, max_steps=2, counters=counters)
    setup_parts = {"start_to_jax_s": t_ready - t_start,
                   "weights_s": t_weights - t_ready,
                   "warm_up_s": time.perf_counter() - t_weights}
    # what set-up left behind is collected now and never scanned again, so
    # no long collection of it lands inside the window
    gc.collect()
    gc.freeze()
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event) if "compil" in event else None)
    rng = np.random.default_rng(seed)
    tracer = None
    if trace:
        shutil.rmtree(OUT_DIR / "trace", ignore_errors=True)
        tracer = Tracer(OUT_DIR / "trace")
    waves = []
    while True:
        requests = gen.wave(traffic, rng, vocab)
        waves.append(driver.run(serve, cfg, params, requests, max_len,
                                on_step=tracer.on_step if tracer and not waves else None,
                                counters=counters))
        if tracer:
            tracer.stop()
        if window_seconds(waves) + waves[-1].seconds > seconds:
            break
    in_window = len(compiles)
    stats_ = dev.memory_stats() or {}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": int(stats_.get("peak_bytes_in_use", 0))}
    attempted = sum(len(w.prompt_len) for w in waves)
    failed = sum(w.failed for w in waves)
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if trace:
        red = xtrace.reduce_dir(str(OUT_DIR / "trace"))
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        win = Window(sizes, traffic, waves,
                     window_seconds(waves) - tracer.overhead,
                     counts.peaks(dev.device_kind), red)
        metrics = {}
        for m in wanted(bench["per_layer"], cell["name"]):
            v = importlib.import_module(f"chipbench.metrics.{m['name']}").read(win)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
        result["breakdown"] = {"device_ops": red["device_ops"],
                               "idle_gaps": red["idle_gaps"]}
    else:
        e2e = end_to_end(waves, waves[0].t_start - t_start)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in wanted(bench["end_to_end"], cell["name"])}
    result.update(metrics=metrics, device=device)

    # the check: after the window, with the engines and their caches freed
    gc.unfreeze()
    gc.collect()
    done = [d for w in waves for d in w.done]
    compared = {}
    if done:
        pick = check.sample(done, np.random.default_rng([seed, 2]))
        got = check.gaps(sizes, params, [done[i] for i in pick], max_len)
        compared = check.compare(got, limits)
        compared["served_tokens"] = {"value": got["served"], "limit": None}
    result["correct"] = bool(done and failed == 0 and check.within(compared))
    compared["failed_requests"] = {"value": failed, "limit": 0}
    gaps = [np.diff(np.concatenate([[w.t_submit], w.t_end])) for w in waves]
    worst = max(range(len(waves)), key=lambda i: gaps[i].max())
    result["diagnostics"] = {
        "compile_events_in_window": in_window, **setup_parts,
        "wave_seconds": [w.seconds for w in waves],
        "longest_step_s": float(gaps[worst].max()),
        "longest_step_at": [worst, int(gaps[worst].argmax())]}
    result["compared"] = compared
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chipbench: no TPU (JAX's first device is {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    bench, cell, sizes, traffic, limits = load_cell(ROOT, args.workload)
    if len(devices) < cell["chips"]:
        print(f"chipbench: {cell['name']} needs {cell['chips']} chips, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    result = run_cell(bench, cell, sizes, traffic, limits, args.seed,
                      args.seconds, args.trace)
    print(f"diagnostics {json.dumps(result.pop('diagnostics'))}", file=sys.stderr)
    for name, c in result["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
