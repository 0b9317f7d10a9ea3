"""Readings that a cell's limit is set from, at the cell's own size.

    python3 chipbench/calibrate.py --workload <cell> --seeds 11 12 13 ... [--out FILE]

For each seed, in one process: the weights from the seed, one wave of the
cell's traffic through the same engine and step the timed window drives,
and then, over the same sample of served requests that a run takes, the
widest and the mean logit gap of the served tokens (lower readings) and of
the tokens the fp8 control puts first (upper readings).  Both are judged by
the cell's limits file as a run judges its numbers: ``program_correct`` and
``control_correct`` (which has to come out false).  The benchmark's own runs
do not run the control.  One JSON line per seed.
"""
import argparse
import json
import pathlib
import sys
import time

HERE = pathlib.Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

import numpy as np  # noqa: E402

from chipbench import check, driver, gen, run  # noqa: E402


def readings(cell_name, sizes, traffic, limits, seed):
    import jax
    from repro.launch import serve
    from chipbench import weights
    cfg = run.repo_config(sizes)
    params = weights.make(cfg, seed)
    w = driver.run(serve, cfg, params,
                   gen.wave(traffic, np.random.default_rng(seed), sizes["vocab_size"]),
                   traffic["max_len"])
    pick = check.sample(w.done, np.random.default_rng([seed, 2]))
    t = time.perf_counter()
    got = check.gaps(sizes, params, [w.done[i] for i in pick], traffic["max_len"],
                     control=True)
    del params
    return dict(cell=cell_name, seed=seed, failed=w.failed, wave_s=w.seconds,
                steps=len(w.t_end), check_s=time.perf_counter() - t, **got,
                program_correct=w.failed == 0 and check.within(check.compare(got, limits)),
                control_correct=check.within(
                    check.compare(check.control_numbers(got), limits)))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--out")
    args = ap.parse_args(argv)
    import jax
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    _, _, sizes, traffic, limits = run.load_cell(run.ROOT, args.workload)
    for seed in args.seeds:
        line = json.dumps(readings(args.workload, sizes, traffic, limits, seed))
        print(line, flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
