"""Perf-regression gate over BENCH_calyx.json.

Compares a freshly generated benchmark file against the committed
baseline and fails (exit 1) if any matching point's cycle count exceeds
the baseline by more than the tolerance (default 2%).  Points are
matched on (design, banks, share, opt_level); a schema-2 baseline (which
predates the scheduling layer) is read as opt_level 0.  Points present
only on one side are reported but never fail the gate — new designs and
a trimmed CI matrix are both expected.

Schema-4 files also carry per-point ``compile_us``/``verify_us`` stamps
(the stage-boundary verifier's share of compile time); the gate fails if
the aggregate verifier overhead — sum(verify_us) / sum(compile_us) over
the new file — exceeds ``--verify-overhead`` (default 15%; the
five-boundary suite measures ~13-14% across the full matrix, see the
"Static verification" section of the README).  Older files without the
stamps skip that check.

Schema-5 files additionally stamp the tracing-off simulator wall clock
per point (``sim_wall_us``); the gate fails if the new file's aggregate
tracing-off sim time grew beyond ``--sim-wall-overhead`` relative to the
baseline's — tracking the disabled trace hook's <2% overhead contract
across PRs (the hook is a single ``tracer is not None`` branch by
construction; the CI budget is looser than 2% because shared-runner
wall clocks are noisy, but a hook creeping into the hot path shows up
here as a step change).  The check is skipped when either file predates
the stamp — schema-4 baselines are read forward-compatibly.

    PYTHONPATH=src python scripts/check_perf_regression.py \
        --baseline BENCH_calyx.json --new /tmp/bench_new.json

The gate also covers ``BENCH_serve.json`` (the serving load harness,
``benchmarks/serve_bench.py``) via ``--serve-baseline``/``--serve-new``:
points are matched on (arch, profile) and fail when the new p99 TTFT
grows — or tokens/sec shrinks — beyond ``--serve-tolerance`` (default
3.0, i.e. 4x; latency quantiles of second-long CPU replays on shared
runners are far noisier than cycle counts, so this catches order-of-
magnitude breakage, not percent drift).  Independently of the baseline,
every new serve point's ``trace_overhead`` (tracing-off vs tracing-on
per-tick wall, measured in lockstep by the bench) must stay under
``--serve-trace-overhead`` (default 5%), every point must be
``deterministic`` and every request must have completed.  Each gate
(calyx, serve, resilience) may be run alone by passing only its file pair.

    PYTHONPATH=src python scripts/check_perf_regression.py \
        --serve-baseline BENCH_serve.json --serve-new /tmp/serve_new.json

``BENCH_resilience.json`` (the chaos/goodput harness,
``benchmarks/resilience_bench.py``) is gated via
``--resilience-baseline``/``--resilience-new``: ``zero_fault`` points
must keep ``resilience_overhead`` under ``--resilience-overhead``
(default 5%) and stay token-equivalent to the plain engine; every fault
campaign must be ``deterministic``, lose zero requests, and hold
``goodput`` at or above ``--resilience-goodput`` (default 0.90).
Against the baseline, a campaign's goodput may not drop by more than
0.05 absolute — goodput is a seeded count ratio, not a wall clock, so
the band only absorbs intentional campaign retuning, not noise.

    PYTHONPATH=src python scripts/check_perf_regression.py \
        --resilience-baseline BENCH_resilience.json \
        --resilience-new /tmp/resilience_new.json
"""
from __future__ import annotations

import argparse
import json
import sys
from typing import Dict, Tuple

Key = Tuple[str, int, bool, int]


def load(path: str) -> Tuple[int, Dict[Key, int],
                             Tuple[float, float], Dict[Key, float]]:
    with open(path) as f:
        data = json.load(f)
    schema = data.get("schema", 0)
    rows: Dict[Key, int] = {}
    sim_wall: Dict[Key, float] = {}
    compile_us = verify_us = 0.0
    for rec in data.get("records", []):
        if "error" in rec or "cycles" not in rec:
            continue
        key = (rec["design"], int(rec["banks"]), bool(rec["share"]),
               int(rec.get("opt_level", 0)))
        rows[key] = int(rec["cycles"])
        compile_us += float(rec.get("compile_us", 0.0))
        verify_us += float(rec.get("verify_us", 0.0))
        if "sim_wall_us" in rec:            # schema 5+
            sim_wall[key] = float(rec["sim_wall_us"])
    return schema, rows, (compile_us, verify_us), sim_wall


def load_serve(path: str) -> Dict[Tuple[str, str], dict]:
    with open(path) as f:
        data = json.load(f)
    return {(rec["arch"], rec["profile"]): rec
            for rec in data.get("records", [])}


def check_serve(args) -> Tuple[list, list]:
    """Returns (regressions, contract_failures) over the serve files."""
    base = load_serve(args.serve_baseline) if args.serve_baseline else {}
    new = load_serve(args.serve_new)
    regressions = []
    contract = []
    for key, rec in sorted(new.items()):
        ovh = float(rec.get("trace_overhead", 0.0))
        tag = "ok" if ovh < args.serve_trace_overhead else "FAIL"
        print(f"  serve {key}: trace_overhead={ovh:+.1%} "
              f"(limit {args.serve_trace_overhead:.0%}) {tag}")
        if ovh >= args.serve_trace_overhead:
            contract.append(f"{key}: trace overhead {ovh:+.1%}")
        if not rec.get("deterministic", False):
            contract.append(f"{key}: span stream not deterministic")
        if rec.get("completed") != rec.get("requests"):
            contract.append(
                f"{key}: {rec.get('completed')}/{rec.get('requests')} "
                f"requests completed")
        if key not in base:
            if base:
                print(f"  serve {key}: new point (no baseline)")
            continue
        ref = base[key]
        tol = args.serve_tolerance
        for metric, worse_is_bigger in (("ttft_us", True),
                                        ("tokens_per_sec", False)):
            new_v = rec["ttft_us"]["p99"] if worse_is_bigger \
                else float(rec[metric])
            ref_v = ref["ttft_us"]["p99"] if worse_is_bigger \
                else float(ref[metric])
            if ref_v <= 0:
                continue
            delta = (new_v - ref_v) / ref_v
            bad = (new_v > ref_v * (1.0 + tol)) if worse_is_bigger \
                else (new_v < ref_v / (1.0 + tol))
            name = "ttft_p99" if worse_is_bigger else metric
            print(f"  serve {key}: {name} {ref_v:.0f} -> {new_v:.0f} "
                  f"({delta:+.1%}) {'REGRESSION' if bad else 'ok'}")
            if bad:
                regressions.append(f"{key}: {name} {delta:+.1%} beyond "
                                   f"{tol:.0%} tolerance")
    return regressions, contract


def load_resilience(path: str) -> Dict[tuple, dict]:
    with open(path) as f:
        data = json.load(f)
    out = {}
    for rec in data.get("records", []):
        key = (rec["arch"], rec["profile"], rec["campaign"],
               rec.get("policy", ""), rec.get("fault_rate", 0.0))
        out[key] = rec
    return out


def check_resilience(args) -> Tuple[list, list]:
    """Returns (regressions, contract_failures) over the chaos files."""
    base = load_resilience(args.resilience_baseline) \
        if args.resilience_baseline else {}
    new = load_resilience(args.resilience_new)
    regressions = []
    contract = []
    for key, rec in sorted(new.items()):
        name = "/".join(str(k) for k in key if k != "")
        if rec["campaign"] == "zero_fault":
            ovh = float(rec.get("resilience_overhead", 0.0))
            tag = "ok" if ovh < args.resilience_overhead else "FAIL"
            print(f"  resilience {name}: overhead={ovh:+.1%} "
                  f"(limit {args.resilience_overhead:.0%}) {tag}")
            if ovh >= args.resilience_overhead:
                contract.append(f"{name}: armed zero-fault overhead "
                                f"{ovh:+.1%}")
            if not rec.get("equivalent", False):
                contract.append(f"{name}: armed engine diverged from "
                                f"the plain engine")
            continue
        goodput = float(rec.get("goodput", 0.0))
        lost = int(rec.get("lost", 0))
        det = bool(rec.get("deterministic", False))
        ok = (goodput >= args.resilience_goodput and lost == 0 and det)
        print(f"  resilience {name}: goodput={goodput:.2f} "
              f"(floor {args.resilience_goodput:.2f}) lost={lost} "
              f"det={det} {'ok' if ok else 'FAIL'}")
        if goodput < args.resilience_goodput:
            contract.append(f"{name}: goodput {goodput:.2f} below "
                            f"{args.resilience_goodput:.2f}")
        if lost:
            contract.append(f"{name}: {lost} request(s) lost")
        if not det:
            contract.append(f"{name}: chaos replay not deterministic")
        ref = base.get(key)
        if ref is None:
            if base:
                print(f"  resilience {name}: new point (no baseline)")
            continue
        drop = float(ref.get("goodput", 0.0)) - goodput
        if drop > 0.05:
            regressions.append(f"{name}: goodput dropped "
                               f"{drop:.2f} vs baseline")
    return regressions, contract


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--baseline",
                    help="committed BENCH_calyx.json")
    ap.add_argument("--new",
                    help="freshly generated benchmark JSON")
    ap.add_argument("--tolerance", type=float, default=0.02,
                    help="allowed relative cycle growth (default 2%%)")
    ap.add_argument("--verify-overhead", type=float, default=0.15,
                    help="max verifier share of compile time over the new "
                         "file's matrix (default 15%%; schema 4+ only)")
    ap.add_argument("--sim-wall-overhead", type=float, default=None,
                    help="max relative growth of the aggregate tracing-off "
                         "sim wall clock vs the baseline (schema 5+ on "
                         "both sides; skipped when unset or unstamped)")
    ap.add_argument("--serve-baseline",
                    help="committed BENCH_serve.json")
    ap.add_argument("--serve-new",
                    help="freshly generated serve benchmark JSON")
    ap.add_argument("--serve-tolerance", type=float, default=3.0,
                    help="allowed relative p99-TTFT growth / tokens-per-sec "
                         "shrink vs the serve baseline (default 3.0 = 4x; "
                         "serve walls are cross-machine noisy)")
    ap.add_argument("--serve-trace-overhead", type=float, default=0.05,
                    help="max per-point tracing overhead in the new serve "
                         "file (default 5%%)")
    ap.add_argument("--resilience-baseline",
                    help="committed BENCH_resilience.json")
    ap.add_argument("--resilience-new",
                    help="freshly generated resilience benchmark JSON")
    ap.add_argument("--resilience-goodput", type=float, default=0.90,
                    help="minimum goodput every fault campaign in the new "
                         "resilience file must hold (default 0.90)")
    ap.add_argument("--resilience-overhead", type=float, default=0.05,
                    help="max armed-but-idle per-tick overhead in the new "
                         "resilience file's zero_fault points (default 5%%)")
    args = ap.parse_args()
    if bool(args.baseline) != bool(args.new):
        ap.error("--baseline and --new must be given together")
    if args.serve_baseline and not args.serve_new:
        ap.error("--serve-baseline requires --serve-new")
    if args.resilience_baseline and not args.resilience_new:
        ap.error("--resilience-baseline requires --resilience-new")
    if not args.new and not args.serve_new and not args.resilience_new:
        ap.error("give --baseline/--new, --serve-new and/or "
                 "--resilience-new")

    regressions = []
    improved = 0
    new = {}
    overhead_fail = None
    sim_wall_fail = None
    if args.new:
        _, base, _, base_sim_wall = load(args.baseline)
        _, new, (compile_us, verify_us), new_sim_wall = load(args.new)
        for key, cycles in sorted(new.items()):
            if key not in base:
                print(f"  new point (no baseline): {key} -> {cycles} "
                      f"cycles")
                continue
            ref = base[key]
            delta = (cycles - ref) / ref if ref else 0.0
            tag = "ok"
            if cycles > ref * (1.0 + args.tolerance):
                regressions.append((key, ref, cycles, delta))
                tag = "REGRESSION"
            elif cycles < ref:
                improved += 1
                tag = "improved"
            print(f"  {key}: {ref} -> {cycles} cycles ({delta:+.1%}) "
                  f"{tag}")
        missing = sorted(set(base) - set(new))
        if missing:
            print(f"  ({len(missing)} baseline points not regenerated — "
                  f"trimmed matrix)")
        if compile_us > 0 and verify_us > 0:
            ratio = verify_us / compile_us
            tag = "ok" if ratio < args.verify_overhead else "FAIL"
            print(f"  verifier overhead: {verify_us / 1e3:.1f}ms of "
                  f"{compile_us / 1e3:.1f}ms compile = {ratio:.1%} "
                  f"(limit {args.verify_overhead:.0%}) {tag}")
            if ratio >= args.verify_overhead:
                overhead_fail = ratio
        shared = sorted(set(base_sim_wall) & set(new_sim_wall))
        if args.sim_wall_overhead is not None and shared:
            base_sum = sum(base_sim_wall[k] for k in shared)
            new_sum = sum(new_sim_wall[k] for k in shared)
            if base_sum > 0:
                growth = (new_sum - base_sum) / base_sum
                tag = "ok" if growth < args.sim_wall_overhead else "FAIL"
                print(f"  sim wall clock (tracing off, {len(shared)} "
                      f"shared points): {base_sum / 1e3:.1f}ms -> "
                      f"{new_sum / 1e3:.1f}ms ({growth:+.1%}, limit "
                      f"+{args.sim_wall_overhead:.0%}) {tag}")
                if growth >= args.sim_wall_overhead:
                    sim_wall_fail = growth
        elif args.sim_wall_overhead is not None:
            print("  sim wall clock check skipped (no shared schema-5 "
                  "points)")
    serve_regressions, serve_contract = ([], [])
    if args.serve_new:
        serve_regressions, serve_contract = check_serve(args)
    res_regressions, res_contract = ([], [])
    if args.resilience_new:
        res_regressions, res_contract = check_resilience(args)
    if regressions:
        print(f"\nFAIL: {len(regressions)} point(s) regressed beyond "
              f"{args.tolerance:.0%}:")
        for key, ref, cycles, delta in regressions:
            print(f"  {key}: {ref} -> {cycles} ({delta:+.1%})")
        return 1
    if overhead_fail is not None:
        print(f"\nFAIL: stage-boundary verifier costs {overhead_fail:.1%} "
              f"of compile time (limit {args.verify_overhead:.0%})")
        return 1
    if sim_wall_fail is not None:
        print(f"\nFAIL: tracing-off sim wall clock grew "
              f"{sim_wall_fail:+.1%} over the baseline (limit "
              f"+{args.sim_wall_overhead:.0%})")
        return 1
    if serve_regressions or serve_contract:
        for msg in serve_regressions + serve_contract:
            print(f"\nFAIL: serve {msg}")
        return 1
    if res_regressions or res_contract:
        for msg in res_regressions + res_contract:
            print(f"\nFAIL: resilience {msg}")
        return 1
    print(f"\nOK: no regressions (calyx: {improved} improved, "
          f"{len(new)} points checked)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
