"""Benchmark harness — one section per paper table/figure + system benches.

Prints ``name,us_per_call,derived`` CSV per line.  Sections:
  paper_tables      Fig 2 / Table 1 / Fig 3 / Table 2 reproduction
  banking_ablation  layout-vs-branchy, restructuring, port model, MoE HLO
  calyx_bench       simulator/estimator differential -> BENCH_calyx.json
  serve_bench       serving load harness -> BENCH_serve.json
  resilience_bench  chaos/goodput harness -> BENCH_resilience.json
  kernel_bench      Pallas kernel microbenches (interpret mode)
  roofline_report   roofline rows of the dry-run cells, when present
"""
from __future__ import annotations

import sys
import time
import traceback


def _emit(name: str, us_per_call: float, derived) -> None:
    print(f"{name},{us_per_call:.1f},{derived}", flush=True)


def main() -> None:
    sections = sys.argv[1:] or ["paper_tables", "banking_ablation",
                                "calyx_bench", "serve_bench",
                                "resilience_bench", "kernel_bench",
                                "roofline_report"]
    t0 = time.time()
    failures = []
    for section in sections:
        print(f"# --- {section} ---", flush=True)
        try:
            if section == "paper_tables":
                from benchmarks import paper_tables
                paper_tables.run(_emit)
            elif section == "banking_ablation":
                from benchmarks import banking_ablation
                banking_ablation.run(_emit)
            elif section == "calyx_bench":
                from benchmarks import calyx_bench
                calyx_bench.run(_emit)
            elif section == "serve_bench":
                from benchmarks import serve_bench
                serve_bench.run(_emit)
            elif section == "resilience_bench":
                from benchmarks import resilience_bench
                resilience_bench.run(_emit)
            elif section == "kernel_bench":
                from benchmarks import kernel_bench
                kernel_bench.run(_emit)
            elif section == "roofline_report":
                from benchmarks import roofline_report
                roofline_report.run(_emit)
            else:
                raise ValueError(f"unknown section {section}")
        except Exception as e:
            failures.append(section)
            print(f"# section {section} FAILED: {e}", flush=True)
            traceback.print_exc()
    print(f"# total {time.time() - t0:.1f}s; failures: {failures or 'none'}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
