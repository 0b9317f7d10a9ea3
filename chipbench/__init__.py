"""On-chip benchmark of batched generation through ``repro.launch.serve.Engine``.

Entry point: ``python3 chipbench/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout (see run.py).
Configurations, traffic mixes, limits and per-layer metric readers are
files of their own under this directory, found by the names that
``BENCHMARK.json`` gives.
"""
