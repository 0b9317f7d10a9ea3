"""Roofline report of the dry-run cells.

Reads the artifacts of a dry-run sweep
(``python -m repro.launch.dryrun --all --both``) and emits one row per
cell: its compute/memory/collective seconds and its dominant resource.
With no artifacts it emits nothing.
"""
from __future__ import annotations

import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]
ARTIFACTS = ROOT / "artifacts"


def load_cells(dirname: str):
    cells = []
    d = ARTIFACTS / dirname
    if not d.exists():
        return cells
    for p in sorted(d.glob("*.json")):
        cells.append(json.loads(p.read_text()))
    return cells


def run(emit) -> None:
    for label, dirname in (("base", "dryrun_baseline"),
                           ("opt", "dryrun_opt")):
        for r in load_cells(dirname):
            key = f"roofline_{label}_{r['arch']}_{r['shape']}_{r['mesh']}"
            if r["status"] == "skipped":
                emit(key, 0.0, "SKIP:full-attention @512k (DESIGN.md §4)")
                continue
            if r["status"] != "ok":
                emit(key, 0.0, f"ERROR:{r.get('error', '?')[:80]}")
                continue
            f = r["roofline"]
            emit(key, f["step_time_s"] * 1e6,
                 f"dom={f['dominant']}"
                 f"|compute_s={f['compute_s']:.4f}"
                 f"|memory_s={f['memory_s']:.4f}"
                 f"|collective_s={f['collective_s']:.4f}"
                 f"|useful_frac={f['useful_flops_frac']:.3f}"
                 f"|roofline_frac={f['roofline_frac']:.4f}")
