import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

This is the proof that the distribution config is coherent: every cell must
``.lower().compile()`` against the production meshes (16x16 single pod,
2x16x16 multi-pod) with real shardings, and the compiled artifact yields
the memory/cost/collective numbers for EXPERIMENTS.md §Dry-run / §Roofline.

Usage:
  python -m repro.launch.dryrun --arch qwen2-7b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod|--both] [--force]

Artifacts: artifacts/dryrun/<arch>__<shape>__<mesh>.json (resumable).
"""
import argparse
import json
import pathlib
import time
import traceback
from typing import Any, Dict

import jax

from repro.launch.mesh import make_production_mesh
from repro.launch.shapes import SHAPES, cell_supported, input_specs
from repro.launch.steps import install_rules, step_and_args
from repro.launch import hlo_analysis, hlo_stats
from repro.models import all_names, get_config
from repro.models.common import clear_sharding_rules

ARTIFACTS = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


def _attn_layer_spans(cfg, s: int):
    """[(n_layers, kv_span)]: how many layers attend over which span."""
    if cfg.family == "ssm":
        return []
    if cfg.family == "hybrid":
        return [(cfg.num_groups, s)]          # shared attn once per group
    if cfg.local_global:
        half = cfg.num_layers // 2
        return [(half, min(s, cfg.sliding_window)), (half, s)]
    return [(cfg.num_layers, s)]


def model_flops(cfg, shape) -> float:
    """Useful-work FLOPs for the cell (6ND train / 2ND prefill / 2N decode),
    plus attention score FLOPs over each layer's true kv span (sliding
    windows and hybrid shared-attention counted exactly)."""
    n_active = cfg.active_param_count()
    n_embed = cfg.vocab_size * cfg.d_model
    n_eff = n_active - (0 if cfg.tie_embeddings else n_embed)
    b, s = shape.global_batch, shape.seq_len
    h_dh = cfg.num_heads * cfg.head_dim
    if shape.kind in ("train", "prefill"):
        # causal: each query sees ~span/2 keys on average (full span) or
        # ~span keys (window smaller than the sequence)
        attn = 0.0
        for layers, span in _attn_layer_spans(cfg, s):
            avg_kv = span / 2 if span == s else span
            attn += 4.0 * layers * b * s * avg_kv * h_dh  # QK^T + PV
        if shape.kind == "train":
            return 6.0 * n_eff * b * s + 3.0 * attn
        return 2.0 * n_eff * b * s + attn
    # decode: one token per sequence reads each layer's kv span once
    attn_dec = sum(4.0 * layers * min(span, s) * h_dh * b
                   for layers, span in _attn_layer_spans(cfg, s))
    return 2.0 * n_eff * b + attn_dec


def _spec_bytes_per_device(tree, n_dev: int) -> float:
    total = 0.0
    for leaf in jax.tree.leaves(tree):
        n = 1
        for d in leaf.shape:
            n *= d
        nbytes = n * leaf.dtype.itemsize
        sh = getattr(leaf, "sharding", None)
        if sh is not None and hasattr(sh, "num_devices"):
            shards = sh.num_devices
            try:
                shard_shape = sh.shard_shape(leaf.shape)
                shard_n = 1
                for d in shard_shape:
                    shard_n *= d
                total += shard_n * leaf.dtype.itemsize
                continue
            except Exception:
                pass
        total += nbytes / n_dev
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: pathlib.Path, force: bool = False) -> Dict[str, Any]:
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    out = outdir / f"{arch}__{shape_name}__{mesh_name}.json"
    if out.exists() and not force:
        return json.loads(out.read_text())
    outdir.mkdir(parents=True, exist_ok=True)

    cfg = get_config(arch)
    kv_dt = os.environ.get("REPRO_KV_DTYPE", "")
    if kv_dt:
        import dataclasses as _dc
        cfg = _dc.replace(cfg, kv_cache_dtype=kv_dt)
    shape = SHAPES[shape_name]
    record: Dict[str, Any] = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "kind": shape.kind,
        "params": cfg.param_count(), "active_params": cfg.active_param_count(),
    }
    ok, why = cell_supported(cfg, shape_name)
    if not ok:
        record.update(status="skipped", reason=why)
        out.write_text(json.dumps(record, indent=1))
        return record

    t0 = time.time()
    try:
        from repro.sharding.rules import (ShardingStrategy,
                                          validate_divisibility)
        mesh = make_production_mesh(multi_pod=multi_pod)
        st = ShardingStrategy()
        if multi_pod:
            st = st.with_pod()
        install_rules(cfg, mesh, st)
        specs = input_specs(cfg, shape_name, mesh, st)
        mb = int(os.environ.get("REPRO_MICROBATCH", "1"))
        fn, args = step_and_args(cfg, shape.kind, specs, microbatches=mb)
        record["microbatches"] = mb
        chips = mesh.devices.size
        with mesh:
            # a decode step takes the donated cache, as the served one does
            donate = (1,) if shape.kind == "decode" else ()
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower

        mem = None
        try:
            ma = compiled.memory_analysis()
            if ma is not None:
                mem = {k: int(getattr(ma, k)) for k in
                       ("argument_size_in_bytes", "output_size_in_bytes",
                        "temp_size_in_bytes", "generated_code_size_in_bytes")
                       if hasattr(ma, k)}
        except Exception:
            pass
        cost = {}
        try:
            ca = compiled.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0]
            cost = {k: float(v) for k, v in ca.items()
                    if isinstance(v, (int, float))}
        except Exception:
            pass
        text = compiled.as_text()
        # trip-count-aware analysis (XLA cost_analysis counts loop bodies
        # once; see hlo_analysis docstring) — this is the §Roofline source.
        mcost = hlo_analysis.analyze(text)
        coll = hlo_stats.CollectiveStats(
            bytes_by_kind={k: int(v)
                           for k, v in mcost.collective_bytes.items()},
            count_by_kind={})
        mf = model_flops(cfg, shape)
        roof = hlo_stats.roofline_terms(
            {"flops": mcost.flops, "bytes accessed": mcost.traffic_bytes},
            coll, chips, mf)
        record.update(
            status="ok",
            lower_s=round(t_lower, 1), compile_s=round(t_compile, 1),
            chips=chips,
            arg_bytes_per_device=_spec_bytes_per_device(args, chips),
            memory_analysis=mem,
            xla_cost={k: cost.get(k) for k in ("flops", "bytes accessed")
                      if k in cost},
            hlo_cost=mcost.as_dict(),
            collectives=coll.as_dict(),
            model_flops=mf,
            roofline=roof.as_dict(),
            uneven_sharding=validate_divisibility(cfg, mesh, st),
            hlo_bytes=len(text),
        )
    except Exception as e:  # failures here are bugs in the system
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
    finally:
        clear_sharding_rules()
    record["wall_s"] = round(time.time() - t0, 1)
    out.write_text(json.dumps(record, indent=1))
    return record


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None,
                    choices=list(SHAPES) + [None])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both", action="store_true",
                    help="run single-pod AND multi-pod meshes")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=str(ARTIFACTS))
    args = ap.parse_args()
    outdir = pathlib.Path(args.out)

    archs = all_names() if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both else [args.multi_pod]

    n_ok = n_skip = n_err = 0
    for arch in archs:
        for shape in shapes:
            for mp in meshes:
                rec = run_cell(arch, shape, mp, outdir, force=args.force)
                tag = rec["status"]
                n_ok += tag == "ok"
                n_skip += tag == "skipped"
                n_err += tag == "error"
                extra = ""
                if tag == "ok":
                    r = rec["roofline"]
                    extra = (f"dom={r['dominant']} "
                             f"roofline={r['roofline_frac']:.3f} "
                             f"compile={rec['compile_s']}s")
                elif tag == "error":
                    extra = rec["error"][:120]
                print(f"[{tag:7s}] {arch:22s} {shape:12s} "
                      f"{'2x16x16' if mp else '16x16':8s} {extra}",
                      flush=True)
    print(f"done: {n_ok} ok, {n_skip} skipped, {n_err} errors")
    if n_err:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
