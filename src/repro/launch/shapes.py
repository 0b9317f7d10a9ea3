"""The assigned input-shape set, per-cell input specs, and skip logic.

Four canonical shapes per architecture (40 cells):
  train_4k    : seq 4096,   global_batch 256   -> train_step
  prefill_32k : seq 32768,  global_batch 32    -> prefill (forward)
  decode_32k  : cache 32768, global_batch 128  -> serve_step
  long_500k   : cache 524288, global_batch 1   -> serve_step (SSM/hybrid only)

``long_500k`` is skipped for pure full-attention architectures (see
DESIGN.md §4) — quadratic attention at 512k would misrepresent them.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models import decode
from ..models import params as MP
from ..models.config import ModelConfig
from ..sharding.rules import (ShardingStrategy, param_pspecs,
                              sanitize_spec)


@dataclasses.dataclass(frozen=True)
class ShapeSpec:
    name: str
    seq_len: int
    global_batch: int
    kind: str          # 'train' | 'prefill' | 'decode'


SHAPES: Dict[str, ShapeSpec] = {
    "train_4k": ShapeSpec("train_4k", 4096, 256, "train"),
    "prefill_32k": ShapeSpec("prefill_32k", 32768, 32, "prefill"),
    "decode_32k": ShapeSpec("decode_32k", 32768, 128, "decode"),
    "long_500k": ShapeSpec("long_500k", 524288, 1, "decode"),
}


def cell_supported(cfg: ModelConfig, shape: str) -> Tuple[bool, str]:
    if shape == "long_500k" and not cfg.supports_long_context:
        return False, ("full-attention architecture: 512k decode is "
                       "quadratic; skipped per assignment (DESIGN.md §4)")
    return True, ""


def _sh(mesh: Mesh, *axes) -> NamedSharding:
    return NamedSharding(mesh, P(*axes))


def _modality_spec(cfg: ModelConfig, batch: int, mesh: Mesh,
                   st: ShardingStrategy) -> Optional[jax.ShapeDtypeStruct]:
    dt = jnp.dtype(cfg.dtype)
    if cfg.family == "vlm":
        return jax.ShapeDtypeStruct((batch, cfg.num_patches, cfg.d_model), dt,
                                    sharding=_sh(mesh, st.batch, None, None))
    if cfg.family == "audio":
        return jax.ShapeDtypeStruct((batch, cfg.encoder_seq, cfg.d_model), dt,
                                    sharding=_sh(mesh, st.batch, None, None))
    return None


def batch_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                st: ShardingStrategy) -> Dict[str, Any]:
    """Training/prefill batch ShapeDtypeStructs (tokens + optional modality)."""
    b, s = shape.global_batch, shape.seq_len
    specs: Dict[str, Any] = {
        "tokens": jax.ShapeDtypeStruct((b, s), jnp.int32,
                                       sharding=_sh(mesh, st.batch, None)),
    }
    mod = _modality_spec(cfg, b, mesh, st)
    if mod is not None:
        specs["modality"] = mod
    return specs


def _cache_axis_for(cfg: ModelConfig, mesh: Mesh, st: ShardingStrategy,
                    batch: int):
    """(batch_axes, head_axis): shard heads over TP only when divisible;
    tiny-batch cells (long_500k) rely on head sharding."""
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(st.tp, 1)
    head_ok = cfg.num_kv_heads % tp == 0 and cfg.num_kv_heads >= tp
    baxes = st.batch if batch >= _axis_prod(mesh, st.batch) else None
    return baxes, (st.tp if head_ok else None)


def _axis_prod(mesh: Mesh, axes) -> int:
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    if axes is None:
        return 1
    out = 1
    for a in (axes if isinstance(axes, (tuple, list)) else (axes,)):
        out *= sizes[a]
    return out


def cache_pspecs(cfg: ModelConfig, batch: int, mesh: Mesh,
                 st: ShardingStrategy) -> Any:
    """PartitionSpecs for the decode cache tree (path-keyed)."""
    baxes, hax = _cache_axis_for(cfg, mesh, st, batch)
    shapes = decode.cache_shapes(cfg, batch, 8)   # structure only

    def spec_for(path: Tuple[str, ...], shape: tuple) -> P:
        name = path[-1]
        stacked_inner = (("self" in path and cfg.family == "vlm")
                         or ("mamba" in path and cfg.family == "hybrid"))
        n_lead = 1 + (1 if stacked_inner else 0)
        lead = [None] * n_lead
        if name in ("k", "v", "k_scale", "v_scale"):
            axes = decode.CROSS_AXES if "cross" in path else decode.KV_AXES
            mesh_axis = {"batch": baxes, "heads": hax}
            return P(*lead, *(mesh_axis.get(a) for a in axes))
        if name == "h":                     # mamba state (…,B,nh,st,hd)
            return P(*lead, baxes, hax, None, None)
        if name == "conv":                  # (…,B,K-1,CH)
            return P(*lead, baxes, None, st.tp)
        if name == "wkv":                   # (…,B,H,dk,dv)
            return P(*lead, baxes, hax, None, None)
        if name.startswith("shift"):        # (…,B,1,D)
            return P(*lead, baxes, None, None)
        raise KeyError(path)

    def walk(tree, path=()):
        if isinstance(tree, tuple):
            return spec_for(path, tree)
        return {k: walk(v, path + (k,)) for k, v in tree.items()}

    return walk(shapes)


def cache_specs_sharded(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                        st: ShardingStrategy) -> Any:
    specs = decode.cache_specs(cfg, shape.global_batch, shape.seq_len)
    pspecs = cache_pspecs(cfg, shape.global_batch, mesh, st)
    return jax.tree.map(
        lambda sd, p: jax.ShapeDtypeStruct(
            sd.shape, sd.dtype,
            sharding=NamedSharding(mesh, sanitize_spec(p, sd.shape, mesh))),
        specs, pspecs)


def decode_input_specs(cfg: ModelConfig, shape: ShapeSpec, mesh: Mesh,
                       st: ShardingStrategy) -> Dict[str, Any]:
    b = shape.global_batch
    baxes, _ = _cache_axis_for(cfg, mesh, st, b)
    return {
        "tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32,
                                       sharding=_sh(mesh, baxes, None)),
        "pos": jax.ShapeDtypeStruct((), jnp.int32),
        "cache": cache_specs_sharded(cfg, shape, mesh, st),
    }


def param_specs_sharded(cfg: ModelConfig, mesh: Mesh,
                        st: ShardingStrategy) -> Any:
    dt = jnp.dtype(cfg.dtype)
    shapes = MP.param_shapes(cfg)
    pspecs = param_pspecs(cfg, st, mesh=mesh)

    def mk(lf, spec):
        return jax.ShapeDtypeStruct(lf[0], dt,
                                    sharding=NamedSharding(mesh, spec))

    return jax.tree.map(mk, shapes, pspecs, is_leaf=MP._is_leaf)


def opt_state_specs_sharded(cfg: ModelConfig, mesh: Mesh,
                            st: ShardingStrategy) -> Any:
    """AdamW m/v mirror params (fp32) + scalar step."""
    shapes = MP.param_shapes(cfg)
    pspecs = param_pspecs(cfg, st, mesh=mesh)

    def mk(lf, spec):
        return jax.ShapeDtypeStruct(lf[0], jnp.float32,
                                    sharding=NamedSharding(mesh, spec))

    mirror = jax.tree.map(mk, shapes, pspecs, is_leaf=MP._is_leaf)
    from ..optim.adamw import OptState
    return OptState(step=jax.ShapeDtypeStruct((), jnp.int32),
                    m=mirror, v=jax.tree.map(lambda x: x, mirror))


def input_specs(cfg: ModelConfig, shape_name: str, mesh: Mesh,
                st: ShardingStrategy) -> Dict[str, Any]:
    """Everything the step function needs, as sharded ShapeDtypeStructs."""
    shape = SHAPES[shape_name]
    params = param_specs_sharded(cfg, mesh, st)
    if shape.kind == "train":
        return {"state": {"params": params,
                          "opt": opt_state_specs_sharded(cfg, mesh, st)},
                "batch": batch_specs(cfg, shape, mesh, st)}
    if shape.kind == "prefill":
        return {"params": params, "batch": batch_specs(cfg, shape, mesh, st)}
    return {"params": params, **decode_input_specs(cfg, shape, mesh, st)}
