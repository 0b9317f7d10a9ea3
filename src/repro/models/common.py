"""Shared layers: norms, RoPE, activations, embedding, sharding constraints."""
from __future__ import annotations

from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from .config import ModelConfig


# ---------------------------------------------------------------------------
# Logical-axis sharding constraints.  The launcher installs rules; model code
# annotates activations with logical axes and stays mesh-agnostic.
# ---------------------------------------------------------------------------

_RULES: dict = {}
_MESH = None


def set_sharding_rules(mesh, rules: dict) -> None:
    global _RULES, _MESH
    _RULES, _MESH = dict(rules), mesh


def clear_sharding_rules() -> None:
    global _RULES, _MESH
    _RULES, _MESH = {}, None


def constrain(x: jax.Array, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axis names (no-op without rules).
    Axes whose mesh-shard count does not divide the dimension are dropped
    (e.g. vocab 51866 over 16-way TP) — GSPMD padding is legal but we keep
    input/constraint shardings even."""
    if _MESH is None:
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    sizes = dict(zip(_MESH.axis_names, _MESH.devices.shape))

    def nshards(ax):
        if ax is None:
            return 1
        axes = ax if isinstance(ax, (tuple, list)) else (ax,)
        out = 1
        for a in axes:
            out *= sizes[a]
        return out

    entries = []
    for dim, a in zip(x.shape, logical_axes):
        ax = _RULES.get(a) if a else None
        entries.append(ax if (ax and dim % nshards(ax) == 0) else None)
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(_MESH, P(*entries)))


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------


def rms_norm(x: jax.Array, w: jax.Array, eps: float = 1e-6,
             plus_one: bool = False) -> jax.Array:
    """Statistics in f32; the (B,S,D) data path stays in the model dtype
    (perf iteration 6 — no materialized f32 activation copies)."""
    dt = x.dtype
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps).astype(dt)
    scale = (1.0 + w.astype(jnp.float32)).astype(dt) if plus_one \
        else w.astype(dt)
    return x * inv * scale


def layer_norm(x: jax.Array, w: jax.Array, b: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dt = x.dtype
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    return ((x - mu.astype(dt)) * inv.astype(dt) * w.astype(dt)
            + b.astype(dt))


def norm(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    """Family-appropriate normalization.  p is dict with 'w' (+ 'b' for LN)."""
    if cfg.family in ("audio",) or cfg.family == "ssm":
        return layer_norm(x, p["w"], p["b"], eps=cfg.norm_eps)
    plus_one = cfg.name.startswith("gemma")
    return rms_norm(x, p["w"], eps=cfg.norm_eps, plus_one=plus_one)


# ---------------------------------------------------------------------------
# Activations
# ---------------------------------------------------------------------------


def activation(cfg: ModelConfig, x: jax.Array) -> jax.Array:
    if cfg.act == "silu":
        return jax.nn.silu(x)
    if cfg.act == "gelu":
        return jax.nn.gelu(x, approximate=True)
    if cfg.act == "relu":
        return jnp.maximum(x, 0.0)
    raise ValueError(cfg.act)


def softcap(x: jax.Array, cap: float) -> jax.Array:
    if not cap:
        return x
    return jnp.tanh(x / cap) * cap


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float, positions: jax.Array) -> Tuple:
    """positions: (..., S) int32 -> (cos, sin) of shape (..., S, head_dim//2)."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32)
                           / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: (B, H, S, D); cos/sin: (B, S, D/2) or (S, D/2).

    Rotations applied in the model dtype — cos/sin tables are cast once
    (tiny) instead of promoting the whole q/k tensors to f32."""
    if cos.ndim == 2:
        cos = cos[None]
        sin = sin[None]
    cos = cos[:, None].astype(x.dtype)    # (B, 1, S, D/2)
    sin = sin[:, None].astype(x.dtype)
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def sinusoid_pos(seq: int, dim: int, offset: int = 0) -> jax.Array:
    """Whisper-style fixed sinusoidal embeddings (S, D)."""
    pos = jnp.arange(offset, offset + seq, dtype=jnp.float32)[:, None]
    div = jnp.exp(-jnp.log(10000.0)
                  * jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    pe = jnp.zeros((seq, dim), jnp.float32)
    pe = pe.at[:, 0::2].set(jnp.sin(pos * div))
    pe = pe.at[:, 1::2].set(jnp.cos(pos * div))
    return pe


# ---------------------------------------------------------------------------
# Embedding / head
# ---------------------------------------------------------------------------


@jax.named_scope("embed")
def embed(cfg: ModelConfig, params, tokens: jax.Array) -> jax.Array:
    x = params["embed"][tokens]            # (B, S, D)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return constrain(x, "batch", "seq", "embed")


def lm_logits(cfg: ModelConfig, params, x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["lm_head"])
    logits = softcap(logits.astype(jnp.float32), cfg.logit_softcap)
    return constrain(logits, "batch", "seq", "vocab")
