"""Parameter trees: shapes, ShapeDtypeStruct specs, initialization, counting.

Shapes are the single source of truth: ``param_shapes`` builds a pytree whose
leaves are (shape tuple, init kind); ``param_specs`` wraps them into
ShapeDtypeStructs (dry-run — never allocates); ``init_params`` materializes
(smoke tests / small training only).
"""
from __future__ import annotations

import math
from typing import Any, Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .config import ModelConfig

Leaf = Tuple[tuple, str]          # (shape, init_kind)


def _leaf(shape, kind="normal") -> Leaf:
    return (tuple(int(s) for s in shape), kind)


def _is_leaf(x) -> bool:
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
            and isinstance(x[1], str))


# ---------------------------------------------------------------------------
# Per-layer shape builders
# ---------------------------------------------------------------------------


def norm_shapes(cfg: ModelConfig) -> Dict[str, Leaf]:
    d = cfg.d_model
    if cfg.family in ("audio", "ssm"):
        return {"w": _leaf((d,), "ones"), "b": _leaf((d,), "zeros")}
    return {"w": _leaf((d,), "zeros" if cfg.name.startswith("gemma")
                       else "ones")}


def attn_shapes(cfg: ModelConfig, cross: bool = False) -> Dict[str, Any]:
    d, h, hkv, dh = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s: Dict[str, Any] = {
        "wq": _leaf((d, h * dh)),
        "wk": _leaf((d, hkv * dh)),
        "wv": _leaf((d, hkv * dh)),
        "wo": _leaf((h * dh, d)),
    }
    if cfg.qkv_bias:
        s["bq"] = _leaf((h * dh,), "zeros")
        s["bk"] = _leaf((hkv * dh,), "zeros")
        s["bv"] = _leaf((hkv * dh,), "zeros")
    if cfg.qk_norm:
        s["q_norm"] = _leaf((h * dh,), "ones")
        s["k_norm"] = _leaf((hkv * dh,), "ones")
    return s


def gated_mlp(cfg: ModelConfig) -> bool:
    return cfg.act == "silu" or cfg.name.startswith("gemma")


def mlp_shapes(cfg: ModelConfig, d_ff: int = 0) -> Dict[str, Leaf]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    s = {"wi": _leaf((d, f)), "wo": _leaf((f, d))}
    if gated_mlp(cfg):
        s["wg"] = _leaf((d, f))
    return s


def moe_shapes(cfg: ModelConfig) -> Dict[str, Leaf]:
    d, f, e = cfg.d_model, cfg.d_ff, cfg.num_experts
    s = {"router": _leaf((d, e)),
         "w1": _leaf((e, d, f)), "w2": _leaf((e, f, d))}
    if gated_mlp(cfg):
        s["wg"] = _leaf((e, d, f))
    return s


def dense_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    s = {"ln1": norm_shapes(cfg), "attn": attn_shapes(cfg),
         "ln2": norm_shapes(cfg), "mlp": mlp_shapes(cfg)}
    if cfg.name.startswith("gemma"):
        s["ln1_post"] = norm_shapes(cfg)
        s["ln2_post"] = norm_shapes(cfg)
    return s


def moe_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": norm_shapes(cfg), "attn": attn_shapes(cfg),
            "ln2": norm_shapes(cfg), "moe": moe_shapes(cfg)}


def cross_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    s = {"ln1": norm_shapes(cfg), "attn": attn_shapes(cfg),
         "ln2": norm_shapes(cfg), "mlp": mlp_shapes(cfg),
         "gate_attn": _leaf((), "zeros"), "gate_mlp": _leaf((), "zeros")}
    return s


def mamba_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    d = cfg.d_model
    d_inner = 2 * d
    nh = d_inner // cfg.ssm_head_dim
    st = cfg.ssm_state
    conv_ch = d_inner + 2 * st
    return {
        "ln": norm_shapes(cfg),
        "in_proj": _leaf((d, 2 * d_inner + 2 * st + nh)),
        "conv_w": _leaf((cfg.ssm_conv_width, conv_ch)),
        "conv_b": _leaf((conv_ch,), "zeros"),
        "A_log": _leaf((nh,), "a_log"),
        "D": _leaf((nh,), "ones"),
        "dt_bias": _leaf((nh,), "dt_bias"),
        "out_proj": _leaf((d_inner, d)),
    }


def rwkv_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    d, f = cfg.d_model, cfg.d_ff
    h, dh = cfg.num_heads, cfg.head_dim
    lora_w, lora_mix = 64, 32
    return {
        "ln1": {"w": _leaf((d,), "ones"), "b": _leaf((d,), "zeros")},
        "ln2": {"w": _leaf((d,), "ones"), "b": _leaf((d,), "zeros")},
        "tm": {
            "mu": _leaf((5, d), "half"),            # ddlerp bases (r,k,v,w,g)
            "mix_A": _leaf((d, 5 * lora_mix)),
            "mix_B": _leaf((5, lora_mix, d), "zeros"),
            "wr": _leaf((d, h * dh)), "wk": _leaf((d, h * dh)),
            "wv": _leaf((d, h * dh)), "wg": _leaf((d, h * dh)),
            "wo": _leaf((h * dh, d)),
            "w0": _leaf((d,), "decay_base"),
            "wlora_A": _leaf((d, lora_w)),
            "wlora_B": _leaf((lora_w, d), "zeros"),
            "u": _leaf((h, dh), "half"),
            "gn_w": _leaf((d,), "ones"), "gn_b": _leaf((d,), "zeros"),
        },
        "cm": {
            "mu_k": _leaf((d,), "half"), "mu_r": _leaf((d,), "half"),
            "wk": _leaf((d, f)), "wv": _leaf((f, d)), "wr": _leaf((d, d)),
        },
    }


def enc_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": norm_shapes(cfg), "attn": attn_shapes(cfg),
            "ln2": norm_shapes(cfg), "mlp": mlp_shapes(cfg)}


def dec_layer_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    return {"ln1": norm_shapes(cfg), "attn": attn_shapes(cfg),
            "ln2": norm_shapes(cfg), "cross": attn_shapes(cfg, cross=True),
            "ln3": norm_shapes(cfg), "mlp": mlp_shapes(cfg)}


# ---------------------------------------------------------------------------
# Group / model assembly
# ---------------------------------------------------------------------------


def _stack(n: int, tree):
    return jax.tree.map(lambda lf: ((n,) + lf[0], lf[1]), tree,
                        is_leaf=_is_leaf)


def group_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    fam = cfg.family
    if fam in ("dense",):
        if cfg.local_global:
            return {"local": dense_layer_shapes(cfg),
                    "global": dense_layer_shapes(cfg)}
        return {"lyr": dense_layer_shapes(cfg)}
    if fam == "moe":
        return {"lyr": moe_layer_shapes(cfg)}
    if fam == "vlm":
        n_self = cfg.cross_attn_every - 1
        return {"self": _stack(n_self, dense_layer_shapes(cfg)),
                "cross": cross_layer_shapes(cfg)}
    if fam == "hybrid":
        n_mamba = cfg.hybrid_attn_every - 1
        return {"mamba": _stack(n_mamba, mamba_layer_shapes(cfg))}
    if fam == "ssm":
        return {"lyr": rwkv_layer_shapes(cfg)}
    if fam == "audio":
        return {"lyr": dec_layer_shapes(cfg)}
    raise ValueError(fam)


def param_shapes(cfg: ModelConfig) -> Dict[str, Any]:
    d, v = cfg.d_model, cfg.vocab_size
    tree: Dict[str, Any] = {
        "embed": _leaf((v, d), "embed"),
        "blocks": _stack(cfg.num_groups, group_shapes(cfg)),
        "final_norm": norm_shapes(cfg),
    }
    if not cfg.tie_embeddings:
        tree["lm_head"] = _leaf((d, v))
    if cfg.family == "hybrid":
        tree["shared_block"] = dense_layer_shapes(cfg)
    if cfg.family == "audio":
        tree["encoder"] = _stack(cfg.encoder_layers, enc_layer_shapes(cfg))
        tree["enc_norm"] = norm_shapes(cfg)
    return tree


# ---------------------------------------------------------------------------
# Specs / init / counting
# ---------------------------------------------------------------------------


def param_specs(cfg: ModelConfig) -> Any:
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda lf: jax.ShapeDtypeStruct(lf[0], dt),
                        param_shapes(cfg), is_leaf=_is_leaf)


def _init_leaf(rng: np.random.Generator, lf: Leaf, dtype, d_model: int):
    shape, kind = lf
    if kind == "zeros":
        return jnp.zeros(shape, dtype)
    if kind == "ones":
        return jnp.ones(shape, dtype)
    if kind == "half":
        return jnp.full(shape, 0.5, dtype)
    if kind == "a_log":
        return jnp.asarray(np.log(rng.uniform(1, 16, shape)), dtype)
    if kind == "dt_bias":
        return jnp.asarray(np.log(np.expm1(rng.uniform(1e-3, 0.1, shape))),
                           dtype)
    if kind == "decay_base":
        return jnp.asarray(rng.uniform(-7.0, -5.0, shape), dtype)
    # fan-in is the contraction dim, second from last: a leading dim of a
    # stacked leaf counts layers (or experts), not inputs
    fan_in = shape[-2] if len(shape) >= 2 else 1
    scale = 0.02 if kind == "embed" else 1.0 / math.sqrt(fan_in)
    arr = rng.normal(0.0, scale, shape).astype(np.float32)
    return jnp.asarray(arr, dtype)


def init_params(cfg: ModelConfig, seed: int = 0) -> Any:
    rng = np.random.default_rng(seed)
    dt = jnp.dtype(cfg.dtype)
    return jax.tree.map(lambda lf: _init_leaf(rng, lf, dt, cfg.d_model),
                        param_shapes(cfg), is_leaf=_is_leaf)


def count_params(cfg: ModelConfig, active_only: bool = False) -> int:
    total = 0
    expert_frac = (cfg.experts_per_token / cfg.num_experts
                   if cfg.num_experts else 1.0)

    def visit(tree, path=""):
        nonlocal total
        if _is_leaf(tree):
            n = 1
            for s in tree[0]:
                n *= s
            if active_only and "/moe/w" in path:
                n = int(n * expert_frac)
            total += n
            return
        for k, v in tree.items():
            visit(v, f"{path}/{k}")

    visit(param_shapes(cfg))
    return total
