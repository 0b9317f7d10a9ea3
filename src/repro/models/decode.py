"""Decode path: cache construction + single-token serve step, all families.

``decode_*`` shapes lower THIS path (one new token against a static
seq_len-sized cache), not the training step.  Caches are stacked over scan
groups so the decode HLO also contains a single group body.

Cache layouts:
  dense/moe : {'k','v'} (G, [layers-per-group,] B, L, Hkv * dh), pos scalar
  vlm       : self caches + precomputed vision cross K/V
  hybrid    : mamba states (O(1)) + shared-attn KV cache
  ssm       : wkv state + shift states (O(1))
  audio     : decoder self cache + precomputed encoder cross K/V
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import mamba as M
from . import moe as MOE
from . import rwkv as R
from .common import embed, lm_logits, norm, sinusoid_pos
from .config import ModelConfig
from .mlp import mlp_block
from .transformer import encode_audio


# The axes of a cache's attention leaves below their stacking over layer
# groups.  A self-attention leaf (k, v and int8's k_scale, v_scale) holds a
# position's K or V of all heads as one row, (B, S_max, Hkv * dh); a cross
# cache (precomputed, never written) keeps a (position, dh) block a head.
# The dry-run's partition specs are read from these.
KV_AXES = ("batch", "position", "heads")
CROSS_AXES = ("batch", "heads", "position", "head_dim")
_KV_POSITION = KV_AXES.index("position") - len(KV_AXES)


def _kv_entry(cfg: ModelConfig, batch: int, max_len: int):
    """Self-attention cache entry laid out as :data:`KV_AXES`; int8 mode
    adds per-token scales, one a head."""
    def shape(per_head: int):
        size = {"batch": batch, "position": max_len,
                "heads": cfg.num_kv_heads * per_head}
        return tuple(size[a] for a in KV_AXES)
    entry = {"k": shape(cfg.head_dim), "v": shape(cfg.head_dim)}
    if cfg.kv_cache_dtype == "int8":
        entry["k_scale"] = entry["v_scale"] = shape(1)
    return entry


def _zeros(shape, dtype):
    return jnp.zeros(shape, dtype)


def _stack_shapes(n: int, tree):
    return jax.tree.map(lambda s: (n,) + s if isinstance(s, tuple) else s,
                        tree, is_leaf=lambda x: isinstance(x, tuple))


# ---------------------------------------------------------------------------
# Cache spec (shapes only — used by the dry-run) and init
# ---------------------------------------------------------------------------


def cache_shapes(cfg: ModelConfig, batch: int, max_len: int) -> Any:
    g = cfg.num_groups
    fam = cfg.family
    if fam == "dense" and cfg.local_global:
        local_len = min(max_len, cfg.sliding_window)
        per = {"local": _kv_entry(cfg, batch, local_len),
               "global": _kv_entry(cfg, batch, max_len)}
    elif fam in ("dense", "moe"):
        per = {"lyr": _kv_entry(cfg, batch, max_len)}
    elif fam == "vlm":
        n_self = cfg.cross_attn_every - 1
        cross_kv = (batch, cfg.num_kv_heads, cfg.num_patches, cfg.head_dim)
        per = {"self": _stack_shapes(n_self, _kv_entry(cfg, batch, max_len)),
               "cross": {"k": cross_kv, "v": cross_kv}}
    elif fam == "hybrid":
        n_mamba = cfg.hybrid_attn_every - 1
        per = {"mamba": _stack_shapes(n_mamba, M.mamba_cache_shape(cfg, batch)),
               "attn": _kv_entry(cfg, batch, max_len)}
    elif fam == "ssm":
        per = {"lyr": R.rwkv_cache_shape(cfg, batch)}
    elif fam == "audio":
        enc_kv = (batch, cfg.num_kv_heads, cfg.encoder_seq, cfg.head_dim)
        per = {"lyr": {"self": _kv_entry(cfg, batch, max_len),
                       "cross": {"k": enc_kv, "v": enc_kv}}}
    else:
        raise ValueError(fam)
    return _stack_shapes(g, per)


def _cache_leaf_dtype(cfg: ModelConfig, path_key: str, shape, parent):
    """int8 only for self-attn k/v whose sibling scale entry exists
    (cross caches are read raw by _cross_decode and stay full precision)."""
    if cfg.kv_cache_dtype == "int8":
        if path_key in ("k", "v") and f"{path_key}_scale" in parent:
            return jnp.dtype(jnp.int8)
        if path_key.endswith("_scale"):
            return jnp.dtype(jnp.float32)
    return jnp.dtype(cfg.dtype)


def _map_cache(cfg: ModelConfig, tree, fn):
    """Map over cache leaves with their dict-key names + parent dict."""
    def walk(t, key="", parent=None):
        if isinstance(t, tuple):
            return fn(key, t, parent or {})
        return {k: walk(v, k, t) for k, v in t.items()}
    return walk(tree)


def cache_specs(cfg: ModelConfig, batch: int, max_len: int):
    return _map_cache(
        cfg, cache_shapes(cfg, batch, max_len),
        lambda key, s, par: jax.ShapeDtypeStruct(
            s, _cache_leaf_dtype(cfg, key, s, par)))


def init_cache(cfg: ModelConfig, params, batch: int, max_len: int,
               modality: Optional[jax.Array] = None):
    """Materialize an empty cache; precompute cross K/V where applicable."""
    dt = jnp.dtype(cfg.dtype)
    cache = _map_cache(
        cfg, cache_shapes(cfg, batch, max_len),
        lambda key, s, par: _zeros(s, _cache_leaf_dtype(cfg, key, s, par)))
    if cfg.family == "vlm" and modality is not None:
        def fill(gp, c):
            _, kx, vx = A.qkv_proj(cfg, gp["cross"]["attn"], modality,
                                   kv_x=modality)
            c = dict(c)
            c["cross"] = {"k": kx.astype(dt), "v": vx.astype(dt)}
            return c
        groups = [fill(jax.tree.map(lambda a: a[i], params["blocks"]),
                       jax.tree.map(lambda a: a[i], cache))
                  for i in range(cfg.num_groups)]
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    if cfg.family == "audio" and modality is not None:
        enc = encode_audio(cfg, params, modality)
        def fill(gp, c):
            _, kx, vx = A.qkv_proj(cfg, gp["lyr"]["cross"], enc, kv_x=enc)
            c = dict(c)
            c["lyr"] = dict(c["lyr"])
            c["lyr"]["cross"] = {"k": kx.astype(dt), "v": vx.astype(dt)}
            return c
        groups = [fill(jax.tree.map(lambda a: a[i], params["blocks"]),
                       jax.tree.map(lambda a: a[i], cache))
                  for i in range(cfg.num_groups)]
        cache = jax.tree.map(lambda *xs: jnp.stack(xs), *groups)
    return cache


# ---------------------------------------------------------------------------
# Cross-attention against a precomputed cache (no causal mask)
# ---------------------------------------------------------------------------


def _cross_decode(cfg: ModelConfig, p, x1, kc, vc):
    b = x1.shape[0]
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // hkv
    q = jnp.einsum("bsd,de->bse", x1, p["wq"])
    if cfg.qkv_bias:
        q = q + p["bq"]
    q = q.reshape(b, 1, h, dh).transpose(0, 2, 1, 3)
    qg = q.reshape(b, hkv, g, 1, dh)
    s = jnp.einsum("bhgqd,bhkd->bhgqk", qg.astype(jnp.float32),
                   kc.astype(jnp.float32)) / (dh ** 0.5)
    pgs = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bhkd->bhgqd", pgs, vc.astype(jnp.float32))
    out = out.reshape(b, h, 1, dh).transpose(0, 2, 1, 3).reshape(b, 1, -1)
    return jnp.einsum("bse,ed->bsd", out.astype(x1.dtype), p["wo"])


# ---------------------------------------------------------------------------
# Per-family group decode steps
# ---------------------------------------------------------------------------


def _dense_decode(cfg, p, x1, c, pos, lens=None, window=0, ring=False):
    h = norm(cfg, p["ln1"], x1)
    a, rows = A.attn_decode(cfg, p["attn"], h, c, pos, lens=lens,
                            window=window, attn_softcap=cfg.attn_softcap,
                            ring=ring)
    if "ln1_post" in p:
        a = norm(cfg, p["ln1_post"], a)
    x1 = x1 + a
    h = norm(cfg, p["ln2"], x1)
    m = mlp_block(cfg, p["mlp"], h)
    if "ln2_post" in p:
        m = norm(cfg, p["ln2_post"], m)
    return x1 + m, rows


def _group_decode(cfg: ModelConfig, params, pos, lens):
    """``step(x1, group params, group cache) -> (x1, new)``: ``new`` holds,
    for each cache leaf, the new token's K/V rows or the whole new state
    (cross caches pass through), as :func:`write_cache` takes them.  A
    self-attention entry of the group cache may be every layer's K/V with
    the layer's number, or for vlm the numbers of the group's self-attention
    layers (:func:`join_kv`)."""
    fam = cfg.family

    if fam == "dense" and cfg.local_global:
        def step(x1, gp, gc):
            x1, cl = _dense_decode(cfg, gp["local"], x1, gc["local"], pos,
                                   window=cfg.sliding_window, ring=True)
            x1, cg = _dense_decode(cfg, gp["global"], x1, gc["global"], pos,
                                   lens)
            return x1, {"local": cl, "global": cg}
    elif fam == "dense":
        def step(x1, gp, gc):
            x1, c = _dense_decode(cfg, gp["lyr"], x1, gc["lyr"], pos, lens)
            return x1, {"lyr": c}
    elif fam == "moe":
        def step(x1, gp, gc):
            p = gp["lyr"]
            h = norm(cfg, p["ln1"], x1)
            a, rows = A.attn_decode(cfg, p["attn"], h, gc["lyr"], pos,
                                    lens=lens)
            x1 = x1 + a
            h = norm(cfg, p["ln2"], x1)
            y, _ = MOE.moe_block(cfg, p["moe"], h)
            return x1 + y, {"lyr": rows}
    elif fam == "vlm":
        def step(x1, gp, gc):
            layers, kv = split_kv(gc["self"])

            def body(xx, lpc):
                lp, lc = lpc
                return _dense_decode(cfg, lp, xx, join_kv(lc, kv), pos, lens)
            x1_, self_new = jax.lax.scan(body, x1, (gp["self"], layers))
            p = gp["cross"]
            h = norm(cfg, p["ln1"], x1_)
            a = _cross_decode(cfg, p["attn"], h, gc["cross"]["k"],
                              gc["cross"]["v"])
            x1_ = x1_ + a * jnp.tanh(p["gate_attn"]).astype(a.dtype)
            h = norm(cfg, p["ln2"], x1_)
            m = mlp_block(cfg, p["mlp"], h)
            x1_ = x1_ + m * jnp.tanh(p["gate_mlp"]).astype(m.dtype)
            return x1_, {"self": self_new, "cross": gc["cross"]}
    elif fam == "hybrid":
        shared = params["shared_block"]

        def step(x1, gp, gc):
            def body(xx, lpc):
                lp, lc = lpc
                delta, lc_new = M.mamba_decode_step(cfg, lp, xx, lc)
                return xx + delta, lc_new
            x1_, mamba_new = jax.lax.scan(body, x1,
                                          (gp["mamba"], gc["mamba"]))
            x1_, attn_new = _dense_decode(cfg, shared, x1_, gc["attn"], pos,
                                          lens)
            return x1_, {"mamba": mamba_new, "attn": attn_new}
    elif fam == "ssm":
        def step(x1, gp, gc):
            x1, c = R.rwkv_decode_step(cfg, gp["lyr"], x1, gc["lyr"])
            return x1, {"lyr": c}
    elif fam == "audio":
        def step(x1, gp, gc):
            p = gp["lyr"]
            h = norm(cfg, p["ln1"], x1)
            a, rows = A.attn_decode(cfg, p["attn"], h, gc["lyr"]["self"],
                                    pos, lens=lens)
            x1 = x1 + a
            h = norm(cfg, p["ln2"], x1)
            x1 = x1 + _cross_decode(cfg, p["cross"], h,
                                    gc["lyr"]["cross"]["k"],
                                    gc["lyr"]["cross"]["v"])
            h = norm(cfg, p["ln3"], x1)
            x1 = x1 + mlp_block(cfg, p["mlp"], h)
            return x1, {"lyr": {"self": rows, "cross": gc["lyr"]["cross"]}}
    else:
        raise ValueError(fam)
    return step


# The named scopes of the served step (``jax.named_scope``: metadata on each
# operation, no operation of its own).  In a profiler trace an operation of
# ``jit_serve_step`` carries the innermost of them: ``layers`` is the layer
# loop itself (reading each layer's parameters and cache), ``kv_write`` (in
# ``layers``; the trace reader's ``attn/kv_write``) the write of every
# layer's new K/V rows into the cache after the loop.  Nested in ``attn``,
# ``qk_norm`` (where the config has it); in ``moe``, its parts ``route``,
# ``dispatch``, ``experts`` and ``combine`` (``models/moe.py``).
SERVE_SCOPES = ("embed", "layers", "attn", "attn/kv_write", "attn/qk_norm",
                "mlp", "moe", "moe/route", "moe/dispatch", "moe/experts",
                "moe/combine", "head")


def _write_leaf(pos, leaf, new):
    """One cache leaf after a step.  A state comes back whole and replaces
    the leaf; a K/V leaf gets back the new token's row (length 1 on its
    position axis, see :data:`KV_AXES`), written in place at ``pos`` mod
    the leaf's length: the ring slot of a sliding-window layer, ``pos``
    itself otherwise."""
    if new.shape == leaf.shape:
        return new
    axis = leaf.ndim + _KV_POSITION
    start = [0] * leaf.ndim
    start[axis] = jnp.mod(pos, leaf.shape[axis])
    return jax.lax.dynamic_update_slice(leaf, new.astype(leaf.dtype), start)


def write_cache(cache, new, pos):
    """The cache after a step, from what the step returned for each leaf
    (see :func:`_write_leaf`)."""
    return jax.tree.map(functools.partial(_write_leaf, pos), cache, new)


def split_kv(cache, name: str = ""):
    """``(xs, kv)`` for a loop over a cache (or part of one): each
    self-attention K/V entry, its leaves laid out as :data:`KV_AXES` behind
    the loops' axes (the layer groups; for vlm also the self-attention
    layers of a group), is replaced in ``xs`` by its layer numbers, in the
    shape of those axes, and given in ``kv`` with those axes merged into one
    layer axis, a free reshape.  An entry :func:`join_kv` made (every
    layer's K/V and the numbers of a group's layers) gives back its numbers
    and K/V, for a loop nested in the group's.  Attention then reads a
    layer's K/V where it lies in the stacked cache: a kernel given a slice
    of it would have XLA copy the slice out first.  Cross caches pass as
    they are."""
    if not isinstance(cache, dict) or name == "cross":
        return cache, None
    if "layer" in cache:
        return cache["layer"], {n: t for n, t in cache.items()
                                if n != "layer"}
    if "k" in cache and cache["k"].ndim > len(KV_AXES):
        loops = cache["k"].shape[:-len(KV_AXES)]
        layers = jnp.arange(math.prod(loops), dtype=jnp.int32)
        return layers.reshape(loops), {
            n: t.reshape((-1,) + t.shape[len(loops):])
            for n, t in cache.items()}
    xs, kv = {}, {}
    for n, t in cache.items():
        xs[n], sub = split_kv(t, n)
        if sub is not None:
            kv[n] = sub
    return xs, kv or None


def join_kv(gc, kv):
    """One loop step's cache from :func:`split_kv`'s ``xs`` as the loop
    slices it: each K/V entry becomes every layer's K/V with this step's
    layer numbers under ``'layer'`` (a scalar in the innermost loop)."""
    if kv is None:
        return gc
    if "k" in kv and not isinstance(kv["k"], dict):
        return dict(kv, layer=gc)
    return {n: join_kv(t, kv.get(n)) for n, t in gc.items()}


def serve_step(cfg: ModelConfig, params, cache, tokens: jax.Array, pos,
               lens: Optional[jax.Array] = None) -> Tuple[jax.Array, Any]:
    """tokens: (B, 1) int32; pos: scalar int32 (next write position); lens:
    (B,) int32, the cached positions each slot attends (default ``pos`` for
    every slot; a free slot may give 0, and its output is the caller's to
    discard).

    Returns (logits (B, 1, V), updated cache).  The layer loop only reads
    the cache, each layer's K/V where it lies (:func:`split_kv`); each K/V
    leaf gets the new token's rows of every layer in one write after the
    loop, so with the cache donated (:func:`make_serve_step`) the step
    updates it in place.
    """
    x1 = embed(cfg, params, tokens)
    if cfg.family == "audio":
        table = sinusoid_pos(cache_max_len(cfg, cache), cfg.d_model)
        pe = jax.lax.dynamic_slice_in_dim(table, pos, 1)
        x1 = x1 + pe[None].astype(x1.dtype)
    blocks, stacked = params["blocks"], {}
    with jax.named_scope("layers"):
        if cfg.scan_layers and cfg.family == "moe" \
                and cfg.moe_dispatch == "grouped":
            blocks, stacked = MOE.split_stacked(blocks)
        cache_xs, kv = split_kv(cache)
        if lens is None:
            lens = jnp.full((tokens.shape[0],), pos, jnp.int32)
    step = _group_decode(cfg, params, pos, lens)

    def body(carry, gpc):
        gp, gc = gpc
        return step(carry, MOE.join_stacked(gp, stacked), join_kv(gc, kv))

    with jax.named_scope("layers"):
        if cfg.scan_layers:
            x1, new = jax.lax.scan(body, x1, (blocks, cache_xs))
        else:
            per_group = []
            for i in range(cfg.num_groups):
                gp = jax.tree.map(lambda a: a[i], params["blocks"])
                gc = jax.tree.map(lambda a: a[i], cache_xs)
                x1, gc_new = step(x1, gp, join_kv(gc, kv))
                per_group.append(gc_new)
            new = jax.tree.map(lambda *xs: jnp.stack(xs), *per_group)
        with jax.named_scope("kv_write"):
            new_cache = write_cache(cache, new, pos)
    with jax.named_scope("head"):
        x1 = norm(cfg, params["final_norm"], x1)
        return lm_logits(cfg, params, x1), new_cache


def cache_max_len(cfg: ModelConfig, cache) -> int:
    """Decoder self-attention cache length (the position-table size)."""
    if cfg.family == "audio":
        return cache["lyr"]["self"]["k"].shape[_KV_POSITION]
    leaves = jax.tree.leaves(cache)
    return max((l.shape[_KV_POSITION] for l in leaves if l.ndim >= 4),
               default=1)


# ---------------------------------------------------------------------------
# Serving plumbing: shared jitted step + per-step stats for observability
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def make_serve_step(cfg: ModelConfig):
    """Jitted :func:`serve_step` closed over ``cfg``, cached per config.

    Every engine/driver built on the same config shares one compilation —
    a fresh ``jax.jit(lambda ...)`` per caller would retrace on each
    instantiation, which both wastes compile time and poisons wall-clock
    comparisons between instrumented and uninstrumented runs of the same
    workload (the serve benchmark measures exactly that differential).
    The program is named ``jit_serve_step`` in traces and compile logs.

    The cache is donated: the returned cache takes over its buffers, and
    the one passed in is deleted once the call dispatches, so a caller
    rebinds its cache to the result.
    """
    def step(params, cache, tokens, pos, lens=None):
        return serve_step(cfg, params, cache, tokens, pos, lens)

    step.__name__ = step.__qualname__ = "serve_step"
    return jax.jit(step, donate_argnums=(1,))


def cache_num_bytes(cache) -> int:
    """Total bytes held by the cache leaves (the serving-memory gauge)."""
    return sum(leaf.size * leaf.dtype.itemsize
               for leaf in jax.tree.leaves(cache))


def step_stats(cfg: ModelConfig, cache) -> Dict[str, int]:
    """Static per-step facts the serving metrics export as gauges: cache
    footprint/length and the approximate FLOPs one decoded token costs
    (2 x active parameters — the standard decode estimate)."""
    from .params import count_params
    return {
        "cache_bytes": cache_num_bytes(cache),
        "cache_max_len": cache_max_len(cfg, cache),
        "approx_flops_per_token": 2 * count_params(cfg, active_only=True),
    }


# ---------------------------------------------------------------------------
# Per-slot cache surgery (quarantine + fault injection — ``repro.launch``)
# ---------------------------------------------------------------------------


@functools.lru_cache(maxsize=None)
def cache_batch_axes(cfg: ModelConfig):
    """Tree (matching the fused cache structure) giving each cache leaf's
    batch-axis index, discovered by diffing shape templates at two batch
    sizes — the one differing dim per leaf is the batch axis.  Robust to
    family layout (dense KV at axis 1 behind the group axis, vlm/hybrid
    inner layer stacking at axis 1 pushing batch to 2, ssm state tensors
    with no length dim) without per-family switch statements."""
    s2, s3 = cache_shapes(cfg, 2, 8), cache_shapes(cfg, 3, 8)

    def ax(a, b):
        diff = [i for i, (x, y) in enumerate(zip(a, b)) if x != y]
        if len(diff) != 1:
            raise ValueError(f"ambiguous batch axis for cache leaf {a}")
        return diff[0]

    return jax.tree.map(ax, s2, s3, is_leaf=lambda x: isinstance(x, tuple))


def _map_slot(cfg: ModelConfig, cache, fn):
    """Apply ``fn(leaf, batch_axis)`` across every leaf of the cache."""
    return jax.tree.map(fn, cache, cache_batch_axes(cfg))


def reset_cache_slot(cfg: ModelConfig, cache, slot: int):
    """Zero one batch slot across every cache leaf (slot quarantine: the
    replacement request re-prefills from position 0, so stale or corrupted
    state must not survive).  Returns the updated cache."""
    def zero(leaf, ax):
        idx = (slice(None),) * ax + (slot,)
        return leaf.at[idx].set(jnp.zeros((), leaf.dtype))
    return _map_slot(cfg, cache, zero)


def corrupt_cache_slot(cfg: ModelConfig, cache, slot: int):
    """Silently poison one batch slot: NaN into every floating cache leaf
    (int8 KV payloads cannot hold NaN — their float32 scale leaves carry
    the poison instead, which contaminates the dequantized values the same
    way).  Fault-injection only; returns the updated cache."""
    def poison(leaf, ax):
        if not jnp.issubdtype(leaf.dtype, jnp.floating):
            return leaf
        idx = (slice(None),) * ax + (slot,)
        return leaf.at[idx].set(jnp.nan)
    return _map_slot(cfg, cache, poison)
