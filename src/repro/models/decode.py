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
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from . import attention as A
from . import mamba as M
from . import moe as MOE
from . import rwkv as R
from .common import embed, lm_logits, norm, rope_freqs, sinusoid_pos
from .config import ModelConfig
from .mlp import mlp_block
from .params import param_specs
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


def _dense_decode(cfg, p, x1, c, pos, window=0, ring=False):
    h = norm(cfg, p["ln1"], x1)
    a, rows = A.attn_decode(cfg, p["attn"], h, c, pos, window=window,
                            attn_softcap=cfg.attn_softcap, ring=ring)
    if "ln1_post" in p:
        a = norm(cfg, p["ln1_post"], a)
    x1 = x1 + a
    h = norm(cfg, p["ln2"], x1)
    m = mlp_block(cfg, p["mlp"], h)
    if "ln2_post" in p:
        m = norm(cfg, p["ln2_post"], m)
    return x1 + m, rows


def _group_decode(cfg: ModelConfig, params, pos):
    """``step(x1, group params, group cache) -> (x1, new)``: ``new`` holds,
    for each cache leaf, the new token's K/V rows or the whole new state
    (cross caches pass through), as :func:`write_cache` takes them."""
    fam = cfg.family

    if fam == "dense" and cfg.local_global:
        def step(x1, gp, gc):
            x1, cl = _dense_decode(cfg, gp["local"], x1, gc["local"], pos,
                                   window=cfg.sliding_window, ring=True)
            x1, cg = _dense_decode(cfg, gp["global"], x1, gc["global"], pos)
            return x1, {"local": cl, "global": cg}
    elif fam == "dense":
        def step(x1, gp, gc):
            x1, c = _dense_decode(cfg, gp["lyr"], x1, gc["lyr"], pos)
            return x1, {"lyr": c}
    elif fam == "moe":
        def step(x1, gp, gc):
            p = gp["lyr"]
            h = norm(cfg, p["ln1"], x1)
            a, rows = A.attn_decode(cfg, p["attn"], h, gc["lyr"], pos)
            x1 = x1 + a
            h = norm(cfg, p["ln2"], x1)
            y, _ = MOE.moe_block(cfg, p["moe"], h)
            return x1 + y, {"lyr": rows}
    elif fam == "vlm":
        def step(x1, gp, gc):
            def body(xx, lpc):
                lp, lc = lpc
                return _dense_decode(cfg, lp, xx, lc, pos)
            x1_, self_new = jax.lax.scan(body, x1, (gp["self"], gc["self"]))
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
            x1_, attn_new = _dense_decode(cfg, shared, x1_, gc["attn"], pos)
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
                                    pos)
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


def serve_step(cfg: ModelConfig, params, cache, tokens: jax.Array, pos
               ) -> Tuple[jax.Array, Any]:
    """tokens: (B, 1) int32; pos: scalar int32 (next write position).

    Returns (logits (B, 1, V), updated cache).  The layer loop only reads
    the cache; each K/V leaf gets the new token's rows of every layer in
    one write after the loop, so with the cache donated
    (:func:`make_serve_step`) the step updates it in place.
    """
    x1 = embed(cfg, params, tokens)
    if cfg.family == "audio":
        table = sinusoid_pos(cache_max_len(cfg, cache), cfg.d_model)
        pe = jax.lax.dynamic_slice_in_dim(table, pos, 1)
        x1 = x1 + pe[None].astype(x1.dtype)
    step = _group_decode(cfg, params, pos)
    blocks, stacked = params["blocks"], {}
    if cfg.scan_layers and cfg.family == "moe" and cfg.moe_dispatch == "grouped":
        with jax.named_scope("layers"):
            blocks, stacked = MOE.split_stacked(blocks)

    def body(carry, gpc):
        gp, gc = gpc
        return step(carry, MOE.join_stacked(gp, stacked), gc)

    with jax.named_scope("layers"):
        if cfg.scan_layers:
            x1, new = jax.lax.scan(body, x1, (blocks, cache))
        else:
            per_group = []
            for i in range(cfg.num_groups):
                gp = jax.tree.map(lambda a: a[i], params["blocks"])
                gc = jax.tree.map(lambda a: a[i], cache)
                x1, gc_new = step(x1, gp, gc)
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
    def step(params, cache, tokens, pos):
        return serve_step(cfg, params, cache, tokens, pos)

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
    """Apply ``fn(leaf, batch_axis)`` across a fused cache tree or the
    per-group list form (``ProfiledServeStep``), where the sliced-off
    group axis shifts every batch axis down by one."""
    axes = cache_batch_axes(cfg)
    if isinstance(cache, list):
        return [jax.tree.map(lambda leaf, ax: fn(leaf, ax - 1), g, axes)
                for g in cache]
    return jax.tree.map(fn, cache, axes)


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


# ---------------------------------------------------------------------------
# Per-operator sliced serve step (layer profiling — ``repro.obs.modelprof``)
# ---------------------------------------------------------------------------

# families with a sliced-segment decomposition; vlm/audio decode steps fold
# modality cross-attention into the group scan and are not sliced yet
PROFILED_FAMILIES = ("dense", "moe", "ssm", "hybrid")


def profile_ops(cfg: ModelConfig) -> Tuple[Tuple[str, int], ...]:
    """Ordered ``(op, group)`` decomposition of one serve_step.

    ``group`` is the scan-group index (``-1`` for the embed/head segments
    outside the block stack).  This is the canonical op list the layer
    profiler, its validator, and the analytic cost model all share — one
    record per entry per engine step.
    """
    if cfg.family not in PROFILED_FAMILIES:
        raise NotImplementedError(
            f"layer profiling not implemented for family {cfg.family!r} "
            f"(supported: {PROFILED_FAMILIES})")
    ops = [("embed", -1)]
    for g in range(cfg.num_groups):
        if cfg.family == "dense" and cfg.local_global:
            ops += [("attn_local", g), ("mlp_local", g),
                    ("attn_global", g), ("mlp_global", g)]
        elif cfg.family == "dense":
            ops += [("attn", g), ("mlp", g)]
        elif cfg.family == "moe":
            ops += [("attn", g), ("moe", g)]
        elif cfg.family == "ssm":
            ops += [("time_mix", g), ("channel_mix", g)]
        else:  # hybrid
            ops += [("scan", g), ("attn", g), ("mlp", g)]
    ops.append(("head", -1))
    return tuple(ops)


class ProfiledServeStep:
    """One decode step as a sequence of independently jitted segments
    (embed / per-group operators / head), each synced with
    ``jax.block_until_ready`` and wall-stamped.

    This is a distinct *execution mode* of the identical math as
    :func:`serve_step` (logits/cache agree with the fused step — asserted
    by tests): slicing the step loses XLA's cross-operator fusion and pays
    one dispatch+sync per segment, so a profiled engine is slower than a
    fused one by a measured, reported factor (``slice_overhead`` in
    BENCH_model.json).  The <5% observability contract covers the
    *recording* layer on top of this mode (see ``obs.modelprof``), exactly
    as PR 8's contract covered the span hooks on top of the engine's
    inherent per-step sync.

    The cache travels as a **list of per-group subtrees** (no per-step
    slice/stack device work — group slicing of the parameters happens once
    per params object and is memoized).  ``init_cache``/``stack_cache``
    convert to and from the fused layout.
    """

    def __init__(self, cfg: ModelConfig):
        self.cfg = cfg
        self.ops = profile_ops(cfg)
        self._gps = None
        self._params_id = None
        self._aux = None            # head/embed/shared params, sliced once
        self._segs = self._build_segments(cfg)

    # -- cache layout --------------------------------------------------------

    @staticmethod
    def init_cache(cfg: ModelConfig, params, batch: int, max_len: int):
        """Family cache in per-group list form."""
        c = init_cache(cfg, params, batch, max_len)
        return [jax.tree.map(lambda a: a[g], c)
                for g in range(cfg.num_groups)]

    @staticmethod
    def stack_cache(groups):
        """Per-group list form back to the fused (stacked) layout."""
        return jax.tree.map(lambda *xs: jnp.stack(xs), *groups)

    # -- segment builders ----------------------------------------------------

    def _build_segments(self, cfg: ModelConfig):
        fam = cfg.family
        segs: Dict[str, Any] = {}

        def embed_seg(emb, tokens):
            return embed(cfg, {"embed": emb}, tokens)

        def head_seg(final_norm, head_w, x1):
            x1 = norm(cfg, final_norm, x1)
            params = {"embed" if cfg.tie_embeddings else "lm_head": head_w}
            return lm_logits(cfg, params, x1)

        segs["embed"] = jax.jit(embed_seg)
        segs["head"] = jax.jit(head_seg)

        def dense_attn(p, x1, c, pos, window=0, ring=False):
            h = norm(cfg, p["ln1"], x1)
            a, rows = A.attn_decode(cfg, p["attn"], h, c, pos,
                                    window=window,
                                    attn_softcap=cfg.attn_softcap,
                                    ring=ring)
            if "ln1_post" in p:
                a = norm(cfg, p["ln1_post"], a)
            return x1 + a, write_cache(c, rows, pos)

        def dense_mlp(p, x1):
            h = norm(cfg, p["ln2"], x1)
            m = mlp_block(cfg, p["mlp"], h)
            if "ln2_post" in p:
                m = norm(cfg, p["ln2_post"], m)
            return x1 + m

        if fam == "dense" and cfg.local_global:
            segs["attn_local"] = jax.jit(functools.partial(
                dense_attn, window=cfg.sliding_window, ring=True))
            segs["mlp_local"] = jax.jit(dense_mlp)
            segs["attn_global"] = jax.jit(dense_attn)
            segs["mlp_global"] = jax.jit(dense_mlp)
        elif fam == "dense":
            segs["attn"] = jax.jit(dense_attn)
            segs["mlp"] = jax.jit(dense_mlp)
        elif fam == "moe":
            def moe_attn(p, x1, c, pos):
                h = norm(cfg, p["ln1"], x1)
                a, rows = A.attn_decode(cfg, p["attn"], h, c, pos)
                return x1 + a, write_cache(c, rows, pos)

            def moe_ffn(p, x1):
                h = norm(cfg, p["ln2"], x1)
                y, _ = MOE.moe_block(cfg, p["moe"], h)
                return x1 + y

            segs["attn"] = jax.jit(moe_attn)
            segs["moe"] = jax.jit(moe_ffn)
        elif fam == "ssm":
            segs["time_mix"] = jax.jit(
                functools.partial(R.rwkv_time_mix_step, cfg))
            segs["channel_mix"] = jax.jit(
                functools.partial(R.rwkv_channel_mix_step, cfg))
        else:  # hybrid
            def mamba_scan(lps, x1, lcs):
                def body(xx, lpc):
                    lp, lc = lpc
                    delta, lc_new = M.mamba_decode_step(cfg, lp, xx, lc)
                    return xx + delta, lc_new
                return jax.lax.scan(body, x1, (lps, lcs))

            segs["scan"] = jax.jit(mamba_scan)
            segs["attn"] = jax.jit(dense_attn)
            segs["mlp"] = jax.jit(dense_mlp)
        return segs

    # -- params slicing (once per params object) -----------------------------

    def _sliced(self, params):
        if self._params_id != id(params):
            gps = [jax.tree.map(lambda a: a[g], params["blocks"])
                   for g in range(self.cfg.num_groups)]
            head_w = params["embed"] if self.cfg.tie_embeddings \
                else params["lm_head"]
            aux = {"embed": params["embed"], "head_w": head_w,
                   "final_norm": params["final_norm"]}
            if self.cfg.family == "hybrid":
                aux["shared"] = params["shared_block"]
            jax.block_until_ready(gps)
            self._gps, self._aux, self._params_id = gps, aux, id(params)
        return self._gps, self._aux

    # -- one profiled step ---------------------------------------------------

    def __call__(self, params, cache_groups, tokens, pos
                 ) -> Tuple[jax.Array, list, list]:
        """Returns ``(logits, new_cache_groups, walls)`` where ``walls``
        aligns with :func:`profile_ops` — one post-sync wall-clock
        microsecond figure per segment."""
        import time as _time
        cfg = self.cfg
        gps, aux = self._sliced(params)
        segs = self._segs
        walls: list = []

        def timed(fn, *args):
            t0 = _time.perf_counter()
            out = fn(*args)
            jax.block_until_ready(out)
            walls.append((_time.perf_counter() - t0) * 1e6)
            return out

        x1 = timed(segs["embed"], aux["embed"], tokens)
        new_groups = []
        for g in range(cfg.num_groups):
            gp, gc = gps[g], cache_groups[g]
            if cfg.family == "dense" and cfg.local_global:
                (x1, cl) = timed(segs["attn_local"], gp["local"], x1,
                                 gc["local"], pos)
                x1 = timed(segs["mlp_local"], gp["local"], x1)
                (x1, cgl) = timed(segs["attn_global"], gp["global"], x1,
                                  gc["global"], pos)
                x1 = timed(segs["mlp_global"], gp["global"], x1)
                new_groups.append({"local": cl, "global": cgl})
            elif cfg.family in ("dense", "moe"):
                (x1, c_new) = timed(segs["attn"], gp["lyr"], x1,
                                    gc["lyr"], pos)
                x1 = timed(segs["mlp" if cfg.family == "dense" else "moe"],
                           gp["lyr"], x1)
                new_groups.append({"lyr": c_new})
            elif cfg.family == "ssm":
                (x1, c_tm) = timed(segs["time_mix"], gp["lyr"], x1,
                                   gc["lyr"])
                (x1, c_cm) = timed(segs["channel_mix"], gp["lyr"], x1,
                                   gc["lyr"])
                new_groups.append({"lyr": {**c_tm, **c_cm}})
            else:  # hybrid
                (x1, mamba_new) = timed(segs["scan"], gp["mamba"], x1,
                                        gc["mamba"])
                (x1, attn_new) = timed(segs["attn"], aux["shared"], x1,
                                       gc["attn"], pos)
                x1 = timed(segs["mlp"], aux["shared"], x1)
                new_groups.append({"mamba": mamba_new, "attn": attn_new})
        logits = timed(segs["head"], aux["final_norm"], aux["head_w"], x1)
        return logits, new_groups, walls


@functools.lru_cache(maxsize=None)
def make_profiled_serve_step(cfg: ModelConfig) -> ProfiledServeStep:
    """Per-config cached :class:`ProfiledServeStep` (same sharing contract
    as :func:`make_serve_step` — every profiled engine/driver on one config
    shares one set of compiled segments)."""
    return ProfiledServeStep(cfg)
