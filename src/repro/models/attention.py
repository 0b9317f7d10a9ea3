"""GQA attention: chunked (flash-style) training path, cached decode path.

The training path is a pure-jnp double-chunked online-softmax attention —
the same math as ``kernels/flash_attention.py`` (which serves as the TPU
kernel) but expressed with lax.scan so it compiles compactly inside the
layer scan and never materializes (S, S) score matrices.  GQA is an einsum
over a folded group dimension — never a materialized head repeat.

Supports: causal masking, sliding windows (gemma2 local layers), attention
softcapping, cross attention (whisper / llama-vision), QKV bias (qwen2),
QK-norm (olmoe).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..kernels import decode_attention as DA
from ..kernels.ops import decode_attention
from .common import apply_rope, constrain, rms_norm, rope_freqs, softcap
from .config import ModelConfig

_NEG = -1e30


def qkv_proj(cfg: ModelConfig, p, x: jax.Array,
             kv_x: Optional[jax.Array] = None
             ) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """x: (B,S,D) -> q (B,H,S,dh), k/v (B,Hkv,Sk,dh)."""
    b, s, _ = x.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    src = x if kv_x is None else kv_x
    sk = src.shape[1]
    q = jnp.einsum("bsd,de->bse", x, p["wq"])
    k = jnp.einsum("bsd,de->bse", src, p["wk"])
    v = jnp.einsum("bsd,de->bse", src, p["wv"])
    if cfg.qkv_bias:
        q = q + p["bq"]
        k = k + p["bk"]
        v = v + p["bv"]
    if cfg.qk_norm:
        # over the whole projection, all heads at once, before the head
        # split and RoPE (OLMoE)
        with jax.named_scope("qk_norm"):
            q = rms_norm(q, p["q_norm"], cfg.norm_eps)
            k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    q = q.reshape(b, s, h, dh).transpose(0, 2, 1, 3)
    k = k.reshape(b, sk, hkv, dh).transpose(0, 2, 1, 3)
    v = v.reshape(b, sk, hkv, dh).transpose(0, 2, 1, 3)
    return constrain(q, "batch", "heads", None, None), \
        constrain(k, "batch", "kv_heads", None, None), \
        constrain(v, "batch", "kv_heads", None, None)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      attn_softcap: float = 0.0, scale: float,
                      q_chunk: int = 512, kv_chunk: int = 512,
                      q_offset: int = 0) -> jax.Array:
    """Online-softmax attention with a STATIC flash schedule.

    The q-chunk loop is unrolled in python; each q chunk scans exactly its
    live kv range (causal frontier / sliding window), with the mask applied
    only to boundary chunks — interior chunks run mask-free.  Static chunk
    indices are the compile-time "bank selection" of the paper's layout
    discipline: no runtime conditionals, dead chunks never lowered.

    ``q_offset``: absolute position of q[0] (decode/prefill continuation).
    q: (B,H,Sq,dh), k/v: (B,Hkv,Sk,dh).
    """
    b, h, sq, dh = q.shape
    hkv, sk = k.shape[1], k.shape[2]
    g = h // hkv
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, sk)
    nq = -(-sq // q_chunk)
    nk = -(-sk // kv_chunk)
    sq_p, sk_p = nq * q_chunk, nk * kv_chunk
    if sq_p != sq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, sq_p - sq), (0, 0)))
    if sk_p != sk:
        k = jnp.pad(k, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, sk_p - sk), (0, 0)))

    qg = q.reshape(b, hkv, g, sq_p, dh)
    k_blocks = k.reshape(b, hkv, nk, kv_chunk, dh).transpose(2, 0, 1, 3, 4)
    v_blocks = v.reshape(b, hkv, nk, kv_chunk, dh).transpose(2, 0, 1, 3, 4)
    full_chunks = sk // kv_chunk       # chunks with no padding

    def make_step(q_blk, q_pos, masked: bool):
        def kv_step(carry, kj_blk):
            m, l, acc = carry
            kj, k_blk, v_blk = kj_blk
            s = jnp.einsum("bhgqd,bhkd->bhgqk", q_blk, k_blk,
                           preferred_element_type=jnp.float32) * scale
            if attn_softcap:
                s = softcap(s, attn_softcap)
            if masked:
                k_pos = kj * kv_chunk + jnp.arange(kv_chunk)
                mask = jnp.ones((q_chunk, kv_chunk), bool)
                if causal:
                    mask &= q_pos[:, None] >= k_pos[None, :]
                if window:
                    mask &= (q_pos[:, None] - k_pos[None, :]) < window
                mask &= (k_pos < sk)[None, :]
                s = jnp.where(mask[None, None, None], s, _NEG)
            m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)
            alpha = jnp.exp(m - m_new)
            l_new = l * alpha + p.sum(axis=-1, keepdims=True)
            acc_new = acc * alpha + jnp.einsum(
                "bhgqk,bhkd->bhgqd", p.astype(v_blk.dtype), v_blk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None
        return kv_step

    def q_chunk_attend(q_blk, qi):
        a_pos = q_offset + qi * q_chunk             # first q position
        b_pos = a_pos + q_chunk - 1                 # last q position
        q_pos = a_pos + jnp.arange(q_chunk)
        # live kv chunk range [lo, hi)
        hi = min(nk, b_pos // kv_chunk + 1) if causal else nk
        lo = 0
        if window:
            # first key any query in the chunk needs: a_pos - window + 1
            lo = max(0, -(-(a_pos - window + 2 - kv_chunk) // kv_chunk))
        # fully-unmasked interior [lo_full, hi_full)
        hi_full = hi
        if causal:
            hi_full = max(lo, min(hi, (a_pos - kv_chunk + 1) // kv_chunk + 1
                                  if a_pos - kv_chunk + 1 >= 0 else 0))
        lo_full = lo
        if window:
            # chunk is unmasked only if the LAST query (b_pos) sees all keys
            lo_full = min(hi_full, max(lo, -(-(b_pos - window + 1)
                                             // kv_chunk)))
        hi_full = min(hi_full, full_chunks)          # padding needs masking
        lo_full = min(lo_full, hi_full)

        m0 = jnp.full((b, hkv, g, q_chunk, 1), _NEG, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk, 1), jnp.float32)
        a0 = jnp.zeros((b, hkv, g, q_chunk, dh), jnp.float32)
        carry = (m0, l0, a0)

        def run(carry, lo_i, hi_i, masked):
            if hi_i <= lo_i:
                return carry
            step = jax.checkpoint(make_step(q_blk, q_pos, masked))
            idx = jnp.arange(lo_i, hi_i)
            carry, _ = jax.lax.scan(
                step, carry,
                (idx, k_blocks[lo_i:hi_i], v_blocks[lo_i:hi_i]))
            return carry

        carry = run(carry, lo, lo_full, True)        # window boundary
        carry = run(carry, lo_full, hi_full, False)  # interior, mask-free
        carry = run(carry, hi_full, hi, True)        # causal/pad boundary
        m, l, acc = carry
        return (acc / jnp.maximum(l, 1e-30)).astype(q.dtype)

    outs = []
    for qi in range(nq):
        q_blk = qg[:, :, :, qi * q_chunk:(qi + 1) * q_chunk]
        fn = jax.checkpoint(q_chunk_attend, static_argnums=(1,))
        outs.append(fn(q_blk, qi))
    out = jnp.concatenate(outs, axis=3) if len(outs) > 1 else outs[0]
    out = out.reshape(b, h, sq_p, dh)
    return out[:, :, :sq]


def attn_block(cfg: ModelConfig, p, x: jax.Array, *,
               rope: Optional[Tuple[jax.Array, jax.Array]] = None,
               causal: bool = True, window: int = 0,
               kv_x: Optional[jax.Array] = None,
               attn_softcap: float = 0.0) -> jax.Array:
    """Full attention sub-block (projections + mixing + output proj)."""
    b, s, d = x.shape
    q, k, v = qkv_proj(cfg, p, x, kv_x=kv_x)
    if rope is not None and kv_x is None:
        cos, sin = rope
        q = apply_rope(q, cos[:s], sin[:s])
        k = apply_rope(k, cos[:s], sin[:s])
    scale = 1.0 / (cfg.head_dim ** 0.5)
    out = chunked_attention(q, k, v, causal=causal and kv_x is None,
                            window=window, attn_softcap=attn_softcap,
                            scale=scale)
    out = out.transpose(0, 2, 1, 3).reshape(b, s, -1)
    out = jnp.einsum("bse,ed->bsd", out, p["wo"])
    return constrain(out, "batch", "seq", "embed")


# ---------------------------------------------------------------------------
# Decode path (single new token against a static KV cache)
# ---------------------------------------------------------------------------


def kernel_serves(*, window: int = 0, attn_softcap: float = 0.0,
                  ring: bool = False, quantized: bool = False) -> bool:
    """Whether :func:`attn_decode` runs the decode-attention kernel (reads
    each slot's live rows only) rather than the jnp path (every position,
    masked): the kernel has no ring buffer, window, softcap or int8 cache."""
    return not (ring or window or attn_softcap or quantized)


# The share of a layer's cache, counted in the kernel's chunks, up to which
# the kernel takes less time than the einsums reading all of it, by the
# kernel's tiles (:func:`~repro.kernels.decode_attention.one_query_tiles`).
# From a step's time over every layer on a TPU v5e at the benchmark cells'
# shapes (PERF.md §6): several query rows sharing a tile on the MXU (qwen2),
# a fixed 1.05-1.25 ms plus 8-12 ms per whole cache read, against the
# einsums' 5.4-5.9 ms, cross at 0.37-0.54; one query a tile on the vector
# unit (OLMoE), 0.56-0.65 ms plus 8-9 ms against 6.66 ms, at about 0.7.
# The einsums serve a step above the share; they and the shares go once
# the kernel reads its rows at the einsums' rate.
KERNEL_MAX_READ_SHARE = {"shared": 0.4, "one_query": 0.7}


def _kernel_reads(lens, width: int, itemsize: int, smax: int):
    """Positions of one layer's cache the kernel reads for slot lengths
    ``lens``: each slot's chunks up to its length."""
    rows = DA.block_rows(width, itemsize, smax)
    return ((lens + rows - 1) // rows * rows).sum()


@jax.named_scope("attn")
def attn_decode(cfg: ModelConfig, p, x1: jax.Array, cache: dict, pos,
                *, lens: Optional[jax.Array] = None, window: int = 0,
                attn_softcap: float = 0.0, ring: bool = False
                ) -> Tuple[jax.Array, dict]:
    """x1: (B, 1, D); cache: {'k','v'} (B, S_max, Hkv * dh) (int8 adds
    {'k_scale','v_scale'} (B, S_max, Hkv)), or every layer's (L, B, S_max,
    ·) with the layer's number under 'layer'; pos: scalar, the new token's
    position; lens: (B,) int32, the cached positions each slot attends
    (``< lens[b]``; default ``pos`` for every slot).

    ``ring=True`` treats the cache as a circular window buffer (sliding-
    window layers): slot i holds absolute position pos - ((pos - i) mod L),
    and attends those below ``pos``.

    The cache is only read: where :func:`kernel_serves` and the slots'
    lengths leave enough of the cache unread (:data:`KERNEL_MAX_READ_SHARE`),
    by the decode-attention kernel, which copies each slot's rows below its
    length from the layer where it lies; else by einsums over every
    position.  Returns (attn output (B,1,D), the new token's rows, each
    (B, 1, ·) like the cache's), which the caller writes at position
    ``pos mod S_max`` of its cache.
    """
    b, _, d = x1.shape
    h, hkv, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    g = h // hkv
    q, k1, v1 = qkv_proj(cfg, p, x1)
    if cfg.rope_theta:
        posv = jnp.full((b, 1), pos, jnp.int32)
        cos, sin = rope_freqs(dh, cfg.rope_theta, posv)
        q = apply_rope(q, cos, sin)
        k1 = apply_rope(k1, cos, sin)
    if lens is None:
        lens = jnp.full((b,), pos, jnp.int32)
    layer = cache.get("layer")

    def leaf(n):                    # one layer's (B, S_max, ·)
        return cache[n] if layer is None else cache[n][layer]

    smax = cache["k"].shape[-2]

    def row(x):                     # (B, Hkv, 1, w) -> (B, 1, Hkv * w)
        return x.transpose(0, 2, 1, 3).reshape(b, 1, -1)

    quantized = "k_scale" in cache
    if quantized:
        # int8 KV cache: per-token absmax scales (beyond-paper feature)
        def _quant(x):
            amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1,
                           keepdims=True) + 1e-6
            scale = amax / 127.0
            qx = jnp.clip(jnp.round(x.astype(jnp.float32) / scale),
                          -127, 127).astype(jnp.int8)
            return qx, scale
        k_q, k_s = _quant(k1)
        v_q, v_s = _quant(v1)
        rows = {"k": row(k_q), "v": row(v_q), "k_scale": row(k_s),
                "v_scale": row(v_s)}
        k_new = k_q.astype(jnp.float32) * k_s
        v_new = v_q.astype(jnp.float32) * v_s
    else:
        dt = cache["k"].dtype
        k_new, v_new = k1.astype(dt), v1.astype(dt)
        rows = {"k": row(k_new), "v": row(v_new)}
    qg = q.reshape(b, hkv, g, 1, dh).astype(jnp.float32)

    def finish(s):
        s = s / (dh ** 0.5)
        return softcap(s, attn_softcap) if attn_softcap else s

    # the new token's own key is attended as a column of its own (where its
    # row goes, the cache holds an older token or nothing)
    s_new = finish(jnp.einsum("bhgqd,bhkd->bhgqk", qg,
                              k_new.astype(jnp.float32)))

    def kernel():
        kc, vc, n = cache["k"], cache["v"], layer
        if n is None:
            kc, vc, n = kc[None], vc[None], 0
        acc, m, l = decode_attention(q.reshape(b, h, dh), kc, vc, lens, n)
        acc = acc.reshape(b, hkv, g, 1, dh)
        m, l = m.reshape(b, hkv, g, 1, 1), l.reshape(b, hkv, g, 1, 1)
        m_all = jnp.maximum(m, s_new)
        alpha, p_new = jnp.exp(m - m_all), jnp.exp(s_new - m_all)
        return (alpha * acc + p_new * v_new[:, :, None].astype(jnp.float32)) \
            / (alpha * l + p_new)

    def einsums():
        if quantized:
            def dequant(n):
                x = leaf(n).astype(jnp.float32).reshape(b, smax, hkv, dh)
                return (x * leaf(n + "_scale")[..., None]).reshape(b, smax, -1)
            kc, vc = dequant("k"), dequant("v")
        else:
            kc, vc = leaf("k"), leaf("v")
        k_pos = jnp.arange(smax)
        if ring:
            # floor-mod (jnp.mod), NOT lax.rem: C-style rem goes negative
            # for pos - k_pos < 0 and would mark empty ring slots as valid
            abs_pos = pos - jnp.mod(pos - k_pos, smax)
            mask = ((abs_pos >= 0) & (abs_pos < pos))[None]
            if window:
                mask &= (pos - abs_pos) < window
        else:
            mask = k_pos[None] < lens[:, None]
            if window:
                mask &= (pos - k_pos) < window
        # each head's query laid over the row of all heads, zero off its own
        # lanes: the cache's rows are read as they lie, with no relayout, at
        # Hkv times the matmul work of a per-head contraction
        own = jnp.eye(hkv, dtype=jnp.float32)
        q_rows = jnp.einsum("bhgqd,hj->bhgqjd", qg, own).reshape(
            b, hkv, g, 1, hkv * dh)
        s_old = finish(jnp.einsum("bhgqc,bkc->bhgqk", q_rows,
                                  kc.astype(jnp.float32)))
        s_old = jnp.where(mask[:, None, None, None], s_old, _NEG)
        m = jnp.maximum(s_old.max(axis=-1, keepdims=True), s_new)
        p_old, p_new = jnp.exp(s_old - m), jnp.exp(s_new - m)
        pv = jnp.einsum("bhgqk,bkc->bhgqc", p_old, vc.astype(jnp.float32))
        pv = jnp.einsum("bhgqjd,hj->bhgqd",
                        pv.reshape(b, hkv, g, 1, hkv, dh), own)
        return (pv + p_new * v_new[:, :, None].astype(jnp.float32)) \
            / (p_old.sum(axis=-1, keepdims=True) + p_new)

    if kernel_serves(window=window, attn_softcap=attn_softcap, ring=ring,
                     quantized=quantized):
        share = KERNEL_MAX_READ_SHARE[
            "one_query" if DA.one_query_tiles(h, hkv, dh) else "shared"]
        reads = _kernel_reads(lens, hkv * dh, cache["k"].dtype.itemsize, smax)
        out = jax.lax.cond(reads < share * b * smax, kernel, einsums)
    else:
        out = einsums()
    out = out.reshape(b, h, 1, dh).transpose(0, 2, 1, 3).reshape(b, 1, -1)
    out = jnp.einsum("bse,ed->bsd", out.astype(x1.dtype), p["wo"])
    return out, rows
