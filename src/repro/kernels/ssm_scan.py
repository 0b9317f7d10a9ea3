"""Chunked decay-scan kernel (SSD / linear attention) for Mamba2 and RWKV6.

Recurrence per head, with per-channel log-decay ``w_t <= 0`` over the key
dimension (RWKV6 "Finch" data-dependent decay; Mamba2 broadcasts a scalar):

    h_t = exp(w_t) (.) h_{t-1}  +  k_t (x) v_t            h in R^{dk x dv}
    o_t = q_t . h_{t-1 or t}                               (see ``diag_mode``)

``diag_mode``:
  * ``"inclusive"`` (Mamba2/SSD): o_t reads h_t (current token included via
    the decay path).
  * ``"bonus"`` (RWKV6): o_t reads h_{t-1} plus a bonus term
    ``(q_t . (u (.) k_t)) v_t`` for the current token.

TPU chunking: grid (B*H, n_chunks), sequential chunk axis carrying the f32
state in VMEM scratch.  Within a chunk the recurrence is materialized in
parallel form: cumulative decays fold the paper's bank-index trick one more
time — positions inside the chunk address the state with compile-time
offsets, never a serial python loop.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _sums(mask: jax.Array, w: jax.Array) -> jax.Array:
    """``out[r, d] = sum_i mask[r, i] w[i, d]`` as a 0/1 matmul at full f32
    precision (the TPU kernel language has no cumsum).  Every entry is a
    direct sum over its own window, never a difference of two running sums:
    subtracting two long accumulations ``W_t - W_s`` cancels catastrophically
    once |W| grows with the chunk length, which is exactly what made
    large-chunk runs drift from small-chunk runs.  Here the rounding error of
    each entry is proportional to the *window* magnitude — large windows have
    vanishing ``exp`` anyway, so the error lands where it cannot matter."""
    return jnp.dot(mask.astype(jnp.float32), w,
                   precision=jax.lax.Precision.HIGHEST,
                   preferred_element_type=jnp.float32)


def _window_sums(w: jax.Array, chunk: int, inclusive: bool) -> jax.Array:
    """Pairwise decay sums ``out[t, s, d] = sum_{s < i <= t} w[i, d]``
    (``inclusive``) or ``sum_{s < i < t}``, one matmul over the (t, s) rows."""
    r = jax.lax.broadcasted_iota(jnp.int32, (chunk * chunk, chunk), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk * chunk, chunk), 1)
    t, s = r // chunk, r % chunk
    mask = (s < i) & ((i <= t) if inclusive else (i < t))
    return _sums(mask, w).reshape(chunk, chunk, w.shape[-1])


def _scan_kernel(q_ref, k_ref, v_ref, w_ref, u_ref, o_ref, h_ref, *,
                 chunk: int, diag_mode: str):
    c = pl.program_id(1)

    @pl.when(c == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    q = q_ref[0].astype(jnp.float32)      # (C, dk)
    k = k_ref[0].astype(jnp.float32)      # (C, dk)
    v = v_ref[0].astype(jnp.float32)      # (C, dv)
    w = w_ref[0].astype(jnp.float32)      # (C, dk), log-decays (<= 0)
    h0 = h_ref[...]                       # (dk, dv) state before this chunk
    t_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    s_idx = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)

    # inclusive (Mamba2/SSD): o_t = q_t . h_t, token t included via the
    # decay path; bonus (RWKV6): o_t reads h_{t-1}, the diagonal comes via u
    inclusive = diag_mode == "inclusive"
    live = (s_idx <= t_idx) if inclusive else (s_idx < t_idx)
    W = _sums(live, w)                    # decay chunk start .. t (or t-1)
    o_inter = jnp.dot(q * jnp.exp(W), h0, preferred_element_type=jnp.float32)
    # intra: sum_{s live} exp(sum_{s<i<=t (or <t)} w_i) (q_t . k_s) v_s; the
    # window of a dead pair is empty (exp(0) = 1), so mask the scores
    rel = jnp.exp(_window_sums(w, chunk, inclusive))          # (C, C, dk)
    scores = jnp.sum(q[:, None, :] * rel * k[None, :, :], axis=-1)
    scores = jnp.where(live, scores, 0.0)
    o = o_inter + jnp.dot(scores, v, preferred_element_type=jnp.float32)
    if not inclusive:
        u = u_ref[0].astype(jnp.float32)                  # (1, dk)
        bonus = jnp.sum(q * u * k, axis=1, keepdims=True) # (C, 1)
        o = o + bonus * v

    o_ref[0] = o.astype(o_ref.dtype)

    # state update: h' = exp(W_last) h0 + sum_s exp(sum_{s<i} w_i) k_s v_s.
    # The per-position suffix decays are direct sums again (never
    # W_last - W_s), and the full-chunk decay is a plain reduction — both
    # keep the f32 carry consistent across chunkings.
    k_dec = k * jnp.exp(_sums(t_idx < s_idx, w))           # (C, dk)
    decay = jnp.exp(jnp.sum(w.T, axis=1, keepdims=True))   # (dk, 1)
    h_ref[...] = decay * h0 + jnp.dot(k_dec.T, v,
                                      preferred_element_type=jnp.float32)


def ssm_scan(q: jax.Array, k: jax.Array, v: jax.Array, w: jax.Array,
             u: Optional[jax.Array] = None, chunk: int = 32,
             diag_mode: str = "inclusive", interpret: bool = True
             ) -> jax.Array:
    """q/k/w: (B, H, S, dk); v: (B, H, S, dv); u: (H, dk) for RWKV bonus.

    Returns o: (B, H, S, dv).  S must be divisible by ``chunk``.
    """
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    assert s % chunk == 0, (s, chunk)
    assert diag_mode in ("inclusive", "bonus")
    nchunks = s // chunk
    if u is None:
        u = jnp.zeros((h, dk), q.dtype)

    qf = q.reshape(b * h, s, dk)
    kf = k.reshape(b * h, s, dk)
    vf = v.reshape(b * h, s, dv)
    wf = w.reshape(b * h, s, dk)
    # (B*H, 1, dk) so the (1, 1, dk) block equals the array in its last two
    # dims, as the TPU tiling rule asks of a block narrower than (8, 128)
    uf = jnp.tile(u, (b, 1)).reshape(b * h, 1, dk)

    kernel = functools.partial(_scan_kernel, chunk=chunk, diag_mode=diag_mode)
    out = pl.pallas_call(
        kernel,
        grid=(b * h, nchunks),
        in_specs=[
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dv), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, chunk, dk), lambda bh, c: (bh, c, 0)),
            pl.BlockSpec((1, 1, dk), lambda bh, c: (bh, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, chunk, dv), lambda bh, c: (bh, c, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, s, dv), q.dtype),
        scratch_shapes=[pltpu.VMEM((dk, dv), jnp.float32)],
        interpret=interpret,
    )(qf, kf, vf, wf, uf)
    return out.reshape(b, h, s, dv)
