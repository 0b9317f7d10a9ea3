"""Decode attention over the live rows of a stacked K/V cache: one new query
a head against, for each slot, only the cached positions below its length.

The cache lies as the served step keeps it, every layer's (L, B, S_max,
Hkv * dh), a position's K or V of all heads as one row; the kernel takes all
of it and the layer's number and copies rows of that layer straight from
HBM.  A custom call that read one layer's slice would make XLA copy the
slice out first (see ``kernels/grouped_matmul.py``), a whole layer's cache
each layer.

Slots go in groups of ``group``, the live ones (length above 0) first, in
an order the kernel lays out in scalar memory before it reads anything.  A
group is walked in chunks of ``rows`` positions up to its longest slot, and
each chunk copies, per slot, only where that slot has a position left: the
read follows each slot's own length.  The copies of the next chunk
(of this group or the next) go out before the current one is waited on, two
buffers deep.  Every slot of a chunk is computed, in one block with no
branch between slots; a slot past its length masks its scores and drops its
product with the (stale) buffer.

Lanes: each KV head's own 128-lane tiles where its width is a multiple of
128; where several heads share one 128-lane tile (qwen2's 2 x 64), each
head's query is laid over the tile, zero off its own lanes, so one dot
serves the tile's heads.  Softmax statistics and the accumulator are f32.
A tile that several query rows share (GQA) takes both dots on the MXU,
with the cache's dtype and f32 results: the probabilities are rounded to
it for the PV dot, as the TPU's default-precision f32 dot rounds its
operands to bf16.  A tile of one query (MHA) is a matrix-vector product,
done as f32 products and sums on the vector unit, with nothing rounded.

Returns, per slot and head, the unnormalised accumulator and the running
max and sum over positions ``< lens[b]``: a slot of length 0 reads nothing
and gets (0, -1e30, 0), which the caller's new-token column then owns.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -1e30
LANES = 128
CHUNK_BYTES = 32 * 1024     # one slot's K (or V) rows of one chunk
ITEM_BYTES = 512 * 1024     # one chunk's K and V rows of its whole group


def tile_width(hkv: int, dh: int) -> int:
    """Lanes of one tile: a head's own where ``dh`` is a multiple of 128,
    else 128 lanes shared by whole heads, else the whole row."""
    if dh % LANES == 0:
        return dh
    if LANES % dh == 0 and (hkv * dh) % LANES == 0:
        return LANES
    return hkv * dh


def one_query_tiles(h: int, hkv: int, dh: int) -> bool:
    """Whether each tile serves one query row (MHA at a head's own lanes),
    a matrix-vector product on the vector unit, rather than several query
    rows sharing both dots on the MXU."""
    return h == hkv and tile_width(hkv, dh) == dh


def block_rows(width: int, itemsize: int, smax: int) -> int:
    """Positions one chunk copies of a slot: about ``CHUNK_BYTES`` of a row
    ``width`` wide, a multiple of 16 and at least 32, at most the cache
    length."""
    rows = max(32, CHUNK_BYTES // (width * itemsize) // 16 * 16)
    return min(rows, smax)


def group_slots(batch: int, rows: int, width: int, itemsize: int) -> int:
    """Slots one chunk covers: the largest divisor of ``batch`` whose K and
    V rows fit ``ITEM_BYTES``."""
    most = max(1, ITEM_BYTES // (2 * rows * width * itemsize))
    return max(d for d in range(1, min(most, batch) + 1) if batch % d == 0)


def _kernel(layer_ref, lens_ref, q_ref, k_hbm, v_hbm, acc_ref, m_ref, l_ref,
            order_ref, kbuf, vbuf, sem, acc_s, m_s, l_s, *,
            rows: int, group: int, tw: int, mt: int, scale: float):
    b, h = q_ref.shape[:2]
    nt = h // mt
    smax = k_hbm.shape[2]
    layer = layer_ref[0]

    def place(sid, n_live):             # live slots first, the rest last
        live = lens_ref[sid] > 0
        order_ref[jnp.where(live, n_live, b - 1 - sid + n_live)] = sid
        return n_live + live.astype(jnp.int32)

    n_live = lax.fori_loop(0, b, place, 0)
    n_groups = (n_live + group - 1) // group
    acc_ref[...] = jnp.zeros(acc_ref.shape, acc_ref.dtype)
    m_ref[...] = jnp.full(m_ref.shape, _NEG, m_ref.dtype)
    l_ref[...] = jnp.zeros(l_ref.shape, l_ref.dtype)

    def slot(gi, i):
        return order_ref[gi * group + i]

    def first_row(c):                   # the last chunk ends at S_max
        return jnp.minimum(c * rows, smax - rows)

    def copies(gi, c, buf):
        out = []
        for i in range(group):
            sid = slot(gi, i)
            src = pl.ds(first_row(c), rows)
            out.append((lens_ref[sid] > c * rows, [
                pltpu.make_async_copy(hbm.at[layer, sid, src], vmem.at[buf, i],
                                      sem.at[j, buf])
                for j, (hbm, vmem) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf)))]))
        return out

    def start(gi, c, buf):
        for live, cps in copies(gi, c, buf):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.start()

    def wait(gi, c, buf):
        for live, cps in copies(gi, c, buf):
            @pl.when(live)
            def _():
                for cp in cps:
                    cp.wait()

    def update(i, hs, alpha, m_new, p_sum, pv, live):
        # the running statistics of slot ``i``'s query rows ``hs``
        l_s[i, hs, :] = alpha * l_s[i, hs, :] + p_sum
        m_s[i, hs, :] = m_new
        acc_s[i, hs, :] = (alpha[:, :1] * acc_s[i, hs, :]
                           + jnp.where(live, pv, 0.0))

    def shared_tile(i, hs, ls, q, mask, live, buf):
        # query rows sharing the tile (GQA): both dots on the MXU
        s = lax.dot_general(q, kbuf[buf, i, :, ls], (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(mask, s * scale, _NEG)
        m_prev = m_s[i, hs, :]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        pv = jnp.dot(p.astype(vbuf.dtype), vbuf[buf, i, :, ls],
                     preferred_element_type=jnp.float32)
        update(i, hs, jnp.exp(m_prev - m_new), m_new,
               p.sum(axis=1, keepdims=True), pv, live)

    def one_query_tile(i, hs, ls, q, mask, live, buf):
        # one query row (MHA), a matrix-vector product: f32 products on the
        # vector unit, summed across the tile's lanes for the scores and
        # across positions (a column) for the output
        k = kbuf[buf, i, :, ls].astype(jnp.float32)
        s = jnp.sum(k * q.astype(jnp.float32), axis=1, keepdims=True)
        s = jnp.where(mask, s * scale, _NEG)
        m_prev = m_s[i, hs, :]
        m_new = jnp.maximum(m_prev, s.max(axis=0, keepdims=True))
        p = jnp.where(mask, jnp.exp(s - m_new[:, :1]), 0.0)
        pv = jnp.sum(p * vbuf[buf, i, :, ls].astype(jnp.float32), axis=0,
                     keepdims=True)
        update(i, hs, jnp.exp(m_prev - m_new), m_new,
               p.sum(axis=0, keepdims=True), pv, live)

    def compute(gi, c, buf):
        # a row of positions for the shared tiles, a column for one query's
        shape = (rows, 1) if mt == 1 else (1, rows)
        pos = first_row(c) + lax.broadcasted_iota(jnp.int32, shape,
                                                  shape.index(rows))
        tile = one_query_tile if mt == 1 else shared_tile
        for i in range(group):
            sid = slot(gi, i)
            ln = lens_ref[sid]
            mask = (pos >= c * rows) & (pos < ln)
            for t in range(nt):
                hs = slice(t * mt, (t + 1) * mt)
                tile(i, hs, slice(t * tw, (t + 1) * tw), q_ref[sid, hs, :],
                     mask, ln > c * rows, buf)

    def as_row(x):                      # (h, 128) lane-broadcast -> (1, 128)
        eye = (lax.broadcasted_iota(jnp.int32, x.shape, 0)
               == lax.broadcasted_iota(jnp.int32, x.shape, 1))
        return jnp.where(eye, x, 0.0).sum(axis=0, keepdims=True)

    def group_body(gi, n):
        longest = lens_ref[slot(gi, 0)]
        for i in range(1, group):
            longest = jnp.maximum(longest, lens_ref[slot(gi, i)])
        n_chunks = (longest + rows - 1) // rows
        acc_s[...] = jnp.zeros(acc_s.shape, acc_s.dtype)
        m_s[...] = jnp.full(m_s.shape, _NEG, m_s.dtype)
        l_s[...] = jnp.zeros(l_s.shape, l_s.dtype)

        def chunk_body(c, n):
            buf = n % 2
            last = c + 1 == n_chunks
            nxt = jnp.where(last, gi + 1, gi)

            @pl.when(nxt < n_groups)
            def _():
                start(nxt, jnp.where(last, 0, c + 1), 1 - buf)

            wait(gi, c, buf)
            compute(gi, c, buf)
            return n + 1

        n = lax.fori_loop(0, n_chunks, chunk_body, n)
        for i in range(group):
            sid = slot(gi, i)
            acc_ref[sid] = acc_s[i]
            m_ref[pl.ds(sid, 1), :] = as_row(m_s[i])
            l_ref[pl.ds(sid, 1), :] = as_row(l_s[i])
        return n

    @pl.when(n_groups > 0)
    def _():
        start(0, 0, 0)

    lax.fori_loop(0, n_groups, group_body, 0)


def decode_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                     lens: jax.Array, layer: jax.Array, *,
                     rows: int | None = None, group: int | None = None,
                     interpret: bool = False):
    """q (B, H, dh); k, v (L, B, S_max, Hkv * dh); lens (B,) int32, each
    slot's number of live positions; layer () int32.  Returns (acc (B, H,
    dh), m (B, H), l (B, H)), all f32, over positions ``< lens`` of
    ``k[layer]`` and ``v[layer]``: ``acc = sum_t exp(s_t - m) v_t`` and
    ``l = sum_t exp(s_t - m)`` with ``s_t = q . k_t / sqrt(dh)``.  ``rows``
    and ``group`` default to :func:`block_rows` and :func:`group_slots`."""
    b, h, dh = q.shape
    _, _, smax, width = k.shape
    hkv = width // dh
    g = h // hkv
    if h > LANES:
        raise ValueError(f"{h} query heads: at most {LANES}")
    tw = tile_width(hkv, dh)
    hpt, nt = tw // dh, width // tw
    itemsize = jnp.dtype(k.dtype).itemsize
    rows = rows or block_rows(width, itemsize, smax)
    group = group or group_slots(b, rows, width, itemsize)
    # each head's query laid over its tile, zero off its own lanes; heads in
    # KV-head order, so a tile's heads are consecutive
    qt = q.reshape(b, nt, hpt, g, 1, dh)
    if hpt > 1:
        qt = qt * jnp.eye(hpt, dtype=q.dtype)[:, None, :, None]
    qt = qt.reshape(b, h, tw).astype(k.dtype)
    vmem = pl.BlockSpec(memory_space=pltpu.VMEM)
    kernel = functools.partial(_kernel, rows=rows, group=group, tw=tw,
                               mt=hpt * g, scale=1.0 / dh ** 0.5)
    acc, m, l = pl.pallas_call(
        kernel,
        out_shape=(jax.ShapeDtypeStruct((b, h, tw), jnp.float32),
                   jax.ShapeDtypeStruct((b, LANES), jnp.float32),
                   jax.ShapeDtypeStruct((b, LANES), jnp.float32)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(1,),
            in_specs=[vmem, pl.BlockSpec(memory_space=pl.ANY),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=(vmem, vmem, vmem),
            scratch_shapes=[
                pltpu.SMEM((b,), jnp.int32),
                pltpu.VMEM((2, group, rows, width), k.dtype),
                pltpu.VMEM((2, group, rows, width), v.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((group, h, tw), jnp.float32),
                pltpu.VMEM((group, h, LANES), jnp.float32),
                pltpu.VMEM((group, h, LANES), jnp.float32),
            ]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lens.astype(jnp.int32),
      qt, k, v)
    if hpt > 1:                         # each head's own lanes of its tile
        acc = acc.reshape(b, nt, hpt, g, hpt, dh)
        acc = jnp.stack([acc[:, :, j, :, j] for j in range(hpt)], axis=2)
    return acc.reshape(b, h, dh), m[:, :h], l[:, :h]
