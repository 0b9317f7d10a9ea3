"""Grouped matmul: each row of ``lhs`` times its group's matrix of one layer
of ``rhs``, the rows sorted by group — the dropless expert FFN of
``models/moe.py``.

Megablox's schedule (``jax.experimental.pallas.ops.tpu.megablox``, whose
``make_group_metadata`` it uses): the grid walks the row tiles once for
every group they hold rows of, in group order, and each visit stores only
its group's rows of the tile, so a tile shared by two groups is finished by
two consecutive visits.  K and N are not tiled: a visit takes one whole
(K, N) matrix.

Unlike megablox's ``gmm`` it takes every layer's matrices (L, G, K, N) and
the layer to use, and reads that layer's matrices where they lie.  The
served step's layer loop would otherwise slice each layer's expert weights
out of the stacked parameters, and XLA copies a slice out before a custom
call reads it: one more read and write of every expert weight a step.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.experimental.pallas.ops.tpu.megablox.gmm import make_group_metadata


def _kernel(layer, offsets, group_ids, m_tile_ids, lhs_ref, rhs_ref, out_ref,
            *, tm: int):
    del layer
    i = pl.program_id(0)
    g = group_ids[i]
    row = m_tile_ids[i] * tm + jax.lax.broadcasted_iota(jnp.int32,
                                                        out_ref.shape, 0)
    mine = (row >= offsets[g]) & (row < offsets[g + 1])
    acc = jnp.dot(lhs_ref[...], rhs_ref[...],
                  preferred_element_type=jnp.float32)
    out_ref[...] = jnp.where(mine, acc.astype(out_ref.dtype), out_ref[...])


def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   layer: jax.Array, *, tm: int = 128,
                   interpret: bool = False) -> jax.Array:
    """lhs (M, K), rhs (L, G, K, N), group_sizes (G,) int32 summing to M,
    layer () int32: (M, N) in ``lhs``'s dtype, f32 accumulation, with
    ``rhs[layer]``.  Row tiles of up to ``tm`` rows."""
    m, k = lhs.shape
    _, g, _, n = rhs.shape
    tm = min(tm, -(-m // 8) * 8)
    pad = -m % tm
    if pad:                     # zero rows, counted to the last group
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
        group_sizes = group_sizes.at[-1].add(pad)
    meta, tiles = make_group_metadata(
        group_sizes=group_sizes, m=m + pad, tm=tm,
        start_group=jnp.int32(0), num_nonzero_groups=g,
        visit_empty_groups=False)
    out = pl.pallas_call(
        functools.partial(_kernel, tm=tm),
        out_shape=jax.ShapeDtypeStruct((m + pad, n), lhs.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(tiles,),
            in_specs=[
                pl.BlockSpec((tm, k), lambda i, lyr, off, gid, mt: (mt[i], 0)),
                pl.BlockSpec((None, None, k, n),
                             lambda i, lyr, off, gid, mt: (lyr[0], gid[i], 0, 0)),
            ],
            out_specs=pl.BlockSpec((tm, n),
                                   lambda i, lyr, off, gid, mt: (mt[i], 0)),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), *meta, lhs, rhs)
    return out[:m]
