"""Jitted public wrappers for the Pallas kernels.

Kernels are compiled on the TPU and run through the Pallas interpreter on
the CPU, so CPU tests exercise the identical kernel bodies.  Any other
backend is an error: nothing falls back to the interpreter silently.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from . import banked_conv2d as _bc
from . import banked_matmul as _bm
from . import flash_attention as _fa
from . import grouped_matmul as _gm
from . import ssm_scan as _ss


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend == "cpu":
        return True
    if backend == "tpu":
        return False
    raise RuntimeError(f"no Pallas kernels for the {backend!r} backend")


@functools.partial(jax.jit, static_argnames=("banks", "block", "out_dtype"))
def matmul(a: jax.Array, b: jax.Array,
           banks: Tuple[int, int, int] = (1, 1, 1),
           block: Optional[Tuple[int, int, int]] = None,
           out_dtype=None) -> jax.Array:
    return _bm.banked_matmul(a, b, banks=banks, block=block,
                             out_dtype=out_dtype, interpret=_interpret())


@functools.partial(jax.jit,
                   static_argnames=("causal", "scale", "block_q", "block_k"))
def attention(q, k, v, causal: bool = True, scale: Optional[float] = None,
              block_q: int = 128, block_k: int = 128) -> jax.Array:
    return _fa.flash_attention(q, k, v, causal=causal, scale=scale,
                               block_q=block_q, block_k=block_k,
                               interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("chunk", "diag_mode"))
def decay_scan(q, k, v, w, u=None, chunk: int = 32,
               diag_mode: str = "inclusive") -> jax.Array:
    return _ss.ssm_scan(q, k, v, w, u=u, chunk=chunk, diag_mode=diag_mode,
                        interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("banks",))
def conv2d(x, w, banks: Tuple[int, int] = (1, 1)) -> jax.Array:
    return _bc.banked_conv2d(x, w, banks=banks, interpret=_interpret())


@jax.jit
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array,
                   layer=0) -> jax.Array:
    """Each row of ``lhs`` (M, K) times its group's matrix of ``rhs``
    (G, K, N), or of ``rhs[layer]`` where ``rhs`` stacks every layer's
    (L, G, K, N): (M, N) in ``lhs``'s dtype.  The rows are sorted by group;
    ``group_sizes`` (G,) int32 counts each group's rows, in order, and sums
    to M."""
    if rhs.ndim == 3:
        rhs = rhs[None]
    return _gm.grouped_matmul(lhs, rhs, group_sizes, jnp.asarray(layer),
                              interpret=_interpret())
