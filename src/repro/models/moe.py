"""Mixture-of-Experts with the paper's banking discipline applied to experts.

``banked`` dispatch (default — the layout-embedded scheme): experts are
memory banks.  Tokens are moved into a static expert-leading capacity
buffer (E, C, D) — row-wise data movement of O(T*k*D) — and all compute is
dense einsums over the expert dimension, which shards over the model axis
exactly like banks: each device owns E/ep experts selected by the
PartitionSpec (a compile-time index), never a runtime branch.

``gather`` dispatch (the "branchy" analogue, for the ablation): per-token
expert-WEIGHT gathers — O(T*D*F) data movement with data-dependent
indexing, mirroring the cost explosion of the paper's conditional
bank-select chains (moving the bank to the request instead of the request
to the bank).

``grouped`` dispatch (dropless, OLMoE): the T*k assignments sorted by
expert and run through the expert FFN as grouped matmuls over the rows each
expert got (``kernels.ops.grouped_matmul``, a Pallas kernel: XLA's own
ragged dot drops the named scope on the TPU), so no assignment is dropped.
The served step's layer loop hands it each layer's number into the stacked
expert weights rather than a slice of them (``split_stacked``), which XLA
would copy out before the kernel reads it.

Inside the ``moe`` scope the served step names its parts: ``route`` (the
router and its top-k), ``dispatch`` (rows to experts), ``experts`` (the
expert FFN) and ``combine`` (rows back to tokens, weighted by their gates).
"""
from __future__ import annotations

import math
from typing import Dict, Tuple

import jax
import jax.numpy as jnp

from ..kernels.ops import grouped_matmul
from .common import activation, constrain
from . import common as _common
from .config import ModelConfig
from .params import gated_mlp


@jax.named_scope("route")
def _router(cfg: ModelConfig, p, x2: jax.Array):
    """x2: (T, D) -> (gates (T,k), idx (T,k), aux metrics).  The gates are
    the top-k softmax probabilities, rescaled to sum to 1 under
    ``moe_norm_topk_prob``."""
    logits = jnp.einsum("td,de->te", x2.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_i = jax.lax.top_k(probs, cfg.experts_per_token)
    if cfg.moe_norm_topk_prob:
        top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)
    e = cfg.num_experts
    me = jnp.mean(jax.nn.one_hot(top_i, e).sum(1), axis=0)      # load/expert
    pe = probs.mean(axis=0)
    aux = e * jnp.sum(me / cfg.experts_per_token * pe)
    zloss = jnp.mean(jax.nn.logsumexp(logits, axis=-1) ** 2)
    return top_p, top_i, {"moe_aux": aux, "moe_zloss": zloss}


def capacity(cfg: ModelConfig, tokens: int) -> int:
    c = int(math.ceil(tokens * cfg.experts_per_token
                      * cfg.moe_capacity_factor / cfg.num_experts))
    return max(8, -(-c // 8) * 8)   # pad to lane multiple


@jax.named_scope("experts")
def _expert_ffn(cfg: ModelConfig, p, xe: jax.Array) -> jax.Array:
    """xe: (E, C, D) -> (E, C, D): dense over the leading expert 'banks'."""
    xe = constrain(xe, "experts", "capacity", None)
    h = jnp.einsum("ecd,edf->ecf", xe, p["w1"])
    if gated_mlp(cfg):
        g = jnp.einsum("ecd,edf->ecf", xe, p["wg"])
        h = activation(cfg, g) * h
    else:
        h = activation(cfg, h)
    h = constrain(h, "experts", "capacity", None)
    out = jnp.einsum("ecf,efd->ecd", h, p["w2"])
    return constrain(out, "experts", "capacity", None)


def moe_block_banked(cfg: ModelConfig, p, x: jax.Array
                     ) -> Tuple[jax.Array, Dict]:
    """x: (B, S, D).  Static-capacity dispatch: scatter rows into the
    expert-leading buffer, dense expert FFN, gather back."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    cap = capacity(cfg, t)
    x2 = x.reshape(t, d)
    top_p, top_i, aux = _router(cfg, p, x2)

    with jax.named_scope("dispatch"):
        # flat (T*k,) assignment stream, token-major; position inside each
        # expert's capacity buffer = number of earlier assignments to it.
        eid = top_i.reshape(t * k)
        gate = top_p.reshape(t * k)
        oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)            # (T*k, E)
        pos = (jnp.cumsum(oh, axis=0) - oh)                     # exclusive
        pos = jnp.take_along_axis(pos, eid[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.minimum(pos, cap - 1)

        # perf iteration 4: expert-leading (E, cap, D) buffer with an
        # explicit expert sharding — the scatter target lives on the
        # expert's owner device (bank = device), never replicated.  Dropped
        # tokens scatter zeros onto the last slot (add-safe).
        x_rep = jnp.repeat(x2, k, axis=0)                       # (T*k, D)
        upd = x_rep * keep[:, None].astype(x.dtype)
        buf = constrain(jnp.zeros((e, cap, d), x.dtype),
                        "experts", "capacity", None)
        buf = buf.at[eid, pos_c].add(upd)
        buf = constrain(buf, "experts", "capacity", None)
    ye = _expert_ffn(cfg, p, buf)
    with jax.named_scope("combine"):
        y_rows = ye[eid, pos_c]                                 # (T*k, D)
        y_rows = (y_rows.astype(jnp.float32)
                  * (gate * keep.astype(jnp.float32))[:, None])
        y2 = y_rows.reshape(t, k, d).sum(axis=1)
    return y2.astype(x.dtype).reshape(b, s, d), aux


EXPERT_WEIGHTS = ("w1", "wg", "w2")


def split_stacked(blocks):
    """A layer loop's parameters for the grouped dispatch: ``blocks`` with
    each stacked expert weight (L, E, ...) replaced by the layer numbers
    0..L-1, and the weights themselves (:func:`join_stacked` puts them
    back)."""
    moe = blocks["lyr"]["moe"]
    stacked = {n: moe[n] for n in EXPERT_WEIGHTS if n in moe}
    layers = jnp.arange(moe["w1"].shape[0], dtype=jnp.int32)
    lyr = dict(blocks["lyr"], moe=dict(moe, **{n: layers for n in stacked}))
    return dict(blocks, lyr=lyr), stacked


def join_stacked(gp, stacked):
    """One layer's parameters as the loop slices them from
    :func:`split_stacked`'s ``blocks``: each expert weight becomes (every
    layer's weights, this layer's number)."""
    if not stacked:
        return gp
    moe = gp["lyr"]["moe"]
    moe = dict(moe, **{n: (w, moe[n]) for n, w in stacked.items()})
    return dict(gp, lyr=dict(gp["lyr"], moe=moe))


def _expert_matmul(rows, w, sizes):
    """``w``: one layer's expert weights (E, K, N), or (every layer's
    (L, E, K, N), this layer's number)."""
    weights, layer = w if isinstance(w, tuple) else (w, 0)
    return grouped_matmul(rows, weights, sizes, layer)


def moe_block_grouped(cfg: ModelConfig, p, x: jax.Array
                      ) -> Tuple[jax.Array, Dict]:
    """x: (B, S, D).  Dropless dispatch: the T*k assignments sorted by
    expert, the expert FFN as grouped matmuls over the rows each expert got
    (group sizes from the routing), the rows put back in assignment order,
    weighted by their gates and summed over each token's k."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.num_experts, cfg.experts_per_token
    x2 = x.reshape(t, d)
    top_p, top_i, aux = _router(cfg, p, x2)
    with jax.named_scope("dispatch"):
        eid = top_i.reshape(t * k)
        order = jnp.argsort(eid, stable=True)                   # by expert
        rows = x2[order // k]                                   # (T*k, D)
        sizes = jnp.bincount(eid, length=e).astype(jnp.int32)   # (E,)
    with jax.named_scope("experts"):
        h = _expert_matmul(rows, p["w1"], sizes)
        if gated_mlp(cfg):
            h = activation(cfg, _expert_matmul(rows, p["wg"], sizes)) * h
        else:
            h = activation(cfg, h)
        out = _expert_matmul(h, p["w2"], sizes)                 # (T*k, D)
    with jax.named_scope("combine"):
        back = jnp.zeros_like(order).at[order].set(
            jnp.arange(t * k, dtype=order.dtype))               # inverse
        y_rows = out[back].astype(jnp.float32) * top_p.reshape(t * k, 1)
        y2 = y_rows.reshape(t, k, d).sum(axis=1)
    return y2.astype(x.dtype).reshape(b, s, d), aux


def moe_block_gather(cfg: ModelConfig, p, x: jax.Array
                     ) -> Tuple[jax.Array, Dict]:
    """Ablation path: per-token expert-weight gathers (the 'branchy'
    analogue).  Only sane at small scale — benchmarks contrast its HLO
    (dynamic-gather of O(T*D*F) weights) against the banked path."""
    b, s, d = x.shape
    t = b * s
    x2 = x.reshape(t, d)
    top_p, top_i, aux = _router(cfg, p, x2)
    y2 = jnp.zeros((t, d), jnp.float32)
    for slot in range(cfg.experts_per_token):
        idx = top_i[:, slot]                       # (T,) dynamic
        w1 = p["w1"][idx]                          # (T, D, F) gather!
        w2 = p["w2"][idx]
        h = jnp.einsum("td,tdf->tf", x2, w1)
        if gated_mlp(cfg):
            wg = p["wg"][idx]
            h = activation(cfg, jnp.einsum("td,tdf->tf", x2, wg)) * h
        else:
            h = activation(cfg, h)
        y = jnp.einsum("tf,tfd->td", h, w2)
        y2 = y2 + top_p[:, slot, None] * y.astype(jnp.float32)
    return y2.astype(x.dtype).reshape(b, s, d), aux


def _ep_context():
    """(mesh, model_axis, batch_axes, tp_size) when EP is available."""
    mesh = _common._MESH
    if mesh is None:
        return None
    rules = _common._RULES
    model_axis = rules.get("experts")
    batch_axes = rules.get("batch")
    if not isinstance(model_axis, str):
        return None
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    tp = sizes.get(model_axis, 1)
    if tp <= 1:
        return None
    return mesh, model_axis, batch_axes, tp


def moe_block_banked_ep(cfg: ModelConfig, p, x: jax.Array, mesh, model_axis,
                        batch_axes, tp: int) -> Tuple[jax.Array, Dict]:
    """Expert-parallel dispatch via shard_map (perf iteration 5).

    Tokens are replicated across the model axis after batch sharding, so
    each expert owner selects the rows bound for ITS experts locally —
    the dispatch itself moves no bytes; one psum over the model axis
    combines expert outputs.  The device index is the bank index: the
    paper's layout-embedded banking at mesh scale, now with explicitly
    scheduled communication."""
    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    e, k = cfg.num_experts, cfg.experts_per_token
    e_loc = e // tp
    gated = gated_mlp(cfg)

    def local_fn(xl, router, w1, w2, wg):
        bl, s, d = xl.shape
        tl = bl * s
        x2 = xl.reshape(tl, d)
        top_p, top_i, aux = _router(cfg, {"router": router}, x2)
        cap = capacity(cfg, tl)                       # local capacity
        eid = top_i.reshape(tl * k)
        gate = top_p.reshape(tl * k)
        oh = jax.nn.one_hot(eid, e, dtype=jnp.int32)
        pos = jnp.cumsum(oh, axis=0) - oh
        pos = jnp.take_along_axis(pos, eid[:, None], axis=1)[:, 0]
        keep = pos < cap
        pos_c = jnp.minimum(pos, cap - 1)

        m = jax.lax.axis_index(model_axis)
        mine = (eid >= m * e_loc) & (eid < (m + 1) * e_loc) & keep
        loc_e = jnp.where(mine, eid - m * e_loc, 0)
        x_rep = jnp.repeat(x2, k, axis=0)
        upd = x_rep * mine[:, None].astype(x.dtype)
        buf = jnp.zeros((e_loc, cap, d), x.dtype).at[loc_e, pos_c].add(upd)

        h = jnp.einsum("ecd,edf->ecf", buf, w1)
        if gated:
            g = jnp.einsum("ecd,edf->ecf", buf, wg)
            h = activation(cfg, g) * h
        else:
            h = activation(cfg, h)
        ye = jnp.einsum("ecf,efd->ecd", h, w2)

        y_rows = ye[loc_e, pos_c]
        w_gate = (gate * mine.astype(jnp.float32))[:, None]
        y2 = (y_rows.astype(jnp.float32) * w_gate).reshape(tl, k, d).sum(1)
        y2 = jax.lax.psum(y2, model_axis)             # combine experts
        aux = {kk: jax.lax.pmean(jax.lax.pmean(vv, batch_axes), model_axis)
               for kk, vv in aux.items()}
        return y2.astype(x.dtype).reshape(bl, s, d), aux

    wg_param = p.get("wg", p["w1"])
    fn = shard_map(
        local_fn, mesh=mesh,
        in_specs=(P(batch_axes, None, None), P(None, None),
                  P(model_axis, None, None), P(model_axis, None, None),
                  P(model_axis, None, None)),
        out_specs=(P(batch_axes, None, None),
                   {"moe_aux": P(), "moe_zloss": P()}),
        check_rep=False)
    return fn(x, p["router"], p["w1"], p["w2"], wg_param)


@jax.named_scope("moe")
def moe_block(cfg: ModelConfig, p, x: jax.Array) -> Tuple[jax.Array, Dict]:
    if cfg.moe_dispatch == "grouped":
        return moe_block_grouped(cfg, p, x)
    if cfg.moe_dispatch == "banked":
        ep = _ep_context()
        if ep is not None and cfg.num_experts % ep[3] == 0:
            mesh, model_axis, batch_axes, tp = ep
            return moe_block_banked_ep(cfg, p, x, mesh, model_axis,
                                       batch_axes, tp)
        return moe_block_banked(cfg, p, x)
    return moe_block_gather(cfg, p, x)
