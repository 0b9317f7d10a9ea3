"""OLMoE-1B-7B-0924's published architecture in the program: QK-norm in
both attention paths, top-k gates kept as softmax probabilities, and the
dropless grouped expert dispatch, each against the plain fp32 reference
(``chipbench/reference/olmoe.py``) at a small size; and the dense
configurations' parameter tree, which the new fields leave as it was."""
import dataclasses
import functools
import pathlib
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = pathlib.Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import weights  # noqa: E402
from chipbench.reference import olmoe as REF  # noqa: E402
from chipbench.reference.common import mm_fp32  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.models import attention as A  # noqa: E402
from repro.models import decode, get_config  # noqa: E402
from repro.models import moe as MOE  # noqa: E402
from repro.models import params as MP  # noqa: E402
from repro.models.common import rope_freqs  # noqa: E402

# fp32 program against the fp32 reference: the two differ only in the order
# of their sums (the program's RoPE and norms run in the same f32), a few
# ulp of values of order 1, so 1e-4 absolute leaves two orders of room while
# anything the mathematics leaves out (a norm, a gain) moves outputs by
# order 0.1 and more
TOL = 1e-4


def _cfg(**kw):
    """The published flags at a small size, in fp32."""
    cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(),
                              num_experts=8, experts_per_token=2, norm_eps=1e-5,
                              qk_norm=True, moe_norm_topk_prob=False,
                              moe_dispatch="grouped")
    return dataclasses.replace(cfg, **kw)


def _sizes(cfg):
    """The reference's view of ``cfg`` (a configuration file's keys)."""
    return {"num_attention_heads": cfg.num_heads, "num_key_value_heads": cfg.num_kv_heads,
            "head_dim": cfg.head_dim, "rms_norm_eps": cfg.norm_eps,
            "rope_theta": cfg.rope_theta, "hidden_size": cfg.d_model,
            "intermediate_size": cfg.d_ff, "num_experts": cfg.num_experts,
            "num_experts_per_tok": cfg.experts_per_token}


@functools.lru_cache(maxsize=None)
def _layer0(cfg, seed=3):
    """Layer 0's parameters as the benchmark draws them (norm gains
    1 + N(0, 0.1^2), so QK-norm's gains are not all ones)."""
    return jax.tree.map(lambda a: a[0], weights.make(cfg, seed)["blocks"]["lyr"])


def _x(cfg, b=2, s=12, seed=0):
    return jnp.asarray(np.random.default_rng(seed).normal(size=(b, s, cfg.d_model)),
                       jnp.float32)


def _err(a, b):
    return float(np.abs(np.asarray(a, np.float32) - np.asarray(b, np.float32)).max())


# -- (b) QK-norm -----------------------------------------------------------

def test_qk_norm_gains_only_where_the_config_asks():
    attn = MP.param_shapes(_cfg())["blocks"]["lyr"]["attn"]
    h, hkv, dh = 4, 4, 16
    assert attn["q_norm"] == ((2, h * dh), "ones")
    assert attn["k_norm"] == ((2, hkv * dh), "ones")
    assert "q_norm" not in MP.param_shapes(_cfg(qk_norm=False))["blocks"]["lyr"]["attn"]


def test_attn_block_matches_the_reference():
    cfg = _cfg()
    p, x = _layer0(cfg)["attn"], _x(cfg)
    got = A.attn_block(cfg, p, x, rope=rope_freqs(cfg.head_dim, cfg.rope_theta,
                                                  jnp.arange(x.shape[1])))
    with jax.default_matmul_precision("highest"):
        want = REF.attention(_sizes(cfg), p, x, mm_fp32)
    assert _err(got, want) < TOL
    # the same weights without QK-norm are another function
    plain = A.attn_block(_cfg(qk_norm=False), p, x, rope=rope_freqs(
        cfg.head_dim, cfg.rope_theta, jnp.arange(x.shape[1])))
    assert _err(plain, want) > 0.05


def _decode_all(cfg, p, x):
    """attn_decode over x (B, S, D) a position at a time, each new row
    written into the cache as the served step writes it."""
    b, s, _ = x.shape
    shape = (b, s, cfg.num_kv_heads * cfg.head_dim)
    cache = {"k": jnp.zeros(shape, jnp.float32), "v": jnp.zeros(shape, jnp.float32)}
    outs = []
    for i in range(s):
        out, rows = A.attn_decode(cfg, p, x[:, i:i + 1], cache, jnp.int32(i))
        cache = decode.write_cache(cache, rows, jnp.int32(i))
        outs.append(out)
    return jnp.concatenate(outs, axis=1)


def test_attn_decode_matches_the_reference_at_every_position():
    cfg = _cfg()
    p, x = _layer0(cfg)["attn"], _x(cfg)
    with jax.default_matmul_precision("highest"):
        want = REF.attention(_sizes(cfg), p, x, mm_fp32)
    assert _err(_decode_all(cfg, p, x), want) < TOL
    assert _err(_decode_all(_cfg(qk_norm=False), p, x), want) > 0.05


# -- (c) plain top-k gates --------------------------------------------------

def test_gates_are_the_raw_softmax_top_k():
    cfg = _cfg()
    p, x2 = _layer0(cfg)["moe"], _x(cfg).reshape(-1, cfg.d_model)
    probs = jax.nn.softmax(x2 @ p["router"], axis=-1)
    top = -np.sort(-np.asarray(probs), axis=-1)[:, :cfg.experts_per_token]
    gates, idx, _ = MOE._router(cfg, p, x2)
    np.testing.assert_allclose(np.asarray(gates), top, rtol=1e-6)
    assert (np.asarray(gates).sum(-1) < 1.0).all()
    np.testing.assert_array_equal(np.take_along_axis(np.asarray(probs), np.asarray(idx), 1),
                                  np.asarray(gates))
    renormed, _, _ = MOE._router(_cfg(moe_norm_topk_prob=True), p, x2)
    np.testing.assert_allclose(np.asarray(renormed).sum(-1), 1.0, rtol=1e-6)


# -- (d) the dropless grouped dispatch --------------------------------------

def _forced(p, to=0):
    """The router made to put expert ``to`` among every token's top k: its
    column follows the inputs' constant first feature, set large."""
    router = p["router"].at[0].set(0.0).at[0, to].set(50.0)
    return dict(p, router=router)


def _x_forced(cfg):
    return _x(cfg, b=4, s=8).at[..., 0].set(1.0)


@pytest.mark.parametrize("routing", ["random", "all_to_one_expert"])
def test_grouped_equals_capacity_at_e_over_k_and_the_reference(routing):
    cfg = _cfg()
    p, x = _layer0(cfg)["moe"], _x(cfg, b=4, s=8)
    if routing == "all_to_one_expert":
        p, x = _forced(p), _x_forced(cfg)
        _, idx, _ = MOE._router(cfg, p, x.reshape(-1, cfg.d_model))
        assert (np.asarray(idx) == 0).any(-1).all()     # every token sends a row to expert 0
    grouped, _ = MOE.moe_block(cfg, p, x)
    whole = dataclasses.replace(cfg, moe_dispatch="banked",
                                moe_capacity_factor=cfg.num_experts / cfg.experts_per_token)
    assert MOE.capacity(whole, x.shape[0] * x.shape[1]) >= x.shape[0] * x.shape[1]
    capacity, _ = MOE.moe_block(whole, p, x)
    assert _err(grouped, capacity) < 1e-5           # the same sums in another order
    with jax.default_matmul_precision("highest"):
        want = REF.moe_ffn(_sizes(cfg), p, x, mm_fp32)
    assert _err(grouped, want) < TOL


def test_a_tight_capacity_drops_what_grouped_keeps():
    cfg = _cfg()
    p, x = _forced(_layer0(cfg)["moe"]), _x_forced(cfg)
    with jax.default_matmul_precision("highest"):
        want = REF.moe_ffn(_sizes(cfg), p, x, mm_fp32)
    tight, _ = MOE.moe_block(dataclasses.replace(cfg, moe_dispatch="banked"), p, x)
    assert _err(tight, want) > 0.05                 # capacity factor 1.25 drops rows
    grouped, _ = MOE.moe_block(cfg, p, x)
    assert _err(grouped, want) < TOL


@pytest.mark.parametrize("stacked", [False, True], ids=["one_layer", "layer_of_stack"])
@pytest.mark.parametrize("sizes", [[3, 0, 5, 9], [0, 0, 17, 0], [8, 8, 8, 8]],
                         ids=["uneven", "one_group", "even"])
def test_grouped_matmul_is_each_rows_own_product(sizes, stacked):
    """Against each row's own product; with ``stacked``, the matrices of
    layer 1 of three, as the served step's layer loop hands them over."""
    rng = np.random.default_rng(1)
    m = sum(sizes)
    lhs = jnp.asarray(rng.normal(size=(m, 32)), jnp.float32)
    layers = jnp.asarray(rng.normal(size=(3, len(sizes), 32, 24)), jnp.float32)
    rhs = layers[1]
    if stacked:
        got = ops.grouped_matmul(lhs, layers, jnp.asarray(sizes, jnp.int32), jnp.int32(1))
    else:
        got = ops.grouped_matmul(lhs, rhs, jnp.asarray(sizes, jnp.int32))
    group = np.repeat(np.arange(len(sizes)), sizes)
    want = np.einsum("mk,mkn->mn", np.asarray(lhs), np.asarray(rhs)[group])
    assert got.shape == (m, 24)
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-5, atol=1e-5)


# -- (e) the dense path's parameters are as they were ------------------------

QWEN2_LEAVES = [
    ("blocks/lyr/attn/bk", (24, 128), "zeros"), ("blocks/lyr/attn/bq", (24, 896), "zeros"),
    ("blocks/lyr/attn/bv", (24, 128), "zeros"), ("blocks/lyr/attn/wk", (24, 896, 128), "normal"),
    ("blocks/lyr/attn/wo", (24, 896, 896), "normal"),
    ("blocks/lyr/attn/wq", (24, 896, 896), "normal"),
    ("blocks/lyr/attn/wv", (24, 896, 128), "normal"), ("blocks/lyr/ln1/w", (24, 896), "ones"),
    ("blocks/lyr/ln2/w", (24, 896), "ones"), ("blocks/lyr/mlp/wg", (24, 896, 4864), "normal"),
    ("blocks/lyr/mlp/wi", (24, 896, 4864), "normal"),
    ("blocks/lyr/mlp/wo", (24, 4864, 896), "normal"),
    ("embed", (151936, 896), "embed"), ("final_norm/w", (896,), "ones"),
]


def _leaves(cfg):
    flat, _ = jax.tree_util.tree_flatten_with_path(MP.param_shapes(cfg), is_leaf=weights._is_leaf)
    return [("/".join(k.key for k in path), shape, kind) for path, (shape, kind) in flat]


def test_qwen2_parameter_tree_and_draw_order_unchanged():
    """``weights.make`` folds its key by the index of each leaf in this
    order, so the same list means the same seeded values."""
    cfg = get_config("qwen2-0.5b")
    assert not cfg.qk_norm and cfg.moe_norm_topk_prob and cfg.moe_dispatch == "banked"
    assert _leaves(cfg) == QWEN2_LEAVES


def test_the_served_step_names_its_new_parts():
    """The scopes the cell's readers take apart (``decode.SERVE_SCOPES``)
    are on the served step's operations as JAX lowers them."""
    cfg = _cfg()
    text = decode.make_serve_step(cfg).lower(
        MP.param_specs(cfg), decode.cache_specs(cfg, 4, 32),
        jax.ShapeDtypeStruct((4, 1), jnp.int32),
        jax.ShapeDtypeStruct((), jnp.int32)).as_text(dialect="hlo", debug_info=True)
    for path in ("attn/qk_norm", "moe/route", "moe/dispatch", "moe/experts", "moe/combine"):
        assert path in decode.SERVE_SCOPES and f"{path}/" in text, path
