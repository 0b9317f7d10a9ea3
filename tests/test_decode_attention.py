"""Decode attention reads each slot's live K/V only: the Pallas kernel
(through its interpreter on the CPU) against a plain reference,
``attn_decode`` through the kernel against its jnp path at the benchmark
configurations' head shapes, the engine's slot lengths and live-position
counter, and the layer numbers the served step hands attention."""
import dataclasses
import functools
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import decode_attention as DA
from repro.kernels import ops
from repro.launch.serve import Engine, Request
from repro.models import attention as A
from repro.models import decode
from repro.models import get_config
from repro.models import params as MP
from repro.obs.metrics import Counter, MetricsRegistry

# f32 operands on both sides: the two differ only in the order of their
# sums (the kernel's running max rescales in steps), a few ulp of values of
# order 1
TOL = 1e-5


def _reference(q, k, v, lens, layer):
    """(acc, m, l) of the kernel from their definition, in f32 over every
    position with a mask."""
    b, h, dh = q.shape
    _, _, smax, width = k.shape
    hkv = width // dh
    kc = k[layer].reshape(b, smax, hkv, dh).astype(jnp.float32)
    vc = v[layer].reshape(b, smax, hkv, dh).astype(jnp.float32)
    qg = q.reshape(b, hkv, h // hkv, dh).astype(jnp.float32)
    s = jnp.einsum("bjgd,bsjd->bjgs", qg, kc, precision="highest") / dh ** 0.5
    live = (jnp.arange(smax)[None] < lens[:, None])[:, None, None]
    s = jnp.where(live, s, -1e30)
    m = s.max(-1, keepdims=True)
    p = jnp.where(live, jnp.exp(s - m), 0.0)
    acc = jnp.einsum("bjgs,bsjd->bjgd", p, vc, precision="highest")
    return acc.reshape(b, h, dh), m.reshape(b, h), p.sum(-1).reshape(b, h)


# (heads, kv heads, head width, cache length, layers, slot lengths, rows a
# chunk, slots a group): qwen2-0.5b's 14/2 x 64 (two heads share a 128-lane
# tile) and OLMoE's 16/16 x 128 (a tile a head), lengths at 0, 1, a chunk
# edge, the last position and between, in groups the lengths do not fill,
# the last chunk starting before its edge where the cache length is not a
# multiple of the chunk; then the kernel's own chunks and groups
KERNEL_CASES = {
    "qwen2_heads": (14, 2, 64, 48, 3, [0, 1, 16, 47, 48, 5, 0, 33], 16, 2),
    "olmoe_heads": (16, 16, 128, 40, 2, [40, 0, 17, 39, 0, 0], 16, 2),
    "whole_row": (4, 1, 16, 24, 2, [3, 0, 24, 9], None, None),
    "default_blocks": (14, 2, 64, 200, 2, [199, 0, 128, 0, 1, 64, 0, 0],
                       None, None),
}


@pytest.mark.parametrize("case", list(KERNEL_CASES))
def test_kernel_matches_its_definition(case):
    h, hkv, dh, smax, layers, lens, rows, group = KERNEL_CASES[case]
    b = len(lens)
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(7), 3)
    q = jax.random.normal(kq, (b, h, dh), jnp.float32)
    k = jax.random.normal(kk, (layers, b, smax, hkv * dh), jnp.float32)
    v = jax.random.normal(kv, (layers, b, smax, hkv * dh), jnp.float32)
    lens = jnp.asarray(lens, jnp.int32)
    layer = jnp.int32(layers - 1)
    acc, m, l = DA.decode_attention(q, k, v, lens, layer, rows=rows,
                                    group=group, interpret=True)
    ra, rm, rl = _reference(q, k, v, lens, layer)
    live = np.asarray(lens) > 0
    np.testing.assert_allclose(np.asarray(m)[live], np.asarray(rm)[live],
                               atol=TOL)
    np.testing.assert_allclose(np.asarray(acc / l[..., None])[live],
                               np.asarray(ra / rl[..., None])[live],
                               atol=TOL)
    # a slot of length 0 reads nothing: (0, -1e30, 0), no NaN
    assert (np.asarray(acc)[~live] == 0).all()
    assert (np.asarray(m)[~live] == -1e30).all()
    assert (np.asarray(l)[~live] == 0).all()


def test_ops_wrapper_takes_one_layer_or_the_stack():
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(kq, (4, 14, 64), jnp.float32)
    k = jax.random.normal(kk, (2, 4, 32, 128), jnp.float32)
    v = jax.random.normal(kv, (2, 4, 32, 128), jnp.float32)
    lens = jnp.asarray([32, 0, 7, 16], jnp.int32)
    stacked = ops.decode_attention(q, k, v, lens, 1)
    one = ops.decode_attention(q, k[1:], v[1:], lens)
    for a, b in zip(stacked, one):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _arch(name):
    """The benchmark configurations' attention at a small model width:
    qwen2-0.5b's 14/2 heads of 64 with QKV bias, OLMoE-1B-7B-0924's 16/16
    heads of 128 with QK-norm; f32, so both paths compute alike."""
    if name == "qwen2-0.5b":
        cfg = dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                                  num_heads=14, num_kv_heads=2, head_dim=64)
    else:
        cfg = dataclasses.replace(get_config("olmoe-1b-7b").reduced(),
                                  num_heads=16, num_kv_heads=16, head_dim=128,
                                  norm_eps=1e-5, qk_norm=True)
    return cfg


# each configuration's cache length: more than the kernel's chunk (64 rows
# of a 128-lane f32 row, 32 of a 2048-lane one), with a last chunk that
# starts before its edge
SMAX = {"qwen2-0.5b": 80, "olmoe-1b-7b-0924": 40}
POSITIONS = {"zero": lambda s, r: 0, "one": lambda s, r: 1,
             "chunk_edge": lambda s, r: r, "last": lambda s, r: s - 1}
SLOTS = 4


@functools.lru_cache(maxsize=None)
def _attn(name):
    """(inputs, the kernel path, the jnp path, the kernel path given only
    the one layer), each compiled once for every position of the tests:
    x1, the layer's parameters and every layer's cache (noise at every
    position, also those no slot attends) with this layer's number."""
    cfg = _arch(name)
    smax, width = SMAX[name], cfg.num_kv_heads * cfg.head_dim
    p = jax.tree.map(lambda a: a[0],
                     MP.init_params(cfg, seed=1)["blocks"]["lyr"]["attn"])
    kx, kk, kv = jax.random.split(jax.random.PRNGKey(5), 3)
    x1 = jax.random.normal(kx, (SLOTS, 1, cfg.d_model), jnp.float32)
    cache = {"k": jax.random.normal(kk, (3, SLOTS, smax, width), jnp.float32),
             "v": jax.random.normal(kv, (3, SLOTS, smax, width), jnp.float32),
             "layer": jnp.int32(1)}
    one = {"k": cache["k"][1], "v": cache["v"][1]}

    def run(c, pos, lens):
        return A.attn_decode(cfg, p, x1, c, pos, lens=lens)

    def compiled(c):
        return jax.jit(run).lower(c, jnp.int32(0),
                                  jnp.zeros(SLOTS, jnp.int32)).compile()

    # the kernel at every share of the cache read, and the einsums
    with mock.patch.object(A, "KERNEL_MAX_READ_SHARE",
                           {"shared": 2.0, "one_query": 2.0}):
        kernel, alone = compiled(cache), compiled(one)
    with mock.patch.object(A, "kernel_serves", lambda **kw: False):
        einsums = compiled(cache)
    return (cfg, cache, one), kernel, einsums, alone


@pytest.mark.parametrize("name", list(SMAX))
@pytest.mark.parametrize("where", list(POSITIONS))
def test_attn_decode_kernel_matches_the_jnp_path(name, where):
    """Slots 1 and 3 are free (length 0): every slot's output and new rows
    agree between the kernel and the einsums over every position, and the
    kernel reads the same from the stacked cache as from the layer alone."""
    (cfg, cache, one), kernel, einsums, alone = _attn(name)
    assert A.kernel_serves()
    smax = SMAX[name]
    rows = DA.block_rows(cfg.num_kv_heads * cfg.head_dim, 4, smax)
    pos = jnp.int32(POSITIONS[where](smax, rows))
    lens = jnp.asarray([pos, 0, pos, 0], jnp.int32)
    got, got_rows = kernel(cache, pos, lens)
    want, want_rows = einsums(cache, pos, lens)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=TOL)
    for n in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(got_rows[n]),
                                      np.asarray(want_rows[n]))
    np.testing.assert_allclose(np.asarray(alone(one, pos, lens)[0]),
                               np.asarray(got), atol=TOL)


def _serve(cfg, params, slots, requests, max_len=24, metrics=None):
    eng = Engine(cfg, params, slots, max_len, metrics=metrics)
    served = [Request(i, np.asarray(p, np.int32), g)
              for i, (p, g) in enumerate(requests)]
    for r in served:
        eng.submit(r)
    eng.admit()
    while eng.inflight:
        eng.step()
    return eng, served


REQUESTS = [([3, 9, 4], 8), ([5, 1], 9)]


def _gqa_qwen2(**kw):
    """The reduced qwen2-0.5b at its published head shape (14/2 x 64)."""
    return dataclasses.replace(get_config("qwen2-0.5b").reduced(),
                               num_heads=14, num_kv_heads=2, head_dim=64, **kw)


def _reduced(arch, **kw):
    return lambda: dataclasses.replace(get_config(arch).reduced(), **kw)


# every family the engine serves, and the int8 K/V cache
SERVED = {
    "qwen2-0.5b": _gqa_qwen2,
    **{arch: _reduced(arch) for arch in (
        "gemma2-27b", "starcoder2-7b", "olmoe-1b-7b", "rwkv6-7b",
        "zamba2-7b", "whisper-large-v3", "llama-3.2-vision-11b")},
    "qwen2-0.5b-int8": lambda: _gqa_qwen2(kv_cache_dtype="int8"),
    "gemma2-27b-int8": _reduced("gemma2-27b", kv_cache_dtype="int8"),
}


@pytest.mark.parametrize("name", list(SERVED))
def test_free_slots_beside_a_request_leave_its_tokens_alone(name):
    """The same two requests in an engine with no free slot, and in one
    with a slot never taken and a short request that frees its slot after
    two steps: attention reads nothing of a free slot (length 0), and the
    two requests get the same tokens, whatever shares their batch."""
    cfg = SERVED[name]()
    params = MP.init_params(cfg, seed=2)
    _, alone = _serve(cfg, params, 2, REQUESTS)
    _, beside = _serve(cfg, params, 4, REQUESTS + [([7], 1)])
    assert all(len(r.out) == r.gen for r in alone + beside)
    assert [r.out for r in beside[:2]] == [r.out for r in alone]


def test_live_counter_sums_each_slots_length():
    """With a registry, each step adds the slots' lengths (the shared
    position for an occupied slot, nothing for a free one), whichever path
    attention takes; the gauge holds slots x max_len."""
    cfg = _gqa_qwen2()
    params = MP.init_params(cfg, seed=2)
    max_len = 80
    occupied = []
    reg = MetricsRegistry()
    requests = REQUESTS + [([7] * 3, 4), ([2] * 60, 8)]
    eng = Engine(cfg, params, 5, max_len, metrics=reg)
    for i, (p, g) in enumerate(requests):
        eng.submit(Request(i, np.asarray(p, np.int32), g))
    eng.admit()
    while eng.inflight:
        occupied.append((eng.pos, eng.inflight))
        eng.step()
    # one slot free throughout, and steps past the kernel's first chunk
    rows = DA.block_rows(cfg.num_kv_heads * cfg.head_dim, 4, max_len)
    assert all(n < 5 for _, n in occupied) and eng.pos > rows
    want = sum(pos * n for pos, n in occupied)
    assert reg.get("serve_kv_positions_live_total").value == want
    assert reg.get("serve_kv_positions").value == 5 * max_len


def test_no_registry_counts_nothing(monkeypatch):
    cfg = get_config("qwen2-0.5b").reduced()
    params = MP.init_params(cfg, seed=2)

    def refuse(*a):
        raise AssertionError("counted without a registry")

    monkeypatch.setattr(Counter, "inc", refuse)
    eng, served = _serve(cfg, params, 3, REQUESTS)
    assert eng.steps > 0 and all(len(r.out) == r.gen for r in served)


def _pallas_inputs(jaxpr):
    """The input shapes of every ``pallas_call`` in ``jaxpr`` and the
    jaxprs nested in it (loops, branches, jitted calls)."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append([v.aval.shape for v in eqn.invars])
        for param in eqn.params.values():
            for sub in param if isinstance(param, (list, tuple)) else [param]:
                if isinstance(sub, jax.extend.core.ClosedJaxpr):
                    found += _pallas_inputs(sub.jaxpr)
                elif isinstance(sub, jax.extend.core.Jaxpr):
                    found += _pallas_inputs(sub)
    return found


# families whose self-attention takes the kernel, with the number of
# self-attention layers stacked in their cache: vlm stacks a group's
# self-attention layers inside the group axis
STACKED_LAYERS = {"qwen2-0.5b": 2, "olmoe-1b-7b": 2,
                  "llama-3.2-vision-11b": 8, "zamba2-7b": 2,
                  "whisper-large-v3": 2}


@pytest.mark.parametrize("name", list(STACKED_LAYERS))
def test_served_step_hands_attention_every_layer(name):
    """In the served step the kernel takes every self-attention layer's K/V
    as the cache stacks them, with the layer's number (a slice of the stack
    would be copied out before the kernel reads it), and its logits are the
    einsums' over every position."""
    cfg = dataclasses.replace(get_config(name).reduced(), dtype="float32")
    params = MP.init_params(cfg, seed=3)
    slots, max_len = 2, 16
    cache = decode.init_cache(cfg, params, batch=slots, max_len=max_len)
    toks = jnp.asarray([[5], [7]], jnp.int32)
    pos, lens = jnp.int32(6), jnp.asarray([6, 0], jnp.int32)
    for n in range(pos):        # noise in every cached position
        _, cache = decode.serve_step(cfg, params, cache, toks, jnp.int32(n))
    step = functools.partial(decode.serve_step, cfg)
    kernel_inputs = _pallas_inputs(jax.make_jaxpr(step)(
        params, cache, toks, pos, lens).jaxpr)
    width = cfg.num_kv_heads * cfg.head_dim
    kv = [s for shapes in kernel_inputs for s in shapes
          if s[1:] == (slots, max_len, width)]
    assert kv and all(s[0] == STACKED_LAYERS[name] for s in kv), kernel_inputs
    with mock.patch.object(A, "KERNEL_MAX_READ_SHARE",
                           {"shared": 2.0, "one_query": 2.0}):
        got, _ = jax.jit(step)(params, cache, toks, pos, lens)
    with mock.patch.object(A, "kernel_serves", lambda **kw: False):
        want, _ = jax.jit(step)(params, cache, toks, pos, lens)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               atol=1e-4)
