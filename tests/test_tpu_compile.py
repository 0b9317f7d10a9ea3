"""Compile the main-path kernels and the full-width serve steps (qwen2-0.5b,
and OLMoE-1B-7B-0924 as its benchmark cell serves it) for one TPU v5e chip,
described rather than attached (nothing runs).

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the tiling, primitives the kernel language lacks, programs that
do not fit the device.  This is the only test file that describes the chip;
the topology is described inside a fixture, so every xdist worker collects
the same tests and only the worker that runs this file loads the TPU
library.  The persistent compilation cache is off around these compiles: an
entry written for a described chip cannot be read back without one.
"""
import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import banked_matmul as BM
from repro.kernels import flash_attention as FA
from repro.kernels import ops
from repro.kernels import ssm_scan as SS
from repro.models import decode, get_config
from repro.models import params as MP

V5E_HBM_BYTES = 16e9


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("bank", [1, 2])
def test_banked_matmul_qwen2_mlp(one_chip, bank):
    fn = functools.partial(BM.banked_matmul, banks=(bank,) * 3,
                           interpret=False)
    hlo = _kernel_hlo(fn, _spec(one_chip, (128, 896)),
                      _spec(one_chip, (896, 4864)))
    assert "tpu_custom_call" in hlo


def test_flash_attention_qwen2_heads(one_chip):
    cfg = get_config("qwen2-0.5b")
    q = _spec(one_chip, (1, cfg.num_heads, 4096, cfg.head_dim))
    kv = _spec(one_chip, (1, cfg.num_kv_heads, 4096, cfg.head_dim))
    fn = functools.partial(FA.flash_attention, causal=True, interpret=False)
    assert "tpu_custom_call" in _kernel_hlo(fn, q, kv, kv)


@pytest.mark.parametrize("diag_mode", ["inclusive", "bonus"])
def test_ssm_scan_rwkv6_heads(one_chip, diag_mode):
    cfg = get_config("rwkv6-7b")
    h, dh = cfg.num_heads, cfg.head_dim
    x = _spec(one_chip, (1, h, 512, dh))
    fn = functools.partial(SS.ssm_scan, chunk=32, diag_mode=diag_mode,
                           interpret=False)
    assert "tpu_custom_call" in _kernel_hlo(fn, x, x, x, x,
                                            _spec(one_chip, (h, dh)))


@pytest.mark.parametrize("k,n", [(2048, 1024), (1024, 2048)], ids=["gate_up", "down"])
def test_grouped_matmul_olmoe_experts(one_chip, monkeypatch, k, n):
    """The olmoe cell's expert matmuls: 64 slots' top-8 rows over 64
    experts, compiled as the Pallas kernel the chip runs."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    hlo = ops.grouped_matmul.lower(
        _spec(one_chip, (512, k)), _spec(one_chip, (64, k, n)),
        _spec(one_chip, (64,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in hlo


def _nbytes(tree):
    return sum(s.size * s.dtype.itemsize for s in jax.tree.leaves(tree))


def _compile_step(sharding, cfg, slots, max_len):
    """(compiled step, param specs, cache specs) of ``cfg``'s serve step for
    ``slots`` x ``max_len``, called as the engine calls it (tokens, position
    and each slot's length), with the Pallas kernels compiled for the
    chip."""
    params = MP.param_specs(cfg)
    cache = jax.eval_shape(
        functools.partial(decode.init_cache, cfg, batch=slots,
                          max_len=max_len), params)

    def place(tree):
        return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype),
                            tree)

    interpret = ops._interpret
    ops._interpret = lambda: False
    try:
        compiled = decode.make_serve_step(cfg).lower(
            place(params), place(cache),
            _spec(sharding, (slots, 1), jnp.int32),
            _spec(sharding, (), jnp.int32),
            _spec(sharding, (slots,), jnp.int32)).compile()
    finally:
        ops._interpret = interpret
    return compiled, params, cache


def _compile_qwen2_step(sharding, slots, max_len):
    """(compiled step, param specs, cache specs) of the full-width qwen2-0.5b
    serve step for ``slots`` x ``max_len``."""
    return _compile_step(sharding, get_config("qwen2-0.5b"), slots, max_len)


def _olmoe_0924():
    """OLMoE-1B-7B-0924 as its benchmark cell serves it: 8 layers at the
    published widths, QK-norm, plain gates, grouped experts."""
    return dataclasses.replace(get_config("olmoe-1b-7b"), num_layers=8,
                               norm_eps=1e-5, qk_norm=True,
                               moe_norm_topk_prob=False,
                               moe_dispatch="grouped")


def test_olmoe_0924_serve_step_fits_one_chip(one_chip, monkeypatch):
    """The olmoe cell's step (8 layers at published widths, QK-norm, plain
    gates, grouped experts) for 64 x 1024: weights and donated cache on one
    chip, the expert matmuls as kernels reading the stacked weights."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = _olmoe_0924()
    params = MP.param_specs(cfg)
    cache = jax.eval_shape(functools.partial(decode.init_cache, cfg, batch=64,
                                             max_len=1024), params)
    place = functools.partial(jax.tree.map, lambda s: _spec(one_chip, s.shape, s.dtype))
    compiled = decode.make_serve_step(cfg).lower(
        place(params), place(cache), _spec(one_chip, (64, 1), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 4
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    # the kernels read each layer's experts out of the stacked weights: no
    # layer's slice of them (64 x 2048 x 1024 bf16) is copied out first
    assert mem.temp_size_in_bytes < 64 * 2048 * 1024 * 2
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


def test_qwen2_serve_step_fits_one_chip(one_chip):
    compiled, params, cache = _compile_qwen2_step(one_chip, 4, 4096)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes >= _nbytes(params)
    # the donated cache comes back in its own buffers
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total


_COMPUTATION = re.compile(r"^(ENTRY )?%([\w.\-]+) .*\{$")
_ARRAY_OP = re.compile(
    r"^\s*(?:ROOT )?%([\w.\-]+) = \w+\[([\d,]*)\]\S* ([a-z][\w\-]*)\((.*)")


def _size(dims):
    return functools.reduce(lambda a, b: a * b, dims, 1)


def _operations(hlo):
    """Per computation of a compiled module, its array-valued instructions
    as {name: (dims, opcode, rest of the line)}, the entry's name, and the
    computations that run as control flow (bodies, conditions, calls)."""
    comps, entry, cur, flow = {}, None, None, set()
    for line in hlo.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            cur = comps.setdefault(m.group(2), {})
            entry = m.group(2) if m.group(1) else entry
            continue
        flow.update(re.findall(r"(?:condition|body|to_apply)=%([\w.\-]+)",
                               line) if " fusion(" not in line else ())
        m = _ARRAY_OP.match(line)
        if m and cur is not None:
            dims = tuple(int(d) for d in m.group(2).split(",") if d)
            cur[m.group(1)] = (dims, m.group(3), m.group(4))
    return comps, entry, flow


def _update_rows(comps, comp, opcode, rest):
    """Elements of the update a dynamic-update-slice writes (for a fusion,
    the one at its root), or None for any other operation."""
    if opcode == "fusion":
        comp = re.search(r"calls=%([\w.\-]+)", rest).group(1)
        roots = [v for v in comps[comp].values()
                 if v[1] == "dynamic-update-slice"]
        if len(roots) != 1:
            return None
        opcode, rest = roots[0][1], roots[0][2]
    if opcode != "dynamic-update-slice":
        return None
    update = re.findall(r"%([\w.\-]+)", rest)[1]
    return _size(comps[comp][update][0])


@pytest.mark.parametrize("slots,max_len", [(512, 528), (256, 1024)],
                         ids=["512x528", "256x1024"])
def test_qwen2_serve_step_updates_the_cache_in_place(one_chip, slots,
                                                     max_len):
    """At the benchmark cells' shapes the step takes the donated cache and
    writes only the new token's rows into it.  No operation the size of the
    stacked cache runs but those row updates, and inside the layer loop no
    operation the size of one layer's K or V runs at all: attention reads
    each layer's rows where they lie in the stacked cache."""
    compiled, _, cache = _compile_qwen2_step(one_chip, slots, max_len)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    assert mem.temp_size_in_bytes < 1e9, mem.temp_size_in_bytes
    kv = jax.tree.leaves(cache)
    stacked = kv[0].shape
    assert len(kv) == 2 and all(leaf.shape == stacked for leaf in kv)
    layer = _size(stacked[1:])
    comps, entry, flow = _operations(compiled.as_text())
    whole, in_loop = [], []
    for comp in [entry, *flow]:
        for name, (dims, opcode, rest) in comps[comp].items():
            if opcode in ("parameter", "constant", "bitcast",
                          "get-tuple-element"):
                continue                # names a buffer, moves nothing
            if dims == stacked:
                whole.append((name, _update_rows(comps, comp, opcode, rest)))
            elif comp != entry and _size(dims) >= layer:
                in_loop.append((name, opcode))
    row = _size(stacked) // max_len
    assert len(whole) == 2 and all(n == row for _, n in whole), whole
    assert in_loop == []


# the benchmark cells (configuration, slots x max_len), and llama-3.2-vision
# (two groups of four self-attention layers and a cross-attention layer) at
# a small batch: its self-attention cache stacks a group's layers inside
# the group axis
CELLS = {"qwen2-0.5b.batch_prefill": ("qwen2-0.5b", 512, 528),
         "qwen2-0.5b.batch_decode": ("qwen2-0.5b", 256, 1024),
         "olmoe-1b-7b-0924.chat_decode": ("olmoe-1b-7b-0924", 64, 1024),
         "llama-3.2-vision-11b.8x1024": ("llama-3.2-vision-11b", 8, 1024)}


def _cell_config(name):
    if name == "olmoe-1b-7b-0924":
        return _olmoe_0924()
    if name == "llama-3.2-vision-11b":
        return dataclasses.replace(get_config(name), num_layers=10)
    return get_config(name)


@pytest.mark.parametrize("cell", list(CELLS))
def test_decode_attention_kernel_reads_the_stacked_cache(one_chip, cell):
    """At each cell's shape the step's attention is the decode-attention
    kernel, a custom call named under ``attn`` (so ``attn_ms_per_step``
    reads it), taking every layer's cache: the cache is still aliased to the
    output, and no layer's K or V is copied out of it.  The temporaries grow
    by less than half a layer's K when the cache doubles in length, and at
    the benchmark cells' shapes are the f32 logits and less than half a
    layer's K (llama-3.2-vision's step copies a group's self-attention
    weights out of their stack, the size of no cache)."""
    name, slots, max_len = CELLS[cell]
    cfg = _cell_config(name)
    compiled, _, cache = _compile_step(one_chip, cfg, slots, max_len)
    calls = [re.search(r'op_name="([^"]*)"', line).group(1)
             for line in compiled.as_text().splitlines()
             if "tpu_custom_call" in line and "op_name=" in line]
    attn = [c for c in calls if "/attn/" in c]
    assert len(attn) == 1 and "decode_attention" in attn[0], calls
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= _nbytes(cache)
    layer_k = (slots * max_len * cfg.num_kv_heads * cfg.head_dim
               * jnp.dtype(cfg.dtype).itemsize)
    longer = _compile_step(one_chip, cfg, slots, 2 * max_len)[0]
    grown = longer.memory_analysis().temp_size_in_bytes - mem.temp_size_in_bytes
    assert grown < layer_k / 2, (grown, layer_k)
    if name != "llama-3.2-vision-11b":
        logits = slots * cfg.vocab_size * 4
        assert mem.temp_size_in_bytes < logits + layer_k / 2, \
            mem.temp_size_in_bytes
