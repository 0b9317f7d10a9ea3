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


def _compile_qwen2_step(sharding, slots, max_len):
    """(compiled step, param specs, cache specs) of the full-width qwen2-0.5b
    serve step for ``slots`` x ``max_len``."""
    cfg = get_config("qwen2-0.5b")
    params = MP.param_specs(cfg)
    cache = jax.eval_shape(
        functools.partial(decode.init_cache, cfg, batch=slots,
                          max_len=max_len), params)

    def place(tree):
        return jax.tree.map(lambda s: _spec(sharding, s.shape, s.dtype),
                            tree)

    compiled = decode.make_serve_step(cfg).lower(
        place(params), place(cache), _spec(sharding, (slots, 1), jnp.int32),
        _spec(sharding, (), jnp.int32)).compile()
    return compiled, params, cache


def test_olmoe_0924_serve_step_fits_one_chip(one_chip, monkeypatch):
    """The olmoe cell's step (8 layers at published widths, QK-norm, plain
    gates, grouped experts) for 64 x 1024: weights and donated cache on one
    chip, the expert matmuls as kernels reading the stacked weights."""
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = dataclasses.replace(get_config("olmoe-1b-7b"), num_layers=8, norm_eps=1e-5,
                              qk_norm=True, moe_norm_topk_prob=False,
                              moe_dispatch="grouped")
    params = MP.param_specs(cfg)
    cache = jax.eval_shape(functools.partial(decode.init_cache, cfg, batch=64,
                                             max_len=1024), params)
    place = functools.partial(jax.tree.map, lambda s: _spec(one_chip, s.shape, s.dtype))
    compiled = decode.make_serve_step(cfg).lower(
        place(params), place(cache), _spec(one_chip, (64, 1), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    assert compiled.as_text().count("tpu_custom_call") >= 3
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
