"""Compile the main-path kernels and the full-width serve step for one TPU
v5e chip, described rather than attached (nothing runs).

The TPU compiler refuses what the Pallas interpreter accepts: blocks not
aligned to the tiling, primitives the kernel language lacks, programs that
do not fit the device.  This is the only test file that describes the chip;
the topology is described inside a fixture, so every xdist worker collects
the same tests and only the worker that runs this file loads the TPU
library.  The persistent compilation cache is off around these compiles: an
entry written for a described chip cannot be read back without one.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels import banked_matmul as BM
from repro.kernels import flash_attention as FA
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


def test_qwen2_serve_step_fits_one_chip(one_chip):
    cfg = get_config("qwen2-0.5b")
    slots, max_len = 4, 4096
    params = MP.param_specs(cfg)
    cache = jax.eval_shape(
        functools.partial(decode.init_cache, cfg, batch=slots,
                          max_len=max_len), params)

    def place(tree):
        return jax.tree.map(lambda s: _spec(one_chip, s.shape, s.dtype),
                            tree)

    compiled = decode.make_serve_step(cfg).lower(
        place(params), place(cache), _spec(one_chip, (slots, 1), jnp.int32),
        _spec(one_chip, (), jnp.int32)).compile()
    mem = compiled.memory_analysis()
    param_bytes = sum(s.size * s.dtype.itemsize
                      for s in jax.tree.leaves(params))
    assert mem.argument_size_in_bytes >= param_bytes
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert total < V5E_HBM_BYTES, total
