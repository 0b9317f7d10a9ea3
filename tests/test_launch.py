"""Launch layer: shapes, sharding rules, cell skip logic, model flops
(host-mesh scale — the 512-device path is exercised by dryrun itself)."""
import pathlib
from types import SimpleNamespace

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.launch import shapes as SH
from repro.launch.compile_cache import use_compile_cache
from repro.launch.dryrun import model_flops
from repro.launch.shapes import SHAPES, cell_supported
from repro.models import all_names, decode, get_config
from repro.models import params as MP
from repro.sharding.rules import (ShardingStrategy, param_pspecs,
                                  sanitize_spec)


@pytest.fixture(scope="module")
def mesh():
    return jax.make_mesh((1, 1), ("data", "model"))


class TestSkips:
    def test_long_context_skips(self):
        runnable = {n: cell_supported(get_config(n), "long_500k")[0]
                    for n in all_names()}
        assert runnable == {
            "gemma2-27b": False, "granite-moe-1b-a400m": False,
            "llama-3.2-vision-11b": False, "olmoe-1b-7b": False,
            "qwen2-0.5b": False, "qwen2-7b": False, "rwkv6-7b": True,
            "starcoder2-7b": False, "whisper-large-v3": False,
            "zamba2-7b": True,
        }

    def test_other_shapes_all_supported(self):
        for n in all_names():
            for s in ("train_4k", "prefill_32k", "decode_32k"):
                assert cell_supported(get_config(n), s)[0]


class TestParamSpecs:
    @pytest.mark.parametrize("arch", all_names())
    def test_pspec_ranks_match_shapes(self, arch):
        cfg = get_config(arch)
        shapes = MP.param_shapes(cfg)
        pspecs = param_pspecs(cfg, ShardingStrategy())
        flat_s = jax.tree.leaves(shapes, is_leaf=MP._is_leaf)
        flat_p = jax.tree.leaves(pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
        assert len(flat_s) == len(flat_p)
        for lf, spec in zip(flat_s, flat_p):
            assert len(spec) <= len(lf[0]), (lf, spec)

    def test_sanitize_drops_uneven(self, mesh):
        big = jax.make_mesh((1,), ("model",)) if False else mesh
        spec = sanitize_spec(P("model", "data"), (51866, 1280), mesh)
        assert spec == P("model", "data")  # 1-device axes always divide

    def test_init_scales_by_fan_in(self):
        # stacked leaves lead with the group axis; the scale must not see it
        cfg = get_config("qwen2-0.5b").reduced()
        lyr = MP.init_params(cfg, seed=0)["blocks"]["lyr"]
        for w, fan_in in ((lyr["attn"]["wq"], cfg.d_model),
                          (lyr["mlp"]["wo"], cfg.d_ff)):
            assert w.shape[0] == cfg.num_groups
            np.testing.assert_allclose(float(np.std(np.asarray(w))),
                                       fan_in ** -0.5, rtol=0.1)

    def test_param_count_magnitudes(self):
        # sanity vs published sizes (within 25%)
        expect = {"qwen2-0.5b": 0.49e9, "qwen2-7b": 7.6e9,
                  "gemma2-27b": 27e9, "olmoe-1b-7b": 6.9e9,
                  "starcoder2-7b": 7.2e9, "rwkv6-7b": 7.6e9}
        for name, n in expect.items():
            got = get_config(name).param_count()
            assert 0.7 * n < got < 1.35 * n, (name, got, n)

    def test_olmoe_active_params_about_1b(self):
        cfg = get_config("olmoe-1b-7b")
        assert 0.9e9 < cfg.active_param_count() < 1.7e9


class TestModelFlops:
    def test_train_flops_6nd_regime(self):
        cfg = get_config("qwen2-7b")
        f = model_flops(cfg, SHAPES["train_4k"])
        n = cfg.param_count()
        tokens = 256 * 4096
        assert f > 6 * 0.8 * n * tokens          # at least ~6ND

    def test_decode_much_smaller_than_prefill(self):
        cfg = get_config("qwen2-7b")
        assert (model_flops(cfg, SHAPES["decode_32k"])
                < 0.01 * model_flops(cfg, SHAPES["prefill_32k"]))

    def test_window_reduces_attn_flops(self):
        g = get_config("gemma2-27b")
        full = model_flops(g, SHAPES["prefill_32k"])
        # a hypothetical all-global gemma would have more attn flops
        import dataclasses
        allglobal = dataclasses.replace(g, local_global=False,
                                        sliding_window=0, num_layers=46)
        assert model_flops(allglobal, SHAPES["prefill_32k"]) > full


class TestInputSpecs:
    @pytest.mark.parametrize("shape", ["train_4k", "prefill_32k",
                                       "decode_32k"])
    def test_specs_build_for_every_arch(self, mesh, shape):
        st = ShardingStrategy()
        for arch in all_names():
            cfg = get_config(arch)
            specs = SH.input_specs(cfg, shape, mesh, st)
            leaves = jax.tree.leaves(specs)
            assert leaves and all(hasattr(l, "shape") for l in leaves)

    @pytest.mark.parametrize("arch", all_names())
    def test_decode_cache_shards_heads_not_positions(self, arch):
        """On the production mesh the tensor-parallel axis splits a K/V
        cache leaf along its heads (the Hkv * dh lanes of a self-attention
        row), never along its positions."""
        mesh = SimpleNamespace(axis_names=("data", "model"),
                               devices=np.empty((16, 16)))
        cfg = get_config(arch)
        batch = SH.SHAPES["decode_32k"].global_batch
        specs = jax.tree_util.tree_flatten_with_path(
            decode.cache_specs(cfg, batch, 64))[0]
        pspecs = jax.tree.leaves(SH.cache_pspecs(cfg, batch, mesh,
                                                 ShardingStrategy()),
                                 is_leaf=lambda x: isinstance(x, P))
        heads_split = cfg.num_kv_heads % 16 == 0
        kv = 0
        for (path, sd), spec in zip(specs, pspecs):
            keys = [k.key for k in path]
            if keys[-1] not in ("k", "v", "k_scale", "v_scale"):
                continue
            kv += 1
            axes = decode.CROSS_AXES if "cross" in keys else decode.KV_AXES
            spec = sanitize_spec(spec, sd.shape, mesh)
            got = dict(zip(axes, tuple(spec)[len(sd.shape) - len(axes):]))
            assert got["position"] is None, (keys, spec)
            assert got["heads"] == ("model" if heads_split else None), \
                (keys, spec)
            assert got["batch"] in ("data", ("data",)), (keys, spec)
        assert kv or cfg.family == "ssm"


class TestCompileCache:
    def test_env_dir_is_left_to_jax(self, monkeypatch):
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
        before = jax.config.jax_compilation_cache_dir
        assert use_compile_cache() == "/elsewhere/cache"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_dir_inside_checkout(self, monkeypatch):
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        before = jax.config.jax_compilation_cache_dir
        try:
            got = use_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
            assert use_compile_cache() == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        root = pathlib.Path(__file__).resolve().parents[1]
        assert pathlib.Path(got) == root / ".jax_cache"
        assert ".jax_cache/" in (root / ".gitignore").read_text().split()
