"""Pallas kernel sweeps: shapes x dtypes vs the pure-jnp oracles
(interpret=True executes the kernel bodies on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.banked_matmul import banked_matmul, derive_block

_TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
        jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _tol(dt):
    return _TOL[dt]


class TestBankedMatmul:
    @pytest.mark.parametrize("m,k,n", [
        (8, 16, 8), (32, 64, 48), (48, 64, 40), (1, 64, 48),
        (17, 33, 9),                       # ragged -> padding path
        (128, 128, 128),
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_shapes_dtypes(self, m, k, n, dtype):
        rng = np.random.default_rng(m * 1000 + k * 10 + n)
        a = jnp.asarray(rng.normal(size=(m, k)), dtype)
        b = jnp.asarray(rng.normal(size=(k, n)), dtype)
        out = ops.matmul(a, b, banks=(2, 2, 2))
        expect = ref.matmul_ref(a, b)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   **_tol(dtype))

    @pytest.mark.parametrize("banks", [(1, 1, 1), (2, 2, 2), (4, 2, 1),
                                       (1, 4, 4)])
    def test_bank_partitions_agree(self, banks):
        rng = np.random.default_rng(7)
        a = jnp.asarray(rng.normal(size=(64, 96)), jnp.float32)
        b = jnp.asarray(rng.normal(size=(96, 64)), jnp.float32)
        out = ops.matmul(a, b, banks=banks)
        np.testing.assert_allclose(out, ref.matmul_ref(a, b), rtol=2e-5,
                                   atol=2e-5)

    def test_derive_block_covers_dims(self):
        bm, bn, bk = derive_block(256, 512, 1024, (2, 4, 8))
        assert bm * 2 >= 256 and bn * 4 >= 512 and bk * 8 >= 1024
        assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0

    def test_f32_accumulation_for_bf16(self):
        """bf16 inputs accumulate in f32: K=512 ones must be exact."""
        a = jnp.ones((8, 512), jnp.bfloat16)
        b = jnp.ones((512, 8), jnp.bfloat16)
        out = ops.matmul(a, b, out_dtype=jnp.float32)
        np.testing.assert_array_equal(np.asarray(out), 512.0)


class TestFlashAttention:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (4, 2), (8, 1)])
    @pytest.mark.parametrize("causal", [True, False])
    def test_gqa_and_masking(self, hq, hkv, causal):
        rng = np.random.default_rng(hq * 10 + hkv)
        q = jnp.asarray(rng.normal(size=(2, hq, 64, 16)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(2, hkv, 64, 16)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(2, hkv, 64, 16)), jnp.float32)
        out = ops.attention(q, k, v, causal=causal, block_q=16, block_k=16)
        expect = ref.attention_ref(q, k, v, causal=causal)
        np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    @pytest.mark.parametrize("s,bq,bk", [(32, 8, 8), (64, 32, 16),
                                         (128, 128, 128)])
    def test_block_shapes(self, dtype, s, bq, bk):
        rng = np.random.default_rng(s)
        q = jnp.asarray(rng.normal(size=(1, 2, s, 8)), dtype)
        k = jnp.asarray(rng.normal(size=(1, 2, s, 8)), dtype)
        v = jnp.asarray(rng.normal(size=(1, 2, s, 8)), dtype)
        out = ops.attention(q, k, v, causal=True, block_q=bq, block_k=bk)
        expect = ref.attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   **_tol(dtype))

    def test_long_context_numerics(self):
        """Online softmax must be stable with large score magnitudes."""
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(1, 1, 64, 8)) * 8, jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 64, 8)) * 8, jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, 64, 8)), jnp.float32)
        out = ops.attention(q, k, v, causal=True, block_q=16, block_k=16)
        assert np.isfinite(np.asarray(out)).all()
        np.testing.assert_allclose(out, ref.attention_ref(q, k, v),
                                   rtol=1e-4, atol=1e-4)


class TestDecayScan:
    @pytest.mark.parametrize("mode", ["inclusive", "bonus"])
    @pytest.mark.parametrize("s,chunk", [(32, 8), (64, 16), (64, 64)])
    def test_modes_and_chunks(self, mode, s, chunk):
        rng = np.random.default_rng(s + chunk)
        q = jnp.asarray(rng.normal(size=(1, 2, s, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 2, s, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 2, s, 12)), jnp.float32)
        w = jnp.asarray(-np.abs(rng.normal(size=(1, 2, s, 8))) * 0.3,
                        jnp.float32)
        u = jnp.asarray(rng.normal(size=(2, 8)), jnp.float32)
        out = ops.decay_scan(q, k, v, w, u=u, chunk=chunk, diag_mode=mode)
        expect = ref.ssm_scan_ref(q, k, v, w, u=u, diag_mode=mode)
        np.testing.assert_allclose(out, expect, rtol=1e-4, atol=1e-4)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        rng = np.random.default_rng(5)
        q = jnp.asarray(rng.normal(size=(2, 2, 32, 8)), dtype)
        k = jnp.asarray(rng.normal(size=(2, 2, 32, 8)), dtype)
        v = jnp.asarray(rng.normal(size=(2, 2, 32, 8)), dtype)
        w = jnp.asarray(-np.abs(rng.normal(size=(2, 2, 32, 8))) * 0.2, dtype)
        out = ops.decay_scan(q, k, v, w, chunk=8)
        expect = ref.ssm_scan_ref(q, k, v, w)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   **_tol(dtype))

    def test_chunking_invariance(self):
        """Different chunk sizes must give identical results."""
        rng = np.random.default_rng(9)
        q = jnp.asarray(rng.normal(size=(1, 1, 64, 8)), jnp.float32)
        k = jnp.asarray(rng.normal(size=(1, 1, 64, 8)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(1, 1, 64, 8)), jnp.float32)
        w = jnp.asarray(-np.abs(rng.normal(size=(1, 1, 64, 8))), jnp.float32)
        o1 = ops.decay_scan(q, k, v, w, chunk=8)
        o2 = ops.decay_scan(q, k, v, w, chunk=32)
        np.testing.assert_allclose(o1, o2, rtol=1e-5, atol=1e-5)

    def test_state_carries_across_chunks(self):
        """An impulse at t=0 must influence outputs in later chunks."""
        s, dk = 32, 4
        q = jnp.ones((1, 1, s, dk), jnp.float32)
        k = jnp.zeros((1, 1, s, dk), jnp.float32).at[0, 0, 0].set(1.0)
        v = jnp.zeros((1, 1, s, 4), jnp.float32).at[0, 0, 0].set(1.0)
        w = jnp.full((1, 1, s, dk), -0.1, jnp.float32)
        out = ops.decay_scan(q, k, v, w, chunk=8)
        assert float(out[0, 0, -1, 0]) > 0  # decayed impulse still visible
        np.testing.assert_allclose(out, ref.ssm_scan_ref(q, k, v, w),
                                   rtol=1e-5, atol=1e-5)


class TestBankedConv2d:
    @pytest.mark.parametrize("cin,cout,h,w,kh,kw", [
        (3, 8, 16, 12, 5, 5), (2, 4, 9, 9, 3, 3), (1, 2, 7, 5, 3, 2),
        (3, 8, 80, 60, 5, 5),                 # the paper's CNN first layer
    ])
    @pytest.mark.parametrize("banks", [(1, 1), (2, 2), (4, 2)])
    def test_shapes_and_banks(self, cin, cout, h, w, kh, kw, banks):
        from repro.kernels import ops as kops
        rng = np.random.default_rng(cin * 100 + h)
        x = jnp.asarray(rng.normal(size=(cin, h, w)), jnp.float32)
        wt = jnp.asarray(rng.normal(size=(cout, cin, kh, kw)), jnp.float32)
        out = kops.conv2d(x, wt, banks=banks)
        expect = ref.conv2d_ref(x, wt)
        assert out.shape == expect.shape
        np.testing.assert_allclose(out, expect, rtol=2e-5, atol=2e-5)

    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_dtypes(self, dtype):
        from repro.kernels import ops as kops
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.normal(size=(2, 10, 10)), dtype)
        wt = jnp.asarray(rng.normal(size=(4, 2, 3, 3)), dtype)
        out = kops.conv2d(x, wt, banks=(2, 2))
        expect = ref.conv2d_ref(x, wt)
        np.testing.assert_allclose(np.asarray(out, np.float32),
                                   np.asarray(expect, np.float32),
                                   **_TOL[dtype])


class TestBackend:
    @pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                                   ("tpu", False)])
    def test_interpreter_only_on_cpu(self, monkeypatch, backend, interpret):
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops._interpret() is interpret

    def test_other_backend_raises(self, monkeypatch):
        monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
        with pytest.raises(RuntimeError, match="gpu"):
            ops._interpret()
