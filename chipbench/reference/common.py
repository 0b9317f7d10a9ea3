"""Pieces the family references share: RMSNorm, half-split RoPE, causal
attention, and the two ways of multiplying (exact fp32, and fp8 for the
control)."""
import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def f32(a):
    return jnp.asarray(a, jnp.float32)


def mm_fp32(a, b):
    """Matrix product in float32 at full precision."""
    return jnp.matmul(f32(a), f32(b), precision=HIGHEST)


def _fp8(x, axis):
    """Round to float8 e4m3 with an absmax scale along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def mm_fp8(a, b):
    """The control: both operands rounded to float8 e4m3 (rows of ``a``,
    columns of ``b`` scaled apart), then multiplied."""
    return jnp.matmul(_fp8(f32(a), -1), _fp8(f32(b), -2), precision=HIGHEST)


MATMULS = {"fp32": mm_fp32, "fp8": mm_fp8}


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(w)


def rope(x, theta):
    """x: (n, heads, L, dh), positions 0..L-1; rotates the two halves."""
    length, dh = x.shape[-2], x.shape[-1]
    ang = (np.arange(length)[:, None]
           / theta ** (np.arange(0, dh, 2) / dh)).astype(np.float32)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(s, p, y, mm):
    """Causal self-attention of y (n, L, d) with RoPE and grouped K/V heads;
    returns the output projection (n, L, d)."""
    n, length, _ = y.shape
    h, hkv, dh = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]

    def heads(z, k):
        return z.reshape(n, length, k, dh).transpose(0, 2, 1, 3)

    def proj(w, b):
        z = mm(y, p[w])
        return z + f32(p[b]) if s["qkv_bias"] else z

    q = rope(heads(proj("wq", "bq"), h), s["rope_theta"])
    k = jnp.repeat(rope(heads(proj("wk", "bk"), hkv), s["rope_theta"]), h // hkv, 1)
    v = jnp.repeat(heads(proj("wv", "bv"), hkv), h // hkv, 1)
    scores = mm(q, k.transpose(0, 1, 3, 2)) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((length, length), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm(att, v).transpose(0, 2, 1, 3).reshape(n, length, h * dh)
    return mm(out, p["wo"])


def silu_ffn(y, wg, wi, wo, mm):
    return mm(jax.nn.silu(mm(y, wg)) * mm(y, wi), wo)
