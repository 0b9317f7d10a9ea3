"""OLMoE-1B-7B-0924 as published (arXiv:2409.02060; the Hugging Face
``modeling_olmoe.py``): pre-RMSNorm; QK-norm, an RMSNorm over the whole q
projection and over the whole k projection, before the head split and
half-split RoPE; multi-head attention without bias; a softmax router whose
top-k probabilities are kept as they are (``norm_topk_prob`` false);
SiLU-gated experts; untied output head.

Every token goes through every expert, weighted by its gate, which is zero
outside its top k: dropless by construction.  The experts are taken in
chunks to bound the memory of the (tokens, experts, width) intermediate.

The step is counted by ``counts.decoder_step_*`` (the QK-norm gains, 2 x
2048 a layer, are left out of its bytes).  ``expert_flops`` and
``expert_bytes`` count one layer's grouped expert matmul for the roofline
reader (``chipbench/metrics/expert_roofline_share.py``).
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench.counts import BF16, experts_touched

from .common import f32, rms, rope
from .dense import head  # noqa: F401  (same final norm and untied head)

EXPERT_CHUNK = 8


def attention(s, p, y, mm):
    """Causal self-attention of y (n, L, d) with QK-norm and RoPE; returns
    the output projection (n, L, d)."""
    n, length, _ = y.shape
    h, hkv, dh = s["num_attention_heads"], s["num_key_value_heads"], s["head_dim"]
    eps = s["rms_norm_eps"]

    def heads(z, k):
        return z.reshape(n, length, k, dh).transpose(0, 2, 1, 3)

    q = rope(heads(rms(mm(y, p["wq"]), p["q_norm"], eps), h), s["rope_theta"])
    k = rope(heads(rms(mm(y, p["wk"]), p["k_norm"], eps), hkv), s["rope_theta"])
    k = jnp.repeat(k, h // hkv, 1)
    v = jnp.repeat(heads(mm(y, p["wv"]), hkv), h // hkv, 1)
    scores = mm(q, k.transpose(0, 1, 3, 2)) / np.sqrt(dh)
    causal = jnp.tril(jnp.ones((length, length), bool))
    att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
    out = mm(att, v).transpose(0, 2, 1, 3).reshape(n, length, h * dh)
    return mm(out, p["wo"])


def gates(s, p, t, mm):
    """(T, E) weight of each expert for each row of t (T, d): its softmax
    probability where it is among the row's top k, else 0."""
    probs = jax.nn.softmax(mm(t, p["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, s["num_experts_per_tok"])
    return (jax.nn.one_hot(top_i, s["num_experts"]) * top_p[..., None]).sum(1)


def moe_ffn(s, p, y, mm):
    n, length, d = y.shape
    e = s["num_experts"]
    t = y.reshape(n * length, d)
    g_all = gates(s, p, t, mm)
    c = min(EXPERT_CHUNK, e)

    def chunk(acc, i):
        sl = lambda w: jax.lax.dynamic_slice_in_dim(w, i * c, c, 0)    # noqa: E731
        wg, w1, w2 = f32(sl(p["wg"])), f32(sl(p["w1"])), f32(sl(p["w2"]))
        g = jax.lax.dynamic_slice_in_dim(g_all, i * c, c, 1)           # (T, c)
        hid = jax.nn.silu(mm(t[None], wg)) * mm(t[None], w1)           # (c, T, F)
        out = mm(hid * g.T[..., None], w2)                             # (c, T, d)
        return acc + out.sum(0), None

    acc, _ = jax.lax.scan(chunk, jnp.zeros_like(t), jnp.arange(e // c))
    return acc.reshape(n, length, d)


def layer(s, p, x, mm):
    eps = s["rms_norm_eps"]
    x = x + attention(s, p["attn"], rms(x, p["ln1"]["w"], eps), mm)
    return x + moe_ffn(s, p["moe"], rms(x, p["ln2"]["w"], eps), mm)


def expert_flops(s, rows):
    """FLOPs of one layer's grouped expert matmul over ``rows`` token-expert
    rows: the gate, up and down projections of each row's expert."""
    return 2 * rows * 3 * s["hidden_size"] * s["intermediate_size"]


def expert_bytes(s, n_occ):
    """HBM bytes one layer's grouped expert matmul needs for ``n_occ``
    tokens: the weights of the experts they touch (expected under uniform
    routing), each read once, and the rows read and written (each token's k
    rows in, k rows out)."""
    k, d = s["num_experts_per_tok"], s["hidden_size"]
    weights = experts_touched(s, n_occ) * 3 * d * s["intermediate_size"]
    return BF16 * (weights + 2 * n_occ * k * d)
