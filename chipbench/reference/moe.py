"""OLMoE-style decoder as the repo's ``moe`` family states it: pre-RMSNorm,
half-split RoPE, multi-head attention without bias or QK-norm, a softmax
router whose top-k weights are renormalised to sum to 1, SiLU-gated experts
(every token through every expert, weighted by its gate, which is zero
outside its top k), untied output head.  The experts are taken in chunks to
bound the memory of the (tokens, experts, width) intermediate."""
import jax
import jax.numpy as jnp

from .common import attention, f32, rms
from .dense import head  # noqa: F401  (same final norm and head)

EXPERT_CHUNK = 8


def moe_ffn(s, p, y, mm):
    n, length, d = y.shape
    e, k = s["num_experts"], s["num_experts_per_tok"]
    t = y.reshape(n * length, d)
    probs = jax.nn.softmax(mm(t, p["router"]), axis=-1)
    top_p, top_i = jax.lax.top_k(probs, k)
    top_p = top_p / top_p.sum(-1, keepdims=True)
    gates = (jax.nn.one_hot(top_i, e) * top_p[..., None]).sum(1)       # (T, E)
    c = min(EXPERT_CHUNK, e)

    def chunk(acc, i):
        sl = lambda w: jax.lax.dynamic_slice_in_dim(w, i * c, c, 0)    # noqa: E731
        wg, w1, w2 = f32(sl(p["wg"])), f32(sl(p["w1"])), f32(sl(p["w2"]))
        g = jax.lax.dynamic_slice_in_dim(gates, i * c, c, 1)           # (T, c)
        hid = jax.nn.silu(mm(t[None], wg)) * mm(t[None], w1)           # (c, T, F)
        out = mm(hid * g.T[..., None], w2)                             # (c, T, d)
        return acc + out.sum(0), None

    acc, _ = jax.lax.scan(chunk, jnp.zeros_like(t), jnp.arange(e // c))
    return acc.reshape(n, length, d)


def layer(s, p, x, mm):
    eps = s["rms_norm_eps"]
    x = x + attention(s, p["attn"], rms(x, p["ln1"]["w"], eps), mm)
    return x + moe_ffn(s, p["moe"], rms(x, p["ln2"]["w"], eps), mm)
