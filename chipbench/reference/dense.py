"""Qwen2-style dense decoder: pre-RMSNorm, half-split RoPE, GQA with QKV
bias, SiLU-gated MLP, output head tied to the embedding."""
from .common import attention, f32, rms, silu_ffn


def layer(s, p, x, mm):
    eps = s["rms_norm_eps"]
    x = x + attention(s, p["attn"], rms(x, p["ln1"]["w"], eps), mm)
    m = p["mlp"]
    return x + silu_ffn(rms(x, p["ln2"]["w"], eps), m["wg"], m["wi"], m["wo"], mm)


def head(s, params, x, mm):
    w = params["embed"].T if s["tie_word_embeddings"] else params["lm_head"]
    return mm(rms(x, params["final_norm"]["w"], s["rms_norm_eps"]), f32(w))
