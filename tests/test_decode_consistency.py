"""Teacher-forced consistency: feeding tokens one-by-one through
serve_step (cache path) must reproduce the training forward's logits at
every position — the strongest correctness check on cache layout, RoPE
offsets, ring buffers, SSM states, and cross-attention caches."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import decode, get_config
from repro.models import params as MP
from repro.models import transformer as TF

# one representative per family mechanic
ARCHS = ["qwen2-0.5b",          # dense GQA + bias + rope
         "gemma2-27b",          # local/global + ring buffer + softcaps
         "starcoder2-7b",       # dense GQA + ungated GELU MLP
         "olmoe-1b-7b",         # MoE
         "rwkv6-7b",            # attention-free state
         "zamba2-7b",           # mamba states + shared attn
         "whisper-large-v3",    # enc-dec with cross cache
         "llama-3.2-vision-11b"]  # vlm cross-attn


def _setup(name, b=2, s=12, seed=0):
    import dataclasses
    cfg = get_config(name).reduced()
    if cfg.num_experts:
        # generous capacity: the full-sequence forward may drop tokens at
        # tight capacity while per-token decode never does (by design) —
        # equalize for the equivalence check
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)
    rng = np.random.default_rng(seed)
    prm = MP.init_params(cfg, seed=seed)
    tokens = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, s)), jnp.int32)
    modality = None
    if cfg.family == "vlm":
        modality = jnp.asarray(
            rng.normal(size=(b, cfg.num_patches, cfg.d_model)), cfg.dtype)
    if cfg.family == "audio":
        modality = jnp.asarray(
            rng.normal(size=(b, cfg.encoder_seq, cfg.d_model)), cfg.dtype)
    return cfg, prm, tokens, modality


@pytest.mark.parametrize("name", ARCHS)
def test_decode_matches_forward(name):
    cfg, prm, tokens, modality = _setup(name)
    b, s = tokens.shape
    full_logits, _ = TF.forward(cfg, prm, tokens, modality=modality)

    cache = decode.init_cache(cfg, prm, b, max_len=s + 4, modality=modality)
    step = jax.jit(lambda p, c, t, pos: decode.serve_step(cfg, p, c, t, pos))
    errs = []
    for i in range(s):
        logits_i, cache = step(prm, cache, tokens[:, i:i + 1],
                               jnp.asarray(i, jnp.int32))
        errs.append(float(jnp.abs(
            logits_i[:, 0] - full_logits[:, i]).max()))
    # positions late in the sequence depend on the whole cache history
    assert max(errs) < 2e-2, f"{name}: max logit divergence {max(errs):.4f}"


def test_gemma_ring_buffer_beyond_window():
    """Decode past the local window: ring buffer must keep exactly the
    last `sliding_window` positions (reduced window = 16)."""
    cfg, prm, tokens, _ = _setup("gemma2-27b", b=1, s=24)
    s = tokens.shape[1]
    assert cfg.sliding_window == 16 < s
    full_logits, _ = TF.forward(cfg, prm, tokens)
    cache = decode.init_cache(cfg, prm, 1, max_len=s)
    step = jax.jit(lambda p, c, t, pos: decode.serve_step(cfg, p, c, t, pos))
    for i in range(s):
        logits_i, cache = step(prm, cache, tokens[:, i:i + 1],
                               jnp.asarray(i, jnp.int32))
    err = float(jnp.abs(logits_i[:, 0] - full_logits[:, -1]).max())
    assert err < 2e-2, f"ring-buffer divergence {err:.4f}"


def test_int8_kv_cache_close_to_bf16():
    """Quantized KV cache must track the exact cache within int8 error."""
    import dataclasses
    cfg, prm, tokens, _ = _setup("qwen2-0.5b", b=2, s=12)
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    b, s = tokens.shape
    full, _ = TF.forward(cfg, prm, tokens)
    cache = decode.init_cache(cfg8, prm, b, max_len=s + 4)
    assert cache["lyr"]["k"].dtype == jnp.int8
    step = jax.jit(lambda p, c, t, pos: decode.serve_step(cfg8, p, c, t, pos))
    errs = []
    for i in range(s):
        li, cache = step(prm, cache, tokens[:, i:i + 1],
                         jnp.asarray(i, jnp.int32))
        errs.append(float(jnp.abs(li[:, 0] - full[:, i]).max()))
    assert max(errs) < 0.35, f"int8 cache divergence {max(errs):.3f}"


def test_int8_cache_halves_bytes():
    import dataclasses
    cfg = get_config("qwen2-7b")
    cfg8 = dataclasses.replace(cfg, kv_cache_dtype="int8")
    def nbytes(c):
        specs = decode.cache_specs(c, 8, 1024)
        return sum(np.prod(s.shape) * s.dtype.itemsize
                   for s in jax.tree.leaves(specs))
    assert nbytes(cfg8) < 0.6 * nbytes(cfg)
