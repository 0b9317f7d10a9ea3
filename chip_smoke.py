"""Bring-up smoke: serve qwen2-0.5b at its published widths on one TPU.

    python chip_smoke.py

Drives the continuous-batching serving path once, in this one process:
``repro.launch.serve.Engine`` over ``decode.make_serve_step``, fed by the
same ``synth_arrivals`` + ``ReplayDriver`` calls that ``serve.main`` makes.
Weights are random, drawn from a seed.  Resilience is off, so any failed
step raises.  Four slots serve eight requests of 16 prompt and 16 generated
tokens each against a 4096-position cache.

Correctness: the chip's logits for request 0 at every generated position
are compared with a plain fp32 forward pass, teacher-forced on the tokens
the chip produced, that runs on the host CPU under
``default_matmul_precision("highest")``.  The error is printed beside its
bf16 tolerance.

The lines before the last are smoke output, not benchmark numbers.  The
last line is one JSON object naming the device.  Without a TPU the script
exits non-zero before serving and prints no such line.
"""
import json
import os
import pathlib
import sys
import time

# the fp32 reference needs the host CPU backend beside the TPU
_platforms = os.environ.get("JAX_PLATFORMS", "")
if _platforms and "cpu" not in _platforms.split(","):
    os.environ["JAX_PLATFORMS"] = _platforms + ",cpu"

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

ARCH = "qwen2-0.5b"
SEED = 0
SLOTS, REQUESTS, PROMPT, GEN = 4, 8, 16, 16
MAX_LEN = 4096
# largest relative L2 error of one position's logits row, bf16 serving path
# against the fp32 reference; a misplaced position or a wrong layer gives
# errors of order 1
LOGITS_RTOL = 0.05


def log(msg: str) -> None:
    print(f"[smoke] {msg}", flush=True)


def reference_logits(cfg, params, tokens: np.ndarray) -> jax.Array:
    """Plain fp32 forward of one sequence through a Qwen2-style decoder
    (pre-RMSNorm, half-split RoPE, GQA with QKV bias, SiLU-gated MLP, tied
    head), written apart from ``repro.models`` so that it shares no code
    with the path under test.  Runs wherever ``params`` live.  Returns
    (S, V) logits."""
    assert (cfg.family == "dense" and cfg.qkv_bias and cfg.tie_embeddings
            and cfg.act == "silu" and not cfg.local_global
            and not cfg.logit_softcap and not cfg.attn_softcap), cfg
    h, hkv, dh, s = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim, len(tokens)

    def f32(a):
        return jnp.asarray(a, jnp.float32)

    def rms(x, w):
        return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True)
                            + cfg.norm_eps) * f32(w)

    ang = np.arange(s)[:, None] / cfg.rope_theta ** (np.arange(0, dh, 2) / dh)
    cos, sin = f32(np.cos(ang)), f32(np.sin(ang))

    def rope(x):                            # (heads, S, dh)
        x1, x2 = x[..., :dh // 2], x[..., dh // 2:]
        return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)

    def heads(x, n):
        return x.reshape(s, n, dh).transpose(1, 0, 2)

    causal = jnp.tril(jnp.ones((s, s), bool))
    emb = f32(params["embed"])
    x = emb[tokens]
    for layer in range(cfg.num_layers):
        p = jax.tree.map(lambda a: f32(a[layer]), params["blocks"]["lyr"])
        a, m = p["attn"], p["mlp"]
        y = rms(x, p["ln1"]["w"])
        q = rope(heads(y @ a["wq"] + a["bq"], h))
        k = jnp.repeat(rope(heads(y @ a["wk"] + a["bk"], hkv)), h // hkv, 0)
        v = jnp.repeat(heads(y @ a["wv"] + a["bv"], hkv), h // hkv, 0)
        scores = q @ k.transpose(0, 2, 1) / np.sqrt(dh)
        att = jax.nn.softmax(jnp.where(causal, scores, -jnp.inf), axis=-1)
        x = x + (att @ v).transpose(1, 0, 2).reshape(s, h * dh) @ a["wo"]
        y = rms(x, p["ln2"]["w"])
        x = x + (jax.nn.silu(y @ m["wg"]) * (y @ m["wi"])) @ m["wo"]
    return rms(x, params["final_norm"]["w"]) @ emb.T


def serve_and_check(cfg) -> dict:
    """Serve the smoke traffic through the engine, compare request 0's
    logits with the fp32 reference on the host CPU, and return what was
    seen.  Raises if a request did not finish."""
    from repro.launch import serve
    from repro.models import params as MP
    from repro.obs.spans import FINISHED

    t0 = time.perf_counter()
    params = MP.init_params(cfg, seed=SEED)
    jax.block_until_ready(params)
    out = {"init_s": time.perf_counter() - t0,
           "params": sum(x.size for x in jax.tree.leaves(params)),
           "param_bytes": sum(x.nbytes for x in jax.tree.leaves(params))}

    eng = serve.Engine(cfg, params, SLOTS, MAX_LEN)
    step = eng._step
    slot0_logits = []       # slot 0's logits row at every engine step

    def recording_step(*args):
        logits, cache = step(*args)
        slot0_logits.append(logits[0, -1])
        return logits, cache

    eng._step = recording_step
    drv = serve.ReplayDriver(eng, serve.synth_arrivals(
        cfg, SEED, REQUESTS, 0.0, PROMPT, GEN))
    t0 = time.perf_counter()
    drv.tick()
    out["first_step_s"] = time.perf_counter() - t0
    if eng.slots[0] is None or eng.slots[0].rid != 0:
        raise RuntimeError("request 0 was not admitted to slot 0 at step 0")
    t0 = time.perf_counter()
    while drv.active:
        drv.tick()
    out["rest_s"] = time.perf_counter() - t0
    finished = [r for r in eng.done if r.reason == FINISHED]
    out.update(steps=eng.steps, finished=len(finished),
               tokens=sum(len(r.out) for r in eng.done))
    if len(finished) != REQUESTS:
        raise RuntimeError(f"{len(finished)}/{REQUESTS} requests finished: "
                           f"{sorted({r.reason for r in eng.done})}")

    # request 0 held slot 0 from step 0: step PROMPT-1+j predicted out[j]
    r0 = next(r for r in eng.done if r.rid == 0)
    chip = np.stack([np.asarray(x, np.float32)
                     for x in slot0_logits[PROMPT - 1:PROMPT - 1 + GEN]])
    if not (chip.argmax(-1) == np.asarray(r0.out)).all():
        raise RuntimeError("recorded logits do not match the engine's tokens")
    tokens = np.concatenate([r0.prompt, np.asarray(r0.out[:-1], np.int32)])
    cpu = jax.devices("cpu")[0]
    t0 = time.perf_counter()
    with jax.default_device(cpu), jax.default_matmul_precision("highest"):
        ref = np.asarray(reference_logits(cfg, jax.device_put(params, cpu),
                                          tokens)[PROMPT - 1:])
    out["ref_s"] = time.perf_counter() - t0
    diff = chip - ref
    out["rel_err"] = float((np.linalg.norm(diff, axis=-1)
                            / np.linalg.norm(ref, axis=-1)).max())
    out["abs_err"] = float(np.abs(diff).max())
    out["ref_absmax"] = float(np.abs(ref).max())
    return out


def main() -> int:
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is on "
              f"{dev.platform!r}); nothing was served", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import get_config

    log(f"compile cache: {use_compile_cache()}")
    count = len(jax.devices())
    log(f"device: {dev.device_kind} ({dev.platform}), {count} device(s)")
    cfg = get_config(ARCH)
    log(f"model: {cfg.name} at published widths: {cfg.num_layers} layers, "
        f"d_model {cfg.d_model}, heads {cfg.num_heads}/{cfg.num_kv_heads}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, {cfg.dtype}")
    r = serve_and_check(cfg)
    log(f"{r['params']} parameters, {r['param_bytes']} bytes; "
        f"host init of the seeded weights {r['init_s']:.2f} s")
    log(f"first engine step, compile included: {r['first_step_s']:.2f} s")
    log(f"served {r['finished']}/{REQUESTS} requests, {r['tokens']} tokens "
        f"in {r['steps']} engine steps ({SLOTS} slots, cache {MAX_LEN}); "
        f"steps after the first took {r['rest_s']:.2f} s")
    log(f"request 0 logits vs fp32 reference on the host CPU "
        f"({GEN} positions, {r['ref_s']:.2f} s): max relative L2 error "
        f"{r['rel_err']:.6f} (tolerance {LOGITS_RTOL}), max abs error "
        f"{r['abs_err']:.6f} (max |logit| {r['ref_absmax']:.4f})")
    log("the numbers above are a bring-up smoke, not benchmark numbers")
    if r["rel_err"] > LOGITS_RTOL:
        print(f"chip_smoke: logits error {r['rel_err']} exceeds "
              f"{LOGITS_RTOL}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind, "count": count}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
