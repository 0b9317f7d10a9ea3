"""Correctness of served tokens against the fp32 reference that the
configuration file names (``reference``, by default its ``family``).

For each sampled request the reference runs once, teacher-forced over its
prompt and served tokens (padded to the cache length, so one compiled shape
serves every run), layer by layer with each layer's weights cast to float32
inside its own call.  A served token's gap is how far the reference's logit
for it lies below the reference's largest logit at that position: 0 where
the served token is the reference's argmax.  With ``mm="fp8"`` the same
forward runs with every product in float8 and the gap is read for the token
that this control puts first.
"""
import jax
import jax.numpy as jnp
import numpy as np

from chipbench import reference
from chipbench.reference.common import MATMULS, f32

ROWS = 8


def sample(done, rng, rows=ROWS):
    """Indices of up to ``rows`` requests: the longest (prompt + served) and
    the one with the most served tokens, then others drawn from ``rng``."""
    total = [len(p) + len(o) for p, o in done]
    first = [int(np.argmax(total)), int(np.argmax([len(o) for _, o in done]))]
    pick = list(dict.fromkeys(first))
    rest = [i for i in rng.permutation(len(done)) if i not in pick]
    return pick + [int(i) for i in rest[:rows - len(pick)]]


def _batch(done, max_len):
    """Token rows (ROWS, max_len) and the (row, position, served token) of
    every served token: position j of a row predicts token j + 1."""
    tokens = np.zeros((ROWS, max_len), np.int32)
    rows, cols, served = [], [], []
    for i, (prompt, out) in enumerate(done):
        seq = np.concatenate([prompt, np.asarray(out[:-1], np.int32)])
        tokens[i, :len(seq)] = seq
        rows += [i] * len(out)
        cols += range(len(prompt) - 1, len(prompt) - 1 + len(out))
        served += list(out)
    return tokens, np.asarray(rows), np.asarray(cols), np.asarray(served)


def logits(sizes, params, tokens, rows, cols, mm="fp32"):
    """Reference logits at (rows, cols) of ``tokens``: (len(rows), vocab)."""
    arch = reference.for_config(sizes)
    fn = MATMULS[mm]
    with jax.default_matmul_precision("highest"):
        layer = jax.jit(lambda p, x: arch.layer(sizes, p, x, fn))
        x = f32(params["embed"][jnp.asarray(tokens)])
        blocks = params["blocks"]["lyr"]
        for i in range(sizes["num_hidden_layers"]):
            x = layer(jax.tree.map(lambda a: a[i], blocks), x)
        top = {k: params[k] for k in ("embed", "final_norm", "lm_head") if k in params}
        return jax.jit(lambda t, h: arch.head(sizes, t, h, fn))(
            top, x[jnp.asarray(rows), jnp.asarray(cols)])


def _gaps(ref, tok):
    """Per token, how far the reference's logit for ``tok`` lies below its
    best logit."""
    picked = jnp.take_along_axis(ref, jnp.asarray(tok)[:, None], 1)[:, 0]
    return jnp.max(ref, -1) - picked


def compare(got, limits):
    """Each number that ``limits`` (a cell's limits file) names, from
    ``got``, beside its limit."""
    return {name: {"value": got[name], "limit": lim["limit"]}
            for name, lim in limits.items()}


def within(compared):
    """Whether every compared number that has a limit is at or under it."""
    return all(c["value"] <= c["limit"] for c in compared.values()
               if c["limit"] is not None)


def control_numbers(got):
    """The fp8 control's numbers (``gaps(..., control=True)``) under the
    names a run compares."""
    return {"max_logit_gap": got["control_max_gap"],
            "mean_logit_gap": got["control_mean_gap"]}


def gaps(sizes, params, done, max_len, control=False):
    """The numbers a run compares, over the served tokens of ``done``:
    'max_logit_gap' (the widest gap) and 'mean_logit_gap', with 'served'
    (how many tokens); with ``control``, the same two for the tokens the
    fp8 control puts first ('control_max_gap', 'control_mean_gap')."""
    tokens, rows, cols, served = _batch(done, max_len)
    ref = logits(sizes, params, tokens, rows, cols)
    g = _gaps(ref, served)
    out = {"max_logit_gap": float(g.max()), "mean_logit_gap": float(g.mean()),
           "served": int(len(served))}
    if control:
        ctl = logits(sizes, params, tokens, rows, cols, mm="fp8")
        c = _gaps(ref, jnp.argmax(ctl, -1))
        out.update(control_max_gap=float(c.max()), control_mean_gap=float(c.mean()))
    return out
