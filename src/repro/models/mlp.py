"""Dense MLP (optionally gated) with activation-sharded intermediates."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import activation, constrain
from .config import ModelConfig
from .params import gated_mlp


@jax.named_scope("mlp")
def mlp_block(cfg: ModelConfig, p, x: jax.Array) -> jax.Array:
    h = jnp.einsum("bsd,df->bsf", x, p["wi"])
    if gated_mlp(cfg):
        g = jnp.einsum("bsd,df->bsf", x, p["wg"])
        h = activation(cfg, g) * h
    else:
        h = activation(cfg, h)
    h = constrain(h, "batch", "seq", "mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p["wo"])
    return constrain(out, "batch", "seq", "embed")
