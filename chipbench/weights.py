"""Seeded weights, made on the device in one jitted call.

The tree has the layout the program's ``param_shapes`` gives; the values are
the benchmark's own, drawn from the run's seed in the served dtype.  Stacked
per-layer leaves are drawn one layer at a time (``lax.map``), so the random
bits of the largest leaf never sit in memory whole.
"""
import jax
import jax.numpy as jnp


def _is_leaf(x):
    return (isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)
            and isinstance(x[1], str))


def _draw(key, shape, kind, dtype):
    if kind == "ones":                  # norm gains
        return (1.0 + 0.1 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if kind == "zeros":                 # biases
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if kind == "embed":
        return (0.02 * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    if kind == "normal":
        scale = 1.0 / (shape[-2] ** 0.5)   # fan-in: the contraction dim
        return (scale * jax.random.normal(key, shape, jnp.float32)).astype(dtype)
    raise ValueError(f"no draw for init kind {kind!r}")


def seed_key(seed):
    """A PRNG key from any non-negative integer seed (wider than 32 bits)."""
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def make(cfg, seed):
    """The weight tree for ``cfg`` (a repo ``ModelConfig``) from ``seed``."""
    from repro.models.params import param_shapes
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes, is_leaf=_is_leaf)
    leaves = [leaf for _, leaf in flat]
    in_blocks = [path[0].key == "blocks" for path, _ in flat]
    dtype = jnp.dtype(cfg.dtype)

    def build(key):
        out = []
        for i, ((shape, kind), per_layer) in enumerate(zip(leaves, in_blocks)):
            k = jax.random.fold_in(key, i)
            if per_layer:
                keys = jax.random.split(k, shape[0])
                out.append(jax.lax.map(
                    lambda kk, s=shape[1:], kd=kind: _draw(kk, s, kd, dtype), keys))
            else:
                out.append(_draw(k, shape, kind, dtype))
        return jax.tree.unflatten(treedef, out)

    return jax.jit(build)(seed_key(seed))
