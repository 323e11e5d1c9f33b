"""What the plain references share: the key from a seed, the weights
from their shapes, LayerNorm and the erf GELU."""
import math

import jax
import jax.numpy as jnp

INIT_STD = 0.02


def seed_key(seed):
    """A PRNG key from any whole number (seeds pass 2**31)."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def init_from_shapes(shapes, seed, dtype=jnp.float32):
    """Every leaf of ``shapes`` from the seed, in one jitted call on the
    device: normal(0, 0.02) everywhere, LayerNorm gains (``*_g``)
    1 + normal(0, 0.02)."""
    def make(key):
        out = {}
        for i, (name, shape) in enumerate(shapes.items()):
            w = INIT_STD * jax.random.normal(jax.random.fold_in(key, i),
                                             shape, jnp.float32)
            if name.endswith("_g"):
                w = w + 1.0
            out[name] = w.astype(dtype)
        return out

    return jax.jit(make)(seed_key(seed))


def layer_norm(x, g, b, eps):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def gelu(x):
    return 0.5 * x * (1.0 + jax.lax.erf(x / math.sqrt(2.0)))


def stacked_layers(w, leaves, num_layers):
    """The per-layer leaves ``l<i>.<leaf>`` stacked along a new first
    axis, for ``jax.lax.scan`` over the layers."""
    return {leaf: jnp.stack([w[f"l{i}.{leaf}"] for i in range(num_layers)])
            for leaf in leaves}
