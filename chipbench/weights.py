"""Random weights from a seed, made on the device in one jitted call.

The tree has the program's layout (``param_shapes``), so the program can
serve it; the values are the benchmark's own, so the reference can read
them without taking anything the program made.  Scales follow the usual
initialisation: embedding 0.02, every matrix ``fan_in ** -0.5``, norm
scales 1 and biases 0.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def jax_key(seed: int):
    """A JAX key from any non-negative whole number (seeds may exceed 32
    bits, which ``PRNGKey`` alone would refuse or fold)."""
    words = np.random.SeedSequence(int(seed)).generate_state(2)
    return jax.random.wrap_key_data(jnp.asarray(words, jnp.uint32))


def _leaf_name(path) -> str:
    return str(getattr(path[-1], "key", path[-1]))


def _draw(key, path, shape: jax.ShapeDtypeStruct):
    name = _leaf_name(path)
    top = str(getattr(path[0], "key", path[0]))
    if name == "scale":
        return jnp.ones(shape.shape, shape.dtype)
    if name in ("b", "bias"):
        return jnp.zeros(shape.shape, shape.dtype)
    std = 0.02 if top == "embed" else shape.shape[-2] ** -0.5
    z = jax.random.normal(key, shape.shape, jnp.float32)
    return (z * std).astype(shape.dtype)


def make_weights(shapes, seed: int):
    """Weights for the ``ShapeDtypeStruct`` tree ``shapes`` from ``seed``."""
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        keys = jax.random.split(key, len(leaves))
        return treedef.unflatten(
            [_draw(k, path, s) for k, (path, s) in zip(keys, leaves)])

    return jax.jit(build)(jax_key(seed))
