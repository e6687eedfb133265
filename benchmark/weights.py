"""What every family's weights share: the key of a ``--seed``, the draw,
and the types a configuration may state. Which leaves a model has, and
in what layout the program takes them, is the family's affair
(``benchmark/families/<family>``); the traffic draws its batches from
the same key."""

from __future__ import annotations

import jax
import jax.numpy as jnp

DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def seed_key(seed: int) -> jax.Array:
    """A key from any whole number up to a little over 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              seed >> 31)


def normal(key, shape, scale, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)
