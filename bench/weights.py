"""Seeded weights, made on the device from ``--seed``: the draw every
model module shares.

Each leaf is a uniform draw built from integer random bits: the top 23
bits of a ``uint32`` become a float32 in [1, 2), shifted to [-1, 1) and
scaled by a power of two.  Every step is exact, so the values do not
depend on how XLA fuses them: a module's stacked tree (one jitted call)
and its reference's layer-by-layer draws hold the same numbers bit for
bit.  A leaf is named by an id folded into the key and drawn at an
amplitude; each model module (``bench/models/<module>.py``) keeps its
own table of both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

GLOBAL = -1         # layer index of the leaves outside the blocks


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number up to 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def uniform(key, layer, leaf_id: int, log2_amp: int, shape) -> jax.Array:
    """U[-2**log2_amp, 2**log2_amp) in float32, exact; ``layer`` (or
    ``GLOBAL``) may be traced."""
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 2), leaf_id)
    bits = jax.random.bits(k, shape, jnp.uint32)
    one_two = lax.bitcast_convert_type((bits >> 9) | jnp.uint32(0x3F800000),
                                       jnp.float32)
    return (one_two - 1.5) * (2.0 ** (log2_amp + 1))
