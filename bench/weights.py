"""Seeded weights, made on the device from ``--seed``.

Each leaf is a uniform draw built from integer random bits: the top 23
bits of a ``uint32`` become a float32 in [1, 2), shifted to [-1, 1) and
scaled by a power of two.  Every step is exact, so the values do not
depend on how XLA fuses them: the program's stacked tree (one jitted
call) and the reference's layer-by-layer draws hold the same numbers
bit for bit.  Matrices are rounded once to the served dtype.

Amplitudes follow the usual init (std ~0.02; residual outputs scaled
down by the depth): ``2**-5`` for embeddings, q/k/v, FFN inputs and the
head, ``2**-8`` for the attention output and FFN down projection.
Norm scales are ``1 + U(-1/8, 1/8)`` and biases ``U(-1/32, 1/32)``, in
float32 as the program keeps them.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from bench.flops import Shapes

# leaf name -> (id folded into the key, log2 amplitude)
_LEAVES = {
    "wq": (1, -5), "wk": (2, -5), "wv": (3, -5), "wo": (4, -8),
    "w_up": (5, -5), "w_gate": (6, -5), "w_down": (7, -8),
    "ln1_scale": (8, -3), "ln1_bias": (9, -5),
    "ln2_scale": (10, -3), "ln2_bias": (11, -5),
    "table": (12, -5), "head": (13, -5),
    "lnf_scale": (14, -3), "lnf_bias": (15, -5),
}
_GLOBAL = -1        # layer index of the leaves outside the blocks


def seed_key(seed: int) -> jax.Array:
    """A threefry key from any whole number up to 2**64."""
    seed = int(seed)
    if not 0 <= seed < 2**64:
        raise ValueError(f"seed {seed} is outside [0, 2**64)")
    return jax.random.wrap_key_data(
        jnp.asarray([seed >> 32, seed & 0xFFFFFFFF], jnp.uint32))


def _uniform(key, layer, name: str, shape) -> jax.Array:
    """U[-2**e, 2**e) in float32, exact; ``layer`` may be traced."""
    leaf_id, e = _LEAVES[name]
    k = jax.random.fold_in(jax.random.fold_in(key, layer + 2), leaf_id)
    bits = jax.random.bits(k, shape, jnp.uint32)
    one_two = lax.bitcast_convert_type((bits >> 9) | jnp.uint32(0x3F800000),
                                       jnp.float32)
    return (one_two - 1.5) * (2.0 ** (e + 1))


def _norm(key, layer, prefix: str, d: int, layernorm: bool) -> dict:
    p = {"scale": 1.0 + _uniform(key, layer, f"{prefix}_scale", (d,))}
    if layernorm:
        p["bias"] = _uniform(key, layer, f"{prefix}_bias", (d,))
    return p


def layer_weights(key, layer, s: Shapes, layernorm: bool, dtype) -> dict:
    """One decoder block in the program's parameter layout."""
    d, dh = s.d_model, s.head_dim

    def w(name, shape):
        return _uniform(key, layer, name, shape).astype(dtype)

    return {
        "ln1": _norm(key, layer, "ln1", d, layernorm),
        "attn": {"wq": w("wq", (d, s.q_heads * dh)),
                 "wk": w("wk", (d, s.kv_heads * dh)),
                 "wv": w("wv", (d, s.kv_heads * dh)),
                 "wo": w("wo", (s.q_heads * dh, d))},
        "ln2": _norm(key, layer, "ln2", d, layernorm),
        "ffn": {"w_up": w("w_up", (d, s.d_ff)),
                "w_gate": w("w_gate", (d, s.d_ff)),
                "w_down": w("w_down", (s.d_ff, d))},
    }


def global_weights(key, s: Shapes, layernorm: bool, dtype) -> dict:
    """Embedding table, output head and final norm."""
    return {"embed": {
                "table": _uniform(key, _GLOBAL, "table",
                                  (s.vocab, s.d_model)).astype(dtype),
                "head": _uniform(key, _GLOBAL, "head",
                                 (s.d_model, s.vocab)).astype(dtype)},
            "ln_f": _norm(key, _GLOBAL, "lnf", s.d_model, layernorm)}


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def model_weights(key, s: Shapes, layernorm: bool, dtype) -> dict:
    """The whole stacked tree the program serves from, in one call."""
    blocks = jax.vmap(lambda l: layer_weights(key, l, s, layernorm, dtype))(
        jnp.arange(s.layers))
    out = global_weights(key, s, layernorm, dtype)
    out["blocks"] = blocks
    return out
