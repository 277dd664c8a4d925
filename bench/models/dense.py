"""The dense decoder block as a model module of the benchmark.

A configuration file names its model module (``"module": "dense"``),
and the harness loads ``bench/models/<module>.py`` by path
(``bench.spec.model``, the one way a module is loaded; a module that
builds on another takes it with ``bench.spec.sibling``).  A module
provides the functions of ``bench.spec.MODEL_API``:

- ``arch_fields(model)``: the program's ``ArchConfig`` fields that the
  file's ``model`` keys imply; ``bench.serve.arch_config`` holds the
  registry's entry to them.
- ``weights(key, model)``: the served tree in the program's parameter
  layout, drawn on the device from the seed in one jitted call with
  ``bench.weights.uniform``.  The reference draws the same leaves again,
  layer by layer, bit for bit.
- ``forward(model, key, tokens, targets, control)``: the plain reference
  and its control, as ``bench.reference.served_gaps`` calls it.
- ``decode_flops``, ``attn_flops``, ``paged_attn_bytes``, each
  ``(model, ticks)``: the work of what was decoded, from each tick's
  per-lane record (``bench.serve.Tick.lanes``).  They are the yardstick
  of ``step.decode_mfu`` and ``kernel.paged_attn_roofline``.
- ``prefill_flops_per_token(model)`` and ``weight_bytes_per_step(model,
  lanes)``: the least work any program does for one prompt token (its
  matmuls), and the fewest weight bytes one decode step over ``lanes``
  lanes reads (for experts, those that the lanes' tokens can route to
  at the fewest).  They bound how fast a chip at its peaks could drain
  a backlog (``bench/test_bench_yardstick.py``).

This module: pre-norm blocks (RMSNorm or LayerNorm), grouped-query
causal attention over the whole context in every layer, with rotary
embeddings on the leading ``partial_rotary_factor`` of each head
(rotate-half pairing), no bias, a gated SiLU FFN, and an untied head.
Its helpers (``norm``, ``rope``, ``attention``, ``embed``, ``head``) are
there for other model modules to build on.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.reference import mm


@dataclass(frozen=True)
class Shapes:
    layers: int
    d_model: int
    q_heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    dtype_bytes: int = 2

    @classmethod
    def from_config(cls, model: dict) -> "Shapes":
        """From a configuration file's model keys (Hugging Face names)."""
        return cls(layers=model["num_hidden_layers"],
                   d_model=model["hidden_size"],
                   q_heads=model["num_attention_heads"],
                   kv_heads=model["num_key_value_heads"],
                   head_dim=model["head_dim"],
                   d_ff=model["intermediate_size"],
                   vocab=model["vocab_size"])


@dataclass(frozen=True)
class Arch:
    shapes: Shapes
    layernorm: bool
    norm_eps: float
    rope_theta: float
    rotary_dim: int
    dtype: str                     # the served dtype of the matrices

    @classmethod
    def from_config(cls, model: dict) -> "Arch":
        s = Shapes.from_config(model)
        frac = model.get("partial_rotary_factor", model.get("rope_pct", 1.0))
        eps = model["rms_norm_eps"] if "rms_norm_eps" in model \
            else model["norm_eps"]
        return cls(shapes=s, layernorm=model["norm"] == "layernorm",
                   norm_eps=eps, rotary_dim=int(round(s.head_dim * frac)),
                   rope_theta=model["rope_theta"],
                   dtype=model["torch_dtype"])

    @property
    def jnp_dtype(self):
        return jnp.dtype(self.dtype)


def arch_fields(model: dict) -> dict:
    """The ``ArchConfig`` fields of the registry entry that serves
    ``model``: the file's sizes, and the dense block's fixed choices."""
    m = model
    return {"num_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "num_q_heads": m["num_attention_heads"],
            "num_kv_heads": m["num_key_value_heads"],
            "d_head": m["head_dim"], "d_ff": m["intermediate_size"],
            "vocab": m["vocab_size"], "rope_theta": m["rope_theta"],
            "norm": m["norm"], "dtype": m["torch_dtype"],
            "gated_ffn": True, "act": "silu", "qkv_bias": False,
            "tie_embeddings": False, "num_experts": 0, "family": "dense"}


# ---------------------------------------------------------------------------
# Weights
# ---------------------------------------------------------------------------
# leaf name -> (id folded into the key, log2 amplitude).  Amplitudes
# follow the usual init (std ~0.02; residual outputs scaled down by the
# depth): 2**-5 for embeddings, q/k/v, FFN inputs and the head, 2**-8 for
# the attention output and FFN down projection.  Norm scales are
# 1 + U(-1/8, 1/8) and biases U(-1/32, 1/32), in float32 as the program
# keeps them.  Matrices are rounded once to the served dtype.
LEAVES = {
    "wq": (1, -5), "wk": (2, -5), "wv": (3, -5), "wo": (4, -8),
    "w_up": (5, -5), "w_gate": (6, -5), "w_down": (7, -8),
    "ln1_scale": (8, -3), "ln1_bias": (9, -5),
    "ln2_scale": (10, -3), "ln2_bias": (11, -5),
    "table": (12, -5), "head": (13, -5),
    "lnf_scale": (14, -3), "lnf_bias": (15, -5),
}


def leaf(key, layer, name: str, shape) -> jax.Array:
    return W.uniform(key, layer, *LEAVES[name], shape)


def _norm_weights(key, layer, prefix: str, a: Arch) -> dict:
    d = a.shapes.d_model
    p = {"scale": 1.0 + leaf(key, layer, f"{prefix}_scale", (d,))}
    if a.layernorm:
        p["bias"] = leaf(key, layer, f"{prefix}_bias", (d,))
    return p


def layer_weights(key, layer, a: Arch) -> dict:
    """One decoder block in the program's parameter layout."""
    s = a.shapes
    d, dh = s.d_model, s.head_dim

    def w(name, shape):
        return leaf(key, layer, name, shape).astype(a.jnp_dtype)

    return {
        "ln1": _norm_weights(key, layer, "ln1", a),
        "attn": {"wq": w("wq", (d, s.q_heads * dh)),
                 "wk": w("wk", (d, s.kv_heads * dh)),
                 "wv": w("wv", (d, s.kv_heads * dh)),
                 "wo": w("wo", (s.q_heads * dh, d))},
        "ln2": _norm_weights(key, layer, "ln2", a),
        "ffn": {"w_up": w("w_up", (d, s.d_ff)),
                "w_gate": w("w_gate", (d, s.d_ff)),
                "w_down": w("w_down", (s.d_ff, d))},
    }


def global_weights(key, a: Arch) -> dict:
    """Embedding table, output head and final norm."""
    s = a.shapes
    return {"embed": {
                "table": leaf(key, W.GLOBAL, "table",
                              (s.vocab, s.d_model)).astype(a.jnp_dtype),
                "head": leaf(key, W.GLOBAL, "head",
                             (s.d_model, s.vocab)).astype(a.jnp_dtype)},
            "ln_f": _norm_weights(key, W.GLOBAL, "lnf", a)}


@functools.partial(jax.jit, static_argnums=(1,))
def _stacked(key, a: Arch) -> dict:
    blocks = jax.vmap(lambda l: layer_weights(key, l, a))(
        jnp.arange(a.shapes.layers))
    out = global_weights(key, a)
    out["blocks"] = blocks
    return out


def weights(key, model: dict) -> dict:
    """The whole stacked tree the program serves from, in one call."""
    return _stacked(key, Arch.from_config(model))


# ---------------------------------------------------------------------------
# Reference: float32 at ``highest``; ``control`` names the served dtype
# whose lower precision every matmul operand is rounded to (or None)
# ---------------------------------------------------------------------------
def norm(x, p, a: Arch):
    if a.layernorm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.norm_eps) * p["scale"] \
            + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + a.norm_eps) \
        * p["scale"]


def rope(x, a: Arch):
    """x (T, H, D); rotate the first ``rotary_dim`` of D, halves paired."""
    t = x.shape[0]
    r = a.rotary_dim
    inv = 1.0 / (a.rope_theta ** (np.arange(0, r, 2, dtype=np.float64)
                                  / r))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * jnp.asarray(inv, jnp.float32)[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2, rest = x[..., :r // 2], x[..., r // 2:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos,
                            rest], axis=-1)


def attention(q, k, v, control):
    """Causal GQA over one sequence: q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)      # (Hkv,G,T,D)
    kt = k.transpose(1, 2, 0)[:, None]                       # (Hkv,1,D,T)
    s = mm(qg, kt, control) / math.sqrt(d)                   # (Hkv,G,T,T)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = mm(p, v.transpose(1, 0, 2)[:, None], control)        # (Hkv,G,T,D)
    return o.transpose(2, 0, 1, 3).reshape(t, hq * d)


def block(x, lw, a: Arch, control):
    """One decoder block over one sequence x (T, d), float32."""
    s = a.shapes
    lw = jax.tree.map(lambda w: w.astype(jnp.float32), lw)
    t = x.shape[0]
    h = norm(x, lw["ln1"], a)
    at = lw["attn"]
    q = rope(mm(h, at["wq"], control).reshape(t, s.q_heads, s.head_dim), a)
    k = rope(mm(h, at["wk"], control).reshape(t, s.kv_heads, s.head_dim), a)
    v = mm(h, at["wv"], control).reshape(t, s.kv_heads, s.head_dim)
    x = x + mm(attention(q, k, v, control), at["wo"], control)
    h = norm(x, lw["ln2"], a)
    f = lw["ffn"]
    y = jax.nn.silu(mm(h, f["w_gate"], control)) * mm(h, f["w_up"], control)
    return x + mm(y, f["w_down"], control)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, key_layer, a: Arch, control):
    key, layer = key_layer
    lw = layer_weights(key, layer, a)
    return jax.lax.map(lambda xi: block(xi, lw, a, control), x)


@functools.partial(jax.jit, static_argnums=(2,))
def embed(tokens, key, a: Arch):
    gw = global_weights(key, a)
    return gw["embed"]["table"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4))
def head(x, targets, key, a: Arch, control):
    """Per position: the largest logit, the argmax, and the logit of
    each target token (``targets`` (N, S, T))."""
    gw = global_weights(key, a)
    w = gw["embed"]["head"].astype(jnp.float32)

    def one(args):
        xi, ti = args                                        # (T,d), (S,T)
        lg = mm(norm(xi, gw["ln_f"], a), w, control)         # (T, V)
        picked = jnp.take_along_axis(lg[None], ti[..., None], axis=-1)
        return lg.max(-1), lg.argmax(-1).astype(jnp.int32), picked[..., 0]
    return jax.lax.map(one, (x, targets))


def forward(model: dict, key, tokens: np.ndarray, targets: np.ndarray,
            control: bool = False) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """Run the model over ``tokens`` (N, T) layer by layer.  Returns per
    position the largest logit (N, T), the argmax (N, T) and the logits
    of ``targets`` (N, S, T)."""
    a = Arch.from_config(model)
    ctl = a.dtype if control else None
    x = embed(jnp.asarray(tokens), key, a)
    for layer in range(a.shapes.layers):
        x = _layer(x, (key, jnp.asarray(layer, jnp.int32)), a, ctl)
    mx, am, picked = head(x, jnp.asarray(targets), key, a, ctl)
    return np.asarray(mx), np.asarray(am), np.asarray(picked)


# ---------------------------------------------------------------------------
# Counts: what the algorithm needs, whatever implements it
# ---------------------------------------------------------------------------
def attended(ticks) -> int:
    """Cached tokens the decoded tokens attended in one layer, summed: a
    lane that decoded ``n`` tokens from a context of ``ctx`` attends
    ctx + 1, ..., ctx + n."""
    return sum(n * ctx + n * (n + 1) // 2
               for t in ticks for n, ctx in t.lanes)


def matmul_params(model: dict) -> int:
    """Weights a decode token multiplies by: q/k/v/o projections, the
    gated FFN's three matrices in every layer, and the output head.  The
    embedding lookup is a gather, not a product."""
    s = Shapes.from_config(model)
    qkv = s.d_model * (s.q_heads + 2 * s.kv_heads) * s.head_dim
    wo = s.q_heads * s.head_dim * s.d_model
    ffn = 3 * s.d_model * s.d_ff
    return s.layers * (qkv + wo + ffn) + s.d_model * s.vocab


def matmul_flops_per_token(model: dict) -> int:
    return 2 * matmul_params(model)


def prefill_flops_per_token(model: dict) -> int:
    """A prompt token's matmuls, attention left out: the least any
    program computes for it."""
    return matmul_flops_per_token(model)


def weight_bytes_per_step(model: dict, lanes: int) -> int:
    """Every matrix a decode token multiplies by is read once per step,
    whatever the number of lanes."""
    return matmul_params(model) * jnp.dtype(model["torch_dtype"]).itemsize


def kv_bytes_per_token_layer(model: dict) -> int:
    """K and V of one cached token in one layer."""
    s = Shapes.from_config(model)
    return 2 * s.kv_heads * s.head_dim * s.dtype_bytes


def attn_flops(model: dict, ticks) -> int:
    """Scores (q.k) and the weighted sum (p.v) of every decoded token
    over the cached tokens it attended, in every layer."""
    s = Shapes.from_config(model)
    return 2 * 2 * s.q_heads * s.head_dim * attended(ticks) * s.layers


def decode_flops(model: dict, ticks) -> int:
    """The decoded tokens' matmuls and attention."""
    tokens = sum(n for t in ticks for n, _ in t.lanes)
    return tokens * matmul_flops_per_token(model) + attn_flops(model, ticks)


def paged_attn_bytes(model: dict, ticks) -> int:
    """Live K/V bytes the paged attention kernel has to read for the
    decoded tokens' attended context, all layers."""
    s = Shapes.from_config(model)
    return attended(ticks) * kv_bytes_per_token_layer(model) * s.layers
