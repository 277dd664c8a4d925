"""Plain reference of the served models, for the check that decides
``correct``.

A decoder-only transformer written out in ``jax.numpy``, in float32 at
``highest`` matmul precision, from the configuration file's keys alone:
pre-norm blocks (RMSNorm or LayerNorm), grouped-query causal attention
with rotary embeddings on the leading ``partial_rotary_factor`` of each
head (rotate-half pairing), a gated SiLU FFN, and an untied head.  It
imports nothing of the program and takes nothing it made: its weights
are drawn again from the seed by ``bench.weights``, one layer at a time,
so it fits beside nothing else on the chip once the program's state is
freed.

``control=True`` computes the same model in the precision below the
served one: every matmul operand is rounded to float8 (e4m3, one scale
per tensor) for a bfloat16 model, to bfloat16 for a float32 one.  The
check must call that model wrong.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass
from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.flops import Shapes

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


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


def _lower(x, served: str):
    """``x`` rounded to the precision below ``served``, back in float32:
    float8 e4m3 with one scale per tensor below bfloat16 or float16,
    bfloat16 below float32."""
    if served == "float32":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def _mm(a, b, control):
    """float32 matmul at full precision; ``control`` names the served
    dtype whose lower precision the operands are rounded to (or None)."""
    if control:
        a, b = _lower(a, control), _lower(b, control)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


def _norm(x, p, a: Arch):
    if a.layernorm:
        mu = x.mean(-1, keepdims=True)
        var = ((x - mu) ** 2).mean(-1, keepdims=True)
        return (x - mu) / jnp.sqrt(var + a.norm_eps) * p["scale"] \
            + p["bias"]
    return x / jnp.sqrt((x * x).mean(-1, keepdims=True) + a.norm_eps) \
        * p["scale"]


def _rope(x, a: Arch):
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


def _attention(q, k, v, control):
    """Causal GQA over one sequence: q (T, Hq, D), k/v (T, Hkv, D)."""
    t, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qg = q.reshape(t, hkv, g, d).transpose(1, 2, 0, 3)      # (Hkv,G,T,D)
    kt = k.transpose(1, 2, 0)[:, None]                       # (Hkv,1,D,T)
    s = _mm(qg, kt, control) / math.sqrt(d)                  # (Hkv,G,T,T)
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = _mm(p, v.transpose(1, 0, 2)[:, None], control)       # (Hkv,G,T,D)
    return o.transpose(2, 0, 1, 3).reshape(t, hq * d)


def _block(x, lw, a: Arch, control):
    """One decoder block over one sequence x (T, d), float32."""
    s = a.shapes
    f32 = functools.partial(jax.tree.map, lambda w: w.astype(jnp.float32))
    lw = f32(lw)
    t = x.shape[0]
    h = _norm(x, lw["ln1"], a)
    at = lw["attn"]
    q = _rope(_mm(h, at["wq"], control).reshape(t, s.q_heads, s.head_dim), a)
    k = _rope(_mm(h, at["wk"], control).reshape(t, s.kv_heads, s.head_dim),
              a)
    v = _mm(h, at["wv"], control).reshape(t, s.kv_heads, s.head_dim)
    x = x + _mm(_attention(q, k, v, control), at["wo"], control)
    h = _norm(x, lw["ln2"], a)
    f = lw["ffn"]
    y = jax.nn.silu(_mm(h, f["w_gate"], control)) * _mm(h, f["w_up"],
                                                        control)
    return x + _mm(y, f["w_down"], control)


@functools.partial(jax.jit, static_argnums=(2, 3))
def _layer(x, key_layer, a: Arch, control):
    key, layer = key_layer
    lw = W.layer_weights(key, layer, a.shapes, a.layernorm, a.jnp_dtype)
    return jax.lax.map(lambda xi: _block(xi, lw, a, control), x)


@functools.partial(jax.jit, static_argnums=(2,))
def _embed(tokens, key, a: Arch):
    gw = W.global_weights(key, a.shapes, a.layernorm, a.jnp_dtype)
    return gw["embed"]["table"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _head(x, targets, key, a: Arch, control):
    """Per position: the largest logit, the argmax, and the logit of
    each target token (``targets`` (N, S, T))."""
    gw = W.global_weights(key, a.shapes, a.layernorm, a.jnp_dtype)
    head = gw["embed"]["head"].astype(jnp.float32)

    def one(args):
        xi, ti = args                                        # (T,d), (S,T)
        lg = _mm(_norm(xi, gw["ln_f"], a), head, control)    # (T, V)
        picked = jnp.take_along_axis(lg[None], ti[..., None], axis=-1)
        return lg.max(-1), lg.argmax(-1).astype(jnp.int32), picked[..., 0]
    return jax.lax.map(one, (x, targets))


def forward(a: Arch, key, tokens: np.ndarray, targets: np.ndarray,
            control: bool = False) -> Tuple[np.ndarray, np.ndarray,
                                            np.ndarray]:
    """Run the model over ``tokens`` (N, T) layer by layer.  Returns per
    position the largest logit (N, T), the argmax (N, T) and the logits
    of ``targets`` (N, S, T)."""
    ctl = a.dtype if control else None
    x = _embed(jnp.asarray(tokens), key, a)
    for layer in range(a.shapes.layers):
        x = _layer(x, (key, jnp.asarray(layer, jnp.int32)), a, ctl)
    mx, am, picked = _head(x, jnp.asarray(targets), key, a, ctl)
    return np.asarray(mx), np.asarray(am), np.asarray(picked)


def pack(seqs: Sequence[Tuple[np.ndarray, np.ndarray]], rows: int = 8,
         min_len: int = 512) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Prompts and served tokens into one padded batch of ``rows`` rows
    and a power-of-two length, so a few shapes serve every run.

    Row i holds prompt_i followed by all served tokens but the last;
    served token j is predicted at position len(prompt_i) - 1 + j.
    Returns (tokens (N, T), served targets (N, T), mask (N, T))."""
    if not 0 < len(seqs) <= rows:
        raise ValueError(f"{len(seqs)} sequences for {rows} rows")
    t = max(min_len, max(len(p) + len(s) - 1 for p, s in seqs))
    t = 1 << (t - 1).bit_length()
    n = rows
    tokens = np.zeros((n, t), np.int32)
    target = np.zeros((n, t), np.int32)
    mask = np.zeros((n, t), bool)
    for i, (p, s) in enumerate(seqs):
        row = np.concatenate([p, s[:-1]]).astype(np.int32)
        tokens[i, :len(row)] = row
        lo = len(p) - 1
        target[i, lo:lo + len(s)] = s
        mask[i, lo:lo + len(s)] = True
    return tokens, target, mask


def served_gaps(a: Arch, seed: int, seqs: List[Tuple[np.ndarray,
                                                      np.ndarray]],
                control: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over every served token of ``seqs``.
    With ``control``, also the widest gap of the tokens that the model
    in the lower precision puts first at the same positions."""
    key = W.seed_key(seed)
    tokens, target, mask = pack(seqs)
    out = {}
    targets = target[:, None]
    if control:
        _, ctl_top, _ = forward(a, key, tokens, target[:, None],
                                control=True)
        targets = np.stack([target, ctl_top], axis=1)
    mx, _, picked = forward(a, key, tokens, targets)
    gaps = mx[:, None] - picked                               # (N, S, T)
    out["served_gap"] = float(gaps[:, 0][mask].max())
    out["served_tokens"] = int(mask.sum())
    if control:
        out["control_gap"] = float(gaps[:, 1][mask].max())
    return out
