"""The check that decides ``correct``, as every model shares it: the
served tokens go through the cell's model module's plain reference
(``bench/models/<module>.py``: ``forward``), and this module packs them
and reads the gaps.

A reference's forward is plain ``jax.numpy`` in float32 at ``highest``
matmul precision (``mm``), from the configuration file's keys alone.  It
imports nothing of the program and takes nothing it made: its weights
are drawn again from the seed by the module's leaves, one layer at a
time, so it fits beside nothing else on the chip once the program's
state is freed.

``control=True`` computes the same model in the precision below the
served one (``mm`` with the served dtype): every matmul operand is
rounded to float8 (e4m3, one scale per tensor) for a bfloat16 model, to
bfloat16 for a float32 one.  The check must call that model wrong.
"""
from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W

F8 = jnp.float8_e4m3fn
F8_MAX = 448.0

# forward(model, key, tokens (N, T), targets (N, S, T), control) ->
# per position the largest logit (N, T), the argmax (N, T) and the
# logits of the targets (N, S, T)
Forward = Callable[..., Tuple[np.ndarray, np.ndarray, np.ndarray]]


def _lower(x, served: str):
    """``x`` rounded to the precision below ``served``, back in float32:
    float8 e4m3 with one scale per tensor below bfloat16 or float16,
    bfloat16 below float32."""
    if served == "float32":
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    amax = jnp.max(jnp.abs(x))
    scale = jnp.where(amax > 0, amax / F8_MAX, 1.0)
    return (x / scale).astype(F8).astype(jnp.float32) * scale


def mm(a, b, control: Optional[str]):
    """float32 matmul at full precision; ``control`` names the served
    dtype whose lower precision the operands are rounded to (or None)."""
    if control:
        a, b = _lower(a, control), _lower(b, control)
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGHEST)


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


def served_gaps(forward: Forward, model: dict, seed: int,
                seqs: List[Tuple[np.ndarray, np.ndarray]],
                control: bool = False) -> dict:
    """The widest gap by which a served token's reference logit lies
    below the reference's best, over every served token of ``seqs``;
    ``forward`` is the cell's model module's, run over the configuration
    file's ``model`` keys.  With ``control``, also the widest gap of the tokens that the model
    in the lower precision puts first at the same positions."""
    key = W.seed_key(seed)
    tokens, target, mask = pack(seqs)
    out = {}
    targets = target[:, None]
    if control:
        _, ctl_top, _ = forward(model, key, tokens, target[:, None], True)
        targets = np.stack([target, ctl_top], axis=1)
    mx, _, picked = forward(model, key, tokens, targets, False)
    gaps = mx[:, None] - picked                               # (N, S, T)
    out["served_gap"] = float(gaps[:, 0][mask].max())
    out["served_tokens"] = int(mask.sum())
    if control:
        out["control_gap"] = float(gaps[:, 1][mask].max())
    return out
