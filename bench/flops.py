"""Operations and bytes of one decode token, counted from the model's
shapes.  The benchmark's yardstick for ``step.decode_mfu`` and
``kernel.paged_attn_roofline``: what the algorithm needs, whatever
implements it.
"""
from __future__ import annotations

from dataclasses import dataclass


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


def matmul_params(s: Shapes) -> int:
    """Weights a decode token multiplies by: q/k/v/o projections, the
    gated FFN's three matrices in every layer, and the output head.  The
    embedding lookup is a gather, not a product."""
    qkv = s.d_model * (s.q_heads + 2 * s.kv_heads) * s.head_dim
    wo = s.q_heads * s.head_dim * s.d_model
    ffn = 3 * s.d_model * s.d_ff
    return s.layers * (qkv + wo + ffn) + s.d_model * s.vocab


def matmul_flops_per_token(s: Shapes) -> int:
    return 2 * matmul_params(s)


def attn_flops(s: Shapes, attended: int) -> int:
    """Scores (q.k) and the weighted sum (p.v) of one query token over
    ``attended`` cached tokens, in every layer."""
    return 2 * 2 * s.q_heads * s.head_dim * attended * s.layers


def kv_bytes_per_token_layer(s: Shapes) -> int:
    """K and V of one cached token in one layer."""
    return 2 * s.kv_heads * s.head_dim * s.dtype_bytes


def decode_flops(s: Shapes, lane_steps: int, attended: int) -> int:
    """``lane_steps`` tokens decoded, attending ``attended`` cached
    tokens in all (summed over those tokens)."""
    return lane_steps * matmul_flops_per_token(s) + attn_flops(s, attended)


def paged_attn_bytes(s: Shapes, attended: int) -> int:
    """Live K/V bytes the paged attention kernel has to read for
    ``attended`` cached tokens (summed over lanes and steps), all layers."""
    return attended * kv_bytes_per_token_layer(s) * s.layers
