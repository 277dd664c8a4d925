"""RecurrentGemma: RG-LRU recurrent blocks + local sliding-window attention,
interleaved 2:1 (rec, rec, attn).

The RG-LRU recurrence  h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t)
is a data-gated linear recurrence -> parallelized over the sequence with
``lax.associative_scan`` for train/prefill and O(1) state for decode, which
is what makes the 500k-context decode cell runnable.

Layer schedule: the 38 layers are executed as scan over 12 homogeneous
(rec, rec, attn) groups plus a 2-layer recurrent tail (38 = 12*3 + 2).
Local attention keeps a ``window``-sized rolling KV cache.
"""
from __future__ import annotations

import math
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ArchConfig

Params = Dict[str, Any]
C_LRU = 8.0


def _pattern_counts(cfg: ArchConfig) -> Tuple[int, int]:
    """(#groups, #tail-rec-layers) with group = (rec, rec, attn)."""
    groups = cfg.num_layers // 3
    tail = cfg.num_layers - groups * 3
    return groups, tail


class RGState(NamedTuple):
    lru_h: jax.Array      # (Lr, B, W) recurrent hidden (float32)
    conv: jax.Array       # (Lr, B, conv_width-1, W) conv lookback
    k_cache: jax.Array    # (La, B, window, Hkv, D)
    v_cache: jax.Array
    pos_cache: jax.Array  # (La, B, window) absolute positions, -1 = empty
    lengths: jax.Array    # (B,)

    @staticmethod
    def zeros(cfg: ArchConfig, batch: int):
        groups, tail = _pattern_counts(cfg)
        lr, la = groups * 2 + tail, groups
        w = cfg.lru_width or cfg.d_model
        _, hkv = cfg.padded_heads(1)
        dt = L._dtype(cfg.dtype)
        return RGState(
            jnp.zeros((lr, batch, w), jnp.float32),
            jnp.zeros((lr, batch, cfg.conv_width - 1, w), dt),
            jnp.zeros((la, batch, cfg.window, hkv, cfg.d_head), dt),
            jnp.zeros((la, batch, cfg.window, hkv, cfg.d_head), dt),
            jnp.full((la, batch, cfg.window), -1, jnp.int32),
            jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_rec_layer(key, cfg: ArchConfig, dtype) -> Params:
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 8)
    scale_o = 0.02 / math.sqrt(2 * cfg.num_layers)
    return {
        "ln": L.init_norm(cfg.norm, d),
        "w_in_x": L.dense_init(ks[0], d, w, dtype),
        "w_in_gate": L.dense_init(ks[1], d, w, dtype),
        "conv_w": L.normal_init(ks[2], (cfg.conv_width, w), dtype, 0.02),
        "conv_b": jnp.zeros((w,), dtype),
        "lru": {
            "lam": jax.random.uniform(ks[3], (w,), jnp.float32, 0.9, 0.999),
            "w_a": L.dense_init(ks[4], w, w, dtype),
            "b_a": jnp.zeros((w,), jnp.float32),
            "w_i": L.dense_init(ks[5], w, w, dtype),
            "b_i": jnp.zeros((w,), jnp.float32),
        },
        "w_out": L.dense_init(ks[6], w, d, dtype, scale=scale_o),
        "mlp": L.init_ffn(ks[7], d, cfg.d_ff, cfg.gated_ffn, dtype,
                          cfg.num_layers),
        "ln_mlp": L.init_norm(cfg.norm, d),
    }


def _init_attn_layer(key, cfg: ArchConfig, dtype, hq, hkv) -> Params:
    ka, kf = jax.random.split(key)
    return {
        "ln": L.init_norm(cfg.norm, cfg.d_model),
        "attn": L.init_attention(ka, cfg, dtype, hq, hkv),
        "ln_mlp": L.init_norm(cfg.norm, cfg.d_model),
        "mlp": L.init_ffn(kf, cfg.d_model, cfg.d_ff, cfg.gated_ffn, dtype,
                          cfg.num_layers),
    }


def init(key, cfg: ArchConfig, tp: int = 1) -> Params:
    dtype = L._dtype(cfg.dtype)
    hq, hkv = cfg.padded_heads(tp)
    groups, tail = _pattern_counts(cfg)
    ke, kr, ka, kt = jax.random.split(key, 4)
    rec_grp = jax.vmap(lambda k: jax.vmap(
        lambda k2: _init_rec_layer(k2, cfg, dtype))(jax.random.split(k, 2)))(
        jax.random.split(kr, groups))                    # (G, 2, ...)
    attn_grp = jax.vmap(lambda k: _init_attn_layer(k, cfg, dtype, hq, hkv))(
        jax.random.split(ka, groups))                    # (G, ...)
    p = {"embed": L.init_embed(ke, cfg.padded_vocab(tp), cfg.d_model, dtype,
                               cfg.tie_embeddings),
         "rec_groups": rec_grp, "attn_groups": attn_grp,
         "ln_f": L.init_norm(cfg.norm, cfg.d_model)}
    if tail:
        p["rec_tail"] = jax.vmap(lambda k: _init_rec_layer(k, cfg, dtype))(
            jax.random.split(kt, tail))
    return p


# ---------------------------------------------------------------------------
# RG-LRU core
# ---------------------------------------------------------------------------
def _lru_gates(lp, x):
    """x: (..., W) -> (a, gated_input) both float32."""
    xf = x.astype(jnp.float32)
    r = jax.nn.sigmoid(xf @ lp["w_a"].astype(jnp.float32) + lp["b_a"])
    i = jax.nn.sigmoid(xf @ lp["w_i"].astype(jnp.float32) + lp["b_i"])
    log_a = -C_LRU * jax.nn.softplus(lp["lam"]) * r
    a = jnp.exp(log_a)
    gated = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12)) * (i * xf)
    return a, gated


def _lru_seq(lp, x, h0):
    """Associative scan over the sequence.  x: (B,S,W); h0: (B,W)."""
    a, b = _lru_gates(lp, x)                              # (B,S,W)
    # fold initial state into the first step: b0' = a0*h0 + b0
    b = b.at[:, 0].add(a[:, 0] * h0)

    def combine(left, right):
        al, bl = left
        ar, br = right
        return al * ar, ar * bl + br

    _, h = lax.associative_scan(combine, (a, b), axis=1)
    return h, h[:, -1]                                    # (B,S,W), (B,W)


def _lru_step(lp, x_t, h):
    a, b = _lru_gates(lp, x_t)                            # (B,W)
    h = a * h + b
    return h, h


def _conv1d_seq(lp, x, lookback):
    """Causal temporal conv, width cw.  x: (B,S,W); lookback: (B,cw-1,W)."""
    cw = lp["conv_w"].shape[0]
    xx = jnp.concatenate([lookback.astype(x.dtype), x], axis=1)
    out = sum(xx[:, i:i + x.shape[1]] * lp["conv_w"][i][None, None, :]
              for i in range(cw))
    new_lookback = xx[:, -(cw - 1):] if cw > 1 else lookback
    return out + lp["conv_b"], new_lookback


def _rec_block_seq(cfg, lp, x, h0, conv0):
    """x: (B,S,d)."""
    h = L.apply_norm(cfg.norm, lp["ln"], x)
    gate = jax.nn.gelu(h @ lp["w_in_gate"])
    xx = h @ lp["w_in_x"]
    xx, conv = _conv1d_seq(lp, xx, conv0)
    y, h_last = _lru_seq(lp["lru"], xx, h0)
    y = (y.astype(gate.dtype) * gate) @ lp["w_out"]
    x = x + y
    m = L.apply_norm(cfg.norm, lp["ln_mlp"], x)
    return x + L.apply_ffn(lp["mlp"], m, cfg.act), h_last, conv


def _attn_block_seq(cfg, lp, x, positions, hq, hkv):
    h = L.apply_norm(cfg.norm, lp["ln"], x)
    q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
    q = L.apply_rope(q, positions, cfg.rope_theta)
    k = L.apply_rope(k, positions, cfg.rope_theta)
    attn = L.blocked_attention(q, k, v, causal=True, window=cfg.window)
    b, s = x.shape[:2]
    x = x + attn.reshape(b, s, hq * cfg.d_head) @ lp["attn"]["wo"]
    m = L.apply_norm(cfg.norm, lp["ln_mlp"], x)
    return x + L.apply_ffn(lp["mlp"], m, cfg.act), k, v


def forward_seq(params, cfg: ArchConfig, tokens, tp: int = 1,
                remat: bool = True, collect_cache: bool = False):
    hq, hkv = cfg.padded_heads(tp)
    groups, tail = _pattern_counts(cfg)
    x = L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    w = cfg.lru_width or cfg.d_model

    def group(carry, gp):
        x = carry
        rec2, attnp = gp
        kv = None
        for i in range(2):
            lp = jax.tree.map(lambda a: a[i], rec2)
            x, _, _ = _rec_block_seq(cfg, lp, x,
                                     jnp.zeros((b, w), jnp.float32),
                                     jnp.zeros((b, cfg.conv_width - 1, w),
                                               x.dtype))
        x, k, v = _attn_block_seq(cfg, attnp, x, positions, hq, hkv)
        return x, (k, v)

    if remat:
        group = jax.checkpoint(group)
    x, kv = lax.scan(group, x, (params["rec_groups"], params["attn_groups"]),
                     unroll=cfg.scan_unroll)
    tail_state = []
    if tail:
        def tail_block(x, lp):
            x, h_last, conv = _rec_block_seq(
                cfg, lp, x, jnp.zeros((b, w), jnp.float32),
                jnp.zeros((b, cfg.conv_width - 1, w), x.dtype))
            return x, (h_last, conv)
        x, tail_state = lax.scan(tail_block, x, params["rec_tail"])
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    return x, kv


def loss(params, cfg: ArchConfig, batch, tp: int = 1):
    h, _ = forward_seq(params, cfg, batch["tokens"], tp=tp)
    return L.lm_loss_chunked(params["embed"], h, batch["labels"],
                             batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving
# ---------------------------------------------------------------------------
def prefill(params, cfg: ArchConfig, tokens, tp: int = 1, max_seq=None):
    """Returns (last_logits, RGState).  Processes the whole prompt with the
    parallel scan, keeping the final recurrent states and the last `window`
    keys/values for the local-attention layers."""
    hq, hkv = cfg.padded_heads(tp)
    groups, tail = _pattern_counts(cfg)
    x = L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    w = cfg.lru_width or cfg.d_model
    win = cfg.window
    state = RGState.zeros(cfg, b)

    def group(carry, gp):
        x = carry
        rec2, attnp = gp
        hs, convs = [], []
        for i in range(2):
            lp = jax.tree.map(lambda a: a[i], rec2)
            x, h_last, conv = _rec_block_seq(
                cfg, lp, x, jnp.zeros((b, w), jnp.float32),
                jnp.zeros((b, cfg.conv_width - 1, w), x.dtype))
            hs.append(h_last)
            convs.append(conv)
        x, k, v = _attn_block_seq(cfg, attnp, x, positions, hq, hkv)
        # rolling window: keep last `win` entries
        if s >= win:
            kw, vw = k[:, -win:], v[:, -win:]
            pw = jnp.broadcast_to(jnp.arange(s - win, s)[None, :], (b, win))
        else:
            # left-pad: newest entry must sit at the END so the decode-time
            # left-roll evicts padding first, then the true oldest token.
            pad = win - s
            kw = jnp.pad(k, [(0, 0), (pad, 0), (0, 0), (0, 0)])
            vw = jnp.pad(v, [(0, 0), (pad, 0), (0, 0), (0, 0)])
            pw = jnp.concatenate(
                [jnp.full((b, pad), -1, jnp.int32),
                 jnp.broadcast_to(jnp.arange(s)[None], (b, s))], axis=1)
        return x, (jnp.stack(hs), jnp.stack(convs), kw, vw, pw)

    x, (hs, convs, kc, vc, pc) = lax.scan(
        group, x, (params["rec_groups"], params["attn_groups"]),
        unroll=cfg.scan_unroll)
    lru_h = hs.reshape(groups * 2, b, w)
    conv = convs.reshape(groups * 2, b, cfg.conv_width - 1, w)
    if tail:
        def tail_block(x, lp):
            x, h_last, cv = _rec_block_seq(
                cfg, lp, x, jnp.zeros((b, w), jnp.float32),
                jnp.zeros((b, cfg.conv_width - 1, w), x.dtype))
            return x, (h_last, cv)
        x, (th, tc) = lax.scan(tail_block, x, params["rec_tail"])
        lru_h = jnp.concatenate([lru_h, th], axis=0)
        conv = jnp.concatenate([conv, tc], axis=0)
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    logits = L.unembed(params["embed"], x[:, -1])
    st = RGState(lru_h, conv, kc, vc, pc, jnp.full((b,), s, jnp.int32))
    return logits, st


def _rec_block_step(cfg, lp, x, h0, conv0):
    """Single-token recurrent block.  x: (B,d)."""
    h = L.apply_norm(cfg.norm, lp["ln"], x)
    gate = jax.nn.gelu(h @ lp["w_in_gate"])
    xx = h @ lp["w_in_x"]                                 # (B,W)
    hist = jnp.concatenate([conv0.astype(xx.dtype), xx[:, None]], axis=1)
    cw = lp["conv_w"].shape[0]
    y = sum(hist[:, i] * lp["conv_w"][i][None, :] for i in range(cw))
    y = y + lp["conv_b"]
    conv = hist[:, 1:]
    hstate, y = _lru_step(lp["lru"], y, h0)
    y = (y.astype(gate.dtype) * gate) @ lp["w_out"]
    x = x + y
    m = L.apply_norm(cfg.norm, lp["ln_mlp"], x)
    return x + L.apply_ffn(lp["mlp"], m, cfg.act), (hstate, conv)


def _attn_block_step(cfg, lp, x, kc, vc, pc, pos, hq, hkv):
    """Single-token local attention with rolling window cache.  x: (B,d)."""
    b = x.shape[0]
    h = L.apply_norm(cfg.norm, lp["ln"], x[:, None])
    q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
    posb = jnp.broadcast_to(pos[:, None], (b, 1))
    q = L.apply_rope(q, posb, cfg.rope_theta)
    k = L.apply_rope(k, posb, cfg.rope_theta)
    # roll the window left by one, append the new entry at the end
    kc = jnp.concatenate([kc[:, 1:], k], axis=1)
    vc = jnp.concatenate([vc[:, 1:], v], axis=1)
    pc = jnp.concatenate([pc[:, 1:], posb], axis=1)
    valid = pc >= 0
    acc, l, _ = L.decode_attention_core(q[:, 0], kc, vc, valid)
    out = (acc / jnp.maximum(l, 1e-20)[..., None]).reshape(b, hq * cfg.d_head)
    x = x + out.astype(x.dtype) @ lp["attn"]["wo"]
    m = L.apply_norm(cfg.norm, lp["ln_mlp"], x)
    return x + L.apply_ffn(lp["mlp"], m, cfg.act), kc, vc, pc


def decode_step(params, cfg: ArchConfig, tokens, state: RGState,
                tp: int = 1):
    hq, hkv = cfg.padded_heads(tp)
    groups, tail = _pattern_counts(cfg)
    x = L.embed(params["embed"], tokens)                  # (B,d)
    pos = state.lengths

    def grp(carry, inp):
        x = carry
        gp, h2, c2, kc, vc, pc = inp
        rec2, attnp = gp
        hs, cs = [], []
        for i in range(2):
            lp = jax.tree.map(lambda a: a[i], rec2)
            x, (hn, cn) = _rec_block_step(cfg, lp, x, h2[i], c2[i])
            hs.append(hn)
            cs.append(cn)
        x, kc, vc, pc = _attn_block_step(cfg, attnp, x, kc, vc, pc, pos,
                                         hq, hkv)
        return x, (jnp.stack(hs), jnp.stack(cs), kc, vc, pc)

    g2 = groups * 2
    h_grp = state.lru_h[:g2].reshape(groups, 2, *state.lru_h.shape[1:])
    c_grp = state.conv[:g2].reshape(groups, 2, *state.conv.shape[1:])
    x, (hs, cs, kc, vc, pc) = lax.scan(
        grp, x, ((params["rec_groups"], params["attn_groups"]),
                 h_grp, c_grp, state.k_cache, state.v_cache,
                 state.pos_cache), unroll=cfg.scan_unroll)
    lru_h = hs.reshape(g2, *state.lru_h.shape[1:])
    conv = cs.reshape(g2, *state.conv.shape[1:])
    if tail:
        def tail_block(x, inp):
            lp, h0, c0 = inp
            x, (hn, cn) = _rec_block_step(cfg, lp, x, h0, c0)
            return x, (hn, cn)
        x, (th, tc) = lax.scan(tail_block, x,
                               (params["rec_tail"], state.lru_h[g2:],
                                state.conv[g2:]))
        lru_h = jnp.concatenate([lru_h, th], axis=0)
        conv = jnp.concatenate([conv, tc], axis=0)
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    logits = L.unembed(params["embed"], x)
    return logits, RGState(lru_h, conv, kc, vc, pc, state.lengths + 1)
