"""RWKV6 "Finch" — attention-free SSM with data-dependent decay.

Per layer: time-mix (token-shift ddlerp -> r/k/v/g/w projections -> WKV6
linear-attention recurrence with per-channel data-dependent decay + bonus)
and channel-mix (token-shift gated FFN).  Decode state is O(d_model) per
layer, so the 500k-context cell runs with constant memory.

Recurrence (head size hs, per head):
    y_t = r_t . (S_{t-1} + diag(u) k_t v_t^T)
    S_t = diag(w_t) S_{t-1} + k_t v_t^T
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


class RWKVState(NamedTuple):
    tm_x: jax.Array     # (L, B, d)   last token for time-mix shift
    cm_x: jax.Array     # (L, B, d)   last token for channel-mix shift
    wkv: jax.Array      # (L, B, H, hs, hs) recurrence state (float32)
    lengths: jax.Array  # (B,)

    @staticmethod
    def zeros(cfg: ArchConfig, batch: int):
        heads = cfg.d_model // cfg.rwkv_head_size
        hs = cfg.rwkv_head_size
        dt = L._dtype(cfg.dtype)
        return RWKVState(
            jnp.zeros((cfg.num_layers, batch, cfg.d_model), dt),
            jnp.zeros((cfg.num_layers, batch, cfg.d_model), dt),
            jnp.zeros((cfg.num_layers, batch, heads, hs, hs), jnp.float32),
            jnp.zeros((batch,), jnp.int32))


def _init_layer(key, cfg: ArchConfig, dtype) -> Params:
    d = cfg.d_model
    ks = jax.random.split(key, 10)
    lora = max(32, d // 64)
    scale_o = 0.02 / math.sqrt(2 * cfg.num_layers)
    heads = d // cfg.rwkv_head_size
    return {
        "ln1": L.init_norm("layernorm", d),
        "ln2": L.init_norm("layernorm", d),
        "mix": {  # ddlerp mixing coefficients for r,k,v,g,w
            "mu": (jax.random.uniform(ks[0], (5, d)) * 0.5 + 0.25
                   ).astype(dtype),
        },
        "wr": L.dense_init(ks[1], d, d, dtype),
        "wk": L.dense_init(ks[2], d, d, dtype),
        "wv": L.dense_init(ks[3], d, d, dtype),
        "wg": L.dense_init(ks[4], d, d, dtype),
        "wo": L.dense_init(ks[5], d, d, dtype, scale=scale_o),
        "w_decay": {
            "w0": jnp.full((d,), -6.0, jnp.float32),
            "a": L.dense_init(ks[6], d, lora, dtype),
            "b": L.dense_init(ks[7], lora, d, dtype),
        },
        "u_bonus": L.normal_init(ks[8], (heads, cfg.rwkv_head_size),
                                 jnp.float32, 0.1),
        "gn": {"scale": jnp.ones((d,), jnp.float32),
               "bias": jnp.zeros((d,), jnp.float32)},
        "cm": {
            "ln": L.init_norm("layernorm", d),
            "mu": (jax.random.uniform(ks[9], (2, d)) * 0.5 + 0.25
                   ).astype(dtype),
            "wk": L.dense_init(ks[0], d, cfg.d_ff, dtype),
            "wv": L.dense_init(ks[1], cfg.d_ff, d, dtype, scale=scale_o),
            "wr": L.dense_init(ks[2], d, d, dtype),
        },
    }


def init(key, cfg: ArchConfig, tp: int = 1) -> Params:
    dtype = L._dtype(cfg.dtype)
    ke, kl = jax.random.split(key)
    blocks = jax.vmap(lambda k: _init_layer(k, cfg, dtype))(
        jax.random.split(kl, cfg.num_layers))
    return {"embed": L.init_embed(ke, cfg.padded_vocab(tp), cfg.d_model,
                                  dtype, cfg.tie_embeddings),
            "blocks": blocks,
            "ln_f": L.init_norm("layernorm", cfg.d_model)}


def _group_norm(p, x, heads):
    b, d = x.shape
    xg = x.reshape(b, heads, d // heads).astype(jnp.float32)
    mu = xg.mean(-1, keepdims=True)
    var = xg.var(-1, keepdims=True)
    xg = (xg - mu) * lax.rsqrt(var + 1e-5)
    return (xg.reshape(b, d) * p["scale"] + p["bias"])


def _time_mix_step(cfg, lp, x_t, prev_x, state):
    """One token through the time-mix block.  x_t: (B, d)."""
    heads, hs = cfg.d_model // cfg.rwkv_head_size, cfg.rwkv_head_size
    mu = lp["mix"]["mu"]                              # (5, d)
    xs = prev_x + (x_t - prev_x) * mu[:, None, :]     # (5, B, d): r,k,v,g,w
    xr, xk, xv, xg, xw = xs
    r = (xr @ lp["wr"]).reshape(-1, heads, hs).astype(jnp.float32)
    k = (xk @ lp["wk"]).reshape(-1, heads, hs).astype(jnp.float32)
    v = (xv @ lp["wv"]).reshape(-1, heads, hs).astype(jnp.float32)
    g = jax.nn.silu(xg @ lp["wg"])
    wd = lp["w_decay"]
    w = (wd["w0"] + (jnp.tanh(xw @ wd["a"]) @ wd["b"]).astype(jnp.float32))
    w = jnp.exp(-jnp.exp(w)).reshape(-1, heads, hs)   # decay in (0,1)
    u = lp["u_bonus"]                                 # (H, hs)
    kv = k[..., :, None] * v[..., None, :]            # (B,H,hs,hs)
    y = jnp.einsum("bhi,bhij->bhj", r, state + u[None, :, :, None] * kv)
    state = w[..., :, None] * state + kv
    y = _group_norm(lp["gn"], y.reshape(-1, cfg.d_model), heads)
    out = (y.astype(g.dtype) * g) @ lp["wo"]
    return out, state


def _channel_mix_step(cfg, lp, x_t, prev_x):
    mu = lp["mu"]
    xk = prev_x + (x_t - prev_x) * mu[0][None, :]
    xr = prev_x + (x_t - prev_x) * mu[1][None, :]
    k = jnp.square(jax.nn.relu(xk @ lp["wk"])) @ lp["wv"]
    return jax.nn.sigmoid(xr @ lp["wr"]) * k


def _layer_scan_seq(cfg, lp, x, tm_x0, cm_x0, wkv0):
    """Run one layer over a full sequence (scan over time).  x: (B,S,d)."""

    def step(carry, x_t):
        tm_prev, cm_prev, st = carry
        h = L.apply_norm("layernorm", lp["ln1"], x_t)
        tm_h_prev = tm_prev
        out, st = _time_mix_step(cfg, lp, h, tm_h_prev, st)
        x1 = x_t + out
        h2 = L.apply_norm("layernorm", lp["cm"]["ln"], x1)
        out2 = _channel_mix_step(cfg, lp["cm"], h2, cm_prev)
        return (h, h2, st), x1 + out2

    (tm_x, cm_x, wkv), y = lax.scan(step, (tm_x0, cm_x0, wkv0),
                                    jnp.moveaxis(x, 1, 0))
    return jnp.moveaxis(y, 0, 1), (tm_x, cm_x, wkv)


def forward_seq(params, cfg: ArchConfig, tokens, state: Optional[RWKVState]
                = None, tp: int = 1, remat: bool = True):
    x = L.embed(params["embed"], tokens)
    b, s, d = x.shape
    if state is None:
        state = RWKVState.zeros(cfg, b)

    def block(x, inp):
        lp, tm0, cm0, st0 = inp
        y, (tm, cm, st) = _layer_scan_seq(cfg, lp, x, tm0, cm0, st0)
        return y, (tm, cm, st)

    if remat:
        block = jax.checkpoint(block)
    x, (tm, cm, wkv) = lax.scan(block, x,
                                (params["blocks"], state.tm_x, state.cm_x,
                                 state.wkv), unroll=cfg.scan_unroll)
    x = L.apply_norm("layernorm", params["ln_f"], x)
    new_state = RWKVState(tm, cm, wkv, state.lengths + s)
    return x, new_state


def loss(params, cfg: ArchConfig, batch, tp: int = 1):
    h, _ = forward_seq(params, cfg, batch["tokens"], tp=tp)
    return L.lm_loss_chunked(params["embed"], h, batch["labels"],
                             batch.get("mask"))


def prefill(params, cfg: ArchConfig, tokens, tp: int = 1, max_seq=None):
    h, state = forward_seq(params, cfg, tokens, tp=tp, remat=False)
    return L.unembed(params["embed"], h[:, -1]), state


def decode_step(params, cfg: ArchConfig, tokens, state: RWKVState,
                tp: int = 1):
    x = L.embed(params["embed"], tokens)                 # (B, d)

    def block(x, inp):
        lp, tm0, cm0, st0 = inp
        h = L.apply_norm("layernorm", lp["ln1"], x)
        out, st = _time_mix_step(cfg, lp, h, tm0, st0)
        x1 = x + out
        h2 = L.apply_norm("layernorm", lp["cm"]["ln"], x1)
        out2 = _channel_mix_step(cfg, lp["cm"], h2, cm0)
        return x1 + out2, (h, h2, st)

    x, (tm, cm, wkv) = lax.scan(block, x,
                                (params["blocks"], state.tm_x, state.cm_x,
                                 state.wkv), unroll=cfg.scan_unroll)
    x = L.apply_norm("layernorm", params["ln_f"], x)
    logits = L.unembed(params["embed"], x)
    return logits, RWKVState(tm, cm, wkv, state.lengths + 1)
