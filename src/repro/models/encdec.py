"""Whisper-style encoder-decoder backbone (audio family).

Per the assignment, the conv/mel frontend is a STUB: ``input_specs`` feeds
precomputed frame embeddings (B, frames, d_model) straight into the encoder.
Decoder: causal self-attention (cached) + cross-attention to the encoder
output (K/V computed once at prefill) + GELU FFN, pre-LayerNorm throughout.
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


class EncDecCache(NamedTuple):
    self_k: jax.Array     # (Ld, B, S, H, D)
    self_v: jax.Array
    cross_k: jax.Array    # (Ld, B, F, H, D)
    cross_v: jax.Array
    lengths: jax.Array    # (B,)

    @staticmethod
    def zeros(cfg: ArchConfig, batch: int, max_seq: int, tp: int = 1):
        hq, hkv = cfg.padded_heads(tp)
        dt = L._dtype(cfg.dtype)
        return EncDecCache(
            jnp.zeros((cfg.num_layers, batch, max_seq, hkv, cfg.d_head), dt),
            jnp.zeros((cfg.num_layers, batch, max_seq, hkv, cfg.d_head), dt),
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_frames, hkv,
                       cfg.d_head), dt),
            jnp.zeros((cfg.num_layers, batch, cfg.encoder_frames, hkv,
                       cfg.d_head), dt),
            jnp.zeros((batch,), jnp.int32))


def _sinusoid(length: int, d: int) -> jax.Array:
    pos = jnp.arange(length, dtype=jnp.float32)[:, None]
    dim = jnp.arange(0, d, 2, dtype=jnp.float32)[None, :]
    ang = pos / jnp.power(10000.0, dim / d)
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1)


def _init_enc_layer(key, cfg, dtype, hq, hkv) -> Params:
    ka, kf = jax.random.split(key)
    return {"ln1": L.init_norm("layernorm", cfg.d_model),
            "attn": L.init_attention(ka, cfg, dtype, hq, hkv),
            "ln2": L.init_norm("layernorm", cfg.d_model),
            "ffn": L.init_ffn(kf, cfg.d_model, cfg.d_ff, False, dtype,
                              cfg.num_layers)}


def _init_dec_layer(key, cfg, dtype, hq, hkv) -> Params:
    ka, kx, kf = jax.random.split(key, 3)
    return {"ln1": L.init_norm("layernorm", cfg.d_model),
            "self_attn": L.init_attention(ka, cfg, dtype, hq, hkv),
            "ln_x": L.init_norm("layernorm", cfg.d_model),
            "cross_attn": L.init_attention(kx, cfg, dtype, hq, hkv),
            "ln2": L.init_norm("layernorm", cfg.d_model),
            "ffn": L.init_ffn(kf, cfg.d_model, cfg.d_ff, False, dtype,
                              cfg.num_layers)}


def init(key, cfg: ArchConfig, tp: int = 1) -> Params:
    dtype = L._dtype(cfg.dtype)
    hq, hkv = cfg.padded_heads(tp)
    ke, kenc, kdec, kp = jax.random.split(key, 4)
    enc = jax.vmap(lambda k: _init_enc_layer(k, cfg, dtype, hq, hkv))(
        jax.random.split(kenc, cfg.encoder_layers))
    dec = jax.vmap(lambda k: _init_dec_layer(k, cfg, dtype, hq, hkv))(
        jax.random.split(kdec, cfg.num_layers))
    return {"embed": L.init_embed(ke, cfg.padded_vocab(tp), cfg.d_model,
                                  dtype, tie=True),
            "pos_dec": L.normal_init(kp, (cfg.max_seq, cfg.d_model), dtype,
                                     0.01),
            "enc": enc, "dec": dec,
            "ln_enc": L.init_norm("layernorm", cfg.d_model),
            "ln_f": L.init_norm("layernorm", cfg.d_model)}


def encode(params, cfg: ArchConfig, frames: jax.Array, tp: int = 1,
           remat: bool = True):
    """frames: (B, F, d_model) precomputed embeddings (frontend stub)."""
    hq, hkv = cfg.padded_heads(tp)
    b, f, d = frames.shape
    x = frames + _sinusoid(f, d)[None].astype(frames.dtype)

    def block(x, lp):
        h = L.apply_norm("layernorm", lp["ln1"], x)
        q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
        a = L.blocked_attention(q, k, v, causal=False,
                                q_block=min(512, f), kv_block=min(512, f))
        x = x + a.reshape(b, f, hq * cfg.d_head) @ lp["attn"]["wo"]
        h = L.apply_norm("layernorm", lp["ln2"], x)
        return x + L.apply_ffn(lp["ffn"], h, "gelu"), None

    if remat:
        block = jax.checkpoint(block)
    x, _ = lax.scan(block, x, params["enc"], unroll=cfg.scan_unroll)
    return L.apply_norm("layernorm", params["ln_enc"], x)


def _decoder_seq(params, cfg, tokens, enc_out, tp: int, remat: bool,
                 collect_cache: bool = False):
    hq, hkv = cfg.padded_heads(tp)
    b, s = tokens.shape
    f = enc_out.shape[1]
    x = L.embed(params["embed"], tokens) + \
        params["pos_dec"][None, :s].astype(L._dtype(cfg.dtype))

    def block(x, lp):
        h = L.apply_norm("layernorm", lp["ln1"], x)
        q, k, v = L.qkv_project(lp["self_attn"], h, hq, hkv, cfg.d_head)
        a = L.blocked_attention(q, k, v, causal=True,
                                q_block=min(512, s), kv_block=min(512, s))
        x = x + a.reshape(b, s, hq * cfg.d_head) @ lp["self_attn"]["wo"]
        h = L.apply_norm("layernorm", lp["ln_x"], x)
        qx = (h @ lp["cross_attn"]["wq"]).reshape(b, s, hq, cfg.d_head)
        kx = (enc_out @ lp["cross_attn"]["wk"]).reshape(b, f, hkv, cfg.d_head)
        vx = (enc_out @ lp["cross_attn"]["wv"]).reshape(b, f, hkv, cfg.d_head)
        ax = L.blocked_attention(qx, kx, vx, causal=False,
                                 q_block=min(512, s), kv_block=min(512, f))
        x = x + ax.reshape(b, s, hq * cfg.d_head) @ lp["cross_attn"]["wo"]
        h = L.apply_norm("layernorm", lp["ln2"], x)
        return x + L.apply_ffn(lp["ffn"], h, "gelu"), (k, v, kx, vx)

    if remat and not collect_cache:
        block = jax.checkpoint(block)
    if collect_cache:
        x, caches = lax.scan(block, x, params["dec"],
                             unroll=cfg.scan_unroll)
    else:
        def block_nc(x, lp):
            y, _ = block(x, lp)
            return y, None
        x, caches = lax.scan(block_nc, x, params["dec"],
                             unroll=cfg.scan_unroll)
    return L.apply_norm("layernorm", params["ln_f"], x), caches


def loss(params, cfg: ArchConfig, batch, tp: int = 1):
    enc_out = encode(params, cfg, batch["frames"], tp=tp)
    h, _ = _decoder_seq(params, cfg, batch["tokens"], enc_out, tp, True)
    return L.lm_loss_chunked(params["embed"], h, batch["labels"],
                             batch.get("mask"))


def prefill(params, cfg: ArchConfig, tokens, frames, tp: int = 1,
            max_seq: Optional[int] = None):
    enc_out = encode(params, cfg, frames, tp=tp, remat=False)
    h, (k, v, kx, vx) = _decoder_seq(params, cfg, tokens, enc_out, tp,
                                     remat=False, collect_cache=True)
    b, s = tokens.shape
    if max_seq is not None and max_seq > s:
        pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    cache = EncDecCache(k, v, kx, vx, jnp.full((b,), s, jnp.int32))
    return L.unembed(params["embed"], h[:, -1]), cache


def decode_step(params, cfg: ArchConfig, tokens, cache: EncDecCache,
                tp: int = 1):
    hq, hkv = cfg.padded_heads(tp)
    b = tokens.shape[0]
    x = L.embed(params["embed"], tokens) + jnp.take(
        params["pos_dec"], cache.lengths, axis=0).astype(L._dtype(cfg.dtype))
    f = cache.cross_k.shape[2]
    cross_valid = jnp.ones((b, f), bool)

    def block(x, inp):
        lp, kc, vc, kx, vx = inp
        h = L.apply_norm("layernorm", lp["ln1"], x[:, None])
        q, k, v = L.qkv_project(lp["self_attn"], h, hq, hkv, cfg.d_head)
        idx = cache.lengths
        kc = jax.vmap(lambda c, kn, i: lax.dynamic_update_slice_in_dim(
            c, kn, i, axis=0))(kc, k[:, 0:1], idx)
        vc = jax.vmap(lambda c, vn, i: lax.dynamic_update_slice_in_dim(
            c, vn, i, axis=0))(vc, v[:, 0:1], idx)
        a = L.decode_attention(q[:, 0], kc, vc, cache.lengths + 1)
        x = x + a.reshape(b, hq * cfg.d_head) @ lp["self_attn"]["wo"]
        h = L.apply_norm("layernorm", lp["ln_x"], x[:, None])
        qx = (h @ lp["cross_attn"]["wq"]).reshape(b, 1, hq, cfg.d_head)
        acc, l, _ = L.decode_attention_core(qx[:, 0], kx, vx, cross_valid)
        ax = (acc / jnp.maximum(l, 1e-20)[..., None]).reshape(
            b, hq * cfg.d_head)
        x = x + ax.astype(x.dtype) @ lp["cross_attn"]["wo"]
        h = L.apply_norm("layernorm", lp["ln2"], x)
        return x + L.apply_ffn(lp["ffn"], h, "gelu"), (kc, vc)

    x, (k_new, v_new) = lax.scan(
        block, x, (params["dec"], cache.self_k, cache.self_v,
                   cache.cross_k, cache.cross_v), unroll=cfg.scan_unroll)
    x = L.apply_norm("layernorm", params["ln_f"], x)
    logits = L.unembed(params["embed"], x)
    return logits, EncDecCache(k_new, v_new, cache.cross_k, cache.cross_v,
                               cache.lengths + 1)
