"""Decoder-only transformer LM (dense / MoE / VLM-text families).

Pure-functional: ``init`` builds a stacked-parameter pytree (layer dim
leading, consumed by ``lax.scan``), ``loss`` / ``prefill`` / ``decode_step``
are jit-able pure functions.  The VLM family accepts precomputed patch
embeddings (frontend stub per the assignment) and M-RoPE positions.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

from repro.models import layers as L
from repro.models.config import ArchConfig

Params = Dict[str, Any]


class KVCache(NamedTuple):
    k: jax.Array          # (L, B, S, Hkv, D)
    v: jax.Array
    lengths: jax.Array    # (B,) valid prefix per request

    @staticmethod
    def zeros(cfg: ArchConfig, batch: int, max_seq: int, tp: int = 1,
              dtype=None):
        _, hkv = cfg.padded_heads(tp)
        dt = dtype or L._dtype(cfg.dtype)
        shape = (cfg.num_layers, batch, max_seq, hkv, cfg.d_head)
        return KVCache(jnp.zeros(shape, dt), jnp.zeros(shape, dt),
                       jnp.zeros((batch,), jnp.int32))


# ---------------------------------------------------------------------------
# Init
# ---------------------------------------------------------------------------
def _init_layer(key, cfg: ArchConfig, dtype, hq, hkv) -> Params:
    ka, kf, kn = jax.random.split(key, 3)
    p = {
        "ln1": L.init_norm(cfg.norm, cfg.d_model),
        "attn": L.init_attention(ka, cfg, dtype, hq, hkv),
        "ln2": L.init_norm(cfg.norm, cfg.d_model),
    }
    if cfg.num_experts:
        p["moe"] = L.init_moe(kf, cfg, dtype)
    else:
        p["ffn"] = L.init_ffn(kf, cfg.d_model, cfg.d_ff, cfg.gated_ffn,
                              dtype, cfg.num_layers)
    return p


def init(key, cfg: ArchConfig, tp: int = 1) -> Params:
    dtype = L._dtype(cfg.dtype)
    hq, hkv = cfg.padded_heads(tp)
    ke, kl = jax.random.split(key)
    layer_keys = jax.random.split(kl, cfg.num_layers)
    blocks = jax.vmap(lambda k: _init_layer(k, cfg, dtype, hq, hkv))(
        layer_keys)
    return {
        "embed": L.init_embed(ke, cfg.padded_vocab(tp), cfg.d_model, dtype,
                              cfg.tie_embeddings),
        "blocks": blocks,
        "ln_f": L.init_norm(cfg.norm, cfg.d_model),
    }


# ---------------------------------------------------------------------------
# Forward (training / prefill): scan over stacked layers
# ---------------------------------------------------------------------------
def _block_seq(cfg: ArchConfig, lp: Params, x: jax.Array,
               positions: jax.Array, hq: int, hkv: int,
               window: int = 0) -> Tuple[jax.Array, Tuple[jax.Array, jax.Array]]:
    """One decoder block over a full sequence. Returns (x, (k, v))."""
    h = L.apply_norm(cfg.norm, lp["ln1"], x)
    q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
    q = L.apply_rope(q, positions, cfg.rope_theta,
                     cfg.mrope_sections if cfg.mrope else None)
    k = L.apply_rope(k, positions, cfg.rope_theta,
                     cfg.mrope_sections if cfg.mrope else None)
    attn = L.blocked_attention(q, k, v, causal=True, window=window)
    b, s, _, _ = attn.shape
    x = x + attn.reshape(b, s, hq * cfg.d_head) @ lp["attn"]["wo"]
    h = L.apply_norm(cfg.norm, lp["ln2"], x)
    if cfg.num_experts:
        y = L.apply_moe(lp["moe"], h.reshape(b * s, cfg.d_model), cfg)
        y = y.reshape(b, s, cfg.d_model)
    else:
        y = L.apply_ffn(lp["ffn"], h, cfg.act)
    return x + y, (k, v)


def forward_seq(params: Params, cfg: ArchConfig, tokens: Optional[jax.Array],
                positions: Optional[jax.Array] = None,
                embeds: Optional[jax.Array] = None, tp: int = 1,
                collect_cache: bool = False, remat: bool = True):
    """Full-sequence forward. Returns (hidden, (k_stack, v_stack) | None)."""
    hq, hkv = cfg.padded_heads(tp)
    x = embeds if embeds is not None else L.embed(params["embed"], tokens)
    b, s, _ = x.shape
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None], (b, s, 3))

    def block(x, lp):
        # sequence-parallel carry: the remat save per layer shards S over
        # "model" (no-op off-mesh / non-divisible)
        x = L.seq_constraint(x)
        y, kv = _block_seq(cfg, lp, x, positions, hq, hkv,
                           window=cfg.window)
        return L.seq_constraint(y), kv

    if remat:
        block = jax.checkpoint(block)

    if collect_cache:
        x, kv = lax.scan(block, x, params["blocks"],
                         unroll=cfg.scan_unroll)
    else:
        def block_nocache(x, lp):
            y, _ = block(x, lp)
            return y, None
        x, kv = lax.scan(block_nocache, x, params["blocks"],
                         unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    return x, kv


def loss(params: Params, cfg: ArchConfig, batch: Dict[str, jax.Array],
         tp: int = 1) -> jax.Array:
    h, _ = forward_seq(params, cfg, batch.get("tokens"),
                       positions=batch.get("positions"),
                       embeds=batch.get("embeds"), tp=tp)
    return L.lm_loss_chunked(params["embed"], h, batch["labels"],
                             batch.get("mask"))


# ---------------------------------------------------------------------------
# Serving: prefill + single-token decode
# ---------------------------------------------------------------------------
def prefill(params: Params, cfg: ArchConfig, tokens: jax.Array,
            tp: int = 1, embeds: Optional[jax.Array] = None,
            max_seq: Optional[int] = None, chunk: Optional[int] = None):
    """Process the prompt; returns (last_logits, KVCache).

    ``chunk`` enables Sarathi-style chunked prefill (the paper's ref [1]):
    the prompt is processed ``chunk`` tokens at a time against the growing
    KV cache, bounding peak activation memory to one chunk's working set.
    """
    b, s = (tokens.shape if tokens is not None else embeds.shape[:2])
    if chunk is not None and s > chunk and s % chunk == 0 \
            and embeds is None:
        return _prefill_chunked(params, cfg, tokens, tp, max_seq, chunk)
    h, kv = forward_seq(params, cfg, tokens, embeds=embeds, tp=tp,
                        collect_cache=True, remat=False)
    k, v = kv
    if max_seq is not None and max_seq > s:
        pad = [(0, 0), (0, 0), (0, max_seq - s), (0, 0), (0, 0)]
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)
    cache = KVCache(k, v, jnp.full((b,), s, jnp.int32))
    logits = L.unembed(params["embed"], h[:, -1])
    return logits, cache


def _prefill_chunked(params: Params, cfg: ArchConfig, tokens: jax.Array,
                     tp: int, max_seq: Optional[int], chunk: int):
    """Chunked prefill: outer fori over chunks, inner fori over layers,
    in-place cache writes (same structure as decode_step, multi-token)."""
    hq, hkv = cfg.padded_heads(tp)
    b, s = tokens.shape
    total = max(max_seq or s, s)
    cache0 = KVCache.zeros(cfg, b, total, tp)
    n_chunks = s // chunk

    def chunk_body(ci, carry):
        kc_all, vc_all, h_last = carry
        toks = lax.dynamic_slice_in_dim(tokens, ci * chunk, chunk, axis=1)
        x = L.embed(params["embed"], toks)                # (B, C, d)
        pos = ci * chunk + jnp.arange(chunk)
        positions = jnp.broadcast_to(pos[None, :], (b, chunk))
        if cfg.mrope:
            positions = jnp.broadcast_to(positions[..., None],
                                         (b, chunk, 3))

        def layer_body(li, inner):
            x, kc_all, vc_all = inner
            lp = jax.tree.map(
                lambda a: lax.dynamic_index_in_dim(a, li, 0,
                                                   keepdims=False),
                params["blocks"])
            h = L.apply_norm(cfg.norm, lp["ln1"], x)
            q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
            q = L.apply_rope(q, positions, cfg.rope_theta,
                             cfg.mrope_sections if cfg.mrope else None)
            k = L.apply_rope(k, positions, cfg.rope_theta,
                             cfg.mrope_sections if cfg.mrope else None)
            kc = lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
            vc = lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
            kc = lax.dynamic_update_slice(kc, k, (0, ci * chunk, 0, 0))
            vc = lax.dynamic_update_slice(vc, v, (0, ci * chunk, 0, 0))
            # chunk queries attend over the whole cache buffer; the causal
            # mask (q_offset) blanks everything past the current position,
            # including the still-zero future slots
            attn = L.blocked_attention(q, kc, vc, causal=True,
                                       window=cfg.window,
                                       q_offset=ci * chunk)
            x = x + attn.reshape(b, chunk, hq * cfg.d_head) \
                @ lp["attn"]["wo"]
            h2 = L.apply_norm(cfg.norm, lp["ln2"], x)
            if cfg.num_experts:
                y = L.apply_moe(lp["moe"], h2.reshape(b * chunk,
                                                      cfg.d_model), cfg)
                y = y.reshape(b, chunk, cfg.d_model)
            else:
                y = L.apply_ffn(lp["ffn"], h2, cfg.act)
            kc_all = lax.dynamic_update_index_in_dim(kc_all, kc, li, 0)
            vc_all = lax.dynamic_update_index_in_dim(vc_all, vc, li, 0)
            return (x + y, kc_all, vc_all)

        x, kc_all, vc_all = lax.fori_loop(
            0, cfg.num_layers, layer_body, (x, kc_all, vc_all),
            unroll=cfg.scan_unroll)
        return (kc_all, vc_all, x[:, -1])

    h_last0 = jnp.zeros((b, cfg.d_model), L._dtype(cfg.dtype))
    k_new, v_new, h_last = lax.fori_loop(
        0, n_chunks, chunk_body, (cache0.k, cache0.v, h_last0))
    h_last = L.apply_norm(cfg.norm, params["ln_f"], h_last)
    logits = L.unembed(params["embed"], h_last)
    return logits, KVCache(k_new, v_new, jnp.full((b,), s, jnp.int32))


def extend_step(params: Params, cfg: ArchConfig, tokens: jax.Array,
                cache: KVCache, tp: int = 1) -> Tuple[jax.Array, KVCache]:
    """Process a multi-token chunk against an existing cache.

    This is one Sarathi prefill chunk as a standalone jit-able step: the
    engine's chunk scheduler calls it between decode iterations so a long
    prompt never stalls the hot decode batch for more than one chunk.
    ``cache.lengths`` must be uniform across the batch (the engine prefills
    one request at a time); the chunk is written at that offset and
    ``lengths`` advances by the chunk length.  Returns the logits of the
    chunk's last token (so the final chunk yields the first sampled token).
    """
    hq, hkv = cfg.padded_heads(tp)
    b, c = tokens.shape
    offset = cache.lengths[0]
    x = L.embed(params["embed"], tokens)                  # (B, C, d)
    pos = offset + jnp.arange(c)
    positions = jnp.broadcast_to(pos[None, :], (b, c))
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, c, 3))

    def body(li, carry):
        x, kc_all, vc_all = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            params["blocks"])
        h = L.apply_norm(cfg.norm, lp["ln1"], x)
        q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
        q = L.apply_rope(q, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        k = L.apply_rope(k, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        kc = lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
        vc = lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
        with jax.named_scope("kv_write"):
            kc = lax.dynamic_update_slice(kc, k, (0, offset, 0, 0))
            vc = lax.dynamic_update_slice(vc, v, (0, offset, 0, 0))
        # the causal mask (q_offset) blanks everything past the current
        # position, including stale/zero future cache slots
        with jax.named_scope("attention"):
            attn = L.blocked_attention(q, kc, vc, causal=True,
                                       window=cfg.window, q_offset=offset)
        x = x + attn.reshape(b, c, hq * cfg.d_head) @ lp["attn"]["wo"]
        with jax.named_scope("ffn"):
            h2 = L.apply_norm(cfg.norm, lp["ln2"], x)
            if cfg.num_experts:
                y = L.apply_moe(lp["moe"], h2.reshape(b * c, cfg.d_model),
                                cfg)
                y = y.reshape(b, c, cfg.d_model)
            else:
                y = L.apply_ffn(lp["ffn"], h2, cfg.act)
        with jax.named_scope("kv_write"):
            kc_all = lax.dynamic_update_index_in_dim(kc_all, kc, li, 0)
            vc_all = lax.dynamic_update_index_in_dim(vc_all, vc, li, 0)
        return (x + y, kc_all, vc_all)

    x, k_new, v_new = lax.fori_loop(0, cfg.num_layers, body,
                                    (x, cache.k, cache.v),
                                    unroll=cfg.scan_unroll)
    h_last = L.apply_norm(cfg.norm, params["ln_f"], x[:, -1])
    logits = L.unembed(params["embed"], h_last)
    return logits, KVCache(k_new, v_new, cache.lengths + c)


def _decode_ffn(cfg: ArchConfig, lp: Params, x: jax.Array) -> jax.Array:
    """One decode token's FFN (or MoE) residual branch, post-norm."""
    h2 = L.apply_norm(cfg.norm, lp["ln2"], x)
    if cfg.num_experts:
        return L.apply_moe(lp["moe"], h2, cfg)
    return L.apply_ffn(lp["ffn"], h2, cfg.act)


def decode_step_paged(params: Params, cfg: ArchConfig, tokens: jax.Array,
                      k_pool: jax.Array, v_pool: jax.Array,
                      tables: jax.Array, lengths: jax.Array, tp: int = 1,
                      attn_fn=None):
    """One decode iteration reading/writing KV through a block table.

    k_pool/v_pool: (L, P+1, page, Hkv, D) page pools (page P is scratch);
    tables: (B, nblk) page ids with unmapped entries pointing at the
    scratch page; lengths: (B,).  The new token's K/V is scattered into
    the page holding position ``lengths[b]`` — no contiguous cache is ever
    materialized, which is the whole point of the paged layout.

    ``attn_fn(q, k_pool_l, v_pool_l, tables, lengths) -> (B, Hq, D)``
    defaults to the reference gather; pass the Pallas paged flash-decode
    wrapper to read pages directly from the pool.
    """
    hq, hkv = cfg.padded_heads(tp)
    attn_fn = attn_fn or L.paged_decode_attention
    ps = k_pool.shape[2]
    nblk = tables.shape[1]
    x = L.embed(params["embed"], tokens)                 # (B, H)
    b = x.shape[0]
    positions = lengths[:, None]                         # (B, 1)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))
    blk = lengths // ps
    # a position past the mapped window must write to the scratch page P,
    # not alias (via clipping) onto the window's last live page
    in_window = blk < nblk
    blk = jnp.clip(blk, 0, nblk - 1)
    page = jnp.take_along_axis(tables, blk[:, None], axis=1)[:, 0]
    page = jnp.where(in_window, page, k_pool.shape[1] - 1)
    off = lengths % ps

    def body(li, carry):
        x, kp, vp = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            params["blocks"])
        kc = lax.dynamic_index_in_dim(kp, li, 0, keepdims=False)
        vc = lax.dynamic_index_in_dim(vp, li, 0, keepdims=False)
        h = L.apply_norm(cfg.norm, lp["ln1"], x[:, None, :])
        q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
        q = L.apply_rope(q, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        k = L.apply_rope(k, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        with jax.named_scope("kv_write"):
            kc = kc.at[page, off].set(k[:, 0])           # (B,) pages/offs
            vc = vc.at[page, off].set(v[:, 0])
        with jax.named_scope("attention"):
            attn = attn_fn(q[:, 0], kc, vc, tables, lengths + 1)
        x = x + attn.reshape(b, hq * cfg.d_head) @ lp["attn"]["wo"]
        with jax.named_scope("ffn"):
            y = _decode_ffn(cfg, lp, x)
        with jax.named_scope("kv_write"):
            kp = lax.dynamic_update_index_in_dim(kp, kc, li, 0)
            vp = lax.dynamic_update_index_in_dim(vp, vc, li, 0)
        return (x + y, kp, vp)

    x, kp, vp = lax.fori_loop(0, cfg.num_layers, body,
                              (x, k_pool, v_pool),
                              unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    logits = L.unembed(params["embed"], x)
    return logits, (kp, vp, lengths + 1)


def decode_fused_paged(params: Params, cfg: ArchConfig, tokens: jax.Array,
                       k_pool: jax.Array, v_pool: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       alive: jax.Array, k_active: jax.Array,
                       n_steps: int, tp: int = 1, attn_fn=None,
                       eos_id: int = -1):
    """Fuse ``n_steps`` paged decode iterations into one ``lax.scan``.

    The whole multi-step loop — greedy argmax sampling, token feedback,
    per-lane length advance, and eos freezing — stays resident on device:
    the host sees one dispatch and one fetch per fusion horizon instead
    of one per token.  ``n_steps`` is static (the engine buckets it to a
    power of two to bound recompiles); ``k_active`` is the traced actual
    horizon — steps at index >= ``k_active`` leave every lane frozen, so
    a bucketed scan emits exactly the same tokens as an exact-length one.

    A frozen lane (inactive, eos'd, or index >= ``k_active``) still runs
    the step — its K/V write lands at its frozen length, one past its
    valid context, on a page it exclusively owns (or the scratch page for
    inactive lanes whose table rows are pre-masked) — but emits nothing:
    ``emitted[j, b]`` masks the steps whose token in ``tokens_out[j, b]``
    is real.

    Returns ``(tokens_out (n_steps, B), emitted (n_steps, B), k_pool,
    v_pool, lengths)``.
    """
    vocab = cfg.vocab

    def step(carry, idx):
        toks, kp, vp, ln, al = carry
        logits, (kp, vp, _) = decode_step_paged(
            params, cfg, toks, kp, vp, tables, ln, tp=tp, attn_fn=attn_fn)
        nxt = jnp.argmax(logits[:, :vocab], axis=-1).astype(toks.dtype)
        run = al & (idx < k_active)
        toks = jnp.where(run, nxt, toks)
        ln = jnp.where(run, ln + 1, ln)
        if eos_id >= 0:
            al = al & ~(run & (nxt == eos_id))
        return (toks, kp, vp, ln, al), (toks, run)

    carry = (tokens, k_pool, v_pool, lengths, alive)
    (_, kp, vp, ln, _), (tok_seq, emit_seq) = lax.scan(
        step, carry, jnp.arange(n_steps))
    return tok_seq, emit_seq, kp, vp, ln


def decode_step(params: Params, cfg: ArchConfig, tokens: jax.Array,
                cache: KVCache, tp: int = 1,
                attn_fn=None) -> Tuple[jax.Array, KVCache]:
    """One decode iteration: tokens (B,) -> logits (B, V), updated cache.

    ``attn_fn(q, k_cache, v_cache, lengths) -> (B, Hq, D)`` may be overridden
    with the sequence-sharded distributed implementation.
    """
    hq, hkv = cfg.padded_heads(tp)
    attn_fn = attn_fn or L.decode_attention
    x = L.embed(params["embed"], tokens)                 # (B, H)
    b = x.shape[0]
    positions = cache.lengths[:, None]                   # (B, 1)
    if cfg.mrope:
        positions = jnp.broadcast_to(positions[..., None], (b, 1, 3))

    def body(li, carry):
        x, kc_all, vc_all = carry
        lp = jax.tree.map(
            lambda a: lax.dynamic_index_in_dim(a, li, 0, keepdims=False),
            params["blocks"])
        kc = lax.dynamic_index_in_dim(kc_all, li, 0, keepdims=False)
        vc = lax.dynamic_index_in_dim(vc_all, li, 0, keepdims=False)
        h = L.apply_norm(cfg.norm, lp["ln1"], x[:, None, :])
        q, k, v = L.qkv_project(lp["attn"], h, hq, hkv, cfg.d_head)
        q = L.apply_rope(q, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        k = L.apply_rope(k, positions, cfg.rope_theta,
                         cfg.mrope_sections if cfg.mrope else None)
        # write new k/v at each request's current length
        idx = cache.lengths                              # (B,)
        with jax.named_scope("kv_write"):
            kc = jax.vmap(lambda c, kn, i: lax.dynamic_update_slice_in_dim(
                c, kn, i, axis=0))(kc, k[:, 0:1], idx)
            vc = jax.vmap(lambda c, vn, i: lax.dynamic_update_slice_in_dim(
                c, vn, i, axis=0))(vc, v[:, 0:1], idx)
        with jax.named_scope("attention"):
            attn = attn_fn(q[:, 0], kc, vc, cache.lengths + 1)  # (B,Hq,D)
        x = x + attn.reshape(b, hq * cfg.d_head) @ lp["attn"]["wo"]
        with jax.named_scope("ffn"):
            y = _decode_ffn(cfg, lp, x)
        # in-place cache update: a scan emitting stacked (k, v) outputs
        # would materialize a SECOND full cache in temp (§Perf iter. 17)
        with jax.named_scope("kv_write"):
            kc_all = lax.dynamic_update_index_in_dim(kc_all, kc, li, 0)
            vc_all = lax.dynamic_update_index_in_dim(vc_all, vc, li, 0)
        return (x + y, kc_all, vc_all)

    x, k_new, v_new = lax.fori_loop(0, cfg.num_layers, body,
                                    (x, cache.k, cache.v),
                                    unroll=cfg.scan_unroll)
    x = L.apply_norm(cfg.norm, params["ln_f"], x)
    logits = L.unembed(params["embed"], x)
    return logits, KVCache(k_new, v_new, cache.lengths + 1)
