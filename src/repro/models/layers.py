"""Shared model components (pure-functional, pytree params).

Everything is written to be (a) exactly correct on one CPU device for the
smoke tests, (b) GSPMD-shardable at the production mesh for the dry-run, and
(c) memory-sane at 32k-500k contexts (blocked attention, chunked CE loss,
capacity-grouped MoE — no T x E x C one-hot dispatch tensors).
"""
from __future__ import annotations

import functools
import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

Params = Dict[str, Any]


def _dtype(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------
def init_norm(kind: str, d: int, dtype=jnp.float32) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


def apply_norm(kind: str, p: Params, x: jax.Array, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    if kind == "rmsnorm":
        y = xf * lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    else:
        mu = jnp.mean(xf, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
        y = (xf - mu) * lax.rsqrt(var + eps)
    y = y * p["scale"].astype(jnp.float32)
    if kind == "layernorm":
        y = y + p["bias"].astype(jnp.float32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Rotary embeddings (RoPE + M-RoPE)
# ---------------------------------------------------------------------------
def rope_freqs(d_head: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, d_head, 2, dtype=jnp.float32)
                            / d_head))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               mrope_sections: Optional[Tuple[int, int, int]] = None):
    """x: (..., S, H, D); positions: (B, S) or (B, S, 3) for M-RoPE."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)                       # (D/2,)
    if positions.ndim == 3:                          # M-RoPE (Qwen2-VL)
        assert mrope_sections is not None
        sec = jnp.asarray(
            sum(([i] * s for i, s in enumerate(mrope_sections)), []))
        sec = sec[: d // 2]                           # (D/2,) section id
        pos = jnp.take_along_axis(
            positions.astype(jnp.float32),
            jnp.broadcast_to(sec[None, None, :],
                             positions.shape[:2] + (d // 2,)).astype(jnp.int32),
            axis=-1)                                  # (B, S, D/2)
        ang = pos * inv[None, None, :]
    else:
        ang = positions.astype(jnp.float32)[..., None] * inv  # (B, S, D/2)
    cos = jnp.cos(ang)[..., None, :]                  # (B, S, 1, D/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Dense / attention initialisation
# ---------------------------------------------------------------------------
def normal_init(key, shape, dtype, scale: float) -> jax.Array:
    """N(0, scale^2) draw in float32, cast to ``dtype``.

    The ``where`` is an identity (a normal draw is never NaN).  It keeps
    XLA from folding ``scale`` into the sqrt(2) that ends the draw, which
    would change the last bit: so a jitted init gives exactly the values
    of an op-by-op one."""
    x = jax.random.normal(key, shape, jnp.float32)
    return (jnp.where(jnp.isnan(x), 0.0, x) * scale).astype(dtype)


def dense_init(key, d_in: int, d_out: int, dtype, scale: float = 0.02):
    return normal_init(key, (d_in, d_out), dtype, scale)


def init_attention(key, cfg, dtype, hq: int, hkv: int) -> Params:
    ks = jax.random.split(key, 5)
    d, dh = cfg.d_model, cfg.d_head
    p = {
        "wq": dense_init(ks[0], d, hq * dh, dtype),
        "wk": dense_init(ks[1], d, hkv * dh, dtype),
        "wv": dense_init(ks[2], d, hkv * dh, dtype),
        "wo": dense_init(ks[3], hq * dh, d, dtype,
                         scale=0.02 / math.sqrt(2 * cfg.num_layers)),
    }
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((hq * dh,), dtype)
        p["bk"] = jnp.zeros((hkv * dh,), dtype)
        p["bv"] = jnp.zeros((hkv * dh,), dtype)
    return p


def qkv_project(p: Params, x: jax.Array, hq: int, hkv: int, dh: int):
    b, s, _ = x.shape
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    return (q.reshape(b, s, hq, dh), k.reshape(b, s, hkv, dh),
            v.reshape(b, s, hkv, dh))


# ---------------------------------------------------------------------------
# Blocked causal attention (training / prefill)
# ---------------------------------------------------------------------------
def blocked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool = True, window: int = 0,
                      q_block: int = 512, kv_block: int = 512,
                      q_offset: int = 0) -> jax.Array:
    """Flash-style online-softmax attention, O(S * block) memory.

    q: (B, Sq, Hq, D); k, v: (B, Skv, Hkv, D) with Hq = G * Hkv.
    ``window`` > 0 restricts attention to the last ``window`` positions
    (RecurrentGemma local attention).  ``q_offset`` is the absolute position
    of q[0] relative to k[0] (prefill continuation).
    """
    b, sq, hq, d = q.shape
    _, skv, hkv, _ = k.shape
    g = hq // hkv
    qb = min(q_block, sq)
    kb = min(kv_block, skv)
    # Ragged tails are padded to a block multiple and masked out (the k-side
    # via the position mask below; the q-side by slicing the output).
    sq_pad = -(-sq // qb) * qb
    skv_pad = -(-skv // kb) * kb
    if sq_pad != sq:
        q = jnp.pad(q, ((0, 0), (0, sq_pad - sq), (0, 0), (0, 0)))
    if skv_pad != skv:
        kpad = ((0, 0), (0, skv_pad - skv), (0, 0), (0, 0))
        k, v = jnp.pad(k, kpad), jnp.pad(v, kpad)
    nq, nk = sq_pad // qb, skv_pad // kb
    scale = 1.0 / math.sqrt(d)

    qr = q.reshape(b, nq, qb, hkv, g, d)
    kr = k.reshape(b, nk, kb, hkv, d)
    vr = v.reshape(b, nk, kb, hkv, d)
    q_pos_base = jnp.arange(qb)
    k_pos_base = jnp.arange(kb)

    def q_block_fn(qi):
        qblk = qr[:, qi].astype(jnp.float32) * scale      # (B,qb,Hkv,G,D)
        q_pos = q_offset + qi * qb + q_pos_base           # (qb,)

        @jax.checkpoint    # flash-style backward: recompute the (qb, kb)
        def kv_step(carry, ki):   # block scores instead of saving them
            m, l, acc = carry
            kblk = kr[:, ki].astype(jnp.float32)          # (B,kb,Hkv,D)
            vblk = vr[:, ki].astype(jnp.float32)
            s_ = jnp.einsum("bqhgd,bkhd->bqhgk", qblk, kblk)
            k_pos = ki * kb + k_pos_base                  # (kb,)
            mask = jnp.broadcast_to(k_pos[None, :] < skv, (qb, kb))
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window:
                mask &= k_pos[None, :] > q_pos[:, None] - window
            s_ = jnp.where(mask[None, :, None, None, :], s_, -jnp.inf)
            m_new = jnp.maximum(m, s_.max(axis=-1))
            # guard fully-masked rows
            m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
            p_ = jnp.exp(s_ - m_safe[..., None])
            p_ = jnp.where(mask[None, :, None, None, :], p_, 0.0)
            corr = jnp.where(jnp.isfinite(m), jnp.exp(m - m_safe), 0.0)
            l_new = l * corr + p_.sum(axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqhgk,bkhd->bqhgd", p_, vblk)
            return (m_new, l_new, acc_new), None

        init = (jnp.full((b, qb, hkv, g), -jnp.inf),
                jnp.zeros((b, qb, hkv, g)),
                jnp.zeros((b, qb, hkv, g, d)))
        (m, l, acc), _ = lax.scan(kv_step, init, jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return out                                        # (B,qb,Hkv,G,D)

    # checkpoint per q block: backward holds ONE q block's kv-scan carries
    # at a time instead of the (nq x nk) stack (see EXPERIMENTS.md §Perf)
    q_block_fn = jax.checkpoint(q_block_fn)
    outs = lax.map(q_block_fn, jnp.arange(nq))            # (nq,B,qb,Hkv,G,D)
    outs = jnp.moveaxis(outs, 0, 1)                       # (B,nq,qb,...)
    return outs.reshape(b, sq_pad, hq, d)[:, :sq].astype(q.dtype)


# ---------------------------------------------------------------------------
# Decode attention against a KV cache (single-device logical form; the
# sequence-sharded distributed version wraps `decode_attention_core`)
# ---------------------------------------------------------------------------
def decode_attention_core(q: jax.Array, k_cache: jax.Array,
                          v_cache: jax.Array, valid: jax.Array):
    """Partial-softmax attention over one cache shard.

    q: (B, Hq, D); k/v_cache: (B, S, Hkv, D); valid: (B, S) bool.
    Returns (acc, lse, m): un-normalized output + log-sum-exp stats so that
    shards can be combined exactly (paper's IS-S split of the AV operator's
    K = context dimension).
    """
    b, hq, d = q.shape
    _, s, hkv, _ = k_cache.shape
    g = hq // hkv
    scale = 1.0 / math.sqrt(d)
    qr = (q.reshape(b, hkv, g, d).astype(jnp.float32)) * scale
    # keep the cache in bf16 — casting it to f32 doubles the resident KV
    # bytes transiently (§Perf iteration 16); accumulate in f32 instead
    s_ = jnp.einsum("bhgd,bshd->bhgs", qr.astype(k_cache.dtype), k_cache,
                    preferred_element_type=jnp.float32)
    s_ = s_ * jnp.float32(1.0)
    s_ = jnp.where(valid[:, None, None, :], s_, -jnp.inf)
    m = s_.max(axis=-1)                                   # (B,Hkv,G)
    m_safe = jnp.where(jnp.isfinite(m), m, 0.0)
    p = jnp.where(valid[:, None, None, :], jnp.exp(s_ - m_safe[..., None]), 0.0)
    l = p.sum(axis=-1)
    acc = jnp.einsum("bhgs,bshd->bhgd", p.astype(v_cache.dtype), v_cache,
                     preferred_element_type=jnp.float32)
    return acc, l, m_safe


def decode_attention(q, k_cache, v_cache, lengths):
    """q: (B,Hq,D); caches (B,S,Hkv,D); lengths: (B,) valid prefix lengths."""
    b, s = k_cache.shape[0], k_cache.shape[1]
    valid = jnp.arange(s)[None, :] < lengths[:, None]
    acc, l, _ = decode_attention_core(q, k_cache, v_cache, valid)
    out = acc / jnp.maximum(l, 1e-20)[..., None]
    bq, hkv, g, d = out.shape
    return out.reshape(bq, hkv * g, d).astype(q.dtype)


def paged_decode_attention(q, k_pool, v_pool, tables, lengths):
    """Reference decode attention through a block table.

    q: (B, Hq, D); pools: (P+1, page, Hkv, D) with page P a scratch page;
    tables: (B, nblk) page ids (unmapped entries point at the scratch
    page); lengths: (B,).  Gathers the slots' pages into a contiguous view
    and runs the standard masked decode attention — the Pallas paged
    flash-decode kernel replaces this without materializing the gather.
    """
    b = q.shape[0]
    nblk = tables.shape[1]
    ps = k_pool.shape[1]
    k = k_pool[tables].reshape(b, nblk * ps, *k_pool.shape[2:])
    v = v_pool[tables].reshape(b, nblk * ps, *v_pool.shape[2:])
    return decode_attention(q, k, v, lengths)


# ---------------------------------------------------------------------------
# FFN
# ---------------------------------------------------------------------------
def init_ffn(key, d_model: int, d_ff: int, gated: bool, dtype,
             num_layers: int = 24) -> Params:
    ks = jax.random.split(key, 3)
    p = {"w_up": dense_init(ks[0], d_model, d_ff, dtype),
         "w_down": dense_init(ks[1], d_ff, d_model, dtype,
                              scale=0.02 / math.sqrt(2 * num_layers))}
    if gated:
        p["w_gate"] = dense_init(ks[2], d_model, d_ff, dtype)
    return p


def _act(name: str, x):
    return jax.nn.silu(x) if name == "silu" else jax.nn.gelu(x)


def apply_ffn(p: Params, x: jax.Array, act: str) -> jax.Array:
    h = x @ p["w_up"]
    if "w_gate" in p:
        h = _act(act, x @ p["w_gate"]) * h
    else:
        h = _act(act, h)
    return h @ p["w_down"]


# ---------------------------------------------------------------------------
# MoE: capacity-grouped dispatch (sort-based, no T x E x C one-hot)
# ---------------------------------------------------------------------------
def init_moe(key, cfg, dtype) -> Params:
    ks = jax.random.split(key, 5)
    e, d, f = cfg.num_experts, cfg.d_model, cfg.d_ff_expert
    scale_down = 0.02 / math.sqrt(2 * cfg.num_layers)
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_up": normal_init(ks[1], (e, d, f), dtype, 0.02),
        "w_gate": normal_init(ks[2], (e, d, f), dtype, 0.02),
        "w_down": normal_init(ks[3], (e, f, d), dtype, scale_down),
    }
    if cfg.num_shared_experts:
        p["shared"] = init_ffn(ks[4], d,
                               cfg.d_ff_expert * cfg.num_shared_experts,
                               cfg.gated_ffn, dtype, cfg.num_layers)
    return p


def moe_capacity(tokens: int, num_experts: int, topk: int,
                 factor: float) -> int:
    c = max(1, int(math.ceil(tokens * topk / num_experts * factor)))
    return -(-c // 16) * 16   # multiple of 16 so C can shard over data axes


def seq_constraint(x: jax.Array) -> jax.Array:
    """Megatron-style sequence parallelism: constrain a (B, S, d) residual
    stream to shard S over "model" (on top of B over the data axes).  Applied
    to the layer-scan carry, it divides the per-layer remat save — the
    dominant train-time memory term — by the TP degree; GSPMD inserts the
    all-gather before attention/FFN and the reduce-scatter after."""
    from repro.distributed import context
    from repro.launch.mesh import data_axes
    mesh = context.current_mesh()
    if mesh is None or "model" not in mesh.axis_names or x.ndim != 3:
        return x
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    b, s, _ = x.shape
    if s % mesh.shape["model"] or (dsize > 1 and b % dsize):
        return x
    from jax.sharding import NamedSharding, PartitionSpec as P
    dp = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(dp, "model", None)))


def _data_chunks(t: int) -> int:
    """Number of data shards to chunk the MoE dispatch over (1 off-mesh)."""
    from repro.distributed import context
    from repro.launch.mesh import data_axes
    mesh = context.current_mesh()
    if mesh is None:
        return 1
    dsize = 1
    for a in data_axes(mesh):
        dsize *= mesh.shape[a]
    return dsize if dsize > 1 and t % dsize == 0 else 1


def _moe_constraint(ge: jax.Array) -> jax.Array:
    """Pin the (X, E, C, d) dispatch tensor to expert-parallel sharding when
    a mesh context is active: chunk axis X over the data axes, E over
    "model".  Chunk-local dispatch means GSPMD never has to move tokens —
    activations are model-replicated going in, so every device builds its
    own chunk x expert slice with zero collectives."""
    from repro.distributed import context
    mesh = context.current_mesh()
    if mesh is None or "model" not in mesh.axis_names:
        return ge
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import data_axes
    x, e, _, _ = ge.shape
    espec = "model" if e % mesh.shape["model"] == 0 else None
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    xspec = (daxes if len(daxes) > 1 else daxes[0]) \
        if (daxes and x % dsize == 0) else None
    return lax.with_sharding_constraint(
        ge, NamedSharding(mesh, P(xspec, espec, None, None)))


def _local_ranks(flat_e: jax.Array, n: int, e: int) -> jax.Array:
    """Rank of each (token, k) pair within its expert group (stable)."""
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    first = jnp.searchsorted(sorted_e, jnp.arange(e))
    rank_sorted = jnp.arange(n) - first[sorted_e]
    return jnp.zeros((n,), jnp.int32).at[order].set(
        rank_sorted.astype(jnp.int32))


def _apply_moe_shardmap(p: Params, x: jax.Array, cfg, mesh) -> jax.Array:
    """Expert-parallel MoE under shard_map: fully local dispatch/combine +
    ONE psum over the expert-sharded "model" axis.

    Each (data, model) device sees its local tokens (replicated over
    "model") and its E/TP expert slice.  Dispatch ranks are computed
    locally; tokens routed to non-local experts or past capacity land in a
    local trash row (exact semantics, no GSPMD scatter across shards).
    The partial expert outputs are summed with lax.psum — the Megatron-EP
    combine, and the paper's Fig. 9 RS/AG stage.
    """
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import data_axes
    daxes = data_axes(mesh)
    dp = daxes if len(daxes) > 1 else daxes[0]
    tp = mesh.shape["model"]
    e, k = cfg.num_experts, cfg.topk
    el = e // tp

    def local_moe(xl, router, w_up, w_gate, w_down):
        tl, d = xl.shape
        c = moe_capacity(tl, e, k, cfg.capacity_factor)
        logits = xl.astype(jnp.float32) @ router          # (tl, E)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, experts = lax.top_k(probs, k)
        weights = weights / jnp.maximum(weights.sum(-1, keepdims=True),
                                        1e-9)
        rank = _local_ranks(experts.reshape(-1), tl * k, e).reshape(tl, k)
        eloc = experts - lax.axis_index("model") * el     # local expert id
        ok = (eloc >= 0) & (eloc < el) & (rank < c)
        se = jnp.where(ok, eloc, el)    # el is out of bounds -> dropped
        # Per-k scatters keep the update operand at (tl, d) — never the
        # (tl*K, d) expansion — and the (expert, rank) pairs are unique by
        # construction, so XLA skips its sort-based deterministic-scatter
        # lowering (the 6 GiB u32 sort payloads of §Perf iteration 6).
        ge = jnp.zeros((el, c, d), xl.dtype)
        for j in range(k):
            ge = ge.at[se[:, j], rank[:, j]].set(
                xl, mode="drop", unique_indices=True)
        up = jnp.einsum("ecd,edf->ecf", ge, w_up)
        gate = jnp.einsum("ecd,edf->ecf", ge, w_gate)
        h = _act(cfg.act, gate) * up
        out_e = jnp.einsum("ecf,efd->ecd", h, w_down)     # (el, C, d)
        y = jnp.zeros((tl, d), jnp.float32)
        for j in range(k):
            yj = out_e.at[se[:, j], rank[:, j]].get(
                mode="fill", fill_value=0)                # (tl, d)
            wj = jnp.where(ok[:, j], weights[:, j], 0.0)
            y = y + yj.astype(jnp.float32) * wj[:, None]
        return lax.psum(y.astype(xl.dtype), "model")

    wspec = P(None, "model", None, None) if p["w_up"].ndim == 4 \
        else P("model", None, None)
    return jax.shard_map(
        local_moe, mesh=mesh,
        in_specs=(P(dp, None), P(None, None), wspec, wspec,
                  P(None, "model", None, None) if p["w_down"].ndim == 4
                  else P("model", None, None)),
        out_specs=P(dp, None),
        check_vma=False)(x, p["router"], p["w_up"], p["w_gate"],
                         p["w_down"])


def apply_moe(p: Params, x: jax.Array, cfg) -> jax.Array:
    """x: (T, d_model) flattened tokens -> (T, d_model).

    Chunk-local, sort-based capacity dispatch.  Tokens are split into one
    chunk per data shard; each chunk ranks its own (token, k) pairs and
    scatters into its own (E, C_local, d) slice with OOB-drop overflow.
    Ranks never cross chunks, so there is NO global argsort — under GSPMD
    the whole dispatch stays device-local (activations arrive replicated
    over "model"), and the only MoE collective left per layer is the
    (T_local, d) partial-sum combine over the expert-sharded model axis.
    Capacity is enforced per chunk (C_local = ceil(T_local*k/E * factor)),
    the standard per-device capacity semantics of TPU MoE stacks.

    When a mesh context is active and shapes divide, the shard_map
    implementation above is used instead (explicitly local + one psum).
    """
    from repro.distributed import context
    mesh = context.current_mesh()
    if (mesh is not None and "model" in mesh.axis_names
            and cfg.num_experts % mesh.shape["model"] == 0
            and _data_chunks(x.shape[0]) > 1 and "shared" not in p):
        return _apply_moe_shardmap(p, x, cfg, mesh).astype(x.dtype)
    t, d = x.shape
    e, k = cfg.num_experts, cfg.topk
    nx = _data_chunks(t)
    tl = t // nx
    c = moe_capacity(tl, e, k, cfg.capacity_factor)

    logits = (x.astype(jnp.float32) @ p["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(probs, k)                # (T, K)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)

    xr = x.reshape(nx, tl, d)
    er = experts.reshape(nx, tl, k)
    wr = weights.reshape(nx, tl, k)

    def _ranks(ec):                                       # (tl, K) -> (tl*K,)
        flat_e = ec.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        first = jnp.searchsorted(sorted_e, jnp.arange(e))
        rank_sorted = jnp.arange(tl * k) - first[sorted_e]
        return jnp.zeros((tl * k,), jnp.int32).at[order].set(
            rank_sorted.astype(jnp.int32))

    rank = jax.vmap(_ranks)(er)                           # (X, tl*K)
    token_idx = jnp.repeat(jnp.arange(tl), k)             # (tl*K,)

    def _dispatch(xc, ec, rk):
        return jnp.zeros((e, c, d), x.dtype).at[
            ec.reshape(-1), rk].set(xc[token_idx], mode="drop")

    ge = _moe_constraint(jax.vmap(_dispatch)(xr, er, rank))  # (X, E, C, d)

    up = jnp.einsum("xecd,edf->xecf", ge, p["w_up"])
    gate = jnp.einsum("xecd,edf->xecf", ge, p["w_gate"])
    h = _act(cfg.act, gate) * up
    out_e = _moe_constraint(
        jnp.einsum("xecf,efd->xecd", h, p["w_down"]))     # (X, E, C, d)

    def _combine(oc, ec, rk, wc):
        y = oc.at[ec.reshape(-1), rk].get(mode="fill", fill_value=0)
        y = y * wc.reshape(-1)[:, None].astype(oc.dtype)
        return y.reshape(tl, k, d).sum(axis=1)            # (tl, d)

    y = jax.vmap(_combine)(out_e, er, rank, wr).reshape(t, d)
    if "shared" in p:
        y = y + apply_ffn(p["shared"], x, cfg.act)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# Embedding + chunked cross-entropy
# ---------------------------------------------------------------------------
def init_embed(key, vocab: int, d_model: int, dtype, tie: bool) -> Params:
    ks = jax.random.split(key, 2)
    p = {"table": dense_init(ks[0], vocab, d_model, dtype, scale=0.02)}
    if not tie:
        p["head"] = dense_init(ks[1], d_model, vocab, dtype, scale=0.02)
    return p


def embed(p: Params, tokens: jax.Array) -> jax.Array:
    out = jnp.take(p["table"], tokens, axis=0)
    return _embed_constraint(out)


_EMBED_CONSTRAINT = [True]   # disabled under microbatch scans (XLA SPMD
#                              partitioner rejects the gather+constraint
#                              combination inside a while body)


def _embed_constraint(x: jax.Array) -> jax.Array:
    """Keep the embedding output d-sharded over "model" (matching the
    d-sharded table) so the backward scatter-add produces a (V, d/TP)
    shard instead of a full replicated f32 (V, d) gradient buffer
    (§Perf iteration 10)."""
    from repro.distributed import context
    from repro.launch.mesh import data_axes
    mesh = context.current_mesh()
    if mesh is None or "model" not in mesh.axis_names \
            or not _EMBED_CONSTRAINT[0]:
        return x
    d = x.shape[-1]
    if d % mesh.shape["model"]:
        return x
    daxes = data_axes(mesh)
    dsize = 1
    for a in daxes:
        dsize *= mesh.shape[a]
    dp = daxes if len(daxes) > 1 else (daxes[0] if daxes else None)
    lead = [None] * (x.ndim - 1)
    if x.shape[0] % max(dsize, 1) == 0 and dp is not None:
        lead[0] = dp
    from jax.sharding import NamedSharding, PartitionSpec as P
    return lax.with_sharding_constraint(
        x, NamedSharding(mesh, P(*lead, "model")))


def unembed(p: Params, h: jax.Array) -> jax.Array:
    w = p.get("head")
    if w is None:
        w = p["table"].T
    return h @ w


def lm_loss_chunked(p_embed: Params, h: jax.Array, labels: jax.Array,
                    mask: Optional[jax.Array] = None,
                    chunk: int = 512) -> jax.Array:
    """Cross-entropy over the (potentially huge, vocab-sharded) head without
    materializing (B, S, V) logits: scan over sequence chunks."""
    b, s, d = h.shape
    chunk = min(chunk, s)
    assert s % chunk == 0
    n = s // chunk
    w = p_embed.get("head")
    if w is None:
        w = p_embed["table"].T
    if mask is None:
        mask = jnp.ones((b, s), jnp.float32)
    hr = jnp.asarray(h).reshape(b, n, chunk, d)
    lr = jnp.asarray(labels).reshape(b, n, chunk)
    mr = jnp.asarray(mask).reshape(b, n, chunk)

    @jax.checkpoint   # recompute the (B, chunk, V) logits in backward —
    def step(carry, i):  # saving them costs chunks x B x chunk x V x 4B
        tot, cnt = carry
        logits = (hr[:, i].astype(jnp.float32) @ w.astype(jnp.float32))
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lr[:, i][..., None],
                                   axis=-1)[..., 0]
        ce = (lse - gold) * mr[:, i]
        return (tot + ce.sum(), cnt + mr[:, i].sum()), None

    (tot, cnt), _ = lax.scan(step, (jnp.float32(0.0), jnp.float32(0.0)),
                             jnp.arange(n))
    return tot / jnp.maximum(cnt, 1.0)
