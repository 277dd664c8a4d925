"""Flash-decode attention Pallas kernel (GQA decode against a KV cache).

This is the paper's attention scheduling made TPU-native (§5b, DESIGN.md §4):
the context dimension S — the K dimension of the AV GEMM — is walked in
blocks (temporal partitioning, the ST axis) with an online-softmax
accumulator resident in VMEM (output-stationary), while the per-(request,
kv-head) grid axes give the head-level parallelism the paper maps across
PUs.  The group dimension G = Hq/Hkv is the small M: it is padded only to
the sublane granularity, exactly like SNAKE's M-granularity of 8.
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _sublane(dtype) -> int:
    return 16 if jnp.dtype(dtype) == jnp.bfloat16 else 8


def _round_up(x: int, g: int) -> int:
    return -(-x // g) * g


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
            block_s: int, s_steps: int, scale: float):
    bi = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (bs, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale  # (G,bs)
    pos = si * block_s + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < len_ref[bi]
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[:, :1]                          # (G, 1)
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)  # (G, bs)
    l_new = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new

    @pl.when(si == s_steps - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, :1], 1e-20)).astype(o_ref.dtype)


def _paged_kernel(tbl_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                  acc_ref, m_ref, l_ref, *, page: int, n_blocks: int,
                  scale: float):
    """Per-(request, kv-head, table-entry) program.

    The grid's S axis walks the slot's BLOCK TABLE instead of a contiguous
    context: the k/v BlockSpec index_map dereferences the scalar-prefetched
    table, so each step DMAs one page straight out of the pool — the paged
    cache is never materialized as a dense (B, S) view.  Unmapped entries
    point at the scratch page and are masked by ``lengths`` exactly like
    the padded tail in the contiguous kernel.
    """
    bi = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, D)
    k = k_ref[0, 0].astype(jnp.float32)            # (page, D)
    v = v_ref[0, 0].astype(jnp.float32)
    s = lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                        preferred_element_type=jnp.float32) * scale
    pos = si * page + lax.broadcasted_iota(jnp.int32, s.shape, 1)
    valid = pos < len_ref[bi]
    s = jnp.where(valid, s, NEG)

    m_prev = m_ref[:, :1]
    m_new = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
    corr = jnp.exp(m_prev - m_new)
    p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
    l_new = l_ref[:, :1] * corr + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = acc_ref[...] * corr + lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[:, :1] = m_new
    l_ref[:, :1] = l_new

    @pl.when(si == n_blocks - 1)
    def _finish():
        o_ref[0, 0] = (acc_ref[...]
                       / jnp.maximum(l_ref[:, :1], 1e-20)).astype(o_ref.dtype)


def paged_grid(q_shape, pool_shape, tables_shape) -> Tuple[int, int, int]:
    """The paged kernel's grid, ``(b, hkv, nblk)``, from the shapes of its
    operands: one program per request, KV head and block-table entry,
    whatever the lengths.  Each program reads one page of K and one of V."""
    return q_shape[0], pool_shape[2], tables_shape[1]


def paged_flash_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Flash decode through a block table (vLLM-style paged attention).

    q: (B, Hq, D); k_pool/v_pool: (P+1, page, Hkv, D) page pools whose last
    page is scratch; tables: (B, nblk) int32 page ids (unmapped -> scratch
    page); lengths: (B,) valid context per request.  Returns (B, Hq, D).

    On real TPUs the page size should be a multiple of the sublane count
    (8 fp32 / 16 bf16) so each page DMA is tile-aligned.
    """
    _, hq, d = q.shape
    page = k_pool.shape[1]
    grid = paged_grid(q.shape, k_pool.shape, tables.shape)
    b, hkv, nblk = grid
    g = hq // hkv
    gp = _round_up(g, _sublane(q.dtype))
    scale = 1.0 / math.sqrt(d)

    qr = q.reshape(b, hkv, g, d)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kt = jnp.moveaxis(k_pool, 2, 1)               # (P+1, Hkv, page, D)
    vt = jnp.moveaxis(v_pool, 2, 1)

    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # tables, lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, d),
                         lambda bi, hi, si, tbl, ln: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, hi, si, tbl, ln: (tbl[bi, si], hi, 0, 0)),
            pl.BlockSpec((1, 1, page, d),
                         lambda bi, hi, si, tbl, ln: (tbl[bi, si], hi, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d),
                               lambda bi, hi, si, tbl, ln: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, n_blocks=nblk,
                          scale=scale),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qr, kt, vt)
    return out[:, :, :g, :].reshape(b, hq, d)


def flash_decode(q: jax.Array, k: jax.Array, v: jax.Array,
                 lengths: jax.Array, block_s: int = 512,
                 interpret: bool = False) -> jax.Array:
    """q: (B, Hq, D); k/v: (B, S, Hkv, D); lengths: (B,) -> (B, Hq, D)."""
    b, hq, d = q.shape
    _, s, hkv, _ = k.shape
    g = hq // hkv
    gp = _round_up(g, _sublane(q.dtype))
    sp = _round_up(s, block_s)
    scale = 1.0 / math.sqrt(d)

    qr = q.reshape(b, hkv, g, d)
    qr = jnp.pad(qr, ((0, 0), (0, 0), (0, gp - g), (0, 0)))
    kt = jnp.moveaxis(k, 2, 1)                    # (B, Hkv, S, D)
    vt = jnp.moveaxis(v, 2, 1)
    kt = jnp.pad(kt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    vt = jnp.pad(vt, ((0, 0), (0, 0), (0, sp - s), (0, 0)))
    s_steps = sp // block_s
    grid = (b, hkv, s_steps)
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                    # lengths
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, gp, d),
                         lambda bi, hi, si, ln: (bi, hi, 0, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si, ln: (bi, hi, si, 0)),
            pl.BlockSpec((1, 1, block_s, d),
                         lambda bi, hi, si, ln: (bi, hi, si, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, gp, d),
                               lambda bi, hi, si, ln: (bi, hi, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((gp, d), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
            pltpu.VMEM((gp, 128), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kernel, block_s=block_s, s_steps=s_steps,
                          scale=scale),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, gp, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qr, kt, vt)
    return out[:, :, :g, :].reshape(b, hq, d)
