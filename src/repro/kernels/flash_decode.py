"""Flash-decode attention Pallas kernel (GQA decode against a KV cache).

This is the paper's attention scheduling made TPU-native (§5b, DESIGN.md §4):
the context dimension S — the K dimension of the AV GEMM — is walked in
blocks (temporal partitioning, the ST axis) with an online-softmax
accumulator resident in VMEM (output-stationary), while the per-(request,
kv-head) grid axes give the head-level parallelism the paper maps across
PUs.  The group dimension G = Hq/Hkv is the small M: it is padded only to
the sublane granularity, exactly like SNAKE's M-granularity of 8.

The paged kernel walks a block table instead: one program per request
and block of table entries, every KV head at once, reading only the
pages that hold context straight from the page pool.
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


#: K+V bytes of one block of pages in VMEM, tile padding included; the
#: block is double-buffered, so the kernel holds twice this.
BLOCK_BYTES = 2 * 2**20


def pages_per_block(pool_shape, dtype, nblk: int) -> int:
    """Pages of one lane that one grid step of the paged kernel reads,
    from the page's VMEM bytes (``(page, Hkv, D)`` tiles padded to the
    sublane count and to 128 lanes) against ``BLOCK_BYTES``."""
    _, page, hkv, d = pool_shape
    page_bytes = (page * _round_up(hkv, _sublane(dtype)) * _round_up(d, 128)
                  * jnp.dtype(dtype).itemsize)
    return max(1, min(nblk, BLOCK_BYTES // (2 * page_bytes)))


def paged_grid(q_shape, pool_shape, tables_shape, dtype) -> Tuple[int, int]:
    """The paged kernel's grid, ``(b, ceil(nblk / ppb))``, from the shapes
    of its operands: one program per request and block of
    ``pages_per_block`` table entries.  A program whose block lies past
    the request's context fetches and computes nothing."""
    nblk = tables_shape[1]
    return q_shape[0], -(-nblk // pages_per_block(pool_shape, dtype, nblk))


def _paged_kernel(tbl_ref, len_ref, q_ref, k_hbm, v_hbm, o_ref,
                  kbuf, vbuf, sem, slot_ref, acc_ref, m_ref, l_ref, *,
                  page: int, ppb: int, lanes: int, steps: int,
                  scale: float):
    """Per-(request, block of table entries) program, all KV heads at once.

    The pools stay in HBM in their stored ``(P+1, page, Hkv, D)`` layout;
    each live page of the block is one async copy of K and one of V,
    addressed by the scalar-prefetched table.  The copies of the next live
    block (this lane's next, or the first block of the next lane that has
    context) are started before this block is computed, into the other
    half of the double buffer, so the grid's sequential order is the
    prefetch chain.  Positions at or past ``lengths`` — the last page's
    tail, unmapped entries — are masked by compare and select.
    """
    bi, si = pl.program_id(0), pl.program_id(1)
    nblk = tbl_ref.shape[1]

    def n_pages(lane):
        return jnp.minimum(pl.cdiv(len_ref[lane], page), nblk)

    def live_in_block(lane, blk):
        return jnp.minimum(ppb, n_pages(lane) - blk * ppb)

    def copies(lane, blk, slot, wait: bool):
        def page_copies(j, carry):
            pid = tbl_ref[lane, blk * ppb + j]
            for kv, (pool, buf) in enumerate(((k_hbm, kbuf), (v_hbm, vbuf))):
                src = pool.at[pid, :, :, pl.ds(0, buf.shape[-1])]
                cp = pltpu.make_async_copy(src, buf.at[slot, j],
                                           sem.at[kv, slot])
                if wait:
                    cp.wait()
                else:
                    cp.start()
            return carry

        lax.fori_loop(0, live_in_block(lane, blk), page_copies, 0)

    def first_lane_with_context(start):
        return lax.fori_loop(
            0, lanes,
            lambda j, c: jnp.where((j == c) & (len_ref[j] <= 0), c + 1, c),
            start)

    @pl.when((bi == 0) & (si == 0))
    def _start_chain():
        slot_ref[0] = 0
        first = first_lane_with_context(0)

        @pl.when(first < lanes)
        def _():
            copies(first, 0, 0, wait=False)

    @pl.when(si == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG)
        l_ref[...] = jnp.zeros_like(l_ref)

    @pl.when(si * ppb < n_pages(bi))
    def _block():
        slot = slot_ref[0]
        more = (si + 1) * ppb < n_pages(bi)
        nxt_lane = jnp.where(more, bi, first_lane_with_context(bi + 1))

        @pl.when(nxt_lane < lanes)
        def _prefetch():
            copies(nxt_lane, jnp.where(more, si + 1, 0), 1 - slot,
                   wait=False)

        copies(bi, si, slot, wait=True)
        q = q_ref[0].astype(jnp.float32) * scale    # (G, Hkv, D)
        d = q.shape[-1]
        length = len_ref[bi]

        def page_step(j, carry):
            k = kbuf[slot, j, :, :, :d].astype(jnp.float32)  # (page,Hkv,D)
            v = vbuf[slot, j, :, :, :d].astype(jnp.float32)
            pos = (si * ppb + j) * page + lax.broadcasted_iota(
                jnp.int32, (page, 1, 1), 0)
            valid = pos < length
            for g in range(q.shape[0]):
                s = jnp.sum(k * q[g], axis=-1, keepdims=True)  # (page,Hkv,1)
                s = jnp.where(valid, s, NEG)
                m_prev = m_ref[g]                               # (Hkv, 1)
                m_new = jnp.maximum(m_prev, s.max(axis=0))
                corr = jnp.exp(m_prev - m_new)
                p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
                l_ref[g] = l_ref[g] * corr + p.sum(axis=0)
                acc_ref[g] = acc_ref[g] * corr + jnp.sum(p * v, axis=0)
                m_ref[g] = m_new
            return carry

        lax.fori_loop(0, live_in_block(bi, si), page_step, 0)
        slot_ref[0] = 1 - slot

    @pl.when(si == steps - 1)
    def _finish():
        o_ref[0] = (acc_ref[...]
                    / jnp.maximum(l_ref[...], 1e-20)).astype(o_ref.dtype)


def paged_flash_decode(q: jax.Array, k_pool: jax.Array, v_pool: jax.Array,
                       tables: jax.Array, lengths: jax.Array,
                       interpret: bool = False) -> jax.Array:
    """Flash decode through a block table (vLLM-style paged attention).

    q: (B, Hq, D); k_pool/v_pool: (P+1, page, Hkv, D) page pools whose last
    page is scratch; tables: (B, nblk) int32 page ids (unmapped -> scratch
    page); lengths: (B,) valid context per request.  Returns (B, Hq, D).

    Each request walks only the ``ceil(lengths / page)`` table entries that
    hold context, ``pages_per_block`` pages and every KV head per grid
    step, read straight from the pools' stored layout.
    """
    _, hq, d = q.shape
    _, page, hkv, _ = k_pool.shape
    nblk = tables.shape[1]
    b, steps = paged_grid(q.shape, k_pool.shape, tables.shape, k_pool.dtype)
    ppb = pages_per_block(k_pool.shape, k_pool.dtype, nblk)
    g = hq // hkv
    # compiled, a page's DMA moves whole (sublane, 128-lane) tiles: a head
    # dim short of a lane multiple is copied with the tiles' padding, which
    # the compute slices off
    lanes_d = d if interpret else _round_up(d, 128)
    qr = q.reshape(b, hkv, g, d).transpose(0, 2, 1, 3)   # (B, G, Hkv, D)
    blk = pl.BlockSpec((1, g, hkv, d), lambda bi, si, tbl, ln: (bi, 0, 0, 0))
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                    # tables, lengths
        grid=(b, steps),
        in_specs=[blk, pl.BlockSpec(memory_space=pl.ANY),
                  pl.BlockSpec(memory_space=pl.ANY)],
        out_specs=blk,
        scratch_shapes=[
            pltpu.VMEM((2, ppb, page, hkv, lanes_d), k_pool.dtype),
            pltpu.VMEM((2, ppb, page, hkv, lanes_d), v_pool.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),
            pltpu.SMEM((1,), jnp.int32),          # buffer half in use
            pltpu.VMEM((g, hkv, d), jnp.float32),
            pltpu.VMEM((g, hkv, 1), jnp.float32),
            pltpu.VMEM((g, hkv, 1), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_kernel, page=page, ppb=ppb, lanes=b,
                          steps=steps, scale=1.0 / math.sqrt(d)),
        grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((b, g, hkv, d), q.dtype),
        # the prefetch chain runs across lanes: the grid is sequential
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), qr, k_pool,
      v_pool)
    return out.transpose(0, 2, 1, 3).reshape(b, hq, d)


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
