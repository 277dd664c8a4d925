"""Jit'd public wrappers for the Pallas kernels.

The kernels are compiled for the TPU.  On the CPU backend, where the
tests run, they run in Pallas interpret mode; any other backend is an
error.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_decode import flash_decode as _flash_decode
from repro.kernels.flash_decode import paged_flash_decode as _paged_flash
from repro.kernels.snake_gemm import (GemmMapping, choose_mapping,
                                      snake_decode_gemm as _snake_gemm)
from repro.kernels.wkv6 import wkv6 as _wkv6


def _interpret() -> bool:
    backend = jax.default_backend()
    if backend not in ("cpu", "tpu"):
        raise RuntimeError(f"Pallas kernels target the TPU (interpret mode "
                           f"on the CPU); backend {backend!r} has neither")
    return backend == "cpu"


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_gemm(a: jax.Array, b: jax.Array, interpret: bool = None):
    """Shape-adaptive small-M GEMM: a (M, K) @ b (K, N)."""
    interp = _interpret() if interpret is None else interpret
    return _snake_gemm(a, b, interpret=interp)


def decode_gemm_mapping(m: int, n: int, k: int, dtype=jnp.bfloat16):
    return choose_mapping(m, n, k, dtype)


@functools.partial(jax.jit, static_argnames=("block_s", "interpret"))
def attention_decode(q, k, v, lengths, block_s: int = 512,
                     interpret: bool = None):
    """GQA flash-decode: q (B,Hq,D) against (B,S,Hkv,D) caches."""
    interp = _interpret() if interpret is None else interpret
    return _flash_decode(q, k, v, lengths, block_s=block_s, interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def attention_decode_paged(q, k_pool, v_pool, tables, lengths,
                           interpret: bool = None):
    """GQA flash-decode through a block table: q (B,Hq,D) against page
    pools (P+1,page,Hkv,D) mapped by tables (B,nblk)."""
    interp = _interpret() if interpret is None else interpret
    return _paged_flash(q, k_pool, v_pool, tables, lengths,
                        interpret=interp)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wkv6_scan(r, k, v, w, u, state0, interpret: bool = None):
    """RWKV6 recurrence with VMEM-resident state."""
    interp = _interpret() if interpret is None else interpret
    return _wkv6(r, k, v, w, u, state0, interpret=interp)
