"""Pallas kernel micro-benchmarks against the jnp reference.

The kernels run as ``kernels.ops`` chooses: compiled on the TPU, in
interpret mode on the CPU.  CPU wall-clock of interpret-mode Pallas is NOT
a TPU performance statement —
what matters here is (a) correctness against the ref.py oracle and (b) the
chosen block mappings (the TPU-native analogue of SNAKE's logical array
shapes), which are printed as derived metrics.
"""
from __future__ import annotations

from typing import List

import jax
import jax.numpy as jnp

from benchmarks.common import Row
from repro.kernels import ops, ref


def run() -> List[Row]:
    rows: List[Row] = []
    key = jax.random.PRNGKey(0)

    # snake decode_gemm: shape-adaptive small-M GEMM
    for m, n, k in ((8, 2048, 1024), (32, 4096, 2048)):
        ka, kb = jax.random.split(jax.random.fold_in(key, m))
        a = jax.random.normal(ka, (m, k), jnp.float32)
        b = jax.random.normal(kb, (k, n), jnp.float32)
        out = ops.decode_gemm(a, b)
        want = ref.decode_gemm_ref(a, b)
        err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                    - want.astype(jnp.float32))))
        rows.append(Row(f"kernels/decode_gemm_{m}x{n}x{k}_maxerr", err,
                        note="kernel vs jnp oracle"))
        mp = ops.decode_gemm_mapping(m, n, k, jnp.float32)
        rows.append(Row(f"kernels/decode_gemm_{m}x{n}x{k}_block_n",
                        float(mp.block_n),
                        note=f"dataflow={mp.dataflow}"))

    # flash decode attention
    b_, s, hkv, g, d = 2, 1024, 2, 4, 64
    kq, kk, kv = jax.random.split(key, 3)
    q = jax.random.normal(kq, (b_, hkv * g, d), jnp.float32)
    kc = jax.random.normal(kk, (b_, s, hkv, d), jnp.float32)
    vc = jax.random.normal(kv, (b_, s, hkv, d), jnp.float32)
    lengths = jnp.array([s, s // 2], jnp.int32)
    out = ops.attention_decode(q, kc, vc, lengths)
    want = ref.flash_decode_ref(q, kc, vc, lengths)
    rows.append(Row("kernels/flash_decode_maxerr",
                    float(jnp.max(jnp.abs(out - want)))))

    # wkv6 recurrence
    bw, t, h, dh = 2, 128, 2, 32
    ks = jax.random.split(key, 5)
    r_ = jax.random.normal(ks[0], (bw, t, h, dh), jnp.float32) * 0.3
    kk_ = jax.random.normal(ks[1], (bw, t, h, dh), jnp.float32) * 0.3
    vv = jax.random.normal(ks[2], (bw, t, h, dh), jnp.float32) * 0.3
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (bw, t, h, dh),
                                         jnp.float32)) * 0.9
    u = jax.random.normal(ks[4], (h, dh), jnp.float32) * 0.1
    s0 = jnp.zeros((bw, h, dh, dh), jnp.float32)
    out, _ = ops.wkv6_scan(r_, kk_, vv, w, u, s0)
    want, _ = ref.wkv6_ref(r_, kk_, vv, w, u, s0)
    rows.append(Row("kernels/wkv6_maxerr",
                    float(jnp.max(jnp.abs(out - want)))))
    return rows
