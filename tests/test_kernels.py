"""Pallas kernel validation: interpret-mode allclose vs ref.py oracles over a
shape x dtype sweep, including ragged/padded edges."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_decode import flash_decode
from repro.kernels.snake_gemm import choose_mapping, snake_decode_gemm
from repro.kernels.wkv6 import wkv6

GEMM_SHAPES = [
    (1, 128, 128), (8, 512, 256), (8, 2048, 8192), (13, 257, 129),
    (16, 4096, 512), (32, 300, 5000), (64, 1024, 2048), (100, 640, 384),
]
DTYPES = [jnp.float32, jnp.bfloat16]


def _tol(dtype):
    # f32 tolerance allows blocked-K reassociation at K up to 16k
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=5e-3, atol=1e-3)


@pytest.mark.parametrize("m,n,k", GEMM_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_snake_gemm_matches_oracle(m, n, k, dtype):
    key = jax.random.PRNGKey(m + n + k)
    a = jax.random.normal(key, (m, k), jnp.float32).astype(dtype)
    b = jax.random.normal(jax.random.fold_in(key, 1), (k, n),
                          jnp.float32).astype(dtype)
    out = snake_decode_gemm(a, b, interpret=True)
    want = ref.decode_gemm_ref(a, b)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_mapping_follows_paper_rule():
    """IS when N > K and resident A feasible; OS when K >= N (paper §3.1)."""
    assert choose_mapping(8, 28672, 8192, jnp.float32).dataflow == "IS"
    assert choose_mapping(8, 8192, 28672, jnp.float32).dataflow == "OS"
    # M padded to sublane granularity only (SNAKE granularity analogue)
    assert choose_mapping(3, 1024, 1024, jnp.float32).block_m == 8
    assert choose_mapping(3, 1024, 1024, jnp.bfloat16).block_m == 16


def test_mapping_blocks_fit_vmem():
    from repro.kernels.snake_gemm import VMEM_BUDGET
    for (m, n, k) in GEMM_SHAPES:
        for dt in DTYPES:
            mp = choose_mapping(m, n, k, dt)
            es = jnp.dtype(dt).itemsize
            if mp.dataflow == "IS":
                used = (mp.block_m * k + k * mp.block_n
                        + mp.block_m * mp.block_n) * es
            else:
                used = (mp.block_m * mp.block_k
                        + mp.block_k * mp.block_n) * es \
                    + mp.block_m * mp.block_n * 4
            assert used <= VMEM_BUDGET, (m, n, k, dt, mp)


FD_SHAPES = [
    (2, 8, 2, 64, 512), (1, 32, 4, 128, 2048), (3, 12, 12, 64, 600),
    (2, 16, 1, 256, 300), (1, 128, 128, 64, 256),
]


@pytest.mark.parametrize("b,hq,hkv,d,s", FD_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_flash_decode_matches_oracle(b, hq, hkv, d, s, dtype):
    key = jax.random.PRNGKey(b * hq + s)
    q = jax.random.normal(key, (b, hq, d), jnp.float32).astype(dtype)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d),
                          jnp.float32).astype(dtype)
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d),
                          jnp.float32).astype(dtype)
    lengths = jnp.asarray([max(1, s - 13 * i) for i in range(b)], jnp.int32)
    out = flash_decode(q, k, v, lengths, block_s=256, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


def test_flash_decode_ragged_lengths():
    """Every request attends to exactly its own prefix."""
    b, hq, hkv, d, s = 4, 4, 2, 64, 256
    key = jax.random.PRNGKey(0)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    k = jax.random.normal(jax.random.fold_in(key, 1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.fold_in(key, 2), (b, s, hkv, d))
    lengths = jnp.asarray([1, 17, 128, 256], jnp.int32)
    out = flash_decode(q, k, v, lengths, block_s=128, interpret=True)
    want = ref.flash_decode_ref(q, k, v, lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    # corrupting KV beyond the valid prefix must not change the output
    k2 = k.at[:, 200:].set(99.0)
    out2 = flash_decode(q, k2, v, jnp.asarray([1, 17, 128, 200], jnp.int32),
                        block_s=128, interpret=True)
    want2 = ref.flash_decode_ref(q, k2, v,
                                 jnp.asarray([1, 17, 128, 200], jnp.int32))
    np.testing.assert_allclose(np.asarray(out2), np.asarray(want2),
                               rtol=2e-5, atol=2e-5)


def test_paged_grid_is_the_grid_the_paged_kernel_runs(monkeypatch):
    """``paged_grid`` (which the engine's grid counters use) is the grid
    that ``paged_flash_decode`` hands to Pallas: one program per request
    and block of ``pages_per_block`` table entries, whatever the
    lengths."""
    from repro.kernels import flash_decode as FD
    b, hq, hkv, d, page, nblk, pages = 3, 8, 2, 64, 16, 37, 40
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (b, hq, d), jnp.float32)
    pool = jax.random.normal(jax.random.fold_in(key, 1),
                             (pages + 1, page, hkv, d), jnp.float32)
    tables = jnp.asarray(np.arange(b * nblk).reshape(b, nblk) % pages,
                         jnp.int32)
    lengths = jnp.asarray([1, 40, nblk * page], jnp.int32)
    grids = []
    real = FD.pl.pallas_call

    def spy(*args, grid_spec, **kw):
        grids.append(tuple(grid_spec.grid))
        return real(*args, grid_spec=grid_spec, **kw)
    monkeypatch.setattr(FD.pl, "pallas_call", spy)
    out = FD.paged_flash_decode(q, pool, pool, tables, lengths,
                                interpret=True)
    # 16 f32 pages of (8-sublane, 128-lane) tiles fill the 2 MiB block
    ppb = FD.pages_per_block(pool.shape, pool.dtype, nblk)
    assert ppb == 16
    assert grids == [(b, 3)]
    assert FD.paged_grid(q.shape, pool.shape, tables.shape,
                         pool.dtype) == (b, -(-nblk // ppb))
    want = ref.flash_decode_ref(
        q, pool[tables].reshape(b, nblk * page, hkv, d),
        pool[tables].reshape(b, nblk * page, hkv, d), lengths)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-5, atol=2e-5)


# (hq, hkv, d): StableLM's MHA (G 1, D 80), Yi-6B's groups (G 8, D 128),
# and G 4 at D 64
PAGED_SHAPES = [(4, 4, 80), (16, 2, 128), (8, 2, 64)]


@pytest.mark.parametrize("hq,hkv,d", PAGED_SHAPES)
@pytest.mark.parametrize("dtype", DTYPES)
def test_paged_kernel_reads_only_live_context(hq, hkv, d, dtype):
    """Lengths at every page and block edge, a length-0 lane on an
    all-scratch row, permuted tables with a page shared by two lanes, and
    garbage in dead pages, page tails and the scratch page: the kernel
    matches the reference on each lane's context and the garbage moves
    no output bit."""
    from repro.kernels import flash_decode as FD
    page, nblk, pages = 16, 35, 64
    ppb = FD.pages_per_block((pages + 1, page, hkv, d), dtype, nblk)
    assert nblk > 2 * ppb                         # three blocks, the last
    lengths = np.array([1, page - 1, page, page + 1,   # partial
                        ppb * page + 40, nblk * page, 0])
    b = len(lengths)
    rng = np.random.default_rng(hq * d)
    n_live = -(-lengths // page)
    perm = iter(rng.permutation(pages))
    tables = np.full((b, nblk), pages)            # unmapped -> scratch
    for i, n in enumerate(n_live):
        tables[i, :n] = [next(perm) for _ in range(n)]
    tables[5, 0] = tables[4, 0]                   # a page held by two lanes
    # dead entries of one lane point at pages of live context
    tables[3, n_live[3]:] = rng.integers(0, pages, nblk - n_live[3])
    key = jax.random.PRNGKey(hq + d)
    q = jax.random.normal(key, (b, hq, d), jnp.float32).astype(dtype)
    kp, vp = (jax.random.normal(jax.random.fold_in(key, i),
                                (pages + 1, page, hkv, d)).astype(dtype)
              for i in (1, 2))
    live = np.zeros((pages + 1, page), bool)      # (page id, offset) read
    for i, n in enumerate(lengths):
        pos = np.arange(n)
        live[tables[i, pos // page], pos % page] = True
    dead = jnp.asarray(~live)[:, :, None, None]
    tj, lj = jnp.asarray(tables, jnp.int32), jnp.asarray(lengths, jnp.int32)
    out = FD.paged_flash_decode(q, kp, vp, tj, lj, interpret=True)
    garbage = FD.paged_flash_decode(
        q, jnp.where(dead, jnp.asarray(3e4, dtype), kp),
        jnp.where(dead, jnp.asarray(-7e3, dtype), vp), tj, lj,
        interpret=True)
    np.testing.assert_array_equal(np.asarray(garbage), np.asarray(out))
    want = ref.flash_decode_ref(
        q, kp[tj].reshape(b, nblk * page, hkv, d),
        vp[tj].reshape(b, nblk * page, hkv, d), lj)
    tol = _tol(dtype) if dtype == jnp.bfloat16 else dict(rtol=2e-5,
                                                         atol=2e-5)
    np.testing.assert_allclose(np.asarray(out[:-1], np.float32),
                               np.asarray(want[:-1], np.float32), **tol)
    assert not np.asarray(out[-1], np.float32).any()   # no context: zeros


WKV_SHAPES = [(1, 16, 2, 32), (2, 33, 4, 64), (1, 8, 1, 128)]


@pytest.mark.parametrize("b,t,h,hs", WKV_SHAPES)
def test_wkv6_matches_oracle(b, t, h, hs):
    key = jax.random.PRNGKey(t * h)
    ks = jax.random.split(key, 6)
    r = jax.random.normal(ks[0], (b, t, h, hs), jnp.float32)
    k = jax.random.normal(ks[1], (b, t, h, hs), jnp.float32)
    v = jax.random.normal(ks[2], (b, t, h, hs), jnp.float32)
    w = jax.nn.sigmoid(jax.random.normal(ks[3], (b, t, h, hs))) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (h, hs), jnp.float32) * 0.1
    s0 = jax.random.normal(ks[5], (b, h, hs, hs), jnp.float32) * 0.1
    y, sT = wkv6(r, k, v, w, u, s0, interpret=True)
    yw, sw = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yw),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sw),
                               rtol=1e-4, atol=1e-4)


def test_wkv6_state_carry_composes():
    """wkv6(T1+T2) == wkv6(T2) . wkv6(T1) — chunked serving correctness."""
    b, t, h, hs = 1, 24, 2, 32
    key = jax.random.PRNGKey(7)
    ks = jax.random.split(key, 6)
    mk = lambda i: jax.random.normal(ks[i], (b, t, h, hs), jnp.float32)
    r, k, v = mk(0), mk(1), mk(2)
    w = jax.nn.sigmoid(mk(3)) * 0.5 + 0.4
    u = jax.random.normal(ks[4], (h, hs)) * 0.1
    s0 = jnp.zeros((b, h, hs, hs))
    y_full, s_full = wkv6(r, k, v, w, u, s0, interpret=True)
    t1 = 10
    y1, s1 = wkv6(r[:, :t1], k[:, :t1], v[:, :t1], w[:, :t1], u, s0,
                  interpret=True)
    y2, s2 = wkv6(r[:, t1:], k[:, t1:], v[:, t1:], w[:, t1:], u, s1,
                  interpret=True)
    np.testing.assert_allclose(np.asarray(jnp.concatenate([y1, y2], axis=1)),
                               np.asarray(y_full), rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s2), np.asarray(s_full),
                               rtol=1e-4, atol=1e-4)
