"""The serving path's kernels and its fused paged decode step, compiled
for a described TPU v5e chip at Yi-6B widths and at the shapes of the
StableLM-3B long-document benchmark cell.

No chip is needed: the TPU compiler runs on the host and refuses here what
the chip would refuse (untiled blocks, VMEM overflow, programs that do not
fit in HBM).  Interpret mode, which the other kernel tests use, sees none
of that.  The topology is described inside a fixture, never at import, so
that only the worker running this file loads the TPU library.
"""
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_decode import flash_decode, paged_flash_decode
from repro.models import registry
from repro.models import transformer as T

B = 8                  # decode batch
PAGE = 16              # tokens per page
PAGES = 1024           # page pool (plus one scratch page)
NBLK = 37              # block-table width: 592 tokens of context
HBM_BYTES = 16 * 2**30
# the StableLM-3B cell (bench/configs/stablelm-3b.json): 4 lanes, 512
# pages of 16 tokens, a 4,096-token table
CELL_B, CELL_PAGES, CELL_NBLK = 4, 512, 256


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")   # no compiler logs in /tmp
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:             # no TPU compiler installed
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip can be cached but never read back
        # without one: keep the persistent cache out of it
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.reset_cache()
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.reset_cache()


@pytest.fixture(scope="module")
def yi():
    return registry.get("yi-6b").config


def _spec(sharding, shape, dtype=jnp.bfloat16):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _check(compiled) -> int:
    """The program holds a Pallas kernel and fits one chip's HBM."""
    assert "tpu_custom_call" in compiled.as_text()
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert total < HBM_BYTES, total
    return total


@pytest.mark.parametrize("arch,b,pages,nblk", [
    ("yi-6b", B, PAGES, NBLK),
    ("stablelm-3b", CELL_B, CELL_PAGES, CELL_NBLK),   # D 80: padded lanes
])
def test_paged_flash_decode_compiles(one_chip, arch, b, pages, nblk):
    cfg = registry.get(arch).config
    pool = _spec(one_chip, (pages + 1, PAGE, cfg.num_kv_heads, cfg.d_head))
    fn = jax.jit(lambda q, k, v, t, ln: paged_flash_decode(
        q, k, v, t, ln, interpret=False))
    _check(fn.lower(_spec(one_chip, (b, cfg.num_q_heads, cfg.d_head)),
                    pool, pool, _spec(one_chip, (b, nblk), jnp.int32),
                    _spec(one_chip, (b,), jnp.int32)).compile())


def test_flash_decode_compiles(one_chip, yi):
    cache = _spec(one_chip, (B, NBLK * PAGE, yi.num_kv_heads, yi.d_head))
    fn = jax.jit(lambda q, k, v, ln: flash_decode(q, k, v, ln,
                                                  interpret=False))
    _check(fn.lower(_spec(one_chip, (B, yi.num_q_heads, yi.d_head)),
                    cache, cache,
                    _spec(one_chip, (B,), jnp.int32)).compile())


def _fused_decode(sharding, cfg, b, pages, nblk):
    """The 8-step fused paged decode, all layers, donated pools: the
    program the paged engine dispatches once per tick, compiled."""
    params = jax.tree.map(
        lambda a: _spec(sharding, a.shape, a.dtype),
        jax.eval_shape(lambda k: T.init(k, cfg), jax.random.PRNGKey(0)))
    pool = _spec(sharding, (cfg.num_layers, pages + 1, PAGE,
                            cfg.num_kv_heads, cfg.d_head))

    def attn(q, k, v, t, ln):
        return paged_flash_decode(q, k, v, t, ln, interpret=False)

    fn = jax.jit(lambda p, toks, kp, vp, t, ln, alive, ka:
                 T.decode_fused_paged(p, cfg, toks, kp, vp, t, ln, alive, ka,
                                      8, attn_fn=attn),
                 donate_argnums=(2, 3))
    i32 = jnp.int32
    return fn.lower(
        params, _spec(sharding, (b,), i32), pool, pool,
        _spec(sharding, (b, nblk), i32), _spec(sharding, (b,), i32),
        _spec(sharding, (b,), jnp.bool_), _spec(sharding, (), i32)
    ).compile()


def test_fused_paged_decode_step_compiles(one_chip, yi):
    total = _check(_fused_decode(one_chip, yi, B, PAGES, NBLK))
    # the bfloat16 weights alone are 12.1e9 bytes
    assert total > 12e9


def test_fused_decode_reads_the_pool_in_its_stored_layout(one_chip):
    """At the cell's shapes the kernel takes each layer's pool as the
    layer loop slices it, ``(P+1, page, Hkv, D)``: no layer of the pool
    is copied or transposed inside the program's loops, nothing has the
    ``(P+1, Hkv, page, D)`` shape of a head-major copy, and the program
    needs less scratch than with the per-call head-major copy."""
    c = registry.get("stablelm-3b").config
    compiled = _fused_decode(one_chip, c, CELL_B, CELL_PAGES, CELL_NBLK)
    _check(compiled)
    layer = f"{CELL_PAGES + 1},{PAGE},{c.num_kv_heads},{c.d_head}"
    head_major = f"[{CELL_PAGES + 1},{c.num_kv_heads},{PAGE},{c.d_head}]"
    hlo = compiled.as_text()
    assert head_major not in hlo
    computation, moved = None, []
    for line in hlo.splitlines():
        if line and not line.startswith(" "):
            computation = line.split()[0]
            continue
        m = re.match(r"\s*(?:ROOT )?%(\S+) = \w+\[([\d,]+)\]\S* "
                     r"(copy|transpose)\(", line)
        if m and computation != "ENTRY" and m.group(2).endswith(layer):
            moved.append(m.group(1))
    assert not moved, moved
    # 5,696,706,048 bytes with the kernel that copied each layer's pool
    # head-major on every call (the same compile of the same program)
    assert compiled.memory_analysis().temp_size_in_bytes < 5_696_706_048
