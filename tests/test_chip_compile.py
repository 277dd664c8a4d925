"""The serving path's kernels and its fused paged decode step, compiled
for a described TPU v5e chip at Yi-6B widths.

No chip is needed: the TPU compiler runs on the host and refuses here what
the chip would refuse (untiled blocks, VMEM overflow, programs that do not
fit in HBM).  Interpret mode, which the other kernel tests use, sees none
of that.  The topology is described inside a fixture, never at import, so
that only the worker running this file loads the TPU library.
"""
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


def test_paged_flash_decode_compiles(one_chip, yi):
    pool = _spec(one_chip, (PAGES + 1, PAGE, yi.num_kv_heads, yi.d_head))
    fn = jax.jit(lambda q, k, v, t, ln: paged_flash_decode(
        q, k, v, t, ln, interpret=False))
    _check(fn.lower(_spec(one_chip, (B, yi.num_q_heads, yi.d_head)),
                    pool, pool, _spec(one_chip, (B, NBLK), jnp.int32),
                    _spec(one_chip, (B,), jnp.int32)).compile())


def test_flash_decode_compiles(one_chip, yi):
    cache = _spec(one_chip, (B, NBLK * PAGE, yi.num_kv_heads, yi.d_head))
    fn = jax.jit(lambda q, k, v, ln: flash_decode(q, k, v, ln,
                                                  interpret=False))
    _check(fn.lower(_spec(one_chip, (B, yi.num_q_heads, yi.d_head)),
                    cache, cache,
                    _spec(one_chip, (B,), jnp.int32)).compile())


def test_fused_paged_decode_step_compiles(one_chip, yi):
    """All 32 layers, 8 fused steps, donated pools: the program the paged
    engine dispatches once per tick."""
    params = jax.tree.map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: T.init(k, yi), jax.random.PRNGKey(0)))
    pool = _spec(one_chip, (yi.num_layers, PAGES + 1, PAGE,
                            yi.num_kv_heads, yi.d_head))

    def attn(q, k, v, t, ln):
        return paged_flash_decode(q, k, v, t, ln, interpret=False)

    fn = jax.jit(lambda p, toks, kp, vp, t, ln, alive, ka:
                 T.decode_fused_paged(p, yi, toks, kp, vp, t, ln, alive, ka,
                                      8, attn_fn=attn),
                 donate_argnums=(2, 3))
    i32 = jnp.int32
    total = _check(fn.lower(
        params, _spec(one_chip, (B,), i32), pool, pool,
        _spec(one_chip, (B, NBLK), i32), _spec(one_chip, (B,), i32),
        _spec(one_chip, (B,), jnp.bool_), _spec(one_chip, (), i32)
    ).compile())
    # the bfloat16 weights alone are 12.1e9 bytes
    assert total > 12e9
