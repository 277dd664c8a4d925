"""Engine device placement: parameters come from a jitted init that equals
the op-by-op one, and ``make_cluster`` puts each replica's parameters, page
pool and block tables on its own device, with tier shipments crossing
devices token-exactly."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import layers as L
from repro.models import registry
from repro.serving.engine import EngineConfig, make_engine

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one architecture per model family
FAMILIES = ["yi-6b", "dbrx-132b", "rwkv6-7b", "recurrentgemma-9b",
            "qwen2-vl-7b", "whisper-small"]


def _bits(x):
    return np.asarray(x).view(np.uint8)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("arch", FAMILIES)
def test_jitted_init_matches_eager(arch, dtype):
    entry = registry.get(arch, reduced=True, dtype=dtype)
    eng = make_engine(entry, EngineConfig(max_batch=2, max_seq=32))
    want = entry.module.init(jax.random.PRNGKey(0), entry.config, 1)
    assert jax.tree.structure(eng.params) == jax.tree.structure(want)
    for got, ref in zip(jax.tree.leaves(eng.params), jax.tree.leaves(want)):
        assert got.dtype == ref.dtype and got.shape == ref.shape
        assert np.array_equal(_bits(got), _bits(ref))
        assert got.devices() == {eng.device}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_normal_init_is_the_plain_draw(dtype):
    """Op by op, ``normal_init`` is the scaled normal draw it replaced."""
    key = jax.random.PRNGKey(7)
    want = (jax.random.normal(key, (64, 96), jnp.float32) * 0.013
            ).astype(dtype)
    got = L.normal_init(key, (64, 96), dtype, 0.013)
    assert np.array_equal(_bits(got), _bits(want))
    jitted = jax.jit(lambda k: L.normal_init(k, (64, 96), dtype, 0.013))
    assert np.array_equal(_bits(jitted(key)), _bits(want))


CLUSTER = textwrap.dedent("""
    import json
    import jax
    from repro.models import registry
    from repro.serving.engine import EngineConfig
    from repro.serving.router import make_cluster
    from repro.serving.scheduler import make_grouped_prefix_trace

    entry = registry.get("yi-6b", reduced=True)
    ecfg = EngineConfig(max_batch=3, max_seq=40, max_new_tokens=6,
                        paged=True, page_size=8, prefix_sharing=True,
                        prefill_chunk=8, use_pallas_decode=True,
                        fuse_steps=4)

    def run(n, tiers=None):
        router = make_cluster(entry, ecfg, n, policy="prefix_affinity",
                              tiers=tiers)
        held = []
        for eng in router.engines:
            arrays = (eng.params, eng.paged.store,
                      eng.paged.tables_device())
            held.append(sorted({d.id for a in jax.tree.leaves(arrays)
                                for d in a.devices()}))
        reqs = make_grouped_prefix_trace(
            entry.config.vocab, rate_req_s=500.0, n_requests=8,
            n_groups=2, prefix_len=16, tail_len=8, seed=0)
        router.run_trace(reqs)
        toks = {r.rid: r.tokens_out for e in router.engines
                for r in e.completed}
        return {"held": held, "tokens": toks,
                "ships": [[src, dst] for _, src, dst in router.ship_log]}

    print("RESULT", json.dumps({"n_devices": len(jax.devices()),
                                "one": run(1), "four": run(4),
                                "tiers": run(4, (1, 3))}))
""")


def test_cluster_places_replicas_and_ships_across_devices():
    """Four virtual CPU devices (the count is fixed when JAX starts, so
    this runs in a child process that never loads the TPU library)."""
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c", CLUSTER], capture_output=True,
                       text=True, env=env, cwd=REPO, timeout=600)
    assert "RESULT" in r.stdout, r.stdout + r.stderr
    out = json.loads(r.stdout.split("RESULT", 1)[1])
    assert out["n_devices"] == 4
    assert out["one"]["held"] == [[0]]
    for run in ("four", "tiers"):
        assert out[run]["held"] == [[0], [1], [2], [3]]
        assert out[run]["tokens"] == out["one"]["tokens"]
    assert len(out["one"]["tokens"]) == 8
    ships = out["tiers"]["ships"]
    assert len(ships) == 8
    assert all(src == 0 and dst in (1, 2, 3) for src, dst in ships)
