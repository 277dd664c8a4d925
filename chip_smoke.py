"""Chip smoke run: Yi-6B served at its full published width on a TPU.

    python chip_smoke.py             # one chip: the paged serving path
    python chip_smoke.py --chips 4   # four chips: one replica on each

The model is ``registry.get("yi-6b")``: 32 layers, d_model 4096, 32 query
and 4 KV heads of 128, d_ff 11008, vocab 64000, bfloat16, random weights
from seed 0.  Requests go through the entry points a user calls:
``EngineConfig`` / ``make_engine`` / ``Scheduler``, and ``make_cluster`` /
``Router`` for several chips.

One chip (the default) serves a seeded shared-prefix trace with paged KV,
the Pallas paged flash-decode kernel, 8 fused decode steps, chunked
prefill and prefix sharing.  It checks that every request finished with
its budget, that the compiled fused decode step holds the Pallas kernel,
and that one decode step's logits through the kernel agree with the same
step through the jnp reference attention, from the same pool state.

``--chips 4`` runs only the several-chip phase: four replicas, each on its
own chip, behind the router with prefix affinity, then a 1:3
prefill/decode split whose page shipments cross chips.  Both are compared
with one replica serving the same trace in the same process.

Any failed check raises, so the script exits non-zero, and so it does
when JAX finds no TPU.  The last line of standard output is the JSON ok
line only when every phase passed.  Nothing printed is a benchmark.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))

PREFIX_LEN = 256          # shared system prompt
TAIL_LEN = 256            # unique part of each prompt
MAX_NEW = 64
N_REQUESTS = 8
RATE = 50.0               # requests/s: the trace arrives within ~0.2 s
# One paged decode step's logits, Pallas kernel against the jnp reference,
# bound on ||kernel - reference|| / ||reference|| over the active lanes.
# Both paths run the model in bfloat16 and differ only in how attention
# is summed and rounded; 32 residual layers amplify that last-bit noise.
# On a v5e chip at this width, from one pool state, three correct
# attentions (the kernel, the reference, and a float32 one) were 0.043 to
# 0.049 apart, while the reference dropping the newest token was 0.11
# away, reading a wrong page 0.68, and no attention at all 1.4.
LOGIT_REL_TOL = 0.08


def engine_config():
    from repro.serving.engine import EngineConfig
    return EngineConfig(max_batch=8,
                        max_seq=PREFIX_LEN + TAIL_LEN + MAX_NEW + 2,
                        max_new_tokens=MAX_NEW, paged=True, page_size=16,
                        use_pallas_decode=True, fuse_steps=8,
                        prefill_chunk=256, prefix_sharing=True)


def check_finished(done, n: int) -> None:
    """Every request completed with its whole decode budget."""
    assert len(done) == n, f"{len(done)} of {n} requests finished"
    for r in done:
        assert r.finish_reason == "budget" and len(r.tokens_out) == MAX_NEW, \
            (r.rid, r.finish_reason, len(r.tokens_out))


def seq_pools(eng):
    """The engine's K and V page pools."""
    return [leaf for leaf, seq in zip(eng.paged.store, eng.paged.is_seq)
            if seq]


def check_fused_kernel(eng) -> None:
    """The fused decode step the engine compiled holds the Pallas kernel."""
    import jax.numpy as jnp
    assert eng.fused_report().get("fused_ticks", 0) > 0, "no fused tick ran"
    b = eng.ecfg.max_batch
    kp, vp = seq_pools(eng)
    lowered = eng._fused_fn(eng.ecfg.fuse_steps).lower(
        eng.params, jnp.zeros((b,), jnp.int32), kp, vp,
        eng.paged.tables_device(), jnp.zeros((b,), jnp.int32),
        jnp.zeros((b,), bool), jnp.asarray(1, jnp.int32))
    assert "tpu_custom_call" in lowered.compile().as_text(), \
        "compiled fused decode step holds no Pallas kernel"
    print("[smoke] fused decode step holds the Pallas kernel")


def check_logits(eng, seed: int = 1) -> float:
    """One decode step through the Pallas read-through against the same
    step through ``L.paged_decode_attention``, from the same pool state
    (two fresh requests prefilled and decoding).  Returns the relative
    L2 gap of their logits."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops as kops
    from repro.models import layers as L
    from repro.models import transformer as T
    from repro.serving.scheduler import RequestState
    cfg = eng.cfg
    rng = np.random.default_rng(seed)
    rids = [1000, 1001]
    for rid in rids:
        prompt = rng.integers(0, cfg.vocab, PREFIX_LEN + TAIL_LEN)
        assert eng.admit(RequestState(rid, prompt.astype(np.int32)))
        while rid not in {r.rid for r in eng.active.values()}:
            eng.tick()
    b = eng.ecfg.max_batch
    active = np.zeros((b,), bool)
    toks = np.zeros((b,), np.int32)
    lengths = np.zeros((b,), np.int32)
    for slot, r in eng.active.items():
        # the cache holds the prompt and every output token but the last,
        # which is the next step's input
        active[slot] = True
        toks[slot] = r.tokens_out[-1]
        lengths[slot] = len(r.prompt) + len(r.tokens_out) - 1
    tables = jnp.where(jnp.asarray(active)[:, None],
                       eng.paged.tables_device(), eng.paged.num_pages)
    kp, vp = seq_pools(eng)

    def step(attn_fn):
        fn = jax.jit(lambda p, t, k, v, tb, ln: T.decode_step_paged(
            p, cfg, t, k, v, tb, ln, attn_fn=attn_fn)[0])
        out = fn(eng.params, jnp.asarray(toks), kp, vp, tables,
                 jnp.asarray(lengths))
        return np.asarray(out, np.float32)[active, :cfg.vocab]

    got = step(kops.attention_decode_paged)
    want = step(L.paged_decode_attention)
    assert np.isfinite(got).all() and np.isfinite(want).all()
    rel = float(np.linalg.norm(got - want) / np.linalg.norm(want))
    print(f"[smoke] logits, Pallas vs reference attention over "
          f"{int(active.sum())} lanes: rel L2 {rel!r}, max abs "
          f"{float(np.abs(got - want).max())!r} (max |ref| "
          f"{float(np.abs(want).max())!r}), argmax agree "
          f"{int((got.argmax(-1) == want.argmax(-1)).sum())}/"
          f"{int(active.sum())}")
    assert rel <= LOGIT_REL_TOL, f"logit gap {rel} > {LOGIT_REL_TOL}"
    return rel


def one_chip(entry) -> None:
    """Serve the shared-prefix trace on one device and check it."""
    import jax

    from repro.serving.engine import make_engine, make_shared_prefix_trace
    from repro.serving.scheduler import Scheduler
    eng = make_engine(entry, engine_config())
    n_bytes = sum(x.nbytes for x in jax.tree.leaves(eng.params))
    print(f"[smoke] {entry.config.name}: {entry.config.num_layers} layers, "
          f"d_model {entry.config.d_model}, {entry.config.dtype}, "
          f"parameters {n_bytes} bytes")
    reqs = make_shared_prefix_trace(
        entry.config.vocab, rate_req_s=RATE, n_requests=N_REQUESTS,
        prefix_len=PREFIX_LEN, tail_len=TAIL_LEN, seed=0)
    metrics = Scheduler(eng).run_trace(reqs)
    metrics.pop("hists")
    print(f"[smoke] serve metrics (not a benchmark): {metrics}")
    check_finished(eng.completed, N_REQUESTS)
    assert metrics["kv_dedup_ratio_peak"] > 1.0, "no prefix page was shared"
    check_fused_kernel(eng)
    check_logits(eng)


def _devices_of(tree) -> set:
    import jax
    return {d for leaf in jax.tree.leaves(tree) for d in leaf.devices()}


def serve_cluster(entry, n: int, tiers=None):
    """One ``make_cluster`` run of the grouped trace; every replica's
    arrays must sit on its own device.  Returns (tokens by rid, metrics,
    ship log, replica devices)."""
    import jax

    from repro.serving.router import make_cluster
    from repro.serving.scheduler import make_grouped_prefix_trace
    devs = jax.devices()
    router = make_cluster(entry, engine_config(), n,
                          policy="prefix_affinity", tiers=tiers)
    for i, eng in enumerate(router.engines):
        held = _devices_of((eng.params, eng.paged.store,
                            eng.paged.tables_device()))
        assert held == {devs[i]}, f"replica {i} arrays on {held}"
    reqs = make_grouped_prefix_trace(
        entry.config.vocab, rate_req_s=RATE, n_requests=2 * N_REQUESTS,
        n_groups=4, prefix_len=PREFIX_LEN, tail_len=TAIL_LEN, seed=0)
    metrics = router.run_trace(reqs)
    done = [r for e in router.engines for r in e.completed]
    check_finished(done, len(reqs))
    return ({r.rid: r.tokens_out for r in done}, metrics,
            list(router.ship_log), [e.device for e in router.engines])


def four_chips(entry) -> None:
    """Four replicas on four devices, then a 1:3 tier split, each against
    one replica on the same trace."""
    base, _, _, _ = serve_cluster(entry, 1)
    gc.collect()                   # the next cluster needs device 0 again
    for tiers in (None, (1, 3)):
        got, metrics, ships, devs = serve_cluster(entry, 4, tiers)
        gc.collect()
        first = sum(got[r][0] == base[r][0] for r in base)
        whole = sum(got[r] == base[r] for r in base)
        label = "tiers 1:3" if tiers else "prefix_affinity"
        per = [p["requests"] for p in metrics["per_replica"]]
        print(f"[smoke] 4 replicas, {label}: requests per replica {per}, "
              f"shipments {metrics['shipments']}; first tokens equal to "
              f"one replica {first}/{len(base)}, whole streams "
              f"{whole}/{len(base)} ({whole / len(base)!r})")
        assert first == len(base), "first tokens differ from one replica"
        if tiers:
            assert len(ships) == len(base), "not every request shipped"
            assert all(devs[src] != devs[dst] for _, src, dst in ships), \
                "a shipment stayed on its device"


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-replica phase")
    args = ap.parse_args()
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} needs {args.chips} "
                 f"devices, JAX found {len(devs)}")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import registry
    print(f"[smoke] device {devs[0].platform} {devs[0].device_kind} "
          f"x{len(devs)}; compile cache {enable_compile_cache()}")
    entry = registry.get("yi-6b")
    if args.chips == 4:
        four_chips(entry)
    else:
        one_chip(entry)
    for d in devs[:args.chips]:
        stats = d.memory_stats() or {}
        print(f"[smoke] {d}: peak_bytes_in_use "
              f"{stats.get('peak_bytes_in_use')} of "
              f"{stats.get('bytes_limit')}")
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
