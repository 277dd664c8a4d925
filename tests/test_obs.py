"""Observability subsystem tests (PR 9).

The contract under test: tracing must be *free* when off and *lossless*
when on.  Tokens and finish reasons are bit-identical with the tracer
attached or not (engine and analytic sims); the Perfetto export
round-trips through ``json`` with monotone per-track timestamps; a
replayed JSONL log reproduces ``trace_report`` exactly; and the metrics
registry reports numbers identical to the legacy ad-hoc numpy math it
replaced (it retains exact samples alongside the bucket counts).
"""
import dataclasses
import json

import numpy as np
import pytest

from repro.core.hw import snake_system
from repro.core.operators import PAPER_MODELS
from repro.core.serving_sim import (nmp_latency_model, simulate_cluster,
                                    simulate_serving)
from repro.models import registry
from repro.obs import (EVENT_KINDS, NULL_TRACER, Histogram, MetricsRegistry,
                       TraceEvent, Tracer, export_perfetto, load_jsonl,
                       pctl, save_jsonl, serving_registry, trace_report)


# ---------------------------------------------------------------------------
# metrics registry: the shared percentile helper + exact-sample histograms
# ---------------------------------------------------------------------------
def test_pctl_matches_numpy_and_handles_empty():
    xs = [0.3, 0.1, 4.0, 2.2, 0.9]
    for q in (50, 90, 99):
        assert pctl(xs, q) == float(np.percentile(xs, q))
    assert pctl([], 99) == 0.0


def test_histogram_buckets_and_exact_stats():
    h = Histogram("lat", buckets=[0.01, 0.1, 1.0])
    samples = [0.005, 0.01, 0.05, 0.5, 2.0, 7.0]
    for v in samples:
        h.observe(v)
    s = h.summary()
    # le semantics: 0.01 lands in the first bucket, overflow catches >1.0
    assert s["buckets"] == {"le_0.01": 2, "le_0.1": 1, "le_1": 1, "inf": 2}
    assert s["count"] == len(samples)
    # stats come from the retained exact samples, not the buckets
    assert h.mean == float(np.mean(samples))
    assert h.quantile(99) == float(np.percentile(samples, 99))
    with pytest.raises(ValueError):
        Histogram("empty", buckets=[])


def test_registry_get_or_create_and_summaries():
    reg = MetricsRegistry()
    assert reg.counter("reqs") is reg.counter("reqs")
    reg.counter("reqs").inc(3)
    reg.gauge("free_pages").set(7.0)
    h = reg.observe_all("ttft_s", [0.1, 0.2])   # default buckets by name
    assert h is reg.histogram("ttft_s") and h.count == 2
    with pytest.raises(ValueError):
        reg.histogram("no_default_buckets_for_this")
    s = reg.summaries()
    assert s["counters"] == {"reqs": 3}
    assert s["gauges"] == {"free_pages": 7.0}
    assert set(s["histograms"]) == {"ttft_s"}
    # the serving registry pre-declares every serving instrument
    assert set(serving_registry().histograms) == {
        "ttft_s", "tpot_s", "gather_cost_s", "fused_horizon", "e2e_s"}


# ---------------------------------------------------------------------------
# tracer core: no-op default, typed kinds, lazy wall-clock origin
# ---------------------------------------------------------------------------
def test_null_tracer_is_inert():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.emit("finish", rid=1, reason="eos") is None
    assert NULL_TRACER.events == []
    assert NULL_TRACER.for_replica(3) is NULL_TRACER


def test_tracer_rejects_unknown_kind_and_anchors_origin():
    tr = Tracer()
    with pytest.raises(ValueError):
        tr.emit("made_up_kind")
    tr.emit("arrival", rid=0, arrival_s=0.5)      # wall ts, lazy t0
    tr.emit("finish", rid=0, reason="budget")
    assert [e.kind for e in tr.events] == ["arrival", "finish"]
    assert tr.events[0].ts == 0.0                 # origin = first event
    assert tr.events[1].ts >= 0.0
    # modeled-clock tracers pass ts explicitly against t0=0
    tm = Tracer(t0=0.0)
    tm.emit("decode_step", ts=1.25, dur=0.5, batch=4)
    assert tm.events[0].ts == 1.25 and tm.events[0].dur == 0.5


def test_bound_tracer_stamps_replica():
    tr = Tracer(t0=0.0)
    tr.for_replica(2).emit("dispatch", ts=0.0, rid=7, policy="round_robin")
    tr.emit("dispatch", ts=0.1, rid=8, policy="round_robin")
    assert [e.replica for e in tr.events] == [2, 0]
    assert all(e.kind in EVENT_KINDS for e in tr.events)


# ---------------------------------------------------------------------------
# sims: tracing must not perturb the report; spans partition the makespan
# ---------------------------------------------------------------------------
def _sim(tracer=None):
    lat = nmp_latency_model(snake_system(), PAPER_MODELS["LLaMA3-70B"],
                            tp=8)
    return simulate_serving(lat, PAPER_MODELS["LLaMA3-70B"], 0.5,
                            system="SNAKE", n_requests=8, input_len=256,
                            output_len=48, max_batch=4,
                            cache_mode="paged", page_size=16,
                            prefill_on_device=True, prefill_chunk=64,
                            fuse_steps=8, tracer=tracer)


def test_sim_report_identical_with_and_without_tracer():
    r0 = _sim(tracer=None)
    tr = Tracer(t0=0.0)
    r1 = _sim(tracer=tr)
    assert dataclasses.asdict(r0) == dataclasses.asdict(r1)
    kinds = {e.kind for e in tr.events}
    assert {"arrival", "admit", "prefill_chunk", "fused_tick",
            "finish"} <= kinds


def test_sim_phases_sum_to_makespan():
    tr = Tracer(t0=0.0)
    _sim(tracer=tr)
    rep = trace_report(tr.events)
    assert rep["finished"] == 8
    total = sum(rep["phases"].values())
    assert abs(total - rep["makespan_s"]) <= 1e-9 * max(1.0, total)


def test_cluster_sim_traced_and_unperturbed():
    lat = nmp_latency_model(snake_system(), PAPER_MODELS["LLaMA3-70B"],
                            tp=8)
    kw = dict(policy="round_robin", n_replicas=2, n_requests=8,
              input_len=256, output_len=32, max_batch=4,
              prefix_sharing=True, shared_prefix_len=128, n_groups=2)
    r0 = simulate_cluster(lat, PAPER_MODELS["LLaMA3-70B"], 0.5, **kw)
    tr = Tracer(t0=0.0)
    r1 = simulate_cluster(lat, PAPER_MODELS["LLaMA3-70B"], 0.5,
                          tracer=tr, **kw)
    assert dataclasses.asdict(r0) == dataclasses.asdict(r1)
    dispatches = [e for e in tr.events if e.kind == "dispatch"]
    assert len(dispatches) == 8
    assert {e.replica for e in tr.events} == {0, 1}


# ---------------------------------------------------------------------------
# exporters: Perfetto JSON round-trip, lossless JSONL replay
# ---------------------------------------------------------------------------
def test_perfetto_roundtrip_monotone_tracks(tmp_path):
    tr = Tracer(t0=0.0)
    _sim(tracer=tr)
    path = tmp_path / "trace.json"
    obj = export_perfetto(tr.events, str(path))
    # the written file and the returned object are the same JSON document
    loaded = json.loads(path.read_text())
    assert loaded == json.loads(json.dumps(obj))
    evs = loaded["traceEvents"]
    assert any(e["ph"] == "X" for e in evs)
    assert any(e["ph"] == "M" and e["name"] == "process_name" for e in evs)
    last = {}
    for e in evs:
        if e["ph"] not in ("X", "C"):
            continue
        key = (e["pid"], e["tid"])
        assert e["ts"] >= last.get(key, float("-inf")), \
            f"track {key} timestamps regressed"
        last[key] = e["ts"]


def test_jsonl_replay_reproduces_trace_report(tmp_path):
    tr = Tracer(t0=0.0)
    _sim(tracer=tr)
    path = tmp_path / "trace.jsonl"
    save_jsonl(tr.events, str(path))
    replayed = load_jsonl(str(path))
    assert replayed == tr.events                  # lossless, field-exact
    assert trace_report(replayed) == trace_report(tr.events)
    assert all(isinstance(e, TraceEvent) for e in replayed)


# ---------------------------------------------------------------------------
# live engine: tokens + finish reasons bit-identical, tracer on or off
# ---------------------------------------------------------------------------
def _engine_run(tracer=None):
    from repro.serving.engine import (EngineConfig, make_engine,
                                      make_shared_prefix_trace)
    entry = registry.get("yi-6b", reduced=True)
    ecfg = EngineConfig(max_batch=3, max_seq=64, max_new_tokens=4,
                        paged=True, page_size=8, prefix_sharing=True,
                        prefill_chunk=4, fuse_steps=4)
    eng = make_engine(entry, ecfg)
    if tracer is not None:
        eng.set_tracer(tracer, replica=0)
    reqs = make_shared_prefix_trace(entry.config.vocab, rate_req_s=500.0,
                                    n_requests=4, prefix_len=16,
                                    tail_len=5, seed=2)
    m = eng.run_trace(reqs)
    toks = {r.rid: list(r.tokens_out) for r in eng.completed}
    reasons = {r.rid: r.finish_reason for r in eng.completed}
    return m, toks, reasons


def test_engine_tokens_bit_identical_tracer_on_off():
    _, base_t, base_r = _engine_run(tracer=None)
    tr = Tracer()
    m, toks, reasons = _engine_run(tracer=tr)
    assert toks == base_t and reasons == base_r
    kinds = {e.kind for e in tr.events}
    assert {"arrival", "admit", "prefill_chunk", "fused_tick",
            "finish", "gauge"} <= kinds
    # the registry's bucketed summaries ride along in the metrics dict
    assert m["hists"]["fused_horizon"]["count"] == m["fused_ticks"]
    rep = trace_report(tr.events)
    assert rep["finished"] == len(base_t)
    total = sum(rep["phases"].values())
    assert abs(total - rep["makespan_s"]) <= 1e-9 * max(1.0, total)


# ---------------------------------------------------------------------------
# engine spans on the profiler's clock; named programs; grid counters
# ---------------------------------------------------------------------------
def _small_engine(**over):
    from repro.serving.engine import EngineConfig, make_engine
    base = dict(max_batch=2, max_seq=64, max_new_tokens=6, paged=True,
                page_size=8, prefill_chunk=8, fuse_steps=4)
    base.update(over)
    return make_engine(registry.get("yi-6b", reduced=True),
                       EngineConfig(**base))


def _serve(eng, prompts, rid0=0):
    from repro.serving.engine import RequestState
    for i, n in enumerate(prompts):
        eng.requeue.append(RequestState(rid0 + i,
                                        np.arange(1, n + 1, dtype=np.int32)))
    while eng.requeue or eng.busy():
        if eng.requeue and eng.admit(eng.requeue[0]):
            eng.requeue.pop(0)
        eng.tick()


def test_engine_spans_nest_in_the_window_on_the_profilers_host_line(
        tmp_path):
    """A traced tick's spans are profiler annotations on the thread that
    holds the enclosing window annotation, inside it, with each child
    inside its parent; their events carry wait_s <= dur."""
    import glob
    import os

    import jax
    from jax.profiler import ProfileData
    eng = _small_engine()
    _serve(eng, [11])                           # compiles every program
    tr = Tracer()
    eng.set_tracer(tr)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        _serve(eng, [11], rid0=10)
    jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    host = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                       for e in line.events]
                if any(n == "bench.window" for n, _, _ in evs):
                    host = evs
    by = {}
    for n, s, e in host:
        by.setdefault(n, []).append((s, e))
    (lo, hi), = by["bench.window"]
    for name in ("serve.prefill_chunk", "serve.prefill_chunk.wait",
                 "serve.fused_tick", "serve.fused_tick.prepare",
                 "serve.fused_tick.dispatch", "serve.fused_tick.wait",
                 "serve.fused_tick.apply"):
        assert by.get(name), f"{name} is not on the window's host line"
        assert all(lo <= s <= e <= hi for s, e in by[name]), name
        parent = name.rsplit(".", 1)[0]
        if parent != "serve":
            assert all(any(ps <= s and e <= pe for ps, pe in by[parent])
                       for s, e in by[name]), f"{name} outside {parent}"
    kinds = [e.kind for e in tr.events]
    assert kinds.count("prefill_chunk") == 2 and "fused_tick" in kinds
    waits = [e for e in tr.events if "wait_s" in e.args]
    assert {e.kind for e in waits} == {"prefill_chunk", "fused_tick",
                                       "decode_step"}
    assert all(0.0 <= e.args["wait_s"] <= e.dur for e in waits)
    assert not any("device_s" in e.args for e in tr.events)


def test_untraced_engine_opens_no_annotation_and_counts_nothing(
        monkeypatch):
    import jax

    from repro.serving.engine import PagedServingEngine

    def boom(*a, **k):
        raise AssertionError("the untraced engine did tracing work")
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", boom)
    monkeypatch.setattr(PagedServingEngine, "_grid_counts", boom)
    monkeypatch.setattr(PagedServingEngine, "_step_pages", boom)
    eng = _small_engine()
    assert eng.tracer is NULL_TRACER
    _serve(eng, [11, 5])
    # both decode paths ran: fused ticks and a one-step tick
    assert eng.fused_horizons and len(eng.completed) == 2
    assert NULL_TRACER.span("fused_tick") is NULL_TRACER.span("defrag")


def test_engine_programs_are_named():
    """Every program the engine jits lowers to a module jit_engine_*."""
    import jax.numpy as jnp

    def module(fn, *args):
        line = next(ln for ln in fn.lower(*args).as_text().splitlines()
                    if ln.startswith("module @"))
        return line.split()[1]

    eng = _small_engine()
    _serve(eng, [11])
    pc = eng.paged
    ki, vi = [i for i, s in enumerate(pc.is_seq) if s]
    lanes = jnp.zeros((2,), jnp.int32)
    assert module(eng._extend, eng.params, jnp.zeros((1, 8), jnp.int32),
                  pc.gather_slot(0, 0)) == "@jit_engine_extend"
    for n in eng._fused_jits:
        assert module(eng._fused_fn(n), eng.params, lanes, pc.store[ki],
                      pc.store[vi], pc.tables_device(), lanes,
                      jnp.ones((2,), bool), jnp.asarray(2, jnp.int32)
                      ) == f"@jit_engine_fused_decode_{n}"
    assert eng._fused_jits
    paged = _small_engine(fuse_steps=1, prefill_chunk=None,
                          use_pallas_decode=True, max_new_tokens=2)
    _serve(paged, [5])
    assert module(paged._paged_decode, paged.params, lanes,
                  paged.paged.store[ki], paged.paged.store[vi],
                  paged.paged.tables_device(), lanes
                  ) == "@jit_engine_paged_decode"
    dense = _small_engine(paged=False, prefill_chunk=None, fuse_steps=1)
    toks = jnp.zeros((1, 5), jnp.int32)
    assert module(dense._prefill, dense.params, toks) == \
        "@jit_engine_prefill"
    assert module(dense._decode, dense.params, dense.cache, lanes) == \
        "@jit_engine_decode"


@pytest.mark.parametrize("ppb,walks", [
    (8, ((8,) * 8, (8,) * 4)),
    (3, ((3,) * 8, (3,) * 4)),
    (1, ((2, 1, 2, 1, 2, 1, 2, 2), (2,) * 4)),
])
def test_grid_counts_match_a_hand_count(monkeypatch, ppb, walks):
    """Prompts of 11 and 5 tokens, pages of 8, a 64-token table (8
    entries), 2 lanes.  The first fused tick stops at lane 1's page edge
    after 3 steps and runs the 4-step bucket; lane 0 attends 12-14
    tokens (2 pages each step), lane 1 6-8 (1 page), and in the frozen
    4th step 15 and 9 (2 pages each).  The second runs 2 steps, both
    lanes at 2 pages.  The kernel walks each lane's pages in blocks of
    ``ppb`` table entries (8 at these shapes: the whole table), so a
    lane-step counts its pages rounded up to whole blocks; the block's
    bytes are steered here to give 3 and 1."""
    from repro.kernels import flash_decode as FD
    # one page of K or V in VMEM: 8 tokens x (4 heads -> 8 f32 sublanes)
    # x (32 -> 128 lanes) x 4 bytes
    monkeypatch.setattr(FD, "BLOCK_BYTES", ppb * 2 * 8 * 8 * 128 * 4)
    eng = _small_engine(prefill_chunk=None, fuse_steps=8)
    tr = Tracer()
    eng.set_tracer(tr)
    from repro.serving.engine import RequestState
    for rid, n in enumerate((11, 5)):
        assert eng.submit(RequestState(rid, np.arange(1, n + 1,
                                                      dtype=np.int32)))
    while eng.busy():
        eng.tick()
    ticks = [e.args for e in tr.events if e.kind == "fused_tick"]
    assert [(t["horizon"], t["grid_pages"], t["live_pages"])
            for t in ticks] == [(3, sum(walks[0]), 3 * 2 + 3 * 1),
                                (2, sum(walks[1]), 2 * 2 + 2 * 2)]