"""Fused decode loop tests (PR 8).

The fused engine scans K decode steps on device (``lax.scan`` over
``decode_step_paged``) and must be *bit-identical* to the per-tick
engine: same tokens, same finish reasons, under every cache feature
combination (prefix sharing, chunked prefill, placement, the Pallas
read-through path, eos stops).  Also covered: the horizon-selection
rule (page-window and budget cutoffs), the incrementally maintained
device block-table mirror, and the analytic sim's fused clock.
"""
import numpy as np
import pytest

from repro.core.hw import snake_system
from repro.core.operators import PAPER_MODELS
from repro.core.serving_sim import nmp_latency_model, simulate_serving
from repro.models import registry
from repro.serving.engine import (EngineConfig, RequestState, make_engine,
                                  make_shared_prefix_trace, make_trace)
from repro.serving.paged_cache import PagedCache

# skewed prompt lengths: ragged tails, different page phases, one prompt
# spanning four pages — the horizon must keep collapsing and recovering
SKEWED_LENS = np.array([9, 17, 5, 30, 12, 24])


def _entry():
    return registry.get("yi-6b", reduced=True)


def _run(entry, trace=None, **over):
    base = dict(max_batch=3, max_seq=48, max_new_tokens=5,
                paged=True, page_size=8)
    base.update(over)
    ecfg = EngineConfig(**base)
    eng = make_engine(entry, ecfg)
    reqs = trace if trace is not None else make_trace(
        entry.config.vocab, rate_req_s=100.0, n_requests=6,
        prompt_len=8, prompt_lens=SKEWED_LENS, seed=3)
    m = eng.run_trace(reqs)
    toks = {r.rid: list(r.tokens_out) for r in eng.completed}
    reasons = {r.rid: r.finish_reason for r in eng.completed}
    return eng, m, toks, reasons


# ---------------------------------------------------------------------------
# token exactness: fused == per-tick, across horizons and cache features
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_fused_token_exact_across_horizons():
    entry = _entry()
    _, _, base_t, base_r = _run(entry, fuse_steps=1)
    for fuse in (2, 8, 64):
        eng, m, toks, reasons = _run(entry, fuse_steps=fuse)
        assert toks == base_t, f"fuse_steps={fuse} diverged"
        assert reasons == base_r
        if fuse >= 8:
            assert m["fused_ticks"] > 0
            assert m["fused_steps_mean"] > 1.0
    # fuse_steps=1 never routes through the fused path at all
    eng1, m1, _, _ = _run(entry, fuse_steps=1)
    assert m1["fused_ticks"] == 0 and m1["fused_steps_mean"] == 0.0


@pytest.mark.slow
def test_fused_token_exact_with_sharing_chunking_placement():
    """The full feature stack under one fused engine: shared prefixes
    (horizon-boundary CoW), chunked prefill (inactive lanes hold live
    shared pages mid-chunk), and region placement."""
    entry = _entry()
    trace = lambda: make_shared_prefix_trace(     # noqa: E731
        entry.config.vocab, rate_req_s=500.0, n_requests=6,
        prefix_len=16, tail_len=5, seed=2)
    over = dict(max_seq=64, prefix_sharing=True, prefill_chunk=4,
                placement="affinity", placement_regions=2)
    _, _, base_t, base_r = _run(entry, trace=trace(), fuse_steps=1, **over)
    eng, _, toks, reasons = _run(entry, trace=trace(), fuse_steps=16,
                                 **over)
    assert toks == base_t and reasons == base_r
    assert eng.paged.pages_in_use() == 0


@pytest.mark.slow
def test_fused_token_exact_pallas_readthrough():
    entry = _entry()
    _, _, base_t, _ = _run(entry, fuse_steps=1, use_pallas_decode=True)
    _, _, toks, _ = _run(entry, fuse_steps=8, use_pallas_decode=True)
    assert toks == base_t


@pytest.mark.slow
def test_fused_eos_budget_reason_parity():
    """Sampled eos budgets: requests finish at staggered lengths, so the
    horizon is budget-capped per wave and finish reasons must agree."""
    entry = _entry()
    trace = lambda: make_trace(                   # noqa: E731
        entry.config.vocab, rate_req_s=100.0, n_requests=6,
        prompt_len=8, prompt_lens=SKEWED_LENS, seed=3, eos_rate=0.4)
    _, _, base_t, base_r = _run(entry, trace=trace(), fuse_steps=1)
    _, _, toks, reasons = _run(entry, trace=trace(), fuse_steps=64)
    assert toks == base_t and reasons == base_r


@pytest.mark.slow
def test_fused_token_level_eos_freezes_lane_mid_horizon():
    """A token-level eos_id cannot be predicted from host state: the lane
    must freeze *inside* the scan (emit mask) and the finish reason must
    still match the per-tick engine."""
    entry = _entry()
    _, _, base_t, _ = _run(entry, fuse_steps=1)
    # pick a token some request actually emits mid-stream as the eos id
    eos_id = next(t[2] for t in base_t.values() if len(t) > 3)
    _, _, b_t, b_r = _run(entry, fuse_steps=1, eos_id=eos_id,
                          max_new_tokens=8)
    _, _, f_t, f_r = _run(entry, fuse_steps=64, eos_id=eos_id,
                          max_new_tokens=8)
    assert f_t == b_t and f_r == b_r
    assert "eos" in set(b_r.values())   # the stop actually triggered


# ---------------------------------------------------------------------------
# horizon selection: page-window and budget cutoffs
# ---------------------------------------------------------------------------
def test_fused_horizon_page_and_budget_cutoffs():
    entry = _entry()
    ecfg = EngineConfig(max_batch=2, max_seq=64, max_new_tokens=32,
                        paged=True, page_size=8, fuse_steps=64)
    eng = make_engine(entry, ecfg)
    assert eng.submit(RequestState(0, np.arange(9, dtype=np.int32)))
    # 9 prompt tokens resident after submit; growth maps a 2nd page so
    # the slot covers 16 positions: 7 decode writes (9..15) fit before
    # the window edge, and the budget allows 31 more -> the page binds
    eng._pre_decode_grow()
    assert eng._fused_horizon() == 7
    slot, req = next(iter(eng.active.items()))
    eng.tick()
    assert int(eng._lengths_host[slot]) == 16
    # fresh page granted on the next tick boundary: full page of 8 steps
    eng._pre_decode_grow()
    assert eng._fused_horizon() == min(8, 32 - len(req.tokens_out)) == 8
    while eng.active:
        eng.tick()
    assert eng.completed[0].finish_reason == "budget"
    assert len(eng.completed[0].tokens_out) == 32
    # budget cutoff: with 12 total the 2nd horizon is capped at the 4
    # remaining steps (page window would have allowed a full 8)
    eng2 = make_engine(entry, EngineConfig(
        max_batch=2, max_seq=64, max_new_tokens=12, paged=True,
        page_size=8, fuse_steps=64))
    assert eng2.submit(RequestState(0, np.arange(9, dtype=np.int32)))
    eng2.tick()                                  # 7 steps: page-capped
    eng2._pre_decode_grow()
    assert eng2._fused_horizon() == 4
    while eng2.active:
        eng2.tick()
    assert len(eng2.completed[0].tokens_out) == 12
    assert eng2.completed[0].finish_reason == "budget"


def test_fused_tick_counters_in_metrics():
    entry = _entry()
    eng, m, _, _ = _run(entry, fuse_steps=8)
    fr = eng.fused_report()
    assert fr["fused_ticks"] == m["fused_ticks"] > 0
    assert set(fr) == {"fused_ticks", "fused_steps_mean"}
    # a fused tick runs only for a horizon of two steps or more
    assert 1.0 < fr["fused_steps_mean"] <= 8
    assert m["fused_steps_mean"] == pytest.approx(fr["fused_steps_mean"])


# ---------------------------------------------------------------------------
# device block-table mirror: incrementally maintained
# ---------------------------------------------------------------------------
def test_paged_cache_table_mirror_incremental():
    entry = _entry()
    pc = PagedCache(entry, max_batch=3, max_seq=32, page_size=8,
                    num_pages=12, share=True)
    ref = lambda: np.where(pc.tables < 0, pc.num_pages,    # noqa: E731
                           pc.tables)
    pc.tables_device()                          # build the mirror once
    prompt = np.arange(20, dtype=np.int32)
    assert pc.alloc_slot(0, 21, tokens=prompt)
    assert pc._tables_dev is not None           # refreshed, not dropped
    np.testing.assert_array_equal(np.asarray(pc.tables_device()), ref())
    assert pc.alloc_slot(1, 21, tokens=prompt)  # maps nothing yet (no KV)
    assert pc.extend_slot(0, 25)
    np.testing.assert_array_equal(np.asarray(pc.tables_device()), ref())
    assert pc.fork_page(0, 0) in (True, False)  # exercise _mirror_set
    np.testing.assert_array_equal(np.asarray(pc.tables_device()), ref())
    pc.free_slot(0)
    assert pc._tables_dev is not None
    np.testing.assert_array_equal(np.asarray(pc.tables_device()), ref())
    assert pc.mirror_consistent()
    pc.defrag()                                 # wholesale renumber: drop
    np.testing.assert_array_equal(np.asarray(pc.tables_device()), ref())


# ---------------------------------------------------------------------------
# analytic mirror: fused sim clock
# ---------------------------------------------------------------------------
def _sim(**kw):
    spec = PAPER_MODELS["LLaMA3-70B"]
    lat = nmp_latency_model(snake_system(), spec, tp=8)
    return simulate_serving(lat, spec, 0.5, system="SNAKE",
                            n_requests=16, cache_mode="paged", **kw)


def test_sim_fused_clock_matches_per_tick():
    base = _sim()
    fused = _sim(fuse_steps=8)
    assert fused.fused_ticks > 0 and fused.fused_steps_mean > 1.0
    assert base.fused_ticks == 0 and base.fused_steps_mean == 0.0
    # fusing moves host boundaries, not modeled device work: token
    # counts agree exactly, and the clock only drifts by the admission
    # quantization (arrivals join at horizon boundaries, like the live
    # engine) — well under a percent at these horizons
    assert fused.decoded_tokens == base.decoded_tokens
    assert fused.completed == base.completed
    assert fused.makespan_s == pytest.approx(base.makespan_s, rel=0.01)
    assert fused.kv_peak_tokens == pytest.approx(base.kv_peak_tokens,
                                                 rel=0.01)
