"""CPU tests of the benchmark's yardstick: traffic, end-to-end
arithmetic, and the backlog against the chip's peaks.  No accelerator
is needed or loaded.  The dense module's FLOP/byte counts are tested in
``test_bench_dense.py``."""
import json
import os

import numpy as np
import pytest

from bench import e2e, peaks, spec, traffic

CHAT = {"arrival": {"kind": "poisson", "rate_req_s": 2.0}, "ramp_s": 5.0,
        "shared_prefix": {"tokens": 64, "count": 1},
        "prompt": {"median": 96, "sigma": 0.8, "min": 32, "max": 400},
        "output": {"median": 20, "sigma": 0.8, "min": 4, "max": 60},
        "round_to": 16, "schedule_seed": 11}
BACKLOG = {"arrival": {"kind": "backlog", "requests": 12}, "ramp_s": 1.0,
           "shared_prefix": {"tokens": 0, "count": 0},
           "prompt": {"median": 200, "sigma": 0.5, "min": 64, "max": 300},
           "output": {"median": 30, "sigma": 0.5, "min": 8, "max": 50},
           "round_to": 16, "schedule_seed": 12}


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["poisson", "backlog"])
def test_traffic_deterministic_per_seed(mix):
    a = traffic.generate(mix, 1000, 2**33 + 5, 10.0)
    b = traffic.generate(mix, 1000, 2**33 + 5, 10.0)
    c = traffic.generate(mix, 1000, 7, 10.0)
    assert [(r.due_s, r.decode_len, r.prompt.tolist()) for r in a] == \
        [(r.due_s, r.decode_len, r.prompt.tolist()) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in c]


@pytest.mark.parametrize("mix", [CHAT, BACKLOG], ids=["poisson", "backlog"])
def test_traffic_lengths_within_clips_and_rounded(mix):
    reqs = traffic.generate(mix, 1000, 3, 10.0)
    sp = mix["shared_prefix"]["tokens"]
    for r in reqs:
        body = len(r.prompt) - r.shared
        assert r.shared == sp
        assert body % 16 == 0
        assert mix["prompt"]["min"] <= body < mix["prompt"]["max"] + 16
        assert mix["output"]["min"] <= r.decode_len <= mix["output"]["max"]
        assert (r.prompt >= 0).all() and (r.prompt < 1000).all()


def test_traffic_seeds_share_one_multiset_of_sizes():
    """Schedule seeds reorder the same gaps and lengths: a schedule
    holds the same work whatever its order."""
    a = traffic.generate(CHAT, 1000, 1, 20.0)
    b = traffic.generate({**CHAT, "schedule_seed": 99}, 1000, 1, 20.0)
    key = lambda reqs: (sorted(len(r.prompt) for r in reqs),
                        sorted(r.decode_len for r in reqs))
    assert key(a) == key(b)
    assert [r.due_s for r in a] != [r.due_s for r in b]
    assert all(x.due_s <= y.due_s for x, y in zip(a, a[1:]))
    assert a[0].due_s == 0.0


def test_schedule_seed_fixes_sizes_and_arrivals_across_seeds():
    a = traffic.generate(CHAT, 1000, 1, 20.0)
    b = traffic.generate(CHAT, 1000, 2, 20.0)
    assert [(r.due_s, len(r.prompt), r.decode_len) for r in a] == \
        [(r.due_s, len(r.prompt), r.decode_len) for r in b]
    assert [r.prompt.tolist() for r in a] != [r.prompt.tolist() for r in b]


def test_poisson_requests_cover_ramp_and_window():
    reqs = traffic.generate(CHAT, 1000, 5, 20.0)
    assert traffic.n_requests(CHAT, 20.0) == len(reqs)
    assert reqs[-1].due_s >= CHAT["ramp_s"] + 20.0


BURSTS = {"factor": 3.0, "every_s": 20.0, "for_s": 5.0}


def test_bursts_triple_the_rate_inside_them():
    mix = {**CHAT, "arrival": {"kind": "poisson", "rate_req_s": 2.0,
                               "bursts": BURSTS}, "ramp_s": 0.0}
    reqs = traffic.generate(mix, 1000, 5, 400.0)
    due = np.array([r.due_s for r in reqs])
    assert (np.diff(due) >= 0).all() and due[-1] >= 400.0
    due = due[due < 400.0]
    hot = (due % 20.0) < 5.0
    # 5 s at 6 req/s against 15 s at 2 req/s in every period
    assert hot.sum() / len(due) == pytest.approx(0.5, abs=0.05)
    # a period holds 2 x (20 + 2 x 5) = 60 expected arrivals
    assert traffic.n_requests(mix, 40.0) == int(np.ceil(120 * 1.1)) + 4


@pytest.mark.parametrize("t", [0.0, 3.0, 5.0, 12.5, 20.0, 47.0, 1234.5])
def test_burst_arrival_time_inverts_the_rate_integral(t):
    arr = {"kind": "poisson", "rate_req_s": 0.7, "bursts": BURSTS}
    u = traffic._rate_integral(t, arr)
    assert traffic._arrival_time(u, arr) == pytest.approx(t)


def test_zipf_prefix_popularity():
    mix = {**CHAT, "shared_prefix": {"tokens": 32, "count": 4,
                                     "popularity": {"zipf": 1.0}}}
    reqs = traffic.generate(mix, 1000, 9, 200.0)
    heads = [r.prompt[:32].tobytes() for r in reqs]
    counts = sorted((heads.count(h) for h in set(heads)), reverse=True)
    assert len(counts) == 4 and counts[0] > counts[1] > counts[3] > 0
    w = 1 / np.arange(1, 5)
    assert counts[0] / len(reqs) == pytest.approx(w[0] / w.sum(), abs=0.02)
    rr = traffic.generate({**mix, "shared_prefix": {
        "tokens": 32, "count": 4, "popularity": "round_robin"}}, 1000, 9,
        200.0)
    assert len({r.prompt[:32].tobytes() for r in rr[:4]}) == 4
    assert rr[0].prompt[:32].tolist() == rr[4].prompt[:32].tolist()


def test_length_mixture_keeps_each_component_and_its_share():
    short = {"weight": 1, "median": 100, "sigma": 0.3, "min": 64, "max": 256}
    long = {"weight": 3, "median": 2500, "sigma": 0.2, "min": 2048,
            "max": 3584}
    mix = {**BACKLOG, "arrival": {"kind": "backlog", "requests": 40},
           "prompt": {"mixture": [short, long]}}
    body = [len(r.prompt) for r in traffic.generate(mix, 1000, 1, 10.0)]
    n_short = sum(b <= 256 for b in body)
    assert n_short == 10
    assert all(2048 <= b < 3584 + 16 for b in body if b > 256)
    assert body[:10] != sorted(body[:10])         # the order is drawn


def test_shares_split_by_largest_remainder():
    assert traffic._shares([1, 3], 40) == [10, 30]
    assert traffic._shares([1, 1, 1], 10) == [4, 3, 3]
    assert sum(traffic._shares([0.2, 0.5, 0.3], 7)) == 7


def test_unrounded_lengths():
    mix = {**BACKLOG, "round_to": 1}
    body = [len(r.prompt) for r in traffic.generate(mix, 1000, 1, 10.0)]
    assert any(b % 16 for b in body)


def test_warmup_covers_every_chunk_length_and_horizon():
    phases = traffic.warmup_requests(CHAT, 1000, prefill_chunk=64,
                                     fuse_steps=8)
    assert sorted(len(r.prompt) for r in phases[0]) == [16, 32, 48, 64]
    assert all(r.decode_len == 1 for r in phases[0])
    assert [p[0].decode_len - 1 for p in phases[1:5]] == [8, 4, 2, 1]
    rids = [r.rid for p in phases for r in p]
    assert len(set(rids)) == len(rids)


def test_percentile_metrics_are_named_by_data():
    class Client:
        reqs = [None] * 4
        times = [[1.5, 2.0, 2.5], [3.0], [4.0, 4.0, 6.0], []]

        @staticmethod
        def due_abs(i):
            return [1.0, 1.0, 2.0, 5.0][i]
    # TTFT samples 0.5, 2.0, 2.0, and the stall 5.0 (10 - 5)
    assert e2e.compute("ttft_p50_ms", Client, 0.5, 10.0, 0.0) == \
        pytest.approx(2000.0)
    assert e2e.compute("ttft_p95_ms", Client, 0.5, 10.0, 0.0) == \
        pytest.approx(5000.0)
    # TPOT samples 0.5 and 1.0
    assert e2e.compute("tpot_p90_ms", Client, 0.5, 10.0, 0.0) == \
        pytest.approx(1000.0)
    with pytest.raises(KeyError):
        e2e.compute("ttft_mean_ms", Client, 0.5, 10.0, 0.0)


def test_nearest_rank():
    assert e2e.nearest_rank([], 90) is None
    assert e2e.nearest_rank([5.0], 90) == 5.0
    assert e2e.nearest_rank(list(range(1, 11)), 90) == 9
    assert e2e.nearest_rank(list(range(1, 11)), 100) == 10
    assert e2e.nearest_rank(list(range(1, 21)), 90) == 18


def test_ttft_counts_stalls_at_the_close():
    due = [0.5, 1.0, 2.0, 9.5, 20.0]
    first = [0.7, None, 2.5, None, None]
    got = e2e.ttft_samples(due, first, t_open=1.0, t_close=10.0)
    # due 0.5 is before the window; due 20 after it
    assert got == pytest.approx([9.0, 0.5, 0.5])


def test_tpot_per_request_over_window_tokens_only():
    times = [[0.5, 1.5, 2.0, 2.0, 3.0],      # 4 in window: (3 - 1.5) / 3
             [1.2, 1.2],                     # one fused tick: 0 / 1
             [5.0],                          # a single token: no sample
             [11.0, 12.0]]                   # after the close
    got = e2e.tpot_samples(times, t_open=1.0, t_close=10.0)
    assert got == pytest.approx([0.5, 0.0])


def test_output_rate_counts_tokens_in_the_window_over_its_length():
    times = [[1.0, 1.5, 2.0], [9.0, 10.0, 10.5]]
    assert e2e.output_tokens(times, 1.0, 10.0) == 4
    # a token delivered exactly at the open belongs to the tick before


# the chip every cell runs on; its peaks bound how fast any program
# could drain a backlog
CHIP = "TPU v5 lite"
with open(os.path.join(spec.ROOT, "BENCHMARK.json")) as f:
    BENCHMARK = json.load(f)
BACKLOG_CELLS = [w["name"] for w in BENCHMARK["workloads"]
                 if spec.load_cell(w["name"]).traffic["arrival"]["kind"]
                 == "backlog"]


def roofline_finished(cell: spec.Cell, peak: peaks.Peak,
                      seconds: float) -> int:
    """Requests of the cell's mix, in ``generate``'s order, that a chip
    at its peaks could finish within ``seconds``: each costs at least its
    prompt's FLOPs at the bf16 peak, and one decode step's weight read
    at the HBM peak for every ``max_batch`` tokens it decodes after its
    first (``prefill_flops_per_token`` and ``weight_bytes_per_step`` of
    the cell's model module)."""
    m, model = cell.model, cell.config["model"]
    max_batch = cell.config["engine"]["max_batch"]
    weight_read_s = m.weight_bytes_per_step(model, max_batch) \
        / peak.hbm_bytes_s
    t, done = 0.0, 0
    for r in traffic.generate(cell.traffic, model["vocab_size"], 0, seconds):
        t += (m.prefill_flops_per_token(model) * len(r.prompt)
              / peak.bf16_flops
              + (r.decode_len - 1) / max_batch * weight_read_s)
        if t > seconds:
            break
        done += 1
    return done


@pytest.mark.parametrize("name", BACKLOG_CELLS)
def test_backlog_outlasts_the_roofline(name):
    """A backlog cell measures throughput only while its lanes stay
    full: it holds at least twice the requests that the chip at its
    peaks could finish within the ramp and the window, so a faster
    program still finds work waiting at the close."""
    cell = spec.load_cell(name)
    seconds = cell.traffic["ramp_s"] + BENCHMARK["run_seconds"]
    could = roofline_finished(cell, peaks.peak_for(CHIP), seconds)
    assert could > 0
    assert cell.traffic["arrival"]["requests"] >= 2 * could
