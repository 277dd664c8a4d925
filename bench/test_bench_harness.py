"""The whole harness on the CPU at a tiny size: the run reaches its
result, its check passes on the sound program and fails when the timed
path is broken underneath.  The look for a chip is skipped; everything
after it is the run as the chip sees it."""
import dataclasses

import pytest

from bench import run as RUN
from bench import spec

TINY_MODEL = {
    "hidden_size": 64, "intermediate_size": 128, "num_attention_heads": 4,
    "num_key_value_heads": 2, "num_hidden_layers": 2, "vocab_size": 4096,
    "head_dim": 16, "rope_theta": 10000.0, "rms_norm_eps": 1e-06,
    "norm": "rmsnorm", "torch_dtype": "bfloat16",
}
TINY_ENGINE = {
    "max_batch": 4, "max_seq": 192, "max_new_tokens": 48, "paged": True,
    "page_size": 16, "num_pages": 48, "use_pallas_decode": False,
    "fuse_steps": 8, "prefill_chunk": 32, "prefix_sharing": True,
}
TINY_MIX = {"arrival": {"kind": "poisson", "rate_req_s": 12.0},
            "ramp_s": 0.3, "shared_prefix": {"tokens": 32, "count": 1},
            "prompt": {"median": 40, "sigma": 0.6, "min": 16, "max": 80},
            "output": {"median": 24, "sigma": 0.5, "min": 8, "max": 48},
            "round_to": 16, "schedule_seed": 3}
# bfloat16 program against the float32 reference at this size (CPU, 8
# seeds): widest gaps of sound runs 0.0005-0.0031; the float8 control
# 0.011-0.023; an altered token more
TINY_CHECK = {"served_gap_limit": 0.006, "min_served_tokens": 100}


def tiny_cell(chips=1, **mix_over):
    cfg = {"registry": "yi-6b", "module": "dense", "model": TINY_MODEL,
           "engine": TINY_ENGINE, "check": TINY_CHECK}
    metrics = [spec.Metric(n, u) for n, u in
               [("ttft_p50_ms", "ms"), ("tpot_p90_ms", "ms"),
                ("output_tok_s", "tokens/s"), ("setup_s", "s")]]
    return spec.Cell(name="tiny.chat", chips=chips, config=cfg,
                     model=spec.model("dense"),
                     traffic={**TINY_MIX, **mix_over},
                     end_to_end=metrics, per_layer=[])


@pytest.fixture
def tiny_registry(monkeypatch):
    """The registry's Yi-6B cut to the tiny file above (the harness
    checks the registry against the file, so both must agree)."""
    from repro.models import registry
    small = dataclasses.replace(
        registry.get_config("yi-6b"), num_layers=2, d_model=64,
        num_q_heads=4, num_kv_heads=2, d_head=16, d_ff=128, vocab=4096,
        rope_theta=10000.0)
    monkeypatch.setattr(registry, "get_config", lambda name: small)


def test_sound_run_is_correct(tiny_registry):
    out = RUN.run_cell(tiny_cell(), seed=2**31 + 11, seconds=1.0,
                       trace=False)
    assert out["correct"], out["check"]
    assert set(out["check"]) == {"served_gap", "served_tokens"}
    assert out["check"]["served_tokens"]["value"] >= 100
    m = out["metrics"]
    assert set(m) == {"ttft_p50_ms", "tpot_p90_ms", "output_tok_s",
                      "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert out["attempted"] > 0 and out["failed"] == 0


def test_control_in_the_programs_place_is_not_correct(tiny_registry):
    """The reference in float8, read for the token it puts first, goes
    through the same decision as the program's tokens and fails it."""
    out = RUN.run_cell(tiny_cell(), seed=2**31 + 11, seconds=1.0,
                       trace=False, control=True)
    assert not out["correct"], out["check"]
    assert set(out["check"]) == {"control_gap", "served_tokens"}
    assert out["check"]["control_gap"]["value"] > TINY_CHECK[
        "served_gap_limit"]


def test_a_cell_on_more_chips_is_refused():
    with pytest.raises(ValueError, match="4 chips"):
        RUN.run_cell(tiny_cell(chips=4), seed=1, seconds=1.0, trace=False)


def test_decide_judges_the_compared_gap_by_its_limit():
    limits = {"served_gap_limit": 0.5, "min_served_tokens": 100}
    gaps = {"served_gap": 0.1, "control_gap": 1.4, "served_tokens": 300}
    assert RUN.decide(limits, gaps, control=False)[1]
    check, ok = RUN.decide(limits, gaps, control=True)
    assert not ok and check["control_gap"] == {"value": 1.4, "limit": 0.5}
    assert not RUN.decide(limits, {**gaps, "served_tokens": 99}, False)[1]
    assert not RUN.decide(limits, {**gaps, "served_gap": None}, False)[1]


def test_failed_counts_requests_finished_off_their_budget():
    class St:
        def __init__(self, n, done=True):
            self.tokens_out, self.done = [0] * n, done

    class Req:
        def __init__(self, n):
            self.decode_len = n

    class Client:
        reqs = [Req(3), Req(3), Req(600), Req(3), Req(3)]
        states = [St(3), St(2), St(512), St(1, done=False), St(2)]
        times = [[2.0] * 3, [2.0] * 2, [3.0] * 512, [2.0], [0.5] * 2]
    # the second was cut short; the third ran to the engine's ceiling;
    # the fourth is still decoding; the fifth finished before the open
    assert RUN.failed(Client, 512, 1.0, 10.0) == 1


def test_altered_token_is_caught(tiny_registry, monkeypatch):
    """The first step's token of every lane, altered where the fused
    decode produces it."""
    from repro.models import transformer
    fused = transformer.decode_fused_paged

    def altered(*a, **kw):
        toks, emitted, kp, vp, ln = fused(*a, **kw)
        return toks.at[0].set((toks[0] + 1) % 4096), emitted, kp, vp, ln

    monkeypatch.setattr(transformer, "decode_fused_paged", altered)
    out = RUN.run_cell(tiny_cell(), seed=5, seconds=1.0, trace=False)
    assert not out["correct"], out["check"]
