"""Fused decode loop: fusion-horizon x batch sweep on the real engine.

The per-tick engine pays a host round-trip per decoded token (dispatch,
fetch, bookkeeping); the fused engine scans K steps on device and
surfaces only at horizon boundaries.  This sweep measures what that
buys on the *decode phase*: every config submits one full batch of
page-aligned prompts (prefill is synchronous, identical serial work on
both engines, and untimed), then times the drain-to-completion decode
loop wall-clock.  Per fusion horizon and batch size:

  * decode tokens/s (wall clock, best of REPS) and speedup vs per-tick
  * host-overhead fraction of the fused ticks (host / (host+device))
  * mean realized horizon (page windows and budgets clip fuse_steps)

Prompt lengths are page multiples ({32, 64, 96}, skewed short) so the
fusion horizon opens to a full page instead of collapsing to the
nearest ragged page edge.  Token streams are asserted identical across
every fusion horizon and every rep — the fused engine is an overhead
optimization, never a decoding change.  Each config reuses one warm
engine across reps (the jit cache is per-engine); the host is shared
and single-core, so the best rep is the config's throughput and the
per-rep values are recorded for transparency.  Also writes the
acceptance artifact ``BENCH_serving_fused.json`` at the repo root
(tokens/s per config + the >=2x @ batch>=64 headline).

  PYTHONPATH=src:. python benchmarks/serving_fused.py
"""
from __future__ import annotations

import argparse
import json
import os
import time
from typing import List

import numpy as np

from benchmarks.common import Row, emit
from repro.models import registry
from repro.serving.engine import EngineConfig, make_engine, make_trace

ARCH = "yi-6b"
PAGE = 32
MAX_SEQ = 224            # 7 pages: up to 96 prompt + 128 decode
MAX_NEW = 128            # decode-dominated: the loop under test is decode
SEED = 0
REPS = 3
ROOT_ARTIFACT = os.path.join(os.path.dirname(__file__), "..",
                             "BENCH_serving_fused.json")


def page_aligned_prompt_lens(n: int, seed: int) -> np.ndarray:
    """Skewed over {32, 64, 96}: mostly short, a long tail — but every
    length a page multiple, so lanes stay phase-locked and the horizon
    opens to the full page instead of the nearest ragged page edge."""
    rng = np.random.default_rng(seed + 1234)
    return rng.choice([PAGE, 2 * PAGE, 3 * PAGE], size=n,
                      p=[0.5, 0.3, 0.2]).astype(np.int64)


def _run_config(entry, batch: int, fuse: int, max_new: int,
                reps: int) -> dict:
    ecfg = EngineConfig(max_batch=batch, max_seq=MAX_SEQ,
                        max_new_tokens=max_new, paged=True,
                        page_size=PAGE, fuse_steps=fuse)
    eng = make_engine(entry, ecfg)
    # warm every jit bucket outside the timed region: one prompt per
    # length (prefill compiles per length) + a full decode (the fused
    # scan and per-tick step compile per batch / horizon bucket)
    warm = make_trace(entry.config.vocab, rate_req_s=1e6, n_requests=3,
                      prompt_len=0,
                      prompt_lens=np.array([PAGE, 2 * PAGE, 3 * PAGE]),
                      seed=99)
    eng.run_trace(warm)
    plens = page_aligned_prompt_lens(batch, SEED)
    tok_s, tokens = [], None
    for _ in range(reps):
        eng.completed.clear()
        eng.reset_fused_counters()
        reqs = make_trace(entry.config.vocab, rate_req_s=1e6,
                          n_requests=batch, prompt_len=0,
                          prompt_lens=plens, seed=SEED)
        for r in reqs:                       # synchronous prefill, untimed
            assert eng.submit(r), "one wave must fit the batch"
        t0 = time.perf_counter()
        while eng.busy():                    # the decode loop under test
            eng.tick()
        wall = time.perf_counter() - t0
        decoded = sum(len(r.tokens_out) for r in eng.completed)
        tok_s.append(decoded / wall)
        rep_tokens = {r.rid: list(r.tokens_out) for r in eng.completed}
        assert tokens is None or rep_tokens == tokens, \
            "decoding must be deterministic across reps"
        tokens = rep_tokens
    fr = eng.fused_report()
    return {"tokens_per_s": max(tok_s), "tokens_per_s_reps": tok_s,
            "_tokens": tokens, "fused_ticks": fr.get("fused_ticks", 0),
            "fused_steps_mean": fr.get("fused_steps_mean", 0.0)}


def run(smoke: bool = False) -> List[Row]:
    entry = registry.get(ARCH, reduced=True)
    batches = (8,) if smoke else (8, 64)
    fuses = (1, 8) if smoke else (1, 8, 32)
    max_new = 32 if smoke else MAX_NEW
    reps = 1 if smoke else REPS
    rows: List[Row] = []
    artifact = {"arch": ARCH, "page_size": PAGE, "max_new": max_new,
                "reps": reps, "measured": "decode-phase wall clock",
                "smoke": smoke, "configs": {}}
    for batch in batches:
        base = None
        for fuse in fuses:
            m = _run_config(entry, batch, fuse, max_new, reps)
            tag = f"b{batch}/fuse{fuse}"
            if fuse == fuses[0]:
                base = m
            else:
                assert m["_tokens"] == base["_tokens"], (
                    f"{tag}: fused tokens diverged from per-tick")
            speedup = m["tokens_per_s"] / max(base["tokens_per_s"], 1e-12)
            rows.append(Row(f"serving_fused/{tag}/decode_tokens_per_s",
                            m["tokens_per_s"]))
            rows.append(Row(f"serving_fused/{tag}/speedup_vs_per_tick",
                            speedup))
            rows.append(Row(f"serving_fused/{tag}/fused_steps_mean",
                            m["fused_steps_mean"]))
            artifact["configs"][tag] = {
                "decode_tokens_per_s": m["tokens_per_s"],
                "decode_tokens_per_s_reps": m["tokens_per_s_reps"],
                "speedup_vs_per_tick": speedup,
                "fused_ticks": m["fused_ticks"],
                "fused_steps_mean": m["fused_steps_mean"],
                "tokens_identical_to_per_tick": fuse == fuses[0] or
                m["_tokens"] == base["_tokens"],
            }
    if not smoke:
        headline = artifact["configs"]["b64/fuse32"]["speedup_vs_per_tick"]
        artifact["headline_speedup_b64"] = headline
        rows.append(Row("serving_fused/headline_speedup_b64", headline,
                        note="fused(32) vs per-tick decode at batch 64"))
        # acceptance artifact: full sweeps only (smoke must not clobber)
        with open(ROOT_ARTIFACT, "w") as f:
            json.dump(artifact, f, indent=1)
    return rows


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    args = ap.parse_args()
    t0 = time.time()
    emit("serving_fused", run(smoke=args.smoke), time.time() - t0)
