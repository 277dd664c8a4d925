"""Drives the served path from the client's side: builds the engine
through the program's entry points (``make_engine``, ``Scheduler``),
warms every program the cell's traffic reaches, runs the warm ramp and
the measured window, and records what the client saw.

The harness times everything on ``time.perf_counter``.  A token counts
as delivered when the ``Scheduler.tick`` that produced it returns: that
is when a streaming client would get it.
"""
from __future__ import annotations

import contextlib
import dataclasses
import time
from dataclasses import dataclass, field
from typing import Dict, List, Tuple

import jax
import numpy as np

from bench import traffic as TR
from bench import weights as W

WARMUP_RID = 1 << 40      # warm-up request ids start here


class CompileCounter:
    """Counts executables built or loaded from the persistent cache
    (JAX's backend-compile event fires for both)."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.names: List[str] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == self.EVENT:
            self.names.append(str(kw.get("fun_name", "?")))


class ServedModel:
    """The program's model module, with ``init`` left to the
    benchmark: the engine's own init draws fixed weights, and the
    benchmark serves weights drawn from ``--seed`` instead.  Everything
    else is the program's module, unchanged."""

    def __init__(self, module):
        self._module = module

    def init(self, key, cfg, tp=1):
        return {}

    def __getattr__(self, name):
        return getattr(self._module, name)


def engine_config(cell_cfg: dict):
    from repro.serving.engine import EngineConfig
    return EngineConfig(**cell_cfg["engine"])


def arch_config(cell_cfg: dict, model):
    """The registry's configuration, checked against the file through
    the fields its model module says the file implies: the file holds
    the configuration as it is run, so any difference is an error, not
    a silent departure."""
    from repro.models import registry
    cfg = registry.get_config(cell_cfg["registry"])
    want = model.arch_fields(cell_cfg["model"])
    got = {k: getattr(cfg, k) for k in want}
    if got != want:
        raise ValueError(f"registry {cell_cfg['registry']!r} differs from "
                         f"the configuration file: {got} != {want}")
    return cfg


@dataclass
class Tick:
    t0: float
    t1: float
    steps: int                 # decode scan steps the device ran
    # per lane that decoded: (tokens decoded, cached tokens before the
    # first of them), so its i-th token attended ctx + i
    lanes: List[Tuple[int, int]]


@dataclass
class Client:
    """What the client saw of each request."""
    reqs: List[TR.Request]
    states: list                                  # RequestState, by rid
    t0: float = 0.0                               # ramp start
    delivered: List[int] = field(default_factory=list)
    times: List[List[float]] = field(default_factory=list)
    ticks: List[Tick] = field(default_factory=list)

    def __post_init__(self):
        self.delivered = [0] * len(self.reqs)
        self.times = [[] for _ in self.reqs]
        self._live: List[int] = []
        self._next = 0                            # next request not yet due

    def due_abs(self, i: int) -> float:
        return self.t0 + self.reqs[i].due_s

    def _refresh_live(self, now: float) -> None:
        while (self._next < len(self.reqs)
               and self.due_abs(self._next) <= now):
            self._live.append(self._next)
            self._next += 1

    def snapshot(self, now: float) -> Dict[int, int]:
        self._refresh_live(now)
        return {i: len(self.states[i].tokens_out) for i in self._live}

    def observe(self, before: Dict[int, int], t0: float, t1: float,
                horizons_before: int, eng) -> None:
        """Record the tokens a tick delivered, and the decode work it did."""
        lanes = []
        still = []
        for i in self._live:
            st = self.states[i]
            n = len(st.tokens_out)
            nb = before.get(i, n)
            if n > self.delivered[i]:
                self.times[i].extend([t1] * (n - self.delivered[i]))
                self.delivered[i] = n
            if n > nb:
                dec = n - nb - (1 if nb == 0 else 0)   # minus prefill's token
                if dec:
                    lanes.append((dec, len(st.prompt) + (n - dec) - 1))
            if not (st.done and self.delivered[i] >= self.reqs[i].decode_len):
                still.append(i)
        self._live = still
        if len(eng.fused_horizons) > horizons_before:
            k = eng.fused_horizons[-1]
            steps = 1 << (k - 1).bit_length()
        else:
            steps = 1 if lanes else 0
        self.ticks.append(Tick(t0, t1, steps, lanes))


def trace_span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on else \
        contextlib.nullcontext()


def drive(sch, client: Client, until: float, annotate: bool = False
          ) -> float:
    """Tick the scheduler until a tick ends at or after ``until``;
    returns that instant.  Sleeps only while nothing is resident and
    the next request is not yet due."""
    eng = sch.engine
    while True:
        now = time.perf_counter()
        if now >= until:
            return now
        if not eng.busy() and not eng.requeue and sch.pending \
                and client.t0 + sch.pending[0].arrival_s > now:
            with trace_span(annotate, "bench.wait_for_arrival"):
                time.sleep(min(0.02, client.t0 + sch.pending[0].arrival_s
                               - now, until - now))
            continue
        before = client.snapshot(now)
        nh = len(eng.fused_horizons)
        with trace_span(annotate, "bench.scheduler_tick"):
            sch.tick(now - client.t0)
        t1 = time.perf_counter()
        with trace_span(annotate, "bench.client_observe"):
            client.observe(before, now, t1, nh, eng)


def build(cell_cfg: dict, model, seed: int):
    """Engine and scheduler through the program's entry points, serving
    the seeded weights of the configuration's model module, made on the
    device in one call."""
    from repro.models import registry
    from repro.serving.engine import make_engine
    from repro.serving.scheduler import Scheduler
    entry = registry.from_config(arch_config(cell_cfg, model))
    entry = dataclasses.replace(entry, module=ServedModel(entry.module))
    eng = make_engine(entry, engine_config(cell_cfg))
    eng.params = model.weights(W.seed_key(seed), cell_cfg["model"])
    jax.block_until_ready(eng.params)
    return eng, Scheduler(eng)


def _states(reqs: List[TR.Request], rid0: int = 0):
    from repro.serving.scheduler import RequestState
    return [RequestState(rid0 + r.rid, r.prompt, arrival_s=r.due_s,
                         decode_len=r.decode_len) for r in reqs]


def warm_up(eng, sch, mix: dict, vocab: int) -> dict:
    """Run every program the mix can reach (``traffic.warmup_requests``),
    each phase to completion.  Returns what the engine did, so the
    caller can check that every path ran."""
    ecfg = eng.ecfg
    phases = TR.warmup_requests(mix, vocab, ecfg.prefill_chunk,
                                ecfg.fuse_steps)
    for n, phase in enumerate(phases):
        if n == len(phases) - 1:
            eng.paged.reset()       # empty: pages go out lowest first
        states = _states(phase, WARMUP_RID)
        sch.enqueue(states)
        while not all(s.done for s in states):
            sch.tick(float("inf"))
    jax.block_until_ready(eng.paged.store)
    buckets = sorted({1 << (k - 1).bit_length()
                      for k in eng.fused_horizons})
    want = sorted({1 << (k - 1).bit_length()
                   for k in range(2, ecfg.fuse_steps + 1)})
    if not eng.defrag_runs or buckets != want:
        raise RuntimeError(f"warm-up missed a path: defrag runs "
                           f"{eng.defrag_runs}, fused buckets {buckets} "
                           f"(want {want})")
    return {"defrag_runs": eng.defrag_runs, "fused_buckets": buckets}


def counters(eng) -> dict:
    """The engine's own counters that the per-layer readers difference."""
    return {"prefill_tokens_skipped": eng.prefill_tokens_skipped,
            "preemptions": eng.preemption_count,
            "active": len(eng.active)}


def start_schedule(sch, reqs: List[TR.Request]) -> Client:
    """Enqueue the whole open-loop schedule; the ramp starts now."""
    states = _states(reqs)
    client = Client(reqs, states, t0=time.perf_counter())
    sch.enqueue(states)
    return client


def waiting(sch, client: Client, at: float) -> int:
    """Requests due by ``at`` that the scheduler has not admitted."""
    return sum(1 for r in sch.pending if client.t0 + r.arrival_s <= at) \
        + len(sch.engine.requeue)


def sample_for_check(client: Client, seed: int,
                     max_requests: int = 8, min_tokens: int = 512
                     ) -> List[int]:
    """Requests whose served tokens the reference checks: the one with
    the most served tokens, then others drawn from the seed until some
    hundreds of tokens are in.  Finished requests first; requests still
    decoding make up the rest where too few finished."""
    served = [i for i, st in enumerate(client.states)
              if len(st.tokens_out) >= 1]
    finished = [i for i in served if client.states[i].done]
    rest = [i for i in served if not client.states[i].done]
    rng = np.random.default_rng(seed)
    out: List[int] = []
    for pool in (finished, rest):
        if not pool:
            continue
        longest = max(pool, key=lambda i: len(client.states[i].tokens_out))
        order = [longest] + [int(i) for i in rng.permutation(pool)
                             if i != longest]
        for i in order:
            if len(out) >= max_requests or sum(
                    len(client.states[j].tokens_out) for j in out) \
                    >= min_tokens:
                return out
            out.append(i)
    return out
