"""Structured lifecycle tracing for the serving path (PR 9).

One :class:`Tracer` collects typed :class:`TraceEvent` records from every
layer of the stack — ``Router`` (dispatch), ``Scheduler`` (arrival /
admit), ``PagedServingEngine`` (prefill chunks, decode steps, fused
ticks with their horizon-clamp reason, growth / preemption, substrate
reconfigurations, finishes), and ``PagedCache`` (CoW forks, defrag,
spilled-page migration).  The analytic mirrors in
``core/serving_sim.py`` emit the *same* event schema on the modeled
clock, so an engine trace and a sim trace can be diffed event-by-event.

Tracing must never perturb the tokens: every emitter sits behind an
``if tracer.enabled`` branch and the default :data:`NULL_TRACER` is a
no-op whose ``enabled`` attribute is a plain ``False`` — the hot path
pays one attribute load + branch when tracing is off.

Timestamps are seconds on the *emitting* clock relative to the tracer's
origin: wall ``time.perf_counter`` for the live engine (origin = first
event), the modeled clock for the sims (construct with ``t0=0.0``).
``dur`` is the span length; instantaneous events carry ``dur == 0``.

The live engine opens its spans with :meth:`Tracer.span`: for its
extent a span is also a ``jax.profiler.TraceAnnotation`` named
``serve.<kind>``, so a ``jax.profiler`` trace shows the engine's work
on the device trace's clock, and on exit it emits the same event as
``emit`` would.  ``phase(name)`` opens a child annotation
``serve.<kind>.<name>``; ``wait()`` is the phase that blocks on a
device fetch, and its seconds add up in the event's ``wait_s``.
Children are profiler annotations only, never events, so span events
stay disjoint.  The sims emit on their modeled clock and never open
one.
Exporters (Perfetto JSON, JSONL save/replay, ``trace_report``) live in
:mod:`repro.obs.export`.
"""
from __future__ import annotations

import contextlib
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional

#: The event schema.  One entry per lifecycle edge; ``args`` carries the
#: per-kind payload (documented in README "Observability"):
#:
#: arrival        request entered a scheduler queue (args: arrival_s,
#:                prompt_len)
#: dispatch       router picked a replica (args: policy)
#: admit          scheduler admission succeeded (args: requeued)
#: prefill_chunk  one prefill chunk advanced (args: tokens, pos, last;
#:                wait_s on the last, whose first token is fetched)
#: decode_step    one per-tick decode iteration (args: batch, finished,
#:                wait_s, grid_pages, live_pages)
#: fused_tick     one K-step fused lax.scan tick (args: batch, horizon,
#:                clamp in {fuse_steps, page_edge, budget}, finished,
#:                wait_s, grid_pages, live_pages)
#: grow           on-demand page growth before a decode step (args: pages)
#: preempt        youngest-first preemption (args: preemptions)
#: cow_fork       copy-on-write fork of a shared page (args: block, page)
#: defrag         page-pool compaction ran (args: live_pages, moved,
#:                cost_s)
#: migrate        spilled pages re-homed (args: pages, cost_s)
#: ship           KV pages shipped between tiers at prefill handoff
#:                (args: pages, bytes, cost_s, src, dst); sims charge
#:                dur on the modeled clock
#: reconfigure    substrate shape-profile change (args: old, new,
#:                modeled_reconfig_s); sims charge dur on their clock
#: finish         request retired (args: reason, tokens)
#: gauge          per-tick counter sample (args: one value per counter
#:                track, e.g. free_pages / min_region_free /
#:                modeled_tokens_per_s)
#:
#: wait_s is the seconds a span spent blocked in its device fetch;
#: grid_pages / live_pages count the paged decode attention's pages in
#: lane-page units (per layer, per KV head): the blocks of table entries
#: the kernel walks for every lane at every scan step the device ran,
#: and the pages that held context for the lanes that emitted a token at
#: that step.
EVENT_KINDS = (
    "arrival", "dispatch", "admit", "prefill_chunk", "decode_step",
    "fused_tick", "grow", "preempt", "cow_fork", "defrag", "migrate",
    "ship", "reconfigure", "finish", "gauge",
)


@dataclass
class TraceEvent:
    ts: float                   # seconds since tracer origin (span start)
    kind: str                   # one of EVENT_KINDS
    replica: int = 0            # Perfetto pid (one process per replica)
    slot: int = -1              # Perfetto tid - 1 (-1: engine-level lane)
    rid: int = -1               # request id (-1: not request-scoped)
    dur: float = 0.0            # span length (0: instantaneous)
    args: Dict[str, Any] = field(default_factory=dict)

    def to_json(self) -> dict:
        return {"ts": self.ts, "kind": self.kind, "replica": self.replica,
                "slot": self.slot, "rid": self.rid, "dur": self.dur,
                "args": self.args}

    @classmethod
    def from_json(cls, d: dict) -> "TraceEvent":
        return cls(ts=d["ts"], kind=d["kind"],
                   replica=d.get("replica", 0), slot=d.get("slot", -1),
                   rid=d.get("rid", -1), dur=d.get("dur", 0.0),
                   args=d.get("args", {}))


def _check_kind(kind: str) -> None:
    if kind not in EVENT_KINDS:
        raise ValueError(f"unknown trace event kind {kind!r} "
                         f"(know {EVENT_KINDS})")


class Span:
    """One open span of the live engine (see the module docstring).
    ``args`` may be filled until the span closes; ``discard()`` closes
    it without an event."""

    __slots__ = ("_tracer", "kind", "args", "_ann", "_ts", "_keep")

    def __init__(self, tracer, kind: str, args: Dict[str, Any]):
        self._tracer = tracer
        self.kind = kind
        self.args = args
        self._keep = True

    def __enter__(self) -> "Span":
        import jax                 # lazily: the sims never open a span
        self._ann = jax.profiler.TraceAnnotation(f"serve.{self.kind}")
        self._ann.__enter__()
        self._ts = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        t1 = time.perf_counter()
        self._ann.__exit__(*exc)
        if self._keep and exc[0] is None:
            self._tracer.emit(self.kind, ts=self._ts, dur=t1 - self._ts,
                              **self.args)

    @contextlib.contextmanager
    def phase(self, name: str):
        import jax
        with jax.profiler.TraceAnnotation(f"serve.{self.kind}.{name}"):
            yield self

    @contextlib.contextmanager
    def wait(self):
        t0 = time.perf_counter()
        with self.phase("wait"):
            yield self
        self.args["wait_s"] = (self.args.get("wait_s", 0.0)
                               + time.perf_counter() - t0)

    def discard(self) -> None:
        self._keep = False


class _NullSpan:
    """The one span of :data:`NULL_TRACER`: opens no annotation, times
    nothing, emits nothing, and has no ``args`` to fill."""

    __slots__ = ()

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc) -> None:
        return None

    def phase(self, name: str) -> "_NullSpan":
        return self

    def wait(self) -> "_NullSpan":
        return self

    def discard(self) -> None:
        return None


_NULL_SPAN = _NullSpan()


class NullTracer:
    """No-op tracer: the hot path's default.  ``enabled`` is a plain
    class attribute so the guard is one load + branch; ``emit`` accepts
    the full signature and drops everything, and ``span`` returns one
    shared no-op span."""

    enabled = False

    def emit(self, kind: str, *, ts: Optional[float] = None,
             replica: Optional[int] = None, slot: int = -1, rid: int = -1,
             dur: float = 0.0, **args) -> None:
        return None

    def span(self, kind: str, **args) -> _NullSpan:
        return _NULL_SPAN

    def for_replica(self, replica: int) -> "NullTracer":
        return self

    @property
    def events(self) -> List[TraceEvent]:
        return []


NULL_TRACER = NullTracer()


class Tracer(NullTracer):
    """Collects :class:`TraceEvent` records in emission order.

    ``t0`` anchors the time origin.  ``None`` (the default) locks it to
    the first emitted event's timestamp — right for wall-clock tracing,
    where ``time.perf_counter`` values are arbitrary.  Pass ``t0=0.0``
    when emitting modeled-clock timestamps (the analytic sims).
    """

    enabled = True

    def __init__(self, t0: Optional[float] = None):
        self._t0 = t0
        self._events: List[TraceEvent] = []

    def emit(self, kind: str, *, ts: Optional[float] = None,
             replica: Optional[int] = None, slot: int = -1, rid: int = -1,
             dur: float = 0.0, **args) -> None:
        _check_kind(kind)
        if ts is None:
            ts = time.perf_counter()
        if self._t0 is None:
            self._t0 = ts
        self._events.append(TraceEvent(
            ts=ts - self._t0, kind=kind,
            replica=0 if replica is None else replica,
            slot=slot, rid=rid, dur=dur, args=args))

    def span(self, kind: str, **args) -> Span:
        """A span of ``kind`` on the wall clock, to use as a context
        manager; ``args`` (and what is added to ``Span.args`` before it
        closes) become the event's."""
        _check_kind(kind)
        return Span(self, kind, args)

    def for_replica(self, replica: int) -> "_BoundTracer":
        """A view of this tracer whose events default to ``replica`` —
        each engine replica gets one (its Perfetto process id)."""
        return _BoundTracer(self, replica)

    @property
    def events(self) -> List[TraceEvent]:
        return self._events


class _BoundTracer:
    """Replica-bound view over a shared :class:`Tracer`."""

    __slots__ = ("_tracer", "replica", "enabled")

    def __init__(self, tracer: Tracer, replica: int):
        self._tracer = tracer
        self.replica = replica
        self.enabled = tracer.enabled

    def emit(self, kind: str, *, ts: Optional[float] = None,
             replica: Optional[int] = None, slot: int = -1, rid: int = -1,
             dur: float = 0.0, **args) -> None:
        self._tracer.emit(kind, ts=ts,
                          replica=self.replica if replica is None
                          else replica,
                          slot=slot, rid=rid, dur=dur, **args)

    def span(self, kind: str, **args) -> Span:
        _check_kind(kind)
        return Span(self, kind, args)

    def for_replica(self, replica: int) -> "_BoundTracer":
        return _BoundTracer(self._tracer, replica)

    @property
    def events(self) -> List[TraceEvent]:
        return self._tracer.events
