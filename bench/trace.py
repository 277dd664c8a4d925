"""Reduction of a JAX profiler trace to the device numbers that the
per-layer metrics read.

The trace (``<dir>/plugins/profile/<time>/*.xplane.pb``) is read with
``jax.profiler.ProfileData``.  Device events sit on the plane
``/device:TPU:<n>``: the line ``XLA Modules`` holds one event per
program run (``jit_<name>(<fingerprint>)``) and ``XLA Ops`` one per
HLO operation, named by the operation's HLO text.  Host events are those
of the ``/host:CPU`` line (thread) that holds the benchmark's window
annotation: its annotations (``bench.*``) and JAX's own dispatch
events.  The Python tracer is off: it records
every call, and a window's worth overflows the host buffers.  All are
nanoseconds from the start of the trace, on one clock.

- busy: the union of the operation intervals, inside the window that
  the annotation ``bench.window`` spans;
- the paged attention kernel: custom calls whose HLO name is the
  program's ``attention_decode_paged`` wrapper;
- the decode programs: every module whose runs hold such a call.
"""
from __future__ import annotations

import glob
import os
import re
import shutil
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
KERNEL = "attention_decode_paged"
_OP_NAME = re.compile(r"^%?([^\s=]+)")
_CONTAINERS = ("while", "conditional", "call")

Span = Tuple[float, float]            # start, end (ns)


@dataclass
class Event:
    name: str
    start: float                      # ns from the trace's start
    dur: float

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclass
class DeviceTrace:
    device_kind: str
    window_s: float
    busy_s: float
    decode_s: float                   # runs of the decode programs
    kernel_s: float                   # the paged attention kernel
    top_ops: List[Tuple[str, float]] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    def breakdown(self) -> dict:
        return {"device_ops": [list(x) for x in self.top_ops[:10]],
                "idle_gaps": [list(x) for x in self.idle_gaps[:10]]}


def union(spans: Iterable[Span]) -> List[Span]:
    """Merged, sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(spans):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(spans: Sequence[Span], lo: float, hi: float) -> List[Span]:
    return [(max(s, lo), min(e, hi)) for s, e in spans
            if e > lo and s < hi]


def op_name(hlo_text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    m = _OP_NAME.match(hlo_text)
    return m.group(1) if m else hlo_text


def is_kernel(hlo_text: str) -> bool:
    return op_name(hlo_text).startswith(KERNEL) and \
        "custom-call(" in hlo_text


def gaps(busy: Sequence[Span], lo: float, hi: float) -> List[Span]:
    """The idle intervals of [lo, hi] between busy spans."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def host_activity(host: Sequence[Event], at: float) -> str:
    """The innermost host event running at ``at`` (the shortest one that
    covers it), or ``idle host``."""
    best: Optional[Event] = None
    for e in host:
        if e.start <= at <= e.end and (best is None or e.dur < best.dur):
            best = e
    return best.name if best else "idle host"


def reduce_events(device_kind: str, modules: Sequence[Event],
                  ops: Sequence[Event], host: Sequence[Event]
                  ) -> DeviceTrace:
    """The numbers of one traced window, from its events."""
    wins = [e for e in host if e.name == WINDOW]
    if not wins:
        raise ValueError(f"the trace holds no {WINDOW!r} annotation")
    lo, hi = wins[0].start, wins[0].end
    busy = clip(union((e.start, e.end) for e in ops), lo, hi)
    kernels = [e for e in ops if is_kernel(e.name)]
    decode_runs = [m for m in modules
                   if any(m.start <= k.start < m.end for k in kernels)]
    decode_names = {m.name for m in decode_runs}
    in_win = lambda e: lo <= e.start < hi   # noqa: E731
    decode_s = sum(m.dur for m in modules
                   if m.name in decode_names and in_win(m)) / 1e9
    kernel_s = sum(k.dur for k in kernels if in_win(k)) / 1e9
    by_op: Dict[str, float] = {}
    for e in ops:
        n = op_name(e.name)
        if in_win(e) and not n.startswith(_CONTAINERS):
            by_op[n] = by_op.get(n, 0.0) + e.dur / 1e9
    top = sorted(by_op.items(), key=lambda kv: -kv[1])[:10]
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])[:10]
    named = [(host_activity(host, (s + e) / 2), (e - s) / 1e9)
             for s, e in idle]
    return DeviceTrace(device_kind=device_kind, window_s=(hi - lo) / 1e9,
                       busy_s=sum(e - s for s, e in busy) / 1e9,
                       decode_s=decode_s, kernel_s=kernel_s, top_ops=top,
                       idle_gaps=named)


def profile_options():
    """Device and host events; no Python call tracing."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    return opts


def _events(line) -> List[Event]:
    return [Event(e.name, float(e.start_ns), float(e.duration_ns))
            for e in line.events]


def read_profile(path: str, device: int = 0):
    """(modules, ops, host events) of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    modules: List[Event] = []
    ops: List[Event] = []
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name == f"/device:TPU:{device}":
            for line in plane.lines:
                if line.name == "XLA Modules":
                    modules = _events(line)
                elif line.name == "XLA Ops":
                    ops = _events(line)
        elif plane.name == "/host:CPU":
            # the thread that ran the window: its annotations and JAX's
            # dispatch events name what the host was doing
            for line in plane.lines:
                evs = _events(line)
                if any(e.name == WINDOW for e in evs):
                    host = evs
    return modules, ops, host


def reduce_dir(trace_dir: str, device_kind: str) -> DeviceTrace:
    """Reduce the one trace under ``trace_dir``, then delete it."""
    try:
        files = glob.glob(os.path.join(trace_dir, "plugins", "profile",
                                       "*", "*.xplane.pb"))
        if len(files) != 1:
            raise FileNotFoundError(f"expected one trace under "
                                    f"{trace_dir}, found {files}")
        return reduce_events(device_kind, *read_profile(files[0]))
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
