"""End-to-end metrics from the client's record of a window.

All on the host clock.  The window is [t_open, t_close]; both ends are
tick boundaries, so a rate covers whole ticks and all of their time.
"""
from __future__ import annotations

import math
import re
from typing import List, Optional, Sequence

# ``ttft_p50_ms``, ``tpot_p90_ms``: a percentile of per-request samples
_PERCENTILE = re.compile(r"^(ttft|tpot)_p(\d{1,2})_ms$")


def nearest_rank(values: Sequence[float], pct: float) -> Optional[float]:
    """The ``pct`` percentile by nearest rank (None for no values)."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(pct / 100.0 * len(v)) - 1)]


def ttft_samples(due: Sequence[float], first: Sequence[Optional[float]],
                 t_open: float, t_close: float) -> List[float]:
    """First-token time minus due time of every request due in the
    window.  One with no first token by the close counts at the close,
    so a stall raises the tail instead of leaving it."""
    out = []
    for d, f in zip(due, first):
        if t_open <= d < t_close:
            out.append((f if f is not None and f <= t_close else t_close)
                       - d)
    return out


def tpot_samples(times: Sequence[Sequence[float]], t_open: float,
                 t_close: float) -> List[float]:
    """Per request with two or more tokens delivered in the window:
    (last - first delivery) / (tokens - 1)."""
    out = []
    for ts in times:
        w = [t for t in ts if t_open < t <= t_close]
        if len(w) >= 2:
            out.append((w[-1] - w[0]) / (len(w) - 1))
    return out


def output_tokens(times: Sequence[Sequence[float]], t_open: float,
                  t_close: float) -> int:
    return sum(1 for ts in times for t in ts if t_open < t <= t_close)


def compute(name: str, client, t_open: float, t_close: float,
            setup_s: float) -> Optional[float]:
    """The end-to-end metric ``name`` of one run (None: nothing to read)."""
    if name == "setup_s":
        return setup_s
    if name == "output_tok_s":
        return output_tokens(client.times, t_open, t_close) \
            / (t_close - t_open)
    m = _PERCENTILE.match(name)
    if m is None:
        raise KeyError(f"no end-to-end metric {name!r}")
    v = nearest_rank(samples(m.group(1), client, t_open, t_close),
                     int(m.group(2)))
    return None if v is None else v * 1e3


def samples(kind: str, client, t_open: float, t_close: float
            ) -> List[float]:
    """Per-request seconds of the window: ``ttft`` or ``tpot``."""
    if kind == "tpot":
        return tpot_samples(client.times, t_open, t_close)
    due = [client.due_abs(i) for i in range(len(client.reqs))]
    first = [ts[0] if ts else None for ts in client.times]
    return ttft_samples(due, first, t_open, t_close)
