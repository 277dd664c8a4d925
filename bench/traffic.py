"""The one traffic generator: a mix is a JSON file of parameters under
``bench/traffic/<name>.json``, and this module turns it and a seed into
requests.

Gaps, lengths and prefix choices are the quantiles of their
distributions at (i + 1/2) / n, so every schedule holds the same work.
Their order is drawn from the mix's ``schedule_seed``: the schedule is
one fixed replay, and ``--seed`` changes the token ids (and the
weights).

Parameters (all lengths in tokens):

- ``arrival``: ``{"kind": "poisson", "rate_req_s": r}``, open loop, or
  ``{"kind": "backlog", "requests": n}``, all due when the ramp starts.
  A Poisson arrival may add ``"bursts": {"factor": f, "every_s": p,
  "for_s": w}``: the rate is f x r for the first w seconds of every p.
- ``ramp_s``: seconds the schedule runs before the window opens.
- ``shared_prefix``: ``{"tokens": t, "count": c}``: each request starts
  with one of c prefixes (system prompts); ``tokens`` 0 for none.  With
  more than one, ``"popularity"`` is ``"round_robin"`` (request i takes
  prefix i mod c) or ``{"zipf": s}`` (prefix j in proportion to
  1 / (j + 1)**s).
- ``prompt`` / ``output``: ``{"median", "sigma", "min", "max"}`` of a
  lognormal, or ``{"mixture": [{"weight": w, "median", ...}, ...]}``;
  the prompt is the part after the shared prefix, the output is the
  decode budget.
- ``round_to``: prompt parts are rounded up to a multiple of this.
- ``schedule_seed``: fixes the order of gaps, lengths and prefixes.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist
from typing import List

import numpy as np


@dataclass
class Request:
    rid: int
    due_s: float            # seconds after the ramp starts
    prompt: np.ndarray      # int32 token ids
    shared: int             # leading tokens of the shared prefix
    decode_len: int         # output tokens, the first one included


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def _lognormal_values(spec: dict, n: int) -> np.ndarray:
    z = np.array([NormalDist().inv_cdf(q) for q in _quantiles(n)])
    v = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(v, spec["min"], spec["max"])


def _shares(weights: List[float], n: int) -> List[int]:
    """``n`` split in proportion to ``weights``, largest remainder."""
    w = np.asarray(weights, float) / sum(weights)
    raw = w * n
    out = np.floor(raw).astype(int)
    for k in np.argsort(-(raw - out), kind="stable")[:n - out.sum()]:
        out[k] += 1
    return [int(x) for x in out]


def _lengths(spec: dict, n: int, rng) -> np.ndarray:
    """n lengths of one lognormal or of a mixture, in an order drawn
    from ``rng``."""
    if "mixture" in spec:
        parts = spec["mixture"]
        v = np.concatenate([_lognormal_values(p, k) for p, k in
                            zip(parts, _shares([p["weight"] for p in parts],
                                               n))])
    else:
        v = _lognormal_values(spec, n)
    return rng.permutation(v)


def _rate_integral(t: float, arr: dict) -> float:
    """Expected arrivals in [0, t): the rate, with its bursts."""
    r = arr["rate_req_s"]
    b = arr.get("bursts")
    if b is None:
        return r * t
    per = r * (b["every_s"] + (b["factor"] - 1) * b["for_s"])
    k, x = divmod(t, b["every_s"])
    return k * per + r * (x + (b["factor"] - 1) * min(x, b["for_s"]))


def _arrival_time(u: float, arr: dict) -> float:
    """The time at which ``_rate_integral`` reaches ``u``."""
    r = arr["rate_req_s"]
    b = arr.get("bursts")
    if b is None:
        return u / r
    per = r * (b["every_s"] + (b["factor"] - 1) * b["for_s"])
    k, x = divmod(u, per)
    hot = r * b["factor"] * b["for_s"]
    inside = x / (r * b["factor"]) if x < hot else \
        b["for_s"] + (x - hot) / r
    return k * b["every_s"] + inside


def n_requests(mix: dict, seconds: float) -> int:
    """Requests a run of ``seconds`` needs: a backlog's own count, or
    enough arrivals to cover the ramp, the window and a tenth more."""
    arr = mix["arrival"]
    if arr["kind"] == "backlog":
        return int(arr["requests"])
    if arr["kind"] == "poisson":
        expected = _rate_integral(mix["ramp_s"] + seconds, arr)
        return int(math.ceil(expected * 1.1)) + 4
    raise ValueError(f"unknown arrival kind {arr['kind']!r}")


def _prefix_choice(sp: dict, n: int, rng) -> np.ndarray:
    """Which shared prefix each request starts with."""
    c = sp["count"]
    if c <= 1:
        return np.zeros(n, np.int64)
    pop = sp["popularity"]
    if pop == "round_robin":
        return np.arange(n) % c
    w = 1.0 / np.arange(1, c + 1) ** pop["zipf"]
    cdf = np.cumsum(w) / w.sum()
    idx = np.searchsorted(cdf, _quantiles(n))
    return rng.permutation(np.minimum(idx, c - 1))


def generate(mix: dict, vocab: int, seed: int, seconds: float
             ) -> List[Request]:
    rng = np.random.default_rng(seed)
    order = np.random.default_rng(mix["schedule_seed"])
    n = n_requests(mix, seconds)
    arr = mix["arrival"]
    if arr["kind"] == "poisson":
        gaps = order.permutation(-np.log1p(-_quantiles(n)))
        expected = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
        due = np.array([_arrival_time(u, arr) for u in expected])
    else:
        due = np.zeros(n)
    step = int(mix["round_to"])
    body = (np.ceil(_lengths(mix["prompt"], n, order) / step) * step
            ).astype(np.int64)
    outs = np.round(_lengths(mix["output"], n, order)).astype(np.int64)
    sp = mix["shared_prefix"]
    prefixes = [rng.integers(0, vocab, sp["tokens"]).astype(np.int32)
                for _ in range(sp["count"] if sp["tokens"] else 0)]
    which = _prefix_choice(sp, n, order) if prefixes else None
    reqs = []
    for i in range(n):
        pre = prefixes[which[i]] if prefixes else np.zeros(0, np.int32)
        tail = rng.integers(0, vocab, int(body[i])).astype(np.int32)
        reqs.append(Request(i, float(due[i]), np.concatenate([pre, tail]),
                            len(pre), int(outs[i])))
    return reqs


def warmup_requests(mix: dict, vocab: int, prefill_chunk: int,
                    fuse_steps: int) -> List[List[Request]]:
    """Requests that make the engine run every program the mix can
    reach, in phases that run one after the other.

    - one request per prefill chunk length the mix can produce (every
      multiple of ``round_to`` up to the chunk), each with a budget of
      one token, so it finishes at its prefill;
    - one request per fused horizon bucket (8, 4, 2 steps and the
      one-step path), each alone so nothing clamps its horizon;
    - two requests whose first one ends while the second still holds
      pages above it, so the pool is compacted (defrag); the caller
      starts this phase on an empty, reset pool so the first request
      holds the lowest pages.
    """
    rng = np.random.default_rng(0)
    step = int(mix["round_to"])

    def req(rid, n, decode_len):
        return Request(rid, 0.0, rng.integers(0, vocab, n).astype(np.int32),
                       0, decode_len)

    phases = [[req(i, t, 1) for i, t in
               enumerate(range(step, prefill_chunk + 1, step))]]
    rid = len(phases[0])
    k = fuse_steps
    while k >= 1:
        phases.append([req(rid, step, 1 + k)])
        rid += 1
        k //= 2
    phases.append([req(rid, 2 * prefill_chunk, 2 + fuse_steps),
                   req(rid + 1, step, 3)])
    return phases
