"""Run one cell of the benchmark once, on the chip this process finds.

    python3 bench/run.py --workload yi6b.chat --seed 7 --seconds 30 --trace 0

``--trace 0`` prints the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiled window.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, [``breakdown``], ``check``); the last lines of
standard error repeat each number the check compared, with its limit.

Steps: weights from the seed on the device; every program the cell's
traffic reaches is warmed; the traffic's schedule runs a warm ramp;
then the window measures for ``--seconds``.  All of that before the
window is ``setup_s``.  An executable built or loaded inside the window
fails the run.  After the window the program's state is freed and the
plain reference of the configuration's model module
(``bench/models/<module>.py``) recomputes a seeded sample of served
requests (``bench/reference.py``).

``--control 1`` puts the control in the program's place for the
check: the reference in the precision below the served one (float8
for bfloat16), read for the token it puts first at each position of
the same sample, judged by the same limit.  Its run
has to come out not correct.  The benchmark's own runs do not use it.

A cell runs one engine on one chip; a cell that asks for more chips
needs a cluster driver (``make_cluster`` + ``Router``) that the harness
does not have yet, and is refused.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int) -> dict:
    """The accelerator, or an exit: the benchmark never falls back to
    the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform "
                 f"{devs[0].platform!r}); nothing was run")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} chips, JAX found "
                 f"{len(devs)}")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def run_cell(cell, seed: int, seconds: float, trace: bool,
             control: bool = False, t_start: float = T_START) -> dict:
    """One run of ``cell``; returns the result line's fields."""
    import jax
    import numpy as np

    from bench import e2e, reference, serve, spec, traffic
    from bench import trace as TRC
    if cell.chips != 1:
        raise ValueError(f"{cell.name}: the harness drives one engine on "
                         f"one chip; a cell on {cell.chips} chips needs "
                         f"a cluster driver it does not have")
    cfg = cell.config
    counter = serve.CompileCounter()
    eng, sch = serve.build(cfg, cell.model, seed)
    t_built = time.perf_counter()
    vocab = cfg["model"]["vocab_size"]
    warm = serve.warm_up(eng, sch, cell.traffic, vocab)
    t_warm = time.perf_counter()
    reqs = traffic.generate(cell.traffic, vocab, seed, seconds)
    client = serve.start_schedule(sch, reqs)
    t_open = serve.drive(sch, client, client.t0 + cell.traffic["ramp_s"])
    setup_s = t_open - t_start
    backlog_open = serve.waiting(sch, client, t_open)
    log(f"set-up {setup_s:.1f} s: build {t_built - t_start:.1f}, warm-up "
        f"{t_warm - t_built:.1f} ({warm}), ramp {t_open - t_warm:.1f}; "
        f"{counter.count} executables built or loaded")
    tracer = None
    if trace:
        from repro.obs.tracer import Tracer
        tracer = Tracer(t0=0.0)
        eng.set_tracer(tracer)
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        jax.profiler.start_trace(trace_dir,
                                 profiler_options=TRC.profile_options())
        t_open = time.perf_counter()
    counts_open = serve.counters(eng)
    compiles_open = counter.count
    ticks_open = len(client.ticks)
    with serve.trace_span(trace, TRC.WINDOW):
        t_close = serve.drive(sch, client, t_open + seconds,
                              annotate=trace)
        if trace:
            jax.block_until_ready(eng.paged.store)
            t_close = time.perf_counter()
    compiles = counter.count - compiles_open
    device_trace = None
    if trace:
        jax.profiler.stop_trace()
    counts_close = serve.counters(eng)
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))
    log(f"compilations inside the window: {compiles}")
    if compiles:
        raise RuntimeError(f"{compiles} executables were built or loaded "
                           f"inside the window: "
                           f"{counter.names[compiles_open:]}")
    if trace:
        device_trace = TRC.reduce_dir(trace_dir, dev.device_kind)
    run = RunRecord(cell=cell, client=client, t_open=t_open,
                    t_close=t_close, ticks=client.ticks[ticks_open:],
                    counters_open=counts_open, counters_close=counts_close,
                    events=tracer.events if tracer else [],
                    device_trace=device_trace, max_batch=eng.ecfg.max_batch)
    if trace:
        metrics = spec.read_metrics(cell.per_layer, run)
    else:
        metrics = {}
        for m in cell.end_to_end:
            v = e2e.compute(m.name, client, t_open, t_close, setup_s)
            if v is not None:
                metrics[m.name] = {"value": v, "unit": m.unit}
    attempted = sum(1 for i in range(len(reqs))
                    if client.due_abs(i) < t_close
                    and not (client.states[i].done
                             and client.times[i]
                             and client.times[i][-1] <= t_open))
    due = [i for i in range(len(reqs)) if client.due_abs(i) < t_close]
    in_window = sum(1 for i in due if client.due_abs(i) >= t_open)
    log(f"window {t_close - t_open:.2f} s, {len(run.ticks)} ticks; "
        f"{len(due)} requests due by its close, {in_window} inside it, "
        f"{len(e2e.tpot_samples(client.times, t_open, t_close))} with "
        f"2+ tokens in it, "
        f"{sum(client.states[i].done for i in due)} finished, "
        f"waiting to be admitted {backlog_open} at the open, "
        f"{serve.waiting(sch, client, t_close)} at the close; "
        f"peak {peak} bytes")
    # the check: the program's state goes first, then the reference
    eng_max_new = eng.ecfg.max_new_tokens
    picks = serve.sample_for_check(client, seed)
    seqs = [(client.states[i].prompt,
             np.asarray(client.states[i].tokens_out, np.int32))
            for i in picks]
    del eng, sch, run
    gc.collect()
    jax.clear_caches()
    t_ref = time.perf_counter()
    gaps = (reference.served_gaps(cell.model.forward, cfg["model"], seed,
                                  seqs, control=control) if seqs else
            {"served_gap": None, "served_tokens": 0, "control_gap": None})
    log(f"reference over {len(seqs)} requests "
        f"({gaps['served_tokens']} served tokens): "
        f"{time.perf_counter() - t_ref:.1f} s")
    if control:
        log(f"the program's own served_gap {gaps['served_gap']!r}")
    check, correct = decide(cfg["check"], gaps, control)
    out = {"correct": correct, "attempted": attempted,
           "failed": failed(client, eng_max_new, t_open, t_close),
           "metrics": metrics, "device": {"memory_peak_bytes": peak}}
    if device_trace is not None:
        out["device"]["busy_s"] = device_trace.busy_s
        out["device"]["window_s"] = device_trace.window_s
        out["breakdown"] = device_trace.breakdown()
    out["check"] = check
    return out


def decide(limits: dict, gaps: dict, control: bool):
    """The numbers compared, each with its limit, and ``correct``: the
    program's served tokens, or with ``control`` the control's tokens in
    their place, judged by the same limit."""
    name = "control_gap" if control else "served_gap"
    gap, limit = gaps[name], limits["served_gap_limit"]
    check = {name: {"value": gap, "limit": limit},
             "served_tokens": {"value": gaps["served_tokens"],
                               "limit": limits["min_served_tokens"]}}
    correct = (gaps["served_tokens"] >= limits["min_served_tokens"]
               and gap is not None and gap <= limit)
    return check, bool(correct)


def failed(client, max_new_tokens: int, t_open: float,
           t_close: float) -> int:
    """Requests finished in the window with another number of tokens
    than their budget: the program cut them short or ran them over."""
    n = 0
    for i, st in enumerate(client.states):
        ts = client.times[i]
        if st.done and ts and t_open < ts[-1] <= t_close:
            want = min(max_new_tokens, max(1, client.reqs[i].decode_len))
            n += len(st.tokens_out) != want
    return n


def log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


class RunRecord:
    """What a per-layer reader gets: the client's record of the window,
    the engine's counters at its ends, the program's trace events and
    the reduced device trace (None without ``--trace 1``)."""

    def __init__(self, **kw):
        self.__dict__.update(kw)


def main(argv=None) -> None:
    args = parse(argv)
    from bench import spec
    cell = spec.load_cell(args.workload)
    info = device_info(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    # cache every executable, however quick to build: set-up then loads
    # the same programs in every run after the first
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    log(f"{info['platform']} {info['kind']} x{info['count']}; "
        f"compile cache {cache}")
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                   bool(args.control))
    out["device"] = {**info, **out["device"]}
    out = {k: out[k] for k in ("correct", "attempted", "failed", "metrics",
                               "device", "breakdown", "check") if k in out}
    for name, c in out["check"].items():
        print(f"[check] {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
