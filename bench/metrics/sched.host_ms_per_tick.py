"""Scheduler: the engine thread's own time outside blocking fetches,
per decode tick, in ms: the sum of ``dur - wait_s`` over the window's
``prefill_chunk``, ``fused_tick`` and ``decode_step`` events, over the
count of ``fused_tick`` and ``decode_step`` events.  A program whose
decode events carry no ``wait_s`` reads nothing.  Moves
``output_tok_s``."""


def read(run):
    host = 0.0
    ticks = 0
    for e in run.events:
        if e.kind in ("fused_tick", "decode_step"):
            if "wait_s" not in e.args:
                return None
            ticks += 1
        elif e.kind != "prefill_chunk":
            continue
        host += e.dur - e.args.get("wait_s", 0.0)
    return host / ticks * 1e3 if ticks else None
