"""Cache: preemptions in the window (the engine's ``preemption_count``)
per request that held a lane in it: those decoding when it opened and
those admitted during it.  Moves ``output_tok_s``."""


def read(run):
    admitted = sum(1 for e in run.events
                   if e.kind == "admit" and not e.args.get("requeued"))
    held = run.counters_open["active"] + admitted
    pre = (run.counters_close["preemptions"]
           - run.counters_open["preemptions"])
    return pre / held if held else None
