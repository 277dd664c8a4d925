"""Model step: device time of the decode programs in the trace (those
whose XLA module holds the paged attention kernel) over the scan steps
they ran.  Moves ``tpot_p90_ms``."""


def read(run):
    t = run.device_trace
    steps = sum(k.steps for k in run.ticks)
    if t is None or not steps or not t.decode_s:
        return None
    return t.decode_s / steps * 1e3
