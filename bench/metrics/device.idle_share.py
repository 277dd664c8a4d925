"""Device: one minus the union of the intervals in which an operation
ran on the chip, over the traced window.  Moves ``tpot_p90_ms``."""


def read(run):
    t = run.device_trace
    if t is None or t.window_s <= 0:
        return None
    return 1.0 - t.busy_s / t.window_s
