"""Model step: the decode steps' model FLOPs (every matmul weight per
token decoded, plus attention over the live context, ``bench.flops``)
over their device time times the chip's bf16 peak, in percent.
Moves ``tpot_p90_ms``."""
from bench.flops import Shapes, decode_flops
from bench.peaks import peak_for


def read(run):
    t = run.device_trace
    if t is None or not t.decode_s:
        return None
    lane_steps = sum(k.lane_steps for k in run.ticks)
    attended = sum(k.attended for k in run.ticks)
    if not lane_steps:
        return None
    s = Shapes.from_config(run.cell.config["model"])
    flops = decode_flops(s, lane_steps, attended)
    return 100.0 * flops / (t.decode_s * peak_for(t.device_kind).bf16_flops)
