"""Model step: the decode steps' model FLOPs (every matmul weight per
token decoded, plus attention over the live context, as the cell's
model module counts them) over their device time times the chip's bf16
peak, in percent.  Moves ``tpot_p90_ms``."""
from bench.peaks import peak_for


def read(run):
    t = run.device_trace
    if t is None or not t.decode_s:
        return None
    flops = run.cell.model.decode_flops(run.cell.config["model"], run.ticks)
    if not flops:
        return None
    return 100.0 * flops / (t.decode_s * peak_for(t.device_kind).bf16_flops)
