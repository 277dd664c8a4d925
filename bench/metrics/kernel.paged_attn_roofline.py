"""Kernel: the least time the chip needs for the paged attention the
window asked for, over the kernel's device time, in percent.  The
least time is the larger of the live K/V bytes (every decoded token's
attended context, all layers) at the HBM peak and the attention FLOPs
at the bf16 peak.  Counts the same work whatever implements it.
Moves ``tpot_p90_ms``."""
from bench.flops import Shapes, attn_flops, paged_attn_bytes
from bench.peaks import peak_for


def read(run):
    t = run.device_trace
    if t is None or not t.kernel_s:
        return None
    attended = sum(k.attended for k in run.ticks)
    if not attended:
        return None
    s = Shapes.from_config(run.cell.config["model"])
    p = peak_for(t.device_kind)
    least = max(paged_attn_bytes(s, attended) / p.hbm_bytes_s,
                attn_flops(s, attended) / p.bf16_flops)
    return 100.0 * least / t.kernel_s
