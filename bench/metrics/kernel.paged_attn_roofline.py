"""Kernel: the least time the chip needs for the paged attention the
window asked for, over the kernel's device time, in percent.  The
least time is the larger of the live K/V bytes (every decoded token's
attended context, all layers) at the HBM peak and the attention FLOPs
at the bf16 peak, as the cell's model module counts them.  Counts the
same work whatever implements it.  Moves ``tpot_p90_ms``."""
from bench.peaks import peak_for


def read(run):
    t = run.device_trace
    if t is None or not t.kernel_s:
        return None
    m, model = run.cell.model, run.cell.config["model"]
    kv_bytes = m.paged_attn_bytes(model, run.ticks)
    if not kv_bytes:
        return None
    p = peak_for(t.device_kind)
    least = max(kv_bytes / p.hbm_bytes_s,
                m.attn_flops(model, run.ticks) / p.bf16_flops)
    return 100.0 * least / t.kernel_s
