"""The per-layer readers of the program's own spans and counters, on
synthetic records of a window: the right value where the program emits
them, nothing where it does not."""
import pytest

from bench import spec
from repro.obs import TraceEvent


class Run:
    def __init__(self, events):
        self.events = events


def ev(kind, dur=0.0, **args):
    return TraceEvent(ts=0.0, kind=kind, dur=dur, args=args)


def window():
    """Two fused ticks and a one-step tick, with prefill chunks between
    them; an admission and gauges that the readers pass over."""
    return [
        ev("admit", requeued=False),
        ev("prefill_chunk", 0.020, tokens=256, pos=256, last=False),
        ev("fused_tick", 3.400, wait_s=3.300, grid_pages=8 * 4 * 256,
           live_pages=3000, batch=4, horizon=8),
        ev("gauge", active=4),
        ev("prefill_chunk", 0.050, wait_s=0.040, tokens=128, pos=384,
           last=True),
        ev("decode_step", 0.430, wait_s=0.420, grid_pages=4 * 256,
           live_pages=400, batch=4, finished=1),
        ev("fused_tick", 1.800, wait_s=1.650, grid_pages=4 * 4 * 256,
           live_pages=1500, batch=4, horizon=4),
    ]


def test_grid_live_share_sums_counts_over_decode_events():
    read = spec.reader("kernel.paged_grid_live_share")
    assert read(Run(window())) == pytest.approx(
        (3000 + 400 + 1500) / (8192 + 1024 + 4096))


def test_host_ms_per_tick_is_self_time_outside_fetches_per_tick():
    read = spec.reader("sched.host_ms_per_tick")
    host = (0.020 + (3.400 - 3.300) + (0.050 - 0.040) + (0.430 - 0.420)
            + (1.800 - 1.650))
    assert read(Run(window())) == pytest.approx(host / 3 * 1e3)


@pytest.mark.parametrize("metric", ["kernel.paged_grid_live_share",
                                    "sched.host_ms_per_tick"])
def test_a_program_without_the_counts_reads_nothing(metric):
    """An engine whose events carry neither ``wait_s`` nor grid counts
    gives nothing to read, and no error."""
    read = spec.reader(metric)
    older = [ev("prefill_chunk", 0.02, tokens=256, pos=256, last=False),
             ev("fused_tick", 3.4, batch=4, horizon=8, device_s=3.3)]
    assert read(Run(older)) is None
    assert read(Run([])) is None
    assert read(Run([ev("prefill_chunk", 0.02, last=False)])) is None
