"""The reduction from a profiler trace to device numbers, on synthetic
events laid out as a TPU trace lays them out."""
import pytest

from bench import trace as T

MS = 1e6          # ns


def ev(name, start_ms, dur_ms):
    return T.Event(name, start_ms * MS, dur_ms * MS)


KERNEL = ("%attention_decode_paged.12 = bf16[32,4,16,128] custom-call("
          "s32[32,256] %get-tuple-element.1, s32[32] %x)")
MATMUL = "%fusion.130 = bf16[32,4096] fusion(bf16[32,4096] %a, bf16[32] %b)"
WHILE = "%while.38 = (s32[], bf16[32,4096]) while(%tuple.1)"


def synthetic():
    # window 0-100 ms; a decode program 10-50 ms (loop holding two kernel
    # calls and a matmul), a prefill program 60-70 ms, idle elsewhere
    modules = [ev("jit__lambda(111)", 10, 40), ev("jit__lambda(222)", 60, 10),
               ev("jit__lambda(111)", 80, 10), ev("jit__lambda(111)", 120, 5)]
    ops = [ev(WHILE, 10, 40), ev(KERNEL, 12, 15), ev(MATMUL, 28, 5),
           ev(KERNEL, 34, 15), ev(MATMUL, 60, 10),
           ev(WHILE, 80, 10), ev(KERNEL, 81, 8),
           ev(KERNEL, 120, 5)]                      # after the window
    host = [ev("bench.window", 0, 100), ev("bench.scheduler_tick", 0, 93),
            ev("$engine.py:805 _prefill_chunk_tick", 52, 6),
            ev("$engine.py:1078 _fused_tick", 71, 8)]
    return modules, ops, host


def test_union_and_gaps():
    assert T.union([(5, 7), (0, 2), (1, 3), (6, 9)]) == [(0, 3), (5, 9)]
    assert T.gaps([(2, 3), (5, 9)], 0, 10) == [(0, 2), (3, 5), (9, 10)]
    assert T.clip([(0, 3), (5, 9)], 1, 6) == [(1, 3), (5, 6)]


def test_kernel_selection_by_hlo_name():
    assert T.is_kernel(KERNEL)
    assert not T.is_kernel(MATMUL)
    assert not T.is_kernel("%attention_decode_paged.3 = bf16[2] copy(%a)")
    assert T.op_name(KERNEL) == "attention_decode_paged.12"


def test_reduction_busy_decode_kernel_and_idle():
    r = T.reduce_events("TPU v5 lite", *synthetic())
    assert r.window_s == pytest.approx(0.100)
    # ops cover 10-50, 60-70, 80-90 inside the window
    assert r.busy_s == pytest.approx(0.060)
    # module 111 holds the kernel: its runs inside the window, 40 + 10 ms
    assert r.decode_s == pytest.approx(0.050)
    assert r.kernel_s == pytest.approx(0.015 + 0.015 + 0.008)
    ops = dict(r.top_ops)
    assert ops["attention_decode_paged.12"] == pytest.approx(0.038)
    assert ops["fusion.130"] == pytest.approx(0.015)
    assert not any(n.startswith("while") for n in ops)
    # idle: 0-10, 50-60, 70-80, 90-100; each named by the innermost host
    # event at its middle
    assert sorted(n for n, _ in r.idle_gaps) == sorted([
        "bench.scheduler_tick", "$engine.py:805 _prefill_chunk_tick",
        "$engine.py:1078 _fused_tick", "bench.window"])
    assert [s for _, s in r.idle_gaps] == pytest.approx([0.010] * 4)
    b = r.breakdown()
    assert set(b) == {"device_ops", "idle_gaps"}
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_reduction_without_kernel_reads_no_kernel_time():
    modules, ops, host = synthetic()
    ops = [o for o in ops if not T.is_kernel(o.name)]
    r = T.reduce_events("TPU v5 lite", modules, ops, host)
    assert r.kernel_s == 0.0 and r.decode_s == 0.0


def test_reduction_needs_the_window_annotation():
    modules, ops, host = synthetic()
    with pytest.raises(ValueError, match="bench.window"):
        T.reduce_events("TPU v5 lite", modules, ops, host[1:])
