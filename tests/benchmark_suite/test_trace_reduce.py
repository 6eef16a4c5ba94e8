"""The reduction from a profiler trace to numbers, on a small trace recorded on
a TPU v5 lite (``benchmark/testdata/small_tpu.xplane.pb.gz``, 10 KB: four calls
of a three-matmul program, each inside a ``bench.step`` span and followed by a
3-ms sleep inside ``bench.sleep`` and 2 ms outside any span), and on hand-made
intervals for what that trace does not hold (collectives)."""
import os

import pytest

import tiny_root
from benchmark.harness import trace

TRACE = os.path.join(tiny_root.REPO, "benchmark", "testdata", "small_tpu.xplane.pb.gz")


@pytest.fixture(scope="module")
def summary():
    assert os.path.getsize(TRACE) < 1 << 20
    return trace.reduce_xplane(TRACE, ("bench.",))


def test_busy_union_and_idle_share(summary):
    assert summary.devices == [0]
    assert summary.window_ns == (50854641.0, 72854374.0)          # first to last device operation
    assert summary.busy_ns[0] == pytest.approx(157089.0)          # 4 calls x ~39.3 us of operations
    assert summary.busy_s == pytest.approx(157089e-9)
    assert summary.window_s == pytest.approx(0.021999733)
    assert 100 * summary.idle_share == pytest.approx(99.28595, abs=1e-4)
    assert summary.busy_ns[0] + sum(summary.gap_ns.values()) == pytest.approx(summary.window_s * 1e9)


def test_time_by_operation(summary):
    top = summary.top_ops(3)
    assert [n for n, _ in top] == ["convolution_tanh_fusion.2 fusion bf16[1024,1024]",
                                   "convert_reduce_fusion fusion (f32[], +1)",
                                   "convolution_tanh_fusion.1 fusion bf16[1024,1024]"]
    assert [s for _, s in top] == pytest.approx([5.8938e-05, 5.179e-05, 4.6299e-05])
    assert summary.modules == {"jit_step": [39536.0, 39610.0, 39497.0, 39552.0]}
    assert summary.module_like("step")[0] == "jit_step" and summary.module_like("nothing") == (None, [])


def test_gaps_go_to_the_host_span_that_covers_them(summary):
    assert summary.gap_ns["bench.sleep"] == pytest.approx(10790680.0)   # 3 sleeps of ~3.6 ms inside the window
    assert summary.gap_ns["bench.step"] == pytest.approx(4640361.0)     # launch and the pull of the result
    assert summary.gap_ns["no_span"] == pytest.approx(6411582.0)        # 3 sleeps of ~2.1 ms outside any span
    assert summary.gap_ns["short_gaps"] == pytest.approx(21.0)
    assert [n for n, _ in summary.top_gaps(2)] == ["bench.sleep", "no_span"]
    assert len(summary.spans["bench.step"]) == 3 and len(summary.spans["bench.sleep"]) == 3
    without = trace.reduce_xplane(TRACE, ())
    assert without.gap_ns["no_span"] == pytest.approx(10790680.0 + 4640361.0 + 6411582.0)


def test_interval_arithmetic_and_attribution():
    assert trace.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    assert trace.total(trace.clip([(0, 3), (5, 6)], 2, 5.5)) == 1.5
    assert trace.complement([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]
    assert trace.subtract([(0, 10)], [(2, 3), (5, 7)]) == [(0, 2), (3, 5), (7, 10)]
    spans = [(0, 10, "outer"), (2, 4, "inner"), (20, 30, "later")]
    assert trace.attribute(1, 12, spans) == {"outer": 1 + 6, "inner": 2, "no_span": 2}


def test_exposed_collective_time_is_what_no_compute_covers():
    """all-reduce from 10 to 30 with compute from 0 to 18 and 26 to 28: exposed
    18..26 and 28..30 — by the same calls reduce_profile makes."""
    coll, rest = trace.union([(10, 30)]), trace.union([(0, 18), (26, 28)])
    assert trace.total(trace.subtract(coll, rest)) == 10
    assert trace.is_collective("all-reduce") and trace.is_collective("all-gather-start")
    assert trace.is_collective("reduce-scatter.3") and not trace.is_collective("fusion")
    assert not trace.is_collective("reduce")


def test_op_names_from_hlo_text():
    text = ("%fusion.1961 = bf16[1,64,16,1024,64]{4,3,2,1,0:T(8,128)(2,1)} fusion(bf16[64]{0} %p), "
            "kind=kLoop, calls=%fused_computation")
    assert trace.parse_op(text) == ("fusion.1961", "fusion", "bf16[1,64,16,1024,64]")
    assert trace.op_label(text) == "fusion.1961 fusion bf16[1,64,16,1024,64]"
    tup = "%ar = (f32[8]{0}, f32[4]{0}) all-reduce-start(f32[8]{0} %a, f32[4]{0} %b), replica_groups={}"
    assert trace.parse_op(tup) == ("ar", "all-reduce-start", "(f32[8], f32[4])")
    assert trace.op_label(tup) == "ar all-reduce-start (f32[8], +1)"
    assert trace.parse_op("jit_step(123)") == ("jit_step(123)", "", "")


class _Event:
    def __init__(self, name, start, dur):
        self.name, self.start_ns, self.duration_ns = name, float(start), float(dur)


class _Line:
    def __init__(self, name, events):
        self.name, self.events = name, events


class _Plane:
    def __init__(self, name, lines):
        self.name, self.lines = name, lines


class _Profile:
    def __init__(self, planes):
        self.planes = planes


def test_reduction_of_a_two_chip_trace_with_collectives():
    def device(n, shift):
        ops = [_Event("%f.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 0 + shift, 18),
               _Event("%ars = f32[8]{0} all-reduce-start(f32[8]{0} %f.1)", 10 + shift, 1),
               _Event("%f.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 26 + shift, 2),
               _Event("%ard = f32[8]{0} all-reduce-done(f32[8]{0} %ars)", 29 + shift, 1),
               _Event("%ag = f32[16]{0} all-gather(f32[8]{0} %ard)", 40 + shift, 5)]
        asyncs = [_Event("%ars = f32[8]{0} all-reduce-start(f32[8]{0} %f.1)", 10 + shift, 20)]
        return _Plane(f"/device:TPU:{n}", [_Line("XLA Ops", ops), _Line("Async XLA Ops", asyncs),
                                           _Line("XLA Modules", [_Event("jit__step(7)", shift, 45)])])
    host = _Plane("/host:CPU", [_Line("python", [_Event(trace.WINDOW_SPAN, 0, 50),
                                                _Event("train_step.step", 0, 32)])])
    s = trace.reduce_profile(_Profile([device(0, 0), device(1, 2), host]), ("train_step.",))
    assert s.devices == [0, 1] and s.window_ns == (0.0, 50.0)
    assert s.collective_ns == {0: 25.0, 1: 25.0}
    assert s.collective_exposed_ns == {0: 8 + 2 + 5, 1: 8 + 2 + 5}   # 18..26, 28..30 and the all-gather
    assert s.busy_ns == {0: 18 + 2 + 1 + 5, 1: 18 + 2 + 1 + 5}
    assert s.modules == {"jit__step": [45.0]}
    assert s.op_ns["f.1 fusion f32[8]"] == 18.0                       # mean over the two chips
