"""The trace reduction on a hand-built trace: busy union, idle share,
exposed collective time and the labels of idle gaps."""
import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parents[3]))
from benchmarks.chip import tracing  # noqa: E402


def test_interval_arithmetic():
    assert tracing.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == \
        [(0, 2.5), (3, 4)]
    assert tracing.subtract([(0, 10)], [(1, 2), (5, 6), (9, 12)]) == \
        [(0, 1), (2, 5), (6, 9)]
    assert tracing.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]
    assert tracing.clip([(-1, 1), (2, 5)], 0, 3) == [(0, 1), (2, 3)]


def trace():
    # window 10..20 s; chip 0: a fusion overlapping an all-gather, a lone
    # all-reduce; chip 1: one long op.  Ops outside the window are clipped.
    ops = {0: [("fusion.1", 9.0, 12.0), ("all-gather.3", 11.0, 13.0),
               ("all-reduce.2", 15.0, 16.0), ("fusion.1", 18.0, 19.0)],
           1: [("fusion.7", 10.0, 14.0), ("all-reduce.2", 14.0, 15.0)]}
    spans = [("traced", 10.0, 20.0), ("engine.step", 10.0, 16.5),
             ("generator wait", 16.5, 18.0), ("next batch", 19.0, 20.0)]
    return tracing.Trace(ops=ops, spans=spans)


def test_reduce_hand_built():
    s = tracing.reduce(trace(), [0, 1])
    assert s["window_s"] == 10.0
    # chip 0 busy: 10-13, 15-16, 18-19 = 5 s; chip 1: 10-15 = 5 s
    assert s["busy_s"] == pytest.approx(5.0)
    assert s["idle_share"] == pytest.approx(0.5)
    # chip 0: all-gather exposed 12-13, all-reduce 15-16; chip 1: 14-15
    assert s["collective_exposed_s"] == pytest.approx((2.0 + 1.0) / 2)
    ops = dict(s["device_ops"])
    assert ops["fusion.7"] == pytest.approx(2.0)
    assert ops["fusion.1"] == pytest.approx(1.5)     # (2 + 1) / 2 chips
    # chip 0's gaps, longest first: 13-15 (engine.step), 16-18 (16-16.5
    # engine.step, middle 17 in generator wait), 19-20 (next batch)
    assert s["idle_gaps"] == [["engine.step at 3.00 s", pytest.approx(2.0)],
                              ["generator wait at 6.00 s", pytest.approx(2.0)],
                              ["next batch at 9.00 s", pytest.approx(1.0)]]
    assert s["ops_from_to_s"] == [0.0, 9.0]
    assert s["span_s"]["engine.step"] == pytest.approx(6.5)
    assert s["steps"] == 1


def test_op_names_from_hlo_text():
    assert tracing.op_name("%copy.3 = bf16[28,32,4096]{2,1,0:T(2,128)} "
                           "copy(bf16[28,32,4096]{2,1,0} %args_0_.1)") == \
        "%copy.3 bf16[28,32,4096]"
    # a fusion that reads an all-reduce's result is no collective
    name = tracing.op_name("%fusion.9 = (s32[], bf16[8]) fusion(bf16[8] "
                           "%all-reduce.2), kind=kLoop")
    assert name == "%fusion.9" and not tracing.COLLECTIVE.search(name)
    assert tracing.op_name("all-gather-start.1") == "all-gather-start.1"


def test_trace_cut_short_fails():
    # the device's events stop at 12 s while the host goes on stepping:
    # the profiler's buffer ran out, and the reduction refuses the trace
    t = tracing.Trace(ops={0: [("fusion.1", 10.0, 11.0), ("fusion.2", 11.5, 12.0)]},
                      spans=[("traced", 10.0, 20.0), ("engine.step", 10.0, 11.2),
                             ("engine.step", 11.4, 12.1), ("engine.step", 13.0, 19.0)])
    assert tracing.cut_short(t, [0], 10.0, 20.0) == pytest.approx(8.0)
    with pytest.raises(RuntimeError, match="buffer ran out"):
        tracing.reduce(t, [0])
    # a device that idles at the end while the host waits is not cut
    t.spans = [("traced", 10.0, 20.0), ("engine.step", 10.0, 11.2),
               ("engine.step", 11.4, 12.1), ("generator wait", 12.5, 20.0)]
    s = tracing.reduce(t, [0])
    assert s["window_s"] == 10.0 and s["busy_s"] == pytest.approx(1.5)


def test_capture_starts_for_the_last_span():
    cap = tracing.Capture(pathlib.Path("unused"), True, span_s=10.0)
    assert cap.start_at(100.0, 151.0) == 141.0
    assert cap.start_at(100.0, 105.0) == 100.0      # a short window: all
    off = tracing.Capture(pathlib.Path("unused"), False)
    assert off.start_at(100.0, 151.0) == float("inf")
    off.stop()
    assert off.trace is None


def test_window_span_required():
    t = trace()
    t.spans = [s for s in t.spans if s[0] != "traced"]
    with pytest.raises(RuntimeError, match="traced"):
        tracing.reduce(t, [0])
