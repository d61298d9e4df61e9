"""The reduction from a profiler trace to busy time, idle share and the
breakdown: on hand-made intervals, and on a small trace recorded on one
TPU v5e (``bench/tests/data/small.xplane.pb``: three ``bench.solve`` spans
of four jitted 1024×1024 products each, 20 ms of sleep after each, inside
one ``bench.window``)."""
from pathlib import Path

import pytest

from bench import trace

DATA = Path(__file__).resolve().parent / "data" / "small.xplane.pb"
MS = 1_000_000


def test_union_merges_overlaps_and_clips_to_the_window():
    got = trace.union([(0, 10, "a"), (5, 20, "b"), (30, 40, "c"),
                       (40, 45, "d"), (90, 200, "e")], 2, 100)
    assert got == [[2, 20], [30, 45], [90, 100]]
    assert trace.gaps(got, 0, 120) == [(0, 2), (20, 30), (45, 90),
                                       (100, 120)]


def _hand_made():
    spans = [(0, 100 * MS, "bench.window"),
             (0, 40 * MS, "bench.wave"), (5 * MS, 15 * MS, "bench.serve_step"),
             (60 * MS, 100 * MS, "bench.wave")]
    ops = {0: [(0, 5 * MS, "%fusion"), (15 * MS, 35 * MS, "%sinkhorn_row"),
               (30 * MS, 40 * MS, "%fusion"), (70 * MS, 90 * MS, "%while"),
               (70 * MS, 79 * MS, "%dot"), (80 * MS, 89 * MS, "%dot")]}
    return trace.Trace(ops, spans)


def test_reduce_hand_made_trace():
    r = trace.reduce(_hand_made(), top=3)
    # busy: [0,5] + [15,40] + [70,90] = 50 ms of the 100 ms window
    assert r.busy_s == pytest.approx(0.050)
    assert r.window_s == pytest.approx(0.100)
    assert r.idle_share == pytest.approx(0.5)
    # the loop's own event holds the two products and is not counted
    assert r.device_ops == [["%sinkhorn_row", pytest.approx(0.020)],
                            ["%dot", pytest.approx(0.018)],
                            ["%fusion", pytest.approx(0.015)]]
    # gaps: [5,15] in a serve step, [40,70] around the window only,
    # [90,100] in the second wave
    assert r.idle_gaps == [["bench.window", pytest.approx(0.030)],
                           ["bench.serve_step", pytest.approx(0.010)],
                           ["bench.wave", pytest.approx(0.010)]]


def test_op_names_drop_the_instruction_and_its_number():
    assert trace.op_name("%fusion.12 = f32[8]{0} fusion(f32[8]{0} %p), "
                         "kind=kLoop") == "%fusion"
    assert trace.op_name("%sinkhorn_row_update_pallas.3 = f32[4,1] "
                         "custom-call(...)") == "%sinkhorn_row_update_pallas"
    assert trace.op_name("%copy-start = (f32[4]) copy-start(%x.1)") == \
        "%copy-start"
    assert trace.op_name("%x.v2") == "%x.v2"


def test_reduce_averages_busy_time_over_devices():
    t = _hand_made()
    t.device_ops[1] = [(0, 100 * MS, "all")]
    r = trace.reduce(t)
    assert r.busy_s == pytest.approx((0.050 + 0.100) / 2)


def test_reduce_refuses_a_trace_without_window_or_device_work():
    t = _hand_made()
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(trace.Trace(t.device_ops, t.spans[1:]))
    with pytest.raises(ValueError, match="no device operation"):
        trace.reduce(trace.Trace({0: []}, t.spans))


def test_reduce_recorded_trace():
    t = trace.load(str(DATA))
    assert list(t.device_ops) == [0]
    names = [n for _, _, n in t.spans]
    assert names.count("bench.window") == 1
    assert names.count("bench.solve") == 3
    r = trace.reduce(t)
    assert 0 < r.busy_s < r.window_s
    # three sleeps of 20 ms sit in the window, outside every solve
    assert r.idle_share > 0.06 / r.window_s * 0.9
    assert len(r.device_ops) >= 1 and r.device_ops[0][1] > 0
    assert r.device_ops[0][0] == "%fusion"
    assert sum(s for _, s in r.device_ops) == pytest.approx(
        r.busy_s, rel=0.5)
    longest = r.idle_gaps[:3]
    assert [n for n, _ in longest] == ["bench.window"] * 3
    assert all(s >= 0.019 for _, s in longest)
