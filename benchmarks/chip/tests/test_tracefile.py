"""The reduction from trace intervals to busy time, idle gaps, kernel time
and the breakdown, on a hand-made trace."""

import pytest

import tracefile


def trace():
    # window 0..100 ns; chip 0 runs ops at 10-30, 20-40 (overlap), 60-70,
    # one that starts before the window and a while loop around 10-40;
    # chip 1 runs one long op.
    return tracefile.Trace(
        devices=[[("%fusion.1 = f32[2]", -5, 5), ("%dot.4 = f32[8]", 10, 30),
                  ("%telemetry_sketch.3 = (f32[4,1]) custom-call(...)", 20, 40), ("%dot.4 = f32[8]", 60, 70),
                  ("%while.1 = (s32[])", 10, 40)],
                 [("%fusion.2 = f32[2]", 0, 90)]],
        host=[("segment_dispatch", 0, 12), ("telemetry_drain", 40, 58),
              ("results_fetch", 58, 62)],
        window=(0, 100))


def test_busy_and_gaps():
    tr = trace()
    assert tracefile.busy_s(tr, 0) == pytest.approx(45e-9)
    assert tracefile.busy_s(tr, 1) == pytest.approx(90e-9)
    assert tracefile.idle_gaps(tr, 0) == [(5, 10), (40, 60), (70, 100)]
    assert tracefile.union([("a", 3, 4), ("b", 1, 3)], 0, 10) == [[1, 4]]


def test_labels_and_kernel_time():
    tr = trace()
    assert tracefile.host_label(tr, 40, 60) == "telemetry_drain"
    assert tracefile.host_label(tr, 70, 100) == "other"
    seconds, calls = tracefile.op_seconds(tr, 0, lambda n: n.startswith("%telemetry"))
    assert (seconds, calls) == (pytest.approx(20e-9), 1)


def test_breakdown():
    b = tracefile.breakdown(trace(), top=2)
    assert b["device_ops"] == [["%dot.4", pytest.approx(30e-9)],
                               ["%telemetry_sketch.3", pytest.approx(20e-9)]]
    assert b["idle_gaps"] == [["other", pytest.approx(30e-9)],
                              ["telemetry_drain", pytest.approx(20e-9)]]
