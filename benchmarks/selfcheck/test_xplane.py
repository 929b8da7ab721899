"""The trace reducer: on made-up intervals, and on a small trace recorded
on the v5e (`recorded/sf1-q6.xplane.pb`: the `sf1-q6` cell, a window of a
few seconds, PR 28)."""

import glob
import os

import pytest

from harness import xplane

HERE = os.path.dirname(os.path.abspath(__file__))


def _made_up():
    # two statements; device ops at 10-20, 15-30 (overlap), 50-60 ns
    ops = [("fusion.1", 10, 20), ("fusion.2", 15, 30), ("fusion.1", 50, 60)]
    return {"devices": {"/device:TPU:0": {
        "ops": ops, "modules": [("jit_scan", 10, 30), ("jit_scan", 50, 60)]}},
        "annotations": [("stmt:q6", 1005, 1040), ("stmt:q6", 1045, 1070)]}


def test_busy_union_idle_share_and_time_by_operation_and_program():
    red = xplane.reduce(_made_up(), (0, 100))
    assert red["busy_s"] == pytest.approx(30e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["op_time"] == {"fusion.1": pytest.approx(20e-9),
                              "fusion.2": pytest.approx(15e-9)}
    assert red["module_time"] == {"jit_scan": pytest.approx(30e-9)}
    assert red["gaps"] == [(0, 10), (30, 50), (60, 100)]
    # a window that cuts an operation counts only the part inside
    assert xplane.reduce(_made_up(), (18, 55))["busy_s"] == pytest.approx(
        17e-9)
    busy = xplane.first_device(_made_up())
    assert busy.within(0, 25) == pytest.approx(15e-9)
    assert busy.within(18, 55) == pytest.approx(17e-9)
    assert busy.within(20, 20) == 0.0 and busy.within(70, 90) == 0.0
    assert busy.gaps(12, 58) == [(30, 50)]


def test_gaps_go_to_the_deepest_host_span_open_at_their_middle():
    host = [(0, 62, [("client: q6 on the wire", 0, 62, 0), ("plan", 2, 9, 2),
                     ("copr.readback", 28, 52, 4),
                     ("executor.next", 20, 61, 2)])]
    named = xplane.name_gaps([(0, 10), (30, 50), (60, 100)], host)
    assert named == [["no statement open", pytest.approx(40e-9)],
                     ["copr.readback", pytest.approx(20e-9)],
                     ["plan", pytest.approx(10e-9)]]


def test_clock_offset_is_the_median_difference():
    sent = [("stmt:q6", 5), ("stmt:q6", 46)]
    assert xplane.clock_offset(_made_up()["annotations"], sent) in (999, 1000)
    with pytest.raises(ValueError):
        xplane.clock_offset([], sent)


def test_a_trace_without_a_device_plane_is_an_error():
    with pytest.raises(ValueError):
        xplane.reduce({"devices": {}, "annotations": []}, (0, 1))


def test_the_recorded_v5e_trace_reduces_to_sane_numbers():
    paths = glob.glob(os.path.join(HERE, "recorded", "*.xplane.pb"))
    assert paths, "the recorded trace is part of the benchmark"
    raw = xplane.read(paths[0])
    assert list(raw["devices"]) == ["/device:TPU:0"]
    dev = raw["devices"]["/device:TPU:0"]
    assert dev["ops"] and dev["modules"] and raw["annotations"]
    w0 = raw["annotations"][0][1]
    w1 = raw["annotations"][-1][2]
    red = xplane.reduce(raw, (w0, w1))
    assert 0 < red["busy_s"] < red["window_s"]
    assert sum(e - s for s, e in red["gaps"]) / 1e9 == pytest.approx(
        red["window_s"] - red["busy_s"], rel=1e-6)
    # every statement's interval holds some device time: each Q6 scans
    busy = xplane.first_device(raw)
    per = [busy.within(s, e) for _, s, e in raw["annotations"]]
    assert min(per) > 0
    assert sum(per) <= red["busy_s"] * (1 + 1e-9)
    assert xplane.top(red["op_time"], 3)[0][1] <= red["busy_s"] * 1.0001 \
        or len(red["op_time"]) > 1
