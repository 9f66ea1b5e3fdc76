"""The capture reduction: busy union, per-op sums, idle share — on
hand-made intervals, and on the small recorded TPU capture beside this file
(``tools/record_small_trace.py``: three jitted calls with host sleeps
between them), checked against an independent sweep."""
import os

import pytest

from benchmark import xplane

HERE = os.path.dirname(os.path.abspath(__file__))
RECORDED = os.path.join(HERE, "small_trace.xplane.pb")


def test_union_merges_overlaps_and_nesting():
    # [0,10) and [5,15) overlap; [20,30) holds [22,25) nested; [30,31) abuts
    iv = [(0, 10), (5, 15), (20, 30), (22, 25), (30, 31)]
    assert xplane.union_seconds(iv) == pytest.approx(26e-9)
    assert xplane.union_seconds([]) == 0.0


def test_reduce_events_on_a_hand_made_device():
    events = {"/device:TPU:0": [("matmul", 0, 400), ("matmul", 600, 1000),
                                ("while", 1000, 2000),
                                ("fusion", 1200, 1500)]}
    s = xplane.reduce_events(events)
    assert s["window_s"] == pytest.approx(2000e-9)
    assert s["busy_s"] == pytest.approx(1800e-9)       # idle 400..600
    assert s["op_seconds"]["matmul"] == pytest.approx(800e-9)
    assert s["op_counts"] == {"matmul": 2, "while": 1, "fusion": 1}
    assert s["top_ops"][0][0] == "while"
    idle = 1 - s["busy_s"] / s["window_s"]
    assert idle == pytest.approx(0.1)


def test_two_devices_are_averaged():
    events = {"/device:TPU:0": [("a", 0, 1000)],
              "/device:TPU:1": [("a", 0, 500)]}
    s = xplane.reduce_events(events)
    assert s["devices"] == 2
    assert s["busy_s"] == pytest.approx(750e-9)
    assert s["op_seconds"]["a"] == pytest.approx(750e-9)


def test_nothing_on_the_device_reads_nothing():
    assert xplane.reduce_events({}) is None
    assert xplane.reduce_events({"/device:TPU:0": []}) is None


@pytest.mark.skipif(not os.path.exists(RECORDED),
                    reason="no recorded capture beside the test")
def test_recorded_capture():
    from jax.profiler import ProfileData
    events = xplane.device_events(ProfileData.from_file(RECORDED))
    assert list(events) == ["/device:TPU:0"]
    evs = events["/device:TPU:0"]
    s = xplane.reduce_events(events)
    # independent sweep: mark every nanosecond boundary
    points = sorted({p for _, a, b in evs for p in (a, b)})
    busy = sum(q - p for p, q in zip(points, points[1:])
               if any(a <= p and q <= b for _, a, b in evs))
    assert s["busy_s"] == pytest.approx(busy / 1e9)
    assert s["window_s"] == pytest.approx((points[-1] - points[0]) / 1e9)
    assert sum(s["op_counts"].values()) == len(evs)
    assert sum(s["op_seconds"].values()) == pytest.approx(
        sum(b - a for _, a, b in evs) / 1e9)
    # three calls with 2 ms host sleeps between them: the device idles
    # for most of the window
    assert 0.5 < 1 - s["busy_s"] / s["window_s"] < 1.0


def test_op_kind_adds_up_the_copies_of_one_operation():
    kind = xplane.op_kind
    assert kind("%fusion.1086 = f32[4,2048,1024]{2,1,0:T(8,128)} "
                "fusion(f32[50304,1024]{1,0} %x)") == \
        "fusion f32[4,2048,1024] fusion"
    assert kind("%jvp__.384 = (f32[64,2048,64]{2,1,0:T(8,128)}, "
                "f32[64,2048,64]{2,1,0:T(8,128)}) custom-call(f32[64,2") == \
        "jvp__ (f32[64,2048,64], f32[64,2048,64]) custom-call"
    assert kind("%fusion.63.remat = bf16[8]{0:T(8,128)(2,1)} fusion(") == \
        "fusion bf16[8] fusion"
    assert kind("jit_pure(123)") == "jit_pure(123)"
