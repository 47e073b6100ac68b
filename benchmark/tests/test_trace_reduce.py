"""The reduction from trace events to numbers: on hand-made events whose
answers are worked out below, and on a small trace recorded on the chip."""

import json
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))

from benchmark import trace_reduce  # noqa: E402

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def ev(plane, line, name, start_us, dur_us):
    return {"plane": plane, "line": line, "name": name,
            "start_ns": start_us * 1e3, "dur_ns": dur_us * 1e3}


def hand_made():
    d0, d1, host = "/device:TPU:0", "/device:TPU:1", "/host:CPU"
    ops, mods = "XLA Ops", "XLA Modules"
    return [
        # device 0: busy 0-40 and 60-100; the all-reduce 30-40 overlaps the
        # fusion 20-35 for 5 us, so 5 us of it are exposed
        ev(d0, mods, "jit_step(1)", 0, 40), ev(d0, mods, "jit_step(1)", 60, 40),
        ev(d0, ops, "fusion.1", 0, 20), ev(d0, ops, "fusion.2", 20, 15),
        ev(d0, ops, "all-reduce.3", 30, 10),
        ev(d0, ops, "fusion.1", 60, 40),
        # device 1: busy 0-50 only, its all-reduce 40-50 wholly exposed
        ev(d1, mods, "jit_step(1)", 0, 50),
        ev(d1, ops, "fusion.1", 0, 40), ev(d1, ops, "all-reduce.3", 40, 10),
        # the host: the gap 40-60 on device 0 lies 15 us in `dispatch` and
        # 5 us outside any span of the benchmark
        ev(host, "python", "bench.dispatch", 35, 20), ev(host, "python", "bench.wait", 60, 40),
        ev(host, "python", "something else", 0, 100),
    ]


def test_hand_made_events():
    r = trace_reduce.reduce(hand_made(), window_ns=(0.0, 100e3))
    assert r["devices"] == 2
    assert r["window_s"] == pytest.approx(100e-6)
    assert r["busy_s_by_device"][0] == pytest.approx(80e-6)
    assert r["busy_s_by_device"][1] == pytest.approx(50e-6)
    assert r["busy_s"] == pytest.approx(65e-6)
    assert r["collective_s_fullest"] == pytest.approx(10e-6)
    assert r["exposed_collective_s_fullest"] == pytest.approx(10e-6)  # device 1
    assert r["module_runs"] == {"jit_step": 2}
    assert r["module_s"]["jit_step"] == pytest.approx((80e-6 + 50e-6) / 2)
    assert r["op_s"]["fusion.1"] == pytest.approx((60e-6 + 40e-6) / 2)
    gaps = dict(r["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(15e-6)
    assert gaps["outside_spans"] == pytest.approx(5e-6)
    assert "wait" not in gaps  # the device was busy all through `wait`
    assert r["device_ops"][0][0] == "fusion.1"


def test_the_window_is_the_hosts_span():
    """With the harness's ``bench.window`` span among the events the window
    is that span: the device idle before the first launch and after the last
    counts, and what ran outside the span does not."""
    events = hand_made() + [ev("/host:CPU", "python", "bench.window", 10, 110)]
    r = trace_reduce.reduce(events)
    assert r["window_s"] == pytest.approx(110e-6)
    assert r["busy_s_by_device"][0] == pytest.approx(70e-6)  # 10-40 and 60-100
    assert r["busy_s_by_device"][1] == pytest.approx(40e-6)  # 10-50
    gaps = dict(r["idle_gaps"])
    assert gaps["dispatch"] == pytest.approx(15e-6)
    assert gaps["outside_spans"] == pytest.approx(25e-6)  # 55-60 and 100-120
    assert "window" not in gaps
    # an explicit window still holds over the span
    assert trace_reduce.reduce(events, window_ns=(0.0, 100e3))["window_s"] == pytest.approx(100e-6)


def test_a_parent_operation_is_not_counted_twice():
    """A `while` covers the operations of its body: the body's time is the
    body's, the rest the parent's own."""
    d0 = "/device:TPU:0"
    events = [
        ev(d0, "XLA Ops", "while.7", 0, 100),
        ev(d0, "XLA Ops", "fusion.1", 10, 30), ev(d0, "XLA Ops", "all-reduce.2", 50, 20),
    ]
    r = trace_reduce.reduce(events, window_ns=(0.0, 100e3))
    assert r["busy_s"] == pytest.approx(100e-6)
    assert r["op_s"]["while.7"] == pytest.approx(50e-6)
    assert r["op_s"]["fusion.1"] == pytest.approx(30e-6)
    # the parent is no compute that hides the collective
    assert r["exposed_collective_s_fullest"] == pytest.approx(20e-6)


def test_no_device_plane_gives_nothing_to_read():
    assert trace_reduce.reduce([ev("/host:CPU", "python", "bench.wait", 0, 5)]) == {"devices": 0}


def test_interval_arithmetic():
    assert trace_reduce._union([(5, 7), (0, 2), (1, 3)]) == [[0, 3], [5, 7]]
    assert trace_reduce._subtract([[0, 10]], [[2, 3], [5, 12]]) == [[0, 2], [3, 5]]
    assert trace_reduce.stable_name("jit__weighted_bcd_fit(1234567)") == "jit__weighted_bcd_fit"
    assert trace_reduce.stable_name("%fusion.7 = f32[8,8]{1,0:T(8,128)} fusion(%a)") == "fusion.7 f32[8,8]"
    assert trace_reduce.stable_name("%while.29 =") == "while.29"


def test_recorded_trace():
    """Events recorded on the v5e (tests/record_trace.py, PR 22), cut to a
    short stretch; the expected numbers were read off the same events by
    hand (``recorded_trace.expected.json`` says how)."""
    with open(os.path.join(DATA, "recorded_trace.json")) as f:
        events = json.load(f)
    with open(os.path.join(DATA, "recorded_trace.expected.json")) as f:
        want = json.load(f)
    r = trace_reduce.reduce(events)
    assert r["devices"] == want["devices"]
    assert r["window_s"] == pytest.approx(want["window_s"], rel=1e-9)
    assert r["busy_s"] == pytest.approx(want["busy_s"], rel=1e-9)
    assert r["module_runs"] == want["module_runs"]
    assert 0 < r["busy_s"] <= r["window_s"]
    assert sum(r["op_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
