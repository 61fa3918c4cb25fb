"""The reduction from a trace to busy/idle, executables' device time and
idle attribution: on a hand-made trace whose numbers can be read off, and
on a small cut of a trace recorded on the chip (data/recorded_trace.json,
1pool-drain, two cycles)."""

import json
import os

import pytest

import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))


def hand_made():
    ops = [("%a", 10.0, 0.010), ("%b", 10.005, 0.010),   # overlap: 15 ms
           ("%a", 12.0, 0.010)]
    return [
        {"name": "/device:TPU:0", "lines": [
            {"name": "XLA Ops", "events": ops},
            {"name": "XLA Modules", "events": [
                ("jit_cycle_body(1)", 10.0, 0.015),
                ("jit_cycle_body(1)", 12.0, 0.010),
                ("jit__lambda(2)", 9.99, 0.001)]}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
            ("cook.stage", 9.9, 0.05), ("cook.dispatch", 9.95, 0.04),
            ("cook.apply", 10.1, 0.4), ("cook.dispatch", 11.9, 0.05),
            ("cook.apply", 12.05, 0.45)]}]}]


def test_hand_made_trace():
    red = trace_reduce.reduce_planes(hand_made())
    assert red["busy_s"] == pytest.approx(0.025)
    assert red["window_s"] == pytest.approx(12.5 - 9.9)
    assert red["devices"] == 1
    assert red["top_ops"][0] == ["%a", pytest.approx(0.020)]
    assert red["host_calls"]["cook.dispatch"] == 2
    runs = trace_reduce.module_seconds(red, ["cycle_body"])
    assert sorted(runs) == pytest.approx([0.010, 0.015])
    assert sum(trace_reduce.module_seconds(red, [""])) == pytest.approx(0.026)
    idle = dict(red["idle_gaps"])
    assert sum(idle.values()) == pytest.approx(red["window_s"] - 0.025)
    assert idle["cook.apply"] == pytest.approx(0.85)
    assert idle["cook.stage"] == pytest.approx(0.05)
    assert idle[trace_reduce.OTHER] > 1.0


def test_no_device_plane_reads_nothing():
    planes = [p for p in hand_made() if p["name"].startswith("/host")]
    assert trace_reduce.reduce_planes(planes) is None


def test_recorded_trace():
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path, encoding="utf-8") as f:
        planes = json.load(f)
    planes = [{"name": p["name"], "lines": [
        {"name": ln["name"], "events": [tuple(e) for e in ln["events"]]}
        for ln in p["lines"]]} for p in planes]
    red = trace_reduce.reduce_planes(planes)
    runs = trace_reduce.module_seconds(red, ["cycle_body"])
    assert len(runs) == 2                       # two cycles were traced
    assert all(0.030 < r < 0.060 for r in runs)  # ~41 ms each on a v5e
    assert 0 < red["busy_s"] <= sum(
        trace_reduce.module_seconds(red, [""])) + 1e-9
    assert red["busy_s"] < 0.1 * red["window_s"]  # the chip mostly idles
    assert red["host_calls"]["cook.apply"] >= 2
    assert dict(red["idle_gaps"])["cook.apply"] > 0.3
