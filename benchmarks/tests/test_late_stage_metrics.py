"""The two metrics of the cycle staged late (PR 31), on a hand-made
window: ``late_stage_share`` is the mean of ``staged_late`` and
``stage_lead_ms`` the mean of ``lead_ms`` over the records that carry
them; records without the fields (the parent's, and for ``lead_ms`` every
cycle of the overlapped order) read as nothing, never as 0 and never as
a raise."""

import json
import os

import run

ROOT = os.path.dirname(run.HERE)
NEW = ("late_stage_share", "stage_lead_ms")


def readers():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        bench = json.load(f)
    return bench, run.load_readers(bench)


def value(name, cycles):
    _bench, by_name = readers()
    read, spec, _entry = by_name[name]
    return read({"cycles": cycles}, spec)


def test_the_entries_sit_on_readers_the_benchmark_has():
    bench, by_name = readers()
    cells = {c["name"] for c in bench["workloads"]}
    for name in NEW:
        _read, spec, entry = by_name[name]
        assert entry in bench["per_layer"][-len(NEW):]
        assert spec["reader"] in ("cycle_stat", "cycle_mean")
        assert (entry["layer"], entry["moves"]) \
            == ("cycle thread cadence", "ttp_p50_ms")
    # a share where every cell has a cycle; a lead only where one is late
    assert "workloads" not in by_name["late_stage_share"][2]
    assert set(by_name["stage_lead_ms"][2]["workloads"]) < cells
    added = {"late_stage_share.json", "stage_lead_ms.json"}
    assert added <= set(os.listdir(os.path.join(run.HERE, "layer_metrics")))


def test_a_window_of_late_cycles_and_one_of_the_overlapped_order():
    late = [{"staged_late": 1, "lead_ms": 130.0, "pipeline_lag_ms": 130.0},
            {"staged_late": 1, "lead_ms": 150.0, "pipeline_lag_ms": 150.0}]
    # what a cycle of the overlapped order carries: the flag, no lead
    lagged = {"staged_late": 0, "pipeline_lag_ms": 1040.0}
    assert value("late_stage_share", late) == 1.0
    assert value("stage_lead_ms", late) == 140.0
    assert value("late_stage_share", late + [lagged, lagged]) == 0.5
    assert value("stage_lead_ms", late + [lagged]) == 140.0
    assert value("late_stage_share", [lagged] * 3) == 0.0
    assert value("stage_lead_ms", [lagged] * 3) is None


def test_the_parents_records_read_as_nothing():
    parents = [{"pipeline_lag_ms": 1057.3, "wait_ms": 734.1,
                "duration_ms": 246.4},
               {"pipeline_lag_ms": 1055.0, "wait_ms": 741.5,
                "duration_ms": 247.4}]
    for name in NEW:
        assert value(name, parents) is None
        assert value(name, []) is None
