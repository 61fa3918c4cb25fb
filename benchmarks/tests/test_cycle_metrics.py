"""The per-layer metrics that read the CycleRecord's split and waits
(PR 26): each new file loads, names a reader that exists, and reads a
number from a recorded /debug/cycles document of the new shape; a record
of the old shape (the parent's) reads as nothing, never as 0; and
``cycle_stat`` by hand on three records."""

import json
import os

import run

ROOT = os.path.dirname(run.HERE)
NEW = ("apply_lookup_ms", "apply_txn_ms", "apply_journal_ms",
       "apply_cluster_ms", "apply_audit_ms", "pack_index_ms",
       "pack_rows_ms", "delta_rows_per_cycle", "cycle_untraced_ms",
       "interval_wait_ms", "pipeline_lag_ms", "store_lock_wait_max_ms",
       "gc_pause_max_ms", "cycle_offcpu_max_ms", "sweep_overlap_max_ms")
#: what a CycleRecord carried before PR 26 (the parent commit's shape)
OLD_FIELDS = ("seq", "kind", "trace_id", "start", "duration_ms",
              "phases_ms", "pools", "jobs_considered", "jobs_placed",
              "skip_reasons", "preemptions", "recompiles", "h2d_bytes",
              "d2h_bytes", "sync_wait_ms", "faults", "pipeline_depth",
              "pipeline_inflight", "pipeline_conflicts", "delta_rows",
              "full_repacks", "audit_events", "kernel_launches", "path",
              "shard", "device", "error")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def recorded_window():
    with open(os.path.join(run.HERE, "tests", "data",
                           "recorded_cycles.json"), encoding="utf-8") as f:
        doc = json.load(f)
    t0, t1 = doc["window"]
    # what run.py hands the readers: the window's fused records
    return {"cycles": [c for c in doc["cycles"] if c["kind"] == "fused"
                       and t0 <= c["start"] < t1],
            "window": (t0, t1)}, doc["cycles"]


def test_each_new_metric_has_its_file_entry_and_reader():
    b = bench()
    entries = {m["name"]: m for m in b["per_layer"]}
    layers = {m["layer"] for m in b["per_layer"] if m["name"] not in NEW}
    e2e = {m["name"] for m in b["end_to_end"]}
    readers = run.load_readers(b)
    assert [m["name"] for m in b["per_layer"]][-len(NEW):] == list(NEW)
    for name in NEW:
        read, spec, entry = readers[name]
        assert callable(read)
        assert entry is entries[name]
        assert (spec["name"], spec["unit"], spec["layer"], spec["moves"]) \
            == (name, entry["unit"], entry["layer"], entry["moves"])
        assert spec["reader"] in ("cycle_mean", "cycle_stat")
        assert entry["layer"] in layers       # an existing layer's name
        assert entry["moves"] in e2e
        assert entry["better"] == "lower" and "workloads" not in entry


def test_each_new_metric_reads_a_number_from_a_recorded_window():
    readers = run.load_readers(bench())
    ctx, every = recorded_window()
    assert len(ctx["cycles"]) >= 3
    assert {c["kind"] for c in every} >= {"fused", "reapers", "monitor"}
    values = {}
    for name in NEW:
        read, spec, _entry = readers[name]
        values[name] = read(ctx, spec)
        assert isinstance(values[name], float), name
    # no lock was contended in the recording: 0.0 is a number, not absent
    assert values["store_lock_wait_max_ms"] == 0.0
    assert values["interval_wait_ms"] > 40.0      # the 50 ms interval
    assert values["sweep_overlap_max_ms"] > 0.0   # a sweep met a cycle
    parts = sum(values[f"apply_{p}_ms"] for p in
                ("lookup", "txn", "journal", "cluster", "audit"))
    apply_ms = readers["apply_ms"][0](ctx, readers["apply_ms"][1])
    assert 0.5 * apply_ms < parts <= apply_ms + 1e-6


def test_the_parents_records_read_as_nothing_not_as_zero():
    readers = run.load_readers(bench())
    ctx, _every = recorded_window()
    old = {"cycles": [{k: c[k] for k in OLD_FIELDS if k in c}
                      for c in ctx["cycles"]]}
    for c in old["cycles"]:
        c["detail_ms"] = {k: v for k, v in
                          next(x for x in ctx["cycles"]
                               if x["seq"] == c["seq"])["detail_ms"].items()
                          if k in ("pack", "stage", "apply")}
    for name in NEW:
        read, spec, _entry = readers[name]
        got = read(old, spec)
        if name == "delta_rows_per_cycle":     # the field existed before
            assert got is not None
        else:
            assert got is None, name


def test_cycle_stat_by_hand_on_three_records():
    read = run.load_readers(bench())["sweep_overlap_max_ms"][0]
    cycles = [
        {"background_ms": {"reapers": 0.0, "monitor": 0.0}, "offcpu_ms": 2.0},
        {"background_ms": {"reapers": 120.5, "monitor": 30.25},
         "offcpu_ms": 80.0},
        {"background_ms": {"monitor": 7.0}},          # no reapers, no offcpu
    ]
    ctx = {"cycles": cycles}
    two = ["background_ms.reapers", "background_ms.monitor"]
    assert read(ctx, {"fields": two, "stat": "max"}) == 150.75
    assert read(ctx, {"fields": two, "stat": "min"}) == 0.0
    assert abs(read(ctx, {"fields": two, "stat": "mean"})
               - (0.0 + 150.75 + 7.0) / 3) < 1e-9
    # a record without the field is left out, not counted as 0
    assert read(ctx, {"fields": ["offcpu_ms"], "stat": "mean"}) == 41.0
    assert read(ctx, {"fields": ["offcpu_ms"], "stat": "min"}) == 2.0
    assert read(ctx, {"fields": ["blocked_ms.gc"], "stat": "max"}) is None
    assert read({"cycles": []}, {"fields": two, "stat": "max"}) is None
