"""``8pool-drain`` as BENCHMARK.json really has it (entry, configuration
file, traffic mix), end to end at a tiny size on the CPU through
``drive_cell.py``; its control and the three planted faults at eight
pools; and the three per-layer metrics that came with the cell, on a
hand-made window.  (test_rehearsal.py's ``8pool-drain`` is drive.py's
made-up eight-pool form of the one-pool file.)  No number here is a
device metric."""

import json
import os

import pytest

import run
from test_rehearsal import HERE, ROOT, drive

CELL = "8pool-drain"
DRIVE = os.path.join(HERE, "drive_cell.py")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


def test_the_entry_is_the_eight_pool_deployment():
    b = bench()
    cell = {c["name"]: c for c in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cook-8pool-50k", "drain", 1)
    _b, _cell, config, mix = run.load_cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL)
    w = config["world"]
    assert w["pools"] == [f"pool{i}" for i in range(8)]
    assert (w["jobs_per_pool"], w["hosts_per_pool"]) == (50000, 1250)
    assert mix["pool"] == "uniform" and mix["backlog_quota"] is None
    # the file asks for its own warm-up: one pool's design point, which
    # the harness passes through and the program stacks by itself
    assert config["scheduler"]["pipeline"] == {
        "depth": 2, "warmup_tasks": 65536, "warmup_hosts": 2048,
        "warmup_users": 250}
    # every other key of the world and the scheduler is the one-pool file's
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "cook-1pool-100kx5k.json")) as f:
        one = json.load(f)
    same = lambda a, b, skip: {k: v for k, v in a.items() if k not in skip} \
        == {k: v for k, v in b.items() if k not in skip}
    assert same(w, one["world"], {"pools", "jobs_per_pool",
                                  "hosts_per_pool"})
    assert same(config["scheduler"], one["scheduler"], {"pipeline"})
    listed = lambda name: next(m for m in b["end_to_end"] + b["per_layer"]
                               if m["name"] == name).get("workloads", [CELL])
    for name in ("placements_per_s", "launches_per_s", "warmup_s",
                 "warmup_runs", "pools_per_dispatch", "overrun_max_ms"):
        assert CELL in listed(name), name


def test_the_real_cell_is_correct_its_control_is_not_and_nothing_compiles():
    rc, out, err = drive("--workload", CELL, "--seed", str(2 ** 31 + 11),
                         "--seconds", "5", "--trace", "0", "--control", "1",
                         script=DRIVE)
    assert rc == 0, err[-2000:]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    for name, (value, limit) in out["checks"].items():
        assert value <= limit, name
    assert set(out["metrics"]) == {"ttp_p50_ms", "ttp_p95_ms",
                                   "placements_per_s", "cycle_ms", "setup_s"}
    assert out["control_correct"] is False, out["control_checks"]
    assert any(v > lim for v, lim in out["control_checks"].values())
    # the program warmed the stacked shapes itself (the harness warms
    # nothing): its warm-up ran, and no executable was built in the window
    assert out["times"]["warmup_s"] > 0
    assert "compiled inside the window" not in err


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
def test_a_broken_timed_path_reads_incorrect_at_eight_pools(fault):
    rc, out, err = drive("--fault", fault, "--workload", CELL,
                         "--seed", "3", "--seconds", "4", "--trace", "0",
                         script=DRIVE)
    assert rc == 0, err[-2000:]
    assert out["correct"] is False
    bad = {k for k, (v, lim) in out["checks"].items() if v > lim}
    assert bad & {"set_gap", "host_gap"}, out["checks"]


def test_the_new_metrics_on_a_hand_made_window():
    readers = run.load_readers(bench())
    ctx = {"cycles": [{"pools": 8, "overrun_ms": 0.0},
                      {"pools": 8, "overrun_ms": 2512.5},
                      {"pools": 7, "overrun_ms": 40.0}],
           "health": {"device": {"warmup_runs": 1, "warmup_s": 12.5}}}
    value = lambda name: readers[name][0](ctx, readers[name][1])
    assert value("pools_per_dispatch") == 7.0      # one split cycle shows
    assert value("overrun_max_ms") == 2512.5
    assert value("warmup_runs") == 1.0
    # a program without the fields or the block: nothing to read, no raise
    empty = {"cycles": [{"duration_ms": 1.0}], "health": {}}
    for name in ("pools_per_dispatch", "overrun_max_ms", "warmup_runs"):
        assert readers[name][0](empty, readers[name][1]) is None
