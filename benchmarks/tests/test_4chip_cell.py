"""``4chip-8pool-drain`` as BENCHMARK.json has it (entry, configuration
file, traffic mix), end to end at a tiny size on FOUR VIRTUAL CPU DEVICES
through ``drive_cell.py``'s real entry; its control and a planted fault;
and the four per-layer metrics that came with the cell, on hand-made
windows and a hand-made four-plane trace reduction.  No number here is a
device metric."""

import json
import os

import pytest

import cost
import run
import trace_reduce
from test_rehearsal import HERE, ROOT, drive

CELL = "4chip-8pool-drain"
DRIVE = os.path.join(HERE, "drive_cell.py")
NEW = ("mesh_devices", "stage_put_ms", "collective_ms",
       "shard_cycle_roofline")


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json"), encoding="utf-8") as f:
        return json.load(f)


@pytest.fixture
def four_devices(monkeypatch):
    """drive() copies the environment: the daemon of the child refuses to
    boot with fewer local devices than ``pipeline.mesh_devices``."""
    monkeypatch.setenv("XLA_FLAGS",
                       "--xla_force_host_platform_device_count=4")


def test_the_entry_is_config_4_on_four_chips():
    b = bench()
    cell = {c["name"]: c for c in b["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == \
        ("cook-8pool-50k-v5e4", "drain", 4)
    assert sum(1 for c in b["workloads"] if c["chips"] == 4) == 1
    _b, _cell, config, mix = run.load_cell(
        os.path.join(ROOT, "BENCHMARK.json"), CELL)
    with open(os.path.join(ROOT, "benchmarks", "configs",
                           "cook-8pool-50k.json")) as f:
        one = json.load(f)
    # the two eight-pool cells differ in the mesh alone
    assert config["world"] == one["world"]
    assert config["guarantees"] == one["guarantees"]
    sched, sched1 = config["scheduler"], one["scheduler"]
    assert {k: v for k, v in sched.items() if k != "pipeline"} \
        == {k: v for k, v in sched1.items() if k != "pipeline"}
    assert sched["pipeline"] == dict(sched1["pipeline"], mesh_devices=4)
    assert "mesh_devices" not in sched1["pipeline"]
    assert config["reduced"] == ["chips", "warmup_grid"]
    entry = {c["name"]: c for c in b["configs"]}["cook-8pool-50k-v5e4"]
    assert entry["source"] == config["source"]
    assert len(entry["source"]) <= 200
    assert entry["reduced"] == config["reduced"]
    assert mix["pool"] == "uniform" and mix["backlog_quota"] is None
    listed = lambda name: next(m for m in b["end_to_end"] + b["per_layer"]
                               if m["name"] == name).get("workloads")
    for name in ("warmup_s", "warmup_runs", "collective_ms",
                 "shard_cycle_roofline"):
        assert CELL in listed(name), name
    for name in ("placements_per_s", "launches_per_s", "stage_lead_ms"):
        assert CELL not in listed(name), name
    for name in ("mesh_devices", "stage_put_ms"):
        assert listed(name) is None, name          # every cell reports it
    assert listed("collective_ms") == [CELL]       # one device: no collective


def test_the_cell_is_correct_on_four_devices_and_its_control_is_not(
        four_devices, tmp_path):
    dump = str(tmp_path / "cycles.json")
    rc, out, err = drive("--workload", CELL, "--seed", str(2 ** 31 + 17),
                         "--seconds", "5", "--trace", "0", "--control", "1",
                         "--dump-cycles", dump, script=DRIVE)
    assert rc == 0, err[-2000:]
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    for name, (value, limit) in out["checks"].items():
        assert value <= limit, name
    # no placements_per_s: the cell is not on that list
    assert set(out["metrics"]) == {"ttp_p50_ms", "ttp_p95_ms", "cycle_ms",
                                   "setup_s"}
    assert out["control_correct"] is False, out["control_checks"]
    assert any(v > lim for v, lim in out["control_checks"].values())
    assert out["times"]["warmup_s"] > 0
    assert "compiled inside the window" not in err
    # every cycle of the window was one dispatch of eight pools over a
    # four-device mesh, and none compiled anything
    with open(dump, encoding="utf-8") as f:
        doc = json.load(f)
    t0, t1 = doc["window"]
    window = [c for c in doc["cycles"]
              if c["kind"] == "fused" and t0 <= c["start"] < t1]
    assert len(window) >= 3
    dispatched = [c for c in window if "mesh_devices" in c]
    assert dispatched and {c["mesh_devices"] for c in dispatched} == {4}
    assert {c["pools"] for c in dispatched} == {8}
    assert all(c["recompiles"] == {} for c in window)
    assert all("stage_put" in c["detail_ms"] for c in dispatched)


def test_without_four_devices_the_daemon_refuses_to_boot(monkeypatch):
    """One local device and ``mesh_devices`` 4: no result, a non-zero
    exit, the key named — not a one-device cycle."""
    monkeypatch.delenv("XLA_FLAGS", raising=False)
    rc, out, err = drive("--workload", CELL, "--seed", "5", "--seconds", "3",
                         "--trace", "0", script=DRIVE)
    assert rc != 0 and out is None
    assert "pipeline.mesh_devices = 4" in err


def test_a_broken_timed_path_reads_incorrect_on_four_devices(four_devices):
    rc, out, err = drive("--fault", "half_batch", "--workload", CELL,
                         "--seed", "3", "--seconds", "4", "--trace", "0",
                         script=DRIVE)
    assert rc == 0, err[-2000:]
    assert out["correct"] is False
    bad = {k for k, (v, lim) in out["checks"].items() if v > lim}
    assert bad & {"set_gap", "host_gap"}, out["checks"]


# ------------------------------------------------------- the new metrics

def readers():
    return run.load_readers(bench())


def test_each_new_metric_has_its_file_entry_and_reader():
    b = bench()
    by_name = readers()
    # (by name, not by place: later PRs add their entries after these)
    assert [m["name"] for m in b["per_layer"] if m["name"] in NEW] \
        == list(NEW)
    files = set(os.listdir(os.path.join(run.HERE, "layer_metrics")))
    for name in NEW:
        _read, spec, entry = by_name[name]
        assert name + ".json" in files
        assert spec["name"] == name
        assert (spec["layer"], spec["moves"]) \
            == (entry["layer"], entry["moves"])
        assert entry["moves"] == "cycle_ms"
    assert by_name["mesh_devices"][1]["reader"] == "cycle_stat"
    assert by_name["stage_put_ms"][1]["reader"] == "cycle_mean"
    assert by_name["collective_ms"][2]["source"] == "device_trace"
    assert {"trace_collective_ms.py", "trace_shard_roofline.py"} \
        <= set(os.listdir(os.path.join(run.HERE, "readers")))


def test_the_record_metrics_on_a_hand_made_window():
    by_name = readers()
    value = lambda name, ctx: by_name[name][0](ctx, by_name[name][1])
    four = {"cycles": [
        {"mesh_devices": 4, "detail_ms": {"stage": 30.0, "stage_put": 12.0}},
        {"mesh_devices": 4, "detail_ms": {"stage": 28.0, "stage_put": 10.0}},
        # a record that only applied: no dispatch, no field
        {"detail_ms": {"apply": 900.0}}]}
    assert value("mesh_devices", four) == 4.0
    assert value("stage_put_ms", four) == 11.0
    # one silent one-device cycle in a four-device window shows
    four["cycles"].append({"mesh_devices": 1, "detail_ms": {}})
    assert value("mesh_devices", four) == 1.0
    # the parent's records: nothing to read, no raise
    parent = {"cycles": [{"duration_ms": 1.0, "detail_ms": {"stage": 9.0}}]}
    assert value("mesh_devices", parent) is None
    assert value("stage_put_ms", parent) is None


def plane(name, ops=(), modules=(), lines=()):
    return {"name": name, "lines": [
        {"name": trace_reduce.OPS_LINE, "events": list(ops)},
        {"name": trace_reduce.MODULES_LINE, "events": list(modules)},
        *lines]}


def four_planes(with_collectives=True):
    """Two cycles on four devices: ``jit_cycle_body`` once a cycle on each
    (10 ms), two all-gathers and an all-reduce in each run, a fusion whose
    OPERAND is named after a collective (must not count)."""
    planes = []
    for d in range(4):
        ops, mods = [], []
        for c in range(2):
            t = c * 1.0
            mods.append(("jit_cycle_body(123)", t, 0.010))
            ops.append(("%fusion.7 = f32[2,65536]{1,0} fusion(f32[8,4]{1,0} "
                        "%all-gather.1), kind=kLoop", t, 0.009))
            if with_collectives:
                ops += [
                    ("%all-gather.1 = f32[8,4]{1,0} all-gather(f32[2,4]{1,0}"
                     " %p), dimensions={0}", t + 0.009, 0.0002),
                    ("%all-gather.2 = s32[8]{0} all-gather(s32[2]{0} %g), "
                     "dimensions={0}", t + 0.0092, 0.0001),
                    # named by its jaxpr primitive, layout with parentheses
                    ("%psum.7 = s32[]{:T(128)} all-reduce(s32[]{:T(128)} "
                     "%fusion.63), to_apply=%add", t + 0.0093, 0.0001)]
        planes.append(plane(f"/device:TPU:{d}", ops, mods))
    planes.append(plane("/host:CPU", lines=[
        {"name": "cook-cycle", "events": [("cook.stage", 0.0, 0.001)]}]))
    return planes


class Pack:
    shares_u = [0] * 250


class Cap:
    cycles = [{"t_stage": (5.0, 5.1), "groups": [[Pack()] * 8],
               "shapes": [(8, 65536, 2048, 1024)]}]


def trace_ctx(planes, kind="TPU v5 lite"):
    return {"trace": trace_reduce.reduce_planes(planes), "capture": Cap(),
            "window": (0.0, 40.0), "device": {"kind": kind}}


def test_collective_ms_is_per_cycle_and_device_and_none_without_one():
    by_name = readers()
    read, spec, _entry = by_name["collective_ms"]
    ctx = trace_ctx(four_planes())
    assert ctx["trace"]["devices"] == 4
    # 0.4 ms of collectives in each of 8 runs, over 8 runs
    assert read(ctx, spec) == pytest.approx(0.4)
    # kernel_ms beside it reads per device too: 10 ms, not 40
    k_read, k_spec, _e = by_name["kernel_ms"]
    assert k_read(ctx, k_spec) == pytest.approx(10.0)
    # no collective in the trace: nothing, never 0 — the operand named
    # after one does not count
    assert read(trace_ctx(four_planes(with_collectives=False)), spec) is None


def test_shard_cycle_roofline_charges_one_shards_pools_to_one_chip():
    by_name = readers()
    read, spec, _entry = by_name["shard_cycle_roofline"]
    ctx = trace_ctx(four_planes())
    got = read(ctx, spec)
    least = cost.least_seconds(
        cost.fused_cycle_cost(2, 65536, 2048, 250, 1024), "TPU v5 lite")
    assert got == pytest.approx(least["seconds"] / 0.010 * 100.0)
    # four times fewer pools than the whole-dispatch roofline beside it
    w_read, w_spec, _e = by_name["fused_cycle_roofline"]
    whole = w_read(ctx, w_spec)
    assert 3.5 < whole / got <= 4.0 + 1e-9
    assert 0 < got < 105.0
    # one device: the shard is the whole dispatch
    one = trace_ctx(four_planes()[:1] + four_planes()[-1:])
    assert one["trace"]["devices"] == 1
    assert read(one, spec) == pytest.approx(w_read(one, w_spec))
    # nothing of the executable in the trace: nothing to read
    empty = trace_ctx([plane("/device:TPU:0", [("%copy.1 = f32[1]{0} "
                                                "copy(f32[1]{0} %x)",
                                                0.0, 0.001)])])
    assert read(empty, spec) is None
