"""Drives benchmarks/run.py here on the CPU at a tiny size: the look for a
chip is the only thing stubbed.  ``--fault <name>`` breaks the timed path
underneath the harness first, so a test can see ``correct`` come out false.

    JAX_PLATFORMS=cpu python benchmarks/tests/drive.py [--fault F] --workload 1pool-drain --seed 1 --seconds 4 --trace 0

The tiny cells are the real files (BENCHMARK.json, configs/, traffic/)
with their sizes overridden below, written to a temporary directory: no
second copy to drift.  ``8pool-drain`` is the one cell made up here: the
one-pool configuration with eight pools, for the stacked dispatch.
"""

import json
import os
import sys
import tempfile

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.dirname(BENCH)]


TINY_WORLD = {"jobs_per_pool": 3000, "hosts_per_pool": 256,
              "backlog_users": 20, "light_users": 5}
TINY_MIX = {"requests_per_s": 10.0, "light_users": 5, "settle_requests": 3,
            "start_after_cycle_s": 0.1, "start_after_scheduler_s": 0.0}
EIGHT = {"name": "8pool-drain", "config": "cook-1pool-100kx5k",
         "traffic": "drain", "chips": 1, "why": "eight pools stacked"}


def tiny_bench(root: str, cell_name: str) -> str:
    """Write the real benchmark with one cell cut to a tiny size under
    ``root``; returns the BENCHMARK.json to hand to run.py."""
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        bench = json.load(f)
    if cell_name == EIGHT["name"]:
        bench["workloads"].append(dict(EIGHT))
        for m in bench["end_to_end"]:
            if m["name"] == "placements_per_s":
                m["workloads"].append(cell_name)
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(os.path.dirname(BENCH), conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    eight = cell_name == EIGHT["name"]
    config["world"].update(TINY_WORLD)
    if eight:
        config["world"]["pools"] = [f"pool{i}" for i in range(8)]
    config["scheduler"]["match_interval_seconds"] = 0.5 if eight else 0.2
    config["scheduler"]["default_matcher"]["max_jobs_considered"] = \
        200 if eight else 100
    mix.update(TINY_MIX)
    if mix.get("backlog_quota"):
        mix["backlog_quota"] = {"count": 5}
    conf["file"] = "configs/tiny.json"
    for rel, doc in ((conf["file"], config),
                     (f"traffic/{cell['traffic']}.json", mix),
                     ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w", encoding="utf-8") as f:
            json.dump(doc, f)
    return os.path.join(root, "BENCHMARK.json")


def plant(fault: str) -> None:
    """Break FusedCycleDriver before the harness wraps it."""
    import numpy as np
    from cook_tpu.sched.fused import FusedCycleDriver
    fetch, apply_ = FusedCycleDriver.fetch_group, FusedCycleDriver.apply_group

    if fault == "state_unchanged":
        # every other cycle returns its state unchanged: nothing launches
        count = [0]

        def apply_group(self, scheduler, gd, queues, results, **kw):
            count[0] += 1
            if count[0] % 2:
                return apply_(self, scheduler, gd, queues, results, **kw)
            for pp in gd.sg.group:
                queues.setdefault(pp.pool.name, [])
        FusedCycleDriver.apply_group = apply_group
        return

    def half(cand_row, cand_assign):
        cand_assign[:, ::2] = -1          # half of the batch left out

    def moved(cand_row, cand_assign):
        ok = cand_assign >= 0             # answers altered where produced
        cand_assign[ok] = np.maximum(cand_assign[ok] - 1, 0)

    change = {"half_batch": half, "answer_altered": moved}[fault]

    def fetch_group(self, gd):
        first = gd.fetched is None
        out = fetch(self, gd)
        if first:
            cand_row, cand_assign = np.array(out[0]), np.array(out[1])
            change(cand_row, cand_assign)
            gd.fetched = (cand_row, cand_assign) + tuple(out[2:])
        return gd.fetched
    FusedCycleDriver.fetch_group = fetch_group


def main(argv) -> int:
    import run
    if "--fault" in argv:
        i = argv.index("--fault")
        fault = argv[i + 1]
        argv = argv[:i] + argv[i + 2:]
        plant(fault)
    run.require_chip = lambda chips: {"platform": "cpu", "kind": "cpu",
                                      "count": 1}
    with tempfile.TemporaryDirectory(prefix="cook-bench-tiny-") as root:
        cell = argv[argv.index("--workload") + 1]
        return run.main(argv + ["--bench-file", tiny_bench(root, cell)])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
