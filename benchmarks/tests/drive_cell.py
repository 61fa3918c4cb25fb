"""drive.py for a cell as BENCHMARK.json REALLY has it: the entry, its
configuration file and its traffic mix are the real ones, with only their
sizes cut (drive.py makes up an eight-pool form of the one-pool file
instead, from before ``8pool-drain`` was a cell).

    JAX_PLATFORMS=cpu python benchmarks/tests/drive_cell.py [--fault F] --workload 8pool-drain --seed 1 --seconds 4 --trace 0

The configuration's own warm-up design point is cut with its world, so
the program warms the tiny stacked shapes by itself and the run shows
that nothing compiles inside the window.
"""

import json
import os
import sys
import tempfile

from drive import BENCH, TINY_MIX, TINY_WORLD, plant
from server import bucket


def tiny_bench(root: str, cell_name: str, seconds: float) -> str:
    """The real benchmark with ``cell_name``'s configuration and mix cut
    to a tiny size, written under ``root``."""
    top = os.path.dirname(BENCH)
    with open(os.path.join(top, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {c["name"]: c for c in bench["workloads"]}[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    with open(os.path.join(top, conf["file"])) as f:
        config = json.load(f)
    with open(os.path.join(BENCH, "traffic", cell["traffic"] + ".json")) as f:
        mix = json.load(f)
    world = config["world"]
    world.update(TINY_WORLD)
    several = len(world["pools"]) > 1
    sched = config["scheduler"]
    sched["match_interval_seconds"] = 0.5 if several else 0.2
    sched["default_matcher"]["max_jobs_considered"] = \
        200 if several else 100
    mix.update(TINY_MIX)
    if mix.get("backlog_quota"):
        mix["backlog_quota"] = {"count": 5}
    pipeline = sched.setdefault("pipeline", {})
    if "warmup_tasks" in pipeline:
        # the design point follows the world: one pool's rows are its
        # backlog plus its share of a window's arrivals (all of them in
        # the worst case), its hosts, every user
        arrivals = int(mix["requests_per_s"] * mix["jobs_per_request"][1]
                       * (seconds + mix.get("settle_requests", 0)))
        pipeline.update(
            warmup_tasks=bucket(world["jobs_per_pool"] + arrivals),
            warmup_hosts=bucket(world["hosts_per_pool"]),
            warmup_users=world["backlog_users"] + world["light_users"])
    conf["file"] = "configs/tiny.json"
    for rel, doc in ((conf["file"], config),
                     (f"traffic/{cell['traffic']}.json", mix),
                     ("BENCHMARK.json", bench)):
        os.makedirs(os.path.dirname(os.path.join(root, rel)), exist_ok=True)
        with open(os.path.join(root, rel), "w", encoding="utf-8") as f:
            json.dump(doc, f)
    return os.path.join(root, "BENCHMARK.json")


def main(argv) -> int:
    import run
    if "--fault" in argv:
        i = argv.index("--fault")
        plant(argv[i + 1])
        argv = argv[:i] + argv[i + 2:]
    run.require_chip = lambda chips: {"platform": "cpu", "kind": "cpu",
                                      "count": 1}
    with tempfile.TemporaryDirectory(prefix="cook-bench-tiny-") as root:
        cell = argv[argv.index("--workload") + 1]
        seconds = float(argv[argv.index("--seconds") + 1])
        return run.main(argv + ["--bench-file",
                                tiny_bench(root, cell, seconds)])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
