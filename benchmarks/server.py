"""The system under test, in this process: a ``cook_tpu.daemon.CookDaemon``
with its REST socket on loopback and ``Scheduler.run()``'s own cycle
thread, plus the benchmark's wrappers around the calls into each layer.

The wrappers do two things and nothing else: they put a
``jax.profiler.TraceAnnotation`` around the call (so idle gaps in a trace
can be attributed), and they keep REFERENCES to what the cycle already
built (the staged packs, the fetched candidate arrays, the match results)
in capture order.  Nothing is copied or computed inside the timed path;
``check.py`` reads the captures after the window has closed.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

import world as worldlib

LAYERS = ("stage", "dispatch_group", "fetch_group", "apply_group")


class Capture:
    """Events of the cycle thread in the order they happened:
    ("stage", cycle), ("fetch", cycle), ("apply", cycle).  A cycle keeps
    the packed pools (host arrays the cycle built) and the fetched host
    outputs; it does not keep the dispatch, whose device buffers the
    program is free to drop."""

    def __init__(self):
        self.events: List[tuple] = []
        self.cycles: List[Dict] = []
        self._by_group: Dict[int, Dict] = {}

    # called from the cycle thread only
    def staged(self, staged, t0: float, t1: float) -> None:
        cyc = {"id": len(self.cycles), "t_stage": (t0, t1),
               "groups": [sg.group for sg in staged.groups],
               "shapes": [(len(sg.group), sg.T, sg.H, sg.cap)
                          for sg in staged.groups],
               "fetched": [], "applied": {}, "t_apply": None}
        self.cycles.append(cyc)
        for sg in staged.groups:
            self._by_group[id(sg)] = cyc
        self.events.append(("stage", cyc))

    def fetched(self, gd) -> None:
        cyc = self._by_group.get(id(gd.sg))
        if cyc is not None and not any(
                g is gd.sg.group for g, _f in cyc["fetched"]):
            cyc["fetched"].append((gd.sg.group, gd.fetched))
            self.events.append(("fetch", cyc))

    def applied(self, gd, results, t0: float, t1: float) -> None:
        cyc = self._by_group.pop(id(gd.sg), None)
        if cyc is None:
            return
        for pp in gd.sg.group:
            res = results.get(pp.pool.name)
            if res is not None:
                cyc["applied"][pp.pool.name] = res
        cyc["t_apply"] = (t0, t1)
        self.events.append(("apply", cyc))


def install_wrappers(capture: Capture) -> None:
    """Wrap FusedCycleDriver's four phase methods at class level, before
    the scheduler exists (the daemon builds and starts it in one call)."""
    import jax
    from cook_tpu.sched.fused import FusedCycleDriver

    if getattr(FusedCycleDriver, "_bench_wrapped", False):
        raise RuntimeError("benchmark wrappers are already installed")
    orig = {name: getattr(FusedCycleDriver, name) for name in LAYERS}
    note = jax.profiler.TraceAnnotation

    def stage(self, scheduler, **kw):
        t0 = time.time()
        with note("cook.stage"):
            staged = orig["stage"](self, scheduler, **kw)
        capture.staged(staged, t0, time.time())
        return staged

    def dispatch_group(self, sg):
        with note("cook.dispatch"):
            return orig["dispatch_group"](self, sg)

    def fetch_group(self, gd):
        with note("cook.fetch"):
            out = orig["fetch_group"](self, gd)
        capture.fetched(gd)
        return out

    def apply_group(self, scheduler, gd, queues, results, **kw):
        t0 = time.time()
        with note("cook.apply"):
            orig["apply_group"](self, scheduler, gd, queues, results, **kw)
        capture.applied(gd, results, t0, time.time())

    FusedCycleDriver.stage = stage
    FusedCycleDriver.dispatch_group = dispatch_group
    FusedCycleDriver.fetch_group = fetch_group
    FusedCycleDriver.apply_group = apply_group
    FusedCycleDriver._bench_wrapped = True
    FusedCycleDriver._bench_orig = orig


def remove_wrappers() -> None:
    from cook_tpu.sched.fused import FusedCycleDriver
    orig = getattr(FusedCycleDriver, "_bench_orig", None)
    if orig:
        for name, fn in orig.items():
            setattr(FusedCycleDriver, name, fn)
        FusedCycleDriver._bench_wrapped = False
        FusedCycleDriver._bench_orig = None


def bucket(n: int, minimum: int = 64) -> int:
    b = minimum
    while b < n:
        b *= 2
    return b


def daemon_conf(config: Dict, data_dir: str, arrivals: int) -> Dict:
    """The daemon configuration a benchmark configuration expands to.  The
    warm-up grid is cut to the cell's own buckets: live rows are backlog +
    arrivals (a launched job stays a row), hosts one pool's, users all."""
    w = config["world"]
    sched = dict(config["scheduler"])
    pipeline = dict(sched.get("pipeline", {}))
    if len(w["pools"]) == 1:
        pipeline.update(
            warmup_tasks=bucket(int(w["jobs_per_pool"]) + arrivals),
            warmup_hosts=bucket(int(w["hosts_per_pool"])),
            warmup_users=int(w["backlog_users"]) + int(w["light_users"]))
    # several pools are stacked [P, T] in one dispatch, which the
    # program's warm-up (P = mesh size = 1) does not cover: such a cell
    # compiles inside its cycles (PERF.md, Open questions)
    sched["pipeline"] = pipeline
    return {"host": "127.0.0.1", "port": 0, "data_dir": data_dir,
            "admins": ["admin"], "clusters": worldlib.cluster_specs(w),
            "scheduler": sched}


class Server:
    """Builds the daemon, loads the world, starts the scheduler."""

    def __init__(self, config: Dict, data_dir: str, arrivals: int):
        from cook_tpu.daemon import CookDaemon
        self.config = config
        self.world = config["world"]
        self.capture = Capture()
        self.arrivals = arrivals
        self.times: Dict[str, float] = {}
        self.scheduler = None
        self.stopped = False
        conf = daemon_conf(config, data_dir, arrivals)
        # api_only: the daemon serves REST and opens the store but does
        # not campaign; the backlog goes in first, then campaign() makes
        # it the leader, which builds, warms and starts the scheduler
        self.daemon = CookDaemon(conf, api_only=True)

    @property
    def url(self) -> str:
        return self.daemon.node_url

    def open(self) -> None:
        t0 = time.time()
        self.daemon.start()
        self.times["store_open_s"] = time.time() - t0

    def load(self, backlog: "worldlib.JobTable", quota: Optional[Dict],
             users: List[str], batch: int = 2000) -> None:
        """Pools, shares, quotas and the backlog, in-process through
        Store.create_jobs with the journal (fsync, group commit) on."""
        from cook_tpu.state import Job, Pool, Resources
        store = self.daemon.store
        t0 = time.time()
        w = self.world
        for pool in w["pools"]:
            store.put_pool(Pool(name=pool))
        for pool in w["pools"]:
            for user in users:
                store.set_share(user, pool, worldlib.user_share(user, w),
                                reason="benchmark")
                if quota and user.startswith("user"):
                    store.set_quota(user, pool, {},
                                    count=float(quota["count"]),
                                    reason="benchmark")
        # backlog submit times precede every arrival and are all distinct
        base_ms = int(time.time() * 1000) - len(backlog.uuid) - 1000
        self.backlog_submit_ms = base_ms + np.arange(len(backlog.uuid),
                                                     dtype=np.int64)
        n = len(backlog.uuid)
        for i in range(0, n, batch):
            store.create_jobs([
                Job(uuid=str(backlog.uuid[j]), user=str(backlog.user[j]),
                    command="true", name="backlog", pool=str(backlog.pool[j]),
                    priority=int(backlog.priority[j]), max_retries=1,
                    resources=Resources(cpus=float(backlog.cpus[j]),
                                        mem=float(backlog.mem[j])),
                    submit_time_ms=int(self.backlog_submit_ms[j]))
                for j in range(i, min(i + batch, n))])
        self.times["backlog_load_s"] = time.time() - t0

    def lead(self, timeout_s: float = 1500.0) -> None:
        """Campaign; returns once the scheduler's cycle thread runs."""
        t0 = time.time()
        install_wrappers(self.capture)
        self.daemon.elector.campaign()
        while self.daemon.api.scheduler is None:
            if self.daemon._done.is_set():
                raise RuntimeError("the daemon failed its takeover "
                                   f"(exit code {self.daemon.exit_code})")
            if time.time() - t0 > timeout_s:
                raise RuntimeError("no scheduler after "
                                   f"{timeout_s:.0f}s of campaigning")
            time.sleep(0.05)
        self.scheduler = self.daemon.scheduler
        # Scheduler.run() has just started the cycle thread and the 30 s
        # sweep threads: their timers count from here
        self.t_scheduler = time.time()
        self.times["lead_s"] = self.t_scheduler - t0
        self.times["warmup_s"] = float(
            self.scheduler.device.get("warmup_s") or 0.0)

    def wait_cycles(self, n: int, timeout_s: float = 900.0) -> None:
        """Until n more cycles have been applied."""
        target = self.applied_cycles() + n
        t0 = time.time()
        while self.applied_cycles() < target:
            self.check_alive()
            if time.time() - t0 > timeout_s:
                raise RuntimeError(f"{n} cycles did not finish in "
                                   f"{timeout_s:.0f}s")
            time.sleep(0.02)

    def applied_cycles(self) -> int:
        return sum(1 for kind, _c in self.capture.events if kind == "apply")

    def last_apply_end(self) -> float:
        for kind, cyc in reversed(self.capture.events):
            if kind == "apply":
                return cyc["t_apply"][1]
        return 0.0

    def check_alive(self) -> None:
        if self.daemon._done.is_set() or self.scheduler.fatal_error:
            raise RuntimeError("the scheduler stopped: "
                               f"{self.scheduler.fatal_error!r}")

    def stop(self) -> None:
        """Shut the daemon down once, and wait for the scheduler's threads:
        a cycle thread still inside XLA when the interpreter exits aborts
        the process."""
        if self.stopped:
            return
        self.stopped = True
        try:
            self.daemon.shutdown()
            for t in getattr(self.scheduler, "_threads", []):
                t.join(timeout=120.0)
        finally:
            remove_wrappers()
