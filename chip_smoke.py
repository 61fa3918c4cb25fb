#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that cook_tpu still starts on the chip.

Drives the production path once, end to end, at BASELINE config 3 (the
repo's production cell): one pool, 5,000 hosts, 100,000 pending jobs
from 200 users made from ``--seed``.

  1. the normal entry point, ``python -m cook_tpu --config <conf>``, as
     ONE child that owns the chip: durable data_dir, fused cycle,
     pipeline depth 2, resident pack, quantized wire, the production
     warm-up grid; the jobs go in over the socket with JobClient.submit;
     the daemon's own cycle thread schedules them; /debug/health,
     /debug/cycles, /metrics and the jobs are read back through the
     client;
  2. a second child, after the first has exited: the same seeded world
     built twice in-process, one synchronous step_cycle() on the device
     against step_rank()+step_match() on the numpy reference — the
     decision check — with the same warm-up grid, which must now come
     out of the compile cache the first child filled;
  3. where JAX reports four or more devices, a third child: the
     pool-sharded cycle on four real devices against four single-device
     runs.

A chip belongs to one process at a time, so this parent never imports
JAX: every phase that needs the chip is a child started after the
previous one has exited.  Standard output ends with two lines: the
full report (``{"report": {...}}``: versions, path, cycles, placements,
fallback and recompile counts, warm-up walls, parity, native libraries,
every check), then the result, exactly
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``
with the device as JAX reports it.  Exit code 0 and ``"ok": true`` only
when every check held ON A TPU.  No accelerator (``JAX_PLATFORMS=cpu``)
or no program beside this file: a message on stderr, a non-zero exit, no
result line.

``--allow-cpu`` and the size flags exist to debug this script in a
sandbox without a chip; a run that uses any of them ends with
``"ok": false`` and exit code 1 whatever it found.
"""

from __future__ import annotations

import argparse
import json
import os
import signal
import subprocess
import sys
import tempfile
import time
import uuid as _uuid

HERE = os.path.dirname(os.path.abspath(__file__))

FULL_JOBS, FULL_HOSTS, FULL_USERS = 100_000, 5_000, 200
HOST_CPUS, HOST_MEM = 64.0, 65536.0
#: tasks outlive the run, so launched jobs hold their capacity
TASK_DURATION_MS = 3_600_000
#: BASELINE.md: >= 99.9 % placement parity against the CPU path
PARITY_BAR = 0.999
SUBMIT_BATCH = 500
SAMPLE_JOBS = 2000
#: BASELINE config 4 (8 pools x 50k jobs on a v5e-8) cut to the four
#: chips of one host; the 5,000-host fleet split over the four pools
MULTI_POOLS, MULTI_JOBS, MULTI_HOSTS, MULTI_USERS = 4, 50_000, 1_250, 200


class SmokeFailure(Exception):
    """A phase failed; the message says which check."""


def log(msg: str) -> None:
    print(f"chip_smoke: {msg}", file=sys.stderr, flush=True)


# ------------------------------------------------------------------ world
def bucket(n: int, minimum: int = 64) -> int:
    """ops/padding.bucket, restated: importing cook_tpu.ops would import
    JAX into the parent."""
    b = minimum
    while b < n:
        b *= 2
    return b


def make_jobs(seed: int, n_jobs: int, n_users: int):
    """The seeded backlog: (uuid, user, cpus, mem, priority) per job.
    Users are drawn at random (not round-robin), sizes from a small
    skewed menu so DRU order is decided by real cumulative shares."""
    import numpy as np
    rng = np.random.default_rng(seed)
    users = rng.integers(0, n_users, n_jobs)
    cpus = rng.choice([1.0, 2.0, 4.0], size=n_jobs, p=[0.5, 0.3, 0.2])
    mem = cpus * rng.choice([1024.0, 2048.0], size=n_jobs)
    prio = rng.integers(0, 100, n_jobs)
    ids = rng.integers(0, 2 ** 32, size=(n_jobs, 4), dtype=np.uint64)
    jobs = []
    for i in range(n_jobs):
        a, b, c, d = (int(x) for x in ids[i])
        u = str(_uuid.UUID(int=(a << 96) | (b << 64) | (c << 32) | d))
        jobs.append((u, f"user{int(users[i]):03d}", float(cpus[i]),
                     float(mem[i]), int(prio[i])))
    return jobs


def user_share(user: str) -> dict:
    """Every tenth user holds a double share, so fair-share order is not
    submission order."""
    k = 2.0 if int(user[4:]) % 10 == 0 else 1.0
    return {"cpus": 400.0 * k, "mem": 409600.0 * k}


def scheduler_section(n_jobs: int, n_hosts: int, n_users: int,
                      depth: int) -> dict:
    """What examples/cook-production.json ships for the cycle; the
    warm-up grid is the production one at the full size (131072 tasks,
    8192 hosts) and the same buckets the live cycle lands in otherwise."""
    return {
        "cycle_mode": "fused",
        "rank_backend": "tpu",
        "default_matcher": {"backend": "auto",
                            "auto_large_j_threshold": 2000,
                            "auto_packing": "throughput"},
        "pipeline": {"depth": depth,
                     "warmup_tasks": bucket(n_jobs),
                     "warmup_hosts": bucket(n_hosts),
                     "warmup_users": n_users},
        "columnar_index": True,
        "resident_pack": True,
        "quantized_wire": True,
    }


def cache_dir() -> str:
    """Where ops/telemetry.enable_compilation_cache puts the cache for
    the conf this script writes (which configures no directory)."""
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") \
        or os.path.join(HERE, ".jax_cache")


def cache_entries() -> int:
    try:
        return len(os.listdir(cache_dir()))
    except FileNotFoundError:
        return 0


# --------------------------------------------------------------- children
def run_child(phase: str, args, timeout_s: float) -> dict:
    """Run one phase of this file as a child that may own the chip; its
    last stdout line is its JSON result."""
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--seed", str(args.seed), "--jobs", str(args.jobs),
           "--hosts", str(args.hosts), "--users", str(args.users)]
    log(f"child {phase}: starting")
    t0 = time.time()
    try:
        p = subprocess.run(cmd, cwd=HERE, stdout=subprocess.PIPE,
                           timeout=timeout_s, text=True)
    except subprocess.TimeoutExpired:
        raise SmokeFailure(f"child {phase} ran past {timeout_s:.0f}s")
    lines = [ln for ln in p.stdout.splitlines() if ln.strip()]
    if p.returncode != 0 or not lines:
        raise SmokeFailure(f"child {phase} exited {p.returncode}: "
                           + " | ".join(lines[-3:]))
    try:
        out = json.loads(lines[-1])
    except ValueError:
        raise SmokeFailure(f"child {phase} printed no JSON: {lines[-1]!r}")
    log(f"child {phase}: done in {time.time() - t0:.1f}s")
    return out


def phase_probe(_args) -> dict:
    import jax
    import jaxlib
    try:
        import libtpu
        libtpu_version = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_version = None
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind, "count": len(devices),
            "versions": {"python": sys.version.split()[0],
                         "jax": jax.__version__,
                         "jaxlib": jaxlib.__version__,
                         "libtpu": libtpu_version}}


def phase_parity(args) -> dict:
    """The decision check: one synchronous fused cycle on the device vs
    the numpy reference (ops/reference_impl.py through the split host
    path) on two identical seeded worlds."""
    from cook_tpu.cluster.fake import factory as fake_factory
    from cook_tpu.daemon import build_scheduler_config
    from cook_tpu.sched import Scheduler
    from cook_tpu.state import Job, Pool, Resources, Store
    from cook_tpu.utils.flight import recorder
    from cook_tpu.utils.metrics import registry

    jobs = make_jobs(args.seed, args.jobs, args.users)

    def build(rank_backend: str, section: dict):
        store = Store()
        store.put_pool(Pool(name="default"))
        for user in sorted({j[1] for j in jobs}):
            store.set_share(user, "default", user_share(user))
        cluster = fake_factory(name="fleet", n_hosts=args.hosts,
                               cpus=HOST_CPUS, mem=HOST_MEM,
                               default_task_duration_ms=TASK_DURATION_MS)
        sched = Scheduler(store, build_scheduler_config(section),
                          [cluster], rank_backend=rank_backend)
        ents = [Job(uuid=u, user=user, command="true", pool="default",
                    priority=prio, resources=Resources(cpus=cpus, mem=mem),
                    submit_time_ms=1000 + i)
                for i, (u, user, cpus, mem, prio) in enumerate(jobs)]
        for i in range(0, len(ents), 2000):
            store.create_jobs(ents[i:i + 2000])
        return store, sched

    def decisions(store) -> dict:
        out = {}
        for job, inst in store.running_instances():
            out[job.uuid] = inst.hostname
        return out

    dev_section = scheduler_section(args.jobs, args.hosts, args.users,
                                    depth=0)
    store_d, sched_d = build("tpu", dev_section)
    seq0 = recorder.last_seq()
    sched_d.step_cycle()
    rec = [r for r in recorder.recent(limit=10) if r["seq"] > seq0][-1]
    fallbacks = sum(v for _l, v in registry.series("cook_kernel_fallback"))
    dec_d = decisions(store_d)

    ref_section = dict(dev_section, cycle_mode="split", rank_backend="cpu",
                       default_matcher={"backend": "cpu"},
                       pipeline={"depth": 0})
    store_r, sched_r = build("cpu", ref_section)
    sched_r.step_rank()
    sched_r.step_match()
    dec_r = decisions(store_r)

    both = set(dec_d) & set(dec_r)
    union = set(dec_d) | set(dec_r)
    same_host = sum(1 for u in both if dec_d[u] == dec_r[u])
    return {
        "device": sched_d.device,
        "path": rec["path"], "faults": rec["faults"],
        "fallback_total": fallbacks,
        "launched_device": len(dec_d), "launched_reference": len(dec_r),
        "launched_set_agreement": len(both) / max(len(union), 1),
        "job_host_agreement": same_host / max(len(both), 1),
        "warmup_s": sched_d.device.get("warmup_s"),
        "warmup_runs": sched_d.device.get("warmup_runs"),
    }


def phase_multichip(args) -> dict:
    """A kernel-level check of the pool-sharded cycle on four real
    devices: make_pool_cycle(pool_mesh(4), structured=True) — BASELINE
    config 4 cut to 4 pools x 50k jobs — against the same pools run one
    by one on one device.  The bare kernel, not the served path: the
    daemon's own four-device cycle (``pipeline.mesh_devices``: pipelined,
    resident, warmed) is what the benchmark cell ``4chip-8pool-drain``
    runs (PERF.md section 4)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh

    from cook_tpu.ops import host_prep
    from cook_tpu.ops.padding import pad_to
    from cook_tpu.ops.reference_impl import UserTasks
    from cook_tpu.parallel.mesh import POOL_AXIS, pool_mesh, pool_sharding
    from cook_tpu.parallel.sharded import (StructuredPoolCycleInputs,
                                           make_pool_cycle)

    scale = args.jobs / FULL_JOBS
    n_jobs = max(int(MULTI_JOBS * scale), 64)
    n_hosts = max(int(MULTI_HOSTS * scale), 8)
    n_users = max(min(MULTI_USERS, n_jobs // 8), 2)
    P, T, H = MULTI_POOLS, bucket(n_jobs), bucket(n_hosts)
    cap = min(1024, T)

    def pool_arrays(seed: int):
        rng = np.random.default_rng(seed)
        owner = rng.integers(0, n_users, n_jobs)
        users, shares, quotas = [], {}, {}
        tid = 0
        for u in range(n_users):
            n = int((owner == u).sum())
            if not n:
                continue
            cpus = rng.choice([1.0, 2.0, 4.0], size=n, p=[0.5, 0.3, 0.2])
            rows = np.stack([cpus, cpus * 1024.0, np.zeros(n), np.ones(n)],
                            axis=1).astype(np.float32)
            # the first tenth of each user's tasks already run: DRU
            # starts from unequal usage, as in a live pool
            pend = [i >= n // 10 for i in range(n)]
            name = f"user{u:03d}"
            users.append(UserTasks(name, list(range(tid, tid + n)), rows,
                                   pend))
            tid += n
            shares[name] = (400.0, 409600.0, 8.0)
            quotas[name] = np.full(4, np.inf, dtype=np.float32)
        arrays, _ = host_prep.pack_rank_inputs(users, shares, quotas)
        for k, fill in (("usage", 0), ("quota", np.inf),
                        ("shares", np.inf), ("first_idx", 0),
                        ("user_rank", 2 ** 31 - 1), ("pending", False),
                        ("valid", False)):
            arrays[k] = pad_to(arrays[k], T, fill=fill)
        job_res = np.concatenate(
            [arrays["usage"][:, :3], np.zeros((T, 1), np.float32)], axis=1)
        capacity = np.zeros((H, 4), np.float32)
        capacity[:n_hosts] = [HOST_CPUS, HOST_MEM, 0.0, 1e6]
        blocked = np.ones(H, dtype=bool)
        blocked[:n_hosts] = False
        return arrays, job_res, capacity, blocked

    pools = [pool_arrays(args.seed + 1 + i) for i in range(P)]

    def stacked(sel) -> StructuredPoolCycleInputs:
        ps = [pools[i] for i in sel]
        n = len(ps)
        field = lambda k: np.stack([p[0][k] for p in ps])
        return StructuredPoolCycleInputs(
            usage=field("usage"), quota=field("quota"),
            shares=field("shares"), first_idx=field("first_idx"),
            user_rank=field("user_rank"), pending=field("pending"),
            valid=field("valid"),
            enqueue_ok=np.ones((n, T), bool),
            launch_ok=np.ones((n, T), bool),
            tokens=np.full((n, T), np.inf, np.float32),
            num_considerable=np.full((n,), cap, np.int32),
            pool_quota=np.full((n, 4), np.inf, np.float32),
            group_quota=np.full((n, 4), np.inf, np.float32),
            group_id=np.full((n,), -1, np.int32),
            job_res=np.stack([p[1] for p in ps]),
            host_gpu=np.zeros((n, H), bool),
            host_blocked=np.stack([p[3] for p in ps]),
            exc_id=np.full((n, T), -1, np.int32),
            exc_mask=np.zeros((n, 1, H), bool),
            avail=np.stack([p[2] for p in ps]),
            capacity=np.stack([p[2] for p in ps]))

    devices = jax.devices()
    mesh4 = pool_mesh(P)
    sh = pool_sharding(mesh4)
    inp4 = StructuredPoolCycleInputs(
        *(jax.device_put(a, sh) for a in stacked(range(P))))
    t0 = time.perf_counter()
    res4 = make_pool_cycle(mesh4, structured=True,
                           considerable_cap=cap)(inp4)
    total4 = int(res4.total_matched)
    first_call_s = time.perf_counter() - t0
    out_devices = sorted(str(s.device)
                         for s in res4.assign.addressable_shards)
    assign4 = np.asarray(res4.assign)

    mesh1 = Mesh(np.array(devices[:1]), (POOL_AXIS,))
    cycle1 = make_pool_cycle(mesh1, structured=True, considerable_cap=cap)
    totals1, same_assign = [], True
    for i in range(P):
        r = cycle1(StructuredPoolCycleInputs(
            *(jnp.asarray(a) for a in stacked([i]))))
        totals1.append(int(r.total_matched))
        same_assign &= bool((np.asarray(r.assign)[0] == assign4[i]).all())
    return {
        "pools": P, "jobs_per_pool": n_jobs, "hosts_per_pool": n_hosts,
        "considerable_cap": cap, "devices": len(devices),
        "output_devices": out_devices,
        "distinct_output_devices": len(set(out_devices)),
        "total_matched_4dev": total4,
        "total_matched_single_runs": totals1,
        "totals_equal": total4 == sum(totals1),
        "assignments_equal": same_assign,
        "first_call_s": round(first_call_s, 2),
    }


# ------------------------------------------------------------ daemon child
def native_report() -> dict:
    """Build (g++, from native/*.cpp) or fall back, per library."""
    from cook_tpu.cluster import remote
    from cook_tpu.native import jobclient, pack, watch_queue
    from cook_tpu.state import replication
    probes = {"libcookpack": pack.native_available,
              "libwatchqueue": watch_queue.native_available,
              "libcookjobclient": jobclient.native_available,
              "libcookrepl": replication.replication_available,
              "libcooktransport": remote.native_available}
    return {name: ("built" if fn() else "python fallback")
            for name, fn in probes.items()}


def parse_metrics(text: str) -> dict:
    """name -> [(labels dict, value)] from a Prometheus exposition."""
    from cook_tpu.utils.metrics import parse_exposition
    out: dict = {}
    for name, labels, value in parse_exposition(text):
        out.setdefault(name, []).append((labels, value))
    return out


def metric_sum(metrics: dict, name: str, **match) -> float:
    return sum(v for labels, v in metrics.get(name, [])
               if all(labels.get(k) == want for k, want in match.items()))


class Daemon:
    """``python -m cook_tpu --config <generated conf>`` as a child in its
    own process group, so nothing it starts outlives the smoke."""

    def __init__(self, conf: dict, workdir: str):
        self.log_path = os.path.join(workdir, "daemon.log")
        conf_path = os.path.join(workdir, "cook.json")
        with open(conf_path, "w", encoding="utf-8") as f:
            json.dump(conf, f, indent=1)
        self._log = open(self.log_path, "wb")
        self.proc = subprocess.Popen(
            [sys.executable, "-m", "cook_tpu", "--config", conf_path],
            cwd=HERE, stdout=self._log, stderr=subprocess.STDOUT,
            start_new_session=True)

    def tail(self, n: int = 30) -> str:
        with open(self.log_path, "r", errors="replace") as f:
            return "".join(f.readlines()[-n:])

    def check_alive(self) -> None:
        rc = self.proc.poll()
        if rc is not None:
            raise SmokeFailure(f"the daemon exited with code {rc}:\n"
                               + self.tail())

    def wait_url(self, timeout_s: float = 120.0) -> str:
        deadline = time.time() + timeout_s
        while time.time() < deadline:
            self.check_alive()
            for line in self.tail(200).splitlines():
                if line.startswith("cook_tpu: serving http://"):
                    return line.split()[2]
            time.sleep(0.2)
        raise SmokeFailure("the daemon never printed its URL:\n"
                           + self.tail())

    def stop(self) -> int:
        """SIGTERM, wait for the clean exit, then make sure the whole
        process group is gone."""
        rc = self.proc.poll()
        if rc is None:
            self.proc.send_signal(signal.SIGTERM)
            try:
                rc = self.proc.wait(timeout=120)
            except subprocess.TimeoutExpired:
                rc = -9
        try:
            os.killpg(self.proc.pid, signal.SIGKILL)
        except ProcessLookupError:
            pass
        self.proc.wait()
        self._log.close()
        return rc


def wait_for(what: str, fn, timeout_s: float, daemon: Daemon,
             interval_s: float = 0.5):
    deadline = time.time() + timeout_s
    while time.time() < deadline:
        daemon.check_alive()
        got = fn()
        if got:
            return got
        time.sleep(interval_s)
    raise SmokeFailure(f"timed out after {timeout_s:.0f}s waiting for "
                       f"{what}:\n{daemon.tail()}")


def drive_daemon(args, probe: dict, workdir: str) -> dict:
    """Phase 1: REST -> store -> fused cycle -> launch on the daemon's own
    threads; every fact below is read back through the client."""
    import numpy as np

    from cook_tpu.client import JobClient, JobClientError

    conf = {
        "host": "127.0.0.1", "port": 0,
        "data_dir": os.path.join(workdir, "data"),
        "admins": ["admin"],
        "clusters": [{
            "factory": "cook_tpu.cluster.fake.factory",
            "kwargs": {"name": "fleet", "n_hosts": args.hosts,
                       "cpus": HOST_CPUS, "mem": HOST_MEM,
                       "default_task_duration_ms": TASK_DURATION_MS,
                       "auto_advance": True}}],
        "scheduler": scheduler_section(args.jobs, args.hosts, args.users,
                                       depth=2),
    }
    jobs = make_jobs(args.seed, args.jobs, args.users)
    mine = {u: (user, cpus, mem) for u, user, cpus, mem, _p in jobs}
    fleet = {f"fleet-h{i}" for i in range(args.hosts)}
    checks: dict = {}
    out: dict = {"checks": checks}

    daemon = Daemon(conf, workdir)
    try:
        url = daemon.wait_url()
        admin = JobClient(url, user="admin", timeout_s=120.0)

        def leader_health():
            try:
                h = admin.debug_health()
            except (JobClientError, OSError):
                return None
            return h if h.get("leader") and h.get("device") else None

        t_boot = time.time()
        health = wait_for("leadership (store open + warm-up compile)",
                          leader_health, 900.0, daemon)
        dev = health["device"]
        log(f"daemon leads after {time.time() - t_boot:.1f}s on "
            f"{dev['platform']}/{dev['device_kind']} x{dev['count']}; "
            f"warm-up {dev.get('warmup_runs')} runs in "
            f"{dev.get('warmup_s')}s, cache {dev['compilation_cache_dir']}")
        out["device"] = dev
        checks["daemon_platform_is_probe_platform"] = (
            dev["platform"] == probe["platform"]
            and dev["device_kind"] == probe["kind"]
            and dev["count"] == probe["count"])
        checks["warmup_ran"] = (dev.get("warmup_runs") or 0) > 0

        # ---- load, over the socket
        for user in sorted({j[1] for j in jobs}):
            admin.set_share(user, {"default": user_share(user)},
                            reason="chip_smoke")
        by_user: dict = {}
        for u, user, cpus, mem, prio in jobs:
            by_user.setdefault(user, []).append(
                {"uuid": u, "command": "true", "name": "smoke",
                 "cpus": cpus, "mem": mem, "priority": prio,
                 "max_retries": 1})
        t_load = time.time()
        acked = []
        for user, specs in sorted(by_user.items()):
            client = JobClient(url, user=user, timeout_s=120.0)
            for i in range(0, len(specs), SUBMIT_BATCH):
                acked += client.submit(specs[i:i + SUBMIT_BATCH])
            client.close()
            daemon.check_alive()
        t_acked = time.time()
        out["load_s"] = round(t_acked - t_load, 1)
        log(f"{len(acked)} jobs ACKed in {out['load_s']}s")
        checks["every_job_acked"] = sorted(acked) == sorted(mine)

        # ---- >= 5 further fused cycles from the daemon's cycle thread,
        # then 3 more over which nothing may compile
        def post_load():
            return [c for c in admin.debug_cycles(limit=400)["cycles"]
                    if c["kind"] == "fused" and c["start"] >= t_acked]

        wait_for(">= 5 fused cycles after the load", lambda: len(
            post_load()) >= 5, 600.0, daemon)
        m_mid = parse_metrics(admin.metrics())
        n_mid = len(post_load())
        wait_for("3 more fused cycles", lambda: len(
            post_load()) >= n_mid + 3, 300.0, daemon)
        cycles = post_load()
        health = admin.debug_health()
        m_end = parse_metrics(admin.metrics())
        n_end_cycles = len(cycles)

        # ---- the checks
        paths = sorted({str(c["path"]) for c in cycles})
        out["path"] = paths[0] if len(paths) == 1 else paths
        out["cycles"] = n_end_cycles
        out["placed_per_cycle"] = [c["jobs_placed"] for c in cycles]
        out["placed"] = sum(out["placed_per_cycle"])
        # the scheduler's own per-cycle readings (host clock), so the
        # first attribution of a cycle's wall comes with the proof
        out["cycle_readings"] = [
            {k: c[k] for k in ("duration_ms", "detail_ms", "phases_ms",
                               "sync_wait_ms", "h2d_bytes", "d2h_bytes",
                               "delta_rows", "full_repacks",
                               "kernel_launches")} for c in cycles]
        checks["health_device_is_probe_platform"] = (
            (health.get("device") or {}).get("platform")
            == probe["platform"])
        # "auto" resolves to the fused XLA cycle
        checks["every_cycle_path_fused"] = paths == ["fused"]
        checks["no_cycle_faults"] = all(
            not c["faults"] and not c["error"] for c in cycles)
        checks["every_cycle_on_the_device"] = all(
            (c.get("device") or {}).get("platform") == probe["platform"]
            for c in cycles)
        checks["every_cycle_placed_jobs"] = all(
            c["jobs_placed"] > 0 for c in cycles)
        checks["every_cycle_launched_kernels"] = all(
            c["kernel_launches"] > 0 for c in cycles)
        out["fallback_total"] = metric_sum(m_end,
                                           "cook_kernel_fallback_total")
        checks["fallback_total_zero"] = out["fallback_total"] == 0
        checks["no_fault_repacks"] = metric_sum(
            m_end, "cook_resident_repack_total", reason="fault") == 0
        checks["warmup_span_recorded"] = metric_sum(
            m_end, "cook_span_duration_seconds_count",
            span="fused.warmup") >= 1
        out["jit_compiles_total"] = metric_sum(m_end,
                                               "cook_jit_compile_total")
        out["steady_recompiles"] = (
            out["jit_compiles_total"]
            - metric_sum(m_mid, "cook_jit_compile_total"))
        checks["steady_recompiles_zero"] = (
            out["steady_recompiles"] == 0
            and all(not c["recompiles"] for c in cycles[-3:]))
        checks["kernel_launches_grew"] = (
            metric_sum(m_end, "cook_kernel_launches_total")
            > metric_sum(m_mid, "cook_kernel_launches_total"))
        out["repacks"] = {labels.get("reason"): v for labels, v in
                          m_end.get("cook_resident_repack_total", [])}
        out["pipeline_conflicts"] = metric_sum(
            m_end, "cook_pipeline_conflicts_total")

        # ---- the client's view of the placements
        running = admin.running()
        per_job: dict = {}
        used: dict = {}
        for inst in running:
            per_job[inst["job_uuid"]] = per_job.get(inst["job_uuid"], 0) + 1
            _user, cpus, mem = mine[inst["job_uuid"]]
            u = used.setdefault(inst["hostname"], [0.0, 0.0])
            u[0] += cpus
            u[1] += mem
        out["running_instances"] = len(running)
        checks["instances_running"] = len(running) >= out["placed"] > 0
        checks["instances_on_fleet_hosts"] = set(used) <= fleet
        checks["no_job_with_two_live_instances"] = all(
            n == 1 for n in per_job.values())
        checks["no_host_over_capacity"] = all(
            c <= HOST_CPUS and m <= HOST_MEM for c, m in used.values())

        rng = np.random.default_rng(args.seed + 1)
        sample = [acked[i] for i in rng.choice(
            len(acked), size=min(SAMPLE_JOBS, len(acked)), replace=False)]
        # half the sample from the running set, so the read-back sees
        # instances and not only waiting jobs
        sample = sorted(set(sample[:len(sample) // 2])
                        | set(sorted(per_job)[:len(sample) // 2]))
        got = []
        for i in range(0, len(sample), 100):
            got += admin.query(sample[i:i + 100])
        checks["sample_reads_back"] = (
            sorted(j["uuid"] for j in got) == sample
            and all((j["user"], j["cpus"], j["mem"]) == mine[j["uuid"]]
                    for j in got))
        live = [j for j in got if j["state"] == "running"]
        checks["sampled_running_jobs_have_one_fleet_instance"] = bool(
            live) and all(
            [i["hostname"] in fleet for i in j["instances"]
             if i["status"] in ("unknown", "running")] == [True]
            for j in live)
        out["sample"] = {"jobs": len(got), "running": len(live)}
    finally:
        rc = daemon.stop()
        tail = daemon.tail(15)
    checks["daemon_exited_cleanly"] = rc == 0
    if rc != 0:
        log(f"daemon exit code {rc}:\n{tail}")
    return out


# -------------------------------------------------------------------- main
def result_line(ok: bool, probe: dict) -> str:
    """The last line of standard output: these keys and no others, the
    device as JAX reported it to the probe child."""
    return json.dumps({"ok": bool(ok), "device": {
        "platform": str(probe["platform"]), "kind": str(probe["kind"]),
        "count": int(probe["count"])}})


def run(args) -> int:
    if not os.path.isdir(os.path.join(HERE, "cook_tpu")):
        log(f"no cook_tpu package beside {os.path.abspath(__file__)}: "
            "this script drives the repository it ships in and is "
            "nothing alone")
        return 2
    debug = args.allow_cpu or (args.jobs, args.hosts, args.users) != (
        FULL_JOBS, FULL_HOSTS, FULL_USERS)
    try:
        probe = run_child("probe", args, 300.0)
    except SmokeFailure as e:
        log(f"JAX found no device: {e}")
        return 3
    log(f"platform={probe['platform']} device_kind={probe['kind']} "
        f"devices={probe['count']} versions={probe['versions']}")
    if probe["platform"] != "tpu" and not args.allow_cpu:
        log(f"JAX reports platform {probe['platform']!r}, not a TPU "
            "(JAX_PLATFORMS="
            f"{os.environ.get('JAX_PLATFORMS', '<unset>')}): refusing to "
            "run the load — a CPU run proves nothing about the chip")
        return 3

    result: dict = {
        "platform": probe["platform"], "device_kind": probe["kind"],
        "n_devices": probe["count"], "versions": probe["versions"],
        "seed": args.seed, "jobs": args.jobs, "hosts": args.hosts,
        "users": args.users,
    }
    failures = []
    try:
        result["native"] = native_report()
        log(f"native libraries: {result['native']}")
        cache_before = cache_entries()
        with tempfile.TemporaryDirectory(prefix="cook-smoke-") as workdir:
            d = drive_daemon(args, probe, workdir)
        checks = d.pop("checks")
        dev = d.pop("device")
        result.update(d)
        result["compilation_cache_dir"] = dev["compilation_cache_dir"]
        result["cache_entries"] = {"before": cache_before,
                                   "after_daemon": cache_entries()}
        result["warmup_cold_s"] = dev.get("warmup_s")

        par = run_child("parity", args, 900.0)
        result["warmup_cached_s"] = par["warmup_s"]
        result["parity"] = {k: par[k] for k in (
            "launched_set_agreement", "job_host_agreement",
            "launched_device", "launched_reference")}
        log(f"parity: {result['parity']}; warm-up cold "
            f"{result['warmup_cold_s']}s, cached "
            f"{result['warmup_cached_s']}s")
        checks["parity_on_the_device"] = (
            par["device"]["platform"] == probe["platform"]
            and par["path"] == "fused" and not par["faults"]
            and par["fallback_total"] == 0)
        checks["parity_launched_set"] = (
            par["launched_set_agreement"] >= PARITY_BAR
            and par["launched_device"] > 0)
        checks["parity_job_host"] = par["job_host_agreement"] >= PARITY_BAR
        if cache_before == 0:
            # the first child compiled everything; the second ran the
            # same warm-up grid and must have found it in the cache
            checks["warmup_hit_the_cache"] = (
                par["warmup_s"] < 0.5 * result["warmup_cold_s"])
        else:
            result["warmup_note"] = (
                f"{cache_before} cache entries existed before this run: "
                "the first warm-up was not cold")

        if probe["count"] >= 4:
            multi = run_child("multichip", args, 900.0)
            result["multichip"] = multi
            checks["multichip_four_distinct_devices"] = \
                multi["distinct_output_devices"] == 4
            checks["multichip_totals_equal_single_runs"] = (
                multi["totals_equal"] and multi["assignments_equal"]
                and multi["total_matched_4dev"] > 0)
        else:
            result["multichip"] = f"skipped ({probe['count']} devices)"
            print(f"multichip: skipped ({probe['count']} devices)",
                  flush=True)
        result["checks"] = checks
        failures = sorted(k for k, ok in checks.items() if not ok)
    except SmokeFailure as e:
        failures = [f"phase failed: {e}"]
    assert "jax" not in sys.modules, "the parent must never import JAX"
    result["failed"] = failures
    if debug:
        result["debug_run"] = ("--allow-cpu or a size flag was used: "
                               "never a pass")
    ok = not failures and not debug and probe["platform"] == "tpu"
    for f in failures:
        log(f"FAILED: {f}")
    print(json.dumps({"report": result}), flush=True)
    # the last line is the result and nothing else: the report is above
    print(result_line(ok, probe), flush=True)
    return 0 if ok else 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--allow-cpu", action="store_true",
                    help="debug this script without a chip; never passes")
    ap.add_argument("--jobs", type=int, default=FULL_JOBS,
                    help="debug size; anything but the default never passes")
    ap.add_argument("--hosts", type=int, default=FULL_HOSTS)
    ap.add_argument("--users", type=int, default=FULL_USERS)
    ap.add_argument("--phase", choices=("probe", "parity", "multichip"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.phase:
        # child mode: this process may own the chip
        sys.path.insert(0, HERE)
        out = {"probe": phase_probe, "parity": phase_parity,
               "multichip": phase_multichip}[args.phase](args)
        print(json.dumps(out), flush=True)
        return 0
    return run(args)


if __name__ == "__main__":
    sys.exit(main())
