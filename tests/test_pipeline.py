"""Pipelined optimistic match cycles (sched/pipeline.py): depth-0
sync-path preservation, conflict-injection reconciliation (no double
launch, queue stays consistent), boot-warmup zero-recompile steady state,
the deterministic pipelined-vs-sync parity harness — including the
chaos run with pipeline_depth=2 (zero duplicate live instances) — and
the cycle thread's tick with slack (a cycle staged a lead before the
deadline and applied at it)."""

import threading
import time

import numpy as np
import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config, PipelineConfig
from cook_tpu.sched import Scheduler
from cook_tpu.state import (
    InstanceStatus,
    Job,
    JobState,
    Pool,
    Resources,
    Store,
)


def build_world(n_jobs=10, n_hosts=4, depth=2, host_cpus=16.0,
                warmup=False, seed=5, considerable=None):
    rng = np.random.default_rng(seed)
    cfg = Config()
    cfg.pipeline.depth = depth
    if considerable is not None:
        cfg.default_matcher.max_jobs_considered = considerable
    if warmup:
        cfg.pipeline.warmup_tasks = 64
        cfg.pipeline.warmup_hosts = 64
        cfg.pipeline.warmup_users = 8
    store = Store()
    store.put_pool(Pool(name="default"))
    hosts = [FakeHost(hostname=f"h{i}",
                      capacity=Resources(cpus=host_cpus, mem=16384.0))
             for i in range(n_hosts)]
    cluster = FakeCluster("fake-1", hosts)
    sched = Scheduler(store, cfg, [cluster], rank_backend="tpu")
    jobs = [Job(uuid=f"00000000-0000-0000-0000-{i:012d}",
                user=f"user{i % 3}", command="true", pool="default",
                priority=int(rng.integers(0, 100)),
                resources=Resources(cpus=1.0, mem=128.0),
                submit_time_ms=1000 + i)
            for i in range(n_jobs)]
    store.create_jobs(jobs)
    return store, sched, cluster, jobs


def live_counts(store):
    out = {}
    for job, _inst in store.running_instances():
        out[job.uuid] = out.get(job.uuid, 0) + 1
    return out


class TestConfig:
    def test_boot_validation(self):
        assert PipelineConfig.from_conf({"depth": 0}).depth == 0
        assert PipelineConfig.from_conf({}).depth == 2  # issue default
        with pytest.raises(ValueError, match="unknown pipeline key"):
            PipelineConfig.from_conf({"detph": 2})
        with pytest.raises(ValueError, match="depth"):
            PipelineConfig.from_conf({"depth": -1})
        with pytest.raises(ValueError, match="boolean"):
            PipelineConfig.from_conf({"warmup_sweep": "true"})

    def test_daemon_section_routes_through_from_conf(self):
        from cook_tpu.daemon import build_scheduler_config
        cfg = build_scheduler_config({"pipeline": {"depth": 0}})
        assert cfg.pipeline.depth == 0
        with pytest.raises(ValueError):
            build_scheduler_config({"pipeline": {"depht": 3}})


class TestDepthZeroSyncPath:
    def test_depth0_is_sync_driver(self):
        _store, sched, _c, _jobs = build_world(depth=0)
        sched.step_cycle()
        assert sched._pipeline is None  # the wrapper is never constructed

    def test_depth0_and_depth2_same_decisions(self):
        """One seeded world per driver; the launched set after draining
        the queue must be identical (depth 2's first step already applies
        its first cycle, so a single-step world matches too)."""

        def run(depth):
            store, sched, _c, jobs = build_world(depth=depth)
            sched.step_cycle()
            return store, {j.uuid: (store.job(j.uuid).state.value,
                                    tuple(sorted(
                                        store.instance(t).hostname
                                        for t in store.job(j.uuid).instances
                                        if store.instance(t) is not None)))
                           for j in jobs}

        _s0, dec0 = run(0)
        _s2, dec2 = run(2)
        assert dec0 == dec2


class TestReconciliation:
    def test_candidate_killed_between_pack_and_apply(self):
        """A job killed while it sits in an in-flight optimistic dispatch
        is dropped by reconciliation: no instance, no crash, conflict
        counted, and the published queue no longer contains it."""
        # capacity 1 task/host and more jobs than slots: step 1 launches
        # some jobs and leaves the rest as live candidates of the
        # in-flight speculative cycle
        store, sched, _c, jobs = build_world(
            n_jobs=8, n_hosts=3, depth=2, host_cpus=1.0)
        sched.step_cycle()
        launched_1 = {u for u, n in live_counts(store).items()}
        waiting = [j for j in jobs if j.uuid not in launched_1]
        assert waiting, "need an unlaunched candidate to kill"
        victim = waiting[0]
        store.kill_job(victim.uuid)
        # free the hosts so the speculative cycle's surviving candidates
        # can launch (completion also advances the store tx watermark)
        for tid in [i.task_id for _j, i in store.running_instances()]:
            store.update_instance_status(tid, InstanceStatus.SUCCESS)
        sched.step_cycle()
        job = store.job(victim.uuid)
        assert job.state is not JobState.RUNNING
        assert not job.instances, "killed candidate must never launch"
        drv = sched._pipeline
        assert drv is not None
        # queue stays consistent: the victim is not in the published queue
        q = sched.pending_queues.get("default", [])
        qu = set(q.uuids) if hasattr(q, "uuids") else {j.uuid for j in q}
        assert victim.uuid not in qu

    def test_candidate_launched_by_overlapped_actor_not_double_launched(
            self):
        """A candidate the store already launched (another actor raced the
        in-flight dispatch) is conflict-dropped: exactly one instance
        ever exists."""
        store, sched, cluster, jobs = build_world(
            n_jobs=8, n_hosts=3, depth=2, host_cpus=1.0)
        sched.step_cycle()
        launched_1 = set(live_counts(store))
        waiting = [j for j in jobs if j.uuid not in launched_1]
        assert waiting
        victim = waiting[0]
        # the "overlapped cycle": a direct store launch behind the
        # pipeline's back
        store.launch_instance(victim.uuid, "race-task-1", hostname="h0",
                              compute_cluster="fake-1")
        sched.step_cycle()
        sched.step_cycle()
        job = store.job(victim.uuid)
        assert job.instances == ["race-task-1"], \
            "overlap-launched candidate must not double launch"
        assert max(live_counts(store).values(), default=0) <= 1

    def test_launch_rate_budget_not_doubled_by_overlap(self):
        """The per-user launch-rate budget must hold across overlapped
        cycles: the speculative cycle is staged before the applied
        cycle's spend() lands, so its staged token budget carries the
        in-flight spends as a delta (same budget as the sync driver)."""
        from cook_tpu.policy import RateLimits
        from cook_tpu.policy.rate_limit import TokenBucketRateLimiter

        def run(depth):
            rl = RateLimits(job_launch=TokenBucketRateLimiter(
                tokens_per_minute=0.0, bucket_size=2.0))
            cfg = Config()
            cfg.pipeline.depth = depth
            store = Store()
            store.put_pool(Pool(name="default"))
            hosts = [FakeHost(hostname=f"h{i}",
                              capacity=Resources(cpus=16.0, mem=16384.0))
                     for i in range(4)]
            sched = Scheduler(store, cfg, [FakeCluster("fake-1", hosts)],
                              rank_backend="tpu", rate_limits=rl)
            jobs = [Job(uuid=f"00000000-0000-0000-0001-{i:012d}",
                        user="one-user", command="true", pool="default",
                        resources=Resources(cpus=1.0, mem=64.0),
                        submit_time_ms=1000 + i)
                    for i in range(6)]
            store.create_jobs(jobs)
            launched = 0
            for _ in range(3):
                for r in sched.step_cycle().values():
                    launched += len(r.launched_task_ids)
            return launched

        assert run(0) == 2
        assert run(2) == 2, "overlap must not hand the user extra tokens"

    def test_quiet_store_zero_conflict_drops(self):
        """On a quiet store (no writers besides the driver) the
        speculation mask makes back-to-back cycles disjoint: zero
        reconciliation drops across a full drain."""
        store, sched, _c, jobs = build_world(n_jobs=12, n_hosts=4, depth=2)
        for _ in range(4):
            sched.step_cycle()
        drv = sched._pipeline
        assert drv is not None
        assert drv.conflicts_state == 0
        assert drv.conflicts_resources == 0
        assert max(live_counts(store).values(), default=0) <= 1
        # everything schedulable launched exactly once
        for j in jobs:
            assert len(store.job(j.uuid).instances) == 1


class TestWarmup:
    def test_zero_recompiles_after_boot_warmup(self):
        """Boot warmup at the world's bucket grid: N steady-state cycles
        (including the very first) trace/compile nothing."""
        from cook_tpu.utils.flight import recorder
        store, sched, _c, _jobs = build_world(
            n_jobs=10, n_hosts=4, depth=2, warmup=True)
        seq0 = recorder.last_seq()
        for _ in range(3):
            sched.step_cycle()
        flight = recorder.summary(since_seq=seq0)
        assert flight.get("recompiles", {}) == {}, \
            f"steady-state recompiles after warmup: {flight['recompiles']}"

    def test_warmup_counts_executions(self):
        _store, sched, _c, _jobs = build_world(warmup=True)
        # __init__ already warmed; an explicit call re-executes (cached)
        assert sched.warmup_kernels() == 1
        sched.config.pipeline.warmup_sweep = True
        assert sched.warmup_kernels() >= 1


def build_pools_world(n_pools=8, depth=2, warmup_tasks=0, backend="tpu",
                      jobs_of=lambda p: 20 + 7 * p, seed=11):
    """A seeded several-pool world whose arithmetic is exact in float32
    (sizes and shares are powers of two, so a DRU tie is a tie under any
    division): pools of UNEQUAL size, so the stacked [P, T] dispatch pads
    every pool but the largest; capacity binds in every pool (4 hosts x
    8 cpus against 40+ cpus of demand), so the order decides who runs."""
    rng = np.random.default_rng(seed)
    cfg = Config()
    cfg.pipeline.depth = depth
    cfg.default_matcher.max_jobs_considered = 16
    if backend == "cpu":
        cfg.default_matcher.backend = "cpu"
    if warmup_tasks:
        cfg.pipeline.warmup_tasks = warmup_tasks
        cfg.pipeline.warmup_hosts = 64
        cfg.pipeline.warmup_users = 8
    store = Store()
    pools = [f"pool{i}" for i in range(n_pools)]
    hosts, jobs = [], []
    for p, pool in enumerate(pools):
        store.put_pool(Pool(name=pool))
        for u in range(5):
            store.set_share(f"user{u}", pool,
                            {"cpus": 4.0 * (1 + (u == 0)),
                             "mem": 4096.0 * (1 + (u == 0))})
        hosts += [FakeHost(hostname=f"{pool}-h{i}", pool=pool,
                           capacity=Resources(cpus=8.0, mem=16384.0))
                  for i in range(4)]
        for _ in range(jobs_of(p)):
            i = len(jobs)
            cpus = float(rng.choice([1, 2, 4]))
            jobs.append(Job(
                uuid=f"00000000-0000-0000-0000-{i:012d}",
                user=f"user{int(rng.integers(0, 5))}", command="true",
                pool=pool, priority=int(rng.integers(0, 100)),
                resources=Resources(cpus=cpus,
                                    mem=cpus * float(rng.choice([512,
                                                                 1024]))),
                submit_time_ms=1000 + i))
    store.create_jobs(jobs)
    cluster = FakeCluster("fake-1", hosts)
    sched = Scheduler(store, cfg, [cluster], rank_backend=backend)
    return store, sched, pools, jobs


class TestStackedPools:
    """Eight pools stacked [8, T] in one dispatch (cook-8pool-50k's shape
    at a tiny size)."""

    def test_warmed_at_boot_the_first_cycles_compile_nothing(self):
        """The warm-up reads the stacking off the store: the first three
        cycles of an eight-pool daemon, WITH arrivals between them (so
        the base mirror's chunk append and the stacked delta scatter both
        run), trace and compile nothing."""
        from cook_tpu.utils.flight import recorder
        from cook_tpu.utils.metrics import registry
        # 300 rows a pool land in the 512 bucket the design point names;
        # 2,400 rows leave the 4,096-row mirror room for a 1,024 chunk
        store, sched, pools, jobs = build_pools_world(
            warmup_tasks=512, jobs_of=lambda p: 300)
        assert sched.device["warmup_runs"] == 1     # P = 8 only, no P = 1
        launches = lambda kernel: sum(
            v for labels, v in registry.series("cook_kernel_launches")
            if labels.get("kernel") == kernel)
        appends, scatters = launches("delta.append"), launches("delta.apply")
        seq0 = recorder.last_seq()
        n = len(jobs)
        for step in range(3):
            sched.step_cycle()
            store.create_jobs([
                Job(uuid=f"00000000-0000-0000-0001-{n + k:012d}",
                    user=f"user{k % 5}", command="true", pool=pool,
                    priority=50, resources=Resources(cpus=1.0, mem=512.0),
                    submit_time_ms=5000 + n + k)
                for k, pool in enumerate(pools * 3)])
            n += len(pools) * 3
        flight = recorder.summary(since_seq=seq0)
        assert flight.get("recompiles", {}) == {}, flight["recompiles"]
        recs = [r for r in recorder.recent(10)
                if r["seq"] > seq0 and r["kind"] == "fused"]
        assert [r["pools"] for r in recs] == [8, 8, 8]
        assert launches("delta.append") > appends
        assert launches("delta.apply") > scatters

    def test_stacked_decisions_are_the_reference_paths_pool_by_pool(self):
        """One fused depth-2 step over eight stacked pools of unequal
        size against the plain split path on the numpy reference
        (ops/reference_impl.py: rank_by_dru, greedy_match): the same
        launched set and the same job -> host map in every pool, exactly."""
        def run(backend):
            store, sched, pools, jobs = build_pools_world(backend=backend)
            if backend == "cpu":
                sched.step_rank()
                sched.step_match()
            else:
                sched.step_cycle()
            placed = {pool: {} for pool in pools}
            for j in jobs:
                for t in store.job(j.uuid).instances:
                    placed[j.pool][j.uuid] = store.instance(t).hostname
            return placed
        ref, got = run("cpu"), run("tpu")
        for pool in ref:
            assert 0 < len(ref[pool]) < 16 + 1, pool   # capacity binds
            assert set(got[pool]) == set(ref[pool]), pool
            assert got[pool] == ref[pool], pool
        # the order decided: some pool left considerable jobs unplaced
        assert any(len(ref[pool]) < 16 for pool in ref)


class Phases:
    """The four phase calls of ONE scheduler's fused driver in the order
    its threads made them — ("stage", n, masks), ("dispatch", n),
    ("fetch", n), ("apply", n), n the cycle's number by stage — with the
    instant each began on ``clock`` and, by cycle, the CycleRecord it
    was applied inside.  ``cost(phase, n)`` seconds pass on a virtual
    clock for each call."""

    def __init__(self, sched, clock=time, cost=None):
        sched._ensure_fused()
        fused = self.fused = sched._fused
        self.log, self.at, self.records = [], [], {}
        self.dispatched_at = {}
        self._cycle_of, self._keep = {}, []
        self.clock, self.cost = clock, cost
        stage, dispatch = fused.stage, fused.dispatch_group
        fetch, apply_ = fused.fetch_group, fused.apply_group

        def staged(scheduler, **kw):
            n = len(self._keep)
            self._note(("stage", n, sorted(k for k, v in kw.items() if v)))
            out = stage(scheduler, **kw)
            self._keep.append(out)
            for sg in out.groups:
                self._cycle_of[id(sg)] = n
            self._spend("stage", n)
            return out

        def dispatched(sg):
            n = self._cycle_of[id(sg)]
            self._note(("dispatch", n))
            self.dispatched_at[n] = clock.perf_counter()
            gd = dispatch(sg)
            if cost is not None:
                # on a virtual clock the device's time is the cost of
                # the fetch; the real outputs are there before any asks
                import jax
                jax.block_until_ready(gd.outs)
            return gd

        def fetched(gd):
            n = self._cycle_of[id(gd.sg)]
            self._note(("fetch", n))
            out = fetch(gd)
            self._spend("fetch", n)
            return out

        def applied(scheduler, gd, queues, results, **kw):
            from cook_tpu.utils.flight import recorder
            n = self._cycle_of[id(gd.sg)]
            self._note(("apply", n))
            self.records[n] = recorder.current()
            apply_(scheduler, gd, queues, results, **kw)
            self._spend("apply", n)

        fused.stage, fused.dispatch_group = staged, dispatched
        fused.fetch_group, fused.apply_group = fetched, applied

    def _note(self, event):
        self.log.append(event)
        self.at.append(self.clock.perf_counter())

    def _spend(self, phase, n):
        if self.cost is not None:
            self.clock.advance(self.cost(phase, n))

    def order(self):
        return [(e[0], e[1]) for e in self.log]

    def when(self, phase, n):
        return self.at[self.order().index((phase, n))]


def arrivals(tick, n=3):
    return [Job(uuid=f"00000000-0000-0000-{tick + 1:04d}-{i:012d}",
                user=f"user{i % 3}", command="true", pool="default",
                priority=50, resources=Resources(cpus=1.0, mem=128.0),
                submit_time_ms=5000 + 10 * tick + i)
            for i in range(n)]


def launched_by(results):
    return sorted((job.uuid, offer.hostname)
                  for r in results.values() for job, offer in r.matched
                  if job.uuid in set(r.launched_job_uuids))


class TestLateStage:
    """A tick with slack (``step_cycle(apply_at=...)``, Scheduler.run's
    own): ONE cycle staged with nothing in flight and applied at the
    deadline.  Everything read here is what this test's scheduler (or
    thread) wrote."""

    def churn_and_step(self, store, sched, cluster, tick, step):
        """Between two ticks: every running task ends, three jobs
        arrive; then the tick."""
        for tid in cluster.running_task_ids():
            cluster.complete_task(tid)
        sched.flush_status_updates()
        store.create_jobs(arrivals(tick))
        return launched_by(step())

    def test_with_slack_the_same_cycle_is_staged_unmasked_fetched_and_applied(
            self, monkeypatch):
        from cook_tpu.utils.metrics import registry
        me, modes, counter_inc = threading.get_ident(), [], \
            registry.counter_inc

        def noting(name, value=1.0, labels=None):
            if threading.get_ident() == me and name == "cook_cycle_stage":
                modes.append(labels["mode"])
            counter_inc(name, value, labels)
        monkeypatch.setattr(registry, "counter_inc", noting)
        # 8 slots, 40 pending and arrivals: every cycle has to choose
        late = build_world(n_jobs=40, n_hosts=4, host_cpus=2.0)
        sync = build_world(n_jobs=40, n_hosts=4, host_cpus=2.0, depth=0)
        phases = Phases(late[1])
        for tick in range(5):
            got = self.churn_and_step(
                *late[:3], tick,
                lambda: late[1].step_cycle(apply_at=time.perf_counter()))
            want = self.churn_and_step(*sync[:3], tick, sync[1].step_cycle)
            assert got == want and len(got) == 8, tick
        assert phases.log == [
            event for n in range(5) for event in
            (("stage", n, []), ("dispatch", n), ("fetch", n), ("apply", n))]
        assert modes == ["late"] * 5
        drv = late[1]._pipeline
        assert drv.inflight() == 0
        assert drv.conflicts_state == drv.conflicts_resources == 0
        for n in range(5):
            doc = phases.records[n].to_doc()
            assert doc["staged_late"] == 1 and doc["lead_ms"] > 0
            assert doc["lead_ms"] == doc["pipeline_lag_ms"]
            assert doc["pipeline_conflicts"] == 0
            assert doc["pipeline_inflight"] == 0
        assert max(live_counts(late[0]).values()) == 1

    def test_a_direct_caller_keeps_the_overlapped_order(self):
        # room for every cycle's four: no cycle comes back empty-handed
        store, sched, _c, _jobs = build_world(
            n_jobs=40, n_hosts=4, host_cpus=8.0, considerable=4)
        phases = Phases(sched)
        sched.step_cycle(apply_at=time.perf_counter())
        assert sched._pipeline.inflight() == 0
        sched.step_cycle()
        sched.step_cycle()
        assert phases.order() == [
            ("stage", 0), ("dispatch", 0), ("fetch", 0), ("apply", 0),
            # nothing in flight: stage, dispatch, fetch, then the next
            # cycle is left in flight behind the apply
            ("stage", 1), ("dispatch", 1), ("fetch", 1),
            ("stage", 2), ("dispatch", 2), ("apply", 1),
            ("fetch", 2), ("stage", 3), ("dispatch", 3), ("apply", 2)]
        assert sched._pipeline.inflight() == 1
        masks = {e[1]: e[2] for e in phases.log if e[0] == "stage"}
        assert masks[0] == masks[1] == []
        assert "exclude" in masks[2] and "exclude" in masks[3]
        for n in (1, 2):
            doc = phases.records[n].to_doc()
            assert doc["staged_late"] == 0 and "lead_ms" not in doc
            assert doc["pipeline_lag_ms"] > 0
        assert max(live_counts(store).values()) == 1

    def test_the_record_counts_the_lead_sleep_as_wait_not_as_cycle(self):
        from cook_tpu.utils.flight import recorder
        _store, sched, _c, _jobs = build_world(
            n_jobs=40, n_hosts=4, host_cpus=2.0)
        phases = Phases(sched)
        sched.step_cycle(apply_at=time.perf_counter())      # compiles
        slept = []

        class Stop:
            def wait(self, timeout):
                slept.append(timeout)
                time.sleep(0.3)
                return False
        sched._stop = Stop()
        # what the loop notes of the sleep BEFORE the record
        recorder.note_tick("wait_ms", 700.0)
        t0 = time.perf_counter()
        sched.step_cycle(apply_at=t0 + 0.3)
        wall_ms = (time.perf_counter() - t0) * 1000.0
        assert len(slept) == 1 and 0.0 < slept[0] <= 0.3
        rec = phases.records[1]
        assert rec.staged_late == 1 and rec.to_doc()["lead_ms"] >= 300.0
        # both sleeps, and only they
        assert 1000.0 <= rec.wait_ms <= 700.0 + wall_ms
        idle_ms = rec.wait_ms - 700.0
        assert rec.duration_ms <= wall_ms - idle_ms + 1.0
        assert rec.duration_ms + idle_ms >= wall_ms - 50.0
        # no part of the cycle holds the sleep
        detail = rec.detail_ms
        parts = sum(detail[k] for k in detail
                    if k in ("pools", "pack", "stage", "dispatch", "fetch",
                             "apply", "pipeline", "publish", "other"))
        assert parts == pytest.approx(rec.duration_ms)
        assert detail["other"] < 150.0
        assert rec.cpu_ms <= rec.duration_ms + 50.0
        assert rec.blocked_ms["gc"] + sum(rec.background_ms.values()) \
            <= rec.duration_ms

    def test_the_lead_is_what_was_observed_and_the_tick_reads_it(self):
        _store, sched, _c, _jobs = build_world(
            n_jobs=40, n_hosts=4, host_cpus=2.0)
        sched._ensure_fused()
        drv = sched._pipeline
        # nothing observed yet: no lead, the overlapped order
        assert drv.lead_s() is None and drv.stage_lead(10.0) is None
        sched.step_cycle()      # the first call's blocking fetch
        lead = drv.lead_s()
        assert lead is not None and lead > 0
        assert drv.inflight() == 1
        # slack, but a cycle in flight: applied at the deadline
        assert drv.stage_lead(lead + 1.0) == 0.0
        sched.step_cycle(apply_at=time.perf_counter())
        assert drv.inflight() == 0
        lead = drv.lead_s()
        assert drv.stage_lead(lead + 1.0) == lead
        # the last tick overran, or ended with less than a lead to spare
        assert drv.stage_lead(-0.5) is None
        assert drv.stage_lead(lead) is None
        # made of the last stage's wall and the last dispatch->ready seen
        drv._stage_s, drv._ready_s = 0.120, 0.045
        assert drv.lead_s() == pytest.approx((0.120 + 0.045) * 1.25)
        # only a fetch that finds its outputs not ready measures again
        from types import SimpleNamespace as NS
        sg = NS(group=[], T=0, H=0, gpu_mode=False)
        monkey = sched._fused.fetch_group
        sched._fused.fetch_group = lambda gd: None
        try:
            for ready, want in ((True, 0.045), (False, 0.5)):
                entry = NS(fetched=False, dispatched_at=time.perf_counter()
                           - 0.5, dispatches=[NS(
                               sg=sg, outs=[NS(is_ready=lambda: ready)])])
                drv._fetch(entry, footprint=False)
                assert drv._ready_s == pytest.approx(want, abs=0.05)
        finally:
            sched._fused.fetch_group = monkey


def virtual_loop(monkeypatch, apply_s, stop_at_wait=None):
    """A fused scheduler whose cycle thread runs ``Scheduler.run``'s loop
    on the virtual clock of tests/test_cycle_accounting.py at a 1 s
    interval: a stage takes 60 ms, the device 40 ms from the dispatch,
    cycle n's apply ``apply_s(n)``.  Every other loop's interval is an
    hour.  ``stop_at_wait``: the cycle thread's k-th wait (1-based) is
    the one a shutdown lands in."""
    from test_cycle_accounting import VirtualStop, VirtualTime

    from cook_tpu.sched import pipeline as pipeline_mod
    from cook_tpu.sched import scheduler as scheduler_mod
    store, sched, _cluster, _jobs = build_world(
        n_jobs=60, n_hosts=4, host_cpus=16.0, considerable=4)
    cfg = sched.config
    cfg.match_interval_seconds = 1.0
    cfg.lingering_task_interval_seconds = 3600.0
    cfg.monitor_interval_seconds = 3600.0
    cfg.elastic.resize_interval_seconds = 3600.0
    params = type("Params", (), {"interval_seconds": 3600.0})()
    sched.rebalancer.effective_params = lambda: params
    sched.step_cycle(apply_at=time.perf_counter())   # compile, real clock
    drv = sched._pipeline      # ... whose observations are not this clock's
    drv._stage_s = drv._ready_s = None
    clock = VirtualTime()
    monkeypatch.setattr(scheduler_mod, "time", clock)
    monkeypatch.setattr(pipeline_mod, "time", clock)

    class Stop(VirtualStop):
        waits = 0

        def wait(self, timeout=None):
            if timeout is not None and timeout < 3600.0:
                self.waits += 1
                if self.waits == stop_at_wait:
                    self.set()
            return super().wait(timeout)
    sched._stop = Stop(clock)
    phases = None

    def cost(phase, n):
        if phase == "stage":
            return 0.060
        if phase == "fetch":     # blocks until the device is done
            return max(0.0, phases.dispatched_at[n] + 0.040
                       - clock.perf_counter())
        return apply_s(n)
    phases = Phases(sched, clock=clock, cost=cost)
    return store, sched, phases


class TestLateStageUnderTheLoop:
    def run_until(self, sched, phases, last):
        """Until cycle ``last`` (by stage) has been applied."""
        done = threading.Event()
        spend = phases._spend

        def spend_and_stop(phase, n):
            spend(phase, n)
            if (phase, n) == ("apply", last):
                sched._stop.set()
                done.set()
        phases._spend = spend_and_stop
        sched.run()
        try:
            assert done.wait(60.0)
        finally:
            sched.shutdown()
        assert not any(t.is_alive() for t in sched._threads)

    def test_both_changes_of_order_cost_one_cycle_and_launch_nothing_twice(
            self, monkeypatch):
        # cycle 3's apply outlasts the interval
        store, sched, phases = virtual_loop(
            monkeypatch, lambda n: 1.5 if n == 3 else 0.2)
        self.run_until(sched, phases, last=6)
        assert phases.order() == [
            # tick 1: nothing observed yet, the overlapped order's first
            # call — one blocking fetch, which is the observation
            ("stage", 0), ("dispatch", 0), ("fetch", 0),
            ("stage", 1), ("dispatch", 1), ("apply", 0),
            # tick 2 has slack: the cycle in flight is applied at its
            # deadline, a period after it was staged, and not restaged
            ("fetch", 1), ("apply", 1),
            # ticks 3 and 4: staged a lead before the deadline
            ("stage", 2), ("dispatch", 2), ("fetch", 2), ("apply", 2),
            ("stage", 3), ("dispatch", 3), ("fetch", 3), ("apply", 3),
            # cycle 3 was applied for 1.5 s: tick 5 has no slack and
            # takes the overlapped order again, one unhidden kernel
            ("stage", 4), ("dispatch", 4), ("fetch", 4),
            ("stage", 5), ("dispatch", 5), ("apply", 4),
            ("fetch", 5), ("apply", 5),
            ("stage", 6), ("dispatch", 6), ("fetch", 6), ("apply", 6)]
        late = {n: phases.records[n].staged_late for n in range(7)}
        assert late == {0: 0, 1: 0, 2: 1, 3: 1, 4: 0, 5: 0, 6: 1}
        # also the records that only applied a cycle dispatched before
        assert {phases.records[n].path for n in range(7)} == {"fused"}
        when = phases.when
        assert when("stage", 0) == pytest.approx(1.0)
        assert when("stage", 1) == pytest.approx(1.0 + 0.060 + 0.040)
        assert when("fetch", 1) == pytest.approx(2.0)
        # late: staged a lead before the deadline, applied AT it
        lead = (0.060 + 0.040) * 1.25
        for n, due in ((2, 3.0), (3, 4.0), (6, 7.5)):
            assert when("stage", n) == pytest.approx(due - lead)
            assert when("fetch", n) == when("apply", n) \
                == pytest.approx(due)
            assert phases.records[n].to_doc()["lead_ms"] \
                == pytest.approx(lead * 1000)
        # the overrun re-anchors: tick 5 starts as cycle 3's apply ends
        assert when("stage", 4) == pytest.approx(5.5)
        assert when("fetch", 5) == pytest.approx(6.5)
        assert phases.records[5].pipeline_lag_ms \
            == pytest.approx((6.5 - when("stage", 5)) * 1000)
        assert "lead_ms" not in phases.records[5].to_doc()
        # masks only in the overlapped order, and no job launched twice
        masked = {e[1] for e in phases.log if e[0] == "stage" and e[2]}
        assert masked == {1, 5}
        counts = live_counts(store)
        assert max(counts.values()) == 1 and len(counts) == 4 * 8
        assert sched._pipeline.conflicts_state == 0

    @pytest.mark.parametrize("stop_at_wait, cycles", [(3, [0, 1]),
                                                      (4, [0, 1, 2])],
                             ids=["before-the-stage", "before-the-apply"])
    def test_a_shutdown_in_either_sleep_applies_nothing_twice(
            self, monkeypatch, stop_at_wait, cycles):
        # the cycle thread's waits: ticks 1 and 2 one each (the
        # overlapped order, then its cycle in flight), tick 3 two — to
        # the stage, then to the deadline
        store, sched, phases = virtual_loop(monkeypatch, lambda n: 0.2,
                                            stop_at_wait=stop_at_wait)
        sched.run()
        for t in sched._threads:
            t.join(timeout=60.0)
        sched.shutdown()
        assert not any(t.is_alive() for t in sched._threads)
        assert sched._stop.is_set()
        order = phases.order()
        assert [n for phase, n in order if phase == "stage"] == cycles
        assert [n for phase, n in order if phase == "apply"] == cycles
        assert sched._pipeline.inflight() == 0
        counts = live_counts(store)
        assert max(counts.values()) == 1 and len(counts) == 4 * len(cycles) + 4


class TestObservability:
    def test_cycle_record_carries_pipeline_fields(self):
        from cook_tpu.utils.flight import recorder
        _store, sched, _c, _jobs = build_world(depth=2)
        seq0 = recorder.last_seq()
        sched.step_cycle()
        recs = [r for r in recorder.recent(10) if r["seq"] > seq0]
        assert recs
        doc = recs[-1]
        assert doc["pipeline_depth"] == 2
        assert "pipeline_inflight" in doc
        assert "pipeline_conflicts" in doc

    def test_pipeline_metrics_exposed(self):
        from cook_tpu.utils.metrics import registry
        _store, sched, _c, _jobs = build_world(depth=2)
        sched.step_cycle()
        text = registry.expose()
        assert "cook_pipeline_depth 2.0" in text

    def test_depth0_gauge_reads_zero(self, monkeypatch):
        """A sync deployment must be distinguishable from a broken
        scrape: the depth gauge reads 0, it is not absent.  Read off
        what THIS thread wrote: the registry is the process's, and under
        the driver's workers a pipelined scheduler another test left
        running wrote 2.0 over it between the step and the scrape."""
        import threading

        from cook_tpu.utils.metrics import registry
        me, wrote, gauge_set = threading.get_ident(), [], registry.gauge_set

        def noting(name, value, labels=None):
            if threading.get_ident() == me and name == "cook_pipeline_depth":
                wrote.append(value)
            gauge_set(name, value, labels)
        monkeypatch.setattr(registry, "gauge_set", noting)
        _store, sched, _c, _jobs = build_world(depth=0)
        sched.step_cycle()
        assert wrote and set(wrote) == {0.0}
        assert "cook_pipeline_depth " in registry.expose()


class TestParityHarness:
    def test_seeded_parity_smoke(self):
        """Tier-1 smoke of the deterministic parity harness: same
        launched job set, all jobs complete, zero conflicts, no
        duplicate live instances."""
        from cook_tpu.sim.simulator import run_pipeline_parity
        result = run_pipeline_parity(seed=3, n_jobs=14, n_hosts=5,
                                     depth=2, span_ms=5000,
                                     duration_ms=1500)
        assert result["ok"], result
        assert result["pipelined_conflicts"] == 0
        assert result["duplicate_live"] == []

    @pytest.mark.slow
    def test_seeded_parity_full(self):
        from cook_tpu.sim.simulator import run_pipeline_parity
        for seed in (0, 1):
            result = run_pipeline_parity(seed=seed, n_jobs=60, n_hosts=10,
                                         depth=2)
            assert result["ok"], result


@pytest.mark.chaos
class TestPipelinedChaos:
    def test_chaos_no_duplicate_live_with_pipeline(self):
        """sim --chaos --pipeline-depth 2: the per-tick duplicate-live
        check holds under node loss + RPC faults + a leader kill landing
        inside the overlapped match->ack window."""
        from cook_tpu.sim.chaos import ChaosConfig, run_chaos
        cc = ChaosConfig(seed=7, n_jobs=14, n_hosts=6,
                         submit_span_ms=12_000, job_duration_ms=3_000,
                         node_loss_every_ms=6_000, node_loss_max=2,
                         rpc_fault_probability=0.1, rpc_fault_max=3,
                         leader_kill_at_ms=8_000, pipeline_depth=2)
        result = run_chaos(cc)
        assert result.ok, result.violations
        assert result.completed == result.total
