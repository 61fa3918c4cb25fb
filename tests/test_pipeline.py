"""Pipelined optimistic match cycles (sched/pipeline.py): depth-0
sync-path preservation, conflict-injection reconciliation (no double
launch, queue stays consistent), boot-warmup zero-recompile steady state,
and the deterministic pipelined-vs-sync parity harness — including the
chaos run with pipeline_depth=2 (zero duplicate live instances)."""

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
                warmup=False, seed=5):
    rng = np.random.default_rng(seed)
    cfg = Config()
    cfg.pipeline.depth = depth
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
