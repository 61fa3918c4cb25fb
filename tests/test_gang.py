"""Gang scheduling: all-or-nothing placement of multi-host slice jobs
(docs/GANG.md) — device/reference reduction parity, matcher + fused +
pipelined all-or-nothing, topology-contiguous packing, same-cycle refill
of freed capacity, atomic launch/lifecycle, whole-gang rebalancing, and
the autoscaler routing fix."""

import numpy as np
import pytest

from cook_tpu.cluster.fake import FakeCluster, FakeHost
from cook_tpu.config import Config
from cook_tpu.ops import reference_impl
from cook_tpu.ops.gang import apply_gang_cycle, build_gang_pack, gang_reduce_kernel
from cook_tpu.sched.scheduler import Scheduler
from cook_tpu.state.schema import (
    GANG_POLICY_KILL,
    Group,
    InstanceStatus,
    Job,
    JobState,
    Reasons,
    Resources,
)
from cook_tpu.state.store import Store

pytestmark = pytest.mark.gang


def make_system(n_hosts=3, cpus=4.0, mem=1024.0, slices=None,
                cycle_mode="split", pipeline_depth=0, backend="cpu"):
    cfg = Config()
    cfg.cycle_mode = cycle_mode
    cfg.pipeline.depth = pipeline_depth
    if backend == "cpu":
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
    store = Store()
    hosts = []
    for i in range(n_hosts):
        attrs = {}
        if slices is not None:
            attrs["slice-id"] = f"s{i // slices}"
        hosts.append(FakeHost(f"h{i}", Resources(cpus=cpus, mem=mem),
                              attributes=attrs))
    cluster = FakeCluster("fake", hosts)
    sched = Scheduler(store, cfg, [cluster], rank_backend=backend)
    return store, cluster, sched


def make_gang(store, guuid="g1", size=3, topology=None, policy=None,
              cpus=4.0, mem=1024.0, user="u", max_retries=5):
    group = Group(uuid=guuid, gang=True, gang_size=size,
                  gang_topology=topology, jobs=[])
    if policy:
        group.gang_policy = policy
    jobs = [Job(uuid=f"{guuid}-m{i}", user=user, command="x",
                max_retries=max_retries,
                resources=Resources(cpus=cpus, mem=mem), group=guuid)
            for i in range(size)]
    group.jobs = [j.uuid for j in jobs]
    store.create_jobs(jobs, groups=[group])
    return group, jobs


def step(sched):
    if sched.config.cycle_mode == "split":
        sched.step_rank()
        return sched.step_match()
    return sched.step_cycle()


# ---------------------------------------------------------------- kernel
class TestGangReduce:
    def test_device_matches_reference(self):
        rng = np.random.default_rng(0)
        for _ in range(20):
            J, G, H = 37, 5, 11
            assign = rng.integers(-1, H, J).astype(np.int32)
            gang_id = rng.integers(-1, G, J).astype(np.int32)
            gang_size = rng.integers(1, 6, G).astype(np.int32)
            gang_attr = rng.integers(0, 3, G).astype(np.int32)
            host_topo = rng.integers(-1, 3, (3, H)).astype(np.int32)
            ref = reference_impl.gang_reduce(
                assign, gang_id, gang_size, gang_attr, host_topo)

            class Pack:
                pass
            pack = Pack()
            pack.gang_id, pack.gang_size = gang_id, gang_size
            pack.gang_attr, pack.host_topo = gang_attr, host_topo
            dev = gang_reduce_kernel(assign, pack)
            np.testing.assert_array_equal(ref[0], dev[0])
            np.testing.assert_array_equal(ref[1], dev[1])

    def test_no_gang_is_structural_noop(self):
        class O:
            hostname = "h0"
            attributes = {}
        jobs = [Job(uuid="a", user="u", command="x")]
        assign = np.array([0], dtype=np.int32)
        out, stats = apply_gang_cycle(jobs, assign, [O()], {})
        assert stats is None
        assert out is assign  # not even copied

    def test_pack_none_without_gang_groups(self):
        g = Group(uuid="g", gang=False)
        jobs = [Job(uuid="a", user="u", command="x", group="g")]
        assert build_gang_pack(jobs, {"g": g}, []) is None


# --------------------------------------------------------------- matching
class TestAllOrNothing:
    # split + the production default (fused depth 2) cover the host and
    # device apply paths; fused depth 0 shares _apply_pool with depth 2
    @pytest.mark.parametrize("mode", ["split", "fused2"])
    def test_whole_gang_places_together(self, mode):
        kw = (dict() if mode == "split" else
              dict(cycle_mode="fused", backend="tpu",
                   pipeline_depth=0 if mode == "fused0" else 2))
        store, cluster, sched = make_system(n_hosts=3, **kw)
        make_gang(store, size=3)
        r = step(sched)["default"]
        assert sorted(r.launched_job_uuids) == ["g1-m0", "g1-m1", "g1-m2"]

    @pytest.mark.parametrize("mode", ["split", "fused2"])
    def test_partial_gang_never_launches(self, mode):
        kw = (dict() if mode == "split" else
              dict(cycle_mode="fused", backend="tpu",
                   pipeline_depth=0 if mode == "fused0" else 2))
        store, cluster, sched = make_system(n_hosts=2, **kw)
        make_gang(store, size=3)
        for _ in range(3):
            r = step(sched)["default"]
            assert r.launched_job_uuids == []
            # missing is exactly 1 on the sync paths; under pipelining
            # the speculative mask can withhold members entirely, so
            # only partial-ness (not the exact count) is stable
            assert r.gang_partial["g1"]["missing"] >= 1
        assert all(store.job(f"g1-m{i}").state is JobState.WAITING
                   for i in range(3))

    def test_freed_capacity_reused_same_cycle(self):
        store, cluster, sched = make_system(n_hosts=2)
        make_gang(store, size=3)  # 2 members match, then drop
        store.create_jobs([Job(uuid="solo", user="v", command="x",
                               resources=Resources(cpus=4, mem=1024))])
        r = step(sched)["default"]
        # the solo job takes capacity the partial gang freed, this cycle
        assert r.launched_job_uuids == ["solo"]

    def test_topology_contiguous_packing(self):
        # slice s0 has 2 hosts, s1 has 3: a topology gang of 3 must land
        # wholly in s1 even though s0's hosts are offered first
        store, cluster, sched = make_system(n_hosts=5, slices=None)
        for i, h in enumerate(cluster._hosts.values()):
            h.attributes["slice-id"] = "s0" if i < 2 else "s1"
        make_gang(store, size=3, topology="slice-id")
        r = step(sched)["default"]
        assert len(r.launched_job_uuids) == 3
        hosts = {store.instance(t).hostname for t in r.launched_task_ids}
        assert hosts == {"h2", "h3", "h4"}

    def test_domain_chosen_by_member_capacity_not_host_count(self):
        # s0: 3 hosts that each fit ONE member; s1: 2 wide hosts that
        # each fit TWO.  Only s1 holds the whole gang of 4 — an argmax
        # on feasible-host count would hard-pin the gang to s0 every
        # cycle and starve it despite the placeable slice next door.
        cfg = Config()
        cfg.cycle_mode = "split"
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        store = Store()
        hosts = [FakeHost(f"small{i}", Resources(cpus=16, mem=1024),
                          attributes={"slice-id": "s0"})
                 for i in range(3)]
        hosts += [FakeHost(f"wide{i}", Resources(cpus=32, mem=2048),
                           attributes={"slice-id": "s1"})
                  for i in range(2)]
        cluster = FakeCluster("fake", hosts)
        sched = Scheduler(store, cfg, [cluster], rank_backend="cpu")
        make_gang(store, size=4, topology="slice-id", cpus=16.0,
                  mem=512.0)
        r = step(sched)["default"]
        assert len(r.launched_job_uuids) == 4
        used = {store.instance(t).hostname for t in r.launched_task_ids}
        assert used == {"wide0", "wide1"}

    def test_heterogeneous_gang_sized_by_largest_member(self):
        # members differ: a 1-cpu member and a 16-cpu member.  Sizing
        # the domain by the FIRST member only would tie-break the gang
        # into the small slice (s0), where the big member never fits —
        # pinned there, the gang starves while s1 could hold it whole.
        cfg = Config()
        cfg.cycle_mode = "split"
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        store = Store()
        hosts = [FakeHost(f"small{i}", Resources(cpus=2, mem=1024),
                          attributes={"slice-id": "s0"})
                 for i in range(2)]
        hosts += [FakeHost(f"wide{i}", Resources(cpus=32, mem=1024),
                           attributes={"slice-id": "s1"})
                  for i in range(2)]
        cluster = FakeCluster("fake", hosts)
        sched = Scheduler(store, cfg, [cluster], rank_backend="cpu")
        group = Group(uuid="g1", gang=True, gang_size=2,
                      gang_topology="slice-id", jobs=["g1-m0", "g1-m1"])
        jobs = [Job(uuid="g1-m0", user="u", command="x", group="g1",
                    resources=Resources(cpus=1, mem=64)),
                Job(uuid="g1-m1", user="u", command="x", group="g1",
                    resources=Resources(cpus=16, mem=64))]
        store.create_jobs(jobs, groups=[group])
        r = step(sched)["default"]
        assert sorted(r.launched_job_uuids) == ["g1-m0", "g1-m1"]
        used = {store.instance(t).hostname for t in r.launched_task_ids}
        assert used <= {"wide0", "wide1"}

    def test_no_slice_fits_blocks_gang(self):
        # every slice is 2 hosts wide; a gang of 3 can never place
        store, cluster, sched = make_system(n_hosts=4, slices=2)
        make_gang(store, size=3, topology="slice-id")
        r = step(sched)["default"]
        assert r.launched_job_uuids == []
        assert "g1" in r.gang_partial

    def test_nongang_decisions_identical(self):
        # seeded non-gang worlds with and without the gang pass active
        # produce the same launched set (acceptance: decision parity)
        def run():
            store, cluster, sched = make_system(n_hosts=4)
            rng = np.random.default_rng(3)
            jobs = [Job(uuid=f"j{i}", user=f"u{i % 3}", command="x",
                        priority=int(rng.integers(0, 100)),
                        resources=Resources(cpus=float(rng.integers(1, 4)),
                                            mem=128.0))
                    for i in range(12)]
            store.create_jobs(jobs)
            r = step(sched)["default"]
            return sorted(r.launched_job_uuids)
        assert run() == run()


# ----------------------------------------------------------------- launch
class TestAtomicLaunch:
    def test_one_denied_member_denies_the_gang(self):
        store = Store()
        store.create_jobs(
            [Job(uuid=f"m{i}", user="u", command="x") for i in range(3)],
            groups=[Group(uuid="g", gang=True, gang_size=3,
                          jobs=["m0", "m1", "m2"])])
        store.kill_job("m1")  # no longer WAITING
        entries = [dict(job_uuid=f"m{i}", task_id=f"t{i}", hostname=f"h{i}",
                        gang="g") for i in range(3)]
        insts, failures = store.launch_instances(entries)
        assert insts == []
        assert len(failures) == 3
        reasons = {f[1] for f in failures}
        assert any(r.startswith("gang-member-denied") for r in reasons)
        # nothing live, no intents
        assert store.launch_intents() == []

    def test_gang_intents_tagged(self):
        store = Store()
        store.create_jobs(
            [Job(uuid=f"m{i}", user="u", command="x") for i in range(2)],
            groups=[Group(uuid="g", gang=True, gang_size=2,
                          jobs=["m0", "m1"])])
        entries = [dict(job_uuid=f"m{i}", task_id=f"t{i}", hostname="h",
                        gang="g") for i in range(2)]
        insts, failures = store.launch_instances(entries)
        assert len(insts) == 2 and not failures
        assert all(i.get("gang") == "g" for i in store.launch_intents())


# -------------------------------------------------------------- lifecycle
class TestGangLifecycle:
    def test_member_failure_requeues_whole_gang_free(self):
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3)
        r = step(sched)["default"]
        assert len(r.launched_task_ids) == 3
        assert sched._gang_barrier["g1"]["released"]
        cluster.fail_task(r.launched_task_ids[0], Reasons.NODE_LOST.code)
        sched.drain_side_effects()
        for i in range(3):
            j = store.job(f"g1-m{i}")
            assert j.state is JobState.WAITING
            insts = {t: store.instance(t) for t in j.instances}
            assert j.attempts_used(insts) == 0  # all mea-culpa
        # siblings carry gang-member-lost, and the barrier re-armed
        codes = {store.instance(t).reason_code
                 for i in range(3) for t in store.job(f"g1-m{i}").instances}
        assert Reasons.GANG_MEMBER_LOST.code in codes
        assert "g1" not in sched._gang_barrier
        # the whole gang relaunches (gang-member-lost hosts NOT excluded)
        r2 = step(sched)["default"]
        assert len(r2.launched_job_uuids) == 3

    def test_kill_policy_takes_gang_down(self):
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3, policy=GANG_POLICY_KILL)
        r = step(sched)["default"]
        cluster.fail_task(r.launched_task_ids[0], Reasons.NON_ZERO_EXIT.code)
        sched.drain_side_effects()
        assert all(store.job(f"g1-m{i}").state is JobState.COMPLETED
                   for i in range(3))

    def test_terminal_member_forces_gang_kill(self):
        # a member out of retries can never rejoin: requeue would strand
        # the siblings forever, so the gang completes instead
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3, max_retries=1)
        r = step(sched)["default"]
        cluster.fail_task(r.launched_task_ids[0], Reasons.NON_ZERO_EXIT.code)
        sched.drain_side_effects()
        assert all(store.job(f"g1-m{i}").state is JobState.COMPLETED
                   for i in range(3))

    def test_killing_a_waiting_member_takes_the_gang(self):
        # a member killed BEFORE placement emits no instance event (there
        # is no instance); the job-state hook must still take the
        # siblings down instead of leaving them gang-deferred forever
        store, cluster, sched = make_system(n_hosts=1, cpus=1.0)
        make_gang(store, size=3, cpus=4.0)  # cannot place on 1 tiny host
        store.kill_job("g1-m1")
        assert all(store.job(f"g1-m{i}").state is JobState.COMPLETED
                   for i in range(3))

    def test_staggered_success_does_not_kill_the_gang(self):
        # a member finishing SUCCESS while its siblings still run is a
        # normal staggered finish, not a gang break
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3)
        r = step(sched)["default"]
        assert len(r.launched_task_ids) == 3
        cluster.complete_task(r.launched_task_ids[0])
        sched.flush_status_updates()
        sched.drain_side_effects()
        states = [store.job(f"g1-m{i}").state for i in range(3)]
        assert states.count(JobState.COMPLETED) == 1
        live = [t for i in range(3)
                for t in store.job(f"g1-m{i}").instances
                if store.instance(t).status not in
                (InstanceStatus.SUCCESS, InstanceStatus.FAILED)]
        assert len(live) == 2

    def test_intent_sweep_rolls_back_whole_gang(self):
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3)
        # crash inside the launch dispatch: instances + intents committed,
        # backend never saw the tasks
        orig = FakeCluster.launch_tasks

        class Crash(BaseException):
            pass

        def crash(self, pool, specs):
            raise Crash()
        FakeCluster.launch_tasks = crash
        try:
            with pytest.raises(Crash):
                step(sched)
        finally:
            FakeCluster.launch_tasks = orig
        intents = store.launch_intents()
        assert len(intents) == 3
        assert all(i.get("gang") == "g1" for i in intents)
        # promotion: a new scheduler sweeps the intents — whole gang
        # refunded (cluster positively does not know the tasks)
        sched2 = Scheduler(store, sched.config, [cluster],
                           rank_backend="cpu")
        assert store.launch_intents() == []
        for i in range(3):
            j = store.job(f"g1-m{i}")
            assert j.state is JobState.WAITING
            insts = {t: store.instance(t) for t in j.instances}
            assert j.attempts_used(insts) == 0
        # and the gang relaunches whole on the new leader
        sched2.step_rank()
        r = sched2.step_match()["default"]
        assert len(r.launched_job_uuids) == 3


# ------------------------------------------------------------- rebalancer
class TestWholeGangPreemption:
    def test_preempting_a_member_takes_the_gang(self):
        store, cluster, sched = make_system(n_hosts=2, cpus=4.0)
        cfg = sched.config
        cfg.rebalancer.enabled = True
        cfg.rebalancer.safe_dru_threshold = 0.0
        cfg.rebalancer.min_dru_diff = 0.0
        cfg.rebalancer.max_preemption = 5
        store.set_share("default", "default", {"cpus": 1.0, "mem": 1.0})
        make_gang(store, size=2, cpus=4.0, user="hog")
        r = step(sched)["default"]
        assert len(r.launched_task_ids) == 2  # gang fills both hosts
        # a starved user's pending job (dru BELOW the gang's min member
        # dru — whole-gang pricing) preempts: the whole gang must go
        store.create_jobs([Job(uuid="p", user="starved", command="x",
                               resources=Resources(cpus=4, mem=512))])
        sched.step_rank()
        decisions = sched.step_rebalance()
        victims = [t for d in decisions.get("default", [])
                   for t in d.victim_task_ids]
        assert set(victims) == set(r.launched_task_ids)
        sched.drain_side_effects()
        live = [j.uuid for j, _i in store.running_instances()]
        assert "g1-m0" not in live and "g1-m1" not in live


# -------------------------------------------------------------- autoscale
class TestAutoscaleRouting:
    def make_k8s(self, name):
        from cook_tpu.cluster.k8s.compute_cluster import factory
        from cook_tpu.cluster.k8s.fake_api import FakeNode
        cluster = factory(name=name)
        cluster.api.add_node(FakeNode(name=f"{name}-n0", cpus=1.0,
                                      mem=128.0))
        return cluster

    def test_demand_routes_to_one_healthy_cluster(self):
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a, b = self.make_k8s("a"), self.make_k8s("b")
        sched = Scheduler(store, cfg, [a, b], rank_backend="cpu")
        store.create_jobs([Job(uuid="big", user="u", command="x",
                               resources=Resources(cpus=64, mem=2048))])
        sched.step_rank()
        sched.step_match()
        synth_a = [p for p in a.api.pods() if p.synthetic]
        synth_b = [p for p in b.api.pods() if p.synthetic]
        # exactly ONE cluster synthesizes the demand (no double
        # provisioning), deterministically the first registered
        assert len(synth_a) == 1 and len(synth_b) == 0

    def test_breaker_open_reroutes_demand(self):
        from cook_tpu.utils.retry import breakers
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a, b = self.make_k8s("a"), self.make_k8s("b")
        sched = Scheduler(store, cfg, [a, b], rank_backend="cpu")
        br = breakers.get("a")
        for _ in range(br.failure_threshold):
            br.record_failure()
        try:
            store.create_jobs([Job(uuid="big", user="u", command="x",
                                   resources=Resources(cpus=64,
                                                       mem=2048))])
            sched.step_rank()
            sched.step_match()
            assert [p for p in a.api.pods() if p.synthetic] == []
            assert len([p for p in b.api.pods() if p.synthetic]) == 1
        finally:
            breakers.reset()

    def test_capped_cluster_falls_through_to_next_scaler(self):
        # the first healthy cluster is at its pod cap: autoscale()
        # creates nothing WITHOUT raising (breaker never opens), so the
        # demand must fall through to the next scaler with room
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a, b = self.make_k8s("a"), self.make_k8s("b")
        a.max_total_pods = 0
        sched = Scheduler(store, cfg, [a, b], rank_backend="cpu")
        store.create_jobs([Job(uuid="big", user="u", command="x",
                               resources=Resources(cpus=64, mem=2048))])
        sched.step_rank()
        sched.step_match()
        assert [p for p in a.api.pods() if p.synthetic] == []
        assert len([p for p in b.api.pods() if p.synthetic]) == 1

    def test_provisioned_cluster_keeps_ownership(self):
        # a second cycle with the same unmatched demand creates nothing
        # (placeholders already stand) — that must NOT read as "capped"
        # and fan the demand out to the next cluster
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a, b = self.make_k8s("a"), self.make_k8s("b")
        sched = Scheduler(store, cfg, [a, b], rank_backend="cpu")
        store.create_jobs([Job(uuid="big", user="u", command="x",
                               resources=Resources(cpus=64, mem=2048))])
        for _ in range(2):
            sched.step_rank()
            sched.step_match()
        assert len([p for p in a.api.pods() if p.synthetic]) == 1
        assert [p for p in b.api.pods() if p.synthetic] == []

    def test_partially_covered_gang_is_not_split_across_scalers(self):
        # cluster a holds placeholders for only PART of a gang (one was
        # reaped) while sitting at its pod budget: the gang must stay
        # routed to a whole — forwarding just the uncovered members
        # would have b synthesize a partial gang pod set, the exact
        # split-slice signal the all-or-none set exists to prevent
        from cook_tpu.cluster.k8s.compute_cluster import SYNTHETIC_PREFIX
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a, b = self.make_k8s("a"), self.make_k8s("b")
        sched = Scheduler(store, cfg, [a, b], rank_backend="cpu")
        make_gang(store, size=3, cpus=8.0)
        sched.step_rank()
        sched.step_match()
        assert len([p for p in a.api.pods() if p.synthetic]) == 3
        a.api.delete_pod(f"{SYNTHETIC_PREFIX}g1-m2")
        a.max_total_pods = 2  # at budget: autoscale() creates nothing
        sched.step_rank()
        sched.step_match()
        assert [p for p in b.api.pods() if p.synthetic] == []

    def test_gang_demand_is_a_colocated_pod_set(self):
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        cfg.autoscaling_enabled = True
        store = Store()
        a = self.make_k8s("a")
        sched = Scheduler(store, cfg, [a], rank_backend="cpu")
        make_gang(store, size=3, topology="slice-id", cpus=8.0)
        sched.step_rank()
        sched.step_match()
        synth = [p for p in a.api.pods() if p.synthetic]
        assert len(synth) == 3  # the whole slice, not a lone pod
        assert all(p.labels.get("cook/gang") == "g1" for p in synth)
        assert all(p.annotations.get("cook/gang-size") == "3"
                   for p in synth)
        assert all(p.annotations.get("cook/gang-affinity") == "slice-id"
                   for p in synth)


# -------------------------------------------------------------- explainer
class TestGangExplainer:
    def test_waiting_on_members_reason(self):
        from cook_tpu.sched.unscheduled import job_reasons
        store, cluster, sched = make_system(n_hosts=2)
        make_gang(store, size=3)
        step(sched)
        reasons = job_reasons(store, store.job("g1-m0"), scheduler=sched)
        texts = " ".join(r["reason"] for r in reasons)
        assert "Waiting on 1 of 3 gang members" in texts

    def test_topology_blocked_reason(self):
        from cook_tpu.sched.unscheduled import job_reasons
        store, cluster, sched = make_system(n_hosts=4, slices=2)
        make_gang(store, size=3, topology="slice-id")
        step(sched)
        reasons = job_reasons(store, store.job("g1-m0"), scheduler=sched)
        texts = " ".join(r["reason"] for r in reasons)
        assert "gang" in texts.lower()

    def test_admission_deferred_gang_has_a_reason(self):
        # a gang throttled at ADMISSION never reaches the match pass, so
        # it has no gang_partial entry — the explainer must still say why
        from cook_tpu.policy import RateLimits, TokenBucketRateLimiter
        from cook_tpu.sched.unscheduled import job_reasons
        store = Store()
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        rl = RateLimits()
        rl.job_launch = TokenBucketRateLimiter(
            tokens_per_minute=0.0, bucket_size=2.0, enforce=True)
        cluster = FakeCluster("fake", [
            FakeHost(f"h{i}", Resources(cpus=4, mem=1024))
            for i in range(3)])
        sched = Scheduler(store, cfg, [cluster], rank_backend="cpu",
                          rate_limits=rl)
        make_gang(store, size=3)  # bucket of 2 can never cover 3
        sched.step_rank()
        sched.step_match()
        reasons = job_reasons(store, store.job("g1-m0"), scheduler=sched)
        texts = " ".join(r["reason"] for r in reasons)
        assert "launch-rate tokens" in texts

    def test_topology_census_counts_member_slots_not_hosts(self):
        # a slice of 2 wide hosts that each fit 2 members HOLDS a gang
        # of 3 (the matcher packs members per host), so its hosts must
        # not be counted under gang_topology_constraint
        from cook_tpu.cluster.base import Offer
        from cook_tpu.sched.constraints import (
            ConstraintContext,
            explain_placement_failure,
        )
        group = Group(uuid="g1", gang=True, gang_size=3,
                      gang_topology="slice-id", jobs=["g1-m0"])
        job = Job(uuid="g1-m0", user="u", command="x", group="g1",
                  resources=Resources(cpus=4, mem=256))
        offers = [Offer(id=f"o{i}", hostname=f"h{i}", slave_id=f"h{i}",
                        pool="default",
                        available=Resources(cpus=8, mem=1024),
                        capacity=Resources(cpus=8, mem=1024),
                        attributes={"slice-id": "s0"})
                  for i in range(2)]
        ctx = ConstraintContext(groups={"g1": group})
        census = explain_placement_failure(job, offers, ctx)
        assert census["constraints"].get("gang_topology_constraint",
                                         0) == 0

    def test_gang_topology_census_persisted(self):
        from cook_tpu.sched.unscheduled import job_reasons
        store, cluster, sched = make_system(n_hosts=4, slices=2)
        make_gang(store, size=3, topology="slice-id")
        step(sched)
        # two-step under-investigation workflow: ask, match, ask again
        job_reasons(store, store.job("g1-m0"), scheduler=sched)
        assert store.job("g1-m0").under_investigation
        step(sched)
        failure = store.job("g1-m0").last_placement_failure
        assert failure is not None
        assert "gang_topology_constraint" in failure.get("constraints", {})


# ----------------------------------------------------- pipelined semantics
class TestPipelinedGroupSemantics:
    def test_unique_group_holds_under_depth2(self):
        # within-batch UNIQUE placement was only exercised on the sync
        # paths; assert it through the pipelined driver end to end
        store, cluster, sched = make_system(
            n_hosts=3, cpus=8.0, cycle_mode="fused", backend="tpu",
            pipeline_depth=2)
        group = Group(uuid="ug", jobs=[f"u{i}" for i in range(3)])
        from cook_tpu.state.schema import GroupPlacementType
        group.placement_type = GroupPlacementType.UNIQUE
        jobs = [Job(uuid=f"u{i}", user="u", command="x",
                    resources=Resources(cpus=2, mem=128), group="ug")
                for i in range(3)]
        store.create_jobs(jobs, groups=[group])
        launched = {}
        for _ in range(4):
            r = sched.step_cycle().get("default")
            if r is not None:
                for t in r.launched_task_ids:
                    inst = store.instance(t)
                    launched[inst.job_uuid] = inst.hostname
        assert len(launched) == 3
        assert len(set(launched.values())) == 3  # one host per cotask

    def test_inflight_gang_is_not_reported_member_denied(self):
        # the speculative footprint clears an in-flight gang's launch_ok
        # bits; the next pack's cohort admission must not misread that
        # as a filter/quota denial — the gang is mid-launch, and the
        # explainer would tell the operator it is blocked
        store, cluster, sched = make_system(
            n_hosts=3, cycle_mode="fused", backend="tpu",
            pipeline_depth=2)
        make_gang(store, size=3)
        for _ in range(2):
            sched.step_cycle()
        deferred = sched.matcher.last_admission_deferred.get("default", {})
        assert deferred.get("g1", {}).get("reason") != "member-denied", \
            deferred
        # and the gang did actually launch whole
        live = {j.uuid for j, _i in store.running_instances()}
        assert live == {"g1-m0", "g1-m1", "g1-m2"}

    def test_gang_conflict_drops_atomically_under_depth2(self):
        # a member killed between stage and apply conflicts at reconcile;
        # the remaining members must NOT launch partial
        store, cluster, sched = make_system(
            n_hosts=3, cycle_mode="fused", backend="tpu",
            pipeline_depth=2)
        make_gang(store, size=3)
        # stage+dispatch happens inside step; kill a member between
        # steps so the in-flight speculative cycle holds a stale gang
        sched.step_cycle()  # launches the gang
        r0 = sched.last_match_results["default"]
        assert len(r0.launched_job_uuids) == 3
        # complete the gang so it goes terminal, then submit a new gang
        for t in list(r0.launched_task_ids):
            cluster.complete_task(t)
        make_gang(store, guuid="g2", size=3)
        sched.step_cycle()
        store.kill_job("g2-m1")
        sched.drain_side_effects()
        for _ in range(3):
            sched.step_cycle()
        # m1 killed: the gang can never be whole; no member may run
        live = [j.uuid for j, _i in store.running_instances()]
        assert not any(u.startswith("g2-") for u in live)


class TestGangRescue:
    def test_constrained_member_last_is_rescued(self):
        # an unconstrained sibling ranked ahead of a constrained member
        # would greedily take the member's only feasible host; the
        # rescue pass re-packs the cohort most-constrained first
        class O:
            def __init__(self, hn):
                self.hostname = hn
                self.attributes = {}
        g = Group(uuid="g", gang=True, gang_size=3,
                  jobs=["a", "b", "c"])
        jobs = [Job(uuid=u, user="u", command="x",
                    resources=Resources(cpus=1, mem=1), group="g")
                for u in ("a", "b", "c")]
        # kernel outcome: a->h0, b->h1, c unmatched (its only host h0
        # was taken by a)
        assign = np.array([0, 1, -1], dtype=np.int32)
        cmask = np.ones((3, 3), dtype=bool)
        cmask[2] = [True, False, False]  # c: only h0
        avail = np.full((3, 4), 4.0, dtype=np.float32)
        out, stats = apply_gang_cycle(
            jobs, assign, [O(f"h{i}") for i in range(3)], {"g": g},
            job_res=np.ones((3, 4), dtype=np.float32),
            cmask_fn=lambda: cmask, avail=avail, capacity=avail)
        assert (out >= 0).all(), out
        assert out[2] == 0  # c got its only host; siblings moved over
        assert stats.partial == {}

    def test_rescue_never_violates_host_placement(self):
        # a group declaring BOTH gang and unique host-placement: the
        # rescue re-pack honors only resources + cmask, so it must not
        # run for such groups — it would happily stack two members back
        # onto the host validate_group_placement just split them off
        from cook_tpu.state.schema import GroupPlacementType

        class O:
            def __init__(self, hn):
                self.hostname = hn
                self.attributes = {}
        g = Group(uuid="g", gang=True, gang_size=2, jobs=["a", "b"])
        g.placement_type = GroupPlacementType.UNIQUE
        jobs = [Job(uuid=u, user="u", command="x",
                    resources=Resources(cpus=1, mem=1), group="g")
                for u in ("a", "b")]
        # post-validator state: b was reset to -1 (duplicate host with
        # a); only h0 has capacity, so any re-pack would co-locate
        assign = np.array([0, -1], dtype=np.int32)
        cmask = np.array([[True, False], [True, False]])
        avail = np.array([[4.0] * 4, [0.0] * 4], dtype=np.float32)
        out, stats = apply_gang_cycle(
            jobs, assign, [O("h0"), O("h1")], {"g": g},
            job_res=np.ones((2, 4), dtype=np.float32),
            cmask_fn=lambda: cmask, avail=avail,
            capacity=np.full((2, 4), 4.0, dtype=np.float32))
        assert (out == -1).all(), out  # dropped whole, NOT co-located
        assert "g" in stats.partial

    def test_requeued_gang_relaunches_when_failed_member_ranks_last(self):
        # rank tie-break is by uuid, so failing m2's instance makes the
        # novel-host-constrained member rank LAST among its siblings —
        # the exact starvation shape the rescue pass exists for
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3)
        sched.step_rank()
        r = sched.step_match()["default"]
        tid_m2 = next(t for t in r.launched_task_ids
                      if store.instance(t).job_uuid == "g1-m2")
        cluster.fail_task(tid_m2, Reasons.NODE_LOST.code)
        sched.drain_side_effects()
        sched.step_rank()
        r2 = sched.step_match()["default"]
        assert len(r2.launched_job_uuids) == 3, r2.gang_partial


class TestCohortAdmission:
    def test_rate_limited_gang_defers_whole_not_partial(self):
        from cook_tpu.policy import RateLimits, TokenBucketRateLimiter
        store = Store()
        cfg = Config()
        cfg.default_matcher.backend = "cpu"
        cfg.columnar_index = False
        rl = RateLimits()
        # 2 tokens/cycle, bucket of 4: a gang of 3 must wait for tokens,
        # never admit 2 members and burn them on the reduction
        rl.job_launch = TokenBucketRateLimiter(
            tokens_per_minute=0.0, bucket_size=4.0, enforce=True)
        cluster = FakeCluster("fake", [
            FakeHost(f"h{i}", Resources(cpus=4, mem=1024))
            for i in range(3)])
        sched = Scheduler(store, cfg, [cluster], rank_backend="cpu",
                          rate_limits=rl)
        make_gang(store, size=3)
        # drain the user's bucket to 2 tokens
        from cook_tpu.policy import pool_user_key
        rl.job_launch.spend(pool_user_key("default", "u"), 2.0)
        sched.step_rank()
        r = sched.step_match()["default"]
        # whole cohort deferred: nothing considered from the gang, and
        # crucially nothing HALF-admitted
        assert r.launched_job_uuids == []
        assert r.considered == 0

    def test_fused_path_defers_rate_limited_gang_whole(self):
        # the device admits rows in rank order until tokens run out —
        # without host-side cohort admission the production fused path
        # would admit 2 of 3 members every cycle and burn them on the
        # reduction forever, explained as a capacity problem
        from cook_tpu.policy import (
            RateLimits,
            TokenBucketRateLimiter,
            pool_user_key,
        )
        store = Store()
        cfg = Config()
        cfg.cycle_mode = "fused"
        cfg.pipeline.depth = 0
        rl = RateLimits()
        rl.job_launch = TokenBucketRateLimiter(
            tokens_per_minute=0.0, bucket_size=4.0, enforce=True)
        cluster = FakeCluster("fake", [
            FakeHost(f"h{i}", Resources(cpus=4, mem=1024))
            for i in range(3)])
        sched = Scheduler(store, cfg, [cluster], rank_backend="tpu",
                          rate_limits=rl)
        make_gang(store, size=3)
        rl.job_launch.spend(pool_user_key("default", "u"), 2.0)
        r = None
        for _ in range(2):
            r = sched.step_cycle()["default"]
        assert r.launched_job_uuids == []
        assert r.gang_partial == {}  # withheld whole, never burned
        why = sched.matcher.last_admission_deferred["default"]
        assert why["g1"]["reason"] == "rate-limited"

    def test_considerable_cap_never_splits_a_gang(self):
        store, cluster, sched = make_system(n_hosts=6, cpus=8.0)
        mc = sched.config.default_matcher
        mc.max_jobs_considered = 2  # smaller than the gang
        make_gang(store, size=3, cpus=1.0, mem=64.0)
        store.create_jobs([Job(uuid="s1", user="v", command="x",
                               resources=Resources(cpus=1, mem=64))])
        sched.step_rank()
        r = sched.step_match()["default"]
        # the gang (3 > cap 2) defers whole; the single still launches
        assert r.launched_job_uuids == ["s1"]

    def test_gang_exactly_filling_cap_is_admitted(self):
        # 1 single + gang of 3 against limit 4: the cap check must not
        # re-charge the whole cohort for every member (that deferred an
        # exactly-fitting gang forever while singles refilled the cap)
        store, cluster, sched = make_system(n_hosts=6, cpus=8.0)
        _, gjobs = make_gang(store, size=3, cpus=1.0, mem=64.0)
        store.create_jobs([Job(uuid="s1", user="v", command="x",
                               resources=Resources(cpus=1, mem=64))])
        ranked = [store.job("s1")] + [store.job(j.uuid) for j in gjobs]
        out = sched.matcher.considerable_jobs("default", ranked, 4)
        assert [j.uuid for j in out] == ["s1", "g1-m0", "g1-m1", "g1-m2"]

    def test_singles_cannot_eat_a_reserved_gang_slot(self):
        # gang of 3 ranked first against limit 3: same-rank singles
        # between its members must not consume the slots the cohort
        # reserved (which would strip the gang post-admission)
        store, cluster, sched = make_system(n_hosts=6, cpus=8.0)
        _, gjobs = make_gang(store, size=3, cpus=1.0, mem=64.0)
        store.create_jobs([Job(uuid="s1", user="v", command="x",
                               resources=Resources(cpus=1, mem=64))])
        ranked = [store.job("g1-m0"), store.job("s1"),
                  store.job("g1-m1"), store.job("g1-m2")]
        out = sched.matcher.considerable_jobs("default", ranked, 3)
        assert [j.uuid for j in out] == ["g1-m0", "g1-m1", "g1-m2"]

    def test_sunk_cohort_returns_rate_tokens_to_singles(self):
        # a launch filter denying one member sinks the whole cohort AND
        # returns its token reservation: the same user's single ranked
        # later must still pass instead of reading "rate-limited"
        from cook_tpu.policy import RateLimits, TokenBucketRateLimiter
        from cook_tpu.policy.plugins import PluginResult

        class RejectM2:
            def check(self, job):
                return (PluginResult.rejected("nope")
                        if job.uuid == "g1-m2" else PluginResult.accepted())

        store, cluster, sched = make_system(n_hosts=6, cpus=8.0)
        rl = RateLimits()
        rl.job_launch = TokenBucketRateLimiter(
            tokens_per_minute=0.0, bucket_size=3.0, enforce=True)
        sched.matcher.rate_limits = rl
        sched.matcher.plugins.launch_filters.append(RejectM2())
        _, gjobs = make_gang(store, size=3, cpus=1.0, mem=64.0)
        store.create_jobs([Job(uuid="s1", user="u", command="x",
                               resources=Resources(cpus=1, mem=64))])
        ranked = [store.job(j.uuid) for j in gjobs] + [store.job("s1")]
        out = sched.matcher.considerable_jobs("default", ranked, 10)
        assert [j.uuid for j in out] == ["s1"]

    def test_gang_with_member_missing_from_queue_defers_whole(self):
        # a cohort that cannot fully admit (a member is not even in the
        # ranked queue) defers outright without stranding cap slots
        store, cluster, sched = make_system(n_hosts=6, cpus=8.0)
        _, gjobs = make_gang(store, size=3, cpus=1.0, mem=64.0)
        store.create_jobs([Job(uuid="s1", user="v", command="x",
                               resources=Resources(cpus=1, mem=64))])
        ranked = [store.job("g1-m0"), store.job("g1-m1"),
                  store.job("s1")]  # m2 absent
        out = sched.matcher.considerable_jobs("default", ranked, 3)
        assert [j.uuid for j in out] == ["s1"]

    def test_concurrent_gangs_spread_across_slices(self):
        # two 3-wide slices, two topology gangs of 3: without per-batch
        # slice claims both would be steered to the same slice and
        # deadlock; with them, both launch — one per slice
        store, cluster, sched = make_system(n_hosts=6, slices=3)
        make_gang(store, guuid="ga", size=3, topology="slice-id",
                  user="ua")
        make_gang(store, guuid="gb", size=3, topology="slice-id",
                  user="ub")
        launched = set()
        for _ in range(2):
            r = step(sched)["default"]
            launched.update(r.launched_job_uuids)
        assert len(launched) == 6
        by_gang_slice = {}
        for u in launched:
            inst = store.instance(store.job(u).instances[-1])
            slice_id = cluster._hosts[inst.hostname].attributes["slice-id"]
            by_gang_slice.setdefault(u.split("-m")[0], set()).add(slice_id)
        assert all(len(s) == 1 for s in by_gang_slice.values())
        assert by_gang_slice["ga"] != by_gang_slice["gb"]


class TestGangStatus:
    def test_barrier_sticky_after_completion(self):
        from cook_tpu.rest.api import gang_status
        store, cluster, sched = make_system(n_hosts=3)
        group, _jobs = make_gang(store, size=3)
        r = step(sched)["default"]
        assert gang_status(store, store.group("g1"))["barrier"] \
            == "released"
        for t in r.launched_task_ids:
            cluster.complete_task(t)
        st = gang_status(store, store.group("g1"))
        # a finished gang must not read as one that never placed
        assert st["barrier"] == "released"
        assert st["members_running"] == 0

    def test_early_finisher_does_not_block_barrier(self):
        # a short member can exit SUCCESS before the last member comes
        # up: "started" (running now, or completed after a run) must
        # release the barrier — requiring every member simultaneously
        # RUNNING would leave it pending for the survivor's whole run
        from cook_tpu.rest.api import gang_status
        store, cluster, sched = make_system(n_hosts=2)
        make_gang(store, size=2)
        held = []
        # the launch's acknowledgements arrive as one batch: hold m1's
        orig = FakeCluster._emit_statuses

        def hold_m1_running(self, updates):
            passed = []
            for u in updates:
                inst = store.instance(u[0])
                if inst is not None and inst.job_uuid == "g1-m1" \
                        and u[1] is InstanceStatus.RUNNING:
                    held.append(u)
                else:
                    passed.append(u)
            orig(self, passed)

        cluster._emit_statuses = hold_m1_running.__get__(cluster)
        try:
            r = step(sched)["default"]
            assert len(r.launched_task_ids) == 2
            # m0 runs and finishes while m1 is still coming up
            cluster.complete_task(store.job("g1-m0").instances[-1])
            sched.flush_status_updates()
            sched.drain_side_effects()
            assert store.job("g1-m0").state is JobState.COMPLETED
            assert not sched._gang_barrier["g1"]["released"]
            # the held member finally reaches RUNNING
            assert len(held) == 1
            orig(cluster, held)
            sched.flush_status_updates()
        finally:
            del cluster._emit_statuses
        assert sched._gang_barrier["g1"]["released"]
        assert gang_status(store, store.group("g1"))["barrier"] \
            == "released"

    def test_non_gang_completion_skips_group_fetch(self):
        # the completion hooks consult the no-clone group_is_gang test:
        # a plain (non-gang) grouped job going terminal must not pay a
        # store.group() deep clone of the whole member list
        store, cluster, sched = make_system(n_hosts=2)
        group = Group(uuid="plain", jobs=["p0"])
        job = Job(uuid="p0", user="u", command="x",
                  resources=Resources(cpus=1.0, mem=64.0), group="plain")
        store.create_jobs([job], groups=[group])
        step(sched)
        calls = []
        orig = store.group
        store.group = lambda u: (calls.append(u), orig(u))[1]
        try:
            cluster.complete_task(store.job("p0").instances[-1])
            sched.flush_status_updates()
            sched.drain_side_effects()
        finally:
            store.group = orig
        assert store.job("p0").state is JobState.COMPLETED
        assert "plain" not in calls

    def test_whole_gang_failure_counts_one_policy_reaction(self):
        from cook_tpu.utils.metrics import registry
        store, cluster, sched = make_system(n_hosts=3)
        make_gang(store, size=3)
        r = step(sched)["default"]

        def requeues():
            for key, v in registry.snapshot().get("counters", {}).items():
                if key.startswith("cook_gang_policy_kills") \
                        and "requeue" in key:
                    return v
            return 0.0
        before = requeues()
        # every member fails in one burst (whole-gang preemption shape):
        # only the FIRST failure finds live siblings to kill
        for t in r.launched_task_ids:
            cluster.fail_task(t, Reasons.NODE_LOST.code)
        sched.drain_side_effects()
        assert requeues() - before == 1.0


# ------------------------------------------------------------------ chaos
@pytest.mark.chaos
class TestGangChaos:
    @pytest.mark.parametrize("depth", [0, 2])
    def test_zero_partial_gangs_under_faults(self, depth):
        from cook_tpu.sim.chaos import ChaosConfig, run_chaos
        cc = ChaosConfig(seed=7, n_jobs=20, n_hosts=9, n_gangs=3,
                         gang_size=3, rpc_fault_probability=0.2,
                         rpc_fault_max=6, node_loss_max=3,
                         pipeline_depth=depth)
        r = run_chaos(cc)
        assert r.ok, r.violations[:5]
        assert r.completed == r.total
        assert r.leader_kills == 1
        assert r.gang_requeues > 0  # the policy actually fired
