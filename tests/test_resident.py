"""Device-resident incremental cycle state (ISSUE 7; ops/delta.py,
sched/fused.py resident pack, state/index.py delta feed).

The resident pack keeps the fused cycle's stacked [P, T] rows/flags wire
arrays on device across cycles and feeds them scatter deltas extracted
off the index's tx-event journal.  The contract under test:

* DECISION PARITY: launched sets byte-identical to rebuild mode across
  sync, pipelined (depth 2), gang, and compaction-crossing workloads —
  residency is pure transport, never policy;
* FENCES: a delta batch straddling a ``ColumnarIndex._maybe_compact``
  forces a clean full repack (row ids were remapped), never a stale-row
  scatter;
* STEADY STATE: quiet cycles ship zero delta rows, zero full repacks,
  zero recompiles (the tier-1 guard twin of PR 4's warmup assertion);
* FAULTS: delta.extract / delta.apply kernel faults degrade to a full
  repack with ``cook_kernel_fallback_total`` incremented — the cycle
  never dies — and a chaos leader kill rebuilds the resident pack from
  scratch on the promoted driver;
* NATIVE: the C++ pack kernels (delta extraction, order merge, queue
  prune) agree bit-for-bit with the numpy fallbacks; environments
  without a toolchain skip via the ``native`` marker.
"""

import numpy as np
import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config
from cook_tpu.sched import Scheduler
from cook_tpu.state import (
    Group,
    InstanceStatus,
    Job,
    Pool,
    Resources,
    Store,
    new_uuid,
)
from cook_tpu.utils.flight import recorder as flight_recorder
from cook_tpu.utils.metrics import registry


def make_cfg(resident=True, depth=0):
    cfg = Config()
    cfg.resident_pack = resident
    cfg.pipeline.depth = depth
    return cfg


def build_world(cfg, n_jobs=18, n_hosts=5, seed=3, cpus=16.0,
                gang_size=0):
    """Deterministic store + cluster + scheduler; fixed uuids so two
    builds produce identical worlds."""
    rng = np.random.default_rng(seed)
    store = Store()
    store.put_pool(Pool(name="default"))
    hosts = [FakeHost(hostname=f"h{i}",
                      capacity=Resources(cpus=cpus, mem=16384.0))
             for i in range(n_hosts)]
    sched = Scheduler(store, cfg, [FakeCluster("fake-1", hosts)],
                      rank_backend="tpu")
    jobs = []
    for i in range(n_jobs):
        j = Job(uuid=f"00000000-0000-0000-0000-{i:012d}",
                user=f"user{i % 3}", command="true", pool="default",
                priority=int(rng.integers(0, 100)),
                resources=Resources(cpus=float(rng.integers(1, 4)),
                                    mem=float(rng.integers(128, 1024))),
                submit_time_ms=1000 + i)
        jobs.append(j)
        store.create_jobs([j])
    if gang_size:
        members = [Job(uuid=f"00000000-0000-0000-0001-{i:012d}",
                       user="ganguser", command="true", group="g1",
                       resources=Resources(cpus=2.0, mem=256.0),
                       submit_time_ms=900)
                   for i in range(gang_size)]
        store.create_jobs(members, groups=[Group(
            uuid="g1", gang=True, gang_size=gang_size,
            jobs=[m.uuid for m in members])])
        jobs.extend(members)
    return store, sched, jobs


def decisions(store, jobs):
    out = {}
    for j in jobs:
        job = store.job(j.uuid)
        hosts = [store.instance(t).hostname for t in job.instances
                 if store.instance(t) is not None]
        out[j.uuid] = (job.state.value, tuple(sorted(hosts)))
    return out


def churn(store, wave, n=4, seed=11):
    """Deterministic mid-run submissions (same uuids across worlds)."""
    rng = np.random.default_rng(seed + wave)
    fresh = [Job(uuid=f"00000000-0000-0000-{wave + 2:04d}-{i:012d}",
                 user=f"user{i % 3}", command="true", pool="default",
                 resources=Resources(cpus=float(rng.integers(1, 4)),
                                     mem=float(rng.integers(128, 512))),
                 submit_time_ms=5000 + wave * 100 + i)
             for i in range(n)]
    store.create_jobs(fresh)
    return fresh


def drive_pair(depth, cycles=4, **world_kw):
    """Two identical worlds, resident on vs off, stepped in lockstep with
    identical churn; returns (decisions_on, decisions_off, store_on)."""
    store_a, sched_a, jobs_a = build_world(make_cfg(True, depth),
                                           **world_kw)
    store_b, sched_b, jobs_b = build_world(make_cfg(False, depth),
                                           **world_kw)
    assert [j.uuid for j in jobs_a] == [j.uuid for j in jobs_b]
    for w in range(cycles):
        sched_a.step_cycle()
        sched_b.step_cycle()
        jobs_a.extend(churn(store_a, w))
        jobs_b.extend(churn(store_b, w))
    sched_a.step_cycle()
    sched_b.step_cycle()
    return decisions(store_a, jobs_a), decisions(store_b, jobs_b), store_a


class TestDeltaFeed:
    def test_rows_tombstones_fences_and_detach(self):
        """The tx-event delta feed's full contract: touched rows,
        tombstones for rows leaving the pending set, user-id-shift
        fences, and the permanent fence after detach."""
        store = Store()
        idx = store.ensure_index()
        cid = idx.attach_pack_consumer()
        j = Job(uuid=new_uuid(), user="mike", command="x",
                resources=Resources(cpus=1.0, mem=64.0))
        store.create_jobs([j])
        d = idx.pack_delta(cid, "default")
        assert d.rows.size == 1 and d.tombstones.size == 0
        assert not d.fence
        # quiet drain: nothing journaled
        d = idx.pack_delta(cid, "default")
        assert d.rows.size == 0 and not d.fence
        # run the job to completion: pending off -> tombstone
        tid = new_uuid()
        store.launch_instance(j.uuid, tid, "h1")
        store.update_instance_status(tid, InstanceStatus.RUNNING)
        store.update_instance_status(tid, InstanceStatus.SUCCESS)
        d = idx.pack_delta(cid, "default")
        assert d.rows.size >= 1
        assert d.tombstones.size >= 1  # left the pending/live set
        # a new user sorting BEFORE existing ones shifts user ids ->
        # cached keys and resident orders are invalid -> fence
        store.create_jobs([Job(uuid=new_uuid(), user="aaa", command="x",
                               resources=Resources(cpus=1.0, mem=64.0))])
        d = idx.pack_delta(cid, "default")
        assert d.fence
        idx.detach_pack_consumer(cid)
        d = idx.pack_delta(cid, "default")
        assert d.fence  # unknown consumer: permanent fence, never stale

    def test_offerless_cycle_never_caches_constrained_jobs(self):
        """Regression (review round 3): a pool packed while NO offers
        exist must not cache a complex (constrained) pending job as
        maskless — when hosts appear, the constraint must still hold."""
        from cook_tpu.state.schema import Constraint
        cfg = make_cfg(True, depth=0)
        store = Store()
        store.put_pool(Pool(name="default"))
        cluster = FakeCluster("fake-1", [])  # no hosts yet
        sched = Scheduler(store, cfg, [cluster], rank_backend="tpu")
        j = Job(uuid=f"00000000-0000-0000-0000-{0:012d}", user="u",
                command="x", resources=Resources(cpus=1.0, mem=64.0),
                constraints=[Constraint(attribute="rack",
                                        operator="EQUALS", pattern="r1")])
        store.create_jobs([j])
        sched.step_cycle()  # offer-less: must NOT cache the pool
        assert "default" not in sched._fused._pack_cache
        # a violating host appears; the constrained job must stay put
        h = FakeHost("h0", capacity=Resources(cpus=8.0, mem=8192.0),
                     attributes={"rack": "r0"})
        with cluster._lock:
            cluster._hosts["h0"] = h
        sched.step_cycle()
        sched.flush_status_updates()
        assert not store.job(j.uuid).instances, \
            "constraint ignored after offer-less cache"


class TestResidentParity:
    def test_sync_parity_with_churn(self):
        dec_on, dec_off, _store = drive_pair(depth=0)
        assert dec_on == dec_off

    def test_resident_actually_ships_deltas(self):
        """The parity above must not pass because residency silently
        disabled itself: after the cold repack, churned cycles scatter
        deltas instead of repacking."""
        seq0 = flight_recorder.last_seq()
        c0 = registry.snapshot()["counters"].get("cook_delta_rows", 0)
        dec_on, dec_off, _ = drive_pair(depth=0)
        assert dec_on == dec_off
        flight = flight_recorder.summary(since_seq=seq0)
        assert flight["delta_rows"] > 0
        # one cold repack per world build; churn must ride deltas
        assert flight["full_repacks"] <= 2
        assert registry.snapshot()["counters"].get(
            "cook_delta_rows", 0) > c0

    def test_pipelined_depth2_parity(self):
        dec_on, dec_off, _ = drive_pair(depth=2)
        assert dec_on == dec_off

    def test_gang_parity(self):
        dec_on, dec_off, store = drive_pair(depth=0, gang_size=3,
                                            n_jobs=10)
        assert dec_on == dec_off
        # the gang launched whole in resident mode (all-or-nothing held)
        live = [u for u, (_s, hosts) in dec_on.items()
                if u.startswith("00000000-0000-0000-0001") and hosts]
        assert len(live) in (0, 3)

    def test_pipelined_gang_parity(self):
        dec_on, dec_off, _ = drive_pair(depth=2, gang_size=3, n_jobs=10)
        assert dec_on == dec_off


class TestShardedResidency:
    def test_two_device_mesh_parity(self):
        """Each pool shard owns its slice of the resident buffers
        (parallel/mesh.pool_sharding): a 2-device mesh with two pools
        must stay decision-identical to rebuild mode."""
        import jax
        from jax.sharding import Mesh
        from cook_tpu.parallel.mesh import POOL_AXIS
        if len(jax.devices()) < 2:
            pytest.skip("needs the 8-device virtual CPU mesh")

        def world(resident):
            store = Store()
            store.put_pool(Pool(name="default"))
            store.put_pool(Pool(name="beta"))
            hosts = [FakeHost(f"h{i}",
                              capacity=Resources(cpus=8.0, mem=8192.0))
                     for i in range(4)]
            bh = [FakeHost(f"b{i}", pool="beta",
                           capacity=Resources(cpus=8.0, mem=8192.0))
                  for i in range(2)]
            cfg = make_cfg(resident, 0)
            sched = Scheduler(store, cfg,
                              [FakeCluster("f", hosts + bh)],
                              rank_backend="tpu")
            sched._ensure_fused()
            sched._fused._mesh = Mesh(np.array(jax.devices()[:2]),
                                      (POOL_AXIS,))
            jobs = []
            for i in range(12):
                j = Job(uuid=f"00000000-0000-0000-0000-{i:012d}",
                        user=f"u{i % 3}", command="x",
                        pool="beta" if i % 3 == 0 else "default",
                        resources=Resources(cpus=1.0, mem=128.0),
                        submit_time_ms=1000 + i)
                jobs.append(j)
                store.create_jobs([j])
            for _ in range(3):
                sched.step_cycle()
            return decisions(store, jobs)

        assert world(True) == world(False)


class TestCompactionFence:
    def _complete_churn(self, store, n=4200):
        """Run >4096 jobs to completion so the NEXT index read triggers
        _maybe_compact's row remap (the fence under test)."""
        for batch in range(0, n, 1024):
            jobs = [Job(uuid=new_uuid(), user="churner", command="true",
                        pool="default",
                        resources=Resources(cpus=1.0, mem=64.0))
                    for _ in range(min(1024, n - batch))]
            store.create_jobs(jobs)
            for j in jobs:
                tid = new_uuid()
                store.launch_instance(j.uuid, tid, "h0")
                store.update_instance_status(tid, InstanceStatus.RUNNING)
                store.update_instance_status(tid, InstanceStatus.SUCCESS)

    def _drive_compaction_pair(self, depth):
        store_a, sched_a, jobs_a = build_world(make_cfg(True, depth))
        store_b, sched_b, jobs_b = build_world(make_cfg(False, depth))
        sched_a.step_cycle()
        sched_b.step_cycle()
        before = registry.snapshot()["counters"].get(
            'cook_resident_repack{reason="compaction"}', 0)
        idx_a = store_a.ensure_index()
        epoch_before = idx_a.compactions
        self._complete_churn(store_a)
        self._complete_churn(store_b)
        jobs_a.extend(churn(store_a, 0))
        jobs_b.extend(churn(store_b, 0))
        sched_a.step_cycle()
        sched_b.step_cycle()
        sched_a.step_cycle()
        sched_b.step_cycle()
        assert idx_a.compactions > epoch_before, \
            "churn did not trigger a compaction; the fence went untested"
        after = registry.snapshot()["counters"].get(
            'cook_resident_repack{reason="compaction"}', 0)
        return (decisions(store_a, jobs_a), decisions(store_b, jobs_b),
                after - before)

    def test_compaction_forces_repack_and_parity(self):
        dec_on, dec_off, repacks = self._drive_compaction_pair(depth=0)
        assert dec_on == dec_off
        assert repacks >= 1, "compaction epoch fence never forced a repack"

    def test_compaction_parity_under_pipelined_driver(self):
        dec_on, dec_off, repacks = self._drive_compaction_pair(depth=2)
        assert dec_on == dec_off
        assert repacks >= 1


class TestSteadyStateGuard:
    def test_quiet_cycles_zero_repacks_zero_recompiles(self):
        """Tier-1 steady-state guard (the moral equivalent of PR 4's
        warmup assertion): over N cycles with ZERO store churn the
        resident pack must ship zero delta rows, run zero full repacks,
        and trace/compile nothing."""
        cfg = make_cfg(True, depth=0)
        # unmatchable pending jobs: the queue stays stable, cycles stay
        # real (pack + dispatch every tick), nothing launches
        store, sched, _jobs = build_world(cfg, n_jobs=12, cpus=0.5)
        sched.step_cycle()  # cold: compiles + cold repack
        seq0 = flight_recorder.last_seq()
        for _ in range(5):
            sched.step_cycle()
        flight = flight_recorder.summary(since_seq=seq0)
        assert flight["cycles"] == 5
        assert flight["full_repacks"] == 0, flight
        assert flight["delta_rows"] == 0, flight
        assert flight.get("recompiles", {}) == {}, flight
        # the quiet-pool fast path actually engaged (the [T]-sized pack
        # products were reused, not rebuilt)
        assert sched._fused._pack_cache, "quiet-pool pack cache empty"

    def test_reservation_keeps_fast_path_unless_owner_in_pool(self):
        """A rebalancer reservation whose owner lives elsewhere must NOT
        re-erect the staging wall: the fast path stays engaged and the
        reserved host is blocked per cycle; only an owner pending in
        THIS pool (exception punch-through) forces the full rebuild."""
        cfg = make_cfg(True, depth=0)
        store, sched, jobs = build_world(cfg, n_jobs=8, cpus=0.5)
        sched.step_cycle()  # cold
        sched.reserved_hosts["ffffffff-0000-0000-0000-000000000000"] = "h0"
        seq0 = flight_recorder.last_seq()
        sched.step_cycle()
        sched.step_cycle()
        s = flight_recorder.summary(since_seq=seq0)
        assert s["full_repacks"] == 0 and s["delta_rows"] == 0, s
        assert sched._fused._pack_cache, "fast path gave up on a plain " \
            "reservation"
        # owner IS a pending row of this pool -> needs the exception
        # mask -> the reuse guard must refuse the cached pack (the full
        # pack handles the punch-through under the index lock)
        sched.reserved_hosts.clear()
        sched.reserved_hosts[jobs[0].uuid] = "h1"
        assert sched._fused._resv_owner_in_pack(
            store.ensure_index(), dict(sched.reserved_hosts),
            sched._fused._pack_cache["default"])
        sched.step_cycle()  # full pack path; still schedules fine

    def test_quiet_cycles_h2d_excludes_table_size(self):
        """Steady-state h2d bytes scale with the delta (zero here), not
        the table: quiet cycles upload only the U/H-sized control
        arrays, never the [T]-sized rows/flags."""
        cfg = make_cfg(True, depth=0)
        store, sched, _jobs = build_world(cfg, n_jobs=12, cpus=0.5)
        sched.step_cycle()
        seq0 = flight_recorder.last_seq()
        sched.step_cycle()
        quiet = flight_recorder.summary(since_seq=seq0)
        off = make_cfg(False, depth=0)
        store_b, sched_b, _ = build_world(off, n_jobs=12, cpus=0.5)
        sched_b.step_cycle()
        seq1 = flight_recorder.last_seq()
        sched_b.step_cycle()
        rebuild = flight_recorder.summary(since_seq=seq1)
        assert quiet["h2d_bytes"] < rebuild["h2d_bytes"], (quiet, rebuild)


class TestFaultDegradation:
    def test_delta_fault_degrades_to_full_repack(self):
        from cook_tpu.utils.faults import injector
        cfg = make_cfg(True, depth=0)
        store, sched, jobs = build_world(cfg)
        sched.step_cycle()  # cold repack
        jobs.extend(churn(store, 0))
        counters0 = registry.snapshot()["counters"]
        injector.clear()
        injector.arm("delta.apply", probability=1.0, max_fires=1)
        try:
            sched.step_cycle()  # delta cycle: apply faults -> repack
        finally:
            injector.clear()
        counters = registry.snapshot()["counters"]
        key = 'cook_kernel_fallback{kernel="delta.apply"}'
        assert counters.get(key, 0) > counters0.get(key, 0)
        rkey = 'cook_resident_repack{reason="fault"}'
        assert counters.get(rkey, 0) > counters0.get(rkey, 0)
        # the degraded cycle still schedules: parity with a clean world
        store_b, sched_b, jobs_b = build_world(make_cfg(False, 0))
        sched_b.step_cycle()
        jobs_b.extend(churn(store_b, 0))
        sched_b.step_cycle()
        assert decisions(store, jobs) == decisions(store_b, jobs_b)

    @pytest.mark.chaos
    def test_chaos_resident_leader_kill_and_delta_faults(self):
        """sim --chaos with resident mode on: the leader kill's
        journal-replay promotion rebuilds the resident pack from scratch
        on the successor's driver, and a delta fault storm degrades to
        full repacks without ever killing a cycle."""
        from cook_tpu.sim.chaos import ChaosConfig, run_chaos
        res = run_chaos(ChaosConfig(seed=7, resident=True,
                                    rpc_fault_probability=0.0,
                                    delta_fault_probability=0.3))
        assert res.ok, res.violations
        assert res.completed == res.total
        assert res.leader_kills == 1
        assert res.delta_faults > 0
        # every fault degraded to a repack; plus the cold build and the
        # post-promotion rebuild
        assert res.flight["full_repacks"] >= res.delta_faults + 2


@pytest.mark.native
class TestNativePack:
    """C++ pack kernels vs the numpy fallbacks (skip when no toolchain:
    the Python extractor is the supported fallback, never an error)."""

    @pytest.fixture(autouse=True)
    def _require_native(self):
        from cook_tpu.native.pack import native_available
        if not native_available():
            pytest.skip("no C++ toolchain: python pack fallback in use")

    def test_pack_diff_matches_numpy(self):
        from cook_tpu.native import pack
        rng = np.random.default_rng(0)
        a = rng.integers(0, 50, 4096).astype(np.int32)
        b = a.copy()
        b[rng.integers(0, 4096, 97)] += 1
        fa = rng.integers(0, 32, 4096).astype(np.uint8)
        fb = fa.copy()
        fb[rng.integers(0, 4096, 41)] ^= 8
        got = pack.pack_diff(a, b, fa, fb)
        want = np.flatnonzero((a != b) | (fa != fb)).astype(np.int32)
        np.testing.assert_array_equal(got, want)
        assert pack.pack_diff(a, a, fa, fa).size == 0

    def test_order_merge_matches_numpy(self):
        from cook_tpu.native import pack
        rng = np.random.default_rng(1)
        n, nd, na = 500, 40, 60
        kb = np.sort(np.frombuffer(
            rng.integers(0, 256, n * 40, dtype=np.uint8).tobytes(),
            dtype="S40").copy())
        st = rng.integers(0, 10**9, n).astype(np.int64)
        uid = rng.integers(0, 99, n).astype(np.int32)
        rows = rng.integers(0, 10**6, n).astype(np.int64)
        del_pos = np.sort(rng.choice(n, nd, replace=False)).astype(np.int64)
        akb = np.sort(np.frombuffer(
            rng.integers(0, 256, na * 40, dtype=np.uint8).tobytes(),
            dtype="S40").copy())
        ast = rng.integers(0, 10**9, na).astype(np.int64)
        auid = rng.integers(0, 99, na).astype(np.int32)
        arows = rng.integers(0, 10**6, na).astype(np.int64)
        post = np.delete(kb, del_pos)
        ins = np.searchsorted(post, akb, side="left").astype(np.int64)
        got = pack.order_merge(kb, st, uid, rows, del_pos, ins,
                               akb, ast, auid, arows)
        want = (np.insert(np.delete(kb, del_pos), ins, akb),
                np.insert(np.delete(st, del_pos), ins, ast),
                np.insert(np.delete(uid, del_pos), ins, auid),
                np.insert(np.delete(rows, del_pos), ins, arows))
        for g, w in zip(got, want):
            np.testing.assert_array_equal(g, w)

    def test_prune_rows_matches_numpy(self):
        from cook_tpu.native import pack
        rng = np.random.default_rng(2)
        rows = rng.integers(0, 10**6, 777).astype(np.int32)
        drop = np.sort(rng.choice(777, 33, replace=False)).astype(np.int64)
        got = pack.prune_rows(rows, drop)
        keep = np.ones(777, dtype=bool)
        keep[drop] = False
        np.testing.assert_array_equal(got, rows[keep])


class TestDeltaKernel:
    def test_scatter_matches_reference_impl(self):
        import jax
        from cook_tpu.ops import reference_impl
        from cook_tpu.ops.delta import PackDeltaApplier
        rng = np.random.default_rng(5)
        rows = rng.integers(0, 99, (2, 64)).astype(np.int32)
        flags = rng.integers(0, 32, (2, 64)).astype(np.uint8)
        idx = np.sort(rng.choice(128, 17, replace=False)).astype(np.int32)
        rv = rng.integers(0, 99, 17).astype(np.int32)
        fv = rng.integers(0, 32, 17).astype(np.uint8)
        applier = PackDeltaApplier(donate=False)
        import jax.numpy as jnp
        dr, df = applier.apply(jnp.asarray(rows), jnp.asarray(flags),
                               idx, rv, fv)
        wr, wf = reference_impl.apply_pack_delta(rows, flags, idx, rv, fv)
        np.testing.assert_array_equal(np.asarray(jax.device_get(dr)), wr)
        np.testing.assert_array_equal(np.asarray(jax.device_get(df)), wf)

    def test_delta_scatter_quantized_matches_wide(self):
        import jax.numpy as jnp
        from cook_tpu.ops.delta import ROWS_WIDE, PackDeltaApplier
        rng = np.random.default_rng(3)
        P, T = 2, 512
        rows0 = np.zeros((P, T), dtype=np.int32)
        flags0 = np.zeros((P, T), dtype=np.uint8)
        idx = np.sort(rng.choice(P * T, 64, replace=False)).astype(
            np.int32)
        vals = ((idx % T) + rng.integers(-100, 100, 64)).astype(np.int32)
        fvals = rng.integers(0, 32, 64).astype(np.uint8)
        ap = PackDeltaApplier(donate=False)
        rw, fw = ap.apply(jnp.asarray(rows0), jnp.asarray(flags0),
                          idx, vals, fvals, quantize=False)
        rq, fq = ap.apply(jnp.asarray(rows0), jnp.asarray(flags0),
                          idx, vals, fvals, quantize=True)
        assert (np.asarray(rw) == np.asarray(rq)).all()
        assert (np.asarray(fw) == np.asarray(fq)).all()
        # and the staged narrow batch was genuinely smaller
        st_w = ap.stage((P, T), idx, vals, fvals, quantize=False)
        st_q = ap.stage((P, T), idx, vals, fvals, quantize=True)
        assert st_q.codec != ROWS_WIDE
        assert st_q.nbytes < st_w.nbytes
