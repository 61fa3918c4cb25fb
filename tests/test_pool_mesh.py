"""``pipeline.mesh_devices``: the fused cycle as ONE SPMD dispatch over a
pool mesh of several devices, under the driver the daemon builds (fused,
depth 2, columnar, resident, quantized wire, warmed) — on the virtual CPU
devices tests/conftest.py forces.

What is held here: four-device decisions equal one-device decisions and
the plain reference's (ops/reference_impl through
tests/test_cycle_parity._reference_pool_cycle), cycle by cycle; every
input lives on the device that owns its pools, the base mirror on all of
them; nothing compiles after the warm-up; a quota group that spans two
devices is enforced as on one (the only decision the all-gather carries);
and the statement itself — default, parsing, what boot refuses, what the
record and the health block say.  A CPU run shows decisions and
placements, never a time.
"""

import numpy as np
import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config, PipelineConfig, PoolQuota
from cook_tpu.sched import Scheduler
from cook_tpu.state import Job, Pool, Resources, Store
from cook_tpu.utils.flight import recorder
from cook_tpu.utils.metrics import registry

from test_cycle_parity import _reference_pool_cycle

CAP = 16
#: the largest pool's rows land in the 256 bucket the warm-up names, and
#: the eight-pool mirror (8 x 256 rows) keeps room for the 1,024-row
#: chunk an arrival's append takes
BIG = 140


def make_cfg(mesh_devices, quota_groups=None, group_count=None):
    cfg = Config()
    cfg.cycle_mode = "fused"
    cfg.default_matcher.max_jobs_considered = CAP
    cfg.pipeline = PipelineConfig.from_conf({
        "depth": 2, "mesh_devices": mesh_devices, "warmup_tasks": BIG,
        "warmup_hosts": 64, "warmup_users": 8})
    assert cfg.columnar_index and cfg.resident_pack and cfg.quantized_wire
    if quota_groups:
        cfg.quota_groups = dict(quota_groups)
        cfg.quota_group_quotas = {
            g: PoolQuota(count=float(group_count))
            for g in set(quota_groups.values())}
    return cfg


def job(i, pool, rng, submit=1000):
    cpus = float(rng.choice([1, 2, 4]))
    return Job(uuid=f"00000000-0000-0000-0000-{i:012d}",
               user=f"user{int(rng.integers(0, 5))}", command="true",
               pool=pool, priority=int(rng.integers(0, 100)),
               resources=Resources(cpus=cpus,
                                   mem=cpus * float(rng.choice([512, 1024]))),
               submit_time_ms=submit + i)


class World:
    """A seeded several-pool world whose arithmetic is exact in float32
    (sizes and shares are powers of two apart), capacity binding in every
    pool, driven cycle by cycle with arrivals and completions between the
    cycles; every dispatch's inputs and fetched outputs are kept as host
    arrays for the plain reference."""

    def __init__(self, n_pools, mesh_devices, jobs_of=lambda p: 20 + 3 * p,
                 seed=5, **cfg_kw):
        small, jobs_of = jobs_of, lambda p: (
            BIG if p == n_pools - 1 else small(p))
        self.rng = np.random.default_rng(seed)
        self.store = Store()
        self.pools = [f"pool{i}" for i in range(n_pools)]
        hosts, self.jobs = [], []
        for p, pool in enumerate(self.pools):
            self.store.put_pool(Pool(name=pool))
            for u in range(5):
                self.store.set_share(
                    f"user{u}", pool, {"cpus": 4.0 * (1 + (u == 0)),
                                       "mem": 4096.0 * (1 + (u == 0))})
            hosts += [FakeHost(hostname=f"{pool}-h{i}", pool=pool,
                               capacity=Resources(cpus=8.0, mem=16384.0))
                      for i in range(4)]
            self.jobs += [job(len(self.jobs) + k, pool, self.rng)
                          for k in range(jobs_of(p))]
        self.store.create_jobs(self.jobs)
        self.cluster = FakeCluster("fake-1", hosts)
        self.compiles0 = self.compiles()
        self.sched = Scheduler(self.store, make_cfg(mesh_devices, **cfg_kw),
                               [self.cluster], rank_backend="tpu")
        self.fused = self.sched._ensure_fused().fused
        self.dispatches = []          # (group pool names, cap, inputs, gd)
        dispatch = self.fused.dispatch_group

        def dispatched(sg):
            import jax
            inp = type(sg.inp)(*jax.device_get(tuple(sg.inp)))
            gd = dispatch(sg)
            self.dispatches.append(([pp.pool.name for pp in sg.group],
                                    min(sg.cap, sg.T), inp, gd, sg))
            return gd
        self.fused.dispatch_group = dispatched
        self.placed = {}              # uuid -> hostname, every launch so far
        self.recs = []                # this world's fused CycleRecords

    @staticmethod
    def compiles():
        return sum(v for _l, v in registry.series("cook_jit_compile"))

    def cycle(self):
        """One pipelined step; returns what it launched, {uuid: host}."""
        # the step joins the record opened here (``cycle`` is
        # re-entrant), so the document kept is this thread's own cycle
        # whatever other schedulers of the process are recording
        with recorder.cycle("fused") as rec:
            self.sched.step_cycle()
        self.recs.append(rec.to_doc())
        new = {}
        for j in self.jobs:
            if j.uuid in self.placed:
                continue
            for t in self.store.job(j.uuid).instances:
                new[j.uuid] = self.store.instance(t).hostname
        self.placed.update(new)
        return new

    def arrive(self, per_pool=2, pools=None):
        new = [job(len(self.jobs) + k, pool, self.rng, submit=9000)
               for k, pool in enumerate((pools or self.pools) * per_pool)]
        self.jobs += new
        self.store.create_jobs(new)

    def complete(self, n):
        """Finish the first ``n`` running tasks in uuid order: capacity
        comes back and a user's running usage falls."""
        running = sorted(
            (j.uuid, t) for j in self.jobs if j.uuid in self.placed
            for t in self.store.job(j.uuid).instances
            if self.store.instance(t).status.name == "RUNNING")
        for _u, tid in running[:n]:
            self.cluster.complete_task(tid)
        self.sched.flush_status_updates()

    def run(self, cycles=7):
        out = []
        for step in range(cycles):
            out.append(self.cycle())
            self.arrive()
            if step % 2:
                self.complete(6)
        return out

    def records(self):
        return self.recs


def against_reference(world):
    """Every dispatch's fetched candidates against the plain reference on
    the inputs that dispatch was given, pool by pool; returns how many
    pool-cycles were compared and how many of them placed something."""
    compared = placed = 0
    for names, cap, inp, gd, _sg in world.dispatches:
        # (the cycle left in flight is fetched here: idempotent)
        cand_row, cand_assign, cand_qpos, n_queue = \
            world.fused.fetch_group(gd)
        for p, name in enumerate(names):
            launchable = (np.asarray(inp.flags)[p] & (1 | 8)) == (1 | 8)
            if not launchable.any() or int(inp.num_considerable[p]) == 0:
                assert (np.asarray(cand_row)[p] < 0).all(), name
                continue
            queue, cand, assign, qpos, _T = _reference_pool_cycle(
                inp, p, cap)
            pad = [-1] * (cap - len(cand))
            assert int(n_queue[p]) == len(queue), name
            assert np.asarray(cand_row)[p].tolist() == cand + pad, name
            assert np.asarray(cand_assign)[p].tolist() == assign + pad, name
            assert np.asarray(cand_qpos)[p].tolist() == qpos + pad, name
            compared += 1
            placed += any(h >= 0 for h in assign)
    return compared, placed


@pytest.fixture(scope="module")
def eight_pools():
    """The eight-pool world run twice: on one device and on four."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs the virtual CPU devices of tests/conftest.py")
    runs = {}
    for n in (1, 4):
        w = World(8, n)
        w.warm_compiles = w.compiles() - w.compiles0
        before = w.compiles()
        w.decisions = w.run()
        w.live_compiles = w.compiles() - before
        runs[n] = w
    return runs


class TestDecisions:
    def test_four_devices_decide_what_one_device_decides(self, eight_pools):
        one, four = eight_pools[1], eight_pools[4]
        assert len(four.decisions) >= 6
        for step, (a, b) in enumerate(zip(one.decisions, four.decisions)):
            assert set(a) == set(b), step
            assert a == b, step                   # and on the same hosts
        # the run tested something: launches in most cycles, in every
        # pool, and capacity bound (not everything ran)
        assert sum(1 for d in four.decisions if d) >= 5
        assert {four.store.job(u).pool for u in four.placed} \
            == set(four.pools)
        assert len(four.placed) < len(four.jobs)

    @pytest.mark.parametrize("devices", [1, 4])
    def test_every_cycle_is_the_plain_references(self, eight_pools, devices):
        w = eight_pools[devices]
        assert len(w.dispatches) >= 7
        assert all(len(names) == 8 for names, *_ in w.dispatches)
        compared, placed = against_reference(w)
        assert compared >= 40 and placed >= 10


class TestPlacement:
    def test_inputs_live_where_their_pools_do(self, eight_pools):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec
        from cook_tpu.parallel.mesh import pool_sharding
        w = eight_pools[4]
        mesh = w.fused.mesh()
        assert mesh.size == 4
        sharded, replicated = pool_sharding(mesh), NamedSharding(
            mesh, PartitionSpec())
        mesh_devs = set(mesh.devices.flat)
        for _names, _cap, _inp, _gd, sg in w.dispatches:
            for field, a in zip(type(sg.inp)._fields, sg.inp):
                want = replicated if field in ("res_base", "disk_base") \
                    else sharded
                assert a.sharding.is_equivalent_to(want, a.ndim), field
                assert a.committed, field
                # nothing lives on device 0 alone
                assert {s.device for s in a.addressable_shards} \
                    == mesh_devs, field
                if want is sharded:
                    assert {s.data.shape[0] for s in a.addressable_shards} \
                        == {a.shape[0] // 4}, field
        # the outputs the apply reads come back from four shards
        gd = w.dispatches[-1][3]
        assert {s.device for s in gd.res.queue_rows.addressable_shards} \
            == mesh_devs
        row = w.fused._pool_row(gd.res.queue_rows, 7)
        assert row.shape == gd.res.queue_rows.shape[1:]
        assert row.devices() == {list(mesh.devices.flat)[3]}
        assert np.array_equal(np.asarray(row),
                              np.asarray(gd.res.queue_rows)[7])

    def test_resident_state_keeps_its_placement(self, eight_pools):
        """Across the delta scatters and mirror appends of the run (the
        arrivals made both happen) the resident buffers stay pool-sharded
        and the mirror stays on every device."""
        from jax.sharding import NamedSharding, PartitionSpec
        from cook_tpu.parallel.mesh import pool_sharding
        w = eight_pools[4]
        mesh = w.fused.mesh()
        launches = {lbl["kernel"]: v for lbl, v in
                    registry.series("cook_kernel_launches")}
        assert launches.get("delta.apply", 0) > 0
        assert launches.get("delta.append", 0) > 0
        assert sum(r["delta_rows"] for r in w.records()) > 0
        st = w.fused._resident[False]
        for buf in (st.rows_dev, st.flags_dev):
            assert buf.sharding.is_equivalent_to(pool_sharding(mesh), 2)
        mir = w.fused._mirror
        for buf in (mir._res, mir._disk):
            assert buf.sharding.is_equivalent_to(
                NamedSharding(mesh, PartitionSpec()), buf.ndim)
            assert len(buf.addressable_shards) == 4
        # the resident rows are what a fresh pack would upload
        assert np.array_equal(np.asarray(st.rows_dev), st.rows_host)
        assert np.array_equal(np.asarray(st.flags_dev), st.flags_host)

    @pytest.mark.parametrize("devices", [1, 4])
    def test_nothing_compiles_after_the_warm_up(self, eight_pools, devices):
        w = eight_pools[devices]
        assert w.sched.device["warmup_runs"] == 1
        assert w.warm_compiles > 0
        assert w.live_compiles == 0
        assert all(r["recompiles"] == {} for r in w.records())

    @pytest.mark.parametrize("devices", [1, 4])
    def test_the_record_and_the_health_block_say_the_mesh(self, eight_pools,
                                                          devices):
        from cook_tpu.rest.api import CookApi
        w = eight_pools[devices]
        recs = w.records()
        assert len(recs) >= 7
        assert [r["mesh_devices"] for r in recs] == [devices] * len(recs)
        assert all(r["pools"] == 8 for r in recs)
        assert all(r["detail_ms"]["stage_put"] <= r["detail_ms"]["stage"]
                   for r in recs)
        dev = CookApi(w.store, scheduler=w.sched).debug_health()["device"]
        assert dev["mesh_devices"] == devices
        assert len(dev["mesh_device_ids"]) == devices
        assert dev["count"] >= devices


class TestQuotaGroupAcrossDevices:
    def test_a_group_spanning_two_devices_is_enforced_as_on_one(self):
        """pool0 (device 0) and pool7 (device 3) share a count quota: what
        either may admit depends on the other's running usage, which only
        the all-gather carries between the shards."""
        group = {"pool0": "g1", "pool7": "g1"}

        def run(devices, **kw):
            w = World(8, devices, **kw)
            return w, w.run(cycles=6)
        one, d1 = run(1, quota_groups=group, group_count=10)
        four, d4 = run(4, quota_groups=group, group_count=10)
        free, dfree = run(4)
        assert d1 == d4
        in_group = lambda w: sum(1 for u in w.placed
                                 if w.store.job(u).pool in group)
        # the cap decided: fewer group launches than without it, and the
        # running total of the two pools never passed it by more than one
        # cycle's admissions of the other pool
        assert in_group(four) < in_group(free)
        assert in_group(four) == in_group(one)
        ids = [np.asarray(inp.group_id).tolist()
               for _n, _c, inp, _gd, _sg in four.dispatches]
        assert all(g[0] == g[7] >= 0 and set(g[1:7]) == {-1} for g in ids)
        # later cycles saw the other pool's launches in the gathered base
        assert sum(1 for dec in d4 if dec) >= 3


class TestUnevenPools:
    def test_three_pools_on_four_devices(self):
        """Pools that do not fill the mesh are padded to one a device
        (P = 4): the empty slot launches nothing and parity holds."""
        runs = {n: World(3, n) for n in (1, 4)}
        dec = {n: w.run(cycles=6) for n, w in runs.items()}
        assert dec[1] == dec[4]
        four = runs[4]
        assert all(sg.inp.rows.shape[0] == 4
                   for *_x, sg in four.dispatches)
        assert all(runs[1].dispatches[i][4].inp.rows.shape[0] == 3
                   for i in range(len(runs[1].dispatches)))
        assert all(r["mesh_devices"] == 4 and r["pools"] == 3
                   for r in four.records())
        compared, placed = against_reference(four)
        assert compared >= 12 and placed >= 4
        assert four.compiles() - four.compiles0 > 0
        assert all(r["recompiles"] == {} for r in four.records())

    def test_a_pool_whose_queue_empties_mid_run(self):
        """pool1 holds three small jobs: they launch in the first cycle,
        the pool drops out of the dispatch (seven pools, still P = 8) and
        comes back when jobs arrive for it."""
        def run(devices):
            w = World(8, devices, jobs_of=lambda p: 3 if p == 1 else 20)
            out = []
            for step in range(7):
                out.append(w.cycle())
                w.arrive(pools=[p for p in w.pools
                                if p != "pool1" or step >= 4])
            return w, out
        (one, d1), (four, d4) = run(1), run(4)
        assert d1 == d4
        sizes = [len(names) for names, *_ in four.dispatches]
        # (an empty-handed step promotes the cycle in flight, so a step
        # may dispatch twice)
        assert sizes[:2] == [8, 8] and 7 in sizes[2:6] and 8 in sizes[6:]
        assert all(sg.inp.rows.shape[0] == 8 for *_x, sg in four.dispatches)
        assert any(four.store.job(u).pool == "pool1"
                   for dec in d4[-2:] for u in dec)
        compared, _placed = against_reference(four)
        assert compared >= 40


class TestTheStatement:
    def test_default_is_one_device(self):
        assert PipelineConfig().mesh_devices == 1
        assert Config().pipeline.mesh_devices == 1
        w = World(2, 1)
        assert w.fused.mesh().size == 1
        assert w.sched.device["mesh_devices"] == 1

    def test_from_conf_round_trip_and_typos(self):
        from cook_tpu.daemon import build_scheduler_config
        cfg = build_scheduler_config({"pipeline": {"mesh_devices": 4}})
        assert cfg.pipeline.mesh_devices == 4
        assert build_scheduler_config({}).pipeline.mesh_devices == 1
        with pytest.raises(ValueError, match="unknown pipeline key "
                                             "'mesh_device'"):
            build_scheduler_config({"pipeline": {"mesh_device": 4}})
        for bad in (0, -1):
            with pytest.raises(ValueError, match="mesh_devices"):
                PipelineConfig.from_conf({"mesh_devices": bad})
        with pytest.raises(ValueError, match="mesh_devices"):
            PipelineConfig(mesh_devices=True)

    def test_boot_refuses_more_devices_than_the_process_has(self):
        import jax
        n = jax.local_device_count() + 1
        cfg = make_cfg(n)
        store = Store()
        store.put_pool(Pool(name="default"))
        with pytest.raises(ValueError) as ei:
            Scheduler(store, cfg, [FakeCluster("f", [])],
                      rank_backend="tpu")
        assert "pipeline.mesh_devices" in str(ei.value)
        assert f"{jax.local_device_count()} local device" in str(ei.value)
        # and the cpu backend has no devices to build a mesh from
        with pytest.raises(ValueError, match="pipeline.mesh_devices"):
            Scheduler(store, make_cfg(2), [FakeCluster("f", [])],
                      rank_backend="cpu")

    def test_boot_refuses_a_mesh_together_with_controller_shards(self):
        from cook_tpu.daemon import CookDaemon
        from cook_tpu.parallel.mesh import ShardAlignmentError
        conf = {"port": 0, "scheduler": {
            "pipeline": {"mesh_devices": 2},
            "partitions": {"count": 4, "shards": 2,
                           "pools": {f"pool{i}": i for i in range(4)}}}}
        with pytest.raises(ShardAlignmentError) as ei:
            CookDaemon(conf).start()
        assert "pipeline.mesh_devices" in str(ei.value)
        assert "partitions.shards" in str(ei.value)
        # a shard worker's own scheduler refuses it too, at construction
        store = Store()
        store.put_pool(Pool(name="default"))
        with pytest.raises(ShardAlignmentError, match="controller shard 1"):
            Scheduler(store, make_cfg(2), [FakeCluster("f", [])],
                      rank_backend="tpu", shard_id=1)
        # one device and shards stay a valid layout
        conf["scheduler"]["pipeline"]["mesh_devices"] = 1
        daemon = CookDaemon(conf)
        try:
            daemon.start()
        finally:
            daemon.shutdown()

    def test_an_apply_only_record_carries_no_mesh(self):
        """``mesh_devices`` is noted at dispatch: a record that dispatched
        nothing leaves it out, as it leaves ``h2d_bytes`` 0."""
        from cook_tpu.utils.flight import CycleRecord
        doc = CycleRecord(1, "fused").to_doc()
        assert "mesh_devices" not in doc and doc["h2d_bytes"] == 0
        with recorder.cycle("fused") as rec:
            recorder.note_mesh(4)
            recorder.note_mesh(2)
        assert rec.to_doc()["mesh_devices"] == 2
