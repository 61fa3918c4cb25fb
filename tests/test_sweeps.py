"""The 30 s sweeps against a large running set (ROADMAP S3, ISSUE 28):
``step_reapers`` and ``Monitor.sweep`` read the store's live entities a
bounded piece at a time instead of cloning the whole running set under
the store lock.  What they DECIDE is unchanged — same kills, same
reasons, same order, no duplicate — and that is pinned here against the
reapers' rules worked out on a cloned snapshot, which is what they read
before; how long they hold the lock is asserted through the lock
monitor's own accounting of holds (utils/locks.py), never a wall clock.
"""

import threading

from cook_tpu.cluster import FakeCluster
from cook_tpu.config import Config
from cook_tpu.sched import Scheduler
from cook_tpu.state import (
    Group,
    InstanceStatus,
    Job,
    Pool,
    Reasons,
    Resources,
    Store,
)
from cook_tpu.state import store as store_mod
from cook_tpu.utils import locks

T0 = 1_700_000_000_000          # the instant every task started
PLAIN, LATE, ORPHANS, GROUPED = 2400, 40, 30, 60


def cpu_config() -> Config:
    cfg = Config()
    cfg.cycle_mode = "split"
    cfg.default_matcher.backend = "cpu"
    cfg.columnar_index = False
    cfg.orphaned_cluster_grace_seconds = 30.0
    return cfg


def build():
    """A few thousand running tasks on cluster c1, among them: LATE with
    a max runtime of 10 s; ORPHANS on a cluster this scheduler lacks;
    one straggler group of GROUPED members of which a third finished in
    1 s (quantile 0.5 x multiplier 3 -> 3 s threshold).  The first five
    grouped members also carry the 10 s max runtime: two reapers want
    them, one may have them."""
    store = Store()
    now = [T0]
    store.clock = lambda: now[0]
    store.put_pool(Pool(name="default"))
    group = Group(uuid="g-stragglers", straggler_quantile=0.5,
                  straggler_multiplier=3.0)
    jobs = []

    def job(kind, i, **kw):
        j = Job(uuid=f"{kind}-{i:05d}", user=f"user{i % 7}", command="true",
                pool="default", resources=Resources(cpus=1.0, mem=64.0),
                max_retries=1, **kw)
        jobs.append(j)
        return j
    for i in range(PLAIN):
        job("plain", i)
    for i in range(LATE):
        job("late", i, max_runtime_ms=10_000)
    for i in range(ORPHANS):
        job("orphan", i)
    for i in range(GROUPED):
        j = job("member", i, group=group.uuid,
                **({"max_runtime_ms": 10_000} if i < 5 else {}))
        group.jobs.append(j.uuid)
    store.create_jobs(jobs, groups=[group])
    store.launch_instances([
        dict(job_uuid=j.uuid, task_id="t-" + j.uuid, hostname="h",
             compute_cluster="gone" if j.uuid.startswith("orphan")
             else "c1") for j in jobs])
    for j in jobs:
        store.update_instance_status("t-" + j.uuid, InstanceStatus.RUNNING)
    now[0] = T0 + 1_000
    for i in range(GROUPED - GROUPED // 3, GROUPED):
        store.update_instance_status(f"t-member-{i:05d}",
                                     InstanceStatus.SUCCESS)
    sched = Scheduler(store, cpu_config(), [FakeCluster("c1", [])],
                      rank_backend="cpu")
    return store, sched, now


def expected_kills(store, sched, current_ms):
    """The reapers' rules on a cloned snapshot of the running set, in
    the reapers' order (what step_reapers read before it stopped
    cloning): (task, reason) pairs."""
    running = store.running_instances()            # clones, one lock hold
    out = [(i.task_id, Reasons.MAX_RUNTIME_EXCEEDED.code)
           for j, i in running if j.max_runtime_ms
           and current_ms - i.start_time_ms > j.max_runtime_ms]
    done = {t for t, _r in out}
    grace = sched.config.orphaned_cluster_grace_seconds * 1000.0
    for _j, i in running:
        if i.task_id not in done and i.compute_cluster not in sched.clusters \
                and current_ms - sched._orphan_first_seen.get(
                    i.task_id, current_ms) >= grace:
            out.append((i.task_id, Reasons.NODE_LOST.code))
            done.add(i.task_id)
    for j, i in running:
        if i.task_id not in done and j.group \
                and current_ms - i.start_time_ms > 3 * 1_000:
            out.append((i.task_id, Reasons.STRAGGLER.code))
    return out


class TestReapersDecideWhatTheyDecided:
    def test_same_kills_same_reasons_same_order_no_duplicate(self):
        store, sched, now = build()
        # 2 s in: nothing is late, the orphans' grace starts
        assert expected_kills(store, sched, T0 + 2_000) == []
        assert sched.step_reapers(current_ms=T0 + 2_000) == []
        # 5 s in: the stragglers and nothing else
        want = expected_kills(store, sched, T0 + 5_000)
        assert len(want) == GROUPED - GROUPED // 3
        assert {r for _t, r in want} == {Reasons.STRAGGLER.code}
        # 40 s in on a fresh world: all three reapers at once
        store, sched, now = build()
        assert sched.step_reapers(current_ms=T0 + 2_000) == []
        want = expected_kills(store, sched, T0 + 40_000)
        killed = sched.step_reapers(current_ms=T0 + 40_000)
        assert killed == [t for t, _r in want]
        assert len(killed) == len(set(killed)) \
            == LATE + ORPHANS + (GROUPED - GROUPED // 3)
        for task, reason in want:
            inst = store.instance(task)
            assert inst.status is InstanceStatus.FAILED, task
            assert inst.reason_code == reason, task
        # the five members both rules wanted went to the first of them
        assert [r for t, r in want if t < "t-member-00005"
                and t.startswith("t-member")] \
            == [Reasons.MAX_RUNTIME_EXCEEDED.code] * 5
        # and the rest of the running set is untouched
        assert len(store.running_instances()) == PLAIN
        assert sched.step_reapers(current_ms=T0 + 40_000) == []


class HoldLedger:
    """Work done per hold of the store lock by this thread, counted off
    the lock monitor's own acquire hook (one call per outermost hold of
    a named lock): clones made and instances looked up in each hold."""

    def __init__(self, store, monkeypatch):
        self.holds = []              # [clones, lookups] per hold
        me = threading.get_ident()
        noted = locks.monitor._note_acquired

        def note_acquired(lock):
            if lock is store._lock and threading.get_ident() == me:
                self.holds.append([0, 0])
            noted(lock)
        monkeypatch.setattr(locks.monitor, "_note_acquired", note_acquired)
        clone = store_mod.fast_clone

        def counted_clone(ent):
            if store._lock in locks.monitor.held():
                self.holds[-1][0] += 1
            return clone(ent)
        monkeypatch.setattr(store_mod, "fast_clone", counted_clone)
        pairs = store_mod.Store._live_pairs

        def counted_pairs(self_, insts, pool, out):
            assert store._lock not in locks.monitor.held()
            pairs(self_, insts, pool, out)
            self.holds[-1][1] += len(insts)
        monkeypatch.setattr(store_mod.Store, "_live_pairs", counted_pairs)


class TestTheSweepsHoldTheLockForABoundedPiece:
    def test_reapers_per_hold(self, monkeypatch):
        store, sched, _now = build()
        sched.step_reapers(current_ms=T0 + 2_000)      # orphans' grace
        live = len(store.running_instances())
        ledger = HoldLedger(store, monkeypatch)
        killed = sched.step_reapers(current_ms=T0 + 40_000)
        assert len(killed) == LATE + ORPHANS + (GROUPED - GROUPED // 3)
        clones = [c for c, _n in ledger.holds]
        lookups = [n for _c, n in ledger.holds]
        # the scan: every live instance looked up, a chunk a hold
        assert sum(lookups) == live
        assert max(lookups) <= store_mod._SCAN_CHUNK
        assert sum(1 for n in lookups if n) == -(-live // store_mod._SCAN_CHUNK)
        # no hold clones more than one task's worth of entities (a kill's
        # own transaction, a group's members): before, ONE hold cloned
        # the whole running set, two entities a pair
        assert max(clones) <= 2 * GROUPED
        assert sum(clones) < live
        # and the scan itself cloned nothing at all
        assert all(c == 0 for c, n in ledger.holds if n)

    def test_monitor_per_hold(self, monkeypatch):
        store, sched, _now = build()
        live = len(store.running_instances())
        ledger = HoldLedger(store, monkeypatch)
        counts = sched.monitor.sweep()
        assert counts["default"]["total"] == 7
        assert sum(n for _c, n in ledger.holds) == live
        assert max(n for _c, n in ledger.holds) <= store_mod._SCAN_CHUNK
        assert max(c for c, _n in ledger.holds) <= 2


class TestAWriterIsNotBlockedForTheSweep:
    """A transaction that arrives while a sweep is mid-scan commits
    before the sweep ends: the scan lets go of the store lock between
    chunks (an outer hold around the whole sweep would time the writer
    out).  Ordered by events, no clock: the sweep waits at a chunk
    boundary, outside the lock, until the writer is through."""

    def drive(self, monkeypatch, sweep_of):
        store, sched, _now = build()
        monkeypatch.setattr(store_mod, "_SCAN_CHUNK", 256)
        mid_scan, wrote, order = threading.Event(), threading.Event(), []
        pairs = store_mod.Store._live_pairs

        def pairs_then_let_the_writer_in(self_, insts, pool, out):
            pairs(self_, insts, pool, out)
            if not mid_scan.is_set():
                mid_scan.set()
                wrote.wait(60)          # generous: eight copies at once
                order.append("scan resumed")
        monkeypatch.setattr(store_mod.Store, "_live_pairs",
                            pairs_then_let_the_writer_in)

        def writer():
            assert mid_scan.wait(60)
            store.create_jobs([Job(
                uuid="arrival-00001", user="user1", command="true",
                pool="default", resources=Resources(cpus=1.0, mem=64.0))])
            order.append("writer committed")
            wrote.set()
        t = threading.Thread(target=writer, name="test-writer")
        t.start()
        sweep_of(sched)()
        order.append("sweep ended")
        t.join(60)
        assert order == ["writer committed", "scan resumed", "sweep ended"]
        assert store.job("arrival-00001") is not None

    def test_reapers(self, monkeypatch):
        self.drive(monkeypatch, lambda sched: lambda: sched.step_reapers(
            current_ms=T0 + 40_000))

    def test_monitor(self, monkeypatch):
        self.drive(monkeypatch, lambda sched: sched.monitor.sweep)


class TestTheSweepsArePaced:
    """utils/pacing.Pacer on a virtual CPU clock: a sweep rests after
    every burst of its own CPU time so that it has used a quarter of the
    time since the burst began, and a small walk never rests."""

    def pacer(self, per_item_s):
        from cook_tpu.utils.pacing import Pacer
        cpu, slept = [0.0], []

        def items(n):
            for k in range(n):
                cpu[0] += per_item_s          # the walk's own work
                yield k
        p = Pacer(share=0.25, burst_s=0.01, every=100,
                  cpu=lambda: cpu[0], sleep=slept.append)
        return p, items, slept

    def test_a_long_walk_rests_three_times_what_it_worked(self):
        p, items, slept = self.pacer(per_item_s=0.0002)   # 20 ms a look
        assert list(p.over(items(1000))) == list(range(1000))
        # a look every 100 items: 9 looks found 20 ms of work each (the
        # look before the first item found none)
        assert len(slept) == 9
        assert all(0.059 < s < 0.062 for s in slept)
        # work : (work + rest) is the share
        worked = 901 * 0.0002            # up to the last look
        assert abs(sum(slept) - 3 * worked) < 1e-9
        assert abs(worked / (worked + sum(slept)) - 0.25) < 1e-9

    def test_a_short_walk_never_rests(self):
        p, items, slept = self.pacer(per_item_s=0.00001)   # 1 ms a look
        list(p.over(items(900)))
        assert slept == []
