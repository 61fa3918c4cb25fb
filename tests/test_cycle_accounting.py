"""The one span mechanism behind a CycleRecord (ISSUE 26): spans that end
inside a cycle add themselves to its record (phases_ms, detail_ms parts
that do not overlap), the cycle thread's waits (store lock, collector
pause, off-CPU, the background loops) are on every record, scheduler
threads' spans enter profiler annotations, and POST /debug/profile is the
operator's hook."""

import contextvars
import gc
import inspect
import json
import os
import threading
import time
import urllib.error
import urllib.request

import pytest

from cook_tpu.cluster import FakeCluster, FakeHost
from cook_tpu.config import Config
from cook_tpu.sched import Scheduler
from cook_tpu.state import Job, Pool, Resources, Store
from cook_tpu.utils import flight, locks, tracing
from cook_tpu.utils.flight import recorder

WRAPPER_LABELS = {"cook.stage", "cook.dispatch", "cook.fetch", "cook.apply"}
APPLY_PARTS = ("apply_lookup", "apply_txn", "apply_journal",
               "apply_cluster", "apply_audit")
PACK_PARTS = ("pack_index", "pack_offers", "pack_rows")
STAGE_PARTS = ("stage_put",)


def make_jobs(n, tag=0, cpus=1.0):
    return [Job(uuid=f"{tag:08d}-0000-0000-0000-{i:012d}",
                user=f"user{i % 5}", command="true", pool="default",
                priority=i % 100, resources=Resources(cpus=cpus, mem=64.0),
                submit_time_ms=1000 + i)
            for i in range(n)]


def build(tmp_path=None, n_jobs=400, n_hosts=32, depth=2, **cfg_kw):
    cfg = Config()
    cfg.pipeline.depth = depth
    for k, v in cfg_kw.items():
        setattr(cfg, k, v)
    if tmp_path is not None:
        store = Store.open(str(tmp_path), fsync=True)
        store.enable_group_commit()
    else:
        store = Store()
    store.put_pool(Pool(name="default"))
    hosts = [FakeHost(hostname=f"h{i}",
                      capacity=Resources(cpus=16.0, mem=16384.0))
             for i in range(n_hosts)]
    sched = Scheduler(store, cfg, [FakeCluster("fake-1", hosts)],
                      rank_backend="tpu")
    store.create_jobs(make_jobs(n_jobs))
    return store, sched


def fused_docs(since):
    return [d for d in recorder.recent(512)
            if d["seq"] > since and d["kind"] == "fused"]


class TestDetailSplit:
    def test_parts_sum_to_apply_and_pack_and_other_is_what_is_left(
            self, tmp_path):
        store, sched = build(tmp_path)
        sched.step_cycle()              # compiles; not the one measured
        store.create_jobs(make_jobs(40, tag=1))
        since = recorder.last_seq()
        sched.step_cycle()
        (doc,) = fused_docs(since)
        d = doc["detail_ms"]
        assert doc["jobs_placed"] > 0
        for key in ("pools", "pack", "stage", "dispatch", "fetch", "apply",
                    "pipeline", "publish", "other") + APPLY_PARTS \
                + PACK_PARTS + STAGE_PARTS:
            assert key in d, key
        # the placement of the staged inputs is a part of the stage
        assert 0.0 < d["stage_put"] <= d["stage"] + 0.05
        apply_parts = sum(d[k] for k in APPLY_PARTS)
        pack_parts = sum(d[k] for k in PACK_PARTS)
        # the parts never overlap, so they cannot exceed the whole; what
        # is missing is the fixed cost between spans (small at this size,
        # under 5 % at the production size: PERF.md section 5)
        assert apply_parts <= d["apply"] + 0.05
        assert apply_parts >= 0.75 * d["apply"]
        assert pack_parts <= d["pack"] + 0.05
        assert pack_parts >= 0.6 * d["pack"]
        top = sum(d[k] for k in flight.DETAIL_TOP_LEVEL)
        assert d["other"] >= 0.0
        assert abs(top + d["other"] - doc["duration_ms"]) < 0.05
        # the launch transaction journaled: its append is apply_journal,
        # carved out of the transaction's own key
        assert d["apply_journal"] > 0 and d["apply_txn"] > 0

    def test_nested_mapped_spans_are_carved_out_not_counted_twice(self):
        with recorder.cycle(kind="fused") as rec:
            with tracing.span("cycle.launch"):
                with tracing.span("cluster.launch-tasks"):
                    time.sleep(0.01)
                    with tracing.span("journal.append"):
                        time.sleep(0.02)
                with tracing.span("fused.launch"):     # unmapped: skipped
                    with tracing.span("store.launch-txn"):
                        with tracing.span("journal.append"):
                            time.sleep(0.01)
        d = rec.detail_ms
        assert d["apply_journal"] >= 29.0
        assert 9.0 <= d["apply_cluster"] < 20.0        # not 30: carved out
        assert d["apply_txn"] < 5.0
        parts = sum(d[k] for k in ("apply_journal", "apply_cluster",
                                   "apply_txn"))
        assert parts <= d["apply"] + 1e-6
        assert d["other"] >= 0.0

    def test_a_bare_duration_goes_through_the_same_table(self):
        with recorder.cycle(kind="fused") as rec:
            with tracing.span("cycle.launch"):
                with tracing.span("cluster.launch-tasks"):
                    time.sleep(0.005)
                    tracing.cycle_time("journal.commit-wait", 0.004)
        assert rec.detail_ms["apply_journal"] == pytest.approx(4.0)
        assert rec.blocked_ms["commit_wait"] == pytest.approx(4.0)
        assert rec.detail_ms["apply_cluster"] >= 0.9     # 5 ms less 4
        assert rec.detail_ms["apply_cluster"] < rec.detail_ms["apply"] - 3.9
        before = len(tracing.tracer.finished)
        tracing.cycle_time("journal.commit-wait", 1.0)   # no cycle: nothing
        assert len(tracing.tracer.finished) == before

    def test_group_commit_waits_land_in_commit_wait(self, tmp_path):
        store, sched = build(tmp_path)      # fsync + group commit
        sched.step_cycle()
        store.create_jobs(make_jobs(20, tag=2))
        since = recorder.last_seq()
        sched.step_cycle()
        (doc,) = fused_docs(since)
        assert doc["jobs_placed"] > 0
        assert doc["blocked_ms"]["commit_wait"] > 0.0
        assert doc["detail_ms"]["apply_journal"] \
            >= doc["blocked_ms"]["commit_wait"]
        spans = {d["span"] for d in tracing.tracer.traces(doc["trace_id"])}
        assert "journal.commit-wait" not in spans and "journal.fsync" in spans

    def test_every_mapped_key_has_one_parent_or_is_top_level(self):
        keys = set(flight.DETAIL_BY_SPAN.values())
        for key in keys:
            assert (key in flight.DETAIL_TOP_LEVEL
                    or key in flight.DETAIL_SWEEPS) \
                != (key in flight.DETAIL_PARENT), key
        assert not set(flight.DETAIL_SWEEPS) & set(flight.DETAIL_TOP_LEVEL)
        assert set(flight.DETAIL_PARENT.values()) \
            <= set(flight.DETAIL_TOP_LEVEL)
        assert set(APPLY_PARTS + PACK_PARTS + STAGE_PARTS) \
            == set(flight.DETAIL_PARENT)

    def test_finish_reads_no_span_ring_and_phases_match_it(self,
                                                           monkeypatch):
        _store, sched = build()
        sched.step_cycle()
        real = tracing.tracer.traces

        def boom(_trace_id):
            raise AssertionError("_finish scanned the span ring")
        monkeypatch.setattr(tracing.tracer, "traces", boom)
        since = recorder.last_seq()
        sched.step_cycle()
        monkeypatch.setattr(tracing.tracer, "traces", real)
        (doc,) = fused_docs(since)
        assert set(doc["phases_ms"]) == {"rank", "match", "launch"}
        # the same numbers the ring scan used to give
        want = {}
        for sp in real(doc["trace_id"]):
            phase = flight.PHASE_BY_SPAN.get(sp["span"])
            if phase:
                want[phase] = want.get(phase, 0.0) + sp["duration_ms"]
        for phase, ms in want.items():
            assert abs(doc["phases_ms"][phase] - ms) < 0.01

    def test_tracer_off_still_cycles_and_measures_nothing(self, monkeypatch):
        _store, sched = build()
        sched.step_cycle()
        monkeypatch.setattr(tracing.tracer, "enabled", False)
        since = recorder.last_seq()
        sched.step_cycle()
        (doc,) = fused_docs(since)
        assert doc["detail_ms"] == {} and doc["phases_ms"] == {}
        assert doc["duration_ms"] > 0 and doc["error"] is None

    def test_removed_probes_are_gone(self):
        from cook_tpu.ops import telemetry
        from cook_tpu.sched import fused
        assert not hasattr(telemetry, "profile_upload")
        assert not hasattr(recorder, "note_phase_detail")
        src = inspect.getsource(fused)
        assert "note_phase_detail" not in src
        assert "profile_upload" not in src


class TestCycleThreadWaits:
    def test_wall_is_cpu_plus_blocked_plus_offcpu(self):
        _store, sched = build()
        since = recorder.last_seq()
        sched.step_cycle()
        sched.step_cycle()
        for doc in fused_docs(since):
            total = doc["cpu_ms"] + sum(doc["blocked_ms"].values()) \
                + doc["offcpu_ms"]
            assert abs(total - doc["duration_ms"]) < 0.01
            assert set(flight.BLOCKED_KEYS) <= set(doc["blocked_ms"])
            assert doc["cpu_ms"] > 0
            assert doc["blocked_ms"]["device"] == doc["sync_wait_ms"]

    def test_store_lock_held_elsewhere_shows_with_its_holder(self):
        store, sched = build()
        sched.step_cycle()
        since = recorder.last_seq()
        sched.step_cycle()
        (quiet,) = fused_docs(since)
        assert quiet["blocked_ms"]["store_lock"] == 0.0
        assert quiet["lock_holder"] is None

        held = threading.Event()
        release = threading.Event()

        def hog():
            with store._lock:
                held.set()
                release.wait(0.2)      # an Event wait, not time.sleep:
                #                        the sanitizer flags sleep-in-lock
        t = threading.Thread(target=hog, name="lock-hog")
        t.start()
        assert held.wait(5)
        since = recorder.last_seq()
        sched.step_cycle()
        t.join()
        (doc,) = fused_docs(since)
        assert doc["blocked_ms"]["store_lock"] >= 150.0
        assert doc["lock_holder"] == "lock-hog"
        assert doc["duration_ms"] >= doc["blocked_ms"]["store_lock"]
        # a wait is not CPU time, and is not left in off-CPU either
        assert doc["cpu_ms"] < doc["duration_ms"] - 150.0
        assert doc["offcpu_ms"] < 50.0

    def test_other_lock_families_get_a_key_of_their_own(self):
        mon = locks.LockMonitor()
        seen = []
        mon.contention_sink = lambda *a: seen.append(a)
        lk = locks.named_rlock("index", monitor=mon)
        plain = locks.named_lock("audit", monitor=mon)
        ready, go = threading.Event(), threading.Event()

        def hold():
            with lk, plain:
                ready.set()
                go.wait(0.1)
        t = threading.Thread(target=hold, name="holder")
        t.start()
        assert ready.wait(5)
        with lk:
            pass
        with plain:
            pass
        t.join()
        assert [(n, h) for n, _s, h in seen] == [("index", "holder")] \
            or [(n, h) for n, _s, h in seen] == [("index", "holder"),
                                                  ("audit", None)]
        assert seen[0][1] >= 0.05
        # uncontended: the sink hears nothing
        seen.clear()
        with lk:
            with lk:
                pass
        assert seen == []
        # and on a record the family names the key
        with recorder.cycle(kind="fused") as rec:
            recorder.note_lock_wait("index[p1]", 0.012, "someone")
            recorder.note_lock_wait("store[p1]", 0.004, "other")
        assert rec.blocked_ms["index_lock"] == pytest.approx(12.0)
        assert rec.blocked_ms["store_lock"] == pytest.approx(4.0)
        assert rec.lock_holder == "someone"

    def test_a_collection_on_another_thread_lands_in_blocked_gc(self):
        junk = []
        for _ in range(20000):          # cyclic garbage worth a pause
            a, b = [], []
            a.append(b)
            b.append(a)
            junk.append(a)
        del junk
        with recorder.cycle(kind="fused") as rec:
            t = threading.Thread(target=gc.collect)
            t.start()
            t.join()
        doc = rec.to_doc()
        assert doc["blocked_ms"]["gc"] > 0.0
        total = doc["cpu_ms"] + sum(doc["blocked_ms"].values()) \
            + doc["offcpu_ms"]
        assert abs(total - doc["duration_ms"]) < 0.01
        # on the record's own thread the pause is counted once: as the
        # pause, not as CPU too
        with recorder.cycle(kind="fused") as own:
            gc.collect()
        assert own.blocked_ms["gc"] > 0.0
        assert own.offcpu_ms > -1.0
        # and every pause reaches the histogram, by generation
        from cook_tpu.utils.metrics import registry
        text = registry.expose()
        assert 'cook_gc_pause_seconds_count{generation="2"}' in text

    def test_a_loop_run_of_the_reapers_is_a_record_and_an_overlap(self):
        _store, sched = build()
        sched.step_cycle()
        started, finish = threading.Event(), threading.Event()

        def slow_reapers():
            started.set()
            finish.wait(5)
            sched.step_reapers()
        since = recorder.last_seq()
        t = threading.Thread(
            target=sched._background_tick, args=("reapers", slow_reapers))
        t.start()
        assert started.wait(5)
        sched.step_cycle()
        finish.set()
        t.join()
        docs = [d for d in recorder.recent(512) if d["seq"] > since]
        (fused,) = [d for d in docs if d["kind"] == "fused"]
        (reap,) = [d for d in docs if d["kind"] == "reapers"]
        assert fused["background_ms"]["reapers"] > 0.0
        assert fused["background_ms"]["reapers"] <= fused["duration_ms"] + 0.01
        assert fused["background_ms"]["monitor"] == 0.0
        assert reap["duration_ms"] >= fused["duration_ms"]
        assert reap["background_ms"] == {}
        # a cycle after the sweep ended overlaps nothing
        since = recorder.last_seq()
        sched.step_cycle()
        (after,) = fused_docs(since)
        assert after["background_ms"]["reapers"] == 0.0
        # direct callers mint no record
        since = recorder.last_seq()
        sched.step_reapers()
        sched.monitor.sweep()
        assert recorder.last_seq() == since
        from cook_tpu.utils.metrics import registry
        assert 'cook_background_loop_seconds_count{loop="reapers"}' \
            in registry.expose()

    def test_staged_tx_and_lag_on_the_applied_cycle(self):
        store, sched = build(depth=2)
        since = recorder.last_seq()
        tx0 = store._tx_id
        sched.step_cycle()
        sched.step_cycle()
        docs = fused_docs(since)
        assert docs[0]["staged_tx"] == tx0
        assert all(d["staged_tx"] is not None for d in docs)
        assert all(d["pipeline_lag_ms"] > 0 for d in docs)
        _s, sync = build(depth=0)
        since = recorder.last_seq()
        sync.step_cycle()
        (doc,) = fused_docs(since)
        assert doc["staged_tx"] is not None and doc["pipeline_lag_ms"] > 0


def overruns(loop):
    from cook_tpu.utils.metrics import registry
    return sum(v for labels, v in registry.series("cook_loop_overrun")
               if labels.get("loop") == loop)


class VirtualTime:
    """``time`` as sched/scheduler.py sees it, with a ``perf_counter``
    that each thread owns: it moves only when that thread waits on the
    stop event or a fake tick says so.  The loop under test then runs
    at full speed and to the microsecond, whatever the machine is doing."""

    def __init__(self):
        self._local = threading.local()

    def __getattr__(self, name):
        return getattr(time, name)

    def perf_counter(self):
        return getattr(self._local, "now", 0.0)

    def advance(self, seconds):
        self._local.now = self.perf_counter() + seconds


class VirtualStop:
    """Scheduler._stop on that clock: a wait shorter than an hour passes
    in virtual time; the hour-long waits of the loops not under test
    block until the stop, as on a real Event."""

    def __init__(self, clock):
        self._clock = clock
        self._event = threading.Event()
        self.set = self._event.set
        self.is_set = self._event.is_set

    def wait(self, timeout=None):
        if timeout is None or timeout >= 3600.0:
            return self._event.wait(timeout)
        self._clock.advance(timeout)
        return self._event.is_set()


class FakeTicks:
    """A jax-free scheduler whose loop ``loop`` runs a fake tick that
    takes ``durations[i]`` (the last one from then on) and is a flight
    record of the loop's kind.  Every other loop's interval is an hour.
    With a ``monkeypatch`` the loops run on virtual time."""

    INTERVAL_FIELD = {"cycle": "match_interval_seconds",
                      "reapers": "lingering_task_interval_seconds",
                      "monitor": "monitor_interval_seconds"}

    def __init__(self, interval, durations, loop="cycle", monkeypatch=None):
        from cook_tpu.sched import scheduler as scheduler_mod
        cfg = Config()
        cfg.cycle_mode = "split"
        cfg.default_matcher.backend = "cpu"
        cfg.rank_interval_seconds = 3600.0
        for field in self.INTERVAL_FIELD.values():
            setattr(cfg, field, 3600.0)
        if loop in self.INTERVAL_FIELD:
            setattr(cfg, self.INTERVAL_FIELD[loop], interval)
        store = Store()
        store.put_pool(Pool(name="default"))
        host = FakeHost(hostname="h0", capacity=Resources(cpus=1.0, mem=64.0))
        self.sched = Scheduler(store, cfg, [FakeCluster("fake-1", [host])],
                               rank_backend="cpu")
        # the rebalancer's interval is the one that is a callable
        self.params = type("Params", (), {"interval_seconds": 3600.0})()
        self.sched.rebalancer.effective_params = lambda: self.params
        self.clock = time
        if monkeypatch is not None:
            self.clock = VirtualTime()
            monkeypatch.setattr(scheduler_mod, "time", self.clock)
            self.sched._stop = VirtualStop(self.clock)
        self.loop = loop
        self.durations = list(durations)
        self.starts = []
        self.stop_after = None
        self.done = threading.Event()
        self.since = recorder.last_seq()
        self.overruns_before = overruns(loop)
        # a background loop's record (kind = the loop's name) is opened
        # by Scheduler._background_tick around the tick
        self.kind = "match" if loop == "cycle" else loop
        if loop == "cycle":
            self.sched.step_match = self.cycle_tick
        elif loop == "reapers":
            self.sched.step_reapers = self.tick
        elif loop == "monitor":
            self.sched.monitor.sweep = self.tick
        elif loop == "rebalance":
            self.params.interval_seconds = interval
            self.sched.step_rebalance = self.tick
        else:       # the optimizer's loop exists only where configured
            from cook_tpu.sched import OptimizerConfig
            cfg.optimizer = OptimizerConfig(interval_seconds=interval)
            self.sched.step_optimize = self.tick

    def tick(self):
        i = len(self.starts)
        self.starts.append(self.clock.perf_counter())
        took = self.durations[min(i, len(self.durations) - 1)]
        if self.clock is time:
            time.sleep(took)
        else:
            self.clock.advance(took)
        if len(self.starts) == self.stop_after:
            self.sched._stop.set()
            self.done.set()

    def cycle_tick(self):
        with recorder.cycle(kind=self.kind):
            self.tick()

    def run(self, ticks):
        """``ticks`` ticks, the last of which stops the loops; the flight
        records of this loop's ticks, oldest first."""
        self.stop_after = ticks
        self.sched.run()
        try:
            assert self.done.wait(20.0)
        finally:
            self.sched.shutdown()
        assert len(self.starts) == ticks
        assert not any(t.is_alive() for t in self.sched._threads)
        return [d for d in recorder.recent(512)
                if d["seq"] > self.since and d["kind"] == self.kind]

    def overruns(self):
        return overruns(self.loop) - self.overruns_before


class TestTheTick:
    def test_run_loop_fills_the_tick_fields_and_they_give_the_period(self):
        _store, sched = build(n_jobs=30, n_hosts=2,
                              match_interval_seconds=0.05,
                              lingering_task_interval_seconds=0.15,
                              monitor_interval_seconds=3600.0)
        sched.step_cycle()              # compile outside the loop
        since = recorder.last_seq()
        t_before = time.time()
        sched.run()
        try:
            assert sched.started_s is not None
            assert t_before <= sched.started_s <= time.time()
            deadline = time.time() + 20
            while time.time() < deadline:
                docs = [d for d in recorder.recent(512) if d["seq"] > since]
                if len([d for d in docs if d["kind"] == "fused"]) >= 8 \
                        and any(d["kind"] == "reapers" for d in docs):
                    break
                time.sleep(0.05)
            names = {t.name for t in sched._threads}
            assert {"cook-cycle", "cook-reapers", "cook-monitor",
                    "cook-rebalance"} <= names
            # the OS threads carry the names too: a profiler trace names
            # each line after its OS thread
            for t in sched._threads:
                comm = f"/proc/self/task/{t.native_id}/comm"
                if os.path.exists(comm):
                    with open(comm) as f:
                        assert f.read().strip() == t.name[:15]
        finally:
            sched.shutdown()
        docs = [d for d in recorder.recent(512) if d["seq"] > since]
        fused = [d for d in docs if d["kind"] == "fused"]
        assert len(fused) >= 8
        periods = []
        for prev, cur in zip(fused[1:], fused[2:]):
            period = (cur["start"] - prev["start"]) * 1000.0
            told = prev["duration_ms"] + cur["flush_audit_ms"] \
                + cur["gc_ms"] + cur["wait_ms"]
            assert abs(period - told) < 10.0, (period, told)
            if not cur["overrun_ms"]:
                periods.append(period)
        # these cycles take a few ms: start to start is the interval
        assert len(periods) >= 4
        periods.sort()
        assert abs(periods[len(periods) // 2] - 50.0) < 10.0, periods
        assert any(d["gc_ms"] > 0 for d in fused)   # the first cycle's
        reap = [d for d in docs if d["kind"] == "reapers"]
        assert reap and reap[0]["wait_ms"] >= 140.0
        assert all(d["kind"] != "monitor" for d in docs)

    def test_a_short_tick_is_followed_by_the_rest_of_the_interval(
            self, monkeypatch):
        fake = FakeTicks(1.0, [0.3], monkeypatch=monkeypatch)
        docs = fake.run(5)
        # start to start is the interval; interval + tick would be 1.3
        assert fake.starts == pytest.approx([1.0, 2.0, 3.0, 4.0, 5.0])
        assert [d["wait_ms"] for d in docs] \
            == pytest.approx([1000.0] + [700.0] * 4)
        assert [d["overrun_ms"] for d in docs] == [0.0] * 5
        assert fake.overruns() == 0

    def test_a_tick_longer_than_the_interval_is_followed_at_once(
            self, monkeypatch):
        fake = FakeTicks(1.0, [1.5], monkeypatch=monkeypatch)
        docs = fake.run(4)
        assert fake.starts == pytest.approx([1.0, 2.5, 4.0, 5.5])
        assert [d["wait_ms"] for d in docs] == [1000.0, 0.0, 0.0, 0.0]
        assert [d["overrun_ms"] for d in docs] \
            == pytest.approx([0.0, 500.0, 500.0, 500.0])
        assert fake.overruns() == 3

    @pytest.mark.parametrize("loop", ["cycle", "reapers", "monitor"])
    def test_one_long_tick_costs_one_immediate_tick_and_no_burst(
            self, monkeypatch, loop):
        fake = FakeTicks(1.0, [0.1, 0.1, 2.5, 0.1], loop=loop,
                         monkeypatch=monkeypatch)
        docs = fake.run(7)
        # the tick after the 2.5 s one starts as it ends, 1.5 s late, and
        # the schedule goes on from THERE: catching up with the old grid
        # would put ticks at 5.5, 5.6, 5.7 and 6.0
        assert fake.starts == pytest.approx(
            [1.0, 2.0, 3.0, 5.5, 6.5, 7.5, 8.5])
        assert [d["overrun_ms"] for d in docs] \
            == pytest.approx([0.0, 0.0, 0.0, 1500.0, 0.0, 0.0, 0.0])
        assert [d["wait_ms"] for d in docs] == pytest.approx(
            [1000.0, 900.0, 900.0, 0.0, 900.0, 900.0, 900.0])
        assert fake.overruns() == 1

    def test_a_callable_interval_that_changes_takes_effect_next_turn(
            self, monkeypatch):
        fake = FakeTicks(1.0, [0.1], loop="rebalance",
                         monkeypatch=monkeypatch)
        tick = fake.sched.step_rebalance

        def rebalance():
            tick()
            if len(fake.starts) == 3:
                fake.params.interval_seconds = 5.0
        fake.sched.step_rebalance = rebalance
        fake.run(5)
        assert fake.starts == pytest.approx([1.0, 2.0, 3.0, 8.0, 13.0])

    def test_an_immediate_loop_ticks_at_once_and_then_on_the_period(
            self, monkeypatch):
        fake = FakeTicks(1.0, [0.4], loop="optimize",
                         monkeypatch=monkeypatch)
        docs = fake.run(3)
        assert fake.starts == pytest.approx([0.0, 1.0, 2.0])
        assert [d["wait_ms"] for d in docs] \
            == pytest.approx([0.0, 600.0, 600.0])

    @pytest.mark.parametrize("interval, tick_s", [(0.0005, 0.02),
                                                  (3600.0, 0.0)],
                             ids=["zero-wait", "long-wait"])
    def test_shutdown_returns_promptly(self, interval, tick_s):
        fake = FakeTicks(interval, [tick_s])
        fake.sched.run()
        if interval < 1.0:                  # in a tick or a zero wait
            deadline = time.time() + 20
            while len(fake.starts) < 3 and time.time() < deadline:
                time.sleep(0.005)
            assert len(fake.starts) >= 3
        else:
            time.sleep(0.05)                # deep in the first wait
        t0 = time.perf_counter()
        fake.sched.shutdown()
        assert time.perf_counter() - t0 < 2.0
        assert not any(t.is_alive() for t in fake.sched._threads)
        n = len(fake.starts)
        time.sleep(0.05)
        assert len(fake.starts) == n

    def test_a_background_loops_first_run_is_one_interval_after_start(self):
        fake = FakeTicks(0.15, [0.0], loop="reapers")
        docs = fake.run(1)
        first = (docs[0]["start"] - fake.sched.started_s) * 1000.0
        assert first >= 145.0, first
        assert docs[0]["wait_ms"] >= 140.0 and docs[0]["overrun_ms"] == 0.0

    def test_health_serves_the_loop_threads_start(self):
        from cook_tpu.rest import ApiServer, CookApi
        store, sched = build(n_jobs=5, n_hosts=1)
        srv = ApiServer(CookApi(store, scheduler=sched, admins=["admin"]))
        srv.start()
        try:
            url = f"http://127.0.0.1:{srv.port}/debug/health"
            with urllib.request.urlopen(url) as r:
                assert json.load(r)["scheduler"] == {"started_s": None}
            sched.started_s = 1234.5
            with urllib.request.urlopen(url) as r:
                assert json.load(r)["scheduler"] == {"started_s": 1234.5}
        finally:
            srv.stop()


class FakeAnnotation:
    names = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.names.append(self.name)

    def __exit__(self, *exc):
        return False


class TestProfilerClock:
    def test_scheduler_threads_annotate_and_never_with_a_wrapper_label(
            self, monkeypatch):
        _store, sched = build(n_jobs=1200)      # more than two cycles place
        sched.step_cycle()
        monkeypatch.setattr(tracing, "_annotation_cls", FakeAnnotation)
        FakeAnnotation.names = []

        def as_a_scheduler_thread():
            tracing.annotate_spans()
            sched.step_cycle()
            sched._background_tick("reapers", sched.step_reapers)
        contextvars.copy_context().run(as_a_scheduler_thread)
        names = set(FakeAnnotation.names)
        assert {"cycle", "fused.cycle", "fused.pack", "fused.stage",
                "fused.dispatch", "fused.fetch", "cycle.launch",
                "pack.rows", "apply.lookup", "store.launch-txn"} <= names
        assert not names & WRAPPER_LABELS
        # a thread that never said it is a scheduler thread (REST) does not
        FakeAnnotation.names = []
        sched.step_cycle()
        with tracing.span("http.request"):
            pass
        assert FakeAnnotation.names == []

    def test_no_span_in_the_tree_carries_a_wrapper_label(self):
        import pathlib
        from cook_tpu.analysis import registry as doc_registry
        root = pathlib.Path(inspect.getfile(flight)).parents[1]
        names = doc_registry.harvest_spans(root)
        assert len(names) > 30
        assert not names & WRAPPER_LABELS
        # every name the table maps is a span of the tree, but for the
        # one bare duration (tracing.cycle_time, state/store.py)
        assert set(flight.DETAIL_BY_SPAN) - names == {"journal.commit-wait"}

    def test_a_jax_free_process_never_imports_jax_for_an_annotation(
            self, monkeypatch):
        import sys
        monkeypatch.setattr(tracing, "_annotation_cls", None)
        monkeypatch.delitem(sys.modules, "jax")

        def probe():
            tracing.annotate_spans()
            return tracing._annotation("fused.pack")
        assert contextvars.copy_context().run(probe) is None
        assert "jax" not in sys.modules

    def test_real_annotations_work_where_jax_is(self, monkeypatch):
        monkeypatch.setattr(tracing, "_annotation_cls", None)

        def probe():
            tracing.annotate_spans()
            with tracing.span("fused.pack") as sp:
                pass
            return sp
        sp = contextvars.copy_context().run(probe)
        assert sp.duration_s is not None
        import jax
        assert tracing._annotation_cls is jax.profiler.TraceAnnotation

    def test_the_warm_up_annotates_its_parts_and_only_while_it_runs(
            self, monkeypatch):
        monkeypatch.setattr(tracing, "_annotation_cls", FakeAnnotation)
        FakeAnnotation.names = []
        cfg = Config()
        cfg.pipeline.warmup_tasks = cfg.pipeline.warmup_hosts = 64
        # the takeover thread, which is no scheduler thread
        Scheduler(Store(), config=cfg)
        assert {"fused.warmup", "warmup.cycle", "warmup.delta_apply",
                "warmup.delta_append"} <= set(FakeAnnotation.names)
        FakeAnnotation.names = []
        with tracing.span("http.request"):
            pass
        assert FakeAnnotation.names == []


class TestProfileEndpoint:
    @pytest.fixture
    def server(self, tmp_path, monkeypatch):
        import jax
        from cook_tpu.rest import ApiServer, CookApi
        calls = []
        monkeypatch.setattr(jax.profiler, "start_trace",
                            lambda path, **kw: calls.append(("start", path)))
        monkeypatch.setattr(jax.profiler, "stop_trace",
                            lambda: calls.append(("stop",)))
        store = Store.open(str(tmp_path / "data"))
        store.put_pool(Pool(name="default"))
        srv = ApiServer(CookApi(store, admins=["admin"]))
        srv.start()
        yield srv, calls, tmp_path / "data"
        srv.stop()
        store.close()

    @staticmethod
    def post(srv, body, user):
        req = urllib.request.Request(
            f"http://127.0.0.1:{srv.port}/debug/profile",
            data=json.dumps(body).encode(), method="POST",
            headers={"Content-Type": "application/json",
                     "X-Cook-User": user})
        with urllib.request.urlopen(req) as r:
            return json.load(r)

    def test_admin_only_one_session_at_a_time(self, server):
        srv, calls, data_dir = server
        with pytest.raises(urllib.error.HTTPError) as e:
            self.post(srv, {"seconds": 1}, "mallory")
        assert e.value.code == 403 and calls == []
        with pytest.raises(urllib.error.HTTPError) as e:
            self.post(srv, {"seconds": 0}, "admin")
        assert e.value.code == 400
        with pytest.raises(urllib.error.HTTPError) as e:
            self.post(srv, {"seconds": "soon"}, "admin")
        assert e.value.code == 400
        got = self.post(srv, {"seconds": 0.3}, "admin")
        assert got["directory"].startswith(str(data_dir / "profiles"))
        assert calls == [("start", got["directory"])]
        with pytest.raises(urllib.error.HTTPError) as e:
            self.post(srv, {"seconds": 0.3}, "admin")
        assert e.value.code == 409
        deadline = time.time() + 5
        while ("stop",) not in calls and time.time() < deadline:
            time.sleep(0.02)
        assert calls[-1] == ("stop",)
        time.sleep(0.05)
        again = self.post(srv, {"seconds": 0.1}, "admin")   # free again
        assert again["seconds"] == 0.1
        deadline = time.time() + 5
        while calls.count(("stop",)) < 2 and time.time() < deadline:
            time.sleep(0.02)
        assert calls.count(("stop",)) == 2


def test_names_the_yardstick_reads_are_stable():
    """docs/OBSERVABILITY.md "Kept stable": what benchmarks/ reads of the
    program by name."""
    from cook_tpu.parallel import sharded
    from cook_tpu.sched import scheduler as scheduler_mod
    from cook_tpu.sched.fused import FusedCycleDriver
    sig = lambda name: list(inspect.signature(
        getattr(FusedCycleDriver, name)).parameters)
    assert sig("stage") == ["self", "scheduler", "exclude", "avail_delta",
                            "token_delta"]
    assert sig("dispatch_group") == ["self", "sg"]
    assert sig("fetch_group") == ["self", "gd"]
    assert sig("apply_group") == ["self", "scheduler", "gd", "queues",
                                  "results", "reconciler"]
    doc = flight.CycleRecord(1, "fused").to_doc()
    for field in ("kind", "start", "duration_ms", "detail_ms",
                  "sync_wait_ms", "h2d_bytes", "path", "error", "faults",
                  "delta_rows", "wait_ms", "overrun_ms", "gc_ms",
                  "flush_audit_ms",
                  "blocked_ms", "offcpu_ms", "background_ms",
                  "pipeline_lag_ms", "staged_tx", "cpu_ms", "lock_holder",
                  "pools", "status_txns", "status_updates"):
        assert field in doc, field
    by_key = {}
    for span, key in flight.DETAIL_BY_SPAN.items():
        by_key.setdefault(key, set()).add(span)
    assert by_key["pack"] == {"fused.pack"}
    assert by_key["stage"] == {"fused.stage"}
    assert by_key["apply"] == {"cycle.launch"}
    assert "def cycle_body(" in inspect.getsource(sharded)
    src = inspect.getsource(scheduler_mod)
    assert '"cook_kernel_fallback"' in src
    from cook_tpu.ops import telemetry
    assert '"cook_jit_compile"' in inspect.getsource(telemetry)
