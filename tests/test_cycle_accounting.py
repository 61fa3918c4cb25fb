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
                + PACK_PARTS:
            assert key in d, key
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
            assert (key in flight.DETAIL_TOP_LEVEL) \
                != (key in flight.DETAIL_PARENT), key
        assert set(flight.DETAIL_PARENT.values()) \
            <= set(flight.DETAIL_TOP_LEVEL)
        assert set(APPLY_PARTS + PACK_PARTS) == set(flight.DETAIL_PARENT)

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
                if len([d for d in docs if d["kind"] == "fused"]) >= 6 \
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
        assert len(fused) >= 6
        for prev, cur in zip(fused[1:], fused[2:]):
            assert cur["wait_ms"] >= 45.0
            period = (cur["start"] - prev["start"]) * 1000.0
            told = prev["duration_ms"] + cur["flush_audit_ms"] \
                + cur["gc_ms"] + cur["wait_ms"]
            assert abs(period - told) < 10.0, (period, told)
        assert any(d["gc_ms"] > 0 for d in fused)   # the first cycle's
        reap = [d for d in docs if d["kind"] == "reapers"]
        assert reap and reap[0]["wait_ms"] >= 140.0
        assert all(d["kind"] != "monitor" for d in docs)

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
                  "delta_rows", "wait_ms", "gc_ms", "flush_audit_ms",
                  "blocked_ms", "offcpu_ms", "background_ms",
                  "pipeline_lag_ms", "staged_tx", "cpu_ms", "lock_holder"):
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
