"""A submission accounts for its own wall: the REST handler's time splits
into a contended store lock (and who held it), the spin while another
thread delivers the queued events, its own delivery, the group-commit
wait, its CPU and the rest off the CPU; who delivered whose events is
counted; a cycle's spin on the notify lock lands on its record; the
tick's audit flush and collection are spans; and the benchmark's
``histogram_mean`` reader turns the phase histogram into a mean per
submission."""

import importlib.util
import os
import threading
import time

import pytest

from cook_tpu.client import JobClient
from cook_tpu.rest import ApiServer, CookApi
from cook_tpu.rest import instrument
from cook_tpu.rest.instrument import request_log
from cook_tpu.state import Job, Pool, Resources, Store
from cook_tpu.utils import flight, tracing
from cook_tpu.utils.flight import recorder
from cook_tpu.utils.metrics import parse_exposition, registry

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = instrument.WAIT_PHASES + ("cpu", "offcpu")


def series(name, **labels):
    """Value of the exposed series ``name`` with exactly these labels
    (0.0 when absent)."""
    for n, lbl, v in parse_exposition(registry.expose()):
        if n == name and lbl == labels:
            return v
    return 0.0


def job(i, tag=7):
    return Job(uuid=f"{tag:08d}-0000-0000-0000-{i:012d}", user="bob",
               command="true", pool="default",
               resources=Resources(cpus=1.0, mem=64.0))


def spec():
    return {"command": "true", "cpus": 1, "mem": 64}


@pytest.fixture(autouse=True)
def capturing():
    """The capture ring on, whatever an earlier test's brownout left."""
    was = request_log.capture
    request_log.capture = True
    yield
    request_log.capture = was


@pytest.fixture
def served(tmp_path):
    """A journaled store with group commit behind a REST server, as the
    daemon ships it, and a client."""
    store = Store.open(str(tmp_path / "data"), fsync=True)
    store.enable_group_commit()
    store.put_pool(Pool(name="default"))
    srv = ApiServer(CookApi(store, admins=["admin"]))
    srv.start()
    client = JobClient(srv.url, user="alice")
    yield store, client
    client.close()
    srv.stop()
    store.close()


def last_submission():
    recs = [r for r in request_log.snapshot(50)["recent"]
            if r["method"] == "POST" and r["endpoint"] == "/jobs"]
    return recs[-1]


def record_of(client):
    """The capture record of the client's last request: the response
    goes out before the handler's own accounting (RequestObserver.end)
    has run, so it is waited for."""
    deadline = time.time() + 10
    while True:
        for r in reversed(request_log.snapshot(256)["recent"]):
            if r["request_id"] == client.last_request_id:
                return r
        assert time.time() < deadline, "no capture record"
        time.sleep(0.005)


def eventually(read, want):
    """``read()`` once it equals ``want`` (the lock waits reach the
    registry when a request or record ends), or its last value."""
    deadline = time.time() + 10
    while True:
        flight.publish_waits()
        got = read()
        if got == want or time.time() > deadline:
            return got
        time.sleep(0.005)


class TestRequestParts:
    def test_a_held_store_lock_is_the_requests_lock_wait_and_its_holder(
            self, served):
        store, client = served
        client.submit([spec()])       # connection and first-use costs
        before = series("cook_lock_wait_seconds_count", lock="store",
                        waiter="http", holder="cycle")
        held, release = threading.Event(), threading.Event()

        def cycle():
            with store._lock:
                held.set()
                release.wait(0.2)     # an Event wait: the sanitizer flags
                #                       a sleep under the lock
        t = threading.Thread(target=cycle, name="cook-cycle")
        t.start()
        assert held.wait(5)
        client.submit([spec()])
        t.join()
        rec = record_of(client)
        assert rec["phases_ms"]["store.lock-wait"] >= 45.0
        assert rec["duration_ms"] >= rec["phases_ms"]["store.lock-wait"]
        assert eventually(lambda: series(
            "cook_lock_wait_seconds_count", lock="store", waiter="http",
            holder="cycle"), before + 1) == before + 1
        assert series("cook_lock_wait_seconds_sum", lock="store",
                      waiter="http", holder="cycle") >= 0.045

    def test_a_drain_elsewhere_is_the_requests_notify_wait_and_counted(
            self, served):
        store, client = served
        client.submit([spec()])
        entered, queued = threading.Event(), threading.Event()
        gate = {"armed": True}

        def slow_subscriber(_tx_id, _events):
            # the cycle thread holds store.notify while it delivers: the
            # first delivery on it waits for the submission's batch
            if threading.current_thread().name == "cook-cycle" \
                    and gate["armed"]:
                gate["armed"] = False
                entered.set()
                for _ in range(1000):
                    if store._event_queue:
                        break
                    queued.wait(0.005)
        store.subscribe(slow_subscriber)
        before = series("cook_store_event_batches_total", drainer="cycle",
                        owner="http")
        records = []

        def cycle():
            with recorder.cycle(kind="fused") as rec:
                store.create_jobs([job(1)])
            records.append(rec)
        t = threading.Thread(target=cycle, name="cook-cycle")
        t.start()
        assert entered.wait(5)
        client.submit([spec()])       # its batch waits on the cycle's drain
        t.join()
        rec = record_of(client)
        assert rec["phases_ms"]["store.notify-wait"] > 0.0
        assert series("cook_store_event_batches_total", drainer="cycle",
                      owner="http") == before + 1
        (cyc,) = records
        assert cyc.foreign_event_batches == 1
        assert cyc.to_doc()["foreign_event_batches"] == 1

    def test_parts_sum_to_the_wall_and_none_exceeds_it(self, served):
        store, client = served
        recs = []
        for i in range(6):
            if i % 2:
                held, release = threading.Event(), threading.Event()

                def cycle():
                    with store._lock:
                        held.set()
                        release.wait(0.01)
                t = threading.Thread(target=cycle, name="cook-cycle")
                t.start()
                assert held.wait(5)
                client.submit([spec(), spec()])
                t.join()
            else:
                client.submit([spec()])
            recs.append(record_of(client))
        assert any("store.lock-wait" in r["phases_ms"] for r in recs)
        for r in recs:
            phases, wall = r["phases_ms"], r["duration_ms"]
            assert phases["cpu"] > 0.0
            assert "journal.commit-wait" in phases
            for name in PARTS:
                assert 0.0 <= phases.get(name, 0.0) <= wall + 0.002
            # offcpu is what is left: the others never overlap, so they
            # fit in the wall (to the capture ring's 1 us rounding)
            inside = sum(phases.get(n, 0.0) for n in PARTS if n != "offcpu")
            assert inside <= wall + 0.005
            assert sum(phases.get(n, 0.0) for n in PARTS) \
                == pytest.approx(wall, abs=0.005)

    def test_a_phase_that_did_not_occur_is_not_observed(self):
        phases = {"journal.append": 0.001}
        request_log.end(method="POST", endpoint="/jobs", status=200,
                        duration_s=0.004, phases=phases, params={},
                        request_id="r", trace_id=None, user="u",
                        bytes_in=1, bytes_out=1, cpu_s=0.003)
        assert phases["cpu"] == pytest.approx(0.003)
        assert phases["offcpu"] == pytest.approx(0.001)
        rec = last_submission()
        assert set(rec["phases_ms"]) == {"journal.append", "cpu", "offcpu"}
        # the CPU a wait burned is the wait's, not the request's own
        phases = {"store.notify-wait": 0.002, tracing.WAIT_CPU: 0.0015}
        request_log.end(method="POST", endpoint="/jobs", status=200,
                        duration_s=0.004, phases=phases, params={},
                        request_id="r", trace_id=None, user="u",
                        bytes_in=1, bytes_out=1, cpu_s=0.0025)
        assert phases["cpu"] == pytest.approx(0.001)
        assert phases["offcpu"] == pytest.approx(0.001)
        assert tracing.WAIT_CPU not in last_submission()["phases_ms"]

    def test_a_coarse_cpu_clock_still_leaves_the_parts_summing_to_the_wall(
            self):
        # a thread CPU clock of 10 ms ticks read one tick for a request of
        # 4 ms: its cpu is what its wall leaves outside its waits
        phases = {"store.drain": 0.001}
        request_log.end(method="POST", endpoint="/jobs", status=200,
                        duration_s=0.004, phases=phases, params={},
                        request_id="r", trace_id=None, user="u",
                        bytes_in=1, bytes_out=1, cpu_s=0.01)
        assert phases["cpu"] == pytest.approx(0.003)
        assert phases["offcpu"] == 0.0
        assert phases["cpu"] + phases["store.drain"] == pytest.approx(0.004)
        # and a tick that fell inside a wait leaves the request's own 0
        phases = {"store.notify-wait": 0.002, tracing.WAIT_CPU: 0.01}
        request_log.end(method="POST", endpoint="/jobs", status=200,
                        duration_s=0.004, phases=phases, params={},
                        request_id="r", trace_id=None, user="u",
                        bytes_in=1, bytes_out=1, cpu_s=0.0)
        assert phases["cpu"] == 0.0
        assert phases["offcpu"] == pytest.approx(0.002)


class FakeAnnotation:
    names = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        FakeAnnotation.names.append(
            (threading.current_thread().name, self.name))

    def __exit__(self, *exc):
        return False


def test_a_submission_is_on_the_profilers_clock_on_the_http_line(
        served, monkeypatch):
    store, client = served
    monkeypatch.setattr(tracing, "_annotation_cls", FakeAnnotation)
    FakeAnnotation.names = []
    held, release = threading.Event(), threading.Event()

    def cycle():
        with store._lock:
            held.set()
            release.wait(0.02)
    t = threading.Thread(target=cycle, name="cook-cycle")
    t.start()
    assert held.wait(5)
    client.submit([spec()])
    t.join()
    record_of(client)
    on_http = {name for thread, name in FakeAnnotation.names
               if thread == "cook-http"}
    assert {"http.request", "store.lock-wait", "store.drain",
            "journal.commit-wait"} <= on_http


class TestLockWaits:
    def test_a_contended_rlock_with_nested_holds_reports_once_and_frees(self):
        from cook_tpu.utils import locks
        mon = locks.LockMonitor()
        seen = []
        mon.contention_sink = lambda *a: seen.append(a)
        lk = locks.named_rlock("store", monitor=mon)
        held, go = threading.Event(), threading.Event()

        def hold():
            with lk:
                held.set()
                go.wait(0.05)
        t = threading.Thread(target=hold, name="cook-cycle")
        t.start()
        assert held.wait(5)
        with lk:                       # contended: reported at acquire
            with lk:                   # re-entrant: never contends
                with lk:
                    pass
            assert len(seen) == 1
        t.join()
        (name, seconds, holder, cpu_s), = seen
        assert (name, holder) == ("store", "cook-cycle")
        assert seconds >= 0.03 and 0.0 <= cpu_s < seconds
        # the outermost release let go: another thread takes it at once
        got = []
        t = threading.Thread(target=lambda: got.append(
            lk.acquire(timeout=1.0) and (lk.release() or True)))
        t.start()
        t.join()
        assert got == [True] and len(seen) == 1
        assert not lk.locked()

    def test_a_lock_wait_inside_a_timed_wait_is_a_part_of_it(self):
        from cook_tpu.utils import locks
        mon = locks.LockMonitor()
        seen = []
        mon.contention_sink = lambda *a: seen.append(a)
        lk = locks.named_rlock("store", monitor=mon)
        held, go = threading.Event(), threading.Event()

        def hold():
            with lk:
                held.set()
                go.wait(0.05)
        t = threading.Thread(target=hold, name="cook-http")
        t.start()
        assert held.wait(5)
        with tracing.collect_phases() as phases:
            with tracing.timed_wait("store.notify-wait"):
                with lk:
                    pass
        t.join()
        # no clock and no sink of its own: the frame has the whole wait
        assert seen == []
        assert "store.lock-wait" not in phases
        assert phases["store.notify-wait"] >= 0.03
        assert not tracing.in_timed_wait()


@pytest.mark.parametrize("name,role", [
    ("cook-cycle", "cycle"), ("cook-monitor", "monitor"),
    ("cook-reapers", "reapers"), ("cook-rebalance", "rebalance"),
    ("cook-http", "http"), ("cook-group-commit", "committer"),
    ("cook-group-commit-p3", "committer"),
    ("Thread-7 (process_request_thread)", "other"), (None, "other"),
    ("thread-140211", "other")])
def test_thread_roles_are_a_fixed_set(name, role):
    assert tracing.thread_role(name) == role
    assert role in tracing.ROLES


class TestCycleSide:
    def test_a_cycles_spin_on_the_notify_lock_is_blocked_notify(self):
        store = Store()
        store.put_pool(Pool(name="default"))
        held, release = threading.Event(), threading.Event()

        def drainer():
            with store._notify_lock:
                held.set()
                release.wait(0.2)
        t = threading.Thread(target=drainer, name="cook-http")
        t.start()
        assert held.wait(5)
        with recorder.cycle(kind="fused") as rec:
            store.create_jobs([job(2)])
        t.join()
        doc = rec.to_doc()
        assert doc["blocked_ms"]["notify"] >= 40.0
        total = doc["cpu_ms"] + sum(doc["blocked_ms"].values()) \
            + doc["offcpu_ms"]
        assert abs(total - doc["duration_ms"]) < 0.01
        # the spin's CPU is the wait's, not the cycle's own
        assert doc["cpu_ms"] < doc["duration_ms"] - 40.0
        assert doc["offcpu_ms"] > -1.0
        # its own batch, delivered by itself once the lock was free
        assert doc["foreign_event_batches"] == 0

    def test_a_quiet_record_reads_zero_notify_not_absent(self):
        with recorder.cycle(kind="fused") as rec:
            pass
        assert rec.to_doc()["blocked_ms"]["notify"] == 0.0
        assert "notify" in flight.BLOCKED_KEYS
        assert rec.to_doc()["foreign_event_batches"] == 0

    def test_the_ticks_audit_flush_and_collection_are_spans(self):
        from cook_tpu.cluster import FakeCluster, FakeHost
        from cook_tpu.config import Config
        from cook_tpu.sched import Scheduler
        store = Store()
        store.put_pool(Pool(name="default"))
        hosts = [FakeHost(hostname=f"h{i}",
                          capacity=Resources(cpus=16.0, mem=16384.0))
                 for i in range(4)]
        sched = Scheduler(store, Config(), [FakeCluster("fake-1", hosts)],
                          rank_backend="tpu")
        store.create_jobs([job(i, tag=9) for i in range(20)])
        sched.step_cycle()
        sched._gc_collect_due = True
        sched.maintain_gc()
        flush = tracing.tracer.recent(1, name="tick.flush-audit")
        gc_span = tracing.tracer.recent(1, name="tick.gc")
        assert flush and gc_span
        since = recorder.last_seq()
        sched.step_cycle()
        (doc,) = [d for d in recorder.recent(8)
                  if d["seq"] > since and d["kind"] == "fused"]
        # the tick's note times the span from outside it
        assert doc["gc_ms"] >= gc_span[0]["duration_ms"] - 0.001
        assert "tick.flush-audit" not in flight.DETAIL_BY_SPAN
        assert "tick.gc" not in flight.DETAIL_BY_SPAN


# ------------------------------------------------- the benchmark's reader
def histogram_mean():
    path = os.path.join(ROOT, "benchmarks", "readers", "histogram_mean.py")
    modspec = importlib.util.spec_from_file_location("histogram_mean", path)
    mod = importlib.util.module_from_spec(modspec)
    modspec.loader.exec_module(mod)
    return mod.read


PHASE = "cook_http_phase_seconds_sum"
REQS = "cook_http_request_duration_seconds_count"
POST = 'endpoint="/jobs",method="POST"'
SPEC = {"sum": "cook_http_phase_seconds",
        "sum_labels": {"endpoint": "/jobs", "method": "POST",
                       "phase": "store.lock-wait"},
        "count": "cook_http_request_duration_seconds",
        "count_labels": {"endpoint": "/jobs", "method": "POST"},
        "scale": 1000.0,
        "present_labels": {"endpoint": "/jobs", "method": "POST",
                           "phase": "cpu"}}


def ctx(before, after):
    return {"metrics_before": before, "metrics_after": after}


class TestHistogramMean:
    def test_label_subset_matching_over_the_window(self):
        read = histogram_mean()
        before = {f'{PHASE}{{{POST},phase="store.lock-wait"}}': 0.5,
                  f"{REQS}{{{POST}}}": 100.0,
                  f'{REQS}{{endpoint="/jobs",method="GET"}}': 7.0}
        after = {f'{PHASE}{{{POST},phase="store.lock-wait"}}': 0.9,
                 f'{PHASE}{{{POST},phase="cpu"}}': 0.2,
                 f'{PHASE}{{endpoint="/jobs",method="GET",'
                 f'phase="store.lock-wait"}}': 5.0,
                 f"{REQS}{{{POST}}}": 110.0,
                 f'{REQS}{{endpoint="/jobs",method="GET"}}': 900.0}
        # 0.4 s over the 10 POSTs of the window: 40 ms a submission; the
        # GET series of the same names match neither side
        assert read(ctx(before, after), SPEC) == pytest.approx(40.0)
        # a subset of labels takes every series that holds it
        both = dict(SPEC, sum_labels={"endpoint": "/jobs",
                                      "phase": "store.lock-wait"},
                    count_labels={"endpoint": "/jobs"})
        assert read(ctx(before, after), both) \
            == pytest.approx(1000.0 * 5.4 / 903.0)

    def test_no_submissions_in_the_window_read_as_nothing(self):
        read = histogram_mean()
        m = {f'{PHASE}{{{POST},phase="store.lock-wait"}}': 0.5,
             f"{REQS}{{{POST}}}": 100.0}
        assert read(ctx(m, dict(m)), SPEC) is None
        assert read(ctx({}, {}), SPEC) is None

    def test_a_phase_absent_from_some_or_all_requests_counts_as_zero(self):
        read = histogram_mean()
        # the phase occurred in 2 of 8 requests: its mean is over all 8
        before = {f"{REQS}{{{POST}}}": 2.0}
        after = {f'{PHASE}{{{POST},phase="store.lock-wait"}}': 0.016,
                 f'{PHASE}{{{POST},phase="cpu"}}': 0.008,
                 f"{REQS}{{{POST}}}": 10.0}
        assert read(ctx(before, after), SPEC) == pytest.approx(2.0)
        # never in the window, while the program records the family
        after = {f'{PHASE}{{{POST},phase="cpu"}}': 0.008,
                 f"{REQS}{{{POST}}}": 10.0}
        assert read(ctx(before, after), SPEC) == 0.0

    def test_a_program_without_the_phases_reads_as_nothing(self):
        read = histogram_mean()
        # the parent: requests and older phases, none of the new ones
        before = {f"{REQS}{{{POST}}}": 2.0}
        after = {f'{PHASE}{{{POST},phase="journal.append"}}': 0.003,
                 f"{REQS}{{{POST}}}": 10.0}
        assert read(ctx(before, after), SPEC) is None
        # the handler's wall itself needs no phase: the parent has it
        wall = {"sum": "cook_http_request_duration_seconds",
                "sum_labels": {"endpoint": "/jobs", "method": "POST"},
                "count": "cook_http_request_duration_seconds",
                "count_labels": {"endpoint": "/jobs", "method": "POST"},
                "scale": 1000.0}
        before = {f"{REQS}{{{POST}}}": 2.0,
                  f"cook_http_request_duration_seconds_sum{{{POST}}}": 0.1}
        after = {f"{REQS}{{{POST}}}": 6.0,
                 f"cook_http_request_duration_seconds_sum{{{POST}}}": 0.3}
        assert read(ctx(before, after), wall) == pytest.approx(50.0)

    def test_on_a_live_exposition_through_the_harness_parser(self, served):
        import sys
        sys.path.insert(0, os.path.join(ROOT, "benchmarks"))
        try:
            import run
        finally:
            sys.path.pop(0)
        store, client = served
        client.submit([spec()])
        record_of(client)
        before = run.parse_metrics(registry.expose())
        for _ in range(3):
            client.submit([spec()])
        record_of(client)
        after = run.parse_metrics(registry.expose())
        read = histogram_mean()
        c = ctx(before, after)
        wall_spec = dict(SPEC, sum="cook_http_request_duration_seconds",
                         sum_labels=SPEC["count_labels"])
        wall = read(c, wall_spec)
        parts = {p: read(c, dict(SPEC, sum_labels=dict(
            SPEC["sum_labels"], phase=p))) for p in PARTS}
        assert wall is not None and wall > 0.0
        assert all(v is not None and v >= 0.0 for v in parts.values())
        assert parts["store.notify-wait"] == 0.0   # never occurred
        assert sum(parts.values()) == pytest.approx(wall, abs=0.01)
