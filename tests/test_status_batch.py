"""A launch burst acknowledged in ONE status transaction that journals
only what changed (Store.update_instance_statuses, ComputeCluster.
_emit_statuses, Scheduler._on_status_updates).

The batch form and the single-entry form are one body, so the contract
is equivalence: the same entries through either give the same return
values, entities, events and replayed store — and the record of a batch
carries no entity the batch did not change."""

import os
import random

import pytest

from cook_tpu.cluster.fake import FakeCluster, FakeHost
from cook_tpu.config import Config
from cook_tpu.sched.scheduler import Scheduler
from cook_tpu.sim import crashpoint
from cook_tpu.state.integrity import parse_journal_line
from cook_tpu.state.partition import PartitionedStore, PartitionMap
from cook_tpu.state.schema import (
    InstanceStatus,
    Job,
    JobState,
    Pool,
    Reasons,
    Resources,
    to_json,
)
from cook_tpu.state.store import Store
from cook_tpu.utils.faults import injector
from cook_tpu.utils.flight import recorder as flight_recorder
from cook_tpu.utils.metrics import registry
from cook_tpu.utils.retry import breakers

RUNNING, FAILED, SUCCESS, UNKNOWN = (
    InstanceStatus.RUNNING, InstanceStatus.FAILED, InstanceStatus.SUCCESS,
    InstanceStatus.UNKNOWN)


@pytest.fixture(autouse=True)
def _clean_global_planes():
    injector.clear()
    breakers.reset()
    yield
    injector.clear()
    breakers.reset()


def job_of(i: int, max_retries: int = 2, pool: str = "default") -> Job:
    return Job(uuid=f"00000000-0000-4000-8000-{i:012d}", user=f"u{i % 3}",
               command="true", pool=pool,
               resources=Resources(cpus=1.0, mem=64.0),
               max_retries=max_retries)


def entry(task_id, status, reason=None, exit_code=None, preempted=False,
          hostname=None):
    return (task_id, status, reason, exit_code, preempted, hostname)


def launched_store(directory=None, n=12):
    """``n`` jobs, all but the last two launched as one burst; a constant
    clock, so two stores fed the same entries carry the same stamps."""
    store = Store.open(directory) if directory else Store()
    store.clock = lambda: 1_000
    store.create_jobs([job_of(i, max_retries=1 + i % 3) for i in range(n)])
    insts, failures = store.launch_instances([dict(
        job_uuid=job_of(i).uuid, task_id=f"t{i}", hostname=f"h{i % 4}",
        compute_cluster="c1") for i in range(n - 2)])
    assert len(insts) == n - 2 and not failures
    return store


def dump(store):
    return {table: {k: to_json(v)
                    for k, v in sorted(getattr(store, "_" + table).items())}
            for table in ("jobs", "instances", "intents")}


def events_of(store):
    seen = []
    store.subscribe(lambda _tx, events: seen.extend(
        (e.kind, dict(e.data)) for e in events))
    return seen


def journal_records(directory):
    with open(os.path.join(directory, "journal.jsonl"), "rb") as f:
        return [parse_journal_line(line.strip()) for line in f
                if line.strip()]


def seeded_mix(seed: int, n_launched: int = 10):
    """RUNNING / FAILED / SUCCESS / redelivered / illegal / unknown-task
    entries over the launched tasks, the same task more than once."""
    rng = random.Random(seed)
    tasks = [f"t{i}" for i in range(n_launched)]
    mix = []
    for _ in range(40):
        kind = rng.choice(["running", "failed", "success", "redeliver",
                           "illegal", "unknown", "preempted"])
        tid = rng.choice(tasks)
        if kind == "running":
            mix.append(entry(tid, RUNNING, hostname=rng.choice(
                [None, "elsewhere"])))
        elif kind == "failed":
            mix.append(entry(tid, FAILED, rng.choice(
                [Reasons.NON_ZERO_EXIT.code, Reasons.NODE_LOST.code,
                 Reasons.REASON_POD_SUBMISSION_FAILED.code]), exit_code=1))
        elif kind == "success":
            mix.append(entry(tid, SUCCESS, exit_code=0))
        elif kind == "redeliver" and mix:
            mix.append(rng.choice(mix))
        elif kind == "illegal":
            # terminal -> live, or anything -> unknown: never allowed
            mix.append(entry(tid, UNKNOWN))
        elif kind == "preempted":
            mix.append(entry(tid, FAILED, Reasons.PREEMPTED_BY_REBALANCER
                             .code, preempted=True))
        else:
            mix.append(entry(f"no-such-task-{rng.randrange(9)}", RUNNING))
    return mix


# ------------------------------------------------ (a) batch == one by one
@pytest.mark.parametrize("seed", range(8))
def test_batch_equals_the_entries_one_by_one(tmp_path, seed):
    mix = seeded_mix(seed)
    a = launched_store(str(tmp_path / "a"))
    b = launched_store(str(tmp_path / "b"))
    ev_a, ev_b = events_of(a), events_of(b)
    got_a = a.update_instance_statuses(mix)
    got_b = [b.update_instance_status(
        e[0], e[1], reason_code=e[2], exit_code=e[3], preempted=e[4],
        hostname=e[5]) for e in mix]
    assert got_a == got_b
    assert any(got_a) and not all(got_a)        # the mix has both kinds
    assert dump(a) == dump(b)
    assert ev_a == ev_b                         # same events, same order
    live = dump(a)
    a.close()
    b.close()
    # either journal replays to the store that wrote it
    for name in ("a", "b"):
        replayed = Store.open(str(tmp_path / name))
        assert dump(replayed) == live
        replayed.close()
    # one record for the batch, one for every entry that wrote something
    assert len(journal_records(str(tmp_path / "a"))) == 3
    assert len(journal_records(str(tmp_path / "b"))) > 3


# ------------------------------ (b) the record holds only what changed
def test_ack_after_launch_journals_no_job_and_deletes_the_intent(tmp_path):
    d = str(tmp_path / "s")
    store = launched_store(d)
    assert len(store.launch_intents()) == 10
    oks = store.update_instance_statuses(
        [entry(f"t{i}", RUNNING, hostname=f"h{i % 4}") for i in range(10)])
    assert oks == [True] * 10
    assert store.launch_intents() == []
    rec = journal_records(d)[-1]
    assert sorted(rec["w"]) == sorted(f"instances/t{i}" for i in range(10))
    assert sorted(rec["d"]) == sorted(f"intents/t{i}" for i in range(10))
    assert all(store.job(job_of(i).uuid).state is JobState.RUNNING
               for i in range(10))
    store.close()


@pytest.mark.parametrize("status,reason,job_state", [
    (FAILED, Reasons.NON_ZERO_EXIT.code, JobState.WAITING),   # requeued
    (SUCCESS, None, JobState.COMPLETED),
])
def test_a_status_that_moves_the_job_journals_the_job(
        tmp_path, status, reason, job_state):
    d = str(tmp_path / "s")
    store = launched_store(d)
    uuid = job_of(1).uuid                       # max_retries 2
    assert store.update_instance_status("t1", status, reason_code=reason)
    rec = journal_records(d)[-1]
    assert sorted(rec["w"]) == [f"instances/t1", f"jobs/{uuid}"]
    assert store.job(uuid).state is job_state
    store.close()


def test_a_redelivered_status_writes_nothing(tmp_path):
    d = str(tmp_path / "s")
    store = launched_store(d)
    assert store.update_instance_status("t0", RUNNING)
    n = len(journal_records(d))
    seen = events_of(store)
    assert store.update_instance_status("t0", RUNNING) is True
    assert store.update_instance_statuses(
        [entry("t0", RUNNING), entry("nope", RUNNING)]) == [True, False]
    assert len(journal_records(d)) == n and seen == []
    store.close()


# ------------------------------------- (c) the same task twice, in order
@pytest.mark.parametrize("second,final,job_state", [
    (entry("t1", FAILED, Reasons.NON_ZERO_EXIT.code), FAILED,
     JobState.WAITING),
    (entry("t1", SUCCESS), SUCCESS, JobState.COMPLETED),
])
def test_same_task_twice_applies_in_order(second, final, job_state):
    store = launched_store()
    seen = events_of(store)
    assert store.update_instance_statuses(
        [entry("t1", RUNNING), second]) == [True, True]
    inst = store.instance("t1")
    assert inst.status is final
    assert inst.mesos_start_time_ms == 1_000    # the RUNNING was applied
    assert store.job(inst.job_uuid).state is job_state
    assert [(k, d.get("old"), d.get("new")) for k, d in seen] == [
        ("instance-status", "unknown", "running"),
        ("instance-status", "running", final.value),
        ("job-state", "running", job_state.value)]
    # and the other way round the second is stale: False, nothing undone
    assert store.update_instance_statuses(
        [second, entry("t1", RUNNING)]) == [True, False]


# ------------------------------------ (d) one illegal entry aborts nothing
@pytest.mark.parametrize("bad", [
    entry("t2", UNKNOWN),                       # illegal transition
    entry("no-such-task", RUNNING),             # unknown task
])
def test_one_bad_entry_aborts_nothing_else(bad):
    store = launched_store()
    store.update_instance_status("t2", RUNNING)
    oks = store.update_instance_statuses(
        [entry("t0", RUNNING), bad, entry("t1", SUCCESS)])
    assert oks == [True, False, True]
    assert store.instance("t0").status is RUNNING
    assert store.instance("t1").status is SUCCESS
    assert store.instance("t2").status is RUNNING
    assert {r["task_id"] for r in store.launch_intents()} == {
        f"t{i}" for i in range(3, 10)}


# --------------- (e) FakeCluster through a Scheduler: one transaction
def cpu_config() -> Config:
    cfg = Config()
    cfg.cycle_mode = "split"
    cfg.default_matcher.backend = "cpu"
    cfg.columnar_index = False
    return cfg


def fake_cluster(name="c1", n_hosts=4):
    return FakeCluster(name, [
        FakeHost(hostname=f"{name}-h{i}",
                 capacity=Resources(cpus=16.0, mem=16384.0))
        for i in range(n_hosts)])


class _CountingStore:
    """Counts the status transactions a scheduler makes on a store."""

    def __init__(self, store):
        self.batches = []
        inner = store.update_instance_statuses

        def counted(updates):
            updates = list(updates)
            self.batches.append(updates)
            return inner(updates)

        store.update_instance_statuses = counted


@pytest.mark.parametrize("n", [1, 7, 40])
def test_a_launch_burst_is_acknowledged_in_one_transaction(n):
    store = Store()
    cluster = fake_cluster()
    sched = Scheduler(store, cpu_config(), [cluster], rank_backend="cpu")
    counted = _CountingStore(store)
    beats = []
    beat = sched.heartbeats.beat
    sched.heartbeats.beat = lambda tid, now: (beats.append(tid),
                                              beat(tid, now))[1]
    store.create_jobs([job_of(i) for i in range(n)])
    with flight_recorder.cycle("cycle") as rec:
        sched.step_rank()
        results = sched.step_match()
    launched = results["default"].launched_task_ids
    assert len(launched) == n
    assert [len(b) for b in counted.batches] == [n]
    assert sorted(beats) == sorted(launched)
    assert store.launch_intents() == []
    assert all(store.instance(t).status is RUNNING for t in launched)
    # the counter that says it engaged, on the cycle's own record
    assert (rec.status_txns, rec.status_updates) == (1, n)
    doc = rec.to_doc()
    assert (doc["status_txns"], doc["status_updates"]) == (1, n)


def test_rejected_tasks_fail_in_the_same_batch():
    store = Store()
    cluster = fake_cluster()
    sched = Scheduler(store, cpu_config(), [cluster], rank_backend="cpu")
    counted = _CountingStore(store)
    store.create_jobs([job_of(i, max_retries=3) for i in range(6)])
    injector.arm("cluster.launch", schedule=[1, 4])
    sched.step_rank()
    results = sched.step_match()
    injector.clear()
    launched = results["default"].launched_task_ids
    assert len(launched) == 6
    [batch] = counted.batches
    assert [e[1] for e in batch] == [RUNNING] * 4 + [FAILED] * 2
    failed = [store.instance(t) for t in launched
              if store.instance(t).status is FAILED]
    assert len(failed) == 2 and all(
        i.reason_code == Reasons.REASON_POD_SUBMISSION_FAILED.code
        for i in failed)
    assert all(store.job(i.job_uuid).state is JobState.WAITING
               for i in failed)                 # mea-culpa: requeued
    assert store.launch_intents() == []


def test_registry_counts_transactions_and_batch_sizes():
    def read():
        snap = registry.snapshot()
        return (snap["counters"].get("cook_status_txn", 0.0),
                snap["histogram_counts"].get("cook_status_batch_size", 0))
    store = launched_store()
    txns, sizes = read()
    store.update_instance_statuses(
        [entry(f"t{i}", RUNNING) for i in range(5)])
    store.update_instance_status("t0", SUCCESS)
    assert read() == (txns + 2, sizes + 2)
    text = registry.expose()
    assert "cook_status_txn_total" in text
    assert 'cook_status_batch_size_bucket{le="4.0"}' in text


# ------------- (f) crash between the guard transaction and the ack
def test_crash_before_the_ack_leaves_intents_the_sweep_reconciles(tmp_path):
    run = crashpoint._Run(str(tmp_path / "pristine"), 2)
    res = crashpoint.CrashPointResult()
    crashpoint._leg_launch_ack(res, run, str(tmp_path))
    assert res.ok, res.summary()
    assert res.legs == {"launch-ack": 2}        # refund and adopt
    # the pristine run acknowledged its burst in ONE record
    recs = journal_records(run.directory)
    assert sorted(recs[-1]["d"]) == sorted(
        f"intents/task-{i}" for i in range(2, 2 + crashpoint.BURST))
    assert all(k.startswith("instances/") for k in recs[-1]["w"])


# ------------------------------------ (g) PartitionedStore routes a batch
def test_partitioned_store_routes_a_mixed_batch():
    ps = PartitionedStore([Store(partition=0), Store(partition=1)],
                          PartitionMap(count=2, pools={"pa": 0, "pb": 1}))
    ps.put_pool(Pool(name="pa"))
    ps.put_pool(Pool(name="pb"))
    jobs = [job_of(i, pool="pa" if i % 2 else "pb") for i in range(6)]
    ps.create_jobs(jobs)
    insts, failures = ps.launch_instances([dict(
        job_uuid=j.uuid, task_id=f"t{i}", hostname="h",
        compute_cluster="c1") for i, j in enumerate(jobs)])
    assert len(insts) == 6 and not failures
    tx_before = [p._tx_id for p in ps.partitions]
    oks = ps.update_instance_statuses(
        [entry("t0", RUNNING), entry("t1", RUNNING), entry("ghost", RUNNING),
         entry("t2", SUCCESS), entry("t3", RUNNING), entry("t1", SUCCESS),
         entry("t2", RUNNING), entry("t4", RUNNING), entry("t5", RUNNING)])
    assert oks == [True, True, False, True, True, True, False, True, True]
    # one transaction per touched partition, not one per entry
    assert [p._tx_id - t for p, t in zip(ps.partitions, tx_before)] == [1, 1]
    assert ps.instance("t1").status is SUCCESS
    assert ps.job(jobs[1].uuid).state is JobState.COMPLETED
    assert [r["task_id"] for r in ps.launch_intents()] == []


# --------------------- (h) the per-task callback path is what it was
@pytest.mark.parametrize("how", ["complete", "fail", "kill", "advance"])
def test_per_task_callbacks_still_arrive_one_by_one(how):
    store = Store()
    cluster = FakeCluster("c1", [FakeHost(
        hostname="c1-h0", capacity=Resources(cpus=8.0, mem=8192.0))],
        default_task_duration_ms=10)
    sched = Scheduler(store, cpu_config(), [cluster], rank_backend="cpu")
    store.create_jobs([job_of(0, max_retries=1)])
    sched.step_rank()
    [tid] = sched.step_match()["default"].launched_task_ids
    counted = _CountingStore(store)
    if how == "complete":
        cluster.complete_task(tid)
    elif how == "fail":
        cluster.fail_task(tid, Reasons.NODE_LOST.code)
    elif how == "kill":
        cluster.safe_kill_task(tid)
    else:
        assert cluster.advance_to(1_000) == [tid]
    assert [len(b) for b in counted.batches] == [1]
    want = SUCCESS if how in ("complete", "advance") else FAILED
    assert store.instance(tid).status is want


def test_a_status_queue_still_gets_the_burst_entry_by_entry():
    store = Store()
    cluster = fake_cluster()
    sched = Scheduler(store, cpu_config(), [cluster], rank_backend="cpu",
                      status_queue_shards=2)
    counted = _CountingStore(store)
    store.create_jobs([job_of(i) for i in range(9)])
    sched.step_rank()
    launched = sched.step_match()["default"].launched_task_ids
    sched.flush_status_updates()
    assert len(launched) == 9
    assert sorted(len(b) for b in counted.batches) == [1] * 9
    assert all(store.instance(t).status is RUNNING for t in launched)
    assert store.launch_intents() == []


# ----------------- batches, single statuses and kills from several threads
def test_concurrent_batches_singles_and_kills_keep_jobs_consistent():
    import sys
    import threading

    from cook_tpu.state import machines
    n = 120
    store = launched_store(n=n + 2)
    seen = events_of(store)
    tasks = [f"t{i}" for i in range(n)]
    errors = []

    def guarded(fn):
        def run():
            try:
                fn()
            except BaseException as e:      # surfaced after the joins
                errors.append(e)
        return run

    def acks(lo, hi):
        return lambda: [store.update_instance_statuses(
            [entry(t, RUNNING) for t in tasks[a:a + 20]])
            for a in range(lo, hi, 10)]     # overlapping slices

    def finishes():
        rng = random.Random(7)
        for _ in range(150):
            t = rng.choice(tasks)
            if rng.random() < 0.5:
                store.update_instance_status(t, SUCCESS, exit_code=0)
            else:
                store.update_instance_statuses(
                    [entry(t, FAILED, Reasons.NON_ZERO_EXIT.code),
                     entry(rng.choice(tasks), RUNNING)])

    def kills():
        for i in range(0, n, 7):
            store.kill_job(job_of(i).uuid)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=guarded(f)) for f in (
            acks(0, n), acks(5, n), finishes, kills, acks(0, n))]
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=60)
        assert not any(th.is_alive() for th in threads)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    # every job's stored state is what its instances say it is: no state
    # change was lost by writing the Job only when it moves
    for i in range(n):
        job = store.job(job_of(i).uuid)
        insts = {t: store.instance(t) for t in job.instances}
        assert job.state is machines.next_job_state(job, insts)[0], job.uuid
    # and every instance walked a legal chain, each step seen once
    last = {}
    for kind, data in seen:
        if kind == "instance-status":
            assert data["old"] == last.get(data["task_id"], "unknown")
            last[data["task_id"]] = data["new"]
    assert all(store.instance(t).status.value == last.get(t, "unknown")
               for t in tasks)
    assert not {r["task_id"] for r in store.launch_intents()} & set(last)
