"""Scheduler flight recorder: a fixed ring of per-cycle CycleRecords.

The per-cycle correlation layer the span ring alone can't give: every
driver cycle (fused production dispatch, split rank/match, rebalance)
opens a :meth:`FlightRecorder.cycle` context that

  1. roots a ``cycle`` tracing span, so every nested span (pack, kernel
     dispatch, fetch, launch RPC) shares the cycle's trace_id and the
     whole cycle exports as one Chrome/Perfetto flamegraph
     (``GET /debug/trace?trace_id=``);
  2. collects the cycle's device telemetry — recompiles per kernel,
     host<->device bytes, device sync-wait time (fed by
     cook_tpu.ops.telemetry), head-of-line skip reasons, preemptions,
     jobs considered/placed;
  3. receives every span that ENDS inside the cycle (tracing.Tracer
     hands each finished span to the current record in O(1)) and routes
     its time to the per-phase durations (rank / match / launch /
     rebalance), the ``detail_ms`` split (:data:`DETAIL_BY_SPAN`) and
     the cycle thread's named waits (``blocked_ms``; with ``offcpu_ms``
     and ``background_ms`` at the end) — the span ring is never
     scanned — and lands the
     finished record in a fixed-size ring served by
     ``GET /debug/cycles`` and the ``cook-tpu debug cycles`` CLI.

This is the repro of the reference's structured match-cycle log documents
(scheduler.clj match cycle logging + prometheus_metrics.clj with-duration
tri-recording), extended with the JAX-level counters the reference never
needed: a recompile storm or transfer regression shows up as a labeled
field on the slow cycle's record, not a mystery p99 blip.
"""

from __future__ import annotations

import contextvars
import gc
import threading
import time
from collections import deque
from contextlib import contextmanager
from typing import Any, Dict, List, Optional

from cook_tpu.utils import locks, tracing
from cook_tpu.utils.metrics import registry

_DEFAULT_CAPACITY = 512

# span name -> canonical phase; phase durations on a CycleRecord are the
# sum of the trace's span durations per phase.  Only TOP-LEVEL phase spans
# are mapped (cycle.rank contains fused.pack; summing both would double
# count), the finer span names stay visible in the trace export.
PHASE_BY_SPAN = {
    "cycle.rank": "rank",
    "rank.cycle": "rank",
    "cycle.match": "match",
    "scheduler.pool-handler": "match",
    "cycle.launch": "launch",
    "rebalancer.pool": "rebalance",
}

# span name -> detail_ms key: THE one table that says which span feeds
# which key (the per-layer benchmark metrics read these keys by name).
# A key's value is the time of the spans mapped to that key only: when a
# mapped span ends inside another mapped span that is not its declared
# parent (DETAIL_PARENT), its time is carved out of the enclosing key —
# the journal append of the burst's one status transaction, inside the
# cluster-launch span, counts as apply_journal, not twice — so the parts
# of a key never overlap.
DETAIL_BY_SPAN = {
    # top level: these partition a fused cycle (with "other")
    "fused.pools": "pools",
    "fused.pack": "pack",
    "fused.stage": "stage",
    "fused.dispatch": "dispatch",
    "fused.fetch": "fetch",
    "cycle.launch": "apply",
    "pipeline.host": "pipeline",
    "cycle.publish": "publish",
    # part of stage (fused._stage_group): host arrays -> device, each
    # [P, ...] input onto the mesh device that owns its pools
    "stage.put": "stage_put",
    # parts of pack (fused._pack_pool_columnar / _pack_pool_cached)
    "pack.index": "pack_index",
    "pack.offers": "pack_offers",
    "pack.rows": "pack_rows",
    # parts of apply (fused._apply_pool, matcher._launch, state/store.py)
    "apply.lookup": "apply_lookup",
    "launch.prepare": "apply_lookup",
    "store.launch-txn": "apply_txn",
    "journal.append": "apply_journal",
    "journal.commit-wait": "apply_journal",
    "launch.specs": "apply_cluster",
    "cluster.launch-tasks": "apply_cluster",
    "store.clear-intents": "apply_cluster",
    "apply.audit": "apply_audit",
    "store.drain-events": "apply_audit",
    # the 30 s sweeps' own records (kind reapers / monitor): which part
    # reads the store and which folds what was read
    "reapers.scan": "scan",
    "monitor.copy": "copy",
    "monitor.fold": "fold",
}
#: detail_ms key -> the key it is a part of
DETAIL_PARENT = {
    "stage_put": "stage",
    "pack_index": "pack", "pack_offers": "pack", "pack_rows": "pack",
    "apply_lookup": "apply", "apply_txn": "apply",
    "apply_journal": "apply", "apply_cluster": "apply",
    "apply_audit": "apply",
}
#: the keys that partition a fused cycle; ``detail_ms.other`` is
#: duration_ms minus these — what no span covers yet
DETAIL_TOP_LEVEL = ("pools", "pack", "stage", "dispatch", "fetch", "apply",
                    "pipeline", "publish")
#: the keys of a background loop's own record (kind reapers / monitor):
#: parts of no fused cycle, so outside the partition above
DETAIL_SWEEPS = ("scan", "copy", "fold")

#: span name -> blocked_ms key: a named wait of the recording thread
#: (both bare durations, tracing.cycle_time: the group-commit round, and
#: the spin in Store._drain_events while another thread delivers events)
BLOCKED_BY_SPAN = {"journal.commit-wait": "commit_wait",
                   "store.notify-wait": "notify"}
# the three tables above merged, so a finished span costs one lookup:
# name -> (phase, detail key, the key's declared parent, blocked key)
_ROUTES = {
    name: (PHASE_BY_SPAN.get(name), DETAIL_BY_SPAN.get(name),
           DETAIL_PARENT.get(DETAIL_BY_SPAN.get(name)),
           BLOCKED_BY_SPAN.get(name))
    for name in {*PHASE_BY_SPAN, *DETAIL_BY_SPAN, *BLOCKED_BY_SPAN}}

#: record kinds that ARE scheduling cycles; every other kind (reapers,
#: monitor, rebalance, ...) is a background loop run whose interval is
#: kept so an overlapping cycle can say which sweep ran beside it
CYCLE_KINDS = frozenset({"cycle", "fused", "rank", "match"})
#: background_ms keys every cycle record carries (0.0 = no overlap)
BACKGROUND_LOOPS = ("reapers", "monitor", "rebalance")
#: blocked_ms keys every record carries; a contended lock of another
#: family adds ``<family>_lock`` beside them
BLOCKED_KEYS = ("store_lock", "commit_wait", "device", "gc", "notify")

# the current record IS the tracer's per-cycle span sink: one context
# variable, set once per cycle (copied contexts — the per-cluster launch
# threads — keep both the telemetry notes and the spans on the record)
_current_record: "contextvars.ContextVar[Optional[CycleRecord]]" = \
    tracing._cycle_var

# ---------------------------------------------------- collector pauses
# ONE gc.callbacks hook for the process: a collection triggered on any
# thread stops every thread, so a cycle that overlaps one was blocked
# for the overlap whichever thread paid for it.  The hook may fire at
# ANY allocation — inside the metrics registry's own lock included — so
# it takes no lock and touches no registry: it only appends to deques;
# FlightRecorder._finish publishes cook_gc_pause_seconds from there.
_gc_recent: "deque[tuple]" = deque(maxlen=1024)     # (t0, t1, gen, thread)
_gc_unpublished: "deque[tuple]" = deque(maxlen=4096)  # (gen, seconds)
_gc_open: List[float] = [0.0]


def _on_gc(phase: str, info: Dict[str, Any]) -> None:
    if phase == "start":
        _gc_open[0] = time.perf_counter()
        return
    t0, t1 = _gc_open[0], time.perf_counter()
    gen = int(info.get("generation", -1))
    _gc_recent.append((t0, t1, gen, threading.get_ident()))
    _gc_unpublished.append((gen, t1 - t0))


if _on_gc not in gc.callbacks:
    gc.callbacks.append(_on_gc)


def _publish_gc_pauses(limit: int = 512) -> None:
    for _ in range(limit):
        try:
            gen, seconds = _gc_unpublished.popleft()
        except IndexError:
            return
        registry.observe("cook_gc_pause_seconds", seconds,
                         {"generation": str(gen)})


# Contended named-lock waits not yet on cook_lock_wait_seconds.  The
# contention sink runs while the waiter holds the lock it waited for, so
# it only appends here, and publish_waits() observes them outside any
# lock, by family and thread role (at a record's end, a request's end).
_lock_waits: "deque[tuple]" = deque(maxlen=16384)  # (family, waiter, holder, s)


def publish_waits(limit: int = 16384) -> None:
    """Observe the pending contended lock waits on
    ``cook_lock_wait_seconds{lock, waiter, holder}`` (one registry call a
    label set) and the pending collector pauses."""
    groups: Dict[tuple, List[float]] = {}
    for _ in range(limit):
        try:
            fam, waiter, holder, seconds = _lock_waits.popleft()
        except IndexError:
            break
        key = (fam, tracing.thread_role(waiter), tracing.thread_role(holder))
        groups.setdefault(key, []).append(seconds)
    for (fam, waiter, holder), values in groups.items():
        registry.observe_many("cook_lock_wait_seconds", values,
                              {"lock": fam, "waiter": waiter,
                               "holder": holder})
    _publish_gc_pauses()

# process-wide shard identity (ISSUE 19): a sharded-controller process
# owns exactly ONE partition shard, so the id is process state, not
# per-record plumbing — set once at shard boot (sched/shard.py), stamped
# onto every CycleRecord minted after.  None = unsharded (classic
# single-controller daemon): records export shard=null and the summary
# roll-up stays flat.
_shard_id: Optional[int] = None


def set_shard(shard: Optional[int]) -> None:
    """Declare this process's shard id (one partition = one process);
    every CycleRecord minted after carries it."""
    global _shard_id
    _shard_id = None if shard is None else int(shard)


def current_shard() -> Optional[int]:
    return _shard_id


# process-wide device identity (platform / device_kind / count as JAX
# reports them; sched/scheduler.py sets it at construction): a process
# holds one backend, so like the shard id it is process state stamped
# onto every CycleRecord — a cycle that ran off the accelerator says so
_device: Optional[Dict[str, Any]] = None


def set_device(device: Dict[str, Any]) -> None:
    """Declare where this process's kernels run; every CycleRecord
    minted after carries the block."""
    global _device
    _device = {k: device[k] for k in ("platform", "device_kind", "count")}


class CycleRecord:
    """One scheduler cycle's instrument-panel readings."""

    __slots__ = ("seq", "kind", "trace_id", "start_s", "duration_ms",
                 "phases", "detail_ms", "pools", "jobs_considered",
                 "jobs_placed", "skip_reasons", "preemptions", "recompiles",
                 "h2d_bytes", "d2h_bytes", "sync_wait_ms", "faults",
                 "error", "pipeline_depth", "pipeline_inflight",
                 "pipeline_conflicts", "delta_rows", "full_repacks",
                 "audit_events", "kernel_launches", "path", "shard",
                 "device", "wait_ms", "overrun_ms", "gc_ms",
                 "flush_audit_ms", "cpu_ms", "blocked_ms", "lock_holder",
                 "offcpu_ms", "background_ms", "staged_tx", "pipeline_lag_ms",
                 "status_txns", "status_updates", "staged_late",
                 "mesh_devices", "foreign_event_batches", "_lock_wait_max",
                 "_wait_cpu", "_thread", "_cpu0", "_t0", "_idle")

    def __init__(self, seq: int, kind: str):
        self.seq = seq
        self.kind = kind
        # which controller shard ran this cycle (ISSUE 19 sharded
        # controllers; None on the classic single process) — the key the
        # stitched /debug/cycles roll-up and fleet trace group by
        self.shard: Optional[int] = _shard_id
        self.device = _device
        self.trace_id: Optional[str] = None
        self.start_s = time.time()
        self.duration_ms = 0.0
        self.phases: Dict[str, float] = {}       # phase -> ms
        self.pools = 0
        self.jobs_considered = 0
        self.jobs_placed = 0
        self.skip_reasons: Dict[str, int] = {}   # reason -> count
        self.preemptions = 0
        self.recompiles: Dict[str, int] = {}     # kernel -> compiles
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        self.sync_wait_ms = 0.0
        # fault-point triggers and degradations observed during this
        # cycle (utils/faults.py + kernel/fused fallbacks): a degraded
        # cycle explains itself without cross-referencing logs
        self.faults: Dict[str, int] = {}
        self.error: Optional[str] = None
        # pipelined-driver readings (sched/pipeline.py): configured depth
        # (0 = sync driver), dispatches in flight when this cycle's step
        # finished staging, and reconciliation conflict drops applied
        # inside this cycle
        self.pipeline_depth = 0
        self.pipeline_inflight = 0
        self.pipeline_conflicts = 0
        # sub-phase breakdown the whole-phase durations hide (ISSUE 7
        # satellite): host staging split into pack (store->arrays) /
        # stage (arrays->wire form) / apply (outputs->transactions), so a
        # staging regression is diagnosable from /debug/cycles without a
        # profiler.  Plus the resident-pack readings: delta rows shipped
        # on-chip this cycle and full repacks (reason-labeled on
        # cook_resident_repack_total).
        self.detail_ms: Dict[str, float] = {}
        self.delta_rows = 0
        self.full_repacks = 0
        # per-job audit events recorded during this cycle (utils/audit.py):
        # the audit lane's own overhead meter — a cycle that recorded
        # nothing proves the quiet fast path stayed zero-work
        self.audit_events = 0
        # device kernel dispatches inside this cycle (ISSUE 14: every
        # InstrumentedJit call counts one) and the cycle path that made
        # them: "split" (per-stage XLA launches), "fused" (one XLA pool
        # cycle), or "mixed" when one cycle's dispatch groups took
        # different paths — a path regression (a fused cycle degrading
        # to split) is visible in /debug/cycles and the Perfetto export
        self.kernel_launches = 0
        self.path: Optional[str] = None
        # devices of the pool mesh this cycle's dispatch ran over (the
        # least, should a cycle's groups differ); None, and absent from
        # the document, on a record that dispatched nothing
        self.mesh_devices: Optional[int] = None
        # the tick around the cycle (Scheduler.run's loop): the wait for
        # its deadline — the part that preceded the record and, where
        # the cycle was staged a lead before the deadline, the rest of
        # it, spent INSIDE the record between dispatch and apply
        # (FlightRecorder.idle; not in duration_ms) — how far past that
        # deadline the tick started (0 = on time), and the idle-point GC
        # and audit flush of the tick before: with duration_ms these
        # reproduce the start-to-start period of consecutive cycles
        self.wait_ms = 0.0
        self.overrun_ms = 0.0
        self.gc_ms = 0.0
        self.flush_audit_ms = 0.0
        # where the cycle thread's wall time went: on the CPU
        # (thread_time, collector pauses on this thread excluded),
        # blocked on a named wait (a contended lock by family, the
        # group-commit round, the device fetch, a collector pause
        # anywhere in the process), or neither — runnable but not
        # running, i.e. waiting for the GIL or a core
        self.cpu_ms = 0.0
        self.blocked_ms: Dict[str, float] = dict.fromkeys(BLOCKED_KEYS, 0.0)
        self.lock_holder: Optional[str] = None
        self.offcpu_ms = 0.0
        # overlap with the other loops' runs (reapers / monitor / ...)
        self.background_ms: Dict[str, float] = {}
        # the store transaction the applied candidates were staged from
        # and how long ago that stage began (sched/pipeline.py)
        self.staged_tx: Optional[int] = None
        self.pipeline_lag_ms = 0.0
        # 1 = staged off a store with every earlier cycle applied, a lead
        # before the tick's deadline, and applied at it: pipeline_lag_ms
        # is then that lead, and the document says so as lead_ms too
        # (absent otherwise)
        self.staged_late = 0
        # status transactions committed from inside this record, and the
        # entries they carried (Store.update_instance_statuses): a launch
        # burst acknowledged in one transaction reads 1 and the burst
        self.status_txns = 0
        self.status_updates = 0
        # event batches of OTHER threads' transactions this cycle's
        # thread delivered (Store._drain_events: whoever holds the notify
        # lock delivers everybody's events)
        self.foreign_event_batches = 0
        self._lock_wait_max = 0.0
        # CPU this thread burned inside its timed waits (the notify
        # spin): counted there, so taken off cpu_ms
        self._wait_cpu = 0.0
        self._thread = threading.get_ident()
        self._cpu0 = time.thread_time()
        self._t0 = time.perf_counter()
        # (t0, t1) of the waits for a deadline inside the record
        self._idle: List[tuple] = []

    def _active_ms(self, t0: float, t1: float, end: float) -> float:
        """How much of [t0, t1] fell inside this record, which ends at
        ``end``, while it was not waiting for its deadline."""
        lo, hi = max(t0, self._t0), min(t1, end)
        over = hi - lo
        for i0, i1 in self._idle:
            over -= max(0.0, min(hi, i1) - max(lo, i0))
        return max(0.0, over) * 1000.0

    def add_span(self, name: str, seconds: float, open_spans,
                 cpu_s: float = 0.0) -> None:
        """A span ended inside this cycle (called by Tracer._record, in
        the ending thread): route its time to the phase, the detail_ms
        key and the blocked_ms key its name maps to.  ``open_spans`` is
        the span stack still open around it, innermost last; ``cpu_s``
        the CPU a bare wait burned (tracing.cycle_time), which a named
        wait of this record's own thread takes off ``cpu_ms``."""
        route = _ROUTES.get(name)
        if route is None:
            return
        phase, key, parent, blocked = route
        ms = seconds * 1000.0
        if phase is not None:
            self.phases[phase] = self.phases.get(phase, 0.0) + ms
        if blocked is not None:
            self.blocked_ms[blocked] += ms
            if cpu_s and threading.get_ident() == self._thread:
                self._wait_cpu += cpu_s * 1000.0
        if key is None:
            return
        detail = self.detail_ms
        detail[key] = detail.get(key, 0.0) + ms
        for outer in reversed(open_spans):
            outer_route = _ROUTES.get(outer.name)
            if outer_route is not None and outer_route[1] is not None:
                if outer_route[1] != parent:
                    # nested under a sibling (or under its own key):
                    # carve it out so no time is counted twice
                    detail[outer_route[1]] = \
                        detail.get(outer_route[1], 0.0) - ms
                break

    def to_doc(self) -> Dict[str, Any]:
        doc = {
            "seq": self.seq, "kind": self.kind, "trace_id": self.trace_id,
            "start": self.start_s, "duration_ms": round(self.duration_ms, 3),
            "phases_ms": {k: round(v, 3) for k, v in self.phases.items()},
            "pools": self.pools,
            "jobs_considered": self.jobs_considered,
            "jobs_placed": self.jobs_placed,
            "skip_reasons": dict(self.skip_reasons),
            "preemptions": self.preemptions,
            "recompiles": dict(self.recompiles),
            "h2d_bytes": self.h2d_bytes,
            "d2h_bytes": self.d2h_bytes,
            "sync_wait_ms": round(self.sync_wait_ms, 3),
            "faults": dict(self.faults),
            "pipeline_depth": self.pipeline_depth,
            "pipeline_inflight": self.pipeline_inflight,
            "pipeline_conflicts": self.pipeline_conflicts,
            "detail_ms": {k: round(v, 3) for k, v in self.detail_ms.items()},
            "delta_rows": self.delta_rows,
            "full_repacks": self.full_repacks,
            "audit_events": self.audit_events,
            "kernel_launches": self.kernel_launches,
            "path": self.path,
            "shard": self.shard,
            "device": self.device,
            "wait_ms": round(self.wait_ms, 3),
            "overrun_ms": round(self.overrun_ms, 3),
            "gc_ms": round(self.gc_ms, 3),
            "flush_audit_ms": round(self.flush_audit_ms, 3),
            "cpu_ms": round(self.cpu_ms, 3),
            "blocked_ms": {k: round(v, 3)
                           for k, v in self.blocked_ms.items()},
            "lock_holder": self.lock_holder,
            "offcpu_ms": round(self.offcpu_ms, 3),
            "background_ms": {k: round(v, 3)
                              for k, v in self.background_ms.items()},
            "staged_tx": self.staged_tx,
            "pipeline_lag_ms": round(self.pipeline_lag_ms, 3),
            "status_txns": self.status_txns,
            "status_updates": self.status_updates,
            "foreign_event_batches": self.foreign_event_batches,
            "staged_late": self.staged_late,
            "error": self.error,
        }
        if self.staged_late:
            doc["lead_ms"] = doc["pipeline_lag_ms"]
        if self.mesh_devices is not None:
            doc["mesh_devices"] = self.mesh_devices
        return doc


class FlightRecorder:
    def __init__(self, capacity: int = _DEFAULT_CAPACITY):
        self._lock = threading.Lock()
        self._ring: "deque[CycleRecord]" = deque(maxlen=capacity)
        self._seq = 0
        self.enabled = True
        # per-thread notes of the tick BETWEEN records (the interval
        # wait, the idle-point GC, the audit flush): attached to the
        # next record opened on the same thread
        self._tick = threading.local()
        # [kind, t0, t1-or-None] of recent background loop runs, on the
        # perf_counter clock (a cycle's overlap is read at its _finish)
        self._background: "deque[list]" = deque(maxlen=64)

    # ------------------------------------------------------------- lifecycle
    @contextmanager
    def cycle(self, kind: str = "cycle", **tags: Any):
        """Open (or join) the current cycle record.  Re-entrant: a nested
        call (e.g. a sub-step that can also run standalone) joins the
        enclosing record instead of splitting the cycle in two."""
        cur = _current_record.get()
        if not self.enabled or cur is not None:
            yield cur
            return
        run: Optional[list] = None
        with self._lock:
            self._seq += 1
            rec = CycleRecord(self._seq, kind)
            if kind not in CYCLE_KINDS:
                run = [kind, rec._t0, None]
                self._background.append(run)
        notes, self._tick.notes = getattr(self._tick, "notes", None), None
        for field, ms in (notes or {}).items():
            setattr(rec, field, ms)     # note_tick's fields are the record's
        token = _current_record.set(rec)
        try:
            with tracing.span("cycle", kind=kind, seq=rec.seq, **tags) as sp:
                rec.trace_id = getattr(sp, "trace_id", None)
                yield rec
        except BaseException as exc:
            rec.error = f"{type(exc).__name__}: {exc}"
            raise
        finally:
            _current_record.reset(token)
            end = time.perf_counter()
            rec.duration_ms = rec._active_ms(rec._t0, end, end)
            rec.cpu_ms = (time.thread_time() - rec._cpu0) * 1000.0
            if run is not None:
                run[2] = end
            self._finish(rec, end)

    @contextmanager
    def idle(self):
        """The recording thread waits for its tick's deadline INSIDE the
        open record: a cycle staged a lead before the deadline sleeps
        the rest of the way to it between dispatch and apply
        (sched/pipeline.py).  That is interval wait like the part of it
        before the record — idle, the GIL released — and is counted
        where that is, in ``wait_ms``, and in no time of the cycle's
        (``duration_ms``, the overlaps with collections and sweeps)."""
        rec = _current_record.get()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            if rec is not None:
                t1 = time.perf_counter()
                rec._idle.append((t0, t1))
                rec.wait_ms += (t1 - t0) * 1000.0

    def _finish(self, rec: CycleRecord, end: float) -> None:
        # phases_ms and detail_ms were accumulated span by span
        # (CycleRecord.add_span): the span ring is not touched
        detail = rec.detail_ms
        for part, whole in DETAIL_PARENT.items():
            if whole in detail:
                # a part no span fed this cycle (nothing launched, so no
                # journal append) took 0 ms of a whole that ran
                detail.setdefault(part, 0.0)
        if any(k in detail for k in DETAIL_TOP_LEVEL):
            detail["other"] = rec.duration_ms - sum(
                detail.get(k, 0.0) for k in DETAIL_TOP_LEVEL)
        blocked = rec.blocked_ms
        blocked["device"] = rec.sync_wait_ms
        gc_ms = own_gc_ms = 0.0
        # a snapshot: the hook appends from whichever thread collects
        for t0, t1, _gen, thread in reversed(tuple(_gc_recent)):
            if t1 <= rec._t0:
                break
            over = rec._active_ms(t0, t1, end)
            if over > 0:
                gc_ms += over
                if thread == rec._thread:
                    own_gc_ms += over
        blocked["gc"] = gc_ms
        # a collection on this very thread is CPU time of this thread:
        # it counts as the pause it was, once; so does a spin
        rec.cpu_ms -= own_gc_ms + rec._wait_cpu
        rec.offcpu_ms = rec.duration_ms - rec.cpu_ms - sum(blocked.values())
        with self._lock:
            if rec.kind in CYCLE_KINDS:
                rec.background_ms = dict.fromkeys(BACKGROUND_LOOPS, 0.0)
                for kind, t0, t1 in self._background:
                    over = rec._active_ms(
                        t0, end if t1 is None else t1, end)
                    if over > 0:
                        rec.background_ms[kind] = \
                            rec.background_ms.get(kind, 0.0) + over
            self._ring.append(rec)
        publish_waits()
        registry.observe("cook_cycle_duration_seconds",
                         rec.duration_ms / 1000.0, {"kind": rec.kind})
        if rec.jobs_considered:
            registry.counter_inc("cook_cycle_jobs_considered",
                                 rec.jobs_considered)
        if rec.jobs_placed:
            registry.counter_inc("cook_cycle_jobs_placed", rec.jobs_placed)

    # ------------------------------------------------------------- telemetry
    def current(self) -> Optional[CycleRecord]:
        return _current_record.get()

    def note_recompile(self, kernel: str, n: int = 1) -> None:
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.recompiles[kernel] = rec.recompiles.get(kernel, 0) + n

    def note_transfer(self, direction: str, nbytes: int) -> None:
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                if direction == "h2d":
                    rec.h2d_bytes += int(nbytes)
                else:
                    rec.d2h_bytes += int(nbytes)

    def note_sync_wait(self, seconds: float) -> None:
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.sync_wait_ms += seconds * 1000.0

    def note_skips(self, reasons: Dict[str, int]) -> None:
        """Head-of-line skip reasons histogram (why a pending job was
        passed over this cycle: over-quota, rate-limited, launch-filtered,
        offensive, unmatched, launch-failed)."""
        rec = _current_record.get()
        if rec is None:
            return
        with self._lock:
            for reason, n in reasons.items():
                if n:
                    rec.skip_reasons[reason] = \
                        rec.skip_reasons.get(reason, 0) + int(n)

    def note_preemptions(self, n: int) -> None:
        rec = _current_record.get()
        if rec is not None and n:
            with self._lock:
                rec.preemptions += int(n)

    def note_pipeline(self, depth: int, inflight: int) -> None:
        """Pipelined-driver shape of the current cycle (sched/pipeline.py):
        configured depth and dispatches in flight after staging."""
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.pipeline_depth = int(depth)
                rec.pipeline_inflight = int(inflight)

    def note_pipeline_conflicts(self, n: int) -> None:
        """Reconciliation conflict drops (candidates re-validated against
        the store and dropped instead of double-launched) inside the
        current cycle."""
        rec = _current_record.get()
        if rec is not None and n:
            with self._lock:
                rec.pipeline_conflicts += int(n)

    def note_tick(self, field: str, ms: float) -> None:
        """A reading of the tick BETWEEN cycles on this thread —
        ``wait_ms`` (the wait for the tick's deadline), ``overrun_ms``
        (how late against it the tick started), ``gc_ms`` (the idle-point
        collection), ``flush_audit_ms`` — carried onto the next record
        this thread opens (Scheduler.run's loop and step_cycle call
        this; a record's callers never do)."""
        notes = getattr(self._tick, "notes", None)
        if notes is None:
            notes = self._tick.notes = {}
        notes[field] = notes.get(field, 0.0) + float(ms)

    def note_lock_wait(self, lock_name: str, seconds: float,
                       holder: Optional[str], cpu_s: float = 0.0) -> None:
        """A CONTENDED named-lock acquisition by the current thread outside
        a timed wait (utils/locks.py reports every one): it is queued for
        ``cook_lock_wait_seconds`` (publish_waits); a wait on the store
        lock is a part of the current request's wall (``store.lock-wait``,
        with the CPU it burned, tracing.wait_time); and the wait lands on
        this thread's record under ``<family>_lock``, and the holder of
        the longest wait is kept."""
        fam = locks.family(lock_name)
        _lock_waits.append(
            (fam, threading.current_thread().name, holder, seconds))
        if fam == "store":
            tracing.wait_time("store.lock-wait", seconds, cpu_s)
        rec = _current_record.get()
        if rec is None:
            return
        key = fam.replace(".", "_") + "_lock"
        rec.blocked_ms[key] = rec.blocked_ms.get(key, 0.0) \
            + seconds * 1000.0
        if seconds > rec._lock_wait_max:
            rec._lock_wait_max = seconds
            rec.lock_holder = holder

    def note_staged(self, staged_tx: int, lag_ms: float,
                    late: bool = False) -> None:
        """The store transaction the cycle being applied was staged from,
        and the time from that stage's start to this apply's start;
        ``late`` = it was staged a lead before its tick's deadline with
        nothing in flight (``staged_late`` 1, that time is the lead)."""
        rec = _current_record.get()
        if rec is not None:
            rec.staged_tx = int(staged_tx)
            rec.pipeline_lag_ms = float(lag_ms)
            rec.staged_late = int(late)

    def note_status_txn(self, entries: int) -> None:
        """One status transaction of ``entries`` updates committed from
        inside the current record (state/store.py); outside a record it
        is one contextvar read."""
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.status_txns += 1
                rec.status_updates += int(entries)

    def note_foreign_batches(self, n: int) -> None:
        """Event batches of other threads' transactions the current
        thread delivered (Store._drain_events); outside a record it is
        one contextvar read."""
        rec = _current_record.get()
        if rec is not None and n:
            with self._lock:
                rec.foreign_event_batches += int(n)

    def note_delta(self, rows: int) -> None:
        """Delta rows scatter-applied into the device-resident pack this
        cycle (0 on a quiet cycle; the steady-state guard asserts it)."""
        rec = _current_record.get()
        if rec is not None and rows:
            with self._lock:
                rec.delta_rows += int(rows)

    def note_repack(self, reason: str) -> None:
        """A full resident-pack repack (reason also labels
        cook_resident_repack_total)."""
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.full_repacks += 1

    def note_audit(self, n: int = 1) -> None:
        """Per-job audit events (utils/audit.py) recorded inside the
        current cycle."""
        rec = _current_record.get()
        if rec is not None and n:
            with self._lock:
                rec.audit_events += int(n)

    def note_kernel_launch(self, kernel: str, n: int = 1) -> None:
        """One device kernel dispatch attributed to the current cycle
        (counted by InstrumentedJit on every call)."""
        rec = _current_record.get()
        if rec is not None and n:
            with self._lock:
                rec.kernel_launches += int(n)

    def note_path(self, path: str) -> None:
        """The cycle's dispatch path (split | fused); two
        different notes inside one cycle record as "mixed".  Also tagged
        onto the live cycle span so the Perfetto export carries it."""
        rec = _current_record.get()
        if rec is None:
            return
        with self._lock:
            if rec.path is None or rec.path == path:
                rec.path = path
            else:
                rec.path = "mixed"
        sp = tracing.tracer.current()
        if sp is not None:
            sp.set_tag("path", rec.path)

    def note_mesh(self, devices: int) -> None:
        """The pool mesh a dispatch of the current cycle ran over, in
        devices: a one-device cycle in a four-device deployment (or the
        reverse) reads off every record."""
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.mesh_devices = (int(devices) if rec.mesh_devices is None
                                    else min(rec.mesh_devices, int(devices)))

    def note_fault(self, point: str, n: int = 1) -> None:
        """A fault-point trigger or degradation (kernel fallback, breaker
        reroute) attributed to the cycle it happened inside."""
        rec = _current_record.get()
        if rec is not None:
            with self._lock:
                rec.faults[point] = rec.faults.get(point, 0) + int(n)

    # ----------------------------------------------------------------- query
    def recent(self, limit: int = 50) -> List[Dict[str, Any]]:
        """Newest-last list of finished cycle record documents."""
        limit = int(limit)
        if limit <= 0:
            return []
        with self._lock:
            records = list(self._ring)
        return [r.to_doc() for r in records[-limit:]]

    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    def recent_durations(self, kinds, limit: int) -> List[float]:
        """duration_ms of the newest ``limit`` records of the given kinds,
        oldest first — the SLO sweep's cheap periodic read (no to_doc
        dict materialization for the whole ring)."""
        with self._lock:
            records = list(self._ring)
        out = [r.duration_ms for r in records if r.kind in kinds]
        return out[-max(int(limit), 0):] if limit > 0 else []

    def summary(self, since_seq: int = 0) -> Dict[str, Any]:
        """Aggregate over records with seq > since_seq (the simulator and
        bench sections snapshot last_seq() at start and summarize their
        own cycles at the end).  A run longer than the ring capacity is
        reported with ``truncated``/``cycles_evicted`` so an aggregate
        over a partial window is never mistaken for the whole run."""
        with self._lock:
            records = [r for r in self._ring if r.seq > since_seq]
            oldest = self._ring[0].seq if self._ring else self._seq + 1
        if not records:
            return {"cycles": 0}
        evicted = max(0, oldest - since_seq - 1)
        durs = sorted(r.duration_ms for r in records)

        def pctl(q: float) -> float:
            idx = min(len(durs) - 1, int(round(q / 100.0 * (len(durs) - 1))))
            return round(durs[idx], 3)

        by_shard: Dict[int, List[float]] = {}
        for r in records:
            if r.shard is not None:
                by_shard.setdefault(r.shard, []).append(r.duration_ms)

        def _shard_agg(durations: List[float]) -> Dict[str, Any]:
            ds = sorted(durations)

            def sp(q: float) -> float:
                i = min(len(ds) - 1, int(round(q / 100.0 * (len(ds) - 1))))
                return round(ds[i], 3)

            return {"cycles": len(ds), "cycle_ms_p50": sp(50),
                    "cycle_ms_p99": sp(99)}

        by_kind: Dict[str, int] = {}
        recompiles: Dict[str, int] = {}
        skips: Dict[str, int] = {}
        faults: Dict[str, int] = {}
        detail: Dict[str, float] = {}
        for r in records:
            by_kind[r.kind] = by_kind.get(r.kind, 0) + 1
            for k, v in r.recompiles.items():
                recompiles[k] = recompiles.get(k, 0) + v
            for k, v in r.skip_reasons.items():
                skips[k] = skips.get(k, 0) + v
            for k, v in r.faults.items():
                faults[k] = faults.get(k, 0) + v
            for k, v in r.detail_ms.items():
                detail[k] = detail.get(k, 0.0) + v
        return {
            "cycles": len(records),
            **({"truncated": True, "cycles_evicted": evicted}
               if evicted else {}),
            "by_kind": by_kind,
            "cycle_ms_p50": pctl(50),
            "cycle_ms_p99": pctl(99),
            # per-shard roll-up (ISSUE 19): keyed by CycleRecord.shard,
            # present only when sharded cycles are in the window so the
            # classic single-process summary shape is unchanged
            **({"by_shard": {str(s): _shard_agg(d)
                             for s, d in sorted(by_shard.items())}}
               if by_shard else {}),
            "jobs_considered": sum(r.jobs_considered for r in records),
            "jobs_placed": sum(r.jobs_placed for r in records),
            "preemptions": sum(r.preemptions for r in records),
            "recompiles": recompiles,
            "skip_reasons": skips,
            "faults": faults,
            "pipeline_conflicts": sum(r.pipeline_conflicts
                                      for r in records),
            "h2d_bytes": sum(r.h2d_bytes for r in records),
            "d2h_bytes": sum(r.d2h_bytes for r in records),
            "sync_wait_ms": round(sum(r.sync_wait_ms for r in records), 3),
            "detail_ms": {k: round(v, 3) for k, v in detail.items()},
            "delta_rows": sum(r.delta_rows for r in records),
            "full_repacks": sum(r.full_repacks for r in records),
            "audit_events": sum(r.audit_events for r in records),
        }

    def reset(self) -> None:
        with self._lock:
            self._ring.clear()


recorder = FlightRecorder()
locks.monitor.contention_sink = recorder.note_lock_wait
