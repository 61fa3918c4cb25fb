"""Fused production cycle driver: every eligible pool's rank + admission +
match in ONE device dispatch, host applies assignments transactionally.

This is the production form of the reference's per-pool match-cycle
architecture (reference: scheduler/src/cook/scheduler/scheduler.clj
:2398-2517 make-pool-handler round-robin; rank cycle :2286-2296) re-drawn
for a device mesh: instead of a host loop over pools with a device round
trip per pool, the host packs all pools' entities into stacked padded
tensors, dispatches the jitted pool-sharded cycle
(parallel/sharded.make_pool_cycle), and walks the returned assignment
vectors to run the transactional launch path (guard txn -> kill-lock ->
cluster launch, scheduler.clj:1028).

Host-side responsibilities that stay host-side (each feeds the kernel a
mask or cap instead of a Python loop over the hot path):
  - plugin launch verdicts (arbitrary host predicates) -> launch_ok
  - offensive-job stifling (scheduler.clj:2205-2257)   -> enqueue_ok
  - launch-rate token budgets                          -> tokens
  - head-of-queue backoff (scheduler.clj:1613-1651)    -> num_considerable
  - pool / quota-group caps (scheduler.clj:2125-2157)  -> pool_quota,
    group_quota + on-device all_gather of running usage
  - within-batch group placement + the launch transaction stay host-side
    post-kernel (they mutate store state).

Pools are grouped by DRU mode (default|gpu — a static of the kernel) and
stacked per group; task/host axes are padded to shared buckets so shapes
recur and XLA reuses the compiled cycle.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..cluster.base import Offer
from ..config import Config
from ..ops import host_prep
from ..ops import telemetry
from ..ops.delta import (
    FLAG_ENQUEUE_OK,
    FLAG_LAUNCH_OK,
    FLAG_PENDING,
    FLAG_USER_FIRST,
    FLAG_VALID,
)
from ..ops.padding import bucket, pad_to
from ..state.schema import DruMode, Job, Pool, SchedulerKind
from ..state.store import Store
from ..utils import audit as _audit
from ..utils import tracing
from ..utils.flight import recorder as _flight
from .constraints import build_constraint_mask, validate_group_placement
from .matcher import MatchCycleResult, Matcher, _BackoffState
from .ranker import build_user_tasks, _quota_vec, _pool_quota_vec

F32 = np.float32
INF = float("inf")


def _count_warmup(kernel: str, runs: int) -> None:
    """``cook_warmup_executions_total{kernel}``: what the boot warm-up
    ran, by kernel."""
    if runs:
        from ..utils.metrics import registry
        registry.counter_inc("cook_warmup_executions", float(runs),
                             {"kernel": kernel})


class _PackedPool:
    """Host-side staging for one pool's cycle inputs."""

    def __init__(self, pool: Pool):
        self.pool = pool
        self.task_ids: List[int] = []
        self.id2job: Dict[int, Job] = {}
        # columnar mode: kernel rows map to job uuids instead of entities.
        # uuid/user/res come as index BASE snapshots + the sorted absolute
        # rows (rows_s); sorted-position lookups go through
        # base[rows_s[pos]] so no full string column is ever gathered
        self.columnar = False
        self.rows_s: Optional[np.ndarray] = None        # i64[T] sorted rows
        self.uuid_base: Optional[np.ndarray] = None     # U36[n] by row
        self.user_base: Optional[np.ndarray] = None     # U64[n] by row
        self.res_base: Optional[np.ndarray] = None      # f32[n, 4] by row
        # structured-mask form (columnar mode; parallel/sharded
        # StructuredPoolCycleInputs): no dense [T, H] mask is ever built
        self.host_gpu: Optional[np.ndarray] = None      # bool[H]
        self.host_blocked: Optional[np.ndarray] = None  # bool[H]
        self.exc_id: Optional[np.ndarray] = None        # i32[T]
        self.exc_mask: Optional[np.ndarray] = None      # bool[E, H]
        self.offers: List[Offer] = []
        self.ctx = None
        self.arrays: Dict[str, np.ndarray] = {}
        self.job_res = None
        self.cmask = None
        self.avail = None
        # overdraft-adjusted availability (pipelined driver only): set by
        # the reconciler when an overlapped cycle consumed capacity this
        # pack's staged avail never saw; the gang rescue/refill places
        # against it instead of pp.avail
        self.avail_headroom: Optional[np.ndarray] = None  # f32[H, 4]
        self.capacity = None
        self.enqueue_ok = None
        self.launch_ok = None
        self.tokens = None
        # compact wire form (CompactPoolCycleInputs): per-user tables +
        # packed admission flags; the device expands them (expand_compact)
        self.compact = False
        self.shares_u: Optional[np.ndarray] = None      # f32[U, 3]
        self.quota_u: Optional[np.ndarray] = None       # f32[U, 4]
        self.tokens_u: Optional[np.ndarray] = None      # f32[U]
        self.flags: Optional[np.ndarray] = None         # u8[T]
        self.disk_base: Optional[np.ndarray] = None     # f32[n] by row
        self.base_compactions = -1   # index compaction epoch at pack
        self.exc_rows: Optional[np.ndarray] = None      # i32[n_exc]
        self.num_considerable = 0
        self.pool_quota = np.full(4, INF, dtype=F32)
        self.group_quota = np.full(4, INF, dtype=F32)
        self.group_id = -1
        self.offensive: List[Job] = []
        self.n_tasks = 0
        self.n_hosts = 0


class _StagedCycle:
    """Phase-1 (stage) output: one cycle's packed pools, grouped by DRU
    mode and ready for dispatch."""

    __slots__ = ("pools", "groups")

    def __init__(self, pools: List[Pool]):
        self.pools = pools
        self.groups: List["_StagedGroup"] = []


class _StagedGroup:
    """One DRU-mode group's staged kernel inputs (host arrays already
    stacked/padded; uploaded by dispatch_group).  With ``resident`` the
    rows/flags fields are the device-resident buffers (already synced by
    the delta scatter — dispatch_group must not re-account them as
    upload bytes)."""

    __slots__ = ("gpu_mode", "group", "inp", "structured", "cap", "T", "H",
                 "stage_ms", "resident")

    def __init__(self, *, gpu_mode, group, inp, structured, cap, T, H,
                 stage_ms, resident=False):
        self.gpu_mode = gpu_mode
        self.group = group
        self.inp = inp
        self.structured = structured
        self.cap = cap
        self.T = T
        self.H = H
        self.stage_ms = stage_ms
        self.resident = resident


class _GroupDispatch:
    """An in-flight device dispatch of one staged group: the kernel result
    refs plus the compact-output refs whose async device->host copies are
    already rolling.  ``fetched`` holds the host arrays after
    fetch_group."""

    __slots__ = ("sg", "res", "outs", "fetched")

    def __init__(self, sg: _StagedGroup, res, outs):
        self.sg = sg
        self.res = res
        self.outs = outs
        self.fetched = None


class _ResidentPack:
    """One DRU-mode group's device-resident wire arrays: the [P, T] rows
    permutation + flags bytes living on device across cycles, plus the
    host shadow the per-cycle diff runs against.  ``key`` pins the group
    composition and bucket shape; ``epoch`` the index compaction epoch
    the row ids are valid in."""

    __slots__ = ("key", "epoch", "rows_dev", "flags_dev", "rows_host",
                 "flags_host")

    def __init__(self, key, epoch, rows_dev, flags_dev, rows_host,
                 flags_host):
        self.key = key
        self.epoch = epoch
        self.rows_dev = rows_dev
        self.flags_dev = flags_dev
        self.rows_host = rows_host
        self.flags_host = flags_host


class FusedCycleDriver:
    def __init__(self, store: Store, config: Config, matcher: Matcher,
                 plugins, rate_limits, mesh=None, shard_id=None):
        self.store = store
        self.config = config
        self.matcher = matcher
        self.plugins = plugins
        self.rate_limits = rate_limits
        self._mesh = mesh
        # sharded-controller mode (ISSUE 19): this process owns ONE mesh
        # shard, so the [P, ...] pool-stacked arrays it builds cover only
        # its partition's pools and its resident buffers are committed
        # per-PROCESS — the mesh it runs on must be this shard's local
        # device slice, never a pool mesh spanning other shards' pools
        # (mesh() enforces this)
        self.shard_id = shard_id
        self._cycles: Dict[Tuple, object] = {}
        # device-resident mirror of the columnar index's immutable res/disk
        # base columns: rows append-only while the compaction epoch is
        # unchanged, so steady-state cycles upload only the NEW rows
        # (ops/delta.DeviceBaseMirror, shared with the columnar rank path)
        from ..ops.delta import DeviceBaseMirror, PackDeltaApplier
        self._mirror = DeviceBaseMirror(mesh=self.mesh)
        # device-RESIDENT pack (ISSUE 7 tentpole): the stacked [P, T]
        # rows/flags wire arrays live in device buffers across cycles,
        # keyed by DRU mode; each stage diffs the freshly built host
        # arrays against the shadow and scatter-applies just the delta
        self._resident: Dict[bool, _ResidentPack] = {}
        self._applier = PackDeltaApplier(mesh=self.mesh)
        # quiet-pool fast path: the index's tx-event delta feed
        # (state/index.py attach_pack_consumer) tells the pack when a
        # pool saw zero churn since its last pack, letting it reuse the
        # cached [T]-sized arrays wholesale instead of rebuilding them
        self._delta_cid: Optional[int] = None
        self._pack_cache: Dict[str, Dict] = {}

    # ------------------------------------------------------------------ mesh
    def mesh(self):
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh

            from ..parallel.mesh import POOL_AXIS
            self._mesh = Mesh(np.array(jax.devices()[:1]), (POOL_AXIS,))
        return self._mesh

    def _put(self, tree):
        """Host arrays to the device, every [P, ...] array split over the
        pool mesh so that each device is handed its own pools' slice
        straight from the host (parallel/mesh.pool_sharding): the
        placement the cycle's shard_map and the resident buffers expect,
        so a dispatch moves nothing between devices.  On one device it
        is the plain uncommitted upload."""
        import jax
        mesh = self.mesh()
        if mesh.size == 1:
            import jax.numpy as jnp
            return jax.tree_util.tree_map(jnp.asarray, tree)
        from ..parallel.mesh import pool_sharding
        return jax.device_put(tree, pool_sharding(mesh))

    @staticmethod
    def _pool_row(stacked, slot: int):
        """Row ``slot`` of a pool-stacked [P, ...] device array, sliced on
        the device that holds it: no gather of the other shards' rows,
        and the slice (an async device op) does not keep the P-wide
        buffer alive."""
        for sh in stacked.addressable_shards:
            lo, hi, _ = sh.index[0].indices(stacked.shape[0])
            if lo <= slot < hi:
                return sh.data[slot - lo]
        raise IndexError(f"pool slot {slot} is on no addressable shard")

    def _cycle_fn(self, gpu_mode: bool, considerable_cap: int,
                  structured: bool = False, compact: bool = False):
        key = (id(self.mesh()), gpu_mode, self.config.max_over_quota_jobs,
               considerable_cap, structured, compact)
        fn = self._cycles.get(key)
        if fn is None:
            from ..parallel.sharded import make_pool_cycle
            fn = telemetry.instrument_jit("fused.pool_cycle", make_pool_cycle(
                self.mesh(), gpu_mode=gpu_mode,
                max_over_quota_jobs=self.config.max_over_quota_jobs,
                considerable_cap=considerable_cap, structured=structured,
                compact=compact))
            self._cycles[key] = fn
        return fn

    # ------------------------------------------------------ dispatch groups
    def _cycle_pools(self) -> List[Pool]:
        """The pools a cycle packs: active and not direct."""
        return [p for p in self.store.pools()
                if p.state == "active"
                and p.scheduler is not SchedulerKind.DIRECT]

    def _stacked(self, n_pools: int) -> int:
        """P of a dispatch that stacks ``n_pools``: padded to a whole
        number of pools per mesh device."""
        n_dev = self.mesh().size
        return max(n_dev, -(-n_pools // n_dev) * n_dev)

    def dispatch_groups(self) -> Dict[bool, int]:
        """DRU mode (gpu?) -> how many pools one dispatch of that mode
        stacks when every pool has pending work, by the rule
        :meth:`stage` groups by.  What the warm-up has to cover."""
        groups: Dict[bool, int] = {}
        for p in self._cycle_pools():
            gm = p.dru_mode is DruMode.GPU
            groups[gm] = groups.get(gm, 0) + 1
        return groups

    # --------------------------------------------------------------- warmup
    def warmup(self, *, tasks: int, hosts: int, users: int = 8,
               sweep: bool = False, gpu: bool = False) -> int:
        """Boot-time cold-start killer (config.PipelineConfig): compile
        AND execute once, with zeroed inputs, what the daemon will really
        dispatch, so the first-call compile spikes land at boot — inside
        the leader's takeover window — and never inside a live cycle.
        Executing (not just AOT-lowering) populates the jit call cache,
        so steady-state cycles at warmed shapes trace zero times; with
        the persistent compilation cache enabled the XLA compile itself
        is also disk-cached across restarts.

        ``tasks`` / ``hosts`` / ``users`` are the design point of ONE
        pool.  How many pools a dispatch stacks is read off the store
        (:meth:`dispatch_groups`; an empty store warms the one default
        pool to come), and the device base mirror is sized for every
        pool's rows, so a several-pool deployment warms the stacked
        [P, T] cycle, its delta scatters and the mirror's chunk appends
        — not the one-pool shapes it never runs.

        ``sweep=True`` warms every (T, H) bucket up to the targets (ramp
        traffic hits warm executables at every scale), else just the
        target buckets.  Returns the number of cycle executions."""
        if tasks <= 0 or hosts <= 0:
            return 0
        if not self.config.columnar_index:
            # warmup covers the production compact/columnar wire form
            # only; silently "warming" the wrong kernel variant would
            # spend boot time and still compile inside the first live
            # cycle (docs/PERFORMANCE.md)
            import logging
            logging.getLogger(__name__).warning(
                "fused-cycle warmup skipped: columnar_index=False packs "
                "the dense PoolCycleInputs variant, which warmup does "
                "not cover")
            return 0

        def grid(n: int, minimum: int = 64) -> List[int]:
            top = bucket(n, minimum=minimum)
            if not sweep:
                return [top]
            out, b = [], minimum
            while b <= top:
                out.append(b)
                b *= 2
            return out

        groups = self.dispatch_groups() or {False: 1}
        if gpu:
            groups.setdefault(True, 1)
        # the base mirror holds the index's rows of EVERY pool: sized for
        # the design point of all of them, or the rows the index already
        # has if those are more; the live mirror keeps this floor, so its
        # capacity (a shape of the cycle) is the warmed one from the
        # first cycle on
        base_rows = self.store.ensure_index().row_count()
        T_top = bucket(tasks)
        mir = bucket(max(T_top * sum(groups.values()), base_rows),
                     minimum=1024)
        self._mirror.reserve(mir)
        U = bucket(max(users, 1), minimum=8)
        runs = 0
        for gm, n_pools in sorted(groups.items()):
            P = self._stacked(n_pools)
            for T in grid(tasks):
                for H in grid(hosts):
                    runs += self._warm_cycle(gm, P, T, H, U, mir)
                if self.config.resident_pack:
                    self._warm_delta_apply(P, T)
        self._warm_delta_append(mir, room=mir - base_rows, design=T_top)
        return runs

    def _warm_cycle(self, gm: bool, P: int, T: int, H: int, U: int,
                    mir: int) -> int:
        """One zero-world execution of the compact fused cycle per distinct
        cap bucket at [P, T] x H; returns the executions."""
        import jax

        from ..parallel.sharded import CompactPoolCycleInputs
        E = 8  # exception bucket floor: no complex jobs in the zero world
        # the dispatch cap is bucket(max matcher cap over the group's
        # pools); pool_matchers overrides can bucket differently from the
        # default, so warm every DISTINCT cap bucket
        caps = {bucket(self.config.default_matcher.max_jobs_considered)}
        caps.update(bucket(mc.max_jobs_considered)
                    for _rx, mc in self.config.pool_matchers)
        caps = sorted({min(c, T) for c in caps})
        f32, i32 = np.float32, np.int32
        runs = 0
        with tracing.span("warmup.cycle", P=P, T=T, H=H, gpu=gm) as sp:
            # jit keys its executables on the inputs' placement: the
            # zeroed inputs carry the live cycle's (pool-sharded, the
            # base mirror replicated), or the warm pass would leave the
            # variant the live cycle calls cold
            replicated = self._mirror.placement.put
            inp = CompactPoolCycleInputs(
                res_base=replicated(np.zeros((mir, 4), dtype=f32)),
                disk_base=replicated(np.zeros(mir, dtype=f32)),
                **self._put(dict(
                    rows=np.zeros((P, T), dtype=i32),
                    flags=np.zeros((P, T), dtype=np.uint8),
                    tokens_u=np.full((P, U), np.inf, dtype=f32),
                    shares_u=np.full((P, U, 3), np.inf, dtype=f32),
                    quota_u=np.full((P, U, 4), np.inf, dtype=f32),
                    num_considerable=np.zeros((P,), dtype=i32),
                    pool_quota=np.full((P, 4), np.inf, dtype=f32),
                    group_quota=np.full((P, 4), np.inf, dtype=f32),
                    group_id=np.full((P,), -1, dtype=i32),
                    host_gpu=np.zeros((P, H), dtype=bool),
                    host_blocked=np.ones((P, H), dtype=bool),
                    exc_rows=np.full((P, E), -1, dtype=i32),
                    exc_mask=np.zeros((P, E, H), dtype=bool),
                    avail=np.zeros((P, H, 4), dtype=f32),
                    capacity=np.zeros((P, H, 4), dtype=f32))))
            for cap in caps:
                fn = self._cycle_fn(gm, cap, True, compact=True)
                res = fn(inp)
                # the apply's per-pool slice of the ranked queue, on
                # every device that holds one
                jax.block_until_ready(
                    [res.n_queue] + [self._pool_row(res.queue_rows, i)
                                     for i in range(P)])
                runs += 1
            _count_warmup("fused.pool_cycle", runs)
            sp.set_tag("runs", runs)
        return runs

    def _warm_delta_apply(self, P: int, T: int) -> None:
        """The resident pack's delta scatter compiles once per (buffer
        shape+sharding, delta bucket, value codec): warm every bucket up
        to the [P, T] buffer's size so a steady-state delta never traces
        inside a live cycle (the zero-recompile guarantee the warmup
        assertion protects).  The warm buffers must carry the SAME
        placement as the live resident buffers — jit keys executables on
        input sharding, so an unsharded warm pass would leave the
        sharded variant cold."""
        import jax

        from ..ops.delta import _DELTA_MIN_BUCKET
        n_flat = P * T
        kbs, k = set(), _DELTA_MIN_BUCKET
        while k < n_flat:
            kbs.add(k)
            k *= 2
        kbs.add(n_flat)  # the clamped top bucket
        with tracing.span("warmup.delta_apply", P=P, T=T) as sp:
            rows_b, flags_b = self._put((np.zeros((P, T), dtype=np.int32),
                                         np.zeros((P, T), dtype=np.uint8)))
            # all-sentinel indices make every scatter a no-op, so the
            # buffers stay zeros; with the quantized wire the narrow
            # value codecs are warmed too (i8 via zero deltas, i16 via an
            # out-of-i8 delta)
            variants = [(0, False)]
            if self.config.quantized_wire:
                variants += [(0, True), (1000, True)]
            runs = 0
            for k in sorted(kbs):
                idx = np.full(k, n_flat, dtype=np.int32)
                for val, quantize in variants:
                    rows_b, flags_b = self._applier.apply(
                        rows_b, flags_b, idx,
                        np.full(k, val, dtype=np.int32),
                        np.zeros(k, dtype=np.uint8), quantize=quantize)
                    runs += 1
            jax.block_until_ready(rows_b)
            _count_warmup("delta.apply", runs)
            sp.set_tag("runs", runs)

    def _warm_delta_append(self, mir: int, room: int, design: int) -> None:
        """Arrivals append rows to the resident base mirror in bucketed
        chunks (ops/delta.DeviceBaseMirror.sync), one executable per
        (capacity, chunk bucket) and column: warm every chunk bucket up
        to one pool's design point (the least bucket at least), as far
        as the mirror has ``room`` (a chunk beyond it re-uploads
        instead)."""
        import jax

        from ..ops.delta import APPEND_MIN_BUCKET, append_chunk
        with tracing.span("warmup.delta_append", rows=mir) as sp:
            # replicated like the live mirror and its chunks
            put = self._mirror.placement.put
            res = put(np.zeros((mir, 4), dtype=np.float32))
            disk = put(np.zeros(mir, dtype=np.float32))
            off = put(np.asarray(0, dtype=np.int32))
            runs, kb = 0, APPEND_MIN_BUCKET
            while kb <= min(max(design, APPEND_MIN_BUCKET), room):
                res = append_chunk(
                    res, put(np.zeros((kb, 4), dtype=np.float32)), off)
                disk = append_chunk(
                    disk, put(np.zeros(kb, dtype=np.float32)), off)
                runs += 2
                kb *= 2
            jax.block_until_ready((res, disk))
            _count_warmup("delta.append", runs)
            sp.set_tag("runs", runs)

    # ---------------------------------------------------------- base mirror
    def _sync_base_mirror(self, res_base: np.ndarray, disk_base: np.ndarray,
                          compactions: int):
        """Bring the device base mirror up to the snapshot (see
        ops/delta.DeviceBaseMirror): full (re)upload on a compaction
        epoch change or capacity overflow, else one bucketed chunk append
        of the rows added since the last cycle."""
        return self._mirror.sync(res_base, disk_base, compactions)

    # ------------------------------------------------------- resident pack
    def reset_resident(self) -> None:
        """Drop ALL device-resident state — the rows/flags pack, the
        quiet-pool cache, and the res/disk base mirror — so the next
        stage rebuilds from scratch (leader handoff, degraded cycle,
        tests).  The mirror must go too: after a device failure its
        buffers live on the failed device state, and its compaction-epoch
        key would otherwise keep handing them out forever.  Safe at any
        time — residency is a pure mirror of what the next full pack
        would build."""
        self._resident.clear()
        self._pack_cache.clear()
        self._mirror.reset()

    def _sync_resident(self, gpu_mode: bool, key: Tuple, rows_p: np.ndarray,
                       flags_p: np.ndarray, epoch: int):
        """Bring the resident [P, T] rows/flags device buffers up to the
        freshly staged host arrays: steady state diffs against the host
        shadow (delta EXTRACTION — native/pack.cpp when built) and
        dispatches the jitted scatter (ops/delta.PackDeltaApplier) of
        just the changed positions; a compaction-epoch fence, group/
        bucket reshape, or kernel fault forces a clean full upload
        (``cook_resident_repack_total{reason=}``).  Returns
        (rows_dev, flags_dev)."""
        from ..native import pack as native_pack
        from ..utils.faults import injector as _faults
        from ..utils.metrics import registry
        st = self._resident.get(gpu_mode)
        reason = None
        if st is None:
            reason = "cold"
        elif st.key != key:
            reason = "shape"
        elif st.epoch != epoch:
            reason = "compaction"
        if reason is None:
            try:
                _faults.fire("delta.extract")
                idx = native_pack.pack_diff(st.rows_host, rows_p,
                                            st.flags_host, flags_p)
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "resident-pack delta extraction failed; full repack")
                registry.counter_inc("cook_kernel_fallback",
                                     labels={"kernel": "delta.extract"})
                _flight.note_fault("kernel.dispatch-fallback")
                reason = "fault"
            else:
                k = int(idx.size)
                if k == 0:
                    _flight.note_delta(0)
                    return st.rows_dev, st.flags_dev
                # a scatter pair costs ~9 B/row vs ~5 B/row for the full
                # upload: past roughly half the table the repack is the
                # cheaper transfer AND skips the scatter dispatch
                if 2 * k > rows_p.size:
                    reason = "oversize"
                else:
                    try:
                        with tracing.span("delta.apply", rows=k,
                                          gpu=gpu_mode):
                            _faults.fire("delta.apply")
                            flat = rows_p.reshape(-1)
                            fflat = flags_p.reshape(-1)
                            # stage (h2d starts on fresh buffers) then
                            # commit (scatter dispatch): under the
                            # pipelined driver this whole block runs in
                            # cycle k+1's STAGE phase while cycle k's
                            # kernel is still in flight, so the delta
                            # bytes move during compute
                            staged = self._applier.stage(
                                tuple(st.rows_dev.shape), idx,
                                flat[idx], fflat[idx],
                                quantize=bool(
                                    self.config.quantized_wire))
                            rows_dev, flags_dev = self._applier.commit(
                                st.rows_dev, st.flags_dev, staged)
                    except telemetry.KernelBuildError:
                        raise  # repeats every cycle: not a fault to absorb
                    except Exception:
                        import logging
                        logging.getLogger(__name__).exception(
                            "resident-pack delta apply failed; full repack")
                        registry.counter_inc(
                            "cook_kernel_fallback",
                            labels={"kernel": "delta.apply"})
                        _flight.note_fault("kernel.dispatch-fallback")
                        reason = "fault"
                    else:
                        registry.counter_inc("cook_delta_rows", float(k))
                        _flight.note_delta(k)
                        st.rows_dev, st.flags_dev = rows_dev, flags_dev
                        st.rows_host, st.flags_host = rows_p, flags_p
                        return rows_dev, flags_dev
        registry.counter_inc("cook_resident_repack",
                             labels={"reason": reason})
        _flight.note_repack(reason)
        telemetry.count_transfer("h2d", rows_p.nbytes + flags_p.nbytes)
        # each pool shard owns its own slice of the resident buffers
        rows_dev, flags_dev = self._put((rows_p, flags_p))
        self._resident[gpu_mode] = _ResidentPack(
            key, epoch, rows_dev, flags_dev, rows_p, flags_p)
        return rows_dev, flags_dev

    # ------------------------------------------------------------------ pack
    def _pack_pool_columnar(self, scheduler, pool: Pool, exclude=None,
                            token_delta=None) -> Optional[_PackedPool]:
        """Pack one pool's cycle inputs straight off the columnar index
        (state/index.py): no entity materialization for the plain-job
        majority — entities are fetched only for rows the vectorized path
        can't decide (user constraints, groups, checkpoint, prior
        instances; see index._is_complex) and for the offensive minority.
        This closes the 'fused cycle packs from entities' gap tracked in
        docs/PARITY.md; decision parity with the entity pack is asserted by
        tests/test_fused_cycle.py."""
        # the pack's three parts carry spans of their own (pack.index,
        # pack.offers, pack.rows -> detail_ms.pack_*): which of the index
        # refresh, the offer staging and the row selection arrivals push
        # off the fast path reads off /debug/cycles
        store = self.store
        with tracing.span("pack.index", pool=pool.name):
            idx = store.ensure_index()
            # ONE snapshot of the reservations: the rebalancer thread
            # mutates reserved_hosts concurrently, and every later read
            # in this pack (owner rows, host blocks, local owners) must
            # see the same set
            resv = dict(scheduler.reserved_hosts)
            # tx-event delta feed (state/index.py attach_pack_consumer):
            # one drain per pack.  A quiet pool — zero journaled rows, no
            # fence — reuses its cached [T]-sized pack products wholesale
            # instead of rebuilding them (the incremental-view-
            # maintenance fast path; ineligible shapes fall through to
            # the full rebuild below)
            if self._delta_cid is None:
                self._delta_cid = idx.attach_pack_consumer()
            delta = idx.pack_delta(self._delta_cid, pool.name)
            cached = self._pack_cache.get(pool.name)
            quiet = (cached is not None and not delta.fence
                     and delta.rows.size == 0
                     and delta.epoch == cached["epoch"]
                     and delta.version == cached["version"]
                     and not self.plugins.launch_filters
                     and not self._resv_owner_in_pack(idx, resv, cached))
            snap = None
            if not quiet:
                self._pack_cache.pop(pool.name, None)
                snap = idx.fused_arrays(pool.name, owner_uuids=list(resv),
                                        compact=True)
        if quiet:
            with tracing.span("pack.rows", pool=pool.name, cached=True):
                return self._pack_pool_cached(scheduler, pool, cached, resv,
                                              exclude=exclude,
                                              token_delta=token_delta)
        if snap is None:
            return None
        with tracing.span("pack.rows", pool=pool.name):
            return self._pack_rows_columnar(scheduler, pool, snap, delta,
                                            resv, exclude=exclude,
                                            token_delta=token_delta)

    def _pack_rows_columnar(self, scheduler, pool: Pool, snap, delta,
                            resv: Dict, exclude=None,
                            token_delta=None) -> _PackedPool:
        """The full (non-quiet) columnar pack over one index snapshot:
        exception rows, admission flags, caps."""
        store, cfg = self.store, self.config
        arrays, rows_s = snap.arrays, snap.rows_s
        uuid_base, complex_rows, owner_rows = \
            snap.uuid_base, snap.complex_s, snap.owner_rows
        users = snap.users
        pp = _PackedPool(pool)
        pp.columnar = True
        pp.rows_s = rows_s
        pp.uuid_base, pp.user_base, pp.res_base = \
            uuid_base, snap.user_base, snap.res_base
        # device-resident base mirror inputs: NO per-task resource columns
        # are gathered on the host at all (expand_compact gathers the
        # res/disk base by rows on device)
        pp.disk_base = snap.disk_base
        pp.base_compactions = snap.compactions
        # sorted-position -> uuid, via the base snapshot (no full gather)
        uuid_at = lambda sel: uuid_base[rows_s[sel]]
        T = rows_s.size
        pp.arrays, pp.n_tasks = arrays, T
        pend = arrays["pending"]
        pp.compact = True

        # per-user share/quota TABLES: the kernel gathers them on device via
        # user_rank (CompactPoolCycleInputs), so the host never broadcasts
        # ~32 B/task of user data into [T]-sized columns
        pp.shares_u, pp.quota_u = self._user_tables(pool, users)

        host_index = self._pack_offers(pp, scheduler, pool)
        offers = pp.offers
        if offers:
            H = len(offers)
            reserved_idx = [host_index[hn]
                            for hn in resv.values()
                            if hn in host_index]
            pp.host_blocked[reserved_idx] = True
            # exception rows = complex jobs + reservation owners (owners
            # must punch through the blanket reserved-host block; owners
            # whose reserved host serves another pool need no exception)
            is_exc = pend & complex_rows
            local_owners = [u for u, hn in resv.items()
                            if hn in host_index]
            if local_owners:
                # int row-membership test against rows resolved under the
                # SAME index lock hold as rows_s (a post-snapshot rows_for
                # could race a compaction's row remap); a string isin would
                # re-gather the full uuid column this pack is built to avoid
                local_rows = np.array(
                    [owner_rows[u] for u in local_owners
                     if u in owner_rows], dtype=np.int64)
                is_exc |= pend & np.isin(rows_s, local_rows)
            cjobs, keep = [], []
            for i in np.flatnonzero(is_exc):
                job = store.job(str(uuid_at(i)))
                if job is not None:
                    cjobs.append(job)
                    keep.append(i)
            crow = np.array(keep, dtype=np.int64)
            ctx = self.matcher._constraint_context(
                cjobs, resv)
            self.matcher._fill_cotask_host_attributes(
                ctx, pool.name, offers, scheduler.clusters)
            pp.ctx = ctx
            if cjobs:
                # the compiler emits COMPLETE rows (gpu isolation,
                # max-tasks, reservations included), so an exception row
                # fully replaces the base
                pp.exc_mask = build_constraint_mask(cjobs, offers, ctx)
                pp.exc_rows = crow.astype(np.int32)
            else:
                pp.exc_mask = np.zeros((1, H), dtype=bool)
                pp.exc_rows = np.zeros(0, dtype=np.int32)

        # offensive-job filter: vectorized over the BASE columns (the
        # compact pack gathers no per-task resource columns), then one
        # [T] bool gather by rows
        enqueue_ok = np.ones(T, dtype=bool)
        limits = cfg.offensive_job_limits
        if limits is not None:
            res_b = snap.res_base
            bad_base = ((res_b[:, 1] > limits.memory_gb * 1024.0)
                        | (res_b[:, 0] > limits.cpus))
            bad = pend & bad_base[rows_s]
            if bad.any():
                enqueue_ok[bad] = False
                pp.offensive = [j for j in (store.job(str(u))
                                            for u in uuid_at(bad))
                                if j is not None]
                # one gather over the existing wire arrays attributes the
                # aggregate to job uuids (utils/audit.py)
                _audit.note_skips(store.audit,
                                  {"offensive": list(uuid_at(bad))},
                                  pool=pool.name)
        pp.enqueue_ok = enqueue_ok

        # plugin launch verdicts: only when a filter is configured, and the
        # per-uuid verdict cache is consulted before materializing an
        # entity (plugins/launch.clj caches accept/defer the same way), so
        # steady state costs no deep copies even with filters on
        launch_ok = np.ones(T, dtype=bool)
        if self.plugins.launch_filters:
            for i in np.flatnonzero(pend):
                uuid = str(uuid_at(i))
                cached = self.plugins.launch_verdict_cached(uuid)
                if cached is None:
                    job = store.job(uuid)
                    if job is None:
                        # vanished-but-still-indexed uuid: cache a synthetic
                        # accept so the next cycle stays copy-free instead
                        # of re-missing and re-fetching forever.  Short TTL:
                        # if the uuid re-materializes (store swap race) the
                        # real filters re-run within seconds, not 60s
                        self.plugins.cache_launch_verdict(uuid, True,
                                                          ttl_s=5.0)
                        cached = True
                    else:
                        cached = self.plugins.launch_allowed(job)
                if not cached:
                    launch_ok[i] = False
            filtered = ~launch_ok
            if filtered.any():
                _audit.note_skips(
                    store.audit,
                    {"launch-filtered": list(uuid_at(filtered))},
                    pool=pool.name)
        # pipelined-driver speculation mask (sched/pipeline.py): rows the
        # in-flight overlapped cycle is about to launch are withheld from
        # THIS cycle's launch candidates (they'd conflict at reconcile).
        # Row ids are only valid within one index compaction epoch; on a
        # mismatch the mask is skipped and reconciliation catches the
        # conflicts instead (rare: compaction between two packs).
        spec_masked = None
        if exclude is not None:
            kind, epoch, rows = exclude
            if kind == "rows" and epoch == snap.compactions and len(rows):
                masked = pend & np.isin(rows_s, rows)
                if masked.any():
                    launch_ok = launch_ok & ~masked
                    spec_masked = masked
                    _audit.note_skips(
                        store.audit,
                        {"pipeline-speculative": list(uuid_at(masked))},
                        pool=pool.name)
        pp.launch_ok = launch_ok

        # launch-rate token budgets per USER (device gathers via user_rank)
        launch_rl = self.rate_limits.job_launch
        pp.tokens_u = self._tokens_u(pool, users, token_delta)

        # gang-cohort admission: every gang member is a complex row, so
        # the materialized exception jobs carry the full cohorts
        gang_members: Dict[str, List] = {}
        if pp.ctx is not None and len(pp.exc_rows):
            for i, job in zip(pp.exc_rows, cjobs):
                if pend[i] and job.group is not None and getattr(
                        pp.ctx.groups.get(job.group), "gang", False):
                    gang_members.setdefault(job.group, []).append(
                        (int(i), job))
        tok_by_user = dict(zip(users, pp.tokens_u.tolist()))
        self._gang_cohort_admission(
            pool, pp.ctx.groups if pp.ctx is not None else {},
            gang_members, launch_ok,
            (lambda u: tok_by_user.get(u, 0.0))
            if launch_rl.enforce else None,
            spec_masked=spec_masked)

        # the admission bools + user-segment boundaries, packed into one
        # wire byte per task (user_rank/first_idx re-derive on device)
        from ..ops.delta import pack_flags
        pp.flags = pack_flags(pend, arrays["valid"], arrays["is_first"],
                              enqueue_ok=enqueue_ok, launch_ok=launch_ok)

        # quiet-pool cache (the delta-feed fast path above): only shapes
        # with no entity-coupled rows are reusable wholesale — no COMPLEX
        # pending rows (their constraint masks depend on entities the
        # event feed doesn't cover; checked against the snapshot, NOT
        # pp.exc_rows, which is only populated when offers exist — an
        # offer-less cycle must not cache a constrained job as maskless),
        # no offensive rows (their stifle kills are in flight), no launch
        # filters (verdict TTLs live outside the index).  Reservations
        # per se are fine: their blanket host blocks are re-applied per
        # cycle by the fast path, and an OWNER entering this pool's
        # pending set is re-checked against the live map on every reuse
        if (not self.plugins.launch_filters and not pp.offensive
                and not (pend & complex_rows).any()):
            flags0 = pp.flags
            if spec_masked is not None and spec_masked.any():
                # cache the PRE-speculation flags: the in-flight footprint
                # changes every cycle and is re-patched by the fast path
                flags0 = flags0.copy()
                flags0[spec_masked] |= np.uint8(FLAG_LAUNCH_OK)
            self._pack_cache[pool.name] = {
                "epoch": snap.compactions, "version": delta.version,
                "rows_s": rows_s, "pend": pend, "flags0": flags0,
                "users": users, "uuid_base": uuid_base,
                "user_base": snap.user_base, "res_base": snap.res_base,
                "disk_base": snap.disk_base}

        self._pack_caps(pp, pool)
        return pp

    def _pack_offers(self, pp: _PackedPool, scheduler, pool: Pool
                     ) -> Optional[Dict[str, int]]:
        """Per-cycle offer staging shared by the full pack and the
        quiet-pool fast path: breaker-filtered offers (a tripped cluster
        contributes none, so the kernel routes demand at healthy
        clusters) plus the STRUCTURED per-host base vectors — gpu
        isolation and max-tasks blocking (constraints.clj:122,433; see
        parallel/sharded.StructuredPoolCycleInputs) — and the
        avail/capacity stacks.  Returns hostname -> index for the full
        path's reservation/exception handling (None when no offers; the
        empty-offer fallback shapes are set here so the two paths can
        never diverge)."""
        with tracing.span("pack.offers", pool=pool.name):
            return self._stage_offers(pp, scheduler, pool)

    def _stage_offers(self, pp: _PackedPool, scheduler, pool: Pool
                      ) -> Optional[Dict[str, int]]:
        cfg = self.config
        offers: List[Offer] = []
        for cluster in scheduler.launchable_clusters(pool.name):
            offers.extend(cluster.pending_offers(pool.name))
        pp.offers = offers
        pp.n_hosts = len(offers)
        if not offers:
            pp.host_gpu = np.zeros(1, dtype=bool)
            pp.host_blocked = np.ones(1, dtype=bool)
            pp.exc_rows = np.zeros(0, dtype=np.int32)
            pp.exc_mask = np.zeros((1, 1), dtype=bool)
            pp.avail = np.zeros((1, 4), dtype=F32)
            pp.capacity = np.zeros((1, 4), dtype=F32)
            return None
        H = len(offers)
        pp.host_gpu = np.array([o.capacity.gpus > 0 for o in offers],
                               dtype=bool)
        host_tasks = np.array([o.task_count for o in offers],
                              dtype=np.int32)
        host_blocked = np.zeros(H, dtype=bool)
        if cfg.max_tasks_per_host is not None:
            host_blocked |= host_tasks >= cfg.max_tasks_per_host
        pp.host_blocked = host_blocked
        pp.avail = np.array(
            [[o.available.cpus, o.available.mem, o.available.gpus,
              o.available.disk] for o in offers], dtype=F32)
        pp.capacity = np.array(
            [[o.capacity.cpus, o.capacity.mem, o.capacity.gpus,
              o.capacity.disk] for o in offers], dtype=F32)
        return {o.hostname: h for h, o in enumerate(offers)}

    def _user_tables(self, pool: Pool, users: List[str]
                     ) -> Tuple[np.ndarray, np.ndarray]:
        """Per-user share/quota tables in segment order (shared by the
        full pack, the quiet-pool fast path, and — via the same module
        function — the columnar rank path)."""
        from .ranker import build_user_tables
        return build_user_tables(self.store, pool.name, users)

    def _tokens_u(self, pool: Pool, users: List[str],
                  token_delta) -> np.ndarray:
        """Per-user launch-rate token budgets, net of the pipelined
        driver's in-flight spends (shared by both pack paths)."""
        launch_rl = self.rate_limits.job_launch
        if not launch_rl.enforce:
            return np.full(max(len(users), 1), INF, dtype=F32)
        from ..policy import pool_user_key
        tokens = np.array(
            [launch_rl.get_token_count(pool_user_key(pool.name, u))
             for u in users], dtype=F32)
        if token_delta:
            # tokens an overlapped in-flight cycle will spend at its
            # apply (the limiter hasn't seen the spends yet)
            tokens = np.maximum(tokens - np.array(
                [token_delta.get(u, 0.0) for u in users], dtype=F32), 0.0)
        return tokens

    def _resv_owner_in_pack(self, idx, resv: Dict, c: Dict) -> bool:
        """True when a reservation OWNER is one of the cached pack's
        pending rows: owners need an exception-mask punch-through, which
        only the full pack builds.  Plain reservations (owner elsewhere)
        stay fast-path compatible — their blanket host blocks are
        per-cycle state applied by _pack_pool_cached."""
        if not resv:
            return False
        owner_rows = idx.rows_for(list(resv))
        if not owner_rows.size:
            return False
        return bool(np.isin(owner_rows, c["rows_s"][c["pend"]]).any())

    def _pack_pool_cached(self, scheduler, pool: Pool, c: Dict,
                          resv: Dict, exclude=None,
                          token_delta=None) -> _PackedPool:
        """Quiet-pool fast path: the index's delta feed reported zero
        churn since this pool's last pack, so the [T]-sized pack products
        (sorted rows, admission flags) are reused WHOLESALE — no index
        snapshot, no order repair, no flags rebuild, no O(T) host work.
        Only the per-user tables, offers, reserved-host blocks, caps,
        and the pipelined driver's speculative mask are rebuilt per
        cycle; the mask is a bit-patch over the cached flags, which the
        resident pack then ships as a device-side scatter delta — an
        in-flight footprint is never a repack (ISSUE 7 tentpole (d)).

        Eligibility was checked by the caller + at cache time: no
        reservation OWNERS pending in this pool, no launch filters, and
        the cached pack had no exception or offensive rows — so
        exceptions are empty and enqueue/launch verdicts are all-accept
        by construction."""
        pp = _PackedPool(pool)
        pp.columnar = True
        pp.compact = True
        rows_s = c["rows_s"]
        pp.rows_s = rows_s
        pp.uuid_base, pp.user_base = c["uuid_base"], c["user_base"]
        pp.res_base, pp.disk_base = c["res_base"], c["disk_base"]
        pp.base_compactions = c["epoch"]
        T = rows_s.size
        pp.n_tasks = T
        pend = c["pend"]
        users = c["users"]
        pp.shares_u, pp.quota_u = self._user_tables(pool, users)

        host_index = self._pack_offers(pp, scheduler, pool)
        if host_index is not None:
            # blanket reserved-host blocks are per-cycle state, applied
            # here exactly as the full path does (owners needing the
            # punch-through exception forced a full rebuild upstream)
            reserved_idx = [host_index[hn] for hn in resv.values()
                            if hn in host_index]
            pp.host_blocked[reserved_idx] = True
            # eligibility guarantees no exception rows; the empty ctx
            # still carries the co-task host attributes the gang/group
            # apply path reads
            pp.exc_mask = np.zeros((1, len(pp.offers)), dtype=bool)
            pp.exc_rows = np.zeros(0, dtype=np.int32)
            ctx = self.matcher._constraint_context([], resv)
            self.matcher._fill_cotask_host_attributes(
                ctx, pool.name, pp.offers, scheduler.clusters)
            pp.ctx = ctx

        pp.enqueue_ok = np.ones(T, dtype=bool)
        launch_ok = np.ones(T, dtype=bool)
        flags = c["flags0"]
        if exclude is not None:
            kind, epoch, rows = exclude
            if kind == "rows" and epoch == c["epoch"] and len(rows):
                masked = pend & np.isin(rows_s, rows)
                if masked.any():
                    launch_ok = launch_ok & ~masked
                    flags = flags.copy()
                    flags[masked] &= np.uint8(~np.uint8(FLAG_LAUNCH_OK))
                    _audit.note_skips(
                        self.store.audit,
                        {"pipeline-speculative":
                             list(pp.uuid_base[rows_s[masked]])},
                        pool=pool.name)
        pp.launch_ok = launch_ok
        pp.tokens_u = self._tokens_u(pool, users, token_delta)
        # no gang members by eligibility, but a gang that admitted last
        # cycle must still shed its stale deferral reason
        self.matcher.last_admission_deferred[pool.name] = {}
        pp.flags = flags
        self._pack_caps(pp, pool)
        return pp

    def _gang_cohort_admission(self, pool: Pool, groups_ctx: Dict,
                               members_by_gang: Dict,
                               launch_ok: np.ndarray,
                               net_tokens, spec_masked=None) -> None:
        """Host-side gang-cohort admission for the fused pack paths
        (mirrors Matcher.considerable_jobs, docs/GANG.md): a gang that
        cannot clear this cycle's throttles WHOLE is withheld whole by
        clearing its members' launch_ok bits.  The device admits rows
        in rank order until tokens/caps run out, so a straddling cohort
        would admit partial, match, and be reset by the reduction —
        burning capacity every cycle when the budget can never cover
        the gang, with a capacity-shaped explanation for what is a
        rate-limit condition.  (Token/cap contention with earlier
        singles can still split a cohort transiently on device; the
        reduction drops it that cycle and the refilled budget admits it
        whole later.)

        ``members_by_gang``: group uuid -> [(task_row, job)] for the
        pack's pending gang members; ``net_tokens``: user -> launch
        tokens net of the pipeline's token_delta, or None when the
        limiter is off."""
        deferred_why: Dict[str, Dict] = {}
        skipped: List = []
        satisfied = set()
        if members_by_gang:
            from ..state.schema import gang_bounds, gang_is_elastic
            from .elastic import satisfied_gangs
            mc = self.config.matcher_for_pool(pool.name)
            backoff = self.matcher._backoff.setdefault(
                pool.name, _BackoffState(mc.max_jobs_considered))
            nc = min(backoff.num_considerable, mc.max_jobs_considered)
            mgr = self.matcher.elastic
            if mgr is not None:
                mgr.start_pool_cycle(pool.name)
            satisfied = satisfied_gangs(
                self.store, {guuid: groups_ctx.get(guuid)
                             for guuid in members_by_gang
                             if groups_ctx.get(guuid) is not None}) or set()
            for guuid, members in members_by_gang.items():
                g = groups_ctx.get(guuid)
                if not getattr(g, "gang", False):
                    continue
                if guuid in satisfied:
                    # GROW path (docs/GANG.md elasticity): the gang runs
                    # at >= min, so its waiting members admit like
                    # singles — capped at gang_max, then metered by the
                    # optimizer's grow budget
                    headroom = self.store.gang_growth_headroom(guuid)
                    grow_skipped: List[str] = []
                    max_skipped: List[str] = []
                    for row, j in members:
                        if not launch_ok[row]:
                            continue
                        if headroom < 1:
                            launch_ok[row] = False
                            max_skipped.append(j.uuid)
                            continue
                        if mgr is not None \
                                and not mgr.admit_grow(pool.name):
                            launch_ok[row] = False
                            grow_skipped.append(j.uuid)
                            continue
                        headroom -= 1
                    reasons = {}
                    if grow_skipped:
                        reasons["gang-grow-deferred"] = grow_skipped
                    if max_skipped:
                        reasons["gang-at-max"] = max_skipped
                    if reasons:
                        _audit.note_skips(self.store.audit, reasons,
                                          pool=pool.name)
                    continue
                # cohort size: gang_size for rigid gangs (bit-identical
                # to the pre-elastic admission), gang_min for elastic
                size = gang_bounds(g)[0] if gang_is_elastic(g) \
                    else int(getattr(g, "gang_size", 0) or 0)
                if not size:
                    continue
                if gang_is_elastic(g):
                    # surplus beyond the cohort is capped by the growth
                    # headroom: admit at most max(size, headroom)
                    # members so an unsatisfied elastic gang cannot
                    # overshoot gang_max through the min-threshold
                    # reduction's partial packing (the cohort itself
                    # always admits — it restores legality)
                    allowed = int(max(
                        size, self.store.gang_growth_headroom(guuid)))
                    over = [(row, j) for row, j in members[allowed:]
                            if launch_ok[row]]
                    if over:
                        for row, _j in over:
                            launch_ok[row] = False
                        _audit.note_skips(
                            self.store.audit,
                            {"gang-at-max": [j.uuid for _r, j in over]},
                            pool=pool.name)
                        members = members[:allowed]
                if len(members) < size:
                    reason = "members-missing"
                elif size > nc:
                    reason = "considerable-cap"
                elif sum(1 for row, _j in members
                         if launch_ok[row]) < size:
                    if spec_masked is not None and all(
                            launch_ok[row] or spec_masked[row]
                            for row, _j in members):
                        # every withheld member is the pipeline's
                        # speculative in-flight footprint: the gang is
                        # mid-launch in the overlapped cycle, not
                        # filter/quota-denied — withhold the rest whole
                        # with no deferral reason (reconcile re-surfaces
                        # the gang if the overlapped launch conflicts)
                        extra = []
                        for row, j in members:
                            if launch_ok[row]:
                                launch_ok[row] = False
                                extra.append(j.uuid)
                        if extra:
                            _audit.note_skips(
                                self.store.audit,
                                {"pipeline-speculative": extra},
                                pool=pool.name)
                        continue
                    reason = "member-denied"
                elif net_tokens is not None \
                        and net_tokens(members[0][1].user) < size:
                    reason = "rate-limited"
                else:
                    continue
                for row, job in members:
                    if launch_ok[row]:
                        launch_ok[row] = False
                        skipped.append((job.uuid, {"why": reason}))
                deferred_why[guuid] = {"size": size, "reason": reason}
        # set every cycle, like considerable_jobs on the split path, so
        # a gang that admitted this cycle sheds last cycle's reason
        self.matcher.last_admission_deferred[pool.name] = deferred_why
        if skipped:
            _audit.note_skips(self.store.audit,
                              {"gang-deferred": skipped}, pool=pool.name)

    def _pack_caps(self, pp: _PackedPool, pool: Pool) -> None:
        """Backoff cap + pool/quota-group caps (shared by both pack paths)."""
        cfg = self.config
        mc = cfg.matcher_for_pool(pool.name)
        backoff = self.matcher._backoff.setdefault(
            pool.name, _BackoffState(mc.max_jobs_considered))
        pp.num_considerable = min(backoff.num_considerable,
                                  mc.max_jobs_considered)
        q = cfg.pool_quota(pool.name)
        if q is not None:
            pp.pool_quota = _pool_quota_vec(q)
        gname = cfg.quota_groups.get(pool.name)
        gq = cfg.quota_group_quotas.get(gname) if gname else None
        if gq is not None:
            pp.group_quota = _pool_quota_vec(gq)

    def _pack_pool(self, scheduler, pool: Pool, exclude=None,
                   token_delta=None) -> Optional[_PackedPool]:
        store, cfg = self.store, self.config
        if cfg.columnar_index:
            return self._pack_pool_columnar(scheduler, pool,
                                            exclude=exclude,
                                            token_delta=token_delta)
        pending = store.pending_jobs(pool.name)
        pp = _PackedPool(pool)
        if not pending:
            return None
        running = store.running_instances(pool.name)
        uts, id2job = build_user_tasks(pending, running)
        shares = {ut.user: tuple(
            store.get_share(ut.user, pool.name).get(d, INF)
            for d in ("cpus", "mem", "gpus")) for ut in uts}
        quotas = {ut.user: _quota_vec(store.get_quota(ut.user, pool.name))
                  for ut in uts}
        arrays, task_ids = host_prep.pack_rank_inputs(
            uts, shares, quotas, pad=False)
        T = arrays["usage"].shape[0]
        pp.task_ids, pp.id2job, pp.arrays, pp.n_tasks = \
            task_ids, id2job, arrays, T

        # offers from every cluster serving this pool
        offers: List[Offer] = []
        # breaker-filtered: a tripped cluster contributes no offers, so
        # the kernel routes demand at healthy clusters
        for cluster in scheduler.launchable_clusters(pool.name):
            offers.extend(cluster.pending_offers(pool.name))
        pp.offers = offers
        pp.n_hosts = len(offers)

        jobs_in_rows = [pp.id2job[t] for t in task_ids]
        pend_rows = arrays["pending"]

        # per-row match resources (running rows never matched, zeroed)
        pp.job_res = np.stack(
            [[j.resources.cpus, j.resources.mem, j.resources.gpus,
              j.resources.disk] for j in jobs_in_rows]).astype(F32) \
            * pend_rows[:, None]

        # constraint mask for pending rows (running rows all-False)
        if offers:
            pend_idx = np.flatnonzero(pend_rows)
            pend_jobs = [jobs_in_rows[i] for i in pend_idx]
            ctx = self.matcher._constraint_context(
                pend_jobs, scheduler.reserved_hosts)
            self.matcher._fill_cotask_host_attributes(
                ctx, pool.name, offers, scheduler.clusters)
            pp.ctx = ctx
            sub = build_constraint_mask(pend_jobs, offers, ctx)
            cmask = np.zeros((T, len(offers)), dtype=bool)
            cmask[pend_idx] = sub
            pp.cmask = cmask
            pp.avail = np.array(
                [[o.available.cpus, o.available.mem, o.available.gpus,
                  o.available.disk] for o in offers], dtype=F32)
            pp.capacity = np.array(
                [[o.capacity.cpus, o.capacity.mem, o.capacity.gpus,
                  o.capacity.disk] for o in offers], dtype=F32)
        else:
            pp.cmask = np.zeros((T, 1), dtype=bool)
            pp.avail = np.zeros((1, 4), dtype=F32)
            pp.capacity = np.zeros((1, 4), dtype=F32)
            pp.n_hosts = 0

        # offensive-job filter -> enqueue_ok (scheduler.clj:2205-2257)
        enqueue_ok = np.ones(T, dtype=bool)
        limits = cfg.offensive_job_limits
        if limits is not None:
            max_mem_mb = limits.memory_gb * 1024.0
            for i, j in enumerate(jobs_in_rows):
                if pend_rows[i] and (j.resources.mem > max_mem_mb
                                     or j.resources.cpus > limits.cpus):
                    enqueue_ok[i] = False
                    pp.offensive.append(j)
        pp.enqueue_ok = enqueue_ok

        # plugin launch verdicts -> launch_ok (cached accept/defer)
        launch_ok = np.ones(T, dtype=bool)
        for i, j in enumerate(jobs_in_rows):
            if pend_rows[i] and not self.plugins.launch_allowed(j):
                launch_ok[i] = False
        # pipelined-driver speculation mask (entity-pack form: by uuid)
        spec_masked = None
        if exclude is not None:
            kind, _epoch, uuids = exclude
            if kind == "uuids" and uuids:
                spec_masked = np.zeros(T, dtype=bool)
                masked_uuids = []
                for i, j in enumerate(jobs_in_rows):
                    if pend_rows[i] and launch_ok[i] and j.uuid in uuids:
                        launch_ok[i] = False
                        spec_masked[i] = True
                        masked_uuids.append(j.uuid)
                if masked_uuids:
                    _audit.note_skips(
                        store.audit,
                        {"pipeline-speculative": masked_uuids},
                        pool=pool.name)
        pp.launch_ok = launch_ok

        # launch-rate token budgets, per user broadcast to tasks
        launch_rl = self.rate_limits.job_launch
        if launch_rl.enforce:
            from ..policy import pool_user_key
            user_tokens = {
                ut.user: launch_rl.get_token_count(
                    pool_user_key(pool.name, ut.user)) for ut in uts}
            if token_delta:
                # overlapped in-flight spends not yet on the limiter
                user_tokens = {
                    u: max(t - token_delta.get(u, 0.0), 0.0)
                    for u, t in user_tokens.items()}
            tok = np.array([user_tokens[pp.id2job[t].user]
                            for t in task_ids], dtype=F32)
        else:
            tok = np.full(T, INF, dtype=F32)
        pp.tokens = tok

        # gang-cohort admission (see the columnar pack / helper doc)
        gang_members: Dict[str, List] = {}
        if offers and pp.ctx is not None:
            for i, job in zip(pend_idx, pend_jobs):
                if job.group is not None and getattr(
                        pp.ctx.groups.get(job.group), "gang", False):
                    gang_members.setdefault(job.group, []).append(
                        (int(i), job))
        self._gang_cohort_admission(
            pool, pp.ctx.groups if pp.ctx is not None else {},
            gang_members, launch_ok,
            (lambda u: user_tokens.get(u, 0.0))
            if launch_rl.enforce else None,
            spec_masked=spec_masked)

        self._pack_caps(pp, pool)
        return pp

    # ------------------------------------------------------------------ step
    def stage(self, scheduler, exclude=None, avail_delta=None,
              token_delta=None) -> "_StagedCycle":
        """Phase 1 of a cycle: host-side staging.  Packs every active
        non-direct pool off the store and builds the per-DRU-mode dispatch
        groups (padded + stacked, ready for :meth:`dispatch_group`).

        The two optional arguments are the pipelined driver's optimistic-
        concurrency hooks (sched/pipeline.py, Omega-style):

        - ``exclude``: pool name -> ("rows"|"uuids", epoch, ids) — launch
          candidates a fetched-but-not-yet-applied overlapped cycle is
          about to consume; they are withheld from this cycle's
          launch_ok so back-to-back cycles don't fight over the head of
          the queue.
        - ``avail_delta``: (cluster, hostname) -> f32[4] — the resources
          those candidates will consume, subtracted from the staged offer
          availability so this cycle's speculative placements stay
          feasible even though the store doesn't show the launches yet.
        - ``token_delta``: pool name -> user -> launch-rate tokens those
          candidates will spend, subtracted from the staged per-user
          token budgets (the rate limiter's spend() lands only at apply,
          after this cycle staged — without the delta a user would get
          depth-x the configured per-cycle launch rate).

        All are None on the sync path, which stays bit-for-bit today's
        behavior."""
        from ..utils.faults import injector as _faults
        _faults.fire("fused.dispatch")

        # the pool table is the cycle's first read of the store: a cycle
        # that meets a sweep waits for the store lock HERE, so the read
        # has a span (and a detail_ms key, "pools") of its own — outside
        # fused.pack, which times what it always timed
        with tracing.span("fused.pools"):
            pools = self._cycle_pools()
        packed: List[_PackedPool] = []
        excl = exclude or {}
        tokd = token_delta or {}
        # "cycle.rank" is the canonical rank-phase span on the cycle trace
        # (flight.PHASE_BY_SPAN): host-side rank staging — the columnar
        # pack that feeds the device the rank+match problem
        with tracing.span("cycle.rank"), tracing.span("fused.pack"):
            for pool in pools:
                pp = self._pack_pool(scheduler, pool,
                                     exclude=excl.get(pool.name),
                                     token_delta=tokd.get(pool.name))
                if pp is not None:
                    packed.append(pp)
            # compact packs must share ONE index compaction epoch: the
            # device base mirror holds one buffer generation, and a pool
            # packed before a mid-cycle compaction carries remapped row
            # ids.  Re-pack stragglers (rare: the dead-row threshold means
            # compaction fires at most once between two packs).
            epochs = {pp.base_compactions for pp in packed if pp.compact}
            if len(epochs) > 1:
                latest = max(epochs)
                refreshed = []
                for pp in packed:
                    if pp.compact and pp.base_compactions != latest:
                        # a stale pack must NEVER be dispatched: its rows_s
                        # are pre-compaction row ids.  A re-pack returning
                        # None (pool's pending drained by the same churn)
                        # just drops the pool from this cycle.
                        pp = self._pack_pool(
                            scheduler, pp.pool,
                            exclude=excl.get(pp.pool.name),
                            token_delta=tokd.get(pp.pool.name))
                        if pp is None or (pp.compact and
                                          pp.base_compactions != latest):
                            continue
                    refreshed.append(pp)
                packed = refreshed
        if avail_delta:
            # the pipelined driver's own host work (detail_ms.pipeline)
            with tracing.span("pipeline.host", step="avail-delta"):
                for pp in packed:
                    for h, o in enumerate(pp.offers):
                        d = avail_delta.get((o.cluster, o.hostname))
                        if d is not None:
                            pp.avail[h] = np.maximum(pp.avail[h] - d, 0.0)
        staged = _StagedCycle(pools)
        if not packed:
            return staged

        # group pools by DRU mode (kernel static)
        by_mode: Dict[bool, List[_PackedPool]] = {}
        for pp in packed:
            by_mode.setdefault(pp.pool.dru_mode is DruMode.GPU, []).append(pp)
        for gpu_mode, group in by_mode.items():
            staged.groups.append(self._stage_group(gpu_mode, group))
        return staged

    def _stage_group(self, gpu_mode: bool,
                     group: List[_PackedPool]) -> "_StagedGroup":
        """Fold quota-group caps and build one DRU-mode group's padded,
        stacked kernel inputs (the wire form :meth:`dispatch_group`
        uploads)."""
        # Quota-group ids are per dispatch; member pools NOT in this
        # dispatch (no pending jobs, different dru-mode, or direct) still
        # consume the group's cap, so their running usage is folded into
        # the cap host-side (the on-device all_gather covers in-dispatch
        # members; reference semantics: scheduler.clj:2125-2157 counts
        # every member pool's running usage).
        gids: Dict[str, int] = {}
        in_dispatch = {pp.pool.name for pp in group}
        missing_by_group: Dict[str, np.ndarray] = {}

        def missing_usage(gname: str) -> np.ndarray:
            m = missing_by_group.get(gname)
            if m is None:
                m = np.zeros(4, dtype=F32)
                idx = (self.store.ensure_index()
                       if self.config.columnar_index else None)
                for member, g in self.config.quota_groups.items():
                    if g != gname or member in in_dispatch:
                        continue
                    if idx is not None:
                        m += idx.pool_usage_base(member)
                        continue
                    for job, _i in self.store.running_instances(member):
                        m += [job.resources.cpus, job.resources.mem,
                              job.resources.gpus, 1.0]
                missing_by_group[gname] = m
            return m

        for pp in group:
            gname = self.config.quota_groups.get(pp.pool.name)
            if not gname:
                continue
            pp.group_id = gids.setdefault(gname, len(gids))
            pp.group_quota = (pp.group_quota
                              - missing_usage(gname)).astype(F32)
        T = bucket(max(pp.n_tasks for pp in group))
        H = bucket(max(max(pp.n_hosts, 1) for pp in group))
        P = self._stacked(len(group))

        def stack(fn, fill=0, dtype=None):
            rows = [fn(pp) for pp in group]
            rows += [np.full_like(rows[0], fill)] * (P - len(group))
            out = np.stack(rows)
            return out if dtype is None else out.astype(dtype)

        def padT(a, fill=0):
            return pad_to(a, T, fill=fill)

        from ..parallel.sharded import (
            CompactPoolCycleInputs,
            PoolCycleInputs,
        )
        arr = lambda k, fill: stack(lambda pp: padT(pp.arrays[k], fill))
        structured = group[0].columnar
        # detail_ms.stage: arrays -> padded, stacked wire form (the upload
        # of what is not device-resident included)
        with tracing.span("fused.stage", pools=len(group)) as stage_sp:
            avail_p = np.zeros((P, H, 4), dtype=F32)
            cap_p = np.zeros((P, H, 4), dtype=F32)
            for i, pp in enumerate(group):
                avail_p[i, :pp.avail.shape[0]] = pp.avail
                cap_p[i, :pp.capacity.shape[0]] = pp.capacity
            # ``host``: every [P, ...] input as a host array; ONE
            # placement at the end (self._put, span stage.put) hands each
            # mesh device its own pools' slice
            host = dict(
                num_considerable=np.array(
                    [pp.num_considerable for pp in group]
                    + [0] * (P - len(group)), dtype=np.int32),
                pool_quota=np.stack(
                    [pp.pool_quota for pp in group]
                    + [np.full(4, INF, dtype=F32)] * (P - len(group))),
                group_quota=np.stack(
                    [pp.group_quota for pp in group]
                    + [np.full(4, INF, dtype=F32)] * (P - len(group))),
                group_id=np.array(
                    [pp.group_id for pp in group]
                    + [-1] * (P - len(group)), dtype=np.int32),
                avail=avail_p, capacity=cap_p)
            resident = {}
            if structured:
                # COMPACT wire form: the per-task upload is the sorted row
                # permutation + one flags byte (~5 B/task); resource
                # columns live in the device-resident base mirror and
                # everything else is derived on device (expand_compact).
                # every pp in the group shares one compaction epoch (step
                # re-packs or drops stale pools right after the pack loop),
                # so the mirror's row indices are valid for all of them —
                # assert rather than silently uploading mixed-epoch content
                # under one mirror key
                epoch = max(pp.base_compactions for pp in group)
                assert all(pp.base_compactions == epoch for pp in group), \
                    [pp.base_compactions for pp in group]
                base_pp = max(group, key=lambda pp: pp.res_base.shape[0])
                mir_res, mir_disk = self._sync_base_mirror(
                    base_pp.res_base, base_pp.disk_base, epoch)
                E = bucket(max(max(len(pp.exc_rows), pp.exc_mask.shape[0])
                               for pp in group), minimum=8)
                U = bucket(max(pp.shares_u.shape[0] for pp in group),
                           minimum=8)
                rows_p = np.zeros((P, T), dtype=np.int32)
                flags_p = np.zeros((P, T), dtype=np.uint8)
                exc_rows_p = np.full((P, E), -1, dtype=np.int32)
                exc_mask_p = np.zeros((P, E, H), dtype=bool)
                host_gpu_p = np.zeros((P, H), dtype=bool)
                # padding hosts stay blocked so zero-resource jobs can
                # never land on them (the dense path's zero rows did this)
                host_blocked_p = np.ones((P, H), dtype=bool)
                shares_u_p = np.full((P, U, 3), INF, dtype=F32)
                quota_u_p = np.full((P, U, 4), INF, dtype=F32)
                tokens_u_p = np.full((P, U), INF, dtype=F32)
                for i, pp in enumerate(group):
                    rows_p[i, :pp.n_tasks] = pp.rows_s
                    flags_p[i, :pp.n_tasks] = pp.flags
                    exc_rows_p[i, :len(pp.exc_rows)] = pp.exc_rows
                    e, h = pp.exc_mask.shape
                    exc_mask_p[i, :e, :h] = pp.exc_mask
                    host_gpu_p[i, :pp.host_gpu.shape[0]] = pp.host_gpu
                    host_blocked_p[i, :pp.host_blocked.shape[0]] = \
                        pp.host_blocked
                    shares_u_p[i, :pp.shares_u.shape[0]] = pp.shares_u
                    quota_u_p[i, :pp.quota_u.shape[0]] = pp.quota_u
                    tokens_u_p[i, :pp.tokens_u.shape[0]] = pp.tokens_u
                host.update(
                    tokens_u=tokens_u_p, shares_u=shares_u_p,
                    quota_u=quota_u_p, host_gpu=host_gpu_p,
                    host_blocked=host_blocked_p, exc_rows=exc_rows_p,
                    exc_mask=exc_mask_p)
                resident = dict(res_base=mir_res, disk_base=mir_disk)
                if self.config.resident_pack:
                    # DEVICE-RESIDENT wire arrays: steady state ships only
                    # the scatter delta, not the [P, T] world (ISSUE 7)
                    key = (tuple(pp.pool.name for pp in group), P, T)
                    resident["rows"], resident["flags"] = \
                        self._sync_resident(gpu_mode, key, rows_p, flags_p,
                                            epoch)
                else:  # rebuild mode: dispatch_group accounts the upload
                    host.update(rows=rows_p, flags=flags_p)
                in_type = CompactPoolCycleInputs
            else:
                cmask_p = np.zeros((P, T, H), dtype=bool)
                for i, pp in enumerate(group):
                    cmask_p[i, :pp.n_tasks, :pp.cmask.shape[1]] = pp.cmask
                host.update(
                    usage=arr("usage", 0), quota=arr("quota", INF),
                    shares=arr("shares", INF),
                    first_idx=arr("first_idx", 0),
                    user_rank=arr("user_rank", 2**31 - 1),
                    pending=arr("pending", False),
                    valid=arr("valid", False),
                    enqueue_ok=stack(lambda pp: padT(pp.enqueue_ok, False)),
                    launch_ok=stack(lambda pp: padT(pp.launch_ok, False)),
                    tokens=stack(lambda pp: padT(pp.tokens, 0.0)),
                    job_res=stack(lambda pp: padT(pp.job_res, 0.0)),
                    cmask=cmask_p)
                in_type = PoolCycleInputs
            with tracing.span("stage.put", arrays=len(host)):
                inp = in_type(**self._put(host), **resident)

            # static match-problem cap: the configured max_jobs_considered
            # (>= every pool's dynamic num_considerable), bucketed so the
            # compiled cycle is reused across config tweaks
            cap = bucket(max(
                self.config.matcher_for_pool(pp.pool.name).max_jobs_considered
                for pp in group))
        stage_ms = (None if stage_sp.duration_s is None
                    else round(stage_sp.duration_s * 1000.0, 1))
        return _StagedGroup(gpu_mode=gpu_mode, group=group, inp=inp,
                            structured=structured, cap=cap, T=T, H=H,
                            stage_ms=stage_ms,
                            resident=structured and bool(
                                self.config.resident_pack))

    def dispatch_group(self, sg: "_StagedGroup") -> "_GroupDispatch":
        """Phase 2: upload one staged group's inputs and dispatch the
        jitted cycle; starts the async device->host copies of the compact
        outputs so a later :meth:`fetch_group` overlaps the transfer with
        whatever the host does in between (the pipelined driver's whole
        point)."""
        # staged wire bytes this dispatch: the device-resident base
        # mirror fields are never re-uploaded per cycle (the mirror sync
        # accounts its own transfers), and in resident-pack mode the
        # rows/flags buffers are device-resident too — only their delta
        # scatter moved bytes, accounted by _sync_resident
        skip = {"res_base", "disk_base"}
        if sg.resident:
            skip |= {"rows", "flags"}
        telemetry.count_transfer("h2d", sum(
            getattr(a, "nbytes", 0)
            for name, a in zip(type(sg.inp)._fields, sg.inp)
            if name not in skip))
        with tracing.span("fused.dispatch", pools=len(sg.group),
                          tasks=sg.T, hosts=sg.H, gpu=sg.gpu_mode,
                          stage_ms=sg.stage_ms):
            res = self._cycle_fn(sg.gpu_mode, min(sg.cap, sg.T),
                                 sg.structured,
                                 compact=sg.structured)(sg.inp)
            # fetch ONLY the compact outputs: [C]-sized candidate
            # triples + the queue count.  The full [T] arrays
            # (order/queue_ok/assign) and the rank-ordered queue_rows
            # stay device-resident; the published RankedQueue fetches
            # queue_rows lazily when a consumer actually touches the
            # queue: the old four-[T]-array fetch was 2.1 MB per cycle
            # at T=131k; this fetches ~50 KB.  (Inside the span: each
            # kick lets go of the GIL, and getting it back can take
            # milliseconds while a sweep runs.)
            outs = (res.cand_row, res.cand_assign, res.cand_qpos,
                    res.n_queue)
            for out_arr in outs:
                copy_async = getattr(out_arr, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
        _flight.note_path("fused")
        _flight.note_mesh(self.mesh().size)
        return _GroupDispatch(sg, res, outs)

    def fetch_group(self, gd: "_GroupDispatch"):
        """Phase 3: one batched device->host fetch of a dispatch's compact
        outputs (each separate np.asarray would pay its own device
        sync).  Idempotent."""
        if gd.fetched is None:
            import jax
            with tracing.span("fused.fetch"), \
                    telemetry.sync_wait("fused.fetch"):
                gd.fetched = jax.device_get(gd.outs)
            telemetry.count_transfer("d2h", sum(
                getattr(a, "nbytes", 0) for a in gd.fetched))
        return gd.fetched

    def apply_group(self, scheduler, gd: "_GroupDispatch", queues, results,
                    reconciler=None) -> None:
        """Phase 4: map one fetched group's outputs back to entities and
        run the transactional launch path per pool.  ``reconciler`` is the
        pipelined driver's pre-launch re-validation hook (see
        :meth:`_apply_pool`)."""
        cand_row, cand_assign, cand_qpos, n_queue = gd.fetched
        with tracing.span("cycle.launch", pools=len(gd.sg.group)):
            for i, pp in enumerate(gd.sg.group):
                self._apply_pool(scheduler, pp, cand_row[i],
                                 cand_assign[i], cand_qpos[i],
                                 int(n_queue[i]), gd.res.queue_rows, i,
                                 queues, results, reconciler=reconciler)

    def step(self, scheduler) -> Tuple[Dict[str, List[Job]],
                                       Dict[str, MatchCycleResult]]:
        """One SYNCHRONOUS fused cycle over all active non-direct pools:
        stage -> dispatch -> fetch -> apply, group by group, exactly the
        pre-pipeline behavior (pipeline_depth=0 routes here).  Returns
        (pending queues, match results); direct pools are handled by the
        scheduler separately."""
        staged_tx = getattr(self.store, "_tx_id", -1)
        staged_at = time.perf_counter()
        staged = self.stage(scheduler)
        queues: Dict[str, List[Job]] = {p.name: [] for p in staged.pools}
        results: Dict[str, MatchCycleResult] = {}
        for sg in staged.groups:
            with tracing.span("cycle.match", pools=len(sg.group),
                              tasks=sg.T, hosts=sg.H, gpu=sg.gpu_mode):
                gd = self.dispatch_group(sg)
                self.fetch_group(gd)
            _flight.note_staged(
                staged_tx, (time.perf_counter() - staged_at) * 1000.0)
            self.apply_group(scheduler, gd, queues, results)
        return queues, results

    # ----------------------------------------------------------------- apply
    def _apply_pool(self, scheduler, pp: _PackedPool, cand_row, cand_assign,
                    cand_qpos, n_queue: int, queue_rows_dev, pool_slot: int,
                    queues, results, reconciler=None) -> None:
        """Map one pool's COMPACT kernel outputs back to entities: queue
        refresh, within-batch group validation, backoff bookkeeping,
        transactional launch.

        ``cand_row``/``cand_assign``/``cand_qpos`` are the [C] admitted-slot
        arrays (-1 = empty slot); the rank-ordered queue rows stay on device
        in ``queue_rows_dev[pool_slot]`` and are fetched only when a queue
        consumer materializes them.

        ``reconciler`` is the pipelined driver's Omega-style pre-launch
        re-validation (sched/pipeline.py): called with (pp, cand_jobs,
        cand_host), returns (state_drop, resource_drop) bool masks over
        the candidates.  State conflicts (no longer WAITING — launched by
        an overlapped cycle, or killed since the pack) are removed
        outright and pruned from the published queue; resource conflicts
        (the host's availability was consumed by an overlapped launch the
        staged snapshot didn't see) fall back to unmatched and retry next
        cycle.  Never passed on the sync path."""
        pool_name = pp.pool.name
        # slice this pool's row off the [P, T] output eagerly (an async
        # device op): the published queue's closure must NOT keep the whole
        # P-wide buffer — or the rest of pp — alive for its lifetime
        with tracing.span("apply.audit", step="queue-slice"):
            dev_rows = self._pool_row(queue_rows_dev, pool_slot)
        rows_s = pp.rows_s
        fetched_rows: List[Optional[np.ndarray]] = [None]

        def fetch_local_rows() -> np.ndarray:
            # one device->host transfer of exactly n_queue i32 rows, paid
            # only when some consumer (rebalancer, /queue page, direct-pool
            # logic) actually touches the published queue
            if fetched_rows[0] is None:
                import jax
                with telemetry.sync_wait("queue.rows"):
                    fetched_rows[0] = np.asarray(jax.device_get(
                        dev_rows[:n_queue]))
                telemetry.count_transfer("d2h", fetched_rows[0].nbytes)
            return fetched_rows[0]

        def local_rows_with_drops(drop_qpos) -> np.ndarray:
            rows = fetch_local_rows()
            if drop_qpos is not None and len(drop_qpos):
                # post-match queue prune (native/pack.cpp when built)
                from ..native.pack import prune_rows
                rows = prune_rows(rows, np.unique(drop_qpos))
            return rows

        def publish_queue(drop_qpos=None):
            if pp.columnar:
                # lazy queue straight over the index BASE snapshots; the
                # row selection itself is DEFERRED (device fetch + drop
                # filter run on first touch), and full-column gathers
                # happen only if someone reads .uuids/.resources/.users
                from .ranker import RankedQueue
                n = n_queue - (len(drop_qpos) if drop_qpos is not None
                               else 0)
                queues[pool_name] = RankedQueue(
                    self.store, pp.uuid_base, pp.res_base, pp.user_base,
                    rows_fn=lambda drop=drop_qpos:
                        rows_s[local_rows_with_drops(drop)],
                    n=n)
            else:
                queues[pool_name] = [
                    pp.id2job[pp.task_ids[r]]
                    for r in local_rows_with_drops(drop_qpos)]

        with tracing.span("apply.lookup", step="fetch"):
            result = MatchCycleResult()
            slots = np.flatnonzero(cand_row >= 0)
            result.considered = len(slots)
            if pp.columnar:
                uuid_prefix = pp.uuid_base[pp.rows_s[cand_row[slots]]]
                fetched = self.store.jobs_bulk([str(u) for u in uuid_prefix])
                cand_jobs, cand_keep = [], []
                for s, job in zip(slots, fetched):
                    if job is not None:
                        cand_jobs.append(job)
                        cand_keep.append(s)
                slots = np.array(cand_keep, dtype=np.int64)
            else:
                cand_jobs = [pp.id2job[pp.task_ids[r]]
                             for r in cand_row[slots]]
        with tracing.span("apply.audit"):
            scheduler._stifle_offensive(pp.offensive)
            # per-job rank attribution for the fetched candidate slots
            # (bounded by the considerable cap, never [T]-sized): the
            # device-computed queue position, straight off the compact
            # outputs already on host (utils/audit.py)
            if len(slots):
                self.store.audit.ranked(
                    [j.uuid for j in cand_jobs],
                    [int(q) for q in cand_qpos[slots]], pool_name,
                    users=[j.user for j in cand_jobs])
        if len(slots) == 0 or not pp.offers:
            # mirror Matcher.match_pool: an empty cycle returns the
            # considerable set unmatched and leaves backoff untouched
            result.unmatched = cand_jobs
            publish_queue()
            results[pool_name] = result
            return

        with tracing.span("apply.lookup", step="validate"):
            cand_host = cand_assign[slots].astype(np.int64)
            # clip padding-host assignments (can't happen: padding hosts have
            # zero capacity and all-False masks, but stay defensive)
            clipped = cand_host >= len(pp.offers)
            if clipped.any():
                cand_host[clipped] = -1
            conflict_qpos = None
            res_conflict = None
            dropped_head_matched = False
            if reconciler is not None:
                with tracing.span("fused.reconcile", pool=pool_name,
                                  candidates=len(slots)):
                    state_drop, res_drop = reconciler(pp, cand_jobs, cand_host)
                # a dropped HEAD that held an assignment DID match (it
                # launched one cycle earlier, or the overlap consumed its
                # host): backoff must not shrink for a transient conflict
                dropped_head_matched = bool(
                    (state_drop[0] or res_drop[0]) and cand_host[0] >= 0) \
                    if len(slots) else False
                if res_drop.any():
                    cand_host[res_drop] = -1
                if state_drop.any():
                    qp = cand_qpos[slots[state_drop]]
                    conflict_qpos = qp[qp >= 0]
                    keep = ~state_drop
                    slots = slots[keep]
                    cand_jobs = [j for j, k in zip(cand_jobs, keep) if k]
                    cand_host = cand_host[keep]
                    res_drop = res_drop[keep]
                res_conflict = res_drop if res_drop.any() else None
                if len(slots) == 0:
                    # every candidate conflicted away: like the empty cycle,
                    # leave backoff untouched (the head DID match — it just
                    # launched one cycle earlier than this stale snapshot saw)
                    publish_queue(conflict_qpos)
                    result.queue_pruned = conflict_qpos is not None \
                        and len(conflict_qpos) > 0
                    results[pool_name] = result
                    return
            cand_host = validate_group_placement(
                cand_jobs, cand_host, pp.offers, pp.ctx)
            # gang all-or-nothing over the fetched candidates (ops/gang.py,
            # docs/GANG.md): partial gangs reset to unmatched with their
            # capacity refilled to group-less candidates in the SAME cycle.
            # Under the pipelined driver a reconcile-dropped member already
            # left its gang incomplete, so a conflicted gang drops atomically
            # here.  Structural no-op when no candidate is a gang member.
            groups_ctx = pp.ctx.groups if pp.ctx is not None else {}
            if any(j.group is not None
                   and getattr(groups_ctx.get(j.group), "gang", False)
                   for j in cand_jobs):
                from ..ops.gang import apply_gang_cycle
                from .elastic import satisfied_gangs
                H = len(pp.offers)
                cand_res = np.array(
                    [[j.resources.cpus, j.resources.mem, j.resources.gpus,
                      j.resources.disk] for j in cand_jobs], dtype=F32)
                satisfied = satisfied_gangs(self.store, groups_ctx)
                cand_host, gstats = apply_gang_cycle(
                    cand_jobs, cand_host, pp.offers, groups_ctx,
                    job_res=cand_res,
                    cmask_fn=lambda: build_constraint_mask(
                        cand_jobs, pp.offers, pp.ctx),
                    # reconcile-adjusted availability when an overlapped
                    # cycle overdrafted the staged snapshot: the rescue and
                    # refill passes must not re-place onto a host the
                    # reconciler just protected
                    avail=(pp.avail_headroom if pp.avail_headroom is not None
                           else pp.avail[:H]),
                    capacity=pp.capacity[:H],
                    device=False,
                    refill_ok=(~res_conflict if res_conflict is not None
                               else None),
                    audit_trail=self.store.audit, audit_pool=pool_name,
                    satisfied=satisfied)
                if gstats is not None:
                    result.gang_partial = gstats.partial
        with tracing.span("apply.audit"):
            if res_conflict is not None:
                # resource-conflicted candidates are a pipeline transient,
                # not a placement failure: keep them out of the unscheduled
                # explainer's persisted per-host summaries
                rp_keep = ~res_conflict
                self.matcher.record_placement_failures(
                    [j for j, k in zip(cand_jobs, rp_keep) if k],
                    cand_host[rp_keep], pp.offers, pp.ctx)
            else:
                self.matcher.record_placement_failures(
                    cand_jobs, cand_host, pp.offers, pp.ctx)

            result.head_matched = (bool(cand_host[0] >= 0)
                                   or dropped_head_matched)
            mc = self.config.matcher_for_pool(pool_name)
            self.matcher._backoff[pool_name].update(mc, result.head_matched)

            for j, job in enumerate(cand_jobs):
                h = int(cand_host[j])
                if h < 0:
                    result.unmatched.append(job)
                else:
                    result.matched.append((job, pp.offers[h]))
        with tracing.span("fused.launch", pool=pool_name,
                          matched=len(result.matched)):
            self.matcher._launch(pool_name, result, scheduler.clusters)
        with tracing.span("apply.audit"):
            # drop this cycle's launches — and any reconcile-conflicted
            # candidates — from the queue by exact position (launched
            # candidates are always queue members — match_valid implies
            # queue_ok, so cand_qpos is valid for every launched slot)
            drops = ([conflict_qpos] if conflict_qpos is not None
                     and len(conflict_qpos) else [])
            if result.launched_job_uuids:
                cand_uuids = np.array([j.uuid for j in cand_jobs])
                launched_c = np.isin(cand_uuids,
                                     np.array(result.launched_job_uuids))
                drops.append(cand_qpos[slots[launched_c]])
            if drops:
                publish_queue(np.concatenate(drops))
                result.queue_pruned = True
            else:
                publish_queue()
            _audit.note_skips(self.store.audit, {
                "unmatched": [j.uuid for j in result.unmatched],
                "launch-failed": [(u, {"why": why})
                                  for u, why in result.launch_failures],
            }, pool=pool_name)
            results[pool_name] = result
