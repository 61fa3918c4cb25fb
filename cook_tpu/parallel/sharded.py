"""Pool-sharded fused scheduling cycle: rank + considerable + match on a
device mesh.

One jitted step runs EVERY pool's rank (DRU segmented prefix sums + sort),
considerable-job admission (pool/group quota, per-user quota, launch-rate
tokens, plugin verdicts, head-of-queue backoff cap — see
ops/considerable.py) and match (greedy bin-pack scan) with pools sharded
over the mesh's "pool" axis via ``shard_map``; cross-pool facts are
reconciled with XLA collectives:

 - per-pool RUNNING usage and quota-group ids are ``all_gather``'d so
   quota-group caps spanning pools (reference: scheduler.clj:2125-2157
   quota-group aggregation) are ENFORCED inside the cycle against a
   globally consistent view — each pool caps its ranked prefix by the
   group's running total, matching the host path's
   Ranker._apply_pool_quota;
 - per-pool matched-resource totals are ``all_gather``'d for the global
   cycle telemetry the reference logs per match cycle
   (scheduler.clj:1210-1280), along with a ``psum`` placement count.

The match job axis is aligned with the rank task axis (running-task rows
are never admitted), so the ranked order permutes match inputs entirely on
device — no host round-trip between rank and match.

This module is the scale axis of the framework (SURVEY.md section 5
"long-context" slot): pools across devices, and within a pool the
job/offer tensors are bucketed so XLA tiles them onto the VPU/MXU.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import dru as dru_ops
from ..ops import match as match_ops
from ..ops.considerable import considerable_body
from ..ops.scan import segmented_cumsum_by_first_idx
from .mesh import POOL_AXIS

INF = jnp.inf


class PoolCycleInputs(NamedTuple):
    """Per-pool padded arrays, stacked on a leading pool axis [P, ...].

    Task/job axes are shared: row t is one task; pending rows double as
    match candidates (job_res/cmask); running rows have pending=False.
    Admission-side arrays come from the host control plane (see
    sched/fused.py): plugin verdicts, rate-limit token budgets, the
    offensive-job filter, backoff caps, and pool/quota-group caps.
    """

    # rank side [P, T, ...]
    usage: jax.Array       # f32[P, T, 4]
    quota: jax.Array       # f32[P, T, 4]
    shares: jax.Array      # f32[P, T, 3]
    first_idx: jax.Array   # i32[P, T]
    user_rank: jax.Array   # i32[P, T]
    pending: jax.Array     # bool[P, T]
    valid: jax.Array       # bool[P, T]
    # admission side
    enqueue_ok: jax.Array        # bool[P, T] False = host-stifled job
    launch_ok: jax.Array         # bool[P, T] launch-plugin verdicts
    tokens: jax.Array            # f32[P, T] user launch-rate budget (inf=off)
    num_considerable: jax.Array  # i32[P] backoff cap on admitted jobs
    pool_quota: jax.Array        # f32[P, 4] pool cap (inf = uncapped)
    group_quota: jax.Array       # f32[P, 4] quota-group cap (inf = uncapped)
    group_id: jax.Array          # i32[P] quota-group id, -1 = none
    # match side
    job_res: jax.Array     # f32[P, T, R]
    cmask: jax.Array       # bool[P, T, H]
    avail: jax.Array       # f32[P, H, R]
    capacity: jax.Array    # f32[P, H, R]

    @classmethod
    def build(cls, *, usage, quota, shares, first_idx, user_rank, pending,
              valid, job_res, cmask, avail, capacity, enqueue_ok=None,
              launch_ok=None, tokens=None, num_considerable=None,
              pool_quota=None, group_quota=None, group_id=None
              ) -> "PoolCycleInputs":
        """Fill permissive defaults for the admission-side arrays (all jobs
        admitted, no caps) so kernel-level callers and tests can exercise
        rank+match alone."""
        P, T = np.shape(pending)[:2]
        ones = jnp.ones((P, T), dtype=bool)
        return cls(
            usage=usage, quota=quota, shares=shares, first_idx=first_idx,
            user_rank=user_rank, pending=pending, valid=valid,
            enqueue_ok=ones if enqueue_ok is None else enqueue_ok,
            launch_ok=ones if launch_ok is None else launch_ok,
            tokens=(jnp.full((P, T), INF, dtype=jnp.float32)
                    if tokens is None else tokens),
            num_considerable=(jnp.full((P,), T, dtype=jnp.int32)
                              if num_considerable is None
                              else num_considerable),
            pool_quota=(jnp.full((P, 4), INF, dtype=jnp.float32)
                        if pool_quota is None else pool_quota),
            group_quota=(jnp.full((P, 4), INF, dtype=jnp.float32)
                         if group_quota is None else group_quota),
            group_id=(jnp.full((P,), -1, dtype=jnp.int32)
                      if group_id is None else group_id),
            job_res=job_res, cmask=cmask, avail=avail, capacity=capacity)


class StructuredPoolCycleInputs(NamedTuple):
    """PoolCycleInputs with the dense bool[P, T, H] constraint mask replaced
    by its STRUCTURE — the insight that at the 1M x 50k design point almost
    every row's mask is derivable from per-host vectors (gpu isolation,
    max-tasks, reservations) plus a small exception set of complex jobs.
    The dense mask costs O(T*H) host build + transfer per cycle (500 MB at
    100k x 5k); the structured form transfers O(T + E*H + H):

      host_gpu     bool[P, H]    host has gpu capacity
      host_blocked bool[P, H]    max-tasks-per-host exceeded, or reserved
                                 (owners punch through via exceptions)
      exc_id       i32[P, T]     row -> exception index, -1 = derive base
      exc_mask     bool[P, E, H] full mask rows for exception jobs

    The per-row base is composed ON DEVICE after compaction, so only the
    admitted C rows ever materialize a mask."""

    usage: jax.Array
    quota: jax.Array
    shares: jax.Array
    first_idx: jax.Array
    user_rank: jax.Array
    pending: jax.Array
    valid: jax.Array
    enqueue_ok: jax.Array
    launch_ok: jax.Array
    tokens: jax.Array
    num_considerable: jax.Array
    pool_quota: jax.Array
    group_quota: jax.Array
    group_id: jax.Array
    job_res: jax.Array
    host_gpu: jax.Array
    host_blocked: jax.Array
    exc_id: jax.Array
    exc_mask: jax.Array
    avail: jax.Array
    capacity: jax.Array


# flag bits of CompactPoolCycleInputs.flags: canonically defined beside
# the delta scatter-apply kernel (ops/delta.py) so the state and sched
# layers can reason about wire flags without importing the mesh layer;
# re-exported here under their historical names
from ..ops.delta import (  # noqa: E402,F401
    FLAG_ENQUEUE_OK,
    FLAG_LAUNCH_OK,
    FLAG_PENDING,
    FLAG_USER_FIRST,
    FLAG_VALID,
)


class CompactPoolCycleInputs(NamedTuple):
    """The minimum-transfer form of StructuredPoolCycleInputs: what the
    host must genuinely SEND each cycle, with everything derivable moved
    onto the device — ~5 B/task on the wire vs the naive ~76 (10.8 MB ->
    ~1 MB per cycle at the 100k x 5k design point):

      - the immutable per-job resource columns live in a DEVICE-RESIDENT
        base mirror (res_base/disk_base, replicated across the mesh; the
        driver appends new rows incrementally and fully resyncs only on
        an index compaction), so the per-cycle per-task upload is just
        the sorted row permutation ``rows`` + one ``flags`` byte,
      - usage (cpus, mem, gpus, 1) and match demand (cpus, mem, gpus,
        disk)*pending are device-side gathers/views of the base,
      - per-USER share/quota/token tables [U, ...] gathered on device via
        user_rank, which is itself re-derived from the FLAG_USER_FIRST
        segment boundaries (as is first_idx),
      - exception rows arrive as a position list ``exc_rows`` (-1 padded)
        and scatter into the [T] exc_id map on device.

    Expanded to StructuredPoolCycleInputs by ``expand_compact`` inside the
    sharded cycle body (so expansion happens post-scatter, per shard)."""

    rows: jax.Array        # i32[P, T] absolute base row per sorted
    #                        position (0 for padding rows; flags=0 there)
    flags: jax.Array       # u8[P, T] FLAG_* bits
    res_base: jax.Array    # f32[N, 4] (cpus, mem, gpus, 1) — REPLICATED
    disk_base: jax.Array   # f32[N] — REPLICATED
    tokens_u: jax.Array    # f32[P, U] per-user launch-rate budget
    shares_u: jax.Array    # f32[P, U, 3]
    quota_u: jax.Array     # f32[P, U, 4]
    num_considerable: jax.Array  # i32[P]
    pool_quota: jax.Array  # f32[P, 4]
    group_quota: jax.Array  # f32[P, 4]
    group_id: jax.Array    # i32[P]
    host_gpu: jax.Array    # bool[P, H]
    host_blocked: jax.Array  # bool[P, H]
    exc_rows: jax.Array    # i32[P, E] task positions of exception jobs, -1 pad
    exc_mask: jax.Array    # bool[P, E, H]
    avail: jax.Array       # f32[P, H, 4]
    capacity: jax.Array    # f32[P, H, 4]


def expand_compact(inp: CompactPoolCycleInputs) -> StructuredPoolCycleInputs:
    """Device-side expansion of the compact wire form (leading pool axis
    preserved; runs inside the shard so every op stays local)."""
    P, T = inp.rows.shape
    usage = jax.vmap(lambda r: inp.res_base[r])(inp.rows)    # [P, T, 4]
    disk = jax.vmap(lambda r: inp.disk_base[r])(inp.rows)    # [P, T]
    flags = inp.flags
    pending = (flags & FLAG_PENDING) != 0
    valid = (flags & FLAG_VALID) != 0
    enqueue_ok = (flags & FLAG_ENQUEUE_OK) != 0
    launch_ok = (flags & FLAG_LAUNCH_OK) != 0
    is_first = (flags & FLAG_USER_FIRST) != 0
    job_res = jnp.concatenate(
        [usage[..., :3], disk[..., None]], axis=-1) * pending[..., None]
    # user_rank / first_idx from the segment boundaries (rows arrive
    # user-sorted; ops/scan.user_segments_from_flags — one derivation
    # shared with the compact rank kernel)
    from ..ops.scan import user_segments_from_flags
    user_rank, first_idx = user_segments_from_flags(is_first, axis=1)
    ur = jnp.clip(user_rank, 0, inp.tokens_u.shape[1] - 1)
    tokens = jnp.take_along_axis(inp.tokens_u, ur, axis=1)
    shares = jax.vmap(lambda s, u: s[u])(inp.shares_u, ur)
    quota = jax.vmap(lambda q, u: q[u])(inp.quota_u, ur)
    # exception-position list -> [T] exc_id map (slot T is the dump row)
    E = inp.exc_rows.shape[1]
    eids = jnp.arange(E, dtype=jnp.int32)[None, :]
    slot = jnp.where(inp.exc_rows >= 0, inp.exc_rows, T)
    exc_id = jax.vmap(
        lambda s, e: jnp.full((T + 1,), -1, dtype=jnp.int32)
        .at[s].set(e, mode="drop")[:T])(slot, jnp.broadcast_to(eids, (P, E)))
    return StructuredPoolCycleInputs(
        usage=usage, quota=quota, shares=shares, first_idx=first_idx,
        user_rank=user_rank, pending=pending, valid=valid,
        enqueue_ok=enqueue_ok, launch_ok=launch_ok, tokens=tokens,
        num_considerable=inp.num_considerable, pool_quota=inp.pool_quota,
        group_quota=inp.group_quota, group_id=inp.group_id,
        job_res=job_res, host_gpu=inp.host_gpu,
        host_blocked=inp.host_blocked, exc_id=exc_id,
        exc_mask=inp.exc_mask, avail=inp.avail, capacity=inp.capacity)


class PoolCycleResult(NamedTuple):
    order: jax.Array          # i32[P, T] rank order (pending first)
    num_ranked: jax.Array     # i32[P] rankable pending count
    dru: jax.Array            # f32[P, T] per-task DRU score (task order)
    assign: jax.Array         # i32[P, T] host or -1, in RANK order
    match_valid: jax.Array    # bool[P, T] admitted for matching (RANK order)
    queue_ok: jax.Array       # bool[P, T] queue membership (RANK order)
    accepted: jax.Array       # bool[P, T] admitted pre-cap (RANK order)
    matched_usage: jax.Array  # f32[P, 4] resources matched per pool (global)
    total_matched: jax.Array  # i32[] global placement count
    # COMPACT outputs: everything the production driver consumes per cycle,
    # O(C + queue) instead of O(T).  The full [T] arrays above stay device-
    # resident (the lazy ranked-queue fetch reads queue_rows on demand),
    # so the driver fetches only the [C]-sized candidate arrays + scalars
    # each cycle.
    queue_rows: jax.Array     # i32[P, T] queue members' task rows in rank
    #                           order; first n_queue entries valid
    n_queue: jax.Array        # i32[P] queue membership count
    cand_row: jax.Array       # i32[P, C] task row per admitted slot, -1 empty
    cand_assign: jax.Array    # i32[P, C] assigned host per slot, -1 unmatched
    cand_qpos: jax.Array      # i32[P, C] queue position per slot, -1 empty


def _segment_totals(cum: jax.Array, first_idx: jax.Array) -> jax.Array:
    """Broadcast each contiguous segment's total (the value of the inclusive
    prefix sum at the segment's last row) back to every row of the segment."""
    T = first_idx.shape[0]
    pos = jnp.arange(T, dtype=jnp.int32)
    is_last = jnp.concatenate(
        [first_idx[1:] != first_idx[:-1], jnp.ones((1,), dtype=bool)])
    seg_last = jax.lax.cummin(jnp.where(is_last, pos, T - 1), axis=0,
                              reverse=True)
    return cum[seg_last]


def _user_running_base(usage, pending, valid, first_idx) -> jax.Array:
    """f32[T, 4]: each task's user's total RUNNING usage in this pool
    (the accumulator seed of pending-jobs->considerable-jobs,
    scheduler.clj:729 / tools.clj:899-915)."""
    run_usage = usage * (valid & ~pending)[:, None]
    cum_run = segmented_cumsum_by_first_idx(run_usage, first_idx)
    return _segment_totals(cum_run, first_idx)


def _compact_admitted(order: jax.Array, match_valid: jax.Array,
                      cap: int) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """Compact the admitted rows (rank order) into a static-``cap`` prefix.

    The greedy match is a sequential ``lax.scan`` over its job axis, so
    running it over all T rank rows costs O(T) scan steps and a [T, H]
    gather even though at most ``num_considerable`` (<= cap) rows are
    admitted.  Compaction keeps the admitted rows' relative order (greedy
    parity is order-dependent) while shrinking the match to O(cap x H).

    Returns (sel i32[cap] rank positions with sentinel T for empty slots,
    task_idx i32[cap] original task rows, valid bool[cap])."""
    T = match_valid.shape[0]
    k = jnp.cumsum(match_valid.astype(jnp.int32)) - 1
    # each admitted row (within cap) writes its rank position into slot k;
    # everything else lands in the discarded dump slot ``cap``
    slot = jnp.where(match_valid & (k < cap), k, cap)
    sel = jnp.full((cap + 1,), T, dtype=jnp.int32).at[slot].set(
        jnp.arange(T, dtype=jnp.int32))[:cap]
    valid = sel < T
    task_idx = order[jnp.minimum(sel, T - 1)]
    return sel, task_idx, valid


def _rank_admit(usage, quota, shares, first_idx, user_rank, pending, valid,
                enqueue_ok, launch_ok, tokens, num_considerable,
                pool_quota, group_quota, pool_base, group_base,
                gpu_mode: bool, max_over_quota_jobs: int):
    """Shared rank + considerable stage of the fused cycle."""
    order, num_ranked, dru, _keep, rankable = dru_ops.rank_body(
        usage, quota, shares, first_idx, user_rank, pending, valid,
        gpu_mode, max_over_quota_jobs)
    run_base = _user_running_base(usage, pending, valid, first_idx)

    # permute every admission input into rank order
    cr = considerable_body(
        usage_r=usage[order], quota_r=quota[order],
        user_r=user_rank[order], run_base_r=run_base[order],
        tokens_r=tokens[order], launch_ok_r=launch_ok[order],
        enqueue_ok_r=enqueue_ok[order], rankable_r=rankable[order],
        pool_base=pool_base, pool_quota=pool_quota,
        group_base=group_base, group_quota=group_quota,
        num_considerable=num_considerable)
    return order, num_ranked, dru, cr


def _match_tail(order, cr, job_res, mask_of, avail, capacity,
                cap: int, T: int):
    """Compact -> gather/compose masks -> greedy match -> scatter back.
    ``mask_of(task_idx)`` produces bool[C, H] for the compacted rows."""
    sel, task_idx, valid_c = _compact_admitted(order, cr.match_valid, cap)
    res_c = job_res[task_idx] * valid_c[:, None]
    mask_c = mask_of(task_idx) & valid_c[:, None]
    assign_c, _avail = match_ops.greedy_assign(
        res_c, mask_c, valid_c, avail, capacity)
    # scatter back to rank order; sentinel slots (sel == T) drop out
    assign = jnp.full((T,), -1, dtype=jnp.int32).at[sel].set(
        assign_c, mode="drop")
    matched = (assign_c >= 0)
    matched_usage = jnp.sum(res_c * matched[:, None], axis=0)[:4]
    return assign, matched_usage, sel, task_idx, valid_c, assign_c


def _compact_outputs(order, queue_ok, sel, task_idx, valid_c, assign_c,
                     T: int):
    """The driver-facing compact form: queue membership compacted to a
    rank-ordered row list + per-admitted-slot (row, host, queue-position)
    triples, so the host fetches O(C + touched-queue-prefix) bytes per
    cycle instead of four full [T] arrays."""
    qpos = jnp.cumsum(queue_ok.astype(jnp.int32)) - 1
    n_queue = jnp.sum(queue_ok.astype(jnp.int32))
    slot = jnp.where(queue_ok, qpos, T)
    queue_rows = jnp.full((T + 1,), T, dtype=jnp.int32).at[slot].set(
        order, mode="drop")[:T]
    cand_row = jnp.where(valid_c, task_idx, -1)
    cand_assign = jnp.where(valid_c, assign_c, -1)
    cand_qpos = jnp.where(valid_c, qpos[jnp.minimum(sel, T - 1)], -1)
    return queue_rows, n_queue, cand_row, cand_assign, cand_qpos


def _pool_cycle_one(usage, quota, shares, first_idx, user_rank, pending,
                    valid, enqueue_ok, launch_ok, tokens, num_considerable,
                    pool_quota, group_quota, pool_base, group_base,
                    job_res, cmask, avail, capacity,
                    gpu_mode: bool, max_over_quota_jobs: int,
                    considerable_cap: Optional[int] = None):
    """One pool's full rank -> considerable -> match with a DENSE
    bool[T, H] constraint mask.

    ``considerable_cap`` (static) bounds the match problem size; it must be
    >= the dynamic ``num_considerable`` or over-cap admitted rows are left
    unmatched this cycle (the fused driver derives it from the pools'
    max_jobs_considered configs)."""
    T = pending.shape[0]
    order, num_ranked, dru, cr = _rank_admit(
        usage, quota, shares, first_idx, user_rank, pending, valid,
        enqueue_ok, launch_ok, tokens, num_considerable, pool_quota,
        group_quota, pool_base, group_base, gpu_mode, max_over_quota_jobs)
    cap = T if considerable_cap is None else min(considerable_cap, T)
    assign, matched_usage, sel, task_idx, valid_c, assign_c = _match_tail(
        order, cr, job_res, lambda ti: cmask[ti], avail, capacity, cap, T)
    compact = _compact_outputs(order, cr.queue_ok, sel, task_idx, valid_c,
                               assign_c, T)
    return (order, num_ranked, dru, assign, cr.match_valid, cr.queue_ok,
            cr.accepted, matched_usage) + compact


def _pool_cycle_structured(usage, quota, shares, first_idx, user_rank,
                           pending, valid, enqueue_ok, launch_ok, tokens,
                           num_considerable, pool_quota, group_quota,
                           pool_base, group_base, job_res, host_gpu,
                           host_blocked, exc_id, exc_mask, avail, capacity,
                           gpu_mode: bool, max_over_quota_jobs: int,
                           considerable_cap: Optional[int] = None):
    """Fused cycle with the STRUCTURED mask (StructuredPoolCycleInputs):
    per-row masks are composed on device for only the compacted rows —
    gpu bidirectional isolation from job_res, host blocks, and full
    exception rows for the complex-job minority."""
    T = pending.shape[0]
    order, num_ranked, dru, cr = _rank_admit(
        usage, quota, shares, first_idx, user_rank, pending, valid,
        enqueue_ok, launch_ok, tokens, num_considerable, pool_quota,
        group_quota, pool_base, group_base, gpu_mode, max_over_quota_jobs)
    cap = T if considerable_cap is None else min(considerable_cap, T)

    def mask_of(task_idx):
        gpu_rows = job_res[task_idx, 2] > 0
        base = jnp.where(gpu_rows[:, None], host_gpu[None, :],
                         ~host_gpu[None, :]) & ~host_blocked[None, :]
        eid = exc_id[task_idx]
        exc_rows = exc_mask[jnp.maximum(eid, 0)]
        return jnp.where((eid >= 0)[:, None], exc_rows, base)

    assign, matched_usage, sel, task_idx, valid_c, assign_c = _match_tail(
        order, cr, job_res, mask_of, avail, capacity, cap, T)
    compact = _compact_outputs(order, cr.queue_ok, sel, task_idx, valid_c,
                               assign_c, T)
    return (order, num_ranked, dru, assign, cr.match_valid, cr.queue_ok,
            cr.accepted, matched_usage) + compact


def single_pool_cycle(usage, quota, shares, first_idx, user_rank, pending,
                      valid, job_res, cmask, avail, capacity,
                      gpu_mode: bool = False, max_over_quota_jobs: int = 100,
                      enqueue_ok=None, launch_ok=None, tokens=None,
                      num_considerable=None, pool_quota=None,
                      group_quota=None, group_base=None,
                      considerable_cap: Optional[int] = None):
    """Single-chip fused rank+considerable+match step (the framework's
    'forward pass').  Jittable as-is; admission inputs default to
    permissive."""
    T = pending.shape[0]
    ones = jnp.ones((T,), dtype=bool)
    enqueue_ok = ones if enqueue_ok is None else enqueue_ok
    launch_ok = ones if launch_ok is None else launch_ok
    tokens = (jnp.full((T,), INF, dtype=jnp.float32)
              if tokens is None else tokens)
    num_considerable = (jnp.asarray(T, dtype=jnp.int32)
                        if num_considerable is None else num_considerable)
    pool_quota = (jnp.full((4,), INF, dtype=jnp.float32)
                  if pool_quota is None else pool_quota)
    group_quota = (jnp.full((4,), INF, dtype=jnp.float32)
                   if group_quota is None else group_quota)
    pool_base = jnp.sum(usage * (valid & ~pending)[:, None], axis=0)[:4]
    group_base = pool_base if group_base is None else group_base
    (order, num_ranked, dru, assign, *_rest) = _pool_cycle_one(
        usage, quota, shares, first_idx, user_rank, pending, valid,
        enqueue_ok, launch_ok, tokens, num_considerable, pool_quota,
        group_quota, pool_base, group_base, job_res, cmask, avail, capacity,
        gpu_mode, max_over_quota_jobs, considerable_cap)
    return order, num_ranked, dru, assign


def make_pool_cycle(mesh, *, gpu_mode: bool = False,
                    max_over_quota_jobs: int = 100,
                    considerable_cap: Optional[int] = None,
                    structured: bool = False, compact: bool = False):
    """Build the jitted pool-sharded cycle for a mesh.  With
    ``structured=True`` the cycle takes StructuredPoolCycleInputs (no dense
    cmask transfer); with ``compact=True`` (implies structured) it takes
    CompactPoolCycleInputs — the minimum-transfer wire form the production
    fused driver sends — expanded on device by ``expand_compact``."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P

    # pools shard over every mesh axis: ("pool",) single-slice, or
    # ("dcn", "pool") with slice-independent pool blocks
    axes = tuple(mesh.axis_names)
    spec = P(axes)
    if compact:
        structured = True
        in_type = CompactPoolCycleInputs
    else:
        in_type = StructuredPoolCycleInputs if structured else PoolCycleInputs

    def cycle_body(inp) -> PoolCycleResult:
        if compact:
            inp = expand_compact(inp)
        # Pass 1 (cheap, vmapped): per-pool RUNNING usage for pool quota and
        # for the quota-group all_gather.
        pool_base = jax.vmap(
            lambda u, p, v: jnp.sum(u * (v & ~p)[:, None], axis=0)[:4]
        )(inp.usage, inp.pending, inp.valid)

        # Reconciliation collective #1: running usage + group ids of every
        # pool, so each pool can enforce its quota-group's cap against the
        # global running total (reference: scheduler.clj:2125-2157). On a
        # 1-D mesh this rides ICI; on ("dcn", "pool") it is the only
        # cross-slice traffic, sized [pools, 4] + [pools].
        base_all, gid_all = pool_base, inp.group_id
        with jax.named_scope("reconcile.quota_groups"):
            for axis in reversed(axes):
                base_all = jax.lax.all_gather(base_all, axis, axis=0,
                                              tiled=True)
                gid_all = jax.lax.all_gather(gid_all, axis, axis=0,
                                             tiled=True)
        group_base = jax.vmap(
            lambda gid: jnp.sum(
                base_all * ((gid_all == gid) & (gid >= 0))[:, None], axis=0)
        )(inp.group_id)

        # Pass 2: the full fused cycle per local pool.
        common = (inp.usage, inp.quota, inp.shares, inp.first_idx,
                  inp.user_rank, inp.pending, inp.valid, inp.enqueue_ok,
                  inp.launch_ok, inp.tokens, inp.num_considerable,
                  inp.pool_quota, inp.group_quota, pool_base, group_base,
                  inp.job_res)
        if structured:
            per_pool = functools.partial(
                _pool_cycle_structured, gpu_mode=gpu_mode,
                max_over_quota_jobs=max_over_quota_jobs,
                considerable_cap=considerable_cap)
            extra = (inp.host_gpu, inp.host_blocked, inp.exc_id,
                     inp.exc_mask, inp.avail, inp.capacity)
        else:
            per_pool = functools.partial(
                _pool_cycle_one, gpu_mode=gpu_mode,
                max_over_quota_jobs=max_over_quota_jobs,
                considerable_cap=considerable_cap)
            extra = (inp.cmask, inp.avail, inp.capacity)
        (order, num_ranked, dru, assign, match_valid, queue_ok, accepted,
         matched_usage, queue_rows, n_queue, cand_row, cand_assign,
         cand_qpos) = jax.vmap(per_pool)(*common, *extra)

        # Reconciliation collective #2: global matched usage + placement
        # count (cycle telemetry, scheduler.clj:1210-1280).
        matched_usage_global = matched_usage
        with jax.named_scope("reconcile.matched_usage"):
            for axis in reversed(axes):
                matched_usage_global = jax.lax.all_gather(
                    matched_usage_global, axis, axis=0, tiled=True)
            total = jax.lax.psum(
                jnp.sum((assign >= 0).astype(jnp.int32)), axes)
        return PoolCycleResult(order=order, num_ranked=num_ranked, dru=dru,
                               assign=assign, match_valid=match_valid,
                               queue_ok=queue_ok, accepted=accepted,
                               matched_usage=matched_usage_global,
                               total_matched=total, queue_rows=queue_rows,
                               n_queue=n_queue, cand_row=cand_row,
                               cand_assign=cand_assign, cand_qpos=cand_qpos)

    # pool-sharded on every field except the device-resident base mirrors,
    # which are replicated (every shard gathers its own pools' rows)
    replicated = {"res_base", "disk_base"}
    in_spec = in_type(*(P() if f in replicated else spec
                        for f in in_type._fields))
    sharded = shard_map(
        cycle_body, mesh=mesh,
        in_specs=(in_spec,),
        out_specs=PoolCycleResult(
            order=spec, num_ranked=spec, dru=spec, assign=spec,
            match_valid=spec, queue_ok=spec, accepted=spec,
            matched_usage=P(), total_matched=P(), queue_rows=spec,
            n_queue=spec, cand_row=spec, cand_assign=spec, cand_qpos=spec),
        check_vma=False)
    # instrumented by the CALLER: sched/fused.py wraps make_pool_cycle's
    # product as instrument_jit("fused.pool_cycle", ...) — wrapping here
    # too would double-count every compile
    return jax.jit(sharded)  # cs-lint: allow=jit-uninstrumented
