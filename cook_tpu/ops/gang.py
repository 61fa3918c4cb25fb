"""Gang all-or-nothing reduction: segment-min over each gang's member
match bits, zeroing partial gangs and re-offering their capacity within
the same cycle (docs/GANG.md).

The coscheduling pass the paper's one-job-one-host matcher lacks
(Ousterhout, ICDCS'82; Gandiva, OSDI'18 treats multi-worker ML jobs as
atomic gangs): a multi-host TPU slice job submitted as a gang group must
come up whole or not at all — a half-placed gang holds capacity while
its own peers starve behind it.

Shared by both match paths (``sched/matcher.py`` and the fused driver's
``sched/fused._apply_pool``) as a post-kernel pass over the assignment
vector:

1. **reduce** — per gang, count matched members (segment-sum of match
   bits) and, for gangs with a topology request, check every matched
   member landed in ONE topology domain (segment-min == segment-max over
   the members' host topology codes).  Incomplete gangs are reset to
   unmatched — the segment-min of a gang's match bits gates the whole
   gang;
2. **refill** — the capacity the dropped members were holding is folded
   back into host availability and the still-unmatched *group-less* jobs
   get one more greedy pass over it, so a dropped partial gang's offers
   are reusable in the SAME cycle instead of idling a full cadence tick.

The device form (:func:`gang_reduce_kernel`) is a jitted jnp segment
reduction with bucketed shapes (compile reuse like every other kernel in
``cook_tpu.ops``); :func:`cook_tpu.ops.reference_impl.gang_reduce` is
the host golden and the fallback when dispatch fails.

Topology preference (slice-local packing) happens BEFORE the match
kernel, in ``sched/constraints.build_constraint_mask``: gang members'
feasibility rows are restricted to the topology domain with the most
member-feasible hosts, so the kernel packs slice-local by construction
and this pass only enforces the invariant.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Callable, Dict, List, Optional, Tuple

import numpy as np

from ..utils import tracing
from ..utils.flight import recorder as _flight
from ..utils.metrics import registry
from . import reference_impl, telemetry
from .padding import bucket, pad_to

F32 = np.float32


@dataclass
class GangPack:
    """Host-side gang arrays for one match batch (built only when the
    batch actually contains gang members — the no-gang path never
    allocates any of this).

    ``gang_size`` is the REDUCTION THRESHOLD — the member count below
    which the gang drops whole.  For rigid gangs that is the declared
    ``gang_size``; for elastic gangs it is ``gang_min`` (docs/GANG.md
    elasticity: the segment reduction compares against min; members
    matched beyond min simply keep their placements as surplus).
    ``declared`` carries the full declared size for stats/explainers."""

    gang_id: np.ndarray          # i32[J], -1 = not a gang member
    gang_size: np.ndarray        # i32[G] reduction threshold (min)
    gang_attr: np.ndarray        # i32[G] row into host_topo, 0 = none
    host_topo: np.ndarray        # i32[A, H] topology code, -1 = absent
    uuids: List[str]             # gang segment -> group uuid
    topology: List[Optional[str]]  # gang segment -> requested attribute
    declared: List[int] = None   # gang segment -> declared gang_size


@dataclass
class GangStats:
    """What the reduction did, for the cycle record / explainer."""

    dropped_jobs: int = 0
    dropped_gangs: int = 0
    refilled: int = 0
    # group uuid -> {"size", "matched", "missing", "topology_blocked"}
    partial: Dict[str, Dict] = field(default_factory=dict)


def _topology_table(topo_names: List[Optional[str]], offers
                    ) -> Tuple[Dict[str, int], np.ndarray]:
    """Topology code table of the gang pack: one row per distinct
    requested attribute, row 0 reserved for "no topology request" (all
    zeros, never read through a required gang).  Code assignment is
    offer-order deterministic."""
    attrs = sorted({a for a in topo_names if a})
    attr_row = {a: i + 1 for i, a in enumerate(attrs)}
    H = max(len(offers), 1)
    host_topo = np.full((len(attrs) + 1, H), -1, dtype=np.int32)
    host_topo[0] = 0
    for a, row in attr_row.items():
        codes: Dict[str, int] = {}
        for h, o in enumerate(offers):
            v = o.attributes.get(a)
            if v is not None:
                host_topo[row, h] = codes.setdefault(v, len(codes))
    return attr_row, host_topo


def build_gang_pack(jobs, groups: Dict[str, object], offers,
                    satisfied=None) -> Optional[GangPack]:
    """Gang arrays for a match batch, or None when no job in the batch
    belongs to a gang group (the structural no-op guard that keeps
    non-gang workloads decision-identical).

    ``satisfied`` (docs/GANG.md elasticity): group uuids of ELASTIC
    gangs already running at >= gang_min live members.  Their waiting
    members in this batch are the GROW path — they place individually
    like group-less jobs, so they are excluded from the pack entirely
    (no cohort gate to fail, no reduction to reset them)."""
    # membership scan FIRST: the gang-free majority must bail before
    # the [J] array below is allocated (a 100k-job gang-free pool would
    # otherwise pay it every match cycle just to hear "None")
    member_rows = [j for j, job in enumerate(jobs)
                   if getattr(job, "group", None)
                   and getattr(groups.get(job.group), "gang", False)
                   and not (satisfied and job.group in satisfied)]
    if not member_rows:
        return None
    from ..state.schema import gang_bounds
    J = len(jobs)
    gang_id = np.full(J, -1, dtype=np.int32)
    uuids: List[str] = []
    sizes: List[int] = []
    declared: List[int] = []
    topo_names: List[Optional[str]] = []
    seg: Dict[str, int] = {}
    for j in member_rows:
        g = groups[jobs[j].group]
        guuid = jobs[j].group
        k = seg.get(guuid)
        if k is None:
            k = seg[guuid] = len(uuids)
            uuids.append(guuid)
            # the reduction gates on the effective MINIMUM: rigid gangs
            # read min == declared size, bit-identically
            sizes.append(gang_bounds(g)[0])
            declared.append(int(getattr(g, "gang_size", 0) or 0))
            topo_names.append(getattr(g, "gang_topology", None) or None)
        gang_id[j] = k
    attr_row, host_topo = _topology_table(topo_names, offers)
    gang_attr = np.array([attr_row.get(a, 0) if a else 0
                          for a in topo_names], dtype=np.int32)
    return GangPack(gang_id=gang_id,
                    gang_size=np.array(sizes, dtype=np.int32),
                    gang_attr=gang_attr, host_topo=host_topo,
                    uuids=uuids, topology=topo_names,
                    declared=declared)


# ------------------------------------------------------------------ device
def gang_reduce_body(assign, gang_id, gang_size, gang_attr, host_topo):
    """The pure gang_min-gated segment reduction the jitted kernel below
    is built from (parity test-asserted against
    reference_impl.gang_reduce)."""
    import jax
    import jax.numpy as jnp
    G = gang_size.shape[0]
    member = gang_id >= 0
    gid = jnp.where(member, gang_id, 0)
    matched = member & (assign >= 0)
    cnt = jax.ops.segment_sum(matched.astype(jnp.int32), gid,
                              num_segments=G)
    h = jnp.clip(assign, 0, host_topo.shape[1] - 1)
    topo = host_topo[gang_attr[gid], h]
    big = jnp.int32(2 ** 30)
    tmin = jax.ops.segment_min(jnp.where(matched, topo, big),
                               gid, num_segments=G)
    tmax = jax.ops.segment_max(jnp.where(matched, topo, -big),
                               gid, num_segments=G)
    topo_ok = (gang_attr <= 0) | ((tmin == tmax) & (tmin >= 0))
    complete = (cnt >= gang_size) & topo_ok
    dropped = matched & ~complete[gid]
    return jnp.where(dropped, jnp.int32(-1), assign), dropped


_KERNEL = None


def _kernel():
    """The jitted segment reduction, built once (bucketed shapes reuse
    the compiled cycle like every other kernel here)."""
    global _KERNEL
    if _KERNEL is None:
        import jax
        _KERNEL = telemetry.instrument_jit("gang.reduce",
                                           jax.jit(gang_reduce_body))
    return _KERNEL


def gang_reduce_kernel(assign: np.ndarray, pack: GangPack
                       ) -> Tuple[np.ndarray, np.ndarray]:
    """Device segment reduction over bucketed shapes.  Padding jobs get
    gang_id -1 (never members); padding gangs get an unreachable size so
    they are incomplete with zero members and touch nothing."""
    import jax.numpy as jnp
    J = len(assign)
    Jb = bucket(J)
    Gb = bucket(len(pack.gang_size), minimum=8)
    Ab = bucket(pack.host_topo.shape[0], minimum=1)
    Hb = bucket(pack.host_topo.shape[1])
    assign_p = pad_to(np.asarray(assign, dtype=np.int32), Jb, fill=-1)
    gid_p = pad_to(pack.gang_id, Jb, fill=-1)
    size_p = pad_to(pack.gang_size, Gb, fill=2 ** 30)
    attr_p = pad_to(pack.gang_attr, Gb, fill=0)
    topo_p = np.full((Ab, Hb), -1, dtype=np.int32)
    topo_p[:pack.host_topo.shape[0], :pack.host_topo.shape[1]] = \
        pack.host_topo
    out, dropped = _kernel()(
        jnp.asarray(assign_p), jnp.asarray(gid_p), jnp.asarray(size_p),
        jnp.asarray(attr_p), jnp.asarray(topo_p))
    with telemetry.sync_wait("gang.reduce"):
        out_np = np.asarray(out)[:J]
        dropped_np = np.asarray(dropped)[:J]
    return out_np, dropped_np


# ------------------------------------------------------------------- cycle
def apply_gang_cycle(jobs, assign: np.ndarray, offers,
                     groups: Dict[str, object], *,
                     job_res: Optional[np.ndarray] = None,
                     cmask_fn: Optional[Callable[[], np.ndarray]] = None,
                     avail: Optional[np.ndarray] = None,
                     capacity: Optional[np.ndarray] = None,
                     device: bool = False,
                     refill_ok: Optional[np.ndarray] = None,
                     audit_trail=None,
                     audit_pool: Optional[str] = None,
                     satisfied=None,
                     ) -> Tuple[np.ndarray, Optional[GangStats]]:
    """The full per-cycle gang pass: reduce partial gangs to nothing and
    refill the freed capacity with still-unmatched group-less jobs.

    Structural no-op (returns ``assign`` unchanged, stats None) when the
    batch has no gang members — non-gang workloads stay
    decision-identical.  ``cmask_fn``/``avail``/``capacity`` feed the
    refill pass and may be omitted to skip it (the caller then re-offers
    freed capacity next cycle instead).

    ``satisfied``: group uuids of elastic gangs already running at >=
    gang_min — their waiting members bypass the reduction (grow path)
    and join the refill pool like group-less jobs (docs/GANG.md
    elasticity).
    """
    pack = build_gang_pack(jobs, groups, offers, satisfied=satisfied)
    if pack is None:
        return assign, None
    assign = np.asarray(assign, dtype=np.int32)
    with tracing.span("gang.reduce", gangs=len(pack.uuids),
                      jobs=len(jobs)):
        if device:
            try:
                out, dropped = gang_reduce_kernel(assign, pack)
            except telemetry.KernelBuildError:
                raise  # repeats every cycle: not a fault to absorb
            except Exception:
                import logging
                logging.getLogger(__name__).exception(
                    "gang reduce dispatch failed; host fallback")
                registry.counter_inc("cook_kernel_fallback",
                                     labels={"kernel": "gang.reduce"})
                _flight.note_fault("kernel.dispatch-fallback")
                out, dropped = reference_impl.gang_reduce(
                    assign, pack.gang_id, pack.gang_size,
                    pack.gang_attr, pack.host_topo)
        else:
            out, dropped = reference_impl.gang_reduce(
                assign, pack.gang_id, pack.gang_size,
                pack.gang_attr, pack.host_topo)
    # ---- rescue pass: a dropped cohort whose members are ALL in the
    # batch may still be packable whole — the kernel assigns in rank
    # order, so an unconstrained sibling ranked ahead of a constrained
    # member (novel-host after a requeue, say) can greedily take the
    # only hosts the constrained member could use, dropping the gang
    # identically every cycle.  Re-match just the cohort, most-
    # constrained member FIRST, against the capacity left by the
    # surviving assignments; accept only a complete packing.
    # the constraint mask is a full O(jobs x hosts) rebuild on the fused
    # path — compute it at most once per cycle, shared by rescue + refill
    cmask: Optional[np.ndarray] = None
    if (dropped.any() and cmask_fn is not None and avail is not None
            and capacity is not None and job_res is not None):
        cmask = np.asarray(cmask_fn(), dtype=bool)
        res_f = np.asarray(job_res, dtype=F32)
        cap_f = np.asarray(capacity, dtype=F32)
        H = cap_f.shape[0]
        avail_left = np.asarray(avail, dtype=F32).copy()
        taken = (out >= 0) & (out < H)
        if taken.any():
            np.subtract.at(avail_left, out[taken], res_f[taken])
        avail_left = np.maximum(avail_left, 0.0)
        from ..state.schema import GroupPlacementType
        for g in sorted({int(x) for x in pack.gang_id[dropped]}):
            rows = np.flatnonzero(pack.gang_id == g)
            if len(rows) < int(pack.gang_size[g]):
                continue  # members missing from the batch: no rescue
            ptype = getattr(groups.get(pack.uuids[g]),
                            "placement_type", None)
            if ptype is not None and ptype is not GroupPlacementType.ALL:
                # the re-pack honors resources + per-job cmask only;
                # within-batch host-placement rules (UNIQUE /
                # ATTRIBUTE_EQUALS / BALANCED) live in
                # validate_group_placement, which already ran — a rescue
                # could silently violate them, so such gangs wait for
                # the normal pass next cycle
                continue
            sub_mask = cmask[rows, :H]
            fits = np.stack([np.all(avail_left >= res_f[r][None, :],
                                    axis=1) for r in rows])
            order = np.argsort((sub_mask & fits).sum(axis=1),
                               kind="stable")
            trial = reference_impl.greedy_match(
                res_f[rows][order], sub_mask[order], avail_left, cap_f)
            # acceptance threshold = the reduction threshold: rigid
            # gangs have exactly `need` rows here so this is the old
            # all-assigned test bit-for-bit; an elastic gang accepts a
            # partial packing of >= gang_min members (the unassigned
            # surplus simply stays unmatched, docs/GANG.md elasticity)
            hit = trial >= 0
            if int(hit.sum()) >= int(pack.gang_size[g]):
                out[rows[order]] = trial
                dropped[rows] = False
                np.subtract.at(avail_left, trial[hit],
                               res_f[rows][order][hit])
                avail_left = np.maximum(avail_left, 0.0)
    stats = GangStats()
    member = pack.gang_id >= 0
    matched_before = member & (assign >= 0)
    matched_final = member & (out >= 0)
    for g, guuid in enumerate(pack.uuids):
        rows = pack.gang_id == g
        matched = int(matched_before[rows].sum())
        # need = reduction threshold (gang_min); size = declared size.
        # Rigid gangs read need == size, so the entry is unchanged.
        need = int(pack.gang_size[g])
        size = int(pack.declared[g]) if pack.declared else need
        if int(matched_final[rows].sum()) >= need \
                and not dropped[rows].any():
            continue  # placed whole (directly or via the rescue pass)
        # topology_blocked: every member matched but the reduction still
        # dropped them — the placements straddled topology domains (or
        # landed outside any), i.e. no single slice took them all
        entry = {
            "size": size, "matched": matched,
            "missing": max(need - matched, 0),
            "topology_blocked": bool(matched >= need
                                     and dropped[rows].any())}
        if need != size:
            entry["min"] = need
        stats.partial[guuid] = entry
    stats.dropped_jobs = int(dropped.sum())
    stats.dropped_gangs = len(
        {int(g) for g in pack.gang_id[dropped]})
    if stats.dropped_jobs:
        registry.counter_inc("cook_gang_partial_drops",
                             float(stats.dropped_gangs))
        # aggregate histogram + per-job attribution from one drop mask
        # (utils/audit.note_skips; the member resets explain themselves
        # on each job's timeline)
        from ..utils import audit as _audit
        _audit.note_skips(audit_trail, {
            "gang-partial": [jobs[i].uuid
                             for i in np.flatnonzero(dropped)]},
            pool=audit_pool)
        # ---- same-cycle refill: the freed capacity goes back to the
        # pool for group-less unmatched jobs (group members need their
        # own group semantics re-validated, so they wait a cycle)
        if (cmask_fn is not None and avail is not None
                and capacity is not None and job_res is not None):
            avail_after = np.asarray(avail, dtype=F32).copy()
            # defensive clip: a padding-host assignment (possible only
            # for zero-resource jobs) must not index past the real hosts
            taken = (out >= 0) & (out < avail_after.shape[0])
            if taken.any():
                np.subtract.at(avail_after, out[taken],
                               np.asarray(job_res, dtype=F32)[taken])
            avail_after = np.maximum(avail_after, 0.0)
            # group-less jobs — plus the grow members of SATISFIED
            # elastic gangs, which the elasticity contract says refill
            # exactly like group-less jobs (docs/GANG.md)
            eligible = ((out < 0) & ~dropped
                        & np.array([not getattr(j, "group", None)
                                    or bool(satisfied
                                            and j.group in satisfied)
                                    for j in jobs], dtype=bool))
            if refill_ok is not None:
                # the caller vetoes rows whose unmatched state is not a
                # plain capacity miss (e.g. pipeline resource conflicts
                # whose staged availability is known-stale)
                eligible &= np.asarray(refill_ok, dtype=bool)
            idx = np.flatnonzero(eligible)
            if idx.size:
                if cmask is None:
                    cmask = np.asarray(cmask_fn(), dtype=bool)
                refill = reference_impl.greedy_match(
                    np.asarray(job_res, dtype=F32)[idx], cmask[idx],
                    avail_after, np.asarray(capacity, dtype=F32))
                hit = refill >= 0
                if hit.any():
                    out[idx[hit]] = refill[hit]
                    stats.refilled = int(hit.sum())
    return out, stats
