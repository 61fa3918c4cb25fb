"""Pallas fused-cycle MEGAKERNEL: rank -> admission -> match ->
gang-reduce in ONE kernel launch, with every [T]-sized intermediate
resident in VMEM (ISSUE 14; ROADMAP item 5).

The fused XLA driver (parallel/sharded.make_pool_cycle) already runs the
whole cycle as one jit, but XLA still materializes the stage boundaries
— ranked order, admission bits, the compacted candidate block, match
assignments, gang gates — as [T]-sized HBM buffers between fusion
islands, and the split driver pays a full launch + HBM round trip per
stage.  This kernel applies the FlashAttention-era recipe to scheduling:
one ``pl.pallas_call`` whose per-pool program keeps the entire
intermediate chain in VMEM scratch/registers, so HBM traffic is
O(wire inputs + compact outputs) and the launch count per cycle is 1.

Stage structure (grid = (2, P); the phase axis is OUTERMOST, so every
pool's phase-0 program runs before any phase-1 program — VMEM scratch
persists across the sequential TPU grid exactly as pallas_match's
running top-K does):

  phase 0  per-pool RUNNING usage -> ``pool_base`` scratch (the
           cross-pool quota-group reconciliation the fused cycle does
           with an all_gather; one scratch row per pool replaces it on
           the single-mesh path this kernel serves);
  phase 1  wire decode (quantized codecs, ops/quant.py) -> DRU
           cumulative-share rank (ops/dru.rank_body) -> considerable
           admission (ops/considerable.considerable_body) -> compacted
           structured-mask match (the pallas_match mask-composition
           recipe: per-row masks are composed IN VMEM for only the
           admitted C rows, absorbed here as the middle stage) -> greedy
           assignment -> compact outputs -> gang ``gang_min``-gated
           segment reduction (ops/gang reduce math) — all without
           leaving the kernel.

BIT-PARITY is the contract, not a goal: phase 1 calls the SAME module
functions the fused XLA driver vmaps (``_pool_cycle_structured`` and
friends), so the decision math has one home and the parity matrix in
tests/test_megakernel.py asserts byte-identical launch decisions across
megakernel / fused / split / depth-2 pipelined drivers, rigid and
elastic gangs, compact and quantized wire.

On CPU the kernel runs in interpret mode (tier-1 honest, like
ops/pallas_match.py).  ON THE TPU IT DOES NOT LOWER: Pallas refuses it
for the v5e at the first in-kernel gather (``res_base[rows]``:
``ValueError: Shape mismatch in input, indices and output`` from the
Mosaic gather lowering rule, at T=128Ki and at T=1Ki alike — CHANGES.md
PR 21), before the in-kernel sort, the scatter, or the VMEM budget below
are ever tried.  So ``auto`` never selects it, and an explicit
``tpu-megakernel`` pin raises ``KernelBuildError`` on the chip instead
of degrading (docs/ROBUSTNESS.md); only a runtime fault on an executable
that has run falls back to the fused XLA driver with
``cook_kernel_fallback_total{kernel=pallas.megacycle}``.  A kernel that
compiles there is ROADMAP S3.

VMEM budget per pool program, on paper (docs/PERFORMANCE.md kernel
registry): rows/flags/order/assign-chain ~ 6 x 4B x T, the structured
mask composition C x H x 1B, host stacks 2 x H x 16B, base gathers
T x 20B — ~13 MB at T=128Ki, C=1Ki, H=8Ki against a v5e core's ~16 MB,
not counting that 1-D and (N, 4) blocks pad their last dimension to 128
lanes.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import quant, telemetry

_BIG = 2 ** 30  # python literal: module-level jnp consts would be captured


class MegaCycleWire(NamedTuple):
    """Device-ready megakernel inputs: the compact wire with each
    quantizable field carried in its NEGOTIATED form (ops/quant.py; the
    codec tags ride separately as static args so one executable serves
    each negotiated shape).  ``rows``/``flags`` may be the
    device-resident buffers (sched/fused._ResidentPack) — then they cost
    zero h2d this cycle and ``rows_codec`` is wide."""

    rows: jax.Array        # [P, T] i32 | i16 | i8 (codec-tagged)
    flags: jax.Array       # u8[P, T]
    res_base: jax.Array    # f32[N, 4] device-resident mirror
    disk_base: jax.Array   # f32[N]
    tokens_u: jax.Array    # f32[P, U]
    shares_u: jax.Array    # f32[P, U, 3]
    quota_u: jax.Array     # f32[P, U, 4]
    num_considerable: jax.Array  # i32[P]
    pool_quota: jax.Array  # f32[P, 4]
    group_quota: jax.Array  # f32[P, 4]
    group_id: jax.Array    # i32[P]
    host_bits: jax.Array   # u8[P, 2, ceil(H/8)] bitpacked (gpu, blocked)
    exc_rows: jax.Array    # i32[P, E]
    exc_mask: jax.Array    # bool[P, E, H]
    avail: jax.Array       # [P, H, 4] f32 | u16 (scale-tagged)
    capacity: jax.Array    # [P, H, 4] f32 | u16
    gang_id: jax.Array     # i32[P, T] sorted-position gang segment, -1
    gang_size: jax.Array   # i32[P, G] reduction threshold (gang_min)
    gang_attr: jax.Array   # i32[P, G]
    host_topo: jax.Array   # i32[P, A, H]


class MegaCycleResult(NamedTuple):
    """Everything the driver consumes per cycle, O(C + queue) on the
    fetch path like PoolCycleResult's compact outputs — plus the fused
    gang stage's verdicts so the host apply can skip its own reduction
    when the candidate set is intact."""

    queue_rows: jax.Array   # i32[P, T] (stays device-resident)
    n_queue: jax.Array      # i32[P]
    cand_row: jax.Array     # i32[P, C]
    cand_assign: jax.Array  # i32[P, C] PRE-gang assignment
    cand_qpos: jax.Array    # i32[P, C]
    cand_gang: jax.Array    # i32[P, C] POST-gang-reduction assignment
    cand_dropped: jax.Array  # i32[P, C] 1 = reduction reset this slot


def _decode_hosts(host_bits, H: int):
    gpu_blk = quant.unpack_bits_device(host_bits[0], H)
    blocked_blk = quant.unpack_bits_device(host_bits[1], H)
    return gpu_blk, blocked_blk


def _gang_reduce_candidates(cand_row, cand_assign, gang_id, gang_size,
                            gang_attr, host_topo):
    """The gang_min-gated segment reduction over the admitted candidate
    slots: map each slot to its task row's gang segment, then run the
    SHARED reduction body (ops/gang.gang_reduce_body — one home for the
    decision math, parity-asserted against reference_impl.gang_reduce).
    Padding slots (cand_row < 0) and padding gangs (unreachable size)
    touch nothing."""
    from .gang import gang_reduce_body
    valid_c = cand_row >= 0
    gid_c = jnp.where(valid_c, gang_id[jnp.maximum(cand_row, 0)], -1)
    return gang_reduce_body(cand_assign, gid_c, gang_size, gang_attr,
                            host_topo)


def _kernel(rows_ref, flags_ref, res_ref, disk_ref, tokens_ref,
            shares_ref, quota_ref, ncons_ref, pq_ref, gq_ref, gid_all_ref,
            hbits_ref, excr_ref, excm_ref, avail_ref, cap_ref,
            gangid_ref, gsize_ref, gattr_ref, gtopo_ref,
            qrows_ref, nq_ref, crow_ref, cassign_ref, cqpos_ref,
            cgang_ref, cdrop_ref, base_s, *, gpu_mode: bool,
            max_over_quota_jobs: int, considerable_cap: int,
            rows_codec: int, avail_scale: float, cap_scale: float,
            n_hosts: int):
    """One (phase, pool) grid step.  Phase 0 banks the pool's running
    usage in the persistent ``base_s`` scratch; phase 1 runs the whole
    fused cycle for the pool against every pool's banked base."""
    s = pl.program_id(0)
    p = pl.program_id(1)
    T = rows_ref.shape[1]
    C = crow_ref.shape[1]

    # --- wire decode (shared with phase 0's usage computation) --------
    rows = quant.expand_rows_device(rows_codec, rows_ref[...][0], T)
    flags = flags_ref[...][0]
    from .delta import FLAG_PENDING, FLAG_VALID
    pending = (flags & FLAG_PENDING) != 0
    valid = (flags & FLAG_VALID) != 0
    res_base = res_ref[...]
    usage = res_base[rows]                                  # [T, 4]

    @pl.when(s == 0)
    def _bank_base():
        pool_base = jnp.sum(usage * (valid & ~pending)[:, None],
                            axis=0)[:4]
        base_s[pl.ds(p, 1), :] = pool_base.reshape(1, 4)
        # neutral output writes: phase-1 programs revisit and overwrite
        qrows_ref[0, :] = jnp.zeros((T,), dtype=jnp.int32)
        nq_ref[0, :] = jnp.zeros((1,), dtype=jnp.int32)
        for ref in (crow_ref, cassign_ref, cqpos_ref, cgang_ref):
            ref[0, :] = jnp.full((C,), -1, dtype=jnp.int32)
        cdrop_ref[0, :] = jnp.zeros((C,), dtype=jnp.int32)

    @pl.when(s == 1)
    def _cycle():
        from .delta import FLAG_ENQUEUE_OK, FLAG_LAUNCH_OK, FLAG_USER_FIRST
        from .scan import user_segments_from_flags
        from ..parallel.sharded import _pool_cycle_structured
        disk = disk_ref[...][:, 0][rows]                    # [T]
        enqueue_ok = (flags & FLAG_ENQUEUE_OK) != 0
        launch_ok = (flags & FLAG_LAUNCH_OK) != 0
        is_first = (flags & FLAG_USER_FIRST) != 0
        job_res = jnp.concatenate(
            [usage[:, :3], disk[:, None]], axis=-1) * pending[:, None]
        user_rank, first_idx = user_segments_from_flags(is_first)
        U = tokens_ref.shape[1]
        ur = jnp.clip(user_rank, 0, U - 1)
        tokens = tokens_ref[...][0][ur]
        shares = shares_ref[...][0][ur]
        quota = quota_ref[...][0][ur]
        # exception-position list -> [T] exc_id map (slot T = dump row),
        # the expand_compact recipe per pool
        E = excr_ref.shape[1]
        exc_rows = excr_ref[...][0]
        eids = jnp.arange(E, dtype=jnp.int32)
        slot = jnp.where(exc_rows >= 0, exc_rows, T)
        exc_id = jnp.full((T + 1,), -1, dtype=jnp.int32) \
            .at[slot].set(eids, mode="drop")[:T]
        host_gpu, host_blocked = _decode_hosts(hbits_ref[...][0], n_hosts)
        avail = quant.expand_fixed_device(avail_scale, avail_ref[...][0])
        capacity = quant.expand_fixed_device(cap_scale, cap_ref[...][0])
        # cross-pool quota-group base off the banked phase-0 scratch —
        # the all_gather's single-mesh twin (same sum order: pool-major)
        bases = base_s[...]                                 # [P, 4]
        gid_all = gid_all_ref[...][:, 0]
        gid = gid_all[p]
        pool_base = base_s[pl.ds(p, 1), :][0]
        group_base = jnp.sum(
            bases * ((gid_all == gid) & (gid >= 0))[:, None], axis=0)

        (_order, _num_ranked, _dru, _assign, _match_valid, _queue_ok,
         _accepted, _matched_usage, queue_rows, n_queue, cand_row,
         cand_assign, cand_qpos) = _pool_cycle_structured(
            usage, quota, shares, first_idx, user_rank, pending, valid,
            enqueue_ok, launch_ok, tokens, ncons_ref[...][0, 0],
            pq_ref[...][0], gq_ref[...][0], pool_base, group_base,
            job_res, host_gpu, host_blocked, exc_id, excm_ref[...][0],
            avail, capacity, gpu_mode, max_over_quota_jobs,
            considerable_cap)

        cand_gang, dropped = _gang_reduce_candidates(
            cand_row, cand_assign, gangid_ref[...][0], gsize_ref[...][0],
            gattr_ref[...][0], gtopo_ref[...][0])

        qrows_ref[0, :] = queue_rows
        nq_ref[0, :] = n_queue.astype(jnp.int32).reshape(1)
        crow_ref[0, :] = cand_row
        cassign_ref[0, :] = cand_assign
        cqpos_ref[0, :] = cand_qpos
        cgang_ref[0, :] = cand_gang
        cdrop_ref[0, :] = dropped.astype(jnp.int32)


_FNS = {}


def _megacycle_fn(*, shapes, gpu_mode: bool, max_over_quota_jobs: int,
                  considerable_cap: int, rows_codec: int,
                  avail_scale: float, cap_scale: float, n_hosts: int,
                  interpret: bool):
    """Build (and cache) the jitted single-launch cycle for one
    negotiated wire shape.  ``shapes`` is the MegaCycleWire shape/dtype
    tuple — part of the cache key like every other bucketed kernel."""
    key = (shapes, gpu_mode, max_over_quota_jobs, considerable_cap,
           rows_codec, avail_scale, cap_scale, n_hosts, interpret)
    fn = _FNS.get(key)
    if fn is not None:
        return fn
    (P, T) = shapes[0][0]
    N = shapes[2][0][0]
    U = shapes[4][0][1]
    E = shapes[12][0][1]
    H = shapes[14][0][1]
    G = shapes[17][0][1]
    A = shapes[19][0][1]
    B = shapes[11][0][2]              # bitpacked host bytes
    C = considerable_cap
    grid = (2, P)
    mem = {"memory_space": pltpu.VMEM}

    def pool_block(shape):
        """One pool's slice, same block for both phases."""
        return pl.BlockSpec((1,) + shape, lambda s, p: (p,) + (0,) * len(shape),
                            **mem)

    def full_block(shape):
        return pl.BlockSpec(shape, lambda s, p: (0,) * len(shape), **mem)

    kernel = functools.partial(
        _kernel, gpu_mode=gpu_mode,
        max_over_quota_jobs=max_over_quota_jobs,
        considerable_cap=considerable_cap, rows_codec=rows_codec,
        avail_scale=avail_scale, cap_scale=cap_scale, n_hosts=n_hosts)
    in_specs = [
        pool_block((T,)),          # rows
        pool_block((T,)),          # flags
        full_block((N, 4)),        # res_base
        full_block((N, 1)),        # disk_base (reshaped)
        pool_block((U,)),          # tokens_u
        pool_block((U, 3)),        # shares_u
        pool_block((U, 4)),        # quota_u
        pool_block((1,)),          # num_considerable (reshaped [P, 1])
        pool_block((4,)),          # pool_quota
        pool_block((4,)),          # group_quota
        full_block((P, 1)),        # group_id (reshaped; cross-pool)
        pool_block((2, B)),        # host_bits
        pool_block((E,)),          # exc_rows
        pool_block((E, H)),        # exc_mask
        pool_block((H, 4)),        # avail
        pool_block((H, 4)),        # capacity
        pool_block((T,)),          # gang_id
        pool_block((G,)),          # gang_size
        pool_block((G,)),          # gang_attr
        pool_block((A, H)),        # host_topo
    ]
    out_shape = (
        jax.ShapeDtypeStruct((P, T), jnp.int32),   # queue_rows
        jax.ShapeDtypeStruct((P, 1), jnp.int32),   # n_queue
        jax.ShapeDtypeStruct((P, C), jnp.int32),   # cand_row
        jax.ShapeDtypeStruct((P, C), jnp.int32),   # cand_assign
        jax.ShapeDtypeStruct((P, C), jnp.int32),   # cand_qpos
        jax.ShapeDtypeStruct((P, C), jnp.int32),   # cand_gang
        jax.ShapeDtypeStruct((P, C), jnp.int32),   # cand_dropped
    )
    out_specs = (
        pool_block((T,)), pool_block((1,)), pool_block((C,)),
        pool_block((C,)), pool_block((C,)), pool_block((C,)),
        pool_block((C,)),
    )
    call = pl.pallas_call(
        kernel, grid=grid, in_specs=in_specs, out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((P, 4), jnp.float32)],
        interpret=interpret)

    def run(wire_arrays):
        outs = call(*wire_arrays)
        return MegaCycleResult(
            queue_rows=outs[0], n_queue=outs[1][:, 0], cand_row=outs[2],
            cand_assign=outs[3], cand_qpos=outs[4], cand_gang=outs[5],
            cand_dropped=outs[6])

    fn = telemetry.instrument_jit("pallas.megacycle", jax.jit(run))
    _FNS[key] = fn
    return fn


def megacycle(wire: MegaCycleWire, *, gpu_mode: bool = False,
              max_over_quota_jobs: int = 100,
              considerable_cap: int = 1024,
              rows_codec: int = quant.ROWS_WIDE,
              avail_scale: float = 0.0, cap_scale: float = 0.0,
              interpret: Optional[bool] = None) -> MegaCycleResult:
    """Dispatch one fused-cycle megakernel launch.

    ``wire`` fields may be numpy or device arrays; the wrapper reshapes
    the 1-D scalars ([P] -> [P, 1], disk [N] -> [N, 1]) for Pallas
    block-shape friendliness.  Codec tags are static — the negotiation
    in sched/fused staging picks them and the executable is cached per
    (shape, codec) exactly like every other bucketed kernel."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cap = int(min(considerable_cap, wire.rows.shape[1]))
    arrays = (
        wire.rows, wire.flags, wire.res_base,
        jnp.asarray(wire.disk_base).reshape(-1, 1),
        wire.tokens_u, wire.shares_u, wire.quota_u,
        jnp.asarray(wire.num_considerable).reshape(-1, 1),
        wire.pool_quota, wire.group_quota,
        jnp.asarray(wire.group_id).reshape(-1, 1),
        wire.host_bits, wire.exc_rows, wire.exc_mask, wire.avail,
        wire.capacity, wire.gang_id, wire.gang_size, wire.gang_attr,
        wire.host_topo)
    arrays = tuple(jnp.asarray(a) for a in arrays)
    # dtypes ride the cache key alongside shapes: two negotiated wires
    # can share every shape and differ only in a narrow dtype
    shapes = tuple((tuple(a.shape), str(a.dtype)) for a in arrays)
    n_hosts = int(wire.exc_mask.shape[2])
    def _scale_key(s):  # 0.0 = wide, else a per-column tuple
        return s if isinstance(s, tuple) else float(s)

    fn = _megacycle_fn(
        shapes=shapes, gpu_mode=bool(gpu_mode),
        max_over_quota_jobs=int(max_over_quota_jobs),
        considerable_cap=cap, rows_codec=int(rows_codec),
        avail_scale=_scale_key(avail_scale),
        cap_scale=_scale_key(cap_scale),
        n_hosts=n_hosts, interpret=bool(interpret))
    return fn(arrays)


def empty_gang_wire(P: int, T: int, H: int) -> Tuple[np.ndarray, ...]:
    """The structural no-op gang wire (no members, one unreachable-size
    padding gang): lets one kernel signature serve gang-free cycles."""
    return (np.full((P, T), -1, dtype=np.int32),
            np.full((P, 8), _BIG, dtype=np.int32),
            np.zeros((P, 8), dtype=np.int32),
            np.full((P, 1, H), -1, dtype=np.int32))
