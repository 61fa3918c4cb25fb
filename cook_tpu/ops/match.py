"""Batched jobs x offers bin-packing assignment kernels.

Replaces the reference's Fenzo hot loop (SURVEY.md HOT LOOP #2; reference:
fenzo.scheduleOnce called from scheduler.clj:617-687, default fitness
cpuMemBinPacker per config.clj:108) with two TPU formulations:

* :func:`greedy_match_kernel` — ``lax.scan`` over jobs in rank order; each
  step evaluates the full host axis (feasibility + fitness) as wide vector
  ops and commits one assignment.  Bit-exact parity with the sequential CPU
  fallback (``reference_impl.greedy_match``); the sequential carry is only
  the H x R availability matrix.

* :func:`auction_match_kernel` — refresh passes of "rebuild every unassigned
  job's top-K preferred hosts against current availability, then K rounds of
  propose + per-host prefix-sum admission in rank order".  One refresh is
  O(J*H) fully-parallel work, so XLA tiles it onto the MXU/VPU without a
  J-length dependency chain; placement-count parity with greedy is asserted
  statistically in tests (>=99.9% per BASELINE.md).

* :func:`waterfill_match_kernel` — prefix-packing over hosts sorted
  tightest-first; NO J x H work at all (O(H log H + J log J) per round), the
  mode for very large considerable sets where even one J x H pass is heavy.

Both kernels take a precompiled constraint mask (bool[J, H]) — the host-side
constraint compiler (cook_tpu.sched.constraints) lowers the reference's
constraint zoo (constraints.clj) into it.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from . import scan as scanlib

NEG_INF = -jnp.inf


class MatchInputs(NamedTuple):
    job_res: jax.Array          # f32[J, R] demands in rank order
    constraint_mask: jax.Array  # bool[J, H]
    avail: jax.Array            # f32[H, R] offered (spare) resources
    capacity: jax.Array         # f32[H, R] total capacity (for fitness)
    valid: jax.Array            # bool[J] False for padding


def _fitness(need: jax.Array, avail: jax.Array, capacity: jax.Array) -> jax.Array:
    """cpuMemBinPacker: mean post-assignment utilization of cpus+mem.
    Higher is better (pack tight, leave big holes elsewhere)."""
    used = capacity - avail
    cap = jnp.maximum(capacity, 1e-9)
    f_cpu = (used[:, 0] + need[0]) / cap[:, 0]
    f_mem = (used[:, 1] + need[1]) / cap[:, 1]
    return (f_cpu + f_mem) * 0.5


def greedy_assign(job_res, constraint_mask, valid, avail, capacity):
    """Pure greedy-scan math (jit/vmap-composable); single source of truth
    shared by :func:`greedy_match_kernel` and the pool-sharded cycle.
    Returns (assign i32[J], remaining avail f32[H, R])."""

    def step(avail, xs):
        need, cmask, ok = xs
        feasible = jnp.all(avail >= need[None, :], axis=1) & cmask & ok
        fitness = jnp.where(feasible, _fitness(need, avail, capacity), NEG_INF)
        host = jnp.argmax(fitness)  # ties -> lowest index, as in the fallback
        found = feasible[host]
        onehot = (jnp.arange(avail.shape[0]) == host)[:, None]
        avail = avail - jnp.where(found, need[None, :] * onehot, 0.0)
        return avail, jnp.where(found, host, -1).astype(jnp.int32)

    avail, assign = jax.lax.scan(step, avail, (job_res, constraint_mask, valid))
    return assign, avail


@jax.jit
def greedy_match_kernel(inp: MatchInputs) -> Tuple[jax.Array, jax.Array]:
    """Sequential-greedy assignment, one job per scan step.

    Returns (assign i32[J] host index or -1, remaining avail f32[H, R]).
    """
    return greedy_assign(inp.job_res, inp.constraint_mask, inp.valid,
                         inp.avail, inp.capacity)


def _prefix_admit(proposes: jax.Array, cand: jax.Array, job_res: jax.Array,
                  avail: jax.Array, rank: jax.Array, H: int
                  ) -> Tuple[jax.Array, jax.Array]:
    """Per-host rank-order prefix admission, shared by the auction rounds,
    the waterfill rounds, and waterfill compaction.

    Proposals are grouped per candidate host (one lexsort); within a host,
    jobs are admitted in rank order while the cumulative demand prefix
    fits the host's CURRENT availability.  Returns (admitted bool[J],
    consumed f32[H, R])."""
    J = proposes.shape[0]
    choice = jnp.where(proposes, cand, H)
    order = jnp.lexsort((rank, choice))
    sorted_choice = choice[order]
    sorted_res = job_res[order] * (sorted_choice < H)[:, None]
    first = jnp.concatenate(
        [jnp.ones((1,), dtype=bool),
         sorted_choice[1:] != sorted_choice[:-1]])
    seg_cum = scanlib.segmented_cumsum(sorted_res, first)
    host_avail = avail[jnp.minimum(sorted_choice, H - 1)]
    fits_prefix = (jnp.all(seg_cum <= host_avail, axis=1)
                   & (sorted_choice < H))
    admitted = jnp.zeros((J,), dtype=bool).at[order].set(fits_prefix)
    consumed = jax.ops.segment_sum(
        job_res * admitted[:, None], jnp.minimum(choice, H - 1),
        num_segments=H)
    return admitted, consumed


def _build_prefs(inp: MatchInputs, assign: jax.Array, avail: jax.Array,
                 K: int) -> Tuple[jax.Array, jax.Array]:
    """Top-K hosts per unassigned job by bin-packing fitness against the
    CURRENT availability (one J x H pass, MXU/VPU-friendly).

    Equal fitness scores are broken by a DETERMINISTIC per-(job, host)
    tie-break: on a perfectly uniform fleet every host ties, and without
    it all jobs rank the same K hosts (the herding caveat,
    docs/PLACEMENT_QUALITY.md) — each refresh pass then admits only ~K
    jobs.  The ranking key is INTEGER-packed (fitness quantized to 22
    bits, 8 per-(job, host) hash bits below it) rather than a float
    epsilon: an additive float32 jitter small enough to sit below real
    fitness differences falls below one ulp once fit >= 0.5 and
    collapses to a handful of values, silently resurrecting the herd.
    The 2^-22 fitness quantization (~2.4e-7 of the [0, 1] score) is far
    below any meaningful tightness difference (host resource
    granularity puts those at ~1e-4)."""
    J, H = inp.constraint_mask.shape
    feasible = (jnp.all(avail[None, :, :] >= inp.job_res[:, None, :], axis=2)
                & inp.constraint_mask & inp.valid[:, None]
                & (assign < 0)[:, None])
    used = inp.capacity - avail
    cap = jnp.maximum(inp.capacity, 1e-9)
    fit = (used[None, :, 0] + inp.job_res[:, 0:1]) / cap[None, :, 0] \
        + (used[None, :, 1] + inp.job_res[:, 1:2]) / cap[None, :, 1]
    jj = jnp.arange(J, dtype=jnp.uint32)[:, None]
    hh = jnp.arange(H, dtype=jnp.uint32)[None, :]
    mix = (jj * jnp.uint32(2654435761)) ^ (hh * jnp.uint32(0x9E3779B9))
    q = (jnp.clip(fit * 0.5, 0.0, 1.0)
         * jnp.float32(1 << 22)).astype(jnp.int32) << 8
    key_int = q | (mix & jnp.uint32(0xFF)).astype(jnp.int32)
    # bitcast, don't convert: float32 can only represent 24 bits of the
    # 30-bit key, so astype would drop exactly the jitter bits — but for
    # POSITIVE floats the IEEE bit-pattern order equals the value order,
    # so the bitcast view preserves the full integer ranking while
    # keeping top_k on the fast float path (int top_k measured ~80x
    # slower in XLA CPU).
    key = jnp.where(feasible,
                    jax.lax.bitcast_convert_type(key_int, jnp.float32),
                    NEG_INF)
    return jax.lax.top_k(key, K)                       # [J, K] each


@functools.partial(jax.jit,
                   static_argnames=("num_prefs", "num_rounds",
                                    "num_refresh", "min_refresh_gain"))
def auction_match_kernel(inp: MatchInputs, *, num_prefs: int = 16,
                         num_rounds: int = 8, num_refresh: int = 64,
                         min_refresh_gain: int = 16
                         ) -> Tuple[jax.Array, jax.Array]:
    """Parallel top-K auction assignment for large J.

    Up to ``num_refresh`` outer passes; each rebuilds every unassigned
    job's ``num_prefs`` best hosts against the *current* availability,
    then runs ``num_rounds`` rounds of:

      1. every unassigned job proposes to its current preference;
      2. proposals are grouped per host (one lexsort) and admitted in rank
         order while the cumulative demand prefix fits the host's
         availability;
      3. jobs whose preferred host can no longer fit them *individually*
         advance their preference pointer (availability only shrinks within a
         cycle, so advancing is safe); contended-but-feasible jobs retry.

    The refresh pass is what makes the kernel converge under bin-packing
    fitness: all jobs rank the same tightest hosts, so a single static
    preference list herds onto (and exhausts) K hosts; rebuilding against
    post-admission availability moves the herd to the next-tightest hosts
    exactly the way the sequential greedy's evolving fitness does.

    The refresh loop is ADAPTIVE (a ``lax.while_loop``): it exits once a
    full pass admits fewer than ``min_refresh_gain`` new jobs — a fixed
    small budget under-places exactly when the workload is hardest
    (placement grows per pass under contention, docs/PLACEMENT_QUALITY),
    while a strict no-progress exit would crawl through tail placements
    one pass at a time now that the tie-break keeps every pass finding a
    few; the production path's waterfill tail places those leftovers at
    no J x H cost.
    Placement decisions can still deviate from greedy (tests bound them
    statistically); the greedy kernel remains the bit-exact parity mode.
    """
    J, H = inp.constraint_mask.shape
    K = min(num_prefs, H)

    def placed(assign):
        return jnp.sum(assign >= 0)

    def cond(state):
        assign, _avail, prev_placed, passes = state
        # the (passes == 0) term is what guarantees the first pass runs:
        # the -1 sentinel alone yields gain=1, below min_refresh_gain.
        # min_refresh_gain: with the r5 per-job tie-break, a contended
        # pass almost always admits SOMETHING, so an exact no-progress
        # exit would burn the whole num_refresh budget crawling through
        # tail placements the production waterfill tail covers anyway —
        # stop once a full pass stops paying for its J x H rebuild.
        gain = placed(assign) - prev_placed
        return ((passes == 0) | (gain >= min_refresh_gain)) \
            & (passes < num_refresh)

    def body(state):
        assign, avail, _prev, passes = state
        before = placed(assign)
        pref_fit, pref_host = _build_prefs(inp, assign, avail, K)
        assign, avail = _auction_rounds(inp, pref_fit, pref_host, num_rounds,
                                        assign=assign, avail=avail)
        return (assign, avail, before, passes + 1)

    init = (jnp.full((J,), -1, dtype=jnp.int32), inp.avail,
            jnp.int32(-1), jnp.int32(0))
    assign, avail, _, _ = jax.lax.while_loop(cond, body, init)
    return assign, avail


def _auction_rounds(inp: MatchInputs, pref_fit: jax.Array,
                    pref_host: jax.Array, num_rounds: int,
                    assign: jax.Array, avail: jax.Array
                    ) -> Tuple[jax.Array, jax.Array]:
    J, H = inp.constraint_mask.shape
    job_idx = jnp.arange(J, dtype=jnp.int32)
    K = pref_host.shape[1]
    pref_ok = pref_fit > NEG_INF

    def one_round(state, _):
        assign, avail, ptr = state
        active = (assign < 0) & inp.valid & (ptr < K)
        safe_ptr = jnp.minimum(ptr, K - 1)
        cand = jnp.take_along_axis(pref_host, safe_ptr[:, None], axis=1)[:, 0]
        cand_ok = jnp.take_along_axis(pref_ok, safe_ptr[:, None], axis=1)[:, 0]
        fits_alone = jnp.all(avail[cand] >= inp.job_res, axis=1) & cand_ok
        proposes = active & fits_alone
        # a host that can't fit the job individually never will again
        ptr = jnp.where(active & ~fits_alone, ptr + 1, ptr)

        admitted, consumed = _prefix_admit(proposes, cand, inp.job_res,
                                           avail, job_idx, H)
        assign = jnp.where(admitted, cand, assign)
        avail = avail - consumed
        return (assign, avail, ptr), None

    init = (assign, avail, jnp.zeros((J,), dtype=jnp.int32))
    (assign, avail, _), _ = jax.lax.scan(one_round, init, None,
                                         length=num_rounds)
    return assign, avail


@functools.partial(jax.jit,
                   static_argnames=("num_rounds", "num_compaction"))
def waterfill_match_kernel(inp: MatchInputs, *, num_rounds: int = 32,
                           num_compaction: int = 16
                           ) -> Tuple[jax.Array, jax.Array]:
    """Prefix-packing ("waterfill") assignment: the large-J kernel.

    The sequential greedy under bin-packing fitness fills hosts one at a
    time in tightness order — job j lands roughly where its cumulative
    demand prefix falls across the cumulative spare capacity of hosts
    sorted tightest-first.  This kernel computes that correspondence
    directly each round:

      1. sort hosts by current utilization (tightest first) -> sigma;
      2. cum_cap = cumsum(avail[sigma]); cum_dem = cumsum over still-active
         jobs in rank order; job j proposes to sigma[k_j] with
         k_j = max over resources of searchsorted(cum_cap_r, cum_dem_jr)
         (the binding resource decides), plus a per-job skip offset that
         advances past hosts rejected by the constraint mask or an
         individual-fit check;
      3. per-host prefix admission in rank order (as in the auction
         kernel); losers retry next round against updated availability.

    One round is O(H log H + J log J) with NO J x H work at all, and jobs
    spread across *many* hosts per round (the auction/greedy formulations
    admit ~one host's worth per sequential step).  Decisions deviate from
    greedy only at host boundaries and constraint holes; tests bound the
    deviation statistically, and the greedy kernel remains the bit-exact
    mode.  Reference being replaced: the Fenzo scheduleOnce loop,
    scheduler.clj:617-687.

    Constraint-mask scope: the mask is a SAFETY guarantee (a masked host is
    never assigned — admission checks it), not a completeness one.  The
    exponential probe can step over a sparse row's few allowed hosts, so a
    job restricted to specific hosts may go unplaced even with capacity
    free.  The production ``auto`` backend therefore routes sparse-mask
    jobs to the exact greedy scan and only bulk dense-mask jobs here
    (sched/matcher.py).
    """
    J, H = inp.constraint_mask.shape
    R = inp.job_res.shape[1]
    rank = jnp.arange(J, dtype=jnp.int32)
    cap = jnp.maximum(inp.capacity, 1e-9)

    def one_round(state):
        assign, avail, skip, rnd, _changed = state
        skip_before = skip
        active = (assign < 0) & inp.valid & (skip < H)
        util = ((cap[:, 0] - avail[:, 0]) / cap[:, 0]
                + (cap[:, 1] - avail[:, 1]) / cap[:, 1]) * 0.5
        sigma = jnp.argsort(-util)                     # tightest first
        cum_cap = jnp.cumsum(avail[sigma], axis=0)     # [H, R]
        dem = jnp.where(active[:, None], inp.job_res, 0.0)
        cum_dem = jnp.cumsum(dem, axis=0)              # [J, R]
        k = jnp.zeros((J,), dtype=jnp.int32)
        for r in range(R):                             # R is static (4)
            k = jnp.maximum(k, jnp.searchsorted(
                cum_cap[:, r], cum_dem[:, r], side="left").astype(jnp.int32))
        k = jnp.clip(k + skip, 0, H - 1)
        cand = sigma[k]
        fits = (jnp.all(avail[cand] >= inp.job_res, axis=1)
                & inp.constraint_mask[rank, cand])
        proposes = active & fits
        # exponential probe on rejection: hosts later in sigma are emptier
        # and more likely to fit, and a +1 crawl converges one host per
        # round; doubling reaches a fitting host in O(log H) rounds
        skip = jnp.where(active & ~fits, skip * 2 + 1, skip)
        # a successful admission resets the probe for the next proposal
        skip = jnp.where(proposes, 0, skip)

        admitted, consumed = _prefix_admit(proposes, cand, inp.job_res,
                                           avail, rank, H)
        assign = jnp.where(admitted, cand, assign)
        avail = avail - consumed
        # fixed point: nothing admitted and no probe advanced means every
        # later round would recompute the identical state — stop paying
        # for it (exact-result-preserving early exit)
        changed = admitted.any() | (skip != skip_before).any()
        return assign, avail, skip, rnd + 1, changed

    init = (jnp.full((J,), -1, dtype=jnp.int32), inp.avail,
            jnp.zeros((J,), dtype=jnp.int32), jnp.int32(0),
            jnp.bool_(True))
    assign, avail, _, _, _ = jax.lax.while_loop(
        lambda s: (s[3] < num_rounds) & s[4], one_round, init)

    # ---- compaction: tightness-improving migrations -------------------
    # The prefix mapping spreads jobs across many hosts per round, which
    # is what makes the kernel fast but also what packs ~19% looser than
    # greedy (docs/PLACEMENT_QUALITY.md).  Each compaction round lets
    # jobs sitting on looser-than-average hosts re-propose — via the same
    # O(H log H + J log J) prefix machinery, no J x H work — onto
    # hosts tighter (pre-round) than their own, moving only when admitted
    # there.  A move frees the old host and consumes the new one
    # atomically per round; a job that isn't admitted stays where it
    # was, so placements are never lost and capacity is never
    # oversubscribed.  Tightness improves in aggregate (measured
    # 0.783 -> 0.822 mean util at 10k x 50k); rounds are bounded and
    # exit early when no move lands.
    def compact_round(state):
        assign, avail, rnd, _changed = state
        placed = assign >= 0
        util = ((cap[:, 0] - avail[:, 0]) / cap[:, 0]
                + (cap[:, 1] - avail[:, 1]) / cap[:, 1]) * 0.5
        job_host = jnp.maximum(assign, 0)
        job_util = util[job_host]
        holds = jnp.zeros((H,), dtype=bool).at[job_host].max(placed)
        n_used = jnp.maximum(jnp.sum(holds), 1)
        mean_used_util = jnp.sum(jnp.where(holds, util, 0.0)) / n_used
        movers = placed & (job_util < mean_used_util)

        sigma = jnp.argsort(-util)                    # tightest first
        cum_cap = jnp.cumsum(avail[sigma], axis=0)
        dem = jnp.where(movers[:, None], inp.job_res, 0.0)
        cum_dem = jnp.cumsum(dem, axis=0)
        k = jnp.zeros((J,), dtype=jnp.int32)
        for r in range(R):
            k = jnp.maximum(k, jnp.searchsorted(
                cum_cap[:, r], cum_dem[:, r],
                side="left").astype(jnp.int32))
        cand = sigma[jnp.clip(k, 0, H - 1)]
        # tightness gate against PRE-round utilization: within-round
        # interactions (another mover draining the destination) can
        # occasionally make an individual move non-improving, so
        # tightness is an aggregate tendency, not a per-move invariant —
        # the HARD invariants are that no placement is ever lost (a job
        # not admitted stays put) and no host is ever oversubscribed
        # (admission checks current avail; frees apply after).
        # Termination is the round bound plus the no-move exit.
        fits = (jnp.all(avail[cand] >= inp.job_res, axis=1)
                & inp.constraint_mask[rank, cand]
                & (util[cand] > job_util + 1e-6)
                & (cand != assign))
        proposes = movers & fits

        moved, consumed = _prefix_admit(proposes, cand, inp.job_res,
                                        avail, rank, H)
        freed = jax.ops.segment_sum(
            inp.job_res * moved[:, None], job_host, num_segments=H)
        avail = avail + freed - consumed
        assign = jnp.where(moved, cand, assign)
        return assign, avail, rnd + 1, moved.any()

    assign, avail, _, _ = jax.lax.while_loop(
        lambda s: (s[2] < num_compaction) & s[3], compact_round,
        (assign, avail, jnp.int32(0), jnp.bool_(True)))
    return assign, avail


# Per-kernel recompile telemetry (ops/telemetry.py): a shape change or new
# static-arg combination shows up as cook_jit_compile_total{kernel=...} and
# a tag on the owning cycle's flight record instead of a silent p99 blip.
from . import telemetry as _telemetry  # noqa: E402

greedy_match_kernel = _telemetry.instrument_jit(
    "match.greedy", greedy_match_kernel)
auction_match_kernel = _telemetry.instrument_jit(
    "match.auction", auction_match_kernel)
waterfill_match_kernel = _telemetry.instrument_jit(
    "match.waterfill", waterfill_match_kernel)

# Backwards-compatible alias; the auction formulation superseded the naive
# every-job-argmax multipass, which converged one host per pass.
multipass_match_kernel = auction_match_kernel
