"""The plain reference: one pool's scheduling decisions for one cycle.

Straight numpy, float32 where the configuration states float32, written
from the upstream semantics and importing nothing of the program:

* per-user task order (-priority, running before pending, submit time,
  uuid) and DRU = max(cum_mem / share_mem, cum_cpus / share_cpus) over the
  user's cumulative usage in that order (Cook dru.clj:43-126,
  tools.clj:614-632); users in name order; ties in DRU broken by (user,
  position);
* at most ``max_over_quota`` tasks past a user's quota stay ranked
  (scheduler.clj:2057-2071);
* considerable jobs: walking the ranked queue, a job is admitted while
  its user's running usage plus the usage of that user's jobs ahead of
  it (admitted or not) stays inside the user's quota; a job withheld by
  the pipeline (``excluded``: about to be launched by the cycle in
  flight) keeps its place in every sum and is not admitted; at most
  ``cap`` admitted jobs (fenzo-max-jobs-considered, scheduler.clj:1615);
* match: admitted jobs one at a time, in rank order, each to the feasible
  host with the highest cpu/mem bin-packing fitness, ties to the lowest
  host index (Fenzo scheduleOnce with cpuMemBinPacker).

``variant="broken_fair_share"`` is the control: the same walk with every
user's share equal and no quota, i.e. with the guarantee "admission in DRU
order under the configured shares and quotas" broken.
"""

from __future__ import annotations

from typing import NamedTuple

import numpy as np

F32 = np.float32
PENDING, RUNNING, EXCLUDED = 0, 1, 2


class Tasks(NamedTuple):
    """One pool's live tasks at the moment a cycle was staged."""

    user: np.ndarray       # i64[n] rank of the user's name among all users
    priority: np.ndarray   # i64[n]
    submit_ms: np.ndarray  # i64[n]
    uuid_rank: np.ndarray  # i64[n] rank of the uuid in string order
    cpus: np.ndarray       # f64[n]
    mem: np.ndarray        # f64[n]
    state: np.ndarray      # u8[n] PENDING | RUNNING | EXCLUDED


def _segment_cumsum(x: np.ndarray, first: np.ndarray) -> np.ndarray:
    """Inclusive prefix sums restarting where ``first`` is set.  The sums
    are of whole numbers below 2**53, so float64 carries them exactly."""
    c = np.cumsum(x, axis=0, dtype=np.float64)
    start = np.flatnonzero(first)
    seg = np.cumsum(first) - 1
    base = np.zeros((len(start),) + c.shape[1:], dtype=np.float64)
    base[1:] = c[start[1:] - 1]
    return c - base[seg]


def cycle_decisions(tasks: Tasks, shares: np.ndarray, quota: np.ndarray,
                    avail: np.ndarray, capacity: np.ndarray, cap: int,
                    max_over_quota: int = 100, variant: str = "",
                    detail: dict = None):
    """(rows i64[k], hosts i64[k]): the admitted jobs in admission order
    as indices into ``tasks``, and the host index each got (-1 = none).

    ``shares`` f64[U, 2] (cpus, mem) and ``quota`` f64[U, 4] (cpus, mem,
    gpus, count) by user rank; ``avail``/``capacity`` f64[H, 4].  With
    ``detail`` a dict, each task's DRU and rank position (-1 = not ranked)
    are left in it, for a diagnosis."""
    n = len(tasks.user)
    if variant == "broken_fair_share":
        shares = np.broadcast_to(shares.min(axis=0), shares.shape)
        quota = np.full_like(quota, np.inf)
    elif variant:
        raise ValueError(f"unknown reference variant {variant!r}")
    if n == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    pending = tasks.state != RUNNING
    order = np.lexsort((tasks.uuid_rank, tasks.submit_ms, pending,
                        -tasks.priority, tasks.user))
    user = tasks.user[order]
    pend = pending[order]
    usage = np.stack([tasks.cpus[order], tasks.mem[order],
                      np.zeros(n), np.ones(n)], axis=1)
    first = np.ones(n, dtype=bool)
    first[1:] = user[1:] != user[:-1]

    cum = _segment_cumsum(usage, first)
    over = np.any(cum.astype(F32) > quota[user].astype(F32), axis=1)
    keep = _segment_cumsum(over.astype(np.float64), first) <= max_over_quota
    sh = shares[user].astype(F32)
    c32 = cum.astype(F32)
    dru = np.maximum(c32[:, 1] / sh[:, 1], c32[:, 0] / sh[:, 0])

    rankable = keep & pend
    pos = np.flatnonzero(rankable)
    ranked = pos[np.lexsort((pos, user[pos], dru[pos]))]

    if detail is not None:
        detail["dru"] = np.empty(n, dtype=F32)
        detail["dru"][order] = dru
        detail["rank"] = np.full(n, -1, dtype=np.int64)
        detail["rank"][order[ranked]] = np.arange(len(ranked))

    # the considerable walk, in rank order
    r_user = user[ranked]
    by_user = np.argsort(r_user, kind="stable")
    u_sorted = r_user[by_user]
    u_first = np.ones(len(ranked), dtype=bool)
    u_first[1:] = u_sorted[1:] != u_sorted[:-1]
    cum_user = np.empty((len(ranked), 4), dtype=np.float64)
    cum_user[by_user] = _segment_cumsum(usage[ranked][by_user], u_first)
    run_base = np.zeros((len(shares), 4), dtype=np.float64)
    np.add.at(run_base, user[~pend], usage[~pend])
    quota_ok = np.all((cum_user + run_base[r_user]).astype(F32)
                      <= quota[r_user].astype(F32), axis=1)
    accepted = quota_ok & (tasks.state[order][ranked] != EXCLUDED)
    admitted = ranked[accepted & (np.cumsum(accepted) <= cap)]

    # greedy bin-packing match
    av = np.asarray(avail, dtype=F32).copy()
    capf = np.asarray(capacity, dtype=F32)
    capd = np.maximum(capf, F32(1e-9))
    hosts = np.full(len(admitted), -1, dtype=np.int64)
    need_all = np.concatenate([usage[admitted][:, :3],
                               np.zeros((len(admitted), 1))],
                              axis=1).astype(F32)
    half = F32(0.5)
    for j in range(len(admitted)):
        need = need_all[j]
        feasible = np.all(av >= need, axis=1)
        if not feasible.any():
            continue
        used = capf - av
        fit = ((used[:, 0] + need[0]) / capd[:, 0]
               + (used[:, 1] + need[1]) / capd[:, 1]) * half
        h = int(np.argmax(np.where(feasible, fit, F32(-np.inf))))
        hosts[j] = h
        av[h] -= need
    return order[admitted], hosts
