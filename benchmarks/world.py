"""The seeded world of a run: backlog jobs, users, shares, hosts.

Imports nothing of the program and no JAX, so the load generator's child
process and the plain reference can use it.  Every seed gets the same
multiset of job sizes, priorities and per-user backlog counts, dealt in
another order: the work of a run does not depend on the seed.
"""

from __future__ import annotations

from typing import Dict, List, NamedTuple

import numpy as np


class JobTable(NamedTuple):
    """Columns of jobs, one row each (numpy arrays of equal length)."""

    uuid: np.ndarray      # U36
    user: np.ndarray      # U16
    pool: np.ndarray      # U16
    cpus: np.ndarray      # f64
    mem: np.ndarray       # f64
    priority: np.ndarray  # i64


def make_uuids(rng: np.random.Generator, n: int) -> np.ndarray:
    """n canonical lowercase uuids from the generator."""
    h = rng.integers(0, 256, size=(n, 16), dtype=np.uint8).tobytes().hex()
    out = np.empty(n, dtype="U36")
    for i in range(n):
        x = h[32 * i:32 * i + 32]
        out[i] = f"{x[:8]}-{x[8:12]}-{x[12:16]}-{x[16:20]}-{x[20:]}"
    return out


def dealt(rng: np.random.Generator, values, weights, n: int) -> np.ndarray:
    """n values in the proportions ``weights``, exactly (largest remainder),
    shuffled by the generator: the same multiset for every seed."""
    w = np.asarray(weights, dtype=np.float64)
    w = w / w.sum()
    counts = np.floor(w * n).astype(np.int64)
    short = n - int(counts.sum())
    if short:
        counts[np.argsort(-(w * n - counts), kind="stable")[:short]] += 1
    out = np.repeat(np.asarray(values), counts)
    rng.shuffle(out)
    return out


def backlog_user_names(n_users: int) -> List[str]:
    return [f"user{i:03d}" for i in range(n_users)]


def light_user_names(n_users: int) -> List[str]:
    return [f"light{i:03d}" for i in range(n_users)]


def user_share(user: str, world: Dict) -> Dict[str, float]:
    """Every ``double_share_every``-th backlog user holds a double share,
    so fair-share order is not submission order."""
    k = 1.0
    every = int(world.get("double_share_every") or 0)
    if every and user.startswith("user") and int(user[4:]) % every == 0:
        k = 2.0
    return {"cpus": float(world["share_cpus"]) * k,
            "mem": float(world["share_mem"]) * k}


def job_sizes(rng: np.random.Generator, sizes: Dict, n: int):
    """(cpus, mem, priority) columns for n jobs from a ``sizes`` block."""
    cpus = dealt(rng, sizes["cpus"], sizes["cpus_p"], n).astype(np.float64)
    per = sizes["mem_per_cpu_mb"]
    mem = cpus * dealt(rng, per, [1.0] * len(per), n).astype(np.float64)
    lo, hi = sizes["priority"]
    prio = dealt(rng, list(range(lo, hi + 1)), [1.0] * (hi - lo + 1), n)
    return cpus, mem, prio.astype(np.int64)


def make_backlog(seed: int, world: Dict) -> JobTable:
    """The backlog of every pool.  Per-user counts follow Zipf weights
    ``1 / rank ** zipf_s``; which user holds which rank is dealt from the
    seed, per pool."""
    rng = np.random.default_rng([int(seed), 1])
    users = np.array(backlog_user_names(int(world["backlog_users"])))
    n = int(world["jobs_per_pool"])
    ranks = np.arange(1, len(users) + 1, dtype=np.float64)
    weights = ranks ** -float(world.get("zipf_s", 0.0))
    cols = {k: [] for k in ("user", "pool", "cpus", "mem", "priority")}
    for pool in world["pools"]:
        owner = dealt(rng, rng.permutation(users), weights, n)
        cpus, mem, prio = job_sizes(rng, world["sizes"], n)
        cols["user"].append(owner)
        cols["pool"].append(np.full(n, pool))
        cols["cpus"].append(cpus)
        cols["mem"].append(mem)
        cols["priority"].append(prio)
    total = n * len(world["pools"])
    return JobTable(uuid=make_uuids(rng, total),
                    **{k: np.concatenate(v) for k, v in cols.items()})


def cluster_specs(world: Dict) -> List[Dict]:
    """One in-process fake cluster per pool, hosts of its own."""
    return [{"factory": "cook_tpu.cluster.fake.factory",
             "kwargs": {"name": f"fleet{i}", "pool": pool,
                        "n_hosts": int(world["hosts_per_pool"]),
                        "cpus": float(world["host_cpus"]),
                        "mem": float(world["host_mem"]),
                        "default_task_duration_ms":
                            int(world["task_duration_ms"])}}
            for i, pool in enumerate(world["pools"])]


def host_names(world: Dict, pool_index: int) -> List[str]:
    """Host names of one pool in the order the fake cluster offers them."""
    return [f"fleet{pool_index}-h{i}"
            for i in range(int(world["hosts_per_pool"]))]
