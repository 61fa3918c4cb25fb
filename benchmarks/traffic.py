"""The one general open-loop traffic generator.

A traffic mix is a data file ``benchmarks/traffic/<name>.json``; this
module turns its parameters, a seed and a window length into a schedule
of requests.  No JAX and nothing of the program is imported here.

Every seed gets the same work in another order: the inter-arrival gaps
are the quantiles of the exponential distribution (so their histogram is
exactly a Poisson process's), and the request sizes, users, pools and job
sizes are dealt in exact proportions; the seed shuffles each of them over
the whole window.  Bursts and lulls therefore fall where the seed puts
them, at every scale, as in a Poisson open loop; what does not change
from seed to seed is the total: the number of requests and of jobs.

Parameters the generator understands (all under the file's top level):

``requests_per_s``   mean arrival rate of POST /jobs requests
``jobs_per_request`` [lo, hi], dealt evenly
``light_users``      number of open-loop users; each request is one user's
``sizes``            {"cpus", "cpus_p", "mem_per_cpu_mb", "priority"}
``pool``             "uniform" over the configuration's pools, or a name
``backlog_quota``    null, or {"count": n}: a job-count quota set on every
                     backlog user in every pool before the scheduler starts
``settle_cycles``    fused cycles to wait for before the window opens
``settle_requests``  light-user requests sent during the settle phase
``start_after_scheduler_s``  the window opens no sooner than this long
                     after the scheduler's threads started: its 30 s sweeps
                     count from then, so they fall at the same place in
                     every window whatever set-up took
``start_after_cycle_s``  the window opens this long after a cycle ends
``trace_after_s``, ``trace_cycles``  where in the window the traced span
                     starts and how many whole cycles it holds
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from world import dealt, job_sizes, light_user_names, make_uuids


def make_schedule(seed: int, mix: Dict, pools: List[str], seconds: float,
                  stream: int = 2) -> List[Dict]:
    """Requests of one window: ``[{"due": offset_s, "user", "pool",
    "jobs": [{"uuid", "cpus", "mem", "priority"}]}]`` in due order."""
    rng = np.random.default_rng([int(seed), stream])
    rate = float(mix["requests_per_s"])
    n = max(int(round(rate * seconds)), 1)
    lo, hi = mix["jobs_per_request"]
    sizes = dealt(rng, list(range(lo, hi + 1)), [1.0] * (hi - lo + 1),
                  n).astype(np.int64)
    # exponential quantiles, rescaled so that the last request falls
    # inside the window, in the seed's order
    gaps = -np.log(1.0 - (np.arange(n) + 0.5) / n)
    gaps *= seconds / gaps.sum() * (n / (n + 1.0))
    rng.shuffle(gaps)
    due = np.cumsum(gaps)
    users = np.array(light_user_names(int(mix["light_users"])))
    who = dealt(rng, users, [1.0] * len(users), n)
    if mix.get("pool", "uniform") == "uniform":
        where = dealt(rng, np.array(pools), [1.0] * len(pools), n)
    else:
        where = np.full(n, mix["pool"])
    total = int(sizes.sum())
    cpus, mem, prio = job_sizes(rng, mix["sizes"], total)
    uuids = make_uuids(rng, total)
    out, k = [], 0
    for i in range(n):
        m = int(sizes[i])
        out.append({"due": float(due[i]), "user": str(who[i]),
                    "pool": str(where[i]),
                    "jobs": [{"uuid": str(uuids[j]), "cpus": float(cpus[j]),
                              "mem": float(mem[j]),
                              "priority": int(prio[j])}
                             for j in range(k, k + m)]})
        k += m
    return out
