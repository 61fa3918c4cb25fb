"""What decides ``correct``: the window's own cycles, one by one, against
the plain reference, and the guarantees the configuration states.

For every cycle staged inside the window the reference (reference.py) is
given the state that cycle was staged from and its decisions are compared
with what that cycle launched:

* which jobs were pending or running comes from the benchmark's own job
  table (world.py, traffic.py) and from the launches of the cycles applied
  before this one was staged, in capture order;
* whether an arrival was visible does not depend on a clock race: a
  request ACKed before the stage call began is visible, one sent after it
  returned is not, and only one in flight during the call takes the
  cycle's own word (its packed uuid column);
* the jobs withheld from the cycle (candidates of cycles fetched but not
  yet applied, depth 2) and the host capacity they will consume are
  worked out here from those cycles' fetched outputs.

Numbers compared, each with its limit (value <= limit passes):
``set_gap`` / ``host_gap``: the worst cycle-and-pool disagreement of the
launched set and of job->host; ``readback_gap``: launched arrivals whose
instance, read back over REST, sits on another host; ``lost_acked``,
``double_run``, ``overcommit``, ``never_placed``, ``fallbacks``,
``off_path_cycles``, ``uncompared``: counts that must be 0.

What this file reads of the program beyond the public results of the four
wrapped calls (README.md lists them as the interface the yardstick
depends on): a packed pool's ``uuid_base``, ``rows_s``, ``offers`` and
``pool.name``; a dispatched group's ``fetched[0]`` (candidate rows) and
``fetched[1]`` (candidate hosts); a pool result's ``launched_job_uuids``
and ``matched``.
"""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

import reference
import world as worldlib

#: BASELINE.md: ">= 99.9 % placement parity against the CPU path"
PARITY_LIMIT = 0.001
LIMITS = {"set_gap": PARITY_LIMIT, "host_gap": PARITY_LIMIT,
          "readback_gap": 0.0, "lost_acked": 0, "double_run": 0,
          "overcommit": 0, "never_placed": 0, "fallbacks": 0,
          "off_path_cycles": 0, "uncompared": 0}


class Jobs:
    """Every job of the run, backlog first, then the window's arrivals."""

    def __init__(self, config: Dict, backlog, backlog_submit_ms, schedule,
                 sent, readback: Dict[str, Dict]):
        w = config["world"]
        self.world = w
        self.pools = list(w["pools"])
        arr = [(j, r, i) for i, r in enumerate(schedule) for j in r["jobs"]]
        self.n_backlog = nb = len(backlog.uuid)
        self.uuid = np.concatenate(
            [backlog.uuid, np.array([j["uuid"] for j, _r, _i in arr],
                                    dtype="U36")])
        users = sorted(set(backlog.user.tolist())
                       | {r["user"] for r in schedule}
                       | set(worldlib.light_user_names(int(w["light_users"]))))
        self.users = users
        urank = {u: k for k, u in enumerate(users)}
        self.user = np.array(
            [urank[u] for u in backlog.user.tolist()]
            + [urank[r["user"]] for _j, r, _i in arr], dtype=np.int64)
        prank = {p: k for k, p in enumerate(self.pools)}
        self.pool = np.array(
            [prank[p] for p in backlog.pool.tolist()]
            + [prank[r["pool"]] for _j, r, _i in arr], dtype=np.int64)
        self.cpus = np.concatenate(
            [backlog.cpus, [j["cpus"] for j, _r, _i in arr]]).astype(float)
        self.mem = np.concatenate(
            [backlog.mem, [j["mem"] for j, _r, _i in arr]]).astype(float)
        self.priority = np.concatenate(
            [backlog.priority, [j["priority"] for j, _r, _i in arr]]
        ).astype(np.int64)
        # an arrival's submit time is the server's stamp, read back
        sub = [int((readback.get(j["uuid"]) or {}).get("submit_time") or 0)
               for j, _r, _i in arr]
        self.submit_ms = np.concatenate(
            [backlog_submit_ms, np.array(sub, dtype=np.int64)])
        self.start_ms = np.concatenate([np.zeros(nb), [float(
            (((readback.get(j["uuid"]) or {}).get("instances") or [{}])[0]
             ).get("start_time") or 0) for j, _r, _i in arr]])
        self.t_due = np.array([(s or {}).get("due", np.inf) for s in sent])
        self.request = np.concatenate(
            [np.full(nb, -1), [i for _j, _r, i in arr]]).astype(np.int64)
        self.t_sent = np.array([(s or {}).get("sent", np.inf) for s in sent])
        self.t_ack = np.array([(s or {}).get("ack", np.inf)
                               if (s or {}).get("ok") else np.inf
                               for s in sent])
        self.uuid_rank = np.empty(len(self.uuid), dtype=np.int64)
        self.uuid_rank[np.argsort(self.uuid)] = np.arange(len(self.uuid))
        self.index = {u: k for k, u in enumerate(self.uuid.tolist())}
        self.by_pool = [np.flatnonzero(self.pool == k)
                        for k in range(len(self.pools))]
        self.shares = np.array(
            [[worldlib.user_share(u, w)["cpus"],
              worldlib.user_share(u, w)["mem"]] for u in users])
        self.host_index = [{h: k for k, h in enumerate(
            worldlib.host_names(w, p))} for p in range(len(self.pools))]
        self.capacity = np.tile(
            np.array([float(w["host_cpus"]), float(w["host_mem"]), 0.0, 0.0]),
            (int(w["hosts_per_pool"]), 1))

    def quota(self, mix: Dict) -> np.ndarray:
        q = np.full((len(self.users), 4), np.inf)
        bq = mix.get("backlog_quota")
        if bq:
            for k, u in enumerate(self.users):
                if u.startswith("user"):
                    q[k, 3] = float(bq["count"])
        return q


def _program_candidates(jobs: Jobs, cyc: Dict) -> Dict[int, tuple]:
    """pool index -> (job idx, host idx) of a fetched cycle's candidates
    that hold a host, in slot order."""
    out = {}
    for group, fetched in cyc["fetched"]:
        cand_row, cand_assign = fetched[0], fetched[1]
        for i, pp in enumerate(group):
            p = jobs.pools.index(pp.pool.name)
            sel = np.flatnonzero((cand_row[i] >= 0) & (cand_assign[i] >= 0)
                                 & (cand_assign[i] < len(pp.offers)))
            uuids = pp.uuid_base[pp.rows_s[cand_row[i][sel]]]
            idx = np.array([jobs.index[str(u)] for u in uuids],
                           dtype=np.int64)
            hosts = np.array(
                [jobs.host_index[p][pp.offers[int(h)].hostname]
                 for h in cand_assign[i][sel]], dtype=np.int64)
            out[p] = (idx, hosts)
    return out


def _program_launched(jobs: Jobs, cyc: Dict) -> Dict[int, Dict[int, int]]:
    """pool index -> {job idx: host idx} of what the cycle launched."""
    out = {}
    for pool_name, res in cyc["applied"].items():
        p = jobs.pools.index(pool_name)
        launched = set(res.launched_job_uuids)
        out[p] = {jobs.index[job.uuid]: jobs.host_index[p][offer.hostname]
                  for job, offer in res.matched if job.uuid in launched}
    return out


def _visible(jobs: Jobs, cyc: Dict, pool: int, rows: np.ndarray
             ) -> np.ndarray:
    """Which of the arrival ``rows`` this cycle's stage could see."""
    t0, t1 = cyc["t_stage"]
    req = jobs.request[rows]
    seen = jobs.t_ack[req] <= t0
    racing = ~seen & (jobs.t_sent[req] < t1)
    if racing.any():
        for group in cyc["groups"]:
            for pp in group:
                if pp.pool.name == jobs.pools[pool] and pp.rows_s is not None:
                    packed = set(pp.uuid_base[pp.rows_s].tolist())
                    seen[racing] = [u in packed for u in
                                    jobs.uuid[rows[racing]].tolist()]
    return seen


def _gaps(ref: Dict[int, int], got: Dict[int, int]):
    union = set(ref) | set(got)
    both = set(ref) & set(got)
    set_gap = (len(union) - len(both)) / max(len(union), 1)
    host_gap = sum(1 for j in both if ref[j] != got[j]) / max(len(both), 1)
    return set_gap, host_gap


def replay(jobs: Jobs, mix: Dict, capture, window, cap: int,
           control: bool = False, log=None) -> Dict:
    """Walk the captured events; compare each window cycle with the
    reference.  With ``control`` the broken reference is also put in the
    program's place and read the same way."""
    n = len(jobs.uuid)
    running = np.zeros(n, dtype=bool)
    host_of = np.full(n, -1, dtype=np.int64)
    H = len(jobs.capacity)
    used = np.zeros((len(jobs.pools), H, 4))
    quota = jobs.quota(mix)
    in_flight: Dict[int, Dict[int, tuple]] = {}
    launches_seen = np.zeros(n, dtype=np.int64)
    pending_compare: List[Dict] = []
    worst = {"set_gap": 0.0, "host_gap": 0.0}
    worst_control = {"set_gap": 0.0, "host_gap": 0.0}
    compared = 0
    res4 = lambda idx: np.stack(
        [jobs.cpus[idx], jobs.mem[idx], np.zeros(len(idx)),
         np.zeros(len(idx))], axis=1)

    for kind, cyc in capture.events:
        if kind == "fetch":
            in_flight[cyc["id"]] = _program_candidates(jobs, cyc)
        elif kind == "apply":
            in_flight.pop(cyc["id"], None)
            got = _program_launched(jobs, cyc)
            for p, placed in got.items():
                idx = np.fromiter(placed.keys(), dtype=np.int64,
                                  count=len(placed))
                hosts = np.fromiter(placed.values(), dtype=np.int64,
                                    count=len(placed))
                running[idx] = True
                host_of[idx] = hosts
                launches_seen[idx] += 1
                np.add.at(used[p], hosts, res4(idx))
            ref = cyc.get("reference")
            if ref is not None:
                for p in sorted(set(ref) | set(got)):
                    s, h = _gaps(ref.get(p, {}), got.get(p, {}))
                    if (s or h) and log:
                        _explain(jobs, cyc, p, ref.get(p, {}),
                                 got.get(p, {}), log)
                    worst["set_gap"] = max(worst["set_gap"], s)
                    worst["host_gap"] = max(worst["host_gap"], h)
                    if control:
                        s, h = _gaps(ref.get(p, {}),
                                     cyc["control"].get(p, {}))
                        worst_control["set_gap"] = max(
                            worst_control["set_gap"], s)
                        worst_control["host_gap"] = max(
                            worst_control["host_gap"], h)
                compared += 1
        elif kind == "stage" and window[0] <= cyc["t_stage"][0] < window[1]:
            cyc["reference"], cyc["control"] = {}, {}
            staged_pools = {pp.pool.name for group in cyc["groups"]
                            for pp in group}
            for p, pool_name in enumerate(jobs.pools):
                if pool_name not in staged_pools:
                    continue
                rows = jobs.by_pool[p]
                arrival = rows[jobs.request[rows] >= 0]
                live = np.ones(len(rows), dtype=bool)
                live[jobs.request[rows] >= 0] = _visible(jobs, cyc, p,
                                                         arrival)
                rows = rows[live]
                state = np.where(running[rows], reference.RUNNING,
                                 reference.PENDING).astype(np.uint8)
                avail = jobs.capacity - used[p]
                for cand in in_flight.values():
                    idx, hosts = cand.get(p, (np.zeros(0, np.int64),) * 2)
                    if len(idx):
                        where = np.searchsorted(rows, idx)
                        state[where] = reference.EXCLUDED
                        np.subtract.at(avail, hosts, res4(idx))
                avail = np.maximum(avail, 0.0)
                tasks = reference.Tasks(
                    user=jobs.user[rows], priority=jobs.priority[rows],
                    submit_ms=jobs.submit_ms[rows],
                    uuid_rank=jobs.uuid_rank[rows], cpus=jobs.cpus[rows],
                    mem=jobs.mem[rows], state=state)
                for key, variant in (("reference", ""),
                                     ("control", "broken_fair_share")):
                    if key == "control" and not control:
                        continue
                    detail = {} if key == "reference" else None
                    picked, hosts = reference.cycle_decisions(
                        tasks, jobs.shares, quota, avail, jobs.capacity,
                        cap, variant=variant, detail=detail)
                    if detail is not None:
                        cyc.setdefault("detail", {})[p] = (
                            rows, detail["dru"], detail["rank"])
                    ok = hosts >= 0
                    cyc[key][p] = dict(zip(rows[picked[ok]].tolist(),
                                           hosts[ok].tolist()))
    if log:
        _lifecycle(jobs, capture, window, log)
    out = dict(worst)
    out["uncompared"] = 0 if compared else 1
    out["cycles_compared"] = compared
    out["double_run"] = int((launches_seen > 1).sum())
    out["_host_of"] = host_of
    if control:
        out["control"] = worst_control
    return out


def _lifecycle(jobs: Jobs, capture, window, log) -> None:
    """How many cycles after the first one that could see it each arrival
    was launched by (0 = by that very cycle), from the reference's own
    decisions; for those that waited, whether the reference admitted them
    in that first cycle at all."""
    staged = [c for c in capture.cycles if "reference" in c]
    launched_in = {}
    for n, cyc in enumerate(staged):
        for placed in cyc["reference"].values():
            for j in placed:
                launched_in.setdefault(j, n)
    waits: Dict[int, int] = {}
    behind = 0
    parts = []
    for k in range(jobs.n_backlog, len(jobs.uuid)):
        r = jobs.request[k]
        first = next((n for n, c in enumerate(staged)
                      if jobs.t_ack[r] <= c["t_stage"][0]), None)
        if first is None or k not in launched_in:
            continue
        d = launched_in[k] - first
        waits[d] = waits.get(d, 0) + 1
        if d > 0:
            # ranked behind the last job that first cycle launched?
            p = int(jobs.pool[k])
            rows, _dru, rank = staged[first].get("detail", {}).get(
                p, (np.zeros(0, np.int64), None, np.zeros(0, np.int64)))
            at = np.searchsorted(rows, k)
            placed = list(staged[first]["reference"].get(p, {}))
            if at < len(rows) and rows[at] == k and placed:
                last = rank[np.searchsorted(rows, placed)].max()
                behind += int(rank[at] > last)
        parts.append((jobs.start_ms[k] / 1000.0 - jobs.t_due[r],
                      jobs.t_ack[r] - jobs.t_due[r],
                      staged[first]["t_stage"][0] - jobs.t_ack[r],
                      jobs.start_ms[k] / 1000.0
                      - staged[first]["t_stage"][0]))
    parts.sort()
    for row in parts[-1:] + parts[len(parts) // 2:len(parts) // 2 + 1]:
        log("slowest and median arrival: ttp %.2fs = due->ack %.2f + "
            "ack->stage %.2f + stage->launch %.2f" % row)
    if parts:
        cols = np.array(parts)
        for i, name in enumerate(("due->ack", "ack->stage",
                                  "stage->launch"), start=1):
            log(f"{name} s p50/p90/p95/p99/max: " + " / ".join(
                f"{np.percentile(cols[:, i], q):.2f}"
                for q in (50, 90, 95, 99, 100)))
    gaps = [b["t_stage"][0] - a["t_stage"][0]
            for a, b in zip(staged, staged[1:])]
    log("stage-to-stage s: " + " ".join(f"{g:.2f}" for g in gaps))
    log("apply s: " + " ".join(
        f"{c['t_apply'][1] - c['t_apply'][0]:.2f}" for c in staged
        if c.get("t_apply")))
    log(f"arrivals by cycles waited past the first that saw them: "
        f"{dict(sorted(waits.items()))}; of those that waited, {behind} "
        "ranked behind the last job that first cycle launched")


def _explain(jobs: Jobs, cyc: Dict, p: int, ref: Dict[int, int],
             got: Dict[int, int], log) -> None:
    """The first differing job of a pool, with both sides' answers."""
    only_ref = sorted(set(ref) - set(got))
    only_got = sorted(set(got) - set(ref))
    moved = sorted(j for j in set(ref) & set(got) if ref[j] != got[j])
    log(f"cycle {cyc['id']} pool {jobs.pools[p]}: reference launched "
        f"{len(ref)}, program {len(got)}; only reference {len(only_ref)}, "
        f"only program {len(only_got)}, other host {len(moved)}")
    rows, dru, rank = cyc.get("detail", {}).get(p, ([], [], []))
    where = {int(j): k for k, j in enumerate(rows)}
    slot = {}
    for group, fetched in cyc["fetched"]:
        for i, pp in enumerate(group):
            if pp.pool.name == jobs.pools[p]:
                sel = np.flatnonzero(fetched[0][i] >= 0)
                for k, u in enumerate(
                        pp.uuid_base[pp.rows_s[fetched[0][i][sel]]]):
                    slot[jobs.index[str(u)]] = (k, int(fetched[1][i][sel[k]]))
    for label, js in (("only reference", only_ref),
                      ("only program", only_got), ("other host", moved)):
        for j in js[:2]:
            k = where.get(j)
            log(f"  {label}: {jobs.uuid[j]} user {jobs.users[jobs.user[j]]} "
                f"prio {jobs.priority[j]} cpus {jobs.cpus[j]:g} mem "
                f"{jobs.mem[j]:g} submit {jobs.submit_ms[j]}; reference: "
                f"dru {None if k is None else float(dru[k])!r} rank "
                f"{None if k is None else int(rank[k])} host {ref.get(j)}; "
                f"program: candidate slot and host {slot.get(j)}, launched "
                f"on {got.get(j)}")


def guarantees(jobs: Jobs, sent, readback: Dict[str, Dict],
               running_list: Optional[List[Dict]], host_of: np.ndarray
               ) -> Dict:
    """The store-side guarantees, from what was read back over REST."""
    lost = never = wrong_host = placed = 0
    for k in range(jobs.n_backlog, len(jobs.uuid)):
        s = sent[jobs.request[k]]
        if not (s and s.get("ok")):
            continue
        doc = readback.get(str(jobs.uuid[k]))
        if doc is None or doc.get("user") != jobs.users[jobs.user[k]] \
                or doc.get("cpus") != jobs.cpus[k] \
                or doc.get("mem") != jobs.mem[k]:
            lost += 1
            continue
        inst = doc.get("instances") or []
        if not inst:
            never += 1
            continue
        placed += 1
        p = jobs.pool[k]
        if host_of[k] >= 0 and jobs.host_index[p].get(
                inst[0].get("hostname")) != host_of[k]:
            wrong_host += 1
    over = doubles = 0
    if running_list is not None:
        per_job: Dict[str, int] = {}
        load: Dict[str, List[float]] = {}
        for inst in running_list:
            u = inst["job_uuid"]
            per_job[u] = per_job.get(u, 0) + 1
            k = jobs.index.get(u)
            if k is None:
                continue
            acc = load.setdefault(inst["hostname"], [0.0, 0.0])
            acc[0] += jobs.cpus[k]
            acc[1] += jobs.mem[k]
        doubles = sum(1 for c in per_job.values() if c > 1)
        cpus, mem = float(jobs.world["host_cpus"]), \
            float(jobs.world["host_mem"])
        over = sum(1 for c, m in load.values() if c > cpus or m > mem)
    return {"lost_acked": lost, "never_placed": never,
            "readback_gap": wrong_host / max(placed, 1),
            "overcommit": over, "double_live": doubles}


def verdict(numbers: Dict) -> tuple:
    """(correct, {name: [value, limit]}) over the compared numbers."""
    table = {k: [numbers[k], LIMITS[k]] for k in LIMITS}
    return all(v <= lim for v, lim in table.values()), table


def control_verdict(numbers: Dict) -> tuple:
    """The same verdict with the control in the program's place: the
    control's decisions, cycle by cycle and pool by pool, are held to the
    limits the program's are held to.  It has to come out not correct."""
    return verdict(dict(numbers, **numbers["control"]))
