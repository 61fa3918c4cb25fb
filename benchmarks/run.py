#!/usr/bin/env python3
"""cook_tpu's benchmark: one cell, one run.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

One run = this process (which IS the server: an in-process CookDaemon with
its REST socket and its own cycle thread, so that it can trace the chip it
holds) plus one child, the load generator, which never imports JAX.  The
run loads the seeded world, warms up, measures for ``--seconds``, checks
the window's own decisions against the plain reference, and prints one
JSON object as the last line of standard output.

Which cells, configurations and per-layer metrics exist is data:
``BENCHMARK.json`` names them, and each has a file of its own under
``benchmarks/`` (README.md).  This file names no cell.
"""

from __future__ import annotations

import time

T_PROCESS = time.time()

import argparse   # noqa: E402
import importlib.util   # noqa: E402
import json   # noqa: E402
import os   # noqa: E402
import shutil   # noqa: E402
import subprocess   # noqa: E402
import sys   # noqa: E402
import tempfile   # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for _p in (HERE, ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)


class BenchFailure(Exception):
    """The run cannot produce a result; exit non-zero, print none."""


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


def load_json(path: str):
    with open(path, encoding="utf-8") as f:
        return json.load(f)


def load_cell(bench_file: str, name: str):
    """(benchmark, cell entry, configuration, traffic mix) by name."""
    bench = load_json(bench_file)
    root = os.path.dirname(os.path.abspath(bench_file))
    cells = {c["name"]: c for c in bench["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in {bench_file}; "
                           f"known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    conf_path = os.path.join(root, conf["file"])
    config = load_json(conf_path)
    # <dir>/configs/<name>.json pairs with <dir>/traffic/<traffic>.json
    mix = load_json(os.path.join(os.path.dirname(os.path.dirname(conf_path)),
                                 "traffic", cell["traffic"] + ".json"))
    return bench, cell, config, mix


def require_chip(chips: int) -> dict:
    """The device as JAX reports it; a BenchFailure unless it is a TPU in
    the peaks table with at least the chips the cell asks for."""
    import jax
    devices = jax.devices()
    dev = {"platform": devices[0].platform, "kind": devices[0].device_kind,
           "count": len(devices)}
    if dev["platform"] != "tpu":
        raise BenchFailure(
            f"JAX reports platform {dev['platform']!r}, not a TPU "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS', '<unset>')}): "
            "a CPU run measures nothing about the chip")
    if dev["count"] < chips:
        raise BenchFailure(f"the cell asks for {chips} chips, JAX has "
                           f"{dev['count']}")
    peaks = load_json(os.path.join(HERE, "peaks.json"))
    if dev["kind"] not in peaks["devices"]:
        raise BenchFailure(f"device kind {dev['kind']!r} is not in "
                           "benchmarks/peaks.json")
    return dev


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def parse_metrics(text: str) -> dict:
    """name -> sum over label sets, from a Prometheus exposition; each
    labelled series is also kept under its full ``name{labels}``."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        head, _, value = line.rpartition(" ")
        name = head.split("{", 1)[0]
        try:
            out[name] = out.get(name, 0.0) + float(value)
            if head != name:
                out[head] = float(value)
        except ValueError:
            pass
    return out


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest-rank rule (no interpolation)."""
    vs = sorted(values)
    if not vs:
        return float("nan")
    k = max(0, min(len(vs) - 1, int(-(-q * len(vs) // 100)) - 1))
    return float(vs[k])


def placements_in(start_times_ms, t0_ms: float, t1_ms: float) -> float:
    """Launches credited to the window [t0, t1).  A launch transaction
    stamps all its instances with one instant, so launches arrive in
    bursts of up to the cap; a plain count would move by a whole burst
    with the phase of the window's ends.  Each burst is therefore spread
    evenly over the interval since the burst before it (the time the
    scheduler took to produce it), and the window is credited with what
    falls inside: the integral over the window of a piecewise-constant
    launch rate, continuous in the window's ends."""
    bursts: dict = {}
    for t in start_times_ms:
        if t:
            bursts[t] = bursts.get(t, 0) + 1
    total, prev = 0.0, None
    for t in sorted(bursts):
        if prev is None:
            inside = 1.0 if t0_ms <= t < t1_ms else 0.0
        else:
            inside = max(0.0, min(t, t1_ms) - max(prev, t0_ms)) / (t - prev)
        total += bursts[t] * inside
        prev = t
    return total


def load_readers(bench: dict):
    """Per-layer metric -> reader function, found by name: the metric's
    file ``layer_metrics/<name>.json`` names its reader module."""
    out = {}
    for m in bench["per_layer"]:
        spec_path = os.path.join(HERE, "layer_metrics", m["name"] + ".json")
        spec = load_json(spec_path)
        path = os.path.join(HERE, "readers", spec["reader"] + ".py")
        modspec = importlib.util.spec_from_file_location(
            "reader_" + spec["reader"], path)
        mod = importlib.util.module_from_spec(modspec)
        modspec.loader.exec_module(mod)
        out[m["name"]] = (mod.read, spec, m)
    return out


def run(args) -> int:
    import check
    import server as serverlib
    import traffic
    import world as worldlib

    bench, cell, config, mix = load_cell(args.bench_file, args.workload)
    if not os.path.isdir(os.path.join(ROOT, "cook_tpu")):
        raise BenchFailure(f"no cook_tpu package beside {HERE}: the "
                           "benchmark drives the repository it ships in")
    device = require_chip(int(cell["chips"]))
    seconds = float(args.seconds)
    w = config["world"]
    workdir = tempfile.mkdtemp(prefix="cook-bench-")
    child = None
    srv = None
    tracing_on = False
    try:
        # ------------------------------------------------------- set-up
        schedule = traffic.make_schedule(args.seed, mix, w["pools"], seconds)
        settle = traffic.make_schedule(
            args.seed, dict(mix, requests_per_s=1.0), w["pools"],
            float(mix.get("settle_requests", 0)), stream=3) \
            if mix.get("settle_requests") else []
        arrivals = sum(len(r["jobs"]) for r in schedule + settle)
        backlog = worldlib.make_backlog(args.seed, w)
        users = worldlib.backlog_user_names(int(w["backlog_users"])) \
            + worldlib.light_user_names(int(w["light_users"]))
        t_world = time.time()
        srv = serverlib.Server(config, os.path.join(workdir, "data"),
                               arrivals)
        srv.open()
        srv.load(backlog, mix.get("backlog_quota"), users)
        log(f"world {t_world - T_PROCESS:.1f}s, store open "
            f"{srv.times['store_open_s']:.1f}s, backlog of "
            f"{len(backlog.uuid)} in {srv.times['backlog_load_s']:.1f}s")
        sched_path = os.path.join(workdir, "schedule.json")
        out_path = os.path.join(workdir, "sent.json")
        with open(sched_path, "w", encoding="utf-8") as f:
            json.dump(schedule, f)
        child = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py"), srv.url,
             sched_path, out_path],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=ROOT)
        srv.lead()
        dev_block = srv.scheduler.device
        log(f"scheduler up in {srv.times['lead_s']:.1f}s (warm-up "
            f"{srv.times['warmup_s']:.1f}s, {dev_block.get('warmup_runs')} "
            f"runs, cache {dev_block.get('compilation_cache_dir')})")
        from cook_tpu.client import JobClient
        admin = JobClient(srv.url, user="admin", timeout_s=120.0)
        t_settle = time.time()
        # the settle requests go in once cycles run, so that every
        # executable an arrival touches (the base mirror's append among
        # them) has run before the window
        srv.wait_cycles(2)
        settle_sent = []
        for req in settle:
            cl = JobClient(srv.url, user=req["user"], timeout_s=120.0)
            t_req = time.time()
            got = cl.submit([dict(j, command="true", name="settle",
                                  max_retries=1) for j in req["jobs"]],
                            pool=req["pool"])
            settle_sent.append({"due": t_req, "sent": t_req, "ok": True,
                                "ack": time.time(), "acked": list(got)})
            cl.close()
        srv.wait_cycles(int(mix.get("settle_cycles", 4)))
        if child.stdout.readline().strip() != "ready":
            raise BenchFailure("the load generator did not come up")
        # the scheduler's 30 s sweeps count from when its threads started;
        # the window opens a fixed time after that, so a sweep falls at the
        # same place in every window however long set-up took ...
        time.sleep(max(srv.t_scheduler + float(
            mix.get("start_after_scheduler_s", 0.0)) - time.time(), 0))
        # ... and in the middle of the interval wait that follows a cycle,
        # so its ends fall between launch bursts
        srv.wait_cycles(1)
        t_start = srv.last_apply_end() + float(
            mix.get("start_after_cycle_s", 0.5))
        srv.times["settle_s"] = t_start - t_settle
        child.stdin.write(f"{t_start!r}\n")
        child.stdin.flush()
        m_before = parse_metrics(admin.metrics())
        t_end = t_start + seconds
        srv.times["window_after_scheduler_s"] = t_start - srv.t_scheduler
        log(f"window opens {t_start - T_PROCESS:.1f}s after process start, "
            f"{t_start - srv.t_scheduler:.1f}s after the scheduler's")

        # ------------------------------------------------------- window
        trace_dir = os.path.join(workdir, "trace")
        trace_span = None
        if args.trace:
            # a few whole cycles, from the end of one apply to the end of
            # a later one: the device keeps ~30k events a session, and one
            # fused cycle writes ~15k (PERF.md section 5)
            import jax
            time.sleep(max(t_start + min(float(mix.get("trace_after_s", 3.0)),
                                         seconds * 0.25) - time.time(), 0))
            srv.wait_cycles(1)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            t_tr0 = time.time()
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing_on = True
            t_tr1 = time.time()
            srv.wait_cycles(int(mix.get("trace_cycles", 2)))
            time.sleep(0.05)
            jax.profiler.stop_trace()
            tracing_on = False
            trace_span = (t_tr1, time.time())
            log(f"trace: start took {t_tr1 - t_tr0:.2f}s, span "
                f"{trace_span[1] - t_tr1:.2f}s")
        while time.time() < t_end:
            srv.check_alive()
            time.sleep(min(0.25, max(t_end - time.time(), 0)))
        m_after = parse_metrics(admin.metrics())
        try:
            child.wait(timeout=180)
        except subprocess.TimeoutExpired:
            raise BenchFailure("the load generator did not finish")
        if child.returncode != 0:
            raise BenchFailure(f"the load generator exited "
                               f"{child.returncode}")
        sent = load_json(out_path)

        # every ACKed arrival is waited for, up to a minute past the close
        acked = [u for s in sent if s and s["ok"] for u in s["acked"]]
        store = srv.daemon.store
        deadline = t_end + 60.0
        waiting = list(acked)
        while waiting and time.time() < deadline:
            srv.check_alive()
            waiting = [u for u in waiting
                       if not (store.job(u) and store.job(u).instances)]
            if waiting:
                time.sleep(0.25)
        drain_s = time.time() - t_end
        memory_peak = memory_peak_bytes()

        # -------------------------------------- read back over REST
        readback = {}
        all_arrivals = [j["uuid"] for r in settle + schedule
                        for j in r["jobs"]]
        known = set(acked) | {u for s in settle_sent for u in s["acked"]}
        ask = [u for u in all_arrivals if u in known]
        for i in range(0, len(ask), 100):
            for doc in admin.query(ask[i:i + 100], partial=True):
                readback[doc["uuid"]] = doc
        running_list = admin.running()
        cycles_doc = admin.debug_cycles(limit=512)["cycles"]
        if args.dump_cycles:
            os.makedirs(os.path.dirname(os.path.abspath(args.dump_cycles)),
                        exist_ok=True)
            with open(args.dump_cycles, "w", encoding="utf-8") as f:
                json.dump({"window": [t_start, t_end], "cycles": cycles_doc,
                           "compiles": {k: v for k, v in m_after.items()
                                        if k.startswith("cook_jit_compile")}},
                          f)
        health = admin.debug_health()
        admin.close()
        srv.stop()

        # ------------------------------------------------------ metrics
        ttp = []
        attempted = failed = 0
        for r, s in zip(schedule, sent):
            for j in r["jobs"]:
                attempted += 1
                doc = readback.get(j["uuid"])
                inst = (doc or {}).get("instances") or []
                if not (s and s["ok"]) or not inst \
                        or not inst[0].get("start_time"):
                    # never placed: it waited until the run gave up
                    failed += 1
                    ttp.append((deadline - (s["due"] if s else t_end))
                               * 1000.0)
                else:
                    ttp.append(inst[0]["start_time"] - s["due"] * 1000.0)
        log("ttp ms p50/p90/p95/p99/max: " + " / ".join(
            f"{percentile(ttp, q):.0f}" for q in (50, 90, 95, 99, 100)))
        ack_ms = [(x["ack"] - x["sent"]) * 1000.0 for x in sent
                  if x and x["ok"]]
        log("ack - sent ms p50/p90/p95/p99/max: " + " / ".join(
            f"{percentile(ack_ms, q):.0f}" for q in (50, 90, 95, 99, 100)))
        in_window = [c for c in cycles_doc if c["kind"] == "fused"
                     and t_start <= c["start"] < t_end]
        launched_in_window = sum(
            1 for i in running_list
            if t_start * 1000.0 <= (i.get("start_time") or 0)
            < t_end * 1000.0)
        placed_in_window = placements_in(
            [i.get("start_time") or 0 for i in running_list],
            t_start * 1000.0, t_end * 1000.0)
        setup_s = t_start - T_PROCESS
        values = {
            "ttp_p50_ms": percentile(ttp, 50),
            "ttp_p95_ms": percentile(ttp, 95),
            "placements_per_s": placed_in_window / seconds,
            "cycle_ms": (sum(c["duration_ms"] for c in in_window)
                         / max(len(in_window), 1)),
            "setup_s": setup_s,
        }
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        cell_metrics = [
            m["name"] for m in bench["end_to_end"]
            if "workloads" not in m or cell["name"] in m["workloads"]]

        # ------------------------------------------------------ correct
        jobs = check.Jobs(config, backlog, srv.backlog_submit_ms,
                          settle + schedule, settle_sent + sent, readback)
        t_chk = time.time()
        cap = int(config["scheduler"].get("default_matcher", {}).get(
            "max_jobs_considered", 1000))
        numbers = check.replay(jobs, mix, srv.capture, (t_start, t_end),
                               cap, control=bool(args.control), log=log)
        numbers.update(check.guarantees(
            jobs, settle_sent + sent, readback, running_list,
            numbers.pop("_host_of")))
        numbers["double_run"] = max(numbers["double_run"],
                                    numbers.pop("double_live"))
        numbers["fallbacks"] = int(
            m_after.get("cook_kernel_fallback_total", 0)
            - m_before.get("cook_kernel_fallback_total", 0))
        numbers["off_path_cycles"] = sum(
            1 for c in in_window if c.get("path") != "fused"
            or c.get("error") or c.get("faults"))
        for series, after in sorted(m_after.items()):
            if series.startswith("cook_jit_compile_total{") \
                    and after != m_before.get(series, 0.0):
                log(f"compiled inside the window: {series} "
                    f"{m_before.get(series, 0.0):g} -> {after:g}")
        correct, table = check.verdict(numbers)
        control_table = None
        if args.control:
            control_correct, control_table = check.control_verdict(numbers)
        check_s = time.time() - t_chk
        log(f"drain {drain_s:.1f}s, check {check_s:.1f}s over "
            f"{numbers['cycles_compared']} cycles, {len(in_window)} fused "
            f"cycles in the window, {launched_in_window} launches")

        result = {"correct": bool(correct), "attempted": attempted,
                  "failed": failed}
        dev_out = dict(device, memory_peak_bytes=memory_peak)
        ctx = {"cycles": in_window, "sent": sent, "schedule": schedule,
               "metrics_before": m_before, "metrics_after": m_after,
               "health": health, "capture": srv.capture, "config": config,
               "mix": mix, "window": (t_start, t_end), "device": device,
               "times": srv.times, "trace": None,
               "launched_in_window": launched_in_window}
        if args.trace:
            import trace_reduce
            red = trace_reduce.reduce_dir(trace_dir)
            if args.dump_trace:
                trace_reduce.dump_names(trace_dir, args.dump_trace)
            if red is None or red["busy_s"] <= 0:
                raise BenchFailure("the trace shows no operation on the "
                                   "device: the run did not drive the chip")
            ctx["trace"] = red
            ctx["trace_span"] = trace_span
            dev_out["busy_s"] = red["busy_s"]
            dev_out["window_s"] = red["window_s"]
            metrics = {}
            for name, (read, spec, entry) in load_readers(bench).items():
                if "workloads" in entry \
                        and cell["name"] not in entry["workloads"]:
                    continue
                value = read(ctx, spec)
                if value is not None:
                    metrics[name] = {"value": float(value),
                                     "unit": entry["unit"]}
            result["metrics"] = metrics
            # an operation's name is its whole HLO line: the head of it
            # (name, result shape, opcode) is what a reader needs
            result["breakdown"] = {
                "device_ops": [[n[:160], s] for n, s in red["top_ops"][:10]],
                "idle_gaps": red["idle_gaps"][:10]}
        else:
            result["metrics"] = {
                k: {"value": float(values[k]), "unit": units[k]}
                for k in cell_metrics}
        result["device"] = dev_out
        result["times"] = {k: round(v, 3) for k, v in dict(
            srv.times, drain_s=drain_s, check_s=check_s).items()}
        result["launched_in_window"] = launched_in_window
        if args.control:
            result["control_correct"] = bool(control_correct)
            result["control_checks"] = control_table
        result["checks"] = table
        report = (result, table, control_table)
    finally:
        if tracing_on:
            import jax
            jax.profiler.stop_trace()
        if child is not None and child.poll() is None:
            child.kill()
            child.wait()
        if srv is not None:
            try:
                srv.stop()
            except Exception:   # the result stands; the exit may be unclean
                log("the daemon did not shut down cleanly")
        shutil.rmtree(workdir, ignore_errors=True)
    # the numbers compared, each beside its limit, are the last lines on
    # standard error; the result is the last line on standard output
    result, table, control_table = report
    if control_table:
        log("control in the program's place: correct = "
            f"{result['control_correct']}, " + ", ".join(
                f"{k} = {v!r} (limit {lim!r})"
                for k, (v, lim) in control_table.items() if v > lim))
    for name, (value, limit) in table.items():
        log(f"check {name} = {value!r} (limit {limit!r})")
    print(json.dumps(result), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also put the broken reference in the program's "
                         "place and report its verdict as control_correct "
                         "(never set by the driver)")
    ap.add_argument("--dump-trace", default="",
                    help="write the trace's plane, line and event names "
                         "to this file (for a look by hand)")
    ap.add_argument("--dump-cycles", default="",
                    help="write every CycleRecord of the run and the "
                         "compile counters to this file (for a diagnosis)")
    ap.add_argument("--bench-file",
                    default=os.path.join(ROOT, "BENCHMARK.json"),
                    help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    try:
        return run(args)
    except BenchFailure as e:
        log(f"FAILED: {e}")
        return 3


if __name__ == "__main__":
    sys.exit(main())
