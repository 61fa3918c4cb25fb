"""From a jax.profiler trace (xplane.pb) to the device numbers.

Busy time is the union of the intervals in which an operation ran on a
device, averaged over the devices that ran any; kernel time is the summed
device duration of an executable's events; idle time is attributed to
what the host's cycle thread was doing, through the TraceAnnotations the
benchmark's wrappers write (server.py).  Kept with the benchmark, and
checked against a small recorded trace (tests/).
"""

from __future__ import annotations

import glob
import os
from typing import Dict, List, Optional, Tuple

#: line of a device plane that carries one event per executed operation
OPS_LINE = "XLA Ops"
#: line that carries one event per executable (jit) run
MODULES_LINE = "XLA Modules"
HOST_LABELS = ("cook.stage", "cook.dispatch", "cook.fetch", "cook.apply")
OTHER = "interval wait"


def union_seconds(intervals: List[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    for a, b in sorted(intervals):
        if end is None or a > end:
            total += b - a
            end = b
        elif b > end:
            total += b - end
            end = b
    return total


def merged(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def load_planes(path: str) -> List[Dict]:
    """[{"name", "lines": [{"name", "events": [(name, start_s, dur_s)]}]}]"""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        lines = []
        for line in plane.lines:
            lines.append({"name": line.name, "events": [
                (ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9)
                for ev in line.events]})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def reduce_planes(planes: List[Dict], device_prefix: str = "/device:TPU"
                  ) -> Optional[Dict]:
    """The reduction proper, over load_planes' plain structure."""
    per_device_busy = []
    op_seconds: Dict[str, float] = {}
    modules: Dict[str, List[float]] = {}
    busy_all: List[Tuple[float, float]] = []
    span = [float("inf"), float("-inf")]
    for plane in planes:
        for line in plane["lines"]:
            for _n, start, dur in line["events"]:
                span[0] = min(span[0], start)
                span[1] = max(span[1], start + dur)
        if not plane["name"].startswith(device_prefix):
            continue
        for line in plane["lines"]:
            if line["name"] == OPS_LINE:
                iv = [(s, s + d) for _n, s, d in line["events"]]
                if iv:
                    per_device_busy.append(union_seconds(iv))
                    busy_all += iv
                for name, _s, d in line["events"]:
                    op_seconds[name] = op_seconds.get(name, 0.0) + d
            elif line["name"] == MODULES_LINE:
                for name, _s, d in line["events"]:
                    modules.setdefault(name, []).append(d)
    if not per_device_busy:
        return None
    host: Dict[str, List[Tuple[float, float]]] = {k: [] for k in HOST_LABELS}
    for plane in planes:
        if plane["name"].startswith(device_prefix):
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name in host:
                    host[name].append((start, start + dur))
    window = span[1] - span[0]
    busy = merged(busy_all)
    gaps = [(a, b) for a, b in zip(
        [span[0]] + [b for _a, b in busy], [a for a, _b in busy] + [span[1]])
        if b > a]
    idle: Dict[str, float] = {}
    for a, b in gaps:
        rest = b - a
        for label, ivs in host.items():
            got = sum(max(0.0, min(b, y) - max(a, x)) for x, y in ivs)
            if got > 0:
                idle[label] = idle.get(label, 0.0) + got
                rest -= got
        if rest > 0:
            idle[OTHER] = idle.get(OTHER, 0.0) + rest
    return {
        "busy_s": sum(per_device_busy) / len(per_device_busy),
        "window_s": window,
        "devices": len(per_device_busy),
        "modules": modules,
        "top_ops": [[k, v] for k, v in sorted(
            op_seconds.items(), key=lambda kv: -kv[1])],
        "idle_gaps": [[k, v] for k, v in sorted(
            idle.items(), key=lambda kv: -kv[1])],
        "host_calls": {k: len(v) for k, v in host.items()},
    }


def reduce_dir(trace_dir: str) -> Optional[Dict]:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        return None
    return reduce_planes(load_planes(paths[-1]))


def module_seconds(red: Dict, patterns: List[str]) -> List[float]:
    """Durations of every run of the executables whose name contains one
    of ``patterns``."""
    out: List[float] = []
    for name, durs in red["modules"].items():
        if any(p in name for p in patterns):
            out += durs
    return out


def dump_names(trace_dir: str, out_path: str) -> None:
    """Planes, lines and the heaviest event names of a trace, as JSON:
    what one looks at by hand before writing a reader."""
    import json
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    planes = load_planes(paths[-1]) if paths else []
    doc = {}
    for plane in planes:
        lines = {}
        for line in plane["lines"]:
            agg: Dict[str, List[float]] = {}
            for name, _s, d in line["events"]:
                a = agg.setdefault(name, [0, 0.0])
                a[0] += 1
                a[1] += d
            top = sorted(agg.items(), key=lambda kv: -kv[1][1])[:25]
            lines[line["name"]] = {"events": len(line["events"]),
                                   "top": [[k, v[0], v[1]] for k, v in top]}
        doc[plane["name"]] = lines
    os.makedirs(os.path.dirname(os.path.abspath(out_path)), exist_ok=True)
    with open(out_path, "w", encoding="utf-8") as f:
        json.dump(doc, f, indent=1)
    # a cut of the trace itself, small enough to keep beside the tests:
    # the executables' line whole, the heaviest and the first operations,
    # and the benchmark's own host annotations
    cut = []
    for plane in planes:
        lines = []
        for line in plane["lines"]:
            ev = line["events"]
            if line["name"] == OPS_LINE:
                keep = sorted(ev, key=lambda e: -e[2])[:60] + ev[:60]
                ev = sorted(set(keep), key=lambda e: e[1])
            elif line["name"] != MODULES_LINE:
                ev = [e for e in ev if e[0] in HOST_LABELS]
            if ev:
                lines.append({"name": line["name"],
                              "events": [[n[:80], s, d] for n, s, d in ev]})
        if lines:
            cut.append({"name": plane["name"], "lines": lines})
    with open(out_path + ".planes.json", "w", encoding="utf-8") as f:
        json.dump(cut, f)
