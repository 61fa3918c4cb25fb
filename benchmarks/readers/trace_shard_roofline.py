"""One shard's share of its roofline, in %: the least time ONE chip could
take for the pools it holds — ``cost.fused_cycle_cost(P // devices, ...)``,
``devices`` the device planes of the trace that ran anything — over the
mean device time of one run of the executable (a trace of N devices holds
N runs a cycle, one a shard).  ``trace_roofline`` beside it charges all P
pools to one chip.  spec: {"modules": [...], "cost": "fused_cycle"}."""

import importlib.util
import os

import cost
import trace_reduce

_spec = importlib.util.spec_from_file_location(
    "reader_trace_roofline", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "trace_roofline.py"))
trace_roofline = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(trace_roofline)


def read(ctx, spec):
    red = ctx["trace"]
    durs = trace_reduce.module_seconds(red, spec["modules"])
    dims = trace_roofline.shapes(ctx)
    devices = int(red.get("devices") or 0)
    if not durs or dims is None or not devices:
        return None
    P, T, H, U, C = dims
    if P % devices:
        return None   # the pools do not split evenly: no one shard's cost
    least = cost.least_seconds(
        cost.fused_cycle_cost(P // devices, T, H, U, C),
        ctx["device"]["kind"])
    return least["seconds"] / (sum(durs) / len(durs)) * 100.0
