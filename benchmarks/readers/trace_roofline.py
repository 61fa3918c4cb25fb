"""An executable's share of its roofline, in %: the least time the chip
could take for the cycle's shapes (cost.py, peaks.json) over the measured
device time of one run.  spec: {"modules": [...], "cost": "fused_cycle"}."""

import cost
import trace_reduce


def shapes(ctx):
    """(P, T, H, U, C) of the last cycle staged in the window."""
    t0, t1 = ctx["window"]
    for cyc in reversed(ctx["capture"].cycles):
        if t0 <= cyc["t_stage"][0] < t1 and cyc["groups"]:
            P, T, H, cap = cyc["shapes"][0]
            users = max(len(pp.shares_u) for pp in cyc["groups"][0])
            return P, T, H, users, min(cap, T)
    return None


def read(ctx, spec):
    durs = trace_reduce.module_seconds(ctx["trace"], spec["modules"])
    dims = shapes(ctx)
    if not durs or dims is None:
        return None
    P, T, H, U, C = dims
    least = cost.least_seconds(cost.fused_cycle_cost(P, T, H, U, C),
                               ctx["device"]["kind"])
    return least["seconds"] / (sum(durs) / len(durs)) * 100.0
