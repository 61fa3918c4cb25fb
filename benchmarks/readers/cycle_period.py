"""Start-to-start interval of consecutive fused cycles in the window, in
ms: what Scheduler.run's loop adds to a cycle (the interval wait, the
idle-point GC, whatever else runs between ticks).  spec: {"stat":
"mean"|"max"}."""


def read(ctx, spec):
    starts = sorted(c["start"] for c in ctx["cycles"])
    gaps = [(b - a) * 1000.0 for a, b in zip(starts, starts[1:])]
    if not gaps:
        return None
    return max(gaps) if spec["stat"] == "max" else sum(gaps) / len(gaps)
