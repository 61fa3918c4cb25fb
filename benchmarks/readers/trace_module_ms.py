"""Device time of executables per cycle, in ms: the summed device durations
of every run of the executables whose name contains one of
spec["modules"] ("" matches all), over the runs of the executable named
by spec["per"] (the fused cycle: one run a cycle)."""

import trace_reduce


def read(ctx, spec):
    red = ctx["trace"]
    durs = trace_reduce.module_seconds(red, spec["modules"])
    cycles = len(trace_reduce.module_seconds(red, [spec["per"]]))
    if not durs or not cycles:
        return None
    return sum(durs) / cycles * 1000.0
