"""Device time in collective operations per cycle and device, in ms: the
summed device durations of the operations whose OPCODE contains one of
spec["ops"] (``%psum.7 = s32[] all-reduce(...)`` is an all-reduce whatever
it is called; an operand named after a collective does not count), over
every device of the trace, divided by the runs of the executable named by
spec["per"] — which a trace of N devices holds N times a cycle, as
``trace_module_ms`` counts them.  None, never 0, where no collective ran
(one device: XLA removes them)."""

import re

import trace_reduce

_OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def opcode(name: str) -> str:
    """The opcode of an operation's HLO line; of a bare name, the name."""
    head, sep, rest = name.partition(" = ")
    m = _OPCODE.search(" " + rest) if sep else None
    return m.group(1) if m else head


def read(ctx, spec):
    red = ctx["trace"]
    seconds = [s for name, s in red["top_ops"]
               if any(op in opcode(name) for op in spec["ops"])]
    runs = len(trace_reduce.module_seconds(red, [spec["per"]]))
    if not seconds or not runs:
        return None
    return sum(seconds) / runs * 1000.0
