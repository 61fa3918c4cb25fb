"""Mean of one CycleRecord field over the fused cycles that started in the
window, or with ``"stat": "median"`` their median, which one stalled cycle
does not move.  spec: {"field": "detail_ms.pack"} (a dotted path)."""

import statistics


def read(ctx, spec):
    vals = []
    for c in ctx["cycles"]:
        v = c
        for part in spec["field"].split("."):
            v = v.get(part) if isinstance(v, dict) else None
            if v is None:
                break
        if v is not None:
            vals.append(float(v))
    if not vals:
        return None
    if spec.get("stat") == "median":
        return statistics.median(vals)
    return sum(vals) / len(vals)
