"""A statistic over the window's fused cycles of the SUM of some
CycleRecord fields: what ``cycle_mean`` (mean or median of one field)
cannot give, above all the maximum, which is what one stalled cycle a
window moves and the mean hides.  spec: {"fields": [dotted paths, summed
per record], "stat": "max" | "min" | "mean"}.  A field a record lacks
reads as absent, not as 0: a record with none of the fields is left out,
and with no record left the metric is left out of the line."""

STATS = {"max": max, "min": min, "mean": lambda vs: sum(vs) / len(vs)}


def read(ctx, spec):
    sums = []
    for c in ctx["cycles"]:
        found = []
        for field in spec["fields"]:
            v = c
            for part in field.split("."):
                v = v.get(part) if isinstance(v, dict) else None
                if v is None:
                    break
            if v is not None:
                found.append(float(v))
        if found:
            sums.append(sum(found))
    if not sums:
        return None
    return STATS[spec["stat"]](sums)
