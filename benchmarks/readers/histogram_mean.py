"""A histogram's sum over the window, per event of another series: the
Δ``<sum>_sum`` of the series whose labels hold ``sum_labels`` over the
Δ``<count>_count`` of those whose labels hold ``count_labels``, times
``scale``.  With the submissions' count as the denominator a phase that a
request did not have counts as 0 in its mean.  Series are matched on a
subset of their labels: ``run.parse_metrics`` keys a labelled series by
its full text.  spec: {"sum": "cook_http_phase_seconds", "sum_labels":
{...}, "count": "cook_http_request_duration_seconds", "count_labels":
{...}, "scale": 1000.0, "present_labels": {...}}.

No denominator in the window reads as nothing.  No numerator series at
all reads as 0 only where a series of the same histogram matching
``present_labels`` exists, which says the program records the family's
phases and this one never occurred; otherwise as nothing (a program that
does not record the phase)."""

import re

_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')


def _series(metrics, name, labels):
    """The keys of ``metrics`` that are series of ``name`` whose labels
    hold every (key, value) of ``labels``."""
    head = name + "{"
    out = []
    for key in metrics:
        if not key.startswith(head):
            continue
        have = dict(_LABEL.findall(key[len(head):-1]))
        if all(have.get(k) == v for k, v in labels.items()):
            out.append(key)
    return out


def _delta(ctx, name, labels):
    after, before = ctx["metrics_after"], ctx["metrics_before"]
    keys = _series(after, name, labels)
    if not keys:
        return None
    return sum(after[k] - before.get(k, 0.0) for k in keys)


def read(ctx, spec):
    den = _delta(ctx, spec["count"] + "_count", spec["count_labels"])
    if not den or den <= 0:
        return None
    num = _delta(ctx, spec["sum"] + "_sum", spec["sum_labels"])
    if num is None:
        present = spec.get("present_labels")
        if present is None or not _series(ctx["metrics_after"],
                                          spec["sum"] + "_sum", present):
            return None
        num = 0.0
    return float(spec.get("scale", 1.0)) * num / den
