"""A percentile of a load-generator interval over the window's requests,
in ms.  spec: {"from": "due"|"sent", "to": "sent"|"ack", "q": 50}."""


def read(ctx, spec):
    vals = sorted((s[spec["to"]] - s[spec["from"]]) * 1000.0
                  for s in ctx["sent"] if s and s["ok"])
    if not vals:
        return None
    k = max(0, min(len(vals) - 1,
                   int(-(-spec["q"] * len(vals) // 100)) - 1))
    return vals[k]
