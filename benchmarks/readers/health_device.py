"""A field of /debug/health's device block.  spec: {"field": "warmup_s"}."""


def read(ctx, spec):
    v = (ctx["health"].get("device") or {}).get(spec["field"])
    return None if v is None else float(v)
