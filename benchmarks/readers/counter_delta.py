"""A /metrics counter after the window minus before it.
spec: {"counter": "cook_jit_compile_total"}."""


def read(ctx, spec):
    name = spec["counter"]
    return (ctx["metrics_after"].get(name, 0.0)
            - ctx["metrics_before"].get(name, 0.0))
