"""The device's idle share of the traced span, in %."""


def read(ctx, spec):
    red = ctx["trace"]
    if not red["window_s"]:
        return None
    return (1.0 - red["busy_s"] / red["window_s"]) * 100.0
