"""Instances whose start_time lies inside the window, over its seconds:
the plain count beside placements_per_s.  A launch transaction stamps its
whole burst (up to the cap) with one instant, so this moves by a burst
when a window's end crosses one."""


def read(ctx, spec):
    t0, t1 = ctx["window"]
    return ctx["launched_in_window"] / (t1 - t0)
