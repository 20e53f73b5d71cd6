"""idle_pct.serve: share of the traced window in which no operation ran on
the device, in %."""


def read(ctx):
    s = ctx["summary"]
    return None if s is None or s.busy_s <= 0 else 100.0 * s.idle_share
