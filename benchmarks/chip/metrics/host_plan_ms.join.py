"""host_plan_ms.join: self time of the join driver's planning spans --
the route probe (``selfjoin.route``), the launch schedule and points copy
(``selfjoin.plan``) and the cell-run plans (``selfjoin.run_plan``) -- per
join in the traced window, in ms. Their waits on device values are
``selfjoin.sync`` spans inside them, so self time leaves them out."""
import progspans

SPANS = ("selfjoin.route", "selfjoin.plan", "selfjoin.run_plan")


def read(ctx):
    r = progspans.window(ctx, __file__)
    joins = r.count("selfjoin.join") if r else 0
    if not joins:
        return None
    return 1000.0 * sum(r.self_time(n) for n in SPANS) / joins
