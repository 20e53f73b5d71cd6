"""sync_wait_ms.join: host time the join driver spends blocked on device
values (``selfjoin.sync``: the route probe's and the run-loop decision's
index fetches, the window-capacity fetch that waits on the caps planner,
the order fetch, each launch's counts sum, the emitted count check and
pairs fetch) per join in the traced window, in ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    joins = r.count("selfjoin.join") if r else 0
    if not joins:
        return None
    return 1000.0 * r.total("selfjoin.sync") / joins
