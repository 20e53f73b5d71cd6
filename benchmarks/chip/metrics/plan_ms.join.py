"""plan_ms.join: summed device time of the window-planning programs (cell
descriptor tables, window capacities, per-launch descriptor gathers) per
join in the traced window, in ms."""
import names


def read(ctx):
    s = ctx["summary"]
    joins = ctx["stats"].get("joins", 0)
    if s is None or not joins:
        return None
    t = s.seconds(s.modules, *names.PLAN_MODULES)
    return 1000.0 * t / joins if t > 0 else None
