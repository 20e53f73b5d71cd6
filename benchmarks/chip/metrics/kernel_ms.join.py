"""kernel_ms.join: summed device time of the fused kernel's events per
join in the traced window, in ms."""
import names


def read(ctx):
    s = ctx["summary"]
    joins = ctx["stats"].get("joins", 0)
    if s is None or not joins:
        return None
    t = s.seconds_prefix(s.ops, names.KERNEL_OP_PREFIX)
    return 1000.0 * t / joins if t > 0 else None
