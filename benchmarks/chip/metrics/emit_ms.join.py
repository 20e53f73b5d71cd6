"""emit_ms.join: summed device time of the pair-emit programs per join in
the traced window, in ms (the result sort runs on the host)."""
import names


def read(ctx):
    s = ctx["summary"]
    joins = ctx["stats"].get("joins", 0)
    if s is None or not joins:
        return None
    t = s.seconds(s.modules, *names.EMIT_MODULES)
    return 1000.0 * t / joins if t > 0 else None
