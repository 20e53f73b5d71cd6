"""kernel_roofline_pct.join: the least time the chip could take for the
joins' distances (``work.py``, from the points and the pairs found) over
the fused kernel's summed device time, in %."""
import names
import work


def read(ctx):
    s, st = ctx["summary"], ctx["stats"]
    if s is None or not st.get("joins") or ctx["peak"] is None:
        return None
    t = s.seconds_prefix(s.ops, names.KERNEL_OP_PREFIX)
    f, b = work.join_work(st["dims"], st["queries_per_join"],
                          st["pairs_unique_per_join"])
    pct, bound = work.roofline_pct(f * st["joins"], b * st["joins"], t,
                                   ctx["peak"])
    st["kernel_roofline_bound.join"] = bound
    return pct
