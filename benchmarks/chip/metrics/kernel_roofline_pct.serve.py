"""kernel_roofline_pct.serve: the least time the chip could take for the
served queries' distances (``work.py``, from the queries and the pairs
returned) over the fused kernel's summed device time, in %."""
import names
import work


def read(ctx):
    s, st = ctx["summary"], ctx["stats"]
    if s is None or not st.get("queries") or ctx["peak"] is None:
        return None
    t = s.seconds_prefix(s.ops, names.KERNEL_OP_PREFIX)
    f, b = work.join_work(st["dims"], st["queries"], st["pairs"])
    pct, bound = work.roofline_pct(f, b, t, ctx["peak"])
    st["kernel_roofline_bound.serve"] = bound
    return pct
