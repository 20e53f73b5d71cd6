"""sort_ms.join: host time of the join driver's result sort
(``selfjoin.sort``, the final ``np.lexsort`` of the pairs) per join in the
traced window, in ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    joins = r.count("selfjoin.join") if r else 0
    if not joins or not r.count("selfjoin.sort"):
        return None
    return 1000.0 * r.total("selfjoin.sort") / joins
