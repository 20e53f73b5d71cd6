"""launch_host_ms.serve: mean host time of one coalesced launch of the
batching service (``serve.launch``: coalescing, query padding, cell-run
order, window descriptors, the capacity sync and every class dispatch) in
the traced window, in ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    n = r.count("serve.launch") if r else 0
    if not n:
        return None
    return 1000.0 * r.total("serve.launch") / n
