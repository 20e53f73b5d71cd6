"""resolve_host_ms.serve: mean host time of answering one coalesced batch
(``serve.resolve``: the counts fetch, pair emit, pair sort and slicing
per request) in the traced window, in ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    n = r.count("serve.resolve") if r else 0
    if not n:
        return None
    return 1000.0 * r.total("serve.resolve") / n
