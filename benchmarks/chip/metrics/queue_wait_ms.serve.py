"""queue_wait_ms.serve: mean time a request part waits in the batching
service's admission queue, from ``submit`` to the launch of its coalesced
batch (``serve.launch``'s ``wait_us_sum`` over its ``parts``, summed over
the traced window), in ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    parts = r.arg("serve.launch", "parts") if r else 0
    if not parts:
        return None
    return r.arg("serve.launch", "wait_us_sum") / parts / 1000.0
