"""sync_wait_ms.serve: host time the serving path spends blocked on
device values (``query.sync``: the window-capacity fetch at launch and the
counts fetch at resolve) per coalesced launch in the traced window, in
ms."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    n = r.count("serve.launch") if r else 0
    if not n:
        return None
    return 1000.0 * r.total("query.sync") / n
