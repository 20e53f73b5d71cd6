"""build_ms.join: host time of ``build_grid`` (ended by
``block_until_ready``) per join in the window, in ms."""


def read(ctx):
    b = ctx["stats"].get("build_s") or []
    return 1000.0 * sum(b) / len(b) if b else None
