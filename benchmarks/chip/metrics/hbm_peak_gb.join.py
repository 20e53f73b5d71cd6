"""hbm_peak_gb.join: the device allocator's peak bytes in use after the
window (``memory_stats()["peak_bytes_in_use"]``), in GB."""


def read(ctx):
    b = ctx["stats"].get("memory_peak_bytes", 0)
    return b / 1e9 if b else None
