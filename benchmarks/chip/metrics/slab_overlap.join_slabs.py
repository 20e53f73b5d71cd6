"""slab_overlap.join_slabs: how many chips were busy at once, on average,
while any was: the sum over device planes of each plane's busy time in the
traced window over the length of the union of all planes' busy intervals,
in devices. 1.0 means the chips took turns; 4.0 means all four were busy
whenever any was.

Busy is as ``tracesum.summarize`` takes it: ``busy_s`` is the mean over
the device planes, and ``idle_by_host`` shares out exactly the stretches
of the window in which no plane was busy, so the union is the window less
their sum."""


def read(ctx):
    s = ctx.get("summary")
    if s is None or s.busy_s <= 0:
        return None
    union = s.window_s - sum(s.idle_by_host.values())
    return s.n_devices * s.busy_s / union if union > 0 else None
