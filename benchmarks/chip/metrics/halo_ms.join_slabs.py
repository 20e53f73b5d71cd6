"""halo_ms.join_slabs: host time of the slab join's partition
(``selfjoin.partition``: the dim-0 partition, slab extents, halo reach and
capacity plan) and halo exchange (``selfjoin.halo``: the ``shard_map``
halo step, the slab grid build program fed by it, the overflow check and
the slabs' grids taken from the build's blocks) per distributed join in
the traced window, in ms.
Also puts the rows the exchange ships (``halo_rows_per_join``) and the
slabs of a join (``slabs_per_join``) into the run's notes."""
import progspans


def read(ctx):
    r = progspans.window(ctx, __file__)
    joins = r.count("selfjoin.join") if r else 0
    if not joins or not r.count("selfjoin.halo"):
        return None
    ctx["stats"]["halo_rows_per_join"] = (
        r.arg("selfjoin.halo", "halo_rows") / joins)
    ctx["stats"]["slabs_per_join"] = r.arg("selfjoin.join", "slabs") / joins
    return 1000.0 * (r.total("selfjoin.partition")
                     + r.total("selfjoin.halo")) / joins
