"""Parity tests for the fused sweep (kernels/fused_join.py).

The fused gather-refine path must produce identical pair sets and counts to
the brute-force oracle (core/brute.py) across every driver, with work
counters equal to the per-cell (``merge_last_dim=False``) sweep's,
including the degenerate grid
shapes (one point per cell, all points in one cell) and the
empty-neighbor-heavy 6-D regime where most (query, offset) probes miss.
The Pallas kernel itself (interpret mode off-TPU) is validated against the
reference lowering bit-for-bit, including the per-query counts and the
in-kernel exclusive-scan slot bases.
"""
import numpy as np
import pytest

from repro.core.brute import brute_force_count, brute_force_join
from repro.core.grid import build_grid_host
from repro.core.selfjoin import (
    _fused_batch_run,
    _fused_pad,
    _offset_tables,
    _round_up,
    _self_join_fused,
    self_join,
    self_join_batched,
    self_join_count,
    self_join_count_compact,
)


def fused_run(index, deltas, is_zero, npts, c, unicomp, method,
              merged=False):
    points_pad, qp = _fused_pad(index, q_size=npts, c=c, merged=merged)
    return _fused_batch_run(index, points_pad, deltas, is_zero, 0, qp=qp,
                            q_size=npts, c=c, unicomp=unicomp,
                            keep_hits=True, method=method, merged=merged)


def sorted_pairs(p):
    return p[np.lexsort((p[:, 1], p[:, 0]))]


def datasets():
    rng = np.random.default_rng(99)
    yield "uniform-2d", rng.uniform(0, 10, (400, 2)), 0.6
    yield "uniform-3d", rng.uniform(0, 10, (300, 3)), 1.0
    centers = rng.uniform(0, 10, (12, 2))
    clustered = centers[rng.integers(0, 12, 350)] + rng.normal(0, 0.1, (350, 2))
    yield "clustered-2d", clustered, 0.25
    # empty-neighbor-heavy: 6-D uniform, >90% of stencil probes miss
    yield "sparse-6d", rng.uniform(0, 60, (250, 6)), 7.0
    dup = rng.integers(0, 3, (120, 3)).astype(np.float64)
    yield "degenerate-dups", dup, 0.5


@pytest.mark.parametrize("unicomp", [True, False])
def test_fused_join_matches_jnp(unicomp):
    for name, pts, eps in datasets():
        a = brute_force_join(pts, eps)
        b = self_join(pts, eps, unicomp=unicomp, distance_impl="fused")
        assert np.array_equal(a, b), name


def test_fused_count_matches_jnp():
    for name, pts, eps in datasets():
        n = pts.shape[1]
        merged_off = {True: (3 ** (n - 1) + 1) // 2, False: 3 ** (n - 1)}
        full_off = {True: (3 ** n + 1) // 2, False: 3 ** n}
        want = brute_force_count(pts, eps)
        for unicomp in (True, False):
            # the per-cell sweep is the work counters' reference
            a = self_join_count(pts, eps, unicomp=unicomp,
                                merge_last_dim=False)
            b = self_join_count(pts, eps, unicomp=unicomp,
                                distance_impl="fused")
            assert a.total_pairs == b.total_pairs == want, name
            assert a.route == b.route == "dense", name   # off the TPU
            assert a.cells_visited == b.cells_visited, name
            assert a.candidates_checked == b.candidates_checked, name
            # the fused sweep defaults to the merged-range stencil: 3^(n-1)
            # offsets (reduced UNICOMP half); the per-cell sweep 3^n
            assert b.n_offsets == merged_off[unicomp], name
            assert a.n_offsets == full_off[unicomp], name
            # the single-capacity launch reports the same work
            s = self_join_count(pts, eps, unicomp=unicomp, bucketed=False)
            assert (s.total_pairs, s.cells_visited, s.candidates_checked) \
                == (b.total_pairs, b.cells_visited, b.candidates_checked), \
                name


def test_fused_batched_matches_jnp():
    for name, pts, eps in datasets():
        a = brute_force_join(pts, eps)
        for nb in (2, 3, 5):
            b = self_join_batched(pts, eps, n_batches=nb,
                                  distance_impl="fused")
            assert np.array_equal(a, b), (name, nb)


def test_fused_count_compact_matches_jnp():
    """The 'compact' route checks exactly the per-cell sweep's live
    candidate slots, packed, and finds the oracle's total."""
    for name, pts, eps in datasets():
        want = brute_force_count(pts, eps)
        for unicomp in (True, False):
            a = self_join_count(pts, eps, unicomp=unicomp,
                                merge_last_dim=False)
            b = self_join_count_compact(pts, eps, unicomp=unicomp,
                                        distance_impl="fused")
            assert b.route == "compact" and b.cells_visited == 0
            assert b.total_pairs == want, (name, unicomp)
            assert b.candidates_checked == a.candidates_checked, \
                (name, unicomp)
            assert b.n_offsets == a.n_offsets, (name, unicomp)


def test_fused_count_query_batching():
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 10, (500, 2))
    a = self_join_count(pts, 0.7)
    assert a.total_pairs == brute_force_count(pts, 0.7)
    for qb in (64, 130, 500):
        b = self_join_count(pts, 0.7, distance_impl="fused", query_batch=qb)
        assert a.total_pairs == b.total_pairs, qb
        assert a.candidates_checked == b.candidates_checked, qb


def test_fused_max_per_cell_one_point_per_cell():
    """Grid-aligned points, eps < spacing/2: every cell holds one point."""
    g = np.stack(np.meshgrid(np.arange(12.0), np.arange(12.0)), -1)
    pts = g.reshape(-1, 2) * 3.0
    idx = build_grid_host(pts, 1.4)
    assert int(idx.max_per_cell) == 1
    for unicomp in (True, False):
        a = brute_force_join(pts, 1.4)
        b = self_join(pts, 1.4, unicomp=unicomp, distance_impl="fused")
        assert np.array_equal(a, b)
    # spacing 3 > eps: no pairs at all
    assert self_join_count(pts, 1.4, distance_impl="fused").total_pairs == 0
    # eps just over the spacing: 4-neighborhood pairs appear
    s = self_join_count(pts, 3.1, distance_impl="fused")
    assert s.total_pairs == brute_force_count(pts, 3.1) > 0


def test_fused_max_per_cell_single_cell():
    """All points inside one grid cell: C == |D|, window == whole dataset."""
    rng = np.random.default_rng(7)
    pts = rng.uniform(0, 0.3, (90, 2))
    idx = build_grid_host(pts, 1.0)
    assert int(idx.max_per_cell) == 90
    for unicomp in (True, False):
        a = brute_force_join(pts, 1.0)
        b = self_join(pts, 1.0, unicomp=unicomp, distance_impl="fused")
        assert np.array_equal(a, b)
        assert a.shape == (90 * 89, 2)  # eps covers the whole cloud
    c = self_join_count_compact(pts, 1.0, distance_impl="fused")
    assert c.total_pairs == 90 * 89


def test_fused_tiny_and_empty():
    pts = np.array([[0.0, 0.0], [10.0, 10.0]])
    assert self_join_count(pts, 1.0, distance_impl="fused").total_pairs == 0
    assert self_join(pts, 1.0, distance_impl="fused").shape == (0, 2)
    pts = np.array([[0.0, 0.0], [0.5, 0.0], [10.0, 10.0]])
    assert self_join_count(pts, 1.0, distance_impl="fused").total_pairs == 2
    assert np.array_equal(self_join(pts, 1.0, distance_impl="fused"),
                          brute_force_join(pts, 1.0))


def test_fused_emit_host_equals_device():
    """Both fill backends consume the same hit set and must agree."""
    rng = np.random.default_rng(11)
    pts = rng.uniform(0, 10, (350, 3))
    index = build_grid_host(pts, 0.9)
    for unicomp in (True, False):
        h = _self_join_fused(index, unicomp=unicomp, sort_result=True,
                             emit="host")
        d = _self_join_fused(index, unicomp=unicomp, sort_result=True,
                             emit="device")
        assert np.array_equal(h, d), unicomp
        # both backends emit query-major: identical row order even UNSORTED
        hu = _self_join_fused(index, unicomp=unicomp, sort_result=False,
                              emit="host")
        du = _self_join_fused(index, unicomp=unicomp, sort_result=False,
                              emit="device")
        assert np.array_equal(hu, du), unicomp
        # multi-batch device emission exercises the pow2 capacity path
        d3 = _self_join_fused(index, unicomp=unicomp, sort_result=True,
                              emit="device", n_batches=3)
        assert np.array_equal(h, d3), unicomp


def test_pallas_kernel_matches_reference():
    """The Pallas kernel (interpret off-TPU) against the reference lowering:
    hits, per-query counts, and in-kernel exclusive-scan slot bases."""
    rng = np.random.default_rng(5)
    for n, npts, eps, unicomp in [(2, 220, 0.8, True), (2, 220, 0.8, False),
                                  (3, 150, 1.2, True)]:
        pts = rng.uniform(0, 10, (npts, n))
        index = build_grid_host(pts, eps)
        deltas, is_zero = _offset_tables(index, unicomp)
        c = _round_up(max(int(index.max_per_cell), 1), 8)
        ref = fused_run(index, deltas, is_zero, npts, c, unicomp, "reference")
        ker = fused_run(index, deltas, is_zero, npts, c, unicomp, "kernel")
        for name, a, b in zip(("ws", "wc", "wcells", "hits", "counts",
                               "slot_base"), ref, ker):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (name, n)
        # slot_base really is the per-tile exclusive scan of counts
        counts = np.asarray(ref[4])
        base = np.asarray(ref[5])
        per_tile = counts.reshape(-1, 128)
        expect = np.cumsum(per_tile, axis=1) - per_tile
        assert np.array_equal(base.reshape(-1, 128), expect)


def test_pallas_kernel_join_end_to_end():
    """Full join through the Pallas kernel path equals the oracle."""
    rng = np.random.default_rng(13)
    pts = rng.uniform(0, 10, (260, 2))
    index = build_grid_host(pts, 0.8)
    a = brute_force_join(pts, 0.8)
    b = _self_join_fused(index, unicomp=True, sort_result=True,
                         method="kernel")
    assert np.array_equal(a, b)


# ---------------------------------------------------------------------------
# Occupancy bucketing (DESIGN.md S6)
# ---------------------------------------------------------------------------

def skewed(seed=31, n_dims=2, n_bg=500, n_cl=260):
    """Heavy cluster + sparse background: guaranteed multi-class plan."""
    rng = np.random.default_rng(seed)
    bg = rng.uniform(0, 10, (n_bg, n_dims))
    cl = rng.normal(5.0, 0.12, (n_cl, n_dims))
    return np.concatenate([bg, cl])


def test_occupancy_plan_partitions_rows():
    from repro.core.grid import occupancy_plan

    pts = skewed()
    index = build_grid_host(pts, 0.5)
    plan = occupancy_plan(index)
    assert plan.n_buckets > 1, "workload must exercise multiple classes"
    assert plan.caps == tuple(sorted(plan.caps))
    assert plan.caps[-1] == plan.cap_global
    assert plan.cap_global == _round_up(int(index.max_per_cell), 8)
    # every sorted row in exactly one bucket, ascending within each
    allsel = np.concatenate(plan.sel)
    assert np.array_equal(np.sort(allsel), np.arange(index.num_points))
    for s in plan.sel:
        assert np.all(np.diff(s) > 0)
    assert sum(plan.hist.values()) == index.num_points
    # plan is cached per index object
    assert occupancy_plan(index) is plan
    # per-bucket capacity really bounds every member row's windows
    from repro.core.grid import cell_window_caps
    caps = cell_window_caps(index)
    rank = np.asarray(index.point_cell_rank)
    for cap, s in zip(plan.caps, plan.sel):
        assert caps[rank[s]].max() <= cap


@pytest.mark.parametrize("unicomp", [True, False])
def test_bucketed_join_bit_identical_to_single_capacity(unicomp):
    """Satellite gate: bucketed and single-capacity fused joins produce
    bit-identical sorted pair sets (and match the oracle)."""
    for n_dims, eps in ((2, 0.5), (3, 0.9)):
        pts = skewed(seed=41 + n_dims, n_dims=n_dims)
        index = build_grid_host(pts, eps)
        from repro.core.grid import occupancy_plan

        assert occupancy_plan(index).n_buckets > 1
        a = brute_force_join(pts, eps)
        b = self_join(pts, eps, unicomp=unicomp, distance_impl="fused",
                      index=index)                      # bucketed (auto)
        s = self_join(pts, eps, unicomp=unicomp, distance_impl="fused",
                      index=index, bucketed=False)      # single capacity
        assert np.array_equal(b, s), (n_dims, unicomp)
        assert np.array_equal(a, b), (n_dims, unicomp)
        # counts: bucketed and single-capacity report identical work
        cb = self_join_count(pts, eps, unicomp=unicomp, index=index,
                             distance_impl="fused")
        cs = self_join_count(pts, eps, unicomp=unicomp, index=index,
                             distance_impl="fused", bucketed=False)
        assert (cb.total_pairs, cb.cells_visited, cb.candidates_checked) \
            == (cs.total_pairs, cs.cells_visited, cs.candidates_checked)


def test_bucketed_join_batched_and_emits():
    """Bucketed launches compose with the batching scheme and both fill
    backends."""
    pts = skewed(seed=77)
    index = build_grid_host(pts, 0.5)
    a = brute_force_join(pts, 0.5)
    for nb in (2, 4):
        b = self_join_batched(pts, 0.5, n_batches=nb,
                              distance_impl="fused", index=index)
        assert np.array_equal(a, b), nb
    h = _self_join_fused(index, unicomp=True, sort_result=True, emit="host")
    d = _self_join_fused(index, unicomp=True, sort_result=True,
                         emit="device")
    assert np.array_equal(h, d)
    assert np.array_equal(h, a)
    k = _self_join_fused(index, unicomp=True, sort_result=True,
                         method="kernel")
    assert np.array_equal(k, a)


@pytest.mark.parametrize("merged", [False, True])
@pytest.mark.parametrize("unicomp", [True, False])
def test_gid_pairs_kernel_matches_reference(merged, unicomp):
    """The global-id pad lane (gid_pairs, DESIGN.md S3): the Pallas kernel
    and the reference lowering agree bit-for-bit on hits/counts/bases, and
    with ids == sorted positions the gid masks reproduce the positional
    join's pair totals exactly."""
    import jax.numpy as jnp

    from repro.core.selfjoin import _merged_offset_tables

    rng = np.random.default_rng(41)
    pts = rng.uniform(0, 8, (300, 3))
    eps = 0.9
    index = build_grid_host(pts, eps)
    npts = index.num_points
    if merged:
        deltas, is_zero = _merged_offset_tables(index, unicomp)
    else:
        deltas, is_zero = _offset_tables(index, unicomp)
    from repro.core.grid import global_window_cap

    c = global_window_cap(index, merged)
    # ids == sorted position: the gid tie-break coincides with the
    # positional triangle, so totals must match the plain sweep
    ids = np.arange(npts, dtype=np.int32)
    outs = {}
    for method in ("reference", "kernel"):
        points_pad, qp = _fused_pad(index, q_size=npts, c=c, merged=merged,
                                    gid=jnp.asarray(ids))
        outs[method] = _fused_batch_run(
            index, points_pad, deltas, is_zero, 0, qp=qp, q_size=npts,
            c=c, unicomp=unicomp, keep_hits=True, method=method,
            merged=merged, gid_pairs=True)
    for a, b in zip(outs["reference"][3:7], outs["kernel"][3:7]):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    counts = np.asarray(outs["reference"][4])
    mult = 2 if unicomp else 1
    expect = brute_force_count(pts, eps)
    assert mult * int(counts.sum()) == expect


def test_join_route_span_holds_no_sync(monkeypatch):
    """A one-chip join decides its sweep from the index alone: the
    top-level ``selfjoin.route`` span opens once per join and no host wait
    on a device value (``selfjoin.sync``) runs inside it."""
    import contextlib

    from repro.core import selfjoin

    opened, stack = [], []
    real = selfjoin.span

    @contextlib.contextmanager
    def recording(name):
        opened.append((name, tuple(stack)))
        stack.append(name)
        try:
            with real(name) as sp:
                yield sp
        finally:
            stack.pop()

    monkeypatch.setattr(selfjoin, "span", recording)
    pts = skewed(seed=5)
    self_join(pts, 0.5, index=build_grid_host(pts, 0.5))
    self_join_batched(pts, 0.5, n_batches=3)
    routes = [outer for name, outer in opened if name == "selfjoin.route"]
    assert routes == [(), ()]
    assert any(name == "selfjoin.sync" for name, _ in opened)
    assert not [name for name, outer in opened
                if "selfjoin.route" in outer]


def test_every_launch_tiles_by_tq_default(monkeypatch):
    """Every fused launch -- the join's buckets and batches, the count
    sweeps, and each capacity class of a ``PreparedJoin`` -- runs the
    kernel at ``fused_join.TQ_DEFAULT`` rows a tile."""
    from repro.core.query_join import bucket_rows, prepare
    from repro.kernels import fused_join

    tiles = []
    real = fused_join.fused_join_hits

    def recording(*args, **kw):
        tiles.append(kw["tq"])
        return real(*args, **kw)

    monkeypatch.setattr(fused_join, "fused_join_hits", recording)
    pts = skewed(seed=9)
    index = build_grid_host(pts, 0.5)
    self_join(pts, 0.5, index=index)
    self_join_batched(pts, 0.5, index=index, n_batches=3)
    self_join(pts, 0.5, index=index, bucketed=False)
    self_join_count(pts, 0.5, index=index)
    self_join_count(pts, 0.5, index=index, query_batch=200)
    self_join_count_compact(pts, 0.5, index=index)
    n_join = len(tiles)
    pj = prepare(index)
    assert pj.bucketed
    pj.join(pts[:300])
    assert pj.warm(256) == bucket_rows(256)
    assert n_join > 6 and len(tiles) > n_join
    assert set(tiles) == {fused_join.TQ_DEFAULT}
