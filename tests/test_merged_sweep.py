"""Merged-range sweep (DESIGN.md S7): 3^n -> 3^(n-1) last-dimension
stencil merging.

The parity oracle is the retained per-cell sweep (``merge_last_dim=False``)
and the brute-force join: pair SETS must be identical (sorted), work
counters (cells_visited / candidates_checked) must match counter-for-
counter, and only ``JoinStats.n_offsets`` may shrink. Boundary-heavy
grids -- points on the dataset edge, a collapsed (3-cell) dimension,
coincident points, and externally supplied geometry with < 3 cells in a
dimension -- exercise the row-clamp of the range probes; the kernel's
last-dimension boundary mask is unit-tested directly with a fabricated
wrapped window.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.brute import brute_force_join
from repro.core.grid import (
    build_grid_host,
    build_grid_with_geometry,
    cell_window_caps,
    global_window_cap,
    occupancy_plan,
    point_last_coords,
    range_window_descriptors_at,
    row_major_strides,
    window_descriptors_at,
)
from repro.core.selfjoin import (
    _merged_offset_tables,
    per_point_neighbor_counts,
    self_join,
    self_join_batched,
    self_join_count,
)
from repro.core.stencil import merged_stencil_offsets, stencil_offsets


def sorted_pairs(p):
    return p[np.lexsort((p[:, 1], p[:, 0]))]


def brute(queries, pts, eps):
    d2 = ((queries[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    counts = hit.sum(1).astype(np.int32)
    q, p = np.nonzero(hit)
    pairs = np.stack([q, p], 1).astype(np.int32)
    return counts, sorted_pairs(pairs)


# ---------------------------------------------------------------------------
# Stencil algebra
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 4, 6])
@pytest.mark.parametrize("unicomp", [True, False])
def test_merged_stencil_covers_per_cell_stencil(n, unicomp):
    """Expanding every reduced offset over its [lo, hi] last-dim span must
    reproduce the per-cell stencil exactly (no cell missed, none doubled).
    """
    reduced, lo, hi = merged_stencil_offsets(n, unicomp)
    if unicomp:
        assert reduced.shape[0] == (3 ** (n - 1) - 1) // 2 + 1
    else:
        assert reduced.shape[0] == 3 ** (n - 1)
    assert np.all(reduced[:, -1] == 0)
    assert np.all(reduced[0] == 0) and np.all(lo <= hi)
    expanded = set()
    for o, l, h in zip(reduced, lo, hi):
        for d in range(int(l), int(h) + 1):
            cell = tuple(o[:-1]) + (d,)
            assert cell not in expanded, cell
            expanded.add(cell)
    flat = {tuple(o) for o in stencil_offsets(n, unicomp)}
    assert expanded == flat


def test_merged_descriptors_equal_per_cell_union():
    """Per (reduced offset, query): the merged window must be exactly the
    concatenation of the three per-cell windows -- same total length, same
    live-cell count, same start (windows are spans of points_sorted)."""
    rng = np.random.default_rng(17)
    pts = rng.uniform(0, 10, (400, 3))
    index = build_grid_host(pts, 0.9)
    npts = index.num_points
    strides = np.asarray(row_major_strides(index.dims))
    reduced, lo, hi = merged_stencil_offsets(3, unicomp=False)
    q_pos = jnp.arange(npts, dtype=jnp.int32)
    dtab, _ = _merged_offset_tables(index, unicomp=False)
    ws, wc, wcells = range_window_descriptors_at(
        index, dtab[0], dtab[1], dtab[2], q_pos)
    for k, o in enumerate(reduced):
        parts = []
        for d in (-1, 0, 1):
            cell = np.array(o)
            cell[-1] = d
            delta = jnp.asarray([int(cell @ strides)])
            s, c = window_descriptors_at(index, delta, q_pos)
            parts.append((np.asarray(s)[0], np.asarray(c)[0]))
        total = sum(c for _, c in parts)
        ncells = sum((c > 0).astype(int) for _, c in parts)
        assert np.array_equal(np.asarray(wc)[k], total), k
        assert np.array_equal(np.asarray(wcells)[k], ncells), k
        # live merged windows start at the first live per-cell window
        live = np.asarray(wc)[k] > 0
        first = np.where(parts[0][1] > 0, parts[0][0],
                         np.where(parts[1][1] > 0, parts[1][0],
                                  parts[2][0]))
        assert np.array_equal(np.asarray(ws)[k][live], first[live])


def test_point_last_coords_matches_float_cell_coords():
    rng = np.random.default_rng(3)
    pts = rng.uniform(-5, 5, (300, 4))
    index = build_grid_host(pts, 0.8)
    lc = np.asarray(point_last_coords(index))
    ps = np.asarray(index.points_sorted)
    expect = np.floor(
        (ps[:, -1] - np.asarray(index.grid_min)[-1]) / 0.8).astype(np.int64)
    assert np.array_equal(lc, expect)


# ---------------------------------------------------------------------------
# Pair-set parity on boundary-heavy grids
# ---------------------------------------------------------------------------

def boundary_datasets():
    rng = np.random.default_rng(29)
    # points ON the dataset min/max edges: their cells sit at coordinate 1
    # and dims-2, so range probes reach grid rows 0 and dims-1
    edge = rng.uniform(0, 8, (300, 3))
    edge[:40] = np.round(edge[:40] / 8) * 8            # snap to 0 / 8
    yield "edge-3d", edge, 0.9
    # a collapsed dimension: every point shares the last coordinate, so
    # the last-dim axis has the minimum 3 cells and every query's row
    # clamp is load-bearing
    flat = rng.uniform(0, 10, (250, 3))
    flat[:, -1] = 4.0
    yield "collapsed-last-3d", flat, 0.7
    # collapsed FIRST dimension (merging acts on the last)
    flat2 = flat.copy()
    flat2[:, 0] = 2.0
    flat2[:, -1] = rng.uniform(0, 10, 250)
    yield "collapsed-first-3d", flat2, 0.7
    # coincident points: zero-distance pairs, duplicate keys
    dup = rng.integers(0, 3, (150, 3)).astype(np.float64)
    yield "coincident-3d", dup, 0.5
    # 1-D data: the reduced stencil degenerates to ONE range probe
    yield "line-1d", rng.uniform(0, 50, (400, 1)), 0.8
    # empty-neighbor-heavy 6-D
    yield "sparse-6d", rng.uniform(0, 60, (220, 6)), 7.0


@pytest.mark.parametrize("unicomp", [True, False])
def test_merged_pair_set_identical_to_oracle(unicomp):
    for name, pts, eps in boundary_datasets():
        index = build_grid_host(pts, eps)
        a = brute_force_join(pts, eps)
        m = self_join(pts, eps, unicomp=unicomp, index=index,
                      distance_impl="fused", merge_last_dim=True)
        u = self_join(pts, eps, unicomp=unicomp, index=index,
                      distance_impl="fused", merge_last_dim=False)
        assert np.array_equal(m, u), name
        assert np.array_equal(m, a), name


def test_merged_counters_and_n_offsets():
    """Acceptance gate: the merged sweep executes 3^(n-1) offsets (UNICOMP
    correspondingly reduced), asserted via JoinStats.n_offsets, with
    cells/candidates counters identical to the per-cell oracle."""
    for name, pts, eps in boundary_datasets():
        n = pts.shape[1]
        index = build_grid_host(pts, eps)
        for unicomp, n_red in ((True, (3 ** (n - 1) - 1) // 2 + 1),
                               (False, 3 ** (n - 1))):
            m = self_join_count(pts, eps, unicomp=unicomp, index=index,
                                distance_impl="fused", merge_last_dim=True)
            u = self_join_count(pts, eps, unicomp=unicomp, index=index,
                                distance_impl="fused", merge_last_dim=False)
            assert m.n_offsets == n_red, (name, unicomp)
            assert u.n_offsets == ((3 ** n + 1) // 2 if unicomp else 3 ** n)
            assert m.total_pairs == u.total_pairs, name
            assert m.cells_visited == u.cells_visited, name
            assert m.candidates_checked == u.candidates_checked, name
            s = self_join_count(pts, eps, unicomp=unicomp, index=index,
                                distance_impl="fused", merge_last_dim=True,
                                bucketed=False)
            assert (s.total_pairs, s.cells_visited, s.candidates_checked,
                    s.n_offsets) == (m.total_pairs, m.cells_visited,
                                     m.candidates_checked, n_red), name


def test_merged_unicomp_equivalent_to_full():
    """UNICOMP-equivalence under merging: the reduced half-stencil with
    the merged zero-span [0, +1] emits the same pair set as the full
    merged sweep and as the unmerged UNICOMP sweep."""
    rng = np.random.default_rng(41)
    pts = rng.uniform(0, 10, (350, 3))
    index = build_grid_host(pts, 0.9)
    uni_m = self_join(pts, 0.9, unicomp=True, index=index,
                      distance_impl="fused", merge_last_dim=True)
    full_m = self_join(pts, 0.9, unicomp=False, index=index,
                       distance_impl="fused", merge_last_dim=True)
    uni_u = self_join(pts, 0.9, unicomp=True, index=index,
                      distance_impl="fused", merge_last_dim=False)
    assert np.array_equal(uni_m, full_m)
    assert np.array_equal(uni_m, uni_u)


def test_merged_batched_and_bucketed():
    rng = np.random.default_rng(31)
    bg = rng.uniform(0, 10, (500, 2))
    cl = rng.normal(5.0, 0.12, (260, 2))
    pts = np.concatenate([bg, cl])
    index = build_grid_host(pts, 0.5)
    assert occupancy_plan(index, merged=True).n_buckets > 1
    a = brute_force_join(pts, 0.5)
    for nb in (2, 4):
        b = self_join_batched(pts, 0.5, n_batches=nb, index=index,
                              distance_impl="fused", merge_last_dim=True)
        assert np.array_equal(a, b), nb
    s = self_join(pts, 0.5, index=index, distance_impl="fused",
                  merge_last_dim=True, bucketed=False)
    assert np.array_equal(a, s)


def test_merged_occupancy_plan_bounds_windows():
    """Merged capacity classes really bound every member row's merged
    windows, and the merged global capacity bounds the per-cell one by at
    most the 3-cell union."""
    rng = np.random.default_rng(53)
    pts = np.concatenate([rng.uniform(0, 10, (400, 2)),
                          rng.normal(5.0, 0.15, (300, 2))])
    index = build_grid_host(pts, 0.5)
    caps = cell_window_caps(index, merged=True)
    caps_flat = cell_window_caps(index, merged=False)
    assert np.all(caps >= caps_flat)          # union >= largest member
    assert np.all(caps <= 3 * np.maximum(caps_flat, 1))
    assert global_window_cap(index, merged=True) >= int(caps.max())
    plan = occupancy_plan(index, merged=True)
    assert sum(plan.hist.values()) == index.num_points
    rank = np.asarray(index.point_cell_rank)
    if plan.sel[0] is not None:
        for cap, sel in zip(plan.caps, plan.sel):
            assert caps[rank[sel]].max() <= cap
    # merged and per-cell plans are cached independently
    assert occupancy_plan(index, merged=True) is plan
    assert occupancy_plan(index) is not plan


# ---------------------------------------------------------------------------
# Custom geometry (< 3 cells in a dimension) and the kernel boundary mask
# ---------------------------------------------------------------------------

def test_merged_external_tiny_grid_dims_under_3():
    """External-query merging on grids with < 3 cells per dimension (only
    reachable through externally supplied geometry): the last-dim span
    clamp must prevent the range probe from wrapping across grid rows --
    with dims[-1] = 2 an unclamped [base-1, base+1] span would pull an
    ADJACENT (stencil-covered) cell in twice and double-count."""
    from repro.core.query_join import prepare

    pts = np.array([[0.2, 0.2], [1.8, 0.3], [1.7, 1.6], [0.1, 1.9],
                    [1.0, 1.0], [0.2, 1.6]])
    q = np.array([[0.2, 1.2], [0.3, 0.3], [1.9, 1.9], [-0.5, 0.5],
                  [2.4, 0.1], [5.0, 5.0], [1.0, 2.9]])
    for dims in ([2, 2], [2, 4], [4, 2], [3, 2]):
        eps = 1.5
        gmin = jnp.zeros(2, dtype=jnp.float64)
        index = build_grid_with_geometry(
            jnp.asarray(pts), eps, gmin, jnp.asarray(dims, jnp.int64))
        counts, pairs = brute(q, pts, eps)
        res = prepare(index, merge_last_dim=True).join(q)
        assert np.array_equal(res.counts, counts), dims
        assert np.array_equal(res.pairs, pairs), dims
        oracle = prepare(index, merge_last_dim=False).join(q)
        assert np.array_equal(res.counts, oracle.counts), dims
        assert np.array_equal(res.pairs, oracle.pairs), dims


def test_merged_selfjoin_custom_geometry_edge_rows():
    """Self-join under externally supplied geometry whose points sit on
    grid row 0 / dims-1 (no eps margin): the descriptor row clamp is what
    keeps the merged sweep exact here."""
    rng = np.random.default_rng(61)
    pts = rng.uniform(0, 6, (300, 2))
    eps = 1.0
    gmin = jnp.zeros(2, dtype=jnp.float64)
    dims = jnp.asarray([6, 6], jnp.int64)   # coords span [0, 5]: edge rows
    index = build_grid_with_geometry(jnp.asarray(pts), eps, gmin, dims)
    for unicomp in (True, False):
        m = self_join(pts, eps, unicomp=unicomp, index=index,
                      distance_impl="fused", merge_last_dim=True)
        u = self_join(pts, eps, unicomp=unicomp, index=index,
                      distance_impl="fused", merge_last_dim=False)
        assert np.array_equal(m, u), unicomp
        _, bp = brute(pts, pts, eps)
        bp = bp[bp[:, 0] != bp[:, 1]]
        assert np.array_equal(m, bp), unicomp


@pytest.mark.parametrize("method", ["reference", "kernel"])
def test_kernel_boundary_mask_kills_wrapped_candidates(method):
    """Unit test of the kernel-side |cand_last - q_last| <= 1 mask: feed a
    fabricated window whose tail rows carry a last-dim cell coordinate 2
    rows away (the wrapped-row signature). With merged=True those rows
    must be masked even though they pass the epsilon threshold; with
    merged=False (coordinate lane absent) they count."""
    from repro.kernels import ops
    from repro.kernels.fused_join import NP_PAD

    tq = 128
    c = 8
    n = 2
    pts = np.zeros((16 + c, NP_PAD))
    pts[:, :n] = 0.05                       # all points within eps of query
    pts[:, n] = 1.0                         # last-dim cell coord lane
    pts[4:8, n] = 3.0                       # "wrapped": |3 - 1| = 2
    q = np.zeros((tq, NP_PAD))
    q[0, :n] = 0.0
    q[0, n] = 1.0                           # query's last-dim cell coord
    ws = np.zeros((1, tq), np.int32)
    wc = np.zeros((1, tq), np.int32)
    wc[0, 0] = 8                            # one live window: rows 0..7
    iz = np.zeros(1, np.int32)
    qpos = np.full(tq, 1 << 20, np.int32)   # external-style: no self mask
    kw = dict(c=c, n_real=n, unicomp=False, external=True, tq=tq,
              method=method)
    _, counts_m, _ = ops.fused_join_hits(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(ws), jnp.asarray(wc),
        jnp.asarray(iz), jnp.asarray(qpos), 0.5, merged=True, **kw)
    _, counts_u, _ = ops.fused_join_hits(
        jnp.asarray(pts), jnp.asarray(q), jnp.asarray(ws), jnp.asarray(wc),
        jnp.asarray(iz), jnp.asarray(qpos), 0.5, merged=False, **kw)
    assert int(np.asarray(counts_m)[0]) == 4   # wrapped rows masked
    assert int(np.asarray(counts_u)[0]) == 8   # lane ignored when unmerged


# ---------------------------------------------------------------------------
# Serving path (PreparedJoin / JoinService) under merging
# ---------------------------------------------------------------------------

def test_merged_serving_parity_and_no_retrace():
    from repro.core.query_join import executable_cache_stats, prepare
    from repro.launch.serve import JoinService

    rng = np.random.default_rng(7)
    bg = rng.uniform(0, 10, (500, 2))
    cl = rng.normal(5.0, 0.12, (260, 2))
    pts = np.concatenate([bg, cl])
    index = build_grid_host(pts, 0.5)
    pj = prepare(index, merge_last_dim=True)
    po = prepare(index, merge_last_dim=False)
    assert pj.merged and not po.merged
    assert pj.n_offsets == 3 and po.n_offsets == 9
    q = np.concatenate([rng.normal(5.0, 0.2, (30, 2)),
                        rng.uniform(-1, 11, (40, 2))])
    counts, pairs = brute(q, pts, 0.5)
    rm, ro = pj.join(q), po.join(q)
    assert np.array_equal(rm.counts, counts)
    assert np.array_equal(rm.pairs, pairs)
    assert np.array_equal(ro.counts, counts)
    assert np.array_equal(ro.pairs, pairs)
    # steady state through JoinService stays retrace-free with merged
    # descriptors (the `make verify` gate's pytest twin)
    svc = JoinService(pts, 0.5, index=index)
    assert svc.prepared.merged
    svc.warmup(64)
    svc.mark_steady()
    for _ in range(4):
        qq = np.concatenate([rng.normal(5.0, 0.15, (20, 2)),
                             rng.uniform(0, 10, (44, 2))])
        res = svc.query(qq)
        b, _ = brute(qq, pts, 0.5)
        assert np.array_equal(res.counts, b)
    svc.assert_no_retrace()
    assert "external_range_windows" in executable_cache_stats()


def test_merged_external_1d():
    """Regression: 1-D external queries through the merged default (the
    reduced stencil degenerates to one range probe and the row vector is
    zero-width -- the zero last-coordinate column must still appear)."""
    from repro.core.query_join import epsilon_join

    rng = np.random.default_rng(83)
    pts = rng.uniform(0, 50, (200, 1))
    q = rng.uniform(-2, 52, (17, 1))
    counts, pairs = brute(q, pts, 0.5)
    res = epsilon_join(q, pts, 0.5)
    assert res.n_offsets == 1
    assert np.array_equal(res.counts, counts)
    assert np.array_equal(res.pairs, pairs)
    oracle = epsilon_join(q, pts, 0.5, merge_last_dim=False)
    assert np.array_equal(oracle.counts, counts)


def test_per_point_counts_merged_matches_oracle():
    for name, pts, eps in boundary_datasets():
        index = build_grid_host(pts, eps)
        m = per_point_neighbor_counts(pts, eps, index=index,
                                      merge_last_dim=True)
        u = per_point_neighbor_counts(pts, eps, index=index,
                                      merge_last_dim=False)
        assert np.array_equal(m, u), name
