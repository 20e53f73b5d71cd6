"""Deterministic behaviour tests for the core self-join (the paper's system).

The oracle is the O(N^2) distance matrix; every implementation (grid join
with/without UNICOMP, batched driver, brute force, CPU R-tree, EGO) must
produce the same ordered-pair set -- the same validation the paper used
across its implementations ("we validated consistency ... by comparing the
total number of neighbors", SVI-B).

Hypothesis property tests live in test_selfjoin_properties.py (skipped when
hypothesis is absent); fused-kernel parity tests in test_fused_join.py.
"""
import numpy as np
import pytest

from repro.core.baselines import ego_join, rtree_join
from repro.core.brute import brute_force_count, brute_force_join
from repro.core.grid import build_grid, build_grid_host, masks_host
from repro.core.selfjoin import (
    JoinStats,
    _sort_pairs,
    per_point_neighbor_counts,
    range_query,
    self_join,
    self_join_batched,
    self_join_count,
)
from repro.core.stencil import stencil_offsets, unicomp_paper_visits


def oracle_pairs(pts, eps):
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    np.fill_diagonal(hit, False)
    i, j = np.nonzero(hit)
    out = np.stack([i, j], 1).astype(np.int32)
    return out[np.lexsort((out[:, 1], out[:, 0]))]


def test_join_matches_oracle_deterministic():
    rng = np.random.default_rng(2)
    for n in (2, 3, 5):
        pts = rng.uniform(0, 10, (200, n))
        eps = 1.0
        assert np.array_equal(self_join(pts, eps), oracle_pairs(pts, eps))


def test_unicomp_equals_full_stencil_deterministic():
    rng = np.random.default_rng(4)
    pts = rng.uniform(0, 10, (250, 3))
    a = self_join(pts, 0.9, unicomp=True)
    b = self_join(pts, 0.9, unicomp=False)
    assert np.array_equal(a, b)


def test_batched_invariant_to_batch_count_deterministic():
    rng = np.random.default_rng(6)
    pts = rng.uniform(0, 10, (300, 2))
    a = self_join(pts, 0.7)
    for nb in (2, 3, 5):
        assert np.array_equal(self_join_batched(pts, 0.7, n_batches=nb), a)


def test_result_symmetry_deterministic():
    """Euclidean distance is reflexive (paper SV-B): (p,q) <-> (q,p)."""
    rng = np.random.default_rng(8)
    pts = rng.uniform(0, 10, (300, 3))
    pairs = self_join(pts, 0.9)
    fwd = set(map(tuple, pairs))
    assert fwd == {(b, a) for a, b in fwd}


def test_baselines_agree():
    rng = np.random.default_rng(7)
    for n in (2, 3, 4):
        pts = rng.uniform(0, 10, (300, n))
        eps = 0.8
        expect = len(oracle_pairs(pts, eps))
        assert brute_force_count(pts, eps) == expect
        assert rtree_join(pts, eps) == expect
        assert ego_join(pts, eps) == expect
        assert self_join_count(pts, eps).total_pairs == expect
        _, rp = rtree_join(pts, eps, return_pairs=True)
        _, ep_ = ego_join(pts, eps, return_pairs=True)
        assert np.array_equal(rp, oracle_pairs(pts, eps))
        assert np.array_equal(ep_, oracle_pairs(pts, eps))
        assert np.array_equal(brute_force_join(pts, eps),
                              oracle_pairs(pts, eps))


def test_unicomp_halves_work():
    """Paper SV-B: UNICOMP reduces cells searched and distance calcs ~2x.

    Holds in the dense regime (several points per cell, most adjacent cells
    non-empty -- the paper's low-dimensionality setting); in sparse data the
    self-cell (never halved) dominates and the ratio drops below 2, which
    matches the paper's observed <2x on some datasets.
    """
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 10, (4000, 3))
    # the paper's per-cell stencil
    s_uni = self_join_count(pts, 1.0, unicomp=True, merge_last_dim=False)
    s_full = self_join_count(pts, 1.0, unicomp=False, merge_last_dim=False)
    assert s_uni.total_pairs == s_full.total_pairs
    # offsets: (3^n+1)/2 vs 3^n
    assert s_uni.offsets == (3**3 + 1) // 2
    assert s_full.offsets == 3**3
    ratio = s_full.candidates_checked / max(s_uni.candidates_checked, 1)
    assert 1.6 < ratio < 2.4
    cells_ratio = s_full.cells_visited / max(s_uni.cells_visited, 1)
    assert 1.6 < cells_ratio < 2.4


def test_paper_unicomp_rule_equivalent_to_half_stencil():
    """Alg. 2's odd/even rule and our lexicographic half-stencil both
    evaluate every unordered adjacent-cell pair exactly once."""
    for n in (1, 2, 3, 4):
        half = {tuple(o) for o in stencil_offsets(n, unicomp=True)}
        half.discard((0,) * n)
        # half-stencil: exactly one of {o, -o} kept
        for o in half:
            assert tuple(-np.array(o)) not in half
        full = {tuple(o) for o in stencil_offsets(n, unicomp=False)}
        assert len(half) == (len(full) - 1) // 2
        # paper rule: for every cell pair (c, c+o), exactly one endpoint
        # evaluates it
        rng = np.random.default_rng(n)
        for _ in range(20):
            c = rng.integers(0, 7, n)
            for o in full:
                if o == (0,) * n:
                    continue
                o = np.array(o)
                a_visits = tuple(o) in unicomp_paper_visits(c, n)
                b_visits = tuple(-o) in unicomp_paper_visits(c + o, n)
                assert a_visits ^ b_visits


def test_jit_grid_matches_host_grid():
    rng = np.random.default_rng(11)
    pts = rng.uniform(0, 20, (500, 3))
    h = build_grid_host(pts, 0.7)
    j = build_grid(pts, 0.7)
    nc = int(h.num_cells)
    assert int(j.num_cells) == nc
    assert np.array_equal(np.asarray(h.cell_keys[:nc]),
                          np.asarray(j.cell_keys[:nc]))
    assert np.array_equal(np.asarray(h.cell_count[:nc]),
                          np.asarray(j.cell_count[:nc]))
    assert int(h.max_per_cell) == int(j.max_per_cell)
    # points grouped identically (order within a cell may differ; compare
    # the sorted point ids per cell)
    for h_idx in (0, nc // 2, nc - 1):
        s, c = int(h.cell_start[h_idx]), int(h.cell_count[h_idx])
        a = np.sort(np.asarray(h.order[s:s + c]))
        s2, c2 = int(j.cell_start[h_idx]), int(j.cell_count[h_idx])
        b = np.sort(np.asarray(j.order[s2:s2 + c2]))
        assert np.array_equal(a, b)


def test_masks_host_prune_consistency():
    """The M_j arrays (paper SIV-C) contain exactly the non-empty per-dim
    coordinates."""
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 10, (200, 2))
    idx = build_grid_host(pts, 1.0)
    M = masks_host(idx)
    from repro.core.grid import cell_coords
    import jax.numpy as jnp

    coords = np.floor(
        (pts - (pts.min(0) - 1.0)) / 1.0).astype(np.int64)
    for j in range(2):
        assert set(M[j]) == set(np.unique(coords[:, j]))


def test_per_point_counts_and_range_query():
    rng = np.random.default_rng(13)
    pts = rng.uniform(0, 10, (400, 3))
    eps = 0.9
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    np.fill_diagonal(hit, False)
    assert np.array_equal(per_point_neighbor_counts(pts, eps), hit.sum(1))
    # external queries (not in the dataset)
    q = rng.uniform(-1, 11, (50, 3))
    dq = ((q[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    expect = (dq <= eps * eps).sum(1)
    got = range_query(q, pts, eps)
    assert np.array_equal(got, expect)


def test_compact_sweep_matches_dense():
    """Empty-neighbor compaction (beyond-paper opt): identical counts,
    gather traffic bounded by the exact live-query cap."""
    from repro.core.grid import build_grid_host
    from repro.core.selfjoin import (compact_cap, self_join_count_compact)

    rng = np.random.default_rng(23)
    for n, eps in ((2, 0.5), (4, 3.0), (5, 6.0)):
        pts = rng.uniform(0, 60, (3000, n))
        dense = self_join_count(pts, eps, unicomp=True)
        assert dense.total_pairs == brute_force_count(pts, eps), n
        comp = self_join_count_compact(pts, eps, unicomp=True)
        assert comp.total_pairs == dense.total_pairs, n
        comp_f = self_join_count_compact(pts, eps, unicomp=False)
        assert comp_f.total_pairs == dense.total_pairs, n
        idx = build_grid_host(pts, eps)
        assert compact_cap(idx, True) <= 3000


def test_per_point_counts_prebuilt_index_and_degenerates():
    """Satellite coverage: per_point_neighbor_counts against the oracle
    with a PREBUILT index, on skewed data, and in the no-neighbor case."""
    rng = np.random.default_rng(29)
    bg = rng.uniform(0, 10, (300, 2))
    cl = rng.normal(5.0, 0.1, (150, 2))
    pts = np.concatenate([bg, cl])
    eps = 0.5
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    np.fill_diagonal(hit, False)
    idx = build_grid_host(pts, eps)
    got = per_point_neighbor_counts(pts, eps, index=idx)
    assert np.array_equal(got, hit.sum(1))
    assert got.sum() == self_join_count(pts, eps, index=idx).total_pairs
    # isolated points: every degree is zero
    iso = np.array([[0.0, 0.0], [5.0, 5.0], [9.0, 9.0]])
    assert np.array_equal(per_point_neighbor_counts(iso, 1.0), [0, 0, 0])
    # coincident points count each other but never themselves
    dup = np.zeros((4, 3))
    assert np.array_equal(per_point_neighbor_counts(dup, 0.1), [3, 3, 3, 3])


def test_build_grid_requires_int64_keys():
    """Regression (satellite): with jax_enable_x64 off, a grid whose key
    space exceeds 2^31 cells would silently truncate keys to int32 (6-D
    key spaces alias); the builders must refuse instead. Grids UNDER the
    boundary now take the int32 fast path (key_dtype_for) and build fine
    without x64 — see tests/test_grid_keys.py for that half."""
    import jax
    import jax.numpy as jnp
    import pytest

    from repro.core.grid import build_grid_with_geometry, grid_geometry

    rng = np.random.default_rng(7)
    pts = rng.uniform(0, 100, (64, 6))
    pts[0] = 0.0
    pts[1] = 100.0                  # pin the extent: eps 2.9 -> ~3.0e9 cells
    jax.config.update("jax_enable_x64", False)
    try:
        with pytest.raises(RuntimeError, match="int64"):
            build_grid_host(pts, 2.9)
        # small grids no longer need x64 at all: int32 fast path
        assert build_grid_host(pts, 5.0).key_dtype == np.int32
        with pytest.raises(RuntimeError, match="jax_enable_x64"):
            gmin = jnp.asarray(pts.min(0) - 5.0, jnp.float32)
            dims = jnp.full((6,), 23, jnp.int32)
            build_grid_with_geometry(jnp.asarray(pts, jnp.float32), 5.0,
                                     gmin, dims)
    finally:
        jax.config.update("jax_enable_x64", True)
    # restored: the guarded builders work again and big grids are int64
    idx = build_grid_host(pts, 2.9)
    assert np.asarray(idx.cell_keys).dtype == np.int64
    g = grid_geometry(jnp.asarray(pts), 2.9)
    assert np.asarray(g[1]).dtype == np.int64


@pytest.mark.parametrize("entry", ["self_join", "self_join_batched",
                                   "self_join_count",
                                   "self_join_count_compact"])
def test_distance_impl_accepts_only_fused(entry):
    """The fused sweep is the only join path: each entry point runs it
    by default and by name, and refuses any other ``distance_impl``."""
    from repro.core import selfjoin

    fn = getattr(selfjoin, entry)
    pts = np.random.default_rng(19).uniform(0, 4, (60, 2))
    want = brute_force_count(pts, 0.6)
    for impl in ({}, {"distance_impl": "fused"}):
        got = fn(pts, 0.6, **impl)
        n = got.shape[0] if isinstance(got, np.ndarray) else got.total_pairs
        assert n == want, impl
    for bad in ("jnp", "pallas", "nope"):
        with pytest.raises(ValueError, match="distance_impl"):
            fn(pts, 0.6, distance_impl=bad)


def test_empty_and_tiny():
    pts = np.array([[0.0, 0.0], [10.0, 10.0]])
    assert self_join_count(pts, 1.0).total_pairs == 0
    assert self_join(pts, 1.0).shape == (0, 2)
    pts = np.array([[0.0, 0.0], [0.5, 0.0], [10.0, 10.0]])
    assert self_join_count(pts, 1.0).total_pairs == 2


def test_batched_more_batches_than_points():
    """n_batches > npts: the batch count clamps to the point count, so no
    empty trailing batch ever schedules a rounded-up query slice over pure
    padding rows. Pair sets match the oracle."""
    rng = np.random.default_rng(23)
    for npts in (1, 2, 3, 5):
        pts = rng.uniform(0, 2, (npts, 2))
        got = self_join_batched(pts, 0.8, n_batches=npts + 4)
        assert np.array_equal(got, oracle_pairs(pts, 0.8)), npts


def _emit_like_chunks(rng):
    """~200k pairs in three query-major chunks as the emit lays them out:
    each query's hits in a run, queries in a random order."""
    n_ids = 50_000
    chunks = []
    for _ in range(3):
        rows = np.sort(rng.integers(0, n_ids, 66_667))
        q = rng.permutation(n_ids)
        chunks.append(np.stack([q[rows], rng.integers(0, n_ids, rows.size)],
                               axis=1))
    return np.concatenate(chunks).astype(np.int32)


_SORT_CASES = {
    "empty": lambda rng: np.empty((0, 2), np.int32),
    "one_row": lambda rng: np.array([[7, 3]], np.int32),
    "emit_chunks": _emit_like_chunks,
    "max_ids": lambda rng: np.concatenate([
        rng.integers(0, 2**31, (5_000, 2)),
        [[2**31 - 1, 2**31 - 1], [2**31 - 1, 0], [0, 2**31 - 1], [0, 0]],
    ]).astype(np.int32),
    "shared_first_column": lambda rng: np.stack([
        rng.integers(0, 4, 20_000), rng.integers(0, 2**31, 20_000)],
        axis=1).astype(np.int32),
    "already_sorted": lambda rng: brute_force_join(
        rng.uniform(0, 10, (400, 2)), 1.0),
    "reversed": lambda rng: brute_force_join(
        rng.uniform(0, 10, (400, 2)), 1.0)[::-1],
}


@pytest.mark.parametrize("case", sorted(_SORT_CASES))
def test_sort_pairs_matches_lexsort(case):
    """The packed-key sort gives the (row, column) lexsort order bit for
    bit, as a fresh C-contiguous (K, 2) int32 array."""
    pairs = _SORT_CASES[case](np.random.default_rng(31))
    ref = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    got = _sort_pairs(pairs)
    assert got.dtype == np.int32
    assert got.shape == pairs.shape
    assert got.flags.c_contiguous
    assert np.array_equal(got, ref)
