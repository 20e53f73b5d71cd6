"""The dense prefix table in the merged window planners (grid.py).

Where prod(dims) is within ``grid._LOOKUP_MAX_CELLS``, the merged capacity
program and the per-cell window tables read span ranks and point bounds
from a table over the key space instead of binary-searching B. The
oracle is the search route itself: caps and (win_start, win_count,
win_cells) must be equal bit for bit, on natural and external geometry,
int32 and int64 keys, edge rows, empty rows and padded (slab) builds
whose sentinel cell sits at key prod(dims).
"""
import glob
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core import grid
from repro.core.grid import (build_grid, build_grid_with_geometry,
                             cell_window_caps_host, dense_prefix_table,
                             prefix_table_size, row_major_strides)
from repro.core.selfjoin import _merged_offset_tables, self_join
from repro.core.stencil import merged_stencil_offsets

BENCH = os.path.join(os.path.dirname(__file__), "..", "benchmarks", "chip")


def _uniform(n, box, seed):
    return np.random.default_rng(seed).uniform(0, 1, (n, len(box))) * box


def _index(case):
    """(index, whether ``cell_window_caps_host`` applies) of a case."""
    if case == "2d":
        pts = _uniform(1500, [3.0, 20.0], 1)
        return build_grid(pts, 0.3), True
    if case == "3d":
        pts = _uniform(1200, [2.0, 3.0, 4.0], 2)
        return build_grid(pts, 0.4), True
    if case == "int64_keys":
        # the legacy key dtype of build_grid_with_geometry (no key_dtype)
        pts = _uniform(900, [4.0, 6.0], 3)
        gmin, dims = grid.host_grid_geometry(pts, 0.5)
        index = build_grid_with_geometry(
            jnp.asarray(pts), 0.5, jnp.asarray(gmin), jnp.asarray(dims))
        assert index.cell_keys.dtype == jnp.int64
        return index, True
    if case == "row_edge":
        # external geometry: points on last coordinates 0 and dims-1, so
        # the merged span's row clamp binds on both sides
        rng = np.random.default_rng(4)
        pts = rng.uniform(0, 6, (500, 2))
        pts[:60, 1] = rng.uniform(0, 0.9, 60)
        pts[60:120, 1] = rng.uniform(5.1, 5.99, 60)
        index = build_grid_with_geometry(
            jnp.asarray(pts), 1.0, jnp.zeros(2), jnp.asarray([6, 6]),
            key_dtype=np.int32)
        return index, True
    if case == "empty_rows":
        # two bands of rows with whole empty rows of cells between them
        rng = np.random.default_rng(5)
        a = rng.uniform([0, 0], [2, 10], (400, 2))
        b = rng.uniform([6, 0], [8, 10], (400, 2))
        return build_grid(np.concatenate([a, b]), 0.5), True
    if case == "padded_slab":
        # a slab build: invalid slots carry the sentinel cell, key ==
        # prod(dims), which the merged spans of the top rows can reach
        pts = _uniform(800, [5.0, 5.0], 6)
        valid = np.arange(800) < 650
        gmin, dims = grid.host_grid_geometry(pts[valid], 0.5)
        pts[~valid] = pts[~valid] * 0 + 1e3
        index = build_grid_with_geometry(
            jnp.asarray(pts), 0.5, jnp.asarray(gmin), jnp.asarray(dims),
            jnp.asarray(valid),
            key_dtype=grid.device_key_dtype(dims, padded=True))
        keys = np.asarray(index.cell_keys)[:int(index.num_cells)]
        assert keys[-1] == int(np.prod(dims))
        return index, False
    raise AssertionError(case)


CASES = ["2d", "3d", "int64_keys", "row_edge", "empty_rows", "padded_slab",
         "above_budget"]


@pytest.mark.parametrize("unicomp", [True, False])
@pytest.mark.parametrize("case", CASES)
def test_prefix_route_equals_search_route(monkeypatch, case, unicomp):
    index, host_oracle = _index("2d" if case == "above_budget" else case)
    if case == "above_budget":
        # one cell under this grid's volume: the searches must stay
        monkeypatch.setattr(grid, "_LOOKUP_MAX_CELLS",
                            grid.grid_volume(index) - 1)
        assert prefix_table_size(index) == 0
        assert dense_prefix_table(index) is None
        _, prefix = grid.dispatch_window_caps(index, merged=True)
        assert prefix is None
        return
    size = prefix_table_size(index)
    assert size > grid.grid_volume(index) + 1
    assert size & (size - 1) == 0
    reduced, _, _ = merged_stencil_offsets(index.n_dims, unicomp=False)
    deltas = jnp.asarray(
        (reduced @ np.asarray(row_major_strides(index.dims))).astype(
            index.cell_keys.dtype))
    caps_s, none = grid._cell_window_caps_device(index, deltas, merged=True)
    caps_t, prefix = grid._cell_window_caps_device(index, deltas,
                                                   merged=True, size=size)
    assert none is None
    assert caps_t.dtype == caps_s.dtype
    assert np.array_equal(caps_t, caps_s)
    if host_oracle:
        ncells = int(index.num_cells)
        assert np.array_equal(np.asarray(caps_t)[:ncells],
                              cell_window_caps_host(index, merged=True))
    # the table's rows: the left search rank of every key, and its points
    rank_lt, point_lt = np.asarray(prefix).T
    keys = np.asarray(index.cell_keys)
    assert np.array_equal(rank_lt, np.searchsorted(keys, np.arange(size)))
    starts = grid.starts_ext(index)
    assert np.array_equal(point_lt, starts[rank_lt])
    dtab, _ = _merged_offset_tables(index, unicomp)
    search = grid._cell_window_table_device(index, dtab, None, merged=True)
    table = grid._cell_window_table_device(index, dtab, prefix, merged=True)
    for name, s, t in zip(("win_start", "win_count", "win_cells"),
                          search, table):
        assert t.dtype == s.dtype, name
        assert np.array_equal(t, s), name


def _join(route, dist, monkeypatch):
    pts = _uniform(2000, [4.0, 12.0], 7)
    eps = 0.3
    if route == "search":
        monkeypatch.setattr(grid, "_LOOKUP_MAX_CELLS", 0)
    if dist:
        from repro.core.distributed import distributed_self_join
        from repro.launch.mesh import make_slab_mesh

        return distributed_self_join(pts, eps, make_slab_mesh(1))
    return self_join(pts, eps, distance_impl="fused")


@pytest.mark.parametrize("path", ["one_chip", "distributed"])
def test_self_join_same_pairs_on_both_routes(monkeypatch, path):
    dist = path == "distributed"
    table = _join("table", dist, monkeypatch)
    search = _join("search", dist, monkeypatch)
    assert table.shape[0] > 0
    assert np.array_equal(table, search)


@pytest.mark.parametrize("route", ["table", "search"])
def test_plan_span_records_the_route(tmp_path, monkeypatch, route):
    """``lookup_table`` on ``selfjoin.plan``, read from a CPU profiler
    trace the way the chip benchmark reads span arguments."""
    monkeypatch.syspath_prepend(BENCH)
    import progspans

    pts = _uniform(1500, [5.0, 5.0], 8)
    if route == "search":
        monkeypatch.setattr(grid, "_LOOKUP_MAX_CELLS", 0)
    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            self_join(pts, 0.3, distance_impl="fused")
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    r = progspans.reduce(*progspans.load(path))
    assert r.count("selfjoin.plan") == 1
    assert r.arg("selfjoin.plan", "lookup_table") == (route == "table")
