"""Cell-run plan tests (DESIGN.md S11, ISSUE 9).

The run-loop kernel's soundness rests on one contract: every row of a run
shares its cell, and equal cell rank implies identical (win_start,
win_count) for ALL stencil offsets -- so gathering the run head's window
once and letting every row of the run refine against it is exact. These
tests prove the partitioning (exactness, per-tile reset, maximality), the
shared-descriptor contract on both sweep modes, bit-parity of the
run-loop kernel against the row-loop on the self-join and external-query
drivers, and that the C10 contract prover accepts healthy plans and
rejects corrupted ones.
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.analysis import contracts
from repro.core.grid import (build_grid_host, cell_run_plan,
                             range_window_descriptors_at, round_up,
                             window_descriptors_at)
from repro.core.selfjoin import (_merged_offset_tables, _offset_tables,
                                 _self_join_fused, dma_window_stats)

TQ = 128


def datasets():
    rng = np.random.default_rng(11)
    yield "uniform-2d", rng.uniform(0, 10, (500, 2)), 0.6
    centers = rng.uniform(0, 10, (8, 3))
    yield ("clustered-3d",
           centers[rng.integers(0, 8, 400)] + rng.normal(0, 0.1, (400, 3)),
           0.5)
    # coincident points: many rows share one cell AND one coordinate
    yield "coincident", np.repeat(rng.uniform(0, 5, (9, 2)), 30, axis=0), 0.7
    # all points in one cell: a single run per tile
    yield "all-one-cell", rng.uniform(4.0, 4.2, (200, 2)), 1.0
    # one point per cell: every run has length 1 (run-loop == row-loop)
    g = np.stack(np.meshgrid(np.arange(15.0), np.arange(15.0)),
                 axis=-1).reshape(-1, 2) * 3.0
    yield "all-distinct-cells", g, 0.9


def _plan_for(index, tq=TQ):
    rank = np.asarray(index.point_cell_rank)
    qp = round_up(int(index.num_points), tq)
    pos = np.minimum(np.arange(qp), index.num_points - 1)
    return cell_run_plan(rank[pos], tq), rank[pos], pos


def test_run_plan_partition_exact():
    """Runs cover every row exactly once, reset per tile, step by at most
    one, and break exactly where the cell identity changes (maximality)."""
    for name, pts, eps in datasets():
        index = build_grid_host(pts, eps)
        plan, ids, _ = _plan_for(index)
        qp = ids.shape[0]
        assert plan.run_ord.shape == (qp,), name
        assert int(plan.run_lengths.sum()) == qp, name
        assert plan.n_runs == plan.run_lengths.shape[0], name
        assert np.all(plan.run_lengths >= 1), name
        o = plan.run_ord.reshape(-1, TQ)
        assert np.all(o[:, 0] == 0), name
        d = np.diff(o, axis=1)
        assert np.all((d == 0) | (d == 1)), name
        # exact + maximal: a run boundary iff the cell id changes
        changed = ids.reshape(-1, TQ)[:, 1:] != ids.reshape(-1, TQ)[:, :-1]
        assert np.array_equal(d == 1, changed), name
        # per-tile ordinal count recomposes the global run count
        assert int((o.max(axis=1) + 1).sum()) == plan.n_runs, name


def test_run_plan_rows_share_windows():
    """The soundness contract: every row of a run has identical
    (win_start, win_count) for all offsets, on BOTH sweep modes."""
    for name, pts, eps in datasets():
        index = build_grid_host(pts, eps)
        plan, ids, pos = _plan_for(index)
        q_pos = jnp.asarray(pos, jnp.int32)
        for unicomp in (True, False):
            deltas, _ = _offset_tables(index, unicomp)
            ws, wc = window_descriptors_at(index, deltas, q_pos)
            dtab, _ = _merged_offset_tables(index, unicomp)
            mws, mwc, _ = range_window_descriptors_at(
                index, dtab[0], dtab[1], dtab[2], q_pos)
            # one global run key: (tile, ordinal) -- rows sharing it must
            # share every descriptor column
            tiles = np.arange(plan.run_ord.size) // TQ
            key = tiles.astype(np.int64) * (plan.run_ord.max() + 1) \
                + plan.run_ord
            for arr in (ws, wc, mws, mwc):
                a = np.asarray(arr)
                first = np.zeros(a.shape[1], np.int64)
                seen = {}
                for r, k in enumerate(key):
                    first[r] = seen.setdefault(int(k), r)
                assert np.array_equal(a, a[:, first]), (name, unicomp)


def test_run_plan_rejects_bad_shape():
    with pytest.raises(ValueError):
        cell_run_plan(np.zeros(100, np.int64), TQ)   # not a tile multiple
    with pytest.raises(ValueError):
        cell_run_plan(np.zeros(TQ, np.int64), 0)


def test_run_plan_property():
    """Hypothesis property: for ARBITRARY id sequences the plan is an
    exact partition whose boundaries are precisely the id changes."""
    hyp = pytest.importorskip("hypothesis")
    st = pytest.importorskip("hypothesis.strategies")

    @hyp.given(st.lists(st.integers(min_value=0, max_value=6),
                        min_size=1, max_size=64),
               st.sampled_from([1, 2, 4, 8, 16]))
    @hyp.settings(deadline=None, max_examples=200)
    def run(ids, tq):
        ids = np.asarray(ids, np.int64)
        qp = round_up(ids.size, tq)
        ids = np.concatenate([ids, np.full(qp - ids.size, ids[-1])])
        plan = cell_run_plan(ids, tq)
        assert int(plan.run_lengths.sum()) == qp
        o = plan.run_ord.reshape(-1, tq)
        assert np.all(o[:, 0] == 0)
        if tq > 1:
            d = np.diff(o, axis=1)
            ch = ids.reshape(-1, tq)[:, 1:] != ids.reshape(-1, tq)[:, :-1]
            assert np.array_equal(d == 1, ch)

    run()


@pytest.mark.parametrize("merged", [True, False])
def test_self_join_run_loop_parity(merged):
    """Run-loop vs row-loop: identical sorted pair sets through both the
    reference lowering and the Pallas kernel (interpret mode)."""
    for name, pts, eps in datasets():
        index = build_grid_host(pts, eps)
        for method in (None, "kernel"):
            row = _self_join_fused(index, unicomp=True, sort_result=True,
                                   merged=merged, method=method,
                                   run_loop=False)
            run = _self_join_fused(index, unicomp=True, sort_result=True,
                                   merged=merged, method=method,
                                   run_loop=True)
            assert np.array_equal(row, run), (name, merged, method)


def test_dma_window_stats_ledger():
    for name, pts, eps in datasets():
        index = build_grid_host(pts, eps)
        d = dma_window_stats(index)
        assert d["dma_windows_run"] <= d["dma_windows_row"], name
        assert d["reduction_factor"] >= 1.0, name
        assert d["dma_bytes_saved"] >= 0, name
        # the histogram accounts for every launched row
        rows_in_hist = sum(int(k) * v
                           for k, v in d["run_length_hist"].items())
        assert rows_in_hist >= index.num_points, name
        if name in ("coincident", "all-one-cell"):
            assert d["dma_windows_run"] < d["dma_windows_row"], name
        if name == "all-distinct-cells":
            # essentially all runs are length 1 (the run loop gathers what
            # the row loop does); only padding extends the final run
            assert d["run_length_hist"].get("1", 0) >= 200, name


def test_external_unsorted_duplicate_batch_regression():
    """Satellite regression (ISSUE 9): an UNSORTED duplicate-heavy
    external batch answers identically with cell-run batching on and off
    -- counts row-for-row, pairs bit-for-bit (sorted canonical order)."""
    from repro.core import query_join as qj

    rng = np.random.default_rng(23)
    pts = rng.uniform(0, 10, (800, 2))
    pts[:200] = rng.normal(5, 0.1, (200, 2))        # skew -> bucketed
    index = build_grid_host(pts, 0.5)
    pj_run = qj.prepare(index)                       # run_loop default on
    pj_row = qj.prepare(index, run_loop=False)       # unsorted oracle
    assert pj_run.run_loop and not pj_row.run_loop
    base = rng.uniform(-0.5, 10.5, (40, 2))
    queries = base[rng.integers(0, 40, 300)]         # duplicate-heavy
    queries[::13] = [5.0, 5.0]                       # coincident block
    for method in (None, "kernel"):
        a = pj_run.join(queries, method=method, with_stats=True)
        b = pj_row.join(queries, method=method, with_stats=True)
        assert np.array_equal(a.counts, b.counts), method
        assert np.array_equal(a.pairs, b.pairs), method
        assert a.candidates_checked == b.candidates_checked, method
    # one-shot wrapper rides the default (run_loop on) path
    c = qj.epsilon_join(queries, pts, 0.5)
    assert np.array_equal(c.counts, b.counts)
    assert np.array_equal(c.pairs, b.pairs)


def test_check_run_plan_contract():
    """C10: healthy plans (driver-composed AND injected) produce zero
    findings; an overlapping-run corruption is caught."""
    for name, pts, eps in datasets():
        index = build_grid_host(pts, eps)
        assert contracts.check_run_plan(index) == [], name
        plan, _, _ = _plan_for(index)
        assert contracts.check_run_plan(index, run_ord=plan.run_ord,
                                        tq=TQ, tag=name) == [], name
    index = build_grid_host(np.random.default_rng(4).uniform(
        0, 10, (500, 2)), 0.6)
    plan, _, _ = _plan_for(index)
    ro = plan.run_ord.reshape(-1, TQ).copy()
    t = int(np.flatnonzero(ro.max(axis=1) > 0)[0])
    ro[t][ro[t] >= 1] -= 1        # merge two different-cell runs
    found = contracts.check_run_plan(index, run_ord=ro.reshape(-1), tq=TQ,
                                     tag="bad")
    assert any(f.rule == "run-partition" for f in found)
    # non-monotone ordinals are rejected too
    ro2 = plan.run_ord.copy()
    ro2[TQ // 2] += 2
    found = contracts.check_run_plan(index, run_ord=ro2, tq=TQ, tag="skip")
    assert any(f.rule == "run-partition" for f in found)
