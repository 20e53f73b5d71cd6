"""External-query epsilon join (core/query_join.py, DESIGN.md S5).

Parity oracle is the O(Q x N) brute-force distance matrix: counts AND
sorted pairs must bit-match for queries inside the indexed volume, outside
it, duplicated, and coinciding with indexed points. The serving property
(no per-request trace/compile) is asserted through the executable-cache
stats; the tiny-grid tests are the regression for the inverted
``clip(qcoords, 1, dims - 2)`` clamp of the original ``range_query``
(coordinate-space bounds masking in ``grid.external_window_descriptors``
replaced it).
"""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.grid import build_grid_host, build_grid_with_geometry
from repro.core.query_join import (
    PreparedJoin,
    bucket_rows,
    epsilon_join,
    executable_cache_stats,
    prepare,
)
from repro.core.selfjoin import range_query, self_join_count


def brute(queries, pts, eps):
    d2 = ((queries[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    counts = hit.sum(1).astype(np.int32)
    q, p = np.nonzero(hit)
    pairs = np.stack([q, p], 1).astype(np.int32)
    return counts, pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]


def workloads():
    rng = np.random.default_rng(42)
    pts2 = rng.uniform(0, 10, (500, 2))
    yield "inside-2d", pts2, 0.6, rng.uniform(0, 10, (80, 2))
    # queries straddling and far outside the indexed volume
    yield "outside-3d", rng.uniform(0, 10, (300, 3)), 1.0, \
        rng.uniform(-8, 18, (60, 3))
    # high-dimensional sparse regime
    yield "sparse-6d", rng.uniform(0, 40, (200, 6)), 6.0, \
        rng.uniform(-5, 45, (40, 6))
    # duplicate query points (identical rows must get identical answers)
    qd = rng.uniform(0, 10, (20, 2))
    yield "dup-queries-2d", pts2, 0.6, np.repeat(qd, 3, axis=0)
    # queries that ARE indexed points: external join has no self-exclusion,
    # so each query counts its coincident point
    yield "coincident-2d", pts2, 0.6, pts2[::7].copy()


def test_epsilon_join_matches_brute_force():
    for name, pts, eps, q in workloads():
        counts, pairs = brute(q, pts, eps)
        res = epsilon_join(q, pts, eps, with_stats=True)
        assert np.array_equal(res.counts, counts), name
        assert np.array_equal(res.pairs, pairs), name
        assert res.total == counts.sum(), name
        assert res.bucket_rows == bucket_rows(q.shape[0]), name
        # counts-only path agrees without materializing the hit set
        assert np.array_equal(
            epsilon_join(q, pts, eps, return_pairs=False).counts, counts), name


def test_emit_backends_agree():
    rng = np.random.default_rng(7)
    pts = rng.uniform(0, 10, (400, 3))
    q = rng.uniform(-1, 11, (70, 3))
    index = build_grid_host(pts, 0.9)
    pj = prepare(index)
    h = pj.join(q, emit="host")
    d = pj.join(q, emit="device")
    assert np.array_equal(h.counts, d.counts)
    assert np.array_equal(h.pairs, d.pairs)
    # both emits are query-major: identical row order even unsorted
    hu = pj.join(q, emit="host", sort_pairs=False)
    du = pj.join(q, emit="device", sort_pairs=False)
    assert np.array_equal(hu.pairs, du.pairs)


def test_pallas_kernel_external_matches_reference():
    """The Pallas kernel path (interpret off-TPU) with external=True."""
    rng = np.random.default_rng(11)
    pts = rng.uniform(0, 10, (300, 2))
    q = rng.uniform(-1, 11, (50, 2))
    index = build_grid_host(pts, 0.8)
    pj = prepare(index)
    ref = pj.join(q, method="reference")
    ker = pj.join(q, method="kernel")
    assert np.array_equal(ref.counts, ker.counts)
    assert np.array_equal(ref.pairs, ker.pairs)
    counts, pairs = brute(q, pts, 0.8)
    assert np.array_equal(ker.counts, counts)
    assert np.array_equal(ker.pairs, pairs)


def test_eps_override_and_validation():
    rng = np.random.default_rng(3)
    pts = rng.uniform(0, 10, (300, 2))
    q = rng.uniform(0, 10, (40, 2))
    index = build_grid_host(pts, 1.0)
    pj = prepare(index)
    # a smaller query radius than the build radius is exact
    counts, pairs = brute(q, pts, 0.5)
    res = pj.join(q, eps=0.5)
    assert np.array_equal(res.counts, counts)
    assert np.array_equal(res.pairs, pairs)
    # a larger radius cannot be served by the +/-1-cell stencil
    with pytest.raises(ValueError):
        pj.join(q, eps=1.5)
    with pytest.raises(ValueError):
        pj.join(q[:, :1])  # wrong dimensionality


def test_tiny_grid_clip_regression():
    """Grids with < 3 cells per dimension (regression for the inverted
    ``clip(qcoords, 1, dims - 2)``: with dims=2 the bounds invert and,
    key-space probing aside, offset deltas alias (radix-2 linearization),
    double-counting adjacent-cell neighbors)."""
    pts = np.array([[0.2, 0.2], [1.8, 0.3], [1.7, 1.6], [0.1, 1.9],
                    [1.0, 1.0], [0.2, 1.6]])
    for dims in ([2, 2], [2, 4], [4, 2]):
        eps = 1.5
        gmin = jnp.zeros(2, dtype=jnp.float64 if pts.dtype == np.float64
                         else jnp.float32)
        index = build_grid_with_geometry(
            jnp.asarray(pts), eps, gmin, jnp.asarray(dims, jnp.int64))
        q = np.array([[0.2, 1.2], [0.3, 0.3], [1.9, 1.9], [-0.5, 0.5],
                      [2.4, 0.1], [5.0, 5.0], [1.0, 2.9]])
        counts, pairs = brute(q, pts, eps)
        res = prepare(index).join(q)
        assert np.array_equal(res.counts, counts), dims
        assert np.array_equal(res.pairs, pairs), dims
        got = range_query(q, pts, eps, index=index)
        assert np.array_equal(got, counts), dims


def test_range_query_wrapper():
    rng = np.random.default_rng(13)
    pts = rng.uniform(0, 10, (400, 3))
    eps = 0.9
    q = rng.uniform(-1, 11, (50, 3))
    counts, pairs = brute(q, pts, eps)
    assert np.array_equal(range_query(q, pts, eps), counts)
    got_counts, got_pairs = range_query(q, pts, eps, return_pairs=True)
    assert np.array_equal(got_counts, counts)
    assert np.array_equal(got_pairs, pairs)


def test_bucket_rows():
    assert bucket_rows(0) == 128
    assert bucket_rows(1) == 128
    assert bucket_rows(128) == 128
    assert bucket_rows(129) == 256
    assert bucket_rows(300) == 512
    assert bucket_rows(512) == 512
    assert bucket_rows(513) == 1024


def test_no_retrace_across_requests():
    """The serve-path regression gate: once a bucket shape is warm, further
    requests (any size within the bucket, any query values, any eps <=
    build eps) must hit cached executables only."""
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 10, (600, 2))
    index = build_grid_host(pts, 0.7)
    pj = prepare(index)
    pj.join(rng.uniform(0, 10, (100, 2)))          # warm the 128-row bucket
    pj.join(rng.uniform(0, 10, (100, 2)), emit="device")
    pj.join(rng.uniform(0, 10, (100, 2)), return_pairs=False)
    mark = executable_cache_stats()
    # the default serve path runs the merged-range descriptors (S7)
    assert mark["external_range_windows"] >= 1
    for k in range(6):
        q = rng.uniform(-2, 12, (17 + 13 * k, 2))  # all inside the bucket
        pj.join(q)
        pj.join(q, emit="device")
        pj.join(q, return_pairs=False, eps=0.3 + 0.05 * k)
    assert executable_cache_stats() == mark
    # a NEW bucket shape compiles exactly once...
    pj.join(rng.uniform(0, 10, (200, 2)))
    grown = executable_cache_stats()
    assert (grown["external_range_windows"]
            == mark["external_range_windows"] + 1)
    # ...and is itself steady afterwards
    pj.join(rng.uniform(0, 10, (150, 2)))
    assert executable_cache_stats() == grown


def test_join_service_steady_state():
    from repro.launch.serve import JoinService

    rng = np.random.default_rng(9)
    pts = rng.uniform(0, 10, (800, 3))
    svc = JoinService(pts, 0.8)
    svc.warmup(64)
    svc.mark_steady()
    expect_total = 0
    for _ in range(5):
        q = rng.uniform(0, 10, (64, 3))
        res = svc.query(q)
        b, _ = brute(q, pts, 0.8)
        assert np.array_equal(res.counts, b)
        expect_total += int(b.sum())
    svc.assert_no_retrace()   # raises on any steady-state compile
    assert svc.total_neighbors == expect_total
    p50, p99 = svc.percentiles()
    assert 0 < p50 <= p99
    assert svc.requests == 5


@pytest.mark.parametrize("case", ["tpu-sparse", "tpu-dense", "cpu"])
def test_count_route_rule(case):
    """self_join_count's route is read off the index and the backend:
    'compact' on the TPU in the empty-neighbour regime (6-D uniform,
    nearly every probe misses), 'dense' on the TPU elsewhere and
    everywhere off the TPU. The route taken counts the oracle's total
    and logs itself in JoinStats.route."""
    from repro.core import selfjoin
    from repro.core.brute import brute_force_count

    rng = np.random.default_rng(21)
    if case == "tpu-dense":
        pts, eps = rng.uniform(0, 10, (400, 2)), 0.6
    else:
        pts, eps = rng.uniform(0, 60, (250, 6)), 7.0
    backend = "cpu" if case == "cpu" else "tpu"
    want = "compact" if case == "tpu-sparse" else "dense"
    index = build_grid_host(pts, eps)
    for unicomp in (True, False):
        for merged in (True, False):
            assert selfjoin._count_route(index, backend, unicomp=unicomp,
                                         merged=merged) == want
    run = (selfjoin.self_join_count_compact if want == "compact"
           else self_join_count)
    stats = run(pts, eps, index=index)
    assert stats.route == want
    assert stats.total_pairs == brute_force_count(pts, eps)


def test_epsilon_join_empty_query_batch():
    rng = np.random.default_rng(2)
    pts = rng.uniform(0, 10, (100, 2))
    res = epsilon_join(np.zeros((0, 2)), pts, 0.5)
    assert res.counts.shape == (0,)
    assert res.pairs.shape == (0, 2)


# ---------------------------------------------------------------------------
# Occupancy-bucketed serving (DESIGN.md S6): a skewed index routes request
# batches through per-capacity-class launches; answers must stay
# bit-identical to brute force and the steady state must stay retrace-free
# across arbitrary class mixes.
# ---------------------------------------------------------------------------

def skewed_index(seed=3, n_dims=2, eps=0.5):
    rng = np.random.default_rng(seed)
    bg = rng.uniform(0, 10, (500, n_dims))
    cl = rng.normal(5.0, 0.12, (260, n_dims))
    pts = np.concatenate([bg, cl])
    return pts, build_grid_host(pts, eps)


def test_bucketed_serving_matches_brute_force():
    pts, index = skewed_index()
    pj = prepare(index)
    assert pj.bucketed and len(pj.classes) > 1
    rng = np.random.default_rng(8)
    # mixes: inside the cluster (big class), background, outside the volume
    q = np.concatenate([rng.normal(5.0, 0.2, (30, 2)),
                        rng.uniform(-1, 11, (40, 2))])
    counts, pairs = brute(q, pts, 0.5)
    for kwargs in ({}, {"emit": "device"}, {"method": "kernel"}):
        res = pj.join(q, **kwargs)
        assert np.array_equal(res.counts, counts), kwargs
        assert np.array_equal(res.pairs, pairs), kwargs
    assert np.array_equal(pj.join(q, return_pairs=False).counts, counts)
    # host and device emits agree per class (sorted output is canonical)
    h = pj.join(q, emit="host")
    d = pj.join(q, emit="device")
    assert np.array_equal(h.pairs, d.pairs)
    # smaller query eps flows through the bucketed launches
    c2, p2 = brute(q, pts, 0.3)
    r2 = pj.join(q, eps=0.3)
    assert np.array_equal(r2.counts, c2)
    assert np.array_equal(r2.pairs, p2)


def test_bucketed_serving_no_retrace():
    """Once warmed, steady-state requests must not compile regardless of
    which capacity classes each request happens to populate. The device-
    emit scatter is exempt (result-size-bucketed, same rule as
    JoinService.assert_no_retrace)."""

    def freeze(stats):
        out = {k: v for k, v in stats.items()
               if k not in ("emit_pairs_device", "trace_events")}
        out["trace_events"] = {k: v for k, v in stats["trace_events"].items()
                               if k != "emit_pairs_device"}
        return out

    pts, index = skewed_index(seed=11)
    pj = prepare(index)
    assert pj.bucketed
    pj.warm(128)
    mark = executable_cache_stats()
    assert mark["window_caps"] >= 1
    rng = np.random.default_rng(5)
    for k in range(6):
        # different sizes, different class mixes (cluster-only,
        # background-only, mixed, all-miss)
        qs = [rng.normal(5.0, 0.1, (9 + 11 * k, 2)),
              rng.uniform(0, 10, (17 + 13 * k, 2)),
              rng.uniform(20, 30, (5, 2))]
        for q in qs:
            pj.join(q)
            pj.join(q, return_pairs=False, eps=0.3 + 0.02 * k)
            pj.join(q, emit="device")
    assert freeze(executable_cache_stats()) == freeze(mark)


def test_warm_covers_full_request_bucket():
    """Regression: warm(n) must cover EVERY request that lands in the same
    request bucket as n -- including one whose rows all fall in a single
    capacity class, which needs a class launch at the full bucket size
    (larger than any class launch a size-n request can need)."""

    def freeze(stats):
        out = {k: v for k, v in stats.items()
               if k not in ("emit_pairs_device", "trace_events")}
        out["trace_events"] = {k: v for k, v in stats["trace_events"].items()
                               if k != "emit_pairs_device"}
        return out

    pts, index = skewed_index(seed=23)
    pj = prepare(index)
    assert pj.bucketed
    pj.warm(64)                      # request bucket: 128 rows
    mark = executable_cache_stats()
    rng = np.random.default_rng(7)
    # 128 queries, every one inside the cluster -> one class at qp_b=128
    q = rng.normal(5.0, 0.1, (128, 2))
    pj.join(q)
    pj.join(q, return_pairs=False)
    assert freeze(executable_cache_stats()) == freeze(mark)


def test_bucketed_join_service_steady_state():
    from repro.launch.serve import JoinService

    pts, index = skewed_index(seed=17)
    svc = JoinService(pts, 0.5, index=index)
    assert svc.prepared.bucketed
    svc.warmup(64)
    svc.mark_steady()
    rng = np.random.default_rng(19)
    for _ in range(4):
        q = np.concatenate([rng.normal(5.0, 0.15, (20, 2)),
                            rng.uniform(0, 10, (44, 2))])
        res = svc.query(q)
        b, _ = brute(q, pts, 0.5)
        assert np.array_equal(res.counts, b)
    svc.assert_no_retrace()


def test_sharded_service_matches_single_index():
    """ShardedJoinService (DESIGN.md S3 serving mode): scatter-gather over
    per-slab indexes answers exactly like the single-index service --
    counts elementwise, pairs as the same sorted set with global point
    ids -- and the steady state never retraces."""
    from repro.launch.serve import JoinService, ShardedJoinService

    rng = np.random.default_rng(31)
    pts = rng.uniform(0, 40, (2500, 3))
    eps = 1.5
    single = JoinService(pts, eps, return_pairs=True)
    sharded = ShardedJoinService(pts, eps, 3, return_pairs=True)
    qs = [np.random.default_rng(seed).uniform(-2, 42, (100, 3))
          for seed in (0, 1)]
    # the executable caches are module-level and shared across services:
    # answer the single-index reference BEFORE marking steady state, or its
    # compilations would trip the sharded service's no-retrace gate
    refs = [single.query(q) for q in qs]
    sharded.warmup(128)
    sharded.mark_steady()
    for q, r1 in zip(qs, refs):
        r2 = sharded.query(q)
        assert np.array_equal(r1.counts, r2.counts)
        p1 = r1.pairs[np.lexsort((r1.pairs[:, 1], r1.pairs[:, 0]))]
        assert np.array_equal(p1, r2.pairs)
    sharded.assert_no_retrace()
    # more slabs than points: empty slabs are skipped, answers unchanged
    tiny = ShardedJoinService(pts[:2], eps, 5, return_pairs=True)
    ref = JoinService(pts[:2], eps, return_pairs=True).query(q[:16])
    got = tiny.query(q[:16])
    assert np.array_equal(ref.counts, got.counts)
