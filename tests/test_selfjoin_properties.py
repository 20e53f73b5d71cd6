"""Hypothesis property tests for the self-join (oracle = O(N^2) matrix).

Separated from test_selfjoin.py so the deterministic suite still collects
when hypothesis is not installed (the seed environment); with hypothesis
present these run as before. ``pytest.importorskip`` keeps the split honest:
this module skips, nothing else does.
"""
import numpy as np
import pytest

pytest.importorskip("hypothesis")

from hypothesis import given, settings, strategies as st  # noqa: E402

from repro.core.selfjoin import self_join, self_join_batched  # noqa: E402


def oracle_pairs(pts, eps):
    d2 = ((pts[:, None, :] - pts[None, :, :]) ** 2).sum(-1)
    hit = d2 <= eps * eps
    np.fill_diagonal(hit, False)
    i, j = np.nonzero(hit)
    out = np.stack([i, j], 1).astype(np.int32)
    return out[np.lexsort((out[:, 1], out[:, 0]))]


@st.composite
def point_sets(draw):
    n = draw(st.integers(2, 5))
    npts = draw(st.integers(2, 120))
    scale = draw(st.sampled_from([1.0, 10.0, 100.0]))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    kind = draw(st.sampled_from(["uniform", "clustered", "degenerate"]))
    if kind == "uniform":
        pts = rng.uniform(0, scale, (npts, n))
    elif kind == "clustered":
        centers = rng.uniform(0, scale, (max(npts // 10, 1), n))
        pts = centers[rng.integers(0, len(centers), npts)] + rng.normal(
            0, scale * 0.01, (npts, n))
    else:  # many duplicate coordinates
        pts = rng.integers(0, 3, (npts, n)).astype(np.float64) * scale * 0.1
    eps = draw(st.sampled_from([0.05, 0.2, 0.5])) * scale
    return pts, eps


@pytest.mark.parametrize("unicomp", [True, False])
@settings(max_examples=20, deadline=None)
@given(point_sets())
def test_join_matches_oracle(unicomp, data):
    """The fused gather-refine join against the O(N^2) oracle, on the
    UNICOMP half stencil and on the full one."""
    pts, eps = data
    expect = oracle_pairs(pts, eps)
    got = self_join(pts, eps, unicomp=unicomp)
    assert np.array_equal(got, expect)


@settings(max_examples=15, deadline=None)
@given(point_sets())
def test_unicomp_equals_full_stencil(data):
    pts, eps = data
    a = self_join(pts, eps, unicomp=True)
    b = self_join(pts, eps, unicomp=False)
    assert np.array_equal(a, b)


@settings(max_examples=10, deadline=None)
@given(point_sets(), st.integers(2, 5))
def test_batched_invariant_to_batch_count(data, nb):
    pts, eps = data
    a = self_join_batched(pts, eps, n_batches=nb)
    b = self_join(pts, eps)
    assert np.array_equal(a, b)


@settings(max_examples=10, deadline=None)
@given(point_sets())
def test_result_symmetry(data):
    """Euclidean distance is reflexive (paper SV-B): (p,q) <-> (q,p)."""
    pts, eps = data
    pairs = self_join(pts, eps)
    fwd = set(map(tuple, pairs))
    assert fwd == {(b, a) for a, b in fwd}
