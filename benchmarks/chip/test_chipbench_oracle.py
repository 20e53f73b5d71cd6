"""The copied f64 reference against a full brute force (no chip)."""
import numpy as np
import pytest

import oracle


@pytest.mark.parametrize("dims,exclude,chunk", [
    (2, False, 1 << 22), (2, True, 37), (3, True, 1 << 22), (2, False, 1)])
def test_strip_scan_equals_brute_force(dims, exclude, chunk):
    rng = np.random.default_rng(dims)
    pts = rng.uniform(0, 10, (600, dims))
    qs = pts if exclude else rng.uniform(-1, 11, (150, dims))
    ex = np.arange(len(qs)) if exclude else None
    eps = 0.9
    band = 0.05                      # wide, so both sets differ
    strip = oracle.StripIndex(pts)
    r_hi = eps + band
    sure, maybe = strip.neighbour_keys(qs, (eps - band) ** 2, r_hi ** 2,
                                       r_hi, exclude=ex, chunk_pairs=chunk)
    want_sure, want_maybe = oracle.brute_keys(pts, qs, eps, band=band,
                                              exclude=ex)
    np.testing.assert_array_equal(sure, want_sure)
    np.testing.assert_array_equal(maybe, want_maybe)
    assert maybe.size > sure.size > 0


def test_reference_keys_wraps_the_strip_scan():
    rng = np.random.default_rng(5)
    pts = rng.uniform(0, 5, (300, 2))
    got = oracle.reference_keys(pts, pts[:40], 0.4, band=1e-6)
    want = oracle.brute_keys(pts, pts[:40], 0.4, band=1e-6)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(g, w)


def test_compare_keys_counts_each_fault():
    sure = np.array([1, 5, 9])
    maybe = np.array([1, 4, 5, 9])
    assert oracle.compare_keys(np.array([1, 5, 9]), sure, maybe) == {
        "missing": 0, "extra": 0, "duplicate": 0, "band": 0}
    r = oracle.compare_keys(np.array([9, 1, 4, 4, 7]), sure, maybe)
    assert r == {"missing": 1, "extra": 1, "duplicate": 1, "band": 1}


def test_f32_band_grows_with_magnitude():
    small = oracle.f32_band(np.array([[1.0, 1.0]]), 0.2)
    large = oracle.f32_band(np.array([[100.0, 1.0]]), 0.2)
    assert 0 < small < large < 1e-4
