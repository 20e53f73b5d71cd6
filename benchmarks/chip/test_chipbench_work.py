"""Roofline arithmetic and the peaks table (no chip)."""
import json

import pytest

import work

V5E = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_join_work_counts_each_pair_once():
    f, b = work.join_work(dims=2, n_queries=1000, n_pairs_unique=5000)
    assert f == 3 * 2 * 5000
    assert b == 4 * 2 * (1000 + 5000) + 4 * 1000


def test_t_min_names_its_bound():
    t, bound = work.t_min(3e6, 1e6, V5E)
    assert bound == "memory" and t == pytest.approx(1e6 / 819e9)
    t, bound = work.t_min(1e15, 1.0, V5E)
    assert bound == "compute" and t == pytest.approx(1e15 / 197e12)


def test_share_is_below_100_when_the_kernel_takes_at_least_t_min():
    f, b = work.join_work(2, 62_500, 780_000)
    t, _ = work.t_min(f, b, V5E)
    pct, _ = work.roofline_pct(f, b, t, V5E)
    assert pct == pytest.approx(100.0)
    pct, _ = work.roofline_pct(f, b, 10 * t, V5E)
    assert pct == pytest.approx(10.0)
    assert work.roofline_pct(f, b, 0.0, V5E)[0] is None


def test_peaks_lookup(tmp_path):
    p = work.peaks("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="TPU v9"):
        work.peaks("TPU v9")
    path = tmp_path / "peaks.json"
    path.write_text(json.dumps({"source": "x", "devices": {}}))
    with pytest.raises(KeyError):
        work.peaks("TPU v5 lite", str(path))
