"""``correct`` comes out false when the timed path is broken underneath,
and when the control stands in for the program (no chip: the harness's
look for a TPU is skipped, everything else of a run is driven)."""
import dataclasses
import os
import subprocess
import sys

import numpy as np
import pytest

import chipbench_fixture
import harness

SEED = 2 ** 33 + 17


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return chipbench_fixture.tiny_root(tmp_path_factory.mktemp("tiny"))


def _run(root, cell, **kw):
    return harness.run(root, cell, SEED, 0.6, False, require_tpu=False,
                       compile_cache=False, **kw)


def _half_pairs(pairs):
    return pairs[: pairs.shape[0] // 2]


def _alter_one(pairs):
    pairs = pairs.copy()
    pairs[0, 1] = (pairs[0, 1] + 1) % (pairs[:, 1].max() + 1)
    return pairs


@pytest.mark.parametrize("cell", ["syn2d.join"])
def test_join_sound_and_control(root, cell):
    out = _run(root, cell, control=True)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert all(c["value"] == 0 for c in out["checks"].values())
    assert (out["control_checks"]["missing"]
            + out["control_checks"]["extra"]) > 0


@pytest.mark.parametrize("fault", [_half_pairs, _alter_one])
def test_join_fault_is_not_correct(root, monkeypatch, fault):
    from repro.core import selfjoin

    real = selfjoin.self_join
    monkeypatch.setattr(selfjoin, "self_join",
                        lambda *a, **kw: fault(real(*a, **kw)))
    out = _run(root, "syn2d.join")
    assert not out["correct"]


def test_serve_sound_and_control(root):
    out = _run(root, "syn2d.serve", control=True)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] >= 5
    assert (out["control_checks"]["missing"]
            + out["control_checks"]["extra"]) > 0


def _serve_half(res):
    """Half of the batch left out: the second half of the queries get no
    answer (counts kept consistent, so only the reference sees it)."""
    keep = res.counts.shape[0] // 2
    counts = res.counts.copy()
    counts[keep:] = 0
    pairs = res.pairs[res.pairs[:, 0] < keep]
    return dataclasses.replace(res, counts=counts, pairs=pairs)


def _serve_alter(res):
    if res.pairs.shape[0] == 0:
        return res
    pairs = res.pairs.copy()
    pairs[0, 1] += 1
    return dataclasses.replace(res, pairs=pairs)


@pytest.mark.parametrize("fault", [_serve_half, _serve_alter])
def test_serve_fault_is_not_correct(root, monkeypatch, fault):
    from repro.core import query_join

    real = query_join.slice_result
    monkeypatch.setattr(query_join, "slice_result",
                        lambda *a, **kw: fault(real(*a, **kw)))
    out = _run(root, "syn2d.serve")
    assert not out["correct"]


def _cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "syn2d.join", "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result(root):
    r = _cli(root)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_exit_nonzero(tmp_path):
    root = chipbench_fixture.tiny_root(tmp_path, shrink=False)
    os.unlink(os.path.join(root, "src"))
    r = _cli(root, {"PYTHONPATH": ""})
    assert r.returncode != 0
    assert r.stdout.strip() == ""
