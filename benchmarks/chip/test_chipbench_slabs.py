"""The four-chip slab cell ``syn2d_slab4.join`` through the harness, on
four placeholder CPU devices in a subprocess (no chip), and its overlap
reader on synthetic device planes (a CPU trace has none)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

import harness
import tracesum

HERE = os.path.dirname(os.path.abspath(__file__))

RUNS = textwrap.dedent("""
    import json, os, sys
    sys.path.insert(0, {here!r})
    import chipbench_fixture, harness, progspans
    root = chipbench_fixture.tiny_root({tmp!r})
    chipbench_fixture.edit_json(
        os.path.join(root, 'benchmarks', 'chip', 'configs',
                     'syn2d_slab4.json'),
        {{'n_points': 8000, 'join_cut': 2, 'eps': 1.5}})

    def run(trace):
        return harness.run(root, 'syn2d_slab4.join', 2 ** 33 + 21, 0.8,
                           trace, require_tpu=False, compile_cache=False)

    out = {{'plain': run(False), 'traced': run(True)}}
    path = progspans.newest_trace(os.path.join(root, 'benchmarks', 'chip'))
    events, _, _, _ = progspans.load(path)
    out['spans'] = [e[:3] for e in events if e[0].startswith('selfjoin.')]
    from repro.core import distributed
    real = distributed.distributed_self_join

    def half(*a, **kw):
        pairs = real(*a, **kw)
        return pairs[: pairs.shape[0] // 2]

    distributed.distributed_self_join = half
    out['fault'] = run(False)
    print(json.dumps(out))
""")


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    tmp = str(tmp_path_factory.mktemp("slabs"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    r = subprocess.run([sys.executable, "-c",
                        RUNS.format(here=HERE, tmp=tmp)],
                       env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_slab_cell_is_correct_on_four_devices(runs):
    out = runs["plain"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["notes"]["slabs"] == 4 and out["notes"]["joins"] >= 1
    assert out["notes"]["compiles_in_window"] == 0
    assert set(out["metrics"]) == {"join_s", "setup_s"}


def test_slab_cell_traced_reads_the_slab_driver(runs):
    out = runs["traced"]
    assert out["correct"], out["checks"]
    m = out["metrics"]
    assert m["halo_ms.join_slabs"]["value"] > 0
    for name in ("sort_ms.join", "sync_wait_ms.join"):
        assert name in m, name
    notes = out["notes"]
    assert notes["halo_rows_per_join"] > 0
    assert notes["slabs_per_join"] == 4


def test_slab_spans_one_join_one_halo_and_nest(runs):
    joins = runs["traced"]["notes"]["joins"]
    spans = runs["spans"]
    count = {}
    for name, _, _ in spans:
        count[name] = count.get(name, 0) + 1
    # the setup join is outside the trace; every window join is whole
    assert count["selfjoin.join"] == count["selfjoin.halo"] == joins
    assert count["selfjoin.partition"] == count["selfjoin.sort"] == joins
    # the slabs' stages, driven in step on one thread, still nest
    open_ends = []
    for name, s, d in sorted(spans, key=lambda e: (e[1], -e[2])):
        while open_ends and open_ends[-1] <= s:
            open_ends.pop()
        assert not open_ends or s + d <= open_ends[-1], name
        open_ends.append(s + d)


def test_slab_cell_fault_is_not_correct(runs):
    assert not runs["fault"]["correct"]
    assert runs["fault"]["checks"]["missing"]["value"] > 0


def _planes(busy_per_device):
    host = (tracesum.HOST_PLANE, [("main", [
        (tracesum.WINDOW_SPAN, 0.0, 1000.0)])])
    return [host] + [
        (f"{tracesum.DEVICE_PREFIX}{k}",
         [(tracesum.OPS_LINE, [("op", s, d) for s, d in ivs])])
        for k, ivs in enumerate(busy_per_device)]


@pytest.mark.parametrize("busy, want", [
    ([[(0.0, 100.0)]] * 4, 4.0),                                  # at once
    ([[(100.0 * k, 100.0)] for k in range(4)], 1.0),               # in turn
    ([[(0.0, 200.0)], [(0.0, 200.0)], [(100.0, 100.0)], []], 2.5),
])
def test_slab_overlap_reads_busy_chips_at_once(busy, want):
    reader = harness._load_module(
        os.path.join(HERE, "metrics", "slab_overlap.join_slabs.py"),
        "slab_overlap_under_test")
    summary = tracesum.summarize(_planes(busy))
    assert reader.read({"summary": summary}) == pytest.approx(want)


def test_blocked_reference_is_the_oracle(tmp_path):
    """The slab driver's reference, taken in blocks along dimension 0,
    gives the oracle's sure and maybe sets over the whole set."""
    import numpy as np

    import oracle

    driver = harness._load_module(
        os.path.join(HERE, "drivers", "join_slabs.py"), "join_slabs_test")
    rng = np.random.default_rng(4)
    pts = rng.uniform(0, 1, size=(3000, 2)) * [40.0, 6.0]
    eps = 0.6
    band = oracle.f32_band(pts, eps)
    got = driver.reference_keys(pts, eps, band)
    want = oracle.reference_keys(pts, pts, eps, band=band,
                                 exclude=np.arange(pts.shape[0]))
    assert all(np.array_equal(g, w) for g, w in zip(got, want))
    assert want[0].size > 0
