"""The program's host spans: the reduction on a synthetic event list, and
the spans themselves on real CPU profiler traces of the fused self-join
and of the batching service (no chip)."""
import glob
import json
import os
import warnings

import numpy as np
import pytest

import chipbench_fixture
import harness
import progspans


def _reduce(events, busy=()):
    return progspans.reduce(events, list(busy), 100.0, 1000.0)


def test_self_time_and_argument_sums():
    events = [
        ("serve.launch", 100.0, 400.0, {"rows": 64, "parts": 2}),
        ("query.sync", 150.0, 100.0, {}),
        ("np.asarray(jax.Array)", 160.0, 80.0, {}),
        ("query.launch", 300.0, 50.0, {}),
        ("query.launch", 360.0, 50.0, {}),
        ("serve.launch", 600.0, 100.0, {"rows": 32, "parts": 1}),
    ]
    r = _reduce(events)
    assert r.count("serve.launch") == 2
    assert r.total("serve.launch") == pytest.approx(500e-9)
    # 400 - (100 + 50 + 50) in the first launch, 100 in the second
    assert r.self_time("serve.launch") == pytest.approx(300e-9)
    assert r.self_time("query.sync") == pytest.approx(100e-9)
    assert r.arg("serve.launch", "rows") == 96
    assert r.arg("serve.launch", "parts") == 3
    assert r.arg("serve.launch", "missing") == 0
    assert r.arg("query.launch", "rows") == 0
    assert r.count("np.asarray(jax.Array)") == 0     # not a program span


def test_window_clipping():
    events = [
        ("selfjoin.plan", 0.0, 200.0, {"launches": 1}),     # half before
        ("selfjoin.plan", 900.0, 300.0, {"launches": 2}),   # half after
        ("selfjoin.plan", 1500.0, 10.0, {"launches": 4}),   # outside
    ]
    r = _reduce(events)
    assert r.count("selfjoin.plan") == 2
    assert r.total("selfjoin.plan") == pytest.approx(200e-9)
    assert r.arg("selfjoin.plan", "launches") == 3
    assert r.window_s == pytest.approx(900e-9)


def test_idle_on_innermost_program_span_past_jax_events():
    events = [
        ("bench.pump", 100.0, 850.0, {}),
        ("serve.resolve", 150.0, 500.0, {"launch": 3, "forced": 1}),
        ("query.sync", 200.0, 300.0, {}),
        ("np.asarray(jax.Array)", 210.0, 280.0, {}),
        ("serve.launch", 700.0, 100.0, {"launch": 4}),
        ("PjitFunction(step)", 720.0, 60.0, {}),
    ]
    # gaps: [100, 250) mid 175 -> serve.resolve; [300, 500) mid 400 ->
    # query.sync under serve.resolve, past np.asarray; [700, 800) mid 750
    # -> serve.launch, past PjitFunction; [850, 1000) mid 925 falls in no
    # program span, so the innermost benchmark span there is named
    busy = [(250.0, 300.0), (500.0, 700.0), (800.0, 850.0)]
    r = _reduce(events, busy)
    assert r.idle == {
        "serve.resolve": pytest.approx(150e-9),
        "serve.resolve/query.sync": pytest.approx(200e-9),
        "serve.launch": pytest.approx(100e-9),
        "bench.pump": pytest.approx(150e-9)}
    notes = r.notes()
    assert notes["forced_resolve_share"] == 1.0
    assert notes["program_idle_ms"].startswith("serve.resolve/query.sync=")


def test_idle_outside_every_span():
    r = _reduce([("selfjoin.join", 100.0, 100.0, {})],
                [(200.0, 201.0)])
    assert r.idle == {"selfjoin.join": pytest.approx(100e-9),
                      "-": pytest.approx(799e-9)}
    assert r.notes()["launches_per_join"] == 0.0


# -- real traces of the program on the CPU ---------------------------------

def _parents(events):
    """Each program span's nearest enclosing program span (or None)."""
    prog = [e for e in events if e[0].startswith(progspans.PROGRAM_PREFIXES)]
    out = []
    for n, s, d, a in prog:
        inside = [p for p in prog if p[1] <= s and s + d <= p[1] + p[2]
                  and p[2] > d]
        par = min(inside, key=lambda p: p[2]) if inside else None
        out.append((n, a, None if par is None else par[0]))
    return out


def _trace(tmp_path, fn):
    import jax
    from jax.profiler import TraceAnnotation

    d = str(tmp_path / "trace")
    jax.profiler.start_trace(d)
    try:
        with TraceAnnotation("bench.window"):
            fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)
    events, busy, lo, hi = progspans.load(path)
    return events, progspans.reduce(events, busy, lo, hi)


def _points(n, seed=0):
    return np.random.default_rng(seed).uniform(0, 10, size=(n, 2))


@pytest.mark.parametrize("n", [1500, 6000])
def test_self_join_spans_nest_and_stay_per_launch(tmp_path, n):
    from repro.core.grid import build_grid
    from repro.core.selfjoin import self_join

    # ~4 points a cell at both sizes, so the cell-run plans are on
    pts, eps = _points(n), 0.5 * (1500 / n) ** 0.5
    self_join(pts, eps, index=build_grid(pts, eps), distance_impl="fused")
    box = {}

    def join():
        box["pairs"] = self_join(pts, eps, index=build_grid(pts, eps),
                                 distance_impl="fused")

    events, r = _trace(tmp_path, join)
    par = _parents(events)
    for name in ("selfjoin.join", "selfjoin.route", "selfjoin.plan",
                 "selfjoin.run_plan", "selfjoin.launch", "selfjoin.sync",
                 "selfjoin.emit", "selfjoin.sort"):
        assert r.count(name), name
    for name, _, parent in par:
        if name == "selfjoin.route" or name == "selfjoin.join":
            assert parent is None, name
        elif name == "selfjoin.sync":
            # the route probe's and the plan's fetches sit under them
            assert parent in ("selfjoin.join", "selfjoin.emit",
                              "selfjoin.route", "selfjoin.plan")
        else:
            assert parent == "selfjoin.join", name
    launches = r.count("selfjoin.launch")
    assert r.count("selfjoin.join") == 1
    assert r.arg("selfjoin.plan", "launches") == launches
    assert r.notes()["launches_per_join"] == launches
    assert box["pairs"].shape[0] > 0
    # one span per launch or sync, never per row or pair: the spans of a
    # join are a few per launch whatever the number of points
    n_spans = sum(a.count for a in r.spans.values())
    assert n_spans <= 7 + 5 * launches
    assert r.count("selfjoin.sync") <= 4 + 2 * launches


@pytest.mark.parametrize("sizes", [(32, 64, 32), (200, 600, 90, 1000)])
def test_serve_spans_count_what_the_service_counts(tmp_path, sizes):
    from repro.launch.serve import BatchingJoinService

    pts = _points(4000)
    svc = BatchingJoinService(pts, 0.3, max_batch=512, max_wait_ms=1.0,
                              return_pairs=True)
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="mark_steady")
        svc.warmup()
    rng = np.random.default_rng(1)
    reqs = [rng.uniform(0, 10, size=(s, 2)) for s in sizes]
    for q in reqs:                     # emit programs compile here
        svc.submit(q)
    svc.drain()
    l0, r0 = svc.n_launches, svc.rows_launched

    def serve():
        for q in reqs:
            svc.submit(q)
            svc.pump()
        svc.drain()

    events, r = _trace(tmp_path, serve)
    for name in ("serve.submit", "serve.launch", "serve.resolve",
                 "query.order", "query.sync", "query.launch", "query.emit",
                 "query.sort"):
        assert r.count(name), name
    expected = {"serve.submit": None, "serve.launch": None,
                "serve.resolve": None, "query.order": "serve.launch",
                "query.launch": "serve.launch", "query.emit": "serve.resolve",
                "query.sort": "serve.resolve"}
    sync_parents = []
    for name, _, parent in _parents(events):
        if name == "query.sync":
            sync_parents.append(parent)
        else:
            assert parent == expected[name], name
    # the caps fetch of each launch, the counts fetch of each class launch
    assert set(sync_parents) == {"serve.launch", "serve.resolve"}
    assert (sync_parents.count("serve.launch") == r.count("serve.launch")
            <= sync_parents.count("serve.resolve"))
    n_launch = r.count("serve.launch")
    assert n_launch == svc.n_launches - l0
    assert r.arg("serve.launch", "rows") == svc.rows_launched - r0
    assert r.count("serve.resolve") == n_launch
    assert r.count("serve.submit") == len(sizes)
    launch_ids = sorted(e[3]["launch"] for e in events
                        if e[0] == "serve.launch")
    resolve_ids = sorted(e[3]["launch"] for e in events
                         if e[0] == "serve.resolve")
    assert launch_ids == resolve_ids == list(range(l0, svc.n_launches))
    assert r.arg("serve.launch", "parts") >= len(sizes)
    assert r.arg("serve.launch", "wait_us_sum") >= 0
    # a launch opens a fixed handful of spans per capacity class, however
    # many rows it carries
    classes = len(svc.prepared[0].classes)
    per_launch = (sum(a.count for a in r.spans.values())
                  - r.count("serve.submit")) / n_launch
    assert per_launch <= 3 + 4 * classes


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return chipbench_fixture.tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.mark.parametrize("cell", ["syn2d.join", "syn2d.serve"])
def test_traced_run_reports_the_span_metrics(tiny, cell):
    with open(os.path.join(tiny, "BENCHMARK.json")) as f:
        bench = json.load(f)
    wanted = {m["name"] for m in bench["per_layer"]
              if m["source"] == "program_span" and cell in m["workloads"]}
    assert wanted
    out = harness.run(tiny, cell, 2 ** 33 + 5, 1.0, True, require_tpu=False,
                      compile_cache=False)
    assert out["correct"]
    assert wanted <= set(out["metrics"])
    notes = out["notes"]
    keys = (("launches_per_join", "syncs_per_join") if cell == "syn2d.join"
            else ("parts_per_launch", "forced_resolve_share"))
    for k in keys + ("program_idle_ms",):
        assert k in notes, k
