"""Self-join perf trajectory: count/fill across distance_impl variants,
plus the serving path (--mode serve) and a CI smoke (--smoke).

    PYTHONPATH=src python benchmarks/bench_selfjoin.py [--out BENCH_selfjoin.json]
    PYTHONPATH=src python benchmarks/bench_selfjoin.py --mode serve
    PYTHONPATH=src python benchmarks/bench_selfjoin.py --smoke

--mode impl (default) times ``self_join_count`` (count) and ``self_join``
(count+fill, unsorted -- the paper reports the result sort separately) for
n in {2, 3, 4, 6} on uniform, clustered, and exponentially skewed
datasets, across distance_impl in {jnp, pallas, fused}, with the grid
index prebuilt (index construction is shared by every impl and benchmarked
in benchmarks/joins.py). The fused impl sweeps the merged-range 3^(n-1)
stencil by default (--no-merge times the per-cell 3^n oracle; --smoke
asserts pair-set parity between the two on every workload -- the CI
parity gate) and runs with autotuning enabled (kernels/autotune.py
measures tiles and the count route once and persists the winners), records
the chosen route, the offsets swept (n_offsets_swept), and the per-cell +
merged window-capacity histograms that drive the occupancy buckets
(DESIGN.md S6/S7), and ASSERTS the routing floor: fused count must not
lose to jnp on any workload (the uniform-6d regression this gate pins
down; --no-assert-floor to disable). The fused entry also records the
cell-run DMA dedup trajectory (DESIGN.md S11): row-loop vs run-loop join
timings and the per-workload analytic DMA-window ledger + run-length
histogram (``dma`` section); --smoke additionally gates run-loop vs
row-loop pair-set parity and the DMA-window reduction (strict decrease
on the clustered workload, >= mean cell occupancy on the 2-D ones).

--mode serve times the external-query serving path (DESIGN.md S5) on the
default serve workload: steady-state (post-warmup) request latency
percentiles and requests/sec of launch.serve.JoinService against the
LEGACY pre-PR-2 path, kept verbatim here as ``legacy_range_query_retrace``
-- a per-request ``@jax.jit`` closure that re-traces and recompiles on
every call. The acceptance claim is steady-state p50 >= 5x better than
the legacy path.

--mode load measures the continuous-batching serving pipeline (DESIGN.md
S8): a closed-loop capacity probe of the per-request JoinService, an
open-loop Poisson frontier sweep of BatchingJoinService, and the GATE
point at --load-overload x capacity where batching must deliver
>= --load-speedup-floor x the baseline's req/s at equal-or-better p99
with coalescing active and no retrace. Records the frontier and an SLO
(2x gate p99) in the "load" section; ``--mode load --smoke`` replays the
gate workload with fewer requests and fails CI if p99 exceeds the
recorded SLO or the coalesce factor is 1.0.

--mode index measures the device-resident index lifecycle (DESIGN.md
S10): host-vs-device build and host-vs-device merged-planning latency
(compile excluded), cold JoinService construction, and a live
``reindex`` swap with its build/plan/warm/swap breakdown -- AFTER
asserting the device build is bit-identical to ``build_grid_host``
field-for-field and pair-for-pair on every workload. Records the
"index" section; ``--mode index --smoke`` is the CI parity smoke.

--mode metrics times the metric-trait join paths (DESIGN.md S12): cosine
on raw embeddings with planted scaled duplicates and jaccard on ~10%-dense
token sets, each with pair-set parity against the metric's brute-force
oracle ASSERTED before timing (smoke and full runs alike). Cosine is also
timed against the plain L2 join on its canonical geometry, pinning the
claim that the metric's steady-state overhead is canonicalization only.
Records the "metrics" section; ``--mode metrics --smoke`` is the CI gate.

--smoke shrinks the impl sweep to one tiny workload (seconds), writes to a
temp file by default, skips the floor assert (noise at this scale), and
schema-validates the payload -- wired into scripts/ci.sh so the harness
and the BENCH schema cannot rot between full runs.

On this CPU container the 'pallas' impl runs the cell_join kernel through
the interpreter and the 'fused' impl runs the reference lowering of
kernels/fused_join.py (same algorithm, same outputs as the Mosaic kernel);
absolute times are machine-local, the IMPL-vs-IMPL ratios are the claim
(interpret-mode CPU timing as proxy, ISSUE 1). The headline acceptance
number is fused-vs-jnp on the 2-D uniform 100k workload.

Writes/updates BENCH_selfjoin.json (repo root by default): each mode
rewrites its own section and preserves the other's, so the file holds the
full perf trajectory; EXPERIMENTS.md tracks the history.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(_ROOT, "src"))
sys.path.insert(0, _ROOT)

from repro.core.grid import build_grid_host                     # noqa: E402
from repro.core.selfjoin import self_join, self_join_count      # noqa: E402
from benchmarks.common import syn                               # noqa: E402

IMPLS = ("jnp", "pallas", "fused")


def clustered(n_points: int, n_dims: int, seed: int = 3) -> np.ndarray:
    """Gaussian clusters in [0, 100]^n (sw_like is 2/3-D only)."""
    rng = np.random.default_rng(seed)
    k = max(n_points // 200, 4)
    centers = rng.uniform(0, 100, (k, n_dims))
    pts = centers[rng.integers(0, k, n_points)]
    return pts + rng.normal(0, 1.5, pts.shape)


def expo(n_points: int, n_dims: int, seed: int = 5,
         scale: float = 10.0) -> np.ndarray:
    """Exponentially distributed coordinates (the paper's expo datasets):
    density concentrates near the origin, producing the long-tailed
    per-cell occupancy skew that exercises the capacity classes hardest."""
    rng = np.random.default_rng(seed)
    return rng.exponential(scale, (n_points, n_dims))


def workloads(args):
    if args.smoke:
        # tiny skewed workloads: exercise the occupancy buckets, the
        # merged-vs-unmerged parity oracle, and the full payload schema in
        # seconds (CI harness-rot gate)
        yield "uniform-2d", syn(4000, 2), 0.4
        yield "clustered-2d", clustered(3000, 2), 0.4
        yield "expo-3d", expo(3000, 3), 1.2
        return
    # eps tuned per dimensionality for paper-like selectivity (a handful of
    # neighbors per point on the uniform sets; denser on the clustered sets).
    yield "uniform-2d", syn(args.points_2d, 2), 0.4
    yield "clustered-2d", clustered(args.points_2d, 2), 0.4
    yield "expo-3d", expo(args.points_3d, 3), 1.2
    yield "uniform-4d", syn(args.points_4d, 4), 6.0
    yield "clustered-4d", clustered(args.points_4d, 4), 3.0
    yield "uniform-6d", syn(args.points_6d, 6), 14.0
    yield "clustered-6d", clustered(args.points_6d, 6), 4.0


def validate_schema(payload: dict) -> None:
    """The BENCH_selfjoin.json contract consumed by EXPERIMENTS.md and the
    acceptance gates; --smoke runs this in CI so it cannot rot."""
    for key in ("bench", "backend", "jax", "results"):
        assert key in payload, key
    assert payload["headline"] is None or {
        "workload", "n_points", "fused_over_jnp_join",
        "fused_over_jnp_count"} <= set(payload["headline"])
    for e in payload["results"]:
        for key in ("workload", "n_points", "n_dims", "eps", "total_pairs",
                    "max_per_cell", "window_caps_hist",
                    "merged_window_caps_hist", "impls"):
            assert key in e, (e.get("workload"), key)
        for impl, t in e["impls"].items():
            assert {"count_s", "join_s"} <= set(t), (e["workload"], impl)
        if "fused" in e["impls"]:
            assert "route" in e["impls"]["fused"], e["workload"]
            assert "n_offsets_swept" in e["impls"]["fused"], e["workload"]
            # cell-run DMA dedup trajectory (DESIGN.md S11)
            assert {"join_row_s", "join_run_s",
                    "run_over_row_join"} <= set(e["impls"]["fused"]), (
                e["workload"])
            assert "dma" in e, e["workload"]
            assert {"dma_windows_row", "dma_windows_run", "dma_bytes_saved",
                    "reduction_factor", "mean_cell_occupancy",
                    "run_length_hist"} <= set(e["dma"]), e["workload"]
    if "load" in payload:
        validate_load_schema(payload["load"])
    if "index" in payload:
        validate_index_schema(payload["index"])
    if "metrics" in payload:
        validate_metrics_schema(payload["metrics"])


def validate_load_schema(load: dict) -> None:
    """Contract of the "load" section (EXPERIMENTS.md SLoad, the CI load
    smoke's SLO source)."""
    for key in ("workload", "knobs", "baseline_capacity", "gate",
                "frontier", "slo_p99_ms"):
        assert key in load, key
    assert {"max_batch", "max_wait_ms"} <= set(load["knobs"])
    gate = load["gate"]
    for key in ("offered_rps", "baseline", "batching",
                "speedup_req_per_sec", "p99_ratio"):
        assert key in gate, key
    for side in ("baseline", "batching"):
        assert {"achieved_rps", "p50_ms", "p99_ms"} <= set(gate[side]), side
    assert gate["batching"].get("coalesce_factor") is not None
    for pt in load["frontier"]:
        assert {"offered_rps", "achieved_rps", "p50_ms", "p99_ms",
                "coalesce_factor"} <= set(pt)


def best_of(fn, trials: int) -> float:
    fn()  # warm-up: jit compile excluded (paper excludes context setup)
    best = float("inf")
    for _ in range(trials):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def legacy_range_query_retrace(index, queries, deltas, max_per_cell):
    """The pre-PR-2 serving path, kept VERBATIM as the regression baseline.

    The ``@jax.jit`` closure below is a new function object on every call,
    so each request pays a fresh trace + compile before executing; it also
    gathers the (Q, C, n) candidate tensor the fused path eliminates and
    can only return counts. core/query_join.py replaced it; this copy
    exists only so --mode serve can keep measuring what the fix is worth.
    """
    import jax
    import jax.numpy as jnp
    from repro.core import grid as grid_lib
    from repro.core.grid import neighbor_rank

    queries = jnp.asarray(queries)

    @jax.jit
    def run(index, queries):
        qcoords = grid_lib.cell_coords(queries, index.grid_min, index.eps)
        qcoords = jnp.clip(qcoords, 1, index.dims - 2)
        qkeys = grid_lib.linearize(qcoords, index.dims)
        eps2 = index.eps * index.eps

        def body(counts, delta):
            nbr = neighbor_rank(index, qkeys + delta)
            nbr_c = jnp.maximum(nbr, 0)
            start = index.cell_start[nbr_c]
            count = jnp.where(nbr >= 0, index.cell_count[nbr_c], 0)
            slots = jnp.arange(max_per_cell, dtype=jnp.int32)
            pos = jnp.minimum(start[:, None] + slots[None, :],
                              index.num_points - 1)
            valid = slots[None, :] < count[:, None]
            cand = index.points_sorted[pos]
            d2 = jnp.sum((queries[:, None, :] - cand) ** 2, axis=-1)
            hits = (d2 <= eps2) & valid
            return counts + hits.sum(axis=1, dtype=jnp.int32), None

        counts0 = jnp.zeros((queries.shape[0],), jnp.int32)
        counts, _ = jax.lax.scan(body, counts0, deltas)
        return counts

    return np.asarray(run(index, queries))


def bench_serve(args):
    """Steady-state serving vs. the legacy re-tracing path."""
    from repro.core.grid import build_grid_host
    from repro.core.query_join import bucket_rows
    from repro.core.selfjoin import _offset_tables, _round_up
    from repro.launch.serve import JoinService

    rng = np.random.default_rng(args.seed)
    pts = rng.uniform(0, 100, (args.serve_points, args.serve_dims))
    eps = args.serve_eps
    B = args.serve_batch
    index = build_grid_host(pts, eps)
    deltas, _ = _offset_tables(index, unicomp=False)
    c = _round_up(max(int(index.max_per_cell), 1), 8)

    # legacy path: EVERY request re-traces (that is the point being measured)
    lat_legacy = []
    legacy_counts = legacy_q = None
    for r in range(max(args.serve_requests_legacy, 1)):
        q = rng.uniform(0, 100, (B, args.serve_dims))
        t0 = time.perf_counter()
        counts = legacy_range_query_retrace(index, q, deltas, c)
        lat_legacy.append(1000 * (time.perf_counter() - t0))
        legacy_counts, legacy_q = counts, q

    # service path: warm once, measure steady state (warmup auto-marks
    # steady, so latencies below land in the steady window)
    svc = JoinService(pts, eps, index=index)
    svc.warmup(B)
    for r in range(args.serve_requests):
        q = rng.uniform(0, 100, (B, args.serve_dims))
        svc.query(q)
    # parity gate: the service must answer the legacy path's last request
    # identically before its timings count
    parity = svc.prepared.counts(legacy_q)
    assert np.array_equal(parity, legacy_counts), "serve parity failure"
    svc.assert_no_retrace()
    p50, p99 = svc.percentiles()
    p50_legacy = float(np.percentile(lat_legacy, 50))
    entry = {
        "workload": (f"uniform-{args.serve_dims}d serve, "
                     f"{args.serve_points} pts indexed, "
                     f"batch {B} external queries/request"),
        "n_points": int(args.serve_points),
        "n_dims": int(args.serve_dims),
        "eps": float(eps),
        "request_batch": int(B),
        "legacy_retrace": {
            "requests": len(lat_legacy),
            "p50_ms": p50_legacy,
            "p99_ms": float(np.percentile(lat_legacy, 99)),
            "note": "per-request @jax.jit closure: trace+compile every call",
        },
        "service": {
            "requests": svc.requests,
            "p50_ms": p50,
            "p99_ms": p99,
            "requests_per_sec": svc.requests_per_sec(),
            "bucket_rows": int(bucket_rows(B)),
            "note": "JoinService steady state (post-warmup), counts-only "
                    "requests; no retrace (asserted)",
        },
        "speedup_service_vs_legacy_p50": p50_legacy / p50,
    }
    print(f"[bench-serve] legacy p50 {p50_legacy:9.1f} ms  "
          f"service p50 {p50:7.2f} ms  "
          f"speedup {entry['speedup_service_vs_legacy_p50']:.1f}x  "
          f"({svc.requests_per_sec():.1f} req/s steady)")
    return entry


def bench_load(args):
    """Continuous-batching throughput gate + latency/throughput frontier
    (DESIGN.md S8, EXPERIMENTS.md SLoad).

    One mixed-size mixed-eps request stream drives both services:

    1. closed-loop capacity probe of the per-request ``JoinService``
       (concurrency 1 -- its max sustained req/s),
    2. open-loop frontier sweep of ``BatchingJoinService`` at multiples
       of that capacity (Poisson arrivals, coordinated-omission-safe
       latency from the scheduled arrival),
    3. the GATE point at ``--load-overload`` x baseline capacity, where
       both services face identical offered load: the acceptance claim is
       batching req/s >= ``--load-speedup-floor`` x baseline at
       equal-or-better p99, with coalesce factor > 1 and the no-retrace
       watchdog green on both services.

    The recorded ``slo_p99_ms`` (2x the gate run's batching p99,
    headroom for machine noise) is what the CI load smoke
    (``--mode load --smoke``) replays against: same workload and knobs,
    fewer requests, FAIL if p99 exceeds the SLO or coalescing silently
    turned off.
    """
    from repro.launch.loadgen import (RequestMix, make_request_stream,
                                      run_closed_loop, run_open_loop)
    from repro.launch.serve import BatchingJoinService, JoinService

    rng = np.random.default_rng(args.seed)
    n_requests = 60 if args.smoke else args.load_requests
    pts = rng.uniform(0, 100, (args.load_points, args.load_dims))
    eps = args.load_eps
    sizes = (16, 32, 64, 128)
    eps_mix = (0.75 * eps, eps)
    mix = RequestMix(sizes=sizes, eps_values=eps_mix)
    stream = make_request_stream(n_requests, mix, args.load_dims,
                                 seed=args.seed + 1)

    # warm BOTH services before marking steady on either: the executable
    # caches are module-global, so a later warmup would trip the earlier
    # service's watchdog as a foreign compile
    baseline = JoinService(pts, eps)
    baseline.warmup(max(sizes))
    svc = BatchingJoinService(pts, eps, max_batch=args.load_max_batch,
                              max_wait_ms=args.load_max_wait_ms)
    svc.warmup()
    baseline.mark_steady()
    svc.mark_steady()

    cap = run_closed_loop(baseline, stream[: min(60, n_requests)])
    print(f"[bench-load] baseline capacity {cap.achieved_rps:8.1f} req/s "
          f"(closed loop, p50 {cap.p50_ms:.2f} ms)", flush=True)

    gate_rate = args.load_overload * cap.achieved_rps
    multiples = (0.5, 1.0, 2.0) if not args.smoke else ()
    frontier = []
    for m in multiples:
        r = run_open_loop(svc, stream, m * cap.achieved_rps,
                          seed=args.seed + 2)
        frontier.append(r)
        print(f"[bench-load] batching @ {m:3.1f}x cap "
              f"({r.offered_rps:7.1f} rps offered): "
              f"achieved {r.achieved_rps:7.1f} p50 {r.p50_ms:6.2f} ms "
              f"p99 {r.p99_ms:6.2f} ms coalesce {r.coalesce_factor:.1f}",
              flush=True)
    gate_base = run_open_loop(baseline, stream, gate_rate,
                              seed=args.seed + 2)
    gate_batch = run_open_loop(svc, stream, gate_rate, seed=args.seed + 2)
    frontier.append(gate_batch)
    baseline.assert_no_retrace()
    svc.assert_no_retrace()
    speedup = gate_batch.achieved_rps / gate_base.achieved_rps
    p99_ratio = gate_batch.p99_ms / gate_base.p99_ms
    print(f"[bench-load] GATE @ {gate_rate:7.1f} rps offered "
          f"({args.load_overload}x capacity): baseline "
          f"{gate_base.achieved_rps:7.1f} req/s p99 {gate_base.p99_ms:7.2f} "
          f"ms | batching {gate_batch.achieved_rps:7.1f} req/s p99 "
          f"{gate_batch.p99_ms:7.2f} ms | speedup {speedup:.2f}x "
          f"coalesce {gate_batch.coalesce_factor:.1f}", flush=True)

    assert gate_batch.coalesce_factor > 1.0, (
        "batching silently disabled: coalesce factor "
        f"{gate_batch.coalesce_factor} at {gate_rate:.0f} rps offered")
    if args.smoke:
        # CI load smoke: replay the gate workload (fewer requests) against
        # the SLO the last full run recorded in the repo BENCH file
        repo_bench = os.path.join(_ROOT, "BENCH_selfjoin.json")
        if os.path.exists(repo_bench):
            with open(repo_bench) as f:
                recorded = json.load(f).get("load")
            if recorded is not None:
                slo = recorded["slo_p99_ms"]
                assert gate_batch.p99_ms <= slo, (
                    f"load smoke p99 {gate_batch.p99_ms:.2f} ms exceeds "
                    f"the recorded SLO {slo:.2f} ms "
                    f"(BENCH_selfjoin.json load.slo_p99_ms)")
                print(f"[bench-load] smoke p99 {gate_batch.p99_ms:.2f} ms "
                      f"within recorded SLO {slo:.2f} ms", flush=True)
    else:
        assert speedup >= args.load_speedup_floor, (
            f"batching speedup {speedup:.2f}x under the "
            f"{args.load_speedup_floor}x floor at {gate_rate:.0f} rps")
        assert gate_batch.p99_ms <= gate_base.p99_ms, (
            f"batching p99 {gate_batch.p99_ms:.2f} ms worse than baseline "
            f"{gate_base.p99_ms:.2f} ms at equal offered load")

    return {
        "workload": {
            "n_points": int(args.load_points),
            "n_dims": int(args.load_dims),
            "eps": float(eps),
            "request_sizes": list(sizes),
            "eps_mix": [float(e) for e in eps_mix],
            "n_requests": int(n_requests),
            "arrivals": "poisson (open loop), latency from scheduled "
                        "arrival (coordinated-omission safe)",
        },
        "knobs": {"max_batch": int(svc.max_batch),
                  "max_wait_ms": float(svc.max_wait_ms)},
        "baseline_capacity": {
            "requests_per_sec": cap.achieved_rps,
            "p50_ms": cap.p50_ms,
            "p99_ms": cap.p99_ms,
            "note": "JoinService closed loop, concurrency 1",
        },
        "gate": {
            "offered_rps": gate_rate,
            "overload_factor": float(args.load_overload),
            "baseline": {k: v for k, v in gate_base.to_dict().items()
                         if k not in ("mode",)},
            "batching": {k: v for k, v in gate_batch.to_dict().items()
                         if k not in ("mode",)},
            "speedup_req_per_sec": speedup,
            "p99_ratio": p99_ratio,
            "no_retrace": True,
        },
        "frontier": [r.to_dict() for r in frontier],
        "slo_p99_ms": 2.0 * gate_batch.p99_ms,
    }


def bench_distributed(args):
    """Fused slab join (DESIGN.md S3) vs the single-device fused join.

    Asserts pair-set parity between ``distributed_self_join`` over
    ``--dist-slabs`` slabs and ``self_join(distance_impl='fused')`` on
    every workload BEFORE timing (the CI parity gate), then records both
    timings. Needs >= --dist-slabs local devices: run under
    XLA_FLAGS=--xla_force_host_platform_device_count=N (scripts/ci.sh
    does). On this container the placeholder devices share one host, so
    the distributed timing carries the partition + halo-exchange overhead
    without any real parallel speedup; the recorded claim is parity +
    overhead trajectory, not a speedup.
    """
    import jax

    from repro.core.distributed import distributed_self_join
    from repro.core.selfjoin import self_join
    from repro.launch.mesh import make_slab_mesh

    n_slabs = args.dist_slabs
    if jax.device_count() < n_slabs:
        raise SystemExit(
            f"--mode distributed needs >= {n_slabs} devices; run under "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={n_slabs}")
    mesh = make_slab_mesh(n_slabs)
    npts = 4000 if args.smoke else args.dist_points
    results = []
    for name, pts, eps in (("uniform-2d", syn(npts, 2), 0.4),
                           ("clustered-2d", clustered(npts, 2), 0.4)):
        index = build_grid_host(pts, eps)
        ref = self_join(pts, eps, index=index, distance_impl="fused")
        got = distributed_self_join(pts, eps, mesh)
        assert np.array_equal(got, ref), (
            f"distributed pair-set parity failure on {name}: "
            f"{got.shape} vs {ref.shape}")
        print(f"[bench-dist] {name:14s} parity OK "
              f"({ref.shape[0]} pairs, {n_slabs} slabs)", flush=True)
        t_single = best_of(
            lambda: self_join(pts, eps, index=index, distance_impl="fused",
                              sort_result=False), args.trials)
        t_dist = best_of(
            lambda: distributed_self_join(pts, eps, mesh,
                                          sort_result=False), args.trials)
        results.append({
            "workload": name,
            "n_points": int(pts.shape[0]),
            "n_dims": int(pts.shape[1]),
            "eps": float(eps),
            "total_pairs": int(ref.shape[0]),
            "n_slabs": int(n_slabs),
            "single_fused_join_s": t_single,
            "distributed_join_s": t_dist,
            "distributed_over_single": t_dist / t_single,
            "pair_set_parity": True,
        })
        print(f"[bench-dist] {name:14s} single {t_single*1e3:9.1f} ms   "
              f"distributed({n_slabs}) {t_dist*1e3:9.1f} ms", flush=True)
    for e in results:   # schema: the keys EXPERIMENTS.md SDist reads
        assert {"workload", "n_slabs", "single_fused_join_s",
                "distributed_join_s", "pair_set_parity"} <= set(e)
    return {
        "n_slabs": int(n_slabs),
        "note": ("CPU placeholder devices share one host: the distributed "
                 "column measures partition + halo exchange + per-slab "
                 "sweep overhead, not parallel speedup; parity is the "
                 "asserted claim"),
        "results": results,
    }


_INDEX_FIELDS = ("grid_min", "eps", "dims", "order", "points_sorted",
                 "cell_keys", "cell_start", "cell_count", "point_cell_rank",
                 "num_cells", "max_per_cell")


def assert_index_parity(host_index, device_index, name: str) -> None:
    """Field-for-field bit-parity of two GridIndex builds (values AND
    dtypes) -- the --mode index acceptance gate."""
    for f in _INDEX_FIELDS:
        a = np.asarray(getattr(host_index, f))
        b = np.asarray(getattr(device_index, f))
        assert a.dtype == b.dtype, (
            f"index dtype mismatch on {name}.{f}: {a.dtype} vs {b.dtype}")
        assert np.array_equal(a, b), (
            f"index bit-parity failure on {name}.{f}")


def bench_index(args):
    """Device-resident index build + planning (DESIGN.md S10).

    Per workload: host (numpy) vs device (jitted) build time, host vs
    device merged-capacity planning time, cold prepare time, and the
    JoinService.reindex build/plan/warm/swap breakdown -- after asserting
    the device index is BIT-IDENTICAL to ``build_grid_host`` field-for-
    field and that downstream pairs match exactly (the acceptance gate).
    Times exclude compile (best_of warms first); the jitted builder is
    shared with the distributed slab join, so these executables are the
    ones a real service re-uses.
    """
    import jax

    from repro.core.grid import (build_grid, cell_window_caps,
                                 cell_window_caps_host)
    from repro.core.selfjoin import self_join
    from repro.launch.serve import JoinService

    rng = np.random.default_rng(args.seed)
    results = []
    for name, pts, eps in workloads(args):
        h_index = build_grid_host(pts, eps)
        d_index = build_grid(pts, eps)
        assert_index_parity(h_index, d_index, name)
        ref = self_join(pts, eps, index=h_index, sort_result=True)
        got = self_join(pts, eps, index=d_index, sort_result=True)
        assert np.array_equal(np.asarray(ref), np.asarray(got)), (
            f"pair-set parity failure on device-built index for {name}")
        print(f"[bench-index] {name:14s} parity OK: {len(_INDEX_FIELDS)} "
              f"fields bit-identical, {ref.shape[0]} pairs identical",
              flush=True)

        t_host = best_of(lambda: build_grid_host(pts, eps), args.trials)
        t_dev = best_of(
            lambda: jax.block_until_ready(build_grid(pts, eps)), args.trials)
        tp_host = best_of(
            lambda: cell_window_caps_host(d_index, merged=True), args.trials)
        tp_dev = best_of(
            lambda: cell_window_caps(d_index, merged=True), args.trials)
        # cold prepare on a FRESH device build: what a re-index pays
        # (per-index plan caches cannot help a new index object)
        t0 = time.perf_counter()
        svc = JoinService(pts, eps)
        prepare_cold_s = time.perf_counter() - t0
        q = pts[:min(256, pts.shape[0])]
        svc.warmup(q.shape[0])
        svc.reindex(rng.permutation(pts))
        svc.query(q)   # same bucket as warmed: swap must not retrace
        svc.assert_no_retrace()   # warmed executables survived the swap

        entry = {
            "workload": name,
            "n_points": int(pts.shape[0]),
            "n_dims": int(pts.shape[1]),
            "eps": float(eps),
            "key_dtype": str(np.asarray(d_index.cell_keys).dtype),
            "num_cells": int(d_index.num_cells),
            "build_host_s": t_host,
            "build_device_s": t_dev,
            "build_device_over_host": t_dev / t_host,
            "plan_host_s": tp_host,
            "plan_device_s": tp_dev,
            "plan_device_over_host": tp_dev / tp_host,
            "prepare_cold_s": prepare_cold_s,
            "reindex": dict(svc.reindex_timings),
            "snapshot_swaps": int(svc.swaps),
            "bit_parity": True,
            "pair_parity": True,
            "total_pairs": int(ref.shape[0]),
        }
        results.append(entry)
        rt = entry["reindex"]
        print(f"[bench-index] {name:14s} build host {t_host*1e3:8.1f} ms  "
              f"device {t_dev*1e3:8.1f} ms   plan host {tp_host*1e3:7.1f} ms"
              f"  device {tp_dev*1e3:7.1f} ms", flush=True)
        print(f"[bench-index] {name:14s} reindex build {rt['build_s']*1e3:.1f}"
              f" ms + plan {rt['plan_s']*1e3:.1f} ms + warm "
              f"{rt['warm_s']*1e3:.1f} ms + swap {rt['swap_s']*1e6:.0f} us "
              f"(no retrace across swap)", flush=True)
    return {
        "note": ("device build/plan on the shared jitted executables "
                 "(grid.build_grid_with_geometry_jit + batched searchsorted "
                 "planners); compile excluded (warmed), parity asserted "
                 "field-for-field and on downstream pairs before timing"),
        "results": results,
    }


def validate_index_schema(section: dict) -> None:
    """Contract of the "index" section (EXPERIMENTS.md SIndexBuild)."""
    assert "results" in section and section["results"], "empty index section"
    for e in section["results"]:
        for key in ("workload", "n_points", "n_dims", "eps", "key_dtype",
                    "build_host_s", "build_device_s", "plan_host_s",
                    "plan_device_s", "prepare_cold_s", "reindex",
                    "bit_parity", "pair_parity"):
            assert key in e, (e.get("workload"), key)
        assert e["bit_parity"] is True and e["pair_parity"] is True
        assert {"build_s", "plan_s", "warm_s", "swap_s"} <= set(e["reindex"])


def metric_workloads(args):
    """Per-metric bench workloads (DESIGN.md S12). Cosine: raw gaussian
    embeddings with planted scaled duplicates (the case L2 misses).
    Jaccard: ~10%-dense token sets over a 64-token vocabulary."""
    rng = np.random.default_rng(args.seed)
    n = 2500 if args.smoke else args.metrics_points
    d = args.metrics_dims
    emb = rng.normal(size=(n, d))
    emb[: n // 50] = emb[n // 2: n // 2 + n // 50] * 2.5   # scaled dups
    yield "cosine", f"cosine-{d}d", emb, 0.9
    vocab = 64
    sets = [tuple(np.flatnonzero(rng.random(vocab) < 0.1))
            for _ in range(n)]
    yield "jaccard", f"jaccard-v{vocab}", sets, 0.5


def bench_metrics(args):
    """Metric-generic join trajectory (DESIGN.md S12): per-metric fused
    join timings with PAIR-SET PARITY vs the metric's brute-force oracle
    asserted on every workload before anything is timed -- smoke and full
    runs alike (the acceptance gate: a metric path that returns L2
    answers cannot produce a plausible-but-wrong benchmark row). For
    cosine the canonical-geometry L2 join is timed too: the metric's
    steady-state overhead is canonicalization only, and the ratio records
    that claim.
    """
    from repro.core import metric as metric_lib
    from repro.core.selfjoin import self_join, self_join_count

    results = []
    for metric, name, data, eps in metric_workloads(args):
        t0 = time.perf_counter()
        canon = metric_lib.canonicalize(data, eps, metric=metric)
        canonicalize_s = time.perf_counter() - t0
        expect = metric_lib.brute_force_join_metric(canon)
        got = self_join(data, eps, metric=metric)
        assert np.array_equal(np.asarray(got), np.asarray(expect)), (
            f"{name}: fused {metric} pair set diverges from the brute "
            f"oracle ({got.shape} vs {expect.shape})")
        print(f"[bench-metrics] {name:14s} pair-set parity vs brute "
              f"oracle OK ({expect.shape[0]} pairs)", flush=True)
        count_s = best_of(
            lambda: self_join_count(data, eps, metric=metric), args.trials)
        join_s = best_of(
            lambda: self_join(data, eps, metric=metric), args.trials)
        entry = {
            "metric": metric,
            "workload": name,
            "n_points": int(canon.geom.shape[0]),
            "eps": float(eps),
            "eps_geom": float(canon.eps_geom),
            "n_feat": int(canon.n_feat),
            "total_pairs": int(expect.shape[0]),
            "pair_parity": True,
            "canonicalize_s": canonicalize_s,
            "count_s": count_s,
            "join_s": join_s,
        }
        if metric == "cosine":
            # the SAME fused machinery on the pre-canonicalized geometry:
            # the ratio isolates what the metric tag itself costs (~1.0)
            geom = np.asarray(canon.geom)
            l2_s = best_of(
                lambda: self_join(geom, float(canon.eps_geom),
                                  distance_impl="fused"), args.trials)
            entry["l2_equiv_join_s"] = l2_s
            entry["over_l2_equiv"] = join_s / l2_s
        results.append(entry)
        print(f"[bench-metrics] {name:14s} count {count_s*1e3:8.1f} ms  "
              f"join {join_s*1e3:8.1f} ms  canonicalize "
              f"{canonicalize_s*1e3:6.1f} ms", flush=True)
    return {
        "note": ("fused join per metric trait (core/metric.py): pair-set "
                 "parity vs the brute oracle asserted before timing; "
                 "cosine also timed against the plain L2 join on its "
                 "canonical geometry (steady-state metric overhead)"),
        "results": results,
    }


def validate_metrics_schema(section: dict) -> None:
    """Contract of the "metrics" section (EXPERIMENTS.md SMetrics)."""
    assert "results" in section and section["results"], "empty metrics section"
    seen = set()
    for e in section["results"]:
        for key in ("metric", "workload", "n_points", "eps", "eps_geom",
                    "n_feat", "total_pairs", "pair_parity",
                    "canonicalize_s", "count_s", "join_s"):
            assert key in e, (e.get("workload"), key)
        assert e["pair_parity"] is True, e["workload"]
        seen.add(e["metric"])
    assert {"cosine", "jaccard"} <= seen, seen


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--mode", default="impl",
                    choices=("impl", "serve", "distributed", "load", "index",
                             "metrics"))
    ap.add_argument("--smoke", action="store_true",
                    help="tiny impl sweep + schema validation (CI gate); "
                         "writes to a temp file unless --out is given")
    ap.add_argument("--assert-floor", dest="assert_floor",
                    action="store_true", default=None,
                    help="fail if routed fused count loses to jnp "
                         "(default: on for full impl runs, off for --smoke)")
    ap.add_argument("--no-assert-floor", dest="assert_floor",
                    action="store_false")
    ap.add_argument("--no-autotune", dest="autotune", action="store_false",
                    default=True,
                    help="disable measured tile/route autotuning "
                         "(kernels/autotune.py) for this run")
    ap.add_argument("--no-merge", action="store_true",
                    help="time the per-cell 3^n sweep instead of the "
                         "merged-range 3^(n-1) sweep (parity oracle, "
                         "DESIGN.md S7); --smoke asserts pair-set parity "
                         "between both regardless")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--points-2d", type=int, default=100_000)
    ap.add_argument("--points-3d", type=int, default=30_000)
    ap.add_argument("--points-4d", type=int, default=20_000)
    ap.add_argument("--points-6d", type=int, default=10_000)
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--impls", default=",".join(IMPLS),
                    help="comma-separated subset of %s" % (IMPLS,))
    # --mode serve: the default serve workload (launch/serve.py defaults)
    ap.add_argument("--serve-points", type=int, default=20_000)
    ap.add_argument("--serve-dims", type=int, default=4)
    ap.add_argument("--serve-eps", type=float, default=2.0)
    ap.add_argument("--serve-batch", type=int, default=256)
    ap.add_argument("--serve-requests", type=int, default=32)
    ap.add_argument("--serve-requests-legacy", type=int, default=6)
    # --mode distributed: fused slab join parity + overhead (DESIGN.md S3)
    ap.add_argument("--dist-slabs", type=int, default=2)
    ap.add_argument("--dist-points", type=int, default=40_000)
    # --mode metrics: per-metric trait joins, parity-gated (DESIGN.md S12)
    ap.add_argument("--metrics-points", type=int, default=20_000)
    ap.add_argument("--metrics-dims", type=int, default=4)
    # --mode load: continuous-batching frontier + SLO gate (DESIGN.md S8)
    ap.add_argument("--load-points", type=int, default=20_000)
    ap.add_argument("--load-dims", type=int, default=4)
    ap.add_argument("--load-eps", type=float, default=2.0)
    ap.add_argument("--load-requests", type=int, default=200)
    ap.add_argument("--load-max-batch", type=int, default=1024)
    ap.add_argument("--load-max-wait-ms", type=float, default=2.0)
    ap.add_argument("--load-overload", type=float, default=6.0,
                    help="gate offered load as a multiple of the measured "
                         "baseline capacity")
    ap.add_argument("--load-speedup-floor", type=float, default=3.0,
                    help="minimum batching-vs-baseline req/s ratio at the "
                         "gate point (full runs only)")
    args = ap.parse_args(argv)
    if args.assert_floor is None:
        args.assert_floor = args.mode == "impl" and not args.smoke
    if args.smoke:
        args.trials = 1
        if args.impls == ",".join(IMPLS):
            args.impls = "jnp,fused"   # interpreted pallas is minutes even
    impls = tuple(args.impls.split(","))
    if args.out is None:
        if args.smoke:
            import tempfile

            args.out = os.path.join(tempfile.gettempdir(),
                                    "bench_selfjoin_smoke.json")
        else:
            args.out = os.path.join(
                os.path.dirname(__file__), "..", "BENCH_selfjoin.json")
    if args.autotune and args.mode == "impl" and not args.smoke:
        # measured tile + route autotuning: winners persist in the cache
        # next to kernels/autotune.py (or $REPRO_AUTOTUNE_CACHE)
        os.environ.setdefault("REPRO_AUTOTUNE", "1")
    out = os.path.abspath(args.out)
    existing = {}
    if os.path.exists(out):
        with open(out) as f:
            existing = json.load(f)

    import jax

    if args.mode in ("serve", "distributed", "load", "index", "metrics"):
        payload = existing or {"bench": "selfjoin-distance-impl"}
        payload["backend"] = jax.default_backend()
        payload["jax"] = jax.__version__
        if args.mode == "serve":
            payload["serve"] = bench_serve(args)
        elif args.mode == "load":
            payload["load"] = bench_load(args)
            validate_load_schema(payload["load"])
        elif args.mode == "index":
            payload["index"] = bench_index(args)
            validate_index_schema(payload["index"])
        elif args.mode == "metrics":
            payload["metrics"] = bench_metrics(args)
            validate_metrics_schema(payload["metrics"])
        else:
            payload["distributed"] = bench_distributed(args)
        with open(out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[bench] wrote {out}")
        return payload

    from repro.core.grid import occupancy_plan

    merge = not args.no_merge
    results = []
    for name, pts, eps in workloads(args):
        index = build_grid_host(pts, eps)
        expect = self_join_count(pts, eps, index=index).total_pairs
        plan = occupancy_plan(index)
        mplan = occupancy_plan(index, merged=True)
        if args.smoke:
            # CI parity oracle (DESIGN.md S7): the merged-range sweep and
            # the per-cell sweep must emit identical sorted pair sets --
            # exercised on every build, not just under pytest. The driver
            # is called with the sweep PINNED (not through the public
            # merge_last_dim default) so a measured 'dense-flat' route
            # verdict can never silently turn this into oracle-vs-oracle.
            from repro.core.selfjoin import _self_join_fused

            pm = _self_join_fused(index, unicomp=True, sort_result=True,
                                  merged=True)
            pf = _self_join_fused(index, unicomp=True, sort_result=True,
                                  merged=False)
            assert np.array_equal(pm, pf), (
                f"merged-range sweep pair-set mismatch vs per-cell oracle "
                f"on {name}: {pm.shape} vs {pf.shape}")
            print(f"[bench] {name:14s} merged/unmerged pair-set parity OK "
                  f"({pm.shape[0]} pairs)", flush=True)
            # Run-loop parity gate (DESIGN.md S11): the cell-run DMA dedup
            # must emit the row-loop's pair set bit-for-bit, with the
            # analytic DMA ledger showing fewer window gathers -- strictly
            # fewer on the clustered workload (co-located queries are its
            # whole point), and by at least the mean cell occupancy factor
            # on the dense 2-D workloads (ISSUE 9 acceptance).
            from repro.core.selfjoin import dma_window_stats

            pr = _self_join_fused(index, unicomp=True, sort_result=True,
                                  merged=True, run_loop=True)
            assert np.array_equal(pm, pr), (
                f"run-loop pair-set mismatch vs row-loop on {name}: "
                f"{pr.shape} vs {pm.shape}")
            dma = dma_window_stats(index)
            assert dma["dma_windows_run"] <= dma["dma_windows_row"], (
                name, dma)
            if name.startswith("clustered"):
                assert dma["dma_windows_run"] < dma["dma_windows_row"], (
                    f"run-loop did not reduce DMA windows on {name}: {dma}")
            if name in ("uniform-2d", "clustered-2d"):
                assert (dma["reduction_factor"]
                        >= dma["mean_cell_occupancy"]), (
                    f"DMA window reduction {dma['reduction_factor']:.2f}x "
                    f"under the mean cell occupancy "
                    f"{dma['mean_cell_occupancy']:.2f}x on {name}")
            print(f"[bench] {name:14s} run-loop pair-set parity OK, DMA "
                  f"windows {dma['dma_windows_row']} -> "
                  f"{dma['dma_windows_run']} "
                  f"({dma['reduction_factor']:.2f}x, mean occupancy "
                  f"{dma['mean_cell_occupancy']:.2f})", flush=True)
        entry = {
            "workload": name,
            "n_points": int(pts.shape[0]),
            "n_dims": int(pts.shape[1]),
            "eps": float(eps),
            "total_pairs": int(expect),
            "max_per_cell": int(index.max_per_cell),
            # per-query candidate-capacity histogram {class: rows} -- the
            # skew that motivates the occupancy buckets (DESIGN.md S6)
            "window_caps_hist": {str(k): v for k, v in
                                 sorted(plan.hist.items())},
            # same histogram over MERGED range-window capacities: what the
            # merged sweep's buckets actually launch at (DESIGN.md S7)
            "merged_window_caps_hist": {str(k): v for k, v in
                                        sorted(mplan.hist.items())},
            "impls": {},
        }
        for impl in impls:
            stats = self_join_count(pts, eps, index=index, distance_impl=impl,
                                    merge_last_dim=merge)
            assert stats.total_pairs == expect, (name, impl, stats)
            # the interpreted cell_join kernel is ~100x slower than its
            # Mosaic build; one timed trial keeps the sweep tractable
            trials = 1 if impl == "pallas" else args.trials
            t_count = best_of(
                lambda: self_join_count(pts, eps, index=index,
                                        distance_impl=impl,
                                        merge_last_dim=merge),
                trials)
            t_join = best_of(
                lambda: self_join(pts, eps, index=index, distance_impl=impl,
                                  sort_result=False, merge_last_dim=merge),
                trials)
            entry["impls"][impl] = {"count_s": t_count, "join_s": t_join}
            if impl == "fused":
                entry["impls"][impl]["route"] = stats.route
                entry["impls"][impl]["n_offsets_swept"] = stats.n_offsets
                # Cell-run DMA dedup trajectory (DESIGN.md S11): row-loop
                # vs run-loop join through the same fused driver, plus the
                # analytic per-workload DMA-window ledger + run-length
                # histogram (the redundancy reduction as a TRACKED number)
                from repro.core.selfjoin import (_self_join_fused,
                                                 dma_window_stats)

                t_row = best_of(
                    lambda: _self_join_fused(index, unicomp=True,
                                             sort_result=False, merged=merge,
                                             run_loop=False), trials)
                t_run = best_of(
                    lambda: _self_join_fused(index, unicomp=True,
                                             sort_result=False, merged=merge,
                                             run_loop=True), trials)
                entry["impls"][impl]["join_row_s"] = t_row
                entry["impls"][impl]["join_run_s"] = t_run
                entry["impls"][impl]["run_over_row_join"] = t_row / t_run
                entry["dma"] = dma_window_stats(index, merged=merge)
                d = entry["dma"]
                print(f"[bench] {name:14s} {'dma':6s} "
                      f"row {t_row*1e3:9.1f} ms   run {t_run*1e3:9.1f} ms  "
                      f"({t_row / t_run:.2f}x)   windows "
                      f"{d['dma_windows_row']} -> {d['dma_windows_run']} "
                      f"({d['reduction_factor']:.2f}x, occ "
                      f"{d['mean_cell_occupancy']:.2f})", flush=True)
            print(f"[bench] {name:14s} {impl:6s} "
                  f"count {t_count*1e3:9.1f} ms   join {t_join*1e3:9.1f} ms"
                  + (f"   route={stats.route} n_off={stats.n_offsets}"
                     if impl == "fused" else ""),
                  flush=True)
        j = entry["impls"]
        if "jnp" in j and "fused" in j:
            entry["speedup_fused_vs_jnp"] = {
                "count": j["jnp"]["count_s"] / j["fused"]["count_s"],
                "join": j["jnp"]["join_s"] / j["fused"]["join_s"],
            }
            if args.assert_floor:
                r = entry["speedup_fused_vs_jnp"]["count"]
                assert r >= 1.0, (
                    f"routing floor violated on {name}: fused count {r:.2f}x "
                    f"vs jnp (route={j['fused']['route']}) -- the routing "
                    f"table must never pin a fused plan that loses to jnp")
        results.append(entry)

    headline = next((e for e in results
                     if e["workload"] == "uniform-2d"
                     and "speedup_fused_vs_jnp" in e), None)
    payload = {
        "bench": "selfjoin-distance-impl",
        "backend": jax.default_backend(),
        "jax": jax.__version__,
        "note": ("CPU proxy timings: 'pallas' via kernel interpreter, "
                 "'fused' via the reference lowering of the fused kernel "
                 "(bit-identical outputs to the Mosaic kernel)"),
        "headline": None if headline is None else {
            "workload": "uniform-2d",
            "n_points": headline["n_points"],
            "fused_over_jnp_join": headline["speedup_fused_vs_jnp"]["join"],
            "fused_over_jnp_count": headline["speedup_fused_vs_jnp"]["count"],
        },
        "results": results,
    }
    for section in ("serve", "distributed", "load", "index"):  # modes preserve others
        if section in existing:
            payload[section] = existing[section]
    validate_schema(payload)
    if args.smoke:
        print("[bench] smoke: schema validated "
              f"({len(results)} workloads, floor assert "
              f"{'on' if args.assert_floor else 'off'})")
    with open(out, "w") as f:
        json.dump(payload, f, indent=1)
    if headline is not None:
        print(f"[bench] headline: fused over jnp (uniform-2d, "
              f"{headline['n_points']} pts): "
              f"join {payload['headline']['fused_over_jnp_join']:.2f}x, "
              f"count {payload['headline']['fused_over_jnp_count']:.2f}x")
    print(f"[bench] wrote {out}")
    return payload


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()   # command-line runs only, not in-process calls
    main()
