#!/usr/bin/env python3
"""Bring-up smoke: the paper's self-join and its serving path on one TPU.

    python chip_smoke.py [--seed N]       # one chip: the phases below
    python chip_smoke.py --four-chips     # the 4-slab join vs one chip

One chip, at the paper's scale (Table I, 2M uniform points), through the
public entry points only:

  join2d   self_join(..., distance_impl="fused") over 2M points in
           [0,100]^2 at eps 0.2 (about 25 neighbours per point, 50M
           ordered pairs), then self_join_count over the same index; the
           count must equal the pair count.
  count6d  self_join_count over configs/selfjoin.py's syn6d2m (2M points
           in [0,100]^6, eps 2.0: int64 cell keys, the high-dimensional
           count route), plus epsilon_join of sampled points against the
           same index for per-row checks.
  serve    a JoinService over the 2-D set: warm, 8 requests of 256
           external queries with pairs, then assert_no_retrace().

Every sampled row and every served query is checked against a plain numpy
f64 brute force over the whole set (``oracle_rows``), which shares no code
with the grid. The chip computes in f32, so a pair whose true distance lies
within ``f32_band`` of eps may go either way; the script prints how many
pairs fell in that band and fails on any disagreement outside it. At the
end the Mosaic kernel must have compiled (``fused_pallas``) and the
reference lowering never (``fused_reference``).

``--four-chips`` runs only the slab path: distributed_self_join over a
4-slab mesh against the single-chip self_join on the same 2-D set. The
pair sets must be identical, and each slab's index and fused launches must
have run on that slab's own device.

Without a TPU the script exits non-zero and prints no result. The last
line of a passing run is one JSON object: {"ok": true, "device": {...}}.
"""
from __future__ import annotations

import argparse
import json
import os
import resource
import sys
import time
import warnings
from concurrent.futures import ThreadPoolExecutor

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
N_POINTS = 2_000_000
EPS_2D = 0.2
N_SAMPLE = 2000
N_REQUESTS, REQUEST_BATCH = 8, 256


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    rss = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2 ** 20
    print(f"[chip_smoke] {msg} (host peak RSS {rss:.1f} GiB)", flush=True)


# ---------------------------------------------------------------------------
# The oracle: numpy f64 brute force over the whole set
# ---------------------------------------------------------------------------

def f32_band(points: np.ndarray, eps: float) -> float:
    """Half-width of the distance band around eps inside which the chip's
    f32 decision may differ from the exact f64 one.

    The kernel decides ``d2(f32(p), f32(q)) <= f32(eps)^2`` in f32 (the TPU
    has no f64; kernels/ops.py). With u = 2^-24 and M = max |coordinate|:
      * rounding each coordinate to f32 moves it by at most u*M, so the
        distance D' between the rounded points is within 2*sqrt(n)*u*M of
        the true D;
      * evaluating D'^2 in f32 (n subtractions, n squares, n - 1 adds)
        errs by at most (3n)*u*D'^2 to first order, and f32(eps)^2 by
        3*u*eps^2; near the threshold D' ~ eps, so a relative error r on
        the squares is r/2 on the distance: together (3n + 3)/2*u*eps.
    A decision can therefore flip only if |D - eps| <= 2*sqrt(n)*u*M +
    (3n + 3)/2*u*eps. The band doubles that for second-order terms.
    """
    u = 2.0 ** -24
    n = points.shape[1]
    m = float(np.abs(points).max())
    return 2.0 * (2.0 * np.sqrt(n) * u * m + (3 * n + 3) / 2 * u * eps)


def oracle_rows(points: np.ndarray, queries: np.ndarray, eps: float,
                band: float, workers: int = 8):
    """For each query: ids of all points with D <= eps - band (the chip
    must report them) and with D <= eps + band (the chip may report
    them), by exact f64 distances to every point of the set."""
    cols = [np.ascontiguousarray(points[:, d]) for d in range(points.shape[1])]
    lo2, hi2 = (max(eps - band, 0.0)) ** 2, (eps + band) ** 2

    def chunk(q):
        d2 = np.zeros((q.shape[0], points.shape[0]))
        tmp = np.empty_like(d2)
        for d, col in enumerate(cols):
            np.subtract(col[None, :], q[:, d:d + 1], out=tmp)
            np.multiply(tmp, tmp, out=tmp)
            d2 += tmp
        return [(np.flatnonzero(row <= lo2), np.flatnonzero(row <= hi2))
                for row in d2]

    parts = [queries[i:i + 16] for i in range(0, queries.shape[0], 16)]
    with ThreadPoolExecutor(workers) as ex:
        return [r for rows in ex.map(chunk, parts) for r in rows]


def check_rows(name: str, got: list, want: list) -> int:
    """Every row: sure <= got <= maybe. Returns the pairs in the band."""
    in_band = 0
    for r, (g, (sure, maybe)) in enumerate(zip(got, want)):
        g = np.unique(g)
        missing = np.setdiff1d(sure, g)
        extra = np.setdiff1d(g, maybe)
        if missing.size or extra.size:
            fail(f"{name}: row {r} disagrees with the f64 oracle outside "
                 f"the band: missing {missing[:8].tolist()}, extra "
                 f"{extra[:8].tolist()}")
        in_band += maybe.size - sure.size
    return in_band


def self_rows(pairs: np.ndarray, ids: np.ndarray) -> list:
    """Neighbour ids of each sampled point from a lexsorted pair list."""
    lo = np.searchsorted(pairs[:, 0], ids, side="left")
    hi = np.searchsorted(pairs[:, 0], ids, side="right")
    return [pairs[a:b, 1] for a, b in zip(lo, hi)]


def drop_self(rows: list, ids: np.ndarray) -> list:
    return [(s[s != i], m[m != i]) for (s, m), i in zip(rows, ids)]


# ---------------------------------------------------------------------------
# Phases
# ---------------------------------------------------------------------------

def phase_join2d(pts, rng):
    from repro.core.grid import build_grid
    from repro.core.selfjoin import _emit_from_hits, self_join, self_join_count

    t0 = time.perf_counter()
    index = build_grid(pts, EPS_2D)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    pairs = self_join(pts, EPS_2D, index=index, distance_impl="fused")
    t_join = time.perf_counter() - t0
    log(f"join2d: self_join done in {t_join:.2f}s")
    emit = "device" if _emit_from_hits._cache_size() else "host"
    if pairs.ndim != 2 or pairs.shape[1] != 2 or pairs.dtype != np.int32:
        fail(f"join2d: pairs have shape {pairs.shape} {pairs.dtype}")
    t0 = time.perf_counter()
    stats = self_join_count(pts, EPS_2D, index=index, distance_impl="fused")
    t_count = time.perf_counter() - t0
    log(f"join2d: {pairs.shape[0]} pairs; build {t_build:.2f}s, join "
        f"{t_join:.2f}s (emit {emit}), count {t_count:.2f}s "
        f"(route {stats.route}, total {stats.total_pairs})")
    if stats.total_pairs != pairs.shape[0]:
        fail(f"join2d: count {stats.total_pairs} != {pairs.shape[0]} pairs")
    ids = np.sort(rng.choice(pts.shape[0], N_SAMPLE, replace=False))
    band = f32_band(pts, EPS_2D)
    want = drop_self(oracle_rows(pts, pts[ids], EPS_2D, band), ids)
    in_band = check_rows("join2d", self_rows(pairs, ids), want)
    log(f"join2d: {N_SAMPLE} sampled rows agree with the f64 oracle; "
        f"{in_band} pairs within the band {band:.3g} of eps")
    return index, {"pairs": int(pairs.shape[0]), "route": stats.route,
                   "emit": emit, "band_pairs": in_band}


def phase_count6d(rng):
    from repro.configs.selfjoin import SHAPES
    from repro.core.grid import build_grid
    from repro.core.query_join import epsilon_join
    from repro.core.selfjoin import self_join_count

    name, n, dims, eps = next(s for s in SHAPES if s[0] == "syn6d2m")
    pts = rng.uniform(0, 100, (n, dims))
    t0 = time.perf_counter()
    index = build_grid(pts, eps)
    t_build = time.perf_counter() - t0
    t0 = time.perf_counter()
    stats = self_join_count(pts, eps, index=index, distance_impl="fused")
    t_count = time.perf_counter() - t0
    log(f"count6d: {name} total {stats.total_pairs} pairs; key dtype "
        f"{index.cell_keys.dtype}, build {t_build:.2f}s, count "
        f"{t_count:.2f}s (route {stats.route})")
    if stats.total_pairs < 0 or stats.total_pairs % 2:
        fail(f"count6d: an ordered-pair total must be even and >= 0, got "
             f"{stats.total_pairs}")
    ids = np.sort(rng.choice(n, N_SAMPLE, replace=False))
    res = epsilon_join(pts[ids], None, index=index, return_pairs=True)
    band = f32_band(pts, eps)
    got = [res.pairs[res.pairs[:, 0] == r, 1] for r in range(N_SAMPLE)]
    # external queries count the indexed point they coincide with
    in_band = check_rows("count6d", got, oracle_rows(pts, pts[ids], eps,
                                                     band))
    log(f"count6d: {N_SAMPLE} sampled rows (epsilon_join over the same "
        f"index) agree with the f64 oracle; {in_band} pairs within the "
        f"band {band:.3g} of eps")
    return {"total": int(stats.total_pairs), "route": stats.route,
            "band_pairs": in_band}


def phase_serve(pts, index, rng):
    from repro.launch.serve import JoinService

    svc = JoinService(pts, EPS_2D, index=index, return_pairs=True)
    t0 = time.perf_counter()
    with warnings.catch_warnings():
        warnings.filterwarnings("ignore", message="mark_steady")
        svc.warmup(REQUEST_BATCH)
    t_warm = time.perf_counter() - t0
    band = f32_band(pts, EPS_2D)
    in_band = 0
    emit = None
    for r in range(N_REQUESTS):
        q = rng.uniform(0, 100, (REQUEST_BATCH, 2))
        res = svc.query(q)
        emit = res.emit
        if res.counts.shape != (REQUEST_BATCH,):
            fail(f"serve: counts shape {res.counts.shape}")
        got = [res.pairs[res.pairs[:, 0] == i, 1]
               for i in range(REQUEST_BATCH)]
        if not all(g.size == c for g, c in zip(got, res.counts)):
            fail("serve: per-query counts disagree with the pairs")
        in_band += check_rows(f"serve request {r}", got,
                              oracle_rows(pts, q, EPS_2D, band))
    svc.assert_no_retrace()
    p50, p99 = svc.percentiles()
    log(f"serve: warm {t_warm:.2f}s; {N_REQUESTS} x {REQUEST_BATCH} queries "
        f"(emit {emit}) agree with the f64 oracle, {in_band} pairs within "
        f"the band; no retrace; p50 {p50:.1f}ms p99 {p99:.1f}ms")
    return {"emit": emit, "band_pairs": in_band}


def phase_four_chips(pts):
    """distributed_self_join on a 4-slab mesh vs the single-chip join. The
    recorders observe where each slab's index and fused launches ran, and
    that every slab's first launch was dispatched before any slab's pairs
    were emitted (the slabs' stages run in step)."""
    import jax

    from repro.core import distributed, selfjoin
    from repro.core.selfjoin import self_join
    from repro.kernels import ops
    from repro.launch.mesh import make_slab_mesh

    mesh = make_slab_mesh(4)
    placed = []
    stages, launch, emit = distributed._slab_stages, \
        ops.fused_join_hits, selfjoin._emit_from_hits

    def stages_rec(index, *a, **kw):
        placed.append(["index", sorted(index.points_sorted.devices(),
                                       key=str)])
        return stages(index, *a, **kw)

    def launch_rec(*a, **kw):
        out = launch(*a, **kw)
        placed.append(["launch", sorted(out[1].devices(), key=str)])
        return out

    def emit_rec(*a, **kw):
        placed.append(["emit", None])
        return emit(*a, **kw)

    distributed._slab_stages = stages_rec
    ops.fused_join_hits = launch_rec
    selfjoin._emit_from_hits = emit_rec
    try:
        t0 = time.perf_counter()
        got = distributed.distributed_self_join(pts, EPS_2D, mesh)
        t_dist = time.perf_counter() - t0
    finally:
        distributed._slab_stages = stages
        ops.fused_join_hits = launch
        selfjoin._emit_from_hits = emit
    # each slab's index opens a slab; a launch belongs to the slab whose
    # index lives on its device
    slabs = {str(devs[0]): {"index": devs, "launch": set(), "first": None}
             for kind, devs in placed if kind == "index" and len(devs) == 1}
    first_emit = min((i for i, (kind, _) in enumerate(placed)
                      if kind == "emit"), default=None)   # None: host emit
    stray = []
    for i, (kind, devs) in enumerate(placed):
        if kind != "launch":
            continue
        s = slabs.get(str(devs[0])) if len(devs) == 1 else None
        if s is None:
            stray.append(devs)
            continue
        s["launch"].update(devs)
        s["first"] = i if s["first"] is None else s["first"]
    for k, s in enumerate(slabs.values()):
        log(f"four-chips: slab {k}: index on {s['index']}, fused launches "
            f"on {sorted(s['launch'], key=str)}, first launch at event "
            f"{s['first']} (first emit at {first_emit})")
    if (len(slabs) != 4 or stray
            or any(s["launch"] != set(s["index"]) for s in slabs.values())):
        fail("four-chips: the slabs did not each run on their own device")
    if first_emit is not None and any(s["first"] > first_emit
                                      for s in slabs.values()):
        fail("four-chips: a slab's first launch waited for another slab's "
             "emit (the slabs did not run at once)")
    t0 = time.perf_counter()
    with jax.default_device(jax.devices()[0]):
        want = self_join(pts, EPS_2D, distance_impl="fused")
    t_one = time.perf_counter() - t0
    same = got.shape == want.shape and np.array_equal(got, want)
    log(f"four-chips: 4-slab join {got.shape[0]} pairs in {t_dist:.2f}s, "
        f"single-chip join {want.shape[0]} pairs in {t_one:.2f}s, pair "
        f"sets identical: {same}")
    if not same:
        fail("four-chips: the slab join's pair set differs from the "
             "single-chip join's")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 4-slab distributed join against the "
                         "single-chip join (needs four TPU chips)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        fail(f"no repro package under {ROOT}/src: run from a checkout")
    sys.path.insert(0, os.path.join(ROOT, "src"))

    from repro.launch import compile_cache

    cache = compile_cache.enable()
    import jax

    devs = jax.devices()
    dev = devs[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX reports {dev.platform!r} devices "
             f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}); this "
             f"smoke runs only on the chip")
    want_chips = 4 if args.four_chips else 1
    if len(devs) < want_chips:
        fail(f"need {want_chips} TPU chips, JAX reports {len(devs)}")
    import repro  # noqa: F401  (x64 on: int64 cell keys)
    from repro.core import query_join

    log(f"device {dev.platform} {dev.device_kind} x{len(devs)}; jax "
        f"{jax.__version__}; x64 {jax.config.jax_enable_x64}; compile "
        f"cache {cache}; seed {args.seed}")
    rng = np.random.default_rng(args.seed)
    pts = rng.uniform(0, 100, (N_POINTS, 2))
    t0 = time.perf_counter()
    if args.four_chips:
        phase_four_chips(pts)
    else:
        log("join2d: start")
        index, j2 = phase_join2d(pts, rng)
        log("count6d: start")
        c6 = phase_count6d(rng)
        log("serve: start")
        sv = phase_serve(pts, index, rng)
        log(f"summary: route2d {j2['route']}, emit {j2['emit']}; route6d "
            f"{c6['route']}; serve emit {sv['emit']}; band pairs "
            f"{j2['band_pairs']} / {c6['band_pairs']} / {sv['band_pairs']}")
    stats = query_join.executable_cache_stats()
    log(f"executables: fused_pallas {stats['fused_pallas']}, "
        f"fused_reference {stats['fused_reference']}; total "
        f"{time.perf_counter() - t0:.1f}s")
    if stats["fused_pallas"] < 1 or stats["fused_reference"] != 0:
        fail("the fused join did not run through the Mosaic kernel alone")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
