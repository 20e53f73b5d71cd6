#!/usr/bin/env python3
"""Find a serving cell's knee: the highest offered rate it sustains.

    python3 benchmarks/chip/sweep.py --workload syn2d.serve --seed 1 \
        --seconds 8 --rates 50 100 200 400 800

One process: the cell's set-up once, then one open-loop window per rate,
the same traffic mix at each. A rate is sustained when the requests of the
window's last quarter wait no longer than those of its first quarter (by
mean latency, within ``--grow`` times), so the backlog does not grow, and
the completed rate keeps up with the offered one. Prints one line per rate
and a final JSON line with the highest sustained rate. The knee is found
once and written into the cell's traffic file as a number; the benchmark
itself never searches for a rate.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def sustained(lat_ms: np.ndarray, wall_s: float, offered: float,
              seconds: float, grow: float) -> bool:
    done = lat_ms[np.isfinite(lat_ms)]
    if done.size < lat_ms.size or done.size < 8:
        return False
    q = done.size // 4
    first, last = done[:q].mean(), done[-q:].mean()
    return bool(last <= grow * first + 1.0
                and wall_s <= seconds * 1.25 + 1.0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--grow", type=float, default=1.5)
    ap.add_argument("--max-wall", type=float, default=600.0,
                    help="stop before a rate once this much time has gone")
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness

    spec = harness.resolve(ROOT, args.workload)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    harness.enable_compile_cache(ROOT)
    harness._devices(int(spec.workload["chips"]), require_tpu=True)
    import repro  # noqa: F401

    rngs = {k: np.random.default_rng([args.seed, i]) for i, k in
            enumerate(("data", "traffic", "check", "warm"))}
    cell = spec.driver().Cell(spec.config, dict(spec.traffic), rngs, spec)
    t0 = time.perf_counter()
    cell.setup()
    print(f"[sweep] {args.workload}: set-up {time.perf_counter() - t0:.1f}s",
          flush=True)
    best = None
    rows = []
    t_sweep = time.perf_counter()
    for rate in args.rates:
        if time.perf_counter() - t_sweep > args.max_wall:
            break
        cell.traffic["rate_rps"] = rate
        cell.prepare(args.seconds)
        e2e = cell.window(args.seconds)
        st = cell.stats()
        ok = sustained(cell.run.latencies_ms, cell.run.wall_s, rate,
                       args.seconds, args.grow)
        row = {"offered_rps": rate,
               "completed_rps": st["attempted"] / st["wall_s"],
               "p50_ms": e2e["query_p50_ms"], "p95_ms": e2e["query_p95_ms"],
               "late_p95_ms": st["late_p95_ms"],
               "rows_per_launch": st["rows_launched"] / max(st["launches"],
                                                             1),
               "programs_added_in_window": st["programs_added_in_window"],
               "sustained": ok}
        rows.append(row)
        print("[sweep] " + json.dumps(row), flush=True)
        if ok and (best is None or rate > best):
            best = rate
    print(json.dumps({"workload": args.workload, "knee_rps": best,
                      "rates": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
