#!/usr/bin/env python3
"""Run one cell of the chip benchmark (BENCHMARK.json at the checkout root).

    python3 benchmarks/chip/run.py --workload syn2d.join --seed 7 \
        --seconds 30 --trace 0

One process, one chip run: it makes the cell's points and traffic from
``--seed``, warms every program the window runs (set-up), measures for
``--seconds``, then compares what the window returned with the f64
reference. ``--trace 1`` records a profiler trace of the window and
reports the cell's per-layer metrics instead of its end-to-end ones.

The compared numbers, each beside its limit, are the last lines on
standard error; the last line on standard output is the result: one JSON
object with ``correct``, ``attempted``, ``failed``, ``metrics``,
``device`` (and ``breakdown`` when traced), ``notes``, and ``checks``
last. Without a TPU, or outside a checkout that holds the program, it
exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import harness

    try:
        out = harness.run(ROOT, args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start=T_START)
    except harness.HarnessError as e:
        print(f"run.py: {e}", file=sys.stderr, flush=True)
        return 2
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {out['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
