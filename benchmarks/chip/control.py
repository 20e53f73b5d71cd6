#!/usr/bin/env python3
"""The control: the reference put in the program's place, one precision down.

The program decides each pair in f32 (the chip has no f64). The step that
would tempt a later change is to hold and compare the points in bfloat16;
the control does exactly that: the plain strip scan of ``oracle.py`` with
every coordinate, difference, square and sum rounded to bfloat16
(``ml_dtypes``), against eps^2 in bfloat16. Its answers must come out as
not correct under the same comparison that judges the program.

    python3 benchmarks/chip/control.py --workload syn2d.join \
        --seconds 5 --seeds 101 102 103

runs, per seed, one short window of the cell at its own size and load,
then compares both the program's answers and the control's answers to the
f64 reference; it prints one JSON line per seed with both sets of numbers.
The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import ml_dtypes
import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))


def _bf16(a) -> np.ndarray:
    return np.asarray(a, np.float32).astype(ml_dtypes.bfloat16).astype(
        np.float32)


def bf16_pairs(points: np.ndarray, queries: np.ndarray, eps: float, *,
               exclude: np.ndarray | None = None) -> np.ndarray:
    """(row, point id) pairs whose bfloat16 distance is within eps."""
    import oracle

    p, q = _bf16(points), _bf16(queries)
    strip = oracle.StripIndex(p)
    sp = strip.sorted.astype(np.float32)
    eps2 = _bf16(_bf16(eps) * _bf16(eps))
    out = []
    # a bfloat16 difference errs by at most 2^-8 of itself: the strip
    # holds every pair the bfloat16 test can accept
    for rows, cand in strip.candidates(q, 1.01 * float(_bf16(eps))):
        d2 = np.zeros(rows.size, np.float32)
        for d in range(q.shape[1]):
            diff = _bf16(sp[cand, d] - q[rows, d])
            d2 = _bf16(d2 + _bf16(diff * diff))
        ids = strip.order[cand]
        keep = d2 <= eps2
        if exclude is not None:
            keep &= ids != exclude[rows]
        out.append(np.stack([rows[keep], ids[keep]], axis=1))
    return (np.concatenate(out).astype(np.int64) if out
            else np.empty((0, 2), np.int64))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, HERE)
    import harness

    for seed in args.seeds:
        try:
            out = harness.run(ROOT, args.workload, seed, args.seconds, False,
                              control=True)
        except harness.HarnessError as e:
            print(f"control.py: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "program": out["checks"],
                          "control": out["control_checks"],
                          "notes": out["notes"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
