"""The work any epsilon join has to do, and the chip's peaks to weigh it.

Counted from the cell's points and its results alone, never from the
program's grid, padding or window sizes, so the number reads the same
whatever implements the join and cannot pass the peak. For Q query rows in
d dimensions and K_u distinct (query, neighbour) pairs found (each
unordered pair of a self-join once):

  F = 3 * d * K_u            flops: one distance (sub, mul, add per lane)
                             per result pair;
  B = 4 * d * (Q + K_u) + 4 * Q
                             bytes: each query and each neighbour read once
                             in f32, one int32 count written per query;
  T_min = max(F / peak FLOP/s, B / peak HBM bytes/s).

A kernel's roofline share is T_min over its summed device time.
"""
from __future__ import annotations

import json
import os

PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


def peaks(device_kind: str, path: str = PEAKS_FILE) -> dict:
    """The peaks of ``device_kind``; a device not in the table is an
    error, never a default."""
    with open(path) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{path}; known: {sorted(table)}")
    return table[device_kind]


def join_work(dims: int, n_queries: int, n_pairs_unique: int):
    """(flops, bytes) of an epsilon join that found ``n_pairs_unique``
    distinct pairs for ``n_queries`` query rows."""
    flops = 3.0 * dims * n_pairs_unique
    nbytes = 4.0 * dims * (n_queries + n_pairs_unique) + 4.0 * n_queries
    return flops, nbytes


def t_min(flops: float, nbytes: float, peak: dict):
    """(seconds, bound) of the least time the chip could take, and which
    term bounds it: 'compute' or 'memory'."""
    tc = flops / float(peak["flops_per_s"])
    tm = nbytes / float(peak["hbm_bytes_per_s"])
    return (tc, "compute") if tc >= tm else (tm, "memory")


def roofline_pct(flops: float, nbytes: float, kernel_s: float,
                 peak: dict):
    """(share of the roofline in %, bound), or (None, bound) where the
    kernel never ran."""
    t, bound = t_min(flops, nbytes, peak)
    if kernel_s <= 0.0:
        return None, bound
    return 100.0 * t / kernel_s, bound
