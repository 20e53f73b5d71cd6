"""Uniform points in a box: the paper's Syn datasets (Table I).

Config keys: ``n_points``, ``dims``, ``lo``, ``hi`` (the box side is
``hi - lo`` at ``n_points``). ``cut`` divides the point count and shrinks
the first side by the same factor, so the density (and with it the
neighbours per point and the points per grid cell) stays as published.
"""
from __future__ import annotations

import numpy as np


def extent(cfg: dict, cut: int) -> np.ndarray:
    """(dims, 2) bounds of the box holding ``n_points / cut`` points."""
    lo, hi = float(cfg["lo"]), float(cfg["hi"])
    box = np.array([[lo, hi]] * int(cfg["dims"]))
    box[0, 1] = lo + (hi - lo) / cut
    return box


def points(cfg: dict, cut: int, rng: np.random.Generator) -> np.ndarray:
    n = int(cfg["n_points"]) // cut
    box = extent(cfg, cut)
    return rng.uniform(box[:, 0], box[:, 1], size=(n, box.shape[0]))
