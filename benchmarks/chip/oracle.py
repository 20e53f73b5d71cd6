"""The plain reference: exact f64 neighbour sets, shared with no program code.

``f32_band`` and the sure/maybe semantics are copied from the bring-up smoke
(``chip_smoke.py``): the chip decides ``d2(f32(p), f32(q)) <= f32(eps)^2``
in f32, so a pair whose true distance lies within the band of eps may go
either way. Everything else about the answer is exact:

  sure   pairs with D <= eps - band, which the program must return;
  maybe  pairs with D <= eps + band, the only ones it may return.

The brute force here measures every query against every point of a strip
of the data sorted by one coordinate: a point outside the strip is further
than eps + band along that axis alone, so it cannot be a neighbour. The
distances themselves are plain numpy f64, query by query.

Answers are compared as int64 pair keys ``row * n_points + point_id``.
"""
from __future__ import annotations

import numpy as np


def f32_band(points: np.ndarray, eps: float) -> float:
    """Half-width of the distance band around eps inside which an f32
    decision may differ from the exact f64 one (see ``chip_smoke.py``):
    coordinate rounding moves D by at most 2*sqrt(n)*u*M, and the f32
    evaluation of D'^2 against eps^2 errs by (3n + 3)/2*u*eps near the
    threshold; the band doubles the sum for second-order terms."""
    u = 2.0 ** -24
    n = points.shape[1]
    m = float(np.abs(points).max())
    return 2.0 * (2.0 * np.sqrt(n) * u * m + (3 * n + 3) / 2 * u * eps)


class StripIndex:
    """The points sorted along their widest coordinate, for strip scans."""

    def __init__(self, points: np.ndarray):
        self.points = np.asarray(points, np.float64)
        span = self.points.max(axis=0) - self.points.min(axis=0)
        self.axis = int(np.argmax(span))
        self.order = np.argsort(self.points[:, self.axis], kind="stable")
        self.sorted = self.points[self.order]
        self.key = np.ascontiguousarray(self.sorted[:, self.axis])

    def candidates(self, queries: np.ndarray, radius: float,
                   chunk_pairs: int = 1 << 22):
        """Yield (rows, cand) chunks: for each query row, the positions in
        ``sorted`` of every point within ``radius`` along the strip axis,
        at most about ``chunk_pairs`` pairs a chunk."""
        q = np.asarray(queries)
        lo = np.searchsorted(self.key, q[:, self.axis] - radius, "left")
        hi = np.searchsorted(self.key, q[:, self.axis] + radius, "right")
        width = hi - lo
        start = 0
        while start < q.shape[0]:
            cum = np.cumsum(width[start:])
            stop = start + max(int(np.searchsorted(cum, chunk_pairs)), 1)
            stop = min(stop, q.shape[0])
            w = width[start:stop]
            rows = np.repeat(np.arange(start, stop), w)
            first = np.repeat(lo[start:stop] - np.cumsum(w) + w, w)
            yield rows, first + np.arange(rows.size)
            start = stop

    def neighbour_keys(self, queries: np.ndarray, radius2_sure: float,
                       radius2_maybe: float, radius: float, *,
                       exclude: np.ndarray | None = None,
                       chunk_pairs: int = 1 << 22):
        """Sorted int64 keys ``row * n + id`` of the sure and maybe sets.

        ``exclude`` gives, per query row, a point id that is not its own
        neighbour (a self-join drops the pair (i, i)); -1 excludes none."""
        q = np.asarray(queries, np.float64)
        n = self.points.shape[0]
        sure, maybe = [], []
        for rows, cand in self.candidates(q, radius, chunk_pairs):
            d2 = np.zeros(rows.size)
            for d in range(q.shape[1]):
                diff = self.sorted[cand, d] - q[rows, d]
                d2 += diff * diff
            ids = self.order[cand]
            keep = d2 <= radius2_maybe
            if exclude is not None:
                keep &= ids != exclude[rows]
            keys = rows[keep].astype(np.int64) * n + ids[keep]
            maybe.append(keys)
            sure.append(keys[d2[keep] <= radius2_sure])
        return _cat(sure), _cat(maybe)


def _cat(parts: list) -> np.ndarray:
    return np.sort(np.concatenate(parts)) if parts else np.empty(0, np.int64)


def reference_keys(points: np.ndarray, queries: np.ndarray, eps: float, *,
                   band: float, exclude: np.ndarray | None = None,
                   strip: StripIndex | None = None):
    """(sure, maybe) pair keys of ``queries`` against ``points``."""
    strip = StripIndex(points) if strip is None else strip
    r_lo = max(eps - band, 0.0)
    r_hi = eps + band
    return strip.neighbour_keys(queries, r_lo * r_lo, r_hi * r_hi, r_hi,
                                exclude=exclude)


def compare_keys(got: np.ndarray, sure: np.ndarray,
                 maybe: np.ndarray) -> dict:
    """How far the program's pair keys stray from sure <= got <= maybe.

    ``missing``: sure pairs not returned; ``extra``: returned pairs outside
    maybe; ``duplicate``: pairs returned more than once; ``band``: returned
    pairs that lie in the f32 band (either answer is right)."""
    got = np.sort(np.asarray(got, np.int64))
    uniq = np.unique(got)
    return {
        "missing": int(np.setdiff1d(sure, uniq, assume_unique=True).size),
        "extra": int(np.setdiff1d(uniq, maybe, assume_unique=True).size),
        "duplicate": int(got.size - uniq.size),
        "band": int(np.intersect1d(uniq, np.setdiff1d(maybe, sure,
                                                      assume_unique=True),
                                   assume_unique=True).size),
    }


def brute_keys(points: np.ndarray, queries: np.ndarray, eps: float, *,
               band: float, exclude: np.ndarray | None = None):
    """The same sets by a full (queries x points) f64 scan: the check of
    the strip scan in the tests, at small sizes only."""
    p = np.asarray(points, np.float64)
    q = np.asarray(queries, np.float64)
    d2 = ((q[:, None, :] - p[None, :, :]) ** 2).sum(-1)
    if exclude is not None:
        d2[np.arange(q.shape[0]), exclude] = np.inf
    n = p.shape[0]
    r_lo, r_hi = max(eps - band, 0.0), eps + band
    rows, ids = np.nonzero(d2 <= r_hi * r_hi)
    maybe = rows.astype(np.int64) * n + ids
    sure = maybe[d2[rows, ids] <= r_lo * r_lo]
    return np.sort(sure), np.sort(maybe)
