"""Compile-time metric trait for the search-and-refine pipeline (DESIGN.md S12).

The paper's pipeline is metric-agnostic in principle: the grid PRUNES in a
geometry space, the refine predicate DECIDES in metric space. This module is
the one place that knows both halves for every supported metric; everything
else (kernels, drivers, services, benchmarks) threads an opaque static
``metric=`` string through to the helpers here.

Each metric provides three things:

  * **canonicalization** (``canonicalize``): map raw input points onto the
    (geometry, features) pair the grid and kernel consume.

      - ``l2``: identity. Geometry IS the point; no feature lanes.
      - ``cosine``: unit-normalize rows (zero-norm / nonfinite input is a
        hard error). On the unit sphere ``cos(a,b) >= t`` is EXACTLY
        ``||a-b||^2 <= 2 - 2t``, so the cosine join reduces to an L2 join
        at threshold ``sqrt(2 - 2t)`` and the whole existing machinery
        (grid, merged-range sweep, cell-run plan, occupancy planner) works
        unchanged. The static ``metric="cosine"`` tag only keys the
        executable; the traced computation is the L2 one.
      - ``jaccard``: token sets become packed bitmaps riding the pad-lane
        mechanism (``TOKEN_BITS`` tokens per lane as exact small-integer
        float words), and the GEOMETRY is the 1-D set-size coordinate:
        ``J(a,b) >= t`` with ``|b| >= |a|`` implies ``|b| - |a| <=
        (1-t)|b| <= (1-t)S_max``, so a 1-D grid over sizes with cell width
        ``max((1-t) * S_max, 1)`` is a sound prune.

  * a **refine predicate** (``tile_refine_hits`` for the fused kernel's
    per-row window form, ``plane_refine_hits`` for the reference lowering's
    column-gather form) evaluated under the same descriptor/count->fill
    contract for every metric, plus the scalar it consumes
    (``device_refine_scalar``: eps^2 for l2/cosine, the raw Jaccard
    threshold t for jaccard).

  * a **brute-force oracle** (``brute_force_join_metric``) built from the
    SAME float expressions as the kernel predicate, so pair-set parity with
    the fused path is structural rather than approximate.

Predicate ownership: ``eps_squared`` / ``l2_sq_hits`` below are the ONLY
place the repo derives a squared-epsilon threshold; ``analysis/lint.py``
(rule ``eps-predicate``) flags any ``d2 <= eps*eps``-shaped comparison that
reappears outside this module.
"""
from __future__ import annotations

import dataclasses
from typing import Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

METRICS = ("l2", "cosine", "jaccard")

# Jaccard bitmap packing: tokens per feature lane. Lanes are stored in the
# points array's float dtype, so the packed word must be EXACT in float32;
# 16-bit words (max 65535 < 2^24) are, 32-bit words are not.
TOKEN_BITS = 16

# |1 - ||x||^2| tolerance for "canonical cosine input" (sanitize check):
# float32 normalization of well-scaled vectors lands well inside this.
NORM_TOL = 1e-3

_POPCOUNT16: Optional[np.ndarray] = None


def check_metric(metric: str) -> str:
    if metric not in METRICS:
        raise ValueError(f"unknown metric {metric!r}; expected one of "
                         f"{METRICS}")
    return metric


def metric_feat_lanes(metric: str, n_feat: int) -> int:
    """Feature lanes a metric rides in the padded points array (0 unless
    the metric carries non-geometric payload; jaccard carries bitmaps)."""
    return int(n_feat) if metric == "jaccard" else 0


# ---------------------------------------------------------------------------
# The refine predicate (single owner of the squared-threshold form)
# ---------------------------------------------------------------------------

def eps_squared(eps):
    """THE squared-threshold derivation. Works on python floats, numpy and
    jax arrays alike (pure operators); every other module must obtain its
    squared epsilon from here so the linter can hold the grep gate."""
    return eps * eps


def l2_sq_hits(d2, eps):
    """``d2 <= eps^2``: the L2 refine predicate against an UNsquared
    threshold (host-side / oracle form)."""
    return d2 <= eps_squared(eps)


def l2_sq_hits_presquared(d2, eps2):
    """``d2 <= eps2`` against an already-squared threshold (kernel form:
    the squaring happened once in ``device_refine_scalar``)."""
    return d2 <= eps2


def device_refine_scalar(metric: str, eps, dtype) -> jax.Array:
    """The (1, 1) scalar operand the fused kernel refines against.

    l2/cosine consume the SQUARED geometry threshold (the kernel compares
    squared distances); jaccard consumes the similarity threshold ``t``
    verbatim (the kernel compares ``inter >= t * union``). The threshold
    stays a TRACED operand for every metric, so serving a mix of radii
    hits one executable per metric.
    """
    s = jnp.asarray(eps, dtype)
    if metric != "jaccard":
        s = eps_squared(s)
    return jnp.reshape(s, (1, 1))


def tile_refine_hits(metric: str, qrow, window_t, scalar, *, n_real: int,
                     n_feat: int):
    """Fused-kernel refine: one query row against its candidate window.

    ``qrow`` is (1, L) and ``window_t`` the TRANSPOSED window (L, C), with
    L the padded lane count (geometry lanes [0, n_real), feature lanes
    [n_real, n_real+n_feat)) and candidates along lanes; ``scalar`` is the
    (1, 1) ``device_refine_scalar`` value. Returns a (1, C) bool. Every
    operand stays 2-D (Mosaic has no 1-D vector layout), and the
    arithmetic is ``plane_refine_hits``' lane by lane, in the same order.
    """
    if metric == "jaccard":
        # Sizes from the geometry lane, NOT bitmap popcounts: a query
        # packed against a smaller index vocabulary keeps its TRUE size
        # (out-of-vocabulary tokens can never intersect indexed sets, so
        # the intersection is exact and the union needs the true size).
        sq = qrow[:, 0:1]
        sc = window_t[0:1, :]
        inter = jnp.zeros(sc.shape, jnp.int32)
        for k in range(n_feat):
            lane = n_real + k
            qw = qrow[:, lane:lane + 1].astype(jnp.int32)
            cw = window_t[lane:lane + 1, :].astype(jnp.int32)
            inter = inter + jax.lax.population_count(qw & cw)
        inter = inter.astype(window_t.dtype)
        union = sq + sc - inter
        return (union > 0) & (inter >= scalar * union)
    d2 = jnp.zeros((1, window_t.shape[1]), window_t.dtype)
    for dim in range(n_real):
        d = qrow[:, dim:dim + 1] - window_t[dim:dim + 1, :]
        d2 = d2 + d * d
    return l2_sq_hits_presquared(d2, scalar)


def plane_refine_hits(metric: str, points_pad, q_batch, cand_pos, scalar, *,
                      n_real: int, n_feat: int):
    """Reference-lowering refine: per-lane COLUMN gathers, no (Q, C, L)
    tensor (matches the fused kernel's arithmetic lane by lane).

    ``q_batch`` is (Q, L), ``cand_pos`` is (Q, C) gather positions into
    ``points_pad`` rows. Returns (Q, C) bool.
    """
    if metric == "jaccard":
        sq = q_batch[:, 0][:, None]
        sc = jnp.take(points_pad[:, 0], cand_pos)
        inter = jnp.zeros(cand_pos.shape, jnp.int32)
        for k in range(n_feat):
            qw = q_batch[:, n_real + k].astype(jnp.int32)[:, None]
            cw = jnp.take(points_pad[:, n_real + k],
                          cand_pos).astype(jnp.int32)
            inter = inter + jax.lax.population_count(qw & cw)
        inter = inter.astype(points_pad.dtype)
        union = sq + sc - inter
        return (union > 0) & (inter >= scalar * union)
    d2 = jnp.zeros(cand_pos.shape, points_pad.dtype)
    for dim in range(n_real):
        cd = jnp.take(points_pad[:, dim], cand_pos)
        d2 = d2 + (q_batch[:, dim][:, None] - cd) ** 2
    return l2_sq_hits_presquared(d2, scalar)


# ---------------------------------------------------------------------------
# Canonicalization
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Canonical:
    """A dataset canonicalized for one metric.

    ``geom`` is what the grid indexes (the points themselves for l2, unit
    rows for cosine, (N, 1) set sizes for jaccard); ``feats`` is the
    non-geometric payload riding the pad lanes (packed token words for
    jaccard, None otherwise). ``eps`` is the threshold in METRIC units as
    given; ``eps_geom`` is the grid cell width / L2 prune radius derived
    from it; ``refine`` is the scalar the fused kernel consumes.
    """

    metric: str
    geom: np.ndarray                  # (N, n_geom)
    feats: Optional[np.ndarray]       # (N, n_feat) packed words, or None
    n_feat: int
    eps: float                        # metric-units threshold
    eps_geom: float                   # grid cell width (geometry space)
    vocab: int = 0                    # jaccard: packed vocabulary size

    @property
    def refine(self) -> float:
        """Kernel scalar in UNsquared form: the geometry radius for
        l2/cosine (the kernel squares it once), the threshold t for
        jaccard (consumed verbatim)."""
        return self.eps if self.metric == "jaccard" else self.eps_geom


def cosine_eps_geom(eps: float) -> float:
    """The cosine -> L2 threshold reduction on the unit sphere:
    ``cos(a,b) >= eps  <=>  ||a-b||^2 = 2 - 2cos(a,b) <= 2 - 2eps``."""
    return float(np.sqrt(max(2.0 - 2.0 * float(eps), 0.0)))


def _unit_rows(points, *, what: str) -> np.ndarray:
    pts = np.asarray(points)
    if pts.ndim != 2:
        raise ValueError(f"{what} must be 2-D (N, d), got shape {pts.shape}")
    if not np.issubdtype(pts.dtype, np.floating):
        pts = pts.astype(np.float64)
    if not np.isfinite(pts).all():
        bad = np.flatnonzero(~np.isfinite(pts).all(axis=1))
        raise ValueError(
            f"cosine metric: {what} rows {bad[:8].tolist()} contain "
            f"non-finite values; clean the embeddings before joining")
    norms = np.linalg.norm(pts, axis=1)
    zero = np.flatnonzero(norms == 0)
    if zero.size:
        raise ValueError(
            f"cosine metric: {what} rows {zero[:8].tolist()} have zero "
            f"norm; direction is undefined for the zero vector")
    return pts / norms[:, None]


def pack_tokens(sets, *, vocab: Optional[int] = None
                ) -> tuple[np.ndarray, np.ndarray, int]:
    """Pack token sets into (sizes, words, vocab).

    ``sets`` is either a sequence of token-id iterables or an (N, V)
    binary membership matrix. Returns float32 ``sizes`` (N,) -- TRUE set
    sizes, counting every distinct token -- and float32 ``words``
    (N, ceil(vocab / TOKEN_BITS)) whose lanes hold exact 16-bit packed
    words. With an explicit ``vocab`` (query-side packing against a fixed
    index vocabulary), out-of-vocabulary tokens still count toward the
    size but set no bits: they cannot intersect any indexed set, so the
    intersection stays exact and the union uses the true size.
    """
    if isinstance(sets, np.ndarray) and sets.ndim == 2:
        mask = np.asarray(sets) != 0
        ind = [np.flatnonzero(row) for row in mask]
    else:
        ind = []
        for s in sets:
            toks = np.unique(np.asarray(list(s), dtype=np.int64))
            if toks.size and toks[0] < 0:
                raise ValueError("jaccard metric: token ids must be >= 0")
            ind.append(toks)
    sizes = np.asarray([t.size for t in ind], np.float32)
    max_tok = max((int(t[-1]) for t in ind if t.size), default=-1)
    if vocab is None:
        vocab = max_tok + 1
        clip = False
    else:
        vocab = int(vocab)
        clip = True
    n_words = max(-(-max(vocab, 1) // TOKEN_BITS), 1)
    words = np.zeros((len(ind), n_words), np.uint16)
    for i, toks in enumerate(ind):
        if clip:
            toks = toks[toks < vocab]
        if toks.size:
            np.bitwise_or.at(
                words[i], toks // TOKEN_BITS,
                (np.uint16(1) << (toks % TOKEN_BITS).astype(np.uint16)))
    return sizes, words.astype(np.float32), int(vocab)


def canonicalize(points, eps, *, metric: str = "l2",
                 vocab: Optional[int] = None) -> Canonical:
    """Canonicalize a dataset for one metric (index-build side)."""
    check_metric(metric)
    if metric == "l2":
        geom = np.asarray(points)
        if geom.ndim != 2:
            raise ValueError(f"points must be 2-D, got shape {geom.shape}")
        e = float(eps)
        return Canonical("l2", geom, None, 0, e, e)
    if metric == "cosine":
        e = float(eps)
        if not (-1.0 <= e < 1.0):
            raise ValueError(
                f"cosine threshold must lie in [-1, 1), got {e}; it is a "
                f"minimum cosine SIMILARITY, not a distance")
        geom = _unit_rows(points, what="points")
        return Canonical("cosine", geom, None, 0, e, cosine_eps_geom(e))
    # jaccard
    t = float(eps)
    if not (0.0 < t <= 1.0):
        raise ValueError(
            f"jaccard threshold must lie in (0, 1], got {t}; it is a "
            f"minimum Jaccard similarity")
    sizes, words, vocab = pack_tokens(points, vocab=vocab)
    s_max = float(sizes.max()) if sizes.size else 0.0
    # |b| >= |a| and J >= t  =>  |b| - |a| <= (1-t)|b| <= (1-t)S_max:
    # a 1-D grid over set sizes at this width is a sound prune. Floor at
    # 1 so t = 1 (exact duplicates) still yields a positive cell width.
    eps_geom = max((1.0 - t) * s_max, 1.0)
    geom = sizes[:, None]
    return Canonical("jaccard", geom, words, words.shape[1], t, eps_geom,
                     vocab)


def canonicalize_queries(canon: Canonical, queries
                         ) -> tuple[np.ndarray, Optional[np.ndarray]]:
    """Canonicalize an EXTERNAL query batch against an indexed dataset's
    canonical form. Returns (geometry rows, feature rows or None)."""
    if canon.metric == "l2":
        q = np.asarray(queries)
        return q, None
    if canon.metric == "cosine":
        return _unit_rows(queries, what="queries"), None
    sizes, words, _ = pack_tokens(queries, vocab=canon.vocab)
    return sizes[:, None].astype(canon.geom.dtype), words


def request_scalar(metric: str, eps: float, *, index_eps: float,
                   index_eps_geom: float) -> float:
    """Map a per-request threshold (METRIC units) onto the kernel scalar,
    validating the index's stencil still covers it.

    l2: smaller radii only. cosine: HIGHER similarity only (a lower
    similarity floor means a larger geometry radius than the grid was
    built for). jaccard: HIGHER thresholds only, and the scalar is t
    itself -- a stricter t shrinks the size-difference prune radius, so
    the build-time windows remain a superset of the candidates.
    """
    check_metric(metric)
    if metric == "l2":
        if eps > index_eps * (1 + 1e-12):
            raise ValueError(
                f"query eps {eps} exceeds index build eps {index_eps}; the "
                f"adjacent-cell stencil only covers the build radius")
        return float(eps)
    if metric == "cosine":
        if eps < index_eps - 1e-12:
            raise ValueError(
                f"query cosine threshold {eps} is below the index build "
                f"threshold {index_eps}; a lower similarity floor needs a "
                f"rebuilt grid")
        geom = cosine_eps_geom(eps)
        return float(min(geom, index_eps_geom))
    if eps < index_eps - 1e-12:
        raise ValueError(
            f"query jaccard threshold {eps} is below the index build "
            f"threshold {index_eps}; a looser threshold needs a rebuilt "
            f"grid")
    return float(eps)


# ---------------------------------------------------------------------------
# Brute-force oracles
# ---------------------------------------------------------------------------

def _popcount16_table() -> np.ndarray:
    global _POPCOUNT16
    if _POPCOUNT16 is None:
        bits = np.unpackbits(
            np.arange(65536, dtype=np.uint16).view(np.uint8).reshape(-1, 2),
            axis=1)
        _POPCOUNT16 = bits.sum(axis=1).astype(np.uint8)
    return _POPCOUNT16


def _jaccard_brute_hits(canon: Canonical, block: int = 512) -> np.ndarray:
    """(K, 2) ordered hit pairs (both directions, self excluded) by exact
    bitmap intersection, using the SAME float comparison as the kernel."""
    words = canon.feats.astype(np.uint16)
    sizes = canon.geom[:, 0].astype(canon.geom.dtype)
    t = canon.geom.dtype.type(canon.eps)
    table = _popcount16_table()
    n = words.shape[0]
    out = []
    for lo in range(0, n, block):
        hi = min(lo + block, n)
        inter = table[words[lo:hi, None, :] & words[None, :, :]] \
            .sum(axis=-1, dtype=np.int64)
        inter_f = inter.astype(canon.geom.dtype)
        union = sizes[lo:hi, None] + sizes[None, :] - inter_f
        hit = (union > 0) & (inter_f >= t * union)
        hit[np.arange(lo, hi) - lo, np.arange(lo, hi)] = False
        a, b = np.nonzero(hit)
        out.append(np.stack([a + lo, b], axis=1).astype(np.int32))
    if not out:
        return np.empty((0, 2), np.int32)
    return np.concatenate(out, axis=0)


def brute_force_join_metric(canon: Canonical, *, tile: int = 256
                            ) -> np.ndarray:
    """Metric-generic brute-force oracle: lexsorted (K, 2) ordered pairs.

    l2/cosine delegate to the blocked L2 oracle on the canonical geometry
    at the reduced threshold; jaccard runs the exact bitmap intersection.
    Every comparison uses the same float expression as the fused kernel,
    so pair-set parity with the grid path is structural.
    """
    if canon.metric in ("l2", "cosine"):
        from repro.core import brute
        return brute.brute_force_join(canon.geom, canon.eps_geom, tile=tile)
    pairs = _jaccard_brute_hits(canon)
    if pairs.shape[0]:
        pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    return pairs


def brute_force_count_metric(canon: Canonical, *, tile: int = 256) -> int:
    """Ordered-pair count under the metric's brute-force oracle."""
    if canon.metric in ("l2", "cosine"):
        from repro.core import brute
        return brute.brute_force_count(canon.geom, canon.eps_geom, tile=tile)
    return int(_jaccard_brute_hits(canon).shape[0])


def jaccard_similarity(a, b) -> float:
    """Exact Jaccard similarity of two token iterables (test helper)."""
    sa, sb = set(a), set(b)
    if not sa and not sb:
        return 0.0
    return len(sa & sb) / len(sa | sb)
