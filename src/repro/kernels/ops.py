"""Jit'd public wrappers for the Pallas kernels.

Off the TPU the kernels execute in interpret mode (the kernel body runs as
traced jnp ops); on a TPU backend they compile to Mosaic. The decision is
made at CALL time from the default backend, so importing the library
neither touches a backend nor fixes the mode. f64 inputs (the paper's
precision, unsupported by the TPU's vector units) are computed in f32 on
TPU -- documented hardware adaptation, validated against the f64 oracle
with f32 tolerances.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis import sanitize as _sanitize
from repro.kernels import cell_join as _cell_join
from repro.kernels import distance_tile as _distance_tile
from repro.kernels import fused_join as _fused_join
from repro.kernels.fused_join import on_tpu


def _kernel_dtype(dtype):
    if on_tpu() and dtype == jnp.float64:
        return jnp.float32  # TPU has no f64; paper precision kept on CPU path
    return dtype


def distance_tile_hits(q, pts, eps):
    """Brute-force tile: (TQ,n) x (N,n) -> (TQ,N) bool epsilon-hits."""
    dt = _kernel_dtype(q.dtype)
    return _distance_tile.distance_tile_hits(
        q.astype(dt), pts.astype(dt), eps, interpret=not on_tpu()
    )


def distance_tile_counts(pts, eps, *, tq: int = 256, tc: int = 256):
    """Fused brute-force per-point neighbor counts (excl. self)."""
    dt = _kernel_dtype(pts.dtype)
    return _distance_tile.distance_tile_counts(
        pts.astype(dt), eps, tq=tq, tc=tc, interpret=not on_tpu()
    )


def cell_join_hits(q, cand, valid, eps):
    """Grid-cell refine: (B,n) x (B,C,n) x (B,C) -> (B,C) bool."""
    dt = _kernel_dtype(q.dtype)
    return _cell_join.cell_join_hits(
        q.astype(dt), cand.astype(dt), valid, eps, interpret=not on_tpu()
    )


def fused_join_hits(points_pad, q_batch, win_start, win_count, is_zero,
                    q_pos, eps, *, c, n_real, unicomp, external=False,
                    merged=False, gid_pairs=False,
                    tq=_fused_join.TQ_DEFAULT, keep_hits=True,
                    run_ord=None, run_loop=False, method=None,
                    metric="l2", n_feat=0):
    """Fused gather-refine sweep (all offsets, one launch) -> hits/counts.

    ``q_pos`` is the (Q_pad,) per-row sorted-position array (zeros for
    external queries). method=None dispatches the Mosaic kernel on TPU and
    the identical reference lowering elsewhere; tests force method='kernel'
    to exercise the Pallas path through the interpreter. ``external=True``
    serves queries that are not members of the indexed set
    (core/query_join.py). ``merged=True`` consumes merged last-dimension
    range windows (DESIGN.md S7; lane ``n_real`` carries cell coordinates
    -- exact small integers, so the TPU f32 downcast is lossless).
    ``gid_pairs=True`` rides GLOBAL point ids in the next pad lane and
    masks pairs by gid instead of sorted position (distributed slab join,
    DESIGN.md S3; ids < 2^24, exact in f32). ``run_loop=True`` with a
    ``run_ord`` plan (grid.cell_run_plan) enables the cell-run DMA dedup
    (DESIGN.md S11): one window gather per run of co-located query rows.
    ``metric``/``n_feat`` (DESIGN.md S12) select the static refine
    predicate (core/metric.py) and the feature-lane layout.
    """
    dt = _kernel_dtype(points_pad.dtype)
    pts, qb = points_pad.astype(dt), q_batch.astype(dt)
    out = _fused_join.fused_join_hits(
        pts, qb, win_start, win_count,
        is_zero, q_pos, eps, c=c, n_real=n_real, unicomp=unicomp,
        external=external, merged=merged, gid_pairs=gid_pairs, tq=tq,
        keep_hits=keep_hits, run_ord=run_ord, run_loop=run_loop,
        method=method, metric=metric, n_feat=n_feat,
    )
    if _sanitize.enabled():
        hits, counts, base = out
        code = _fused_join.sanitize_errcodes(
            pts, qb, jnp.asarray(win_start, jnp.int32),
            jnp.asarray(win_count, jnp.int32), counts, base, hits,
            c=c, tq=tq, check_hits=keep_hits, metric=metric, n_real=n_real)
        _sanitize.record(
            f"fused_join[c={c},tq={tq},merged={merged},ext={external},"
            f"metric={metric}]",
            code)
    return out


def fused_window_hits(points_sorted, q, cand_pos, valid, eps):
    """Gather-free refine for the compacted sweep: positions, not coords."""
    dt = _kernel_dtype(q.dtype)
    return _fused_join.fused_window_hits(
        points_sorted.astype(dt), q.astype(dt), cand_pos, valid, eps
    )
