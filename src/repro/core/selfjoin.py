"""The self-join (paper Alg. 1 + SV optimizations), TPU-native formulation.

The paper's CUDA kernel is thread-per-point: each thread walks the 3^n
adjacent cells of its point, binary-searches B per cell, and appends result
pairs through a global atomic. On a TPU there are no per-lane scatters or
atomics, so we restructure the same computation as an **offset sweep**
(DESIGN.md S2):

    for each stencil offset o in {-1,0,1}^n (or the UNICOMP half-stencil):
        nbr[h]   = rank in B of (cell h + o)          -- one batched searchsorted
        for every query point i (vectorized):          -- regular, branch-free
            candidates = A[start[nbr[rank_i]] : +count]  (padded to C_max slots)
            hits       = ||q_i - cand||^2 <= eps^2       (masked)

The candidate distance evaluation runs in ONE path, the fused sweep of
kernels/fused_join.py: the gather happens INSIDE the kernel (window
descriptors via scalar prefetch, HBM->VMEM dynamic slice per window), all
stencil offsets sweep in ONE launch with the query tile VMEM-resident
throughout, and count+fill share a single distance evaluation per
candidate: the kernel returns the masked hit set plus per-query counts and
the per-tile exclusive-scan slot bases, so the fill phase only scatters
(DESIGN.md S4). No (B, C, n) intermediate exists. Launches are
occupancy-bucketed (DESIGN.md S6): query rows partition by
candidate-capacity class (grid.occupancy_plan) and each bucket sweeps at
ITS static window capacity, so skewed data stops paying the global
max_per_cell per row. Every launch uses the kernel's query tile
``fused_join.TQ_DEFAULT``.

The join's decisions come from the index and the backend, never from a
measurement: the sweep (merged 3^(n-1) ranges, or per-cell 3^n where the
coordinates leave no free pad lane) from ``n_dims`` (``_resolve_merge``),
the cell-run loop from mean cell occupancy (``_join_run_loop``), and the
count route ('dense', or 'compact' on the TPU in the empty-neighbour
regime) from the grid's volume, cell count and largest cell
(``_count_route``). The ``jnp``
gather helpers below serve only ``per_point_neighbor_counts`` and the
distributed count step; ``core/brute.py`` is the independent oracle.

Result emission replaces the paper's atomics with the fused single-pass
count -> fill above. The paper sorts the key/value result after the
kernel, and we optionally do the same. Batching over query points (paper
SV-A) bounds both the result buffer and the per-batch hit set; the driver
``self_join_batched`` uses >= 3 batches like the paper and overlaps device
compute with host transfers via JAX async dispatch.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.core import metric as metric_lib
from repro.core.grid import (GridIndex, build_grid,
                             neighbor_rank, round_up as _round_up)
from repro.core.stencil import stencil_offsets


@dataclasses.dataclass(frozen=True)
class JoinStats:
    """Work counters (paper Table II analogue: cells and distances checked)."""

    total_pairs: int          # ordered pairs with dist <= eps (excl. self)
    cells_visited: int        # non-empty adjacent cells evaluated
    candidates_checked: int   # candidate slots with a real point
    offsets: int              # stencil offsets swept
    # count route (``_count_route``):
    #   'dense'     occupancy-bucketed fused sweep (full window per probe)
    #   'compact'   per-offset live-query packing before the gather (TPU,
    #               empty-neighbour regime)
    route: str = "dense"

    @property
    def n_offsets(self) -> int:
        """Stencil offsets swept: 3^n (full) / (3^n+1)/2 (UNICOMP) for the
        per-cell sweep; 3^(n-1) / (3^(n-1)+1)/2 for the merged-range sweep
        (DESIGN.md S7)."""
        return self.offsets


def _offset_tables(index: GridIndex, unicomp: bool):
    """Static offset list -> (deltas (n_off,), is_zero (n_off,)) device arrays."""
    from repro.core.grid import offset_deltas

    offs = stencil_offsets(index.n_dims, unicomp)          # (n_off, n) np
    deltas = offset_deltas(offs, index.dims)               # (n_off,)
    is_zero = jnp.asarray(np.all(offs == 0, axis=1))
    return deltas, is_zero


def _merged_offset_tables(index: GridIndex, unicomp: bool):
    """Merged-range sweep tables (DESIGN.md S7).

    Returns (dtab (3, n_off) int64, is_zero (n_off,)): row 0 the linearized
    reduced offsets (last coordinate 0), rows 1/2 the lo/hi last-dimension
    span deltas each reduced offset covers ({-1..+1}; the UNICOMP zero
    offset spans [0, +1]). Packed as one array so the jitted descriptor
    preps keep a single traced-operand signature for both sweep modes.
    """
    from repro.core.grid import offset_deltas
    from repro.core.stencil import merged_stencil_offsets

    reduced, lo, hi = merged_stencil_offsets(index.n_dims, unicomp)
    deltas = offset_deltas(reduced, index.dims)
    dtab = jnp.stack([deltas, jnp.asarray(lo), jnp.asarray(hi)])
    is_zero = jnp.asarray(np.all(reduced == 0, axis=1))
    return dtab, is_zero


def _resolve_merge(index: GridIndex, merge_last_dim: Optional[bool]) -> bool:
    """The shared merge-resolution rule applied to this index (see
    ``kernels.fused_join.resolve_merge_last_dim``)."""
    from repro.kernels.fused_join import resolve_merge_last_dim

    return resolve_merge_last_dim(index.n_dims, merge_last_dim)


def _neighbor_ranks_for_delta(index: GridIndex, delta: jax.Array) -> jax.Array:
    """Rank in B of (cell + offset) for every non-empty cell; -1 if absent.

    Padding cells resolve to padding slots whose cell_count is 0, so they
    contribute no candidates downstream.
    """
    from repro.core.grid import _pad_probe

    valid = jnp.arange(index.num_points) < index.num_cells
    base = jnp.where(valid, index.cell_keys, 0)
    qk = _pad_probe(base + delta, valid, index.cell_keys.dtype)
    return neighbor_rank(index, qk)


def _distance_hits_jnp(q, cand, valid, eps):
    """Reference candidate evaluation: (B,n) x (B,C,n) -> (B,C) bool hits."""
    d2 = jnp.sum((q[:, None, :] - cand) ** 2, axis=-1)
    return metric_lib.l2_sq_hits(d2, eps) & valid


def _gather_batch(index: GridIndex, nbr_rank_cells, q_start, q_size, max_per_cell):
    """Candidate window of each query in the batch under one stencil offset.

    Returns (q (q_size,n), cand (q_size,C,n), cand_pos (q_size,C) int32,
    valid (q_size,C) bool, q_pos (q_size,) int32 position in sorted order).
    """
    q_pos = q_start + jnp.arange(q_size, dtype=jnp.int32)
    q_ok = q_pos < index.num_points
    q_pos_c = jnp.minimum(q_pos, index.num_points - 1)
    q = index.points_sorted[q_pos_c]
    rank = index.point_cell_rank[q_pos_c]
    nbr = nbr_rank_cells[rank]                       # (q_size,) rank in B or -1
    nbr_c = jnp.maximum(nbr, 0)
    start = index.cell_start[nbr_c]
    count = jnp.where(nbr >= 0, index.cell_count[nbr_c], 0)
    slots = jnp.arange(max_per_cell, dtype=jnp.int32)
    cand_pos = start[:, None] + slots[None, :]       # (q_size, C)
    valid = (slots[None, :] < count[:, None]) & q_ok[:, None]
    cand_pos_c = jnp.minimum(cand_pos, index.num_points - 1)
    cand = index.points_sorted[cand_pos_c]
    return q, cand, cand_pos_c, valid, q_pos_c, q_ok


def _check_impl(distance_impl: str) -> None:
    """The fused sweep is the join's only path; the keyword stays for
    callers that name it."""
    if distance_impl != "fused":
        raise ValueError(f"unknown distance_impl {distance_impl!r}; the "
                         f"fused sweep is the only path")


def _resolve_index(points, eps, index: Optional[GridIndex]) -> GridIndex:
    if index is not None:
        return index
    # device build (bit-identical to build_grid_host; DESIGN.md S10)
    return build_grid(np.asarray(points), float(eps))


# ---------------------------------------------------------------------------
# Fused path: single-pass count -> fill around kernels/fused_join.py. One
# kernel launch sweeps every stencil offset; the fill reuses the count
# pass's hit set / per-tile totals, so each candidate distance is evaluated
# exactly once and no (B, C, n) gathered intermediate exists (DESIGN.md S4).
#
# Occupancy bucketing (DESIGN.md S6): instead of ONE launch padded to the
# global max_per_cell, query rows are partitioned by candidate-capacity
# class (grid.occupancy_plan) and each bucket launches with its own static
# window capacity -- on skewed data most rows live in the small classes, so
# the padding-lane distance evaluations of the single-capacity sweep
# disappear. Per-bucket counts/slot bases compose back into the same
# single-pass count -> fill contract. Every launch tiles its query rows by
# the kernel's ``fused_join.TQ_DEFAULT``.
# ---------------------------------------------------------------------------

@partial(jax.jit, static_argnames=("qp", "q_limit", "merged"))
def _fused_prep(index: GridIndex, points_pad: jax.Array, deltas: jax.Array,
                q_start: jax.Array, *, qp: int, q_limit: int,
                merged: bool = False):
    """Window descriptors + contiguous query slice for one batch.

    Pure index arithmetic and a contiguous slice -- explicitly NOT a
    ``points_sorted[cand_pos]`` gather; candidate coordinates are only ever
    touched inside the fused kernel. ``q_limit`` < qp zeroes the windows of
    tile-padding query rows so batches rounded up to the tile unit never
    overlap the next batch's queries.

    ``merged``: ``deltas`` is the (3, n_off) merged table
    (``_merged_offset_tables``) and the descriptors are last-dimension
    range windows; the extra ``wcells`` return is the per-window non-empty
    cell count (1/0 for per-cell windows), keeping merged and unmerged
    work counters identical.
    """
    from repro.core.grid import (range_window_descriptors,
                                 window_descriptors)

    if merged:
        ws, wc, wcells = range_window_descriptors(
            index, deltas[0], deltas[1], deltas[2], q_start, qp)
    else:
        ws, wc = window_descriptors(index, deltas, q_start, qp)
        wcells = (wc > 0).astype(jnp.int32)
    if q_limit < qp:
        ok = jnp.arange(qp, dtype=jnp.int32) < q_limit
        wc = jnp.where(ok, wc, 0)
        wcells = jnp.where(ok, wcells, 0)
    q_batch = jax.lax.dynamic_slice(
        points_pad, (q_start, jnp.asarray(0, q_start.dtype)),
        (qp, points_pad.shape[1]))
    q_pos = jnp.asarray(q_start, jnp.int32) + jnp.arange(qp, dtype=jnp.int32)
    return ws, wc, wcells, q_batch, q_pos


@partial(jax.jit, static_argnames=("qp", "merged"))
def _fused_bucket_prep(index: GridIndex, points_pad: jax.Array,
                       deltas: jax.Array, sel: jax.Array, nsel: jax.Array,
                       *, qp: int, merged: bool = False):
    """Window descriptors + gathered query rows for one occupancy bucket.

    ``sel`` is the bucket's (qp,) sorted-position selection (ascending
    A-order, padded with any in-range value); rows >= ``nsel`` are padding
    and get zeroed windows. The candidate windows stay contiguous runs of
    ``points_sorted`` -- only the QUERY side is permuted.
    """
    from repro.core.grid import (range_window_descriptors_at,
                                 window_descriptors_at)

    q_ok = jnp.arange(qp, dtype=jnp.int32) < nsel
    q_pos = jnp.minimum(sel.astype(jnp.int32), index.num_points - 1)
    if merged:
        ws, wc, wcells = range_window_descriptors_at(
            index, deltas[0], deltas[1], deltas[2], q_pos, q_ok)
    else:
        ws, wc = window_descriptors_at(index, deltas, q_pos, q_ok)
        wcells = (wc > 0).astype(jnp.int32)
    q_batch = points_pad[q_pos]
    return ws, wc, wcells, q_batch, q_pos


def _fused_pad(index: GridIndex, *, q_size: int, c: int,
               q_start_max: int = 0, merged: bool = False,
               gid=None, feats=None):
    """One padded-points copy shared by every batch of a sweep. The tail
    covers the C-slot window reads and the worst batch's rounded-up query
    slice (``q_start_max`` = largest batch origin), so the per-batch
    dynamic_slice never clamps. Merged sweeps ride the per-point last-dim
    cell coordinate in the first pad lane (the kernel's boundary mask);
    query slices of this copy inherit it. ``gid`` (distributed slab join)
    rides the per-point global id in the next free lane. ``feats``
    (metric feature payload in SORTED point order, DESIGN.md S12) rides
    immediately after the coordinate lanes."""
    from repro.kernels.fused_join import TQ_DEFAULT

    qp = _round_up(max(q_size, 1), TQ_DEFAULT)
    tail = max(c, q_start_max + qp - index.num_points)
    return _pad_sweep_points(index, gid, feats, tail=tail,
                             merged=merged), qp


@partial(jax.jit, static_argnames=("tail", "merged"))
def _pad_sweep_points(index: GridIndex, gid, feats, *, tail: int,
                      merged: bool):
    """``_fused_pad``'s copy as one program: run eagerly, its dozen small
    ops compiled one by one, per device."""
    from repro.core.grid import point_last_coords
    from repro.kernels.fused_join import pad_points

    lc = point_last_coords(index) if merged else None
    return pad_points(index.points_sorted, tail, last_coord=lc, gid=gid,
                      feats=feats)


def _host_cell_ranks(index: GridIndex) -> np.ndarray:
    """Host copy of ``point_cell_rank``, cached per index -- run planning
    (DESIGN.md S11) happens on the host alongside the launch schedule."""
    from repro.core.grid import index_cached

    return index_cached(index, "rank_np",
                        lambda: np.asarray(index.point_cell_rank))


def _launch_run_plan(index: GridIndex, sel: Optional[np.ndarray],
                     q_start: int, *, qp: int):
    """Cell-run plan of one fused launch (DESIGN.md S11).

    Row identities are the queries' cell RANKS at the same clamped
    positions the descriptor preps resolve windows from, so a row and its
    windows can never disagree about the cell. Padding rows group with
    whatever cell their clamped position lands in -- their window counts
    are zeroed by the preps, so any grouping of them is inert (the kernel
    masks every slot of a count-0 window).
    """
    from repro.core.grid import cell_run_plan
    from repro.kernels.fused_join import TQ_DEFAULT

    rank = _host_cell_ranks(index)
    npts = index.num_points
    if sel is None:
        pos = int(q_start) + np.arange(qp)
    else:
        pos = np.zeros(qp, np.int64)
        pos[:sel.shape[0]] = sel
    return cell_run_plan(rank[np.minimum(pos, npts - 1)], TQ_DEFAULT)


@partial(jax.jit, static_argnames=("qp", "q_limit"))
def _fused_table_prep(index: GridIndex, points_pad: jax.Array, tab_ws,
                      tab_wc, tab_wcells, q_start: jax.Array, *, qp: int,
                      q_limit: int):
    """Run-mode descriptor prep for a contiguous batch: GATHER from the
    per-cell tables (``grid.cell_window_tables``) instead of re-running
    the searchsorted plane per launch -- the descriptor half of the
    paper's duplicate-search removal (SIV-C). Produces bit-identical
    hits/counts/work-counters to ``_fused_prep``: table columns replicate
    the per-row descriptor math per cell rank, and the only rows whose
    ``win_start`` can differ are dead ones (count forced to 0), which no
    consumer reads."""
    npts = index.num_points
    q_pos = jnp.asarray(q_start, jnp.int32) + jnp.arange(qp, dtype=jnp.int32)
    rank = index.point_cell_rank[jnp.minimum(q_pos, npts - 1)]
    ok = (q_pos < npts) & (jnp.arange(qp, dtype=jnp.int32) < q_limit)
    ws = tab_ws[:, rank]
    wc = jnp.where(ok[None, :], tab_wc[:, rank], 0)
    wcells = jnp.where(ok[None, :], tab_wcells[:, rank], 0)
    q_batch = jax.lax.dynamic_slice(
        points_pad, (q_start, jnp.asarray(0, q_start.dtype)),
        (qp, points_pad.shape[1]))
    return ws, wc, wcells, q_batch, q_pos


@partial(jax.jit, static_argnames=("qp",))
def _fused_table_bucket_prep(index: GridIndex, points_pad: jax.Array,
                             tab_ws, tab_wc, tab_wcells, sel: jax.Array,
                             nsel: jax.Array, *, qp: int):
    """Run-mode descriptor prep for an occupancy bucket (see
    ``_fused_table_prep``); mirrors ``_fused_bucket_prep`` row for row."""
    npts = index.num_points
    q_ok = jnp.arange(qp, dtype=jnp.int32) < nsel
    q_pos = jnp.minimum(sel.astype(jnp.int32), npts - 1)
    rank = index.point_cell_rank[q_pos]
    ws = tab_ws[:, rank]
    wc = jnp.where(q_ok[None, :], tab_wc[:, rank], 0)
    wcells = jnp.where(q_ok[None, :], tab_wcells[:, rank], 0)
    q_batch = points_pad[q_pos]
    return ws, wc, wcells, q_batch, q_pos


def _fused_batch_run(index: GridIndex, points_pad, deltas, is_zero, q_start,
                     *, qp: int, q_size: int, c: int, unicomp: bool,
                     keep_hits: bool, method: Optional[str] = None,
                     merged: bool = False,
                     gid_pairs: bool = False, run_plan=None,
                     metric: str = "l2", n_feat: int = 0,
                     refine_eps=None):
    """One contiguous query batch through the fused kernel.

    ``run_plan`` (a ``grid.RunPlan`` for THIS launch's rows) switches on
    the cell-run path (DESIGN.md S11): descriptors gather from the cached
    per-cell tables and the kernel DMAs one window per run.

    ``metric``/``n_feat`` (DESIGN.md S12) select the static refine
    predicate; ``refine_eps`` overrides the scalar the kernel refines
    against (``metric.Canonical.refine``) when the index's cell width is
    not it -- the jaccard grid prunes on set sizes at ``eps_geom`` while
    the kernel compares against the similarity threshold t.
    """
    from repro.core.grid import cell_window_tables
    from repro.kernels import ops

    if run_plan is not None:
        tab_ws, tab_wc, tab_wcells = cell_window_tables(
            index, deltas, merged=merged, tag=unicomp)
        ws, wc, wcells, q_batch, q_pos = _fused_table_prep(
            index, points_pad, tab_ws, tab_wc, tab_wcells,
            jnp.asarray(q_start, jnp.int32), qp=qp,
            q_limit=max(q_size, 1))
    else:
        ws, wc, wcells, q_batch, q_pos = _fused_prep(
            index, points_pad, deltas, jnp.asarray(q_start, jnp.int32),
            qp=qp, q_limit=max(q_size, 1), merged=merged)
    hits, counts, base = ops.fused_join_hits(
        points_pad, q_batch, ws, wc, is_zero.astype(jnp.int32), q_pos,
        index.eps if refine_eps is None else refine_eps,
        c=c, n_real=index.n_dims, unicomp=unicomp,
        merged=merged, gid_pairs=gid_pairs, keep_hits=keep_hits,
        run_ord=None if run_plan is None else jnp.asarray(run_plan.run_ord),
        run_loop=run_plan is not None, method=method, metric=metric,
        n_feat=n_feat)
    return ws, wc, wcells, hits, counts, base, q_pos


def _fused_bucket_launch(index: GridIndex, points_pad, deltas, is_zero,
                         sel: np.ndarray, *, qp: int, c: int, unicomp: bool,
                         keep_hits: bool, method: Optional[str] = None,
                         merged: bool = False,
                         gid_pairs: bool = False, run_plan=None,
                         metric: str = "l2", n_feat: int = 0,
                         refine_eps=None):
    """One occupancy bucket through the fused kernel at ITS capacity.
    ``run_plan`` / ``metric`` / ``n_feat`` / ``refine_eps`` as in
    ``_fused_batch_run`` (bucket selections keep cells contiguous: a
    cell's rows share window counts, hence a capacity class, and
    ``BucketPlan.sel`` is ascending A-order)."""
    from repro.core.grid import cell_window_tables
    from repro.kernels import ops

    nsel = sel.shape[0]
    sel_pad = np.zeros(qp, np.int32)
    sel_pad[:nsel] = sel
    if run_plan is not None:
        tab_ws, tab_wc, tab_wcells = cell_window_tables(
            index, deltas, merged=merged, tag=unicomp)
        ws, wc, wcells, q_batch, q_pos = _fused_table_bucket_prep(
            index, points_pad, tab_ws, tab_wc, tab_wcells,
            jnp.asarray(sel_pad), jnp.asarray(nsel, jnp.int32), qp=qp)
    else:
        ws, wc, wcells, q_batch, q_pos = _fused_bucket_prep(
            index, points_pad, deltas, jnp.asarray(sel_pad),
            jnp.asarray(nsel, jnp.int32), qp=qp, merged=merged)
    hits, counts, base = ops.fused_join_hits(
        points_pad, q_batch, ws, wc, is_zero.astype(jnp.int32), q_pos,
        index.eps if refine_eps is None else refine_eps,
        c=c, n_real=index.n_dims, unicomp=unicomp,
        merged=merged, gid_pairs=gid_pairs, keep_hits=keep_hits,
        run_ord=None if run_plan is None else jnp.asarray(run_plan.run_ord),
        run_loop=run_plan is not None, method=method, metric=metric,
        n_feat=n_feat)
    return ws, wc, wcells, hits, counts, base, q_pos


@partial(jax.jit, static_argnames=("c", "unicomp", "capacity"))
def _emit_from_hits(index: GridIndex, ids, hits, counts, slot_base,
                    win_start, q_pos, *, c: int, unicomp: bool,
                    capacity: int):
    """Fill phase of the fused path: scatter pairs from the count pass's hit
    set. No distances here -- positions come from the window descriptors and
    output slots from the kernel's per-tile exclusive scan (``slot_base``)
    offset by the exclusive scan of the per-tile totals. ``q_pos`` is the
    launch's per-row sorted-position array (contiguous batch or occupancy
    bucket selection); ``ids`` maps sorted positions to emitted point ids
    (``index.order`` for the single-device join, the slab's GLOBAL id
    array for the distributed join)."""
    from repro.kernels.fused_join import TQ_DEFAULT as tq

    n_off, qp, _ = hits.shape
    npts = index.num_points
    orig = ids
    q_pos_c = jnp.minimum(q_pos, npts - 1)
    slots = jnp.arange(c, dtype=jnp.int32)
    cand_pos = win_start[:, :, None] + slots[None, None, :]
    # query-major flattening: a query's hits are contiguous in slot order
    h = hits.astype(bool).transpose(1, 0, 2).reshape(qp, n_off * c)
    cp = jnp.minimum(cand_pos.transpose(1, 0, 2).reshape(qp, n_off * c),
                     npts - 1)
    rank = jnp.cumsum(h, axis=1) - 1              # within-query hit rank
    tile_tot = counts.reshape(-1, tq).sum(axis=1).astype(jnp.int64)
    tile_base = jnp.cumsum(tile_tot) - tile_tot
    qbase = jnp.repeat(tile_base, tq) + slot_base.astype(jnp.int64)
    pos = qbase[:, None] + rank
    qid = jnp.broadcast_to(orig[q_pos_c][:, None], h.shape)
    cid = orig[cp]
    keys = jnp.full((capacity,), -1, jnp.int32)
    vals = jnp.full((capacity,), -1, jnp.int32)
    if unicomp:
        # every hit is an unordered pair -> two ordered result rows
        idx_fwd = jnp.where(h, 2 * pos, capacity)
        idx_rev = jnp.where(h, 2 * pos + 1, capacity)
        keys = keys.at[idx_fwd].set(qid, mode="drop")
        vals = vals.at[idx_fwd].set(cid, mode="drop")
        keys = keys.at[idx_rev].set(cid, mode="drop")
        vals = vals.at[idx_rev].set(qid, mode="drop")
        total = 2 * counts.sum(dtype=jnp.int64)
    else:
        idx = jnp.where(h, pos, capacity)
        keys = keys.at[idx].set(qid, mode="drop")
        vals = vals.at[idx].set(cid, mode="drop")
        total = counts.sum(dtype=jnp.int64)
    return keys, vals, total


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def _sort_pairs(pairs: np.ndarray) -> np.ndarray:
    """The join's canonical result order: ``pairs`` sorted by (row, column).

    ``pairs`` is (K, 2) of non-negative int32 ids. The order equals
    ``pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]``: for such ids it is
    the order of the 64-bit key ``row << 32 | column``, which one
    ``np.sort`` orders without lexsort's two argsort passes and row
    gather (equal keys are equal rows, so stability does not matter).
    Returns a fresh C-contiguous (K, 2) int32 array."""
    key = pairs[:, 0].astype(np.int64) << 32
    key |= pairs[:, 1]
    key.sort()
    out = np.empty((key.shape[0], 2), np.int32)
    out[:, 0] = key >> 32
    out[:, 1] = key & 0xFFFFFFFF
    return out


def _emit_from_hits_host(order: np.ndarray, hits, win_start,
                         q_pos: np.ndarray, npts: int,
                         unicomp: bool) -> np.ndarray:
    """Host-side fill from the count pass's hit set (no distances, no device
    scatter). The result is host-bound anyway (the paper copies each batch
    off-device, SV-A), and compacting the (n_off, Q, C) hit bitmap with one
    ``np.nonzero`` beats an XLA scatter of mostly-dropped updates by orders
    of magnitude off-TPU; on TPU the device path ``_emit_from_hits`` keeps
    the scatter close to the data. ``q_pos`` maps launch rows to sorted
    positions (contiguous batch or occupancy bucket selection)."""
    # query-major like the device emit, so both backends produce the SAME
    # row order (per query: offsets in sweep order, slots in window order)
    h = np.asarray(hits).astype(bool).transpose(1, 0, 2)   # (Q, n_off, C)
    ws = np.asarray(win_start)
    q, off, s = np.nonzero(h)
    cand_pos = ws[off, q] + s
    qid = order[np.minimum(q_pos[q], npts - 1)]
    cid = order[cand_pos]
    if unicomp:
        out = np.empty((2 * qid.shape[0], 2), np.int32)
        out[0::2, 0] = qid
        out[0::2, 1] = cid
        out[1::2, 0] = cid
        out[1::2, 1] = qid
    else:
        out = np.stack([qid, cid], axis=1).astype(np.int32)
    return out


def _fused_launches(index: GridIndex, *, n_batches: int,
                    bucketed: Optional[bool], merged: bool = False,
                    row_ok: Optional[np.ndarray] = None,
                    gid=None, feats=None):
    """The launch schedule of one fused sweep: occupancy buckets (each
    chunked to the batching bound), or contiguous batches when the plan is
    a single class. Returns (launches, points_pad, c_max) where every
    launch is (sel|None, q_start, q_size, qp, c). ``merged``
    schedules against the merged range-window capacities (DESIGN.md S7)
    and pads the points copy with the boundary-mask coordinate lane.

    ``row_ok`` (distributed slab join, DESIGN.md S3) restricts query rows
    to a boolean mask over sorted positions (the slab's OWNED rows);
    every launch then becomes an explicit selection. ``gid`` rides the
    per-point global ids in a pad lane of the points copy.
    """
    from repro.core.grid import (CAP_ALIGN, BucketPlan,
                                 cell_window_caps_cached, filter_plan_rows,
                                 global_window_cap, occupancy_plan)

    npts = index.num_points
    if bucketed is None:
        bucketed = True
    with span("selfjoin.sync"):
        # the window capacities the schedule is planned on, cached per
        # index: a fetch of the index's cell counts, or the host wait on
        # the caps planner's device program (grid.cell_window_caps)
        c_glob = global_window_cap(index, merged)
        if bucketed and c_glob > CAP_ALIGN:
            cell_window_caps_cached(index, merged=merged)
    n_batches = max(min(int(n_batches), max(npts, 1)), 1)
    batch_rows = -(-max(npts, 1) // n_batches)  # ceil
    plan = occupancy_plan(index, merged=merged) if bucketed else None
    if row_ok is not None:
        if plan is None:
            plan = BucketPlan(caps=(c_glob,), sel=(None,),
                              cap_global=c_glob, hist={c_glob: npts})
        plan = filter_plan_rows(plan, row_ok)
    launches = []
    if plan is None or plan.sel[0] is None:
        cap = c_glob if plan is None else plan.caps[0]
        points_pad, qp = _fused_pad(
            index, q_size=batch_rows, c=c_glob,
            q_start_max=(n_batches - 1) * batch_rows, merged=merged,
            gid=gid, feats=feats)
        for b in range(n_batches):
            q_size = min(batch_rows, npts - b * batch_rows)
            launches.append((None, b * batch_rows, q_size, qp, cap))
        return launches, points_pad, c_glob
    from repro.kernels.fused_join import TQ_DEFAULT

    points_pad, _ = _fused_pad(index, q_size=1, c=c_glob, merged=merged,
                               gid=gid, feats=feats)
    for cap, sel in zip(plan.caps, plan.sel):
        for i in range(0, sel.shape[0], batch_rows):
            piece = sel[i:i + batch_rows]
            qp = _round_up(piece.shape[0], TQ_DEFAULT)
            launches.append((piece, 0, piece.shape[0], qp, cap))
    return launches, points_pad, c_glob


def _join_run_loop(index: GridIndex) -> bool:
    """Default run-loop decision for the pair-emitting fused join
    (DESIGN.md S11): sharing one window gather across a run only saves
    traffic when cells hold >= 2 queries on average -- below that, runs
    degenerate to rows and the run bookkeeping is pure overhead. The count
    path always runs the row loop. Bit-parity with the row loop is
    guaranteed (and CI-gated) either way, so this is purely a performance
    choice.
    """
    return index.num_points >= 2 * max(int(index.num_cells), 1)


def _self_join_fused(index: GridIndex, **kw):
    """Single-pass count -> fill driver of the fused sweep.

    Per launch (an occupancy bucket chunk, or a contiguous batch when the
    capacity plan collapses to one class): one fused sweep produces the hit
    set + per-query counts; the exact result size follows from the counts
    (sync point), and the fill is a pure compaction/scatter over the same
    hit set -- no second distance pass. ``emit`` selects the fill backend:
    'device' (scatter sized by the counts, with the kernel's per-tile slot
    bases; default on TPU) or 'host' (np.nonzero compaction of the hit
    bitmap; default elsewhere). Device capacities round to powers of two
    across launches so the emit scatter compiles O(log) times, not per
    launch. Bucketed and single-capacity schedules emit the same pair SET
    (row order differs across buckets; ``sort_result`` canonicalizes).

    ``merged`` (default) sweeps the 3^(n-1) merged-range stencil
    (DESIGN.md S7); ``merged=False`` keeps the per-cell 3^n sweep as the
    parity oracle. Both emit the same pair set (asserted in tests and by
    the CI bench smoke) -- the fill machinery is shared unchanged because
    merged windows are still contiguous runs of ``points_sorted``.

    Per-shard reuse (the distributed slab join, DESIGN.md S3) supplies
    ``row_ok`` (query rows restricted to the slab's OWNED sorted
    positions), ``ids`` (sorted position -> GLOBAL point id, replacing
    ``index.order`` in the emit), and ``gid_pairs`` (the kernel's
    UNICOMP/self masks compare global ids riding a pad lane instead of
    local sorted positions). The single-device join is the special case
    row_ok=None, ids=index.order, gid_pairs=False.

    ``run_loop`` (DESIGN.md S11): True routes every launch through the
    cell-run DMA dedup (one window gather per run of co-located queries,
    per-cell descriptor tables); None (default) decides by mean cell
    occupancy (``_join_run_loop``). Pair sets are bit-identical either
    way -- the run plan only regroups when each window is fetched.

    ``metric`` / ``n_feat`` / ``feats`` / ``refine_eps`` (DESIGN.md S12):
    the static refine predicate, its feature payload (SORTED point
    order), and the kernel scalar when it differs from the index's cell
    width (jaccard). The fill machinery is metric-agnostic -- it only
    consumes the hit mask and window descriptors.

    Host spans (DESIGN.md S8, "Observability"): the whole call runs under
    ``selfjoin.join``; launch planning under ``selfjoin.plan`` (its
    ``launches``, and ``lookup_table``: 1 where the merged window
    planners read the dense prefix table, ``grid.dense_prefix_table``,
    0 where they binary-search) and ``selfjoin.run_plan``, each dispatch
    under ``selfjoin.launch``, every host wait on a device program under
    ``selfjoin.sync``, the fill under ``selfjoin.emit`` and the result
    sort under ``selfjoin.sort``.

    The work itself is ``_self_join_fused_stages``, driven to its end
    here; the distributed slab join drives one per slab in step.
    """
    stages = _self_join_fused_stages(index, **kw)
    with span("selfjoin.join"):
        while True:
            try:
                next(stages)
            except StopIteration as stop:
                return stop.value


def _self_join_fused_stages(index: GridIndex, *, unicomp: bool,
                            sort_result: bool, n_batches: int = 1,
                            method: Optional[str] = None,
                            emit: Optional[str] = None,
                            bucketed: Optional[bool] = None,
                            merged: bool = True,
                            row_ok: Optional[np.ndarray] = None,
                            ids: Optional[np.ndarray] = None,
                            gid_pairs: bool = False,
                            run_loop: Optional[bool] = None,
                            metric: str = "l2", n_feat: int = 0,
                            feats=None, refine_eps=None):
    """``_self_join_fused`` as a generator that yields just before each
    host wait on a device value (the run-loop decision, the order fetch,
    the window-capacity fetch, each launch's counts sum, each emit's count
    check and pairs fetch) and returns the pairs. Driven to its end
    (``_self_join_fused``) it dispatches and waits in the one-chip join's
    order. It never yields inside one of its spans, so that the spans of
    stages driven in step on one thread still nest; the caller opens the
    ``selfjoin.join`` span around them."""
    from repro.core.grid import dispatch_window_caps, prefix_table_size

    if emit is None:
        emit = "device" if jax.default_backend() == "tpu" else "host"
    if run_loop is None:
        yield
        with span("selfjoin.sync"):
            run_loop = _join_run_loop(index)
    if merged:
        deltas, is_zero = _merged_offset_tables(index, unicomp)
    else:
        deltas, is_zero = _offset_tables(index, unicomp)
    npts = index.num_points
    if ids is None:
        yield
    with span("selfjoin.sync"):
        order_np = (np.asarray(index.order) if ids is None
                    else np.asarray(ids))
    ids_dev = index.order if ids is None else jnp.asarray(
        np.asarray(ids).astype(np.int32))
    gid = jnp.asarray(order_np.astype(np.int32)) if gid_pairs else None
    mult = 2 if unicomp else 1
    if merged:
        # the schedule below waits on the caps planner: queue it first
        dispatch_window_caps(index, merged=True)
    yield
    with span("selfjoin.plan") as sp:
        launches, points_pad, _ = _fused_launches(
            index, n_batches=n_batches, bucketed=bucketed,
            merged=merged, row_ok=row_ok, gid=gid, feats=feats)
        sp.set_metadata(launches=len(launches), lookup_table=int(
            merged and prefix_table_size(index) > 0))
    single = len(launches) == 1

    def finish(run):
        """Drain one launch: blocks on ITS buffers only, so the next
        launch's kernel (already dispatched, JAX async) overlaps the
        transfer -- the paper's SV-A compute/copy overlap, kept on the
        fused path."""
        ws, hits, counts, base, q_pos, cap = run
        total = counts.sum(dtype=jnp.int64)
        yield
        with span("selfjoin.sync"):
            ordered = mult * int(total)
        if emit == "host":
            with span("selfjoin.emit"):
                pairs = _emit_from_hits_host(
                    order_np, hits, ws, np.asarray(q_pos), npts, unicomp)
            assert pairs.shape[0] == ordered
            return pairs
        capacity = max(ordered if single else _next_pow2(ordered), 1)
        with span("selfjoin.emit"):
            keys, vals, cnt = _emit_from_hits(
                index, ids_dev, hits, counts, base, ws, q_pos,
                c=cap, unicomp=unicomp, capacity=capacity)
        yield
        with span("selfjoin.emit"):
            with span("selfjoin.sync"):
                assert int(cnt) == ordered, (int(cnt), ordered)
                keys, vals = (np.asarray(keys)[:ordered],
                              np.asarray(vals)[:ordered])
            return np.stack([keys, vals], axis=1)

    chunks = []
    prev = None
    for sel, q_start, q_size, qp, cap in launches:
        plan = None
        if run_loop:
            with span("selfjoin.run_plan"):
                plan = _launch_run_plan(index, sel, q_start, qp=qp)
        with span("selfjoin.launch"):
            if sel is None:
                ws, _, _, hits, counts, base, q_pos = _fused_batch_run(
                    index, points_pad, deltas, is_zero, q_start, qp=qp,
                    q_size=q_size, c=cap, unicomp=unicomp,
                    keep_hits=True, method=method,
                    merged=merged, gid_pairs=gid_pairs, run_plan=plan,
                    metric=metric, n_feat=n_feat, refine_eps=refine_eps)
            else:
                ws, _, _, hits, counts, base, q_pos = \
                    _fused_bucket_launch(
                        index, points_pad, deltas, is_zero, sel, qp=qp,
                        c=cap, unicomp=unicomp, keep_hits=True,
                        method=method, merged=merged,
                        gid_pairs=gid_pairs, run_plan=plan,
                        metric=metric, n_feat=n_feat,
                        refine_eps=refine_eps)
        if prev is not None:
            chunks.append((yield from finish(prev)))
        prev = (ws, hits, counts, base, q_pos, cap)
    if prev is not None:
        chunks.append((yield from finish(prev)))
    from repro.analysis import sanitize
    sanitize.raise_pending()   # REPRO_SANITIZE: launches already drained
    out = (np.concatenate(chunks, axis=0) if chunks
           else np.empty((0, 2), np.int32))
    if sort_result:
        with span("selfjoin.sort"):
            out = _sort_pairs(out)
    return out


def _self_join_count_fused(index: GridIndex, *, unicomp: bool,
                           query_batch: Optional[int] = None,
                           method: Optional[str] = None,
                           bucketed: Optional[bool] = None,
                           merged: bool = True,
                           row_ok: Optional[np.ndarray] = None,
                           ids: Optional[np.ndarray] = None,
                           gid_pairs: bool = False,
                           metric: str = "l2", n_feat: int = 0,
                           feats=None, refine_eps=None) -> JoinStats:
    """Count-only fused sweep (keep_hits=False: no O(n_off*Q*C) buffer).

    Occupancy-bucketed by default; each bucket launch counts at ITS window
    capacity and the per-launch totals/work counters sum to exactly the
    single-capacity sweep's (every query row is in exactly one bucket).
    An explicit ``query_batch`` keeps the contiguous batched sweep (the
    paper's SV-A memory bound) at the global capacity. The merged-range
    sweep reports the SAME cells_visited / candidates_checked as the
    per-cell sweep (a merged window's cell count and length are exactly
    the sum of its constituent per-cell windows'); only ``offsets``
    shrinks to 3^(n-1).
    """
    from repro.core.grid import global_window_cap

    if merged:
        deltas, is_zero = _merged_offset_tables(index, unicomp)
        n_off = int(deltas.shape[1])
    else:
        deltas, is_zero = _offset_tables(index, unicomp)
        n_off = int(deltas.shape[0])
    npts = index.num_points
    mult = 2 if unicomp else 1
    gid = (jnp.asarray(np.asarray(ids).astype(np.int32))
           if gid_pairs else None)
    if query_batch:
        c = global_window_cap(index, merged)
        q_size = int(query_batch)
        points_pad, qp = _fused_pad(
            index, q_size=q_size, c=c,
            q_start_max=((npts - 1) // q_size) * q_size, merged=merged,
            gid=gid, feats=feats)
        launches = [(None, q_start, min(q_size, npts - q_start), qp, c)
                    for q_start in range(0, npts, q_size)]
    else:
        launches, points_pad, _ = _fused_launches(
            index, n_batches=1, bucketed=bucketed, merged=merged,
            row_ok=row_ok, gid=gid, feats=feats)
    total = cells = cands = 0
    for sel, q_start, q_size, qp, cap in launches:
        if sel is None:
            _, wc, wcells, _, counts, _, _ = _fused_batch_run(
                index, points_pad, deltas, is_zero, q_start, qp=qp,
                q_size=q_size, c=cap, unicomp=unicomp, keep_hits=False,
                method=method, merged=merged, gid_pairs=gid_pairs,
                metric=metric, n_feat=n_feat, refine_eps=refine_eps)
        else:
            _, wc, wcells, _, counts, _, _ = _fused_bucket_launch(
                index, points_pad, deltas, is_zero, sel, qp=qp, c=cap,
                unicomp=unicomp, keep_hits=False, method=method,
                merged=merged, gid_pairs=gid_pairs,
                metric=metric, n_feat=n_feat, refine_eps=refine_eps)
        total += mult * int(counts.sum(dtype=jnp.int64))
        cells += int(wcells.sum(dtype=jnp.int64))
        cands += int(wc.sum(dtype=jnp.int64))
    from repro.analysis import sanitize
    sanitize.raise_pending()   # REPRO_SANITIZE: counts already drained
    return JoinStats(
        total_pairs=total,
        cells_visited=cells,
        candidates_checked=cands,
        offsets=n_off,
    )


def dma_window_stats(index: GridIndex, *, unicomp: bool = True,
                     merged: bool = True,
                     bucketed: Optional[bool] = None) -> dict:
    """Analytic DMA-window accounting of one fused sweep's launch schedule
    (DESIGN.md S11) -- no kernels run. Reports the window gathers a
    row-loop sweep would issue (``n_off * rows``), the gathers the
    run-loop sweep issues (``n_off * runs``), the HBM->VMEM bytes the
    dedup avoids, the run-length histogram, and the mean cell occupancy
    the reduction should track. The bench writes this into
    BENCH_selfjoin.json's "dma" section and the CI smoke gates on it.
    """
    from repro.kernels.fused_join import LANES
    from repro.kernels.ops import _kernel_dtype

    if merged:
        deltas, _ = _merged_offset_tables(index, unicomp)
        n_off = int(deltas.shape[1])
    else:
        deltas, _ = _offset_tables(index, unicomp)
        n_off = int(deltas.shape[0])
    launches, points_pad, _ = _fused_launches(
        index, n_batches=1, bucketed=bucketed, merged=merged)
    np_pad = LANES                 # each window DMA moves full rows
    dtype_bytes = np.dtype(_kernel_dtype(points_pad.dtype)).itemsize
    rows = runs = saved = 0
    hist: dict = {}
    for sel, q_start, q_size, qp, cap in launches:
        plan = _launch_run_plan(index, sel, q_start, qp=qp)
        rows += n_off * qp
        runs += n_off * plan.n_runs
        saved += n_off * (qp - plan.n_runs) * cap * np_pad * dtype_bytes
        lens, cnts = np.unique(plan.run_lengths, return_counts=True)
        for ln, cnt in zip(lens, cnts):
            hist[int(ln)] = hist.get(int(ln), 0) + int(cnt)
    return {
        "offsets": n_off,
        "dma_windows_row": int(rows),
        "dma_windows_run": int(runs),
        "dma_bytes_saved": int(saved),
        "reduction_factor": rows / max(runs, 1),
        "mean_cell_occupancy": (index.num_points
                                / max(int(index.num_cells), 1)),
        "run_length_hist": {str(k): v for k, v in sorted(hist.items())},
    }


@partial(jax.jit, static_argnames=("cap_q", "max_per_cell", "unicomp"))
def _count_compact(
    index: GridIndex,
    deltas: jax.Array,          # o != 0 offsets only
    *,
    cap_q: int,
    max_per_cell: int,
    unicomp: bool,
):
    """Compacted sweep over the non-zero stencil offsets.

    In high dimensionality most (query, offset) probes hit an EMPTY neighbor
    cell (uniform 6-D: >90% misses), yet the dense sweep still gathers a full
    max_per_cell window of padding for each -- the dominant HBM traffic term
    (EXPERIMENTS.md SPerf). Here queries with a live neighbor are packed into
    ``cap_q`` slots per offset BEFORE the gather, so traffic scales with
    *actual* candidate volume. ``cap_q`` is exact: the driver computes
    max-over-offsets of the live-query count from the host grid, so no
    overflow is possible. The o=0 (own cell) pass stays dense -- every query
    is live there. The refine is gather-free: candidate POSITIONS go in,
    the per-dim coordinate reads stay inside ``ops.fused_window_hits``.
    """
    from repro.kernels.ops import fused_window_hits

    npts = index.num_points

    def body(carry, delta):
        total, slots = carry
        nbr_cells = _neighbor_ranks_for_delta(index, delta)
        rank = index.point_cell_rank
        nbr_all = nbr_cells[rank]                     # (|D|,)
        live = nbr_all >= 0
        packed = jnp.argsort(~live)[:cap_q].astype(jnp.int32)
        p_live = live[packed]
        q_pos = packed
        nbr = nbr_all[packed]
        nbr_c = jnp.maximum(nbr, 0)
        start = index.cell_start[nbr_c]
        count = jnp.where(p_live, index.cell_count[nbr_c], 0)
        sl = jnp.arange(max_per_cell, dtype=jnp.int32)
        cand_pos = jnp.minimum(start[:, None] + sl[None, :], npts - 1)
        valid = sl[None, :] < count[:, None]
        q = index.points_sorted[q_pos]
        hits = fused_window_hits(index.points_sorted, q, cand_pos, valid,
                                 index.eps)
        if unicomp:
            n = 2 * hits.sum()
        else:
            hits = hits & (cand_pos != q_pos[:, None])
            n = hits.sum()
        return (total + n.astype(jnp.int64),
                slots + valid.sum(dtype=jnp.int64)), None

    init = (jnp.zeros((), jnp.int64), jnp.zeros((), jnp.int64))
    (total, slots), _ = jax.lax.scan(body, init, deltas)
    return total, slots


def compact_cap(index: GridIndex, unicomp: bool) -> int:
    """Exact max live-query count over non-zero offsets (host side)."""
    ncells = int(index.num_cells)
    keys = np.asarray(index.cell_keys[:ncells])
    counts = np.asarray(index.cell_count[:ncells]).astype(np.int64)
    deltas = np.asarray(_offset_tables(index, unicomp)[0][1:])  # skip o=0
    cap = 1
    for delta in deltas:
        pos = np.searchsorted(keys, keys + delta)
        pos = np.minimum(pos, ncells - 1)
        live = keys[pos] == keys + delta
        cap = max(cap, int(counts[live].sum()))
    return cap


def self_join_count_compact(
    points,
    eps,
    *,
    unicomp: bool = True,
    index: Optional[GridIndex] = None,
    distance_impl: str = "fused",
) -> JoinStats:
    """self_join_count with empty-neighbor compaction (beyond-paper opt):
    a dense fused pass over the own-cell offset, then ``_count_compact``
    over the others."""
    _check_impl(distance_impl)
    index = _resolve_index(points, eps, index)
    max_per_cell = _round_up(max(int(index.max_per_cell), 1), 8)
    deltas, is_zero = _offset_tables(index, unicomp)
    cap_q = _round_up(compact_cap(index, unicomp), 128)
    # o = 0 dense pass (every query is live in its own cell)
    points_pad, qp = _fused_pad(index, q_size=index.num_points,
                                c=max_per_cell)
    _, wc0, _, _, counts0, _, _ = _fused_batch_run(
        index, points_pad, deltas[:1], is_zero[:1], 0, qp=qp,
        q_size=index.num_points, c=max_per_cell, unicomp=unicomp,
        keep_hits=False)
    t0 = (2 if unicomp else 1) * int(counts0.sum(dtype=jnp.int64))
    k0 = int(wc0.sum(dtype=jnp.int64))
    tn, slots = _count_compact(
        index, deltas[1:], cap_q=min(cap_q, index.num_points),
        max_per_cell=max_per_cell, unicomp=unicomp)
    return JoinStats(
        total_pairs=t0 + int(tn),
        cells_visited=0,
        candidates_checked=k0 + int(slots),
        offsets=int(deltas.shape[0]),
        route="compact",
    )


def _count_route(index: GridIndex, backend: str, *, unicomp: bool,
                 merged: bool) -> str:
    """The count sweep's route, read off the index: 'compact' on the TPU
    where most (query, offset) probes find an empty cell -- the probes a
    point makes over the per-cell stencil (``vol``) times the grid's
    occupied share under 3 -- and the dense windows are wide enough to
    pay for the packing (``vol`` times the largest cell >= 256); 'dense'
    otherwise, and everywhere off the TPU, where the packing sort costs
    more than the padding it saves."""
    if backend != "tpu":
        return "dense"
    from repro.core.grid import host_dims
    from repro.core.stencil import merged_stencil_offsets

    if merged:   # each merged offset spans three per-cell ones
        vol = 3 * merged_stencil_offsets(index.n_dims, unicomp)[0].shape[0]
    else:
        vol = stencil_offsets(index.n_dims, unicomp).shape[0]
    # float prod: a fine 6-D grid overflows int64, and the rule needs a
    # ratio only
    volume = max(float(np.prod(host_dims(index), dtype=np.float64)), 1.0)
    occupancy = max(int(index.num_cells), 1) / volume
    c = max(int(index.max_per_cell), 1)
    if vol * occupancy < 3.0 and vol * c >= 256:
        return "compact"
    return "dense"


def self_join_count(
    points,
    eps,
    *,
    unicomp: bool = True,
    index: Optional[GridIndex] = None,
    distance_impl: str = "fused",
    query_batch: Optional[int] = None,
    bucketed: Optional[bool] = None,
    merge_last_dim: Optional[bool] = None,
    metric: str = "l2",
    vocab: Optional[int] = None,
) -> JoinStats:
    """Total ordered-pair count + work counters (no materialized result).

    One fused count sweep on the route ``_count_route`` reads off the
    index and the backend, logged in ``JoinStats.route``: 'dense' (the
    occupancy-bucketed sweep; ``bucketed=False`` forces the
    single-capacity launch, and an explicit ``query_batch`` runs
    contiguous batches of that many rows at the global capacity) or, on
    the TPU in the empty-neighbour regime and without ``query_batch``,
    'compact' (``self_join_count_compact``: per-offset live-query
    packing; it reports no per-cell visit counter, cells_visited=0).

    ``merge_last_dim`` (default on) runs the dense sweep over the 3^(n-1)
    merged-range stencil (DESIGN.md S7); ``merge_last_dim=False`` keeps
    the per-cell 3^n sweep as the parity oracle. Totals and
    cells/candidates counters are identical either way; only ``offsets``
    changes. 'compact' always sweeps per cell.

    ``metric`` / ``vocab`` as in ``self_join`` (DESIGN.md S12): cosine
    canonicalizes onto the unit sphere and counts as L2 does; jaccard
    always runs the dense sweep over the 1-D size grid (the kernel carries
    the bitmap refine). ``distance_impl`` accepts only 'fused'.
    """
    _check_impl(distance_impl)
    metric_lib.check_metric(metric)
    if metric != "l2" or isinstance(points, metric_lib.Canonical):
        canon = _metric_canonical(points, eps, metric, vocab)
        if canon.metric == "jaccard":
            idx = _metric_grid(canon)
            return _self_join_count_fused(
                idx, unicomp=unicomp, query_batch=query_batch,
                bucketed=bucketed, merged=False, metric="jaccard",
                n_feat=canon.n_feat, feats=_metric_feats_sorted(canon, idx),
                refine_eps=canon.eps)
        if canon.metric == "cosine":
            index = _metric_grid(canon)
        points, eps = canon.geom, canon.eps_geom
    index = _resolve_index(points, eps, index)
    merged = _resolve_merge(index, merge_last_dim)
    if query_batch is None and _count_route(
            index, jax.default_backend(), unicomp=unicomp,
            merged=merged) == "compact":
        return self_join_count_compact(points, eps, unicomp=unicomp,
                                       index=index)
    return _self_join_count_fused(
        index, unicomp=unicomp, query_batch=query_batch, bucketed=bucketed,
        merged=merged)


def _join_sweep(index: GridIndex, merge_last_dim: Optional[bool]) -> bool:
    """The pair-emitting join's sweep (merged or per-cell), decided from
    the index alone under the ``selfjoin.route`` span: no device value is
    fetched."""
    with span("selfjoin.route"):
        return _resolve_merge(index, merge_last_dim)


def _metric_canonical(points, eps, metric: str,
                      vocab=None) -> metric_lib.Canonical:
    """Resolve the (points, eps, metric) triple to a ``metric.Canonical``:
    pass-through for an already-canonicalized dataset (``eps`` must then
    be None or match), ``metric.canonicalize`` otherwise."""
    if isinstance(points, metric_lib.Canonical):
        canon = points
        if metric not in ("l2", canon.metric):
            raise ValueError(
                f"metric={metric!r} conflicts with the canonical dataset's "
                f"metric {canon.metric!r}")
        if eps is not None and float(eps) != canon.eps:
            raise ValueError(
                f"eps={eps} conflicts with the canonical dataset's "
                f"threshold {canon.eps}; canonicalize at the new threshold")
        return canon
    return metric_lib.canonicalize(points, eps, metric=metric, vocab=vocab)


def _metric_feats_sorted(canon: metric_lib.Canonical,
                         index: GridIndex):
    """Feature payload permuted into the index's sorted point order
    (``points_sorted[i] == points[order[i]]``), or None."""
    if canon.feats is None:
        return None
    return jnp.asarray(np.asarray(canon.feats)[np.asarray(index.order)])


def _metric_grid(canon: metric_lib.Canonical) -> GridIndex:
    """Grid over the canonical GEOMETRY at the derived prune radius: the
    points themselves for l2, unit rows for cosine (both exact L2 grids),
    the 1-D set-size coordinate for jaccard (DESIGN.md S12)."""
    return build_grid(np.asarray(canon.geom), float(canon.eps_geom))


def _metric_self_join(canon: metric_lib.Canonical, *, unicomp: bool,
                      sort_result: bool, bucketed: Optional[bool] = None,
                      index: Optional[GridIndex] = None) -> np.ndarray:
    """Pair-emitting fused join for a canonicalized non-L2 dataset.

    Cosine runs the full L2 machinery (merged sweep, occupancy buckets,
    run loop) on the unit-sphere geometry -- the metric tag keys the
    executable and the sanitize normalization check. Jaccard forces the
    per-cell sweep over the 1-D size grid (merged last-dim reduction is
    meaningless in 1-D) with the bitmap payload riding the feature lanes
    and the kernel refining against the similarity threshold t itself.
    """
    if index is None:
        index = _metric_grid(canon)
    if canon.metric == "jaccard":
        return _self_join_fused(
            index, unicomp=unicomp, sort_result=sort_result,
            bucketed=bucketed, merged=False, metric="jaccard",
            n_feat=canon.n_feat, feats=_metric_feats_sorted(canon, index),
            refine_eps=canon.eps)
    return _self_join_fused(
        index, unicomp=unicomp, sort_result=sort_result, bucketed=bucketed,
        merged=_join_sweep(index, None), metric=canon.metric)


def self_join(
    points,
    eps,
    *,
    unicomp: bool = True,
    index: Optional[GridIndex] = None,
    distance_impl: str = "fused",
    sort_result: bool = True,
    bucketed: Optional[bool] = None,
    merge_last_dim: Optional[bool] = None,
    metric: str = "l2",
    vocab: Optional[int] = None,
):
    """Single-batch self-join. Returns (pairs (K,2) int32 np.ndarray).

    The fused single-pass count -> fill (``_self_join_fused``),
    occupancy-bucketed by default (``bucketed=False`` forces the
    single-capacity launch; both produce the same pair set) over the
    merged-range stencil (``merge_last_dim=False`` keeps the per-cell 3^n
    sweep as the parity oracle; DESIGN.md S7). The sweep is decided from
    the index under the ``selfjoin.route`` span. For the incremental /
    overlapped execution the paper uses, see ``self_join_batched``.

    ``metric`` (DESIGN.md S12): 'l2' (default, ``eps`` is the radius),
    'cosine' (``points`` are raw embeddings, ``eps`` the minimum cosine
    similarity in [-1, 1)), or 'jaccard' (``points`` are token-id
    iterables or an (N, V) binary matrix, ``eps`` the minimum Jaccard
    similarity in (0, 1]; ``vocab`` optionally fixes the packed
    vocabulary). ``points`` may also be a pre-built ``metric.Canonical``
    (then pass ``eps=None``). Non-L2 metrics canonicalize and build their
    own geometry grid; ``index`` applies to 'l2' only. ``distance_impl``
    accepts only 'fused'.
    """
    _check_impl(distance_impl)
    metric_lib.check_metric(metric)
    if metric != "l2" or isinstance(points, metric_lib.Canonical):
        canon = _metric_canonical(points, eps, metric, vocab)
        if canon.metric == "l2":
            points, eps = canon.geom, canon.eps
        else:
            return _metric_self_join(
                canon, unicomp=unicomp, sort_result=sort_result,
                bucketed=bucketed)
    index = _resolve_index(points, eps, index)
    return _self_join_fused(
        index, unicomp=unicomp, sort_result=sort_result, bucketed=bucketed,
        merged=_join_sweep(index, merge_last_dim))


def self_join_batched(
    points,
    eps,
    *,
    unicomp: bool = True,
    n_batches: int = 3,
    index: Optional[GridIndex] = None,
    distance_impl: str = "fused",
    sort_result: bool = True,
    bucketed: Optional[bool] = None,
    merge_last_dim: Optional[bool] = None,
):
    """The paper's batching scheme (SV-A): >= 3 query batches, each batch's
    result copied to the host while the next batch computes (JAX async
    dispatch provides the overlap; on TPU these run on separate streams).

    Memory high-water is O(|D|/n_batches * C_max) intermediates + one batch
    result, instead of the full result set -- this is what lets result sets
    larger than device memory complete (paper Fig. 1 regime).
    ``distance_impl`` accepts only 'fused'.
    """
    _check_impl(distance_impl)
    index = _resolve_index(points, eps, index)
    return _self_join_fused(
        index, unicomp=unicomp, sort_result=sort_result,
        n_batches=n_batches, bucketed=bucketed,
        merged=_join_sweep(index, merge_last_dim))


def range_query(
    queries,
    points,
    eps,
    *,
    index: Optional[GridIndex] = None,
    return_pairs: bool = False,
    merge_last_dim: Optional[bool] = None,
):
    """Epsilon-range counts for EXTERNAL query points against an indexed set.

    Thin compatibility wrapper over ``core.query_join`` (DESIGN.md S5),
    which this function's original implementation grew into. Two bugs of
    that implementation are fixed by the delegation:

      * it defined its ``@jax.jit`` closure per CALL, so every serve
        request paid a fresh trace + compile; the query-join path uses
        module-level jitted functions cached per static bucket shape, and
      * it clamped query cell coordinates with ``clip(qcoords, 1,
        dims - 2)``, whose bounds invert (hi < lo) on grids with < 3 cells
        in a dimension, silently redirecting every query to cell 0; the
        query-join descriptors mask out-of-grid probes exactly in
        coordinate space instead (``grid.external_window_descriptors``).

    Returns (Q,) int32 neighbor counts -- or ``(counts, pairs)`` with
    ``return_pairs`` -- for the DBSCAN-style use the paper cites (SII).
    Services answering sustained traffic should hold a
    ``query_join.prepare(index)`` / ``launch.serve.JoinService`` instead.
    """
    from repro.core.query_join import epsilon_join

    index = _resolve_index(points, eps, index)
    res = epsilon_join(queries, None, index=index, return_pairs=return_pairs,
                       merge_last_dim=merge_last_dim)
    if return_pairs:
        return res.counts, res.pairs
    return res.counts


# Module-level jits for per_point_neighbor_counts: these used to be defined
# inside the function body (the PR-2 per-call @jax.jit retrace pattern --
# every call re-traced from an empty cache; analysis/lint.py's per-call-jit
# rule now bans the shape). ``cap`` is the only closed-over value and rides
# as a static argname, so the executable cache is shared across calls.
@partial(jax.jit, static_argnames=("cap",))
def _neighbor_counts_merged(index, dtab, *, cap: int):
    from repro.core.grid import range_window_descriptors_at

    npts = index.num_points
    q_pos = jnp.arange(npts, dtype=jnp.int32)
    ws, wc, _ = range_window_descriptors_at(
        index, dtab[0], dtab[1], dtab[2], q_pos)
    q = index.points_sorted
    slots = jnp.arange(cap, dtype=jnp.int32)

    def body(deg, xs):
        ws_o, wc_o = xs
        cand_pos = jnp.minimum(
            ws_o[:, None] + slots[None, :], npts - 1)
        valid = slots[None, :] < wc_o[:, None]
        cand = index.points_sorted[cand_pos]
        hits = _distance_hits_jnp(q, cand, valid, index.eps)
        hits = hits & (cand_pos != q_pos[:, None])
        deg = deg.at[index.order].add(
            hits.sum(axis=1).astype(jnp.int32))
        return deg, None

    deg0 = jnp.zeros((npts,), jnp.int32)
    deg, _ = jax.lax.scan(body, deg0, (ws, wc))
    return deg


@partial(jax.jit, static_argnames=("cap",))
def _neighbor_counts_dense(index, deltas, is_zero, *, cap: int):
    def body(deg, xs):
        delta, _ = xs
        nbr_cells = _neighbor_ranks_for_delta(index, delta)
        q, cand, cand_pos, valid, q_pos, _ = _gather_batch(
            index, nbr_cells, jnp.asarray(0, jnp.int32),
            index.num_points, cap,
        )
        hits = _distance_hits_jnp(q, cand, valid, index.eps)
        hits = hits & (cand_pos != q_pos[:, None])
        deg = deg.at[index.order[q_pos]].add(hits.sum(axis=1).astype(jnp.int32))
        return deg, None

    deg0 = jnp.zeros((index.num_points,), jnp.int32)
    deg, _ = jax.lax.scan(body, deg0, (deltas, is_zero))
    return deg


def per_point_neighbor_counts(
    points,
    eps,
    *,
    index: Optional[GridIndex] = None,
    merge_last_dim: Optional[bool] = None,
) -> np.ndarray:
    """|epsilon-neighborhood| of each point (excl. self) -- the range-query
    building block the paper cites for DBSCAN/OPTICS. Sweeps the MERGED
    3^(n-1) range stencil by default (DESIGN.md S7) with a scatter-add on
    the query id; ``merge_last_dim=False`` keeps the per-cell 3^n sweep as
    the parity oracle."""
    index = _resolve_index(points, eps, index)
    merged = _resolve_merge(index, merge_last_dim)
    if merged:
        from repro.core.grid import global_window_cap
        dtab, _ = _merged_offset_tables(index, unicomp=False)
        cap = global_window_cap(index, merged=True)
        return np.asarray(_neighbor_counts_merged(index, dtab, cap=cap))
    deltas, is_zero = _offset_tables(index, unicomp=False)
    cap = _round_up(max(int(index.max_per_cell), 1), 8)
    return np.asarray(_neighbor_counts_dense(index, deltas, is_zero, cap=cap))
