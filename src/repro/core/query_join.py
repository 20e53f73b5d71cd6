"""External-query epsilon joins against a prebuilt grid index (DESIGN.md S5).

The paper's self-join is the symmetric case of the operation a similarity
*service* actually runs: an index-once/query-many epsilon join, where the
indexed set D is built once (paper SIV) and request batches of EXTERNAL
query points -- not members of D, possibly outside its volume, possibly
duplicated -- are answered against it (the regime of Gowanlock's Hybrid
KNN-Join and GTS). This module generalizes the fused gather-refine path
(kernels/fused_join.py) to that workload:

  * window descriptors come from each query's OWN cell coordinates under
    D's grid geometry -- by default the MERGED-RANGE 3^(n-1) stencil
    (``grid.external_range_descriptors``, DESIGN.md S7; no UNICOMP,
    external queries have no self-pair or triangle rule), with the
    per-cell 3^n sweep (``grid.external_window_descriptors``) retained
    behind ``merge_last_dim=False`` as the parity oracle, and
  * the same single-pass count -> fill driver returns per-query neighbor
    COUNTS and neighbor PAIRS from one distance evaluation per candidate.

Serving without re-tracing (the bug this subsystem fixes): every jitted
function here is MODULE-LEVEL, so XLA executables are cached by input
shape, and request batches are padded to a small set of static bucket
shapes (``bucket_rows``: tile multiples growing by powers of two), so a
service sees O(log max_batch) compilations total -- not one per request,
which is what the old ``@jax.jit``-closure-per-call ``range_query`` paid.
``TRACE_EVENTS`` / ``executable_cache_stats`` make that property observable
(asserted by launch/serve.py's smoke and tests/test_query_join.py).

Cell-run batching (DESIGN.md S11): by default each request batch is
stably sorted by the query's clipped grid-cell coordinate TUPLE before
launch, so co-located queries form contiguous runs and the fused kernel
(``run_loop=True``) gathers each run's candidate window once instead of
once per row. The inverse permutation restores request row numbering on
the counts and the emitted pair query-ids, so answers are identical to
the unsorted launch (``prepare(index, run_loop=False)`` keeps the
row-loop path as the parity oracle).

Typical use:

    index = build_grid(points, eps)          # once (device build)
    pj = prepare(index)                      # once: pads, offset tables
    res = pj.join(queries)                   # per request: counts + pairs

``epsilon_join(queries, points, eps)`` is the one-shot convenience wrapper;
``core.selfjoin.range_query`` delegates here for backward compatibility.
"""
from __future__ import annotations

import collections
import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.core import grid as grid_lib
from repro.core import metric as metric_lib
from repro.core.grid import (GridIndex, build_grid, cell_run_plan,
                             round_up as _round_up)
from repro.core.stencil import stencil_offsets
from repro.kernels.fused_join import TQ_DEFAULT

_C_ALIGN = 8   # window capacity alignment (lane unit, matches selfjoin)
# Device-emit scatter capacity floor: result buffers round up to powers of
# two with this minimum, so a service compiles O(log max_result) emit
# executables over its lifetime instead of one per small result size.
_EMIT_CAP_MIN = 1024

# Trace-time event counters: the body of a jitted function executes only
# while TRACING, so these increments count compilations, not calls. The
# serve smoke and the no-retrace tests snapshot this dict across requests.
# It holds compile events only; serving counters ride the host spans of
# ``launch/serve.py`` (DESIGN.md S8, "Observability").
TRACE_EVENTS: collections.Counter = collections.Counter()


def _bump(name: str) -> None:
    TRACE_EVENTS[name] += 1


def metric_free(trace_events: dict) -> dict:
    """A copy of ``trace_events`` as it is: every key is a compile event.
    Kept for callers that still filter through it."""
    return dict(trace_events)


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def bucket_rows(n_queries: int) -> int:
    """Static padded row count for a request of ``n_queries`` queries.

    Multiples of the kernel's query tile ``TQ_DEFAULT`` growing by powers
    of two (128, 256, 512, ...), so a service compiles O(log max_batch)
    executables across all request sizes instead of one per distinct
    size. Occupancy-bucket launches pad their rows the same way.
    """
    n = max(int(n_queries), 1)
    return TQ_DEFAULT * _next_pow2(-(-n // TQ_DEFAULT))


@jax.jit
def _external_windows(index: GridIndex, offsets: jax.Array,
                      queries_pad: jax.Array, q_limit: jax.Array):
    """Jitted descriptor computation; cached by (n_off, Q_pad) shape."""
    _bump("external_windows")
    n = index.grid_min.shape[0]
    return grid_lib.external_window_descriptors(
        index, offsets, queries_pad[:, :n], q_limit)


@jax.jit
def _external_range_windows(index: GridIndex, offsets: jax.Array,
                            lo_off: jax.Array, hi_off: jax.Array,
                            queries_pad: jax.Array, q_limit: jax.Array):
    """Merged-range descriptor computation (DESIGN.md S7); cached by
    (n_off, Q_pad) shape. Returns (win_start, win_count) -- the external
    join does not report per-cell work counters."""
    _bump("external_range_windows")
    n = index.grid_min.shape[0]
    ws, wc, _ = grid_lib.external_range_descriptors(
        index, offsets, lo_off, hi_off, queries_pad[:, :n], q_limit)
    return ws, wc


@jax.jit
def _window_caps(wc: jax.Array) -> jax.Array:
    """Per-query candidate capacity: max window length over all offsets.

    The occupancy-bucketing analogue of ``grid.cell_window_caps`` for
    EXTERNAL queries, whose capacity follows from their own neighborhoods
    rather than the index's cells."""
    _bump("window_caps")
    return wc.max(axis=0)


@jax.jit
def _bucket_select(ws: jax.Array, wc: jax.Array, q_pad: jax.Array,
                   sel: jax.Array, nsel: jax.Array):
    """Gather one capacity class's rows out of the request batch.

    ``sel`` is the class's (qp_b,) row selection (padded with 0); rows >=
    ``nsel`` get zeroed window counts so bucket padding never contributes
    candidates. Cached per (request, bucket) shape pair."""
    _bump("bucket_select")
    ok = jnp.arange(sel.shape[0], dtype=jnp.int32) < nsel
    ws_b = ws[:, sel]
    wc_b = jnp.where(ok[None, :], wc[:, sel], 0)
    return ws_b, wc_b, q_pad[sel]


@partial(jax.jit, static_argnames=("c", "capacity"))
def _emit_pairs_device(order, hits, counts, slot_base, win_start, *,
                       c: int, capacity: int):
    """Device fill: scatter (query row, point id) pairs from the count
    pass's hit set -- no distances, same single-pass discipline as
    ``selfjoin._emit_from_hits`` minus the self-join masking. Query-major
    row order (per query: offsets in sweep order, slots in window order),
    identical to the host emit."""
    _bump("emit_pairs_device")
    n_off, qp, _ = hits.shape
    npts = order.shape[0]
    h = hits.astype(bool).transpose(1, 0, 2).reshape(qp, n_off * c)
    slots = jnp.arange(c, dtype=jnp.int32)
    cand = win_start[:, :, None] + slots[None, None, :]
    cp = jnp.minimum(cand.transpose(1, 0, 2).reshape(qp, n_off * c), npts - 1)
    rank = jnp.cumsum(h, axis=1) - 1              # within-query hit rank
    tile_tot = counts.reshape(-1, TQ_DEFAULT).sum(axis=1).astype(jnp.int64)
    tile_base = jnp.cumsum(tile_tot) - tile_tot
    qbase = jnp.repeat(tile_base, TQ_DEFAULT) + slot_base.astype(jnp.int64)
    pos = qbase[:, None] + rank
    qid = jnp.broadcast_to(jnp.arange(qp, dtype=jnp.int32)[:, None], h.shape)
    cid = order[cp]
    keys = jnp.full((capacity,), -1, jnp.int32)
    vals = jnp.full((capacity,), -1, jnp.int32)
    idx = jnp.where(h, pos, capacity)
    keys = keys.at[idx].set(qid, mode="drop")
    vals = vals.at[idx].set(cid, mode="drop")
    return keys, vals


def _emit_pairs_host(order_np: np.ndarray, hits, win_start,
                     npts: int) -> np.ndarray:
    """Host fill: one ``np.nonzero`` compaction of the hit bitmap (default
    off-TPU, same rationale as ``selfjoin._emit_from_hits_host``)."""
    h = np.asarray(hits).astype(bool).transpose(1, 0, 2)   # (Q, n_off, C)
    ws = np.asarray(win_start)                             # (n_off, Q)
    q, off, s = np.nonzero(h)
    cand = np.minimum(ws[off, q] + s, npts - 1)
    return np.stack([q.astype(np.int32), order_np[cand]], axis=1)


@dataclasses.dataclass(frozen=True)
class QueryJoinResult:
    """One request's answer: per-query neighbor counts and (optionally)
    the neighbor pairs as (query row, original point id) int32 rows."""

    counts: np.ndarray                 # (Q,) int32
    pairs: Optional[np.ndarray]        # (K, 2) int32, or None
    n_offsets: int                     # stencil cells probed per query
    bucket_rows: int                   # static padded batch shape used
    emit: Optional[str]                # 'host' | 'device' | None (counts-only)
    candidates_checked: Optional[int]  # total live window slots (with_stats)

    @property
    def total(self) -> int:
        return int(self.counts.sum())


def coalesce_requests(batches) -> tuple[np.ndarray, np.ndarray]:
    """Concatenate request query batches into ONE joint batch.

    The continuous-batching service (launch/serve.py BatchingJoinService)
    merges queued requests into a single fused launch; ``bounds`` records
    each request's row span so ``slice_result`` can hand every caller its
    own answer back. Empty requests are legal (zero-width spans).

    Returns (queries (sum Q_i, n), bounds (k+1,) int64) with request i
    owning joint rows [bounds[i], bounds[i+1]).
    """
    if not batches:
        raise ValueError("coalesce_requests needs at least one request")
    arrs = [np.asarray(b) for b in batches]
    n = arrs[0].shape[1] if arrs[0].ndim == 2 else -1
    for a in arrs:
        if a.ndim != 2 or a.shape[1] != n:
            raise ValueError(
                f"coalesced requests must share (Q_i, n) shape; got "
                f"{[tuple(x.shape) for x in arrs]}")
    sizes = np.asarray([a.shape[0] for a in arrs], np.int64)
    bounds = np.concatenate([[0], np.cumsum(sizes)]).astype(np.int64)
    return np.concatenate(arrs, axis=0), bounds


def slice_result(res: QueryJoinResult, lo: int, hi: int) -> QueryJoinResult:
    """One request's view of a coalesced result: rows [lo, hi).

    Counts slice directly; pairs require the coalesced result SORTED by
    query row (``sort_pairs=True``, the default) so each request's pairs
    are one contiguous span found by binary search, with query ids
    rebased to the request's own row numbering.
    """
    lo, hi = int(lo), int(hi)
    pairs = None
    if res.pairs is not None:
        if res.pairs.shape[0] and np.any(np.diff(res.pairs[:, 0]) < 0):
            raise ValueError(
                "slice_result needs the coalesced pairs sorted by query "
                "row (join with sort_pairs=True)")
        a = np.searchsorted(res.pairs[:, 0], lo, side="left")
        b = np.searchsorted(res.pairs[:, 0], hi, side="left")
        pairs = res.pairs[a:b].copy()
        pairs[:, 0] -= lo
    return QueryJoinResult(
        counts=res.counts[lo:hi], pairs=pairs, n_offsets=res.n_offsets,
        bucket_rows=res.bucket_rows, emit=res.emit, candidates_checked=None)


@dataclasses.dataclass
class _FusedLaunch:
    """One dispatched fused sweep: the request rows it serves, the device
    handles (counts / hit bitmap / slot bases), and the static shapes its
    pair emit needs. ``rows`` is None for a whole-batch (unbucketed)
    launch."""

    rows: Optional[np.ndarray]
    n_rows: int
    hits: Optional[jax.Array]
    counts: jax.Array
    base: jax.Array
    ws: jax.Array
    c: int


class PendingJoin:
    """An in-flight request: every device computation has been DISPATCHED
    but nothing is materialized on the host yet. ``result()`` blocks on
    the device values, emits pairs, and assembles the final
    ``QueryJoinResult``.

    This is the double-buffering seam of the batching service (DESIGN.md
    S8): on an asynchronous backend the host can assemble and dispatch
    batch k+1 between ``join_async(batch_k)`` and ``pending_k.result()``,
    overlapping host-side batch assembly with device execution. The
    split is also what lets a sharded service dispatch every slab's sweep
    before blocking on any of them."""

    def __init__(self, prepared: "PreparedJoin", launches: list, *,
                 wc, qp: int, n_queries: int, return_pairs: bool,
                 sort_pairs: bool, emit: Optional[str], with_stats: bool,
                 perm: Optional[np.ndarray] = None):
        self._pj = prepared
        self._launches = launches
        self._wc = wc
        self._qp = qp
        self._n_queries = n_queries
        # cell-sort permutation (DESIGN.md S11): launch row i served
        # request row perm[i]; None when the batch ran unsorted
        self._perm = perm
        self._return_pairs = return_pairs
        self._sort_pairs = sort_pairs
        self._emit = emit
        self._with_stats = with_stats
        self._result: Optional[QueryJoinResult] = None

    def ready(self) -> bool:
        """True once every launch's device values have landed, i.e.
        ``result()`` will not block on execution. Non-blocking; a backend
        whose arrays lack ``is_ready`` reports True (result() then blocks
        as usual)."""
        if self._result is not None:
            return True
        for ln in self._launches:
            for arr in (ln.counts, ln.hits, ln.base):
                if arr is not None and hasattr(arr, "is_ready"):
                    if not arr.is_ready():
                        return False
        return True

    def result(self) -> QueryJoinResult:
        """Block on the device work and assemble the answer (idempotent)."""
        if self._result is not None:
            return self._result
        from repro.analysis import sanitize
        sanitize.raise_pending()   # REPRO_SANITIZE: we block on devices here
        pj, n_queries = self._pj, self._n_queries
        counts_np = np.zeros(n_queries, np.int32)
        chunks = []
        perm = self._perm
        for ln in self._launches:
            with span("query.sync"):
                counts_b = np.asarray(ln.counts)[: ln.n_rows]
            rows = (np.arange(ln.n_rows) if ln.rows is None else ln.rows)
            if perm is not None:
                rows = perm[rows]   # sorted-batch row -> request row
            counts_np[rows] = counts_b
            if self._return_pairs:
                with span("query.emit"):
                    p = pj._emit(self._emit, ln.hits, ln.counts, ln.base,
                                 ln.ws, c=ln.c,
                                 total=int(counts_b.sum()))
                if ln.rows is not None:
                    p[:, 0] = ln.rows[p[:, 0]]   # launch row -> batch row
                if perm is not None:
                    p[:, 0] = perm[p[:, 0]]      # batch row -> request row
                chunks.append(p)
        pairs = None
        if self._return_pairs:
            pairs = (chunks[0] if len(chunks) == 1
                     else np.concatenate(chunks, axis=0) if chunks
                     else np.empty((0, 2), np.int32))
            assert pairs.shape[0] == int(counts_np.sum())
            if self._sort_pairs:
                with span("query.sort"):
                    pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
        cands = (int(np.asarray(self._wc).sum())
                 if self._with_stats else None)
        self._result = QueryJoinResult(
            counts=counts_np, pairs=pairs, n_offsets=pj.n_offsets,
            bucket_rows=self._qp,
            emit=self._emit if self._return_pairs else None,
            candidates_checked=cands)
        self._launches = self._wc = None   # release device references
        return self._result


class PreparedJoin:
    """A grid index prepared for serving: offset tables, the padded points
    copy, and the occupancy capacity classes (DESIGN.md S6) are built ONCE;
    every per-request computation dispatches into module-level jitted
    functions cached per bucket shape.

    When the index is skewed (global window capacity above the smallest
    class), each request batch is partitioned by PER-QUERY candidate
    capacity (max window length over the stencil) and every class launches
    the fused sweep at ITS static capacity -- the serving-side inheritance
    of the self-join's occupancy bucketing. Rows with zero candidates are
    dropped before any launch. The class set and the pow2 ladder of bucket
    sizes are both known at prepare time, so ``warm()`` can compile every
    steady-state executable off the request path.

    ``canon`` (DESIGN.md S12) makes the prepared index METRIC-aware: it is
    the ``metric.Canonical`` the index was built from, and the index must
    be the grid over ``canon.geom`` at ``canon.eps_geom``. Requests then
    arrive in RAW metric form (embeddings for cosine; token-id sets or an
    (Q, V) binary matrix for jaccard) and are canonicalized per request
    against the index's normalization/vocabulary. The metric tag is a
    STATIC of the fused executable, so each metric warms its own ladder;
    per-request thresholds stay traced within a metric.
    """

    def __init__(self, index: GridIndex,
                 merge_last_dim: Optional[bool] = None,
                 run_loop: bool = True,
                 canon: Optional[metric_lib.Canonical] = None):
        from repro.core.grid import capacity_classes, external_range_cap
        from repro.core.stencil import merged_stencil_offsets
        from repro.kernels.fused_join import (pad_points,
                                              resolve_merge_last_dim)

        self.index = index
        self.n_dims = index.n_dims
        self.eps = float(index.eps)
        self.canon = canon
        self.metric = "l2" if canon is None else canon.metric
        self.n_feat = 0 if canon is None else int(canon.n_feat)
        # metric-units build threshold (cos similarity / jaccard t); the
        # geometry eps above stays the radius the stencil covers
        self.metric_eps = self.eps if canon is None else float(canon.eps)
        # default kernel refine scalar (UNsquared form, see Canonical)
        self.refine = self.eps if canon is None else float(canon.refine)
        feats = None
        if canon is not None:
            metric_lib.check_metric(canon.metric)
            # index.eps round-trips through the geometry dtype (float32
            # for jaccard set sizes), so compare at float32 resolution
            if abs(self.eps - float(canon.eps_geom)) > 1e-5 * max(1.0,
                                                                  self.eps):
                raise ValueError(
                    f"index eps {self.eps} does not match the canonical "
                    f"geometry radius {canon.eps_geom}; build the grid "
                    f"over canon.geom at canon.eps_geom")
            if canon.feats is not None:
                # feature lanes ride sorted point order:
                # points_sorted[i] == points[order[i]]
                feats = jnp.asarray(
                    np.asarray(canon.feats)[np.asarray(index.order)])
        self.feats = feats
        # jaccard geometry is the 1-D set-size axis: the merged-range
        # reduction has nothing to merge there and the bitmap predicate
        # wants the plain per-cell sweep, so force it off
        if self.metric == "jaccard":
            merge_last_dim = False
        # merged-range sweep (DESIGN.md S7): 3^(n-1) reduced offsets, full
        # stencil (external queries have no UNICOMP)
        self.merged = resolve_merge_last_dim(self.n_dims, merge_last_dim)
        if self.merged:
            self.c = external_range_cap(index, _C_ALIGN)
            reduced, lo, hi = merged_stencil_offsets(self.n_dims,
                                                     unicomp=False)
            self.n_offsets = reduced.shape[0]
            self.offsets = jnp.asarray(reduced)              # (n_off, n)
            self.lo_off = jnp.asarray(lo)
            self.hi_off = jnp.asarray(hi)
            self.points_pad = pad_points(
                index.points_sorted, self.c,
                last_coord=grid_lib.point_last_coords(index), feats=feats)
        else:
            self.c = _round_up(max(int(index.max_per_cell), 1), _C_ALIGN)
            offs = stencil_offsets(self.n_dims, unicomp=False)  # full 3^n
            self.n_offsets = offs.shape[0]
            self.offsets = jnp.asarray(offs)                 # (n_off, n)
            self.points_pad = pad_points(index.points_sorted, self.c,
                                         feats=feats)
        self.is_zero = jnp.zeros((self.n_offsets,), jnp.int32)  # unused mask
        self.order_np = np.asarray(index.order)
        self.dtype = np.dtype(index.points_sorted.dtype)
        self.gmin_np = np.asarray(index.grid_min)
        self.classes = capacity_classes(self.c, _C_ALIGN)
        self.bucketed = len(self.classes) > 1
        # cell-run batching (DESIGN.md S11): sort request batches by grid
        # cell so the fused kernel gathers each run's window once
        self.run_loop = bool(run_loop)
        self.q_pos0: dict = {}   # zeros (qp,) per launch shape (external)

    def _pad_queries(self, q: np.ndarray,
                     feats: Optional[np.ndarray] = None
                     ) -> tuple[jax.Array, int]:
        # TQ_DEFAULT is the request padding unit and every launch's query
        # tile. Lane width comes from the padded points copy, so queries
        # and candidates always agree (the kernel derives its statics the
        # same way).
        qp = bucket_rows(q.shape[0])
        q_pad = np.zeros((qp, int(self.points_pad.shape[1])), self.dtype)
        q_pad[: q.shape[0], : self.n_dims] = q
        if feats is not None:
            q_pad[: q.shape[0],
                  self.n_dims: self.n_dims + self.n_feat] = feats
        if self.merged:
            # last-dim cell coordinate rides the first pad lane AFTER any
            # feature lanes (kernel boundary mask); same float computation
            # as grid.cell_coords, clipped -- any query whose raw
            # coordinate leaves the clip range has no live window, so the
            # clip never changes a mask
            qc = np.floor((q[:, -1] - self.gmin_np[-1]) / self.eps)
            q_pad[: q.shape[0], self.n_dims + self.n_feat] = np.clip(
                qc, -(1 << 24), 1 << 24)
        return jnp.asarray(q_pad), qp

    def _q_pos(self, qp: int) -> jax.Array:
        """External queries have no sorted position; the kernel's q_pos
        prefetch is a cached zeros array per launch shape."""
        z = self.q_pos0.get(qp)
        if z is None:
            z = jnp.zeros((qp,), jnp.int32)
            self.q_pos0[qp] = z
        return z

    def _launch_run_ord(self, gid: Optional[np.ndarray],
                        qp_b: int) -> jax.Array:
        """run_ord scalar-prefetch for one launch: the launch rows' cell
        group ids padded to the launch shape with the edge id (pad rows
        join the LAST run -- inert, their window counts are zeroed by the
        q_limit / bucket masks). ``gid`` is None for an empty batch."""
        if gid is None or not gid.size:
            return self._q_pos(qp_b)   # zeros: one run per tile
        ids = np.full(qp_b, gid[-1], np.int64)
        ids[: gid.size] = gid
        return jnp.asarray(cell_run_plan(ids, TQ_DEFAULT).run_ord)

    def _emit(self, emit, hits, counts, base, ws, *, c: int,
              total: int) -> np.ndarray:
        """One launch's fill: host bitmap compaction or device scatter."""
        if emit == "host":
            return _emit_pairs_host(
                self.order_np, hits, ws, self.index.num_points)
        if emit == "device":
            capacity = max(_next_pow2(total), _EMIT_CAP_MIN)
            keys, vals = _emit_pairs_device(
                self.index.order, hits, counts, base, ws,
                c=c, capacity=capacity)
            return np.stack(
                [np.asarray(keys)[:total], np.asarray(vals)[:total]], axis=1)
        raise ValueError(f"unknown emit backend {emit!r}")

    def join_async(self, queries, *, eps: Optional[float] = None,
                   return_pairs: bool = True, sort_pairs: bool = True,
                   emit: Optional[str] = None, method: Optional[str] = None,
                   with_stats: bool = False) -> PendingJoin:
        """Dispatch an epsilon join and return WITHOUT materializing.

        Runs the launch half of ``join`` -- query padding, window
        descriptors, every fused-sweep dispatch -- and hands back a
        ``PendingJoin`` whose ``result()`` blocks on the device values and
        assembles the ``QueryJoinResult``. The batching service overlaps
        host assembly of the next coalesced batch with the device
        execution of this one through exactly this seam (DESIGN.md S8);
        the occupancy partition of a skewed index still costs one small
        host sync here (the per-query capacity vector decides the launch
        shapes, so it cannot be deferred).
        """
        from repro.kernels import ops

        qf = None
        if self.metric == "l2":
            q = np.asarray(queries, self.dtype)
        elif isinstance(queries, tuple) and len(queries) == 2:
            # pre-canonicalized (geometry, features) pair: the batching
            # service canonicalizes once at admission so coalesced parts
            # and slab fan-outs do not re-normalize/re-pack per launch
            qg, qf = queries
            q = np.asarray(qg, self.dtype)
            qf = None if qf is None else np.asarray(qf)
        else:
            # raw metric input -> (geometry, features) under the INDEX's
            # canonical form (unit rows for cosine; sizes + packed bitmap
            # words against the index vocabulary for jaccard)
            qg, qf = metric_lib.canonicalize_queries(self.canon, queries)
            q = np.asarray(qg, self.dtype)
        if q.ndim != 2 or q.shape[1] != self.n_dims:
            raise ValueError(f"queries must be (Q, {self.n_dims}), "
                             f"got {q.shape}")
        if eps is None:
            eps = self.refine
        else:
            # per-request threshold in METRIC units -> kernel scalar,
            # validating the build-time stencil still covers it
            eps = metric_lib.request_scalar(
                self.metric, float(eps), index_eps=self.metric_eps,
                index_eps_geom=self.eps)
        n_queries = q.shape[0]
        perm = gid = None
        if self.run_loop and n_queries:
            # Cell-run batching (DESIGN.md S11): stable sort by the
            # clipped cell-coordinate TUPLE -- exact cell identity (a
            # linearized key could alias distinct out-of-grid cells) --
            # so co-located queries form contiguous runs. Out-of-grid
            # clip collisions are safe: such queries have no live window.
            with span("query.order"):
                qc = np.clip(
                    np.floor((q - self.gmin_np[None, :]) / self.eps),
                    -(1 << 24), 1 << 24).astype(np.int64)
                perm = np.lexsort(qc.T)
            q, qc = q[perm], qc[perm]
            if qf is not None:
                qf = qf[perm]
            head = np.ones(n_queries, bool)
            head[1:] = np.any(qc[1:] != qc[:-1], axis=1)
            gid = np.cumsum(head) - 1      # per-row cell group id
        q_dev, qp = self._pad_queries(q, qf)
        if self.merged:
            ws, wc = _external_range_windows(
                self.index, self.offsets, self.lo_off, self.hi_off, q_dev,
                jnp.asarray(n_queries, jnp.int32))
        else:
            ws, wc = _external_windows(
                self.index, self.offsets, q_dev,
                jnp.asarray(n_queries, jnp.int32))
        if return_pairs and emit is None:
            emit = "device" if jax.default_backend() == "tpu" else "host"
        launches = []
        if not self.bucketed:
            with span("query.launch"):
                ro = (self._launch_run_ord(gid, qp)
                      if self.run_loop else None)
                hits, counts, base = ops.fused_join_hits(
                    self.points_pad, q_dev, ws, wc, self.is_zero,
                    self._q_pos(qp), eps, c=self.c, n_real=self.n_dims,
                    unicomp=False, external=True, merged=self.merged,
                    keep_hits=return_pairs, run_ord=ro,
                    run_loop=self.run_loop, method=method,
                    metric=self.metric, n_feat=self.n_feat)
            launches.append(_FusedLaunch(
                rows=None, n_rows=n_queries, hits=hits, counts=counts,
                base=base, ws=ws, c=self.c))
        else:
            with span("query.sync"):
                caps = np.asarray(_window_caps(wc))[:n_queries]
            caps_aligned = np.minimum(_round_up(caps, _C_ALIGN), self.c)
            cls = np.searchsorted(np.asarray(self.classes), caps_aligned)
            for k, cb in enumerate(self.classes):
                rows = np.flatnonzero((cls == k) & (caps > 0))
                if not rows.size:
                    continue   # empty class (or all-miss rows: counts stay 0)
                with span("query.launch"):
                    qp_b = bucket_rows(rows.size)
                    sel = np.zeros(qp_b, np.int32)
                    sel[:rows.size] = rows
                    ws_b, wc_b, q_b = _bucket_select(
                        ws, wc, q_dev, jnp.asarray(sel),
                        jnp.asarray(rows.size, jnp.int32))
                    # rows ascend batch order, so equal-cell rows stay
                    # contiguous within the class launch
                    ro = (self._launch_run_ord(gid[rows], qp_b)
                          if self.run_loop else None)
                    hits, counts, base = ops.fused_join_hits(
                        self.points_pad, q_b, ws_b, wc_b, self.is_zero,
                        self._q_pos(qp_b), eps, c=cb, n_real=self.n_dims,
                        unicomp=False, external=True, merged=self.merged,
                        keep_hits=return_pairs, run_ord=ro,
                        run_loop=self.run_loop, method=method,
                        metric=self.metric, n_feat=self.n_feat)
                launches.append(_FusedLaunch(
                    rows=rows, n_rows=rows.size, hits=hits, counts=counts,
                    base=base, ws=ws_b, c=cb))
        return PendingJoin(
            self, launches, wc=wc, qp=qp, n_queries=n_queries,
            return_pairs=return_pairs, sort_pairs=sort_pairs, emit=emit,
            with_stats=with_stats, perm=perm)

    def join(self, queries, *, eps: Optional[float] = None,
             return_pairs: bool = True, sort_pairs: bool = True,
             emit: Optional[str] = None, method: Optional[str] = None,
             with_stats: bool = False) -> QueryJoinResult:
        """Epsilon join of a query batch against the prepared index.

        ``eps`` is in METRIC units and defaults to the index's build
        threshold; per-request overrides must stay within what the
        build-time stencil covers (smaller radii for l2, HIGHER similarity
        floors for cosine/jaccard -- ``metric.request_scalar`` validates).
        Counts include an indexed point that exactly coincides with a
        query (external queries have no self). The threshold is a traced
        operand of the fused sweep, so serving a MIX of thresholds within
        one metric hits one executable.

        On a skewed index the batch is served through the occupancy
        buckets: per-query capacities from the window descriptors, one
        fused launch per populated class at its own static capacity,
        counts scattered back to request rows and pair query-ids remapped.
        The pair SET matches the single-capacity launch bit-for-bit after
        sorting (row order across classes differs; ``sort_pairs``
        canonicalizes). ``join_async`` is the non-blocking half.
        """
        return self.join_async(
            queries, eps=eps, return_pairs=return_pairs,
            sort_pairs=sort_pairs, emit=emit, method=method,
            with_stats=with_stats).result()

    def counts(self, queries, *, eps: Optional[float] = None,
               method: Optional[str] = None) -> np.ndarray:
        """Counts-only fast path (no O(n_off * Q * C) hit buffer)."""
        return self.join(queries, eps=eps, return_pairs=False,
                         method=method).counts

    def _warm_queries(self, n: int):
        """A metric-VALID dummy batch of ``n`` raw queries: warm joins run
        through the same canonicalization as real requests, which rejects
        zero vectors under cosine and expects token sets under jaccard."""
        if self.metric == "cosine":
            raw = np.zeros((n, self.n_dims), self.dtype)
            raw[:, 0] = 1.0
            return raw
        if self.metric == "jaccard":
            return [() for _ in range(n)]   # empty token sets (size 0)
        return np.zeros((n, self.n_dims), self.dtype)

    def warm(self, batch_size: int, *, return_pairs: Optional[bool] = None
             ) -> int:
        """Compile every steady-state executable for requests of up to
        ``batch_size`` queries, OFF the request path.

        The request-level shapes are warmed by dummy joins; on a skewed
        index the per-class row partition of a future request is data-
        dependent, but its SHAPE space is not: each class's bucket size is
        a pow2 tile multiple bounded by the batch, so every (class, size)
        executable is compiled here and ``assert_no_retrace`` can hold
        over arbitrary steady-state request mixes. ``return_pairs=None``
        (default) warms BOTH the pair-serving and counts-only sweeps.
        Returns the request bucket's padded row count.
        """
        from repro.kernels import ops

        n = max(int(batch_size), 1)
        variants = ((True, False) if return_pairs is None
                    else (bool(return_pairs),))
        for keep in variants:
            self.join(self._warm_queries(n), return_pairs=keep)
        if self.bucketed:
            qp = bucket_rows(n)
            ws = jnp.zeros((self.n_offsets, qp), jnp.int32)
            wc = jnp.zeros((self.n_offsets, qp), jnp.int32)
            q_pad = jnp.zeros((qp, int(self.points_pad.shape[1])),
                              self.dtype)
            for cb in self.classes:
                s = TQ_DEFAULT
                # ladder bound: ANY request landing in this request bucket
                # (up to qp rows, not just n) may put all its rows in one
                # class, so warm class launches up to qp rows
                while s <= qp:
                    ws_b, wc_b, q_b = _bucket_select(
                        ws, wc, q_pad, jnp.zeros((s,), jnp.int32),
                        jnp.asarray(0, jnp.int32))
                    for keep in variants:
                        # zeros run_ord (one run per tile) is a valid
                        # plan; only the run_loop STATIC flag must match
                        # steady state for the warm to cover it
                        _, counts, _ = ops.fused_join_hits(
                            self.points_pad, q_b, ws_b, wc_b, self.is_zero,
                            self._q_pos(s), self.refine, c=cb,
                            n_real=self.n_dims, unicomp=False,
                            external=True, merged=self.merged,
                            keep_hits=keep,
                            run_ord=(self._q_pos(s) if self.run_loop
                                     else None),
                            run_loop=self.run_loop,
                            metric=self.metric, n_feat=self.n_feat)
                        np.asarray(counts)   # block: compile now, not later
                    s *= 2
        return bucket_rows(n)


def prepare(index: GridIndex,
            merge_last_dim: Optional[bool] = None,
            run_loop: bool = True,
            canon: Optional[metric_lib.Canonical] = None) -> PreparedJoin:
    """Prepare a grid index for repeated external-query joins.

    ``merge_last_dim`` (default on) serves requests through the 3^(n-1)
    merged-range stencil (DESIGN.md S7); ``False`` keeps the per-cell
    3^n sweep as the parity oracle. ``run_loop`` (default on) cell-sorts
    request batches and shares each run's window gather (DESIGN.md S11);
    ``False`` keeps the unsorted row-loop launch as the parity oracle.
    ``canon`` attaches the metric the index was canonicalized for
    (DESIGN.md S12); requests then arrive in raw metric form."""
    return PreparedJoin(index, merge_last_dim=merge_last_dim,
                        run_loop=run_loop, canon=canon)


def epsilon_join(queries, points, eps: Optional[float] = None, *,
                 index: Optional[GridIndex] = None,
                 return_pairs: bool = True, sort_pairs: bool = True,
                 emit: Optional[str] = None, method: Optional[str] = None,
                 with_stats: bool = False,
                 merge_last_dim: Optional[bool] = None,
                 metric: str = "l2",
                 vocab: Optional[int] = None) -> QueryJoinResult:
    """One-shot external-query epsilon join: counts and pairs of all
    indexed points within ``eps`` of each query.

    Builds the grid over ``points`` unless ``index`` is supplied. Services
    answering many requests against one dataset should hold a
    ``prepare(index)`` object instead (launch/serve.py's JoinService does);
    the underlying executables are shared either way -- this wrapper only
    re-pays the cheap host-side preparation per call.

    ``metric`` selects the similarity (DESIGN.md S12): ``eps`` is then the
    metric-units threshold (minimum cosine similarity / minimum Jaccard
    similarity), ``points`` the raw dataset (or a pre-built
    ``metric.Canonical``), and ``queries`` raw metric input. ``vocab``
    fixes the jaccard packing vocabulary. ``index`` must be None for
    non-L2 metrics (the grid is built over the canonical geometry here).
    """
    metric_lib.check_metric(metric)
    if metric != "l2" or isinstance(points, metric_lib.Canonical):
        if index is not None:
            raise ValueError(
                "epsilon_join: pass raw points (or a Canonical), not a "
                "prebuilt index, for non-L2 metrics -- the grid must be "
                "built over the canonical geometry")
        canon = (points if isinstance(points, metric_lib.Canonical)
                 else metric_lib.canonicalize(points, eps, metric=metric,
                                              vocab=vocab))
        idx = build_grid(np.asarray(canon.geom), float(canon.eps_geom))
        return prepare(idx, merge_last_dim=merge_last_dim, canon=canon).join(
            queries, eps=None, return_pairs=return_pairs,
            sort_pairs=sort_pairs, emit=emit, method=method,
            with_stats=with_stats)
    if index is None:
        index = build_grid(np.asarray(points), float(eps))
    return prepare(index, merge_last_dim=merge_last_dim).join(
        queries, eps=eps, return_pairs=return_pairs, sort_pairs=sort_pairs,
        emit=emit, method=method, with_stats=with_stats)


def executable_cache_stats() -> dict:
    """Compilation-cache observability for the serving path.

    Returns per-function XLA executable-cache sizes plus the trace-event
    counters; a healthy steady-state service shows these CONSTANT across
    requests (asserted by launch/serve.py and tests/test_query_join.py).
    """
    from repro.kernels import fused_join as fj

    def size(f) -> int:
        try:
            return int(f._cache_size())
        except Exception:
            return -1

    return {
        "external_windows": size(_external_windows),
        "external_range_windows": size(_external_range_windows),
        "window_caps": size(_window_caps),
        "bucket_select": size(_bucket_select),
        "fused_reference": size(fj._fused_join_hits_reference),
        "fused_pallas": size(fj._fused_join_hits_pallas),
        "emit_pairs_device": size(_emit_pairs_device),
        # prepare-path builders/planners (DESIGN.md S10): these compile
        # during build/reindex, never per steady-state request, so the
        # serve watchdog exempts them (launch/serve.py assert_no_retrace).
        "grid_build": size(grid_lib.build_grid_with_geometry_jit),
        "grid_caps": size(grid_lib._cell_window_caps_device),
        "grid_extspan": size(grid_lib._external_span_device),
        "trace_events": dict(TRACE_EVENTS),
    }
