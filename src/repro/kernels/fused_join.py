"""Fused gather-refine Pallas TPU kernel (DESIGN.md S4).

The unfused offset sweep (core/selfjoin.py history, kernels/cell_join.py)
materializes a ``(B, C, n)`` gathered-candidate tensor in HBM per stencil
offset and then evaluates distances over it -- the dominant cost is HBM
traffic the paper's shared-memory refine never pays. This kernel removes the
intermediate: the *positions* of each query's candidate window (``win_start``
/ ``win_count`` from ``core.grid.window_descriptors``) arrive in SMEM, one
query tile's worth per grid step, and the kernel performs the HBM->VMEM
candidate gather itself with a dynamic slice of ``points_sorted``,
immediately consuming the window for the distance + epsilon threshold. The
candidate coordinates live only in VMEM.

One ``pallas_call`` sweeps the whole stencil: the grid is

    (query tiles, stencil offsets)       -- offsets innermost

so the query tile block (index map depends on the tile index only) stays
VMEM-resident across all offsets of the sweep -- the locality
kernels/cell_join.py's docstring promises but the per-offset dispatch of the
unfused path could not deliver.

Per grid step the kernel fuses, per query row:

    gather window -> squared distance -> eps threshold -> UNICOMP/self mask

writes the tile's hit rows as one block and adds their per-row totals to
``counts`` (accumulated across offsets). The per-tile exclusive scan of the
counts (``slot_base``) -- the slot assignment the fill phase uses -- is one
XLA op after the launch, shared with the reference lowering. Count and fill
share ONE distance evaluation per candidate: the driver (core/selfjoin.py)
sizes the result buffer from ``counts`` and scatters pairs from the
returned ``hits`` mask without ever recomputing a distance.

Outputs (for a query batch of Q_pad rows, C-slot windows, n_off offsets):

    hits      (n_off, Q_pad, C) int8 -- fully masked epsilon-hits
    counts    (Q_pad,)          int32 -- per-query hit totals over all offsets
    slot_base (Q_pad,)          int32 -- per-tile exclusive scan of counts

A ``reference`` lowering with identical semantics runs on backends without
Mosaic (this container): it ``lax.scan``s the stencil offsets (mirroring the
kernel's innermost offset axis) and evaluates each offset's full
``(Q_pad, C)`` window plane at once -- squared distances accumulate in place
over per-coordinate column gathers, so the reference path never materializes
a ``(B, C, n)`` candidate tensor either, and UNICOMP/merged/gid masking is
the shared ``_mask_hits``. The Pallas kernel is validated against it in
tests/test_fused_join.py.

Cell-run DMA dedup (DESIGN.md S11, ``run_loop=True``): an SMEM
run-ordinal array (``grid.cell_run_plan``) groups each tile's rows into RUNS
sharing a grid cell; since same-cell rows have identical windows for every
offset, the window DMA advances once per run (slot = ordinal mod 2, still
two slots / two semaphores; the current run's last row issues the next run's
copy, the head row waits, interior rows reuse the resident slot) -- the
paper's duplicate-search removal (SIV-C) applied to the gather stream. The
reference lowering accepts and ignores the ordinals: evaluating every row
against its OWN descriptors is exactly the run-loop's semantics whenever the
run plan satisfies the shared-window contract (proven by
``analysis.contracts.check_run_plan``), so bit-parity is structural.

Merged-range sweeps (DESIGN.md S7): with ``merged=True`` the windows are
last-dimension RANGE spans (up to three adjacent cells' contiguous points,
``grid.range_window_descriptors``), the sweep runs 3^(n-1) reduced offsets,
and the kernel applies the last-dimension boundary mask
|cand_last - q_last| <= 1 from the cell coordinates riding lane ``n_real``
of the padded point/query arrays (exact integers in float, never derived
from float positions).

Hardware adaptation notes (honest limits of this port):
  * each row's window is fetched with an explicit ``pltpu.make_async_copy``
    (HBM -> VMEM scratch) inside a ``fori_loop``, the Mosaic-lowerable
    form, DOUBLE-BUFFERED across rows: two VMEM window slots, the copy for
    row r+1 issued before row r's compute (pallas_guide.md "Patterns:
    Double Buffering"). Off-TPU the copies run through the interpreter.
  * Mosaic slices rows out of an (8, 128)-tiled HBM array only at full
    lane width, so the kernel's copy of the points is 128 lanes wide and
    each window DMA moves C x 128 words, of which only the padded lane
    count is read. The window is transposed in VMEM so that candidates run
    along lanes: every vector in the row body is 2-D (Mosaic has no 1-D
    layout), and the hit row is stored as 32-bit (int8 rows pack four to a
    sublane) and converted once per tile.
  * the per-tile descriptors (window start/count, query positions, run
    ordinals) arrive as (1, tq) SMEM blocks per grid step, so SMEM holds a
    tile's worth whatever the launch size.
  * Mosaic lowers no 64-bit scalar, so the kernel is traced with x64 off;
    its operands are 32-bit on the chip (f64 points are computed in f32).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import metric as metric_lib

NP_PAD = 8     # minimum lane padding of the coordinate axis (cell_join.py)
LANES = 128    # TPU vreg lane width: the kernel's HBM points row width
TQ_DEFAULT = 128  # query tile rows


def on_tpu() -> bool:
    """True when the kernels compile to Mosaic; elsewhere they run in the
    Pallas interpreter. Asked per call, never at import."""
    return jax.default_backend() == "tpu"


def pad_width(n_lanes: int) -> int:
    """Padded lane count for ``n_lanes`` occupied lanes: at least NP_PAD,
    rounded up to the 8-lane unit. Metrics with feature payloads (jaccard
    bitmaps) widen the points array past NP_PAD; the kernel reads the
    width back off the array shapes, so L2/cosine layouts are unchanged."""
    return max(NP_PAD, -(-int(n_lanes) // 8) * 8)


def resolve_merge_last_dim(n_dims: int,
                           merge_last_dim: bool | None,
                           extra_lanes: int = 0) -> bool:
    """THE merge-resolution rule, shared by the self-join drivers and the
    external-query service: merged-range sweeps default ON and fall back
    to the per-cell sweep when there is no free pad lane to carry the
    boundary-mask coordinates (n_dims >= NP_PAD). ``extra_lanes`` reserves
    additional pad lanes the caller needs besides the coordinates -- the
    distributed slab join rides the global point id in one (DESIGN.md S3),
    so its merged sweep needs TWO free lanes."""
    if merge_last_dim is None:
        merge_last_dim = True
    return bool(merge_last_dim) and n_dims + extra_lanes < NP_PAD


def pad_points(points_sorted: jax.Array, tail: int,
               last_coord: jax.Array | None = None,
               gid: jax.Array | None = None,
               feats: jax.Array | None = None) -> jax.Array:
    """(N, n) -> (N + tail, L) zero-padded copy for in-kernel gathers,
    with L = ``pad_width`` of the occupied lanes (NP_PAD unless feature
    lanes widen it).

    ``tail`` >= C guarantees every C-slot window read is in bounds
    (win_start + C <= N + tail, see grid.window_descriptors); zero pad rows
    are never hits because their window slots are masked by win_count.

    ``feats`` (metric feature payload, DESIGN.md S12): per-point non-
    geometric lanes -- the jaccard metric's packed 16-bit token words as
    exact small-integer floats -- stored in lanes [n, n + n_feat)
    immediately after the coordinates, BEFORE the merged/gid lanes, so
    the refine predicate addresses them at a metric-static offset.

    ``last_coord`` (merged-range sweeps, DESIGN.md S7): per-point
    last-dimension CELL coordinate, stored in the first lane after the
    coordinate+feature lanes as an exactly-representable float so the
    kernel's boundary mask reads it with the same gather as the
    coordinates. Requires a free lane below NP_PAD in the featureless
    layout; the lane is excluded from the distance sum by the kernel's
    static ``n_real``.

    ``gid`` (distributed slab joins, DESIGN.md S3): per-point GLOBAL id,
    stored in the lane after the coordinates (and after ``feats`` /
    ``last_coord`` when they ride). The kernel's ``gid_pairs`` masks
    compare these instead of sorted positions, making the UNICOMP
    intra-cell tie-break device-independent. Ids are small integers
    (< 2^24), exact in f32, so the TPU downcast never reorders them; tail
    rows carry -1.
    """
    n = points_sorted.shape[1]
    n_feat = 0 if feats is None else feats.shape[1]
    lanes = (n + n_feat + (0 if last_coord is None else 1)
             + (0 if gid is None else 1))
    np_pad = pad_width(lanes)
    out = jnp.pad(points_sorted, ((0, tail), (0, np_pad - n)))
    lane = n
    if feats is not None:
        fp = jnp.pad(feats.astype(points_sorted.dtype), ((0, tail), (0, 0)))
        out = jax.lax.dynamic_update_slice(out, fp, (0, lane))
        lane += n_feat
    if last_coord is not None:
        lc = jnp.pad(last_coord.astype(points_sorted.dtype), (0, tail))
        out = out.at[:, lane].set(lc)
        lane += 1
    if gid is not None:
        g = jnp.pad(gid.astype(points_sorted.dtype), (0, tail),
                    constant_values=-1)
        out = out.at[:, lane].set(g)
    return out


def _mask_hits(hit, cand_pos, q_pos, zero, unicomp: bool,
               external: bool = False, gq=None, gc=None, ldiff=None):
    """UNICOMP triangle / full-stencil self mask (same rule as the drivers).

    ``external`` queries are not members of the indexed set: there is no
    self-pair to drop and no triangle rule to apply (every epsilon-hit is a
    result), so the mask is the identity. The self-join is the special case
    ``external=False`` with the query batch sliced out of ``points_sorted``.

    Under the merged-range sweep the UNICOMP rule is unchanged: the zero
    REDUCED offset's window spans the own cell plus the key+1 cell, and
    ``cand_pos > q_pos`` is exact for both (own cell: the triangle; key+1
    cell: every candidate sits at a later sorted position than any
    own-cell query).

    ``gq``/``gc`` (distributed slab joins): GLOBAL ids of query/candidate
    replace sorted positions in the tie-break, so every slab resolves an
    intra-cell pair the same way regardless of its local sort (DESIGN.md
    S3 ownership rule). Merged sweeps must then split the zero reduced
    offset's window by ``ldiff`` (last-dim cell delta): the key+1 cell's
    candidates are NON-zero-offset pairs and all count, only the own-cell
    part applies the gid triangle -- local positions got this for free
    (own-cell rows always precede key+1 rows in A-order), global ids do
    not.
    """
    if external:
        return hit
    # ``zero`` is a scalar; it is broadcast as an int and the masks are
    # combined with logical ops (Mosaic selects no boolean vectors)
    off_zero = jnp.broadcast_to(zero, hit.shape) == 0
    if gq is not None:
        if unicomp:
            tri = gc > gq
            if ldiff is not None:
                tri = (ldiff > 0) | ((ldiff == 0) & tri)
            return hit & (off_zero | tri)
        return hit & (gc != gq)
    if unicomp:
        return hit & (off_zero | (cand_pos > q_pos))
    return hit & (cand_pos != q_pos)


# ---------------------------------------------------------------------------
# Pallas kernel
# ---------------------------------------------------------------------------

def _fused_kernel(iz_ref, ws_ref, wc_ref, qpos_ref, ord_ref, scal_ref, q_ref,
                  pts_ref, hits_ref, counts_ref, win_ref, sem_ref, hit_ref,
                  *, c, tq, n_real, unicomp, external, merged, gid_pairs,
                  run_loop, metric, n_feat):
    # Index arithmetic is int32 throughout, whatever jax_enable_x64 says:
    # Mosaic lowers no int64 scalar math. The per-tile descriptor blocks
    # (ws/wc/qpos/ord) arrive in SMEM, one (1, tq) block per grid step.
    j = pl.program_id(1)           # stencil offset (innermost: q tile resident)
    scal = scal_ref[...]           # (1, 1) metric refine scalar (core.metric)
    np_pad = q_ref.shape[1]        # occupied lanes of the 128-lane windows
    zero = iz_ref[j]

    @pl.when(j == 0)
    def _init():
        counts_ref[...] = jnp.zeros_like(counts_ref)

    # Double-buffered row DMA: two VMEM window slots; the copy for row
    # r + 1 is issued before row r's compute, so the gather of the next
    # window overlaps the current distance evaluation (pallas_guide.md
    # "Patterns: Double Buffering"). The merged sweep's windows are up to
    # 3 cells long, which is what makes the overlap worth having.
    def win_dma(r, slot):
        return pltpu.make_async_copy(
            pts_ref.at[pl.ds(ws_ref[0, r], c), :],
            win_ref.at[slot], sem_ref.at[slot])

    win_dma(0, 0).start()
    slots = jax.lax.broadcasted_iota(jnp.int32, (1, c), 1)
    two = jnp.int32(2)

    def row(r, carry):
        if run_loop:
            # Cell-run DMA (DESIGN.md S11): rows with equal run ordinals
            # share their window for every offset (grid.cell_run_plan
            # contract), so the gather advances per RUN. slot = ordinal
            # mod 2 alternates run to run; the run's LAST row issues the
            # next run's copy (overlapping the remaining compute), the
            # HEAD row waits, interior rows reuse the resident slot.
            o = ord_ref[0, r]
            slot = jax.lax.rem(o, two)
            nxt = ord_ref[0, jnp.minimum(r + 1, tq - 1)]
            prev = ord_ref[0, jnp.maximum(r - 1, 0)]

            @pl.when((r + 1 < tq) & (nxt != o))
            def _prefetch():
                win_dma(r + 1, jax.lax.rem(o + 1, two)).start()

            @pl.when((r == 0) | (o != prev))
            def _wait():
                win_dma(r, slot).wait()
        else:
            slot = jax.lax.rem(r, two)

            @pl.when(r + 1 < tq)
            def _prefetch():
                win_dma(r + 1, jax.lax.rem(r + 1, two)).start()

            win_dma(r, slot).wait()
        q_pos = qpos_ref[0, r]                # global sorted position
        start = ws_ref[0, r]
        cnt = wc_ref[0, r]
        window_t = win_ref[slot, :, :np_pad].T            # (NP, C)
        qrow = q_ref[pl.ds(r, 1), :]                      # (1, NP)
        # metric refine (core.metric, DESIGN.md S12): the predicate skips
        # pad lanes by the static (n_real, n_feat) layout -- with the
        # merged sweep, lane n_real + n_feat carries the last-dimension
        # cell coordinate, not a zero
        hit = metric_lib.tile_refine_hits(metric, qrow, window_t, scal,
                                          n_real=n_real, n_feat=n_feat)
        cand_pos = start + slots
        hit = hit & (slots < cnt)
        ldiff = None
        if merged:
            # last-dimension boundary mask (DESIGN.md S7): a candidate
            # whose last-dim cell coordinate wrapped across a grid row is
            # not a stencil neighbor; coordinates ride the lane after the
            # coordinate+feature lanes as exact integers, so the float
            # compare is exact
            ml = n_real + n_feat
            ldiff = window_t[ml:ml + 1, :] - qrow[:, ml:ml + 1]
            hit = hit & (jnp.abs(ldiff) <= 1)
        gq = gc = None
        if gid_pairs:
            # global ids ride the lane after the coordinates/features (and
            # after the merged coordinate lane); exact small ints in float
            gl = n_real + n_feat + (1 if merged else 0)
            gq, gc = qrow[:, gl:gl + 1], window_t[gl:gl + 1, :]
        hit = _mask_hits(hit, cand_pos, q_pos, zero, unicomp, external,
                         gq, gc, ldiff if gid_pairs else None)
        hit_ref[pl.ds(r, 1), :] = hit.astype(jnp.int32)
        return carry

    jax.lax.fori_loop(jnp.int32(0), jnp.int32(tq), row, jnp.int32(0))
    # one whole-block store per grid step: int8 rows are packed four to a
    # sublane, so the row loop writes 32-bit rows and converts here
    tile_hits = hit_ref[...]
    hits_ref[0] = tile_hits.astype(hits_ref.dtype)
    counts_ref[...] += jnp.sum(tile_hits, axis=1, keepdims=True,
                               dtype=jnp.int32)


def _tile_exclusive_scan(counts, tq: int):
    """Per-tile exclusive scan of per-row counts: the fill's slot bases."""
    ctile = counts.reshape(-1, tq)
    return (jnp.cumsum(ctile, axis=1) - ctile).reshape(-1)


@functools.partial(
    jax.jit, static_argnames=("c", "tq", "n_real", "unicomp", "external",
                              "merged", "gid_pairs", "keep_hits", "run_loop",
                              "interpret", "metric", "n_feat"))
def _fused_join_hits_pallas(points_pad, q_batch, win_start, win_count,
                            is_zero, q_pos, run_ord, scal, *, c, tq, n_real,
                            unicomp, external=False, merged=False,
                            gid_pairs=False, keep_hits=True, run_loop=False,
                            interpret=False, metric="l2", n_feat=0):
    n_off, qp = win_start.shape
    np_pad = points_pad.shape[1]   # pad_width: NP_PAD unless feats widen it
    if keep_hits:
        hits_shape, hits_map = (n_off, qp, c), (lambda i, j: (j, i, 0))
    else:
        # count-only launch: one revisited (1, tq, c) block per tile serves
        # as scratch, so no O(n_off * Q * C) buffer is ever allocated.
        hits_shape, hits_map = (1, qp, c), (lambda i, j: (0, i, 0))
    smem = pltpu.SMEM
    # per-tile descriptor rows, one (1, tq) SMEM block per grid step: the
    # tile axis is split out so the block's last two dims are whole dims
    tile_row = pl.BlockSpec((None, 1, tq), lambda i, j: (i, 0, 0),
                            memory_space=smem)
    off_row = pl.BlockSpec((None, None, 1, tq), lambda i, j: (j, i, 0, 0),
                           memory_space=smem)

    def rows(a):
        a = jnp.asarray(a, jnp.int32)
        return a.reshape(a.shape[:-1] + (qp // tq, 1, tq))

    operands = (jnp.asarray(is_zero, jnp.int32), rows(win_start),
                rows(win_count), rows(q_pos), rows(run_ord), scal, q_batch,
                # the window DMA slices rows out of a (8, 128)-tiled HBM
                # array, which Mosaic only allows at full 128-lane width
                jnp.pad(points_pad, ((0, 0), (0, LANES - np_pad))))
    call = pl.pallas_call(
        functools.partial(_fused_kernel, c=c, tq=tq, n_real=n_real,
                          unicomp=unicomp, external=external, merged=merged,
                          gid_pairs=gid_pairs, run_loop=run_loop,
                          metric=metric, n_feat=n_feat),
        grid=(qp // tq, n_off),
        in_specs=[
            pl.BlockSpec(memory_space=smem),               # is_zero
            off_row, off_row,                              # win start/count
            tile_row, tile_row,                            # q_pos, run_ord
            pl.BlockSpec((1, 1), lambda i, j: (0, 0)),     # refine scalar
            pl.BlockSpec((tq, np_pad), lambda i, j: (i, 0)),
            pl.BlockSpec(memory_space=pl.ANY),             # points (HBM)
        ],
        out_specs=[
            pl.BlockSpec((1, tq, c), hits_map),
            pl.BlockSpec((tq, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(hits_shape, jnp.int8),
            jax.ShapeDtypeStruct((qp, 1), jnp.int32),
        ],
        scratch_shapes=[
            pltpu.VMEM((2, c, LANES), points_pad.dtype),   # double-buffered
            pltpu.SemaphoreType.DMA((2,)),                 # window DMA slots
            pltpu.VMEM((tq, c), jnp.int32),                # tile hit rows
        ],
        interpret=interpret,
    )
    if interpret:
        hits, counts = call(*operands)
    else:
        # Mosaic lowers no 64-bit scalar: trace the kernel, its index maps
        # and its ref slices with 32-bit literals, whatever x64 says (the
        # grid keys stay int64 outside; every operand here is 32-bit)
        with jax.enable_x64(False):
            hits, counts = call(*operands)
    counts = counts[:, 0]
    return hits, counts, _tile_exclusive_scan(counts, tq)


# ---------------------------------------------------------------------------
# Reference lowering (identical semantics, no Mosaic required)
# ---------------------------------------------------------------------------

def _offset_hits(points_pad, q_batch, ws, wc, zero, q_pos, scal, *,
                 c, n_real, unicomp, external=False, merged=False,
                 gid_pairs=False, metric="l2", n_feat=0):
    """Masked hits of every query against one offset's windows.

    The metric refine accumulates lane-by-lane over (Q, C) column gathers
    (``metric.plane_refine_hits``), so no (Q, C, n) candidate tensor
    exists on this path either.
    """
    slots = jnp.arange(c, dtype=jnp.int32)
    cand_pos = ws[:, None] + slots[None, :]               # (Q, C)
    hit = metric_lib.plane_refine_hits(metric, points_pad, q_batch,
                                       cand_pos, scal, n_real=n_real,
                                       n_feat=n_feat)
    hit = hit & (slots[None, :] < wc[:, None])
    ldiff = None
    if merged:
        # last-dimension boundary mask, identical to the kernel's: cell
        # coordinates ride the lane after the coordinate+feature lanes of
        # points_pad / q_batch as exact integers (grid.point_last_coords)
        ml = n_real + n_feat
        ldiff = (jnp.take(points_pad[:, ml], cand_pos)
                 - q_batch[:, ml][:, None])
        hit = hit & (jnp.abs(ldiff) <= 1)
    gq = gc = None
    if gid_pairs:
        gl = n_real + n_feat + (1 if merged else 0)
        gq = q_batch[:, gl][:, None]
        gc = jnp.take(points_pad[:, gl], cand_pos)
    return _mask_hits(hit, cand_pos, q_pos[:, None], zero, unicomp, external,
                      gq, gc, ldiff if gid_pairs else None)


@functools.partial(
    jax.jit, static_argnames=("c", "tq", "n_real", "unicomp", "external",
                              "merged", "gid_pairs", "keep_hits", "metric",
                              "n_feat"))
def _fused_join_hits_reference(points_pad, q_batch, win_start, win_count,
                               is_zero, q_pos, run_ord, scal, *, c, tq,
                               n_real, unicomp, external=False, merged=False,
                               gid_pairs=False, keep_hits=True, metric="l2",
                               n_feat=0):
    # ``run_ord`` is accepted for arity parity with the kernel and IGNORED:
    # evaluating each row against its own descriptors is the run-loop's
    # semantics whenever the plan satisfies the shared-window contract
    # (module docstring), so the reference is the oracle for both modes.
    del run_ord
    n_off, qp = win_start.shape
    scals = scal[0, 0]

    def per_offset(counts, xs):
        ws, wc, zero = xs
        hit = _offset_hits(points_pad, q_batch, ws, wc, zero, q_pos, scals,
                           c=c, n_real=n_real, unicomp=unicomp,
                           external=external, merged=merged,
                           gid_pairs=gid_pairs, metric=metric,
                           n_feat=n_feat)
        counts = counts + hit.sum(axis=1, dtype=jnp.int32)
        out = hit.astype(jnp.int8) if keep_hits else jnp.zeros((), jnp.int8)
        return counts, out

    counts0 = jnp.zeros((qp,), jnp.int32)
    counts, hits = jax.lax.scan(
        per_offset, counts0, (win_start, win_count, is_zero))
    if not keep_hits:
        hits = jnp.zeros((1, qp, c), jnp.int8)
    return hits, counts, _tile_exclusive_scan(counts, tq)


# ---------------------------------------------------------------------------
# Public entry point
# ---------------------------------------------------------------------------

def fused_join_hits(points_pad, q_batch, win_start, win_count, is_zero,
                    q_pos, eps, *, c, n_real, unicomp, external=False,
                    merged=False, gid_pairs=False, tq=TQ_DEFAULT,
                    keep_hits=True, run_ord=None, run_loop=False,
                    method=None, metric="l2", n_feat=0):
    """Fused gather-refine sweep over all stencil offsets in one launch.

    Args:
      points_pad: (N + tail, NP_PAD) ``pad_points`` output, tail >= c.
      q_batch:    (Q_pad, NP_PAD) query coordinates, Q_pad % tq == 0. For the
                  self-join these are rows of ``points_pad`` at sorted
                  positions ``q_pos`` -- a contiguous batch OR an
                  occupancy-bucket selection (DESIGN.md S6); with
                  ``external`` it is ANY query set (zero-padded pad
                  rows/lanes), and the window descriptors come from the
                  queries' own cell coordinates
                  (``grid.external_window_descriptors``).
      win_start / win_count: (n_off, Q_pad) int32 from
                  ``grid.window_descriptors`` / ``window_descriptors_at``
                  (self-join) or ``grid.external_window_descriptors``
                  (external queries); count 0 for padding queries /
                  out-of-grid probes.
      is_zero:    (n_off,) int32, 1 for the o = 0 offset (UNICOMP triangle).
      q_pos:      (Q_pad,) int32 global sorted position of every query row,
                  read from SMEM by the kernel (self-join masking only;
                  pass zeros with ``external``). Padding rows may carry any
                  in-range value -- their windows are count-0.
      eps:        scalar refine threshold in the metric's UNsquared form:
                  the geometry radius for l2/cosine (squared once by
                  ``metric.device_refine_scalar``), the Jaccard similarity
                  threshold t for jaccard. Traced, so a mix of radii per
                  metric shares one executable.
      c:          static window capacity (the launch's bucket capacity; the
                  global ``max_per_cell`` rounded up in the unbucketed case).
      n_real:     static true dimensionality (reference path skips pad lanes).
      unicomp:    static; triangle rule on o = 0 vs. full-stencil self mask.
      external:   static; True disables BOTH masks (queries are not members
                  of the indexed set -- every epsilon-hit is a result).
      merged:     static; True = windows are MERGED last-dimension range
                  spans (DESIGN.md S7): lane ``n_real`` of points_pad and
                  q_batch carries last-dim cell coordinates
                  (``pad_points(..., last_coord=...)``) and the kernel
                  applies the boundary mask |cand_last - q_last| <= 1.
      gid_pairs:  static; True = the lane after the coordinates (and after
                  the merged coordinate lane) carries GLOBAL point ids
                  (``pad_points(..., gid=...)``) and the UNICOMP/self
                  masks compare those instead of sorted positions -- the
                  device-independent tie-break of the distributed slab
                  join (DESIGN.md S3).
      keep_hits:  static; False = count-only (no O(n_off*Q*C) hits buffer).
      run_ord:    (Q_pad,) int32 per-tile run ordinals from
                  ``grid.cell_run_plan(...).run_ord`` -- required when
                  ``run_loop`` is True, otherwise optional (read but
                  unused; pass zeros to keep launch shapes identical).
      run_loop:   static; True = cell-run DMA dedup (module docstring): the
                  kernel gathers one window per RUN of equal ordinals. The
                  caller owns the contract that equal ordinals imply equal
                  (win_start, win_count) columns for all offsets
                  (``analysis.contracts.check_run_plan``).
      method:     'kernel' | 'reference' | None (auto: kernel on TPU). The
                  kernel compiles to Mosaic on a TPU backend and runs in
                  the Pallas interpreter elsewhere, decided per call.
      metric:     static metric tag ('l2' | 'cosine' | 'jaccard'): selects
                  the refine predicate (core.metric) and keys a SEPARATE
                  executable per metric -- no traced branch.
      n_feat:     static count of metric feature lanes riding points_pad /
                  q_batch at lanes [n_real, n_real + n_feat) (jaccard
                  bitmap words; 0 otherwise).

    Returns (hits, counts, slot_base); hits is (1, Q_pad, c) scratch when
    ``keep_hits`` is False.
    """
    tpu = on_tpu()
    if method is None:
        method = "kernel" if tpu else "reference"
    metric_lib.check_metric(metric)
    q_pos = jnp.asarray(q_pos, jnp.int32)
    if run_ord is None:
        if run_loop:
            raise ValueError("run_loop=True requires a run_ord plan "
                             "(grid.cell_run_plan)")
        run_ord = jnp.zeros((win_start.shape[1],), jnp.int32)
    run_ord = jnp.asarray(run_ord, jnp.int32)
    scal = metric_lib.device_refine_scalar(metric, eps, points_pad.dtype)
    if method == "kernel":
        return _fused_join_hits_pallas(
            points_pad, q_batch, win_start, win_count, is_zero, q_pos,
            run_ord, scal, c=c, tq=tq, n_real=n_real, unicomp=unicomp,
            external=external, merged=merged, gid_pairs=gid_pairs,
            keep_hits=keep_hits, run_loop=run_loop, interpret=not tpu,
            metric=metric, n_feat=n_feat)
    if method == "reference":
        return _fused_join_hits_reference(
            points_pad, q_batch, win_start, win_count, is_zero, q_pos,
            run_ord, scal, c=c, tq=tq, n_real=n_real, unicomp=unicomp,
            external=external, merged=merged, gid_pairs=gid_pairs,
            keep_hits=keep_hits, metric=metric, n_feat=n_feat)
    raise ValueError(f"unknown fused_join method {method!r}")


@functools.partial(jax.jit, static_argnames=("c", "tq", "check_hits",
                                             "metric", "n_real"))
def sanitize_errcodes(points_pad, q_batch, win_start, win_count, counts,
                      base, hits, *, c, tq, check_hits=False, metric="l2",
                      n_real=None):
    """Device-side invariant reduction for one fused launch -> int32 bitmask.

    The sanitized-mode checker (``REPRO_SANITIZE=1``, analysis/sanitize.py):
    recomputes the launch's safety conditions with plain jnp ops over the
    SAME descriptors and outputs the kernel consumed/produced, so the kernel
    and its checker cannot share a miscompile. Stays async -- the caller
    queues the scalar and the driver forces it at its existing sync points.

    Bits (constants in analysis/sanitize.py):
      oob-gather     a live window's [start, start + c) gather would leave
                     the padded points buffer (corrupted descriptor).
      cap-overflow   win_count > c: the granted capacity silently truncates
                     the window (undersized ``cell_window_caps``).
      scan-mismatch  slot_base is not the per-tile exclusive scan of counts
                     (or, with ``check_hits``, counts disagree with the hits
                     mask) -- the emit path's slot writes would collide.
      nonfinite      NaN/Inf in the points or query coordinates. With
                     ``metric='jaccard'`` the check covers the GEOMETRY
                     lanes [0, n_real) only: the bitmap feature lanes are
                     packed integer words, not coordinates.
      count-range    negative window counts, or per-query totals outside
                     [0, n_off * c].
      unnormalized   (``metric='cosine'`` only) a NONZERO point or query
                     row whose coordinate-lane squared norm is off unity by
                     more than ``metric.NORM_TOL``: raw embeddings reached
                     the kernel without canonicalization. All-zero rows are
                     padding, not input (canonicalize rejects zero rows).
    """
    from repro.analysis import sanitize as _san

    np_total = points_pad.shape[0]
    n_off, _ = win_start.shape
    live = win_count > 0
    oob = live & ((win_start < 0) | (win_start + c > np_total))
    code = jnp.where(jnp.any(oob), _san.E_OOB_GATHER, 0)
    code = code | jnp.where(jnp.any(win_count > c), _san.E_CAP_OVERFLOW, 0)
    bad_range = ((win_count < 0).any() | (counts < 0).any()
                 | (counts > n_off * c).any())
    code = code | jnp.where(bad_range, _san.E_COUNT_RANGE, 0)
    ctile = counts.reshape(-1, tq)
    scan_bad = jnp.any(
        ((jnp.cumsum(ctile, axis=1) - ctile).reshape(-1)) != base)
    if check_hits:
        scan_bad = scan_bad | jnp.any(
            hits.astype(jnp.int32).sum(axis=(0, 2)) != counts)
    code = code | jnp.where(scan_bad, _san.E_SCAN_MISMATCH, 0)
    n_chk = points_pad.shape[1] if (metric != "jaccard" or n_real is None) \
        else n_real
    finite = (jnp.all(jnp.isfinite(points_pad[:, :n_chk]))
              & jnp.all(jnp.isfinite(q_batch[:, :n_chk])))
    code = code | jnp.where(~finite, _san.E_NONFINITE, 0)
    if metric == "cosine" and n_real is not None:
        def off_unit(rows):
            n2 = jnp.sum(rows[:, :n_real] * rows[:, :n_real], axis=1)
            return jnp.any((n2 > 0) & (jnp.abs(n2 - 1) > metric_lib.NORM_TOL))
        code = code | jnp.where(off_unit(points_pad) | off_unit(q_batch),
                                _san.E_UNNORMALIZED, 0)
    return code.astype(jnp.int32)


def fused_window_hits(points_sorted, q, cand_pos, valid, eps):
    """Positional drop-in for selfjoin._distance_hits_jnp without the gather.

    (B, n) queries x (B, C) candidate *positions* -> (B, C) bool hits; the
    compacted sweep (selfjoin._count_compact) uses this so distance_impl=
    'fused' never materializes the (B, C, n) candidate tensor there either.
    """
    d2 = jnp.zeros(cand_pos.shape, q.dtype)
    for dim in range(q.shape[1]):
        cd = jnp.take(points_sorted[:, dim], cand_pos)
        d2 = d2 + (q[:, dim][:, None] - cd) ** 2
    return metric_lib.l2_sq_hits(d2, eps) & valid
