"""The epsilon-grid index of paper SIV.

The paper's index has four components (Fig. 2a):
    A   point-id lookup array, |A| = |D|, grouped by grid cell
    G   per non-empty cell, the [min, max] range into A
    B   sorted linearized ids of the non-empty cells (binary-searched)
    M_j per-dimension list of non-empty cell coordinates (range masking)

Only non-empty cells are stored, so space is O(|D|) independent of the
(hyper)volume (paper SIV-D). We provide two builders:

  * ``build_grid`` -- the PRIMARY builder (DESIGN.md S10): geometry and the
    static key dtype fixed on the host, then key computation + stable sort
    + segment detection inside one cached jitted executable
    (``build_grid_with_geometry``), shapes padded to |D| (the number of
    non-empty cells is at most |D|). Also usable inside shard_map / pjit
    where host round-trips are impossible (core/distributed.py).
  * ``build_grid_host`` -- exact, numpy, on the host; the reference the
    device build is bit-identical to. Mirrors the paper's CPU fallback
    ("inserting points into the grid requires far less work than
    constructing the R-tree", SVI-B).

Both produce the same ``GridIndex`` pytree -- field-for-field equal on the
same input -- and the joins in ``selfjoin.py`` consume either.

TPU adaptation note (DESIGN.md S2): the per-thread binary search of B in the
paper's kernel is replaced by vectorized ``searchsorted`` over all cells per
stencil offset at *search* time; the per-dimension masks M_j are kept for the
host path and subsumed by the searchsorted miss (-1) on the device path.
"""
from __future__ import annotations

import collections
import dataclasses
import weakref
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

# Sentinel linear key for padding slots in B. Must compare greater than any
# real key so searchsorted never matches it.
PAD_KEY = jnp.iinfo(jnp.int64).max


def key_dtype_for(dims) -> np.dtype:
    """Narrowest safe cell-key dtype for a grid of ``dims`` cells.

    int32 when ``prod(dims) < 2^31`` (every linear key, and every probe
    key a host-built grid's interior geometry can form, fits), else
    int64. The int32 fast path halves searchsorted bandwidth AND removes
    the ``jax_enable_x64`` requirement for small grids; exact python-int
    arithmetic so a 6-D grid just past the boundary cannot wrap into the
    int32 route (regression-tested in tests/test_grid_keys.py).
    """
    volume = 1
    for d in np.asarray(dims).ravel():
        volume *= int(d)
    return np.dtype(np.int32) if volume < 2**31 else np.dtype(np.int64)


def pad_key_for(dtype) -> int:
    """The padding/miss sentinel for a key array of ``dtype``: the dtype's
    max. Real keys are < prod(dims) <= sentinel - 1 by ``key_dtype_for``'s
    strict bound, so a sentinel probe can only land on padding slots --
    whose ``cell_count`` is 0 -- never on a real cell."""
    return int(np.iinfo(np.dtype(dtype)).max)


def sentinel_margin(dims, key_dtype=None) -> int:
    """``pad_key_for`` sentinel minus the largest possible real key, in
    exact python-int arithmetic (no numpy wrap-around on 6-D volumes).

    Positive margin proves the sentinel can never alias a real cell key;
    0 means the out-of-grid sentinel cell of a padded build (key ==
    prod(dims)) coincides with the padding sentinel. The contract prover
    (analysis/contracts.py C4) checks this for every index geometry."""
    if key_dtype is None:
        key_dtype = key_dtype_for(dims)
    volume = 1
    for d in np.asarray(dims).ravel():
        volume *= int(d)
    return pad_key_for(key_dtype) - (volume - 1)


def device_key_dtype(dims, padded: bool = False) -> np.dtype:
    """Static key dtype for a DEVICE build of known geometry.

    ``key_dtype_for`` widened to int64 when a padded build would need the
    out-of-set sentinel cell (key == prod(dims)) and that key would not
    clear the int32 padding sentinel: the sentinel cell key must both fit
    the dtype and stay strictly below ``pad_key_for`` (C9,
    analysis/contracts.py ``check_device_sentinel``). Exact python-int
    arithmetic throughout.
    """
    kd = key_dtype_for(dims)
    if padded and kd == np.int32 and sentinel_margin(dims, kd) < 2:
        kd = np.dtype(np.int64)
    return kd


def _pad_probe(arr: jax.Array, mask: jax.Array, key_dtype) -> jax.Array:
    """``arr`` cast to the index's key dtype with ``~mask`` lanes set to
    the dtype's miss sentinel (the dtype-aware form of
    ``jnp.where(mask, keys, PAD_KEY)``, which overflows when the keys are
    int32)."""
    kd = jnp.dtype(key_dtype)
    pad = jnp.asarray(pad_key_for(kd), kd)
    return jnp.where(mask, arr.astype(kd), pad)


def _require_int64_keys() -> None:
    """Refuse to build a grid whose keys would silently truncate to int32.

    With ``jax_enable_x64`` off, ``jnp.asarray`` of an int64 host array and
    every ``linearize`` result downcast to int32 without warning; on >=4-D
    grids the linear key space exceeds 2^31 and distinct cells ALIAS to the
    same key (and ``PAD_KEY`` wraps negative, so padding slots match real
    searches). Importing ``repro`` enables x64 globally; this guard catches
    grid builds that genuinely need 64-bit keys (``key_dtype_for``) from
    processes that disabled or bypassed that import.
    """
    if not jax.config.jax_enable_x64:
        raise RuntimeError(
            "epsilon-grid cell keys require int64, but jax_enable_x64 is "
            "off: linearized keys (grid.linearize) and PAD_KEY would "
            "silently truncate to int32 and alias distinct cells on "
            "high-dimensional grids. Enable it with "
            "jax.config.update('jax_enable_x64', True) -- importing the "
            "`repro` package does this for you (unless REPRO_NO_X64 is "
            "set, in which case only int32-keyed grids -- prod(dims) < "
            "2^31 -- can be built).")


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class GridIndex:
    """The paper's index (A/G/B + geometry), as a JAX pytree.

    Arrays are padded to static shapes: ``cell_keys``/``cell_start``/
    ``cell_count`` have length ``num_points`` with ``num_cells`` valid
    entries; padding keys are PAD_KEY.
    """

    # --- geometry (paper SIV-B) ---
    grid_min: jax.Array      # (n,) g_j^min = min x_j - eps
    eps: jax.Array           # () scalar
    dims: jax.Array          # (n,) |g_j| cells per dimension, int64
    # --- components (paper SIV-C) ---
    order: jax.Array         # (|D|,) int32 == A : point ids grouped by cell
    points_sorted: jax.Array # (|D|, n)  D[A] : coordinates in A-order
    cell_keys: jax.Array     # (|D|,) int64 == B : sorted linear ids (padded)
    cell_start: jax.Array    # (|D|,) int32 == G.min : offset into A
    cell_count: jax.Array    # (|D|,) int32 == G.max-G.min+1
    point_cell_rank: jax.Array  # (|D|,) int32: rank in B of each sorted point's cell
    num_cells: jax.Array     # () int32 |G| = |B|
    max_per_cell: jax.Array  # () int32 (exact on host path; reported on jit path)

    @property
    def n_dims(self) -> int:
        return self.points_sorted.shape[1]

    @property
    def num_points(self) -> int:
        return self.points_sorted.shape[0]

    @property
    def key_dtype(self):
        """Cell-key dtype: int32 on small grids (``key_dtype_for``),
        int64 otherwise. Probe keys must cast through ``_pad_probe`` so
        their miss sentinel matches this dtype."""
        return self.cell_keys.dtype


def cell_coords(points: jax.Array, grid_min: jax.Array, eps) -> jax.Array:
    """n-dimensional integer cell coordinates of each point (int64).

    The grid range is appended by eps on both sides (paper SIV-B), so every
    point's coordinate is >= 1 and adjacent-cell lookups never go negative.
    """
    return jnp.floor((points - grid_min) / eps).astype(jnp.int64)


def linearize(coords: jax.Array, dims: jax.Array) -> jax.Array:
    """Row-major linear cell id (paper Fig. 2b's lexicographic cell id).

    int64: for 6-D data the id space is prod |g_j| which overflows int32.
    """
    coords = coords.astype(jnp.int64)
    dims = dims.astype(jnp.int64)
    n = coords.shape[-1]
    key = coords[..., 0]
    for j in range(1, n):
        key = key * dims[j] + coords[..., j]
    return key


def row_major_strides(dims: jax.Array) -> jax.Array:
    """s_j = prod_{k>j} dims_k, the ``linearize`` convention -- so
    key(c + o) = key(c) + o @ s for any offset vector o.

    THE stride formula: the offset tables (selfjoin), the distributed slab
    join, and the host-side occupancy planner (``cell_window_caps``) must
    all agree with ``linearize`` bit-for-bit, or window capacities
    undercount and the kernel silently truncates candidates. jnp, usable
    under jit; host code converts with ``np.asarray``.
    """
    dims = jnp.asarray(dims).astype(jnp.int64)
    rev = jnp.cumprod(dims[::-1])
    return jnp.concatenate([rev[-2::-1], jnp.ones((1,), dims.dtype)])


def offset_deltas(offsets, dims: jax.Array) -> jax.Array:
    """Key delta ``o @ s`` of each (n_off, n) stencil offset row, as an
    elementwise multiply-sum: XLA's TPU backend has no int64 dot."""
    return (jnp.asarray(offsets) * row_major_strides(dims)).sum(axis=-1)


def grid_geometry(points: jax.Array, eps) -> tuple[jax.Array, jax.Array]:
    """grid_min (g_j^min) and dims (|g_j|) per paper SIV-B.

    g_j^min = min_j - eps ; g_j^max = max_j + eps ; |g_j| = ceil(range/eps).
    """
    eps = jnp.asarray(eps, points.dtype)
    gmin = points.min(axis=0) - eps
    gmax = points.max(axis=0) + eps
    dims = jnp.ceil((gmax - gmin) / eps).astype(jnp.int64) + 1
    return gmin, dims


# ---------------------------------------------------------------------------
# Host (exact) build -- mirrors the paper's host-side index construction.
# ---------------------------------------------------------------------------

def host_grid_geometry(points: np.ndarray,
                       eps) -> tuple[np.ndarray, np.ndarray]:
    """Exact numpy grid geometry (paper SIV-B): THE one copy shared by
    ``build_grid_host`` and the device-build dispatcher (``build_grid``),
    so both builders derive bit-identical gmin/dims from the same IEEE
    operations and the resulting indexes can be compared field-for-field."""
    points = np.asarray(points)
    gmin = points.min(axis=0) - eps
    gmax = points.max(axis=0) + eps
    dims = (np.ceil((gmax - gmin) / eps)).astype(np.int64) + 1
    return gmin, dims


def build_grid_host(points: np.ndarray, eps: float) -> GridIndex:
    """Exact epsilon-grid build in numpy. Returns a device GridIndex.

    Keys are built in the narrowest safe dtype (``key_dtype_for``): int32
    when prod(dims) < 2^31 -- the natural eps-margin geometry keeps every
    point's coords in [1, dims-2], so every probe key the stencil can form
    stays inside [0, prod(dims)) and int32 is exact WITHOUT
    ``jax_enable_x64``. Larger grids keep int64 keys and the x64 guard.
    """
    points = np.asarray(points)
    npts, n = points.shape
    gmin, dims = host_grid_geometry(points, eps)
    key_dtype = key_dtype_for(dims)
    if key_dtype == np.int64:
        _require_int64_keys()

    coords = np.floor((points - gmin) / eps).astype(np.int64)
    keys = coords[:, 0]
    for j in range(1, n):
        keys = keys * dims[j] + coords[:, j]
    keys = keys.astype(key_dtype)

    order = np.argsort(keys, kind="stable").astype(np.int32)
    keys_sorted = keys[order]

    uniq, start, count = np.unique(keys_sorted, return_index=True, return_counts=True)
    ncells = uniq.shape[0]

    cell_keys = np.full(npts, np.iinfo(key_dtype).max, dtype=key_dtype)
    cell_keys[:ncells] = uniq
    cell_start = np.zeros(npts, dtype=np.int32)
    cell_start[:ncells] = start
    cell_count = np.zeros(npts, dtype=np.int32)
    cell_count[:ncells] = count

    rank = np.searchsorted(uniq, keys_sorted).astype(np.int32)

    return GridIndex(
        grid_min=jnp.asarray(gmin),
        eps=jnp.asarray(eps, dtype=points.dtype),
        dims=jnp.asarray(dims),
        order=jnp.asarray(order),
        points_sorted=jnp.asarray(points[order]),
        cell_keys=jnp.asarray(cell_keys),
        cell_start=jnp.asarray(cell_start),
        cell_count=jnp.asarray(cell_count),
        point_cell_rank=jnp.asarray(rank),
        num_cells=jnp.asarray(ncells, dtype=jnp.int32),
        max_per_cell=jnp.asarray(int(count.max()) if ncells else 0, dtype=jnp.int32),
    )


def masks_host(index: GridIndex) -> list[np.ndarray]:
    """The paper's per-dimension masking arrays M_j (SIV-C).

    M_j = sorted unique non-empty cell coordinates in dimension j. Used by the
    host reference search; the device path folds this pruning into the
    neighbor-table searchsorted (a miss there prunes the same cells and more).
    """
    keys = np.asarray(index.cell_keys[: int(index.num_cells)])
    dims = np.asarray(index.dims)
    n = dims.shape[0]
    coords = np.empty((keys.shape[0], n), dtype=np.int64)
    rem = keys.copy()
    for j in range(n - 1, 0, -1):
        coords[:, j] = rem % dims[j]
        rem //= dims[j]
    coords[:, 0] = rem
    return [np.unique(coords[:, j]) for j in range(n)]


# ---------------------------------------------------------------------------
# Device build -- key computation, stable sort and segment detection on the
# accelerator (the paper builds its index on the GPU; DESIGN.md S10).
# ---------------------------------------------------------------------------

def build_grid(points, eps, *, device: bool = True) -> GridIndex:
    """Primary epsilon-grid build: host geometry, DEVICE construction.

    Geometry (gmin/dims) is derived on the host with the exact numpy
    arithmetic of ``build_grid_host`` (``host_grid_geometry``), which also
    fixes the static key dtype; the O(|D| log |D|) work -- linearized key
    computation, stable sort, segment detection -- runs inside ONE cached
    jitted executable (``build_grid_with_geometry``). The result is
    bit-identical to ``build_grid_host`` field-for-field: same geometry
    ops, same key dtype and dtype-max padding, and stable sorts of equal
    key arrays produce equal permutations (property-tested in
    tests/test_device_build.py). ``device=False`` dispatches to the numpy
    builder unchanged.
    """
    pts_np = np.asarray(points)
    if not device:
        return build_grid_host(pts_np, float(eps))
    gmin, dims = host_grid_geometry(pts_np, eps)
    key_dtype = key_dtype_for(dims)
    if key_dtype == np.int64:
        _require_int64_keys()    # fail before tracing, same error as host
    return build_grid_with_geometry_jit(
        jnp.asarray(pts_np), eps, jnp.asarray(gmin), jnp.asarray(dims),
        key_dtype=key_dtype)


def build_grid_with_geometry(
    points: jax.Array, eps, gmin: jax.Array, dims: jax.Array,
    valid: Optional[jax.Array] = None, *, key_dtype=None,
) -> GridIndex:
    """Jittable grid build against externally supplied geometry.

    The one device builder: ``build_grid`` (primary path) and the
    distributed slab join (core/distributed.py) both dispatch here -- the
    latter builds every slab's local grid against the *global* gmin/dims
    so cell coordinates (and the UNICOMP cell-pair ownership rule) are
    consistent across devices (DESIGN.md S3).

    ``valid`` marks real points; invalid (padding) points are assigned the
    out-of-set sentinel cell key prod(dims), which sorts after every real
    cell and can never be produced by a real cell + stencil-offset lookup,
    so padding points are unreachable as candidates. ``max_per_cell``
    excludes the sentinel cell.

    ``key_dtype`` must be STATIC (dims are traced under jit, so the dtype
    cannot be derived here): callers with concrete geometry pass
    ``key_dtype_for(dims)`` (or ``device_key_dtype`` when ``valid`` is
    used) to ride the int32 fast path; ``None`` keeps the legacy int64
    route, which requires x64. Padding slots carry the dtype-max sentinel
    (``pad_key_for``), matching the host build.
    """
    if key_dtype is None:
        key_dtype = np.dtype(np.int64)
    key_dtype = np.dtype(key_dtype)
    if key_dtype == np.int64:
        _require_int64_keys()
    npts, _ = points.shape
    keys = linearize(cell_coords(points, gmin, eps), dims).astype(key_dtype)
    # out-of-set sentinel cell key == prod(dims): exact in the key dtype
    # (int32 route has volume < 2^31; device_key_dtype widens when the
    # sentinel would collide with the padding sentinel). Explicit dtype=
    # because jnp.prod promotes int32 to the default int otherwise.
    sentinel = jnp.prod(dims.astype(key_dtype), dtype=key_dtype)
    if valid is not None:
        keys = jnp.where(valid, keys, sentinel)

    order = jnp.argsort(keys, stable=True).astype(jnp.int32)
    keys_sorted = keys[order]

    # Segment boundaries of the sorted key array -> non-empty cells.
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), keys_sorted[1:] != keys_sorted[:-1]]
    )
    ncells = is_start.sum().astype(jnp.int32)
    # Rank of each sorted point's cell in B (0-based).
    rank = (jnp.cumsum(is_start) - 1).astype(jnp.int32)

    # Scatter segment starts into padded arrays. Valid slots: [0, ncells).
    seg_idx = jnp.where(is_start, rank, npts)  # pad writes -> dropped
    positions = jnp.arange(npts, dtype=jnp.int32)
    cell_start = jnp.zeros(npts, jnp.int32).at[seg_idx].set(positions, mode="drop")
    cell_keys = jnp.full(npts, pad_key_for(key_dtype), key_dtype)
    cell_keys = cell_keys.at[seg_idx].set(keys_sorted, mode="drop")
    # count[h] = start[h+1] - start[h]; for the last valid cell use npts.
    nxt = jnp.concatenate([cell_start[1:], jnp.zeros((1,), jnp.int32)])
    idx = jnp.arange(npts, dtype=jnp.int32)
    nxt = jnp.where(idx == ncells - 1, npts, nxt)
    cell_count = jnp.where(idx < ncells, nxt - cell_start, 0).astype(jnp.int32)

    real_count = jnp.where(cell_keys < sentinel, cell_count, 0)
    return GridIndex(
        grid_min=gmin,
        eps=jnp.asarray(eps, points.dtype),
        dims=dims,
        order=order,
        points_sorted=points[order],
        cell_keys=cell_keys,
        cell_start=cell_start,
        cell_count=cell_count,
        point_cell_rank=rank,
        num_cells=ncells,
        max_per_cell=real_count.max().astype(jnp.int32),
    )


# THE jitted device builder: one executable per (shape, key dtype), shared
# by build_grid and the distributed slab join (core/distributed.py).
build_grid_with_geometry_jit = jax.jit(
    build_grid_with_geometry, static_argnames=("key_dtype",))


def window_descriptors(
    index: GridIndex,
    deltas: jax.Array,
    q_start: jax.Array | int = 0,
    q_size: Optional[int] = None,
) -> tuple[jax.Array, jax.Array]:
    """Per-(offset, query) candidate windows in kernel-friendly layout.

    For the query batch at sorted positions [q_start, q_start + q_size) and
    every stencil offset delta (linearized), returns

        win_start (n_off, q_size) int32 -- offset into ``points_sorted`` of
            the adjacent cell's candidate window, and
        win_count (n_off, q_size) int32 -- its length (0 when the adjacent
            cell is empty, absent from B, or the query slot is padding).

    This is pure index arithmetic: one batched ``searchsorted`` over B for
    the whole (offset x query) plane, no point-coordinate gather. The fused
    kernel (kernels/fused_join.py) prefetches these two arrays as scalars
    (pltpu.PrefetchScalarGridSpec) and performs the HBM->VMEM candidate
    gather itself, so the (B, C, n) gathered intermediate of the unfused
    sweep never exists (DESIGN.md S4).

    A window is always a contiguous run of ``points_sorted`` rows because a
    grid cell's points are contiguous in A-order (paper Fig. 2a), and
    ``win_start + win_count <= |D|`` always holds, so a kernel may read a
    fixed C-padded window anywhere as long as ``points_sorted`` carries C
    rows of tail padding.
    """
    npts = index.num_points
    if q_size is None:
        q_size = npts
    q_pos = jnp.asarray(q_start, jnp.int32) + jnp.arange(q_size, dtype=jnp.int32)
    return window_descriptors_at(index, deltas, q_pos, q_pos < npts)


def window_descriptors_at(
    index: GridIndex,
    deltas: jax.Array,
    q_pos: jax.Array,
    q_ok: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Candidate windows for EXPLICIT sorted positions (``q_pos``, (Q,)).

    The occupancy-bucketed launch loop (DESIGN.md S6) partitions query rows
    by candidate-capacity class, so a bucket's query rows are an ascending
    but non-contiguous subset of sorted order; this variant resolves each
    row's own cell from its position rather than a contiguous batch origin.
    ``q_ok`` masks padding slots (window count forced to 0); candidate
    windows themselves stay contiguous runs of ``points_sorted`` regardless
    of the query partition.
    """
    npts = index.num_points
    q_pos = q_pos.astype(jnp.int32)
    if q_ok is None:
        q_ok = q_pos < npts
    q_pos_c = jnp.minimum(q_pos, npts - 1)
    rank = index.point_cell_rank[q_pos_c]            # (Q,) rank of own cell
    own_key = index.cell_keys[rank]                  # (Q,) int64
    qk = own_key[None, :] + deltas[:, None]          # (n_off, Q) int64
    nbr = neighbor_rank(index, qk)                   # (n_off, Q), -1 = miss
    live = (nbr >= 0) & q_ok[None, :]
    nbr_c = jnp.maximum(nbr, 0)
    win_start = jnp.where(live, index.cell_start[nbr_c], 0).astype(jnp.int32)
    win_count = jnp.where(live, index.cell_count[nbr_c], 0).astype(jnp.int32)
    return win_start, win_count


def _rank_to_point(index: GridIndex, rank: jax.Array) -> jax.Array:
    """Sorted-point position of a cell RANK's window start; ranks >=
    ``num_cells`` map to ``num_points`` (the exclusive end of real points).

    The bridge between key-rank space and point space that makes merged
    range windows work: consecutive ranks in B own consecutive runs of
    ``points_sorted``, so the span of ranks [lo, hi) is exactly the point
    span [_rank_to_point(lo), _rank_to_point(hi)).
    """
    npts = index.num_points
    rank_c = jnp.minimum(rank, npts - 1)
    return jnp.where(rank < index.num_cells,
                     index.cell_start[rank_c], npts).astype(jnp.int32)


def range_window_descriptors_at(
    index: GridIndex,
    deltas: jax.Array,
    lo_off: jax.Array,
    hi_off: jax.Array,
    q_pos: jax.Array,
    q_ok: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """MERGED candidate windows for explicit sorted positions (DESIGN.md S7).

    For each reduced stencil offset (``deltas`` = linearized first-(n-1)-
    coordinate offsets, last coordinate 0) the three cells differing only
    in the last coordinate occupy adjacent key ranks, so their windows are
    ONE contiguous span of ``points_sorted``. Per (offset, query) this
    resolves the span [base + lo_off, base + hi_off] in key space with one
    searchsorted pair (left on the low key, right on the high key) and
    converts ranks to point positions via ``_rank_to_point``.

    The last-dimension span is clamped to the grid row: a query whose cell
    sits at last coordinate 0 (or dims-1) must not let the range probe
    wrap into the previous (next) row of the grid -- keys are dense across
    row boundaries, so an unclamped [base-1, base+1] would silently pull a
    wrapped cell's points into the window. Natural grid geometry keeps
    every point's coordinates in [1, dims-2] (paper SIV-B eps margins) so
    the clamp is a no-op there, but externally supplied geometry
    (``build_grid_with_geometry``) can place points on the row edge; the
    fused kernel's last-dimension boundary mask (kernels/fused_join.py)
    backstops the same hazard candidate-by-candidate.

    Returns (win_start, win_count, win_cells), each (n_off, Q) int32;
    ``win_cells`` is the number of non-empty cells inside each merged
    window -- the per-cell work counter the unmerged sweep reported as its
    live-probe count, preserved so merged and unmerged JoinStats match
    counter-for-counter.
    """
    npts = index.num_points
    q_pos = q_pos.astype(jnp.int32)
    if q_ok is None:
        q_ok = q_pos < npts
    q_pos_c = jnp.minimum(q_pos, npts - 1)
    rank = index.point_cell_rank[q_pos_c]            # (Q,) rank of own cell
    own_key = index.cell_keys[rank]                  # (Q,) int64
    dim_last = index.dims.astype(jnp.int64)[-1]
    q_last = own_key % dim_last                      # (Q,) last-dim coord
    base = own_key[None, :] + deltas[:, None]        # (n_off, Q) int64
    lo = jnp.maximum(lo_off[:, None], -q_last[None, :])
    hi = jnp.minimum(hi_off[:, None], dim_last - 1 - q_last[None, :])
    lo_rank = jnp.searchsorted(index.cell_keys, base + lo,
                               side="left").astype(jnp.int32)
    hi_rank = jnp.searchsorted(index.cell_keys, base + hi,
                               side="right").astype(jnp.int32)
    live = (hi_rank > lo_rank) & q_ok[None, :]
    start = _rank_to_point(index, lo_rank)
    end = _rank_to_point(index, hi_rank)
    win_start = jnp.where(live, start, 0).astype(jnp.int32)
    win_count = jnp.where(live, end - start, 0).astype(jnp.int32)
    win_cells = jnp.where(live, hi_rank - lo_rank, 0).astype(jnp.int32)
    return win_start, win_count, win_cells


def range_window_descriptors(
    index: GridIndex,
    deltas: jax.Array,
    lo_off: jax.Array,
    hi_off: jax.Array,
    q_start: jax.Array | int = 0,
    q_size: Optional[int] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Merged-range windows for a contiguous query batch (see
    ``range_window_descriptors_at``)."""
    npts = index.num_points
    if q_size is None:
        q_size = npts
    q_pos = (jnp.asarray(q_start, jnp.int32)
             + jnp.arange(q_size, dtype=jnp.int32))
    return range_window_descriptors_at(
        index, deltas, lo_off, hi_off, q_pos, q_pos < npts)


def external_range_descriptors(
    index: GridIndex,
    offsets: jax.Array,
    lo_off: jax.Array,
    hi_off: jax.Array,
    queries: jax.Array,
    q_limit: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Merged-range windows for EXTERNAL query points (DESIGN.md S7).

    The merged analogue of ``external_window_descriptors``: adjacency on
    the first n-1 coordinates is resolved in coordinate space with exact
    bounds masking (no key aliasing on tiny grids), and the last dimension
    becomes a per-query key-span [q_last + lo_off, q_last + hi_off]
    clamped to [0, dims-1] -- which also handles queries up to one cell
    OUTSIDE the volume in the last dimension (q_last = -1 probes row 0
    only; q_last = dims probes row dims-1 only; farther out the clamped
    span inverts and the probe is dead, the exact answer).

    Returns (win_start, win_count, win_cells), each (n_off, Q) int32.
    """
    qcoords = cell_coords(queries, index.grid_min, index.eps)   # (Q, n)
    dims = index.dims.astype(jnp.int64)
    n = qcoords.shape[1]
    row = qcoords[None, :, :-1] + offsets[:, None, :-1]   # (n_off, Q, n-1)
    row_ok = jnp.all((row >= 0) & (row < dims[:-1]), axis=-1) if n > 1 \
        else jnp.ones(row.shape[:2], bool)
    q_last = qcoords[:, -1]                               # (Q,) int64
    lo_last = jnp.maximum(q_last[None, :] + lo_off[:, None], 0)
    hi_last = jnp.minimum(q_last[None, :] + hi_off[:, None], dims[-1] - 1)
    live = row_ok & (lo_last <= hi_last)
    row_c = jnp.clip(row, 0, dims[:-1] - 1)               # safe linearize
    # append an explicit zero last coordinate: row_c is width n-1, which
    # is 0 for 1-D data, so zeros_like(row_c[..., :1]) would stay empty
    zero_last = jnp.zeros(row_c.shape[:-1] + (1,), row_c.dtype)
    base = linearize(jnp.concatenate([row_c, zero_last], axis=-1),
                     index.dims)
    kd = index.cell_keys.dtype
    # dead probes get an inverted sentinel span (lo > hi) in the INDEX
    # key dtype; `live` already masks them, the sentinel just keeps the
    # searchsorted inputs in range for int32-keyed grids
    lo_key = _pad_probe(base + lo_last, live, kd)
    hi_key = jnp.where(live, (base + hi_last).astype(kd),
                       jnp.asarray(pad_key_for(kd) - 1, kd))
    lo_rank = jnp.searchsorted(index.cell_keys, lo_key,
                               side="left").astype(jnp.int32)
    hi_rank = jnp.searchsorted(index.cell_keys, hi_key,
                               side="right").astype(jnp.int32)
    if q_limit is not None:
        q_ok = jnp.arange(queries.shape[0], dtype=jnp.int32) < q_limit
        live = live & q_ok[None, :]
    live = live & (hi_rank > lo_rank)
    start = _rank_to_point(index, lo_rank)
    end = _rank_to_point(index, hi_rank)
    win_start = jnp.where(live, start, 0).astype(jnp.int32)
    win_count = jnp.where(live, end - start, 0).astype(jnp.int32)
    win_cells = jnp.where(live, hi_rank - lo_rank, 0).astype(jnp.int32)
    return win_start, win_count, win_cells


def point_last_coords(index: GridIndex) -> jax.Array:
    """Last-dimension cell coordinate of every sorted point, int32.

    Derived EXACTLY from the int64 cell keys (key mod dims[-1]), never
    from float coordinates -- the fused kernel's merged boundary mask
    compares these as (exactly representable) floats, so a TPU f32
    downcast can never disagree with the build-time cell assignment.
    """
    keys = index.cell_keys[index.point_cell_rank]
    return (keys % index.dims.astype(jnp.int64)[-1]).astype(jnp.int32)


def external_window_descriptors(
    index: GridIndex,
    offsets: jax.Array,
    queries: jax.Array,
    q_limit: Optional[jax.Array] = None,
) -> tuple[jax.Array, jax.Array]:
    """Candidate windows for EXTERNAL query points (core/query_join.py).

    ``window_descriptors`` derives each query's cell from its position in
    ``points_sorted``; here the cell comes from the query's own coordinates
    under the dataset's grid geometry, so ``queries`` may be ANY point set --
    inside the indexed volume, outside it, or duplicated.

    Adjacency is resolved in COORDINATE space, not linearized-key space:
    ``target = cell_coords(q) + o`` per stencil offset ``o`` (the (n_off, n)
    int64 offset vectors, not their linearized deltas), masked where any
    dimension leaves [0, dims). This supersedes the historical
    ``clip(qcoords, 1, dims - 2)`` clamp, which inverted (hi < lo) on grids
    with < 3 cells in a dimension and silently redirected every query to
    cell 0; exact bounds masking has no such degenerate case, and it also
    prevents linearized keys of out-of-range coordinates from aliasing into
    other real cells (a double-count hazard the key-space probe has when a
    dimension has < 3 cells).

    A query farther than eps outside the volume has out-of-range coords in
    some dimension for every offset -> all probes masked -> zero candidates,
    which is the exact answer. A query within eps of the volume has coords
    in [0, dims), and its true neighbors' cells are covered by the masked
    stencil (real points occupy the interior band by construction).

    Returns (win_start, win_count), each (n_off, Q) int32, count 0 for
    masked probes, absent cells, and query rows >= ``q_limit`` (tile
    padding).
    """
    qcoords = cell_coords(queries, index.grid_min, index.eps)   # (Q, n)
    dims = index.dims.astype(jnp.int64)
    target = qcoords[None, :, :] + offsets[:, None, :]          # (n_off, Q, n)
    in_grid = jnp.all((target >= 0) & (target < dims), axis=-1)
    keys = _pad_probe(linearize(target, index.dims), in_grid,
                      index.cell_keys.dtype)
    nbr = neighbor_rank(index, keys)                            # (n_off, Q)
    live = nbr >= 0
    if q_limit is not None:
        q_ok = jnp.arange(queries.shape[0], dtype=jnp.int32) < q_limit
        live = live & q_ok[None, :]
    nbr_c = jnp.maximum(nbr, 0)
    win_start = jnp.where(live, index.cell_start[nbr_c], 0).astype(jnp.int32)
    win_count = jnp.where(live, index.cell_count[nbr_c], 0).astype(jnp.int32)
    return win_start, win_count


def neighbor_rank(index: GridIndex, query_keys: jax.Array) -> jax.Array:
    """Vectorized membership lookup in B: rank of each key, or -1 if absent.

    This is the TPU-native replacement for the paper's per-thread binary
    search (Alg. 1 line 11): one batched ``searchsorted`` over all queries.
    """
    pos = jnp.searchsorted(index.cell_keys, query_keys).astype(jnp.int32)
    pos = jnp.minimum(pos, index.num_points - 1)
    hit = index.cell_keys[pos] == query_keys
    return jnp.where(hit, pos, -1)


# ---------------------------------------------------------------------------
# Cell-run plans (DESIGN.md S11): queries sharing a grid cell have identical
# window descriptors for EVERY stencil offset (both descriptor families above
# derive (win_start, win_count) purely from the query's cell rank), so the
# fused kernel can gather each cell's candidate window once per RUN of
# co-located query rows instead of once per row -- the paper's duplicate-
# search-removal (SIV-C) applied to the DMA stream.
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class RunPlan:
    """Cell-run partition of one fused launch's query rows.

    ``run_ord[i]`` is row i's run ordinal WITHIN its tq-tile: it resets to 0
    at every tile boundary and increments by exactly 1 where the row's cell
    identity changes, so rows with equal ordinals inside a tile form one run
    and (by the descriptor purity argument above) share ``win_start`` /
    ``win_count`` columns for all offsets. The run-loop kernel derives its
    DMA schedule entirely from this array (head = ordinal change, slot =
    ordinal mod 2); ``n_runs`` / ``run_lengths`` are the host-side
    accounting behind ``JoinStats.dma_windows_issued`` and the bench
    run-length histogram.
    """

    run_ord: np.ndarray       # (qp,) int32 per-tile run ordinals
    n_runs: int               # total runs across all tiles
    run_lengths: np.ndarray   # (n_runs,) int32 rows per run


def cell_run_plan(cell_of_row: np.ndarray, tq: int) -> RunPlan:
    """Partition a launch's rows into maximal same-cell runs, per tile.

    ``cell_of_row`` is any per-row cell identity in launch order -- the
    self-join drivers use ``point_cell_rank`` at each row's sorted
    position, the external serving path the (sorted) query batch's cell
    coordinates collapsed to group ids. Rows are grouped while the
    identity repeats; runs additionally split at ``tq``-tile boundaries
    because the kernel's grid iterates tiles (per-tile DMA warm-up and
    outputs), which is why ``run_ord`` can reset per tile.

    The partition is exact: every row belongs to exactly one run, ordinals
    within a tile start at 0 and step by {0, 1}, and a step of 1 happens
    precisely where the cell identity changes. ``analysis.contracts.
    check_run_plan`` (C10) re-proves this against an independently derived
    cell-of-row oracle; tests/test_cell_runs.py fuzzes it.
    """
    ids = np.asarray(cell_of_row)
    qp = ids.shape[0]
    if tq <= 0 or qp % tq:
        raise ValueError(f"run plan rows {qp} must be a positive multiple "
                         f"of tq={tq}")
    head = np.ones(qp, bool)
    head[1:] = ids[1:] != ids[:-1]
    head[np.arange(0, qp, tq)] = True
    run_ord = (np.cumsum(head.reshape(-1, tq), axis=1, dtype=np.int64) - 1)
    starts = np.flatnonzero(head)
    lengths = np.diff(np.append(starts, qp)).astype(np.int32)
    return RunPlan(run_ord=run_ord.reshape(-1).astype(np.int32),
                   n_runs=int(starts.size),
                   run_lengths=lengths)


@partial(jax.jit, static_argnames=("merged",))
def _cell_window_table_device(index: GridIndex, deltas, prefix, *,
                              merged: bool):
    """Per-CELL window descriptor tables, shape (n_off, num_points).

    Column r holds the (win_start, win_count, win_cells) triple of cell
    rank r -- the same arithmetic as ``window_descriptors_at`` /
    ``range_window_descriptors_at`` evaluated once per CELL instead of
    once per query row. Columns beyond ``num_cells`` are dead (count 0):
    they are only ever gathered through clamped padding rows, whose
    counts the preps re-zero anyway. Computing the table once per index
    and gathering per launch removes the per-launch searchsorted over
    (n_off x rows) -- the paper's duplicate-search removal (SIV-C) on the
    descriptor side, feeding the run-loop kernel's DMA-side dedup.

    ``prefix`` (merged only): the index's dense prefix table
    (``dense_prefix_table``), which answers the merged span's rank and
    point bounds by one gather a side; None keeps the binary searches.
    """
    npts = index.num_points
    valid = jnp.arange(npts) < index.num_cells
    own_key = jnp.where(valid, index.cell_keys, 0)
    if merged:
        # int32 probes on the table route (its volume is at most
        # _LOOKUP_MAX_CELLS); the searches keep their int64 probes
        kd = jnp.int64 if prefix is None else jnp.int32
        dtab, lo_off, hi_off = jnp.asarray(deltas).astype(kd)
        own = own_key.astype(kd)
        dim_last = index.dims[-1].astype(kd)
        q_last = own % dim_last
        base = own[None, :] + dtab[:, None]
        lo = base + jnp.maximum(lo_off[:, None], -q_last[None, :])
        hi = base + jnp.minimum(hi_off[:, None],
                                dim_last - 1 - q_last[None, :])
        if prefix is None:
            lo_rank = jnp.searchsorted(index.cell_keys, lo,
                                       side="left").astype(jnp.int32)
            hi_rank = jnp.searchsorted(index.cell_keys, hi,
                                       side="right").astype(jnp.int32)
            start = _rank_to_point(index, lo_rank)
            end = _rank_to_point(index, hi_rank)
        else:
            (lo_rank, start), (hi_rank, end) = _prefix_span(prefix, lo, hi)
        live = (hi_rank > lo_rank) & valid[None, :]
        ws = jnp.where(live, start, 0).astype(jnp.int32)
        wc = jnp.where(live, end - start, 0).astype(jnp.int32)
        wcells = jnp.where(live, hi_rank - lo_rank, 0).astype(jnp.int32)
        return ws, wc, wcells
    qk = own_key[None, :] + deltas[:, None]
    nbr = neighbor_rank(index, qk)
    live = (nbr >= 0) & valid[None, :]
    nbr_c = jnp.maximum(nbr, 0)
    ws = jnp.where(live, index.cell_start[nbr_c], 0).astype(jnp.int32)
    wc = jnp.where(live, index.cell_count[nbr_c], 0).astype(jnp.int32)
    wcells = (wc > 0).astype(jnp.int32)
    return ws, wc, wcells


def cell_window_tables(index: GridIndex, deltas, *, merged: bool, tag):
    """Cached per-cell descriptor tables (see ``_cell_window_table_device``).

    ``deltas`` is the linearized offset table (unmerged) or the
    ``(dtab, lo_off, hi_off)`` triple (merged); ``tag`` disambiguates
    offset tables that share ``merged`` (the drivers pass the unicomp
    flag). Cached per index via ``index_cached`` so repeated sweeps and
    the run-loop's steady state never recompute the plane. Merged tables
    read the dense prefix table where the grid's volume allows it.
    """
    prefix = dense_prefix_table(index) if merged else None
    return index_cached(
        index, f"wintab/{bool(merged)}/{tag}",
        lambda: _cell_window_table_device(index, deltas, prefix,
                                          merged=merged))


# ---------------------------------------------------------------------------
# Dense prefix table: where the grid's key space is small, the merged
# window planners answer "how many non-empty cells (points) lie below key
# k" by one gather from a table over every key, instead of a binary search
# of B.
# ---------------------------------------------------------------------------

# Dense-lookup budget: prod(dims) at or below this many cells (x4 bytes a
# table column) buys the dense tables; beyond it, binary search (the
# paper's trade) wins.
_LOOKUP_MAX_CELLS = 1 << 23   # 32 MB


def host_dims(index: GridIndex) -> np.ndarray:
    """Host copy of ``index.dims``, cached per index: the one fetch the
    planners take the grid's geometry (strides, volume) from."""
    return index_cached(index, "dims_np", lambda: np.asarray(index.dims))


def grid_volume(index: GridIndex) -> int:
    """prod(dims), the size of the linear key space (exact python int)."""
    volume = 1
    for d in host_dims(index):
        volume *= int(d)
    return volume


def prefix_table_size(index: GridIndex) -> int:
    """Rows of the index's dense prefix table -- the power of two past
    prod(dims) + 1, so that one compiled program serves every grid of
    that size class -- or 0 where prod(dims) exceeds ``_LOOKUP_MAX_CELLS``
    and the planners keep their binary searches."""
    volume = grid_volume(index)
    if volume > _LOOKUP_MAX_CELLS:
        return 0
    return 1 << (volume + 1).bit_length()


def _prefix_table(index: GridIndex, size: int) -> jax.Array:
    """The dense prefix table over keys [0, size) (traced; ``size`` >
    prod(dims) + 1, from ``prefix_table_size``), (size, 2) int32 -- the
    two columns side by side, so that one gather reads both:

        [k, 0] -- number of non-empty cells with key < k, i.e. the left
                  ``searchsorted`` rank of k in B;
        [k, 1] -- number of points in those cells, i.e. that rank's
                  ``_rank_to_point``.

    One scatter of the present keys (sorted, so the scatter is in order)
    and an exclusive cumsum. Every cell key lies in [0, prod(dims)]: real
    cells below prod(dims), the out-of-set sentinel cell of a padded build
    (``build_grid_with_geometry`` ``valid``) at it, so the table counts
    the sentinel cell past prod(dims) exactly as the search does, and its
    tail holds every cell.
    """
    n = index.cell_keys.shape[0]
    live = jnp.arange(n, dtype=jnp.int32) < index.num_cells
    slot = jnp.where(live, index.cell_keys, size).astype(jnp.int32)
    present = jnp.zeros(size, jnp.int32).at[slot].set(
        1, mode="drop", indices_are_sorted=True)
    rank_lt = jnp.cumsum(present) - present
    return jnp.stack([rank_lt, _rank_to_point(index, rank_lt)], axis=1)


def _prefix_span(prefix: jax.Array, lo_key: jax.Array, hi_key: jax.Array):
    """((lo_rank, lo_point), (hi_rank, hi_point)) of merged spans
    [lo_key, hi_key]: the left rank of lo_key is row lo of the table, the
    right rank of hi_key row hi + 1, and each row carries its point bound.
    Probes are clipped into the table: below 0 no cell precedes them, and
    past prod(dims) + 1 every cell does -- the ranks the binary search
    gives those probes (dead-lane sentinels and wrapped probes among
    them)."""
    top = prefix.shape[0] - 1
    lo = prefix[jnp.clip(lo_key, 0, top)]
    hi = prefix[jnp.clip(hi_key + 1, 0, top)]
    return (lo[..., 0], lo[..., 1]), (hi[..., 0], hi[..., 1])


def dense_prefix_table(index: GridIndex):
    """The index's dense prefix table (``_prefix_table``), or None where
    the grid's volume keeps the binary searches (``prefix_table_size``).
    Built once per index, by the merged capacity program that every merged
    join plans on first (``dispatch_window_caps``), and gathered from by
    the per-cell window tables."""
    if not prefix_table_size(index):
        return None
    return dispatch_window_caps(index, merged=True)[1]


# ---------------------------------------------------------------------------
# Occupancy bucketing (DESIGN.md S6): partition query rows into candidate-
# capacity classes so the fused kernel pads each window to its BUCKET's
# capacity instead of the global max_per_cell. On skewed data the global max
# is 5-10x the median cell, so a single-capacity launch spends most of its
# window lanes on padding; per-bucket static capacities keep kernel shapes
# static (one cached executable per class) while sizing the work to the data.
# ---------------------------------------------------------------------------

CAP_ALIGN = 8  # lane alignment of window capacities (matches the kernels)


def round_up(x, m: int):
    """Round up to a multiple of m (python ints and np arrays alike) --
    THE capacity/tile alignment helper (selfjoin and query_join alias it)."""
    return ((x + m - 1) // m) * m


@dataclasses.dataclass(frozen=True)
class BucketPlan:
    """Static partition of sorted query rows into capacity classes.

    ``caps[k]`` is bucket k's window capacity (ascending, CAP_ALIGN-aligned,
    the last equals the global capacity); ``sel[k]`` holds the bucket's
    sorted positions in ascending A-order (``None`` for the single-bucket
    plan, meaning "all rows, contiguous"). ``hist`` maps each capacity class
    to its query count -- the window-length histogram that motivated the
    classes (EXPERIMENTS.md SBuckets).
    """

    caps: tuple
    sel: tuple
    cap_global: int
    hist: dict

    @property
    def n_buckets(self) -> int:
        return len(self.caps)


def capacity_classes(cap_global: int, align: int = CAP_ALIGN) -> tuple:
    """Pow2-growing capacity ladder (align, 2*align, ...) capped at
    ``cap_global`` (which is kept even when not a power of two)."""
    cap_global = max(int(cap_global), align)
    out = []
    v = align
    while v < cap_global:
        out.append(v)
        v *= 2
    out.append(cap_global)
    return tuple(out)


def starts_ext(index: GridIndex) -> np.ndarray:
    """Host-side rank -> point-span bridge: ``cell_start`` of each valid
    rank with ``num_points`` appended as the exclusive end, so the point
    span of ranks [lo, hi) is ``starts_ext[lo] : starts_ext[hi]``. THE one
    copy of that convention -- the merged capacity planners (here) and the
    sparse counter (core/selfjoin.py) must agree with
    ``_rank_to_point`` bit-for-bit or window capacities undercount."""
    ncells = int(index.num_cells)
    return np.concatenate(
        [np.asarray(index.cell_start[:ncells]),
         np.asarray([index.num_points])]).astype(np.int64)


def cell_window_caps_host(index: GridIndex, merged: bool = False) -> np.ndarray:
    """Numpy reference for ``cell_window_caps``: 3^(n-1) host searchsorted
    sweeps, one per stencil offset. Kept as the independent oracle the
    device planner is property-tested against (tests/test_device_build.py);
    the serving path uses the batched device planner below.
    """
    from repro.core.stencil import merged_stencil_offsets, stencil_offsets

    ncells = int(index.num_cells)
    keys = np.asarray(index.cell_keys[:ncells])
    counts = np.asarray(index.cell_count[:ncells]).astype(np.int64)
    strides = np.asarray(row_major_strides(index.dims))
    caps = np.zeros(ncells, np.int64)
    if not merged:
        deltas = stencil_offsets(index.n_dims, unicomp=False) @ strides
        for delta in deltas:
            probe = keys + delta
            pos = np.minimum(np.searchsorted(keys, probe), ncells - 1)
            live = keys[pos] == probe
            caps = np.maximum(caps, np.where(live, counts[pos], 0))
        return caps.astype(np.int32)
    reduced, _, _ = merged_stencil_offsets(index.n_dims, unicomp=False)
    deltas = reduced @ strides
    dim_last = int(np.asarray(index.dims)[-1])
    last = keys % dim_last
    lo = keys + np.maximum(-1, -last)
    hi = keys + np.minimum(1, dim_last - 1 - last)
    ext = starts_ext(index)
    for delta in deltas:
        lo_rank = np.searchsorted(keys, lo + delta, side="left")
        hi_rank = np.searchsorted(keys, hi + delta, side="right")
        span = ext[hi_rank] - ext[lo_rank]
        caps = np.maximum(caps, np.where(hi_rank > lo_rank, span, 0))
    return caps.astype(np.int32)


@partial(jax.jit, static_argnames=("merged", "size"))
def _cell_window_caps_device(index: GridIndex, deltas: jax.Array,
                             merged: bool, size: int = 0):
    """Batched device form of the per-cell capacity sweep: ONE searchsorted
    over the (offset x cell) plane per probe side instead of a host loop of
    3^(n-1) sweeps. Operates on the full padded key array; lanes at rank >=
    ``num_cells`` are dead (padding-sentinel probes land on zero-count
    padding slots). Returns the (npts,) int64 caps and the dense prefix
    table (None unless ``size``); the un-jitted wrapper slices the valid
    prefix of the caps -- the single host sync of the plan.

    ``size`` (merged only, ``prefix_table_size``): build the dense prefix
    table (``_prefix_table``) and read the span bounds from it, one
    gather per probe side in int32, instead of the binary searches.

    Probe overflow note: on the int32 key route the host reference promotes
    ``keys + delta`` to int64 while the device add wraps, but a wrapped
    probe is strictly negative (|key|, |delta| < volume < 2^31) and ranks
    to 0 where it can never equal a real key -- the same dead answer the
    host's out-of-range int64 probe gets at rank ``ncells``. The only
    geometry where the merged hi-probe could wrap PAST the padding sentinel
    is volume within 2 of 2^31, which contract C9 rejects
    (analysis/contracts.py ``check_device_sentinel``).
    """
    keys = index.cell_keys                           # (npts,) pad-sentineled
    kd = keys.dtype
    n = keys.shape[0]
    is_cell = jnp.arange(n, dtype=jnp.int32) < index.num_cells
    if merged and size:
        prefix = _prefix_table(index, size)
        own = jnp.where(is_cell, keys, 0).astype(jnp.int32)
        dim_last = index.dims[-1].astype(jnp.int32)
        last = own % dim_last
        lo = own + jnp.maximum(-1, -last)
        hi = own + jnp.minimum(1, dim_last - 1 - last)
        d32 = deltas.astype(jnp.int32)[:, None]
        (lo_rank, start), (hi_rank, end) = _prefix_span(
            prefix, lo[None, :] + d32, hi[None, :] + d32)
        live = (hi_rank > lo_rank) & is_cell[None, :]
        hit = jnp.where(live, (end - start).astype(jnp.int64), 0)
        return jnp.max(hit, axis=0), prefix
    counts = jnp.where(is_cell, index.cell_count, 0).astype(jnp.int64)
    deltas = deltas.astype(kd)[:, None]              # (n_off, 1)
    if not merged:
        probe = _pad_probe(keys[None, :] + deltas, is_cell[None, :], kd)
        pos = jnp.minimum(jnp.searchsorted(keys, probe), n - 1)
        live = keys[pos] == probe
        hit = jnp.where(live, counts[pos], 0)        # (n_off, npts)
        return jnp.max(hit, axis=0), None
    dim_last = index.dims.astype(kd)[-1]
    last = keys % dim_last
    lo = keys + jnp.maximum(jnp.asarray(-1, kd), -last)
    hi = keys + jnp.minimum(jnp.asarray(1, kd), dim_last - 1 - last)
    # dead lanes: inverted sentinel span (lo=pad, hi=pad-1), the idiom of
    # ``external_range_descriptors`` -- both ranks land in the padding tail
    # and the hi_rank > lo_rank mask kills the lane
    lo_key = _pad_probe(lo[None, :] + deltas, is_cell[None, :], kd)
    hi_key = jnp.where(is_cell[None, :], hi[None, :] + deltas,
                       jnp.asarray(pad_key_for(kd) - 1, kd))
    lo_rank = jnp.searchsorted(keys, lo_key, side="left").astype(jnp.int32)
    hi_rank = jnp.searchsorted(keys, hi_key, side="right").astype(jnp.int32)
    span = (_rank_to_point(index, hi_rank)
            - _rank_to_point(index, lo_rank)).astype(jnp.int64)
    hit = jnp.where(hi_rank > lo_rank, span, 0)
    return jnp.max(hit, axis=0), None


def cell_window_caps(index: GridIndex, merged: bool = False) -> np.ndarray:
    """Per non-empty cell: the largest candidate window any of its points
    can see. Pure index arithmetic; an upper bound for any sub-stencil
    (e.g. the UNICOMP half), so one plan serves both sweep modes.

    ``merged=False``: max over the FULL 3^n stencil of the single neighbor
    cell's count (own cell included). ``merged=True``: max over the
    3^(n-1) reduced stencil of the MERGED last-dimension range window
    (DESIGN.md S7) -- the contiguous span of up to three cells' points,
    clamped at the grid row like ``range_window_descriptors_at``.

    The sweep itself runs on the device (``_cell_window_caps_device``,
    batched over all reduced offsets at once); this wrapper materializes
    the offset table, launches the jitted planner, and performs the single
    host sync that fixes the static bucket-capacity classes. Bit-equal to
    ``cell_window_caps_host``.
    """
    return _fetch_window_caps(index, _launch_window_caps(index, merged))


def _launch_window_caps(index: GridIndex, merged: bool):
    """Dispatch ``cell_window_caps``' device sweep; no wait. Returns the
    program's (caps, dense prefix table or None)."""
    from repro.core.stencil import merged_stencil_offsets, stencil_offsets

    dims = host_dims(index)
    strides = np.ones(dims.shape[0], np.int64)
    strides[:-1] = np.cumprod(dims[:0:-1].astype(np.int64))[::-1]
    if merged:
        reduced, _, _ = merged_stencil_offsets(index.n_dims, unicomp=False)
        deltas = reduced @ strides
    else:
        deltas = stencil_offsets(index.n_dims, unicomp=False) @ strides
    kd = np.dtype(index.cell_keys.dtype)
    return _cell_window_caps_device(
        index, jnp.asarray(deltas.astype(kd)), merged=merged,
        size=prefix_table_size(index) if merged else 0)


def _fetch_window_caps(index: GridIndex, launched) -> np.ndarray:
    ncells = int(index.num_cells)
    return np.asarray(launched[0])[:ncells].astype(np.int32)


@jax.jit
def _external_span_device(index: GridIndex) -> jax.Array:
    """Device form of the external range-cap sweep: point span of keys
    [k, k+2] for every present key k, batched ``searchsorted`` with
    side='right'. Padding lanes probe pad-1 and span zero."""
    keys = index.cell_keys
    kd = keys.dtype
    n = keys.shape[0]
    is_cell = jnp.arange(n, dtype=jnp.int32) < index.num_cells
    hi_key = jnp.where(is_cell, keys + jnp.asarray(2, kd),
                       jnp.asarray(pad_key_for(kd) - 1, kd))
    hi_rank = jnp.searchsorted(keys, hi_key, side="right").astype(jnp.int32)
    lo = _rank_to_point(index, jnp.arange(n, dtype=jnp.int32))
    span = (_rank_to_point(index, hi_rank) - lo).astype(jnp.int64)
    return jnp.where(is_cell, span, 0)


# Derived structures (bucket plans, lookup tables, route decisions) are
# pure functions of the (immutable) index; cache them per live GridIndex so
# repeated joins against the same index pay the planning work once. Keyed
# by (id, tag) with a weakref finalizer for eviction -- GridIndex holds jax
# arrays and is itself unhashable. Bounded LRU: a long-lived re-indexing
# service (launch/serve.py reindex) swaps snapshots indefinitely, and the
# finalizer alone only fires when the OLD index is garbage collected --
# anything still referencing a retired index would pin its plans forever.
# Entries are pure recomputable values (never executables), so eviction can
# only cost a rebuild, never a retrace.
_INDEX_CACHE_MAX = 64
_INDEX_CACHE: "collections.OrderedDict" = collections.OrderedDict()
_MISSING = object()

INDEX_CACHE_STATS = {"hits": 0, "misses": 0, "evictions": 0, "finalized": 0}


def index_cache_stats() -> dict:
    """Snapshot of the per-index plan cache counters plus current size."""
    out = dict(INDEX_CACHE_STATS)
    out["size"] = len(_INDEX_CACHE)
    return out


def _finalize_index_entry(key) -> None:
    # The entry may already be gone (LRU eviction raced the GC): pop with a
    # sentinel default so a late finalizer never raises or double-counts.
    if _INDEX_CACHE.pop(key, _MISSING) is not _MISSING:
        INDEX_CACHE_STATS["finalized"] += 1


def index_cached(index: GridIndex, tag: str, build):
    """Memoize ``build()`` on the index object under ``tag`` (bounded LRU)."""
    key = (id(index), tag)
    value = _INDEX_CACHE.get(key, _MISSING)
    if value is not _MISSING:
        INDEX_CACHE_STATS["hits"] += 1
        _INDEX_CACHE.move_to_end(key)
        return value
    INDEX_CACHE_STATS["misses"] += 1
    value = build()
    _INDEX_CACHE[key] = value
    weakref.finalize(index, _finalize_index_entry, key)
    while len(_INDEX_CACHE) > _INDEX_CACHE_MAX:
        _INDEX_CACHE.popitem(last=False)
        INDEX_CACHE_STATS["evictions"] += 1
    return value


def cell_window_caps_cached(index: GridIndex,
                            merged: bool = False) -> np.ndarray:
    """``cell_window_caps`` memoized per index object -- the merged caps
    feed both ``global_window_cap`` and the occupancy plan build, and a
    6-D pass is 3^(n-1) host searchsorted sweeps worth not repeating."""
    return index_cached(index, f"cellcaps/{merged}",
                        lambda: _fetch_window_caps(
                            index, dispatch_window_caps(index, merged)))


def dispatch_window_caps(index: GridIndex, merged: bool = False):
    """The device sweep behind ``cell_window_caps_cached``, dispatched
    once per index and not waited on: a driver queues it before it waits
    on other devices, and the cached fetch then finds it under way.
    Returns the program's (caps, dense prefix table or None)."""
    return index_cached(index, f"cellcaps_dev/{merged}",
                        lambda: _launch_window_caps(index, merged))


def global_window_cap(index: GridIndex, merged: bool = False,
                      align: int = CAP_ALIGN) -> int:
    """Aligned global window capacity of one fused launch: the unbucketed
    static window size. Unmerged: the paper's max_per_cell. Merged: the
    largest merged range window any cell sees (<= 3 * max_per_cell,
    computed exactly; cached per index)."""
    if not merged:
        return round_up(max(int(index.max_per_cell), 1), align)

    def build():
        caps = cell_window_caps_cached(index, merged=True)
        top = int(caps.max()) if caps.size else 0
        return round_up(max(top, 1), align)

    return index_cached(index, f"capglobal/{align}/{merged}", build)


def external_range_cap(index: GridIndex, align: int = CAP_ALIGN) -> int:
    """Upper bound on ANY merged range window an external query can see.

    An external query's window spans keys [base-1, base+1]; its minimal
    present key k bounds the span by [k, k+2] -- so the max over present
    keys k of the point span of [k, k+2] dominates every possible query
    window, including windows whose center cell is absent from B (which
    per-cell caps cannot see). Sweep on the device
    (``_external_span_device``); cached per index.
    """
    def build():
        span = np.asarray(_external_span_device(index))
        top = int(span.max()) if span.size else 0
        return round_up(max(top, 1), align)

    return index_cached(index, f"extcap/{align}", build)


def occupancy_plan(index: GridIndex, align: int = CAP_ALIGN,
                   merged: bool = False) -> BucketPlan:
    """Window-length histogram -> capacity classes -> query-row partition.

    Rows keep ascending A-order inside every bucket (a cell's points share
    a class, so selections are runs of whole cells) and each row appears in
    exactly ONE bucket: per-bucket counts and slot bases compose back into
    the single-pass count -> fill contract by concatenation. ``merged``
    plans classes on the merged range-window capacities (DESIGN.md S7).
    """
    return index_cached(index, f"plan/{align}/{merged}",
                        lambda: _build_occupancy_plan(index, align, merged))


def filter_plan_rows(plan: BucketPlan, row_ok: np.ndarray) -> BucketPlan:
    """Restrict a BucketPlan to the sorted rows where ``row_ok`` is True.

    The distributed slab join (core/distributed.py) launches the fused
    sweep only over rows its slab OWNS -- halo rows are candidates, never
    queries -- so every bucket's selection is intersected with the
    ownership mask (ascending A-order preserved). Contiguous single-class
    plans (``sel`` None) become explicit selections; classes left empty
    are dropped; the capacity ladder and histogram keep the surviving
    rows' counts.
    """
    row_ok = np.asarray(row_ok, bool)
    caps, sels, hist = [], [], {}
    for cap, sel in zip(plan.caps, plan.sel):
        rows = (np.flatnonzero(row_ok).astype(np.int32) if sel is None
                else sel[row_ok[sel]])
        if rows.size:
            caps.append(cap)
            sels.append(rows)
            hist[int(cap)] = int(rows.size)
    if not caps:
        return BucketPlan(caps=(plan.cap_global,), sel=(np.zeros(0, np.int32),),
                          cap_global=plan.cap_global,
                          hist={plan.cap_global: 0})
    return BucketPlan(caps=tuple(caps), sel=tuple(sels),
                      cap_global=plan.cap_global, hist=hist)


def _build_occupancy_plan(index: GridIndex, align: int,
                          merged: bool = False) -> BucketPlan:
    npts = index.num_points
    cap_global = global_window_cap(index, merged, align)
    if cap_global <= align or npts == 0:
        return BucketPlan(caps=(cap_global,), sel=(None,),
                          cap_global=cap_global, hist={cap_global: npts})
    classes = capacity_classes(cap_global, align)
    caps = cell_window_caps_cached(index, merged=merged)  # (ncells,)
    caps_aligned = np.minimum(
        round_up(np.maximum(caps, 1), align), cap_global)
    cls_of_cell = np.searchsorted(np.asarray(classes), caps_aligned)
    rank = np.asarray(index.point_cell_rank)             # (npts,) cell of row
    cls_of_row = cls_of_cell[rank]
    hist, sels, kept = {}, [], []
    for k, cap in enumerate(classes):
        rows = np.flatnonzero(cls_of_row == k).astype(np.int32)
        if rows.size:
            hist[int(cap)] = int(rows.size)
            sels.append(rows)
            kept.append(int(cap))
    if len(kept) == 1:
        # one populated class: single contiguous launch at that capacity
        return BucketPlan(caps=(kept[0],), sel=(None,),
                          cap_global=cap_global, hist=hist)
    return BucketPlan(caps=tuple(kept), sel=tuple(sels),
                      cap_global=cap_global, hist=hist)

