"""Distributed self-join: spatial slab decomposition with eps-halo exchange.

The paper is single-GPU; this module is the scale-out design of DESIGN.md S3
(the slab + halo shape of Gowanlock's multi-GPU follow-on work and Karsin's
multi-GPU join pipelines, PAPERS.md).

Decomposition
-------------
Points are partitioned into contiguous slabs along dimension 0 (equal-count
quantile boundaries, computed on the host: ``partition_points_host``; empty
slabs are legal and handled). Each slab:

  1. exchanges a k-hop eps-halo with its slab neighbors via
     ``lax.ppermute`` -- exactly the points within eps (in dim 0) of the
     shared boundary, which is all another slab can ever need
     (``_assemble_candidates``; ``halo_reach`` derives k, parcels are
     capacity-bounded with overflow *detected*, never silent),
  2. builds its local grid over (local + halo) candidates against the
     GLOBAL grid geometry, so cell coordinates -- and the UNICOMP
     cell-pair ownership rule -- are consistent across slabs, and
  3. joins only pairs whose *query* point it owns.

Two join paths share that decomposition:

``distributed_self_join`` -- the fused pair join: per slab, the SAME fast
path as the single-device join (merged-range sweep, occupancy buckets,
single-pass count -> fill; ``selfjoin._self_join_fused_stages``, the
slabs driven in step so the chips work at once) restricted to
owned query rows, with GLOBAL point ids riding a kernel pad lane
(``gid_pairs``) so the UNICOMP intra-cell tie-break is device-independent.
Emits (K, 2) global-id pairs bit-identical to
``self_join(distance_impl='fused')`` after the (row, column) sort;
``return_pairs=False`` runs the count-only launches.

``distributed_self_join_count`` -- the legacy jnp offset-sweep counter,
retained for the 'model'-axis offset parallelism: the stencil offset table
is sharded over the second mesh axis and partial counts are psum-reduced,
matching how the LM stack uses the same axis for tensor parallelism.

Correctness of single counting: with globally consistent cell coordinates the
UNICOMP half-stencil assigns each unordered adjacent-cell pair to exactly one
directed evaluation; the device owning the query endpoint of that evaluation
is unique, and (since qualifying pairs are within eps in dim 0) its candidate
set is guaranteed to contain the other endpoint. Intra-cell pairs use the
global-id total order as the tie-break, which is device-independent.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.core import grid as grid_lib
from repro.core.grid import (GridIndex, build_grid_with_geometry,
                             device_key_dtype, host_grid_geometry,
                             offset_deltas)
from repro.core.selfjoin import (_distance_hits_jnp, _gather_batch,
                                 _neighbor_ranks_for_delta,
                                 _self_join_count_fused,
                                 _self_join_fused_stages, _sort_pairs)
from repro.core.stencil import stencil_offsets


@dataclasses.dataclass(frozen=True)
class DistJoinConfig:
    pts_per_device: int          # P: local slab size (padded)
    n_dims: int
    halo_capacity: int           # H: slots per direction per hop
    max_per_cell: int            # C: candidate window per cell
    unicomp: bool = True
    slab_axis: str = "slab"
    model_axis: Optional[str] = "model"   # None -> no offset-parallelism
    distance_impl: str = "jnp"
    # halo reach: a slab narrower than eps (equal-count partition of skewed
    # data at high slab counts) needs points from k>1 slabs away. The driver
    # auto-computes k from the partition boundaries.
    k_hops: int = 1
    # static cell-key dtype name for the padded device build: the driver
    # fixes it host-side from the global geometry (device_key_dtype with
    # padded=True -- the slab grids carry the out-of-set sentinel cell), so
    # small grids ride the int32 fast path and work under REPRO_NO_X64.
    # A string keeps the config hashable for the step cache.
    key_dtype: str = "int64"


def partition_points_host(points: np.ndarray, n_slabs: int):
    """Equal-count slab partition along dim 0 (host side).

    Returns (coords (n_slabs, P, n), gids (n_slabs, P) int32 with -1 padding).
    Equal-count boundaries keep devices load-balanced under skew -- the
    distributed analogue of the paper's non-empty-cell index (DESIGN.md S3).
    """
    pts = np.asarray(points)
    npts, n = pts.shape
    order = np.argsort(pts[:, 0], kind="stable")
    slabs = np.array_split(order, n_slabs)
    pcap = max(len(s) for s in slabs)
    coords = np.zeros((n_slabs, pcap, n), dtype=pts.dtype)
    gids = np.full((n_slabs, pcap), -1, dtype=np.int32)
    for k, s in enumerate(slabs):
        coords[k, : len(s)] = pts[s]
        gids[k, : len(s)] = s
        if len(s):
            coords[k, len(s):] = pts[s[0]]  # harmless filler (masked by gid)
    widths = [pts[s, 0].max() - pts[s, 0].min() for s in slabs if len(s) > 1]
    return coords, gids, min(widths) if widths else 0.0


def _next_pow2(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length()


def slab_extents(coords: np.ndarray, gids: np.ndarray):
    """Per-slab [min, max] extent along dim 0; empty slabs (possible when
    ``n_slabs`` approaches the point count, or under heavy skew) carry the
    neutral (+inf, -inf) pair instead of raising on an empty reduction."""
    n_slabs = coords.shape[0]
    mins = np.full(n_slabs, np.inf)
    maxs = np.full(n_slabs, -np.inf)
    for i in range(n_slabs):
        own = gids[i] >= 0
        if own.any():
            mins[i] = coords[i, own, 0].min()
            maxs[i] = coords[i, own, 0].max()
    return mins, maxs


def halo_reach(mins: np.ndarray, maxs: np.ndarray, eps: float) -> int:
    """Hop count k such that every slab's eps-neighborhood along dim 0 is
    covered by its k-hop slab neighbors (skewed data -> narrow slabs ->
    k > 1). Empty slabs sit at the END of the sorted partition
    (``np.array_split`` of the x0-sorted order only under-fills trailing
    slabs), so an empty slab's +inf min terminates the inner scan exactly
    where a too-far real slab would."""
    n_slabs = mins.shape[0]
    k_hops = 1
    for i in range(n_slabs):
        if not np.isfinite(maxs[i]):
            continue
        for h in range(1, n_slabs - i):
            if mins[i + h] <= maxs[i] + eps:
                k_hops = max(k_hops, h)
            else:
                break
    return k_hops


def _halo_exchange(x, valid, axis, n_dev, direction, hops: int = 1):
    """Shift (x, valid) ``hops`` steps along ``axis``. direction=+1 sends
    right (device i's value lands on device i+hops)."""
    idx = jax.lax.axis_index(axis)
    if direction > 0:
        perm = [(i, i + hops) for i in range(n_dev - hops)]
    else:
        perm = [(i, i - hops) for i in range(hops, n_dev)]
    rx = jax.lax.ppermute(x, axis, perm)
    rv = jax.lax.ppermute(valid, axis, perm)
    # devices with no sending neighbor receive zeros; zero validity is False.
    edge = (idx < hops) if direction > 0 else (idx >= n_dev - hops)
    rv = jnp.where(edge, False, rv)
    return rx, rv


def _pack_mask(coords, gids, mask, capacity):
    """Select masked rows, in order, into ``capacity`` slots
    (validity-flagged). Row i scatters to slot (masked rows before i), so
    the indices never decrease and the compaction needs no sort (an
    argsort of a slab takes ~20 s to compile for the TPU); unmasked rows
    carry -1 and combine by max, and slots left empty stay -1."""
    rows = jnp.arange(mask.shape[0], dtype=jnp.int32)
    slot = jnp.cumsum(mask) - mask
    take = jnp.full((capacity,), -1, jnp.int32).at[slot].max(
        jnp.where(mask, rows, -1), mode="drop", indices_are_sorted=True)
    sent = take >= 0
    take = jnp.maximum(take, 0)
    overflow = mask.sum() > capacity
    return coords[take], gids[take], sent, overflow


def _assemble_candidates(coords, gids, eps, *, cfg: "DistJoinConfig",
                         n_slab: int):
    """Device-side candidate assembly: local slab + k-hop eps-halo parcels.

    The shared first phase of BOTH distributed paths (the legacy count
    step and the fused pair join): each slab learns its h-hop neighbors'
    dim-0 boundaries, selects exactly the points those neighbors need
    (within eps of the boundary), and ships the parcels via
    ``lax.ppermute``. Returns

        (cand_coords (P + 2*H*k, n), cand_gids, cand_valid, cand_owned,
         owned (P,), halo_overflow ())

    where the first P rows are the local slab (owned) and the rest the
    received parcels (validity-flagged; overflow against the H-slot parcel
    capacity is detected, never silent). Invalid parcel slots carry the
    slab's anchor coordinate -- harmless for consumers that mask validity;
    the pair path's build step gives them out-of-volume coordinates.
    """
    slab = cfg.slab_axis
    P_loc, H = cfg.pts_per_device, cfg.halo_capacity
    coords = coords.reshape(P_loc, cfg.n_dims)
    gids = gids.reshape(P_loc)
    owned = gids >= 0
    big = jnp.asarray(jnp.finfo(coords.dtype).max / 4, coords.dtype)

    # Receiver r needs every point p with |p.x0 - slab_r| <= eps; when
    # equal-count slabs are narrower than eps (skew), that spans k > 1
    # neighbors. For each hop h: learn the h-hop neighbor's boundary,
    # select exactly what it needs, ship the parcel h hops.
    my_min0 = jnp.where(owned, coords[:, 0], big).min()
    my_max0 = jnp.where(owned, coords[:, 0], -big).max()
    parcels_c, parcels_g, parcels_v = [], [], []
    halo_overflow = jnp.array(False)
    for h in range(1, cfg.k_hops + 1):
        left_max, lm_ok = _halo_exchange(
            my_max0, jnp.array(True), slab, n_slab, +1, hops=h)
        right_min, rm_ok = _halo_exchange(
            my_min0, jnp.array(True), slab, n_slab, -1, hops=h)
        left_max = jnp.where(lm_ok, left_max, -big)
        right_min = jnp.where(rm_ok, right_min, big)
        send_left = owned & (coords[:, 0] <= left_max + eps)
        send_right = owned & (coords[:, 0] >= right_min - eps)
        cl, gl, vl, ofl = _pack_mask(coords, gids, send_left, H)
        cr, gr, vr, ofr = _pack_mask(coords, gids, send_right, H)
        # ship h hops: sending "left" means device i -> i-h, i.e. I
        # receive my h-hop RIGHT neighbor's left edge, and vice versa.
        hcl, hvl = _halo_exchange(cl, vl, slab, n_slab, -1, hops=h)
        hgl, _ = _halo_exchange(gl, vl, slab, n_slab, -1, hops=h)
        hcr, hvr = _halo_exchange(cr, vr, slab, n_slab, +1, hops=h)
        hgr, _ = _halo_exchange(gr, vr, slab, n_slab, +1, hops=h)
        parcels_c += [hcl, hcr]
        parcels_g += [hgl, hgr]
        parcels_v += [hvl, hvr]
        halo_overflow = halo_overflow | ofl | ofr
    halo_coords = jnp.concatenate(parcels_c, axis=0)
    halo_gids = jnp.concatenate(parcels_g, axis=0)
    halo_valid = jnp.concatenate(parcels_v, axis=0)

    n_halo = 2 * H * cfg.k_hops
    anchor = coords[0]
    cand_coords = jnp.concatenate(
        [coords, jnp.where(halo_valid[:, None], halo_coords, anchor)], axis=0
    )
    cand_gids = jnp.concatenate([gids, jnp.where(halo_valid, halo_gids, -1)])
    cand_valid = jnp.concatenate([owned, halo_valid])
    cand_owned = jnp.concatenate([owned, jnp.zeros(n_halo, bool)])
    return cand_coords, cand_gids, cand_valid, cand_owned, owned, \
        halo_overflow


def make_distributed_count_step(mesh: Mesh, cfg: DistJoinConfig):
    """Build the jitted distributed count step for ``mesh``.

    Returns (step, in_shardings): ``step(coords, gids, eps)`` with
    coords (S*P, n) sharded over the slab axis, gids (S*P,) likewise;
    returns (ordered_pair_count, halo_overflow, cell_overflow) replicated.
    """
    slab = cfg.slab_axis
    n_slab = mesh.shape[slab]
    axes = (slab,) if cfg.model_axis is None else (slab, cfg.model_axis)
    n_model = 1 if cfg.model_axis is None else mesh.shape[cfg.model_axis]

    offs = stencil_offsets(cfg.n_dims, cfg.unicomp)      # (n_off, n)
    n_off = offs.shape[0]
    n_off_pad = -(-n_off // n_model) * n_model
    offs_pad = np.zeros((n_off_pad, cfg.n_dims), np.int64)
    offs_pad[:n_off] = offs
    off_valid = np.arange(n_off_pad) < n_off
    off_zero = np.zeros(n_off_pad, bool)
    off_zero[:n_off] = np.all(offs == 0, axis=1)

    P_loc, H, C = cfg.pts_per_device, cfg.halo_capacity, cfg.max_per_cell

    def local_fn(coords, gids, eps, offsets, ovalid, ozero):
        cand_coords, cand_gids, cand_valid, cand_owned, owned, \
            halo_overflow = _assemble_candidates(
                coords, gids, eps, cfg=cfg, n_slab=n_slab)
        coords = cand_coords[:P_loc]

        # -- global geometry (consistent cell coords across devices) --------
        big = jnp.asarray(jnp.finfo(coords.dtype).max / 4, coords.dtype)
        lo = jnp.where(owned[:, None], coords, big).min(axis=0)
        hi = jnp.where(owned[:, None], coords, -big).max(axis=0)
        gmin = jax.lax.pmin(lo, slab) - eps
        gmax = jax.lax.pmax(hi, slab) + eps
        dims = jnp.ceil((gmax - gmin) / eps).astype(jnp.int64) + 1
        n_halo = 2 * H * cfg.k_hops

        # -- local grid over candidates, global geometry ---------------------
        # invalid padding slots get the sentinel cell: unreachable as
        # candidates and excluded from the max_per_cell bound.
        index = build_grid_with_geometry(cand_coords, eps, gmin, dims,
                                         valid=cand_valid,
                                         key_dtype=np.dtype(cfg.key_dtype))
        valid_sorted = cand_valid[index.order]
        owned_sorted = cand_owned[index.order]
        gid_sorted = cand_gids[index.order]
        cell_overflow = index.max_per_cell > C

        deltas = offset_deltas(offsets, dims)
        n_cand = P_loc + n_halo

        def body(total, xs):
            delta, o_ok, o_zero = xs
            nbr_cells = _neighbor_ranks_for_delta(index, delta)
            q, cand, cand_pos, vmask, q_pos, _ = _gather_batch(
                index, nbr_cells, jnp.asarray(0, jnp.int32), n_cand, C
            )
            hits = _distance_hits_jnp(q, cand, vmask, eps)
            hits = hits & valid_sorted[cand_pos] & owned_sorted[q_pos][:, None]
            hits = hits & o_ok
            gq = gid_sorted[q_pos][:, None]
            gc = gid_sorted[cand_pos]
            if cfg.unicomp:
                hits = hits & jnp.where(o_zero, gc > gq, gc != gq)
                inc = 2 * hits.sum()  # every unicomp hit is one unordered pair
            else:
                hits = hits & (gc != gq)
                inc = hits.sum()
            return total + inc.astype(jnp.int64), None

        total, _ = jax.lax.scan(
            body, jnp.zeros((), jnp.int64), (deltas, jnp.asarray(ovalid), jnp.asarray(ozero))
        )
        total = jax.lax.psum(total, axes)
        halo_overflow = jax.lax.pmax(halo_overflow.astype(jnp.int32), axes)
        cell_overflow = jax.lax.pmax(cell_overflow.astype(jnp.int32), axes)
        return total, halo_overflow, cell_overflow

    off_spec = P(cfg.model_axis) if cfg.model_axis else P()
    from repro.compat import shard_map

    fn = shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(P(slab), P(slab), P(), off_spec, off_spec, off_spec),
        out_specs=(P(), P(), P()),
        check_vma=False,
    )

    offsets_dev = jnp.asarray(offs_pad)
    ovalid_dev = jnp.asarray(off_valid)
    ozero_dev = jnp.asarray(off_zero)

    @jax.jit
    def step(coords, gids, eps):
        return fn(coords, gids, eps, offsets_dev, ovalid_dev, ozero_dev)

    in_shardings = (
        NamedSharding(mesh, P(slab)),
        NamedSharding(mesh, P(slab)),
    )
    return step, in_shardings


def distributed_self_join_count(
    points: np.ndarray,
    eps: float,
    mesh: Mesh,
    *,
    unicomp: bool = True,
    halo_capacity: Optional[int] = None,
    max_per_cell: Optional[int] = None,
    model_axis: Optional[str] = None,
    metric: str = "l2",
) -> int:
    """Host-facing driver: partition, shard, count. Raises on overflow.

    ``metric="cosine"`` canonicalizes at entry (unit rows, reduced L2
    threshold, DESIGN.md S12); the slab pipeline then runs unchanged.
    Jaccard is not distributed (its bitmap lanes do not ride the halo
    exchange yet)."""
    points, eps = _canonicalize_for_slabs(points, eps, metric)
    pts = np.asarray(points)
    slab_axis = mesh.axis_names[0]
    n_slabs = mesh.shape[slab_axis]
    if pts.shape[0] == 0:
        return 0
    coords, gids, min_width = partition_points_host(pts, n_slabs)
    mins, maxs = slab_extents(coords, gids)
    k_hops = halo_reach(mins, maxs, eps)
    if halo_capacity is None:
        halo_capacity = coords.shape[1]          # worst case: whole slab
    if max_per_cell is None:
        from repro.core.grid import build_grid_host

        max_per_cell = int(build_grid_host(pts, eps).max_per_cell)
    # the step derives gmin/dims on-device with the same arithmetic; the key
    # dtype must be STATIC, so fix it here from the host geometry
    _, dims_h = host_grid_geometry(pts, eps)
    cfg = DistJoinConfig(
        pts_per_device=coords.shape[1],
        n_dims=pts.shape[1],
        halo_capacity=halo_capacity,
        max_per_cell=max(8, -(-max_per_cell // 8) * 8),
        unicomp=unicomp,
        slab_axis=slab_axis,
        model_axis=model_axis,
        k_hops=k_hops,
        key_dtype=device_key_dtype(dims_h, padded=True).name,
    )
    step, in_sh = make_distributed_count_step(mesh, cfg)
    coords_flat = coords.reshape(-1, pts.shape[1])
    gids_flat = gids.reshape(-1)
    coords_dev = jax.device_put(coords_flat, in_sh[0])
    gids_dev = jax.device_put(gids_flat, in_sh[1])
    total, halo_of, cell_of = step(coords_dev, gids_dev, jnp.asarray(eps, pts.dtype))
    if int(halo_of):
        raise _halo_overflow_error(
            cfg.halo_capacity,
            halo_capacity_plan(coords, gids, mins, maxs, eps, k_hops))
    if int(cell_of):
        raise RuntimeError("max_per_cell overflow")
    return int(total)


# ---------------------------------------------------------------------------
# Fused slab join (DESIGN.md S3): pairs with global ids, built on the
# PR 1-4 fast path -- merged-range sweep, occupancy buckets, single-pass
# count -> fill -- run per slab over the (local + halo) candidate set.
# ---------------------------------------------------------------------------

# The slab grids build against the global geometry in ONE shard_map
# program over the mesh (build_slab_grids), not one single-device
# program per chip: a single-device executable is compiled for its device,
# so a build per chip would compile the build's large sort once per chip.

_HALO_STEPS: dict = {}


def make_halo_step(mesh: Mesh, cfg: DistJoinConfig):
    """Build the jitted halo-assembly step: the shard_map phase of the
    fused slab join. ``step(coords, gids, eps)`` with coords (S*P, n) /
    gids (S*P,) sharded over the slab axis returns the per-slab candidate
    blocks (coords, gids, valid, owned), each (S*(P + 2*H*k), ...) sharded
    over slab, plus the replicated halo-overflow flag.

    Steps are cached per (mesh, cfg) -- both hashable -- so repeated joins
    of same-shaped workloads (the bench loop, a recurring pipeline) reuse
    one traced executable instead of paying a fresh shard_map trace per
    call (the re-tracing failure mode ISSUE 2 banned from the serve path).
    """
    key = (mesh, cfg)
    cached = _HALO_STEPS.get(key)
    if cached is not None:
        return cached
    slab = cfg.slab_axis
    n_slab = mesh.shape[slab]

    def halo_fn(coords, gids, eps):
        cand_coords, cand_gids, cand_valid, cand_owned, _, halo_of = \
            _assemble_candidates(coords, gids, eps, cfg=cfg, n_slab=n_slab)
        halo_of = jax.lax.pmax(halo_of.astype(jnp.int32), slab)
        return cand_coords, cand_gids, cand_valid, cand_owned, halo_of

    from repro.compat import shard_map

    fn = shard_map(
        halo_fn,
        mesh=mesh,
        in_specs=(P(slab), P(slab), P()),
        out_specs=(P(slab), P(slab), P(slab), P(slab), P()),
        check_vma=False,
    )
    step = jax.jit(fn)
    in_shardings = (
        NamedSharding(mesh, P(slab)),
        NamedSharding(mesh, P(slab)),
    )
    _HALO_STEPS[key] = (step, in_shardings)
    return step, in_shardings


@partial(jax.jit, static_argnames=("mesh", "slab_axis", "key_dtype"))
def build_slab_grids(cand_c, cand_v, cand_g, cand_o, eps, gmin, dims, far,
                     *, mesh: Mesh, slab_axis: str, key_dtype):
    """The slab grid builds as one ``shard_map`` program: each device
    builds its slab's grid from its candidate block, against the global
    geometry (``build_grid_with_geometry``, as ``build_grid``). One
    program over the mesh compiles once, where a single-device build per
    chip would compile once per chip.

    Takes the halo step's blocks (sharded over the slab axis) and the
    replicated geometry, with ``far`` the coordinates that invalid slots
    take (far outside the volume). Returns the grid fields ``order``,
    ``points_sorted``, ``cell_keys``, ``cell_start``, ``cell_count``,
    ``point_cell_rank`` (each slab's block of rows), ``num_cells`` and
    ``max_per_cell`` (one row per slab), and the slab's global ids and
    owned-and-valid flags in sorted order."""
    from repro.compat import shard_map

    def build_fn(cand_c, cand_v, cand_g, cand_o, eps, gmin, dims, far):
        cc = jnp.where(cand_v[:, None], cand_c, far)
        index = build_grid_with_geometry(cc, eps, gmin, dims, cand_v,
                                         key_dtype=key_dtype)
        return (index.order, index.points_sorted, index.cell_keys,
                index.cell_start, index.cell_count, index.point_cell_rank,
                index.num_cells[None], index.max_per_cell[None],
                cand_g[index.order], (cand_o & cand_v)[index.order])

    rows = P(slab_axis)
    return shard_map(build_fn, mesh=mesh, in_specs=(rows,) * 4 + (P(),) * 4,
                     out_specs=(rows,) * 10, check_vma=False)(
        cand_c, cand_v, cand_g, cand_o, eps, gmin, dims, far)


@dataclasses.dataclass(frozen=True)
class HaloParcel:
    """One (shipping slab, hop, direction) halo parcel and its exact size."""
    slab: int          # slab shipping the parcel
    hop: int           # 1..k_hops
    direction: int     # -1 toward lower slabs, +1 toward higher
    need: int          # rows the parcel must carry

    @property
    def dest(self) -> int:
        return self.slab + self.direction * self.hop

    def describe(self) -> str:
        return (f"slab {self.slab} -> slab {self.dest} (hop {self.hop}, "
                f"direction {self.direction:+d}) ships {self.need} rows")


def halo_capacity_plan(coords: np.ndarray, gids: np.ndarray,
                       mins: np.ndarray, maxs: np.ndarray, eps: float,
                       k_hops: int) -> list:
    """Every halo parcel the exchange ships, with exact sizes.

    Slabs hold x0-sorted points, so each parcel count is one
    ``searchsorted`` against the receiving slab's boundary. This is the
    full per-parcel capacity plan behind ``exact_halo_capacity`` -- the
    overflow raises report its worst parcel so an under-capacity failure
    names the slab/hop/direction to act on."""
    n_slabs = coords.shape[0]
    plan = []
    for j in range(n_slabs):
        x0 = coords[j, gids[j] >= 0, 0]          # sorted ascending
        if not x0.size:
            continue
        for h in range(1, k_hops + 1):
            if j - h >= 0 and np.isfinite(maxs[j - h]):
                # parcel j -> j-h: points with x0 <= maxs[j-h] + eps
                need = int(np.searchsorted(x0, maxs[j - h] + eps,
                                           side="right"))
                plan.append(HaloParcel(j, h, -1, need))
            if j + h < n_slabs and np.isfinite(mins[j + h]):
                # parcel j -> j+h: points with x0 >= mins[j+h] - eps
                need = int(x0.size - np.searchsorted(
                    x0, mins[j + h] - eps, side="left"))
                plan.append(HaloParcel(j, h, +1, need))
    return plan


def worst_halo_parcel(plan) -> Optional[HaloParcel]:
    return max(plan, key=lambda p: p.need) if plan else None


def exact_halo_capacity(coords: np.ndarray, gids: np.ndarray,
                        mins: np.ndarray, maxs: np.ndarray, eps: float,
                        k_hops: int) -> int:
    """Largest parcel any (slab, hop, direction) ship needs -- the max of
    ``halo_capacity_plan``. This is the per-slab capacity plan of the fused
    path: the default ``halo_capacity`` that makes overflow impossible, and
    the bound user-supplied capacities are checked against on-device."""
    worst = worst_halo_parcel(
        halo_capacity_plan(coords, gids, mins, maxs, eps, k_hops))
    return worst.need if worst is not None else 1


def _halo_overflow_error(capacity: int, plan) -> RuntimeError:
    """Actionable under-capacity report: worst parcel + minimal fix."""
    worst = worst_halo_parcel(plan)
    if worst is None:
        return RuntimeError(f"halo capacity overflow: capacity {capacity}")
    over = [p for p in plan if p.need > capacity]
    return RuntimeError(
        f"halo capacity overflow: capacity {capacity} < required "
        f"{worst.need}; {len(over)} parcel(s) exceed it, worst: "
        f"{worst.describe()}. Pass halo_capacity >= {worst.need}, or "
        f"omit it for the exact default.")


def _canonicalize_for_slabs(points, eps, metric: str):
    """Metric entry gate for the distributed drivers: cosine reduces to L2
    on canonical geometry (exact, DESIGN.md S12) so the whole slab + halo
    pipeline runs unchanged; jaccard's packed bitmap lanes do not ride the
    halo exchange yet, so it is rejected loudly rather than mis-joined."""
    from repro.core import metric as metric_lib

    metric_lib.check_metric(metric)
    if metric == "jaccard":
        raise NotImplementedError(
            "distributed jaccard join: bitmap feature lanes do not ride "
            "the slab halo exchange yet; use the single-device fused path "
            "(core.selfjoin.self_join(metric='jaccard'))")
    if metric == "cosine":
        canon = metric_lib.canonicalize(points, eps, metric="cosine")
        return np.asarray(canon.geom), float(canon.eps_geom)
    return points, eps


def distributed_self_join(
    points: np.ndarray,
    eps: float,
    mesh: Mesh,
    *,
    unicomp: bool = True,
    merge_last_dim: Optional[bool] = None,
    bucketed: Optional[bool] = None,
    sort_result: bool = True,
    halo_capacity: Optional[int] = None,
    method: Optional[str] = None,
    emit: Optional[str] = None,
    return_pairs: bool = True,
    metric: str = "l2",
):
    """Distributed self-join returning globally-consistent PAIRS.

    The fused slab join of DESIGN.md S3: points partition into equal-count
    dim-0 slabs (one per device on the mesh's first axis), the eps-halo
    exchange runs on-device via ``shard_map`` + ``ppermute``
    (``make_halo_step``), and each slab then runs the SAME fused fast path
    as the single-device join -- merged-range sweep, occupancy buckets
    restricted to the rows the slab owns, single-pass count -> fill --
    over its (local + halo) candidate set, against the global grid
    geometry.

    Pair ownership (single emission of every pair): the fused kernel's
    UNICOMP/self masks compare GLOBAL ids riding a pad lane
    (``gid_pairs``), so the intra-cell tie-break is device-independent,
    and only rows a slab OWNS launch as queries -- each unordered pair is
    emitted by exactly the slab owning its designated query endpoint,
    whose candidate set provably contains the other endpoint (points
    within eps are within eps in dim 0, hence inside the k-hop halo).

    The result is the same (K, 2) int32 ordered-pair array as
    ``self_join(distance_impl='fused')`` -- bit-identical after the
    ``sort_result`` sort (asserted across device counts, UNICOMP and
    sweep modes in tests/test_distributed.py and the CI bench smoke).
    ``return_pairs=False`` runs the count-only fused sweep (no hit
    buffers) and returns the total ordered-pair count.

    ``halo_capacity`` defaults to the exact per-slab requirement
    (``exact_halo_capacity``), making overflow impossible; a smaller
    explicit capacity is CHECKED on-device and raises instead of silently
    dropping candidates.

    The slab grids build in one ``shard_map`` program over the mesh
    (``build_slab_grids``), so it compiles once and not once per chip.
    The pairs path then runs the slabs at once (DESIGN.md S3): each slab's
    join is a generator that yields before every host wait on a device
    value, and ``drive_stages`` advances them round-robin on the calling
    thread, so each chip has its work queued while the host waits on
    another. The count path (``return_pairs=False``) is not staged: its
    slabs run one after another. The driver is single-threaded by design:
    program spans are read on the thread of the benchmark window, and the
    step caches and sanitize checks are module globals. Spans: one
    ``selfjoin.join`` (``slabs``) around the call, ``selfjoin.partition``,
    ``selfjoin.halo`` (``halo_rows`` shipped), the slabs' own
    ``selfjoin.*`` spans, and ``selfjoin.sort`` for the global sort.
    """
    from repro.kernels.fused_join import NP_PAD, resolve_merge_last_dim

    # cosine canonicalizes at entry (unit rows + reduced L2 threshold,
    # DESIGN.md S12); jaccard is rejected -- its bitmap lanes do not ride
    # the halo exchange
    points, eps = _canonicalize_for_slabs(points, eps, metric)
    pts = np.asarray(points)
    npts, n = pts.shape
    if n >= NP_PAD:
        raise ValueError(
            f"distributed pairs need a free global-id pad lane: n_dims={n} "
            f">= NP_PAD={NP_PAD}")
    if npts >= 1 << 24:
        # the gid lane is compared as float; TPU kernels run f32, where
        # ids >= 2^24 collapse and the gid masks silently mis-pair
        raise ValueError(
            f"distributed pairs carry global ids in a float pad lane, "
            f"exact only below 2^24: npts={npts}")
    empty = np.empty((0, 2), np.int32)
    if npts == 0:
        return empty if return_pairs else 0
    # the merged sweep additionally rides the last-dim cell coordinate:
    # two free lanes or fall back to the per-cell stencil
    merged = resolve_merge_last_dim(n, merge_last_dim, extra_lanes=1)
    slab_axis = mesh.axis_names[0]
    n_slabs = mesh.shape[slab_axis]
    with span("selfjoin.join") as join_span:
        join_span.set_metadata(slabs=n_slabs)
        with span("selfjoin.partition"):
            coords, gids, _ = partition_points_host(pts, n_slabs)
            mins, maxs = slab_extents(coords, gids)
            k_hops = halo_reach(mins, maxs, eps)
            plan = halo_capacity_plan(coords, gids, mins, maxs, eps, k_hops)
            worst = worst_halo_parcel(plan)
            h_need = worst.need if worst is not None else 1
        # default capacity rounds up to a power of two (capped at the slab
        # size): the halo step is cached per (mesh, cfg), and the exact
        # requirement is data-dependent -- same-shaped workloads with fresh
        # data would otherwise miss the cache and re-trace every call (and
        # leak one executable per distinct capacity)
        h_default = min(_next_pow2(h_need), coords.shape[1])
        cfg = DistJoinConfig(
            pts_per_device=coords.shape[1],
            n_dims=n,
            halo_capacity=(h_default if halo_capacity is None
                           else int(halo_capacity)),
            max_per_cell=0,              # per-slab grids: no global C bound
            unicomp=unicomp,
            slab_axis=slab_axis,
            model_axis=None,
            k_hops=k_hops,
        )
        with span("selfjoin.halo") as halo_span:
            halo_span.set_metadata(halo_rows=sum(p.need for p in plan))
            slabs = _exchange_halos(mesh, cfg, coords, gids, pts, eps, plan)
        parts = drive_stages(
            [_slab_stages(index, ids, owned, return_pairs=return_pairs,
                          unicomp=unicomp, method=method, emit=emit,
                          bucketed=bucketed, merged=merged)
             for index, ids, owned in slabs],
            [index.points_sorted.device for index, _, _ in slabs])
        if not return_pairs:
            return sum(parts)
        out = np.concatenate(parts, axis=0) if parts else empty
        if sort_result:
            with span("selfjoin.sort"):
                out = _sort_pairs(out)
        return out


def _exchange_halos(mesh: Mesh, cfg: DistJoinConfig, coords, gids, pts,
                    eps, plan):
    """The ``shard_map`` halo step, the slab grid builds it feeds (one
    program over the mesh, ``build_slab_grids``) and the halo
    overflow check; the candidates stay on the devices. Returns, per
    slab, (its grid, its global ids and its owned rows in sorted order),
    each on the slab's device: the slab axis is the mesh's first, so row
    k of the device grid holds slab k."""
    n = pts.shape[1]
    n_slabs = coords.shape[0]
    step, in_sh = make_halo_step(mesh, cfg)
    coords_dev = jax.device_put(coords.reshape(-1, n), in_sh[0])
    gids_dev = jax.device_put(gids.reshape(-1), in_sh[1])
    cand_c, cand_g, cand_v, cand_o, halo_of = step(
        coords_dev, gids_dev, jnp.asarray(eps, pts.dtype))
    # global geometry, EXACTLY as build_grid_host derives it (the one shared
    # numpy copy): cell coords (and the UNICOMP cell-pair ownership) agree
    # across slabs AND with the single-device join
    gmin, dims = host_grid_geometry(pts, eps)
    gmax = pts.max(axis=0) + eps
    # padded slab builds carry the out-of-set sentinel cell -> static key
    # dtype via device_key_dtype (int32 fast path on small grids)
    slab_kd = device_key_dtype(dims, padded=True)
    # invalid candidate slots: coordinates far outside the volume, so a
    # window that reaches the sentinel cell (a top-corner stencil probe can
    # alias its key) evaluates no spurious hits
    far = gmax + 4.0 * max(float(eps), 1.0)
    eps_np = np.asarray(eps, pts.dtype)
    built = build_slab_grids(cand_c, cand_v, cand_g, cand_o, eps_np, gmin,
                             dims, far, mesh=mesh, slab_axis=cfg.slab_axis,
                             key_dtype=np.dtype(slab_kd))
    if int(halo_of):
        raise _halo_overflow_error(cfg.halo_capacity, plan)
    devices = np.asarray(mesh.devices).reshape(n_slabs, -1)[:, 0]
    blocks = [{s.device: s.data for s in x.addressable_shards}
              for x in built]
    slabs = []
    for dev in devices:
        (order, points_sorted, cell_keys, cell_start, cell_count, rank,
         ncells, max_per_cell, ids, owned) = (b[dev] for b in blocks)
        index = GridIndex(
            grid_min=jax.device_put(gmin, dev),
            eps=jax.device_put(eps_np, dev),
            dims=jax.device_put(dims, dev),
            order=order, points_sorted=points_sorted, cell_keys=cell_keys,
            cell_start=cell_start, cell_count=cell_count,
            point_cell_rank=rank, num_cells=ncells.reshape(()),
            max_per_cell=max_per_cell.reshape(()))
        slabs.append((index, ids, owned))
    return slabs


def _slab_stages(index: GridIndex, ids, owned, *, return_pairs: bool,
                 unicomp: bool, merged: bool, method, emit, bucketed):
    """One slab's fused join of the rows it owns, as stages for
    ``drive_stages`` (run with the slab's device as the default device);
    returns its (global-id) pairs, or its ordered-pair count. Only the
    pairs path is staged: the count sweep runs to its end in one turn."""
    with span("selfjoin.sync"):
        ids, row_ok = np.asarray(ids), np.asarray(owned)
    if not row_ok.any():
        return np.empty((0, 2), np.int32) if return_pairs else 0
    if not return_pairs:
        return _self_join_count_fused(
            index, unicomp=unicomp, method=method, bucketed=bucketed,
            merged=merged, row_ok=row_ok, ids=ids,
            gid_pairs=True).total_pairs
    return (yield from _self_join_fused_stages(
        index, unicomp=unicomp, sort_result=False, method=method, emit=emit,
        bucketed=bucketed, merged=merged, row_ok=row_ok, ids=ids,
        gid_pairs=True))


def drive_stages(stages: list, devices: list) -> list:
    """Run the slabs' stage generators (each yields before a host wait on
    a device value) to their ends, round-robin on the calling thread:
    each turn advances one stage to its next wait, so every slab has its
    device work queued before the host blocks on any one of them.
    ``devices[k]`` is the default device while stage k runs. Returns each
    stage's return value, in order."""
    out = [None] * len(stages)
    live = list(range(len(stages)))
    while live:
        for k in list(live):
            with jax.default_device(devices[k]):
                try:
                    next(stages[k])
                except StopIteration as stop:
                    out[k] = stop.value
                    live.remove(k)
    return out
