"""Serving driver: a persistent external-query epsilon-join service over a
grid-indexed set, or LM token decoding -- selected by --arch.

Epsilon-join service (the paper's operator in the index-once/query-many
regime, DESIGN.md S5):
    python -m repro.launch.serve --arch selfjoin --points 20000 --dims 4 \
        --eps 1.0 --requests 8 --request-batch 256
``JoinService`` builds the grid index ONCE (paper SIV) and prepares the
fused external-query join path (core/query_join.py): offset tables and the
padded points copy are computed at startup, request batches are padded to
static bucket shapes, and every per-request computation dispatches into
module-level jitted functions whose XLA executables are cached per bucket --
so steady-state requests pay pure execution, never trace/compile (the bug
the original ``range_query``-per-request loop had). The driver warms the
request bucket, then reports p50/p99 latency and requests/sec over the
steady-state window, and FAILS (exit code) if any steady-state request
grew a compilation cache -- the no-retrace regression gate `make verify`
runs.

LM decode service:
    python -m repro.launch.serve --arch smoke-lm --reduced --tokens 32
Prefills a prompt batch and decodes tokens autoregressively with the KV
cache, reporting per-token latency.
"""
from __future__ import annotations

import argparse
import threading
import time
import warnings
from collections import deque
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation as span

from repro.configs import get_config
from repro.launch.mesh import make_smoke_mesh
from repro.models.lm import LMModel


class _JoinServiceBase:
    """Serving-side bookkeeping shared by the single-index, slab-sharded
    and batching services: steady-state latency percentiles that reflect
    execution rather than trace time, and a compilation-cache watchdog
    (``assert_no_retrace``) so a regression back to per-request tracing
    can never pass silently.

    Latency samples taken before ``mark_steady`` land in
    ``warmup_latencies_ms`` and are EXCLUDED from ``percentiles`` /
    ``requests_per_sec``; every ``warmup()`` implementation auto-marks
    steady (with a warning) so a caller that forgets ``mark_steady`` can
    no longer report warmup-tainted stats.
    """

    def __init__(self, return_pairs: bool = False):
        self.return_pairs = return_pairs
        self.latencies_ms: list[float] = []        # steady-state window
        self.warmup_latencies_ms: list[float] = []  # pre-steady samples
        self.total_neighbors = 0
        self.requests = 0
        self._steady = False
        self._warm_buckets: set[int] = set()
        self._cache_mark: Optional[dict] = None

    def _answer(self, queries: np.ndarray, eps: Optional[float]):
        raise NotImplementedError

    def mark_steady(self) -> None:
        """Snapshot compilation caches; later requests must not grow them,
        and later latency samples enter the steady-state window."""
        from repro.core.query_join import executable_cache_stats

        self._steady = True
        self._cache_mark = executable_cache_stats()

    def _auto_steady(self) -> None:
        """Called by ``warmup()``: enter steady state if the caller has
        not done so explicitly (warn -- forgetting ``mark_steady`` used to
        silently mix compile latencies into the report)."""
        if not self._steady:
            warnings.warn(
                "mark_steady() was never called; auto-marking steady "
                "after warmup() so reported stats exclude the warmup "
                "window", stacklevel=3)
            self.mark_steady()

    def query(self, queries: np.ndarray, *, eps: Optional[float] = None):
        """Answer one request; records the latency sample in the steady
        or warmup window depending on ``mark_steady``."""
        t0 = time.perf_counter()
        res = self._answer(queries, eps)
        dt_ms = 1000 * (time.perf_counter() - t0)
        (self.latencies_ms if self._steady
         else self.warmup_latencies_ms).append(dt_ms)
        self.requests += 1
        self.total_neighbors += res.total
        return res

    def _steady_window(self) -> list[float]:
        if self.latencies_ms:
            return self.latencies_ms
        if self.warmup_latencies_ms:
            warnings.warn(
                "no steady-state samples recorded (mark_steady/warmup "
                "never ran before queries); falling back to the warmup "
                "window -- stats include compile time", stacklevel=3)
            return self.warmup_latencies_ms
        return []

    def percentiles(self) -> tuple[float, float]:
        lat = np.asarray(self._steady_window())
        return (float(np.percentile(lat, 50)), float(np.percentile(lat, 99)))

    def requests_per_sec(self) -> float:
        win = self._steady_window()
        total_s = sum(win) / 1000
        return len(win) / total_s if total_s > 0 else float("inf")

    def assert_no_retrace(self) -> None:
        """Raise if any request since ``mark_steady`` traced or compiled.

        The device-emit scatter is exempt: its result-buffer capacity is a
        static shape bucketed to powers of two (with a floor), so a
        pair-serving service legitimately compiles O(log max_result) emit
        executables on demand as larger results first appear -- warmup
        cannot know result sizes in advance. The prepare-path
        builders/planners (``grid_build``/``grid_caps``/``grid_extspan``)
        are exempt too: they compile during index build and background
        ``reindex``, never per steady-state request.
        The request-path functions (window descriptors, fused sweep) must
        stay frozen; those are what the per-request re-tracing bug
        burned."""
        from repro.core.query_join import executable_cache_stats

        def freeze(stats: dict) -> dict:
            out = {k: v for k, v in stats.items()
                   if k not in ("emit_pairs_device", "trace_events",
                                "grid_build", "grid_caps", "grid_extspan")}
            out["trace_events"] = {
                k: v for k, v in stats["trace_events"].items()
                if k != "emit_pairs_device"}
            return out

        now = executable_cache_stats()
        if (self._cache_mark is not None
                and freeze(now) != freeze(self._cache_mark)):
            raise RuntimeError(
                "serve path recompiled during steady state: "
                f"{freeze(self._cache_mark)} -> {freeze(now)}")


class JoinService(_JoinServiceBase):
    """Persistent epsilon-join service: index once, answer many requests.

    Wraps ``core.query_join.prepare`` with the serving-side bookkeeping of
    ``_JoinServiceBase`` plus bucket warmup (compile off the request
    path).

    The serving state is ONE snapshot tuple ``(index, prepared)``:
    ``reindex`` rebuilds both in a background thread (device build,
    DESIGN.md S10) and swaps them with a single reference assignment, so
    every request observes either the old snapshot or the new one, never a
    mix -- the first slice of the ROADMAP mutable-index item.
    """

    def __init__(self, points: np.ndarray, eps: float, *,
                 index=None, return_pairs: bool = False,
                 merge_last_dim: Optional[bool] = None,
                 metric: str = "l2", vocab: Optional[int] = None):
        from repro.core import metric as metric_lib
        from repro.core.grid import build_grid
        from repro.core.query_join import prepare

        super().__init__(return_pairs)
        metric_lib.check_metric(metric)
        self.metric = metric
        self.vocab = vocab
        self.eps = float(eps)          # METRIC-units threshold throughout
        self.merge_last_dim = merge_last_dim
        t0 = time.perf_counter()
        if metric != "l2":
            if index is not None:
                raise ValueError(
                    "JoinService: non-L2 metrics build their own index "
                    "over the canonical geometry; pass raw points")
            canon = metric_lib.canonicalize(points, eps, metric=metric,
                                            vocab=vocab)
            index = build_grid(np.asarray(canon.geom),
                               float(canon.eps_geom))
            prepared = prepare(index, merge_last_dim=merge_last_dim,
                               canon=canon)
        else:
            if index is None:
                index = build_grid(np.asarray(points), float(eps))
            prepared = prepare(index, merge_last_dim=merge_last_dim)
        self._snapshot = (index, prepared)
        self.build_s = time.perf_counter() - t0
        self.swaps = 0
        self.reindex_timings: Optional[dict] = None
        self._reindex_thread: Optional[threading.Thread] = None
        self._reindex_error: Optional[BaseException] = None

    @property
    def index(self):
        return self._snapshot[0]

    @property
    def prepared(self):
        return self._snapshot[1]

    def warmup(self, batch_size: int) -> int:
        """Compile the executables serving ``batch_size``-query requests
        (off the request path): the request bucket AND, on a skewed index,
        every (capacity class, bucket size) launch a steady-state request
        mix can need (``PreparedJoin.warm``). Returns the request bucket's
        padded row count."""
        from repro.core.query_join import bucket_rows

        qp = bucket_rows(batch_size)
        if qp not in self._warm_buckets:
            self.prepared.warm(batch_size, return_pairs=self.return_pairs)
            self._warm_buckets.add(qp)
        self._auto_steady()
        return qp

    def reindex(self, points: np.ndarray, *, wait: bool = True) -> None:
        """Rebuild the index over ``points`` and atomically swap the
        serving snapshot (DESIGN.md S10).

        Device build + planning + bucket warm-up all run in a background
        thread; requests keep being answered from the OLD snapshot until
        the single ``_snapshot`` assignment at the end. Executables are
        module-level and keyed by static shapes (bucket rows, capacity
        class, point count), so a new snapshot whose classes match the old
        one's reuses every warmed executable and the no-retrace watchdog
        stays green across the swap; a snapshot with genuinely new classes
        compiles here -- off the request path -- and the driver must
        ``mark_steady`` again. ``wait=False`` returns immediately; call
        ``join_reindex`` (or the next ``reindex``) to surface errors.
        """
        if self._reindex_thread is not None and self._reindex_thread.is_alive():
            raise RuntimeError("reindex already in progress")
        self.join_reindex()          # surface a previous failure, if any
        # non-L2 input may be ragged (token sets); canonicalize in-thread
        pts = np.asarray(points) if self.metric == "l2" else points

        def work():
            try:
                from repro.core import metric as metric_lib
                from repro.core.grid import build_grid
                from repro.core.query_join import prepare

                t0 = time.perf_counter()
                canon = None
                if self.metric != "l2":
                    canon = metric_lib.canonicalize(
                        pts, self.eps, metric=self.metric, vocab=self.vocab)
                    geom, eps_geom = np.asarray(canon.geom), canon.eps_geom
                else:
                    geom, eps_geom = pts, self.eps
                index = jax.block_until_ready(
                    build_grid(geom, float(eps_geom)))
                t1 = time.perf_counter()
                prepared = prepare(index,
                                   merge_last_dim=self.merge_last_dim,
                                   canon=canon)
                t2 = time.perf_counter()
                for qp in sorted(self._warm_buckets):
                    prepared.warm(qp, return_pairs=self.return_pairs)
                t3 = time.perf_counter()
                self._snapshot = (index, prepared)   # THE atomic swap
                self.swaps += 1
                self.reindex_timings = {
                    "build_s": t1 - t0, "plan_s": t2 - t1,
                    "warm_s": t3 - t2,
                    "swap_s": time.perf_counter() - t3}
            except BaseException as e:   # noqa: BLE001 -- surfaced in caller
                self._reindex_error = e

        th = threading.Thread(target=work, name="join-reindex", daemon=True)
        self._reindex_thread = th
        th.start()
        if wait:
            self.join_reindex()

    def join_reindex(self) -> None:
        """Block until any in-flight reindex has swapped; re-raise its
        error in the caller's thread if it failed."""
        th = self._reindex_thread
        if th is not None:
            th.join()
        if self._reindex_error is not None:
            err, self._reindex_error = self._reindex_error, None
            raise RuntimeError("background reindex failed") from err

    def _answer(self, queries: np.ndarray, eps: Optional[float] = None):
        return self.prepared.join(queries, eps=eps,
                                  return_pairs=self.return_pairs)


class ShardedJoinService(_JoinServiceBase):
    """Slab-sharded epsilon-join service (DESIGN.md S3 serving mode).

    The indexed set partitions into equal-count dim-0 slabs (the same
    partitioner as the distributed self-join); each slab holds its OWN
    grid index and ``PreparedJoin`` -- index once per slab. A request fans
    out to every slab (an external query near a slab boundary has
    neighbors on both sides), per-slab counts sum, and pair point-ids
    remap through the slab's global-id table, so the answer is identical
    to the single-index service (asserted in tests/test_query_join.py).
    No ownership rule is needed: every indexed point lives in exactly one
    slab, so no pair can be found twice.

    Warmup compiles every slab's executables off the request path; the
    no-retrace gate is inherited unchanged (the executable caches are
    module-level, shared across slabs -- a steady-state request may not
    grow them no matter which slab it lands on).
    """

    def __init__(self, points: np.ndarray, eps: float, n_slabs: int, *,
                 return_pairs: bool = False,
                 merge_last_dim: Optional[bool] = None,
                 metric: str = "l2", vocab: Optional[int] = None):
        from repro.core import metric as metric_lib
        from repro.core.distributed import partition_points_host
        from repro.core.grid import build_grid_host
        from repro.core.query_join import prepare

        super().__init__(return_pairs)
        metric_lib.check_metric(metric)
        self.metric = metric
        self.eps = float(eps)          # METRIC-units threshold
        # canonicalize ONCE over the full set (slab grids partition the
        # canonical geometry; queries canonicalize against this form)
        self._query_canon = None
        if metric != "l2":
            self._query_canon = metric_lib.canonicalize(
                points, eps, metric=metric, vocab=vocab)
            pts = np.asarray(self._query_canon.geom)
        else:
            pts = np.asarray(points)
        t0 = time.perf_counter()
        coords, gids, _ = partition_points_host(pts, n_slabs)
        self.n_slabs = n_slabs
        self.slab_gids: list[np.ndarray] = []
        self.prepared: list = []
        self.indexes: list = []
        # slab k lives on local device k (round-robin when there are fewer
        # devices than slabs): its index, warmup and joins all run there
        self.devices: list = []
        devs = jax.local_devices()
        for k in range(n_slabs):
            own = gids[k] >= 0
            if not own.any():
                continue                      # empty slab: nothing to index
            sg = gids[k][own]
            self.slab_gids.append(sg)
            canon_k = None
            if self._query_canon is not None:
                qc = self._query_canon
                canon_k = metric_lib.Canonical(
                    qc.metric, coords[k][own],
                    None if qc.feats is None else qc.feats[sg],
                    qc.n_feat, qc.eps, qc.eps_geom, qc.vocab)
            dev = devs[k % len(devs)]
            with jax.default_device(dev):
                idx = build_grid_host(coords[k][own],
                                      float(self._query_canon.eps_geom
                                            if canon_k else eps))
                self.prepared.append(prepare(
                    idx, merge_last_dim=merge_last_dim, canon=canon_k))
            self.indexes.append(idx)
            self.devices.append(dev)
        self.build_s = time.perf_counter() - t0

    def warmup(self, batch_size: int) -> int:
        from repro.core.query_join import bucket_rows

        qp = bucket_rows(batch_size)
        if qp not in self._warm_buckets:
            for pj, dev in zip(self.prepared, self.devices):
                with jax.default_device(dev):
                    pj.warm(batch_size, return_pairs=self.return_pairs)
            self._warm_buckets.add(qp)
        self._auto_steady()
        return qp

    def _join_async(self, k: int, queries, **kw):
        with jax.default_device(self.devices[k]):
            return self.prepared[k].join_async(queries, **kw)

    def _answer(self, queries: np.ndarray, eps: Optional[float] = None):
        # canonicalize raw metric queries ONCE (the pre-canonicalized
        # tuple path in join_async), not once per slab
        if self._query_canon is not None:
            from repro.core import metric as metric_lib
            queries = metric_lib.canonicalize_queries(self._query_canon,
                                                      queries)
        # dispatch EVERY slab before resolving ANY: the k-th slab's fused
        # sweep executes on device while the (k+1)-th is still being set
        # up on the host (join_async seam, DESIGN.md S8)
        pendings = [self._join_async(k, queries, eps=eps,
                                     return_pairs=self.return_pairs,
                                     sort_pairs=False)
                    for k in range(len(self.prepared))]
        return _merge_slab_results([p.result() for p in pendings],
                                   self.slab_gids, self.return_pairs)


def _merge_slab_results(results, slab_gids, return_pairs: bool):
    """Scatter-gather merge of per-slab join results into the single-index
    answer: counts sum, pair point-ids remap through each slab's global-id
    table, merged pairs lexsort to the canonical order."""
    from repro.core.query_join import QueryJoinResult

    counts = None
    chunks = []
    bucket = 0
    n_off = 0
    emit = None
    for res, sg in zip(results, slab_gids):
        counts = res.counts if counts is None else counts + res.counts
        bucket, n_off, emit = res.bucket_rows, res.n_offsets, res.emit
        if return_pairs and res.pairs.shape[0]:
            p = res.pairs.copy()
            p[:, 1] = sg[p[:, 1]]             # slab point id -> global id
            chunks.append(p)
    pairs = None
    if return_pairs:
        pairs = (np.concatenate(chunks, axis=0) if chunks
                 else np.empty((0, 2), np.int32))
        pairs = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    return QueryJoinResult(
        counts=counts, pairs=pairs, n_offsets=n_off,
        bucket_rows=bucket, emit=emit,
        candidates_checked=None)


class BatchTicket:
    """Handle for one submitted request: completes when every part of the
    request (a request wider than ``max_batch`` is split) has been sliced
    out of its coalesced launch."""

    def __init__(self, n_parts: int, n_queries: int):
        self.n_parts = n_parts
        self.n_queries = n_queries
        self.t_submit = time.perf_counter()
        self.t_done: Optional[float] = None
        self._parts: dict = {}

    def done(self) -> bool:
        return len(self._parts) == self.n_parts

    def _add_part(self, part: int, res) -> None:
        self._parts[part] = res
        if self.done() and self.t_done is None:
            self.t_done = time.perf_counter()

    def result(self):
        """The request's QueryJoinResult, identical to serving it alone
        (parts concatenate back in submission order; pair query-rows of
        part k rebase by the rows of parts < k)."""
        from repro.core.query_join import QueryJoinResult

        if not self.done():
            raise RuntimeError(
                f"ticket incomplete: {len(self._parts)}/{self.n_parts} "
                f"parts resolved (call service.drain() first)")
        parts = [self._parts[i] for i in range(self.n_parts)]
        if len(parts) == 1:
            return parts[0]
        counts = np.concatenate([p.counts for p in parts])
        pairs = None
        if parts[0].pairs is not None:
            chunks = []
            row0 = 0
            for p in parts:
                q = p.pairs.copy()
                q[:, 0] += row0
                chunks.append(q)
                row0 += p.counts.shape[0]
            pairs = np.concatenate(chunks, axis=0)
        return QueryJoinResult(
            counts=counts, pairs=pairs, n_offsets=parts[0].n_offsets,
            bucket_rows=parts[0].bucket_rows, emit=parts[0].emit,
            candidates_checked=None)

    def latency_ms(self) -> float:
        if self.t_done is None:
            raise RuntimeError("ticket not complete")
        return 1000 * (self.t_done - self.t_submit)


class _Sub:
    """One admission-queue entry: a request part awaiting coalescing."""

    __slots__ = ("queries", "eps_key", "ticket", "part", "t_arrival")

    def __init__(self, queries, eps_key, ticket, part):
        self.queries = queries
        self.eps_key = eps_key
        self.ticket = ticket
        self.part = part
        self.t_arrival = time.perf_counter()


class _Inflight:
    """A launched coalesced batch whose device results are outstanding."""

    __slots__ = ("pendings", "subs", "bounds", "launch")

    def __init__(self, pendings, subs, bounds, launch):
        self.pendings = pendings      # one PendingJoin per slab
        self.subs = subs
        self.bounds = bounds
        self.launch = launch          # id shared by its launch/resolve spans


class BatchingJoinService(_JoinServiceBase):
    """Continuous-batching epsilon-join service (DESIGN.md S8).

    Requests from independent callers enter an admission queue
    (``submit``) and are coalesced -- FIFO, same epsilon -- into single
    fused launches of up to ``max_batch`` queries, so the per-launch
    dispatch overhead that dominates small requests amortizes across
    callers and the kernel runs at the occupancy the paper's batching
    scheme targets. Coalesced batch sizes land on the same pow2 bucket
    ladder as direct requests (``bucket_rows``), and ``warmup`` compiles
    EVERY rung up to ``max_batch``, so ``PreparedJoin.warm``'s no-retrace
    contract holds over arbitrary coalescing patterns. A flushed batch
    dispatches through ``join_async`` and resolves lazily: up to two
    batches stay in flight, so host-side assembly (queue scan, request
    concatenation, descriptor setup) of batch k+1 overlaps device
    execution of batch k (double buffering). Per-request results slice
    back out of the coalesced ``QueryJoinResult`` by query-row range
    (``slice_result``) -- bitwise identical to serving the request alone
    (tests/test_serve_batching.py property-tests arbitrary partitions).

    Host spans (DESIGN.md S8, "Observability"): ``serve.submit`` per
    request, ``serve.launch`` per coalesced batch (``launch`` id,
    ``rows``, ``parts``, and ``wait_us_sum``, the parts' summed
    admission-queue wait), and ``serve.resolve`` per batch answered (the
    same ``launch`` id; ``forced`` 1 when it resolves without its device
    values known to be down). The counters ride the spans, so a profiler
    trace reads them over exactly the window it covers; with no profiler
    running they are not computed.

    A request wider than ``max_batch`` splits into parts that ride
    separate launches and concatenate on completion; an empty request
    completes immediately. With ``n_slabs > 1`` each coalesced batch
    scatter-gathers across the slab-sharded indexes exactly like
    ``ShardedJoinService``.
    """

    def __init__(self, points: np.ndarray, eps: float, *,
                 index=None, n_slabs: int = 1, return_pairs: bool = False,
                 merge_last_dim: Optional[bool] = None,
                 max_batch: int = 1024, max_wait_ms: float = 2.0,
                 metric: str = "l2", vocab: Optional[int] = None):
        from repro.core import metric as metric_lib
        from repro.core.grid import build_grid_host
        from repro.core.query_join import prepare

        super().__init__(return_pairs)
        metric_lib.check_metric(metric)
        if max_batch < 1:
            raise ValueError(f"max_batch must be >= 1, got {max_batch}")
        self.max_batch = int(max_batch)
        self.max_wait_ms = float(max_wait_ms)
        self.metric = metric
        self.eps = float(eps)          # METRIC-units threshold
        # full-set canonical form: admission-time query canonicalization
        # (slab grids partition the canonical geometry)
        self._query_canon = None
        if metric != "l2":
            if index is not None:
                raise ValueError(
                    "BatchingJoinService: non-L2 metrics build their own "
                    "index over the canonical geometry; pass raw points")
            self._query_canon = metric_lib.canonicalize(
                points, eps, metric=metric, vocab=vocab)
        t0 = time.perf_counter()
        if n_slabs > 1:
            from repro.core.distributed import partition_points_host

            qc = self._query_canon
            pts = np.asarray(points if qc is None else qc.geom)
            eps_geom = float(eps if qc is None else qc.eps_geom)
            coords, gids, _ = partition_points_host(pts, n_slabs)
            self.slab_gids = []
            self.indexes = []
            self.prepared = []
            for k in range(n_slabs):
                own = gids[k] >= 0
                if not own.any():
                    continue
                sg = gids[k][own]
                self.slab_gids.append(sg)
                canon_k = None
                if qc is not None:
                    canon_k = metric_lib.Canonical(
                        qc.metric, coords[k][own],
                        None if qc.feats is None else qc.feats[sg],
                        qc.n_feat, qc.eps, qc.eps_geom, qc.vocab)
                idx = build_grid_host(coords[k][own], eps_geom)
                self.indexes.append(idx)
                self.prepared.append(
                    prepare(idx, merge_last_dim=merge_last_dim,
                            canon=canon_k))
        else:
            qc = self._query_canon
            if index is not None:
                idx = index
            elif qc is not None:
                idx = build_grid_host(np.asarray(qc.geom),
                                      float(qc.eps_geom))
            else:
                idx = build_grid_host(np.asarray(points), float(eps))
            self.slab_gids = None
            self.indexes = [idx]
            self.prepared = [prepare(idx, merge_last_dim=merge_last_dim,
                                     canon=qc)]
        self.n_slabs = len(self.prepared)
        self.build_s = time.perf_counter() - t0
        self._queue: deque[_Sub] = deque()
        self._queued_rows = 0
        self._inflight: deque[_Inflight] = deque()
        self.n_launches = 0
        self.n_coalesced = 0
        self.rows_launched = 0

    # -- admission ---------------------------------------------------------

    def submit(self, queries: np.ndarray, *,
               eps: Optional[float] = None) -> BatchTicket:
        """Enqueue one request; returns a ticket that completes once every
        part has been served from a coalesced launch (``pump``/``drain``
        advance the pipeline). Does not block."""
        with span("serve.submit"):
            return self._submit(queries, eps)

    def _submit(self, queries, eps: Optional[float]) -> BatchTicket:
        from repro.core.query_join import QueryJoinResult

        pj0 = self.prepared[0]
        if self.metric != "l2":
            # canonicalize at ADMISSION (once per request, not per launch/
            # slab): geometry + feature lanes coalesce as one 2-D array
            # and split back at launch into join_async's tuple path
            from repro.core import metric as metric_lib

            qg, qf = metric_lib.canonicalize_queries(self._query_canon,
                                                     queries)
            q = np.asarray(qg, pj0.dtype)
            if qf is not None:
                q = np.concatenate([q, np.asarray(qf, pj0.dtype)], axis=1)
        else:
            q = np.asarray(queries, pj0.dtype)
            if q.ndim != 2 or q.shape[1] != pj0.n_dims:
                raise ValueError(f"queries must be (Q, {pj0.n_dims}), "
                                 f"got {q.shape}")
        eps_key = float(self.eps if eps is None else eps)
        n = q.shape[0]
        if n == 0:
            t = BatchTicket(1, 0)
            t._add_part(0, QueryJoinResult(
                counts=np.zeros(0, np.int32),
                pairs=(np.empty((0, 2), np.int32) if self.return_pairs
                       else None),
                n_offsets=pj0.n_offsets, bucket_rows=0, emit=None,
                candidates_checked=None))
            return t
        parts = [q[i:i + self.max_batch]
                 for i in range(0, n, self.max_batch)]
        ticket = BatchTicket(len(parts), n)
        for i, p in enumerate(parts):
            self._queue.append(_Sub(p, eps_key, ticket, i))
            self._queued_rows += p.shape[0]
        return ticket

    # -- pipeline ----------------------------------------------------------

    def _flush_due(self, now: float) -> bool:
        if not self._queue:
            return False
        if self._queued_rows >= self.max_batch:
            return True
        return 1000 * (now - self._queue[0].t_arrival) >= self.max_wait_ms

    def _form_group(self) -> list[_Sub]:
        """Pop the next coalesced batch off the queue: FIFO from the head,
        same epsilon (the threshold is one traced scalar per launch), up
        to ``max_batch`` rows. Skipped entries (different eps, or too wide
        to fit the remaining budget) keep their queue position."""
        head_eps = self._queue[0].eps_key
        group: list[_Sub] = []
        rows = 0
        keep: list[_Sub] = []
        while self._queue:
            sub = self._queue.popleft()
            if (sub.eps_key == head_eps
                    and rows + sub.queries.shape[0] <= self.max_batch):
                group.append(sub)
                rows += sub.queries.shape[0]
            else:
                keep.append(sub)
        self._queue.extendleft(reversed(keep))
        self._queued_rows -= rows
        return group

    def _launch(self, group: list[_Sub]) -> None:
        from repro.core.query_join import coalesce_requests

        with span("serve.launch") as sp:
            if span.is_enabled():
                now = time.perf_counter()
                sp.set_metadata(
                    launch=self.n_launches, parts=len(group),
                    rows=sum(s.queries.shape[0] for s in group),
                    wait_us_sum=int(1e6 * sum(now - s.t_arrival
                                              for s in group)))
            qcat, bounds = coalesce_requests([s.queries for s in group])
            eps = group[0].eps_key
            single = self.slab_gids is None
            pj0 = self.prepared[0]
            if self.metric != "l2":
                # split the admission-time concatenation back into the
                # (geometry, features) pair join_async consumes directly
                qsend = (qcat[:, :pj0.n_dims],
                         qcat[:, pj0.n_dims:] if pj0.n_feat else None)
            else:
                qsend = qcat
            pendings = [pj.join_async(qsend, eps=eps,
                                      return_pairs=self.return_pairs,
                                      sort_pairs=single)
                        for pj in self.prepared]
            self._inflight.append(_Inflight(pendings, group, bounds,
                                            self.n_launches))
            self.n_launches += 1
            self.n_coalesced += len(group)
            self.rows_launched += qcat.shape[0]

    def _resolve_oldest(self, forced: bool) -> None:
        from repro.core.query_join import slice_result

        infl = self._inflight.popleft()
        with span("serve.resolve", launch=infl.launch, forced=int(forced)):
            if self.slab_gids is None:
                res = infl.pendings[0].result()
            else:
                res = _merge_slab_results(
                    [p.result() for p in infl.pendings],
                    self.slab_gids, self.return_pairs)
            for k, sub in enumerate(infl.subs):
                part = slice_result(res, int(infl.bounds[k]),
                                    int(infl.bounds[k + 1]))
                sub.ticket._add_part(sub.part, part)
                self.total_neighbors += part.total

    def pump(self) -> None:
        """Advance the pipeline without blocking on admission: launch
        every due batch (oldest waiter past ``max_wait_ms``, or a full
        ``max_batch`` of rows queued), then resolve inflight batches --
        eagerly while their device values are already down (free), and
        forcibly past the double-buffer depth of two, so the NEXT ``pump``
        assembles batch k+1 on the host while batch k still executes."""
        now = time.perf_counter()
        while self._flush_due(now):
            self._launch(self._form_group())
        while self._inflight:
            forced = len(self._inflight) > 2
            if not (forced or all(p.ready() for p
                                  in self._inflight[0].pendings)):
                break
            self._resolve_oldest(forced)

    def drain(self) -> None:
        """Flush and resolve everything: queued requests launch regardless
        of due time, all inflight batches resolve. Every ticket issued
        before the call is complete afterwards."""
        while self._queue:
            self._launch(self._form_group())
        while self._inflight:
            self._resolve_oldest(True)

    # -- service interface -------------------------------------------------

    @property
    def coalesce_factor(self) -> float:
        """Mean requests per fused launch (1.0 = batching is a no-op)."""
        return self.n_coalesced / self.n_launches if self.n_launches else 0.0

    def warmup(self, batch_size: Optional[int] = None) -> int:
        """Compile every executable a steady-state coalescing pattern can
        reach, off the request path: coalesced batches land on ANY pow2
        rung up to ``max_batch`` rows (not just the one bucket a fixed
        request size would hit), so the whole ladder warms -- for every
        slab. ``batch_size`` is accepted for interface parity with the
        other services but deliberately IGNORED for the ladder top: the
        coalescer is free to fill any group to ``max_batch`` rows no
        matter how small individual requests are (and wider requests
        split into ``max_batch``-row parts), so warming less than the
        full ladder would retrace in steady state. Returns the top
        rung's padded row count."""
        from repro.core.query_join import bucket_rows

        top = bucket_rows(self.max_batch)
        s = bucket_rows(1)
        while s <= top:
            if s not in self._warm_buckets:
                for pj in self.prepared:
                    pj.warm(s, return_pairs=self.return_pairs)
                self._warm_buckets.add(s)
            s *= 2
        self._auto_steady()
        return top

    def _answer(self, queries: np.ndarray, eps: Optional[float] = None):
        # synchronous convenience path: admit, drain, slice. Throughput
        # callers should submit()/pump() concurrently instead.
        ticket = self.submit(queries, eps=eps)
        self.drain()
        return ticket.result()


def _metric_workload(args, rng):
    """(points, eps, make_queries) for the service smoke, per metric.

    l2 keeps the uniform box; cosine serves random embeddings at a
    similarity floor; jaccard serves random binary token matrices at a
    Jaccard floor ((Q, V) matrix form, 2-D so the batching coalescer
    accepts it)."""
    if args.metric == "cosine":
        eps = args.eps if -1.0 <= args.eps < 1.0 else 0.9
        if eps != args.eps:
            print(f"[serve] --eps {args.eps} is not a cosine similarity; "
                  f"using {eps}")
        pts = rng.normal(size=(args.points, args.dims))
        return pts, eps, lambda n: rng.normal(size=(n, args.dims))
    if args.metric == "jaccard":
        eps = args.eps if 0.0 < args.eps <= 1.0 else 0.5
        if eps != args.eps:
            print(f"[serve] --eps {args.eps} is not a jaccard threshold; "
                  f"using {eps}")
        vocab = 64
        pts = (rng.random((args.points, vocab)) < 0.1).astype(np.float32)
        return pts, eps, lambda n: (
            rng.random((n, vocab)) < 0.1).astype(np.float32)
    pts = rng.uniform(0, 100, size=(args.points, args.dims))
    return pts, args.eps, lambda n: rng.uniform(0, 100,
                                                size=(n, args.dims))


def serve_selfjoin(args):
    rng = np.random.default_rng(args.seed)
    pts, eps, make_queries = _metric_workload(args, rng)
    if args.batching:
        svc = BatchingJoinService(
            pts, eps, n_slabs=args.slabs,
            return_pairs=args.return_pairs,
            merge_last_dim=not args.no_merge,
            max_batch=args.max_batch, max_wait_ms=args.max_wait_ms,
            metric=args.metric)
        print(f"[serve] batching service: {args.points} pts, "
              f"{svc.n_slabs} slab(s), max_batch={svc.max_batch}, "
              f"max_wait={svc.max_wait_ms}ms "
              f"(indexed in {svc.build_s:.3f}s)")
    elif args.slabs > 1:
        svc = ShardedJoinService(pts, eps, args.slabs,
                                 return_pairs=args.return_pairs,
                                 merge_last_dim=not args.no_merge,
                                 metric=args.metric)
        sweep = ("merged-range" if svc.prepared[0].merged else "per-cell")
        cells = sum(int(i.num_cells) for i in svc.indexes)
        print(f"[serve] indexed {args.points} pts across "
              f"{len(svc.prepared)} slabs in {svc.build_s:.3f}s "
              f"(|G|={cells} non-empty cells total, {sweep} sweep)")
    else:
        svc = JoinService(pts, eps, return_pairs=args.return_pairs,
                          merge_last_dim=not args.no_merge,
                          metric=args.metric)
        sweep = "merged-range" if svc.prepared.merged else "per-cell"
        print(f"[serve] indexed {args.points} pts in {svc.build_s:.3f}s "
              f"(metric={args.metric}, |G|={int(svc.index.num_cells)} "
              f"non-empty cells, C={svc.prepared.c}, "
              f"{svc.prepared.n_offsets} {sweep} stencil offsets)")
    t0 = time.perf_counter()
    qp = svc.warmup(args.request_batch)   # auto-marks steady (warns)
    print(f"[serve] warmed bucket {qp} rows in "
          f"{time.perf_counter()-t0:.3f}s (compile, off the request path)")
    if args.batching:
        # throughput path: admit everything through the queue, pump, drain
        tickets = [svc.submit(make_queries(args.request_batch))
                   for _ in range(args.requests)]
        t0 = time.perf_counter()
        svc.pump()
        svc.drain()
        wall = time.perf_counter() - t0
        svc.latencies_ms = [t.latency_ms() for t in tickets]
        svc.requests = len(tickets)
        p50, p99 = svc.percentiles()
        print(f"[serve] {args.requests} requests x {args.request_batch} "
              f"queries coalesced into {svc.n_launches} launches "
              f"(coalesce factor {svc.coalesce_factor:.1f}): "
              f"p50 {p50:.1f}ms p99 {p99:.1f}ms "
              f"{len(tickets) / wall:.1f} req/s")
    else:
        if args.reindex and not type(svc) is JoinService:
            raise SystemExit("--reindex needs the single-index service "
                             "(no --slabs/--batching)")
        for r in range(args.requests):
            if args.reindex and r == args.requests // 2:
                # mid-load re-index: background device build + plan, then
                # one atomic snapshot swap. Same point set (permuted), so
                # bucket classes match and every warmed executable is
                # reused -- the no-retrace gate below must stay green.
                svc.reindex(rng.permutation(pts), wait=True)
                t = svc.reindex_timings
                print(f"[serve] reindexed {args.points} pts mid-load: "
                      f"build {t['build_s']*1000:.1f}ms "
                      f"plan {t['plan_s']*1000:.1f}ms "
                      f"warm {t['warm_s']*1000:.1f}ms "
                      f"swap {t['swap_s']*1e6:.0f}us "
                      f"(snapshot swaps: {svc.swaps})")
            q = make_queries(args.request_batch)
            svc.query(q)
        p50, p99 = svc.percentiles()
        print(f"[serve] {args.requests} requests x {args.request_batch} "
              f"queries{' (+pairs)' if args.return_pairs else ''}: "
              f"p50 {p50:.1f}ms p99 {p99:.1f}ms "
              f"{svc.requests_per_sec():.1f} req/s "
              f"({svc.total_neighbors} neighbors found)")
    svc.assert_no_retrace()   # regression gate: steady state never compiles
    print("[serve] no-retrace check passed: steady-state requests hit "
          "cached executables only")
    return p50


def serve_lm(args):
    cfg = get_config(args.arch, reduced=args.reduced)
    model = LMModel(cfg, None)
    params, _ = model.init(jax.random.PRNGKey(args.seed))
    B, S = args.request_batch, args.prompt_len
    rng = np.random.default_rng(args.seed)
    batch = {"tokens": jnp.asarray(rng.integers(0, cfg.vocab, (B, S)),
                                   jnp.int32),
             "labels": jnp.zeros((B, S), jnp.int32)}
    caches = model.init_caches(B, S + args.tokens)
    prefill = jax.jit(model.prefill)
    decode = jax.jit(model.decode_step, donate_argnums=(2,))
    t0 = time.time()
    logits, caches = prefill(params, batch, caches)
    logits.block_until_ready()
    print(f"[serve] prefill {B}x{S} in {time.time()-t0:.3f}s")
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    lat = []
    out = [tok]
    for _ in range(args.tokens):
        t0 = time.time()
        logits, caches = decode(params, tok, caches)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        tok.block_until_ready()
        lat.append(time.time() - t0)
        out.append(tok)
    lat_ms = 1000 * np.asarray(lat[1:])  # drop compile step
    print(f"[serve] decoded {args.tokens} tokens: "
          f"p50 {np.percentile(lat_ms, 50):.1f}ms/token")
    return float(np.median(lat_ms))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="selfjoin")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    # selfjoin service
    ap.add_argument("--points", type=int, default=20000)
    ap.add_argument("--dims", type=int, default=4)
    ap.add_argument("--eps", type=float, default=2.0)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--request-batch", type=int, default=256)
    ap.add_argument("--return-pairs", action="store_true",
                    help="materialize neighbor pairs per request, not "
                         "just counts")
    ap.add_argument("--metric", default="l2",
                    choices=("l2", "cosine", "jaccard"),
                    help="similarity metric for the join service "
                         "(DESIGN.md S12); --eps is then the metric-units "
                         "threshold (minimum cosine / Jaccard similarity)")
    ap.add_argument("--no-merge", action="store_true",
                    help="serve through the per-cell 3^n stencil instead "
                         "of the merged-range 3^(n-1) sweep (parity "
                         "oracle, DESIGN.md S7)")
    ap.add_argument("--slabs", type=int, default=1,
                    help="shard the index into N dim-0 slabs and serve "
                         "requests scatter-gather across them "
                         "(ShardedJoinService, DESIGN.md S3)")
    ap.add_argument("--reindex", action="store_true",
                    help="re-index a permutation of the point set halfway "
                         "through the request loop (background device "
                         "build + atomic snapshot swap; the no-retrace "
                         "gate must stay green across it)")
    ap.add_argument("--batching", action="store_true",
                    help="serve through the continuous-batching admission "
                         "queue (BatchingJoinService, DESIGN.md S8); "
                         "composes with --slabs")
    ap.add_argument("--max-batch", type=int, default=1024,
                    help="coalesced launch budget in query rows")
    ap.add_argument("--max-wait-ms", type=float, default=2.0,
                    help="admission-queue flush deadline for the oldest "
                         "waiting request")
    # lm service
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    args = ap.parse_args(argv)
    if args.arch == "selfjoin":
        return serve_selfjoin(args)
    return serve_lm(args)


if __name__ == "__main__":
    from repro.launch import compile_cache

    compile_cache.enable()   # command-line runs only, not in-process calls
    main()
