"""Batch slab self-join traffic: whole distributed joins, back to back,
over one seeded set.

One join is what a user of the four-chip deployment runs:
``distributed_self_join(points, eps, make_slab_mesh(slabs))`` with its
defaults -- the dim-0 partition, the eps-halo exchange, one grid build
and fused join per slab on its own chip, and the global sort -- until the
sorted pairs are on the host. Every join of the window starts from the
same points, so each one does the same work.

``uniform_box`` cuts the first side of the box, and the slab join cuts
along the first dimension too. So the driver reverses the order of the
coordinates before joining (and checks on the same reversed points): the
uncut 100-unit side becomes dimension 0, and each of four slabs of the
500,000-point cut is 25 x 25 with a halo of eps a side, the same share of
halo rows as the full 2M deployment's. Relabelling axes changes no
distance and no pair.

Traffic keys: ``driver`` ("join_slabs"). The configuration's ``join_cut``
divides its point count, and ``slabs`` is the number of slabs (and of
chips).

Checks, over every distinct answer the window produced: ``missing``,
``extra`` and ``duplicate`` pairs against the f64 reference over all rows
(``oracle.py``), each with the limit 0. The reference is taken in blocks
of queries along dimension 0 (``reference_keys``), each against the
points within eps plus the band of it, so that the oracle's strip scan
runs across the short side of each block.
"""
from __future__ import annotations

import hashlib
import time

import numpy as np

from jax.profiler import TraceAnnotation as span

import oracle


class Cell:
    def __init__(self, cfg: dict, traffic: dict, rngs: dict, env):
        gen = env.generator(cfg["generator"])
        pts = gen.points(cfg, int(cfg["join_cut"]), rngs["data"])
        self.points = np.ascontiguousarray(pts[:, ::-1])
        self.eps = float(cfg["eps"])
        self.slabs = int(cfg["slabs"])
        self.mesh = None
        self.answers: dict = {}        # digest -> pairs
        self.n_joins = 0
        self.join_s: list = []

    def _join(self):
        from repro.core.distributed import distributed_self_join

        t0 = time.perf_counter()
        with span("bench.self_join"):
            pairs = distributed_self_join(self.points, self.eps, self.mesh)
        return pairs, time.perf_counter() - t0

    def setup(self) -> None:
        """One whole join compiles (or loads) every program the window
        runs: the same points give the same shapes."""
        from repro.launch.mesh import make_slab_mesh

        self.mesh = make_slab_mesh(self.slabs)
        self._join()

    def prepare(self, seconds: float) -> None:
        """Nothing to draw: every join of the window uses the points."""

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with span("bench.join"):
                pairs, tj = self._join()
            self.n_joins += 1
            self.join_s.append(tj)
            digest = hashlib.blake2b(pairs.tobytes(), digest_size=16)
            digest.update(str(pairs.shape).encode())
            self.answers.setdefault(digest.hexdigest(), pairs)
        wall = time.perf_counter() - t0
        return {"join_s": wall / self.n_joins}

    def stats(self) -> dict:
        pairs = max((p.shape[0] for p in self.answers.values()), default=0)
        return {"kind": "join", "joins": self.n_joins,
                "queries_per_join": int(self.points.shape[0]),
                "dims": int(self.points.shape[1]),
                "pairs_per_join": int(pairs),
                "pairs_unique_per_join": int(pairs) // 2,
                "slabs": self.slabs, "attempted": self.n_joins,
                "failed": 0}

    def release(self) -> None:
        import gc

        gc.collect()

    def use_control(self, control) -> None:
        """Put the control's answer in place of the program's."""
        n = self.points.shape[0]
        pairs = control.bf16_pairs(self.points, self.points, self.eps,
                                   exclude=np.arange(n))
        self.answers = {"control": pairs}

    def check(self, rng: np.random.Generator) -> dict:
        pts = self.points
        n = pts.shape[0]
        band = oracle.f32_band(pts, self.eps)
        sure, maybe = reference_keys(pts, self.eps, band)
        worst = {"missing": 0, "extra": 0, "duplicate": 0}
        band_pairs = 0
        for pairs in self.answers.values():
            got = pairs[:, 0].astype(np.int64) * n + pairs[:, 1]
            res = oracle.compare_keys(got, sure, maybe)
            for k in worst:
                worst[k] = max(worst[k], res[k])
            band_pairs = max(band_pairs, res["band"])
        return {"checks": worst,
                "notes": {"distinct_answers": len(self.answers),
                          "band_pairs": band_pairs,
                          "reference_pairs": int(sure.size),
                          "band": band}}


def reference_keys(pts: np.ndarray, eps: float, band: float):
    """``oracle.reference_keys`` of the self-join of ``pts`` (no self
    pairs), block by block along dimension 0: the queries with x0 in
    [a, b) against the points with x0 in [a - r, b + r), r = eps + band,
    which hold every neighbour they can have. Keys are ``row * n + id``
    over all of ``pts``, sorted."""
    n = pts.shape[0]
    x = pts[:, 0]
    reach = eps + band
    lo, hi = float(x.min()), float(x.max())
    blocks = int(min(max((hi - lo) // (8.0 * reach), 1), 64))
    edges = np.linspace(lo, hi, blocks + 1)
    edges[-1] = np.inf
    sure, maybe = [], []
    for a, b in zip(edges[:-1], edges[1:]):
        q = np.flatnonzero((x >= a) & (x < b))
        p = np.flatnonzero((x >= a - reach) & (x < b + reach))
        if not q.size:
            continue
        s, m = oracle.reference_keys(pts[p], pts[q], eps, band=band,
                                     exclude=np.searchsorted(p, q))
        for keys, out in ((s, sure), (m, maybe)):
            out.append(q[keys // p.size] * n + p[keys % p.size])
    return (np.sort(np.concatenate(sure)), np.sort(np.concatenate(maybe)))
