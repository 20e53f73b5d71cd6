"""Batch self-join traffic: whole joins, back to back, over one seeded set.

One join is what a user of the paper's operator runs: ``build_grid(points,
eps)``, ended by ``block_until_ready``, then ``self_join(points, eps,
index=..., distance_impl="fused")`` with its defaults, until the sorted
pairs are on the host. Every join of the window starts from the same
points and builds afresh, so each one does the same work.

Traffic keys: ``driver`` ("join"). The configuration's ``join_cut`` is
the factor by which its point count is divided.

Checks, over every distinct answer the window produced: ``missing``,
``extra`` and ``duplicate`` pairs against the f64 reference over all rows
(``oracle.py``), each with the limit 0.
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
        self.points = gen.points(cfg, int(cfg["join_cut"]), rngs["data"])
        self.eps = float(cfg["eps"])
        self.answers: dict = {}        # digest -> pairs
        self.n_joins = 0
        self.build_s: list = []
        self.join_s: list = []

    def _join(self):
        import jax

        from repro.core.grid import build_grid
        from repro.core.selfjoin import self_join

        t0 = time.perf_counter()
        with span("bench.build"):
            index = jax.block_until_ready(build_grid(self.points, self.eps))
        t1 = time.perf_counter()
        with span("bench.self_join"):
            pairs = self_join(self.points, self.eps, index=index,
                              distance_impl="fused")
        t2 = time.perf_counter()
        return pairs, t1 - t0, t2 - t0

    def setup(self) -> None:
        """One whole join compiles (or loads) every program the window
        runs: the same points give the same shapes."""
        self._join()

    def prepare(self, seconds: float) -> None:
        """Nothing to draw: every join of the window uses the points."""

    def window(self, seconds: float) -> dict:
        t0 = time.perf_counter()
        while time.perf_counter() - t0 < seconds:
            with span("bench.join"):
                pairs, tb, tj = self._join()
            self.n_joins += 1
            self.build_s.append(tb)
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
                "build_s": self.build_s, "attempted": self.n_joins,
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
        sure, maybe = oracle.reference_keys(
            pts, pts, self.eps, band=band, exclude=np.arange(n))
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
