"""Radius-query serving traffic: an open loop into a BatchingJoinService.

The service indexes the configuration's whole point set. Requests arrive
on a Poisson-like schedule at ``rate_rps`` (``openloop.py``; every seed gets
the same gaps and the same request sizes, in its own order) for the
window's length; each request is ``size`` queries, sizes drawn in equal
shares from ``sizes``, at the build eps. Latency counts from the scheduled
arrival to the request's ticket completing.

Traffic keys: ``driver`` ("serve"), ``rate_rps``, ``sizes``,
``max_batch``, ``max_wait_ms``, ``return_pairs``, ``placement``
("uniform": uniform over the points' bounding box), ``warm_draws`` and
``warm_strata`` (set-up batches per coalesced width, see ``Cell.setup``),
``check_queries`` (how many queries of the window, whole requests drawn
from the seed, are compared with the reference).

Checks: ``missing``, ``extra``, ``duplicate`` pairs over the sampled
requests, ``count_mismatch`` (queries whose count differs from their
pairs) and ``unanswered`` (requests that never completed), each with the
limit 0.
"""
from __future__ import annotations

import warnings

import numpy as np

import openloop
import oracle


def draw_queries(traffic: dict, n: int, rng: np.random.Generator,
                 box: np.ndarray) -> np.ndarray:
    """``n`` queries placed as the mix says; ``box`` is the points'
    (2, dims) bounding box."""
    placement = traffic["placement"]
    if placement == "uniform":
        return rng.uniform(box[0], box[1], size=(n, box.shape[1]))
    raise ValueError(f"unknown placement {placement!r}")


def _programs() -> dict:
    """The program's executable-cache sizes and compile events by name:
    what grows between two readings was compiled or loaded in between."""
    from repro.core.query_join import executable_cache_stats, metric_free

    st = executable_cache_stats()
    ev = metric_free(st.pop("trace_events"))
    return {**{k: v for k, v in st.items() if v > 0},
            **{"traced." + k: v for k, v in ev.items()}}


def _added(before: dict, after: dict) -> dict:
    return {k: after[k] - before.get(k, 0) for k in after
            if after[k] > before.get(k, 0)}


class Cell:
    def __init__(self, cfg: dict, traffic: dict, rngs: dict, env):
        gen = env.generator(cfg["generator"])
        self.traffic = traffic
        self.points = gen.points(cfg, 1, rngs["data"])
        self.box = np.stack([self.points.min(axis=0),
                             self.points.max(axis=0)])
        self.eps = float(cfg["eps"])
        self.rngs = rngs
        self.svc = None
        self.requests: list = []
        self.sched = None
        self.run = None
        self.counters: dict = {}
        self.control = None

    def setup(self) -> None:
        """Build the service over the whole index and warm every program
        the window can reach. ``warmup`` compiles the kernel for each rung
        of the batch ladder. The pair emit compiles per capacity class,
        class row bucket and power of two of the class's pairs, which
        depend on the queries a launch holds. A launch coalesces whole
        requests, so it holds a multiple of the smallest request size up
        to ``max_batch`` rows: set-up serves batches of every such width,
        ``warm_draws`` drawn as the window draws its queries and one from
        each of ``warm_strata`` strata of a query pool ranked by neighbour
        count, so that the launches of the window, whose mix of sparse and
        dense queries varies around the mean, meet no shape that set-up
        has not. All queries come from the "warm" stream."""
        from repro.launch.serve import BatchingJoinService

        t = self.traffic
        top = int(t["max_batch"])
        self.svc = BatchingJoinService(
            self.points, self.eps, max_batch=top,
            max_wait_ms=float(t["max_wait_ms"]),
            return_pairs=bool(t["return_pairs"]))
        with warnings.catch_warnings():
            warnings.filterwarnings("ignore", message="mark_steady")
            self.svc.warmup()
        warm = self.rngs["warm"]
        pool = draw_queries(t, int(t["warm_strata"]) * top, warm, self.box)
        counts = np.concatenate([self._serve(pool[i:i + top]).counts
                                 for i in range(0, pool.shape[0], top)])
        strata = np.array_split(pool[np.argsort(counts, kind="stable")],
                                int(t["warm_strata"]))
        step = min(int(s) for s in t["sizes"])
        for rows in range(step, top + 1, step):
            for _ in range(int(t["warm_draws"])):
                self._serve(draw_queries(t, rows, warm, self.box))
            for st in strata:
                self._serve(st[warm.choice(st.shape[0], rows, replace=False)])
        self.svc.mark_steady()

    def _serve(self, queries: np.ndarray):
        tk = self.svc.submit(queries)
        self.svc.drain()
        return tk.result()

    def prepare(self, seconds: float) -> None:
        """Draw the window's requests and their schedule (set-up)."""
        t = self.traffic
        rng = self.rngs["traffic"]
        n = max(int(round(float(t["rate_rps"]) * seconds)), 1)
        sizes = np.resize(np.asarray(t["sizes"], np.int64), n)
        sizes = rng.permutation(sizes)
        self.requests = [draw_queries(t, int(s), rng, self.box)
                         for s in sizes]
        self.sched = openloop.fixed_poisson_schedule(
            n, float(t["rate_rps"]), rng)

    def window(self, seconds: float) -> dict:
        svc = self.svc
        l0, r0, c0 = svc.n_launches, svc.rows_launched, _programs()
        self.run = openloop.run_open_loop(svc, self.requests, self.sched)
        added = _added(c0, _programs())
        self.counters = {"launches": svc.n_launches - l0,
                         "rows_launched": svc.rows_launched - r0,
                         "programs_added_in_window": sum(added.values()),
                         "programs_added_by": ",".join(
                             f"{k}+{v}" for k, v in sorted(added.items()))}
        lat = self.run.latencies_ms
        done = lat[np.isfinite(lat)]
        return {"query_p50_ms": float(np.percentile(done, 50)),
                "query_p95_ms": float(np.percentile(done, 95))}

    def stats(self) -> dict:
        lat = self.run.latencies_ms
        res = [t.result() for t in self.run.tickets if t is not None
               and t.done()]
        return {"kind": "serve", "requests": len(self.requests),
                "queries": int(sum(q.shape[0] for q in self.requests)),
                "dims": int(self.points.shape[1]),
                "pairs": int(sum(r.pairs.shape[0] for r in res)),
                "attempted": len(self.requests),
                "failed": int(np.count_nonzero(~np.isfinite(lat))),
                "late_p95_ms": float(np.percentile(self.run.late_ms, 95)),
                "wall_s": self.run.wall_s, **self.run.stall,
                **self.counters}

    def release(self) -> None:
        import gc

        self.svc = None
        gc.collect()

    def use_control(self, control) -> None:
        """Answer the checked requests with the control instead."""
        self.control = control

    def _answer(self, i: int):
        """(pairs, counts) of request ``i`` as the window returned it, or
        as the control computes it."""
        if self.control is None:
            res = self.run.tickets[i].result()
            return res.pairs, res.counts
        q = self.requests[i]
        pairs = self.control.bf16_pairs(self.points, q, self.eps)
        return pairs, np.bincount(pairs[:, 0], minlength=q.shape[0])

    def check(self, rng: np.random.Generator) -> dict:
        n = self.points.shape[0]
        tickets = self.run.tickets
        unanswered = sum(1 for tk in tickets if tk is None or not tk.done())
        budget = int(self.traffic["check_queries"])
        picked, rows = [], 0
        for i in rng.permutation(len(tickets)):
            if rows >= budget:
                break
            if tickets[i] is not None and tickets[i].done():
                picked.append(int(i))
                rows += self.requests[i].shape[0]
        queries, got, mismatch, row0 = [], [], 0, 0
        for i in sorted(picked):
            pairs, counts = self._answer(i)
            q = self.requests[i]
            pairs = np.asarray(pairs, np.int64).reshape(-1, 2)
            inside = (pairs[:, 0] >= 0) & (pairs[:, 0] < q.shape[0])
            per_row = np.bincount(pairs[inside, 0], minlength=q.shape[0])
            counts = np.asarray(counts).reshape(-1)
            mismatch += int(np.count_nonzero(~inside))
            mismatch += (int(np.count_nonzero(per_row != counts))
                         if counts.shape == per_row.shape else q.shape[0])
            got.append((pairs[:, 0] + row0) * n + pairs[:, 1])
            queries.append(q)
            row0 += q.shape[0]
        qcat = np.concatenate(queries)
        band = max(oracle.f32_band(self.points, self.eps),
                   oracle.f32_band(qcat, self.eps))
        sure, maybe = oracle.reference_keys(self.points, qcat, self.eps,
                                            band=band)
        res = oracle.compare_keys(np.concatenate(got), sure, maybe)
        return {"checks": {"missing": res["missing"], "extra": res["extra"],
                           "duplicate": res["duplicate"],
                           "count_mismatch": mismatch,
                           "unanswered": unanswered},
                "notes": {"checked_requests": len(picked),
                          "checked_queries": row0, "band_pairs": res["band"],
                          "reference_pairs": int(sure.size), "band": band}}
