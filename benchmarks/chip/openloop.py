"""Open-loop load generator, copied from ``repro/launch/loadgen.py`` (the
``submit`` branch of ``run_open_loop``) so that a change to the program
cannot change how it is measured.

Requests arrive on a schedule fixed before the window, independent of the
service; each latency counts from the SCHEDULED arrival to the moment the
request's ticket completed, so queueing under load is charged to the
service. What differs from the original:

* in place of the original's i.i.d. exponential gaps,
  ``fixed_poisson_schedule`` gives every seed the same set of gaps (the
  quantiles of the exponential at the offered rate) in a seeded order, so
  the work of a window does not change with the seed, only its order;
* the loop records how late each submit ran behind its schedule;
* each phase of the loop runs under a host span (``bench.submit``,
  ``bench.pump``, ``bench.wait``, ``bench.drain``) for the trace;
* the loop keeps its longest single phase, with the thread's CPU time in
  it, and the time the process spent in garbage collection, so that a
  stall in an untraced run still says whether the host computed or
  waited.
"""
from __future__ import annotations

import gc
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from jax.profiler import TraceAnnotation as _span


def fixed_poisson_schedule(n_requests: int, rate_rps: float,
                           rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets whose gaps are the ``n_requests`` mid-quantiles of
    the exponential at ``rate_rps``, in an order drawn from ``rng``: a
    Poisson-like stream with the same total and the same gap set on every
    seed."""
    u = (np.arange(n_requests) + 0.5) / n_requests
    gaps = -np.log1p(-u) / rate_rps
    return np.cumsum(rng.permutation(gaps))


@dataclass
class OpenLoopResult:
    latencies_ms: np.ndarray          # per request, NaN if never completed
    late_ms: np.ndarray               # submit time minus scheduled arrival
    wall_s: float                     # schedule start to the last completion
    tickets: list = field(repr=False)
    stall: dict = field(default_factory=dict)


class _Phases:
    """Runs each phase of the loop under its span; keeps the longest one
    by wall time with the thread's CPU time in it (a long phase with
    little CPU time waited: on the device, or for a core), and the
    garbage collector's pauses."""

    def __init__(self):
        self.wall, self.name, self.cpu = 0.0, "", 0.0
        self.gc_pauses: list = []
        self._gc_t0 = 0.0

    @contextmanager
    def phase(self, name: str):
        w0, c0 = time.perf_counter(), time.thread_time()
        with _span(name):
            yield
        w = time.perf_counter() - w0
        if w > self.wall:
            self.wall, self.name = w, name
            self.cpu = time.thread_time() - c0

    def _gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        else:
            self.gc_pauses.append(time.perf_counter() - self._gc_t0)

    def summary(self) -> dict:
        return {"stall_ms": 1000.0 * self.wall, "stall_phase": self.name,
                "stall_cpu_ms": 1000.0 * self.cpu,
                "gc_ms": 1000.0 * sum(self.gc_pauses),
                "gc_max_ms": 1000.0 * max(self.gc_pauses, default=0.0)}


def run_open_loop(svc, requests: list, sched: np.ndarray) -> OpenLoopResult:
    """Offer ``requests`` (query arrays) to a batching service at the
    scheduled offsets ``sched``. Arrivals enter ``svc.submit`` the moment
    they are due and ``svc.pump`` advances the launch/resolve pipeline
    between arrivals; ``svc.drain`` finishes what is left at the end."""
    n = len(requests)
    tickets = [None] * n
    late = np.zeros(n)
    ph = _Phases()
    gc.callbacks.append(ph._gc)
    t0 = time.perf_counter()
    i = 0
    while i < n:
        now = time.perf_counter() - t0
        if sched[i] <= now:
            with ph.phase("bench.submit"):
                while i < n and sched[i] <= now:
                    tickets[i] = svc.submit(requests[i])
                    late[i] = now - sched[i]
                    i += 1
        with ph.phase("bench.pump"):
            svc.pump()
        if i < n:
            now = time.perf_counter() - t0
            if sched[i] > now:
                with ph.phase("bench.wait"):
                    time.sleep(min(sched[i] - now, 5e-4))
    with ph.phase("bench.drain"):
        svc.drain()
    wall = time.perf_counter() - t0
    gc.callbacks.remove(ph._gc)
    lat = np.array([np.nan if t is None or t.t_done is None
                    else 1000.0 * ((t.t_done - t0) - s)
                    for t, s in zip(tickets, sched)])
    return OpenLoopResult(latencies_ms=lat, late_ms=1000.0 * late,
                          wall_s=wall, tickets=tickets, stall=ph.summary())
