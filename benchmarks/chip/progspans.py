"""The program's own host spans in a traced run, reduced per name.

The program opens ``jax.profiler.TraceAnnotation`` spans where its work
happens: ``selfjoin.*`` in the join driver (``core/selfjoin.py``),
``query.*`` in the prepared external join (``core/query_join.py``) and
``serve.*`` in the batching service (``launch/serve.py``). Their counters
ride as numeric arguments of the span they belong to, which the trace
keeps as the event's stats. They sit on the host plane of the same
profiler trace as the device ops, on the thread that runs the window, so
they share its clock and are clipped to the window like everything else.

``window(ctx, reader_file)`` loads the newest ``.xplane.pb`` under the
benchmark's ``runs/trace/`` (the harness has just written this run's
trace there), once per file, and reduces it with ``reduce``:

* per span name: count, total and self time (duration minus the child
  program spans), and the sum of each numeric argument, over the events
  clipped to the ``bench.window`` span;
* each device idle gap goes to the innermost PROGRAM span covering its
  midpoint, skipping JAX's own events (``np.asarray``, ``PjitFunction``)
  in between, and is keyed ``<parent program span>/<span>``; a gap under
  no program span keeps the innermost ``bench.*`` span's name, or ``-``.

It returns None where the newest trace is not this run's (its window
lasts other than ``ctx["summary"].window_s``), and where the program
opens no span of its own there.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

import tracesum

PROGRAM_PREFIXES = ("selfjoin.", "query.", "serve.")

_CACHE: dict = {}


@dataclass
class Agg:
    count: int = 0
    total_s: float = 0.0
    self_s: float = 0.0
    args: dict = field(default_factory=dict)


@dataclass
class Reduction:
    window_s: float
    spans: dict                        # name -> Agg
    idle: dict                         # key -> seconds of device idle

    def count(self, name: str) -> int:
        a = self.spans.get(name)
        return a.count if a else 0

    def total(self, name: str) -> float:
        a = self.spans.get(name)
        return a.total_s if a else 0.0

    def self_time(self, name: str) -> float:
        a = self.spans.get(name)
        return a.self_s if a else 0.0

    def arg(self, name: str, key: str) -> float:
        a = self.spans.get(name)
        return a.args.get(key, 0) if a else 0

    def notes(self) -> dict:
        """The run's notes read off the spans: launches and syncs per
        join, parts per launch, the share of forced resolves, and the
        device idle per program span (per join, or per launch)."""
        out: dict = {}
        joins, launches = self.count("selfjoin.join"), \
            self.count("serve.launch")
        if joins:
            out["launches_per_join"] = self.arg("selfjoin.plan",
                                                "launches") / joins
            out["syncs_per_join"] = self.count("selfjoin.sync") / joins
        if launches:
            out["parts_per_launch"] = self.arg("serve.launch",
                                               "parts") / launches
        if self.count("serve.resolve"):
            out["forced_resolve_share"] = (self.arg("serve.resolve", "forced")
                                           / self.count("serve.resolve"))
        per = joins or launches
        if per:
            out["program_idle_ms"] = ",".join(
                f"{k}={1000.0 * v / per:.6g}" for k, v in
                sorted(self.idle.items(), key=lambda kv: -kv[1]))
        return out


def _is_program(name: str) -> bool:
    return name.startswith(PROGRAM_PREFIXES)


def reduce(host_events: list, busy: list, lo: float, hi: float
           ) -> Reduction:
    """``host_events``: [(name, start_ns, dur_ns, {arg: value})] of one
    thread (they nest); ``busy``: device-busy [start, end) intervals in
    ns. Both are clipped to the window [lo, hi) here."""
    evs = sorted(((s, d, n, a) for n, s, d, a in host_events
                  if tracesum._clip(s, d, lo, hi)),
                 key=lambda e: (e[0], -e[1]))
    spans: dict = {}
    # self time: one sweep with a stack of the open events; a program
    # span's clipped length is charged against its nearest open program
    # ancestor, across any JAX or benchmark events in between
    stack: list = []                   # (end, name, is_program)
    for s, d, n, a in evs:
        while stack and stack[-1][0] <= s:
            stack.pop()
        if _is_program(n):
            a0, a1 = tracesum._clip(s, d, lo, hi)
            agg = spans.setdefault(n, Agg())
            agg.count += 1
            agg.total_s += (a1 - a0) * 1e-9
            agg.self_s += (a1 - a0) * 1e-9
            for k, v in a.items():
                if isinstance(v, (int, float)):
                    agg.args[k] = agg.args.get(k, 0) + v
            parent = next((p for p in reversed(stack) if p[2]), None)
            if parent is not None:
                spans[parent[1]].self_s -= (a1 - a0) * 1e-9
        stack.append((s + d, n, _is_program(n)))
    idle: dict = {}
    stretches = tracesum.gaps(busy, lo, hi)
    stack, j = [], 0
    for gs, ge in stretches:
        mid = 0.5 * (gs + ge)
        while j < len(evs) and evs[j][0] <= mid:
            s, d, n, _ = evs[j]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((s + d, n))
            j += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        prog = [n for _, n in stack if _is_program(n)]
        if prog:
            key = prog[-1] if len(prog) == 1 else f"{prog[-2]}/{prog[-1]}"
        else:
            bench = [n for _, n in stack if n.startswith(tracesum.SPAN_PREFIX)
                     and n != tracesum.WINDOW_SPAN]
            key = bench[-1] if bench else "-"
        idle[key] = idle.get(key, 0.0) + (ge - gs) * 1e-9
    return Reduction(window_s=(hi - lo) * 1e-9, spans=spans, idle=idle)


def load(path: str):
    """(host_events, busy, lo, hi) of one ``.xplane.pb``: the events with
    their numeric stats on the host thread that holds ``bench.window``,
    the device-busy intervals (as ``tracesum.summarize`` takes them), and
    the window's bounds in ns. None where the trace has no window."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, window, busy = None, None, []
    for pl in pd.planes:
        if pl.name == tracesum.HOST_PLANE and window is None:
            for ln in pl.lines:
                evs = [(ev.name, float(ev.start_ns), float(ev.duration_ns),
                        dict(ev.stats) if _is_program(ev.name) else {})
                       for ev in ln.events]
                win = [e for e in evs if e[0] == tracesum.WINDOW_SPAN]
                if win:
                    window, host = win[0], evs
                    break
        elif pl.name.startswith(tracesum.DEVICE_PREFIX):
            lines = {ln.name: [(float(ev.start_ns), float(ev.duration_ns))
                               for ev in ln.events] for ln in pl.lines
                     if ln.name in (tracesum.OPS_LINE, tracesum.MODULES_LINE)}
            busy.extend((s, s + d) for s, d in (
                lines.get(tracesum.OPS_LINE)
                or lines.get(tracesum.MODULES_LINE, [])))
    if window is None:
        return None
    lo, hi = window[1], window[1] + window[2]
    events = [e for e in host if e[0] != tracesum.WINDOW_SPAN]
    busy = [iv for iv in (tracesum._clip(s, e - s, lo, hi) for s, e in busy)
            if iv]
    return events, busy, lo, hi


def newest_trace(bench_dir: str):
    paths = glob.glob(os.path.join(bench_dir, "runs", "trace", "**",
                                   "*.xplane.pb"), recursive=True)
    return max(paths, key=os.path.getmtime) if paths else None


def window(ctx: dict, reader_file: str):
    """The reduction of this run's trace, for a reader at
    ``<bench>/metrics/<name>.py``; None where there is none. The first
    call also puts ``Reduction.notes()`` into ``ctx["stats"]``."""
    summary = ctx.get("summary")
    if summary is None:
        return None
    bench_dir = os.path.dirname(os.path.dirname(os.path.abspath(
        reader_file)))
    path = newest_trace(bench_dir)
    if path is None:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        loaded = load(path)
        _CACHE[key] = None if loaded is None else reduce(*loaded)
    red = _CACHE[key]
    if red is None or abs(red.window_s - summary.window_s) > 1e-6:
        return None
    if not red.spans:
        return None
    for k, v in red.notes().items():
        ctx["stats"].setdefault(k, v)
    return red
