"""Reduction of a profiler trace to the numbers the metric readers use.

The JAX profiler writes ``<dir>/plugins/profile/<run>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it into planes of lines of events, each
with a name, a start and a duration in nanoseconds. This module turns that
into plain tuples first (``load``), so that the reduction below
(``summarize``) runs the same on a recorded trace and on a synthetic event
list in the tests.

Keys it relies on (checked by hand on a TPU v5 lite trace, see PERF.md):

* device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
  one event per executed HLO operation (a Pallas kernel is one custom-call
  event), their ``XLA Modules`` line one event per executed program, named
  after the jitted function (``jit_<name>(<id>)``);
* the host plane ``/host:CPU`` holds the benchmark's own spans
  (``jax.profiler.TraceAnnotation``), all named ``bench.*``; the span
  ``bench.window`` bounds the measured window.
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def load(trace_dir: str) -> list:
    """[(plane, [(line, [(name, start_ns, dur_ns), ...]), ...]), ...] of
    the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    pd = ProfileData.from_file(max(paths, key=os.path.getmtime))
    return [(pl.name, [(ln.name, [(ev.name, float(ev.start_ns),
                                   float(ev.duration_ns))
                                  for ev in ln.events])
                       for ln in pl.lines])
            for pl in pd.planes]


def union_length(intervals) -> float:
    """Total length covered by a set of [start, end) intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps(intervals, lo: float, hi: float) -> list:
    """The uncovered stretches of [lo, hi) as (start, end) pairs."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        if s > cur:
            out.append((cur, min(s, hi)))
        cur = max(cur, e)
        if cur >= hi:
            break
    if cur < hi:
        out.append((cur, hi))
    return [(s, e) for s, e in out if e > s]


def _innermost(stretches: list, events: list):
    """(stretches, names): for each stretch (sorted by start), the name of
    the innermost event covering its midpoint, by one sweep over events
    of one thread sorted by (start, -duration), which nest."""
    names, stack, j = [], [], 0
    for gs, ge in stretches:
        mid = 0.5 * (gs + ge)
        while j < len(events) and events[j][0] <= mid:
            s, d, n = events[j]
            while stack and stack[-1][0] <= s:
                stack.pop()
            stack.append((s + d, n))
            j += 1
        while stack and stack[-1][0] <= mid:
            stack.pop()
        names.append(stack[-1][1] if stack else "host: outside any span")
    return stretches, names


def _clip(s: float, d: float, lo: float, hi: float):
    a, b = max(s, lo), min(s + d, hi)
    return (a, b) if b > a else None


@dataclass
class Summary:
    window_s: float
    busy_s: float                      # mean over device planes
    n_devices: int
    ops: dict = field(default_factory=dict)      # name -> [seconds, count]
    modules: dict = field(default_factory=dict)  # name -> [seconds, count]
    spans: dict = field(default_factory=dict)    # name -> [seconds, ...]
    idle_by_host: dict = field(default_factory=dict)  # host event -> s
    device_events: tuple = (0, 0)      # (in the window, in the trace)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds(self, table: dict, *needles: str) -> float:
        """Summed seconds of the events whose name holds any needle."""
        return sum(v[0] for k, v in table.items()
                   if any(n in k for n in needles))

    def seconds_prefix(self, table: dict, prefix: str) -> float:
        """Summed seconds of the events whose name starts with ``prefix``."""
        return sum(v[0] for k, v in table.items() if k.startswith(prefix))

    def top(self, table: dict, k: int = 10) -> list:
        return [[n, v[0]] for n, v in sorted(table.items(),
                                             key=lambda kv: -kv[1][0])[:k]]


def summarize(planes: list) -> Summary:
    """Device busy time, per-name device totals, host spans and what the
    host was doing in each device gap, all within the ``bench.window``
    span."""
    host_lines = [lines for name, lines in planes if name == HOST_PLANE]
    spans: dict = {}
    span_line = None
    for lines in host_lines:
        for lname, events in lines:
            for name, s, d in events:
                if name.startswith(SPAN_PREFIX):
                    spans.setdefault(name, []).append((s, d))
                    span_line = events
    if WINDOW_SPAN not in spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    ws, wd = spans[WINDOW_SPAN][0]
    lo, hi = ws, ws + wd
    devices = [lines for name, lines in planes
               if name.startswith(DEVICE_PREFIX)]
    ops: dict = {}
    modules: dict = {}
    busy_total = 0.0
    all_busy = []
    n_in = n_all = 0
    for lines in devices:
        by_line = dict(lines)
        busy_events = by_line.get(OPS_LINE) or by_line.get(MODULES_LINE, [])
        busy = [iv for iv in (_clip(s, d, lo, hi)
                              for _, s, d in busy_events) if iv]
        n_in, n_all = n_in + len(busy), n_all + len(busy_events)
        busy_total += union_length(busy)
        all_busy.extend(busy)
        for line, table in ((OPS_LINE, ops), (MODULES_LINE, modules)):
            for name, s, d in by_line.get(line, []):
                iv = _clip(s, d, lo, hi)
                if iv:
                    acc = table.setdefault(name, [0.0, 0])
                    acc[0] += (iv[1] - iv[0]) * 1e-9
                    acc[1] += 1
    idle: dict = {}
    host_events = sorted(((s, d, n) for n, s, d in (span_line or [])
                          if n != WINDOW_SPAN), key=lambda e: (e[0], -e[1]))
    for (gs, ge), name in zip(*_innermost(gaps(all_busy, lo, hi),
                                          host_events)):
        idle[name] = idle.get(name, 0.0) + (ge - gs) * 1e-9
    return Summary(
        window_s=wd * 1e-9,
        busy_s=(busy_total / len(devices) * 1e-9) if devices else 0.0,
        n_devices=len(devices), ops=ops, modules=modules,
        spans={k: [d * 1e-9 for _, d in v] for k, v in spans.items()},
        idle_by_host=idle, device_events=(n_in, n_all))
