"""The benchmark harness: one run of one cell, driven by BENCHMARK.json.

Everything that belongs to one configuration, traffic mix or per-layer
metric sits in files of its own, found by name:

  BENCHMARK.json  ``configs[].file``        the configuration (JSON), which
                                            names its point generator
  generators/<generator>.py                 ``points(cfg, cut, rng)``
  traffic/<traffic>.json                    the mix's parameters, which
                                            name its driver
  drivers/<driver>.py                       ``Cell``: setup, window, stats,
                                            release, check
  metrics/<metric>.py                       ``read(ctx)`` -> number or None

so a later change adds files and entries and edits none of these.
"""
from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if HERE not in sys.path:
    sys.path.insert(0, HERE)


class HarnessError(RuntimeError):
    """A run that cannot produce a result (no chip, a bad spec)."""


def _load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise HarnessError(f"missing file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _read_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise HarnessError(f"missing file {path}")
    with open(path) as f:
        return json.load(f)


@dataclass
class Spec:
    """One workload of BENCHMARK.json resolved to its files."""

    root: str
    dir: str                           # the benchmark's own directory
    bench: dict
    workload: dict
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list

    def generator(self, name: str):
        return _load_module(os.path.join(self.dir, "generators",
                                         f"{name}.py"),
                            f"chipbench_gen_{name}")

    def driver(self):
        name = self.traffic["driver"]
        return _load_module(os.path.join(self.dir, "drivers", f"{name}.py"),
                            f"chipbench_driver_{name}")

    def metric_reader(self, name: str):
        return _load_module(os.path.join(self.dir, "metrics", f"{name}.py"),
                            f"chipbench_metric_{name}")


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def resolve(root: str, workload: str) -> Spec:
    """The cell ``workload`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and metrics."""
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r}; known: "
                           f"{sorted(cells)}")
    cell = cells[workload]
    configs = {c["name"]: c for c in bench["configs"]}
    if cell["config"] not in configs:
        raise HarnessError(f"workload {workload!r} names an unknown "
                           f"config {cell['config']!r}")
    config = _read_json(os.path.join(root, configs[cell["config"]]["file"]))
    bench_dir = os.path.join(root, bench["paths"][0])
    traffic = _read_json(os.path.join(bench_dir, "traffic",
                                      f"{cell['traffic']}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer = [m for m in bench["per_layer"] if _applies(m, workload)]
    return Spec(root, bench_dir, bench, cell, config, traffic, e2e,
                per_layer)


def _devices(chips: int, require_tpu: bool):
    import jax

    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise HarnessError(
            f"no TPU: JAX reports {devs[0].platform!r} devices; the "
            f"benchmark measures only on the chip")
    if len(devs) < chips:
        raise HarnessError(f"the cell needs {chips} chips, JAX reports "
                           f"{len(devs)}")
    return devs


def enable_compile_cache(root: str) -> str:
    """JAX's persistent compilation cache at the fixed ``<root>/.jax_cache``
    unless ``JAX_COMPILATION_CACHE_DIR`` names one; every program is kept,
    however fast it compiled, so that later runs compile nothing."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(root, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts XLA backend compilations (cache loads excluded) while on."""

    def __init__(self):
        import jax

        self.on = False
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, duration: float, **kw) -> None:
        if self.on and "backend_compile" in event:
            self.n += 1


def _memory_peak(devs) -> int:
    """Peak bytes in use on the fullest device (0 where the backend keeps
    no allocator stats, as the CPU)."""
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devs)


def _trace_options():
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0      # Python tracing would swamp the host
    opts.host_tracer_level = 2
    return opts


def run(root: str, workload: str, seed: int, seconds: float, trace: bool,
        *, t_start: float | None = None, require_tpu: bool = True,
        compile_cache: bool = True, control: bool = False) -> dict:
    """One run of one cell. Returns the result line's object, with the
    compared numbers under ``checks`` (last). ``control`` also judges the
    control's answers (``control.py``) under ``control_checks``."""
    t_start = time.perf_counter() if t_start is None else t_start
    spec = resolve(root, workload)
    src = os.path.join(root, "src")
    if not os.path.isdir(os.path.join(src, "repro")):
        raise HarnessError(f"no program under {src}: run from a checkout")
    if src not in sys.path:
        sys.path.insert(0, src)
    if compile_cache:
        enable_compile_cache(root)
    devs = _devices(int(spec.workload["chips"]), require_tpu)
    t_devices = time.perf_counter() - t_start
    import jax

    import repro  # noqa: F401  (x64 on, as for every user of the program)
    import tracesum
    import work

    dev = devs[0]
    peak = work.peaks(dev.device_kind) if require_tpu else None
    rngs = {k: np.random.default_rng([int(seed), i]) for i, k in
            enumerate(("data", "traffic", "check", "warm"))}
    t0 = time.perf_counter()
    cell = spec.driver().Cell(spec.config, spec.traffic, rngs, spec)
    t_data = time.perf_counter() - t0
    t0 = time.perf_counter()
    cell.setup()
    cell.prepare(float(seconds))
    t_warm = time.perf_counter() - t0
    counter = _CompileCounter()
    trace_dir = os.path.join(spec.dir, "runs", "trace", workload)
    if trace:
        shutil.rmtree(trace_dir, ignore_errors=True)
        jax.profiler.start_trace(trace_dir, profiler_options=_trace_options())
    setup_s = time.perf_counter() - t_start
    counter.on = True
    with jax.profiler.TraceAnnotation(tracesum.WINDOW_SPAN):
        e2e = cell.window(float(seconds))
    counter.on = False
    if trace:
        jax.profiler.stop_trace()
    stats = cell.stats()
    stats["compiles_in_window"] = counter.n
    stats["memory_peak_bytes"] = _memory_peak(devs)
    stats["setup_devices_s"] = t_devices
    stats["setup_data_s"] = t_data
    stats["setup_warm_s"] = t_warm
    summary = None
    if trace:
        summary = tracesum.summarize(tracesum.load(trace_dir))
        with open(os.path.join(trace_dir, "summary.json"), "w") as f:
            json.dump({"ops": summary.ops, "modules": summary.modules,
                       "idle_by_host": summary.idle_by_host,
                       "spans": {k: [len(v), sum(v)] for k, v
                                 in summary.spans.items()},
                       "busy_s": summary.busy_s,
                       "device_events": summary.device_events,
                       "window_s": summary.window_s}, f, indent=1)
    values = {"setup_s": setup_s, **e2e}
    metrics = {}
    if not trace:
        for m in spec.end_to_end:
            if m["name"] not in values:
                raise HarnessError(f"the driver gave no {m['name']!r}")
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        ctx = {"summary": summary, "stats": stats, "peak": peak,
               "config": spec.config, "values": values}
        for m in spec.per_layer:
            v = spec.metric_reader(m["name"]).read(ctx)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    cell.release()
    verdict = cell.check(rngs["check"])
    checks = {k: {"value": v, "limit": 0}
              for k, v in verdict["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs),
              "memory_peak_bytes": stats["memory_peak_bytes"]}
    out = {"correct": bool(correct and stats["failed"] == 0),
           "attempted": int(stats["attempted"]),
           "failed": int(stats["failed"]),
           "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        out["breakdown"] = {
            "device_ops": summary.top(summary.ops),
            "idle_gaps": [[n, s] for n, s in sorted(
                summary.idle_by_host.items(), key=lambda kv: -kv[1])[:10]]}
    out["notes"] = {**verdict["notes"],
                    **{k: v for k, v in stats.items()
                       if isinstance(v, (int, float, str))}}
    if control:
        import control as control_mod

        cell.use_control(control_mod)
        out["control_checks"] = cell.check(
            np.random.default_rng([int(seed), 2]))["checks"]
    out["checks"] = checks
    return out
