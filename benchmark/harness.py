"""What every cell shares: finding a cell's files by the names in
``BENCHMARK.json``, the device, compile counting, the span tracer handed
to the program, and the reduction of a run to the contract's line.

A cell, a configuration or a per-layer metric is added by adding files
and entries, editing none:

- ``benchmark/workloads/<cell>.json``: ``driver`` (a module of
  ``benchmark/drivers``), the traffic parameters, the limits of its
  comparison;
- the configuration's ``file`` as ``BENCHMARK.json`` gives it;
- ``benchmark/metrics/<metric>.json``: ``reader`` (a module of
  ``benchmark/readers``) and its ``args``.
"""

from __future__ import annotations

import contextlib
import importlib.util
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_MODULES = {}                      # path -> driver or reader loaded from it


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def load_cell(bench: dict, workload: str, root: str = ROOT) -> dict:
    """The cell's entry, its workload file and its configuration file."""
    entry = next((w for w in bench["workloads"] if w["name"] == workload),
                 None)
    if entry is None:
        raise KeyError(f"BENCHMARK.json has no workload {workload!r}")
    config = next(c for c in bench["configs"] if c["name"] == entry["config"])
    return {"name": workload, "chips": entry["chips"],
            "traffic": _load(os.path.join(root, "benchmark", "workloads",
                                          workload + ".json")),
            "config_name": config["name"],
            "config": _load(os.path.join(root, config["file"]))}


def module_for(package: str, name: str, root: str = ROOT):
    """The module ``benchmark/<package>/<name>.py`` of ``root`` (``-`` read
    as ``_``), loaded by its file: a driver or a reader that a later PR
    adds is a new file there and nothing else."""
    mod = name.replace("-", "_")
    path = os.path.join(os.path.abspath(root), "benchmark", package,
                        mod + ".py")
    if path not in _MODULES:
        spec = importlib.util.spec_from_file_location(
            f"benchmark_{package}_{mod}", path)
        _MODULES[path] = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(_MODULES[path])
    return _MODULES[path]


def progress(what: str, t_start: float) -> None:
    """One line of set-up progress on standard error."""
    print(f"benchmark: +{time.perf_counter() - t_start:.1f}s {what}",
          file=sys.stderr, flush=True)


def device_fields() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes() -> int:
    """Peak bytes in use on the fullest device (0 where the backend
    keeps no such count: the CPU)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class CompileMeter:
    """Backend-compile seconds and persistent-cache traffic from JAX's own
    monitoring events (copied from ``chip_smoke.CompileMeter``).  The
    drivers snapshot it around the window: compilations inside read 0."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.hits = 0
        self.misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += duration
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1


class SpanTracer:
    """The tracer object a program seam takes (``ServeEngine(tracer=)``):
    ``span(name, **tags)`` opens a ``jax.profiler.TraceAnnotation`` (the
    span lands on the profiler's clock beside the device ops) and records
    host start and end.  Recording is a list append; it is on in every run
    so traced and untraced runs do the same host work."""

    def __init__(self):
        import jax
        self._annotate = jax.profiler.TraceAnnotation
        self.spans = []            # [name, start_s, end_s]

    @contextlib.contextmanager
    def span(self, name: str, **tags):
        rec = [name, time.perf_counter(), 0.0]
        self.spans.append(rec)
        try:
            with self._annotate(name):
                yield rec
        finally:
            rec[2] = time.perf_counter()

    def seconds(self, names, since: float = 0.0) -> float:
        """Total seconds of the spans called one of ``names`` that began
        at or after ``since``."""
        return sum(e - s for name, s, e in self.spans
                   if name in names and s >= since)


@contextlib.contextmanager
def traced_window(traced: bool, trace_dir: str):
    """The measured loop runs inside this: the ``bench.window``
    annotation, inside a profiler trace when ``traced``."""
    import jax
    if traced:
        jax.profiler.start_trace(trace_dir)
    try:
        with jax.profiler.TraceAnnotation("bench.window"):
            yield
    finally:
        if traced:
            jax.profiler.stop_trace()


class Check:
    """One number compared beside its limit."""

    def __init__(self, name: str, value: float, limit: float):
        self.name, self.value, self.limit = name, float(value), float(limit)

    @property
    def ok(self) -> bool:
        return self.value <= self.limit          # NaN fails

    def row(self) -> dict:
        return {"name": self.name, "value": self.value, "limit": self.limit,
                "ok": self.ok}


def layer_metrics(bench: dict, cell: str, ctx: dict, root: str = ROOT) -> dict:
    """Every per-layer metric due in ``cell`` whose reader finds something
    to read; a reader that finds nothing returns None and is left out."""
    out = {}
    for m in bench["per_layer"]:
        if cell not in m.get("workloads", [cell]):
            continue
        spec = _load(os.path.join(root, "benchmark", "metrics",
                                  m["name"] + ".json"))
        value = module_for("readers", spec["reader"], root).read(
            ctx, **spec.get("args", {}))
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
