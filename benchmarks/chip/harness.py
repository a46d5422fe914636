"""What every cell shares: loading the benchmark's data files, the run's
record of spans and counters, the compile clock, peaks and percentiles.

Nothing here names a cell, a configuration or a metric: those are files
found by the names in ``BENCHMARK.json`` (see ``run.py``).
"""

from __future__ import annotations

import contextlib
import dataclasses
import importlib.util
import json
import statistics
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

HERE = Path(__file__).resolve().parent


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: Path, name: str):
    """Import one file of the benchmark (a traffic kind, a metric reader)
    by its path: the names carry dots, so they are not importable names."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str, table: Optional[Path] = None) -> Dict[str, float]:
    """The chip's published peaks.  A kind missing from the table is an
    error: a default would put another chip's peak under this one."""
    rows = load_json(table or HERE / "peaks.json")["devices"]
    if device_kind not in rows:
        raise KeyError(f"no peaks for device_kind {device_kind!r} in "
                       f"peaks.json; known: {sorted(rows)}")
    return rows[device_kind]


def percentile(values: List[float], q: int) -> float:
    """The q-th percentile (1..99) by ``statistics.quantiles``' default
    (exclusive) method, over every value."""
    if len(values) == 1:
        return float(values[0])
    return float(statistics.quantiles(values, n=100)[q - 1])


class CompileClock:
    """Adds up JAX's compile events (tracing, lowering, backend compile or
    persistent-cache read) and counts backend compiles, so that a compile
    inside the measured window shows."""

    _DURATIONS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        self.cache_hits = 0
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self._DURATIONS:
            self.seconds += duration
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    def snapshot(self) -> Dict[str, float]:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.cache_hits,
                "cache_misses": self.cache_misses}


@dataclasses.dataclass
class Span:
    name: str
    t0: float
    t1: float


class Recorder:
    """The run's record: harness spans (host clock, also written into the
    profiler's trace as ``TraceAnnotation``s so that idle gaps on the
    device can be put to what the host was doing) and counters that the
    traffic module fills.  The per-layer readers read only this."""

    def __init__(self, *, trace_dir: Optional[str] = None,
                 trace_seconds: float = 0.0):
        self.spans: List[Span] = []
        self.counters: Dict[str, Any] = {}
        self.trace_dir = trace_dir
        self.trace_seconds = trace_seconds
        self.tracing = False
        self.window_t0 = self.window_t1 = 0.0
        self.trace_reduction: Optional[Dict[str, Any]] = None

    @contextlib.contextmanager
    def span(self, name: str):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append(Span(name, t0, time.perf_counter()))

    def append(self, key: str, value: Any) -> None:
        self.counters.setdefault(key, []).append(value)

    # -- the measured window ---------------------------------------------
    def open_window(self) -> None:
        """Start the window (and the profiler, in a traced run)."""
        if self.trace_dir:
            import jax
            jax.profiler.start_trace(self.trace_dir)
            self.tracing = True
        self.window_t0 = time.perf_counter()

    def tick(self) -> None:
        """Called by the traffic module after each unit of work (a batch,
        a request, a step): a traced run stops its profiler at the first
        tick past ``trace_seconds``, so the trace holds whole units."""
        if self.tracing and self.elapsed() >= self.trace_seconds:
            import jax
            with self.span("bench.stop_trace"):
                jax.profiler.stop_trace()
            self.tracing = False

    def elapsed(self) -> float:
        return time.perf_counter() - self.window_t0

    def close_window(self) -> None:
        """End the window (and stop a profiler still running)."""
        self.window_t1 = time.perf_counter()
        if self.tracing:
            self.trace_seconds = 0.0
            self.tick()

    @property
    def window_s(self) -> float:
        return self.window_t1 - self.window_t0

    def span_seconds(self, name: str) -> float:
        """Total host seconds in spans of this name inside the window."""
        return sum(s.t1 - s.t0 for s in self.spans
                   if s.name == name and s.t0 >= self.window_t0)

    @property
    def work_window_s(self) -> float:
        """The window less the time spent writing the trace out."""
        return self.window_s - self.span_seconds("bench.stop_trace")

    def span_summary(self) -> Dict[str, List[float]]:
        """{span name: [count, median s, longest s]} inside the window: a
        unit of work that stalled shows as a long one."""
        by: Dict[str, List[float]] = {}
        for s in self.spans:
            if s.t0 >= self.window_t0:
                by.setdefault(s.name, []).append(s.t1 - s.t0)
        return {k: [len(v), statistics.median(v), max(v)]
                for k, v in by.items()}


def annotate(obj, attr: str, name: str) -> None:
    """Wrap the callable ``obj.<attr>`` in a profiler annotation ``name``,
    so that a trace puts the device programs it launches to that name
    (``tracereduce``).  The call, its arguments and its result stay the
    program's own."""
    import jax
    fn = getattr(obj, attr)

    def call(*args, **kwargs):
        with jax.profiler.TraceAnnotation(name):
            return fn(*args, **kwargs)
    setattr(obj, attr, call)


def device_info(devices) -> Dict[str, Any]:
    """Platform, kind, count, and the allocator's peak on the fullest of
    the chips the cell used."""
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devices)
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(peak)}


@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def checks_line(checks: List[Check]) -> Dict[str, Dict[str, float]]:
    return {c.name: {"value": c.value, "limit": c.limit} for c in checks}


def correct(checks: List[Check]) -> bool:
    """A run, or the control, is correct when every number is within its
    limit."""
    return all(c.ok for c in checks)
