"""Metrics core: labeled Counter / Gauge / Histogram families + spans.

Role model: the operator profiler is the reference's only runtime lens
(src/engine/profiler.{h,cc} — per-op timelines); TVM/nGraph-style stacks
grew per-layer *metrics* on top of traces to drive their optimization
loops.  This module is that layer for mxnet_tpu: a process-wide registry
of named metric families that every subsystem (executor, engine, kvstore,
io, trainer) emits through, with one switch (`MXTPU_TELEMETRY` /
:func:`enable`) governing all of it.

Design constraints:

- **zero-cost-when-disabled** — every record path checks one module-level
  flag before any label resolution, dict lookup, or timestamping, so hot
  paths (engine.track on every chunk write, wait_for_var on every read)
  pay a single predictable branch when telemetry is off;
- **thread-safe** — io prefetch threads, kvstore engine workers, and the
  checkpoint writer all emit concurrently; one registry lock serializes
  family creation, one lock per family serializes its samples;
- **one timeline** — :func:`span` / :func:`timed` emit BOTH a latency
  histogram observation and a chrome-trace complete event through the
  profiler's sink (profiler.record, same monotonic timebase), so host
  spans land next to op spans and xprof device traces.
"""
from __future__ import annotations

import os
import re
import threading
import time
from contextlib import contextmanager
from typing import Dict, Optional, Sequence, Tuple

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry",
    "enabled", "enable", "disable",
    "counter", "gauge", "histogram", "get_registry", "reset",
    "span", "timed", "DEFAULT_BUCKETS",
]

_NAME_RE = re.compile(r"[a-zA-Z_:][a-zA-Z0-9_:]*$")
_LABEL_RE = re.compile(r"[a-zA-Z_][a-zA-Z0-9_]*$")

# Prometheus-conventional latency buckets (seconds).
DEFAULT_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1,
                   0.25, 0.5, 1.0, 2.5, 5.0, 10.0)


class _State:
    __slots__ = ("enabled",)

    def __init__(self, on: bool):
        self.enabled = on


_state = _State(os.environ.get("MXTPU_TELEMETRY", "0").lower()
                not in ("", "0", "false"))


def enabled() -> bool:
    """Is the telemetry runtime recording?"""
    return _state.enabled


def enable(on: bool = True):
    """Turn metric recording on (or off with ``on=False``).  Disabled is
    the default unless ``MXTPU_TELEMETRY=1`` is set in the environment."""
    _state.enabled = bool(on)


def disable():
    enable(False)


def sanitize_name(name: str) -> str:
    """Coerce an arbitrary string into a valid Prometheus metric name."""
    name = re.sub(r"[^a-zA-Z0-9_:]", "_", name)
    if not name or not _NAME_RE.match(name):
        name = "_" + name
    return name


class MetricFamily:
    """One named metric with a fixed label-name schema and per-label-value
    samples.  Subclasses define the sample record type and record verbs."""

    typename = "untyped"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = ()):
        if not _NAME_RE.match(name):
            raise ValueError(f"invalid metric name {name!r}")
        for ln in labelnames:
            if not _LABEL_RE.match(ln) or ln.startswith("__"):
                raise ValueError(f"invalid label name {ln!r}")
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._lock = threading.Lock()
        self._samples: Dict[Tuple[str, ...], object] = {}

    # ------------------------------------------------------------------ labels
    def _key(self, labels: dict) -> Tuple[str, ...]:
        if tuple(labels) != self.labelnames and \
                set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: expected labels {self.labelnames}, "
                f"got {tuple(labels)}")
        return tuple(str(labels[ln]) for ln in self.labelnames)

    def clear(self):
        with self._lock:
            self._samples.clear()

    def samples(self):
        """[(label_values_tuple, sample)] — a consistent snapshot."""
        with self._lock:
            return list(self._samples.items())


class Counter(MetricFamily):
    """Monotonically increasing value (e.g. ``*_total`` counts/bytes)."""

    typename = "counter"

    def inc(self, amount: float = 1.0, **labels):
        if not _state.enabled:
            return
        if amount < 0:
            raise ValueError("counters cannot decrease")
        key = self._key(labels)
        with self._lock:
            self._samples[key] = self._samples.get(key, 0.0) + amount

    def value(self, **labels) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._samples.get(key, 0.0))

    def total(self) -> float:
        """Sum over every label combination (test/report convenience)."""
        with self._lock:
            return float(sum(self._samples.values()))


class Gauge(MetricFamily):
    """Point-in-time value that can go up and down."""

    typename = "gauge"

    def set(self, value: float, **labels):
        if not _state.enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._samples[key] = float(value)

    def inc(self, amount: float = 1.0, **labels):
        if not _state.enabled:
            return
        key = self._key(labels)
        with self._lock:
            self._samples[key] = self._samples.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels):
        self.inc(-amount, **labels)

    def value(self, **labels) -> float:
        key = self._key(labels)
        with self._lock:
            return float(self._samples.get(key, 0.0))


class _HistSample:
    __slots__ = ("counts", "sum", "count")

    def __init__(self, n_buckets: int):
        self.counts = [0] * n_buckets  # per-bucket (non-cumulative)
        self.sum = 0.0
        self.count = 0


class Histogram(MetricFamily):
    """Bucketed distribution (latencies, sizes).  Exported in Prometheus
    cumulative-bucket form (``_bucket{le=...}`` + ``_sum`` + ``_count``)."""

    typename = "histogram"

    def __init__(self, name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Sequence[float] = DEFAULT_BUCKETS):
        super().__init__(name, help, labelnames)
        bl = sorted(float(b) for b in buckets)
        if not bl:
            raise ValueError("histogram needs at least one bucket")
        self.buckets = tuple(bl)  # +Inf is implicit

    def observe(self, value: float, **labels):
        if not _state.enabled:
            return
        key = self._key(labels)
        value = float(value)
        with self._lock:
            s = self._samples.get(key)
            if s is None:
                s = self._samples[key] = _HistSample(len(self.buckets) + 1)
            for i, ub in enumerate(self.buckets):
                if value <= ub:
                    s.counts[i] += 1
                    break
            else:
                s.counts[-1] += 1  # +Inf bucket
            s.sum += value
            s.count += 1

    def count(self, **labels) -> int:
        key = self._key(labels)
        with self._lock:
            s = self._samples.get(key)
            return s.count if s is not None else 0

    def sum(self, **labels) -> float:
        key = self._key(labels)
        with self._lock:
            s = self._samples.get(key)
            return s.sum if s is not None else 0.0


class Registry:
    """Name -> family map.  Families register once (module import time);
    get-or-create keeps re-imports and notebooks idempotent."""

    def __init__(self):
        self._lock = threading.Lock()
        self._families: Dict[str, MetricFamily] = {}

    def get_or_create(self, cls, name, help="", labelnames=(), **kwargs):
        with self._lock:
            fam = self._families.get(name)
            if fam is not None:
                if type(fam) is not cls:
                    raise ValueError(
                        f"metric {name!r} already registered as "
                        f"{fam.typename}, not {cls.typename}")
                if tuple(labelnames) != fam.labelnames:
                    raise ValueError(
                        f"metric {name!r} already registered with labels "
                        f"{fam.labelnames}, not {tuple(labelnames)}")
                return fam
            fam = cls(name, help, labelnames, **kwargs)
            self._families[name] = fam
            return fam

    def get(self, name: str) -> Optional[MetricFamily]:
        with self._lock:
            return self._families.get(name)

    def collect(self):
        """Families in registration order (stable export order)."""
        with self._lock:
            return list(self._families.values())

    def reset(self):
        """Zero every family's samples.  Families stay registered —
        instrumented modules hold references created at import time."""
        for fam in self.collect():
            fam.clear()


_default_registry = Registry()


def get_registry() -> Registry:
    return _default_registry


def reset():
    """Zero all metric values in the default registry (test isolation)."""
    _default_registry.reset()


def counter(name, help="", labels=()) -> Counter:
    return _default_registry.get_or_create(Counter, name, help, labels)


def gauge(name, help="", labels=()) -> Gauge:
    return _default_registry.get_or_create(Gauge, name, help, labels)


def histogram(name, help="", labels=(), buckets=DEFAULT_BUCKETS) -> Histogram:
    return _default_registry.get_or_create(Histogram, name, help, labels,
                                           buckets=buckets)


# ---------------------------------------------------------------------------
# spans — one region, two sinks: a latency histogram (this registry) and a
# chrome-trace complete event (profiler.record), so `telemetry.span` regions
# line up with op spans and xprof device slices on one timeline.
# ---------------------------------------------------------------------------
@contextmanager
def span(name: str, category: str = "host", device: str = "host",
         sync=None, histogram_name: Optional[str] = None, trace=None,
         **labels):
    """Time a region.

    When the profiler is running, emits a chrome-trace event named
    ``name`` under ``category`` (profiler parity — same sink and timebase
    as op spans).  When telemetry is enabled, observes the duration into
    histogram ``histogram_name`` (default: sanitized ``<name>_seconds``)
    with ``labels``.  ``trace`` additionally lands the region in the
    distributed-tracing span buffer under that trace id when request
    tracing is on (``telemetry/tracing.py`` — the ``GET /spans.json``
    lens).  While a ``jax.profiler`` session runs, the region is also a
    ``TraceAnnotation`` on the profiler's clock (the sink
    ``tracing.phase`` writes).  ``sync`` is an optional zero-arg
    callable run before closing (e.g. ``block_until_ready``) so async
    dispatch doesn't under-report.  When every sink is off the region
    runs untimed.
    """
    from .. import profiler as _prof
    from . import tracing as _tracing

    prof_on = _prof.is_running()
    trace_on = trace is not None and _tracing.trace_on()
    # a jax.profiler session: the region also goes on the profiler's
    # own clock, beside tracing.phase()'s spans and the device's ops
    annotation = _tracing._TraceAnnotation(name) \
        if _tracing._TraceAnnotation.is_enabled() else None
    if not (prof_on or _state.enabled or trace_on
            or annotation is not None):
        yield
        return
    us0 = _prof.now_us() if prof_on else 0.0
    if annotation is not None:
        annotation.__enter__()
    t0 = time.perf_counter()
    try:
        yield
    finally:
        if sync is not None:
            try:
                sync()
            except Exception:
                pass
        dt = time.perf_counter() - t0
        if annotation is not None:
            annotation.__exit__(None, None, None)
        if prof_on:
            _prof.record(name, device, us0, _prof.now_us(), category)
        if trace_on:
            _tracing.record_span(name, category, trace, dt, **labels)
        if _state.enabled:  # re-check: may have flipped inside the region
            hname = histogram_name or sanitize_name(name) + "_seconds"
            histogram(hname, f"wall time of {name} (seconds)",
                      labels=tuple(labels)).observe(dt, **labels)


def timed(name: str, category: str = "host", **labels):
    """Decorator form of :func:`span`."""

    def deco(fn):
        import functools

        @functools.wraps(fn)
        def wrapper(*args, **kwargs):
            with span(name, category=category, **labels):
                return fn(*args, **kwargs)

        return wrapper

    return deco
