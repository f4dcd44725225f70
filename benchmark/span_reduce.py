"""From the program's own span ring to the numbers the span metrics
read.

``mxnet_tpu.telemetry.tracing.phase`` writes each phase of the serving
engine and of the trainer twice: as a ``TraceAnnotation`` into the
profiler's trace, and as one record of a bounded ring (``sid``,
``parent``, ``name``, ``dur_s``, the end stamp ``t``, its attributes,
and ``prof: true`` when a profiler session was running as it closed).
The harness hands a reader no trace bounds, so the readers take the
ring: the ``prof`` records are the spans that ended inside the traced
window, and every share takes numerator and denominator from that one
set.

The arithmetic works on plain lists of dicts, so it is tested without
the program.  ``window_records`` gives ``None`` -- and says why on
stderr -- when the ring is full (its first spans may be gone) or holds
no ``prof`` record (an untraced run, or a program without the spans).
"""
import math
import sys

from benchmark.trace_reduce import union_seconds


def say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def window_records(spans, capacity, who="span_reduce"):
    """The records of ``spans`` that closed while a profiler session
    ran, or ``None`` when they cannot be trusted to be all of them."""
    if len(spans) >= capacity:
        say(f"{who}: the span ring is full ({len(spans)} of {capacity}): "
             "the window's first spans may be gone; no value")
        return None
    prof = [s for s in spans if s.get("prof")]
    if not prof:
        say(f"{who}: the span ring holds no record of a profiler "
             f"session ({len(spans)} records); no value")
        return None
    return prof


def ring(who):
    """``window_records`` of the program's ring; ``None`` too where the
    program has no such ring."""
    try:
        from mxnet_tpu.telemetry import tracing
        spans, capacity = tracing.spans(), tracing.span_ring_size()
    except (ImportError, AttributeError) as e:
        say(f"{who}: the program has no span ring ({e}); no value")
        return None
    return window_records(spans, capacity, who)


def named(records, *names):
    return [r for r in records if r["name"] in names]


def total_s(records, *names):
    return sum(r["dur_s"] for r in named(records, *names))


def percentile(values, q):
    """The ``q``-th percentile as the smallest value with at least that
    share of the sample at or below it (the benchmark's rule: no
    interpolation)."""
    s = sorted(values)
    return s[max(0, math.ceil(q / 100.0 * len(s)) - 1)]


def self_s(records, *names):
    """Self time of the spans called ``names``: each one's duration
    less the part of its interval that its children (the records whose
    ``parent`` is its ``sid``) cover."""
    children = {}
    for r in records:
        if r.get("parent") is not None:
            children.setdefault(r["parent"], []).append(
                (r["t"] - r["dur_s"], r["t"]))
    total = 0.0
    for r in named(records, *names):
        lo, hi = r["t"] - r["dur_s"], r["t"]
        total += r["dur_s"] - union_seconds(
            [(max(s, lo), min(e, hi))
             for s, e in children.get(r["sid"], ()) if e > lo and s < hi])
    return total
