"""Request-scoped distributed tracing + the serving-fleet SLO plane.

PRs 1/5/14 rebuilt the source paper's engine profiler as process
metrics, a flight recorder, and a fleet-federated metrics plane — all
*aggregate* lenses.  Nothing could answer "where did THIS request's
21 ms of p99 TTFT go?" across router -> replica -> scheduler ->
paged-KV.  This module is that per-request lens, plus the burn-rate SLO
evaluation the future autoscaler (ROADMAP item 3) will close its
control loop over.

**Trace propagation.**  The router mints a W3C-``traceparent``-style
header per ``POST /generate``::

    traceparent: 00-<32 hex trace-id>-<16 hex parent-span-id>-<2 hex flags>

(flags bit 0 = sampled, exactly the W3C grammar), forwards it on every
re-route attempt (same trace id, fresh parent span id), and the replica
server threads it through :class:`~mxnet_tpu.serving.scheduler.Request`.
Sampling is decided ONCE at mint time (``MXTPU_TRACE_SAMPLE``) and
rides the flags byte, so every hop agrees without coordination.

**Spans.**  :func:`record_span` appends one flat dict to a bounded
per-process ring (``MXTPU_SPAN_RING``) — a pure host-side deque write,
never a device sync (``tools/lint.py`` proves the tick-path callers;
``spans_payload`` is a declared ``analysis/config.py:ENTRY_POINTS``
flush path).  Spans are stamped with the END wall time ``t`` plus
``dur_s`` (the flight-ring convention), so ``tools/fleetstat.py trace
<id>`` can join router + replica buffers onto one clock-corrected
timebase via the PR-14 ``identity.clock.offset_s`` machinery.  The
request's terminal span additionally lands in the PR-5 flight ring
(``health.record_step(loop="serve", ...)``), so a crash dump carries
the last requests too.

**SLO plane.**  :class:`SloPlane` turns the router's per-request
records into multi-window (5 s / 60 s) burn rates against two
objectives — ``availability`` (request relayed without a 5xx/transport
failure) and ``ttft`` (time-to-first-token under ``MXTPU_SLO_TTFT_MS``)
— both targeting the ``MXTPU_SLO_AVAIL`` good-fraction.  burn rate =
observed bad fraction / error budget ``(1 - MXTPU_SLO_AVAIL)``: 1.0
burns the budget exactly at the objective, >1 is an alert.  The plane
keeps exemplar trace ids for the SLOWEST ``serve_ttft_seconds``
observations, so a burning SLO links straight to offending traces
(``GET /slo`` on the router; ``fleetstat.py --slo`` renders the table).

Env knobs (docs/how_to/env_var.md round 20): ``MXTPU_TRACE``,
``MXTPU_TRACE_SAMPLE``, ``MXTPU_SPAN_RING``, ``MXTPU_SLO_TTFT_MS``,
``MXTPU_SLO_AVAIL``.  Span model + runbook: docs/tracing.md.
"""
from __future__ import annotations

import os
import re
import threading
import time
from collections import deque

from jax.profiler import StepTraceAnnotation as _StepTraceAnnotation
from jax.profiler import TraceAnnotation as _TraceAnnotation

from . import registry as _reg

__all__ = [
    "trace_on", "enable_tracing", "sample_rate", "span_ring_size",
    "mint_traceparent", "parse_traceparent", "child_traceparent",
    "mint_span_id", "record_span", "spans", "spans_payload",
    "clear_spans", "slo_ttft_ms", "slo_avail", "SloPlane", "TICK_EVERY",
    "recording", "phase",
]

# --- tracing + SLO metric families (docs/telemetry.md) ----------------------
_TM_SPANS = _reg.counter(
    "trace_spans_total",
    "spans recorded into the bounded per-process span buffer "
    "(GET /spans.json) by emitting component", labels=("svc",))
_TM_SLO_BURN = _reg.gauge(
    "slo_burn_rate",
    "SLO error-budget burn rate per objective and trailing window: "
    "observed bad fraction / (1 - MXTPU_SLO_AVAIL); 1.0 burns the "
    "budget exactly at the objective, >1 pages",
    labels=("objective", "window"))
_TM_SLO_VIOL = _reg.counter(
    "slo_violations_total",
    "requests that violated an SLO objective: availability (5xx or "
    "transport failure through the router) or ttft (time-to-first-"
    "token above MXTPU_SLO_TTFT_MS)", labels=("objective",))

# Decode-tick span cadence: with tracing on, every TICK_EVERY-th engine
# tick emits one span per sampled live request (a per-tick span per
# request would swamp the ring at decode rates).  Tests lower it to 1.
TICK_EVERY = 16

_TP_RE = re.compile(r"^00-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")


class _State:
    __slots__ = ("enabled",)

    def __init__(self, on):
        self.enabled = on


_state = _State(os.environ.get("MXTPU_TRACE", "0").lower()
                not in ("", "0", "false", "off"))


def trace_on() -> bool:
    """Is span recording on?  (``MXTPU_TRACE=1`` at import, or
    :func:`enable_tracing` at runtime.)  One attribute read — cheap
    enough to guard every tick-path call site."""
    return _state.enabled


def enable_tracing(on: bool = True):
    """Turn span recording on/off at runtime (bench A/B, tests)."""
    _state.enabled = bool(on)


def recording() -> bool:
    """Is anyone looking?  True when :func:`trace_on` or a
    ``jax.profiler`` session is running: the two conditions under which
    :func:`phase` records.  An attribute read and one call into the
    profiler's flag -- cheap enough for every tick-path call site."""
    return _state.enabled or _TraceAnnotation.is_enabled()


def sample_rate() -> float:
    """``MXTPU_TRACE_SAMPLE`` — fraction of routed requests minted with
    the W3C sampled flag (default 1.0).  Unsampled requests still get a
    trace id (log/exemplar correlation) but record no spans."""
    try:
        return min(max(float(
            os.environ.get("MXTPU_TRACE_SAMPLE", "1") or 1.0), 0.0), 1.0)
    except ValueError:
        return 1.0


# a busy engine writes ~470 spans a second (six a tick, five an
# admission and its request's record, at 25 ms a tick and an admission
# a tick: 4,600-4,800 in a traced 10 s window of the benchmark): the
# default holds half a minute of them, so that a reader of a 10 s
# window finds its first spans still there
_SPAN_RING = 16384


def span_ring_size() -> int:
    """``MXTPU_SPAN_RING`` — bounded span-buffer capacity (default
    16384 spans; the oldest are overwritten)."""
    try:
        return max(int(os.environ.get("MXTPU_SPAN_RING", _SPAN_RING)), 16)
    except ValueError:
        return _SPAN_RING


def slo_ttft_ms() -> float:
    """``MXTPU_SLO_TTFT_MS`` — the TTFT objective threshold
    (default 250 ms)."""
    try:
        return max(float(os.environ.get("MXTPU_SLO_TTFT_MS", "250")
                         or 250.0), 0.0)
    except ValueError:
        return 250.0


def slo_avail() -> float:
    """``MXTPU_SLO_AVAIL`` — target good fraction for BOTH objectives
    (default 0.99: 99% of requests succeed, 99% under the TTFT
    threshold).  The error budget is ``1 - MXTPU_SLO_AVAIL``."""
    try:
        v = float(os.environ.get("MXTPU_SLO_AVAIL", "0.99") or 0.99)
    except ValueError:
        return 0.99
    return min(max(v, 0.0), 0.999999)


# ---------------------------------------------------------------------------
# trace-id grammar (W3C traceparent, version 00)
# ---------------------------------------------------------------------------
def mint_span_id() -> str:
    return os.urandom(8).hex()


def mint_traceparent(sampled=None) -> str:
    """A fresh ``00-<trace>-<span>-<flags>`` header.  ``sampled=None``
    decides via ``MXTPU_TRACE_SAMPLE`` (always False when tracing is
    off — unsampled ids still correlate logs and SLO exemplars)."""
    if sampled is None:
        sampled = trace_on() and os.urandom(1)[0] < sample_rate() * 256.0
    return "00-%s-%s-%02x" % (os.urandom(16).hex(), mint_span_id(),
                              1 if sampled else 0)


def parse_traceparent(header):
    """``{"trace", "parent", "sampled"}`` from a traceparent header, or
    None when absent/malformed (a bad client header degrades to a fresh
    trace, never a 4xx)."""
    if not header or not isinstance(header, str):
        return None
    m = _TP_RE.match(header.strip().lower())
    if m is None:
        return None
    return {"trace": m.group(1), "parent": m.group(2),
            "sampled": bool(int(m.group(3), 16) & 1)}


def child_traceparent(trace: str, sampled: bool, span=None) -> str:
    """Same trace, fresh parent span id — what the router forwards on
    each (re-)route attempt.  Pass ``span`` to reuse a pre-minted id
    (the router records its attempt span under the SAME id it
    forwards, so the replica's spans parent it exactly)."""
    return "00-%s-%s-%02x" % (trace, span or mint_span_id(),
                              1 if sampled else 0)


# ---------------------------------------------------------------------------
# the bounded per-process span buffer (GET /spans.json)
# ---------------------------------------------------------------------------
_spans: deque = deque(maxlen=span_ring_size())
_spans_lock = threading.Lock()
_span_seq = 0


def _next_sid() -> str:
    global _span_seq
    with _spans_lock:
        _span_seq += 1
        return "%d-%d" % (os.getpid(), _span_seq)


def record_span(name, svc, trace, dur_s, t=None, parent=None, span=None,
                **attrs):
    """Append one span: a pure host-side dict + deque write (the lint
    proves the tick-path callers never sync the device through here).

    ``t`` is the END wall-clock stamp (``time.time()`` now when omitted)
    and ``dur_s`` the span length — the flight-ring convention, so
    cross-host joins shift ``t`` by the clock offset and draw
    ``[t - dur_s, t]``.  ``trace`` may be None for ambient process
    events (e.g. a step-time KV eviction with no admitting request).
    Extra ``attrs`` land flat on the record; reserved keys lose.  A
    record written while a ``jax.profiler`` session runs says so
    (``prof: true``): those are the spans a traced window holds."""
    global _spans
    rec = dict(attrs)
    if _TraceAnnotation.is_enabled():
        rec["prof"] = True
    sid = span or _next_sid()
    with _spans_lock:
        rec.update(sid=sid, trace=trace, parent=parent, name=str(name),
                   svc=str(svc), t=(time.time() if t is None else float(t)),
                   dur_s=float(dur_s))
        if _spans.maxlen != span_ring_size():
            _spans = deque(_spans, maxlen=span_ring_size())
        _spans.append(rec)
    _TM_SPANS.inc(svc=str(svc))
    return rec


# ---------------------------------------------------------------------------
# phases: the engine's and the trainer's own spans, on the profiler's clock
# ---------------------------------------------------------------------------
class _NoPhase:
    """What :func:`phase` hands out while nobody is looking: one shared
    object, no clock read, nothing recorded."""

    __slots__ = ()
    t0 = t1 = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NO_PHASE = _NoPhase()
_open_phases = threading.local()


class _Phase:
    """One open :func:`phase`.  ``t0`` / ``t1`` are its
    ``time.perf_counter()`` stamps (``t1`` once closed), so that a
    caller which needs the same duration for another sink takes it from
    here and not from a second pair of clock reads.  ``start_ns`` is
    its start on the clock the profiler stamps the annotation with
    (``time.time_ns()``: the host plane's events count nanoseconds of
    the same wall clock), read once, right after the annotation
    opened."""

    __slots__ = ("name", "svc", "trace", "attrs", "parent", "sid", "t0",
                 "t1", "start_ns", "_annotation")

    def __init__(self, name, svc, trace, parent, step, attrs):
        self.name, self.svc, self.trace = name, svc, trace
        self.attrs, self.parent = attrs, parent
        self.t0 = self.t1 = None
        if step is None:
            self._annotation = _TraceAnnotation(name, **attrs)
        else:
            self._annotation = _StepTraceAnnotation(name, step_num=step,
                                                    **attrs)
            self.attrs = dict(attrs, step=step)

    def __enter__(self):
        stack = getattr(_open_phases, "stack", None)
        if stack is None:
            stack = _open_phases.stack = []
        self.sid = _next_sid()
        if stack:
            self.parent = stack[-1].sid
        stack.append(self)
        self._annotation.__enter__()
        self.start_ns = time.time_ns()
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.t1 = time.perf_counter()
        self._annotation.__exit__(*exc)
        _open_phases.stack.pop()
        dur_s = self.t1 - self.t0
        # the end stamp on the start's clock: [t - dur_s, t] is then
        # the annotation's interval, beside the device's operations
        record_span(self.name, self.svc, self.trace, dur_s,
                    t=self.start_ns * 1e-9 + dur_s, parent=self.parent,
                    span=self.sid, start_ns=self.start_ns, **self.attrs)
        return False


def phase(name, svc, trace=None, parent=None, step=None, **attrs):
    """A span of the program itself (an engine tick, a train step), into
    two sinks at once: a ``jax.profiler.TraceAnnotation`` -- so it lies
    on the profiler's clock, on its thread's line of the host plane,
    beside the device's operations, with ``attrs`` as its stats -- and,
    on exit, one record of the span ring (``GET /spans.json``) holding
    ``dur_s``, ``start_ns`` (its start in nanoseconds of the wall
    clock, which is the clock the profiler stamps the annotation and
    the device's operations with), the end stamp ``t`` = ``start_ns *
    1e-9 + dur_s``, ``attrs``, ``parent`` (the ``sid`` of the enclosing
    open phase on this thread, else the ``parent`` given) and ``prof:
    true`` when a profiler session was running at exit
    (:func:`record_span`).  ``step=<n>`` makes the annotation a
    ``StepTraceAnnotation`` (the profiler's step view; the record holds
    ``step`` too).  While :func:`recording` is false this returns one
    shared do-nothing object: no allocation, no clock read, the ring
    untouched."""
    if not recording():
        return _NO_PHASE
    return _Phase(name, svc, trace, parent, step, attrs)


def spans(trace=None):
    """Snapshot of the buffer, oldest first (optionally one trace's)."""
    with _spans_lock:
        out = list(_spans)
    if trace is not None:
        out = [s for s in out if s.get("trace") == trace]
    return out


def clear_spans():
    """Drop the buffer (bench A/B runs, test isolation)."""
    with _spans_lock:
        _spans.clear()


def spans_payload(trace=None) -> dict:
    """The ``GET /spans.json`` body: this process's identity + clock
    offset (so ``fleetstat.py trace`` lanes and aligns it with the
    PR-14 offset machinery) and the span snapshot.  Declared in
    ``analysis/config.py:ENTRY_POINTS`` — the flush path must stay a
    pure host-side buffer read."""
    from . import health as _health

    ident = _health.host_identity()
    return {"host": ident["host"], "pid": ident["pid"],
            "rank": ident["rank"], "clock": _health.clock_offset(),
            "trace_on": trace_on(), "spans": spans(trace)}


# ---------------------------------------------------------------------------
# the SLO plane (router-side)
# ---------------------------------------------------------------------------
class SloPlane:
    """Multi-window burn rates over per-request records.

    :meth:`record` is on the router's per-request path: one bounded
    deque append + counter bumps under a lock.  :meth:`snapshot` (the
    ``GET /slo`` body; also called from the router's scrape sweep so the
    gauges stay fresh without polling) recomputes each trailing
    window's bad fraction and burn rate, and returns the slowest-TTFT
    exemplar trace ids."""

    WINDOWS = (5.0, 60.0)

    def __init__(self, ttft_ms=None, avail=None, capacity=4096,
                 max_exemplars=8):
        self.ttft_s = (slo_ttft_ms() if ttft_ms is None
                       else float(ttft_ms)) / 1e3
        self.avail = slo_avail() if avail is None else float(avail)
        self.max_exemplars = int(max_exemplars)
        self._lock = threading.Lock()
        self._records = deque(maxlen=int(capacity))
        self._violations = {"availability": 0, "ttft": 0}
        self._exemplars = []          # [(ttft_s, trace, t)] slowest first

    def record(self, ok, ttft_s=None, trace=None):
        """One terminal routed request: ``ok`` = relayed without a
        5xx/transport failure; ``ttft_s`` when the replica reported
        one.  Returns the (availability, ttft) violation pair."""
        bad_avail = not ok
        bad_ttft = ttft_s is not None and ttft_s > self.ttft_s
        with self._lock:
            self._records.append(
                (time.time(), bool(ok), ttft_s, trace))
            if bad_avail:
                self._violations["availability"] += 1
            if bad_ttft:
                self._violations["ttft"] += 1
            if ttft_s is not None:
                self._exemplars.append((float(ttft_s), trace, time.time()))
                self._exemplars.sort(key=lambda e: -e[0])
                del self._exemplars[self.max_exemplars:]
        if bad_avail:
            _TM_SLO_VIOL.inc(objective="availability")
        if bad_ttft:
            _TM_SLO_VIOL.inc(objective="ttft")
        return bad_avail, bad_ttft

    def snapshot(self) -> dict:
        now = time.time()
        with self._lock:
            recs = list(self._records)
            viol = dict(self._violations)
            exemplars = list(self._exemplars)
        budget = max(1.0 - self.avail, 1e-9)
        windows = {}
        for w in self.WINDOWS:
            sel = [r for r in recs if r[0] >= now - w]
            n = len(sel)
            bad_avail = sum(1 for r in sel if not r[1])
            with_ttft = [r for r in sel if r[2] is not None]
            bad_ttft = sum(1 for r in with_ttft if r[2] > self.ttft_s)
            label = "%ds" % int(w)
            burn_avail = (bad_avail / n) / budget if n else 0.0
            burn_ttft = (bad_ttft / len(with_ttft)) / budget \
                if with_ttft else 0.0
            _TM_SLO_BURN.set(burn_avail, objective="availability",
                             window=label)
            _TM_SLO_BURN.set(burn_ttft, objective="ttft", window=label)
            windows[label] = {
                "requests": n,
                "bad_availability": bad_avail,
                "bad_ttft": bad_ttft,
                "burn_rate": {"availability": round(burn_avail, 4),
                              "ttft": round(burn_ttft, 4)},
            }
        return {
            "objectives": {"ttft_ms": round(self.ttft_s * 1e3, 3),
                           "availability": self.avail},
            "error_budget": round(budget, 9),
            "windows": windows,
            "violations_total": viol,
            "exemplars": [
                {"trace": tr, "ttft_ms": round(tt * 1e3, 3), "t": at}
                for tt, tr, at in exemplars],
        }
