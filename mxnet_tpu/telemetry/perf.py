"""Per-program performance attribution plane (docs/perf_attr.md).

bench.py answers "how fast is the build this round"; this module
answers "where does the step time GO" while a real run is running.
Three ledgers, all host-side, all pure arithmetic:

- **analytical cost rows** — at a program's first dispatch the plane
  reads the COMPILED executable's ``cost_analysis()`` (analytical
  FLOPs / bytes-accessed straight from the optimized HLO, the ground
  truth hand-maintained formulas like ``TRAIN_FLOPS_PER_IMG`` drift
  away from) and records one row per compiled program, keyed by the
  same ``structural_signature``-derived label the PR-5 memory rows
  use.  Backends without ``cost_analysis`` fall back to an "unknown"
  row — the capture never raises and never runs when the plane is
  disarmed.
- **runtime attribution** — the already-timed dispatch sites
  (executor fwd/fwdbwd, FusedTrainer.step) feed a
  per-program cumulative host-wall ledger, and the fit loops split
  each step's wall into ``data_wait`` / ``dispatch`` /
  ``window_stall`` buckets (plus the epoch-boundary ``boundary_sync``
  drain) from perf_counter stamps they already take — zero new
  per-batch device syncs by construction.
- **roofline/MFU** — analytical FLOPs over measured wall against the
  device-kind peak table (hoisted here from bench.py so bench and
  telemetry can never disagree) yields a live ``program_mfu``; the
  operational intensity (flops/byte) against the machine balance
  (peak FLOP/s over peak bytes/s) yields the classic roofline verdict
  — a ratio >= 1 means the program SHOULD be compute-bound.

Armed by ``MXTPU_PERF_ATTR=1`` (or :func:`enable`); served on
``GET /profile`` and ``/metrics.json``; rendered by
``tools/explain.py``; folded into the flight dump.
"""
from __future__ import annotations

import os
import threading
from collections import OrderedDict

from . import registry as _reg

__all__ = [
    "PEAK_TFLOPS", "PEAK_GBPS",
    "peak_flops", "peak_bytes_per_sec", "machine_balance", "device_kind",
    "enabled", "enable", "disable",
    "attach_cost_analysis", "record_cost", "cost_table",
    "record_dispatch", "record_step_buckets", "record_bucket",
    "runtime_table", "bucket_table",
    "publish_gauges", "profile_payload", "speedometer_suffix", "reset",
]

# ---------------------------------------------------------------------------
# device peaks (single source of truth — bench.py imports these)
# ---------------------------------------------------------------------------
# (substring, peak TFLOP/s) matched against jax's device_kind, first hit
# wins — "v5p" must precede "v5", and the nominal "cpu" row stays LAST
# so it can never shadow an accelerator kind.  bf16 peaks per chip.
# The "cpu" entry is a NOMINAL attribution reference (0.1 TFLOP/s), not
# a hardware claim: it exists so MFU-shaped numbers stay comparable
# across CPU CI runs instead of degenerating to null.
PEAK_TFLOPS = (
    ("v6", 918.0),
    ("v5p", 459.0),
    ("v5", 197.0),
    ("v4", 275.0),
    ("v3", 123.0),
    ("v2", 45.0),
    ("cpu", 0.1),
)
# (substring, peak HBM GB/s) — the denominator of machine balance for
# the roofline verdict.  Same matching rules; the "cpu" row is the same
# kind of nominal reference as its FLOP/s twin.
PEAK_GBPS = (
    ("v6", 1640.0),
    ("v5p", 2765.0),
    ("v5", 819.0),
    ("v4", 1228.0),
    ("v3", 900.0),
    ("v2", 700.0),
    ("cpu", 50.0),
)


def peak_flops(kind):
    """Peak FLOP/s for a jax ``device_kind`` string (None when the kind
    is not in the table — callers surface that, never guess)."""
    k = str(kind or "").lower()
    for sub, tflops in PEAK_TFLOPS:
        if sub in k:
            return tflops * 1e12
    return None


def peak_bytes_per_sec(kind):
    """Peak memory bytes/s for a jax ``device_kind`` (None on a miss)."""
    k = str(kind or "").lower()
    for sub, gbps in PEAK_GBPS:
        if sub in k:
            return gbps * 1e9
    return None


def machine_balance(kind):
    """FLOPs per byte at which this device flips from memory- to
    compute-bound (peak FLOP/s over peak bytes/s); None off-table."""
    pf, pb = peak_flops(kind), peak_bytes_per_sec(kind)
    return (pf / pb) if pf and pb else None


_device_kind = None


def device_kind():
    """The local device kind, resolved once and cached ("unknown" when
    the backend cannot be asked)."""
    global _device_kind
    if _device_kind is None:
        try:
            import jax

            _device_kind = str(jax.devices()[0].device_kind)
        except Exception:  # noqa: BLE001 — attribution must never raise
            _device_kind = "unknown"
    return _device_kind


# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------
def _env_armed() -> bool:
    return os.environ.get("MXTPU_PERF_ATTR", "").strip().lower() \
        not in ("", "0", "false", "off", "no")


_armed = _env_armed()


def enabled() -> bool:
    """Is the attribution plane armed (``MXTPU_PERF_ATTR`` / enable())?"""
    return _armed


def enable():
    global _armed
    _armed = True


def disable():
    global _armed
    _armed = False


# ---------------------------------------------------------------------------
# telemetry families (docs/telemetry.md)
# ---------------------------------------------------------------------------
_TM_PROG_COST = _reg.gauge(
    "program_cost",
    "per-compiled-program analytical cost captured from the executable's "
    "cost_analysis() at first dispatch (component=flops/bytes_accessed/"
    "peak_memory; flops and bytes are per call)",
    labels=("program", "component"))
_TM_PROG_WALL = _reg.counter(
    "program_wall_seconds",
    "cumulative host wall attributed to each compiled program at its "
    "dispatch site (perf plane; MXTPU_PERF_ATTR)",
    labels=("program",))
_TM_MFU = _reg.gauge(
    "program_mfu",
    "model FLOPs utilization per program: analytical FLOPs x dispatches "
    "over measured wall x device peak (perf plane)",
    labels=("program",))
_TM_ROOFLINE = _reg.gauge(
    "program_roofline",
    "operational intensity (flops/byte) over machine balance — >= 1 "
    "means the program should be compute-bound, < 1 memory-bound",
    labels=("program",))
_TM_STEP_TIME = _reg.counter(
    "step_time_seconds",
    "cumulative step wall split into buckets (data_wait/dispatch/"
    "window_stall per step; boundary_sync at epoch boundaries)",
    labels=("bucket",))

# ---------------------------------------------------------------------------
# ledgers (host-side, capped, lock-guarded — exporter threads read them)
# ---------------------------------------------------------------------------
_CAP = 128
_lock = threading.Lock()
_costs: "OrderedDict[str, dict]" = OrderedDict()
_runtime: "OrderedDict[str, dict]" = OrderedDict()
_buckets: "OrderedDict[str, dict]" = OrderedDict()
_steps = {"count": 0, "wall_s": 0.0}


def attach_cost_analysis(program: str, jitted, *args, **kwargs) -> bool:
    """Capture one compiled program's analytical cost row.

    Call ONCE per program at its first dispatch (the jit's compilation
    cache makes ``compile()`` a lookup; the re-trace behind ``lower()``
    is a one-time cost paid only while the plane is armed — never per
    batch).  Backends whose executable lacks ``cost_analysis`` (or
    raise from it) get an "unknown" row; this function never raises.
    Returns True when a real cost row landed."""
    if not _armed:
        return False
    flops = bytes_acc = None
    source = "unknown"
    try:
        cost = jitted.lower(*args, **kwargs).compile().cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        f = float(cost.get("flops", -1.0))
        b = float(cost.get("bytes accessed", -1.0))
        flops = f if f > 0 else None
        bytes_acc = b if b > 0 else None
        if flops is not None or bytes_acc is not None:
            source = "cost_analysis"
    except Exception:  # noqa: BLE001 — attribution must never break dispatch
        pass
    record_cost(program, flops=flops, bytes_accessed=bytes_acc,
                source=source)
    return source == "cost_analysis"


def record_cost(program: str, flops=None, bytes_accessed=None,
                peak_memory=None, source: str = "unknown"):
    """Record (or refresh) one program's cost row.  ``peak_memory``
    defaults to the PR-5 memory row's peak bytes for the same label —
    the two planes share the program key on purpose."""
    if peak_memory is None:
        from . import health as _health

        try:
            for row in _health.program_table():
                if row["program"] == program:
                    peak_memory = row.get("peak_bytes")
                    break
        except Exception:  # noqa: BLE001
            peak_memory = None
    entry = {
        "program": str(program),
        "flops": float(flops) if flops else None,
        "bytes_accessed": float(bytes_accessed) if bytes_accessed else None,
        "peak_memory": int(peak_memory) if peak_memory else None,
        "source": source,
    }
    with _lock:
        _costs[entry["program"]] = entry
        _costs.move_to_end(entry["program"])
        while len(_costs) > _CAP:
            _costs.popitem(last=False)
    if _reg.enabled():
        for comp in ("flops", "bytes_accessed", "peak_memory"):
            if entry[comp] is not None:
                _TM_PROG_COST.set(float(entry[comp]),
                                  program=entry["program"], component=comp)
    return entry


def record_dispatch(program: str, seconds: float):
    """Fold one dispatch's host wall into the program's runtime ledger.
    No-op when the plane is disarmed; pure dict arithmetic when armed."""
    if not _armed or program is None:
        return
    with _lock:
        row = _runtime.get(program)
        if row is None:
            row = _runtime[program] = {"program": str(program),
                                       "wall_s": 0.0, "dispatches": 0}
            while len(_runtime) > _CAP:
                _runtime.popitem(last=False)
        row["wall_s"] += float(seconds)
        row["dispatches"] += 1
    _TM_PROG_WALL.inc(float(seconds), program=str(program))


def record_step_buckets(wall_s: float, **buckets):
    """Fold one step's decomposition into the bucket ledger.  The
    buckets of one call partition that step's wall by construction
    (the stamps nest), so the ledger's step buckets always sum to the
    accumulated step wall."""
    if not _armed:
        return
    with _lock:
        _steps["count"] += 1
        _steps["wall_s"] += float(wall_s)
        for name, sec in buckets.items():
            b = _buckets.get(name)
            if b is None:
                b = _buckets[name] = {"seconds": 0.0, "count": 0,
                                      "in_step": True}
            b["seconds"] += float(sec)
            b["count"] += 1
            b["in_step"] = True
    for name, sec in buckets.items():
        _TM_STEP_TIME.inc(float(sec), bucket=name)


def record_bucket(name: str, seconds: float):
    """Fold a NON-step bucket (epoch-boundary drain, serving admit) —
    reported alongside the step buckets but outside the sums-to-step-
    wall identity."""
    if not _armed:
        return
    with _lock:
        b = _buckets.get(name)
        if b is None:
            b = _buckets[name] = {"seconds": 0.0, "count": 0,
                                  "in_step": False}
        b["seconds"] += float(seconds)
        b["count"] += 1
    _TM_STEP_TIME.inc(float(seconds), bucket=name)


def cost_table():
    with _lock:
        return [dict(r) for r in _costs.values()]


def runtime_table():
    with _lock:
        return [dict(r) for r in _runtime.values()]


def bucket_table():
    with _lock:
        return {n: dict(b) for n, b in _buckets.items()}


def reset(costs: bool = True):
    """Clear the ledgers (tests, and bench warmup isolation).  Pass
    ``costs=False`` to keep the compile-time cost rows — bench resets
    runtime between warmup and the timed loop without re-compiling."""
    global _device_kind
    with _lock:
        _runtime.clear()
        _buckets.clear()
        _steps["count"] = 0
        _steps["wall_s"] = 0.0
        if costs:
            _costs.clear()
    if costs:
        _device_kind = None


# ---------------------------------------------------------------------------
# derivation + surfaces
# ---------------------------------------------------------------------------
def _derive(rt, cost, peak, balance):
    """(mfu, intensity, ratio, verdict) for one program from its
    runtime row + cost row against the device peaks; Nones where a
    term is unknown."""
    mfu = intensity = ratio = None
    verdict = "unknown"
    flops = cost.get("flops") if cost else None
    nbytes = cost.get("bytes_accessed") if cost else None
    wall = rt.get("wall_s") or 0.0
    n = rt.get("dispatches") or 0
    if flops and peak and wall > 0.0 and n > 0:
        mfu = (flops * n) / (wall * peak)
    if flops and nbytes:
        intensity = flops / nbytes
        if balance:
            ratio = intensity / balance
            verdict = "compute_bound" if ratio >= 1.0 else "memory_bound"
    return mfu, intensity, ratio, verdict


def publish_gauges():
    """Fold the ledgers into the ``program_mfu`` / ``program_roofline``
    gauge families.  Called by the exporter right before a scrape
    renders (and by :func:`profile_payload`) — pure host arithmetic
    over the locked ledgers, never a device touch (ENTRY_POINTS)."""
    if not (_armed and _reg.enabled()):
        return
    kind = device_kind()
    peak, balance = peak_flops(kind), machine_balance(kind)
    with _lock:
        rows = [dict(r) for r in _runtime.values()]
        costs = {p: dict(c) for p, c in _costs.items()}
    for rt in rows:
        mfu, _, ratio, _ = _derive(rt, costs.get(rt["program"]),
                                   peak, balance)
        if mfu is not None:
            _TM_MFU.set(mfu, program=rt["program"])
        if ratio is not None:
            _TM_ROOFLINE.set(ratio, program=rt["program"])


def profile_payload(topn=None) -> dict:
    """The ``GET /profile`` document: ranked programs (device wall,
    MFU, roofline verdict, memory), the step-bucket decomposition, and
    the peaks the numbers were derived against.  ``topn`` defaults to
    ``MXTPU_PROFILE_TOPN`` (20); <= 0 means unranked-complete (the
    flight dump uses that so a post-mortem never reads a truncated
    table)."""
    if topn is None:
        try:
            topn = int(os.environ.get("MXTPU_PROFILE_TOPN", "20") or 20)
        except ValueError:
            topn = 20
    publish_gauges()
    kind = device_kind()
    peak, bw = peak_flops(kind), peak_bytes_per_sec(kind)
    balance = machine_balance(kind)
    with _lock:
        rt = {p: dict(r) for p, r in _runtime.items()}
        costs = {p: dict(c) for p, c in _costs.items()}
        buckets = {n: dict(b) for n, b in _buckets.items()}
        steps = dict(_steps)
    programs = []
    for label in set(rt) | set(costs):
        row_rt = rt.get(label, {"wall_s": 0.0, "dispatches": 0})
        cost = costs.get(label)
        mfu, intensity, ratio, verdict = _derive(row_rt, cost, peak,
                                                 balance)
        programs.append({
            "program": label,
            "wall_s": row_rt.get("wall_s", 0.0),
            "dispatches": row_rt.get("dispatches", 0),
            "flops": cost.get("flops") if cost else None,
            "bytes_accessed": cost.get("bytes_accessed") if cost else None,
            "peak_memory": cost.get("peak_memory") if cost else None,
            "cost_source": cost["source"] if cost else "unknown",
            "mfu": mfu,
            "intensity": intensity,
            "roofline_ratio": ratio,
            "roofline": verdict,
        })
    programs.sort(key=lambda p: p["wall_s"], reverse=True)
    total = len(programs)
    if topn and topn > 0:
        programs = programs[:topn]
    return {
        "version": 1,
        "armed": enabled(),
        "device_kind": kind,
        "peak_flops": peak,
        "peak_bytes_per_sec": bw,
        "machine_balance": balance,
        "programs": programs,
        "programs_total": total,
        "buckets": buckets,
        "steps": steps,
    }


def speedometer_suffix() -> str:
    """`` mfu=0.42 top=dispatch`` for the epoch log line: the MFU of
    the program with the most attributed wall plus the dominant step
    bucket.  Pure host reads of the ledgers — adds zero device syncs
    to the Speedometer; empty when disarmed or before any data."""
    if not _armed:
        return ""
    kind = device_kind()
    peak, balance = peak_flops(kind), machine_balance(kind)
    with _lock:
        rows = [dict(r) for r in _runtime.values()]
        costs = {p: dict(c) for p, c in _costs.items()}
        buckets = [(n, b["seconds"]) for n, b in _buckets.items()
                   if b.get("in_step")]
    parts = []
    if rows:
        top = max(rows, key=lambda r: r["wall_s"])
        mfu, _, _, _ = _derive(top, costs.get(top["program"]), peak,
                               balance)
        if mfu is not None:
            parts.append("mfu=%.2f" % mfu)
    if buckets:
        dom = max(buckets, key=lambda kv: kv[1])[0]
        parts.append("top=%s" % dom)
    return (" " + " ".join(parts)) if parts else ""
