"""The measuring side of the autotuner: bounded schedule search.

TVM's observation (arXiv:1802.04799), scoped to our two Pallas
consumers: no single hand-picked tiling wins across shapes and device
generations, but a SMALL per-(kernel, shape, dtype, device_kind) search
— warmup + best-of-k wall timing of each candidate, winner cached —
recovers the headroom at a one-time cost.  Searching happens only at
bind/admit-time call sites (``PagedSlots`` construction, an explicit
epilogue ``tune()``), NEVER per tick: ``measure`` blocks on the device
by design and is a declared ``analysis/config.py`` boundary, and the
steady-state loops only ever see the already-chosen schedule through
the pure :func:`~mxnet_tpu.autotune.cache.schedule_for`.
"""
from __future__ import annotations

import logging
import os
import time

from .. import telemetry as _tm
from . import cache as _cache

__all__ = ["trials_budget", "measure", "ensure"]

# --- autotune metric families (docs/telemetry.md) ---------------------------
_TM_TRIALS = _tm.counter(
    "autotune_trials_total",
    "candidate schedules benchmarked by the autotuner, per kernel "
    "(zero on a warm schedule cache: every consumer should hit)",
    labels=("kernel",))
_TM_CACHE = _tm.counter(
    "autotune_cache_total",
    "schedule-cache lookups at tuning call sites: hit = a persisted or "
    "in-process winner was reused, miss = none existed yet (a miss in "
    "search mode triggers a bounded search; in readonly mode the "
    "consumer keeps its default schedule)",
    labels=("result",))
_TM_REJECTED = _tm.counter(
    "autotune_rejected_total",
    "candidate schedules whose build or compile raised during a search "
    "on a TPU (the compiler's message is logged once per kernel and "
    "shape) — a lowering the chip refuses is a fault to read, not a "
    "candidate to skip in silence",
    labels=("kernel",))
_TM_BEST = _tm.gauge(
    "autotune_best_us",
    "best-of-k microseconds of the winning schedule at its last "
    "search, per kernel",
    labels=("kernel",))


def trials_budget() -> int:
    """``MXTPU_AUTOTUNE_TRIALS`` — max candidates measured per search
    (default 16; 0 disables searching while still honoring cached
    winners)."""
    try:
        return max(int(os.environ.get("MXTPU_AUTOTUNE_TRIALS", "16")
                       or 16), 0)
    except ValueError:
        return 16


def measure(fn, warmup=2, best_of=5):
    """Best-of-k wall microseconds of ``fn()`` (which must return
    device values; they are blocked on).  The autotuner's sanctioned
    sync boundary — never reachable from a steady-state loop."""
    import jax

    for _ in range(max(warmup, 0)):
        jax.block_until_ready(fn())
    best = float("inf")
    for _ in range(max(best_of, 1)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best * 1e6


def ensure(kernel: str, keysig: str, default, candidates, bench_fn,
           warmup=2, best_of=5):
    """The tuning call site: return the schedule to install for
    ``(kernel, keysig)`` on this device kind.

    - cache ``off``: ``default``, no counters — the autotuner is out of
      the picture entirely;
    - cached winner (in-process or loaded from disk): count a ``hit``,
      return it — zero trials;
    - miss in ``readonly`` mode: count the miss, return ``default``;
    - miss in ``search`` mode: measure up to :func:`trials_budget`
      ``candidates`` through ``bench_fn(candidate) -> fn`` (the returned
      thunk is timed with warmup + best-of-k), record + persist the
      winner, return it.  A candidate whose build raises is skipped (a
      lowering's shape gate may reject it) — on a TPU it is also
      counted (``autotune_rejected_total``) and the first one per
      search logged with the compiler's message, so a kernel the chip
      refuses is not quietly replaced by the reference; if every
      candidate fails, ``default`` wins.

    ``default`` should normally appear in ``candidates`` so a search
    can never do worse than not searching.
    """
    mode, _path = _cache.cache_spec()
    if mode == "off":
        return default
    _cache.prime()
    sentinel = object()
    got = _cache.schedule_for(kernel, keysig, sentinel)
    if got is not sentinel:
        _TM_CACHE.inc(result="hit")
        return got
    _TM_CACHE.inc(result="miss")
    if mode == "readonly":
        return default
    best_sched, best_us, trials, rejected = None, float("inf"), 0, 0
    budget = trials_budget()
    for cand in candidates:
        if trials >= budget:
            break
        try:
            fn = bench_fn(cand)
            us = measure(fn, warmup=warmup, best_of=best_of)
        except Exception as e:  # noqa: BLE001 — candidate rejected by its gate
            if _on_tpu():
                rejected += 1
                _TM_REJECTED.inc(kernel=kernel)
                if rejected == 1:
                    logging.getLogger("mxnet_tpu.autotune").warning(
                        "autotune: %s %s candidate %r refused on the "
                        "TPU: %s", kernel, keysig, cand, e)
            continue
        trials += 1
        if us < best_us:
            best_sched, best_us = cand, us
    if trials:
        _TM_TRIALS.inc(trials, kernel=kernel)
    if best_sched is None:
        return default
    _TM_BEST.set(best_us, kernel=kernel)
    _cache.record(kernel, keysig, best_sched, best_us, trials)
    if _tm.perf.enabled():
        _log_winner_roofline(kernel, best_us, trials)
    return best_sched


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _log_winner_roofline(kernel: str, best_us: float, trials: int):
    """Achieved-vs-roofline context for a search winner (perf plane,
    docs/perf_attr.md): when a cost row exists for a program whose
    label mentions the kernel, compare the winner's achieved wall to
    the analytical roofline floor — max(flops/peak_flops,
    bytes/peak_bw) — else just name the peaks the consumer's live MFU
    will be measured against.  Logging only; never raises."""
    try:
        kind = _tm.perf.device_kind()
        pf = _tm.perf.peak_flops(kind)
        pb = _tm.perf.peak_bytes_per_sec(kind)
        row = next((r for r in _tm.perf.cost_table()
                    if kernel in r["program"]), None)
        msg = ("autotune: %s winner %.1fus over %d trials on %s"
               % (kernel, best_us, trials, kind))
        if row and pf and pb and (row["flops"] or row["bytes_accessed"]):
            floor_s = max((row["flops"] or 0.0) / pf,
                          (row["bytes_accessed"] or 0.0) / pb)
            msg += (" (roofline floor %.1fus, achieved %.0f%% of it)"
                    % (floor_s * 1e6,
                       100.0 * floor_s * 1e6 / best_us if best_us else 0.0))
        elif pf:
            msg += " (peak %.0f TFLOP/s, no cost row yet)" % (pf / 1e12)
        logging.getLogger("mxnet_tpu.autotune").info(msg)
    except Exception:  # noqa: BLE001 — reporting must never break a search
        pass
