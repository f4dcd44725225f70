"""Analyzer configuration: the declared invariants.

This file is the single place where the package names its steady-state
entry points, its sanctioned sync boundaries, and the primitive sets
each rule family matches on.  Growing the system (a new trainer loop,
a new background thread) means growing THIS file — the lint then
proves the new surface obeys the same invariants.
"""

# --------------------------------------------------------------- host-sync
# Steady-state entry points: code reachable from these must never block
# on the device.  These are the per-batch/per-tick hot loops the
# zero-host-sync counter tests (test_async_pipeline / test_parallel /
# test_amp / test_checkpoint) sample dynamically.
ENTRY_POINTS = (
    "mxnet_tpu.module.base_module.BaseModule._fit_epochs",
    "mxnet_tpu.trainer.FusedTrainer.step",
    "mxnet_tpu.trainer.FusedTrainer.step_multi",
    "mxnet_tpu.serving.scheduler.SlotScheduler._tick",
    "mxnet_tpu.kvstore_fused.FusedUpdateEngine.handle_push",
    "mxnet_tpu.kvstore_fused.FusedUpdateEngine.handle_pull",
    "mxnet_tpu.checkpoint.snapshot",
    "mxnet_tpu.checkpoint.CheckpointManager.save",
    # elastic membership poll: runs every batch inside the fit loops —
    # must stay pure host-side flag reads (ISSUE 13)
    "mxnet_tpu.parallel.coordinator.CoordinatorClient.step_poll",
    # fleet plane steady-state loops (ISSUE 14): the heartbeat carries
    # the flight-ring step-timing feed, the coordinator's federation
    # sweep scrapes member /metrics.json — both must stay pure
    # host-side (HTTP + ring reads), never touching the device
    "mxnet_tpu.parallel.coordinator.CoordinatorClient._heartbeat_loop",
    "mxnet_tpu.telemetry.fleet.FleetScraper.scrape_once",
    # serving fleet (ISSUE 15): the router's replica-health scrape loop
    # and the paged-KV allocator tick (page allocation, block tables,
    # prefix index) are pure host-side bookkeeping — the device only
    # ever sees the jitted step/prefill dispatches
    "mxnet_tpu.serving.router.ReplicaRouter.scrape_once",
    "mxnet_tpu.serving.paged_kv.PagedSlots.step",
    # tracing + SLO plane (ISSUE 16): the per-request router relay and
    # the span-buffer flush behind GET /spans.json are steady-state
    # host paths — spans are pure dict/ring writes, never a device sync
    "mxnet_tpu.serving.router.ReplicaRouter.route_generate",
    "mxnet_tpu.telemetry.tracing.spans_payload",
    # perf-attribution plane (ISSUE 20): the per-scrape gauge fold and
    # the /profile payload walk the host-side ledgers the hot loops fed
    # with perf_counter stamps — pure dict arithmetic, never a device
    # touch; the ledger writers (record_dispatch/record_step_buckets)
    # are covered through the fit/tick entry points above
    "mxnet_tpu.telemetry.perf.publish_gauges",
    "mxnet_tpu.telemetry.perf.profile_payload",
)

# Sanctioned sync boundaries: the analyzer does not descend into these.
# Each entry is qualname -> why syncing behind it is the design, not a
# leak.  A boundary is NOT a free pass for its callers — the call site
# itself stays on the hot path; only the callee's interior is excused.
BOUNDARIES = {
    "mxnet_tpu.engine.AsyncWindow.drain":
        "the explicit epoch/checkpoint-boundary drain — THE sanctioned "
        "sync point of the bounded-window design",
    "mxnet_tpu.engine.AsyncWindow._wait_one":
        "window-full backpressure: blocking when MXTPU_ASYNC_DEPTH is "
        "exceeded is the bounded-depth contract",
    "mxnet_tpu.telemetry.health.sentinel_check":
        "sentinel reporting boundary: syncs parked device futures only "
        "at drain/window-overflow sites by contract (PR 5)",
    "mxnet_tpu.checkpoint.CheckpointWrite.__init__":
        "background writer thread: device->host fetch + file IO run "
        "off-loop; capture only dispatches jnp.copy",
    "mxnet_tpu.monitor.Monitor.toc_print":
        "opt-in debugging Monitor: interval-gated stat rendering syncs "
        "by contract (PR-5 keeps the per-batch tic() sync-free; "
        "production loops install no monitor)",
    # the serving tick's ONE host sync (PR 35): sampling needs the step's
    # logits on the host, and that fetch has always been the tick's wait
    # for the device (``np.asarray`` of a device array, which the
    # analyzer cannot type).  While a trace is being taken the same wait
    # is written in two parts, ``block_until_ready`` then the copy, so
    # that each gets a span of its own; with nobody looking it is the
    # one ``np.asarray`` it was
    "mxnet_tpu.serving.scheduler.SlotScheduler._to_host":
        "the tick's (and an admission's) one fetch of what sampling "
        "reads, split into engine.wait + engine.fetch only while "
        "tracing.recording(): the same sync in two spans, not a second",
    # autotuner (ISSUE 18): schedule search is a bind/admit-time
    # activity ONLY — PagedSlots construction and explicit tune() call
    # sites.  measure() blocks on each candidate by design; the
    # steady-state loops see tuned schedules exclusively through the
    # pure autotune.cache.schedule_for lookup, which never syncs.
    "mxnet_tpu.autotune.search.measure":
        "the autotuner's candidate timer: warmup + best-of-k "
        "block_until_ready at bind/admit-time search sites — never "
        "reachable from a steady-state tick",
    # perf-attribution plane (ISSUE 20): the cost capture re-lowers the
    # already-compiled program once per program lifetime (first
    # dispatch, guarded by per-program flags and the MXTPU_PERF_ATTR
    # arm) — compile() is a cache lookup; never a per-batch activity
    "mxnet_tpu.telemetry.perf.attach_cost_analysis":
        "one-time per-program compile-cache probe for the analytical "
        "cost row at first dispatch — flag-guarded at every call site, "
        "never per batch, no device sync (lower/compile only)",
}

# Device->host sync primitives, matched as method names on any receiver.
SYNC_METHODS = frozenset({
    "asnumpy", "wait_to_read", "item", "tolist", "block_until_ready",
})
# …and as resolved/dotted calls (module functions).
SYNC_CALLS = frozenset({
    "jax.device_get", "device_get",
})
# numpy module aliases whose asarray/array on an NDArray-typed argument
# is a hidden host sync (goes through NDArray.__array__ -> asnumpy).
NUMPY_MODULES = frozenset({"numpy"})
NUMPY_SYNC_FUNCS = frozenset({"asarray", "array", "ascontiguousarray"})
# builtins that trigger NDArray.__float__/__int__/__bool__ host syncs
# when applied to an NDArray-typed argument.
BUILTIN_CASTS = frozenset({"float", "int", "bool"})
# NDArray-ish class names for the cheap local type inference.
NDARRAY_CLASSES = frozenset({"NDArray", "RowSparseNDArray"})

# ------------------------------------------------------------ trace-purity
# Extra trace roots beyond what static jit/pallas/scan detection finds:
# whole modules whose functions are traced by construction.
TRACED_MODULES = (
    "mxnet_tpu.optim_rules",      # fused/flat/sparse optimizer kernels
)
# Decorators that mark a function as an op implementation — op bodies
# are traced by the executor's graph_fn.
OP_REGISTER_DECORATORS = frozenset({
    "register()", "registry.register()", "ops.register()",
})
# jax entry points whose function argument becomes traced code.
TRACING_CALLS = frozenset({
    "jit", "pallas_call", "scan", "vmap", "pmap", "custom_vjp",
    "custom_jvp", "checkpoint", "remat", "shard_map", "while_loop",
    "fori_loop", "cond", "switch", "defvjp", "defjvp",
})
# Module prefixes that must not be called from traced code (host-impure).
TRACE_BANNED_MODULE_PREFIXES = (
    ("time", "host clock read inside a traced function"),
    ("numpy.random", "host RNG inside a traced function (use the ctx key)"),
    ("random", "host RNG inside a traced function (use the ctx key)"),
    ("mxnet_tpu.telemetry", "telemetry from traced code runs at trace "
                            "time only and vanishes from the compiled "
                            "program — record at the dispatch site"),
)
# Telemetry instrument method names (module-global Counter/Gauge/
# Histogram objects created from the telemetry registry).
TELEMETRY_INSTRUMENT_METHODS = frozenset({"inc", "observe", "set", "dec"})
# Parameter names that are NOT traced arrays in op-impl signatures.
UNTRACED_PARAM_NAMES = frozenset({
    "self", "cls", "ctx", "attrs", "key", "is_train", "platform",
    "mesh", "sharding", "axis", "name",
})

# ------------------------------------------------------------------- locks
# Thread-entry markers: functions handed to these run on another thread.
THREAD_TARGET_CALLS = frozenset({
    "Thread", "threading.Thread", "Timer", "threading.Timer",
})
THREAD_REGISTER_CALLS = frozenset({
    "signal.signal", "atexit.register", "weakref.finalize",
})
# Method names that are thread entries by framework contract.
THREAD_ENTRY_METHOD_NAMES = frozenset({
    "do_GET", "do_POST", "do_PUT", "do_DELETE", "handle", "handle_error",
    "service_actions", "run",
})
# Lock-ish constructors (Condition aliases the lock it wraps).
LOCK_CONSTRUCTORS = frozenset({
    "Lock", "RLock", "Condition", "threading.Lock", "threading.RLock",
    "threading.Condition",
})

# --------------------------------------------------------------- env-docs
ENV_VAR_PATTERN = r"\b((?:MXTPU|BENCH)_[A-Z0-9_]+)\b"
ENV_DOC = "docs/how_to/env_var.md"
# Extra scan surface beyond mxnet_tpu/ (repo-relative).
ENV_EXTRA_FILES = ("bench.py",)
ENV_EXTRA_DIRS = ("tools",)
# Documented knobs that are read outside the scanned surface (tests/,
# pytest.ini, examples) — documented-but-not-in-source is fine for these.
ENV_DOC_ONLY_OK = frozenset({
    "MXTPU_TPU_TESTS",      # read by tests/test_tpu_consistency.py gate
    "MXTPU_LC_PLATFORM",    # read by examples/transformer-lm/train_long_context.py
})
