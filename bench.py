"""Benchmark: ResNet-50 ImageNet-shape training throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline", ...}.
Baseline: the reference's strongest published single-device number —
ResNet-50 training, batch 32, P100: 181.53 img/s (BASELINE.md,
docs/how_to/perf.md:132-139).  vs_baseline = ours / 181.53.

Also reports MFU = achieved model FLOP/s over the chip's peak bf16 FLOP/s
(peak looked up from the device_kind; "mfu": null when the kind is unknown).

Failure behaviour: backend init runs under a watchdog — if jax can't
produce a device within BENCH_INIT_TIMEOUT_S (default 240s), or anything
else raises, the bench prints a JSON line with an "error" field and exits
non-zero; it never answers with numbers from an earlier run.
BENCH_DEVICE_CHECK=1 makes it probe the backend, print the device line,
and exit without benchmarking.

The run uses the FusedTrainer fast path (whole train step = one XLA
computation, buffer donation, bf16 compute with fp32 master weights —
the TPU-native equivalent of the reference's fp32 cuDNN path).
"""
import json
import os
import sys
import threading
import time

import numpy as np

BASELINE_IMG_S = 181.53  # P100 ResNet-50 train b32 (docs/how_to/perf.md:132-139)

# ResNet-50 @ 224x224: ~4.089 GFLOP forward per image (2 FLOPs/MAC);
# training step ~= 3x forward (fwd + 2x in bwd).
TRAIN_FLOPS_PER_IMG = 3 * 4.089e9

# The device-kind -> peak FLOP/s table lives in the telemetry perf
# plane (mxnet_tpu/telemetry/perf.py:PEAK_TFLOPS, round 22) — ONE
# table, so bench MFU and the live program_mfu gauge can never
# disagree.  _peak_flops below delegates to it.


def _emit(payload):
    print(json.dumps(payload), flush=True)


def _bench_trend_check(current_fallback=None):
    """Run the committed-trajectory regression sentinel
    (tools/bench_trend.py) and surface its table on stderr; returns its
    exit code (0 clean, 1 regression/fallback, negative = the sentinel
    itself failed).  ``current_fallback`` marks the round being captured
    RIGHT NOW as an artifact fallback, so a non-live round is loud in
    its own log instead of a footnote discovered rounds later."""
    import subprocess

    script = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "tools", "bench_trend.py")
    cmd = [sys.executable, script]
    if current_fallback:
        cmd += ["--current-fallback", str(current_fallback)[:200]]
    try:
        r = subprocess.run(cmd, capture_output=True, text=True,
                           timeout=120)
        text = (r.stdout or "") + (r.stderr or "")
        if text.strip():
            print("[bench_trend] " + text.strip().replace(
                "\n", "\n[bench_trend] "), file=sys.stderr, flush=True)
        return r.returncode
    except Exception as exc:  # noqa: BLE001 — the sentinel must not kill the bench
        print("[bench_trend] sentinel failed: %r" % (exc,),
              file=sys.stderr, flush=True)
        return -1


def _fail(msg, metric="resnet50_train_imgs_per_sec_per_chip"):
    """A failed run says so: the error, value 0, and the caller's
    non-zero exit.  Nothing measured earlier is embedded in its place."""
    payload = {"metric": metric, "value": 0.0, "unit": "img/s",
               "vs_baseline": 0.0, "error": msg}
    # regression sentinel: the failing round reports its own
    # non-liveness on stderr
    payload["bench_trend_rc"] = _bench_trend_check(current_fallback=msg)
    _emit(payload)


def _peak_flops(device_kind):
    """Peak FLOP/s for a device kind — the telemetry perf plane's
    shared table (None on a miss; callers record a
    ``peak_flops_unknown`` note instead of guessing)."""
    from mxnet_tpu.telemetry import perf as _perf

    return _perf.peak_flops(device_kind)


def _init_backend(timeout_s, retry_timeout_s, notes):
    """Initialize the jax backend under a two-window watchdog; returns
    ``(devices, attempts)`` where attempts counts jax.devices() calls
    (1 = clean first try) — recorded in the JSON next to init_notes so
    a slow or retried init is diagnosable from the output alone.

    jax backend init is not interruptible from Python, so the watchdog
    cannot re-run an init that hangs — instead it retries by EXTENDING
    the deadline once (``BENCH_INIT_RETRY_TIMEOUT_S``, default 2x the first
    window) before hard-exiting with the diagnostic JSON line the driver
    can parse.  An init that *raises* is genuinely retried once.  Every
    attempt lands in ``notes`` (emitted as ``init_notes`` in the bench
    JSON), so a slow-but-successful init is visible instead of silent.

    Round 21 adds PHASE attribution: init walks three phases — ``import``
    (the jax import itself), ``device enumeration`` (``jax.devices()``,
    where the plugin handshake lives), ``first compile`` (a 1-element
    jitted add, the first XLA client round-trip) — and the watchdog
    stamps the in-flight phase into every timeout note, so a hung
    artifact says WHICH phase wedged instead of just "init timed out".
    """
    state = {"done": False, "phase": "import"}
    deadline = {"at": time.monotonic() + timeout_s, "extended": False}

    def watchdog():
        while not state["done"]:
            now = time.monotonic()
            if now >= deadline["at"]:
                if not deadline["extended"]:
                    deadline["extended"] = True
                    deadline["at"] = now + retry_timeout_s
                    notes.append(
                        "backend init exceeded the %ds window during "
                        "phase '%s'; watchdog extended once for a %ds "
                        "retry window"
                        % (timeout_s, state["phase"], retry_timeout_s))
                else:
                    _fail("backend init timed out after retry "
                          "(%ds + %ds windows) during phase '%s': %s"
                          % (timeout_s, retry_timeout_s, state["phase"],
                             "; ".join(notes)))
                    os._exit(2)
            time.sleep(1.0)

    threading.Thread(target=watchdog, daemon=True).start()
    tic = time.monotonic()
    attempts = 0
    try:
        import jax

        state["phase"] = "device enumeration"
        try:
            attempts += 1
            devices = jax.devices()
        except Exception as exc:  # noqa: BLE001 — plugin flake: retry once
            notes.append("device enumeration raised %r; retrying once"
                         % (exc,))
            time.sleep(2.0)
            attempts += 1
            devices = jax.devices()
        state["phase"] = "first compile"
        import jax.numpy as jnp

        jax.block_until_ready(jax.jit(lambda x: x + 1)(jnp.zeros((1,))))
        init_s = time.monotonic() - tic
        if init_s > min(timeout_s, 60):
            notes.append("backend init took %.1fs (last phase: %s)"
                         % (init_s, state["phase"]))
        return devices, attempts
    finally:
        state["done"] = True  # disarm even when init raises


def main():
    if "--shard-micro" in sys.argv:
        # subprocess mode for _shard_micro on single-device hosts: the
        # parent owns the accelerator, this process runs the virtual
        # CPU mesh and prints ONE json line
        _emit(_shard_micro_body())
        return 0
    from mxnet_tpu import compile_cache

    compile_cache.enable()
    timeout_s = int(os.environ.get("BENCH_INIT_TIMEOUT_S", "240"))
    retry_s = int(os.environ.get("BENCH_INIT_RETRY_TIMEOUT_S",
                                 str(2 * timeout_s)))
    init_notes = []
    try:
        devices, init_attempts = _init_backend(timeout_s, retry_s, init_notes)
    except Exception as exc:  # noqa: BLE001 — diagnostic JSON is the contract
        _fail("backend init failed after retry: %r (%s)"
              % (exc, "; ".join(init_notes) or "first attempt"))
        return 2
    if not devices:
        _fail("backend initialized but exposed no devices")
        return 2
    dev = devices[0]
    kind = getattr(dev, "device_kind", str(dev))

    if os.environ.get("BENCH_DEVICE_CHECK"):
        _emit({"metric": "device_check", "value": 1, "unit": "devices",
               "vs_baseline": 0.0, "platform": dev.platform,
               "device_kind": kind, "n_devices": len(devices),
               "init_attempts": init_attempts,
               **({"init_notes": init_notes} if init_notes else {})})
        return 0

    try:
        return _bench(dev, kind, init_notes, init_attempts)
    except Exception as exc:  # noqa: BLE001
        _fail("bench failed on %s: %r" % (kind, exc))
        return 2


def _dispatch_micro():
    """Executor hot-path micro-bench (round 6): Python-overhead-per-step
    of the Module-path train step and recompiles across re-binds.

    Times 100 fused train-step dispatches on a tiny (near-no-op) graph —
    the graph computes nothing worth measuring, so the per-step cost IS
    the host-side overhead (input gather, jit cache lookup, dispatch).
    Then re-binds the same symbol structure across 3 bucket shapes twice:
    with the program cache on, the second sweep must hit the cache and
    the `recompiles` delta should be 0.
    """
    import jax

    from mxnet_tpu import sym, telemetry as tm
    from mxnet_tpu.context import default_accelerator_context
    from mxnet_tpu.telemetry import perf as _perf

    was_enabled = tm.enabled()
    perf_was = _perf.enabled()
    tm.enable()
    try:
        ctx = default_accelerator_context()
        net = sym.SoftmaxOutput(
            sym.FullyConnected(sym.Variable("data"), num_hidden=8,
                               name="bench_fc"),
            name="softmax")
        shapes = [(8, 16), (8, 32), (8, 64)]
        compile_ctr = tm.get_registry().get("executor_compile_total")

        def sweep():
            last = None
            for shp in shapes:
                last = net.simple_bind(ctx, data=shp)
                last.forward(is_train=True)
                last.backward()
            return last

        ex = sweep()                      # warm: one trace per shape
        before = compile_ctr.total()
        ex = sweep()                      # re-bind the same 3 structures
        recompiles = compile_ctr.total() - before

        # arm the perf plane only AFTER the recompile sweep: the
        # one-time cost capture re-traces the program for lower(), and
        # that bookkeeping trace must not read as a cache miss above
        _perf.enable()
        ex.forward(is_train=True)
        ex.backward()                     # warm + one-time cost capture
        jax.block_until_ready(ex.outputs[0]._read())
        _perf.reset(costs=False)          # keep cost rows, drop warmup wall
        n = 100
        tic = time.perf_counter()
        for _ in range(n):
            ex.forward(is_train=True)
            ex.backward()
        jax.block_until_ready(ex.outputs[0]._read())
        dt = time.perf_counter() - tic
        out = {"dispatch_us_per_step": round(dt / n * 1e6, 1),
               "recompiles": int(recompiles)}
        # agreement check (round 22): bench-side MFU (plane cost row
        # FLOPs over the loop's own wall) vs the plane's program_mfu
        # (same FLOPs over the wall its dispatch sites accumulated) —
        # the two denominators measure the same loop, so the values
        # must track each other
        prof = _perf.profile_payload(topn=0)
        row = next((p for p in prof["programs"]
                    if p["program"] == getattr(ex, "_program_label", None)),
                   None)
        if row and row.get("flops") and prof.get("peak_flops") and dt > 0:
            out["dispatch_bench_mfu"] = round(
                row["flops"] * n / (dt * prof["peak_flops"]), 6)
            if row.get("mfu") is not None:
                out["dispatch_program_mfu"] = round(row["mfu"], 6)
        return out
    finally:
        if not was_enabled:
            tm.disable()
        if not perf_was:
            _perf.disable()
            _perf.reset()


def _kv_update_micro():
    """KVStore update-path micro-bench (round 7): eager per-key push/pull
    vs the bucketed jit-fused engine (kvstore_fused.py) on a ~100-param
    model.

    Each timed step is the Module-path kvstore half: one batched
    ``push(keys, grads)`` (reduce + optimizer update) + one batched
    ``pull(keys, outs)``.  Eager pays ~6 tiny dispatches per key; fused
    pays one compiled program per bucket — the reported ratio is the
    per-step dispatch-overhead win.  ``kv_buckets`` records the fused
    plan size under the default MXTPU_KV_BUCKET_MB.
    """
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd

    rng = np.random.RandomState(7)
    # ~100 keys, conv/bias-shaped mix (~1.7MB total) like a small convnet
    shapes = ([(128, 32), (32,), (64, 64), (64,)] * 25)
    weights = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    grads = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
    keys = list(range(len(shapes)))

    def run(fused):
        prev = os.environ.get("MXTPU_FUSED_UPDATE")
        os.environ["MXTPU_FUSED_UPDATE"] = "1" if fused else "0"
        try:
            kv = mx.kv.create("local")
            kv.set_optimizer(mx.optimizer.create(
                "sgd", learning_rate=0.05, momentum=0.9,
                rescale_grad=1.0 / 32))
            kv.init(keys, [nd.array(w) for w in weights])
            gnds = [[nd.array(g)] for g in grads]
            outs = [nd.zeros(s) for s in shapes]

            def step():
                kv.push(keys, gnds)
                kv.pull(keys, outs)

            for _ in range(3):  # warmup: plan build + bucket compiles
                step()
            jax.block_until_ready([o._read() for o in outs])
            n = 30
            tic = time.perf_counter()
            for _ in range(n):
                step()
            jax.block_until_ready([o._read() for o in outs])
            dt = (time.perf_counter() - tic) / n
            nbuckets = kv._fused.num_buckets if kv._fused is not None else 0
            return dt, nbuckets
        finally:
            if prev is None:
                os.environ.pop("MXTPU_FUSED_UPDATE", None)
            else:
                os.environ["MXTPU_FUSED_UPDATE"] = prev

    eager_dt, _ = run(False)
    fused_dt, nbuckets = run(True)
    return {"kv_update_us_per_step": round(fused_dt * 1e6, 1),
            "kv_update_us_per_step_eager": round(eager_dt * 1e6, 1),
            "kv_update_speedup": round(eager_dt / max(fused_dt, 1e-9), 1),
            "kv_buckets": nbuckets}


def _pipeline_micro():
    """Async-pipeline micro-bench (round 8): the Module-fit hot loop with
    device-resident fused metrics + the bounded in-flight window
    (MXTPU_ASYNC_DEPTH) vs the eager per-batch-sync loop, and step_multi
    vs single-step dispatch on the same workload — the regression
    tracker for the round-5 finding that step_multi came out SLOWER than
    single dispatch once its host stacking tax was counted.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import engine, sym, telemetry as tm

    was_enabled = tm.enabled()
    tm.enable()
    prevs = {k: os.environ.get(k)
             for k in ("MXTPU_FUSED_METRICS", "MXTPU_ASYNC_DEPTH")}
    try:
        data = sym.Variable("data")
        net = sym.SoftmaxOutput(
            sym.FullyConnected(data, name="pipe_fc", num_hidden=64),
            name="softmax")
        rs = np.random.RandomState(3)
        nsteps, b = 16, 64
        x = rs.uniform(-1, 1, (b * nsteps, 128)).astype(np.float32)
        y = rs.randint(0, 64, b * nsteps).astype(np.float32)

        def run_loop(fused, depth, epochs=3):
            os.environ["MXTPU_FUSED_METRICS"] = "1" if fused else "0"
            os.environ["MXTPU_ASYNC_DEPTH"] = str(depth)
            it = mx.io.NDArrayIter(x, y, batch_size=b)
            mod = mx.mod.Module(net)
            mod.bind(data_shapes=it.provide_data,
                     label_shapes=it.provide_label)
            mod.init_params()
            mod.init_optimizer(optimizer="sgd", optimizer_params=(
                ("learning_rate", 0.05),))
            metric = mx.metric.create("acc")

            def epoch():
                # fit's steady-state body: dispatch, enqueue metric,
                # bound the window; values only read at the boundary
                it.reset()
                metric.reset()
                window = engine.AsyncWindow()
                for batch in it:
                    mod.forward_backward(batch)
                    mod.update()
                    mod.update_metric(metric, batch.label)
                    window.push(mod._output_handles())
                window.drain()
                metric.get_global_name_value()

            epoch()  # warm: compiles + metric kernels
            reg = tm.get_registry()
            stall = reg.get("trainer_host_stall_seconds")
            syncs = reg.get("metric_host_sync_total")
            s0 = stall.sum(site="window") if stall is not None else 0.0
            c0 = syncs.total() if syncs is not None else 0.0
            tic = time.perf_counter()
            for _ in range(epochs):
                epoch()
            dt = time.perf_counter() - tic
            stall_us = ((stall.sum(site="window") - s0) / (epochs * nsteps)
                        * 1e6 if stall is not None else 0.0)
            sync_per_epoch = ((syncs.total() - c0) / epochs
                              if syncs is not None else 0.0)
            return (dt / (epochs * nsteps) * 1e6, stall_us, sync_per_epoch)

        eager_us, _, eager_syncs = run_loop(fused=False, depth=1)
        fused_d1_us, _, _ = run_loop(fused=True, depth=1)
        fused_us, stall_us, fused_syncs = run_loop(fused=True, depth=2)

        # --- step_multi vs single-step dispatch, same workload ---------
        from mxnet_tpu.trainer import FusedTrainer

        k = 8
        tr = FusedTrainer(net, optimizer="sgd",
                          optimizer_params={"lr": 0.05,
                                            "rescale_grad": 1.0 / b})
        tr.init(data=(b, 128))
        xb = jax.device_put(x[:b])
        yb = jax.device_put(y[:b])

        def barrier():
            name = sorted(tr.params)[0]
            return float(np.asarray(tr.params[name]).ravel()[0])

        tr.step(data=xb, softmax_label=yb)  # compile
        barrier()
        iters = 48
        tic = time.perf_counter()
        for _ in range(iters):
            tr.step(data=xb, softmax_label=yb)
        barrier()
        single_us = (time.perf_counter() - tic) / iters * 1e6

        stacked = {"data": jnp.stack([xb] * k),
                   "softmax_label": jnp.stack([yb] * k)}
        tr.step_multi(**stacked)  # compile (pre-stacked, non-donated)
        barrier()
        calls = max(iters // k, 1)
        tic = time.perf_counter()
        for _ in range(calls):
            tr.step_multi(**stacked)
        barrier()
        multi_us = (time.perf_counter() - tic) / (calls * k) * 1e6

        return {
            "pipeline_us_per_step": round(fused_us, 1),
            "pipeline_us_per_step_fused_d1": round(fused_d1_us, 1),
            "pipeline_us_per_step_eager": round(eager_us, 1),
            "pipeline_fused_speedup": round(eager_us / max(fused_us, 1e-9), 2),
            "host_stall_us_per_step": round(stall_us, 1),
            "metric_sync_per_epoch": round(fused_syncs, 1),
            "metric_sync_per_epoch_eager": round(eager_syncs, 1),
            "step_single_us_per_step": round(single_us, 1),
            "step_multi_us_per_step": round(multi_us, 1),
            "steps_per_call_speedup": round(
                single_us / max(multi_us, 1e-9), 2),
        }
    finally:
        for k_, v_ in prevs.items():
            if v_ is None:
                os.environ.pop(k_, None)
            else:
                os.environ[k_] = v_
        if not was_enabled:
            tm.disable()


def _survival_micro():
    """Survival-layer micro-bench (round 15): what checkpointing costs
    the training loop.  ckpt_capture_us_per_step is the HOT-LOOP tax —
    the async device-copy dispatch at a snapshot step (the fetch + file
    IO run on the writer thread and must not appear here);
    ckpt_write_ms is the background writer's wall time for the full
    state (fetch + fsync + atomic publish); ckpt_resume_ms is
    checksum-validated restore."""
    import tempfile

    import numpy as np

    from mxnet_tpu import checkpoint as ck
    from mxnet_tpu import sym
    from mxnet_tpu.trainer import FusedTrainer

    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=256,
                           name="surv_fc"), name="softmax")
    rs = np.random.RandomState(11)
    b = 64
    x = rs.uniform(-1, 1, (b, 512)).astype(np.float32)
    y = rs.randint(0, 256, b).astype(np.float32)
    tr = FusedTrainer(net, optimizer="adam",
                      optimizer_params={"lr": 0.05,
                                        "rescale_grad": 1.0 / b})
    tr.init(data=(b, 512))
    tr.step(data=x, softmax_label=y)  # compile
    name = sorted(tr.params)[0]
    float(np.asarray(tr.params[name]).ravel()[0])  # barrier

    n = 40
    tic = time.perf_counter()
    for _ in range(n):
        tr.step(data=x, softmax_label=y)
    float(np.asarray(tr.params[name]).ravel()[0])
    plain_us = (time.perf_counter() - tic) / n * 1e6

    out = {}
    with tempfile.TemporaryDirectory() as d:
        writes = []
        tic = time.perf_counter()
        for i in range(n):
            tr.step(data=x, softmax_label=y)
            if i % 10 == 0:  # capture WITHOUT draining: dispatch only
                writes.append(tr.save_state(d, background=True))
        float(np.asarray(tr.params[name]).ravel()[0])
        armed_us = (time.perf_counter() - tic) / n * 1e6
        for w in writes:
            w.wait()
        tic = time.perf_counter()
        tr.save_state(d, background=False)
        write_ms = (time.perf_counter() - tic) * 1e3
        tic = time.perf_counter()
        tr.restore_state(d)
        resume_ms = (time.perf_counter() - tic) * 1e3
        state_bytes = sum(
            int(v.size) * np.dtype(v.dtype).itemsize
            for v in tr._checkpoint_arrays().values())
    out["ckpt_step_us_plain"] = round(plain_us, 1)
    out["ckpt_step_us_armed"] = round(armed_us, 1)
    out["ckpt_capture_us_per_step"] = round(armed_us - plain_us, 1)
    out["ckpt_write_ms"] = round(write_ms, 2)
    out["ckpt_resume_ms"] = round(resume_ms, 2)
    out["ckpt_state_bytes"] = int(state_bytes)
    return out


def _health_micro():
    """Health-layer micro-bench (round 9): the fused training hot loop
    with MXTPU_SENTINEL off vs on (the in-program isfinite+norm
    accumulator; <3% overhead target — the sentinel adds one tiny
    reduction to an already-compiled step and ZERO host syncs), and the
    flight recorder's per-record host cost (a bounded ring append).
    """
    import numpy as np

    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.telemetry import health
    from mxnet_tpu.trainer import FusedTrainer
    from mxnet_tpu import sym

    was_enabled = tm.enabled()
    tm.enable()
    prev = os.environ.get("MXTPU_SENTINEL")
    try:
        net = sym.SoftmaxOutput(
            sym.FullyConnected(sym.Variable("data"), num_hidden=64,
                               name="health_fc"),
            name="softmax")
        rs = np.random.RandomState(9)
        b = 64
        x = rs.uniform(-1, 1, (b, 128)).astype(np.float32)
        y = rs.randint(0, 64, b).astype(np.float32)

        def run(sentinel):
            os.environ["MXTPU_SENTINEL"] = "1" if sentinel else "0"
            tr = FusedTrainer(net, optimizer="sgd",
                              optimizer_params={"lr": 0.05,
                                                "rescale_grad": 1.0 / b})
            tr.init(data=(b, 128))
            tr.step(data=x, softmax_label=y)  # compile
            health.sentinel_check()
            name = sorted(tr.params)[0]
            float(np.asarray(tr.params[name]).ravel()[0])  # barrier
            n = 60
            tic = time.perf_counter()
            for _ in range(n):
                tr.step(data=x, softmax_label=y)
            health.sentinel_check()
            float(np.asarray(tr.params[name]).ravel()[0])
            return (time.perf_counter() - tic) / n * 1e6

        off_us = run(False)
        on_us = run(True)

        # flight-recorder record cost: the pure host-side ring append
        # the fit loops pay per step
        n = 20000
        tic = time.perf_counter()
        for i in range(n):
            health.record_step(loop="bench", step=i, depth=2,
                               dispatch_s=0.0)
        rec_us = (time.perf_counter() - tic) / n * 1e6
        return {
            "health_sentinel_us_per_step": round(on_us, 1),
            "health_sentinel_us_per_step_off": round(off_us, 1),
            "health_sentinel_overhead_pct": round(
                (on_us - off_us) / max(off_us, 1e-9) * 100.0, 2),
            "flight_record_us": round(rec_us, 3),
        }
    finally:
        if prev is None:
            os.environ.pop("MXTPU_SENTINEL", None)
        else:
            os.environ["MXTPU_SENTINEL"] = prev
        if not was_enabled:
            tm.disable()


def _shard_micro_body():
    """Sharded-update micro-bench (round 11): the fused kvstore bucket
    step with the cross-replica sharded update (MXTPU_SHARD_UPDATE=1,
    arXiv:2004.13336) vs the replicated per-key bucket programs, on the
    process mesh.  Reports the per-step dispatch cost of each, the
    optimizer-state bytes per replica (the 1/N residency win), and the
    logical collective payload per sharded step."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry as tm
    from mxnet_tpu.parallel.mesh import global_mesh
    from jax.sharding import NamedSharding, PartitionSpec as P

    was_enabled = tm.enabled()
    tm.enable()
    prev = os.environ.get("MXTPU_SHARD_UPDATE")
    prev_cap = os.environ.get("MXTPU_KV_BUCKET_MB")
    try:
        mesh = global_mesh()
        repl = NamedSharding(mesh, P())
        rng = np.random.RandomState(11)
        # deliberately small keys + a tiny bucket cap: the section
        # measures DISPATCH/RESIDENCY structure (sharded vs replicated,
        # bytes per replica, collective payload), and virtual-CPU rigs
        # serialize every mesh collective through the host cores —
        # MB-scale buckets there turn one step into seconds of
        # rendezvous without changing any reported ratio
        os.environ.setdefault("MXTPU_KV_BUCKET_MB", "0.05")
        shapes = [(64, 37), (37,), (128, 16), (19,)] * 6
        weights = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
        grads = [rng.uniform(-1, 1, s).astype(np.float32) for s in shapes]
        keys = list(range(len(shapes)))

        def run(shard):
            os.environ["MXTPU_SHARD_UPDATE"] = "1" if shard else "0"
            kv = mx.kv.create("local")
            kv.set_optimizer(mx.optimizer.create(
                "adam", learning_rate=1e-3, rescale_grad=1.0 / 64))
            kv.init(keys, [nd.array(w) for w in weights])
            gnds = [[nd.NDArray(jax.device_put(g, repl))] for g in grads]
            outs = [nd.zeros(s) for s in shapes]

            def step():
                kv.push(keys, gnds)
                kv.pull(keys, outs)

            for _ in range(3):  # warmup: plan build + bucket compiles
                step()
            jax.block_until_ready([o._read() for o in outs])
            coll = tm.get_registry().get("executor_collective_bytes_total")
            c0 = coll.total() if coll is not None else 0
            n = 20
            tic = time.perf_counter()
            for _ in range(n):
                step()
            jax.block_until_ready([o._read() for o in outs])
            dt = (time.perf_counter() - tic) / n
            cps = ((coll.total() - c0) / n) if coll is not None else 0
            return dt, kv._fused.state_memory(), cps

        repl_dt, repl_mem, _ = run(False)
        shard_dt, shard_mem, coll_per_step = run(True)
        return {
            "shard_update_us_per_step": round(shard_dt * 1e6, 1),
            "shard_update_us_per_step_replicated": round(repl_dt * 1e6, 1),
            "optimizer_state_bytes_per_replica": int(
                shard_mem["per_replica_bytes"]),
            "optimizer_state_bytes_per_replica_replicated": int(
                repl_mem["per_replica_bytes"]),
            "collective_bytes_per_step": int(coll_per_step),
            "shard_replicas": int(shard_mem["replicas"]),
            "shard_buckets": int(shard_mem["sharded_buckets"]),
        }
    finally:
        if prev is None:
            os.environ.pop("MXTPU_SHARD_UPDATE", None)
        else:
            os.environ["MXTPU_SHARD_UPDATE"] = prev
        if prev_cap is None:
            os.environ.pop("MXTPU_KV_BUCKET_MB", None)
        else:
            os.environ["MXTPU_KV_BUCKET_MB"] = prev_cap
        if not was_enabled:
            tm.disable()


def _shard_micro():
    """Run the sharded-update micro on this process's mesh when it has
    >= 2 devices (the MULTICHIP path), else in a fresh subprocess on an
    8-virtual-CPU mesh (the backend is already owned by this process,
    so a single-chip host cannot re-init it for a second mesh)."""
    import jax

    if len(jax.devices()) >= 2:
        return _shard_micro_body()
    import subprocess
    import sys

    # the child is pinned to the CPU: the chip stays with this process
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               MXTPU_PLATFORM="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "")
                          + " --xla_force_host_platform_device_count=8"))
    r = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--shard-micro"],
                       capture_output=True, text=True, timeout=600, env=env)
    for line in reversed(r.stdout.strip().splitlines()):
        try:
            payload = json.loads(line)
        except json.JSONDecodeError:
            continue
        payload["shard_mesh"] = "8-virtual-cpu-subprocess"
        return payload
    return {"shard_error": "subprocess rc=%d: %s"
            % (r.returncode, (r.stderr or r.stdout)[-300:])}


_DIST_PS_WORKER = r'''
import os, sys, time
import numpy as np
import mxnet_tpu as mx

kv = mx.kv.create("dist_sync")
keys = list(range(8))
shapes = [(256, 64)] * 8
kv.init(keys, [mx.nd.ones(s) for s in shapes])
kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.01,
                                     rescale_grad=1.0))
grads = [[mx.nd.ones(s)] for s in shapes]
outs = [mx.nd.zeros(s) for s in shapes]
kv.push(keys, grads); kv.pull(keys, outs)  # warm
kv.barrier()
n = 20
tic = time.perf_counter()
for _ in range(n):
    kv.push(keys, grads)
    kv.pull(keys, outs)
us = (time.perf_counter() - tic) / n * 1e6
if kv.rank == 0:
    print('{"dist_ps_us": %f}' % us, flush=True)
kv.barrier()
'''

_DIST_ELASTIC_WORKER = r'''
import os, time
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=2")
slot = int(os.environ["MXTPU_ELASTIC_SLOT"])
gen = int(os.environ["MXTPU_DIST_GENERATION"])
if slot == 1 and gen == 0:
    os.environ["MXTPU_FAULT_PLAN"] = "host_crash:crash_after:6"
os.environ["MXTPU_ASYNC_DEPTH"] = "1"
import jax
jax.config.update("jax_platforms", "cpu")
import numpy as np
import mxnet_tpu as mx
from mxnet_tpu import io as mx_io, sym
from mxnet_tpu.parallel import dist
from mxnet_tpu.parallel.mesh import create_mesh
from mxnet_tpu.trainer import FusedTrainer

OUT = os.environ["DIST_MICRO_OUT"]
net = sym.SoftmaxOutput(
    sym.FullyConnected(sym.Variable("data"), num_hidden=32, name="fc"),
    sym.Variable("softmax_label"), name="softmax")
rs = np.random.RandomState(5)
X = rs.uniform(-1, 1, (160, 16)).astype(np.float32)
Y = rs.randint(0, 10, 160).astype(np.float32)


def main():
    np.random.seed(0)
    mx.random.seed(0)
    tr = FusedTrainer(net, optimizer="sgd",
                      optimizer_params={"lr": 0.05},
                      mesh=create_mesh((2,), ("data",)))
    train = mx_io.NDArrayIter(X, Y, batch_size=8)
    marked = []

    def cb(param):
        if not marked:
            marked.append(1)
            with open(os.path.join(OUT, "gen%d_first_step_%d"
                                   % (gen, slot)), "w") as f:
                f.write(repr(time.time()))

    tr.fit(train, num_epoch=30, resume=True, batch_end_callback=cb)


dist.elastic_main(main)
'''


def _dist_micro():
    """Multi-host runtime micro (round 17, docs/multihost.md): the
    per-step kvstore cost of the collective dist_sync path (fused
    bucketed dispatch — the cross-host all-reduce is in-trace) vs the
    PS transport (per-key RPCs over the 2-worker/1-server local rig),
    plus generation_failover_ms — the end-to-end wall time from a
    SIGKILL-shaped host death to the shrunk generation's first resumed
    training step under the elastic launcher (detect via lease expiry
    + relaunch + checkpoint resume + re-bind)."""
    import re
    import subprocess
    import sys
    import tempfile
    from datetime import datetime

    import numpy as np

    import mxnet_tpu as mx

    out = {}
    # collective transport, in-process: batched push/pull through the
    # fused bucket engine (same math a pod runs over DCN)
    kv = mx.kv.create("dist_sync")
    keys = list(range(8))
    shapes = [(256, 64)] * 8
    kv.init(keys, [mx.nd.ones(s) for s in shapes])
    kv.set_optimizer(mx.optimizer.create("sgd", learning_rate=0.01,
                                         rescale_grad=1.0))
    grads = [[mx.nd.ones(s)] for s in shapes]
    outs_ = [mx.nd.zeros(s) for s in shapes]
    kv.push(keys, grads)
    kv.pull(keys, outs_)
    outs_[0].asnumpy()
    n = 20
    tic = time.perf_counter()
    for _ in range(n):
        kv.push(keys, grads)
        kv.pull(keys, outs_)
    outs_[0].asnumpy()
    out["dist_step_us_per_step_collective"] = round(
        (time.perf_counter() - tic) / n * 1e6, 1)

    repo = os.path.dirname(os.path.abspath(__file__))
    launch = os.path.join(repo, "tools", "launch.py")
    # every child below is pinned to the CPU: this process holds the
    # chip, a chip belongs to one process at a time, and a child that
    # reached for it would fail or hang
    env = dict(os.environ, JAX_PLATFORMS="cpu", MXTPU_PLATFORM="cpu",
               PYTHONPATH=repo + os.pathsep + os.environ.get(
                   "PYTHONPATH", ""))
    with tempfile.TemporaryDirectory() as d:
        # PS transport: real worker+server processes on localhost
        ps_path = os.path.join(d, "ps_worker.py")
        with open(ps_path, "w") as f:
            f.write(_DIST_PS_WORKER)
        r = subprocess.run(
            [sys.executable, launch, "-n", "2", "-s", "1",
             "--launcher", "local", sys.executable, ps_path],
            capture_output=True, text=True, timeout=300, env=env)
        m = re.search(r'\{"dist_ps_us": ([0-9.]+)\}', r.stdout)
        if m:
            out["dist_step_us_per_step_ps"] = round(float(m.group(1)), 1)
        else:
            out["dist_ps_error"] = "rc=%d: %s" % (
                r.returncode, (r.stderr or r.stdout)[-200:])

        # elastic failover: kill one of two hosts mid-epoch, measure
        # death-observed -> first resumed step of the shrunk generation
        ew_path = os.path.join(d, "elastic_worker.py")
        with open(ew_path, "w") as f:
            f.write(_DIST_ELASTIC_WORKER)
        eenv = dict(env, DIST_MICRO_OUT=d, MXTPU_CKPT_DIR=os.path.join(
            d, "ckpt"), MXTPU_CKPT_EVERY="2", MXTPU_COORD_LEASE_S="1.0",
            MXTPU_DIST_BARRIER_TIMEOUT_S="8", XLA_FLAGS="")
        r = subprocess.run(
            [sys.executable, launch, "-n", "2", "--max-restarts", "1",
             "--launcher", "elastic", "--rejoin-progress", "3",
             "--exit-grace", "60", sys.executable, ew_path],
            capture_output=True, text=True, timeout=420, env=eenv)
        log = r.stdout + r.stderr
        crash = re.search(
            r"^([0-9-]+ [0-9:,]+) launch\.py slot 1 crashed", log, re.M)
        marker = os.path.join(d, "gen1_first_step_0")
        if crash and os.path.exists(marker):
            t_crash = datetime.strptime(
                crash.group(1), "%Y-%m-%d %H:%M:%S,%f").timestamp()
            with open(marker) as f:
                t_resume = float(f.read())
            out["generation_failover_ms"] = round(
                (t_resume - t_crash) * 1e3, 1)
            out["dist_generations"] = len(re.findall(
                r"launch\.py generation \d+: world=", log))
        else:
            out["dist_failover_error"] = "rc=%d: %s" % (
                r.returncode, log[-200:])
    return out


def _fleet_micro():
    """Fleet observability micro (round 18, docs/multihost.md): the
    coordinator-side federation + straggler plane on an in-process
    2-member rig — fleet_scrape_ms (one /metrics.json federation sweep
    over both members' HTTP endpoints), straggler_detect_ms (first
    inflated heartbeat to the coordinator naming the slow host), and
    merge_trace_ms (two synthetic per-host flight dumps folded into one
    chrome trace by tools/fleetstat.py merge-trace)."""
    import importlib.util
    import tempfile

    from mxnet_tpu import telemetry as tm
    from mxnet_tpu.parallel.coordinator import CoordinatorService

    out = {}
    was_enabled = tm.enabled()
    tm.enable()
    servers = []
    svc = None
    try:
        # two per-"host" registries behind real HTTP = a 2-member fleet
        # in one process (the same shape a pod runs, minus the DCN)
        for i in range(2):
            reg = tm.Registry()
            reg.get_or_create(tm.Counter, "trainer_samples_total",
                              "samples", ("loop",)).inc(64 * (i + 1),
                                                        loop="fused")
            servers.append(tm.start_http_server(0, registry=reg))
        svc = CoordinatorService(port=0, lease_s=1.0).start()
        for i, srv in enumerate(servers):
            svc.join("h%d" % i, host="h%d" % i, rank=i,
                     telemetry_addr="127.0.0.1:%d"
                                    % srv.server_address[1])
        tic = time.perf_counter()
        snap = svc.scraper.scrape_once()
        out["fleet_scrape_ms"] = round(
            (time.perf_counter() - tic) * 1e3, 2)
        if not all(s.get("ok") for s in snap.values()):
            out["fleet_scrape_error"] = "scrape failed: %r" % (snap,)

        # injected slow host: h1's heartbeats carry a 10x step wall;
        # measure first slow report -> the coordinator naming it
        tic = time.perf_counter()
        named = None
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            svc.heartbeat("h0", steps={"count": 32, "step_wall_s": 0.01,
                                       "dispatch_s": 0.002})
            svc.heartbeat("h1", steps={"count": 32, "step_wall_s": 0.10,
                                       "dispatch_s": 0.002})
            named = svc.cluster().get("straggler")
            if named:
                break
            time.sleep(0.05)
        if named and named.get("member") == "h1":
            out["straggler_detect_ms"] = round(
                (time.perf_counter() - tic) * 1e3, 1)
        else:
            out["straggler_error"] = "straggler never flagged: %r" % (
                named,)
    finally:
        for srv in servers:
            srv.shutdown()
        if svc is not None:
            svc.stop()
        if not was_enabled:
            tm.disable()

    # merge-trace over synthetic two-host dumps (h1's clock runs 2.5s
    # behind, its dump carries the matching offset estimate)
    spec = importlib.util.spec_from_file_location(
        "mxtpu_fleetstat",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "tools", "fleetstat.py"))
    fleetstat = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(fleetstat)
    with tempfile.TemporaryDirectory() as d:
        paths = []
        for i in range(2):
            skew = 0.0 if i == 0 else -2.5
            ring = [{"seq": s, "step": s, "loop": "fused",
                     "t": 1000.0 + 0.01 * s + skew,
                     "wall_s": 0.01, "dispatch_s": 0.004}
                    for s in range(256)]
            dump = {"version": 2, "ring": ring,
                    "identity": {"host": "h%d" % i, "rank": i,
                                 "generation": 0,
                                 "clock": {"offset_s": -skew}}}
            p = os.path.join(d, "flight_h%d.json" % i)
            with open(p, "w") as f:
                json.dump(dump, f)
            paths.append(p)
        tic = time.perf_counter()
        fleetstat.merge_trace(paths, os.path.join(d, "trace.json"))
        out["merge_trace_ms"] = round((time.perf_counter() - tic) * 1e3, 2)
    return out


def _serve_micro():
    """Serving micro-bench (round 10): the continuous-batching decode
    scheduler (mxnet_tpu/serving/) under a synthetic Poisson arrival
    load — served tokens/s, p50/p99 time-to-first-token, and mean slot
    occupancy.  Drives the SlotScheduler directly (the HTTP layer adds
    ~connection overhead, not decode behavior); prompts span several
    prefill buckets so admission exercises the bucketed-length programs
    the way mixed traffic would.
    """
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models, telemetry as tm
    from mxnet_tpu.models.decode import KVDecoder
    from mxnet_tpu.serving import SlotScheduler

    was_enabled = tm.enabled()
    tm.enable()
    sched = None
    try:
        L_, H_, D_, T_, V_ = 2, 4, 128, 128, 512
        net = models.transformer.transformer_lm(
            num_layers=L_, num_heads=H_, d_model=D_, seq_len=T_,
            vocab_size=V_)
        ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                             data=(1, T_), softmax_label=(1, T_))
        rs = np.random.RandomState(11)
        params = {}
        for name, arr in ex.arg_dict.items():
            if name in ("data", "softmax_label"):
                continue
            arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
            params[name] = arr
        dec = KVDecoder(params, num_layers=L_, num_heads=H_, max_len=T_)
        sched = SlotScheduler(dec, num_slots=4, queue_size=64,
                              default_deadline_ms=120000)
        # warm every program mixed traffic will hit: one request per
        # prefill bucket + the shared step/adopt programs
        for plen in (5, 12, 30):
            sched.generate(rs.randint(0, V_, plen), max_new_tokens=2,
                           timeout=120)
        n_req, max_new = 24, 12
        reqs = []
        tic = time.perf_counter()
        ticks0 = sched.stats["ticks"]
        slot_ticks0 = sched.stats["slot_ticks"]
        for _ in range(n_req):
            time.sleep(float(rs.exponential(0.01)))  # Poisson arrivals
            reqs.append(sched.submit(
                rs.randint(0, V_, int(rs.randint(4, 32))),
                max_new_tokens=max_new))
        for r in reqs:
            r.wait(300)
        dt = time.perf_counter() - tic
        toks = sum(len(r.tokens) for r in reqs)
        ttfts = sorted(r.ttft for r in reqs if r.ttft is not None)
        ticks = sched.stats["ticks"] - ticks0
        slot_ticks = sched.stats["slot_ticks"] - slot_ticks0
        pct = lambda q: ttfts[min(int(q * len(ttfts)), len(ttfts) - 1)]
        return {
            "serve_tokens_per_sec": round(toks / dt, 1),
            "serve_ttft_p50_ms": round(pct(0.50) * 1e3, 1),
            "serve_ttft_p99_ms": round(pct(0.99) * 1e3, 1),
            "serve_slot_occupancy_mean": round(
                slot_ticks / max(ticks, 1), 2),
            "serve_outcomes_ok": sum(1 for r in reqs
                                     if r.outcome == "ok"),
            "serve_requests": n_req,
        }
    finally:
        if sched is not None:
            sched.close()
        if not was_enabled:
            tm.disable()


def _router_micro():
    """Serving-fleet micro-bench (round 19, ISSUE 15).  Two parts:

    (1) a Poisson soak through the replica router
    (serving/router.py) against a 2-replica in-process fleet sharing
    one decoder — fleet-wide served tokens/s, p50/p99 TTFT through the
    router, and the retry counter (0 on a healthy fleet);

    (2) paged-vs-contiguous co-batching at EQUAL slot count: a mixed
    long/short workload where the long requests share an 80-token
    system prefix.  The contiguous backend prefills every long prompt
    at its full bucket; the paged backend (MXTPU_KV_BLOCK-style pages
    + prefix cache) computes the shared prefix once and prefills only
    the tails — the acceptance ratio
    ``paged_vs_contiguous_tokens_per_sec`` (>= 1.2 on this rig).
    """
    import json as _json
    import threading
    import urllib.request

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models, telemetry as tm
    from mxnet_tpu.models.decode import KVDecoder
    from mxnet_tpu.serving import (ReplicaRouter, SlotScheduler,
                                   serve_decoder, start_router)

    was_enabled = tm.enabled()
    tm.enable()
    out = {}
    servers, scheds = [], []
    rsrv = router = None
    try:
        L_, H_, D_, T_, V_ = 2, 4, 128, 128, 512
        net = models.transformer.transformer_lm(
            num_layers=L_, num_heads=H_, d_model=D_, seq_len=T_,
            vocab_size=V_)
        ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                             data=(1, T_), softmax_label=(1, T_))
        rs = np.random.RandomState(19)
        params = {}
        for name, arr in ex.arg_dict.items():
            if name in ("data", "softmax_label"):
                continue
            arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
            params[name] = arr
        dec = KVDecoder(params, num_layers=L_, num_heads=H_, max_len=T_)

        # ---- (1) routed Poisson soak over a 2-replica fleet ----------
        for _ in range(2):
            s, sch = serve_decoder(dec, port=0, num_slots=4,
                                   queue_size=64,
                                   default_deadline_ms=120000)
            servers.append(s)
            scheds.append(sch)
        addrs = ["127.0.0.1:%d" % s.server_address[1] for s in servers]
        router = ReplicaRouter(replicas=addrs, scrape_s=0.2, retries=2)
        rsrv = start_router(router, port=0)
        rport = rsrv.server_address[1]

        def post(body):
            req = urllib.request.Request(
                "http://127.0.0.1:%d/generate" % rport,
                data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                return r.status, _json.loads(r.read())

        # warm every replica's programs (each bucket mixed traffic hits)
        for sch in scheds:
            for plen in (5, 12, 30):
                sch.generate(rs.randint(0, V_, plen), max_new_tokens=2,
                             timeout=300)
        retries0 = tm.get_registry().get("router_retries_total").total()
        n_req, max_new = 24, 12
        results, errors = [], []

        def client(i):
            try:
                prompt = rs.randint(0, V_, int(rs.randint(4, 32)))
                results.append(post({"prompt": prompt.tolist(),
                                     "max_tokens": max_new}))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        tic = time.perf_counter()
        threads = []
        for i in range(n_req):
            time.sleep(float(rs.exponential(0.01)))  # Poisson arrivals
            t = threading.Thread(target=client, args=(i,))
            t.start()
            threads.append(t)
        for t in threads:
            t.join(300)
        dt = time.perf_counter() - tic
        if errors:
            raise errors[0]
        toks = sum(o["n_tokens"] for _, o in results)
        ttfts = sorted(o["ttft_ms"] for _, o in results
                       if o.get("ttft_ms") is not None)
        pct = lambda q: ttfts[min(int(q * len(ttfts)), len(ttfts) - 1)]
        out["serve_fleet_tokens_per_sec"] = round(toks / dt, 1)
        out["serve_fleet_ttft_p50_ms"] = round(pct(0.50), 1)
        out["serve_fleet_ttft_p99_ms"] = round(pct(0.99), 1)
        out["serve_fleet_ok"] = sum(1 for st, o in results
                                    if st == 200 and o["outcome"] == "ok")
        out["serve_fleet_requests"] = n_req
        out["serve_fleet_replicas"] = len(addrs)
        out["router_retry_total"] = int(
            tm.get_registry().get("router_retries_total").total()
            - retries0)

        # ---- (2) paged vs contiguous co-batching, equal slot count ---
        prefix = rs.randint(0, V_, 80)       # the shared system prompt

        def mixed_workload(seed):
            w = []
            r2 = np.random.RandomState(seed)
            for i in range(20):
                if i % 4 == 3:               # short, prefix-free
                    w.append(r2.randint(0, V_, int(r2.randint(4, 16))))
                else:                        # long, shared prefix
                    w.append(np.concatenate(
                        [prefix,
                         r2.randint(0, V_, int(r2.randint(4, 16)))]))
            return w

        def soak(sched, seed):
            # warm the buckets THIS traffic hits with a disjoint prefix
            # (the measured run still pays its one shared-prefix fill)
            warm = np.concatenate(
                [rs.randint(0, V_, 80), rs.randint(0, V_, 8)])
            sched.generate(warm, max_new_tokens=2, timeout=300)
            sched.generate(rs.randint(0, V_, 6), max_new_tokens=2,
                           timeout=300)
            sched.generate(rs.randint(0, V_, 12), max_new_tokens=2,
                           timeout=300)
            reqs = []
            r3 = np.random.RandomState(seed + 1)
            tic = time.perf_counter()
            for p in mixed_workload(seed):
                time.sleep(float(r3.exponential(0.002)))
                reqs.append(sched.submit(p, max_new_tokens=8))
            for r in reqs:
                r.wait(300)
            dt = time.perf_counter() - tic
            assert all(r.outcome == "ok" for r in reqs), \
                [r.outcome for r in reqs]
            return sum(len(r.tokens) for r in reqs) / dt

        cont = SlotScheduler(dec, num_slots=4, queue_size=64,
                             default_deadline_ms=120000, paged=False)
        try:
            cont_tps = soak(cont, 77)
        finally:
            cont.close()
        paged = SlotScheduler(dec, num_slots=4, queue_size=64,
                              default_deadline_ms=120000, paged=True,
                              kv_block=16)
        try:
            paged_tps = soak(paged, 77)
            pstats = paged.paged_stats()
        finally:
            paged.close()
        out["serve_paged_tokens_per_sec"] = round(paged_tps, 1)
        out["serve_contiguous_tokens_per_sec"] = round(cont_tps, 1)
        out["paged_vs_contiguous_tokens_per_sec"] = round(
            paged_tps / cont_tps, 3)
        out["serve_prefix_pages"] = pstats["prefix_pages"]
        return out
    finally:
        if rsrv is not None:
            rsrv.shutdown()
        if router is not None:
            router.stop()
        for s in servers:
            s.shutdown()
        for sch in scheds:
            sch.close()
        if not was_enabled:
            tm.disable()


def _trace_micro():
    """Request-tracing overhead micro-bench (round 20, ISSUE 16).

    The SAME routed Poisson workload as ``_router_micro``'s soak — a
    2-replica in-process fleet behind the replica router — run three
    ways: tracing OFF, tracing ON at sample rate 1.0, and sampled at
    25%.  Span recording is pure host-side dict/ring writes (never a
    device sync — tools/lint.py proves the tick-path callers), so the
    acceptance gate is ``trace_overhead_pct`` <= 2 on this rig.  The
    on-run's SLO plane numbers ride along (every routed request feeds
    the router's burn-rate windows).
    """
    import json as _json
    import threading
    import urllib.request

    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import models, telemetry as tm
    from mxnet_tpu.models.decode import KVDecoder
    from mxnet_tpu.serving import (ReplicaRouter, serve_decoder,
                                   start_router)
    from mxnet_tpu.telemetry import tracing

    was_enabled = tm.enabled()
    was_tracing = tracing.trace_on()
    sample0 = os.environ.get("MXTPU_TRACE_SAMPLE")
    tm.enable()
    out = {}
    servers, scheds = [], []
    rsrv = router = None
    try:
        L_, H_, D_, T_, V_ = 2, 4, 128, 128, 512
        net = models.transformer.transformer_lm(
            num_layers=L_, num_heads=H_, d_model=D_, seq_len=T_,
            vocab_size=V_)
        ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                             data=(1, T_), softmax_label=(1, T_))
        rs = np.random.RandomState(20)
        params = {}
        for name, arr in ex.arg_dict.items():
            if name in ("data", "softmax_label"):
                continue
            arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
            params[name] = arr
        dec = KVDecoder(params, num_layers=L_, num_heads=H_, max_len=T_)

        for _ in range(2):
            s, sch = serve_decoder(dec, port=0, num_slots=4,
                                   queue_size=64,
                                   default_deadline_ms=120000)
            servers.append(s)
            scheds.append(sch)
        addrs = ["127.0.0.1:%d" % s.server_address[1] for s in servers]
        router = ReplicaRouter(replicas=addrs, scrape_s=0.2, retries=2)
        rsrv = start_router(router, port=0)
        rport = rsrv.server_address[1]

        def post(body):
            req = urllib.request.Request(
                "http://127.0.0.1:%d/generate" % rport,
                data=_json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=300) as r:
                return r.status, _json.loads(r.read())

        for sch in scheds:      # warm every bucket the traffic hits
            for plen in (5, 12, 30):
                sch.generate(rs.randint(0, V_, plen), max_new_tokens=2,
                             timeout=300)
        n_req, max_new = 24, 12

        def soak(seed):
            rs2 = np.random.RandomState(seed)
            prompts = [rs2.randint(0, V_, int(rs2.randint(4, 32)))
                       for _ in range(n_req)]
            results, errors = [], []

            def client(p):
                try:
                    results.append(post({"prompt": p.tolist(),
                                         "max_tokens": max_new}))
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)

            tic = time.perf_counter()
            threads = []
            for p in prompts:
                time.sleep(float(rs2.exponential(0.01)))
                t = threading.Thread(target=client, args=(p,))
                t.start()
                threads.append(t)
            for t in threads:
                t.join(300)
            dt = time.perf_counter() - tic
            if errors:
                raise errors[0]
            return sum(o["n_tokens"] for _, o in results) / dt

        # identical workload (same seed) three ways: A/B the span path.
        # One unmeasured soak settles threads/caches, then each arm of
        # the off/on comparison takes its best of two runs — the soak
        # is Poisson-arrival threaded HTTP, whose run-to-run scheduling
        # jitter would otherwise swamp a <=2% span-recording overhead.
        tracing.enable_tracing(False)
        soak(100)
        off_tps = max(soak(101) for _ in range(2))
        tracing.clear_spans()
        os.environ["MXTPU_TRACE_SAMPLE"] = "1"
        tracing.enable_tracing(True)
        on_tps = max(soak(101) for _ in range(2))
        n_spans = len(tracing.spans())
        tracing.clear_spans()
        os.environ["MXTPU_TRACE_SAMPLE"] = "0.25"
        sampled_tps = soak(101)
        out["serve_trace_off_tokens_per_sec"] = round(off_tps, 1)
        out["serve_trace_on_tokens_per_sec"] = round(on_tps, 1)
        out["serve_trace_sampled_tokens_per_sec"] = round(sampled_tps, 1)
        out["trace_overhead_pct"] = round(
            (off_tps - on_tps) / off_tps * 100.0, 2)
        out["serve_trace_spans"] = n_spans
        slo = router.slo.snapshot()
        out["slo_burn_rate_availability_60s"] = \
            slo["windows"]["60s"]["burn_rate"]["availability"]
        out["slo_violations_availability"] = \
            slo["violations_total"]["availability"]
        return out
    finally:
        tracing.enable_tracing(was_tracing)
        tracing.clear_spans()
        if sample0 is None:
            os.environ.pop("MXTPU_TRACE_SAMPLE", None)
        else:
            os.environ["MXTPU_TRACE_SAMPLE"] = sample0
        if rsrv is not None:
            rsrv.shutdown()
        if router is not None:
            router.stop()
        for s in servers:
            s.shutdown()
        for sch in scheds:
            sch.close()
        if not was_enabled:
            tm.disable()


def _autotune_micro():
    """Autotune micro-bench (round 21, ISSUE 18).  Four numbers:

    - ``paged_attn_{gather,kernel}_us_per_step``: one full decode step
      (all layers) over the paged pool through the PR-15 gather
      materialization vs the tuned paged-attention schedule the
      autotuner picks for this rig — plus the ratio as
      ``paged_attn_kernel_speedup`` (higher is better; the acceptance
      gate is >= 1.2x);
    - ``autotune_search_ms``: wall cost of the bounded first search
      (``MXTPU_AUTOTUNE_TRIALS`` candidates, warmup + best-of-k each);
    - ``autotune_cache_hit``: a SECOND in-process run against the file
      the first search persisted — 1 iff it reused the winner with
      zero new trials (the whole point of the on-disk cache);
    - ``epilogue_tuned_vs_default_us``: the residual epilogue's tuned
      ``block_rows`` vs the static default, same jitted kernel timing
      ``tune()`` used (negative = the tuned block is faster).

    Runs against a private temp ``MXTPU_SCHEDULE_CACHE`` in search mode
    and restores the caller's autotune state on the way out.
    """
    import functools
    import shutil
    import tempfile

    import jax
    import jax.numpy as jnp

    from mxnet_tpu import autotune as at, telemetry as tm
    from mxnet_tpu.autotune import search as at_search
    from mxnet_tpu.ops import paged_attention as pa
    from mxnet_tpu.ops import residual_epilogue as repi

    was_enabled = tm.enabled()
    tm.enable()
    cache0 = os.environ.get("MXTPU_SCHEDULE_CACHE")
    tmpd = tempfile.mkdtemp(prefix="mxtpu_autotune_bench_")
    os.environ["MXTPU_SCHEDULE_CACHE"] = \
        "search:" + os.path.join(tmpd, "schedules.json")
    at.reset()
    out = {}
    try:
        # serving-shaped decode step: B slots, M pages/slot (a 512-token
        # context window), half-full ragged cursors (make_bench_fn's
        # honest steady-state mix) — the regime where the gather path
        # materializes every page and a liveness-bounded walk does not
        B, H, M, block, dh, L = 4, 8, 32, 16, 64, 2
        dtype = jnp.float32
        platform = jax.default_backend()
        sig = pa.keysig(B, H, M, block, dh, dtype)
        default = pa.default_schedule(platform, block, dh, dtype)
        cands = pa.candidate_schedules(platform, block, dh, dtype)
        bench = functools.partial(pa.make_bench_fn, B=B, H=H, M=M,
                                  block=block, dh=dh, L=L, dtype=dtype)
        tic = time.perf_counter()
        winner = at.ensure("paged_attention", sig, default, cands, bench,
                           warmup=1, best_of=3)
        out["autotune_search_ms"] = round(
            (time.perf_counter() - tic) * 1e3, 1)
        gather_us = at.measure(bench({"impl": "gather"}),
                               warmup=1, best_of=5)
        kernel_us = at.measure(bench(winner), warmup=1, best_of=5)
        out["paged_attn_gather_us_per_step"] = round(gather_us, 1)
        out["paged_attn_kernel_us_per_step"] = round(kernel_us, 1)
        out["paged_attn_kernel_impl"] = winner.get("impl", "gather")
        out["paged_attn_kernel_speedup"] = round(gather_us / kernel_us, 2)
        # second in-process run: forget the memo (NOT the file), re-ensure
        trials0 = at_search._TM_TRIALS.total()
        hits0 = at_search._TM_CACHE.value(result="hit")
        at.reset()
        again = at.ensure("paged_attention", sig, default, cands, bench,
                          warmup=1, best_of=3)
        hit = (again == winner
               and at_search._TM_TRIALS.total() == trials0
               and at_search._TM_CACHE.value(result="hit") > hits0)
        out["autotune_cache_hit"] = int(hit)
        # epilogue knob: ResNet-tail shape, interpret timing on a
        # CPU rig (exactly what tune() itself measures)
        rows, channels = 2048, 256
        interp = jax.default_backend() != "tpu"
        tuned = repi.tune(rows, channels, interpret=interp)
        rs = np.random.RandomState(0)
        x2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(np.float32))
        s2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(np.float32))
        sc = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))
        b_ = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))

        def _epi_us(br):
            fn = jax.jit(functools.partial(
                repi._pallas_fwd, interpret=interp, block_rows=br))
            return at.measure(lambda: fn(x2, s2, sc, b_),
                              warmup=1, best_of=3)

        default_us = _epi_us(repi._default_block_rows(rows))
        tuned_us = _epi_us(int(tuned["block_rows"]))
        out["epilogue_tuned_block_rows"] = int(tuned["block_rows"])
        out["epilogue_tuned_vs_default_us"] = round(
            tuned_us - default_us, 1)
        return out
    finally:
        if cache0 is None:
            os.environ.pop("MXTPU_SCHEDULE_CACHE", None)
        else:
            os.environ["MXTPU_SCHEDULE_CACHE"] = cache0
        at.reset()
        shutil.rmtree(tmpd, ignore_errors=True)
        if not was_enabled:
            tm.disable()


def _sparse_micro():
    """Row-sparse embedding-update micro-bench (round 13): the fused
    sparse bucket (touched-rows-only jitted update, kvstore_fused +
    sparse.py) vs the dense-gradient path on a table whose row count
    dwarfs one batch's lookups — the regime where the dense scatter
    plus full-table optimizer sweep is the step bottleneck.

    Both sides run the same Module-path kvstore step (one batched push)
    with the same Adam state; the dense side is fed ``todense()`` of
    the identical row-sparse gradient, so the arithmetic being timed is
    equivalent.  Emits the ISSUE-9 acceptance ratio
    (``sparse_update_speedup`` >= 3 on this table), the touched-row
    fraction, and sustained touched-rows-per-second through the sparse
    path."""
    import jax
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu import nd, sparse

    rows = int(os.environ.get("BENCH_SPARSE_ROWS", "300000"))
    dim = int(os.environ.get("BENCH_SPARSE_DIM", "64"))
    lookups = int(os.environ.get("BENCH_SPARSE_LOOKUPS", "4096"))
    rng = np.random.RandomState(11)
    table = rng.uniform(-1, 1, (rows, dim)).astype(np.float32)
    idx_steps = [rng.randint(0, rows, lookups).astype(np.int32)
                 for _ in range(8)]
    val_steps = [rng.uniform(-1, 1, (lookups, dim)).astype(np.float32)
                 for _ in range(8)]
    uniq = np.mean([np.unique(i).size for i in idx_steps])

    def run(sparse_grads):
        kv = mx.kv.create("local")
        kv.set_optimizer(mx.optimizer.create(
            "adam", learning_rate=0.05, rescale_grad=1.0 / lookups))
        init = sparse.full_row_sparse(nd.array(table)) if sparse_grads \
            else nd.array(table)
        kv.init(0, init)
        grads = []
        for i, v in zip(idx_steps, val_steps):
            g = sparse.RowSparseNDArray(nd.NDArray(i), nd.NDArray(v),
                                        (rows, dim))
            grads.append([g] if sparse_grads else [g.todense()])

        def step(n):
            kv.push([0], grads[n % len(grads)])

        for w in range(3):
            step(w)
        jax.block_until_ready(kv._store[0]._read())
        n = 20
        tic = time.perf_counter()
        for s in range(n):
            step(s)
        jax.block_until_ready(kv._store[0]._read())
        return (time.perf_counter() - tic) / n

    dense_dt = run(False)
    sparse_dt = run(True)
    return {
        "sparse_update_us_per_step": round(sparse_dt * 1e6, 1),
        "sparse_update_us_per_step_dense": round(dense_dt * 1e6, 1),
        "sparse_update_speedup": round(dense_dt / max(sparse_dt, 1e-9), 1),
        "sparse_touched_row_fraction": round(float(uniq) / rows, 5),
        "embedding_rows_per_sec": round(uniq / max(sparse_dt, 1e-9)),
        "sparse_table_rows": rows,
    }


def _amp_micro():
    """AMP micro-bench (round 14, docs/amp.md): ResNet-50 training
    through the Module/Executor/KVStore path with MXTPU_AMP=bf16 +
    dynamic loss scaling vs plain fp32 — img/s per chip and MFU both
    ways (the ROADMAP >= 0.35 target's measurement), the loss-scale
    ladder's final state, and the fused residual-epilogue kernel's
    per-block time vs XLA's unfused elementwise chain.

    On the CPU fallback rig the model drops to the cifar-style
    resnet-8 at a small batch (recorded in ``amp_model``): the section
    then measures dispatch/machinery structure, not chip throughput.
    On a >=2-device host the Module binds across the process mesh, so
    the fused update runs the SHARDED bucket programs and the reported
    ``amp_master_bytes_per_replica`` is the 1/N master residency."""
    import jax
    import numpy as np

    import jax.numpy as jnp

    import mxnet_tpu as mx
    from mxnet_tpu import amp, models, nd
    from mxnet_tpu import executor as ex_mod
    from mxnet_tpu.io import DataBatch
    from mxnet_tpu.module import Module

    devs = jax.devices()
    on_cpu = devs[0].platform == "cpu"
    if on_cpu:
        layers, img, batch, iters = 8, 32, 8, 8
    else:
        layers, img = 50, 224
        batch = int(os.environ.get("BENCH_AMP_BATCH", "256"))
        iters = int(os.environ.get("BENCH_AMP_ITERS", "12"))
    nclass = 100 if on_cpu else 1000
    net = models.get_symbol(f"resnet-{layers}", num_classes=nclass,
                            image_shape=(3, img, img))
    rng = np.random.RandomState(11)
    data = rng.uniform(0, 1, (batch, 3, img, img)).astype(np.float32)
    labels = rng.randint(0, nclass, batch).astype(np.float32)
    mk_ctx = mx.cpu if on_cpu else mx.tpu
    contexts = [mk_ctx(i) for i in range(len(devs))] if len(devs) > 1 \
        else [mk_ctx(0)]

    def run(amp_on):
        for k, v in (("MXTPU_AMP", "bf16"),
                     ("MXTPU_LOSS_SCALE", "dynamic")):
            if amp_on:
                os.environ[k] = v
            else:
                os.environ.pop(k, None)
        amp.reset_scaler()
        ex_mod.program_cache_clear()
        mod = Module(net, context=contexts)
        mod.bind(data_shapes=[("data", data.shape)],
                 label_shapes=[("softmax_label", labels.shape)])
        mod.init_params(initializer=mx.init.Xavier())
        mod.init_optimizer(kvstore="local", optimizer="sgd",
                           optimizer_params={"learning_rate": 0.05,
                                             "momentum": 0.9})
        batch_nd = DataBatch(data=[nd.array(data)],
                             label=[nd.array(labels)])

        def step():
            mod.forward(batch_nd, is_train=True)
            mod.backward()
            mod.update()

        for _ in range(2):  # compile + settle
            step()
        ex = mod._exec_group.execs[0]
        pname = sorted(ex.arg_dict)[-1]
        jax.block_until_ready(ex.arg_dict[pname]._read())
        tic = time.perf_counter()
        for _ in range(iters):
            step()
        jax.block_until_ready(ex.arg_dict[pname]._read())
        dt = time.perf_counter() - tic
        mem = mod._kvstore._fused.state_memory() \
            if mod._kvstore is not None and mod._kvstore._fused else {}
        rep = amp.global_scaler().report() if amp_on else {}
        return batch * iters / dt, mem, rep

    fp32_rate, _, _ = run(False)
    amp_rate, mem, rep = run(True)

    # sharded fp32 masters (the MULTICHIP payload): bf16-STORED params
    # through the fused kvstore on the process mesh — masters ride the
    # sharded flat state at 1/N bytes per replica.  (The Module run
    # above keeps params f32 — there the params ARE the masters.)
    if len(devs) > 1:
        try:
            import jax as _jax
            from jax.sharding import NamedSharding, PartitionSpec as P
            from mxnet_tpu.parallel.mesh import global_mesh

            os.environ["MXTPU_AMP"] = "bf16"
            repl = NamedSharding(global_mesh(), P())
            kvm = mx.kv.create("local")
            kvm.set_optimizer(mx.optimizer.create(
                "sgd", learning_rate=0.05, momentum=0.9))
            mshapes = [(256, 64), (64,), (128, 32)]
            kvm.init(list(range(len(mshapes))),
                     [nd.array(rng.uniform(-1, 1, s).astype(
                         np.float32)).astype(jnp.bfloat16)
                      for s in mshapes])
            mgrads = [[nd.NDArray(_jax.device_put(rng.uniform(
                -0.1, 0.1, s).astype(np.float32), repl))]
                for s in mshapes]
            for _ in range(3):
                kvm.push(list(range(len(mshapes))), mgrads)
            mem = kvm._fused.state_memory()
        except Exception:  # noqa: BLE001 — payload stays Module-only
            pass
    os.environ.pop("MXTPU_AMP", None)
    os.environ.pop("MXTPU_LOSS_SCALE", None)
    amp.reset_scaler()

    out = {
        "amp_model": f"resnet-{layers}_b{batch}_{img}px"
                     + ("_cpu" if on_cpu else ""),
        "amp_imgs_per_sec": round(amp_rate, 1),
        "amp_imgs_per_sec_fp32": round(fp32_rate, 1),
        "amp_speedup": round(amp_rate / max(fp32_rate, 1e-9), 3),
        "amp_loss_scale_final": rep.get("scale"),
        "amp_overflows": rep.get("overflow_total"),
        "amp_skipped_steps": rep.get("skipped_steps_total"),
        "amp_master_bytes_per_replica": mem.get(
            "master_bytes_per_replica", 0),
        "amp_shard_replicas": mem.get("replicas", 1),
    }
    if not on_cpu:
        peak = _peak_flops(devs[0].device_kind)
        if peak and layers == 50:
            per_chip = amp_rate / len(devs)
            out["amp_mfu"] = round(
                per_chip * TRAIN_FLOPS_PER_IMG / peak, 4)
            out["amp_mfu_fp32"] = round(
                (fp32_rate / len(devs)) * TRAIN_FLOPS_PER_IMG / peak, 4)

    # --- fused residual-epilogue kernel vs XLA's unfused chain --------
    from mxnet_tpu.ops import residual_epilogue as re_mod

    n, h, w, c = (8, 14, 14, 256) if on_cpu else (64, 56, 56, 256)
    x = jnp.asarray(rng.uniform(-1, 1, (n, h, w, c)).astype(np.float32))
    s = jnp.asarray(rng.uniform(-1, 1, (n, h, w, c)).astype(np.float32))
    sc = jnp.asarray(rng.uniform(0.5, 1.5, (c,)).astype(np.float32))
    b = jnp.asarray(rng.uniform(-0.5, 0.5, (c,)).astype(np.float32))
    impl = "auto" if not on_cpu else "lax"

    fused = jax.jit(lambda x_, s_: re_mod.residual_epilogue(
        x_, s_, sc, b, channel_axis=-1, impl=impl,
        platform=devs[0].platform))
    unfused = jax.jit(lambda x_, s_: jnp.maximum(
        (x_ + s_) * sc.reshape(1, 1, 1, -1) + b.reshape(1, 1, 1, -1),
        0.0))

    def time_fn(fn):
        jax.block_until_ready(fn(x, s))
        reps = 30
        tic = time.perf_counter()
        for _ in range(reps):
            out_ = fn(x, s)
        jax.block_until_ready(out_)
        return (time.perf_counter() - tic) / reps * 1e6

    out["epilogue_us_per_block"] = round(time_fn(fused), 1)
    out["epilogue_us_per_block_xla"] = round(time_fn(unfused), 1)
    out["epilogue_block"] = f"{n}x{h}x{w}x{c}"
    return out


def _passes_micro():
    """Graph-rewrite pipeline micro-bench (round 12): bind/trace cost
    and node count with MXTPU_GRAPH_PASSES off vs on, per-pass node
    deltas, and the predict-path throughput with Conv+BN folding on vs
    off (the pass the serving path rides).

    The subject net is a conv+BN stack with residual elemwise chains
    and a constant subgraph — small enough for the CPU fallback rig,
    shaped so every pass has something to do.
    """
    import jax

    import mxnet_tpu as mx
    from mxnet_tpu import passes, sym
    from mxnet_tpu import executor as ex_mod
    from mxnet_tpu.context import default_accelerator_context
    from mxnet_tpu.predict import Predictor

    ctx = default_accelerator_context()
    shape = (8, 3, 32, 32)

    def build():
        d = sym.Variable("data")
        x = d
        for i, nf in enumerate((16, 16, 32, 32)):
            c = sym.Convolution(x, num_filter=nf, kernel=(3, 3), pad=(1, 1),
                                stride=(2, 2) if i == 2 else (1, 1),
                                no_bias=(i % 2 == 0), name=f"pm_c{i}")
            b = sym.BatchNorm(c, fix_gamma=False, name=f"pm_b{i}")
            a = sym.Activation(b, act_type="relu", name=f"pm_r{i}")
            # elemwise chain + duplicated subexpression per block
            x = sym.exp(sym.tanh(a * 0.5)) + sym.exp(sym.tanh(a * 0.5))
        x = sym.broadcast_add(x, sym.ones((1, 32, 1, 1)) * 0.125)
        fc = sym.FullyConnected(sym.Flatten(x), num_hidden=10, name="pm_fc")
        return sym.SoftmaxOutput(fc, label=sym.Variable("softmax_label"),
                                 name="softmax")

    net = build()

    def timed_bind(env_val):
        prior = os.environ.get("MXTPU_GRAPH_PASSES")
        os.environ["MXTPU_GRAPH_PASSES"] = env_val
        try:
            ex_mod.program_cache_clear()
            tic = time.perf_counter()
            ex = net.simple_bind(ctx, grad_req="null", data=shape)
            out = ex.forward(is_train=False)[0]
            jax.block_until_ready(out._read())
            return (time.perf_counter() - tic) * 1e3
        finally:
            if prior is None:
                os.environ.pop("MXTPU_GRAPH_PASSES", None)
            else:
                os.environ["MXTPU_GRAPH_PASSES"] = prior
    trace_ms_before = round(timed_bind("off"), 1)
    trace_ms_after = round(timed_bind("default"), 1)

    report = passes.pipeline_report(net)
    nodes_before = report[0]["nodes_before"] if report else None
    nodes_after = report[-1]["nodes_after"] if report else None

    # predict path: BN-fold on vs off, same checkpoint values
    rs = np.random.RandomState(0)
    probe = net.simple_bind(ctx, grad_req="null", data=shape)
    args, auxs = {}, {}
    for k_, v_ in probe.arg_dict.items():
        if k_ in ("data", "softmax_label"):
            continue
        args[k_] = mx.nd.array(
            rs.uniform(-0.25, 0.25, v_.shape).astype(np.float32))
    for k_, v_ in probe.aux_dict.items():
        lo, hi = (0.5, 1.5) if "var" in k_ else (-0.1, 0.1)
        auxs[k_] = mx.nd.array(
            rs.uniform(lo, hi, v_.shape).astype(np.float32))
    x = rs.uniform(-1, 1, shape).astype(np.float32)

    def infer_rate(env_val):
        prior = os.environ.get("MXTPU_GRAPH_PASSES")
        os.environ["MXTPU_GRAPH_PASSES"] = env_val
        try:
            ex_mod.program_cache_clear()
            p = Predictor(symbol=net, arg_params=dict(args),
                          aux_params=dict(auxs),
                          input_shapes={"data": shape})
            p.forward(data=x)
            p.get_output(0)  # compile + settle
            n = 30
            tic = time.perf_counter()
            for _ in range(n):
                p.forward(data=x)
                p.get_output(0)
            dt = time.perf_counter() - tic
            return shape[0] * n / dt, p._n_bn_folded
        finally:
            if prior is None:
                os.environ.pop("MXTPU_GRAPH_PASSES", None)
            else:
                os.environ["MXTPU_GRAPH_PASSES"] = prior

    rate_nofold, _ = infer_rate("0")
    rate_fold, n_folded = infer_rate("default")

    out = {
        "passes_trace_ms_before": trace_ms_before,
        "passes_trace_ms_after": trace_ms_after,
        "passes_nodes_before": nodes_before,
        "passes_nodes_after": nodes_after,
        "passes_convbn_folded": int(n_folded),
        "passes_infer_img_s_nofold": round(rate_nofold, 1),
        "passes_infer_img_s_bnfold": round(rate_fold, 1),
        "passes_bnfold_speedup": round(rate_fold / max(rate_nofold, 1e-9), 3),
    }
    for row in report:
        out[f"passes_nodes_after_{row['pass']}"] = row["nodes_after"]
    return out


def _bench(dev, kind, init_notes=(), init_attempts=1):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401 (sets matmul precision policy)
    from mxnet_tpu import models
    from mxnet_tpu.trainer import FusedTrainer

    batch = int(os.environ.get("BENCH_BATCH", "32"))
    # BENCH_EXPLAIN (round 22): arm the perf-attribution plane for the
    # whole bench so a profile document (ranked programs, cost rows,
    # MFU) can be written next to the headline number
    explain = os.environ.get("BENCH_EXPLAIN", "").strip()
    if explain:
        from mxnet_tpu.telemetry import perf as _perf

        _perf.enable()
    net = models.get_symbol("resnet-50", num_classes=1000)
    dtype = jnp.bfloat16 if os.environ.get("BENCH_DTYPE", "bf16") == "bf16" else jnp.float32

    tr = FusedTrainer(
        net,
        optimizer="sgd",
        optimizer_params={"lr": 0.1, "momentum": 0.9, "rescale_grad": 1.0 / batch},
        dtype=dtype,
    )
    tr.init(data=(batch, 3, 224, 224))

    # Synthetic batches staged on device BEFORE the timed loop: this
    # measures the training step, not the input pipeline, which a
    # training job overlaps with the step (docs/how_to/perf.md).  A few
    # distinct batches rotate so no per-step caching can help.
    rs = np.random.RandomState(0)
    staged = []
    for i in range(4):
        data = rs.uniform(0, 1, (batch, 3, 224, 224)).astype(np.float32)
        label = rs.randint(0, 1000, batch).astype(np.float32)
        staged.append({"data": jax.device_put(data),
                       "softmax_label": jax.device_put(label)})

    def fetch_barrier():
        # pulling real bytes of an updated parameter ends the timing
        # in work the device has actually finished
        name = sorted(tr.params)[0]
        return float(np.asarray(tr.params[name]).ravel()[0])

    for i in range(8):  # compile + settle
        tr.step(**staged[i % len(staged)])
    fetch_barrier()

    iters = int(os.environ.get("BENCH_ITERS", "60"))
    # steps-per-call: k steps fused into one dispatch (FusedTrainer.
    # step_multi, a lax.scan over the step body).  Amortizing the
    # per-call dispatch of small-batch steps is a framework feature,
    # not a bench trick (whether it pays on a local chip is ROADMAP
    # S5's to settle) — the training math is
    # step-for-step identical (tests/test_train.py::
    # test_step_multi_matches_sequential_steps).
    spc_env = os.environ.get("BENCH_STEPS_PER_CALL", "auto")
    spc = (8 if batch <= 64 else 1) if spc_env == "auto" else max(1, int(spc_env))
    if spc > 1:
        stacked = {
            k_: jnp.stack([staged[i % len(staged)][k_] for i in range(spc)])
            for k_ in ("data", "softmax_label")
        }
        tr.step_multi(**stacked)  # compile
        fetch_barrier()
        tr.step_multi(**stacked)  # settle
        fetch_barrier()
        calls = max(iters // spc, 1)
        tic = time.perf_counter()
        for _ in range(calls):
            tr.step_multi(**stacked)
        fetch_barrier()
        dt = time.perf_counter() - tic
        img_s = batch * spc * calls / dt
    else:
        tic = time.perf_counter()
        for i in range(iters):
            tr.step(**staged[i % len(staged)])
        fetch_barrier()
        dt = time.perf_counter() - tic
        img_s = batch * iters / dt
    peak = _peak_flops(kind)
    mfu = (img_s * TRAIN_FLOPS_PER_IMG / peak) if peak else None
    payload = {
        "metric": "resnet50_train_imgs_per_sec_per_chip",
        "value": round(img_s, 2),
        "unit": "img/s",
        "vs_baseline": round(img_s / BASELINE_IMG_S, 3),
        "captured_utc": time.strftime("%Y-%m-%d", time.gmtime()),
        "device_kind": kind,
        "batch": batch,
        "mfu": round(mfu, 4) if mfu is not None else None,
        "model_tflops_per_sec": round(img_s * TRAIN_FLOPS_PER_IMG / 1e12, 2),
        "steps_per_call": spc,
    }
    if peak is None:
        # an unknown device kind must leave a note, not a bare null MFU
        payload["peak_flops_unknown"] = (
            "device_kind %r has no telemetry/perf.py:PEAK_TFLOPS entry"
            % kind)
    payload["init_attempts"] = int(init_attempts)
    if init_notes:
        # a slow/retried backend init is a datapoint, not a silent event
        payload["init_notes"] = list(init_notes)
    if explain:
        # write the perf plane's full profile document (tools/explain.py
        # renders it); BENCH_EXPLAIN=1 picks a default path
        from mxnet_tpu.telemetry import perf as _perf

        out_path = explain if explain.lower() not in ("1", "true") \
            else "BENCH_EXPLAIN.json"
        try:
            with open(out_path, "w") as f:
                json.dump(_perf.profile_payload(topn=0), f, indent=1)
            payload["explain_path"] = out_path
        except OSError as exc:
            payload["explain_error"] = repr(exc)

    if os.environ.get("BENCH_EXTRAS", "1") == "1":
        # secondary datapoint (inference b32; P100 baseline 713.17 img/s)
        # under a watchdog: if its extra compile hangs, the ALREADY
        # MEASURED training number must still reach stdout — losing the
        # primary metric to an optional extra would repeat round 1's
        # silent-timeout failure
        # exactly-one-emit: whichever of (main thread, watchdog) claims
        # the flag first emits; the loser stays silent — otherwise a
        # score() finishing inside the watchdog's final window could
        # print the metric line twice
        lock = threading.Lock()
        state = {"emitted": False}

        def claim():
            with lock:
                if state["emitted"]:
                    return False
                state["emitted"] = True
                return True

        def extras_watchdog():
            deadline = time.monotonic() + float(
                os.environ.get("BENCH_EXTRAS_TIMEOUT_S", "480"))
            while time.monotonic() < deadline:
                if state["emitted"]:
                    return
                time.sleep(1.0)
            if claim():
                payload["extras_error"] = "inference extras timed out"
                _emit(payload)
                os._exit(0)

        threading.Thread(target=extras_watchdog, daemon=True).start()
        deadline = time.monotonic() + float(
            os.environ.get("BENCH_EXTRAS_TIMEOUT_S", "480")) - 20.0

        class _Extras(dict):
            """Every recorded extra lands in the payload IMMEDIATELY
            (under the emit lock) so a watchdog timeout in a LATER block
            cannot discard minutes of already-measured numbers."""

            def __setitem__(self, k, v):
                super().__setitem__(k, v)
                with lock:
                    if not state["emitted"]:
                        payload[k] = v

            def setdefault(self, k, v):
                if k not in self:
                    self[k] = v
                return self[k]

        extras = _Extras()

        def _time_steps(step_fn, barrier, iters):
            """warmup already done by caller; barrier -> timed loop ->
            barrier (the one copy of the measurement scaffold the
            single-batch blocks share)."""
            barrier()
            tic_ = time.perf_counter()
            for _ in range(iters):
                step_fn()
            barrier()
            return time.perf_counter() - tic_
        try:
            # inference: reuse the ALREADY-COMPILED trainer's params with
            # its eval graph — one forward-only compile, no separate
            # predictor build (round-2 extras timed out rebuilding one)
            infer_iters = 30
            warm = tr.eval(data=staged[0]["data"])  # compile
            # barrier on the warmup's OWN output: params have no data
            # dependency on an eval, so fetch_barrier() would let the
            # warmup execution bleed into the timed window
            float(np.asarray(warm[0]).ravel()[0])
            itic = time.perf_counter()
            for i in range(infer_iters):
                out = tr.eval(data=staged[i % len(staged)]["data"])
            float(np.asarray(out[0]).ravel()[0])
            idt = time.perf_counter() - itic
            inf = batch * infer_iters / idt
            extras["resnet50_infer_b32_imgs_per_sec"] = round(inf, 1)
            # methodology: the train symbol's eval forward reusing staged
            # train batches, NOT the predictor ABI path earlier rounds'
            # benchmark_score measured — keyed distinctly so round-over-
            # round ratios aren't misread as apples-to-apples
            extras["eval_forward_vs_p100_infer_baseline"] = round(
                inf / 713.17, 2)
        except Exception as exc:  # noqa: BLE001
            extras["extras_error"] = repr(exc)
        try:
            # large-batch train: the chip's best-case throughput (the b32
            # headline stays baseline-comparable; this shows the ceiling).
            # Needs a fresh compile for the new shape — only start it when
            # enough budget remains for compile (~60s) + measurement.
            big = int(os.environ.get("BENCH_LARGE_BATCH", "256"))
            if big > batch and time.monotonic() < deadline - 120:
                big_tr = FusedTrainer(
                    net, optimizer="sgd",
                    optimizer_params={"lr": 0.1, "momentum": 0.9,
                                      "rescale_grad": 1.0 / big},
                    dtype=dtype)
                big_tr.init(data=(big, 3, 224, 224))
                bdata = {"data": jax.device_put(rs.uniform(
                    0, 1, (big, 3, 224, 224)).astype(np.float32)),
                    "softmax_label": jax.device_put(
                        rs.randint(0, 1000, big).astype(np.float32))}
                big_tr.step(**bdata)  # compile
                bname = sorted(big_tr.params)[0]
                bbarrier = lambda: float(
                    np.asarray(big_tr.params[bname]).ravel()[0])
                bbarrier()
                big_tr.step(**bdata)  # settle
                biters = 12
                bdt = _time_steps(lambda: big_tr.step(**bdata),
                                  bbarrier, biters)
                big_img_s = big * biters / bdt
                extras["resnet50_train_b%d_imgs_per_sec" % big] = round(
                    big_img_s, 1)
                if peak:
                    extras["mfu_b%d" % big] = round(
                        big_img_s * TRAIN_FLOPS_PER_IMG / peak, 4)
            elif big > batch:
                extras["large_batch_skipped"] = "insufficient extras budget"
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # transformer-LM train + KV-cache decode: the beyond-parity
            # model family's own numbers, when budget remains
            if time.monotonic() < deadline - 150 and os.environ.get(
                    "BENCH_LM", "1") == "1":
                L_, H_, D_, T_, V_ = 4, 8, 512, 512, 8192
                lm = models.transformer.transformer_lm(
                    num_layers=L_, num_heads=H_, d_model=D_, seq_len=T_,
                    vocab_size=V_)
                lm_tr = FusedTrainer(
                    lm, optimizer="adam", optimizer_params={"lr": 1e-3},
                    dtype=dtype)
                bsz = 8
                lm_tr.init(data=(bsz, T_), softmax_label=(bsz, T_))
                toks = jax.device_put(rs.randint(
                    0, V_, (bsz, T_)).astype(np.float32))
                labs = jax.device_put(rs.randint(
                    0, V_, (bsz, T_)).astype(np.float32))
                lm_tr.step(data=toks, softmax_label=labs)  # compile
                lname = sorted(lm_tr.params)[0]
                lbarrier = lambda: float(
                    np.asarray(lm_tr.params[lname]).ravel()[0])
                lm_iters = 15
                ldt = _time_steps(
                    lambda: lm_tr.step(data=toks, softmax_label=labs),
                    lbarrier, lm_iters)
                extras["transformer_lm_train_tokens_per_sec"] = round(
                    bsz * T_ * lm_iters / ldt, 0)

                from mxnet_tpu.models.decode import KVDecoder

                dec = KVDecoder(lm_tr.params, num_layers=L_,
                                num_heads=H_, max_len=T_, dtype=dtype)
                dstate, dlog = dec.prefill(np.zeros((bsz, 32), np.int64))
                tok = np.asarray(dlog[:, -1]).argmax(-1)
                dstate, dwarm = dec.step(dstate, tok)   # compile
                float(np.asarray(dwarm).ravel()[0])     # warmup barrier
                dn = 40
                dtic = time.perf_counter()
                for _ in range(dn):
                    dstate, dlog2 = dec.step(dstate, tok)
                float(np.asarray(dlog2).ravel()[0])
                ddt = time.perf_counter() - dtic
                extras["kv_decode_tokens_per_sec"] = round(
                    bsz * dn / ddt, 1)
                # fused decode: the WHOLE n-token loop in one dispatch
                # (generate_scan) — decode's analog of steps-per-call.
                # The timed window includes the 8-token prefill dispatch
                # generate_scan performs internally, so the reported
                # rate (still counting only the 64 generated tokens) is
                # a conservative lower bound on the scan loop itself
                fn_tok = 64
                dec.generate_scan(np.zeros((bsz, 8), np.int64),
                                  fn_tok)           # compile
                ftic = time.perf_counter()
                dec.generate_scan(np.zeros((bsz, 8), np.int64), fn_tok)
                fdt = time.perf_counter() - ftic
                extras["kv_decode_fused_tokens_per_sec"] = round(
                    bsz * fn_tok / fdt, 1)
            elif os.environ.get("BENCH_LM", "1") == "1":
                extras["lm_skipped"] = "insufficient extras budget"
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # executor hot-path: dispatch_us_per_step (Python overhead of
            # a fused train-step) + recompiles across bucket-shape
            # re-binds (program cache regression tracker, ISSUE 2)
            if os.environ.get("BENCH_DISPATCH", "1") == "1":
                # per-key sets (dict.update bypasses _Extras.__setitem__,
                # which is what lands keys in the payload immediately)
                for k_, v_ in _dispatch_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # kvstore update hot-path: eager per-key push/pull vs the
            # bucketed jit-fused engine on a ~100-param model (ISSUE 3)
            if os.environ.get("BENCH_KV", "1") == "1":
                for k_, v_ in _kv_update_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # async-pipeline hot loop: fused device metrics + bounded
            # window vs the eager per-batch-sync loop, and the fixed
            # step_multi vs single dispatch (ISSUE 4)
            if os.environ.get("BENCH_PIPELINE", "1") == "1":
                for k_, v_ in _pipeline_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # health layer: sentinel-on vs sentinel-off fused-loop
            # overhead (<3% target) + flight-recorder per-record cost
            # (ISSUE 5)
            if os.environ.get("BENCH_HEALTH", "1") == "1":
                for k_, v_ in _health_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # survival layer: async-checkpoint capture tax on the hot
            # loop + writer wall time + validated-resume time (ISSUE 11)
            if os.environ.get("BENCH_CKPT", "1") == "1":
                for k_, v_ in _survival_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # mesh-sharded update path: sharded vs replicated bucket
            # step, optimizer-state bytes per replica, collective
            # payload — the MULTICHIP runs' primary section (ISSUE 7)
            if os.environ.get("BENCH_SHARD", "1") == "1":
                for k_, v_ in _shard_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # elastic multi-host runtime: collective-vs-PS kvstore step
            # cost + the generation failover wall time on the
            # multi-process CPU rig (ISSUE 13)
            if os.environ.get("BENCH_DIST", "1") == "1":
                for k_, v_ in _dist_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # fleet observability plane: federation scrape, straggler
            # detection latency, merge-trace cost (ISSUE 14)
            if os.environ.get("BENCH_FLEET", "1") == "1":
                for k_, v_ in _fleet_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # serving hot path: continuous-batching scheduler under a
            # Poisson arrival load — served tok/s, TTFT tail, slot
            # occupancy (ISSUE 6)
            if os.environ.get("BENCH_SERVE", "1") == "1":
                for k_, v_ in _serve_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # serving fleet: Poisson soak through the replica router +
            # paged-vs-contiguous co-batching at equal slots (ISSUE 15)
            if os.environ.get("BENCH_ROUTER", "1") == "1":
                for k_, v_ in _router_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # request tracing + SLO plane: the routed soak with span
            # recording off/on/sampled — trace_overhead_pct is the
            # host-side cost of the per-request lens (ISSUE 16)
            if os.environ.get("BENCH_TRACE", "1") == "1":
                for k_, v_ in _trace_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # schedule autotuner: paged-attention kernel vs gather per
            # decode step, search cost, persisted-cache reuse, and the
            # epilogue's tuned block_rows vs its default (ISSUE 18)
            if os.environ.get("BENCH_AUTOTUNE", "1") == "1":
                for k_, v_ in _autotune_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # graph-rewrite pipeline: bind/trace cost + node counts
            # passes-off vs on, and the Conv+BN-folded predict path
            # (ISSUE 8)
            if os.environ.get("BENCH_PASSES", "1") == "1":
                for k_, v_ in _passes_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # row-sparse embedding update: touched-rows-only fused
            # bucket vs the dense-gradient scatter path (ISSUE 9)
            if os.environ.get("BENCH_SPARSE", "1") == "1":
                for k_, v_ in _sparse_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        try:
            # first-class AMP: bf16 Module training vs fp32 (MFU toward
            # the ROADMAP >= 0.35 target), loss-scale ladder state, and
            # the fused residual-epilogue kernel vs XLA's chain; on a
            # multi-device host the Module spans the mesh, so masters
            # run SHARDED (1/N bytes per replica) — ISSUE 10
            if os.environ.get("BENCH_AMP", "1") == "1":
                for k_, v_ in _amp_micro().items():
                    extras[k_] = v_
        except Exception as exc:  # noqa: BLE001
            extras.setdefault("extras_error", repr(exc))
        # the MFU config is the bench's biggest resident (560M params:
        # ~7.8 GB of masters + Adam slots + bf16 cache on a 16 GB chip):
        # drop every earlier section's device state first, or their live
        # buffers + compiled-executable scratch tip it into
        # RESOURCE_EXHAUSTED (observed once the fused-decode extra
        # joined the lineup)
        import gc

        # (plain del per name: locals() is a snapshot in CPython, so
        # dynamic deletion would silently do nothing; the barrier
        # lambdas close over their trainers and must go too.  One guarded
        # del PER NAME — a grouped `del a, b, c` aborts at the first
        # unbound name, leaving the rest of a partially-initialized
        # section alive and defeating this cleanup's purpose)
        try:
            del big_tr
        except NameError:
            pass
        try:
            del bdata
        except NameError:
            pass
        try:
            del bbarrier
        except NameError:
            pass
        try:
            del lm_tr
        except NameError:
            pass
        try:
            del toks
        except NameError:
            pass
        try:
            del labs
        except NameError:
            pass
        try:
            del lbarrier
        except NameError:
            pass
        try:
            del dec
        except NameError:
            pass
        try:
            del dstate
        except NameError:
            pass
        try:
            del dlog
        except NameError:
            pass
        try:
            del dlog2
        except NameError:
            pass
        try:
            del dwarm
        except NameError:
            pass
        try:
            del tok
        except NameError:
            pass
        try:
            del tr
        except NameError:
            pass
        try:
            del staged
        except NameError:
            pass
        try:
            del fetch_barrier
        except NameError:
            pass
        gc.collect()
        try:
            # compute-bound MFU headline: a ~220M-param LM config where
            # the MXU is actually fed (ResNet-50-with-BN is HBM-roofline-
            # bound at ~0.175 on v5e; tools/probe_lm_mfu.py sweeps this
            # family with the SAME shared config + FLOP rule)
            if peak and time.monotonic() < deadline - 180 and \
                    os.environ.get("BENCH_LM_MFU", "1") == "1":
                from mxnet_tpu.models.transformer import (
                    MFU_HEADLINE_CONFIG, lm_train_flops_per_token)

                cfg = MFU_HEADLINE_CONFIG
                Tm, Vm = cfg["seq_len"], cfg["vocab_size"]
                Bm = int(os.environ.get("BENCH_LM_MFU_BATCH", "8"))
                big_lm = models.transformer.transformer_lm(**cfg)
                mtr = FusedTrainer(big_lm, optimizer="adam",
                                   optimizer_params={"lr": 1e-4},
                                   dtype=dtype)
                mtr.init(data=(Bm, Tm), softmax_label=(Bm, Tm))
                mtoks = jax.device_put(rs.randint(
                    0, Vm, (Bm, Tm)).astype(np.float32))
                mlabs = jax.device_put(rs.randint(
                    0, Vm, (Bm, Tm)).astype(np.float32))
                mtr.step(data=mtoks, softmax_label=mlabs)  # compile
                mname = sorted(mtr.params)[0]
                mbarrier = lambda: float(
                    np.asarray(mtr.params[mname]).ravel()[0])
                mbarrier()
                mdt = _time_steps(
                    lambda: mtr.step(data=mtoks, softmax_label=mlabs),
                    mbarrier, 10)
                mtok_s = Bm * Tm * 10 / mdt
                fpt = lm_train_flops_per_token(
                    cfg["num_layers"], cfg["d_model"], cfg["d_ff"], Tm, Vm)
                extras["transformer_lm_mfu"] = round(
                    mtok_s * fpt / peak, 4)
                extras["transformer_lm_mfu_tokens_per_sec"] = round(
                    mtok_s, 0)
                extras["transformer_lm_mfu_config"] = (
                    "L%d D%d ff%d T%d V%d b%d %s" % (
                        cfg["num_layers"], cfg["d_model"], cfg["d_ff"],
                        Tm, Vm, Bm, jnp.dtype(dtype).name))
        except Exception as exc:  # noqa: BLE001
            extras["lm_mfu_error"] = repr(exc)  # the headline must not
            #                                     vanish behind an earlier
            #                                     block's unrelated error
        # postamble: the regression sentinel judges the COMMITTED
        # trajectory (this round's numbers land in it next commit); its
        # table goes to stderr, its verdict rides the payload
        extras["bench_trend_rc"] = _bench_trend_check()
        if not claim():
            return 0  # the watchdog already emitted the primary payload
        payload.update(extras)

    _emit(payload)
    return 0


if __name__ == "__main__":
    sys.exit(main())
