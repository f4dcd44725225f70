#!/usr/bin/env python3
"""The benchmark's command: one run of one cell.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process.  It holds the chip, makes inputs and weights from the
seed, warms the cell's own shapes (set-up), measures for ``--seconds``,
frees the program's state, decides ``correct`` against the plain
reference, and prints one JSON object as its last line.  Without a TPU
(or with fewer chips than the cell asks for) it prints no result and
exits 2.  ``--rehearse`` runs the cell's tiny sizes on whatever backend
JAX has, marks the line as a rehearsal and proves nothing about speed.
"""
import time

_T_START = time.perf_counter()

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def _say(*parts):
    print(*parts, file=sys.stderr, flush=True)


def _enable_compile_cache():
    """JAX's persistent cache where ``JAX_COMPILATION_CACHE_DIR`` says,
    else at a fixed path inside the checkout; every program goes in."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


class _CompileCounter:
    """Counts programs handed to the compiler (or fetched from the
    persistent cache) and cold compiles, from JAX's own monitoring."""

    def __init__(self):
        import jax.monitoring as mon

        self.lowered = 0
        self.cache_misses = 0
        mon.register_event_duration_secs_listener(self._duration)
        mon.register_event_listener(self._event)

    def _duration(self, event, _secs, **_kw):
        if event == "/jax/core/compile/jaxpr_to_mlir_module_duration":
            self.lowered += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Tracer:
    """``jax.profiler`` around the traced window, into a directory under
    ``TMPDIR`` that is read once and removed.  The device's lines and
    the host's ``TraceAnnotation`` spans are kept; the per-call Python
    tracer, most of a trace's bytes and of its overhead, is off."""

    def __init__(self, keep=None, need_device=True):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        self.keep, self.need_device = keep, need_device
        self.annotation = None

    def start(self):
        import jax

        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.enable_hlo_proto = False
        jax.profiler.start_trace(self.dir, profiler_options=options)
        self.annotation = jax.profiler.TraceAnnotation("bench_window")
        self.annotation.__enter__()

    def stop(self):
        import jax

        self.annotation.__exit__(None, None, None)
        jax.profiler.stop_trace()

    def read(self, chips, driver):
        """The reduced trace; idle gaps go by the driver's own spans
        (``host_spans``), or by its ``default_gap_label``."""
        from benchmark import trace_reduce

        try:
            return trace_reduce.reduce_dir(
                self.dir, chips, driver.default_gap_label,
                span_names=getattr(driver, "host_spans", ()),
                keep=self.keep, need_device=self.need_device)
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes on any backend: a check of the "
                         "harness, not a measurement")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1: copy the .xplane.pb here, to "
                         "be read by hand")
    args = ap.parse_args(argv)

    from benchmark import harness

    try:
        cell = harness.resolve(args.workload, rehearse=args.rehearse)
        driver_mod = harness.load_driver(cell.traffic["driver"])
        readers = [(m, harness.load_metric(m["name"]))
                   for m in cell.per_layer] if args.trace else []
    except (harness.BenchmarkError, OSError, KeyError, ValueError) as e:
        _say(f"benchmark: {e}")
        return 2
    if importlib.util.find_spec("mxnet_tpu") is None:
        _say("benchmark: the program under test (mxnet_tpu/) is not in "
             f"{ROOT}; nothing was run")
        return 2

    import jax

    cache_dir = _enable_compile_cache()
    try:
        devices = jax.devices()
    except RuntimeError as e:
        _say(f"benchmark: JAX found no device: {e}")
        return 2
    platform, kind = devices[0].platform, devices[0].device_kind
    if platform != "tpu" and not args.rehearse:
        _say(f"benchmark: JAX found no TPU (devices: {devices}); nothing "
             "was run")
        return 2
    if len(devices) < cell.chips:
        _say(f"benchmark: {cell.name} needs {cell.chips} chip(s), JAX has "
             f"{len(devices)}")
        return 2
    if args.rehearse:
        peak = None         # no share of a peak is read off a rehearsal
    else:
        try:
            peak = harness.peak_of(kind)
        except harness.BenchmarkError as e:
            _say(f"benchmark: {e}")
            return 2
    used = devices[:cell.chips]
    compiles = _CompileCounter()

    driver = driver_mod.Driver(cell, args.seed, rehearse=args.rehearse)
    driver.setup()
    if args.trace and hasattr(driver, "count_kernels"):
        driver.count_kernels()
    setup_s = time.perf_counter() - _T_START
    lowered_before = compiles.lowered
    tracer = Tracer(args.keep_trace, need_device=not args.rehearse) \
        if args.trace else None
    seconds = args.seconds
    if args.trace:
        seconds = min(seconds, float(cell.traffic.get("trace_seconds", 5)))
    record = driver.window(seconds, tracer)
    in_window = compiles.lowered - lowered_before
    memory_peak = max(
        ((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
         for d in used), default=0)
    _say("set-up %.3f s; window %.3f s; programs lowered in set-up %d "
         "(%d cold compiles), inside the window %d; compile cache %s"
         % (setup_s, record["window_s"], lowered_before,
            compiles.cache_misses, in_window, cache_dir))
    for line in record.get("notes", ()):
        _say(line)
    trace = None
    if tracer is not None:
        trace = tracer.read(cell.chips, driver)

    driver.free()
    checks = [("programs_lowered_in_window", float(in_window), 0.0)]
    checks += driver.check()
    if record.get("kernels_expected") is not None:
        checks.append(("kernels_missing_from_step", float(max(
            0, record["kernels_expected"] - record["kernels_in_step"])),
            0.0))
    compared = {name: {"value": value, "limit": limit}
                for name, value, limit in checks}
    correct = all(value <= limit for _n, value, limit in checks) \
        and record["failed"] == 0

    metrics = {}
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    if args.trace:
        ctx = {"record": record, "trace": trace, "peak": peak,
               "chips": cell.chips, "cell": cell}
        for m, reader in readers:
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = dict(record["end_to_end"], setup_s=setup_s)
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": units[m["name"]]}
    device = {"platform": platform, "kind": kind, "count": len(devices),
              "memory_peak_bytes": int(memory_peak)}
    result = {"correct": bool(correct), "attempted": record["attempted"],
              "failed": record["failed"], "metrics": metrics,
              "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
    if args.rehearse:
        result["rehearsal"] = True
    result["compared"] = compared
    for name, value, limit in checks:
        _say("compared %s: %.6g (limit %.6g)%s"
             % (name, value, limit, "" if value <= limit else "  <-- over"))
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
