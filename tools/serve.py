#!/usr/bin/env python
"""Decode-serving process: continuous batching over a transformer-LM
checkpoint (mxnet_tpu/serving/), as one replica or as a routed fleet.

The deployment entrypoint the C-predict ABI story was missing: one
process owns the bound KVDecoder, admits concurrent request streams
over HTTP, and batches their decode steps into one jitted program per
tick.  Ops surface: ``/metrics`` (Prometheus), ``/healthz``,
``POST /generate``, ``POST /admin/drain|undrain`` — see docs/serving.md
for the runbook.

    # serve a save_checkpoint()-style transformer_lm checkpoint
    python tools/serve.py --prefix ckpt/lm --epoch 10 \
        --num-layers 4 --num-heads 8 --max-len 512 --port 9200

    # smoke/demo: a randomly initialized tiny LM (no checkpoint needed)
    python tools/serve.py --demo --port 9200

    # paged KV cache with prefix reuse (16-token pages)
    python tools/serve.py --demo --kv-block 16

    # a routed 2-replica local fleet (router + 2 replica subprocesses);
    # a chip belongs to one process, so N replicas need N chips — or
    # MXTPU_PLATFORM=cpu in the environment to keep them all off it
    python tools/serve.py --router --fleet 2 --demo --port 9100

    # router over existing replicas / a coordinator registry
    python tools/serve.py --router --replicas h1:9200,h2:9200
    python tools/serve.py --router --coord 10.0.0.1:8476

    curl -s localhost:9200/generate -d \
        '{"prompt": [1, 2, 3], "max_tokens": 16}'

SIGTERM drains gracefully: the scheduler stops admitting, queued and
in-flight requests finish, then the process exits 0 — so a plain
``kill`` IS the restart step of the rolling-upgrade runbook.

Request tracing + SLO plane (docs/tracing.md): ``--trace`` (or
``MXTPU_TRACE=1``) turns on span recording — the router mints/forwards
W3C ``traceparent`` per request, every process serves its span buffer
at ``GET /spans.json``, the router serves burn rates at ``GET /slo``,
and ``tools/fleetstat.py trace <id> --router host:port`` joins one
request's spans into a clock-corrected chrome trace.

Knobs (flags override env): MXTPU_SERVE_SLOTS, MXTPU_SERVE_QUEUE,
MXTPU_SERVE_DEADLINE_MS, MXTPU_PREDICT_INT8, MXTPU_KV_BLOCK,
MXTPU_PREFIX_CACHE, MXTPU_SERVE_REPLICAS, MXTPU_ROUTER_SCRAPE_S,
MXTPU_ROUTER_RETRIES, MXTPU_TRACE, MXTPU_TRACE_SAMPLE,
MXTPU_SLO_TTFT_MS, MXTPU_SLO_AVAIL (docs/how_to/env_var.md rounds
10 + 19 + 20).
"""
import argparse
import os
import signal
import sys
import threading
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        description="continuous-batching decode server / fleet router")
    ap.add_argument("--prefix", help="checkpoint prefix (save_checkpoint)")
    ap.add_argument("--epoch", type=int, default=0)
    ap.add_argument("--demo", action="store_true",
                    help="serve a randomly initialized tiny LM (smoke)")
    ap.add_argument("--num-layers", type=int, default=2)
    ap.add_argument("--num-heads", type=int, default=2)
    ap.add_argument("--d-model", type=int, default=64,
                    help="demo model width (checkpoints carry their own)")
    ap.add_argument("--vocab-size", type=int, default=256,
                    help="demo vocab (checkpoints carry their own)")
    ap.add_argument("--max-len", type=int, default=128,
                    help="KV-cache length = prompt + generation budget")
    ap.add_argument("--dtype", default="float32",
                    choices=["float32", "bfloat16"])
    ap.add_argument("--int8", action="store_true",
                    help="post-training int8 weight quantization "
                         "(or MXTPU_PREDICT_INT8=1)")
    ap.add_argument("--slots", type=int, default=None,
                    help="decode slots (MXTPU_SERVE_SLOTS, default 4)")
    ap.add_argument("--queue", type=int, default=None,
                    help="admission queue bound (MXTPU_SERVE_QUEUE, 16)")
    ap.add_argument("--deadline-ms", type=int, default=None,
                    help="default per-request deadline "
                         "(MXTPU_SERVE_DEADLINE_MS, 30000)")
    ap.add_argument("--kv-block", type=int, default=None,
                    help="paged KV cache page size in tokens "
                         "(MXTPU_KV_BLOCK; 0/unset = contiguous)")
    ap.add_argument("--register", action="store_true",
                    help="self-register this replica with the PR-13 "
                         "coordinator (--coord / MXTPU_COORD_ADDR)")
    ap.add_argument("--router", action="store_true",
                    help="run the fleet router instead of a replica")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="with --router: spawn N local replica "
                         "subprocesses (same model flags) and route "
                         "over them.  Each replica process claims a "
                         "chip of its own: N replicas need N chips, or "
                         "MXTPU_PLATFORM=cpu (the router itself never "
                         "initialises a backend)")
    ap.add_argument("--replicas", default=None,
                    help="with --router: static host:port list "
                         "(MXTPU_SERVE_REPLICAS)")
    ap.add_argument("--coord", default=None,
                    help="coordinator host:port (MXTPU_COORD_ADDR): "
                         "replica self-registration / router discovery")
    ap.add_argument("--scrape-s", type=float, default=None,
                    help="router healthz scrape interval "
                         "(MXTPU_ROUTER_SCRAPE_S, 1s)")
    ap.add_argument("--retries", type=int, default=None,
                    help="router idempotent re-routes per request "
                         "(MXTPU_ROUTER_RETRIES, 2)")
    ap.add_argument("--trace", action="store_true",
                    help="record request spans (MXTPU_TRACE=1): "
                         "/spans.json per process, /slo + traceparent "
                         "minting on the router — docs/tracing.md")
    ap.add_argument("--port", type=int, default=9200)
    ap.add_argument("--addr", default="127.0.0.1")
    return ap.parse_args(argv)


def build_decoder(args):
    """KVDecoder from a checkpoint (or random demo params)."""
    import jax.numpy as jnp
    import numpy as np

    import mxnet_tpu as mx
    from mxnet_tpu.models.decode import KVDecoder

    quantize = "int8" if (args.int8 or os.environ.get(
        "MXTPU_PREDICT_INT8", "0").lower() not in ("", "0", "false")) \
        else None
    dtype = jnp.bfloat16 if args.dtype == "bfloat16" else jnp.float32
    if args.demo:
        from mxnet_tpu import models

        net = models.transformer.transformer_lm(
            num_layers=args.num_layers, num_heads=args.num_heads,
            d_model=args.d_model, seq_len=args.max_len,
            vocab_size=args.vocab_size)
        ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                             data=(1, args.max_len),
                             softmax_label=(1, args.max_len))
        rs = np.random.RandomState(0)
        params = {}
        for name, arr in ex.arg_dict.items():
            if name in ("data", "softmax_label"):
                continue
            arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
            params[name] = arr
    else:
        if not args.prefix:
            raise SystemExit("need --prefix (or --demo)")
        _, params, _ = mx.model.load_checkpoint(args.prefix, args.epoch)
    return KVDecoder(params, num_layers=args.num_layers,
                     num_heads=args.num_heads, max_len=args.max_len,
                     dtype=dtype, quantize=quantize)


def _arm_sigterm():
    """SIGTERM/SIGINT -> a stop event the main loop polls, so ``kill``
    triggers the graceful drain instead of an abrupt death."""
    stop = threading.Event()

    def _handler(signum, frame):
        stop.set()

    signal.signal(signal.SIGTERM, _handler)
    try:
        signal.signal(signal.SIGINT, _handler)
    except ValueError:
        pass
    return stop


def _main_replica(args):
    from mxnet_tpu import compile_cache, telemetry
    from mxnet_tpu.serving import serve_decoder

    compile_cache.enable()  # replicas compile; the router never does
    telemetry.enable()  # a server without metrics is not operable
    if args.trace:
        telemetry.tracing.enable_tracing()
    stop = _arm_sigterm()
    decoder = build_decoder(args)
    server, scheduler = serve_decoder(
        decoder, port=args.port, addr=args.addr, num_slots=args.slots,
        queue_size=args.queue, default_deadline_ms=args.deadline_ms,
        kv_block=args.kv_block)
    host, port = server.server_address[:2]
    client = None
    if args.register or args.coord:
        from mxnet_tpu.serving import register_replica

        client = register_replica(f"{host}:{port}",
                                  coordinator=args.coord)
        print(f"registered with coordinator {client.addr} as "
              f"{client.member}", flush=True)
    paged = scheduler.paged_stats()
    print(f"serving on http://{host}:{port}  "
          f"(slots={scheduler.num_slots} queue={scheduler.queue_size} "
          f"deadline_ms={scheduler.default_deadline_ms} "
          f"int8={decoder.quantize == 'int8'} "
          f"paged={paged['block'] if paged else 0})", flush=True)
    try:
        while not stop.wait(0.5):
            pass
        # the PR-11 drain, wired to the signal (ISSUE 15): stop
        # admitting, let queued + in-flight requests finish (bounded by
        # their deadlines), then exit 0 — `kill` == the restart step of
        # the rolling-upgrade runbook
        print("SIGTERM: draining (in-flight requests finishing)",
              flush=True)
        scheduler.drain()
        while not scheduler.drained:
            time.sleep(0.05)
        print("drained, exiting", flush=True)
    finally:
        if client is not None:
            client.leave(why="drained")
        server.shutdown()
        scheduler.close()
    return 0


def _spawn_fleet(args):
    """Spawn ``--fleet N`` replica subprocesses (same model flags,
    ephemeral ports) and collect their addresses from the 'serving on'
    line.  Children die with us (SIGTERM -> graceful drain)."""
    import re
    import subprocess

    flags = [sys.executable, os.path.abspath(__file__)]
    if args.demo:
        flags.append("--demo")
    else:
        flags += ["--prefix", args.prefix or "", "--epoch",
                  str(args.epoch)]
    flags += ["--num-layers", str(args.num_layers),
              "--num-heads", str(args.num_heads),
              "--d-model", str(args.d_model),
              "--vocab-size", str(args.vocab_size),
              "--max-len", str(args.max_len),
              "--dtype", args.dtype,
              "--port", "0", "--addr", args.addr]
    if args.int8:
        flags.append("--int8")
    if args.slots is not None:
        flags += ["--slots", str(args.slots)]
    if args.queue is not None:
        flags += ["--queue", str(args.queue)]
    if args.deadline_ms is not None:
        flags += ["--deadline-ms", str(args.deadline_ms)]
    if args.kv_block is not None:
        flags += ["--kv-block", str(args.kv_block)]
    if args.trace:
        flags.append("--trace")   # one flag traces the whole fleet
    procs, addrs = [], []
    for _ in range(args.fleet):
        procs.append(subprocess.Popen(
            flags, stdout=subprocess.PIPE,
            stderr=subprocess.STDOUT, text=True))
    try:
        for p in procs:
            addr, deadline = None, time.time() + 180
            while time.time() < deadline:
                line = p.stdout.readline()
                if not line:
                    break
                sys.stdout.write("[replica %d] %s" % (p.pid, line))
                m = re.search(r"serving on http://([0-9.]+:[0-9]+)", line)
                if m:
                    addr = m.group(1)
                    break
            if addr is None:
                raise SystemExit(
                    f"replica pid {p.pid} never reported its address")
            addrs.append(addr)
            # keep the pipe drained so the child never blocks on stdout
            t = threading.Thread(
                target=lambda f=p.stdout: [None for _ in f],
                daemon=True)
            t.start()
    except BaseException:
        for p in procs:
            p.kill()
        raise
    return procs, addrs


def _main_router(args):
    from mxnet_tpu import telemetry
    from mxnet_tpu.serving import ReplicaRouter, start_router

    telemetry.enable()
    if args.trace:
        telemetry.tracing.enable_tracing()
    stop = _arm_sigterm()
    procs = []
    replicas = [a.strip() for a in (args.replicas or "").split(",")
                if a.strip()] or None
    if args.fleet:
        procs, spawned = _spawn_fleet(args)
        replicas = (replicas or []) + spawned
    router = ReplicaRouter(replicas=replicas, coordinator=args.coord,
                           scrape_s=args.scrape_s, retries=args.retries)
    server = start_router(router, port=args.port, addr=args.addr)
    host, port = server.server_address[:2]
    n = len(router.replicas())
    print(f"routing on http://{host}:{port} over {n} replica(s) "
          f"(scrape every {router.scrape_s}s, retries {router.retries}"
          f"{', coordinator ' + args.coord if args.coord else ''}"
          f"{', tracing on' if args.trace else ''}) — "
          f"GET /slo for burn rates, /spans.json for the span buffer",
          flush=True)
    try:
        while not stop.wait(0.5):
            pass
        print("SIGTERM: stopping router"
              + (" and draining local fleet" if procs else ""),
              flush=True)
    finally:
        for p in procs:
            p.terminate()       # SIGTERM -> each replica drains
        for p in procs:
            try:
                p.wait(timeout=120)
            except Exception:  # noqa: BLE001 — last resort on shutdown
                p.kill()
        server.shutdown()
        router.stop()
    return 0


def main(argv=None):
    args = _parse_args(argv)
    if args.router:
        return _main_router(args)
    return _main_replica(args)


if __name__ == "__main__":
    sys.exit(main())
