#!/usr/bin/env python
"""Data-pipeline throughput benchmark.

Parity target: the reference documents >1K images decoded per second with
4 decode threads (docs/how_to/perf.md:161, "Data IO" section) for the
ImageRecordIter path.  This tool measures the same stages on this
framework:

  1. recordio read      — native frame scanner (src/recordio.cc)
  2. jpeg decode        — PIL/libjpeg in worker processes or threads
  3. decode + augment   — resize/crop pipeline (image.py ImageIter)

Usage: python tools/bench_io.py [--n 2000] [--threads 4] [--size 224]
Prints one line per stage: images/s.
"""
import argparse
import os
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np  # noqa: E402


def make_record_file(path, n, side=256):
    """Write n synthetic jpeg records (label + jpeg payload)."""
    from mxnet_tpu import recordio
    from mxnet_tpu.image import imencode

    rs = np.random.RandomState(0)
    writer = recordio.MXRecordIO(path, "w")
    # a realistic photographic-complexity image compresses to ~20-40KB
    base = rs.randint(0, 255, (side, side, 3)).astype(np.uint8)
    for i in range(n):
        # vary content a little so decode work is not degenerate
        img = np.roll(base, i % side, axis=0)
        payload = recordio.pack_img(
            recordio.IRHeader(0, float(i % 1000), i, 0), img, quality=90)
        writer.write(payload)
    writer.close()


def bench_read(path, n):
    from mxnet_tpu import recordio

    reader = recordio.MXRecordIO(path, "r")
    tic = time.perf_counter()
    count = 0
    while True:
        rec = reader.read()
        if rec is None:
            break
        count += 1
    dt = time.perf_counter() - tic
    reader.close()
    return count / dt


def bench_raw_decode(path, threads):
    """Pure jpeg decode through the iterator's worker pool — the stage the
    reference's >1K img/s @ 4 threads figure measures."""
    from concurrent.futures import ThreadPoolExecutor

    from mxnet_tpu import recordio
    from mxnet_tpu.image import imdecode_np

    reader = recordio.MXRecordIO(path, "r")
    payloads = []
    while True:
        rec = reader.read()
        if rec is None:
            break
        payloads.append(recordio.unpack(rec)[1])
    reader.close()
    pool = ThreadPoolExecutor(max_workers=threads)
    list(pool.map(imdecode_np, payloads[:64]))  # warmup
    tic = time.perf_counter()
    list(pool.map(imdecode_np, payloads))
    dt = time.perf_counter() - tic
    pool.shutdown()
    return len(payloads) / dt


def bench_pipeline(path, threads, size):
    """Full ImageRecordIter path: shard read -> decode -> augment -> batch."""
    from mxnet_tpu import image as img_mod

    it = img_mod.ImageRecordIter(
        path_imgrec=path, data_shape=(3, size, size), batch_size=50,
        preprocess_threads=threads, shuffle=False)
    next(iter(it))  # warmup (thread spin-up)
    it.reset()
    tic = time.perf_counter()
    count = 0
    for batch in it:
        count += batch.data[0].shape[0]
    dt = time.perf_counter() - tic
    return count / dt


def bench_device_prefetch(path, threads, size, depth=2):
    """Full stacked pipeline: ImageRecordIter -> PrefetchingIter ->
    DevicePrefetchIter, consumed by a simulated compute step — measures
    the rate the TRAINER sees with host prep AND device staging
    overlapped."""
    import jax

    from mxnet_tpu import image as img_mod, io as mio

    it = mio.DevicePrefetchIter(
        mio.PrefetchingIter(img_mod.ImageRecordIter(
            path_imgrec=path, data_shape=(3, size, size), batch_size=50,
            preprocess_threads=threads, shuffle=False)),
        depth=depth)
    batch = next(iter(it))  # warmup
    jax.block_until_ready(batch.data[0].jax_array)
    tic = time.perf_counter()
    count = 0
    for batch in it:
        # a consumer touch per batch (sum) stands in for the train step
        jax.block_until_ready(batch.data[0].jax_array.sum())
        count += batch.data[0].shape[0]
    dt = time.perf_counter() - tic
    return count / dt


def bench_mp_pipeline(path, workers, size, batches=30):
    """Sharded-host multi-process pipeline: N decode processes ->
    shared-memory ring -> this process staging to device
    (mp_io.MultiProcessImageRecordIter).  The process fan-out is the
    scale-out answer where thread counts stop helping (GIL/allocator
    contention on the python stages)."""
    from mxnet_tpu.image import MultiProcessImageRecordIter

    it = MultiProcessImageRecordIter(
        path_imgrec=path, data_shape=(3, size, size), batch_size=50,
        num_workers=workers, stall_timeout=180)
    try:
        src = iter(it)
        next(src)  # worker spin-up + first decode out of the timing
        tic = time.perf_counter()
        count = 0
        for batch in src:
            count += batch.data[0].shape[0]
            if count >= batches * 50:
                break
        dt = time.perf_counter() - tic
        return count / dt
    finally:
        it.close()


def sweep(args):
    """Thread-scaling table + host-CPU ceiling model."""
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "bench.rec")
        make_record_file(path, args.n)
        ncores = os.cpu_count() or 1
        print(f"io scaling sweep: n={args.n} images, host cores={ncores}")
        print(f"{'threads':>8} {'decode img/s':>13} {'pipeline img/s':>15} "
              f"{'staged img/s':>13}")
        per_thread = []
        for t in args.sweep:
            dec = bench_raw_decode(path, t)
            pipe = bench_pipeline(path, t, args.size)
            staged = bench_device_prefetch(path, t, args.size)
            per_thread.append((t, dec, pipe, staged))
            print(f"{t:>8} {dec:>13.0f} {pipe:>15.0f} {staged:>13.0f}")
        best_dec = max(d for _, d, _, _ in per_thread)
        best_pipe = max(p for _, _, p, _ in per_thread)
        # ceiling model: decode is GIL-free native libjpeg, so it scales
        # with PHYSICAL cores; this box's core count bounds what any
        # thread count can show
        print(f"host_cores: {ncores}")
        print(f"best_decode_img_s: {best_dec:.0f}")
        print(f"best_pipeline_img_s: {best_pipe:.0f}")
        chip_demand = 5600  # ResNet-50 img/s at MFU 0.35 on v5e
        need = chip_demand / max(best_pipe, 1.0)
        print(f"chip_demand_img_s: {chip_demand}")
        print(f"hosts_or_core_multiple_needed: {need:.1f}")


def main():
    # the host pipeline is what's being measured: pin the cpu platform
    # before any staging runs, so this process never claims the chip
    if os.environ.get("MXTPU_PLATFORM", "cpu") == "cpu":
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except Exception:  # noqa: BLE001 — a backend already won the race
            pass
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=2000)
    ap.add_argument("--threads", type=int, default=4)
    ap.add_argument("--size", type=int, default=224)
    ap.add_argument("--sweep", type=int, nargs="*", default=None,
                    help="measure a thread-scaling table at these "
                         "thread counts (e.g. --sweep 1 2 4 8)")
    args = ap.parse_args()
    if args.sweep is not None:
        args.sweep = args.sweep or [1, 2, 4, 8]
        return sweep(args)

    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, "bench.rec")
        make_record_file(path, args.n)
        rec_rate = bench_read(path, args.n)
        print("recordio_read: %.0f rec/s" % rec_rate)
        dec_rate = bench_raw_decode(path, args.threads)
        print("decode(threads=%d): %.0f img/s" % (args.threads, dec_rate))
        pipe_rate = bench_pipeline(path, args.threads, args.size)
        print("pipeline(threads=%d): %.0f img/s" % (args.threads, pipe_rate))
        # the same pipeline with the host staging arena disabled — shows
        # what pooled batch buffers buy (storage.py stage_to_device)
        from mxnet_tpu import storage

        print("pipeline_pool_bytes: %d" % storage.pool_bytes())
        with storage.pooling_disabled():
            nopool_rate = bench_pipeline(path, args.threads, args.size)
        print("pipeline_no_pool(threads=%d): %.0f img/s" %
              (args.threads, nopool_rate))
        target = 1000.0
        print("target_1k_met: %s" % ("yes" if dec_rate >= target else "no"))
        for w in (1, 2, 4):
            mp_rate = bench_mp_pipeline(path, w, args.size)
            print("mp_pipeline(workers=%d): %.0f img/s" % (w, mp_rate))


if __name__ == "__main__":
    main()
