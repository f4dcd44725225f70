"""Probe: the chip's two roofline corners, measured not quoted.

1. MXU corner — dense bf16 matmul MFU at sizes from 2k to 8k: how close
   can ANY program get to the datasheet peak (v5e: 197 TFLOP/s)?
2. HBM corner — streaming read+write bandwidth via y = a*x + y over
   arrays far larger than VMEM (v5e datasheet: 819 GB/s).

The iteration loop runs ON DEVICE (lax.fori_loop) so one dispatch
covers all iterations: a host-side loop of short kernels measures the
per-call dispatch, not the silicon.

Together with tools/probe_nhwc.py (the ResNet-50 train step itself)
these pin where that workload sits on the roofline: if matmul MFU is
high and the train step's implied bytes/s ~= the measured stream
bandwidth, the step is HBM-bound and its MFU ceiling is a property of
the workload's arithmetic intensity, not the framework.

Run on a chip:  python tools/probe_peak.py
"""
import time

import jax
import jax.numpy as jnp
import numpy as np

PEAK_TFLOPS = 197.0   # v5e bf16 datasheet
PEAK_GBS = 819.0      # v5e HBM datasheet


def matmul_mfu(n, iters=None):
    if iters is None:
        # constant total FLOP across sizes, so the single dispatch+fetch
        # round trip is amortized equally (~55 TFLOP ≈ 300ms at peak)
        iters = max(1, round(50 * (8192 / n) ** 3))
    a = jnp.asarray(np.random.RandomState(0).normal(size=(n, n)),
                    jnp.bfloat16)
    b = jnp.asarray(np.random.RandomState(1).normal(size=(n, n)),
                    jnp.bfloat16)

    @jax.jit
    def chain(a, b):
        # chained matmuls (each consumes the last result) so the device
        # loop can't be folded away or overlapped into nothing
        def body(_, c):
            return jax.lax.dot(
                c, b, preferred_element_type=jnp.float32
            ).astype(jnp.bfloat16)

        return jax.lax.fori_loop(0, iters, body, a)

    def fetch(out):
        # end the timing in bytes of the result (bench.py's discipline)
        return float(np.asarray(out[0, 0], np.float32))

    fetch(chain(a, b))                          # compile + warm
    tic = time.perf_counter()
    fetch(chain(a, b))                          # ONE dispatch, iters matmuls
    dt = time.perf_counter() - tic
    tflops = 2.0 * n * n * n * iters / dt / 1e12
    print(f"matmul {n}x{n}x{n} bf16: {tflops:8.1f} TFLOP/s  "
          f"mfu={tflops / PEAK_TFLOPS:.3f}", flush=True)


def hbm_bandwidth(mb=512, iters=100):
    n = mb * 1024 * 1024 // 4
    x = jnp.zeros((n,), jnp.float32)
    y = jnp.ones((n,), jnp.float32)

    @jax.jit
    def axpy_loop(x, y):
        def body(_, c):
            return 1.0001 * c + y

        return jax.lax.fori_loop(0, iters, body, x)

    def fetch(out):
        return float(np.asarray(out[0], np.float32))

    fetch(axpy_loop(x, y))
    tic = time.perf_counter()
    fetch(axpy_loop(x, y))
    dt = time.perf_counter() - tic
    # per iter: read c, read y, write out = 3 * mb
    gbs = 3 * mb * iters / 1024 / dt
    print(f"hbm axpy {mb}MB: {gbs:8.1f} GB/s  "
          f"of datasheet {PEAK_GBS:.0f} ({gbs / PEAK_GBS:.2f})", flush=True)


if __name__ == "__main__":
    print("devices:", jax.devices(), flush=True)
    for n in (2048, 4096, 8192):
        matmul_mfu(n)
    hbm_bandwidth()
