"""Probe: decompose the framework-vs-raw-JAX ResNet-50 gap on the chip.

Round 4 measured framework b32 = 2361 img/s vs a raw-JAX NHWC probe at
2610 (capture deleted in PR 21; not measured this round) — ~10% overhead that is by
construction not roofline.  This probe splits it:

  device  — framework step time with the device saturated (the bench
            discipline: async steps, one trailing fetch barrier)
  host    — wall time of step() WITHOUT waiting for the device (pure
            python/dispatch cost per call: pytree flatten, _shard_batch,
            jit-cache lookup, PjRt enqueue)
  raw     — the hand-written NHWC train step from tools/probe_nhwc.py,
            same batch, same discipline (the honest ceiling)

If device ~= raw, the remaining delta is host-side and amortizes with
batch size; if device > raw, the compiled step itself is heavier
(layout/cast/fusion loss) and the HLO needs attention.

Run on the bench chip:  python tools/probe_gap.py [batch ...]
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def framework(batch, iters=40):
    import jax
    import jax.numpy as jnp

    import mxnet_tpu  # noqa: F401
    from mxnet_tpu import models
    from mxnet_tpu.trainer import FusedTrainer

    net = models.get_symbol("resnet-50", num_classes=1000)
    tr = FusedTrainer(net, optimizer="sgd",
                      optimizer_params={"lr": 0.1, "momentum": 0.9,
                                        "rescale_grad": 1.0 / batch},
                      dtype=jnp.bfloat16)
    tr.init(data=(batch, 3, 224, 224))
    rs = np.random.RandomState(0)
    staged = {"data": jax.device_put(
        rs.uniform(0, 1, (batch, 3, 224, 224)).astype(np.float32)),
        "softmax_label": jax.device_put(
            rs.randint(0, 1000, batch).astype(np.float32))}
    pname = sorted(tr.params)[0]

    def barrier():
        return float(np.asarray(tr.params[pname]).ravel()[0])

    for _ in range(6):
        tr.step(**staged)
    barrier()

    tic = time.perf_counter()
    for _ in range(iters):
        tr.step(**staged)
    barrier()
    dev_dt = (time.perf_counter() - tic) / iters

    # host-only: the same calls, but timed WITHOUT the trailing barrier —
    # per-call wall time is the python+dispatch cost while the device
    # queue stays ahead (valid because dev_dt >> host_dt)
    tic = time.perf_counter()
    for _ in range(iters):
        tr.step(**staged)
    host_dt = (time.perf_counter() - tic) / iters
    barrier()
    note = ""
    if host_dt >= dev_dt:
        # the no-barrier loop came out SLOWER than the barriered one:
        # the split's premise (dev >> host) failed this window — the
        # call is host/transport-bound and the % is not a clean split
        note = "  [host-bound window: split premise failed]"
    print(f"framework b{batch}: {batch / dev_dt:8.1f} img/s   "
          f"step {dev_dt * 1e3:6.2f} ms   host-side {host_dt * 1e3:5.2f} ms "
          f"({host_dt / dev_dt * 100:4.1f}%){note}", flush=True)

    # the fix the host-side split motivates: k steps per dispatch
    # (FusedTrainer.step_multi) pays the call cost once per k steps
    k = 8
    stacked = {k_: jnp.stack([v] * k) for k_, v in staged.items()}
    tr.step_multi(**stacked)  # compile
    barrier()
    calls = max(iters // k, 2)
    tic = time.perf_counter()
    for _ in range(calls):
        tr.step_multi(**stacked)
    barrier()
    multi_dt = (time.perf_counter() - tic) / (calls * k)
    print(f"framework b{batch} multi(k={k}): {batch / multi_dt:8.1f} img/s   "
          f"step {multi_dt * 1e3:6.2f} ms", flush=True)


if __name__ == "__main__":
    import jax

    print("devices:", jax.devices(), flush=True)
    batches = [int(a) for a in sys.argv[1:]] or [32, 128]
    for b in batches:
        framework(b)
    # the raw ceiling, same session/same chip state (tools/ is not a
    # package: load the probe module by path)
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "probe_nhwc", os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "probe_nhwc.py"))
    probe_nhwc = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(probe_nhwc)
    for b in batches:
        probe_nhwc.run("NHWC", b)
