#!/usr/bin/env python
"""Measure kvstore / collective aggregation bandwidth.

Parity: tools/bandwidth/measure.py (reference) — times repeated
push+pull of model-sized gradient sets through a kvstore and reports
GB/s, so users can check comm cost < compute cost per batch
(docs/how_to/perf.md:148-154).

TPU-native addition: ``--kv-store collective`` times the same payload as
an in-step psum over the device mesh (the path FusedTrainer uses), which
is what actually rides ICI on pods.
"""
from __future__ import annotations

import argparse
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def model_sizes(network, num_classes=1000):
    """Parameter sizes (floats) for a named model, via symbol shape
    inference (parity: the reference infers from the symbol zoo)."""
    import mxnet_tpu as mx
    from mxnet_tpu import models

    net = models.get_symbol(network, num_classes=num_classes)
    arg_shapes, _, _ = net.infer_shape(data=(2, 3, 224, 224))
    import numpy as np

    names = net.list_arguments()
    return [int(np.prod(s)) for n, s in zip(names, arg_shapes)
            if n not in ("data", "softmax_label")]


def measure_kvstore(kv_type, sizes, num_devices, repeat):
    import numpy as np

    import mxnet_tpu as mx

    kv = mx.kv.create(kv_type)
    arrays = [[mx.nd.array(np.ones(s, np.float32)) for _ in range(num_devices)]
              for s in sizes]
    outs = [[mx.nd.zeros((s,)) for _ in range(num_devices)] for s in sizes]
    for i, s in enumerate(sizes):
        kv.init(i, mx.nd.zeros((s,)))
    total_bytes = sum(sizes) * 4 * 2 * num_devices  # push + pull, all devs
    t0 = time.time()
    for _ in range(repeat):
        for i in range(len(sizes)):
            kv.push(i, [a.reshape((sizes[i],)) for a in arrays[i]],
                    priority=-i)
        for i in range(len(sizes)):
            kv.pull(i, out=outs[i], priority=-i)
        for o in outs:
            o[0].wait_to_read()
    dt = time.time() - t0
    return total_bytes * repeat / dt / 1e9, dt / repeat


def measure_collective(sizes, num_devices, repeat):
    """psum over an n-device mesh — the fused-step gradient path."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    devices = jax.devices()[:num_devices]
    mesh = Mesh(np.array(devices), ("data",))

    @jax.jit
    def allreduce(*xs):
        f = jax.shard_map(lambda *ys: tuple(jax.lax.psum(y, "data") for y in ys),
                      mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        return f(*xs)

    args = [jax.device_put(
        np.ones((num_devices, s), np.float32),
        NamedSharding(mesh, P("data"))) for s in sizes]
    jax.block_until_ready(allreduce(*args))
    t0 = time.time()
    for _ in range(repeat):
        out = allreduce(*args)
    jax.block_until_ready(out)
    dt = time.time() - t0
    total_bytes = sum(sizes) * 4 * 2 * num_devices
    return total_bytes * repeat / dt / 1e9, dt / repeat


def main():
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--network", default="resnet-50")
    ap.add_argument("--kv-store", default="device",
                    help="local | device | dist_* | collective")
    ap.add_argument("--num-devices", type=int, default=1)
    ap.add_argument("--repeat", type=int, default=5)
    ap.add_argument("--num-classes", type=int, default=1000)
    args = ap.parse_args()

    sizes = model_sizes(args.network, args.num_classes)
    print(f"{args.network}: {len(sizes)} params, "
          f"{sum(sizes) * 4 / 1e6:.1f} MB")
    if args.kv_store == "collective":
        gbs, per_iter = measure_collective(sizes, args.num_devices, args.repeat)
    else:
        gbs, per_iter = measure_kvstore(args.kv_store, sizes,
                                        args.num_devices, args.repeat)
    print(f"kvstore={args.kv_store} devices={args.num_devices} "
          f"bandwidth={gbs:.2f} GB/s per-iter={per_iter * 1e3:.1f} ms")


if __name__ == "__main__":
    main()
