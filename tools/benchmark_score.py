#!/usr/bin/env python
"""Inference throughput sweep (parity: example/image-classification/
benchmark_score.py — the script behind every inference table in the
reference's perf.md).

Times jitted forward passes with device-resident inputs, each timing
ended by fetching output bytes, printing img/s per (model, batch).

Usage:
  python tools/benchmark_score.py [--models resnet-50,inception-v3]
                                  [--batches 1,32] [--iters 30]
"""
import argparse
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))

import numpy as np  # noqa: E402


def score(model, batch, iters, dtype_name):
    import jax
    import jax.numpy as jnp

    from mxnet_tpu import models
    from mxnet_tpu.executor import _build_graph_fn

    dtype = jnp.bfloat16 if dtype_name == "bf16" else jnp.float32
    image = (3, 299, 299) if model == "inception-v3" else (3, 224, 224)
    net = models.get_symbol(model, num_classes=1000)
    gfn = _build_graph_fn(net)
    rs = np.random.RandomState(0)
    arg_shapes, _, aux_shapes = net.infer_shape(
        data=(batch,) + image, softmax_label=(batch,))
    args = {n: jax.device_put(jnp.asarray(
                rs.uniform(-0.1, 0.1, s).astype(np.float32), dtype))
            for n, s in zip(net.list_arguments(), arg_shapes)}
    aux = {n: jax.device_put(jnp.asarray(
               rs.uniform(0.1, 1.0, s).astype(np.float32), dtype))
           for n, s in zip(net.list_auxiliary_states(), aux_shapes)}
    key = jax.random.PRNGKey(0)

    @jax.jit
    def fwd(args, aux):
        outs, _ = gfn(args, aux, key, False)
        return outs[0]

    out = fwd(args, aux)
    float(np.asarray(out).ravel()[0])  # compile + real sync
    tic = time.perf_counter()
    for _ in range(iters):
        out = fwd(args, aux)
    float(np.asarray(out).ravel()[0])
    dt = (time.perf_counter() - tic) / iters
    return batch / dt


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="alexnet,vgg,inception-bn,"
                                        "inception-v3,resnet-50,resnet-152")
    ap.add_argument("--batches", default="1,32")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--dtype", default="bf16", choices=("bf16", "fp32"))
    args = ap.parse_args()

    for model in args.models.split(","):
        for b in (int(x) for x in args.batches.split(",")):
            try:
                r = score(model, b, args.iters, args.dtype)
                print(f"{model} batch={b}: {r:.1f} img/s", flush=True)
            except Exception as exc:  # noqa: BLE001 — sweep keeps going
                print(f"{model} batch={b}: FAILED {exc!r}", flush=True)


if __name__ == "__main__":
    main()
