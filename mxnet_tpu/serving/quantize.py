"""Post-training int8 weight quantization for the predict path.

In the spirit of TVM (arXiv:1802.04799): inference graphs are lowered
with quantized constants and the dequantization folded into consumers.
Here the mechanism is XLA fusion instead of a graph rewrite — weights
are stored as **int8 device arrays + per-channel fp scales** and the
``q.astype(compute) * scale`` dequantization is emitted *inside* the
already-jitted inference program, so the cast/multiply fuse into the
matmul (or gather) that consumes the weight.  Device memory holds int8
(4x smaller than fp32 — the KV-decode weight footprint drops
accordingly); compute stays in the program's compute dtype, which keeps
the pass numerically boring: symmetric per-channel scales bound the
per-weight error at ``max|w|/254`` per channel.

Scheme: per-channel symmetric.  For a weight ``w`` with output channels
on ``axis`` (axis 0 for both ``FullyConnected`` ``(out, in)`` layouts
and conv ``(O, I, kH, kW)``), ``scale_c = max|w_c| / 127`` and
``q = round(w / scale)`` clipped to [-127, 127] (-128 unused, keeping
the grid symmetric).  Rows that are entirely zero get scale 1 so the
roundtrip stays exact.

This is the int8 analog of the bf16 predict dtype
(``MXTPU_PREDICT_DTYPE``): same dequantize-in-compute philosophy, half
the storage of bf16 again, scales carrying the dynamic range the int8
grid lacks.

Backend-agnostic by construction: the ``_DequantView`` param dict
dequantizes on read inside whatever program traces it, so the int8
path composes unchanged with the contiguous slot pool AND the paged
KV backend (`serving/paged_kv.py`) — behind the fleet router every
replica can serve int8 paged (test-pinned in
tests/test_serving_fleet.py).
"""
from __future__ import annotations

import jax
import numpy as np

__all__ = ["QuantizedTensor", "quantize_per_channel", "quantize_params",
           "default_weight_filter", "prepare_inference_params"]


class QuantizedTensor:
    """int8 payload + per-channel fp32 scale, dequantized lazily.

    ``dequantize()`` emits ``q.astype(dtype) * scale`` — called inside a
    jit trace the int8 array is the captured constant and the
    cast/multiply fuse into the consumer; called eagerly it materializes
    the fp weight (tests, debugging).
    """

    __slots__ = ("q", "scale", "dtype", "axis")

    def __init__(self, q, scale, dtype=np.float32, axis=0):
        self.q = q
        self.scale = scale
        self.dtype = dtype
        self.axis = axis

    @property
    def shape(self):
        return tuple(self.q.shape)

    @property
    def nbytes(self):
        return int(np.prod(self.shape)) + 4 * int(np.prod(self.scale.shape))

    def dequantize(self):
        import jax.numpy as jnp

        return self.q.astype(self.dtype) * jnp.asarray(self.scale,
                                                       self.dtype)

    def __repr__(self):
        return (f"QuantizedTensor(shape={self.shape}, axis={self.axis}, "
                f"dtype={np.dtype(self.dtype).name})")


# the decode programs take their weights as ARGUMENTS (models/decode.py:
# _WeightProgram): the int8 payload and its scale are the traced
# children, dtype and axis ride as static aux data
jax.tree_util.register_pytree_node(
    QuantizedTensor,
    lambda t: ((t.q, t.scale), (t.dtype, t.axis)),
    lambda aux, kids: QuantizedTensor(kids[0], kids[1], *aux))


def quantize_per_channel(w, axis=0):
    """``w`` (numpy, any float dtype) -> (int8 q, fp32 scale) with the
    scale shaped to broadcast against ``w`` (size-1 on every axis but
    ``axis``)."""
    w = np.asarray(w, np.float32)
    reduce_axes = tuple(i for i in range(w.ndim) if i != axis)
    amax = np.abs(w).max(axis=reduce_axes, keepdims=True) \
        if reduce_axes else np.abs(w)
    scale = amax / 127.0
    scale = np.where(scale == 0.0, 1.0, scale).astype(np.float32)
    q = np.clip(np.rint(w / scale), -127, 127).astype(np.int8)
    return q, scale


def default_weight_filter(name, arr):
    """The weights the pass touches by default: float 2-D matmul /
    embedding tables and 4-D conv kernels named ``*weight`` (biases,
    norms, and positional tables stay fp — they are tiny and their
    precision is load-bearing)."""
    if not name.endswith("weight"):
        return False
    dtype = np.dtype(getattr(arr, "dtype", np.float32))
    if dtype.kind != "f":
        return False
    ndim = len(getattr(arr, "shape", ()))
    return ndim in (2, 4)


def quantize_params(params, dtype=np.float32, weight_filter=None,
                    device_put=True):
    """Quantize a name->array dict.  Returns a new dict where every
    filtered entry is a :class:`QuantizedTensor` (int8 on device when
    ``device_put``) and everything else passes through untouched.
    """
    import jax
    import jax.numpy as jnp

    weight_filter = weight_filter or default_weight_filter
    out = {}
    for name, arr in params.items():
        host = np.asarray(arr.asnumpy() if hasattr(arr, "asnumpy") else arr)
        if not weight_filter(name, host):
            out[name] = arr
            continue
        q, scale = quantize_per_channel(host, axis=0)
        if device_put:
            q = jax.device_put(q)
            scale = jax.device_put(scale)
        out[name] = QuantizedTensor(q, scale, dtype=jnp.dtype(dtype))
    return out


def prepare_inference_params(symbol, arg_params, aux_params, quantize="int8",
                             dtype=np.float32, weight_filter=None,
                             device_put=True):
    """Rewrite (symbol, params) for serving: Conv+BN fold, THEN int8.

    Ordering is the whole point: inference-mode Conv+BN folding
    (passes/convbn.py) multiplies each conv's weight rows by the BN
    scale ``gamma/sqrt(var+eps)`` — the per-channel symmetric scales
    below must be computed from the FOLDED weights, or the int8 grid
    would be sized to a dynamic range the deployed weights no longer
    have (channels with large BN scale would clip, channels with small
    BN scale would waste grid).  ``Predictor`` reproduces this ordering
    internally; this helper is the explicit form for serving code that
    manages its own executors.

    Returns ``(symbol, params, aux_params, n_folded)`` where ``params``
    maps each quantized weight to a :class:`QuantizedTensor` (and
    passes everything else through); ``quantize=None`` skips the int8
    step and returns the folded fp params.
    """
    from ..passes import apply_convbn_fold

    symbol, arg_params, aux_params, n_folded = apply_convbn_fold(
        symbol, arg_params, aux_params)
    if quantize == "int8":
        arg_params = quantize_params(arg_params, dtype=dtype,
                                     weight_filter=weight_filter,
                                     device_put=device_put)
    return symbol, arg_params, aux_params, n_folded
