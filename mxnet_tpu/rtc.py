"""Runtime kernel compilation from user-supplied source.

Parity: python/mxnet/rtc.py + src/common/mxrtc.cc (MXRtc: user CUDA
source strings compiled with NVRTC, cached CUfunction launched on
NDArrays).  The TPU-native analogue compiles user-supplied **Pallas**
kernel source: the source text defines the kernel body (a function of
input/output Refs), which is wrapped in ``pl.pallas_call`` and jitted.
Compilation is cached per (name, source); on CPU backends the kernel runs
in Pallas interpret mode so the feature works everywhere tests run.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .base import MXNetError
from .ndarray import NDArray


class Rtc:
    """Parity: mx.rtc.Rtc (python/mxnet/rtc.py:11-90).

    The reference signature was ``Rtc(name, inputs, outputs, kernel)``
    where kernel was raw CUDA C.  Here ``kernel`` is Python source that
    must define a function ``<name>(<in_refs>..., <out_refs>...)`` written
    against the Pallas API; the namespace exposes ``pl`` (jax.experimental
    .pallas), ``pltpu`` (TPU primitives, when importable), ``jnp``, ``jax``
    and ``lax``.

    inputs/outputs: [(argname, NDArray_template), ...] — templates fix
    shapes/dtypes exactly like the reference bound shapes at Rtc() time.
    """

    def __init__(self, name, inputs, outputs, kernel):
        from jax.experimental import pallas as pl
        from jax.experimental.pallas import tpu as pltpu

        self.name = name
        self._in_templates = list(inputs)
        self._out_templates = list(outputs)

        ns = {"pl": pl, "pltpu": pltpu, "jnp": jnp, "jax": jax,
              "lax": jax.lax}
        try:
            exec(compile(kernel, f"<rtc:{name}>", "exec"), ns)
        except SyntaxError as e:
            raise MXNetError(f"Rtc kernel '{name}' failed to parse: {e}") from e
        if name not in ns or not callable(ns[name]):
            raise MXNetError(
                f"Rtc kernel source must define a function named '{name}'")
        self._kernel = ns[name]

        self._out_shapes = tuple(
            jax.ShapeDtypeStruct(tuple(t.shape), t.dtype)
            for _, t in self._out_templates)
        self._compiled_cache = {}

    def _compiled(self, *raw):
        # interpret mode must track where the *inputs* live, not the
        # process default backend: CPU-resident arrays need interpret=True
        # even when a TPU is attached.
        from jax.experimental import pallas as pl

        platforms = {d.platform for a in raw
                     for d in getattr(a, "devices", lambda: set())()}
        on_tpu = platforms == {"tpu"} and platforms
        fn = self._compiled_cache.get(on_tpu)
        if fn is None:
            call = pl.pallas_call(self._kernel, out_shape=self._out_shapes,
                                  interpret=not on_tpu)
            fn = self._compiled_cache[on_tpu] = jax.jit(call)
        return fn(*raw)

    def push(self, inputs, outputs, grid_dims=None, block_dims=None):
        """Run the kernel (parity: MXRtcPush).  grid/block dims are
        accepted for signature parity; Pallas grids are fixed at build
        time, so they are validated but not re-applied."""
        if len(inputs) != len(self._in_templates):
            raise MXNetError(f"Rtc '{self.name}' expects "
                             f"{len(self._in_templates)} inputs")
        if len(outputs) != len(self._out_templates):
            raise MXNetError(f"Rtc '{self.name}' expects "
                             f"{len(self._out_templates)} outputs")
        raw = [x._read() if isinstance(x, NDArray) else jnp.asarray(x)
               for x in inputs]
        res = self._compiled(*raw)
        if not isinstance(res, (tuple, list)):
            res = (res,)
        for dst, val in zip(outputs, res):
            dst._set(val)
        return outputs
