"""Predict-only inference API.

Parity: include/mxnet/c_predict_api.h + src/c_api/c_predict_api.cc
(reference): a self-contained ABI — ``MXPredCreate`` (symbol JSON +
param blob + input shapes), ``MXPredSetInput``, ``MXPredForward``,
``MXPredGetOutputShape``, ``MXPredGetOutput``, ``MXPredPartialForward``,
``MXPredFree`` — used by the amalgamation/mobile/JNI builds, with the
engine forced to the synchronous NaiveEngine (``MXNET_PREDICT_ONLY``,
include/mxnet/base.h:72-74).

TPU-native design: a Predictor is ONE jitted XLA computation (inputs →
outputs) with weights captured as device constants; ``forward`` is a
single dispatch.  The same class backs the C predict ABI exported from
src/ (see src/c_predict.cc) so non-Python frontends get the reference's
deployment story.
"""
from __future__ import annotations

import os

import numpy as np

from . import ndarray as nd
from . import symbol as sym_mod
from .base import MXNetError


class Predictor:
    """Parity: the ``MXPredCreate``/``SetInput``/``Forward``/``GetOutput``
    lifecycle rolled into one object.

    ``dtype``: inference compute precision.  ``"bfloat16"`` casts fp32
    weights/inputs to bf16 *inside* the compiled program (the casts fuse
    into the first consumers) and casts outputs back to fp32 — the
    deployment analog of ``FusedTrainer(dtype='bfloat16')``.  Default is
    the checkpoint's own precision; the ``MXTPU_PREDICT_DTYPE`` env var
    sets it for non-Python clients of the C ABI (src/c_predict.cc),
    which construct this class without kwargs.

    Graph passes: the bind below runs the training-safe rewrite
    pipeline (mxnet_tpu.passes) like every executor bind, and the
    constructor additionally applies inference-only Conv+BN folding —
    frozen BatchNorm moving stats and affine params are folded into the
    producing conv's weights/bias, removing a normalization per conv
    from every forward.  ``MXTPU_GRAPH_PASSES=0`` restores the
    unrewritten graph bit-identically.

    ``quantize="int8"``: post-training weight quantization
    (serving/quantize.py) — fp 2-D matmul and 4-D conv ``*weight``
    params are stored as int8 + per-channel symmetric scales and
    dequantized *inside* the compiled program, so the
    ``astype * scale`` fuses into each weight's consumer.  4x smaller
    weight residency than fp32 (composable with ``dtype="bfloat16"``:
    int8 storage, bf16 compute).  ``MXTPU_PREDICT_INT8=1`` sets it for
    kwarg-less C-ABI clients, like ``MXTPU_PREDICT_DTYPE``.
    """

    def __init__(self, symbol_json_str=None, param_bytes=None,
                 input_shapes=None, dev_type="cpu", dev_id=0,
                 symbol=None, arg_params=None, aux_params=None,
                 output_index=None, dtype=None, quantize=None):
        from . import context as ctx_mod
        from .executor import simple_bind

        if symbol is None:
            if symbol_json_str is None:
                raise MXNetError("need symbol or symbol_json_str")
            symbol = sym_mod.load_json(symbol_json_str)
        if arg_params is None:
            arg_params, aux_params = {}, {}
            if param_bytes is not None:
                loaded = _load_param_bytes(param_bytes)
                for k, v in loaded.items():
                    tp, name = k.split(":", 1)
                    if tp == "arg":
                        arg_params[name] = v
                    elif tp == "aux":
                        aux_params[name] = v
        aux_params = aux_params or {}

        # parity: MXPredCreatePartialOut — cut the graph at selected
        # internal outputs (by index, name, or list thereof)
        if output_index is not None:
            internals = symbol.get_internals()
            indices = output_index if isinstance(output_index, (list, tuple)) \
                else [output_index]
            picked = []
            names = internals.list_outputs()
            for sel in indices:
                if isinstance(sel, str):
                    if sel not in names:
                        raise MXNetError(
                            f"unknown output {sel!r}; internals: {names}")
                    picked.append(internals[names.index(sel)])
                elif isinstance(sel, int):
                    picked.append(internals[sel])
                else:
                    raise MXNetError(
                        f"output_index entries must be int or str, got {sel!r}")
            symbol = picked[0] if len(picked) == 1 else sym_mod.Group(picked)

        # inference-mode Conv+BN folding (passes/convbn.py): the predict
        # path never trains, so every frozen BatchNorm behind a conv is
        # folded into the conv's weights/bias BEFORE binding — and,
        # critically, before int8 quantization below computes per-channel
        # scales, so the scales see the folded dynamic range.  Runs on
        # the cut (output_index) symbol; MXTPU_GRAPH_PASSES gates it.
        from .passes import apply_convbn_fold

        symbol, arg_params, aux_params, self._n_bn_folded = \
            apply_convbn_fold(symbol, arg_params, aux_params)

        self.symbol = symbol
        self._input_names = [n for n in symbol.list_arguments()
                             if n not in arg_params]
        input_shapes = dict(input_shapes or {})
        missing = [n for n in self._input_names if n not in input_shapes]
        if missing:
            # label-style args (e.g. softmax_label) are not fed at
            # inference; infer their shapes from the given inputs and
            # bind zeros (the reference's predict path does the same by
            # treating outputs as plain activations without labels)
            try:
                arg_shapes, _, _ = symbol.infer_shape(**input_shapes)
                inferred = dict(zip(symbol.list_arguments(), arg_shapes))
                for n in missing:
                    input_shapes[n] = inferred[n]
            except Exception as e:
                raise MXNetError(
                    f"input_shapes missing for inputs {missing}") from e
            self._input_names = [n for n in self._input_names
                                 if n not in missing]
            label_args = set(missing)  # bound to zeros by design
        else:
            label_args = set()

        device = ctx_mod.Context(dev_type, dev_id) \
            if isinstance(dev_type, str) else dev_type
        self._exec = simple_bind(symbol, device, grad_req="null",
                                 **input_shapes)
        self._exec.copy_params_from(arg_params, aux_params,
                                    allow_extra_params=True)
        # every weight must have come from the checkpoint: simple_bind
        # leaves unset args at ZERO, so a silently-skipped load would
        # "work" and return uniform softmax outputs instead of failing
        uncovered = [n for n in self._exec.arg_dict
                     if n not in self._input_names and n not in arg_params
                     and n not in label_args]
        if uncovered:
            raise MXNetError(
                f"params file covers no value for {uncovered[:5]} "
                "(corrupt/truncated checkpoint, or name mismatch)")
        self._dirty = True

        if dtype is None:
            dtype = os.environ.get("MXTPU_PREDICT_DTYPE") or None
        if quantize is None and os.environ.get(
                "MXTPU_PREDICT_INT8", "0").lower() not in ("", "0", "false"):
            quantize = "int8"
        if quantize not in (None, "int8"):
            raise MXNetError(f"unknown quantize mode {quantize!r} "
                             "(supported: 'int8')")
        self._dtype = dtype  # normalized to a jnp dtype in _build_fast_forward
        self._quantize = quantize
        self._wire_dtype = None  # host-side upload dtype (set below)
        self._build_fast_forward()
        self._fast_outs = None
        self._inflight = {}   # ticket -> list of dispatched outputs
        self._inflight_lock = __import__("threading").Lock()
        self._ticket = 0
        self._step = 0

    def _build_fast_forward(self):
        """One jitted computation per Predictor: params/inputs → outputs.

        Unlike Executor.forward (which runs eager NDArray writes, an
        eager RNG fold, and output re-wrapping per call — each one a
        host↔device round trip), this path is a single dispatch: the RNG fold happens
        *inside* the program (the step counter is a traced scalar), the
        dtype casts fuse into their consumers, and outputs stay raw jax
        arrays until ``get_output`` copies them out (parity note: the
        reference forces the synchronous NaiveEngine for predict,
        include/mxnet/base.h:72-74 — here "synchronous" is simply one
        XLA program per forward)."""
        import jax
        import jax.numpy as jnp

        if getattr(self._exec, "_placed", False):
            self._infer_jit = None  # ctx-group graphs: outer must stay unjitted
            if self._dtype not in (None, "float32") or self._quantize:
                import warnings

                warnings.warn(
                    "Predictor dtype=%r / quantize=%r is not applied on "
                    "ctx-group (placed) graphs — the executor fallback "
                    "computes in the checkpoint's own precision"
                    % (self._dtype, self._quantize),
                    stacklevel=3)
            return
        graph_fn = self._exec._graph_fn
        cast = None if self._dtype is None else jnp.dtype(self._dtype)
        # weights are immutable after construction (set_input only accepts
        # declared inputs; reshape() builds a whole new Predictor), so
        # snapshot them once — forward() then only uploads the inputs
        self._param_snapshot = {
            k: v._read() for k, v in self._exec.arg_dict.items()
            if k not in self._input_names}
        self._aux_snapshot = {
            k: v._read() for k, v in self._exec.aux_dict.items()}
        # int8 weight quantization (serving/quantize.py): move the
        # filtered weights out of the fp snapshot into an int8+scale
        # tree; _infer dequantizes them INSIDE the program, directly in
        # the compute dtype, so storage is int8 and the multiply fuses
        # into each weight's consumer
        self._qparams = {}
        if self._quantize == "int8":
            from .serving.quantize import (default_weight_filter,
                                           quantize_per_channel)

            for k in list(self._param_snapshot):
                v = self._param_snapshot[k]
                if not default_weight_filter(k, v):
                    continue
                q, scale = quantize_per_channel(np.asarray(v), axis=0)
                self._qparams[k] = (jax.device_put(q),
                                    jax.device_put(scale))
                del self._param_snapshot[k]
        # upload inputs over the wire ALREADY in the compute dtype: the
        # in-graph cast would throw the upper half of every fp32 mantissa
        # away on arrival anyway, so casting on the host first halves the
        # host->device bytes — where input upload bounds the predictor,
        # that is half its time
        if cast is not None and cast != jnp.float32:
            self._wire_dtype = cast

        def _infer(params, qparams, aux, inputs, step, base_key):
            key = jax.random.fold_in(base_key, step)
            merged = dict(params)
            dq = cast if cast is not None else jnp.float32
            for k, (q, scale) in qparams.items():
                merged[k] = q.astype(dq) * scale.astype(dq)
            merged.update(inputs)
            if cast is not None and cast != jnp.float32:
                merged = {k: v.astype(cast) if v.dtype == jnp.float32 else v
                          for k, v in merged.items()}
                aux = {k: v.astype(cast) if v.dtype == jnp.float32 else v
                       for k, v in aux.items()}
            outs, _ = graph_fn(merged, aux, key, False)
            if cast is not None and cast != jnp.float32:
                outs = [o.astype(jnp.float32) if o.dtype == cast else o
                        for o in outs]
            return outs

        self._infer_jit = jax.jit(_infer)

    # ------------------------------------------------------------------ API
    def _coerce_input(self, name, value):
        """Validate name/shape and coerce to the bound dtype (shared by
        set_input and forward kwargs)."""
        if name not in self._input_names:
            raise MXNetError(f"unknown input {name}; inputs: {self._input_names}")
        arr = self._exec.arg_dict[name]
        value = np.asarray(value, dtype=arr.dtype)
        if value.shape != arr.shape:
            raise MXNetError(
                f"shape mismatch for {name}: got {value.shape}, bound {arr.shape}")
        return arr, value

    def _upload_input(self, name, value):
        """Single host→device transfer straight onto the bound array's
        device — no eager broadcast op, no default-device detour.

        The host value is copied first: jax's cpu backend may alias a
        numpy buffer zero-copy into the device array, so without the
        copy a caller that mutates (or frees — the C ABI case) its
        buffer after set_input would corrupt the bound input.  The copy
        restores the old ``arr[:] = value`` semantics at memcpy cost,
        negligible next to the transfer it precedes."""
        import jax

        arr, value = self._coerce_input(name, value)
        if self._wire_dtype is not None and value.dtype == np.float32:
            value = value.astype(self._wire_dtype)  # astype copies
        else:
            value = np.array(value, copy=True)
        arr._set(jax.device_put(value, arr._read().sharding))

    def set_input(self, name, value):
        """Parity: MXPredSetInput."""
        self._upload_input(name, value)
        self._dirty = True

    def forward(self, **inputs):
        """Parity: MXPredForward (kwargs are a convenience for set_input)."""
        if self._infer_jit is None:  # ctx-group fallback: executor path
            for name, value in inputs.items():
                self.set_input(name, value)
            self._exec.forward(is_train=False)
            self._fast_outs = None
            self._dirty = False
            return
        self._fast_outs = self._dispatch(inputs)

    def _dispatch(self, inputs):
        """Upload inputs and dispatch one forward (shared by forward and
        forward_async); returns the raw output arrays without joining."""
        from . import random as _random

        arg_dict = self._exec.arg_dict
        for name, value in inputs.items():
            self._upload_input(name, value)
        feeds = {n: arg_dict[n]._read() for n in self._input_names}
        # the key is a traced argument (not a closure constant) so a
        # later mx.random.seed() is honored, matching Executor.forward
        outs = self._infer_jit(
            self._param_snapshot, self._qparams, self._aux_snapshot,
            feeds, np.uint32(self._step), _random.current_key())
        self._step += 1
        self._dirty = False
        return outs

    def forward_async(self, **inputs):
        """Dispatch a forward WITHOUT joining it; returns a ticket for
        ``get_async``.  Several tickets may be in flight at once — each
        call's input upload, compute, and device→host output fetch queue
        independently, so consecutive calls pipeline all three stages
        against each other.  Where the transfers bound the predictor
        this hides compute and output-fetch time under the next call's
        input upload; a strict
        ``forward()``/``get_output()`` loop instead pays the full
        upload+compute+fetch round trip per call.

        The C ABI exposes this pair as MXPredForwardAsync /
        MXPredGetOutputAsync (src/c_predict.cc)."""
        if self._infer_jit is None:
            raise MXNetError("forward_async is not supported on ctx-group "
                             "(placed) graphs — use forward()")
        outs = self._dispatch(inputs)
        # get_output() after forward_async keeps last-forward-wins
        # semantics (this IS the most recent forward)
        self._fast_outs = outs
        for o in outs:
            start = getattr(o, "copy_to_host_async", None)
            if start is not None:
                try:
                    start()  # fetch streams while later calls compute
                except Exception:  # noqa: BLE001 — fetch runs in get_async
                    break
        with self._inflight_lock:
            self._ticket += 1
            ticket = self._ticket
            self._inflight[ticket] = list(outs)
            # abandoned tickets (multi-output partial fetches, clients
            # that error out) must not pin device buffers forever: keep
            # at most 64 in flight, evicting oldest-first (dict preserves
            # insertion order) — a pipelined client holds a handful
            while len(self._inflight) > 64:
                self._inflight.pop(next(iter(self._inflight)))
        return ticket

    def get_async(self, ticket, index=0):
        """Join output ``index`` of an in-flight ``forward_async`` ticket
        as a host array.  Each output is fetchable once; the ticket
        retires after its last unfetched output is taken (or via
        ``discard_async``)."""
        with self._inflight_lock:
            outs = self._inflight.get(ticket)
            if outs is None:
                raise MXNetError(
                    f"unknown or already-retired ticket {ticket}")
            if not 0 <= index < len(outs) or outs[index] is None:
                raise MXNetError(
                    f"ticket {ticket}: output {index} is out of range or "
                    f"already fetched ({len(outs)} outputs)")
            out, outs[index] = outs[index], None
            if all(o is None for o in outs):
                del self._inflight[ticket]
        return np.asarray(out, dtype=np.float32) \
            if out.dtype != np.float32 else np.asarray(out)

    def discard_async(self, ticket):
        """Drop an in-flight ticket without fetching (frees its device
        output buffers); unknown tickets are a no-op."""
        with self._inflight_lock:
            self._inflight.pop(ticket, None)

    def partial_forward(self, step):
        """Parity: MXPredPartialForward — the reference runs the op
        sequence up to `step` for debugging.  XLA executes the graph as
        one fused computation, so partial execution is served from the
        internals graph: output `step` of get_internals()."""
        internals = self.symbol.get_internals()
        names = internals.list_outputs()
        step = min(step, len(names) - 1)
        sub = internals[step]
        shapes = {n: self._exec.arg_dict[n].shape for n in self._input_names}
        ex = sub.simple_bind(self._exec._ctx, grad_req="null", **shapes)
        ex.copy_params_from(
            {k: v for k, v in self._exec.arg_dict.items()
             if k not in self._input_names},
            dict(self._exec.aux_dict), allow_extra_params=True)
        for n in self._input_names:
            if n in ex.arg_dict:
                ex.arg_dict[n][:] = self._exec.arg_dict[n].asnumpy()
        ex.forward(is_train=False)
        return [o.asnumpy() for o in ex.outputs]

    def get_output_shape(self, index=0):
        """Parity: MXPredGetOutputShape — usable BEFORE the first forward
        (the reference computes output shapes at MXPredCreate so C clients
        can size their buffers, c_predict_api.cc)."""
        if self._fast_outs is not None:
            return tuple(self._fast_outs[index].shape)
        if self._exec._outputs_cache is None and self._exec._pending is None:
            shapes = {n: self._exec.arg_dict[n].shape
                      for n in self._input_names}
            _, out_shapes, _ = self.symbol.infer_shape(**shapes)
            return tuple(out_shapes[index])
        return tuple(self._exec.outputs[index].shape)

    def get_output(self, index=0):
        """Parity: MXPredGetOutput — blocking copy-out."""
        if self._dirty:
            self.forward()
        if self._fast_outs is not None:
            return np.asarray(self._fast_outs[index])
        return self._exec.outputs[index].asnumpy()

    @property
    def num_outputs(self):
        if self._fast_outs is not None:
            return len(self._fast_outs)
        return len(self.symbol.list_outputs())

    def _input_shape(self, name):
        """Bound shape of an input (used by the C ABI to reshape flat
        buffers, src/c_predict.cc)."""
        return tuple(self._exec.arg_dict[name].shape)

    def reshape(self, input_shapes):
        """Parity: MXPredReshape — rebind with new input shapes (the jit
        cache makes repeat shapes free)."""
        arg_params = {k: v for k, v in self._exec.arg_dict.items()
                      if k not in self._input_names}
        aux_params = dict(self._exec.aux_dict)
        new = Predictor(symbol=self.symbol, arg_params=arg_params,
                        aux_params=aux_params, input_shapes=input_shapes,
                        dev_type=self._exec._ctx,  # keep the original device
                        dtype=self._dtype, quantize=self._quantize)
        self.__dict__.update(new.__dict__)


def _load_param_bytes(param_bytes):
    import tempfile, os

    with tempfile.NamedTemporaryFile(suffix=".params", delete=False) as f:
        f.write(param_bytes)
        path = f.name
    try:
        return nd.load(path)
    finally:
        os.unlink(path)


def create(prefix, epoch, input_shapes, dev_type="cpu", dev_id=0,
           dtype=None, quantize=None):
    """Load a save_checkpoint()-style checkpoint into a Predictor
    (parity: the common MXPredCreate usage in c_predict_api examples)."""
    from .model import load_checkpoint

    symbol, arg_params, aux_params = load_checkpoint(prefix, epoch)
    return Predictor(symbol=symbol, arg_params=arg_params,
                     aux_params=aux_params, input_shapes=input_shapes,
                     dev_type=dev_type, dev_id=dev_id, dtype=dtype,
                     quantize=quantize)
