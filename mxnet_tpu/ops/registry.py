"""Operator registry — the single source of truth for the op surface.

TPU-native replacement for the reference's *three* op registration systems
(legacy OperatorProperty, include/mxnet/operator.h:166-297; NNVM FCompute,
include/mxnet/op_attr_types.h:24-63; deprecated SimpleOp,
src/operator/operator_util.cc).  One registry serves both execution styles:

- imperative:  mxnet_tpu.ndarray autogenerates ``nd.<op>`` functions that
  dispatch through a jit cache (parity: MXImperativeInvoke,
  src/c_api/c_api_ndarray.cc:19-280 — the jit cache plays the role of the
  engine PushAsync; PjRt async dispatch is the engine),
- symbolic:    mxnet_tpu.symbol autogenerates ``sym.<Op>`` constructors; the
  executor traces registered forward fns into one XLA computation.

Each op is a pure function ``fn(ctx, *inputs, **attrs)`` over jax arrays.
Gradients come from jax.vjp — ops needing MXNet's special backward semantics
(loss output ops that ignore head gradients) wrap themselves in
jax.custom_vjp at definition site.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, field
from typing import Callable, Optional, Sequence

import jax

from ..base import MXNetError, frozen_attrs

_OPS: dict[str, "OpDef"] = {}


class OpCtx:
    """Per-invocation context handed to op implementations.

    Carries mode and randomness — the TPU-shaped analogue of the
    reference's OpContext {is_train, RunContext, requested resources}
    (include/mxnet/op_attr_types.h:32-63).  Randomness: instead of a
    mutable mshadow PRNG resource, ops pull fresh subkeys derived from an
    explicit key (pure & replayable inside jit).
    """

    __slots__ = ("is_train", "_key", "_nsplit", "platform", "mesh")

    def __init__(self, is_train: bool = False, key=None, platform=None,
                 mesh=None):
        self.is_train = is_train
        self._key = key
        self._nsplit = 0
        # the platform this graph will EXECUTE on ("tpu"/"cpu"), threaded
        # from the executor's bind ctx / the trainer's mesh.  Ops that
        # pick platform-specific lowerings (Pallas vs lax) must use this,
        # not jax.default_backend(): a registered accelerator plugin can
        # be the default backend while the computation is being lowered
        # for a CPU mesh (e.g. dryrun_multichip on a TPU-attached host).
        self.platform = platform
        # the device mesh the graph is partitioned over (None: one
        # device).  GSPMD cannot partition a Mosaic kernel, so an op
        # that lowers to one must shard_map it over this mesh itself.
        self.mesh = mesh

    def rng(self):
        if self._key is None:
            raise MXNetError("op requires a PRNG key but none was supplied")
        # trace-ok: OpCtx lives for one trace; the key-split counter is
        # trace-time bookkeeping that gives each rng() call a distinct fold
        self._nsplit += 1
        return jax.random.fold_in(self._key, self._nsplit)


@dataclass
class OpDef:
    name: str
    fn: Callable  # fn(ctx, *inputs, **attrs) -> out | tuple | (outs, aux_updates)
    arg_names: Sequence[str] = ("data",)
    # subset of arg_names that are learned parameters (auto-created as
    # variables during symbol composition, like Convolution's weight/bias)
    param_names: Sequence[str] = ()
    aux_names: Sequence[str] = ()  # auxiliary states (BatchNorm moving stats)
    num_outputs: int = 1
    output_names: Sequence[str] = ("output",)
    needs_rng: bool = False
    varargs: bool = False  # variadic inputs (Concat, ElementWiseSum, add_n)
    # infer_params(attrs, *known_input_shapes) -> {param_or_aux_name: shape}
    infer_params: Optional[Callable] = None
    # which positional args may be omitted (e.g. bias under no_bias)
    optional_args: Callable = None  # optional_args(attrs) -> set of dropped names
    # attr-dependent output count: num_outputs_fn(attrs) -> int
    num_outputs_fn: Callable = None
    attr_defaults: dict = field(default_factory=dict)
    doc: str = ""

    def resolve_arg_names(self, attrs) -> list:
        names = list(self.arg_names)
        if self.optional_args is not None:
            dropped = self.optional_args(attrs)
            names = [n for n in names if n not in dropped]
        return names


def register(
    name,
    *,
    arg_names=("data",),
    param_names=(),
    aux_names=(),
    num_outputs=1,
    output_names=("output",),
    needs_rng=False,
    varargs=False,
    infer_params=None,
    optional_args=None,
    attr_defaults=None,
    num_outputs_fn=None,
    aliases=(),
):
    """Decorator registering an op implementation under ``name``.

    Parity: MXNET_REGISTER_OP_PROPERTY (include/mxnet/operator.h:538) and
    NNVM_REGISTER_OP — collapsed into one mechanism.
    """

    def deco(fn):
        op = OpDef(
            name=name,
            fn=fn,
            arg_names=tuple(arg_names),
            param_names=tuple(param_names),
            aux_names=tuple(aux_names),
            num_outputs=num_outputs,
            output_names=tuple(output_names),
            needs_rng=needs_rng,
            varargs=varargs,
            infer_params=infer_params,
            optional_args=optional_args,
            attr_defaults=dict(attr_defaults or {}),
            num_outputs_fn=num_outputs_fn,
            doc=fn.__doc__ or "",
        )
        _OPS[name] = op
        for alias in aliases:
            _OPS[alias] = op
        return fn

    return deco


def get(name: str) -> OpDef:
    try:
        return _OPS[name]
    except KeyError:
        raise MXNetError(f"operator '{name}' is not registered") from None


def exists(name: str) -> bool:
    return name in _OPS


def list_ops() -> list:
    """Parity: MXSymbolListAtomicSymbolCreators introspection."""
    return sorted(_OPS)


# --------------------------------------------------------------------------
# Imperative dispatch with a jit cache.
#
# Key insight (SURVEY.md §7): the reference pays an engine-push per op; we
# pay a dict lookup + PjRt async dispatch of a cached executable.  The cache
# key is (op, static attrs, is_train); jax.jit's internal cache handles
# shape/dtype polymorphism beneath it.
# --------------------------------------------------------------------------
@functools.lru_cache(maxsize=8192)
def _jitted(name: str, fattrs: tuple, is_train: bool, with_key: bool):
    op = _OPS[name]
    attrs = {k: v for k, v in fattrs}

    if with_key:

        def run(key, *inputs):
            ctx = OpCtx(is_train=is_train, key=key)
            return op.fn(ctx, *inputs, **attrs)

    else:

        def run(*inputs):
            ctx = OpCtx(is_train=is_train)
            return op.fn(ctx, *inputs, **attrs)

    return jax.jit(run)


def invoke(name: str, inputs, attrs=None, is_train: bool = True, key=None):
    """Imperative op invocation on raw jax arrays.

    Parity: MXImperativeInvoke (src/c_api/c_api_ndarray.cc:19-280).
    Returns raw outputs (single array, tuple, or (outs, aux) for aux ops —
    imperative calls of aux ops drop the aux updates, as the reference's
    imperative BatchNorm does with its in-place aux TBlobs).
    """
    op = get(name)
    attrs = dict(attrs or {})
    if op.needs_rng and key is None:
        from .. import random as _random

        key = _random.next_key()
    fn = _jitted(op.name, frozen_attrs(attrs), bool(is_train), key is not None)
    from .. import profiler as _prof

    if _prof.is_running() and _prof.mode() == "all":
        # parity: imperative ops profiled under mode='all'
        # (MXNET_PROFILER_MODE, env_var.md:64-67); sync for accurate dur
        holder = {}

        def _sync():
            import jax as _jax

            if "out" in holder:
                _jax.block_until_ready(holder["out"])

        with _prof.span(op.name, category="imperative", sync=_sync):
            holder["out"] = out = fn(key, *inputs) if key is not None else fn(*inputs)
    else:
        out = fn(key, *inputs) if key is not None else fn(*inputs)
    from .. import engine

    engine.on_push(out)
    return out
