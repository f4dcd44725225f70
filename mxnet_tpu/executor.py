"""Graph executor.

Parity: src/executor/graph_executor.cc + python/mxnet/executor.py
(reference).  The reference compiles a Symbol into a static plan (gradient
graph, memory plan, cached engine ops — GraphExecutor::Init,
graph_executor.cc:316-351) and runs it by pushing ops to the dependency
engine.  TPU-natively the *whole plan is one XLA computation*:

- bind traces the graph into a pure function f(args, aux, key) ->
  (outputs, new_aux) and jits it — XLA buffer assignment replaces
  PlanMemory, XLA fusion replaces per-node kernels,
- the gradient graph (nnvm::pass::Gradient, graph_executor.cc:167-223) is
  jax.vjp over f, compiled together with the forward into one fused
  fwd+bwd executable — outputs and gradients materialize from a single
  device dispatch,
- forward(is_train=True) is *lazy*: it records inputs; if backward() is
  called before outputs are read, only the fused fwd+bwd computation runs
  (the reference gets the same effect from engine asynchrony: Python never
  blocks, SURVEY.md §3.1),
- grad_req write/add/null follow include/mxnet/op_attr_types.h OpReqType.

Executors created with ``shared_exec`` reuse the donor's compiled cache —
the TPU analogue of bucketing's shared memory pool
(GraphExecutor::Init(shared_exec), graph_executor.cc:330-334): what's
shared on TPU is compilation + params, while XLA reuses buffers per-call.
Beyond that object-identity path, a process-wide program cache keyed on
``Symbol.structural_signature()`` lets ANY bind of a structurally-equal
graph reuse the jitted executables (MXTPU_PROGRAM_CACHE, bounded LRU) —
repeated simple_bind, reshape, bucket regeneration, and serving rebinds
stop retracing/recompiling.
"""
from __future__ import annotations

import functools
import os
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from . import ops
from . import telemetry as _tm
from .base import MXNetError
from .context import Context, current_context
from .ndarray import NDArray
from .symbol import Symbol, _topo_order

_GRAD_REQ = ("write", "add", "null")

# --- telemetry families (zero-cost when disabled; docs/telemetry.md) -------
_TM_COMPILE = _tm.counter(
    "executor_compile_total",
    "graph traces handed to XLA: one per jit cache miss, including "
    "per-shape recompiles", labels=("kind",))
_TM_COMPILE_SEC = _tm.histogram(
    "executor_compile_seconds",
    "Python-trace portion of each XLA compile (seconds)", labels=("kind",))
_TM_GRAPH_CACHE = _tm.counter(
    "executor_graph_cache_total",
    "compiled graph-fn reuse: hit = shared_exec donor reused, miss = "
    "fresh jit built", labels=("result",))
_TM_FWD_SEC = _tm.histogram(
    "executor_forward_seconds",
    "Executor.forward wall time (dispatch; device-complete only under "
    "the profiler's sync mode)")
_TM_BWD_SEC = _tm.histogram(
    "executor_backward_seconds", "Executor.backward wall time (dispatch)")
_TM_COLLECTIVE = _tm.counter(
    "executor_collective_bytes_total",
    "logical payload bytes of mesh collectives the sharded paths "
    "request per dispatch (grad all-reduce, sharded-update param "
    "all-gather; estimate at dispatch, not wire bytes)", labels=("op",))


def _count_traces(fn, kind):
    """Wrap a to-be-jitted callable so each trace (= each XLA compile,
    including per-shape recompiles) increments the compile counter and
    times the Python-trace slice.  Runs at trace time only — compiled
    executions never reach this code."""

    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        _TM_COMPILE.inc(kind=kind)
        t0 = time.perf_counter()
        res = fn(*args, **kwargs)
        _TM_COMPILE_SEC.observe(time.perf_counter() - t0, kind=kind)
        return res

    return wrapper


# ---------------------------------------------------------------------------
# Process-wide compiled-program cache.
#
# The reference amortizes graph setup with shared memory pools
# (GraphExecutor::Init(shared_exec)); on TPU the expensive artifact is the
# XLA executable, and the jit holding it was reachable only through
# object-identity ``shared_exec`` — BucketingModule regenerating a bucket
# symbol, executor_manager, Executor.reshape, and repeated simple_bind in
# tests/serving all retraced and recompiled structurally-identical graphs
# (the compile-amortization problem TVM/nGraph solve with artifact caches
# keyed on graph signature).  This cache keys the jitted fwd / fused
# fwd+bwd pair on Symbol.structural_signature() (+ platform + layout
# pass), so ANY bind of an equal-structure graph reuses the executables;
# jax.jit's own per-aval cache then handles shape/dtype variations under
# each entry.  Bounded LRU; MXTPU_PROGRAM_CACHE=0/off disables, =N sets
# capacity (docs/how_to/env_var.md).
# ---------------------------------------------------------------------------
_PROGRAM_CACHE_DEFAULT_CAPACITY = 64
_program_cache: "OrderedDict" = OrderedDict()
_program_cache_lock = threading.Lock()


def program_cache_capacity() -> int:
    """Resolved MXTPU_PROGRAM_CACHE capacity (0 = cache disabled)."""
    raw = os.environ.get("MXTPU_PROGRAM_CACHE", "").strip().lower()
    if raw in ("", "on", "true", "yes", "default"):
        return _PROGRAM_CACHE_DEFAULT_CAPACITY
    if raw in ("0", "off", "false", "no", "disable", "disabled"):
        return 0
    try:
        return max(int(raw), 0)
    except ValueError:
        return _PROGRAM_CACHE_DEFAULT_CAPACITY


def program_cache_clear():
    """Drop every cached program (test isolation; frees held symbols)."""
    with _program_cache_lock:
        _program_cache.clear()


def program_cache_get(key):
    """Look up an entry by explicit key in the process-wide program LRU.

    Non-bind subsystems (the kvstore's bucketed fused-update engine)
    key their jitted programs into the same LRU so engine rebuilds,
    Module rebinds, and bucket-plan regeneration reuse executables; a
    hit counts in ``executor_graph_cache_total`` like a bind-time hit.
    Returns ``None`` when absent or when the cache is disabled (the
    caller builds and should then call :func:`program_cache_put`)."""
    if program_cache_capacity() <= 0:
        return None
    with _program_cache_lock:
        entry = _program_cache.get(key)
        if entry is not None:
            _program_cache.move_to_end(key)
    if entry is not None:
        _TM_GRAPH_CACHE.inc(result="hit")
    return entry


def program_cache_put(key, entry):
    """Insert an entry built after a :func:`program_cache_get` miss.

    Counts the miss and evicts least-recently-used entries past
    capacity; insertion is skipped (miss still counted) when the cache
    is disabled — the caller keeps its own reference either way."""
    _TM_GRAPH_CACHE.inc(result="miss")
    capacity = program_cache_capacity()
    if capacity <= 0:
        return
    with _program_cache_lock:
        _program_cache[key] = entry
        _program_cache.move_to_end(key)
        while len(_program_cache) > capacity:
            _program_cache.popitem(last=False)


def _compiled_programs(symbol: Symbol, platform: Optional[str],
                       shard_sig=None):
    """(graph_fn, jit_fwd, jit_fwdbwd) for a symbol, through the cache.

    Cache-key discipline: everything that changes the traced computation
    and is not already a jit cache axis must be in the key — the layout
    pass (channels_last) and the lowering platform are; grad reqs are not
    (they are static jit arguments of the fwdbwd program), and input
    avals are not (jax.jit keys on them per call).  ``shard_sig`` is the
    bind's mesh-sharding signature (executor `shardings` / group2ctx
    PartitionSpec placements): the traced Python is sharding-agnostic,
    but keying on it keeps a mesh-annotated bind's entry distinct from a
    single-device bind of the same structure, so cache hits always
    return programs whose jit-level sharding history matches the bind.

    The graph-rewrite pipeline (mxnet_tpu.passes; MXTPU_GRAPH_PASSES)
    runs FIRST, so the key is the POST-pass signature: differently-
    written but equivalent graphs — duplicated subexpressions, dead
    no-op nodes, unfused elementwise chains — rewrite to one canonical
    structure and converge on a single compiled entry.  Different pass
    selections need no extra key axis for the same reason: the
    rewritten structure IS the selection's fingerprint.

    The autotuner's schedule-cache fingerprint (mode + path + epoch) IS
    a key axis: tuned kernels (the residual epilogue's block_rows) bake
    their schedule in at trace time, so a program compiled before a
    search landed would silently keep the stale tiling — composing the
    fingerprint makes the next bind rebuild against the new winner.
    """
    from . import autotune as _autotune
    from . import passes as _passes

    symbol = _passes.apply_graph_passes(symbol)
    channels_last = channels_last_default()
    capacity = program_cache_capacity()
    key = None
    if capacity > 0:
        key = (symbol.structural_signature(), platform, channels_last,
               shard_sig, _autotune.fingerprint())
        with _program_cache_lock:
            entry = _program_cache.get(key)
            if entry is not None:
                _program_cache.move_to_end(key)
        if entry is not None:
            _TM_GRAPH_CACHE.inc(result="hit")
            return entry
    graph_fn = _build_graph_fn(symbol, channels_last=channels_last,
                               platform=platform)
    jit_fwd = jax.jit(_count_traces(graph_fn, "fwd"), static_argnums=(3,))
    jit_fwdbwd = jax.jit(
        _count_traces(_make_fwdbwd(graph_fn, placed=False), "fwdbwd"),
        static_argnames=("gnames", "add_names", "rs_specs"))
    entry = (graph_fn, jit_fwd, jit_fwdbwd)
    if key is not None:
        with _program_cache_lock:
            _program_cache[key] = entry
            _program_cache.move_to_end(key)
            while len(_program_cache) > capacity:
                _program_cache.popitem(last=False)
    _TM_GRAPH_CACHE.inc(result="miss")
    return entry


# ---------------------------------------------------------------------------
# Channels-last (NHWC) execution pass.
#
# The public API is NCHW (reference parity) but TPU compute wants the
# channel dim minor: XLA tiles the minor axis onto the 128-wide MXU/VPU
# lanes, and a logically-NCHW conv graph makes layout assignment insert
# transposes it cannot always elide (measured: ResNet-50 train step was
# HBM-bound at 14% MFU).  This pass keeps weights/params in their logical
# layouts and retraces the *activation* flow: 4D activations are
# transposed to NHWC once where they enter a spatial chain (normally the
# graph input) and back where they leave it (normally the global-pool /
# Flatten boundary); spatial ops run with __layout__="NHWC" (ops/nn.py),
# elementwise chains pass through untouched, and anything unknown falls
# back to NCHW — the pass can only change op *layouts*, never op math.
# Opt out with MXTPU_CONV_LAYOUT=NCHW.
# ---------------------------------------------------------------------------
_CL_SPATIAL = {"Convolution", "Pooling", "BatchNorm", "LRN"}
_CL_UNARY = {
    # single-tensor-input ops that commute with transpose
    "abs", "arccos", "arccosh", "arcsin", "arcsinh", "arctan", "arctanh",
    "ceil", "cos", "cosh", "degrees", "exp", "expm1", "fix", "floor",
    "gamma", "gammaln", "log", "log10", "log1p", "log2", "negative",
    "radians", "rint", "round", "rsqrt", "sign", "sin", "sinh", "sqrt",
    "square", "tan", "tanh", "sigmoid", "relu", "_copy", "identity",
    "BlockGrad", "stop_gradient", "Activation", "Dropout", "clip",
    "smooth_l1",
    "_plus_scalar", "_minus_scalar", "_rminus_scalar", "_mul_scalar",
    "_div_scalar", "_rdiv_scalar", "_power_scalar", "_rpower_scalar",
    "_maximum_scalar", "_minimum_scalar", "_hypot_scalar",
    # a pre-fused elementwise chain (passes/prefuse.py) is itself a pure
    # elementwise map, so it passes NHWC through like its parts would
    "_fused_elemwise", "Cast",
}
_CL_MULTI = {
    # same-shape multi-tensor elementwise (incl. residual adds)
    "elemwise_add", "_plus", "_add", "_Plus", "elemwise_sub", "_minus",
    "_sub", "_Minus", "elemwise_mul", "_mul", "_Mul", "elemwise_div",
    "_div", "_Div", "_power", "_Power", "_maximum", "_Maximum",
    "_minimum", "_Minimum", "_hypot", "_grad_add",
    "ElementWiseSum", "add_n", "_sum",
}
_CL_CHANNEL_AXIS = {"Concat": "dim", "concat": "dim",
                    "SliceChannel": "axis", "split": "axis"}
# fused residual epilogues (ops/residual_epilogue.py): the two 4D
# activation inputs ride NHWC (that IS the Pallas kernel's layout);
# the per-channel affine/stat inputs stay logical 1-D
_CL_EPILOGUE = {"_residual_epilogue", "_residual_epilogue_bn"}


def channels_last_default() -> bool:
    return os.environ.get("MXTPU_CONV_LAYOUT", "NHWC").upper() != "NCHW"


def _to_nhwc(x):
    return jnp.transpose(x, (0, 2, 3, 1))


def _to_nchw(x):
    return jnp.transpose(x, (0, 3, 1, 2))


def _cl_eligible(node, ins):
    """Can this spatial op run channels-last on these traced inputs?"""
    data = ins[0]
    if data.ndim != 4:
        return False
    if node.op == "Convolution":
        return len(ins) >= 2 and ins[1].ndim == 4  # 2D kernel only
    return True


def _cl_adapt(node, ins, lay, hwio_params=frozenset()):
    """Pick the execution layout for one node (trace time, zero runtime
    cost beyond the transposes actually emitted).  Returns
    (adapted_inputs, attrs, out_is_nhwc).

    ``hwio_params``: conv-weight variables whose STORAGE is physically
    HWIO (FusedTrainer keeps masters/momentum/compute-cache in the
    layout the NHWC conv consumes, so no per-step relayout traffic —
    measured +1.2 ms/step of 'data formatting' on ResNet-50 b32
    otherwise); the conv is told via __wlayout__ and reads it directly.
    """
    from .base import parse_attr, parse_bool

    name = node.op
    inlay = [lay.get((id(src), oidx), False) for src, oidx in node.inputs]
    attrs = node.attrs
    if name in _CL_SPATIAL and _cl_eligible(node, ins):
        data = ins[0] if inlay[0] else _to_nhwc(ins[0])
        # remaining inputs (weights/stats) must arrive in their logical
        # layouts — a computed weight coming off an NHWC activation chain
        # (dynamic-filter nets) is converted back
        rest = [(_to_nchw(x) if l else x)
                for x, l in zip(ins[1:], inlay[1:])]
        attrs = {**attrs, "__layout__": "NHWC"}
        if (name == "Convolution" and len(node.inputs) >= 2
                and node.inputs[1][0].is_variable
                and node.inputs[1][0].name in hwio_params):
            attrs["__wlayout__"] = "HWIO"
        return [data] + rest, attrs, True
    if name in _CL_EPILOGUE and len(ins) >= 2 and any(inlay[:2]) \
            and ins[0].ndim == 4 and ins[1].ndim == 4:
        a = ins[0] if inlay[0] else _to_nhwc(ins[0])
        b = ins[1] if inlay[1] else _to_nhwc(ins[1])
        rest = [(_to_nchw(x) if l else x)
                for x, l in zip(ins[2:], inlay[2:])]
        return [a, b] + rest, {**attrs, "__layout__": "NHWC"}, True
    if name in _CL_UNARY and len(ins) == 1 and inlay[0]:
        return ins, attrs, True
    if name in _CL_MULTI and any(inlay) and all(x.ndim == 4 for x in ins):
        return [x if l else _to_nhwc(x) for x, l in zip(ins, inlay)], attrs, True
    if name in _CL_CHANNEL_AXIS and any(inlay) and all(x.ndim == 4 for x in ins):
        axis_key = _CL_CHANNEL_AXIS[name]
        axis = int(parse_attr(attrs.get(axis_key, 1)))
        squeeze = (parse_bool(attrs.get("squeeze_axis", False))
                   if name in ("SliceChannel", "split") else False)
        if axis == 1 and not squeeze:
            ins = [x if l else _to_nhwc(x) for x, l in zip(ins, inlay)]
            return ins, {**attrs, axis_key: 3}, True
    # fallback: this op runs NCHW — convert whatever arrived channels-last
    return [(_to_nchw(x) if l else x) for x, l in zip(ins, inlay)], attrs, False


def _eval_node(node, topo_index, env, key, is_train, lay=None, platform=None,
               hwio_params=frozenset(), layout_report=None, mesh=None):
    """Evaluate one op node into env; returns {aux_name: new_val} updates.

    ``lay`` (entry -> is_nhwc) enables the channels-last pass; None keeps
    plain NCHW evaluation (the placed/segment path).  ``platform`` is the
    execution platform threaded into OpCtx (see registry.OpCtx), ``mesh``
    the device mesh the graph is partitioned over.
    ``layout_report`` (a dict with "conv_w"/"other" sets) collects which
    variables are consumed as NHWC conv weights vs by anything else —
    the discovery pass behind FusedTrainer's HWIO weight storage (a
    variable is only HWIO-safe when NHWC convs are its ONLY consumers;
    any other reader would silently misinterpret the transposed axes).
    """
    od = ops.get(node.op)
    ins = [env[id(src)][oidx] for src, oidx in node.inputs]
    attrs = node.attrs
    out_nhwc = False
    if lay is not None:
        ins, attrs, out_nhwc = _cl_adapt(node, ins, lay, hwio_params)
        if layout_report is not None:
            for idx, (src, _oidx) in enumerate(node.inputs):
                if not src.is_variable:
                    continue
                if (node.op == "Convolution" and out_nhwc and idx == 1):
                    layout_report["conv_w"].add(src.name)
                else:
                    layout_report["other"].add(src.name)
    octx = ops.OpCtx(
        is_train=is_train,
        key=jax.random.fold_in(key, topo_index) if od.needs_rng else None,
        platform=platform,
        mesh=mesh,
    )
    res = od.fn(octx, *ins, **attrs)
    aux_updates = {}
    if od.aux_names:
        res, updates = res
        aux_arg_names = node.inputs[-len(od.aux_names):]
        for (aux_node, _), val in zip(aux_arg_names, updates):
            aux_updates[aux_node.name] = val
    if not isinstance(res, tuple):
        res = (res,)
    env[id(node)] = res
    if lay is not None:
        for k in range(len(res)):
            lay[(id(node), k)] = out_nhwc
    return aux_updates


def _build_graph_fn(symbol: Symbol, channels_last: Optional[bool] = None,
                    platform: Optional[str] = None,
                    hwio_params=frozenset(), layout_report=None,
                    mesh=None):
    """Build f(arg_dict, aux_dict, key, is_train) -> (outputs, new_aux_dict).

    This is the tracing equivalent of GraphExecutor::InitCachedOps
    (graph_executor.cc:518-648): one closure per graph, evaluated under
    jax.jit so every node fuses into a single XLA program.  With
    ``channels_last`` (default from MXTPU_CONV_LAYOUT) 4D activation
    chains execute NHWC; graph outputs are always converted back to the
    logical NCHW layout.  ``platform`` tells platform-sensitive ops
    (FlashAttention: Pallas vs lax) what they will lower for; None means
    "the default backend".  ``mesh`` is the device mesh the caller
    partitions this graph over: ops that lower to a Mosaic kernel
    shard_map it over that mesh (GSPMD cannot partition one).
    """
    if channels_last is None:
        channels_last = channels_last_default()
    out_entries = list(symbol._outputs)
    topo = _topo_order([n for n, _ in out_entries])
    # row-sparse-gradient Embedding nodes (sparse.rs_plan): evaluated
    # inline so (a) an optional zero "probe" rides on the gathered rows
    # — its vjp cotangent IS the per-row gradient, no dense scatter into
    # the table — and (b) the looked-up ids surface through new_aux for
    # the fwdbwd wrapper's in-trace unique-row segment-sum.  Probe-less
    # calls compute exactly what the Embedding op computes (clip + take),
    # so fwd-only paths and MXTPU_SPARSE_UPDATE=0 are bit-identical.
    from . import sparse as _sparse

    rs_nodes = {id(node): wname
                for wname, node in _sparse.rs_plan(symbol).items()}

    def fn(arg_vals: Dict, aux_vals: Dict, key, is_train: bool):
        env = {}
        lay = {} if channels_last else None
        new_aux = dict(aux_vals)
        for i, node in enumerate(topo):
            if node.is_variable:
                if node.is_aux:
                    env[id(node)] = (aux_vals[node.name],)
                else:
                    env[id(node)] = (arg_vals[node.name],)
                continue
            rsw = rs_nodes.get(id(node))
            if rsw is not None:
                data = env[id(node.inputs[0][0])][node.inputs[0][1]]
                w = env[id(node.inputs[1][0])][node.inputs[1][1]]
                if lay is not None:
                    if lay.get((id(node.inputs[0][0]), node.inputs[0][1])):
                        data = _to_nchw(data)
                    if lay.get((id(node.inputs[1][0]), node.inputs[1][1])):
                        w = _to_nchw(w)
                idx = jnp.clip(data.astype(jnp.int32), 0, w.shape[0] - 1)
                out = jnp.take(w, idx, axis=0)
                probe = arg_vals.get("__rs_probe__:" + rsw)
                if probe is not None:
                    out = out + probe.reshape(out.shape).astype(out.dtype)
                env[id(node)] = (out,)
                if lay is not None:
                    lay[(id(node), 0)] = False
                new_aux["__rs_idx__:" + rsw] = idx.reshape(-1)
                continue
            new_aux.update(_eval_node(node, i, env, key, is_train, lay,
                                      platform, hwio_params, layout_report,
                                      mesh))
        outputs = [
            _to_nchw(env[id(n)][i]) if lay and lay.get((id(n), i))
            else env[id(n)][i]
            for n, i in out_entries
        ]
        return outputs, new_aux

    return fn


# ---------------------------------------------------------------------------
# ctx_group placement (parity: nnvm::pass::PlaceDevice + _CrossDeviceCopy,
# graph_executor.cc:225-314)
# ---------------------------------------------------------------------------
def placement_plan(symbol: Symbol, group2ctx, default_ctx):
    """Assign every graph node a concrete jax.Device from its ctx_group.

    Returns (node_ctx, var_ctx, n_distinct) where node_ctx maps
    id(op_node) -> Context, var_ctx maps variable *name* -> Context (a
    variable lives with its first consumer, mirroring PlaceDevice's
    device propagation), and n_distinct counts distinct concrete devices
    in the plan.  group2ctx entries not matching any annotation are
    ignored, as in the reference (bind warns once per unknown group).
    """
    group2ctx = {g: c for g, c in group2ctx.items()
                 if isinstance(c, Context)}
    topo = _topo_order([n for n, _ in symbol._outputs])
    node_ctx, var_ctx = {}, {}
    # a variable's OWN annotation wins (reference PlaceDevice honors the
    # node's __ctx_group__); unannotated variables fall to first consumer
    for node in topo:
        if node.is_variable:
            grp = node.extra_attrs.get("ctx_group")
            if grp and grp in group2ctx:
                var_ctx[node.name] = group2ctx[grp]
    for node in topo:
        if node.is_variable:
            continue
        grp = node.extra_attrs.get("ctx_group")
        ctx = group2ctx.get(grp) if grp else None
        if ctx is None:
            ctx = default_ctx
        node_ctx[id(node)] = ctx
        for src, _ in node.inputs:
            if src.is_variable and src.name not in var_ctx:
                var_ctx[src.name] = ctx  # first consumer wins
    distinct = {c.jax_device for c in node_ctx.values()} | {
        c.jax_device for c in var_ctx.values()}
    return node_ctx, var_ctx, len(distinct)


# ---------------------------------------------------------------------------
# group2ctx -> mesh placement (the GSPMD half of PlaceDevice).
#
# A group2ctx value may be a jax.sharding.PartitionSpec (or a Sharding)
# instead of a Context: the group's variables are then placed as
# NamedSharding annotations on the process mesh
# (context.process_mesh(); MXTPU_MESH_SHAPE) and the whole graph stays
# ONE compiled SPMD program — XLA GSPMD inserts the collectives the
# reference's _CrossDeviceCopy edges would have been.  Contexts keep the
# segmented per-device plan for true disjoint-device model parallelism.
# ---------------------------------------------------------------------------
_warned_unknown_groups = set()


def _resolve_group_sharding(value):
    """group2ctx value -> NamedSharding on the process mesh, or None
    when the value is a Context (the segmented-placement path)."""
    from jax.sharding import PartitionSpec, Sharding

    if isinstance(value, Sharding):
        return value
    if isinstance(value, PartitionSpec):
        from .context import mesh_sharding

        return mesh_sharding(value)
    return None


def sharding_plan(symbol: Symbol, group2ctx):
    """{variable name: Sharding} for PartitionSpec-valued group2ctx
    entries, following placement_plan's propagation (a variable's own
    ctx_group wins; otherwise first consumer's group)."""
    spec_groups = {}
    for g, v in (group2ctx or {}).items():
        sh = _resolve_group_sharding(v)
        if sh is not None:
            spec_groups[g] = sh
    if not spec_groups:
        return {}
    topo = _topo_order([n for n, _ in symbol._outputs])
    var_sh = {}
    for node in topo:
        if node.is_variable:
            grp = node.extra_attrs.get("ctx_group")
            if grp in spec_groups:
                var_sh[node.name] = spec_groups[grp]
    for node in topo:
        if node.is_variable:
            continue
        grp = node.extra_attrs.get("ctx_group")
        sh = spec_groups.get(grp) if grp else None
        if sh is None:
            continue
        for src, _ in node.inputs:
            if src.is_variable and src.name not in var_sh:
                var_sh[src.name] = sh
    return var_sh


def _fit_sharding_rank(sh, ndim):
    """Adapt a NamedSharding to an array's rank: a group-level spec like
    P("model", None) also covers the group's rank-1 biases (Megatron
    convention: the bias shards with its weight's output dim) by
    truncating trailing spec entries the array has no dims for."""
    from jax.sharding import NamedSharding, PartitionSpec

    if not isinstance(sh, NamedSharding) or len(sh.spec) <= ndim:
        return sh
    return NamedSharding(sh.mesh, PartitionSpec(*sh.spec[:ndim]))


def _warn_unmatched_groups(symbol: Symbol, group2ctx):
    """A group2ctx entry naming a group no node is annotated with used
    to be silently ignored — a typo'd group name trained fully on the
    default device with nothing to say about it.  Warn once per name."""
    if not group2ctx:
        return
    annotated = {n.extra_attrs.get("ctx_group")
                 for n in symbol.nodes if n.extra_attrs.get("ctx_group")}
    for g in group2ctx:
        if g not in annotated and g not in _warned_unknown_groups:
            _warned_unknown_groups.add(g)
            import warnings

            warnings.warn(
                f"group2ctx group {g!r} matches no ctx_group annotation "
                f"in the symbol (annotated groups: {sorted(annotated)}); "
                "the entry is ignored", stacklevel=3)


class _Segment:
    """A maximal run of topo-consecutive op nodes on one device, compiled
    as one XLA program.  Transfers between segments are the explicit
    _CrossDeviceCopy points."""

    __slots__ = ("device", "nodes", "indices", "inputs", "outputs", "jit_fn")

    def __init__(self, device):
        self.device = device
        self.nodes = []
        self.indices = []  # global topo index per node (stable RNG folding)

    def finalize(self, produced_by_me, needed_entries):
        # entries this segment consumes but does not produce
        seen, ins = set(), []
        for node in self.nodes:
            for src, oidx in node.inputs:
                e = (id(src), oidx)
                if e not in produced_by_me and e not in seen:
                    seen.add(e)
                    ins.append(e)
        self.inputs = ins
        self.outputs = list(needed_entries)

        nodes, indices = self.nodes, self.indices
        inputs, outputs = self.inputs, self.outputs

        platform = getattr(self.device, "platform", None)

        def seg_fn(in_vals, key, is_train):
            env = {}
            for (nid, oidx), v in zip(inputs, in_vals):
                env.setdefault(nid, {})[oidx] = v
            aux_updates = {}
            for node, gi in zip(nodes, indices):
                aux_updates.update(_eval_node(node, gi, env, key, is_train,
                                              platform=platform))
            return tuple(env[nid][oidx] for nid, oidx in outputs), aux_updates

        self.jit_fn = jax.jit(_count_traces(seg_fn, "segment"),
                              static_argnums=(2,))


def _build_placed_fn(symbol: Symbol, node_ctx, var_ctx, default_ctx):
    """Multi-device execution plan for a ctx_group-annotated graph.

    The graph is cut into per-device segments; each segment is its own
    jit (committed to its device via its inputs), and jax.device_put
    between segments is the explicit transfer point — the TPU-native
    _CrossDeviceCopy.  XLA's async dispatch overlaps segments on
    different devices exactly the way the reference's dependency engine
    overlaps ctx_group stages (docs/how_to/model_parallel_lstm.md).
    Autodiff traces through the segment jits, so the fused fwd+bwd path
    and grad placement follow the same plan.
    """
    default_dev = default_ctx.jax_device
    node_device = {k: c.jax_device for k, c in node_ctx.items()}
    var_device = {k: c.jax_device for k, c in var_ctx.items()}
    out_entries = list(symbol._outputs)
    topo = _topo_order([n for n, _ in out_entries])

    segments = []
    node_seg = {}  # id(op_node) -> segment index
    for i, node in enumerate(topo):
        if node.is_variable:
            continue
        dev = node_device.get(id(node), default_dev)
        if not segments or segments[-1].device is not dev:
            segments.append(_Segment(dev))
        segments[-1].nodes.append(node)
        segments[-1].indices.append(i)
        node_seg[id(node)] = len(segments) - 1

    # entries needed outside their producing segment: graph outputs + any
    # entry crossing a segment boundary (those are the transfer points)
    needed = set((id(n), i) for n, i in out_entries if not n.is_variable)
    for si, seg in enumerate(segments):
        for node in seg.nodes:
            for src, oidx in node.inputs:
                if not src.is_variable and node_seg[id(src)] != si:
                    needed.add((id(src), oidx))
    for seg in segments:
        produced = set()
        for node in seg.nodes:
            for k in range(node.num_outputs()):
                produced.add((id(node), k))
        seg.finalize(produced, sorted(needed & produced))

    var_nodes = [n for n in topo if n.is_variable]

    def fn(arg_vals: Dict, aux_vals: Dict, key, is_train: bool):
        env = {}
        for n in var_nodes:
            val = aux_vals[n.name] if n.is_aux else arg_vals[n.name]
            dev = var_device.get(n.name, default_dev)
            env[id(n)] = (jax.device_put(val, dev),)
        new_aux = dict(aux_vals)
        for seg in segments:
            ins = tuple(jax.device_put(env[nid][oidx], seg.device)
                        for nid, oidx in seg.inputs)
            outs, aux_updates = seg.jit_fn(
                ins, jax.device_put(key, seg.device), is_train)
            for (nid, oidx), v in zip(seg.outputs, outs):
                env.setdefault(nid, {})[oidx] = v
            new_aux.update(aux_updates)
        outputs = [env[id(n)][i] for n, i in out_entries]
        return outputs, new_aux

    return fn


def _zero_cotangent(x):
    """Zero cotangent for an aux leaf: floats get zeros_like; integer/
    bool leaves (the row-sparse path's looked-up ids riding in new_aux)
    take jax's float0 convention — an int-dtyped zero would be rejected
    by the vjp."""
    if jnp.issubdtype(jnp.result_type(x), jnp.inexact):
        return jnp.zeros_like(x)
    return np.zeros(np.shape(x), jax.dtypes.float0)


def _make_fwdbwd(graph_fn, placed: bool):
    """Build the fused fwd+bwd evaluator over ``graph_fn``.

    ``gnames`` (args needing grads) and ``add_names`` (the grad_req="add"
    subset) are static arguments: every write/add/null combination lowers
    to its own fully-fused XLA program.  ``grad_ins`` carries the current
    grad buffers for ``add_names`` so accumulation happens INSIDE the
    compiled program (reference OpReqType kAddTo semantics,
    include/mxnet/op_attr_types.h) instead of an eager read-add-write
    round trip per param.  An empty ``head_grads`` means "seed with ones":
    the cotangents are built in-trace from the forward outputs — a
    loss-graph backward() therefore costs no per-call jax.eval_shape and
    no extra host dispatches for the seed arrays.

    ``rs_specs`` (static) lists the row-sparse-gradient embedding
    weights as ``(name, n_ids, row_dim, dtype)``: each gets an in-trace
    zero probe differentiated INSTEAD of the table itself, and its
    cotangent — the per-lookup gradient rows — is coalesced by the
    in-trace unique-row segment-sum into the ``(indices, values)`` pair
    returned as that weight's gradient.  The dense scatter into the
    full table never happens.

    ``loss_scale`` (None when AMP loss scaling is off — the off path
    traces bit-identically) is the scaler's DEVICE scalar: gradients
    are multiplied by it in-trace at the vjp boundary.  The boundary —
    not the ones seed — because the reference's loss-output ops
    (SoftmaxOutput & co.) discard the head cotangent by contract, so a
    seed-side scale would silently not propagate through the graphs
    the Module path actually trains.  The fused kvstore bucket update
    unscales (and detects overflow / skips) in ITS program; the scale
    is constant between optimizer steps, so grad_req="add"
    accumulation across backwards composes exactly.
    """

    def fwdbwd(arg_vals, aux_vals, key, head_grads, grad_ins, loss_scale,
               gnames: tuple, add_names: tuple, rs_specs: tuple = ()):
        def fwd_for_grad(grad_args):
            merged = dict(arg_vals)
            merged.update(grad_args)
            outs, new_aux = graph_fn(merged, aux_vals, key, True)
            return outs, new_aux

        grad_args = {k: arg_vals[k] for k in gnames}
        for wname, n_ids, row_dim, dt in rs_specs:
            # zero probe built in-trace (XLA folds it): the graph fn
            # adds it onto the gathered rows, so d out/d probe is the
            # row gradient — shape-stable at n_ids slots
            grad_args["__rs_probe__:" + wname] = jnp.zeros(
                (n_ids, row_dim), jnp.dtype(dt))
        (outs, new_aux), vjp_fn = jax.vjp(
            lambda ga: fwd_for_grad(ga), grad_args, has_aux=False
        )
        provided_heads = bool(head_grads)
        if not head_grads:
            # ones seed — custom_vjp loss ops discard it (parity with
            # reference loss-op backward semantics); placement follows
            # each output, so the placed path needs no device_put either
            head_grads = [jnp.ones_like(o) for o in outs]
        else:
            # caller-provided seeds follow the OUTPUT dtype (an
            # amp_cast-rewritten graph may emit bf16 outputs; an f32
            # ones seed would be rejected by the vjp)
            head_grads = [
                h.astype(o.dtype) if h.dtype != o.dtype else h
                for h, o in zip(head_grads, outs)
            ]
        if provided_heads and placed:
            # the seed cotangent must sit where its primal output sits,
            # or the last segment's transposed pjit sees mixed device
            # commitments; interior cotangents then follow the
            # transposed device_put edges automatically
            head_grads = [
                jax.device_put(h, next(iter(o.devices())))
                for h, o in zip(head_grads, outs)
            ]
        # cotangent: (outputs_cot, aux_cot=zeros; float0 for int leaves)
        aux_cot = jax.tree_util.tree_map(_zero_cotangent, new_aux)
        (grads,) = vjp_fn((list(head_grads), aux_cot))
        if rs_specs:
            from . import sparse as _sparse

            grads = dict(grads)
            for wname, n_ids, row_dim, dt in rs_specs:
                vals = grads.pop("__rs_probe__:" + wname)
                ids = new_aux["__rs_idx__:" + wname]
                sid, gvals, _first = _sparse.coalesce_rows(ids, vals)
                grads[wname] = (sid, gvals)
        if loss_scale is not None:
            grads = {
                k: ((g[0], g[1] * loss_scale.astype(g[1].dtype))
                    if isinstance(g, tuple)
                    else g * loss_scale.astype(g.dtype))
                for k, g in grads.items()
            }
        if add_names:
            grads = dict(grads)
            for k in add_names:
                # grad_in + g, matching the retired eager path's operand
                # order bit-for-bit
                grads[k] = grad_ins[k] + grads[k]
        return outs, new_aux, grads

    return fwdbwd


class Executor:
    """Parity: include/mxnet/executor.h Executor + python/mxnet/executor.py."""

    def _platform(self):
        """Platform of this executor's bind device, for OpCtx threading."""
        try:
            return self._ctx.jax_device.platform
        except Exception:  # noqa: BLE001 — unresolvable ctx: defer to default
            return None

    def __init__(self, symbol: Symbol, ctx: Optional[Context], args, args_grad,
                 grad_req="write", aux_states=None, group2ctx=None,
                 shared_exec: "Executor" = None, shardings=None):
        self._symbol = symbol
        self._ctx = ctx or current_context()
        self._group2ctx = group2ctx or {}
        _warn_unmatched_groups(symbol, self._group2ctx)
        # mesh-sharding annotations: explicit `shardings` ({var name ->
        # jax Sharding}, e.g. from DataParallelExecutorGroup) merged
        # over group2ctx PartitionSpec placements.  These place the
        # bound arrays; the jitted programs see the placements through
        # their committed inputs (GSPMD spans the mesh from them), and
        # the signature below keys the program cache.
        self._shardings = dict(sharding_plan(symbol, self._group2ctx))
        self._shardings.update(shardings or {})
        self._shard_sig = tuple(sorted(
            (name, str(sh)) for name, sh in self._shardings.items())) or None
        arg_names = symbol.list_arguments()
        aux_names = symbol.list_auxiliary_states()

        # --- normalize arg containers (parity: executor bind signature) ----
        if isinstance(args, dict):
            self.arg_dict = {k: args[k] for k in arg_names if k in args}
            missing = [k for k in arg_names if k not in args]
            if missing:
                raise MXNetError(f"bind: missing arguments {missing}")
            self.arg_arrays = [self.arg_dict[k] for k in arg_names]
        else:
            args = list(args or [])
            if len(args) != len(arg_names):
                raise MXNetError(
                    f"bind: expected {len(arg_names)} args ({arg_names}), got {len(args)}"
                )
            self.arg_arrays = args
            self.arg_dict = dict(zip(arg_names, args))

        if isinstance(args_grad, dict):
            self.grad_dict = dict(args_grad)
        elif args_grad is None:
            self.grad_dict = {}
        else:
            self.grad_dict = dict(zip(arg_names, args_grad))
        self.grad_arrays = [self.grad_dict.get(k) for k in arg_names]

        if isinstance(grad_req, str):
            self.grad_req = {k: grad_req for k in arg_names}
        elif isinstance(grad_req, (list, tuple)):
            self.grad_req = dict(zip(arg_names, grad_req))
        else:
            self.grad_req = {k: grad_req.get(k, "null") for k in arg_names}
        for k, v in self.grad_req.items():
            if v not in _GRAD_REQ:
                raise MXNetError(f"invalid grad_req {v} for {k}")
        # args without a grad array can't be written
        for k in arg_names:
            if k not in self.grad_dict:
                self.grad_req[k] = "null"

        if isinstance(aux_states, dict):
            self.aux_dict = dict(aux_states)
        else:
            self.aux_dict = dict(zip(aux_names, aux_states or []))
        missing_aux = [k for k in aux_names if k not in self.aux_dict]
        if missing_aux:
            raise MXNetError(f"bind: missing aux states {missing_aux}")
        self.aux_arrays = [self.aux_dict[k] for k in aux_names]

        # place annotated arrays on their mesh shardings (one batched
        # transfer; arrays already carrying the target sharding pass).
        # Any mesh annotation commits the WHOLE bind to that mesh:
        # unannotated arrays default to replicated, or the jit would see
        # mixed single-device/mesh operands and refuse to compile.
        if self._shardings:
            from jax.sharding import NamedSharding, PartitionSpec

            meshes = [sh.mesh for sh in self._shardings.values()
                      if isinstance(sh, NamedSharding) and sh.mesh.size > 1]
            if meshes:
                repl = NamedSharding(meshes[0], PartitionSpec())
                for name in list(arg_names) + list(aux_names):
                    self._shardings.setdefault(name, repl)
            todo, targets = {}, {}
            for name, sh in self._shardings.items():
                for store in (self.arg_dict, self.aux_dict, self.grad_dict):
                    arr = store.get(name)
                    if arr is None or getattr(arr, "stype",
                                              "default") != "default":
                        # a row-sparse grad holder has no dense buffer
                        # to place; its (indices, values) land sharded
                        # by the backward program itself
                        continue
                    raw = arr._read()
                    tgt = _fit_sharding_rank(sh, raw.ndim)
                    if getattr(raw, "sharding", None) != tgt:
                        todo[id(arr)] = raw
                        targets[id(arr)] = (arr, tgt)
            if todo:
                moved = jax.device_put(
                    todo, {k: targets[k][1] for k in todo})
                for k, raw in moved.items():
                    targets[k][0]._chunk.write(raw)

        # ctx_group placement (parity: PlaceDevice, graph_executor.cc:225-314):
        # only a plan spanning >1 device changes execution; a single-device
        # plan keeps the whole-graph jit fast path.
        self._placed = False
        self._plan = None
        if self._group2ctx:
            node_dev, var_dev, n_distinct = placement_plan(
                symbol, self._group2ctx, self._ctx)
            self._placed = n_distinct > 1
            if self._placed:
                self._plan = (node_dev, var_dev)
        self._grad_names = [k for k in arg_names if self.grad_req.get(k) != "null"]
        # row-sparse gradient emission: args whose grad buffer is a
        # RowSparseNDArray holder (simple_bind allocates them for
        # grad_stype="row_sparse" variables when MXTPU_SPARSE_UPDATE is
        # on) leave the vjp'd name set and get probe specs instead
        rs_holders = sorted(
            k for k, g in self.grad_dict.items()
            if getattr(g, "stype", "default") == "row_sparse")
        self._rs_specs = self._build_rs_specs(symbol, rs_holders) \
            if rs_holders else ()
        rs_set = {s[0] for s in self._rs_specs}
        # static arguments of the fused fwd+bwd program: which args need
        # grads, and which of those accumulate (grad_req="add") INSIDE the
        # compiled program — fixed at bind time, so precomputed once
        self._gnames = tuple(k for k in self._grad_names if k not in rs_set)
        self._add_names = tuple(
            k for k in self._grad_names
            if self.grad_req.get(k) == "add" and k not in rs_set)
        if self._placed:
            self._graph_fn = _build_placed_fn(symbol, node_dev, var_dev, self._ctx)
            # segments carry their own jits; the outer pipeline must stay
            # un-jitted or GSPMD would re-place everything on one device —
            # and the program cache is skipped: the plan is keyed by
            # concrete devices, not graph structure
            self._jit_fwd = self._graph_fn
            self._jit_fwdbwd = _make_fwdbwd(self._graph_fn, placed=True)
            _TM_GRAPH_CACHE.inc(result="miss")
        elif shared_exec is not None and shared_exec._symbol is symbol:
            # object-identity fast path (no signature hash); the donor's
            # entry already sits in the program cache when it is enabled
            self._graph_fn = shared_exec._graph_fn
            self._jit_fwd = shared_exec._jit_fwd
            self._jit_fwdbwd = shared_exec._jit_fwdbwd
            _TM_GRAPH_CACHE.inc(result="hit")
        else:
            self._graph_fn, self._jit_fwd, self._jit_fwdbwd = \
                _compiled_programs(symbol, self._platform(),
                                   shard_sig=self._shard_sig)
        # AMP dynamic loss scaling is a BIND-TIME decision (docs/amp.md):
        # placed (ctx_group segmented) graphs skip the pass pipeline and
        # therefore the whole AMP policy
        from . import amp as _amp

        self._amp_scale = (not self._placed) and _amp.scaling_active()
        self._step = 0
        self._pending = None  # (args_raw, aux_raw, key) of last train forward
        self._outputs_cache: Optional[List] = None
        # per-step input-dict reuse (see _gather_inputs): {name: value}
        # dicts mutated in place + (ndarray, chunk, version) fingerprints
        self._args_cache = ({}, {})
        self._aux_cache = ({}, {})
        self._monitor_callback = None
        self._monitor_fn = None   # lazily-compiled internals tap
        self._monitor_names = None
        # device-memory accounting (telemetry/health.py): one
        # attribution row per bound program, keyed by structure so
        # rebinds refresh rather than multiply; shape math here, the
        # compiled memory_analysis upgrade happens at first forward on
        # non-CPU backends
        self._program_label = self._record_bind_memory()
        self._mem_analyzed = False
        # perf-attribution plane (telemetry/perf.py, MXTPU_PERF_ATTR):
        # one analytical cost row per compiled program at first
        # dispatch, fwd and fwdbwd each captured once (the fwdbwd row
        # wins the shared label once training runs); the train
        # forward's host wall is carried into backward's dispatch
        # record so the fused program owns the whole fwd+bwd wall
        self._cost_fwd_done = False
        self._cost_fwdbwd_done = False
        self._pending_fwd_wall = 0.0

    def _build_rs_specs(self, symbol, rs_holders):
        """Static ``(name, n_ids, row_dim, dtype)`` probe specs for the
        fused fwd+bwd program, one per row-sparse grad holder.  The id
        count comes from the Embedding node's data-input shape under the
        bound arg shapes, so the spec (and the compiled program) is
        fixed per bind like every other shape."""
        from . import sparse as _sparse

        if self._placed:
            raise MXNetError(
                "row_sparse gradients are not supported with ctx_group "
                "Context placement; use mesh PartitionSpec placement or "
                "dense gradients")
        plan = _sparse.rs_plan(symbol)
        known = {k: v.shape for k, v in self.arg_dict.items()}
        shapes, _ = symbol._infer(known, {}, partial=True)
        specs = []
        for wname in rs_holders:
            node = plan.get(wname)
            w_arr = self.arg_dict.get(wname)
            if node is None or w_arr is None \
                    or self.grad_req.get(wname) != "write":
                raise MXNetError(
                    f"bind: arg {wname!r} has a row_sparse gradient "
                    "buffer but is not the sole weight of one Embedding "
                    "op with grad_req='write'; bind a dense gradient "
                    "instead")
            src, oidx = node.inputs[0]
            dshape = shapes.get((src.name, "var")) if src.is_variable \
                else shapes.get((id(src), oidx))
            if dshape is None or len(w_arr.shape) != 2:
                raise MXNetError(
                    f"bind: cannot infer the lookup shape feeding "
                    f"Embedding weight {wname!r}")
            specs.append((wname, int(np.prod(dshape)),
                          int(w_arr.shape[1]),
                          np.dtype(w_arr.dtype).name))
        return tuple(specs)

    def _record_bind_memory(self):
        try:
            try:
                sig = str(self._symbol.structural_signature())[:10]
            except Exception:  # noqa: BLE001
                sig = "%x" % (id(self._symbol) & 0xFFFFFF)
            label = f"{self._symbol.name or 'graph'}[{sig}]"

            def _nd_bytes(nd_arr):
                return int(nd_arr.size) * np.dtype(nd_arr.dtype).itemsize

            arg_b = sum(_nd_bytes(v) for v in self.arg_dict.values())
            arg_b += sum(_nd_bytes(v) for v in self.aux_dict.values())
            grad_b = sum(_nd_bytes(v) for v in self.grad_dict.values()
                         if v is not None)
            out_b = 0
            try:
                shapes = {k: v.shape for k, v in self.arg_dict.items()}
                _, out_shapes, _ = self._symbol.infer_shape(**shapes)
                out_b = sum(int(np.prod(s)) * 4 for s in out_shapes or ())
            except Exception:  # noqa: BLE001 — unknown outputs stay 0
                pass
            _tm.health.record_program(label, argument=arg_b + grad_b,
                                      output=out_b, source="shape_math")
            return label
        except Exception:  # noqa: BLE001 — accounting must never break bind
            return self._symbol.name or "graph"

    # ---------------------------------------------------------------- running
    @staticmethod
    def _read_through_cache(nd_dict, cache):
        """Per-step input gather without rebuilding the dict.

        The {name: jax.Array} dict handed to the jit is held and mutated
        in place; an entry is re-read only when its NDArray object, chunk,
        or chunk version changed since the last step (optimizer writes
        bump the version; bind-time storage sharing swaps the object).  A
        pending host_waiter (async kvstore pull) always forces the read so
        deferred engine writes land before dispatch.
        """
        vals, fps = cache
        for k, v in nd_dict.items():
            ch = v._chunk
            fp = fps.get(k)
            if (fp is None or ch.host_waiter is not None or fp[0] is not v
                    or fp[1] is not ch or fp[2] != ch.version):
                vals[k] = v._read()
                ch = v._chunk
                fps[k] = (v, ch, ch.version)
        return vals

    def _gather_inputs(self):
        args = self._read_through_cache(self.arg_dict, self._args_cache)
        aux = self._read_through_cache(self.aux_dict, self._aux_cache)
        from . import random as _random

        key = jax.random.fold_in(_random.current_key(), self._step)
        self._step += 1
        return args, aux, key

    def forward(self, is_train=False, **kwargs):
        """Parity: Executor.forward (python/mxnet/executor.py:84 ->
        GraphExecutor::Forward)."""
        perf_on = _tm.perf.enabled()
        tp0 = time.perf_counter() if perf_on else 0.0
        for k, v in kwargs.items():
            if k not in self.arg_dict:
                raise MXNetError(f"unknown input {k}")
            if isinstance(v, NDArray):
                self.arg_dict[k]._set(v._read())
            else:
                arr = np.asarray(v)
                if arr.dtype == np.float64:
                    # untyped Python floats arrive as f64; the framework
                    # default is f32.  Everything else (int labels, f16
                    # inputs, ...) keeps its dtype
                    arr = arr.astype(np.float32)
                self.arg_dict[k]._set(jnp.asarray(arr))
        args, aux, key = self._gather_inputs()
        if is_train:
            # lazy: defer compute so backward() can run the fused fwd+bwd
            self._pending = (args, aux, key)
            self._outputs_cache = None
            outs = self.outputs  # materializes via _jit_fwd (train mode)
            self._pending_fwd_wall = \
                (time.perf_counter() - tp0) if perf_on else 0.0
            return outs
        else:
            from . import profiler as _prof

            t0 = time.perf_counter() if _tm.enabled() else None
            with _prof.span(f"forward[{self._symbol.name or 'graph'}]",
                            device=str(self._ctx),
                            sync=lambda: jax.block_until_ready(
                                self._outputs_cache[0]._read())
                            if self._outputs_cache else None):
                try:
                    outs, new_aux = self._jit_fwd(args, aux, key, False)
                except Exception as e:  # noqa: BLE001 — OOM gets a report
                    _tm.health.reraise_if_oom(e, site="executor.forward")
                    raise
                self._pending = None
                self._outputs_cache = [NDArray(o) for o in outs]
                if not self._mem_analyzed:
                    # accelerator backends: upgrade the shape-math row
                    # with the compiled program's memory analysis (a
                    # cache lookup there; skipped entirely on CPU)
                    self._mem_analyzed = True
                    _tm.health.attach_compiled_analysis(
                        self._program_label, self._jit_fwd,
                        args, aux, key, False)
                if perf_on and not self._cost_fwd_done:
                    self._cost_fwd_done = True
                    _tm.perf.attach_cost_analysis(
                        self._program_label, self._jit_fwd,
                        args, aux, key, False)
            if t0 is not None:
                _TM_FWD_SEC.observe(time.perf_counter() - t0)
            if perf_on:
                _tm.perf.record_dispatch(self._program_label,
                                         time.perf_counter() - tp0)
            if self._monitor_callback is not None:
                self._run_monitor(args, aux, key)
        return self.outputs

    def backward(self, out_grads=None):
        """Parity: Executor.backward (executor.py:123 ->
        GraphExecutor::Backward); grads land in grad_arrays per grad_req."""
        if self._pending is None:
            raise MXNetError("backward() requires forward(is_train=True) first")
        from . import profiler as _prof

        perf_on = _tm.perf.enabled()
        t0 = time.perf_counter() if (_tm.enabled() or perf_on) else None
        with _prof.span(f"forward_backward[{self._symbol.name or 'graph'}]",
                        device=str(self._ctx),
                        sync=lambda: jax.block_until_ready(
                            self._outputs_cache[0]._read())
                        if self._outputs_cache else None):
            self._backward_impl(out_grads)
        if t0 is not None:
            _TM_BWD_SEC.observe(time.perf_counter() - t0)
            if perf_on:
                # the fused program owns the train forward's host wall
                # too — so the per-program ledger matches the wall a
                # caller timing fwd+bwd (bench _dispatch_micro) sees
                _tm.perf.record_dispatch(
                    self._program_label,
                    time.perf_counter() - t0 + self._pending_fwd_wall)
                self._pending_fwd_wall = 0.0

    def _backward_impl(self, out_grads):
        args, aux, key = self._pending
        from jax.sharding import NamedSharding, PartitionSpec, \
            SingleDeviceSharding

        ref = next(iter(args.values()), None)
        ref_sh = getattr(ref, "sharding", None)
        if out_grads is None:
            # loss-output graphs: ops define their own grads (custom_vjp)
            # and ignore the seed; plain graphs get an in-trace ones seed
            # (sum-of-outputs loss) — see _make_fwdbwd
            head = []
        else:
            if isinstance(out_grads, NDArray):
                out_grads = [out_grads]
            head = [g._read() if isinstance(g, NDArray) else jnp.asarray(g) for g in out_grads]
            # pin head grads to the executor's device (caller may have
            # created them on the default device); a mesh-sharded bind
            # replicates them over its mesh — a single-device committed
            # seed would otherwise refuse to enter the SPMD program
            if isinstance(ref_sh, SingleDeviceSharding):
                head = [
                    jax.device_put(h, ref_sh)
                    if getattr(h, "sharding", None) != ref_sh
                    else h
                    for h in head
                ]
            elif isinstance(ref_sh, NamedSharding) and ref_sh.mesh.size > 1:
                repl = NamedSharding(ref_sh.mesh, PartitionSpec())
                head = [
                    jax.device_put(h, repl)
                    if getattr(h, "sharding", None) is None
                    or h.sharding.device_set != ref_sh.device_set
                    else h
                    for h in head
                ]
        grad_ins = {k: self.grad_dict[k]._read() for k in self._add_names}
        loss_scale = None
        if self._amp_scale:
            from . import amp as _amp

            loss_scale = _amp.global_scaler().scale_raw()
            # the scaler's device scalar must share the bind's committed
            # placement (4 bytes; an async transfer only after the
            # scale-update program moved it)
            if isinstance(ref_sh, NamedSharding) and ref_sh.mesh.size > 1:
                repl = NamedSharding(ref_sh.mesh, PartitionSpec())
                if getattr(loss_scale, "sharding", None) != repl:
                    loss_scale = jax.device_put(loss_scale, repl)
            elif isinstance(ref_sh, SingleDeviceSharding) \
                    and getattr(loss_scale, "sharding", None) != ref_sh:
                loss_scale = jax.device_put(loss_scale, ref_sh)
        try:
            outs, new_aux, grads = self._jit_fwdbwd(
                args, aux, key, head, grad_ins, loss_scale,
                gnames=self._gnames, add_names=self._add_names,
                rs_specs=self._rs_specs
            )
        except Exception as e:  # noqa: BLE001 — OOM gets a report
            _tm.health.reraise_if_oom(e, site="executor.backward")
            raise
        if not self._cost_fwdbwd_done and _tm.perf.enabled():
            # one-time analytical cost row for the fused fwd+bwd
            # program — same label as the memory row; overwrites the
            # eval-forward row once training runs (the fwdbwd program
            # is the one the fit loops attribute wall to)
            self._cost_fwdbwd_done = True
            _tm.perf.attach_cost_analysis(
                self._program_label, self._jit_fwdbwd,
                args, aux, key, head, grad_ins, loss_scale,
                gnames=self._gnames, add_names=self._add_names,
                rs_specs=self._rs_specs)
        self._outputs_cache = [NDArray(o) for o in outs]
        self._write_aux(new_aux)
        for k, g in grads.items():
            req = self.grad_req.get(k, "null")
            tgt = self.grad_dict.get(k)
            if tgt is None or req == "null":
                continue
            if isinstance(g, tuple):
                # row-sparse emission: the coalesced (indices, values)
                # pair rebinds the holder's storage — no dense buffer
                tgt._set_rows(*g)
                continue
            # grad_req="add" was already accumulated inside the compiled
            # program (grad_ins); every req lands with a plain write
            tgt._set(g)
        if self._monitor_callback is not None:
            self._run_monitor(args, aux, key)

    def _write_aux(self, new_aux):
        for k, v in new_aux.items():
            if k in self.aux_dict:
                self.aux_dict[k]._set(v)

    @property
    def outputs(self) -> List[NDArray]:
        if self._outputs_cache is None:
            if self._pending is None:
                raise MXNetError("no forward has been run")
            args, aux, key = self._pending
            t0 = time.perf_counter() if _tm.enabled() else None
            try:
                outs, new_aux = self._jit_fwd(args, aux, key, True)
            except Exception as e:  # noqa: BLE001 — OOM gets a report
                _tm.health.reraise_if_oom(e, site="executor.outputs")
                raise
            if t0 is not None:
                _TM_FWD_SEC.observe(time.perf_counter() - t0)
            self._outputs_cache = [NDArray(o) for o in outs]
            self._write_aux(new_aux)
        return self._outputs_cache

    @property
    def output_dict(self):
        return dict(zip(self._symbol.list_outputs(), self.outputs))

    # ------------------------------------------------------------- monitoring
    def set_monitor_callback(self, callback):
        """Parity: GraphExecutor::SetMonitorCallback (graph_executor.cc:63) —
        taps every internal output (used by mx.mon.Monitor)."""
        self._monitor_callback = callback

    def _run_monitor(self, args, aux, key):
        # compiled ONCE and cached: the reference's monitor is a near-free
        # callback on already-computed outputs (executor.cc monitor), so
        # re-tracing the whole graph in eager python per monitored batch
        # (O(graph) interpreter overhead) is not acceptable here either
        if self._monitor_fn is None:
            internals = self._symbol.get_internals()
            if self._placed:
                # internals share the same node objects, so the stored plan
                # (keyed by id(node) / var name) places them identically —
                # a flat _build_graph_fn would feed ops mixed-device operands
                self._monitor_fn = _build_placed_fn(internals, *self._plan,
                                                    self._ctx)
            else:
                self._monitor_fn = jax.jit(_build_graph_fn(internals),
                                           static_argnums=(3,))
            self._monitor_names = internals.list_outputs()
        outs, _ = self._monitor_fn(args, aux, key, False)
        for name, val in zip(self._monitor_names, outs):
            self._monitor_callback(name, NDArray(val))

    # ------------------------------------------------------------------- misc
    def copy_params_from(self, arg_params, aux_params=None,
                         allow_extra_params=False):
        for k, v in (arg_params or {}).items():
            if k in self.arg_dict:
                self.arg_dict[k]._set(v._read())
            elif not allow_extra_params:
                raise MXNetError(f"unknown param {k}")
        for k, v in (aux_params or {}).items():
            if k in self.aux_dict:
                self.aux_dict[k]._set(v._read())
            elif not allow_extra_params:
                raise MXNetError(f"unknown aux {k}")

    def reshape(self, partial_shaping=False, allow_up_sizing=False, **kwargs):
        """Parity: Executor.reshape — rebind with new shapes; on TPU this is
        just a fresh simple_bind (jit handles per-shape compilation cache)."""
        shapes = {k: v.shape for k, v in self.arg_dict.items()}
        shapes.update(kwargs)
        # carry the bound dtypes over (type_dict is honored now), so a
        # reshaped executor keeps e.g. integer-label buffers integer
        types = {k: v.dtype for k, v in self.arg_dict.items()}
        types.update({k: v.dtype for k, v in self.aux_dict.items()})
        return simple_bind(self._symbol, self._ctx, grad_req=self.grad_req,
                           type_dict=types, group2ctx=self._group2ctx or None,
                           shared_exec=self, shardings=self._shardings or None,
                           **shapes)

    @property
    def symbol(self):
        return self._symbol


def simple_bind(symbol: Symbol, ctx=None, grad_req="write", type_dict=None,
                group2ctx=None, shared_exec=None, shardings=None,
                **kwargs) -> Executor:
    """Parity: Symbol.simple_bind (python/mxnet/symbol.py:726): infer
    shapes, allocate arrays (+grads per grad_req), bind.

    ``type_dict`` assigns per-name dtypes to args/aux (parity: the
    reference's simple_bind type inference); a ``Variable(dtype=...)``
    annotation is the per-symbol default, and anything undeclared
    allocates float32.  Grad arrays always match their arg's dtype.
    ``shardings`` ({var name -> jax Sharding}) places the named arrays
    on the process mesh at bind — the named-axis path a
    DataParallelExecutorGroup or a group2ctx PartitionSpec annotation
    resolves to.
    """
    ctx = ctx or current_context()
    arg_shapes, out_shapes, aux_shapes = symbol.infer_shape(**kwargs)
    if arg_shapes is None:
        raise MXNetError(f"simple_bind: cannot infer shapes from {kwargs}")
    arg_names = symbol.list_arguments()
    aux_names = symbol.list_auxiliary_states()
    dtypes = {}
    for node in symbol.nodes:
        if node.is_variable and "__dtype__" in node.extra_attrs:
            dtypes[node.name] = node.extra_attrs["__dtype__"]
    dtypes.update(type_dict or {})

    def _dtype(name):
        return jnp.dtype(np.dtype(dtypes.get(name, np.float32)))

    # ctx_group-annotated graphs: allocate each variable on its group's
    # device so params/grads live where their layer computes
    var_ctx = {}
    if group2ctx:
        _, var_ctx, _ = placement_plan(symbol, group2ctx, ctx)
    args = {}
    for name, shape in zip(arg_names, arg_shapes):
        args[name] = NDArray(jnp.zeros(shape, dtype=_dtype(name)),
                             ctx=var_ctx.get(name, ctx))
    aux = {}
    for name, shape in zip(aux_names, aux_shapes):
        aux[name] = NDArray(jnp.zeros(shape, dtype=_dtype(name)),
                            ctx=var_ctx.get(name, ctx))

    if isinstance(grad_req, str):
        req = {k: grad_req for k in arg_names}
    elif isinstance(grad_req, (list, tuple)):
        req = dict(zip(arg_names, grad_req))
    else:
        req = {k: grad_req.get(k, "null") for k in arg_names}
    # grad_stype="row_sparse" variables (threaded through the symbol's
    # __grad_stype__ annotation) get a RowSparseNDArray holder instead
    # of a table-sized dense buffer — the backward rebinds it with the
    # coalesced (indices, values) pair each step.  MXTPU_SPARSE_UPDATE=0
    # keeps dense buffers (and thereby the dense scatter) bit-identically.
    from . import sparse as _sparse

    rs_grad_names = set()
    if _sparse.sparse_update_enabled() and _sparse.annotated_rs_names(symbol):
        rs_grad_names = {name for name in _sparse.rs_plan(symbol)
                         if req.get(name) == "write"}
    shape_of = dict(zip(arg_names, arg_shapes))
    grads = {}
    for k in arg_names:
        if req.get(k, "null") == "null":
            continue
        if k in rs_grad_names:
            grads[k] = _sparse.zeros("row_sparse", shape_of[k],
                                     ctx=var_ctx.get(k, ctx),
                                     dtype=_dtype(k))
        else:
            grads[k] = NDArray(jnp.zeros(shape_of[k], dtype=_dtype(k)),
                               ctx=var_ctx.get(k, ctx))
    return Executor(symbol, ctx, args, grads, req, aux, group2ctx=group2ctx,
                    shared_exec=shared_exec, shardings=shardings)
