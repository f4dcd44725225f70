"""FusedTrainer — whole-step compilation: forward+backward+optimizer in ONE
XLA computation with buffer donation.

This is the TPU-native performance path (SURVEY.md §7): where the reference
overlaps per-op engine dispatch with per-key kvstore push/pull
(threaded_engine_perdevice.cc + comm.h priority scheduling), XLA gets the
entire training step as a single program — fusion handles elementwise
chains, GSPMD inserts gradient all-reduces over the mesh, and latency
hiding replaces the engine's comm/compute overlap (all collectives are
scheduled inside one program rather than as separate engine ops).

Donation (`donate_argnums` on params/opt-state/aux) gives in-place
semantics — the functional analogue of the reference's in-place optimizer
updates + PlanMemory inplace sharing.

Mixed precision: dtype='bfloat16' keeps fp32 master weights and runs
compute in bf16 (MXU fast path); the reference's fp16 path is
test_dtype.py-style casting.
"""
from __future__ import annotations

import os
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import random as _random
from . import telemetry as _tm
from .executor import _build_graph_fn
from .initializer import Uniform
from .base import MXNetError
from .ndarray import NDArray
from .optim_rules import (  # noqa: F401 — rules shared with kvstore_fused
    _RULES, _adam_rule, _rmsprop_rule, _sgd_rule,
)

# --- telemetry families (docs/telemetry.md).  The `loop` label separates
# the fused whole-step path from the Module fit loop. -----------------------
_TM_SAMPLES = _tm.counter(
    "trainer_samples_total", "training samples dispatched",
    labels=("loop",))
_TM_STEP_SEC = _tm.histogram(
    "trainer_step_seconds",
    "train-step dispatch wall time (async: device completion not "
    "included)", labels=("loop",))


# The pure per-tensor update rules (_sgd_rule/_adam_rule/_rmsprop_rule)
# live in optim_rules.py — they are shared with the kvstore's bucketed
# fused-update engine; `lr` arrives per-call (a traced scalar, so
# schedules don't recompile).


class FusedTrainer:
    """One-jit-call-per-step trainer over a Symbol.

    data parallel: pass a mesh (or n_devices) — inputs shard over 'data',
    params replicate, XLA all-reduces gradients.
    """

    def __init__(self, symbol, data_names=("data",), label_names=("softmax_label",),
                 optimizer="sgd", optimizer_params=None, mesh: Optional[Mesh] = None,
                 initializer=None, dtype=None, sharding_rules=(),
                 remat=None, fixed_param_names=(), clip_global_norm=None,
                 lr_scheduler=None):
        # rematerialization = the reference's MXNET_BACKWARD_DO_MIRROR
        # (recompute activations in backward, env_var.md:55-57) — on TPU
        # it is jax.checkpoint around the forward.  Default follows the
        # same env var for parity.
        if remat is None:
            remat = os.environ.get("MXNET_BACKWARD_DO_MIRROR", "0") == "1"
        self.remat = bool(remat)
        self.symbol = symbol
        self.data_names = list(data_names)
        self.label_names = list(label_names)
        self.mesh = mesh
        # dtype=None follows the process AMP policy (MXTPU_AMP=bf16 →
        # bf16 compute + the fp32 masters this trainer always keeps);
        # an explicit dtype still wins — "bf16 by default" is one env
        # flag for the FusedTrainer path too
        if dtype is None:
            from . import amp as _amp

            dtype = _amp.amp_dtype() or jnp.float32
        self.dtype = jnp.dtype(dtype)
        opt_params = dict(optimizer_params or {})
        opt_params.setdefault("lr", opt_params.pop("learning_rate", 0.01))
        # lr schedule (parity: lr_scheduler.py's role in optimizer.py):
        # callable(num_update) -> lr, evaluated on the host each step and
        # fed to the jitted step as a traced scalar — no recompilation
        self._base_lr = float(opt_params.pop("lr"))
        self._lr_scheduler = lr_scheduler
        if lr_scheduler is not None and hasattr(lr_scheduler, "base_lr"):
            lr_scheduler.base_lr = self._base_lr
        if optimizer not in _RULES:
            raise ValueError(f"FusedTrainer supports {sorted(_RULES)}; "
                             f"use Module for {optimizer}")
        self._init_state, self._update = _RULES[optimizer](opt_params)
        self._sharding_rules = tuple(sharding_rules)
        # params excluded from the vjp: XLA prunes their whole gradient
        # subgraph (Module parity: fixed_param_names; e.g. frozen trunks)
        if isinstance(fixed_param_names, str):
            fixed_param_names = (fixed_param_names,)
        self._fixed = frozenset(fixed_param_names)
        # global-norm gradient clipping (beyond the per-element
        # clip_gradient the optimizer kernels apply): rescale the WHOLE
        # gradient tree when ||g||_2 exceeds the threshold — the standard
        # transformer-training guard
        if clip_global_norm is not None and not float(clip_global_norm) > 0:
            raise ValueError("clip_global_norm must be > 0 (a negative "
                             "threshold would flip gradient signs; 0 would "
                             "silently disable clipping)")
        self._clip_global_norm = (None if clip_global_norm is None
                                  else float(clip_global_norm))
        self._initializer = initializer or Uniform(0.01)
        # per-param multipliers (reference parity: optimizer.py
        # set_lr_mult/set_wd_mult) — static per param, folding into the
        # compile.  Like set_wd_mult, params not named *_weight/*_gamma
        # (biases, norm betas) default to NO weight decay; explicit
        # __wd_mult__/__lr_mult__ Variable attrs override.
        self._lr_mult, self._wd_mult = {}, {}
        for name in symbol.list_arguments():
            if not (name.endswith("_weight") or name.endswith("_gamma")):
                self._wd_mult[name] = 0.0
        for name, attr in symbol.attr_dict().items():
            if "__lr_mult__" in attr:
                self._lr_mult[name] = float(attr["__lr_mult__"])
            if "__wd_mult__" in attr:
                self._wd_mult[name] = float(attr["__wd_mult__"])
        # platform-sensitive ops (FlashAttention) must lower for the mesh
        # this trainer will run on, NOT jax.default_backend(): with an
        # accelerator plugin registered, a CPU-device mesh (the multichip
        # dryrun, multi-process CPU workers) still sees backend "tpu"
        platform = None
        if mesh is not None:
            try:
                platform = next(iter(mesh.devices.flat)).platform
            except Exception:  # noqa: BLE001
                platform = None
        self._platform = platform
        # graph-rewrite pipeline (mxnet_tpu.passes; MXTPU_GRAPH_PASSES):
        # the EXECUTED graph is the rewritten one — fewer traced nodes
        # per step compile — while self.symbol stays the user-facing
        # interface (list_arguments/infer_shape/attr_dict all read the
        # original; passes never rename variables, so the name spaces
        # agree)
        from . import passes as _passes

        self._exec_symbol = _passes.apply_graph_passes(symbol)
        self._graph_fn = _build_graph_fn(self._exec_symbol,
                                         platform=platform, mesh=mesh)
        # conv weights stored physically HWIO (filled by init(); see
        # _discover_hwio_params) — logical OIHW at every API boundary
        self._hwio: frozenset = frozenset()
        self.params: Dict[str, jax.Array] = {}
        self.aux: Dict[str, jax.Array] = {}
        self.opt_state: Dict[str, tuple] = {}
        # mixed precision keeps a DONATED bf16 copy of the params carried
        # step-to-step: the forward reads it directly and the next copy is
        # written inside the optimizer update (where the f32 master is
        # already in registers), instead of re-reading the whole f32
        # master tree to re-cast it at the top of every step — on
        # ResNet-50 that re-cast alone is ~100MB/step of HBM traffic
        self._use_ccache = self.dtype != jnp.float32
        self._cparams: Dict[str, jax.Array] = {}
        self._step_fn = None
        self._step = 0
        # health-layer state (set for real by _build_step)
        self._sentinel = False
        self._sent_names: tuple = ()
        self._mem_recorded = False
        self._donated_bytes = None
        self._cost_recorded = False

    # ------------------------------------------------------------------ setup
    def init(self, **input_shapes):
        arg_shapes, _, aux_shapes = self.symbol.infer_shape(**input_shapes)
        arg_names = self.symbol.list_arguments()
        aux_names = self.symbol.list_auxiliary_states()
        inputs = set(self.data_names + self.label_names)
        repl = (NamedSharding(self.mesh, P()) if self.mesh is not None else None)
        from .parallel.mesh import shard_params

        for name, shape in zip(arg_names, arg_shapes):
            if name in inputs:
                continue
            arr = NDArray(jnp.zeros(shape, dtype=jnp.float32))
            self._initializer(name, arr)
            self.params[name] = arr._read()
        if self.mesh is not None:
            # tensor-parallel rules shard matching params; rest replicate
            self.params = shard_params(self.mesh, self.params, self._sharding_rules)
        # HWIO weight storage: initialize in logical OIHW (fan-in/out
        # correct for the initializer), then flip the stored layout to
        # what the NHWC convs consume — masters, momentum, and compute
        # cache all live HWIO, so the step has ZERO weight-relayout
        # traffic (the xprof A/B measured +1.2 ms/step of 'data
        # formatting' on ResNet-50 b32 with OIHW storage).
        self._hwio = self._discover_hwio_params(
            arg_names, arg_shapes, aux_names, aux_shapes)
        if self._hwio:
            self._graph_fn = _build_graph_fn(
                self._exec_symbol, platform=self._platform,
                hwio_params=self._hwio, mesh=self.mesh)
            for name in self._hwio:
                v = jnp.transpose(self.params[name], (2, 3, 1, 0))
                if self.mesh is not None:
                    v = jax.device_put(v, self.params[name].sharding)
                self.params[name] = v
        unknown = self._fixed - set(self.params)
        if unknown:
            raise MXNetError(f"fixed_param_names not in the model: "
                             f"{sorted(unknown)} (have "
                             f"{sorted(self.params)[:8]}...)")
        for name, raw in self.params.items():
            if name in self._fixed:
                continue
            self.opt_state[name] = tuple(
                jax.device_put(s, raw.sharding) if self.mesh is not None else s
                for s in self._init_state(raw)
            )
        for name, shape in zip(aux_names, aux_shapes):
            arr = NDArray(jnp.zeros(shape, dtype=jnp.float32))
            self._initializer(name, arr)
            raw = arr._read()
            if repl is not None:
                raw = jax.device_put(raw, repl)
            self.aux[name] = raw
        self._refresh_compute_cache()
        self._build_step()
        return self

    def _discover_hwio_params(self, arg_names, arg_shapes, aux_names,
                              aux_shapes):
        """Trace the graph abstractly and collect conv-weight variables
        consumed by NHWC convs; those get HWIO physical storage.  Params
        matched by a sharding rule are excluded (rule specs are written
        against logical OIHW axes).  MXTPU_HWIO_STORAGE=0 opts out."""
        from .executor import channels_last_default

        if (os.environ.get("MXTPU_HWIO_STORAGE", "1") == "0"
                or not channels_last_default()):
            return frozenset()
        report = {"conv_w": set(), "other": set()}
        # probe the REWRITTEN graph — HWIO safety is about how the
        # executed graph consumes each weight, not how the user wrote it
        probe = _build_graph_fn(self._exec_symbol, layout_report=report)
        args = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
                for n, s in zip(arg_names, arg_shapes)}
        aux = {n: jax.ShapeDtypeStruct(tuple(s), jnp.float32)
               for n, s in zip(aux_names, aux_shapes)}
        try:
            jax.eval_shape(lambda a, x, k: probe(a, x, k, True),
                           args, aux, jax.random.PRNGKey(0))
        except Exception:  # noqa: BLE001 — abstract trace unsupported
            return frozenset()  # (custom ops needing values): keep OIHW
        # HWIO-safe = consumed ONLY as NHWC conv weights (a tied second
        # use — in-graph weight norms, a sibling NCHW conv — would read
        # the transposed axes as OIHW) and not under a sharding rule
        # (rule specs name logical OIHW axes)
        return frozenset(
            n for n in report["conv_w"] - report["other"]
            if not any(r.matches(n) for r in self._sharding_rules))

    def _logical_param(self, name, v):
        """Stored -> logical layout (HWIO conv weights back to OIHW)."""
        return jnp.transpose(v, (3, 2, 0, 1)) if name in self._hwio else v

    def _refresh_compute_cache(self):
        """(Re)build the carried compute-dtype param copy from the f32
        masters.  Call after any direct overwrite of ``self.params``
        outside step() (init/load_checkpoint do it for you)."""
        if not self._use_ccache:
            return
        dtype = self.dtype
        self._cparams = jax.jit(
            lambda p: {k: v.astype(dtype) if v.dtype == jnp.float32 else v
                       for k, v in p.items()})(self.params)

    def _build_step(self):
        graph_fn = self._graph_fn
        update = self._update
        dtype = self.dtype
        data_names = self.data_names
        label_names = self.label_names

        fixed = self._fixed
        use_ccache = self._use_ccache
        # numerics sentinel (MXTPU_SENTINEL, sampled at build): the step
        # ALSO returns a per-param isfinite mask + the global grad norm,
        # computed inside the same compiled program — zero extra
        # dispatches, synced only at reporting boundaries
        sentinel = _tm.health.sentinel_mode() is not None
        self._sentinel = sentinel
        self._sent_names = tuple(k for k in self.params if k not in fixed)
        sent_names = self._sent_names
        self._mem_recorded = False
        self._donated_bytes = None
        self._cost_recorded = False

        def train_step(params, cparams, aux, opt_state, batch, key, step, lr):
            # the per-step RNG fold happens INSIDE the compiled step (step
            # arrives as a traced scalar): an eager fold_in per step() call
            # would be one extra host->device dispatch on the hot path
            key = jax.random.fold_in(key, step)
            if use_ccache:
                compute_params = cparams
            else:
                compute_params = {
                    k: v.astype(dtype) if v.dtype == jnp.float32 else v
                    for k, v in params.items()
                }
            compute_aux = {k: v.astype(dtype) for k, v in aux.items()}
            args = dict(compute_params)
            for k in data_names:
                args[k] = batch[k].astype(dtype)
            for k in label_names:
                args[k] = batch[k]

            def fwd(p):
                a = dict(args)
                a.update(p)
                outs, new_aux = graph_fn(a, compute_aux, key, True)
                # master aux stays fp32
                new_aux = {k: v.astype(jnp.float32) for k, v in new_aux.items()}
                return outs, new_aux

            if self.remat:
                fwd = jax.checkpoint(fwd)
            trainable = {k: v for k, v in compute_params.items()
                         if k not in fixed}
            (outs, new_aux), vjp_fn = jax.vjp(fwd, trainable)
            head = [jnp.ones(o.shape, o.dtype) for o in outs]
            aux_cot = jax.tree_util.tree_map(jnp.zeros_like, new_aux)
            (grads,) = vjp_fn((head, aux_cot))

            f32_grads = {k: grads[k].astype(jnp.float32)
                         for k in params if k not in fixed}
            if sentinel:
                # raw (pre-clip) grads: a finite clip rescale cannot
                # mask an inf/nan, and the norm is the divergence
                # signal.  Flags + norm pack into ONE output leaf —
                # the extra dispatch cost is one tiny array
                fin_vec = jnp.stack([jnp.isfinite(f32_grads[k]).all()
                                     for k in sent_names]).astype(
                                         jnp.float32)
                gnorm_s = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                       for g in f32_grads.values()))
                sent_vec = jnp.concatenate([fin_vec, gnorm_s[None]])
            if self._clip_global_norm is not None:
                gnorm = jnp.sqrt(sum(jnp.sum(jnp.square(g))
                                     for g in f32_grads.values()))
                scale = jnp.minimum(1.0, self._clip_global_norm
                                    / jnp.maximum(gnorm, 1e-12))
                f32_grads = {k: g * scale for k, g in f32_grads.items()}

            new_params = {}
            new_cparams = {}
            new_opt = {}
            for k, w in params.items():
                if k in fixed:
                    new_params[k] = w
                    if use_ccache:
                        new_cparams[k] = cparams[k]
                    continue
                nw, ns = update(w, f32_grads[k], opt_state[k],
                                lr * self._lr_mult.get(k, 1.0),
                                self._wd_mult.get(k, 1.0))
                new_params[k] = nw
                if use_ccache:
                    new_cparams[k] = (nw.astype(dtype)
                                      if nw.dtype == jnp.float32 else nw)
                new_opt[k] = ns
            if sentinel:
                return (new_params, new_cparams, new_aux, new_opt, outs,
                        sent_vec)
            return new_params, new_cparams, new_aux, new_opt, outs

        self._step_fn = jax.jit(train_step, donate_argnums=(0, 1, 2, 3))

        def multi_step(params, cparams, aux, opt_state, stacked, key,
                       step0, lrs):
            # k steps in ONE dispatch: scan over the leading steps axis.
            # Per-step semantics (RNG fold by absolute step index, lr from
            # the host-computed schedule) are identical to train_step, so
            # step() and step_multi() are interchangeable mid-run.
            # Inputs arrive either pre-stacked ``(k, B, ...)`` or as
            # k-tuples of per-step ``(B, ...)`` arrays (the device-side
            # feed from DevicePrefetchIter batches) — tuples are stacked
            # HERE, inside the compiled program, so the caller never pays
            # a separate host-dispatched stack for data already on device.
            stacked = {k_: jnp.stack(v) if isinstance(v, tuple) else v
                       for k_, v in stacked.items()}
            k = lrs.shape[0]
            idxs = step0 + 1 + jnp.arange(k, dtype=jnp.int32)

            def body(carry, xs):
                p, cp, a, o = carry
                batch, idx, lr = xs
                res = train_step(p, cp, a, o, batch, key, idx, lr)
                if sentinel:
                    p, cp, a, o, outs, sent = res
                    return (p, cp, a, o), (outs, sent)
                p, cp, a, o, outs = res
                return (p, cp, a, o), outs

            (params, cparams, aux, opt_state), ys = jax.lax.scan(
                body, (params, cparams, aux, opt_state),
                (stacked, idxs, lrs))
            if sentinel:
                outs, sents = ys
                # sents is (k, n_params+1): row i flags step step0+1+i,
                # last column is that step's grad norm
                return params, cparams, aux, opt_state, outs, sents
            return params, cparams, aux, opt_state, ys

        self._multi_fn = jax.jit(multi_step, donate_argnums=(0, 1, 2, 3))
        # variant that ALSO donates the stacked batch (argnum 4): the
        # scan consumes the batch exactly once, so when nobody else holds
        # it XLA reuses its HBM instead of carrying a dead (k, B, ...)
        # buffer across the whole k-step program
        self._multi_fn_donate = jax.jit(multi_step,
                                        donate_argnums=(0, 1, 2, 3, 4))

        def eval_step(params, cparams, aux, batch, key):
            if use_ccache:
                compute_params = cparams
            else:
                compute_params = {
                    k: v.astype(dtype) if v.dtype == jnp.float32 else v
                    for k, v in params.items()
                }
            compute_aux = {k: v.astype(dtype) for k, v in aux.items()}
            args = dict(compute_params)
            for k in data_names:
                args[k] = batch[k].astype(dtype)
            for k in label_names:
                if k in batch:
                    args[k] = batch[k]
                else:
                    args[k] = jnp.zeros((batch[data_names[0]].shape[0],), jnp.float32)
            outs, _ = graph_fn(args, compute_aux, key, False)
            return outs

        self._eval_fn = jax.jit(eval_step)

    # ---------------------------------------------------------------- running
    def _mesh_spans_hosts(self) -> bool:
        """True when this trainer's mesh includes another process's
        devices (the multi-host collective path, docs/multihost.md)."""
        if self.mesh is None:
            return False
        me = jax.process_index()
        return any(d.process_index != me for d in self.mesh.devices.flat)

    def _place_global(self, raw, sharding):
        """Place one batch array onto the mesh.  Single-host meshes take
        the plain transfer; a mesh spanning other processes cannot
        ``device_put`` a committed local array (non-addressable
        devices), so each process contributes its ADDRESSABLE shards of
        the replicated global batch via make_array_from_callback — the
        canonical multi-host feed (every host constructs the same
        global batch; XLA sees one sharded array)."""
        if not self._mesh_spans_hosts():
            return jax.device_put(raw, sharding)
        host = np.asarray(raw)
        return jax.make_array_from_callback(
            host.shape, sharding, lambda idx, _h=host: _h[idx])

    def _shard_batch(self, batch):
        out = {}
        for k, v in batch.items():
            if isinstance(v, NDArray):
                raw = v._read()
            elif isinstance(v, jax.Array):
                raw = v  # already on device — never round-trip to host
            else:
                raw = jnp.asarray(np.asarray(v))
            if self.mesh is not None:
                out[k] = self._place_global(raw, NamedSharding(
                    self.mesh, P("data", *([None] * (raw.ndim - 1)))))
            else:
                out[k] = raw
        return out

    def current_lr(self):
        """The learning rate the NEXT step will apply."""
        if self._lr_scheduler is not None:
            return float(self._lr_scheduler(self._step + 1))
        return self._base_lr

    def step(self, **batch):
        """Run one fused train step; returns outputs (list of jax arrays)."""
        lr = np.float32(self.current_lr())  # single source of lr truth
        self._step += 1
        with _tm.tracing.phase("train_step", "trainer", step=self._step):
            return self._step_phases(batch, lr)

    def _step_phases(self, batch, lr):
        """``step`` under its ``train_step`` span: placing the batch
        (``train.shard_batch``), then the call of the step program and
        the unpacking of its result (``train.dispatch``)."""
        import time as _time

        perf_on = _tm.perf.enabled()
        t0 = _time.perf_counter() if (_tm.enabled() or perf_on) else None
        with _tm.tracing.phase("train.shard_batch", "trainer"):
            sb = self._shard_batch(batch)
            self._record_step_memory(sb)
        with _tm.tracing.phase("train.dispatch", "trainer"):
            try:
                res = self._step_fn(
                    self.params, self._cparams, self.aux, self.opt_state,
                    sb, _random.current_key(),
                    np.int32(self._step), lr)
            except Exception as e:  # noqa: BLE001 — OOM gets a report
                _tm.health.reraise_if_oom(e, site="trainer.step")
                raise
            if perf_on and not self._cost_recorded:
                # one-time analytical cost row for the fused step program
                # (telemetry/perf.py) — compile() is a cache lookup here,
                # the dispatch above already built the executable
                self._cost_recorded = True
                _tm.perf.attach_cost_analysis(
                    f"fused_step[{self.symbol.name or 'graph'}]",
                    self._step_fn, self.params, self._cparams, self.aux,
                    self.opt_state, sb, _random.current_key(),
                    np.int32(self._step), lr)
            if self._sentinel:
                (self.params, self._cparams, self.aux, self.opt_state,
                 outs, sent) = res
                _tm.health.sentinel_record(
                    site="fused_step", step=self._step,
                    names=self._sent_names, finite=sent, packed_norm=True)
            else:
                (self.params, self._cparams, self.aux, self.opt_state,
                 outs) = res
        if t0 is not None:
            _TM_STEP_SEC.observe(_time.perf_counter() - t0, loop="fused")
            _TM_SAMPLES.inc(next(iter(sb.values())).shape[0], loop="fused")
            _tm.health.donation_saved(self._donated_bytes or 0,
                                      site="trainer_step")
            if perf_on:
                _tm.perf.record_dispatch(
                    f"fused_step[{self.symbol.name or 'graph'}]",
                    _time.perf_counter() - t0)
        return outs

    def lower_step(self, **batch):
        """The fused step lowered for this batch's shapes, without
        running it: ``.compile().as_text()`` of the result shows which
        kernels (``tpu_custom_call``) and collectives the step program
        holds.  Bring-up and inspection only — never on the hot path."""
        return self._step_fn.lower(
            self.params, self._cparams, self.aux, self.opt_state,
            self._shard_batch(batch), _random.current_key(),
            np.int32(self._step), np.float32(self.current_lr()))

    def _tree_nbytes(self, *trees):
        total = 0
        for tree in trees:
            for leaf in jax.tree_util.tree_leaves(tree):
                try:
                    total += int(leaf.size) * np.dtype(leaf.dtype).itemsize
                except Exception:  # noqa: BLE001
                    pass
        return total

    def _record_step_memory(self, sb):
        """First-dispatch memory attribution for the fused step: the
        donated param/state trees alias their outputs (XLA reuses the
        HBM), so peak ~ arguments + batch.  Shape math; accelerator
        backends get the compiled memory_analysis upgrade through the
        executor-bound programs."""
        if self._mem_recorded:
            return
        self._mem_recorded = True
        try:
            donated = self._tree_nbytes(self.params, self._cparams,
                                        self.aux, self.opt_state)
            self._donated_bytes = donated
            batch_b = self._tree_nbytes(sb)
            label = f"fused_step[{self.symbol.name or 'graph'}]"
            _tm.health.record_program(label, argument=donated + batch_b,
                                      output=donated, alias=donated,
                                      source="shape_math")
        except Exception:  # noqa: BLE001 — accounting must never break step
            pass

    def step_multi(self, _donate=None, **stacked):
        """Run k fused train steps in ONE dispatch.

        Every value carries a leading steps axis — either pre-stacked
        ``(k, B, ...)`` where a step() input would be ``(B, ...)``, or a
        k-list/tuple of per-step ``(B, ...)`` arrays (e.g. batches from
        ``DevicePrefetchIter`` via ``io.step_multi_feeds``), which the
        compiled program stacks ON DEVICE — no host re-stacking, no extra
        dispatch.  One compiled lax.scan executes the k steps back to
        back, so the per-call host/dispatch cost is paid once per k
        steps instead of once per step (whether that pays on a local
        chip is ROADMAP S5's to settle).  Interchangeable
        with step(): same per-step RNG folds, same lr schedule, same
        optimizer updates.

        ``_donate`` controls batch-buffer donation: ``True`` hands the
        input buffers to XLA (single-use feeds — the iterator pipeline;
        the arrays are consumed), ``False`` preserves them (benchmarks
        replaying one stack), ``None`` (default) donates exactly when
        every input was a host array — the device buffer was created
        here, so nobody else can hold it.

        Returns the per-step outputs stacked on axis 0, still lazy
        (async futures) — reading/blocking is the caller's sync point."""
        with _tm.tracing.phase("train_step", "trainer",
                               step=self._step + 1):
            return self._step_multi_phases(_donate, stacked)

    def _shard_stacked(self, stacked):
        """``step_multi``'s inputs placed on the device(s); the second
        return says whether every device buffer was made here (and so
        may be donated)."""
        sb = {}
        owned = True
        for k_, v in stacked.items():
            if isinstance(v, (list, tuple)):
                # per-step device feed: keep the tuple structure; the jit
                # stacks in-trace
                if any(isinstance(e, (NDArray, jax.Array)) for e in v):
                    owned = False  # caller may still hold these buffers
                sb[k_] = tuple(
                    e._read() if isinstance(e, NDArray)
                    else (e if isinstance(e, jax.Array)
                          else jnp.asarray(np.asarray(e)))
                    for e in v)
                if self.mesh is not None:
                    sh = NamedSharding(self.mesh, P(
                        "data", *([None] * (sb[k_][0].ndim - 1))))
                    sb[k_] = tuple(self._place_global(e, sh)
                                   for e in sb[k_])
                continue
            if isinstance(v, NDArray):
                raw = v._read()
                owned = False
            elif isinstance(v, jax.Array):
                raw = v
                owned = False
            else:
                raw = jnp.asarray(np.asarray(v))
            if self.mesh is not None:
                # axis 0 is steps — the data-parallel shard axis is 1
                sb[k_] = self._place_global(raw, NamedSharding(
                    self.mesh, P(None, "data", *([None] * (raw.ndim - 2)))))
            else:
                sb[k_] = raw
        return sb, owned

    def _step_multi_phases(self, _donate, stacked):
        """``step_multi`` under its ``train_step`` span, in the same two
        phases as ``step``."""
        import time as _time
        import warnings as _warnings

        with _tm.tracing.phase("train.shard_batch", "trainer"):
            sb, owned = self._shard_stacked(stacked)
            self._record_step_memory(sb)
        first = next(iter(sb.values()))
        k = len(first) if isinstance(first, tuple) else first.shape[0]
        if self._lr_scheduler is not None:
            lrs = np.asarray([self._lr_scheduler(self._step + 1 + i)
                              for i in range(k)], np.float32)
        else:
            lrs = np.full((k,), self._base_lr, np.float32)
        step0 = np.int32(self._step)
        self._step += k
        donate = owned if _donate is None else bool(_donate)
        fn = self._multi_fn_donate if donate else self._multi_fn
        t0 = _time.perf_counter() if _tm.enabled() else None
        with _tm.tracing.phase("train.dispatch", "trainer"), \
                _warnings.catch_warnings():
            if donate:
                # batch donation is best-effort: when no output aliases
                # the batch (or the platform can't donate) jax warns per
                # call — the fallback is exactly the non-donated behavior
                _warnings.filterwarnings(
                    "ignore", message="Some donated buffers were not usable")
            try:
                res = fn(
                    self.params, self._cparams, self.aux, self.opt_state,
                    sb, _random.current_key(), step0, lrs)
            except Exception as e:  # noqa: BLE001 — OOM gets a report
                _tm.health.reraise_if_oom(e, site="trainer.step_multi")
                raise
            if self._sentinel:
                (self.params, self._cparams, self.aux, self.opt_state,
                 outs, sents) = res
                # sents rows map to steps step0+1 .. step0+k
                _tm.health.sentinel_record(site="fused_step_multi",
                                           step=int(step0) + 1,
                                           names=self._sent_names,
                                           finite=sents, packed_norm=True)
            else:
                (self.params, self._cparams, self.aux, self.opt_state,
                 outs) = res
        if t0 is not None:
            _TM_STEP_SEC.observe(_time.perf_counter() - t0, loop="fused")
            per_step = (first[0].shape[0] if isinstance(first, tuple)
                        else first.shape[1])
            _TM_SAMPLES.inc(int(k * per_step), loop="fused")
            donated_b = self._donated_bytes or 0
            if donate:
                donated_b += self._tree_nbytes(sb)
            _tm.health.donation_saved(donated_b, site="trainer_step_multi")
        return outs

    def eval(self, **batch):
        key = jax.random.fold_in(_random.current_key(), 0)
        return self._eval_fn(self.params, self._cparams, self.aux,
                             self._shard_batch(batch), key)

    def get_params(self):
        return ({k: NDArray(self._logical_param(k, v))
                 for k, v in self.params.items()},
                {k: NDArray(v) for k, v in self.aux.items()})

    # ------------------------------------------------------------------- fit
    def fit(self, train_data, eval_data=None, eval_metric="acc",
            validation_metric=None, num_epoch=1, batch_end_callback=None,
            epoch_end_callback=None, logger=None, checkpoint=None,
            resume=None):
        """Module.fit-shaped loop on the fused step (the whole-step-
        compiled perf path): per-batch metric updates, Speedometer-style
        callbacks, per-epoch eval — without hand-rolling the loop.

        Calls init() from the first batch's shapes if needed.  Returns
        self.  The metric sees the step's outputs (same contract as
        Module.update_metric).

        Survival layer (docs/fault_tolerance.md): ``checkpoint`` is a
        CheckpointManager or a directory (default: armed by
        ``MXTPU_CKPT_DIR`` + ``MXTPU_CKPT_EVERY``) — snapshots every N
        steps without draining the async window, saves a boundary
        checkpoint on SIGTERM (raising ``checkpoint.Preempted``), and a
        final one when training completes.  ``resume=True`` (or a
        path) restores the newest complete checkpoint — params,
        optimizer state, RNG, and the mid-epoch batch cursor — so a
        killed run continues step-exact."""
        import logging as _logging

        from . import metric as metric_mod
        from .module.base_module import BatchEndParam, _as_list

        log = logger or _logging.getLogger()
        if not isinstance(eval_metric, metric_mod.EvalMetric):
            if validation_metric is None and eval_data is not None:
                validation_metric = metric_mod.create(eval_metric)
            eval_metric = metric_mod.create(eval_metric)
        if validation_metric is not None and not isinstance(
                validation_metric, metric_mod.EvalMetric):
            validation_metric = metric_mod.create(validation_metric)
        if eval_data is not None and validation_metric is None:
            raise ValueError(
                "pass validation_metric when eval_metric is a metric "
                "instance (instances hold state; eval needs its own)")
        import time as _time

        train_names = ([d[0] for d in train_data.provide_data]
                       + [l[0] for l in train_data.provide_label])
        # feed the eval iterator's REAL labels when it provides them: the
        # zeros placeholder in eval_step only works for shape-agnostic
        # label consumers (e.g. Reshape(-1) loss heads); a fixed-shape
        # label consumer would fail or mis-trace with it
        eval_label_names = ([l[0] for l in getattr(eval_data, "provide_label",
                                                   None) or []]
                            if eval_data is not None else [])
        eval_names = ([d[0] for d in eval_data.provide_data]
                      + eval_label_names if eval_data is not None else None)
        from . import engine as _engine
        from . import checkpoint as _ckpt

        if isinstance(checkpoint, _ckpt.CheckpointManager):
            mgr = checkpoint
        elif checkpoint:
            mgr = _ckpt.CheckpointManager(str(checkpoint))
        else:
            mgr = _ckpt.CheckpointManager.from_env()
        start_epoch, resume_nbatch = 0, -1
        if resume not in (None, False):
            if not self.params:
                shapes = {d[0]: tuple(d[1]) for d in
                          list(train_data.provide_data)
                          + list(train_data.provide_label or [])}
                self.init(**shapes)
            path = (resume if isinstance(resume, str)
                    and os.path.exists(os.path.join(resume, _ckpt.MANIFEST))
                    else _ckpt.resolve_resume(resume, mgr))
            if path is None:
                log.warning("fit(resume=%r): no complete checkpoint "
                            "found; starting fresh", resume)
            else:
                meta = self.restore_state(path)
                if meta.get("epoch") is not None:
                    start_epoch = int(meta["epoch"])
                if meta.get("nbatch") is not None:
                    resume_nbatch = int(meta["nbatch"])
                log.info("resumed from %s (step %d, epoch %d, batch "
                         "cursor %d)", path, self._step, start_epoch,
                         resume_nbatch)
        if mgr is not None:
            mgr.install_preempt_handler()
        try:
            self._fit_impl(train_data, eval_data, eval_metric,
                           validation_metric, num_epoch,
                           batch_end_callback, epoch_end_callback, log,
                           train_names, eval_names, eval_label_names,
                           _engine, _time, mgr, start_epoch,
                           resume_nbatch)
            if mgr is not None and self.params:
                # terminal checkpoint: a resume of a finished run is a
                # no-op instead of a silent full retrain
                self.save_state(mgr, epoch=num_epoch, nbatch=-1,
                                background=False)
        except BaseException:
            # black box first, then crash: the ring + registry +
            # memory report of the dying run (MXTPU_FLIGHT_RECORD path)
            _tm.health.auto_dump("exception")
            raise
        finally:
            if mgr is not None:
                try:
                    mgr.wait()
                except Exception as exc:  # noqa: BLE001 — log, don't mask
                    log.warning("checkpoint writer failed: %r", exc)
                mgr.uninstall_preempt_handler()
        return self

    def _fit_impl(self, train_data, eval_data, eval_metric,
                  validation_metric, num_epoch, batch_end_callback,
                  epoch_end_callback, log, train_names, eval_names,
                  eval_label_names, _engine, _time, mgr=None,
                  start_epoch=0, resume_nbatch=-1):
        from . import checkpoint as _ckpt
        from .module.base_module import BatchEndParam, _as_list
        from .parallel import coordinator as _coordinator

        # elastic membership (docs/multihost.md): armed by
        # MXTPU_COORD_ADDR; step_poll is a pure host-side flag check
        coord = _coordinator.client_from_env()
        flight = _tm.health.flight_enabled()
        perf_on = _tm.perf.enabled()
        rec = flight or perf_on
        for epoch in range(start_epoch, num_epoch):
            tic = _time.time()
            eval_metric.reset()
            train_data.reset()
            # bounded in-flight window (MXTPU_ASYNC_DEPTH): step() and the
            # fused metric update are pure async dispatches, so this is
            # the only place the steady-state loop blocks
            window = _engine.AsyncWindow()
            prev_tick = None  # per-epoch: wall_s must not span eval/reset
            for nbatch, batch in enumerate(train_data):
                if epoch == start_epoch and nbatch <= resume_nbatch:
                    # mid-epoch resume: the checkpoint's cursor already
                    # trained these batches — replay the iterator past
                    # them so the step/RNG/schedule sequence lines up
                    continue
                feed = dict(zip(train_names,
                                list(batch.data) + list(batch.label)))
                if not self.params:
                    self.init(**{k: tuple(v.shape)
                                 for k, v in feed.items()})
                t0 = _time.perf_counter() if rec else 0.0
                outs = self.step(**feed)
                eval_metric.update(batch.label, [NDArray(o) for o in outs])
                tp = _time.perf_counter() if perf_on else 0.0
                window.push(list(outs))
                if rec:
                    # step-timing feed (ISSUE 14): wall_s = batch-to-
                    # batch host wall, reported by the coordinator
                    # heartbeat for straggler detection (host-side only)
                    now = _time.perf_counter()
                    if flight:
                        _tm.health.record_step(
                            loop="fused", step=self._step, epoch=epoch,
                            nbatch=nbatch, depth=len(window),
                            dispatch_s=now - t0,
                            wall_s=(now - prev_tick
                                    if prev_tick is not None else now - t0),
                            program=f"fused_step"
                                    f"[{self.symbol.name or 'graph'}]")
                    if perf_on:
                        # step decomposition (docs/perf_attr.md): the
                        # three buckets partition this step's wall by
                        # construction — data_wait is the iterator +
                        # inter-step host work, dispatch the async
                        # enqueues, window_stall the bounded-window
                        # backpressure inside push()
                        _tm.perf.record_step_buckets(
                            wall_s=(now - prev_tick
                                    if prev_tick is not None else now - t0),
                            data_wait=(max(t0 - prev_tick, 0.0)
                                       if prev_tick is not None else 0.0),
                            dispatch=tp - t0,
                            window_stall=now - tp)
                    prev_tick = now
                if coord is not None and coord.step_poll():
                    # membership changed: boundary checkpoint, then the
                    # named exit — the next generation resumes on the
                    # surviving mesh (re-bind via the checkpoint
                    # re-shard contract)
                    w = None
                    if mgr is not None:
                        w = self.save_state(mgr, epoch=epoch,
                                            nbatch=nbatch,
                                            background=False)
                    coord.raise_generation_changed(
                        getattr(w, "path", None))
                if mgr is not None:
                    if mgr.preempted:
                        # window boundary under preemption: capture is
                        # ordered behind the in-flight steps, written
                        # synchronously, then the run dies a named death
                        w = self.save_state(mgr, epoch=epoch,
                                            nbatch=nbatch,
                                            background=False)
                        raise _ckpt.Preempted(
                            "SIGTERM: checkpoint saved to "
                            f"{getattr(w, 'path', mgr.directory)!r}; "
                            "restart with fit(resume=True)")
                    if mgr.due(self._step):
                        self.save_state(mgr, epoch=epoch, nbatch=nbatch)
                if batch_end_callback is not None:
                    params = BatchEndParam(epoch=epoch, nbatch=nbatch,
                                           eval_metric=eval_metric,
                                           locals=None)
                    for cb in _as_list(batch_end_callback):
                        cb(params)
            td0 = _time.perf_counter() if perf_on else 0.0
            window.drain()
            if perf_on:
                _tm.perf.record_bucket("boundary_sync",
                                       _time.perf_counter() - td0)
            for name, val in eval_metric.get_global_name_value():
                log.info("Epoch[%d] Train-%s=%f", epoch, name, val)
            log.info("Epoch[%d] Time cost=%.3f", epoch,
                     _time.time() - tic)
            if epoch_end_callback is not None:
                arg, aux = self.get_params()
                for cb in _as_list(epoch_end_callback):
                    cb(epoch, self.symbol, arg, aux)
            if eval_data is not None:
                vm = validation_metric
                vm.reset()
                eval_data.reset()
                window = _engine.AsyncWindow()
                for batch in eval_data:
                    feed = dict(zip(eval_names,
                                    list(batch.data)
                                    + (list(batch.label)
                                       if eval_label_names else [])))
                    outs = self.eval(**feed)
                    vm.update(batch.label, [NDArray(o) for o in outs])
                    window.push(list(outs))
                window.drain()
                for name, val in vm.get_global_name_value():
                    log.info("Epoch[%d] Validation-%s=%f", epoch, name, val)
        return self

    def rebind_mesh(self, mesh: Optional[Mesh]):
        """Re-bind the step loop onto a new mesh shape (elastic shrink/
        grow — ISSUE 13): re-place params, aux, and optimizer state on
        the new mesh (XLA re-shards; the flat sharded kvstore state
        follows the same ``sync_shard_state`` contract on its next plan
        build) and recompile the step programs.  ``None`` collapses to
        single-device.  Training state is carried, not reset — step
        counter, RNG stream, and schedules continue; this is the
        in-process half of the generation restart (a restarted process
        gets the same effect from init() + restore_state())."""
        if not self.params:
            self.mesh = mesh
            return self
        self.mesh = mesh
        if mesh is not None:
            try:
                self._platform = next(iter(mesh.devices.flat)).platform
            except Exception:  # noqa: BLE001
                pass
            from .parallel.mesh import shard_params

            self.params = shard_params(mesh, self.params,
                                       self._sharding_rules)
            repl = NamedSharding(mesh, P())
            self.aux = {k: jax.device_put(v, repl)
                        for k, v in self.aux.items()}
            self.opt_state = {
                k: tuple(jax.device_put(s, self.params[k].sharding)
                         if s.ndim else jax.device_put(s, repl)
                         for s in v)
                for k, v in self.opt_state.items()}
        else:
            # collapse to the default device: host round-trip is the
            # portable way off an arbitrary sharding layout
            self.params = {k: jnp.asarray(np.asarray(v))
                           for k, v in self.params.items()}
            self.aux = {k: jnp.asarray(np.asarray(v))
                        for k, v in self.aux.items()}
            self.opt_state = {k: tuple(jnp.asarray(np.asarray(s))
                                       for s in v)
                              for k, v in self.opt_state.items()}
        self._refresh_compute_cache()
        self._build_step()
        return self

    # ------------------------------------------------------- survival layer
    def _checkpoint_arrays(self):
        """Device-resident snapshot set for the async checkpointer: the
        f32 masters, aux states, and every optimizer-state slot — the
        arrays the fused step owns (the bf16 compute cache is derived,
        never saved).  Values are live jax arrays; checkpoint.snapshot
        makes the detached device copies."""
        arrs = {}
        for k, v in self.params.items():
            arrs["param/" + k] = v
        for k, v in self.aux.items():
            arrs["aux/" + k] = v
        for k, slots in self.opt_state.items():
            for i, s in enumerate(slots):
                arrs[f"opt/{k}/{i}"] = s
        return arrs

    def _checkpoint_meta(self, epoch=None, nbatch=None):
        key = np.asarray(_random.current_key())
        return {
            "trainer": "fused",
            "step": int(self._step),
            "epoch": None if epoch is None else int(epoch),
            "nbatch": None if nbatch is None else int(nbatch),
            "signature": self._exec_symbol.structural_signature(),
            "hwio": sorted(self._hwio),
            "rng_key": key.tolist(),
            "rng_dtype": str(key.dtype),
        }

    def save_state(self, target, epoch=None, nbatch=None, background=True):
        """Write a resumable checkpoint (params + aux + optimizer state
        + step/epoch/batch cursor + RNG state) through the survival
        layer (checkpoint.py): device-side capture ordered after the
        in-flight steps — the AsyncWindow is NOT drained — with the
        fetch + file IO on a background writer.  ``target`` is a
        :class:`~mxnet_tpu.checkpoint.CheckpointManager` or a
        directory.  Returns the write handle (or None when the
        manager skipped an in-flight duplicate)."""
        from . import checkpoint as _ckpt

        if not self.params:
            raise MXNetError("save_state: trainer not initialized")
        meta = self._checkpoint_meta(epoch=epoch, nbatch=nbatch)
        arrays = self._checkpoint_arrays()
        if isinstance(target, _ckpt.CheckpointManager):
            return target.save(self._step, arrays, meta=meta,
                               background=background)
        return _ckpt.save(str(target), self._step, arrays, meta=meta,
                          background=background)

    def restore_state(self, source):
        """Restore from a survival-layer checkpoint into this
        INITIALIZED trainer: validates the manifest (checksums + the
        bound graph's structural signature), re-applies this trainer's
        shardings/layouts (the checkpoint may come from a different
        shard layout or HWIO config), and restores the step cursor and
        RNG stream for bit-parity resume.  ``source`` is a checkpoint
        path, a directory of checkpoints (newest complete wins), or a
        CheckpointManager.  Returns the checkpoint's meta dict."""
        import jax.numpy as jnp

        from . import checkpoint as _ckpt

        if not self.params:
            raise MXNetError("restore_state: call init() first (shapes/"
                             "shardings come from init)")
        if isinstance(source, _ckpt.CheckpointManager):
            path = source.latest()
        elif isinstance(source, str) and os.path.exists(
                os.path.join(source, _ckpt.MANIFEST)):
            path = source
        else:
            path = _ckpt.latest(str(source))
        if path is None:
            raise _ckpt.CheckpointError(
                f"no complete checkpoint found under {source!r}")
        arrays, manifest = _ckpt.load(path)
        meta = manifest.get("meta", {})
        sig = self._exec_symbol.structural_signature()
        saved_sig = meta.get("signature")
        if saved_sig is not None and saved_sig != sig:
            raise _ckpt.CheckpointError(
                f"checkpoint {path!r} was saved from a different graph "
                f"(signature {saved_sig[:16]}... vs bound "
                f"{sig[:16]}...); refusing to load mismatched weights")
        saved_hwio = set(meta.get("hwio", ()))

        def _relayout(k, host):
            # stored-layout translation between configs: the checkpoint
            # carries arrays in ITS stored layout and names the HWIO set
            if host.ndim != 4:
                return host
            if k in saved_hwio and k not in self._hwio:
                return np.transpose(host, (3, 2, 0, 1))
            if k not in saved_hwio and k in self._hwio:
                return np.transpose(host, (2, 3, 1, 0))
            return host

        def _put(host, like):
            raw = jnp.asarray(host)
            if raw.shape != like.shape:
                raise _ckpt.CheckpointError(
                    f"checkpoint {path!r}: shape {raw.shape} does not "
                    f"match the bound {tuple(like.shape)}")
            return (jax.device_put(raw, like.sharding)
                    if self.mesh is not None else raw)

        for k in self.params:
            name = "param/" + k
            if name not in arrays:
                raise _ckpt.CheckpointError(
                    f"checkpoint {path!r} lacks param {k!r}")
            self.params[k] = _put(_relayout(k, arrays[name]),
                                  self.params[k])
        for k in self.aux:
            name = "aux/" + k
            if name not in arrays:
                raise _ckpt.CheckpointError(
                    f"checkpoint {path!r} lacks aux state {k!r}")
            self.aux[k] = _put(arrays[name], self.aux[k])
        for k, slots in self.opt_state.items():
            new = []
            for i, s in enumerate(slots):
                name = f"opt/{k}/{i}"
                if name not in arrays:
                    raise _ckpt.CheckpointError(
                        f"checkpoint {path!r} lacks optimizer state "
                        f"{k}:{i} (different optimizer?)")
                host = arrays[name]
                if host.ndim == 4 and host.shape != tuple(s.shape):
                    host = _relayout(k, host)
                new.append(_put(host, s))
            self.opt_state[k] = tuple(new)
        if meta.get("step") is not None:
            self._step = int(meta["step"])
        if meta.get("rng_key") is not None:
            _random._state["key"] = jnp.asarray(np.array(
                meta["rng_key"],
                dtype=np.dtype(meta.get("rng_dtype", "uint32"))))
        self._refresh_compute_cache()
        if _tm.enabled():
            _ckpt._TM_RESUME.inc(status="ok")
        return meta

    # ------------------------------------------------------------ checkpoints
    def _gather(self, v):
        """Full host value of a (possibly sharded) array.  On multi-host
        meshes arrays span non-addressable devices, so gather across
        processes first."""
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            v = multihost_utils.process_allgather(v, tiled=True)
        return np.asarray(v)

    def save_checkpoint(self, prefix, epoch, save_optimizer_states=False,
                        background=False):
        """Write ``prefix-symbol.json`` + ``prefix-%04d.params`` — the
        Module checkpoint format, loadable by Module/FeedForward — plus a
        FusedTrainer-format ``.states`` file (flat per-key slot arrays +
        the step counter; NOT Module's pickled-updater format) when
        ``save_optimizer_states``.

        ``background=True`` overlaps the checkpoint with training:
        params are immutable jax arrays, so snapshotting their refs (and
        the step counter) is free, and the device→host fetch + file
        write run on a writer thread while step() keeps training — on
        slow host links the fetch dominates checkpoint time, so this
        hides essentially all of it.  Returns a ``threading.Thread``
        (already started; ``join()`` before relying on the files);
        a raise on the writer thread is re-raised by ``join`` via the
        returned thread's ``exc`` attribute being checked in
        ``wait_checkpoint``."""
        if background and jax.process_count() > 1:
            # the writer thread's gather collectives would interleave
            # with training-step collectives in host-dependent order —
            # a deadlock class; multi-process saves stay synchronous
            import warnings

            warnings.warn("background checkpointing is single-process "
                          "only; saving synchronously", stacklevel=2)
            background = False

        if background:
            # SNAPSHOT at HBM speed: the fused step DONATES its buffers,
            # so bare refs would be invalidated by the next step() — a
            # device-side copy per tensor (dispatched async) detaches
            # the snapshot; only the slow device→host fetch runs on the
            # writer thread.  The synchronous path below reads the live
            # tensors directly (no duplicate HBM footprint).
            def snap(v):
                return jnp.copy(v) if isinstance(v, jax.Array) else v
        else:
            def snap(v):
                return v

        params = {k: snap(v) for k, v in self.params.items()}
        aux = {k: snap(v) for k, v in self.aux.items()}
        step = self._step
        opt_state = {k: [snap(s) for s in v]
                     for k, v in self.opt_state.items()} \
            if save_optimizer_states else None

        def _write():
            from . import ndarray as nd_mod
            from .model import save_checkpoint as _save

            # HWIO-stored conv weights leave in logical OIHW; the
            # transpose runs on HOST numpy so the writer thread never
            # dispatches device work against the training stream
            arg = {k: NDArray(np.transpose(self._gather(v), (3, 2, 0, 1))
                              if k in self._hwio else self._gather(v))
                   for k, v in params.items()}
            auxd = {k: NDArray(self._gather(v)) for k, v in aux.items()}
            _save(prefix, epoch, self.symbol, arg, auxd)
            if opt_state is not None:
                flat = {"__step__": NDArray(np.array([step], np.int64))}
                for k, states in opt_state.items():
                    for i, s in enumerate(states):
                        host = self._gather(s)
                        # slot arrays mirror their param's layout: HWIO-
                        # stored conv weights leave in logical OIHW so a
                        # .states file loads into ANY trainer config
                        # (MXTPU_HWIO_STORAGE=0, NCHW mode); shape-guard
                        # because some optimizers carry scalar slots
                        if (k in self._hwio and host.ndim == 4
                                and host.shape == params[k].shape):
                            host = np.transpose(host, (3, 2, 0, 1))
                        flat[f"{k}:{i}"] = NDArray(host)
                nd_mod.save("%s-%04d.states" % (prefix, epoch), flat)

        if not background:
            _write()
            return None
        import threading

        def _runner():
            try:
                _write()
            except BaseException as e:  # noqa: BLE001 — surfaced in join
                thread.exc = e

        thread = threading.Thread(target=_runner, daemon=False,
                                  name="ckpt-writer")
        thread.exc = None
        thread.start()
        return thread

    @staticmethod
    def wait_checkpoint(thread):
        """Join a background save and re-raise any writer-thread error."""
        if thread is None:
            return
        thread.join()
        if getattr(thread, "exc", None) is not None:
            raise thread.exc

    def load_checkpoint(self, prefix, epoch, load_optimizer_states=False):
        """Restore params/aux (and optimizer state + step counter) saved
        by save_checkpoint into this INITIALIZED trainer, re-applying the
        trainer's shardings.  Missing files or key mismatches raise —
        silently training on reset state is worse than failing."""
        from . import ndarray as nd_mod
        from .base import MXNetError
        from .model import load_checkpoint as _load

        if not self.params:
            raise MXNetError("load_checkpoint: call init() first (the "
                             "trainer's shapes/shardings come from init)")
        _, arg, aux = _load(prefix, epoch)
        missing = set(self.params) - set(arg)
        if missing:
            raise MXNetError(f"checkpoint {prefix!r} lacks params "
                             f"{sorted(missing)[:5]}...")
        missing_aux = set(self.aux) - set(aux)
        if missing_aux:
            # same contract as params: silently keeping init values for
            # e.g. BatchNorm moving stats is worse than failing
            raise MXNetError(f"checkpoint {prefix!r} lacks aux states "
                             f"{sorted(missing_aux)[:5]}...")
        for k, v in arg.items():
            if k in self.params:
                host = v.asnumpy()
                if k in self._hwio:  # checkpoints are logical OIHW
                    host = np.transpose(host, (2, 3, 1, 0))
                raw = jnp.asarray(host)
                self.params[k] = (jax.device_put(raw, self.params[k].sharding)
                                  if self.mesh is not None else raw)
        for k, v in aux.items():
            if k in self.aux:
                raw = jnp.asarray(v.asnumpy())
                self.aux[k] = (jax.device_put(raw, self.aux[k].sharding)
                               if self.mesh is not None else raw)
        if load_optimizer_states:
            spath = "%s-%04d.states" % (prefix, epoch)
            flat = nd_mod.load(spath)  # missing file raises, like Module
            step = flat.pop("__step__", None)
            if step is not None:
                self._step = int(step.asnumpy()[0])
            for k in list(self.opt_state):
                states = []
                for i in range(len(self.opt_state[k])):
                    arr = flat.get(f"{k}:{i}")
                    if arr is None:
                        raise MXNetError(
                            f"optimizer state {k}:{i} missing from {spath!r} "
                            "(different optimizer, or a truncated save?)")
                    host = arr.asnumpy()
                    # .states slots are logical OIHW on disk (save-side
                    # canonicalization); flip the ones mirroring an
                    # HWIO-stored param back to storage layout
                    stored = tuple(self.opt_state[k][i].shape)
                    if (k in self._hwio and host.ndim == 4
                            and tuple(host.shape[d]
                                      for d in (2, 3, 1, 0)) == stored):
                        host = np.transpose(host, (2, 3, 1, 0))
                    raw = jnp.asarray(host)
                    if self.mesh is not None:
                        raw = jax.device_put(raw,
                                             self.opt_state[k][i].sharding)
                    states.append(raw)
                self.opt_state[k] = tuple(states)
        self._refresh_compute_cache()
        return self
