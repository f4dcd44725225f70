"""Pipeline parallelism — GPipe-style microbatch pipelining over a mesh axis.

The reference approximates pipelining with ctx_group placement + the
dependency engine's opportunistic overlap (docs/how_to/
model_parallel_lstm.md); there is no scheduled-microbatch pipeline.
TPU-native design goes further: stages live on a 'pipe' mesh axis, and
one `shard_map`-wrapped `lax.scan` drives the classic GPipe schedule —
each tick every device runs its stage on the activation `ppermute`d from
the previous stage, so the whole pipeline (fill, steady state, drain) is
ONE XLA program.  Backward falls out of jax autodiff: the transpose of
ppermute is the reverse rotation, giving the mirror-image backward
schedule for free.

Schedule & memory profile:
- bubble: (S-1)/(S-1+M) of ticks are fill/drain for S stages and M
  microbatches (`bubble_fraction`); amortize with M >> S.
- activation memory: the autodiff of the scan saves each tick's stage
  activations, i.e. the GPipe profile (O(M) per stage).  1F1B's memory
  advantage (O(S) in-flight microbatches) is obtained here the XLA way:
  pass ``remat=True`` to checkpoint each stage invocation so backward
  recomputes stage activations tick by tick — the scan carry is then the
  only live activation, at ~1/3 extra stage FLOPs (same trade the
  reference exposes as MXNET_BACKWARD_DO_MIRROR, env_var.md:55-57).
- input/output replication: the microbatched input is replicated to all
  stages and outputs are psum-shared (losses are computed replicated) —
  per-device feed memory is O(batch), same order as data-parallel
  training; the per-stage *weights and activations* are what pipelining
  shards.  For feeds too big to replicate, stream microbatches from host
  with a prefetching iterator instead of staging the whole batch.

Real models: stages don't need to be single layers.  The usual layout is
embed/head OUTSIDE the pipeline (computed with plain GSPMD sharding) and
the repeated trunk inside, `blocks_per_stage` blocks per device via
`stacked_blocks_stage` (tests/test_pipeline_moe.py pipelines a 4-block
transformer LM; examples/model-parallel-lstm/lstm_pipeline.py pipelines
the reference's model-parallel LSTM-PTB workload with one LSTM layer per
stage).

Shapes:
- stage parameters are stacked on a leading stage axis and sharded over
  'pipe' (each device holds its stage's slice),
- the microbatched input is [n_micro, micro_batch, ...],
- every stage maps the activation shape to itself (equal-width trunk;
  width changes belong outside the pipelined region).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def stack_stage_params(per_stage_params):
    """[{name: array}, ...] -> {name: array stacked on axis 0} (all stages
    must share parameter structure — the usual 'repeated block' layout)."""
    names = per_stage_params[0].keys()
    return {n: jnp.stack([p[n] for p in per_stage_params]) for n in names}


def shard_stacked(mesh: Mesh, stacked, axis_name: str = "pipe"):
    """Place each stage's parameter slice on its pipeline device."""
    return {
        n: jax.device_put(
            v, NamedSharding(mesh, P(axis_name, *([None] * (v.ndim - 1)))))
        for n, v in stacked.items()
    }


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the GPipe forward scan (`pipeline_apply`):
    (S-1)/(S-1+M) — each stage does M useful ticks out of M+S-1."""
    return (n_stages - 1) / (n_stages - 1 + n_micro)


def bubble_fraction_1f1b(n_stages: int, n_micro: int) -> float:
    """Idle fraction of the LOCKSTEP 1F1B train step
    (`make_pipeline_train_step`): (2S-1)/(M+2S-1).

    Each of the M+2S-1 ticks costs one forward plus one backward on
    every device (masked slots still execute), and a stage fills M of
    its fwd slots and M of its bwd slots — so the fill/drain is ~2x the
    classic asynchronous 1F1B's (S-1)/(M+S-1).  That is the price of
    running the whole schedule as one SPMD scan; amortize with M >> S,
    which the O(S) activation stash makes affordable."""
    return (2 * n_stages - 1) / (n_micro + 2 * n_stages - 1)


def stacked_blocks_stage(block_fn):
    """Build a stage_fn running `blocks_per_stage` identical blocks.

    block_fn(block_params, x) -> y.  The per-stage parameter slice must
    carry a leading block axis on every leaf ({name: [B, ...]}); the
    blocks run sequentially via lax.scan.  With stack_stage_params the
    full tree is {name: [n_stages, B, ...]} — L = n_stages*B total
    blocks, the standard "repeated trunk" pipeline layout.
    """

    def stage_fn(params, x, stage):
        def body(h, blk):
            return block_fn(blk, h), None

        y, _ = jax.lax.scan(body, x, params)
        return y

    return stage_fn


def pipeline_apply(stage_fn, stacked_params, micro_inputs, mesh: Mesh,
                   axis_name: str = "pipe", remat: bool = False):
    """Run the GPipe schedule; returns [n_micro, ...] last-stage outputs.

    stage_fn(params_slice, x, stage_index) -> y; every stage must map the
    same activation shape to itself (classic equal-width pipeline).
    stage_index arrives as a traced scalar — use jnp.where/lax.cond on it
    for stage-dependent behavior.  remat=True recomputes stage
    activations in backward (1F1B's memory profile; module docstring).
    """
    n_stages = mesh.shape[axis_name]
    n_micro = micro_inputs.shape[0]
    ticks = n_micro + n_stages - 1
    fn = jax.checkpoint(stage_fn, static_argnums=()) if remat else stage_fn

    param_specs = {n: P(axis_name, *([None] * (v.ndim - 1)))
                   for n, v in stacked_params.items()}

    @partial(jax.shard_map, mesh=mesh,
             in_specs=(param_specs, P()),
             out_specs=P(),
             check_vma=False)
    def run(params, xs):
        # params: {name: [1, ...]} my stage's slice; xs: [n_micro, mb, ...]
        my = {n: v[0] for n, v in params.items()}
        stage = jax.lax.axis_index(axis_name)
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
        act_shape = xs.shape[1:]

        def tick(carry, t):
            held = carry  # activation this device just produced
            # rotate activations one stage forward; stage 0's incoming slot
            # is then overwritten by the next microbatch (or zeros while
            # draining)
            incoming = jax.lax.ppermute(held, axis_name, fwd_perm)
            feed = jnp.where(
                t < n_micro,
                jax.lax.dynamic_index_in_dim(
                    xs, jnp.minimum(t, n_micro - 1), keepdims=False),
                jnp.zeros(act_shape, xs.dtype))
            x_in = jnp.where(stage == 0, feed, incoming)
            y = fn(my, x_in, stage)
            # only the last stage's finished ticks are real outputs
            out = jnp.where(stage == n_stages - 1, y,
                            jnp.zeros_like(y))
            return y, out

        _, outs = jax.lax.scan(tick, jnp.zeros(act_shape, xs.dtype),
                               jnp.arange(ticks))
        # tick t on the last stage finishes microbatch t-(n_stages-1);
        # gather those and share them with every stage (losses are
        # computed replicated)
        outs = outs[n_stages - 1:]
        return jax.lax.psum(outs, axis_name)

    return run(stacked_params, micro_inputs)


def microbatch(x, n_micro):
    """[batch, ...] -> [n_micro, batch/n_micro, ...]."""
    if x.shape[0] % n_micro:
        raise ValueError(f"batch {x.shape[0]} not divisible by {n_micro}")
    return x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:])


# ===========================================================================
# Heterogeneous pipeline: per-stage parameter trees, shape-changing stage
# boundaries, and a 1F1B training schedule.
#
# The stacked-array pipeline above requires every stage to share one
# parameter structure and one activation shape — fine for a repeated
# trunk, wrong for a real model whose first stage embeds tokens and whose
# last stage projects to the vocabulary.  This section removes both
# restrictions:
#
# * per-stage pytrees: stages hand in arbitrary (and different) parameter
#   trees.  Internally the UNION of all stages' leaves is stacked on a
#   leading stage axis and sharded over the pipe axis — each device
#   materializes real values for its own stage's leaves and zeros for the
#   others (zeros cost memory: keep per-stage-exclusive leaves small or
#   shard them further, e.g. vocab-shard a large embedding over 'pipe'
#   and all_gather it inside the stage).  Stage dispatch is a
#   `lax.switch` on the device's pipe index — SPMD-legal because the
#   branches contain no collectives.
#
# * shape-changing boundaries: inter-stage activations are flattened per
#   sample and padded to the widest boundary, so stage i may map
#   [mb, T, D] -> [mb, T, 4D] (or an LSTM pipeline may narrow its hidden
#   width per layer).  `ppermute` moves one uniform [mb, F] buffer; each
#   stage statically slices/reshapes its true input and pads its output.
#
# * 1F1B schedule (`make_pipeline_train_step`): one fused XLA program
#   scans T = M + 2S - 1 ticks; at tick t, stage s runs the forward of
#   microbatch t-s and the backward of microbatch t+s-(2S-1) (each when
#   in range).  Forward activations rotate s->s+1 and backward cotangents
#   rotate s->s-1 every tick.  Per-stage activation memory is a
#   2S+1-deep stash of boundary INPUTS (backward recomputes the stage,
#   remat-style, via jax.vjp at the bwd tick) — O(S) in-flight
#   microbatches versus the O(M) residuals autodiff keeps for the GPipe
#   scan, at the standard one-extra-forward remat cost.  Idle fraction
#   is (2S-1)/(M+2S-1) (`bubble_fraction_1f1b` — the lockstep scan pays
#   ~2x the classic 1F1B fill/drain; amortize with M >> S); `tools/
#   pipeline_memory.py` prints the measured memory table.
# ===========================================================================


def _tree_paths(tree):
    """Pytree -> (ordered path-key list, {path: leaf}, treedef)."""
    from jax.tree_util import keystr, tree_flatten_with_path

    leaves, treedef = tree_flatten_with_path(tree)
    keys = [keystr(p) for p, _ in leaves]
    return keys, dict(zip(keys, (v for _, v in leaves))), treedef


class UnionMeta:
    """Bookkeeping for per-stage trees embedded in one stacked union."""

    def __init__(self, per_stage_params):
        self.n_stages = len(per_stage_params)
        self.stage_keys = []   # per stage: ordered leaf path keys
        self.stage_defs = []   # per stage: treedef
        self.union = {}        # path -> (shape, dtype)
        for tree in per_stage_params:
            keys, leaves, treedef = _tree_paths(tree)
            self.stage_keys.append(keys)
            self.stage_defs.append(treedef)
            for k in keys:
                sig = (tuple(leaves[k].shape), jnp.result_type(leaves[k]))
                if k in self.union and self.union[k] != sig:
                    raise ValueError(
                        f"leaf {k!r} has shape/dtype {sig} on one stage but "
                        f"{self.union[k]} on another; same-named leaves must "
                        "match across stages (rename stage-specific layers)")
                self.union[k] = sig

    def stage_tree(self, stage, union_slice):
        """{path: leaf} union slice -> stage's own pytree."""
        from jax.tree_util import tree_unflatten

        keys = self.stage_keys[stage]
        return tree_unflatten(self.stage_defs[stage],
                              [union_slice[k] for k in keys])

    def embed_grads(self, stage, grads_tree, like):
        """Stage's grad pytree -> union-slice dict (zeros elsewhere)."""
        from jax.tree_util import tree_leaves

        out = {k: jnp.zeros_like(v) for k, v in like.items()}
        for k, g in zip(self.stage_keys[stage], tree_leaves(grads_tree)):
            out[k] = g.astype(like[k].dtype)
        return out


def union_stack(per_stage_params, mesh=None, axis_name="pipe"):
    """Per-stage trees -> ({path: [S, ...] stacked array}, UnionMeta).

    Leaves absent from a stage are zero-filled at that stage's index.
    With ``mesh`` the stacked arrays are placed sharded over the pipe
    axis so each device holds only its stage's slice.
    """
    meta = UnionMeta(per_stage_params)
    stage_leaves = [_tree_paths(tree)[1] for tree in per_stage_params]
    stacked = {}
    for k, (shape, dtype) in meta.union.items():
        stacked[k] = jnp.stack([
            leaves[k] if k in leaves else jnp.zeros(shape, dtype)
            for leaves in stage_leaves])
    if mesh is not None:
        stacked = shard_stacked(mesh, stacked, axis_name)
    return stacked, meta


def union_unstack(stacked, meta):
    """Stacked union -> list of per-stage pytrees (host-side interop)."""
    return [meta.stage_tree(s, {k: v[s] for k, v in stacked.items()})
            for s in range(meta.n_stages)]


def _boundary_chain(stage_fns, meta, stacked, xs_local_sds):
    """Abstract-eval the stage chain; returns (in_sds, out_sds) per stage
    under LOCAL (per-device) batch shapes."""
    in_sds, out_sds = [], []
    cur = xs_local_sds
    for s, fn in enumerate(stage_fns):
        params_aval = meta.stage_tree(s, {
            k: jax.ShapeDtypeStruct(v.shape[1:], v.dtype)
            for k, v in stacked.items()})
        in_sds.append(cur)
        cur = jax.eval_shape(fn, params_aval, cur)
        out_sds.append(cur)
    return in_sds, out_sds


def _flat_len(sds):
    n = 1
    for d in sds.shape[1:]:
        n *= d
    return n


def _boundary_setup(stage_fns, meta, stacked, xs_shape, xs_dtype, S, dp):
    """Shared trace-time setup: abstract-eval the stage chain under local
    batch shapes and size the flat boundary buffer.

    Returns (in_sds, out_sds, F, bdt): per-stage in/out ShapeDtypeStructs,
    the padded per-sample boundary width, and the buffer dtype."""
    xs_local = jax.ShapeDtypeStruct((xs_shape[1] // dp,) + xs_shape[2:],
                                    xs_dtype)
    in_sds, out_sds = _boundary_chain(stage_fns, meta, stacked, xs_local)
    bdtypes = {s.dtype for s in out_sds[:-1]}
    if len(bdtypes) > 1:
        raise ValueError(f"boundary activations mix dtypes {bdtypes}")
    F = max((_flat_len(s) for s in out_sds[:-1]), default=1)
    bdt = out_sds[0].dtype if S > 1 else jnp.float32
    return in_sds, out_sds, F, bdt


def _flatpad(y, F):
    flat = y.reshape(y.shape[0], -1)
    return jnp.pad(flat, ((0, 0), (0, F - flat.shape[1])))


def _unflat(buf, sds):
    n = _flat_len(sds)
    return buf[:, :n].reshape(sds.shape).astype(sds.dtype)


def pipeline_apply_tree(stage_fns, stacked, meta, micro_inputs,
                        mesh: Mesh, axis_name: str = "pipe",
                        data_axis=None):
    """Forward GPipe pass with per-stage trees + shape-changing stages.

    Returns [n_micro, mb, ...] last-stage outputs.  Differentiable: grads
    of a loss on the result flow back through scan+switch+ppermute with
    the GPipe (all-forward-then-all-backward) memory profile; use
    `make_pipeline_train_step` for the O(S)-memory 1F1B schedule.
    """
    S = mesh.shape[axis_name]
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {S}-way pipe axis")
    M = micro_inputs.shape[0]
    dp = mesh.shape[data_axis] if data_axis else 1
    ticks = M + S - 1

    in_sds, out_sds, F, bdt = _boundary_setup(
        stage_fns, meta, stacked, micro_inputs.shape, micro_inputs.dtype,
        S, dp)
    y_sds = out_sds[-1]

    branches = []
    for i, fn in enumerate(stage_fns):
        def br(sl, buf_in, x0, i=i, fn=fn):
            p = meta.stage_tree(i, sl)
            x = x0 if i == 0 else _unflat(buf_in, in_sds[i])
            y = fn(p, x)
            if i == S - 1:
                return jnp.zeros((y.shape[0], F), bdt), y
            return _flatpad(y, F).astype(bdt), jnp.zeros(y_sds.shape,
                                                         y_sds.dtype)
        branches.append(br)

    pspecs = {k: P(axis_name, *([None] * (len(sig[0]))))
              for k, sig in meta.union.items()}
    xspec = (P(None, data_axis) if data_axis else P())

    @partial(jax.shard_map, mesh=mesh, in_specs=(pspecs, xspec),
             out_specs=xspec, check_vma=False)
    def run(params, xs):
        sl = {k: v[0] for k, v in params.items()}
        stage = jax.lax.axis_index(axis_name)
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]
        mb = xs.shape[1]

        def tick(buf_in, t):
            m = jnp.clip(t - stage, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(xs, m, keepdims=False)
            flat_out, y = jax.lax.switch(stage, branches, sl, buf_in, x0)
            ok = (t - stage >= 0) & (t - stage < M)
            out = jnp.where(ok & (stage == S - 1), y,
                            jnp.zeros_like(y))
            return jax.lax.ppermute(flat_out, axis_name, fwd_perm), out

        _, outs = jax.lax.scan(tick, jnp.zeros((mb, F), bdt),
                               jnp.arange(ticks))
        outs = outs[S - 1:]  # last stage finishes microbatch t-(S-1)
        return jax.lax.psum(outs, axis_name)

    return run(stacked, micro_inputs)


def make_pipeline_train_step(stage_fns, loss_fn, meta, mesh: Mesh,
                             axis_name: str = "pipe", data_axis=None):
    """Build the fused 1F1B train step.

    stage_fns[i](params_i, x) -> y; loss_fn(y_last, labels) -> scalar
    (mean over its microbatch).  Returns step(stacked, xs, labels) ->
    (loss, grads) where grads is a stacked union dict sharded like the
    params (stage s's grads live on stage s's devices; zeros for leaves a
    stage doesn't own) — feed it straight to a sharded optimizer update,
    or `union_unstack` it for host-side use.

    Schedule: tick t runs fwd(microbatch t-s) and bwd(microbatch
    t+s-(2S-1)) on stage s; boundary inputs are stashed (depth 2S+1) and
    each backward recomputes its stage via jax.vjp — O(S) activation
    memory, (2S-1)/(M+2S-1) lockstep bubble (`bubble_fraction_1f1b`),
    one extra stage forward per microbatch (remat trade).
    """
    S = mesh.shape[axis_name]
    if len(stage_fns) != S:
        raise ValueError(f"{len(stage_fns)} stage fns for {S}-way pipe axis")
    dp = mesh.shape[data_axis] if data_axis else 1
    D = 2 * S + 1  # stash depth: max fwd->bwd gap is 2(S-1)+1 ticks

    def step(stacked, xs, labels):
        M = xs.shape[0]
        ticks = M + 2 * S - 1
        in_sds, out_sds, F, bdt = _boundary_setup(
            stage_fns, meta, stacked, xs.shape, xs.dtype, S, dp)

        fwd_branches, bwd_branches = [], []
        for i, fn in enumerate(stage_fns):
            def fbr(sl, buf_in, x0, lab, i=i, fn=fn):
                p = meta.stage_tree(i, sl)
                x = x0 if i == 0 else _unflat(buf_in, in_sds[i])
                y = fn(p, x)
                if i == S - 1:
                    return (jnp.zeros((x.shape[0], F), bdt),
                            loss_fn(y, lab).astype(jnp.float32))
                return _flatpad(y, F).astype(bdt), jnp.float32(0.0)

            def bbr(sl, x_stash, x0, lab, dy, i=i, fn=fn):
                p = meta.stage_tree(i, sl)
                x = x0 if i == 0 else _unflat(x_stash, in_sds[i])
                if i == S - 1:
                    # loss seeds its own cotangent: 1/M for the
                    # mean-over-microbatches total
                    def g(pp, xx):
                        return loss_fn(fn(pp, xx), lab)
                    _, vjpf = jax.vjp(g, p, x)
                    dparams, dx = vjpf(jnp.float32(1.0 / M))
                else:
                    _, vjpf = jax.vjp(fn, p, x)
                    dparams, dx = vjpf(_unflat(dy, out_sds[i]))
                dunion = meta.embed_grads(i, dparams, sl)
                if i == 0:
                    dxf = jnp.zeros((x.shape[0], F), bdt)
                else:
                    dxf = _flatpad(dx, F).astype(bdt)
                return dunion, dxf

            fwd_branches.append(fbr)
            bwd_branches.append(bbr)

        pspecs = {k: P(axis_name, *([None] * len(sig[0])))
                  for k, sig in meta.union.items()}
        dspec = (P(None, data_axis) if data_axis else P())

        @partial(jax.shard_map, mesh=mesh,
                 in_specs=(pspecs, dspec, dspec),
                 out_specs=(P(), pspecs),
                 check_vma=False)
        def run(params, xs, labels):
            sl = {k: v[0] for k, v in params.items()}
            stage = jax.lax.axis_index(axis_name)
            fwd_perm = [(i, (i + 1) % S) for i in range(S)]
            bwd_perm = [(i, (i - 1) % S) for i in range(S)]
            mb = xs.shape[1]

            def tick(carry, t):
                buf_in, dy_in, stash, gacc, loss_acc = carry
                # ---- forward slot: microbatch t - stage
                fm = t - stage
                do_f = (fm >= 0) & (fm < M)
                mf = jnp.clip(fm, 0, M - 1)
                x0 = jax.lax.dynamic_index_in_dim(xs, mf, keepdims=False)
                lf = jax.lax.dynamic_index_in_dim(labels, mf, keepdims=False)
                flat_out, lc = jax.lax.switch(stage, fwd_branches,
                                              sl, buf_in, x0, lf)
                flat_out = jnp.where(do_f, flat_out,
                                     jnp.zeros_like(flat_out))
                loss_acc = loss_acc + jnp.where(
                    do_f & (stage == S - 1), lc, 0.0)
                # stash this stage's INPUT for the bwd recompute; slot D
                # is a scratch row so out-of-range ticks clobber nothing
                slot = jnp.where(do_f, mf % D, D)
                stash = jax.lax.dynamic_update_index_in_dim(
                    stash, buf_in, slot, 0)
                # ---- backward slot: microbatch t + stage - (2S-1)
                bm = t + stage - (2 * S - 1)
                do_b = (bm >= 0) & (bm < M)
                mbk = jnp.clip(bm, 0, M - 1)
                x0b = jax.lax.dynamic_index_in_dim(xs, mbk, keepdims=False)
                lb = jax.lax.dynamic_index_in_dim(labels, mbk,
                                                  keepdims=False)
                x_st = jax.lax.dynamic_index_in_dim(stash, mbk % D,
                                                    keepdims=False)
                dun, dx = jax.lax.switch(stage, bwd_branches,
                                         sl, x_st, x0b, lb, dy_in)
                gacc = jax.tree_util.tree_map(
                    lambda a, d: a + jnp.where(do_b, d,
                                               jnp.zeros_like(d)),
                    gacc, dun)
                dx = jnp.where(do_b, dx, jnp.zeros_like(dx))
                return ((jax.lax.ppermute(flat_out, axis_name, fwd_perm),
                         jax.lax.ppermute(dx, axis_name, bwd_perm),
                         stash, gacc, loss_acc), None)

            init = (jnp.zeros((mb, F), bdt), jnp.zeros((mb, F), bdt),
                    jnp.zeros((D + 1, mb, F), bdt),
                    {k: jnp.zeros_like(v) for k, v in sl.items()},
                    jnp.float32(0.0))
            (_, _, _, gacc, loss_acc), _ = jax.lax.scan(
                tick, init, jnp.arange(ticks))

            loss = jax.lax.psum(loss_acc, axis_name) / M
            if data_axis:
                loss = jax.lax.pmean(loss, data_axis)
                gacc = {k: jax.lax.pmean(v, data_axis)
                        for k, v in gacc.items()}
            return loss, {k: v[None] for k, v in gacc.items()}

        return run(stacked, xs, labels)

    return jax.jit(step)
