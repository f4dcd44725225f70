"""Paged decode attention: walk the block table inside the kernel.

The PR-15 paged step *gather-materializes* a slot's whole KV table
every tick — ``pool[bt]`` + transpose + reshape rebuilds the contiguous
``(L, B, H, S, dh)`` layout before a single score is computed, paying
for every allocated page whether or not the slot's cursor ever reached
it.  This module computes the same decode attention straight off the
page pool, three lowerings behind one schedule-driven entry:

- **pallas** — the TPU kernel: grid over ``(B, H)`` (or flattened,
  a schedule knob), per-slot block table and cursors ride as scalar
  prefetch, and the kernel DMAs ONE ``(block, dh)`` VMEM tile per KV
  page from the HBM-resident pool — optionally only the pages the
  cursor has reached (``live_only``).  Decode is forward-only, so no
  custom VJP.  ``interpret=True`` runs the same kernel on CPU: the
  parity-test hook, bitwise against the gather path on aligned shapes.
- **pagewalk** — a lax lowering of the same idea for hosts without a
  TPU: a ``fori_loop`` whose trip count is the *live* page count
  (``max(cursor)``-bounded, a traced scalar — no host sync, no
  recompile), gathering ``chunk`` pages per iteration.  Same attention
  math, but loop-carried accumulation reassociates the reductions, so
  it is allclose-not-bitwise vs gather — which is why it is installed
  by the autotuner or an explicit ``MXTPU_PAGED_KERNEL=pagewalk``,
  never silently.
- **gather** — the PR-15 reference math on the materialized table, kept
  as the structural fallback behind :func:`supports` (same pattern as
  ``ops/residual_epilogue.py``) and as the search baseline every
  candidate must beat.

Schedules are plain dicts (``{"impl": ..., ...knobs}``) chosen by
``mxnet_tpu.autotune`` at ``PagedSlots`` construction — never per
tick.  See ``docs/autotune.md``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import mxu_precision

__all__ = [
    "supports", "keysig", "default_schedule", "candidate_schedules",
    "paged_attention", "gather_tables", "make_bench_fn",
]

# the masking constant of the decode stack (== models.decode.NEG_INF;
# kept literal so this op module never imports the models package)
NEG_INF = -1e30

_PAGEWALK_CHUNKS = (1, 2, 4, 8)


def supports(block: int, dh: int, dtype) -> bool:
    """Will Mosaic take the Pallas kernel at ``(block, dh)`` KV pages?
    One page is one VMEM tile DMA'd out of the pool: ``dh`` must fill
    whole 128-wide lanes (the v5e compiler refuses a narrower slice of
    the pool: "must be aligned to tiling (128)") and ``block`` whole
    sublane tiles — 8 rows of f32, 16 of packed bf16.  Everything else
    takes gather, by this gate."""
    dt = jnp.dtype(dtype)
    if dt == jnp.dtype(jnp.float32):
        rows = 8
    elif dt == jnp.dtype(jnp.bfloat16):
        rows = 16
    else:
        return False
    return block > 0 and dh > 0 and block % rows == 0 and dh % 128 == 0


def keysig(B: int, H: int, M: int, block: int, dh: int, dtype) -> str:
    """The autotuner shape signature of one decode-step workload."""
    return "b%dh%dm%dk%dd%d_%s" % (B, H, M, block, dh,
                                   jnp.dtype(dtype).name)


def default_schedule(platform: str, block: int, dh: int, dtype) -> dict:
    """What runs with no tuned winner: the kernel on a TPU whose shape
    qualifies, the bitwise gather path everywhere else."""
    if platform == "tpu" and supports(block, dh, dtype):
        return {"impl": "pallas", "grid": "bh", "live_only": True}
    return {"impl": "gather"}


def candidate_schedules(platform: str, block: int, dh: int, M: int,
                        dtype) -> list:
    """The search space for one shape signature.  Gather is always a
    candidate (the winner can never lose to not tuning); pagewalk chunk
    sizes must divide the block-table width; pallas variants (grid
    layout x live-page DMA) only where the compiled kernel can run."""
    cands = [{"impl": "gather"}]
    for ch in _PAGEWALK_CHUNKS:
        if ch <= M and M % ch == 0:
            cands.append({"impl": "pagewalk", "chunk": ch})
    if platform == "tpu" and supports(block, dh, dtype):
        for grid in ("bh", "flat"):
            for live in (True, False):
                cands.append({"impl": "pallas", "grid": grid,
                              "live_only": live})
    return cands


# ---------------------------------------------------------------- gather
def gather_tables(pool, bt, block: int):
    """``(P, L, H, blk, dh)[bt (B, M)] -> (L, B, H, M*blk, dh)`` — the
    PR-15 materialization, shared here so the op-level baseline and the
    serving gather path stay the same expression."""
    B, M = bt.shape
    _P, L, H, blk, dh = pool.shape
    t = pool[bt]                                 # (B, M, L, H, blk, dh)
    t = t.transpose(2, 0, 3, 1, 4, 5)            # (L, B, H, M, blk, dh)
    return t.reshape(L, B, H, M * block, dh)


def _attend(q, kc, vc, cursor):
    """The reference decode attention over a contiguous table slice —
    exactly the PR-15 step math (bitwise anchor for every lowering)."""
    S = kc.shape[2]
    dh = q.shape[-1]
    valid = jnp.arange(S)[None, :] <= cursor[:, None]
    scores = jnp.einsum("bhnd,bhsd->bhns", q, kc) \
        / jnp.sqrt(jnp.asarray(dh, q.dtype))
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhns,bhsd->bhnd", att, vc)


def _gather_attention(q, pool_k, pool_v, bt, cursor, layer, block):
    kc = gather_tables(pool_k, bt, block)[layer]
    vc = gather_tables(pool_v, bt, block)[layer]
    return _attend(q, kc, vc, cursor)


# -------------------------------------------------------------- pagewalk
def _pagewalk_attention(q, pool_k, pool_v, bt, cursor, layer, block,
                        chunk):
    B, H, _n, dh = q.shape
    M = bt.shape[1]
    ch = int(chunk)
    if ch < 1 or M % ch:
        ch = 1                                   # always-valid fallback
    S = M * block
    qs = q[:, :, 0, :]                           # (B, H, dh)
    # live trip count: pages any slot's cursor has reached — a traced
    # scalar, so raggedness never retraces and never syncs the host
    n_live = (jnp.max(cursor) + block) // block
    n_it = (n_live + ch - 1) // ch
    scale = jnp.sqrt(jnp.asarray(dh, q.dtype))
    valid = (jnp.arange(S)[None, :] <= cursor[:, None])[:, None, :]

    def scores_body(it, buf):
        pgs = jax.lax.dynamic_slice(bt, (0, it * ch), (B, ch))
        k = pool_k[pgs, layer]                   # (B, ch, H, blk, dh)
        s = jnp.einsum("bhd,bchkd->bhck", qs, k) \
            .reshape(B, H, ch * block) / scale
        return jax.lax.dynamic_update_slice(buf, s, (0, 0, it * ch * block))

    scores = jax.lax.fori_loop(
        0, n_it, scores_body, jnp.full((B, H, S), NEG_INF, q.dtype))
    scores = jnp.where(valid, scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1)        # dead pages: exact 0

    def ctx_body(it, acc):
        pgs = jax.lax.dynamic_slice(bt, (0, it * ch), (B, ch))
        v = pool_v[pgs, layer]
        a = jax.lax.dynamic_slice(
            att, (0, 0, it * ch * block),
            (B, H, ch * block)).reshape(B, H, ch, block)
        return acc + jnp.einsum("bhck,bchkd->bhd", a, v)

    ctx = jax.lax.fori_loop(
        0, n_it, ctx_body, jnp.zeros((B, H, dh), q.dtype))
    return ctx[:, :, None, :]


# ---------------------------------------------------------------- pallas
def _pallas_attention(q, pool_k, pool_v, bt, cursor, layer, block,
                      schedule, interpret):
    B, H, _n, dh = q.shape
    M = bt.shape[1]
    S = M * block
    flat = schedule.get("grid") == "flat"
    live_only = bool(schedule.get("live_only", True))

    def kernel(bt_ref, cur_ref, q_ref, pk_ref, pv_ref, o_ref,
               kbuf, vbuf, sem):
        if flat:
            i = pl.program_id(0)
            b, h = i // H, i % H
        else:
            b, h = pl.program_id(0), pl.program_id(1)
        cur = cur_ref[b]
        if live_only:
            # skipped (dead) pages leave vbuf unread-after-write garbage;
            # their attention weights are exact zeros, but 0 * NaN is
            # NaN — zero the value tiles so dead pages contribute exact
            # zeros like the gather path.  kbuf garbage is safe: dead
            # scores are replaced wholesale by NEG_INF below.
            vbuf[...] = jnp.zeros((S, dh), vbuf.dtype)
        for m in range(M):
            def _dma(m=m):
                pg = bt_ref[b, m]
                cp = pltpu.make_async_copy(
                    pk_ref.at[pg, layer, h],
                    kbuf.at[pl.ds(m * block, block)], sem)
                cp.start()
                cp.wait()
                cp = pltpu.make_async_copy(
                    pv_ref.at[pg, layer, h],
                    vbuf.at[pl.ds(m * block, block)], sem)
                cp.start()
                cp.wait()
            if live_only:
                pl.when(m * block <= cur)(_dma)
            else:
                _dma()
        qv = q_ref[0, 0]                                 # (1, dh)
        # Mosaic only takes a matmul that accumulates in 32 bits, and a
        # bf16 one only at single-pass precision (the package default is
        # fp32 passes): both products and the softmax between them run
        # in f32 whatever the cache dtype; the weights drop back to the
        # cache dtype for the MXU
        prec = mxu_precision(qv)
        scores = jnp.einsum("nd,sd->ns", qv, kbuf[...], precision=prec,
                            preferred_element_type=jnp.float32) \
            / jnp.sqrt(jnp.asarray(dh, jnp.float32))
        s_idx = jax.lax.broadcasted_iota(jnp.int32, (1, S), 1)
        scores = jnp.where(s_idx <= cur, scores, NEG_INF)
        att = jax.nn.softmax(scores, axis=-1)
        o_ref[0, 0] = jnp.einsum(
            "ns,sd->nd", att.astype(vbuf.dtype), vbuf[...],
            precision=prec,
            preferred_element_type=jnp.float32).astype(o_ref.dtype)

    if flat:
        grid = (B * H,)
        qmap = lambda i, *_: (i // H, i % H, 0, 0)
    else:
        grid = (B, H)
        qmap = lambda b, h, *_: (b, h, 0, 0)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # bt, cursor
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, dh), qmap),
            pl.BlockSpec(memory_space=pl.ANY),   # pool_k stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # pool_v stays in HBM
        ],
        out_specs=pl.BlockSpec((1, 1, 1, dh), qmap),
        scratch_shapes=[
            pltpu.VMEM((S, dh), q.dtype),
            pltpu.VMEM((S, dh), q.dtype),
            pltpu.SemaphoreType.DMA,
        ])
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, 1, dh), q.dtype),
        grid_spec=gs,
        interpret=interpret,
        name="paged_attn",
    )(bt.astype(jnp.int32), cursor.astype(jnp.int32), q, pool_k, pool_v)


# ------------------------------------------------------------------ entry
def paged_attention(q, pool_k, pool_v, bt, cursor, layer, *, block,
                    schedule=None, interpret=False):
    """Decode attention for one layer straight off the page pool.

    ``q``: ``(B, H, 1, dh)``; ``pool_k``/``pool_v``: ``(P, L, H, block,
    dh)``; ``bt``: ``(B, M)`` page ids; ``cursor``: ``(B,)`` absolute
    positions (attend over ``[0, cursor[b]]``).  Returns ``(B, H, 1,
    dh)``.  ``schedule`` picks the lowering (``None`` = gather); shapes
    the Pallas gate rejects fall back to gather even when forced —
    ragged shapes never crash, they just take the reference path."""
    sched = schedule or {"impl": "gather"}
    impl = sched.get("impl", "gather")
    if impl == "pallas" and not supports(block, q.shape[-1], q.dtype):
        impl = "gather"
    if impl == "pallas":
        # interpreted only when asked for (the CPU parity tool) — never
        # inferred from the backend: a forced kernel that cannot lower
        # says so
        interp = bool(interpret or sched.get("interpret", False))
        return _pallas_attention(
            q, pool_k, pool_v, bt, cursor, layer, block, sched, interp)
    if impl == "pagewalk":
        return _pagewalk_attention(q, pool_k, pool_v, bt, cursor, layer,
                                   block, sched.get("chunk", 1))
    return _gather_attention(q, pool_k, pool_v, bt, cursor, layer, block)


# ------------------------------------------------------------- benchmark
def make_bench_fn(schedule, *, B, H, M, block, dh, L, dtype=jnp.float32):
    """A thunk timing one decode step's attention (all ``L`` layers)
    under ``schedule``, on a synthetic steady-state pool: per-slot
    cursors spread raggedly across the context (mean ~half full — the
    regime a serving mix actually sits in), block tables dense.  The
    gather baseline amortizes ONE materialization over all layers,
    exactly like the serving step, so the comparison is never rigged
    against it.  Used by the ``PagedSlots`` tuning call site and
    ``bench.py::_autotune_micro``."""
    S = M * block
    P = B * M + 1
    rs = np.random.RandomState(0)
    pool_k = jnp.asarray(rs.normal(size=(P, L, H, block, dh))
                         .astype(jnp.dtype(dtype).name))
    pool_v = jnp.asarray(rs.normal(size=(P, L, H, block, dh))
                         .astype(jnp.dtype(dtype).name))
    q = jnp.asarray(rs.normal(size=(B, H, 1, dh))
                    .astype(jnp.dtype(dtype).name))
    bt = jnp.asarray(
        rs.permutation(np.arange(1, P))[:B * M].reshape(B, M)
        .astype(np.int32))
    cursor = jnp.asarray(np.linspace(block, S - 1, B).astype(np.int32))

    sched = schedule or {"impl": "gather"}
    # the arrays are jit ARGUMENTS, not closure captures: captured
    # device values become compile-time constants and XLA folds part of
    # the work into the executable, timing a fiction
    if sched.get("impl", "gather") == "gather":
        def step(q, pool_k, pool_v, bt, cursor):
            kc = gather_tables(pool_k, bt, block)
            vc = gather_tables(pool_v, bt, block)
            return sum(_attend(q, kc[i], vc[i], cursor)
                       for i in range(L))
    else:
        def step(q, pool_k, pool_v, bt, cursor):
            return sum(
                paged_attention(q, pool_k, pool_v, bt, cursor, i,
                                block=block, schedule=sched)
                for i in range(L))
    jitted = jax.jit(step)
    return lambda: jitted(q, pool_k, pool_v, bt, cursor)
