"""Paged decode attention: walk the block table inside the kernel.

The PR-15 paged step *gather-materializes* a slot's whole KV table
every tick — ``pool[bt]`` + transpose + reshape rebuilds the contiguous
``(L, B, H, S, dh)`` layout before a single score is computed, paying
for every allocated page whether or not the slot's cursor ever reached
it.  This module computes the same decode attention straight off the
page pool, two lowerings behind one schedule-driven entry:

- **pallas** — the TPU kernel: one program a slot (grid ``(B,)``), the
  block table and the limits ride as scalar prefetch.  A K/V head
  serves ``R`` query rows (``q`` ``(B, Hkv, R, dh)``): one for the
  GPT-2 block, whose every query head has K/V of its own; (query
  heads a K/V head) x (tokens of the slot's block) for a grouped-query
  decoder that decodes a block a slot (``models/sdar.py``), all of
  which see the same keys ``[0, limit]``.  The pool is
  ``(P, L, H, block, dh)`` row-major, so ``pool[pg, layer]`` is one
  contiguous ``(H, block, dh)`` run: a page comes in for ALL heads in
  one DMA.  The program walks its slot's live pages (those the cursor
  has reached: ``limit``) a chunk at a time: every copy of a chunk is started
  before any is waited for, the next chunk's copies — after a slot's
  last chunk, the next slot's first — fly while this chunk's scores,
  softmax and weighted sum are computed (two VMEM buffers), and a
  running max / sum / accumulator in f32 joins the chunks — so neither
  the copies nor the arithmetic ever cover a page past the cursor, and
  VMEM holds two chunks whatever ``max_len`` is (:func:`chunk_pages`).
  Decode is forward-only, so no custom VJP.  ``interpret=True`` runs
  the same kernel on CPU: the parity-test hook.  The sums over
  positions are taken chunk by chunk, so against gather it is allclose
  in f32 (a few ulp), not bitwise.
- **gather** — the PR-15 reference math on one layer's materialized
  table, kept as the structural fallback behind :func:`supports` (same
  pattern as ``ops/residual_epilogue.py``), as the reference the tests
  hold the kernel to, and as the search baseline it must beat.

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
    "chunk_pages", "paged_attention", "layer_table", "dense_attention",
    "online_softmax", "history_attention", "make_bench_fn",
]

# the masking constant of the decode stack (== models.decode.NEG_INF;
# kept literal so this op module never imports the models package)
NEG_INF = -1e30
# a prefill over a slot window of up to this many positions takes one
# softmax over ``[history | tail]`` (``dense_attention``); over a longer
# window it takes the history a key block at a time
# (``history_attention``): at 30 heads, 2048 rows and a window of 17,408
# the one softmax's float32 scores would be 4.8 GB
DENSE_WINDOW = 4096
HISTORY_KEY_BLOCK = 1024


def supports(block: int, dh: int, dtype) -> bool:
    """Will Mosaic take the Pallas kernel at ``(block, dh)`` KV pages?
    A page is DMA'd out of the pool as ``(H, block, dh)`` tiles: ``dh``
    must fill whole 128-wide lanes (the v5e compiler refuses a narrower
    slice of the pool: "must be aligned to tiling (128)") and ``block``
    whole sublane tiles — 8 rows of f32, 16 of packed bf16.  Everything
    else takes gather, by this gate."""
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
    qualifies, the gather path everywhere else."""
    if platform == "tpu" and supports(block, dh, dtype):
        return {"impl": "pallas"}
    return {"impl": "gather"}


def candidate_schedules(platform: str, block: int, dh: int, dtype) -> list:
    """The search space for one shape signature.  Gather is always a
    candidate (the winner can never lose to not tuning); the pallas
    kernel (it has no knob: its chunk follows from the shapes) only
    where the compiled kernel can run."""
    cands = [{"impl": "gather"}]
    if platform == "tpu" and supports(block, dh, dtype):
        cands.append({"impl": "pallas"})
    return cands


# ---------------------------------------------------------------- gather
def layer_table(pool, bt, layer):
    """``(P, L, H, blk, dh)[bt (B, M), layer] -> (B, H, M*blk, dh)``: one
    layer's table of each slot in the contiguous layout."""
    B, M = bt.shape
    _P, _L, H, blk, dh = pool.shape
    return pool[bt, layer].transpose(0, 2, 1, 3, 4).reshape(
        B, H, M * blk, dh)


def dense_attention(q, k, v, mask):
    """Masked softmax attention over a contiguous table: ``q`` ``(B, H,
    n, dh)``, ``k``/``v`` ``(B, H, S, dh)``, ``mask`` broadcast against
    ``(B, H, n, S)``.  The decode stack's math (``models/decode.py``'s
    views hold the same lines): the bitwise anchor for every lowering;
    a masked-out entry weighs exactly zero."""
    scores = jnp.einsum("bhnd,bhsd->bhns", q, k) \
        / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    scores = jnp.where(mask, scores, NEG_INF)
    att = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhns,bhsd->bhnd", att, v)


def online_softmax(trips, block, carry):
    """A softmax over keys taken ``trips`` key blocks at a time, online
    in float32: ``block(j)`` gives block ``j``'s scores ``(H, T, K)``,
    masked with ``NEG_INF``, and its values ``(K, H, d)``; ``carry`` is
    the running maximum and sum of exponentials ``(H, T)`` and the
    weighted values ``(T, H, d)``.  Returns them after the last block,
    in a loop whose trip count may ride on a traced scalar.  A block the
    loop visits must hold a seen key of each row: the maximum is then
    finite and an unseen key weighs exactly 0."""
    def body(j, carry):
        m, l, acc = carry
        s, v = block(j)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        keep = jnp.exp(m - m_new)
        acc = acc * keep.T[..., None] + jnp.einsum(
            "hts,shd->thd", p.astype(v.dtype), v,
            preferred_element_type=jnp.float32)
        return m_new, l * keep + jnp.sum(p, axis=-1), acc

    return jax.lax.fori_loop(0, trips, body, carry)


def history_attention(q, k, v, pool_k, pool_v, bt_row, layer, hist, seen,
                      *, block):
    """A prefill's attention with the softmax taken online, in float32:
    ``q`` ``(H, R, dh)`` (``R`` = query heads a K/V head x the tail's
    ``T`` rows), the tail's own ``k, v`` ``(H, T, dh)`` under ``seen``
    ``(R, T)``, then the slot's history -- positions ``[0, hist)`` of
    the table row ``bt_row`` in ``layer`` of the pools --
    ``HISTORY_KEY_BLOCK`` positions at a time (:func:`online_softmax`),
    each block's pages read from the pool as it comes (``pool[pages,
    layer]``: whole pages, never the layer), in a loop of ``ceil(hist /
    HISTORY_KEY_BLOCK)`` trips.  The largest score matrix is ``(H, R,
    max(T, HISTORY_KEY_BLOCK))``.  Returns ``(H, R, dh)`` in ``q``'s
    dtype."""
    H, R, dh = q.shape
    kb = HISTORY_KEY_BLOCK
    scale = 1.0 / np.sqrt(dh)
    f32 = jnp.float32
    s = jnp.einsum("hrd,htd->hrt", q, k, preferred_element_type=f32)
    s = jnp.where(seen, s * scale, NEG_INF)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    acc = jnp.einsum("hrt,htd->rhd", p.astype(v.dtype), v,
                     preferred_element_type=f32)
    pages = kb // block
    # the row in whole blocks: a block past the table reads the scratch
    # page, masked like every position past ``hist``
    bt_row = jnp.pad(bt_row, (0, -bt_row.shape[0] % pages))

    def keys(pool, j):                                   # (kb, H, dh)
        ids = jax.lax.dynamic_slice_in_dim(bt_row, j * pages, pages)
        return pool[ids, layer].transpose(0, 2, 1, 3).reshape(kb, H, dh)

    def one(j):
        s = jnp.einsum("hrd,khd->hrk", q, keys(pool_k, j),
                       preferred_element_type=f32)
        at = j * kb + jnp.arange(kb)
        return jnp.where(at < hist, s * scale, NEG_INF), keys(pool_v, j)

    m, l, acc = online_softmax((hist + kb - 1) // kb, one,
                               (m, jnp.sum(p, axis=-1), acc))
    return (acc / l.T[..., None]).transpose(1, 0, 2).astype(q.dtype)


def _gather_attention(q, pool_k, pool_v, bt, limit, layer):
    S = bt.shape[1] * pool_k.shape[3]
    valid = jnp.arange(S)[None, :] <= limit[:, None]
    return dense_attention(q, layer_table(pool_k, bt, layer),
                           layer_table(pool_v, bt, layer),
                           valid[:, None, None, :])


# ---------------------------------------------------------------- pallas
# VMEM the kernel may hold in page buffers (K and V, two chunks each);
# the chunk — pages in flight — follows from it and the page's shape, so
# VMEM never grows with the block table.  A chunk is unrolled (one DMA
# descriptor a page), hence the second cap.
_VMEM_BUDGET = 2 << 20
_MAX_CHUNK_PAGES = 16


def chunk_pages(H: int, block: int, dh: int, dtype, M: int) -> int:
    """Pages of one slot the kernel keeps in flight: what fits the VMEM
    budget twice over (two buffers) for K and V, at most the table."""
    page = H * block * dh * jnp.dtype(dtype).itemsize
    return int(max(1, min(M, _MAX_CHUNK_PAGES, _VMEM_BUDGET // (4 * page))))


def _pallas_attention(q, pool_k, pool_v, bt, limit, layer, block,
                      interpret):
    B, H, R, dh = q.shape       # H: K/V heads; R query rows each
    M = bt.shape[1]
    C = chunk_pages(H, block, dh, q.dtype, M)
    Cb = C * block

    def kernel(bt_ref, cur_ref, q_ref, pk_ref, pv_ref, o_ref,
               kbuf, vbuf, sem, first_ref):
        b = pl.program_id(0)
        cur = cur_ref[b]

        def live_pages(slot):
            # pages holding [0, limit]: at least one, so every program
            # runs a chunk and the chain of prefetches never breaks
            return jnp.clip(cur_ref[slot] // block + 1, 1, M)

        n_live = live_pages(b)
        n_chunks = (n_live + C - 1) // C

        def copies(slot, c, buf, j):
            # page j of the slot's chunk c, all heads at once:
            # pool[pg, layer] is (H, block, dh), contiguous in HBM
            pg = bt_ref[slot, c * C + j]
            rows = pl.ds(j * block, block)
            return (pltpu.make_async_copy(pk_ref.at[pg, layer],
                                          kbuf.at[buf, :, rows],
                                          sem.at[0, buf]),
                    pltpu.make_async_copy(pv_ref.at[pg, layer],
                                          vbuf.at[buf, :, rows],
                                          sem.at[1, buf]))

        def start(slot, c, buf):
            live = live_pages(slot)
            for j in range(C):
                @pl.when(c * C + j < live)
                def _(j=j):
                    for cp in copies(slot, c, buf, j):
                        cp.start()

        def wait(c, buf):
            for j in range(C):
                live = c * C + j < n_live

                @pl.when(live)
                def _(j=j):
                    for cp in copies(b, c, buf, j):
                        cp.wait()

                # a page past the cursor was never fetched: its buffer
                # rows hold whatever was there, and 0 x NaN is NaN, so
                # the value rows are zeroed before they meet a weight.
                # The key rows may stay: their scores are replaced
                # wholesale by NEG_INF below
                @pl.when(jnp.logical_not(live))
                def _(j=j):
                    vbuf[buf, :, pl.ds(j * block, block)] = jnp.zeros(
                        (H, block, dh), vbuf.dtype)

        # programs run one after another (one core walks the grid): each
        # starts the NEXT slot's first chunk behind its own last one and
        # leaves in ``first_ref`` which buffer that went to, so only
        # the first slot's first fetch is waited for in the open
        @pl.when(b == 0)
        def _():
            first_ref[0] = 0
            start(0, 0, 0)

        first = first_ref[0]
        qv = q_ref[0]                                    # (H, R, dh)
        # Mosaic only takes a matmul that accumulates in 32 bits, and a
        # bf16 one only at single-pass precision (the package default is
        # fp32 passes): both products and the softmax between them run
        # in f32 whatever the cache dtype; the weights drop back to the
        # cache dtype for the MXU
        prec = mxu_precision(qv)
        scale = jnp.sqrt(jnp.asarray(dh, jnp.float32))

        def body(c, carry):
            m, l, acc = carry
            buf = (first + c) % 2

            # what flies during this chunk's arithmetic: the slot's next
            # chunk, or behind its last one the next slot's first
            last = c + 1 == n_chunks

            @pl.when(jnp.logical_or(jnp.logical_not(last), b + 1 < B))
            def _():
                start(jnp.where(last, b + 1, b), jnp.where(last, 0, c + 1),
                      1 - buf)

            wait(c, buf)
            s = jnp.einsum("hnd,hsd->hns", qv, kbuf[buf], precision=prec,
                           preferred_element_type=jnp.float32) / scale
            pos = c * Cb + jax.lax.broadcasted_iota(jnp.int32, (1, 1, Cb), 2)
            s = jnp.where(pos <= cur, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.einsum(
                "hns,hsd->hnd", p.astype(vbuf.dtype), vbuf[buf],
                precision=prec, preferred_element_type=jnp.float32)
            return m_new, l, acc

        _m, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((H, R, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, R, 1), jnp.float32),
             jnp.zeros((H, R, dh), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        first_ref[0] = (first + n_chunks) % 2    # the buffer after my last

    qmap = lambda b, *_: (b, 0, 0, 0)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # bt, limit
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, R, dh), qmap),
            pl.BlockSpec(memory_space=pl.ANY),   # pool_k stays in HBM
            pl.BlockSpec(memory_space=pl.ANY),   # pool_v stays in HBM
        ],
        out_specs=pl.BlockSpec((1, H, R, dh), qmap),
        scratch_shapes=[
            pltpu.VMEM((2, H, Cb, dh), q.dtype),
            pltpu.VMEM((2, H, Cb, dh), q.dtype),
            pltpu.SemaphoreType.DMA((2, 2)),     # (K | V, buffer)
            pltpu.SMEM((1,), jnp.int32),         # first chunk's buffer
        ])
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, R, dh), q.dtype),
        grid_spec=gs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # in order: see above
        interpret=interpret,
        name="paged_attn",
    )(bt.astype(jnp.int32), limit.astype(jnp.int32), q, pool_k, pool_v)


# ------------------------------------------------------------------ entry
def paged_attention(q, pool_k, pool_v, bt, limit, layer, *, block,
                    schedule=None, interpret=False):
    """Decode attention for one layer straight off the page pool.

    ``q``: ``(B, H, R, dh)``, ``R`` query rows a K/V head (1 for a
    decoder of one token a slot whose query heads each have K/V of
    their own); ``pool_k``/``pool_v``: ``(P, L, H, block, dh)``;
    ``bt``: ``(B, M)`` page ids; ``limit``: ``(B,)`` absolute positions
    (every row of slot ``b`` attends over ``[0, limit[b]]``: its
    cursor, or the last position of the block it decodes).  Returns
    ``(B, H, R, dh)``.  ``schedule`` picks the lowering (``None`` =
    gather); shapes the Pallas gate rejects fall back to gather even when forced —
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
            q, pool_k, pool_v, bt, limit, layer, block, interp)
    return _gather_attention(q, pool_k, pool_v, bt, limit, layer)


# ------------------------------------------------------------- benchmark
def make_bench_fn(schedule, *, B, H, M, block, dh, L, dtype=jnp.float32):
    """A thunk timing one decode step's attention (all ``L`` layers)
    under ``schedule``, on a synthetic steady-state pool: per-slot
    cursors spread raggedly across the context (mean ~half full — the
    regime a serving mix actually sits in), block tables dense.  Every
    lowering is called a layer at a time, exactly like the serving
    step.  Used by the ``PagedSlots`` tuning call site and
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

    # the arrays are jit ARGUMENTS, not closure captures: captured
    # device values become compile-time constants and XLA folds part of
    # the work into the executable, timing a fiction
    def step(q, pool_k, pool_v, bt, cursor):
        return sum(
            paged_attention(q, pool_k, pool_v, bt, cursor, i,
                            block=block, schedule=schedule)
            for i in range(L))

    jitted = jax.jit(step)
    return lambda: jitted(q, pool_k, pool_v, bt, cursor)
