"""Latent decode attention: read each slot's live page rows where they lie.

Absorbed multi-head latent attention (``models/mla.py``) is multi-query
attention over ONE row a token that is key and value at once: a query
``[q_lat | q_rope]`` of ``W`` numbers a head scores against the row's
first ``W`` numbers, and the weighted sum is taken over its first
``rank`` (the latent).  The rows live in a page pool ``(L, P, block,
Wp)``: a page of ``block`` tokens is ``block`` rows of ``Wp`` =
:func:`page_width` ``(W)`` numbers, ``W`` rounded up to whole 128-wide
lanes and the lanes behind ``W`` zero, so that ``pool[layer, page]`` is
one contiguous, tile-aligned run in the chip's memory (a row of 576
bf16 is 4.5 lane tiles; a page laid out as one ``block * 576`` row of a
``(P, block * 576)`` matrix is strewn over 72 tiles it shares with 7
other pages, and Mosaic refuses to copy it: "must be aligned to tiling
(8)").  Two lowerings behind one schedule-driven entry, the pattern of
``ops/paged_attention.py``:

- **pallas** -- the TPU kernel: one program a slot (grid ``(B,)``), the
  block table and the cursors ride as scalar prefetch.  The program
  walks the pages its slot's cursor has reached, :func:`chunk_pages` of
  them at a time: every page of a chunk is one DMA of ``block x Wp``
  numbers into one of two VMEM buffers, the next chunk's copies --
  behind a slot's last chunk the next slot's first -- fly while this
  chunk's scores, softmax and weighted sum are computed, and a running
  maximum, sum and accumulator in float32 join the chunks.  Neither the
  copies nor the products cover a page past the cursor; positions past
  the cursor inside its page weigh exactly zero.  The arithmetic is
  :func:`dense_attention`'s: operands in the cache dtype, float32
  scores and sums, weights cast to the cache dtype before the value
  product, float32 accumulation; the sums over positions are taken
  chunk by chunk, so against it the kernel is allclose, not bitwise.
  ``interpret=True`` runs the same kernel on the CPU: the parity hook.
- **gather** -- every slot's whole table looked up by ``(layer, page)``
  in the pool as it lies (no slice of one layer first, no fill for
  indices out of bounds: there are none) and :func:`dense_attention`
  over it: the reference the tests hold the kernel to, and what runs
  off the TPU and for shapes :func:`supports` refuses.

Schedules are plain dicts (``{"impl": ...}``) chosen once, when
``PagedSlots`` is bound -- never per tick.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import mxu_precision

__all__ = ["supports", "default_schedule", "page_width", "chunk_pages",
           "dense_attention", "page_table", "latent_attention",
           "make_bench_fn"]

NEG_INF = -1e30
_LANES = 128


def page_width(width: int) -> int:
    """Numbers a row takes in the pool: ``width`` in whole lanes."""
    return -(-int(width) // _LANES) * _LANES


def supports(block: int, width: int, dtype) -> bool:
    """Will Mosaic take the kernel over pages of ``block`` rows of
    ``width`` numbers?  A page is copied as one ``(block, width)`` run
    of tiles into a slice of a VMEM buffer: ``width`` whole lanes (what
    :func:`page_width` gives) and ``block`` whole tiles of 8 rows,
    float32 or bfloat16 (the v5e compiler, asked: "must be aligned to
    tiling (8)")."""
    if jnp.dtype(dtype) not in (jnp.dtype(jnp.float32),
                                jnp.dtype(jnp.bfloat16)):
        return False
    return block > 0 and width > 0 and block % 8 == 0 \
        and width % _LANES == 0


def default_schedule(platform: str, block: int, width: int, dtype) -> dict:
    """The kernel on a TPU whose page shape qualifies, gather
    everywhere else."""
    if platform == "tpu" and supports(block, width, dtype):
        return {"impl": "pallas"}
    return {"impl": "gather"}


# ---------------------------------------------------------------- gather
def page_table(pool, pages, layer, width):
    """``(L, P, block, Wp)[layer, pages (..., M)] -> (..., M * block,
    width)``: the rows of the named pages of one layer, looked up by
    both coordinates in the pool as it lies.  The caller's page ids are
    in bounds (a block table names pool pages and nothing else), so no
    clamp and no fill is staged."""
    rows = pool.at[layer, pages].get(mode="promise_in_bounds")
    shape = pages.shape[:-1] + (pages.shape[-1] * pool.shape[2],
                                pool.shape[3])
    return rows.reshape(shape)[..., :width]


def dense_attention(q, table, valid, rank, denominator):
    """The absorbed form over a contiguous table, the anchor of every
    lowering: ``q`` ``(B, H, W)`` = ``[q_lat (rank) | q_rope]``,
    ``table`` ``(B, S, W)`` the rows ``[latent | rotary key]``,
    ``valid`` ``(B, S)`` which of them exist.  Scores in float32 over
    ``denominator``, an invalid row at exactly zero weight, weights
    cast to the table's dtype before the product with the latents.
    Returns ``(B, H, rank)``."""
    lat, k_rope = table[..., :rank], table[..., rank:]
    s = (jnp.einsum("bhr,bsr->bhs", q[..., :rank], lat,
                    preferred_element_type=jnp.float32)
         + jnp.einsum("bhd,bsd->bhs", q[..., rank:], k_rope,
                      preferred_element_type=jnp.float32)) / denominator
    p = jax.nn.softmax(jnp.where(valid[:, None, :], s, NEG_INF), axis=-1)
    return jnp.einsum("bhs,bsr->bhr", p.astype(lat.dtype), lat)


def _gather_attention(q, pool, bt, cursor, layer, rank, denominator):
    table = page_table(pool, bt, layer, q.shape[-1])
    valid = jnp.arange(table.shape[1])[None, :] <= cursor[:, None]
    return dense_attention(q, table, valid, rank, denominator)


# ---------------------------------------------------------------- pallas
# VMEM the kernel may hold in page buffers (two chunks); the chunk --
# pages in flight -- follows from it and the page's shape, so VMEM never
# grows with the block table
_VMEM_BUDGET = 4 << 20
_MAX_CHUNK_PAGES = 64


def chunk_pages(block: int, width: int, dtype, M: int) -> int:
    """Pages of one slot the kernel keeps in flight: what fits the VMEM
    budget twice over (two buffers), at most the table."""
    page = block * width * jnp.dtype(dtype).itemsize
    return int(max(1, min(M, _MAX_CHUNK_PAGES, _VMEM_BUDGET // (2 * page))))


def _pallas_attention(q, pool, bt, cursor, layer, rank, denominator,
                      interpret, chunk=None):
    B, H, Wp = q.shape
    M = bt.shape[1]
    block = pool.shape[2]
    C = int(chunk or chunk_pages(block, Wp, pool.dtype, M))
    Cb = C * block
    # the value product covers the latent's lanes where they are whole
    # lane tiles, the whole row where they are not (the caller cuts)
    R = rank if rank % _LANES == 0 else Wp
    inv = np.float32(1.0) / np.float32(denominator)

    def kernel(bt_ref, cur_ref, q_ref, pool_ref, o_ref, buf_ref, sem,
               first_ref):
        b = pl.program_id(0)
        cur = cur_ref[b]

        def live_pages(slot):
            # pages holding [0, cursor]: at least one, so every program
            # runs a chunk and the chain of prefetches never breaks
            return jnp.clip(cur_ref[slot] // block + 1, 1, M)

        n_live = live_pages(b)
        n_chunks = (n_live + C - 1) // C

        def copy(slot, c, buf, j):
            # page j of the slot's chunk c: pool[layer, pg] is (block,
            # Wp), contiguous in HBM
            pg = bt_ref[slot, c * C + j]
            rows = pl.ds(pl.multiple_of(j * block, block), block)
            return pltpu.make_async_copy(pool_ref.at[layer, pg],
                                         buf_ref.at[buf, rows],
                                         sem.at[buf])

        def in_chunk(slot, c):
            return jnp.clip(live_pages(slot) - c * C, 0, C)

        def start(slot, c, buf):
            def one(j, carry):
                copy(slot, c, buf, j).start()
                return carry
            jax.lax.fori_loop(0, in_chunk(slot, c), one, 0)

        def wait(c, buf):
            def one(j, carry):
                copy(b, c, buf, j).wait()
                return carry
            jax.lax.fori_loop(0, in_chunk(b, c), one, 0)

        # programs run one after another (one core walks the grid): each
        # starts the NEXT slot's first chunk behind its own last one and
        # leaves in ``first_ref`` which buffer that went to, so only
        # the first slot's first fetch is waited for in the open.  A
        # page past the cursor is never fetched: its buffer rows hold
        # what an earlier chunk left there, finite like the pool, or
        # the zeros written here, so a weight of zero gives zero
        @pl.when(b == 0)
        def _():
            first_ref[0] = 0
            buf_ref[...] = jnp.zeros(buf_ref.shape, buf_ref.dtype)
            start(0, 0, 0)

        first = first_ref[0]
        qv = q_ref[0]                                    # (H, Wp)
        # Mosaic only takes a matmul that accumulates in 32 bits, and a
        # bf16 one only at single-pass precision
        prec = mxu_precision(qv)

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
            rows = buf_ref[buf]                          # (Cb, Wp)
            s = jax.lax.dot_general(
                qv, rows, (((1,), (1,)), ((), ())), precision=prec,
                preferred_element_type=jnp.float32) * inv
            pos = c * Cb + jax.lax.broadcasted_iota(jnp.int32, (1, Cb), 1)
            s = jnp.where(pos <= cur, s, NEG_INF)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(s - m_new)
            l = alpha * l + jnp.sum(p, axis=-1, keepdims=True)
            acc = alpha * acc + jnp.dot(
                p.astype(rows.dtype), rows[:, :R], precision=prec,
                preferred_element_type=jnp.float32)
            return m_new, l, acc

        _m, l, acc = jax.lax.fori_loop(
            0, n_chunks, body,
            (jnp.full((H, 1), NEG_INF, jnp.float32),
             jnp.zeros((H, 1), jnp.float32),
             jnp.zeros((H, R), jnp.float32)))
        o_ref[0] = (acc / l).astype(o_ref.dtype)
        first_ref[0] = (first + n_chunks) % 2    # the buffer after my last

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                   # bt, cursor
        grid=(B,),
        in_specs=[
            pl.BlockSpec((1, H, Wp), lambda b, *_: (b, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((1, H, R), lambda b, *_: (b, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, Cb, Wp), pool.dtype),
            pltpu.SemaphoreType.DMA((2,)),       # one a buffer
            pltpu.SMEM((1,), jnp.int32),         # first chunk's buffer
        ])
    out = pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((B, H, R), pool.dtype),
        grid_spec=gs,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),  # in order: see above
        interpret=interpret,
        name="latent_attn",
    )(bt.astype(jnp.int32), cursor.astype(jnp.int32), q, pool)
    return out[..., :rank]


# ------------------------------------------------------------------ entry
def latent_attention(q, pool, bt, cursor, layer, *, rank, denominator,
                     schedule=None):
    """Absorbed latent attention of one layer straight off the page
    pool.

    ``q``: ``(B, H, W)`` = ``[q_lat (rank) | q_rope]``; ``pool``: ``(L,
    P, block, Wp)`` with ``Wp`` = ``page_width(W)``, lanes behind ``W``
    zero; ``bt``: ``(B, M)`` page ids; ``cursor``: ``(B,)``: slot ``b``
    attends over positions ``[0, cursor[b]]``; ``denominator``: a host
    float the scores are divided by.  Returns ``(B, H, rank)`` in the
    pool's dtype.  ``schedule`` picks the lowering (``None`` = gather);
    a shape the gate rejects takes gather even when forced, except
    interpreted (no Mosaic in that).  ``schedule["chunk"]`` overrides
    the pages in flight: tests walk a table of six pages in several
    chunks with it, the micro-benchmark chose :func:`chunk_pages`'
    cap."""
    sched = schedule or {"impl": "gather"}
    block, Wp = pool.shape[2], pool.shape[3]
    if sched.get("impl") == "pallas" and (
            sched.get("interpret") or supports(block, Wp, pool.dtype)):
        qp = jnp.pad(q.astype(pool.dtype),
                     ((0, 0), (0, 0), (0, Wp - q.shape[-1])))
        return _pallas_attention(
            qp, pool, bt, cursor, layer, rank, denominator,
            bool(sched.get("interpret", False)), sched.get("chunk"))
    return _gather_attention(q, pool, bt, cursor, layer, rank, denominator)


# ------------------------------------------------------------- benchmark
def make_bench_fn(schedule, *, B, H, M, block, width, rank, L,
                  dtype=jnp.bfloat16, cursors=None, pages=None):
    """A thunk timing one decode step's latent attention (all ``L``
    layers) under ``schedule``, without the pool write, on a synthetic
    pool of ``pages`` pages (``B * M + 1`` where None): block tables
    dense, drawn without repetition; ``cursors`` ``(B,)`` where given,
    else spread raggedly across the context."""
    Wp = page_width(width)
    P = int(pages or B * M + 1)
    rs = np.random.RandomState(0)
    name = jnp.dtype(dtype).name
    # made on the device a layer at a time: the pool of a served model
    # is gigabytes, a host copy of it would be too
    key = jax.random.PRNGKey(0)
    pool = jnp.stack([
        jnp.pad(jax.random.normal(jax.random.fold_in(key, i),
                                  (P, block, width), jnp.float32)
                .astype(name), ((0, 0), (0, 0), (0, Wp - width)))
        for i in range(L)])
    q = jnp.asarray(rs.normal(size=(B, H, width)).astype(name))
    bt = jnp.asarray(rs.permutation(np.arange(1, P))[:B * M]
                     .reshape(B, M).astype(np.int32))
    if cursors is None:
        cursors = np.linspace(block, M * block - 1, B)
    cursor = jnp.asarray(np.asarray(cursors).astype(np.int32))
    denominator = float(np.sqrt(width))

    # the arrays are jit ARGUMENTS, not closure captures (captured
    # device values become compile-time constants)
    def step(q, pool, bt, cursor):
        return sum(
            latent_attention(q, pool, bt, cursor, i, rank=rank,
                             denominator=denominator, schedule=schedule)
            for i in range(L))

    jitted = jax.jit(step)
    return lambda: jitted(q, pool, bt, cursor)
