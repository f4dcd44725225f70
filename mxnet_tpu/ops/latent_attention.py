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

A prefill's tail attends in the EXPANDED form (``models/mla.py:
mla_expanded``): keys and values ``lat @ W_kvb[h]`` a head, over
``[history | own rows]``.  The same page rows' schedule picks its
lowering too: :func:`latent_prefill`, the Pallas kernel
``latent_prefill``, reads the slot's pages where they lie (the tail's
own pages are written before it runs, so one walk covers history and
tail) and expands each block of keys in VMEM; under gather the decoder
looks the slot's rows up (:func:`page_table`) and runs its XLA form,
the reference the kernel is held to.  What the cache view hands the
decoder for either is a :class:`PrefillHistory`.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import mxu_precision

__all__ = ["supports", "default_schedule", "page_width", "chunk_pages",
           "dense_attention", "page_table", "latent_attention",
           "make_bench_fn", "PrefillHistory", "prefill_supports",
           "prefill_on_kernel", "prefill_tiles", "latent_prefill"]

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


# --------------------------------------------------------------- prefill
@dataclasses.dataclass(frozen=True)
class PrefillHistory:
    """What a prefill's tail stands behind: the page rows ``pool`` ``(L,
    P, block, Wp)`` with the tail's own pages written already, the
    slot's block-table row ``bt_row`` ``(M,)``, the ``layer``, the row
    ``width`` (``W`` of the ``Wp`` lanes), ``hist`` positions before the
    tail and the tail's ``length`` real tokens (traced scalars), and the
    page rows' ``schedule``.  It unpacks as ``(table, hist)``, the
    gather lowering's history: the slot's rows of the layer, ``(M *
    block, W)``, looked up by ``(layer, page)``, and ``hist``."""
    pool: Any
    bt_row: Any
    layer: int
    width: int
    hist: Any
    length: Any
    schedule: Any = None

    def __iter__(self):
        table = page_table(self.pool, self.bt_row, self.layer, self.width)
        return iter((table, self.hist))


def prefill_supports(block, width, rank, nope, v_dim, dtype) -> bool:
    """The step kernel's gate on the page, and the latent, the key's
    nope part and the value in whole lanes: the kernel slices the rows
    and the expanded keys at those widths."""
    return supports(block, width, dtype) and all(
        n % _LANES == 0 for n in (rank, nope, v_dim))


def prefill_on_kernel(history, rank, nope, v_dim) -> bool:
    """Does ``history``'s schedule put this prefill on the kernel?  As
    for the step: the kernel where the schedule asks for it and the gate
    passes, always when interpreted (no Mosaic in that); never for a
    plain ``(table, hist)``."""
    sched = getattr(history, "schedule", None) or {}
    if sched.get("impl") != "pallas":
        return False
    _L, _P, block, width = history.pool.shape
    return bool(sched.get("interpret")) or prefill_supports(
        block, width, rank, nope, v_dim, history.pool.dtype)


# a chunk's queries whole in one tile (each key block is then expanded
# once a head), cut into sub-tiles of at most _PREFILL_SUB rows for the
# scores; keys _PREFILL_KEYS at a time; as many heads a program as the
# VMEM budget takes (the pages of a key block are copied once a group)
_PREFILL_QUERY = 2048
_PREFILL_SUB = 512
_PREFILL_KEYS = 512
_PREFILL_HEADS = (16, 8, 4, 2, 1)
_PREFILL_VMEM = 48 << 20


def _prefill_vmem(tq, sub, kb, heads, dq, dkv, rank, v_dim, width, itemsize):
    """What the kernel asks of VMEM: queries, ``W_kvb``'s rows and
    outputs of a head group twice (the pipeline's two buffers), two
    buffers of pages, the float32 running maximum, sum (a lane tile a
    row) and accumulator, and the temporaries of a key block's
    expansion and of one sub-tile's scores."""
    io = 2 * heads * (tq * page_width(dq) + dkv * page_width(rank)
                      + tq * page_width(v_dim)) * itemsize
    pages = 2 * kb * width * itemsize
    scratch = heads * tq * (2 * _LANES + page_width(v_dim)) * 4
    temps = kb * page_width(dkv) * (4 + itemsize) + sub * kb * 12 \
        + sub * page_width(dq) * itemsize
    return io + pages + scratch + temps


def prefill_tiles(T, H, block, width, rank, nope, v_dim, dtype, M):
    """``{"query", "sub", "keys", "heads"}`` for ``T`` queries of ``H``
    heads over a table of ``M`` pages: the query tile holds the tail
    whole up to :data:`_PREFILL_QUERY`, its sub-tiles are the largest of
    512, 256, 128 that divides it, keys go :data:`_PREFILL_KEYS` at a
    time (the table whole where it is shorter), and the head group is
    the largest that fits the VMEM budget."""
    itemsize = jnp.dtype(dtype).itemsize
    tq = min(-(-int(T) // 16) * 16, _PREFILL_QUERY)
    sub = next((s for s in (_PREFILL_SUB, 256, 128) if tq % s == 0), tq)
    kb = min(max(_PREFILL_KEYS // block, 1) * block, M * block)
    dq, dkv = nope + width - rank, nope + v_dim
    heads = next((g for g in _PREFILL_HEADS if H % g == 0 and _prefill_vmem(
        tq, sub, kb, g, dq, dkv, rank, v_dim, width, itemsize)
        <= _PREFILL_VMEM), 1)
    return {"query": tq, "sub": sub, "keys": kb, "heads": heads}


def _pallas_prefill(q, w, pool, bt_row, scalars, *, nope, inv, tq, sub, kb,
                    heads, interpret):
    """``q`` ``(H, Tp, Dq)`` = ``[q_nope | q_rope, zeros]`` a head and
    query, ``w`` ``(H, nope + v, rank)``, ``pool`` ``(L, P, block, Wp)``,
    ``bt_row`` ``(M,)``, ``scalars`` ``[hist, length, layer]``.  Returns
    ``(H, Tp, v)`` in the pool's dtype; rows of pad queries alone zero."""
    H, Tp, Dq = q.shape
    _H, Dkv, rank = w.shape
    v_dim = Dkv - nope
    _L, _P, block, Wp = pool.shape
    M = bt_row.shape[0]
    G, dt = heads, pool.dtype
    n_groups, n_tiles, n_blocks = H // G, Tp // tq, pl.cdiv(M * block, kb)
    ppb, n_sub = kb // block, tq // sub
    nt = (((1,), (1,)), ((), ()))

    def dot_nt(a, b):
        return jax.lax.dot_general(a, b, nt, precision=mxu_precision(a, b),
                                   preferred_element_type=jnp.float32)

    def kernel(bt_ref, sc_ref, q_ref, w_ref, pool_ref, o_ref, buf_ref, sem,
               m_ref, l_ref, acc_ref, cur_ref):
        g, i, j = pl.program_id(0), pl.program_id(1), pl.program_id(2)
        hist, length, layer = sc_ref[0], sc_ref[1], sc_ref[2]

        def last_key(tile):
            # the last position the tile's real queries attend to
            return hist + jnp.minimum((tile + 1) * tq, length) - 1

        def live_blocks(tile):
            # key blocks up to that position; none for pad queries alone
            return jnp.where(tile * tq < length, last_key(tile) // kb + 1, 0)

        def in_block(tile, blk):
            # pages of the key block that hold a position the tile reads
            return jnp.clip(last_key(tile) // block + 1 - blk * ppb, 0, ppb)

        def copy(blk, buf, p):
            # pool[layer, page] is (block, Wp), contiguous in HBM
            rows = pl.ds(pl.multiple_of(p * block, block), block)
            return pltpu.make_async_copy(
                pool_ref.at[layer, bt_ref[blk * ppb + p]],
                buf_ref.at[buf, rows], sem.at[buf])

        def each_page(tile, blk, buf, act):
            def one(p, carry):
                act(copy(blk, buf, p))
                return carry
            jax.lax.fori_loop(0, in_block(tile, blk), one, 0)

        # the key blocks are copied in grid order, each while the live
        # block before it is computed; ``cur_ref`` says which buffer the
        # next live block went to.  A page the walk does not reach is
        # never copied: its buffer rows hold an earlier block's rows,
        # finite like the pool, or these zeros, and are masked out
        @pl.when((g == 0) & (i == 0) & (j == 0))
        def _():
            buf_ref[...] = jnp.zeros(buf_ref.shape, buf_ref.dtype)
            cur_ref[0] = 0
            each_page(0, 0, 0, lambda c: c.start())

        @pl.when(j == 0)
        def _():
            m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
            l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
            acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

        n_live = live_blocks(i)

        @pl.when(j < n_live)
        def _():
            buf = cur_ref[0]
            # the next live block: this tile's next, the next tile's
            # first, or the next head group's first
            more = j + 1 < n_live
            tile_on = (i + 1 < n_tiles) & (live_blocks(i + 1) > 0)

            @pl.when(more | tile_on | (g + 1 < n_groups))
            def _():
                nxt_i = jnp.where(more, i, jnp.where(tile_on, i + 1, 0))
                each_page(nxt_i, jnp.where(more, j + 1, 0), 1 - buf,
                          lambda c: c.start())

            each_page(i, j, buf, lambda c: c.wait())
            rows = buf_ref[buf]                          # (kb, Wp)
            lat, k_rope = rows[:, :rank], rows[:, rank:]
            k0 = j * kb

            def expand(h):
                # the block's keys and values of head h, rounded to the
                # cache dtype as the XLA form's expansion rounds them
                kv = dot_nt(lat, w_ref[h]).astype(dt)    # (kb, nope + v)
                return kv[:, :nope], kv[:, nope:]

            def attend(h, k_nope, v, r0, masked):
                rs = pl.ds(r0, sub)
                qh = q_ref[h, rs, :]
                s = (dot_nt(qh[:, :nope], k_nope)
                     + dot_nt(qh[:, nope:], k_rope)) * inv
                if masked:
                    # key <= query's position, and no pad key
                    t = i * tq + r0 + jax.lax.broadcasted_iota(
                        jnp.int32, s.shape, 0)
                    pos = k0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
                    s = jnp.where(pos <= hist + jnp.minimum(t, length - 1), s,
                                  NEG_INF)
                m = m_ref[h, rs, :]
                m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
                alpha = jnp.exp(m - m_new)
                p = jnp.exp(s - m_new)
                l_new = alpha * l_ref[h, rs, :] + jnp.sum(p, axis=-1,
                                                          keepdims=True)
                acc_new = alpha * acc_ref[h, rs, :] + jnp.dot(
                    p.astype(dt), v, precision=mxu_precision(v),
                    preferred_element_type=jnp.float32)
                m_ref[h, rs, :] = m_new
                l_ref[h, rs, :] = l_new
                acc_ref[h, rs, :] = acc_new

            def head(h, carry):
                k_nope, v = expand(h)

                def sub_tile(r, carry):
                    r0 = pl.multiple_of(r * sub, sub)
                    t0 = i * tq + r0
                    last = hist + jnp.minimum(t0 + sub, length) - 1
                    # a sub-tile of pad queries alone reads nothing; one
                    # whose first query stands at or past the block's
                    # last key needs no mask
                    needed = (t0 < length) & (k0 <= last)
                    plain = k0 + kb - 1 <= hist + t0

                    @pl.when(needed & plain)
                    def _():
                        attend(h, k_nope, v, r0, False)

                    @pl.when(needed & jnp.logical_not(plain))
                    def _():
                        attend(h, k_nope, v, r0, True)

                    return carry

                jax.lax.fori_loop(0, n_sub, sub_tile, 0)
                return carry

            def head_below(h, carry):
                # every sub-tile takes the block unmasked, in straight-line
                # code
                k_nope, v = expand(h)
                for r in range(n_sub):
                    attend(h, k_nope, v, r * sub, False)
                return carry

            # a block wholly below the diagonal of a tile of real queries
            # alone, or the walk sub-tile by sub-tile
            below = (k0 + kb - 1 <= hist + i * tq) & ((i + 1) * tq <= length)

            @pl.when(below)
            def _():
                jax.lax.fori_loop(0, G, head_below, 0)

            @pl.when(jnp.logical_not(below))
            def _():
                jax.lax.fori_loop(0, G, head, 0)

            cur_ref[0] = 1 - buf

        @pl.when(j == jnp.maximum(n_live, 1) - 1)
        def _():
            l = l_ref[...]
            o_ref[...] = (acc_ref[...] / jnp.where(l > 0, l, 1.0)).astype(
                o_ref.dtype)

    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,         # bt_row, [hist, length, layer]
        grid=(n_groups, n_tiles, n_blocks),
        in_specs=[
            pl.BlockSpec((G, tq, Dq), lambda g, i, j, *_: (g, i, 0)),
            pl.BlockSpec((G, Dkv, rank), lambda g, i, j, *_: (g, 0, 0)),
            pl.BlockSpec(memory_space=pl.ANY),   # the pool stays in HBM
        ],
        out_specs=pl.BlockSpec((G, tq, v_dim), lambda g, i, j, *_: (g, i, 0)),
        scratch_shapes=[
            pltpu.VMEM((2, kb, Wp), dt),
            pltpu.SemaphoreType.DMA((2,)),       # one a buffer
            pltpu.VMEM((G, tq, 1), jnp.float32),     # running maximum
            pltpu.VMEM((G, tq, 1), jnp.float32),     # running sum
            pltpu.VMEM((G, tq, v_dim), jnp.float32),  # accumulator
            pltpu.SMEM((1,), jnp.int32),         # the next live block's buffer
        ])
    vmem = _prefill_vmem(tq, sub, kb, G, Dq, Dkv, rank, v_dim, Wp,
                         jnp.dtype(dt).itemsize)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((H, Tp, v_dim), dt),
        grid_spec=gs,
        compiler_params=pltpu.CompilerParams(
            # in order: the copies run ahead along the grid
            dimension_semantics=("arbitrary",) * 3,
            vmem_limit_bytes=int(vmem + (16 << 20))),
        interpret=interpret,
        name="latent_prefill",
    )(bt_row.astype(jnp.int32), scalars, q, w, pool)


def latent_prefill(q_nope, q_rope, w_kvb, history, *, nope, v_dim,
                   denominator, tiles=None):
    """The expanded form of one tail on the kernel: ``q_nope`` ``(T, H,
    nope)``, ``q_rope`` ``(T, H, rope)`` turned already, ``w_kvb`` ``(H
    * (nope + v_dim), rank)``, ``history`` a :class:`PrefillHistory`
    whose pool holds the tail's rows at positions ``hist ..``.  Query
    ``t`` attends over positions ``0 .. hist + min(t, length - 1)`` in
    one softmax: scores in float32 over ``denominator`` (a host float),
    keys and values expanded to the cache dtype, weights rounded to it
    before the value product, float32 sums -- ``mla_expanded``'s
    rounding points, so against its XLA form it is allclose, not
    bitwise.  Key blocks past a query tile's last real position are
    neither copied nor multiplied, and the mask is built only in the
    blocks the diagonal crosses.  Returns ``(T, H * v_dim)`` in the
    pool's dtype; rows of pad queries (``t >= length``) are not the
    caller's to read.  ``tiles`` overrides :func:`prefill_tiles`."""
    pool = history.pool
    _L, _P, block, Wp = pool.shape
    T, H, _ = q_nope.shape
    rank = w_kvb.shape[1]
    dt = pool.dtype
    tl = tiles or prefill_tiles(T, H, block, Wp, rank, nope, v_dim, dt,
                                history.bt_row.shape[0])
    tq = tl["query"]
    Tp = -(-T // tq) * tq
    # a head's query [q_nope | q_rope, zeros] against a row's [latent |
    # rotary key, zeros]: the rotary scores over the row's last lanes
    q = jnp.concatenate(
        [q_nope.astype(dt),
         jnp.pad(q_rope.astype(dt),
                 ((0, 0), (0, 0), (0, Wp - rank - q_rope.shape[-1])))], -1)
    q = jnp.pad(q, ((0, Tp - T), (0, 0), (0, 0))).transpose(1, 0, 2)
    scalars = jnp.stack([jnp.asarray(history.hist, jnp.int32),
                         jnp.asarray(history.length, jnp.int32),
                         jnp.asarray(history.layer, jnp.int32)])
    out = _pallas_prefill(
        q, w_kvb.astype(dt).reshape(H, nope + v_dim, rank), pool,
        history.bt_row, scalars, nope=nope,
        inv=np.float32(1.0) / np.float32(denominator), tq=tq,
        sub=tl["sub"], kb=tl["keys"], heads=tl["heads"],
        interpret=bool((history.schedule or {}).get("interpret", False)))
    return out[:, :T].transpose(1, 0, 2).reshape(T, H * v_dim)


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
