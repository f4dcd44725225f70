"""Paged KV cache: block-table indirection + prompt-prefix reuse.

The PR-6 slot pool reserves one CONTIGUOUS ``(L, H, max_len, dh)`` cache
row per slot — a 4-token health-check request holds the same device
memory as a max_len chat, and two requests sharing a system prompt each
recompute and store identical K/V.  This module replaces the per-slot
row with vLLM-style paging: ONE shared device pool of fixed-size pages
(``MXTPU_KV_BLOCK`` tokens per page), per-slot *block tables* mapping
each slot's logical cache positions onto pool pages, gathered inside
the jitted decode programs, so

- long and short requests co-batch without padding waste (a slot holds
  exactly ``ceil(tokens/block)`` pages, not ``max_len/block``);
- identical prompt prefixes map to the SAME immutable pages: full
  prompt blocks are chain-hashed into a prefix index, admission reuses
  the longest cached chain and prefills only the tail (the shared
  system prompt is computed ONCE — ``serve_prefix_hits_total``);
- copy-on-write at the divergence point is structural: sharing is
  block-aligned and a request's first write lands at its prompt length,
  so the partially-filled divergence block is always per-fork private —
  mutating one fork can never corrupt the shared prefix (pinned by
  tests/test_serving_fleet.py).

Page allocation, refcounts, block tables and the prefix index are pure
HOST-side bookkeeping (``PagedSlots.step`` is a declared
``analysis/config.py:ENTRY_POINTS`` steady-state loop — lint proves it
never touches the device); the device work stays the serving invariant:
one jitted step over all slots per tick, one bucketed prefill per
admission (or per chunk of a long one), zero traces on a warm server
(``executor_compile_total{kind=decode_step_paged|decode_prefill_paged}``).

What a page holds, and what lives beside the pages, is the decoder's
to say (``decoder.paged_layout()``); what its block computes is the
decoder's too.  This module holds no model mathematics: its two
programs (:class:`_CachePrograms`) call the decoder's one ``forward(p,
tokens, view)`` over a *cache view* (:class:`_StepView`,
:class:`_PrefillView`), and the view is where K/V rows, page rows and
per-slot state live and how a query reaches them.  A layout may
declare:

- ``kv_pages``: ``(layers, heads, head_dim, dtype)`` — the pair of
  ``(P, layers, heads, block, head_dim)`` K/V pools
  (``models/decode.py:KVDecoder``, the GPT-2 block).  ``view.attend``
  writes a layer's new rows and attends over what the slot has seen:
  in the step by the schedule's lowering (``ops/paged_attention.py``:
  the Pallas kernel, or gather) over the slot's table; in a prefill
  over this layer's pages of the history and the tail's own K/V as
  they come from the projections, side by side in one softmax, and no
  table is scattered into (``_PrefillView.attend``).  It is the
  contiguous decoder's softmax over the same keys (absolute positions,
  masked-out entries at exact zero weight) with the tail's keys
  standing behind the table and not inside it: logits agree with it to
  the last bits, not bitwise (tests state the tolerance and the gap
  measured);
- ``pages``: name -> ``(layers, row width, dtype)`` — page rows of the
  decoder's own, one ``(layers, P, block, lanes)`` array each
  (``models/ling.py``, ``models/kimi.py``: one latent row a token and
  MLA layer): a page is ``block`` rows of the width in whole 128-wide
  lanes, zero behind the width, so that ``pool[layer, page]`` is one
  contiguous, tile-aligned run on the chip — what a kernel can copy
  and a lookup by ``(layer, page)`` can read in place
  (``ops/latent_attention.py``).  In the step ``view.attend_pages``
  writes each slot's new row at its cursor and attends over the slot's
  live pages by the schedule's lowering (the Pallas kernel, or a lookup
  of the slot's table and the dense form over it); in a prefill
  ``view.append`` writes the tail's rows a page at a time and hands
  back this layer's rows of the slot and ``hist``, how many of them
  stand before the tail.  No program slices one layer out of the pool
  first: that is a copy of the layer (666 MB at the Kimi cell's size,
  2 ms, before ISSUE 34);
- ``state``: name -> ``(shape, dtype)`` — a FIXED-SIZE STATE PER SLOT
  beside the pages, one ``(num_slots, *shape)`` array each (a
  linear-attention layer's recurrent state; ``view.state`` /
  ``view.set_state``).  A prefill starts from a zero state and
  overwrites the slot's row, so a reused slot never sees its
  predecessor;
- ``counters``: names of int32 device counters the forward adds to
  (``view.count``), reported by ``stats()`` under those names;
- ``prefix_reuse``: whether a prefill's tail may stand behind rows the
  slot's pages already hold -- a cached prefix picked up at a block
  boundary, or the earlier chunks of the same prompt.  It would also
  need the state there, which nothing snapshots: a decoder with state
  says ``False``, no page of it is ever shared
  (``stats()["prefix_reuse"]``) and its prompts are one program each.
  This flag is all that is asked: no family and no kind of page is
  named here.

An admission is :meth:`PagedSlots.begin_admit` (prefix lookup, page
allocation) and one or more :meth:`PagedSlots.admit_chunk` (a prefill
program each): a tail that fits the largest bucket is one program, a
longer one goes in chunks of that bucket's whole pages, each behind
``hist`` = the cached prefix and the chunks before it, so that whoever
drives the backend may run steps between two chunks
(``serving/scheduler.py`` does; :meth:`PagedSlots.admit` does not).

Still one block table a slot, whatever is declared, and the whole cache
is donated through every program.

A decoder may decode A BLOCK OF ``n`` TOKENS A SLOT in one step
(``decoder.block_length``; 1 where it says nothing, and then every
program is what it was): the step takes ``(B, n)`` tokens at positions
``cursor .. cursor + n - 1``, writes the block's ``n`` K/V rows a slot
and layer as one ``dynamic_update_slice`` and lets every query of the
block attend over ``[0, cursor + n)``, its own block included
(``models/sdar.py``: a block under denoising).  A cursor then stands
on a block boundary always -- an admission prefills the prompt's whole
blocks, its remainder rides in the first decoded block -- and ``n``
divides the page, so a block never straddles two pages.  Such a step
moves no cursor by itself: the rows it wrote are provisional, the next
step of the same block overwrites them, and only a slot the caller
names in ``commit`` advances, by ``n`` (:meth:`PagedSlots.step`).  A
prefill's tail mask is block-causal with the same ``n``.

The K/V pools' layout is the kernel's.  Each is one ``(P, L, H, block,
dh)`` array that the Mosaic kernel reads row-major (``{4,3,2,1,0}``).
A program that writes it in a way the TPU compiler would rather lay out
otherwise — a scatter of rows gets ``{4,2,3,1,0}`` — makes XLA copy the
WHOLE pool into that layout and back before every layer's kernel (2 +
2 L copies of 1.6 GB a tick at the 1.3B width, 70% of the device's time
before ISSUE 26).  So there are two ways into the pools,
``_StepView._write_rows`` (one ``dynamic_update_slice`` a slot) and
``_PrefillView._write_pages`` (whole pages), both in place in that
layout, and the programs take the cache's buffers over (``donate``):
``PagedSlots`` holds the only reference and replaces it with each
call's outputs.  Any new program that touches the pools goes into
``tests/test_tpu_compile.py``'s compiled-program test, which fails on a
pool-shaped copy; nothing on the CPU shows one.
"""
from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict

import numpy as np

from .. import telemetry as _tm
from ..base import MXNetError
from ..telemetry import tracing as _tracing

__all__ = ["PagedSlots", "PoolExhausted", "kv_block", "prefix_cache_on",
           "paged_kernel_mode"]

# --- paged serving metric families (docs/telemetry.md) ----------------------
_TM_PREFIX_HITS = _tm.counter(
    "serve_prefix_hits_total",
    "prompt blocks served from the prefix cache instead of being "
    "prefilled (each hit skips one MXTPU_KV_BLOCK-token block of "
    "prefill compute)")
_TM_PAGES = _tm.gauge(
    "serve_kv_pages",
    "KV-cache page pool occupancy: total usable pages, currently free "
    "pages, and pages pinned by the prompt-prefix cache",
    labels=("state",))
_TM_STATE_SLOTS = _tm.gauge(
    "serve_state_slots",
    "slots whose per-slot recurrent state is in use (decoders that "
    "declare state beside their pages: linear-attention layers)")
_TM_LATENT_PAGES = _tm.gauge(
    "serve_latent_pages",
    "pages in use of a decoder that declares its own page rows (one "
    "latent row a token and MLA layer)")
_TM_COUNTED = _tm.gauge(
    "serve_decoder_counted",
    "what the served decoder's programs have counted on the device "
    "since start, under the names the decoder declares "
    "(paged_layout()[\"counters\"]), as of the last stats() read",
    labels=("name",))


class PoolExhausted(MXNetError):
    """No free KV page and nothing evictable — the pool is fully pinned
    by live requests (size the pool, or shed load upstream)."""


def kv_block() -> int:
    """``MXTPU_KV_BLOCK`` — tokens per KV page; 0/unset keeps the PR-6
    contiguous slot cache."""
    try:
        return max(int(os.environ.get("MXTPU_KV_BLOCK", "0") or 0), 0)
    except ValueError:
        return 0


def prefix_cache_on() -> bool:
    """``MXTPU_PREFIX_CACHE`` — prompt-prefix page reuse (default on
    whenever paging is on)."""
    return os.environ.get("MXTPU_PREFIX_CACHE", "1").lower() \
        not in ("0", "false", "off")


def paged_kernel_mode() -> str:
    """``MXTPU_PAGED_KERNEL`` — the step-attention lowering (ISSUE 18).

    ``auto`` (default, also ``1``): consult the autotuner — with a
    schedule cache, the tuned winner; without one, the Pallas kernel on
    a TPU whose shape qualifies and the PR-15 gather path everywhere
    else.  ``0``/``off``/``gather``: pin the gather path (bit-identical
    to PR 15).  ``pallas`` / ``interpret``: force the kernel of
    ``ops/paged_attention.py`` (``interpret`` is the CPU-parity
    hook).  The step's attention over page rows of a decoder's own
    (``ops/latent_attention.py``) follows the same mode:
    :meth:`PagedSlots._resolve_page_schedule`."""
    raw = os.environ.get("MXTPU_PAGED_KERNEL", "auto").strip().lower()
    if raw in ("", "1", "auto"):
        return "auto"
    if raw in ("0", "off", "false", "gather"):
        return "gather"
    return raw


# the cache pytree among a program's arguments after the weights: it is
# donated, each program's output is the cache from then on
_CACHE_ARG = (0,)


class _CacheView:
    """What ``decoder.forward`` sees of the cache: the K/V pools, the
    page rows and the per-slot state it declared, the positions of its
    tokens and which of them are real.  ``step`` tells the decode step's
    view from a prefill's, whose ``length`` counts its real tokens."""
    length = None

    def __init__(self, programs, cache, positions, valid):
        self._pg = programs
        self.kv = tuple(cache["kv"])
        self.pages = dict(cache["pages"])
        self._state = dict(cache["state"])
        self.positions, self.valid = positions, valid
        self.counts = 0

    def count(self, counts):
        self.counts = self.counts + counts

    def cache(self):
        return {"kv": self.kv, "pages": self.pages, "state": self._state}

    def embed(self, tok, table):
        """The hidden state under a learned positional table ``(1,
        max_len, D)``: the token rows ``tok`` (``(B, D)`` in the step,
        ``(1, T, D)`` in a prefill) plus the table's at the view's
        positions (a free row's and a pad token's clipped into it), as
        ``(B, n, D)``: one position a slot, or one sequence."""
        import jax.numpy as jnp

        last = self._pg.max_blocks * self._pg.block - 1
        rows = jnp.take(table[0], jnp.clip(self.positions, 0, last), axis=0)
        return (tok + rows)[:, None] if self.step else tok + rows[None]


class _StepView(_CacheView):
    """``n`` tokens a slot from its cursor on (``n`` = the decoder's
    ``block_length``, 1 for most; page ``bt[b, cursor // block]``,
    offset ``cursor % block``): state rows are the slots themselves;
    ``n`` K/V rows or a page row a slot are written at its cursor and
    the slot attends over ``[0, cursor + n)`` of its table.  With ``n``
    > 1 ``positions`` and ``valid`` are flat, ``(B n,)``, a slot's block
    side by side.  Free rows ride along with ``bt[b] = 0`` / ``cursor =
    0``: their writes land in the scratch page the allocator never
    hands out."""
    step = True

    def __init__(self, programs, cache, bt, cursor, occupied):
        import jax.numpy as jnp

        n = programs.block_n
        if n == 1:
            super().__init__(programs, cache, cursor, occupied)
            self._limit = cursor
        else:
            super().__init__(
                programs, cache,
                (cursor[:, None] + jnp.arange(n)).reshape(-1),
                jnp.repeat(occupied, n))
            self._limit = cursor + (n - 1)
        self._bt, self._cursor = bt, cursor
        # where each slot's row goes, as scalar pairs made once a
        # program: a step's trace holds ``2 L B`` row writes
        pages = jnp.take_along_axis(
            bt, (cursor // programs.block)[:, None], axis=1)[:, 0]   # (B,)
        offs = cursor % programs.block
        self._at = [(pages[b], offs[b]) for b in range(bt.shape[0])]

    def state(self, name):
        return self._state[name]

    def set_state(self, name, value):
        self._state[name] = value.astype(self._state[name].dtype)

    def _write_rows(self, pool, new, layer):
        """The K or V rows of a slot: ``new[b]`` ``(H, n, dh)`` lands at
        ``pool[page, layer, :, off:off + n]``, as one
        ``dynamic_update_slice`` a slot (a scatter of rows, or a ``fori_loop`` over the slots, is
        laid out otherwise by the TPU compiler: module docstring).  The
        indices, never negative, skip the wrap-around ``lax`` would
        stage for each."""
        import jax

        new = new[:, None]                           # (B, 1, H, n, dh)
        for b, (page, off) in enumerate(self._at):
            pool = jax.lax.dynamic_update_slice(
                pool, jax.lax.slice_in_dim(new, b, b + 1),
                (page, layer, 0, off, 0), allow_negative_indices=False)
        return pool

    def attend(self, layer, q, k, v):
        """``k, v`` ``(B, H, n, dh)``, ``q`` ``(B, H, R, dh)`` (``R`` =
        ``n`` x the query heads a K/V head): the new rows go into the
        K/V pools, then the schedule's lowering (the Pallas kernel, or
        gather) walks each slot's block table over the pools just
        written, up to the block's last position, in the named scope
        ``attn.pages``.  Returns ``(B, H, R, dh)``."""
        import jax

        from ..ops import paged_attention as _pa

        pool_k, pool_v = self.kv = tuple(
            self._write_rows(pool, new, layer)
            for pool, new in zip(self.kv, (k, v)))
        with jax.named_scope("attn.pages"):
            return _pa.paged_attention(
                q, pool_k, pool_v, self._bt, self._limit, layer,
                block=self._pg.block, schedule=self._pg.schedule)

    def attend_pages(self, name, layer, rows, q, *, rank, denominator):
        """The page rows' counterpart of :meth:`attend`: ``rows`` (B, W)
        land at each slot's cursor in page rows ``name`` of ``layer``;
        then ``q`` (B, H, W) attends over the slot's pages just
        written, positions ``[0, cursor]``: scores against a row's
        ``W`` numbers over ``denominator``, the weighted sum over its
        first ``rank`` (``ops/latent_attention.py``: the schedule's
        lowering reads the pool where it lies, the kernel the live
        pages alone).  Returns ``(B, H, rank)``."""
        import jax
        import jax.numpy as jnp

        from ..ops import latent_attention as _la

        pool = self.pages[name]
        rows = jnp.pad(rows.astype(pool.dtype),
                       ((0, 0), (0, pool.shape[-1] - rows.shape[-1])))
        rows = rows[None, :, None]                       # (1, B, 1, lanes)
        # one dynamic_update_slice a slot, as the K/V pools' row writes
        for b, (page, off) in enumerate(self._at):
            pool = jax.lax.dynamic_update_slice(
                pool, rows[:, b:b + 1], (layer, page, off, 0),
                allow_negative_indices=False)
        self.pages[name] = pool
        return _la.latent_attention(
            q, pool, self._bt, self._cursor, layer, rank=rank,
            denominator=denominator,
            schedule=self._pg.page_schedules[name])


class _PrefillView(_CacheView):
    """One sequence into slot ``slot``, right-padded: its ``length``
    real tokens stand at positions ``hist .. hist + length - 1`` behind
    ``hist`` tokens of shared pages (0 for a decoder that reuses no
    prefix).  ``hist`` is a whole number of pages (only full blocks are
    shared), so the tail starts on a page boundary and goes into the
    cache a PAGE at a time: every page that holds a real token is
    written whole, the pages of pad tokens alone aim out of bounds and
    are dropped.  The rows of the last, partial page beyond the prompt
    therefore hold the pad tokens': finite values at positions ``>
    cursor``, which the step masks to exact zero weight and which the
    decode write at each position replaces before the mask reaches it;
    only full blocks are promoted to the prefix index, so no shared
    page ever holds one.  The prefill never reads back what it wrote:
    a K/V query attends over the tail's own rows and, of the pool, one
    layer's pages of the history (:meth:`attend`); the all-layer table
    a prefill scattered its rows into until ISSUE 30 was copied whole
    twice a layer by the TPU compiler, as the pool had been.
    The state starts at zero and the state after the last real token
    overwrites the slot's row.  ``slot``, ``hist`` and ``length`` ride
    as traced scalars, so the program count is one per padded bucket
    length."""
    step = False

    def __init__(self, programs, cache, bt_row, slot, tokens, hist, length):
        import jax.numpy as jnp

        j = jnp.arange(tokens.shape[0])
        super().__init__(programs, cache, hist + j, j < length)
        self._bt_row, self._slot, self.length = bt_row, slot, length
        # the pool pages of the tail's pages; out of bounds (a dropped
        # write) for a page without a real token
        n = jnp.arange(-(-tokens.shape[0] // programs.block))
        self._page_ids = jnp.where(
            n * programs.block < length,
            bt_row[jnp.clip(hist // programs.block + n, 0,
                            programs.max_blocks - 1)],
            programs.num_pages)
        self._hist = hist

    def state(self, name):
        import jax.numpy as jnp

        s = self._state[name]
        return jnp.zeros(s.shape[1:], s.dtype)

    def set_state(self, name, value):
        import jax

        s = self._state[name]
        self._state[name] = jax.lax.dynamic_update_slice(
            s, value.astype(s.dtype)[None],
            (self._slot,) + (0,) * (s.ndim - 1),
            allow_negative_indices=False)

    def _write_pages(self, pool, new, layer):
        """Whole K or V pages: ``new`` ``(T, H, dh)`` holds the tail's
        positions from its page boundary on, page ``n`` of it lands at
        ``pool[page_ids[n], layer]``."""
        import jax.numpy as jnp

        block = self._pg.block
        T, H, dh = new.shape
        new = jnp.pad(new, ((0, -T % block), (0, 0), (0, 0)))
        vals = new.reshape(-1, block, H, dh).transpose(0, 2, 1, 3)
        return pool.at[self._page_ids, layer].set(vals, mode="drop")

    def attend(self, layer, q, k, v):
        """``k, v`` ``(1, H, T, dh)``, ``q`` ``(1, H, G T, dh)`` (``G``
        query heads a K/V head, a head's ``T`` rows side by side; 1 for
        most): the tail's pages go into the K/V pools, and the query
        attends over this layer's pages of the slot, gathered from the
        pool and masked to the history (positions ``< hist``), and
        behind them the tail's own ``k, v`` as they come from the
        projections (a causal mask is all a real query needs: pad keys
        stand behind it; block-causal for a decoder of ``n`` tokens a
        step, key ``j`` seen by query ``i`` iff ``j // n <= i // n``):
        the two blocks side by side on the key axis, one softmax.
        Returns ``q``'s shape."""
        import jax.numpy as jnp

        from ..ops import paged_attention as _pa

        self.kv = tuple(
            self._write_pages(pool, a[0].transpose(1, 0, 2), layer)
            for pool, a in zip(self.kv, (k, v)))
        hk, hv = (_pa.layer_table(pool, self._bt_row[None], layer)
                  for pool in self.kv)               # (1, H, S, dh)
        S, T = hk.shape[2], k.shape[2]
        history = jnp.broadcast_to(jnp.arange(S) < self._hist, (T, S))
        if self._pg.block_n > 1:        # block-causal
            at = jnp.arange(T) // self._pg.block_n
            tail = at[None, :] <= at[:, None]
        else:
            tail = jnp.arange(T)[None, :] <= jnp.arange(T)[:, None]
        seen = jnp.concatenate([history, tail], axis=1)
        if q.shape[2] != T:
            seen = jnp.tile(seen, (q.shape[2] // T, 1))
        return _pa.dense_attention(q, jnp.concatenate([hk, k], axis=2),
                                   jnp.concatenate([hv, v], axis=2), seen)

    def append(self, name, layer, rows):
        """``rows`` (T, W): the tail's page rows, written whole pages at
        a time like the K/V pools'.  Returns what stands before the
        tail: ``(table (S, W), hist)``, this layer's rows of the slot
        looked up in the pool by ``(layer, page)`` (whole pages; never
        a slice of the layer first) and how many of them, from position
        0 on, are the history; the rest of the table is not the
        caller's to read.  ``None`` for a layout that can have no
        history (``prefix_reuse`` False)."""
        import jax.numpy as jnp

        from ..ops import latent_attention as _la

        pg = self._pg
        pool = self.pages[name]
        T, W = rows.shape
        rows = jnp.pad(rows.astype(pool.dtype),
                       ((0, -T % pg.block), (0, pool.shape[-1] - W)))
        self.pages[name] = pool = pool.at[layer, self._page_ids].set(
            rows.reshape(-1, pg.block, pool.shape[-1]), mode="drop")
        if not pg.layout["prefix_reuse"]:
            return None
        return _la.page_table(pool, self._bt_row, layer, W), self._hist


class _CachePrograms:
    """The two jitted programs of a served decoder,
    ``jit_decode_step_<family>`` and ``jit_prefill_<family>_b<bucket>``.
    Both call ``decoder.forward`` over a cache view and nothing else;
    the cache the decoder declared (``decoder.paged_layout()``), one
    pytree ``{"kv": (pool_k, pool_v) or (), "pages": {...}, "state":
    {...}}``, is donated to each call, and the counters it declared
    (one int32 a name, never donated: ``stats()`` reads them from
    another thread) go in and come out beside it."""

    step_view, prefill_view = _StepView, _PrefillView

    def __init__(self, decoder, layout, block, max_blocks, num_pages,
                 num_slots, schedule=None, page_schedules=None):
        from ..models.decode import _WeightProgram, _count_compiles

        self.dec, self.layout = decoder, layout
        # tokens a slot a step (a block decoder's block; else 1)
        self.block_n = int(getattr(decoder, "block_length", 1))
        self.block, self.max_blocks = int(block), int(max_blocks)
        self.num_pages, self.num_slots = int(num_pages), int(num_slots)
        # the K/V step's attention schedule (ops/paged_attention.py);
        # None is gather
        self.schedule = schedule
        # the step's attention over page rows, a schedule a name
        # (ops/latent_attention.py); gather where none is given
        self.page_schedules = {
            name: (page_schedules or {}).get(name)
            for name in layout["pages"]}
        self._WeightProgram, self._count = _WeightProgram, _count_compiles
        self._step_jit = _WeightProgram(
            decoder, _count_compiles(self._step_program,
                                     "decode_step_paged"),
            f"decode_step_{decoder.family}", donate=_CACHE_ARG)
        self._prefill_cache = {}

    def pool_structs(self):
        """Shapes of the cache pytree, as the 1-tuple ``PagedSlots``
        keeps in ``pool``: what a program is lowered with when nothing
        may take the cache's buffers."""
        import jax

        from ..ops import latent_attention as _la

        kv = ()
        if "kv_pages" in self.layout:
            layers, heads, dh, dtype = self.layout["kv_pages"]
            kv = (jax.ShapeDtypeStruct(
                (self.num_pages, layers, heads, self.block, dh), dtype),) * 2
        pages = {n: jax.ShapeDtypeStruct(
            (layers, self.num_pages, self.block, _la.page_width(width)),
            dtype)
            for n, (layers, width, dtype) in self.layout["pages"].items()}
        state = {n: jax.ShapeDtypeStruct((self.num_slots,) + tuple(shape),
                                         dtype)
                 for n, (shape, dtype) in self.layout["state"].items()}
        return ({"kv": kv, "pages": pages, "state": state},)

    def init_pool(self):
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.pool_structs())

    def _step_program(self, p, cache, counters, bt, tokens, cursor,
                      occupied):
        view = self.step_view(self, cache, bt, cursor, occupied)
        logits = self.dec.forward(p, tokens, view)
        return (view.cache(),), (logits, counters + view.counts)

    def _prefill_program(self, p, cache, counters, bt_row, tokens, slot,
                         hist, t):
        view = self.prefill_view(self, cache, bt_row, slot, tokens[0],
                                 hist, t)
        logits = self.dec.forward(p, tokens[0], view)
        return (view.cache(),), (logits, counters + view.counts)

    def prefill(self, bucket):
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = self._WeightProgram(
                self.dec, self._count(self._prefill_program,
                                      "decode_prefill_paged"),
                f"prefill_{self.dec.family}_b{bucket}", donate=_CACHE_ARG)
        return self._prefill_cache[bucket]


class _Admission:
    """A prompt on its way into a slot: what
    :meth:`PagedSlots.begin_admit` decided (``row``: the slot's pages,
    shared then owned, ``bt_row`` the same as a block-table row;
    ``hist`` tokens of it a cached prefix; ``tail`` the tokens to
    prefill; ``chunked``: it takes more than one program) and how many
    of the tail's tokens the chunks so far have put in (``done``)."""
    __slots__ = ("slot", "trace", "t0", "p_len", "hashes", "n_shared",
                 "hist", "tail", "done", "row", "bt_row", "chunked")

    @property
    def pending(self):
        return self.done < self.tail.size


class PagedSlots:
    """Paged scheduler backend: the device pool + pure-host page
    bookkeeping (block tables, refcounts, prefix index).

    The pool holds ``num_pages`` usable pages plus page 0, a scratch
    page free rows write into (never allocated).  Default sizing —
    ``num_slots * max_len/block`` — matches the contiguous footprint,
    so prefix sharing turns straight into headroom.  Refcounts: one per
    slot whose table references the page, plus one while the prefix
    index pins it; a page drops to the free list at refcount 0.  The
    prefix index evicts LRU pages nothing else references when the
    free list runs dry; a request that still cannot get a page at
    admission fails that admission, and one starving mid-decode is
    delivered truncated (reported by :meth:`step`, finished ``ok`` by
    the scheduler like the contiguous cache-window end).
    """

    paged = True

    def __init__(self, decoder, num_slots, block=None, num_pages=None,
                 prefix_cache=None, prefill_buckets=None, kernel=None):
        if decoder.mesh is not None:
            raise MXNetError(
                "paged KV is not supported together with a tensor-"
                "parallel mesh yet (serve the paged fleet data-parallel)")
        self.decoder = decoder
        self.num_slots = int(num_slots)
        self.block = int(block if block is not None else (kv_block() or 16))
        if self.block < 1:
            raise MXNetError(f"KV block must be >= 1, got {self.block}")
        if decoder.max_len % self.block:
            raise MXNetError(
                f"MXTPU_KV_BLOCK {self.block} must divide the decoder's "
                f"max_len {decoder.max_len}")
        self.max_blocks = decoder.max_len // self.block
        self.num_pages = int(
            num_pages if num_pages is not None
            else self.num_slots * self.max_blocks)
        if self.num_pages < self.max_blocks:
            raise MXNetError(
                f"pool of {self.num_pages} pages cannot hold one "
                f"max_len request ({self.max_blocks} pages)")
        self.prefix_on = (prefix_cache_on() if prefix_cache is None
                          else bool(prefix_cache))
        self.prefill_buckets = tuple(prefill_buckets or ())
        self.kernel_mode = (paged_kernel_mode() if kernel is None
                            else str(kernel).strip().lower())
        if self.kernel_mode not in ("auto", "gather", "pallas", "interpret"):
            raise MXNetError(
                f"unknown MXTPU_PAGED_KERNEL mode {self.kernel_mode!r} "
                "(want auto, gather/0, pallas or interpret)")
        layout = decoder.paged_layout()
        self.schedule = self._resolve_schedule(layout.get("kv_pages"))
        self.page_schedules = {
            name: self._resolve_page_schedule(width, dtype)
            for name, (_layers, width, dtype) in layout["pages"].items()}
        self.programs = _CachePrograms(
            decoder, layout, self.block, self.max_blocks,
            self.num_pages + 1, self.num_slots, schedule=self.schedule,
            page_schedules=self.page_schedules)
        # tokens a slot a step: a block decoder's block, which a page
        # holds whole (a block never straddles two pages)
        self.block_n = self.programs.block_n
        if self.block % self.block_n:
            raise MXNetError(
                f"KV block {self.block} must be a multiple of the "
                f"decoder's block_length {self.block_n}")
        # a decoder says whether a prefix of its cache can be picked up
        # at a block boundary (per-slot state cannot)
        self.prefix_on = self.prefix_on and layout["prefix_reuse"]
        # the same flag says a prefill's tail may stand behind rows the
        # slot's pages already hold: a tail over the largest bucket then
        # goes in chunks of that bucket's whole pages (0: it cannot)
        self._bucket_max = max(self.prefill_buckets, default=0)
        self._chunk = self._bucket_max // self.block * self.block \
            if layout["prefix_reuse"] else 0
        # host-side counts since start, beside the decoder's own
        self._host_counts = {"prefill_chunks": 0, "prompt_tokens": 0,
                             "prefix_tokens_hit": 0}
        # the device counters the decoder's programs add to, by the
        # names it declared, and what stats() has read of them so far
        # (host side, never wraps)
        self._counter_names = tuple(layout.get("counters", ()))
        self._counters = None
        self._counted = np.zeros(len(self._counter_names), np.int64)
        self._counters_seen = np.zeros(len(self._counter_names), np.int64)
        self._reset_pool()
        # trace id of the admission currently allocating, so _alloc can
        # attribute its prefix evictions; None for step-time evictions
        self._trace_ctx = None
        self._set_gauges()

    # ----------------------------------------------------------------- pool
    def _reset_pool(self):
        """A zeroed cache (pools, page rows, a state a slot: what the
        decoder declared) with every page free, no slot holding any and
        an empty prefix index."""
        import jax.numpy as jnp

        self.pool = self.programs.init_pool()
        self._read_counters()           # keep what the lost pool counted
        self._counters = jnp.zeros(len(self._counter_names), jnp.int32)
        self._counters_seen[:] = 0
        self.bt = np.zeros((self.num_slots, self.max_blocks), np.int32)
        self.cursor = np.zeros(self.num_slots, np.int32)
        self._free = list(range(self.num_pages, 0, -1))   # pop() -> page 1 last
        self._ref = np.zeros(self.num_pages + 1, np.int64)
        self._prefix = OrderedDict()      # chain hash -> page (LRU first)
        self._page_hash = {}              # page -> chain hash
        self._slot_pages = [[] for _ in range(self.num_slots)]

    def _run(self, program, *args):
        """Call a paged program on the pool, which it takes over
        (``donate``): its outputs are the pool from here on.  A call
        that raises after it took the buffers leaves nothing to serve
        from, so the backend starts again from :meth:`_reset_pool` and
        every slot loses its pages: :meth:`step` refuses to tick a slot
        without pages, which is how the scheduler comes to fail the
        requests that were live."""
        import jax

        try:
            self.pool, (out, self._counters) = program(
                *self.pool, self._counters, *args)
        except Exception:
            if any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(self.pool)):
                self._reset_pool()
                self._set_gauges()
            raise
        return out

    # ------------------------------------------------------------- schedule
    def _resolve_schedule(self, kv_pages):
        """The step-attention schedule for the shape signature of the
        K/V pools the decoder declared (``kv_pages``) — decided ONCE, here at bind
        time, never per tick (the search's device syncs are the declared
        ``autotune.search.measure`` boundary).  ``None`` means the PR-15
        gather step (and is all there is without K/V pages)."""
        import jax

        from .. import autotune as _autotune
        from ..ops import paged_attention as _pa

        mode = self.kernel_mode
        if mode == "gather" or kv_pages is None:
            return None
        L, H, dh, dtype = kv_pages
        B, M, blk = self.num_slots, self.max_blocks, self.block
        if mode in ("pallas", "interpret"):
            if not _pa.supports(blk, dh, dtype):
                return None         # shape gate even when forced
            return {"impl": "pallas", "interpret": mode == "interpret"}
        platform = jax.default_backend()
        sched = _autotune.ensure(
            "paged_attention",
            _pa.keysig(B, H, M, blk, dh, dtype),
            _pa.default_schedule(platform, blk, dh, dtype),
            _pa.candidate_schedules(platform, blk, dh, dtype),
            lambda c: _pa.make_bench_fn(c, B=B, H=H, M=M, block=blk,
                                        dh=dh, L=L, dtype=dtype))
        return None if sched.get("impl") == "gather" else dict(sched)

    def _resolve_page_schedule(self, width, dtype):
        """The schedule of the step's attention over page rows of
        ``width`` numbers (``ops/latent_attention.py``), decided once,
        here, by the mode :func:`paged_kernel_mode` reads, the platform
        and the kernel's gate: ``gather`` pins the lookup, ``pallas``
        asks for the kernel wherever Mosaic takes the page's shape,
        ``interpret`` runs it interpreted whatever the shape (the CPU
        parity hook: no Mosaic in it), ``auto`` takes the kernel on a
        TPU.  There is nothing to search: the kernel's chunk follows
        from the shapes."""
        import jax

        from ..ops import latent_attention as _la

        mode = self.kernel_mode
        if mode == "interpret":
            return {"impl": "pallas", "interpret": True}
        if mode == "gather":
            return {"impl": "gather"}
        return _la.default_schedule(
            "tpu" if mode == "pallas" else jax.default_backend(),
            self.block, _la.page_width(width), dtype)

    # --------------------------------------------------------- bookkeeping
    def _set_gauges(self):
        _TM_PAGES.set(self.num_pages, state="total")
        _TM_PAGES.set(len(self._free), state="free")
        _TM_PAGES.set(len(self._prefix), state="prefix")
        if self.programs.layout["state"]:
            _TM_STATE_SLOTS.set(self._slots_in_use())
        if self.programs.layout["pages"]:
            _TM_LATENT_PAGES.set(self.num_pages - len(self._free))

    def _slots_in_use(self):
        return sum(1 for pages in self._slot_pages if pages)

    def _read_counters(self):
        """Fold the device counters into the host's totals.  The device
        side is int32 and may wrap (after ~2**31 assignments, hours of
        traffic): the host adds the difference modulo 2**32 since its
        last read, so a reader that comes by now and then never sees
        the wrap.  The one device fetch of the counters (none for a
        decoder that declares no counter); never called from a tick."""
        if self._counters is not None and self._counter_names:
            now = np.asarray(self._counters).astype(np.int64)
            self._counted += (now - self._counters_seen) % (1 << 32)
            self._counters_seen = now
        return self._counted

    def stats(self):
        """The ``/healthz`` ``paged`` payload."""
        layout = self.programs.layout
        out = {"block": self.block,
               "pages_total": self.num_pages,
               "pages_free": len(self._free),
               "prefix_pages": len(self._prefix),
               "prefix_reuse": self.prefix_on,
               "family": self.decoder.family,
               # the step's attention over K/V pages, where there are any
               "kernel": (self.schedule or {"impl": "gather"})["impl"]
               if "kv_pages" in layout else "none",
               # the step's attention over page rows of the decoder's
               # own: the kernel only if every kind of them takes it
               "latent_kernel": "none" if not layout["pages"] else
               "pallas" if all(s["impl"] == "pallas" for s in
                               self.page_schedules.values()) else "gather"}
        if layout["state"]:
            out["state_slots_in_use"] = self._slots_in_use()
        if layout["pages"]:
            out["latent_pages_in_use"] = self.num_pages - len(self._free)
        # prefill programs run, tokens of admitted prompts, and how many
        # of those came from shared pages
        out.update(self._host_counts)
        for name, n in zip(self._counter_names, self._read_counters()):
            out[name] = int(n)
            _TM_COUNTED.set(int(n), name=name)
        return out

    def _alloc(self, n):
        """``n`` pages off the free list, evicting LRU prefix-only pages
        when it runs dry; all-or-nothing (rolls back on exhaustion)."""
        got = []
        while len(got) < n:
            if self._free:
                got.append(self._free.pop())
                continue
            evicted = None
            for hh, pg in self._prefix.items():     # LRU order
                if self._ref[pg] == 1:              # only the index holds it
                    evicted = (hh, pg)
                    break
            if evicted is None:
                self._free.extend(got)
                raise PoolExhausted(
                    f"KV page pool exhausted: {self.num_pages} pages all "
                    f"pinned by live requests (needed {n})")
            hh, pg = evicted
            del self._prefix[hh]
            del self._page_hash[pg]
            self._ref[pg] = 0
            got.append(pg)
            if _tracing.trace_on():
                _tracing.record_span(
                    "kv_evict", "replica", self._trace_ctx, 0.0, page=pg)
        for pg in got:
            self._ref[pg] = 1           # owned by the requesting slot
        return got

    def _block_hashes(self, prompt, n_blocks):
        """Chain hashes of the prompt's full blocks: ``d_i = H(d_{i-1}
        || tokens_i)`` — a block's hash commits to its whole prefix, so
        one dict hit per block reconstructs the longest shared chain."""
        prev = b"mxtpu-prefix"
        out = []
        for i in range(n_blocks):
            prev = hashlib.blake2b(
                prev + prompt[i * self.block:(i + 1) * self.block]
                .tobytes(), digest_size=16).digest()
            out.append(prev)
        return out

    @property
    def max_prompt(self):
        """The longest prompt an admission takes: the cache window where
        the tail may go in chunks, else the largest prefill bucket."""
        return self.decoder.max_len if self._chunk else self._bucket_max

    # ------------------------------------------------------------ admission
    def begin_admit(self, slot, prompt, trace=None):
        """Prefix lookup + page allocation for ``prompt`` into ``slot``;
        no program runs.  Returns the :class:`_Admission` that
        :meth:`admit_chunk` carries forward.  The slot's block table and
        cursor stay free (zero) until the last chunk is in: a step that
        runs between two chunks writes the free row's garbage into the
        scratch page, not into this prompt's first page.  For a block
        decoder only the prompt's whole blocks are prefilled (the
        remainder is the caller's to put into the first decoded block).
        ``trace``: the admitting request's trace id -- kv_admit /
        kv_prefix_hit spans land under it, and prefix pages evicted to
        make room are attributed to it (ISSUE 16)."""
        adm = _Admission()
        adm.slot, adm.trace, adm.t0 = slot, trace, time.perf_counter()
        prompt = np.asarray(prompt, np.int64)
        p_len = int(prompt.size) // self.block_n * self.block_n
        prompt = prompt[:p_len]
        blk = self.block
        adm.p_len = p_len
        # chain hashes of the prompt's full blocks (none: no index)
        adm.hashes = self._block_hashes(prompt, p_len // blk) \
            if self.prefix_on else []
        shared = []
        # reuse the longest cached chain, capped so >=1 tail token is
        # always prefilled (its logits seed the first sampled token) and
        # the cursor page stays fork-private
        for i in range((p_len - 1) // blk):
            pg = self._prefix.get(adm.hashes[i]) \
                if i < len(adm.hashes) else None
            if pg is None:
                break
            shared.append(pg)
            self._prefix.move_to_end(adm.hashes[i])
        adm.n_shared = n_shared = len(shared)
        adm.hist = n_shared * blk
        adm.tail = prompt[adm.hist:]
        adm.done = 0
        adm.chunked = adm.tail.size > self._bucket_max
        if adm.chunked and not self._chunk:
            raise MXNetError(
                f"a tail of {adm.tail.size} tokens exceeds the largest "
                f"prefill bucket {self._bucket_max}, and this decoder's "
                "prefill takes no history (per-slot state): it cannot go "
                "in chunks")
        # pin the matched chain BEFORE allocating: _alloc evicts ref==1
        # prefix pages, which would otherwise include this request's own
        # shared chain under pool pressure — the evicted page would come
        # back as an owned tail page and the prefill would overwrite the
        # shared prefix
        for pg in shared:
            self._ref[pg] += 1
        self._trace_ctx = trace
        try:
            # at least one page: step() takes a slot that holds none
            # as never admitted (a prompt shorter than a block)
            owned = self._alloc(
                max((p_len + blk - 1) // blk - n_shared, not p_len))
        except PoolExhausted:
            for pg in shared:
                self._ref[pg] -= 1
            raise
        finally:
            self._trace_ctx = None
        adm.row = shared + owned
        adm.bt_row = np.zeros(self.max_blocks, np.int32)
        adm.bt_row[:len(adm.row)] = adm.row
        self._slot_pages[slot] = list(adm.row)
        self._host_counts["prompt_tokens"] += p_len
        self._host_counts["prefix_tokens_hit"] += adm.hist
        if n_shared:
            _TM_PREFIX_HITS.inc(n_shared)
        if not p_len:
            self._finish_admit(adm, None)
        return adm

    def next_chunk(self, adm):
        """``(tokens, bucket)`` of the program :meth:`admit_chunk` runs
        next."""
        t = int(adm.tail.size) - adm.done
        if t > self._bucket_max:
            t = self._chunk
        return t, next(b for b in self.prefill_buckets if b >= t)

    def admit_chunk(self, adm):
        """The next prefill program of ``adm``: up to the largest
        bucket's worth of the tail (whole pages of it, so that the next
        chunk starts on a page boundary), in the smallest bucket that
        holds it, behind ``hist`` = the shared prefix and the chunks
        that went before.  Returns what the decoder's forward gives for
        a prefill (the next-token logits row of the chunk's last
        token), without waiting for it; after the last chunk
        (``adm.pending`` false) the slot is admitted."""
        import jax.numpy as jnp

        from ..models.decode import _snap

        t, bucket = self.next_chunk(adm)
        padded = np.zeros((1, bucket), np.int64)
        padded[0, :t] = adm.tail[adm.done:adm.done + t]
        # _snap: never alias a host array into an async dispatch.  The
        # slot goes in as a host value: a program that does not read it
        # (jit prunes unused arguments) then costs no transfer, where
        # each explicit one before the launch costs 0.3-0.45 ms on the
        # chip (PERF.md section 6, PR 29)
        args = (_snap(adm.bt_row), jnp.asarray(padded), np.int32(adm.slot),
                jnp.int32(adm.hist + adm.done), jnp.int32(t))
        logits = self._run(self.programs.prefill(bucket), *args)
        adm.done += t
        self._host_counts["prefill_chunks"] += 1
        if not adm.pending:
            self._finish_admit(adm, bucket)
        return logits

    def _finish_admit(self, adm, bucket):
        """The prompt is in: the slot gets its block table and cursor,
        the prompt's full blocks go to the prefix index."""
        slot, row = adm.slot, adm.row
        self.bt[slot] = adm.bt_row
        self.cursor[slot] = adm.p_len
        # promote this prompt's full blocks: they are never written
        # again (writes happen at cursor >= p_len), so they are safe to
        # share with every later identical prefix
        for pg, digest in zip(row, adm.hashes):
            if digest not in self._prefix:
                self._prefix[digest] = pg
                self._page_hash[pg] = digest
                self._ref[pg] += 1
        self._set_gauges()
        if bucket is not None and adm.trace is not None \
                and _tracing.trace_on():
            if adm.n_shared:
                _tracing.record_span(
                    "kv_prefix_hit", "replica", adm.trace, 0.0,
                    blocks=adm.n_shared, tokens=adm.hist)
            _tracing.record_span(
                "kv_admit", "replica", adm.trace,
                time.perf_counter() - adm.t0, slot=slot,
                pages_shared=adm.n_shared,
                pages_owned=len(row) - adm.n_shared,
                bucket=bucket)

    def admit(self, slot, prompt, trace=None):
        """A whole admission at once: :meth:`begin_admit`, then every
        chunk back to back (one program where the tail fits the largest
        bucket).  Returns the last chunk's logits; None for a block
        decoder's prompt shorter than one block, which runs no
        program."""
        adm = self.begin_admit(slot, prompt, trace)
        logits = None
        while adm.pending:
            logits = self.admit_chunk(adm)
        return logits

    # ----------------------------------------------------------------- tick
    def step(self, tokens, occupied, commit=None):
        """One jitted step over the pool (the paged allocator tick —
        declared in analysis/config.py:ENTRY_POINTS).  Rows crossing a
        block boundary get their next page here; a row the pool cannot
        feed is reported in ``starved`` for the scheduler to deliver
        truncated (its garbage write lands in the scratch page).
        ``tokens`` ``(B,)``, or ``(B, n)`` for a block decoder; the
        cursors of the slots in ``commit`` (every occupied one where
        None) advance by ``n``: what the others wrote stays
        provisional, to be overwritten by their next step."""
        from ..models.decode import _snap

        starved = []
        for b in np.flatnonzero(occupied):
            b = int(b)
            if not self._slot_pages[b]:
                raise MXNetError(
                    f"slot {b} holds no pages: it was never admitted, or "
                    "the pool was lost to a failed call and started anew")
            c = int(self.cursor[b])
            if c >= self.decoder.max_len:
                raise MXNetError(
                    f"slot cursor at max_len {self.decoder.max_len}: "
                    "finish or evict the request before ticking it")
            idx = c // self.block
            if c % self.block == 0 and len(self._slot_pages[b]) <= idx:
                try:
                    pg = self._alloc(1)[0]
                except PoolExhausted:
                    starved.append(b)
                    continue
                self.bt[b, idx] = pg
                self._slot_pages[b].append(pg)
        # _snap: bt/cursor are mutated in place right below and on the
        # next tick — aliasing them into the async dispatch races
        # ``occupied`` as a host copy of its own (nothing mutates it): no
        # transfer where the program does not read it (see admit)
        args = (_snap(self.bt), _snap(tokens), _snap(self.cursor),
                np.array(occupied, bool))
        logits = self._run(self.programs._step_jit, *args)
        adv = (occupied if commit is None else commit).copy()
        adv[starved] = False
        self.cursor[adv] += self.block_n
        if starved:
            self._set_gauges()
        return logits, starved

    def lower_step(self):
        """The paged step program lowered for this pool's shapes,
        without running it: ``.compile().as_text()`` shows whether the
        Pallas kernel (``tpu_custom_call``) is in it.  Inspection only:
        it is handed the pool's shape, never its buffers."""
        from ..models.decode import _snap

        shape = (self.num_slots,) + (self.block_n,) * (self.block_n > 1)
        return self.programs._step_jit.lower(
            *self._lowering_args(), _snap(self.bt),
            _snap(np.zeros(shape, np.int64)), _snap(self.cursor),
            _snap(np.zeros(self.num_slots), bool))

    def lower_prefill(self, bucket):
        """One prefill bucket's program, lowered the same way."""
        import jax.numpy as jnp

        from ..models.decode import _snap

        return self.programs.prefill(bucket).lower(
            *self._lowering_args(), _snap(self.bt[0]),
            jnp.asarray(np.zeros((1, bucket), np.int64)), jnp.int32(0),
            jnp.int32(0), jnp.int32(1))

    def _lowering_args(self):
        """What stands for the cache and the counters when a program
        is lowered without being run: shapes, never the buffers."""
        import jax

        return self.programs.pool_structs() + (jax.ShapeDtypeStruct(
            (len(self._counter_names),), np.int32),)

    def exhausted(self, slot):
        return self.cursor[slot] >= self.decoder.max_len

    def release(self, slot):
        for pg in self._slot_pages[slot]:
            self._ref[pg] -= 1
            if self._ref[pg] == 0:
                self._free.append(pg)
        self._slot_pages[slot] = []
        self.bt[slot] = 0
        self.cursor[slot] = 0
        self._set_gauges()
