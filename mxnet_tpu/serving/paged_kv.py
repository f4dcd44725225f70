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
admission, zero traces on a warm server
(``executor_compile_total{kind=decode_step_paged|decode_prefill_paged}``).

What a page holds, and what lives beside the pages, is the decoder's
to say.  Two kinds of decoder are served:

- ``models/decode.py:KVDecoder`` (GPT-2 block) declares nothing and
  gets the K/V pool of :class:`_PagedPrograms`: two ``(P, L, H, block,
  dh)`` arrays, whose programs hold that block's mathematics
  (``_block_qkv`` + ``_ln``/``_fc`` of ``models/decode.py``).  The
  gathered table reconstructs exactly the contiguous layout (absolute
  positions, ``start=0``) and masked-out entries contribute exact
  zeros — paged and contiguous decode are BITWISE equal on aligned
  prompts (tests pin it).
- a decoder with ``paged_layout()`` (``models/ling.py:LingDecoder``)
  declares its page rows by layer kind (``pages``: name -> layers, row
  width, dtype; one ``(layers, P, block * width)`` array each, a page
  one row, so that gathering a table is a lookup of whole rows) and a
  FIXED-SIZE STATE PER SLOT beside them (``state``: name -> shape,
  dtype; one ``(num_slots, *shape)`` array each — a linear-attention
  layer's recurrent state).  :class:`_DeclaredPrograms` holds no model
  mathematics: its two programs call the decoder's one ``forward`` over
  a *cache view* (:class:`_StepView`, :class:`_PrefillView`).  Still
  one block table a slot; a prefill starts from a zero state and
  overwrites the slot's row, so a reused slot never sees its
  predecessor; pages and state are donated through every program like
  the K/V pool.  A cached prefix would also need the state at that
  block boundary, which nothing snapshots: such a decoder says
  ``prefix_reuse: False`` and no page of it is ever shared
  (``stats()["prefix_reuse"]``).

The K/V pool's layout is the kernel's.  Each side of the pool is one
``(P, L, H, block, dh)`` array that the Mosaic kernel reads row-major
(``{4,3,2,1,0}``).  A program that writes it in a way the TPU compiler
would rather lay out otherwise — a scatter of rows gets
``{4,2,3,1,0}`` — makes XLA copy the WHOLE pool into that layout and
back before every layer's kernel (2 + 2 L copies of 1.6 GB a tick at
the 1.3B width, 70% of the device's time before ISSUE 26).  So there
are two ways into the pool, ``_PagedPrograms._write_rows`` (one
``dynamic_update_slice`` a slot) and ``_write_pages`` (whole pages),
both in place in that layout, and the programs take the pool's buffers
over (``donate``): ``PagedSlots`` holds the only reference and replaces
it with each call's outputs.  Any new program that touches the pool
goes into ``tests/test_tpu_compile.py``'s compiled-program test, which
fails on a pool-shaped copy; nothing on the CPU shows one.
"""
from __future__ import annotations

import hashlib
import os
import time
from collections import OrderedDict
from functools import partial

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
_TM_EXPERT_PAIRS = _tm.gauge(
    "serve_expert_assignments",
    "token-expert assignments of the served MoE layers since start, as "
    "of the last stats() read: on experts held here, on experts held "
    "elsewhere, and distinct held experts hit summed over layers and "
    "programs", labels=("where",))


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
    to PR 15).  ``pallas`` / ``interpret`` / ``pagewalk``: force one
    lowering of ``ops/paged_attention.py`` (``interpret`` is the
    CPU-parity hook; ``pagewalk`` the lax live-page walk)."""
    raw = os.environ.get("MXTPU_PAGED_KERNEL", "auto").strip().lower()
    if raw in ("", "1", "auto"):
        return "auto"
    if raw in ("0", "off", "false", "gather"):
        return "gather"
    return raw


# pool_k, pool_v among a paged program's arguments after the weights:
# both are donated, each program's outputs are the pool from then on
_POOL_ARGS = (0, 1)


class _PagedPrograms:
    """The jitted decode programs over the page pool.

    Pool layout ``(P, L, H, block, dh)`` — page-major so one gather by
    page id reconstructs a slot's table.  The layer math is the
    decoder's own (``_block_qkv`` + shared ``_ln``/``_fc``), run over
    the gathered table in the contiguous layout, so a paged step is
    bitwise the contiguous step whenever the table contents match.
    """

    def __init__(self, decoder, block, max_blocks, num_pages,
                 schedule=None):
        import jax

        from ..models.decode import _WeightProgram, _count_compiles

        self.dec = decoder
        self.block = int(block)
        self.max_blocks = int(max_blocks)
        self.num_pages = int(num_pages)
        # step-attention schedule (ops/paged_attention.py, picked by
        # mxnet_tpu.autotune at PagedSlots construction).  None/"gather"
        # keeps the PR-15 materialized-table math verbatim; prefill
        # always gathers (one admission-time cost, not the per-tick one)
        self.schedule = schedule if (
            schedule and schedule.get("impl") != "gather") else None
        self._step_jit = _WeightProgram(
            decoder, _count_compiles(self._forward_step,
                                     "decode_step_paged"),
            "decode_step_paged", donate=_POOL_ARGS)
        self._prefill_cache = {}

    def pool_structs(self):
        """Shape and dtype of ``(pool_k, pool_v)``: what a program is
        lowered with when nothing may take the pool's buffers."""
        import jax

        d = self.dec
        s = jax.ShapeDtypeStruct(
            (self.num_pages, d.L, d.H, self.block, d.dh), d._cache_dtype)
        return s, s

    def init_pool(self):
        import jax.numpy as jnp

        return tuple(jnp.zeros(s.shape, s.dtype)
                     for s in self.pool_structs())

    # -------------------------------------------------------------- writes
    # The two ways into the pool.  Both leave it in the row-major layout
    # the Mosaic kernel reads (module docstring, "The pool's layout");
    # tests/test_tpu_compile.py compiles every program that uses them.
    def _write_rows(self, pool, new, layer, at):
        """One row a slot: ``new[b]`` ``(H, 1, dh)`` lands at
        ``pool[page, layer, :, off]`` for ``(page, off) = at[b]``, as
        one ``dynamic_update_slice`` a slot (a ``fori_loop`` over the
        slots is laid out like the scatter again).  A step's trace
        holds ``2 L B`` of these writes, so ``at`` is a list of scalar
        pairs made once a program, and the indices, never negative,
        skip the wrap-around ``lax`` would stage for each."""
        import jax

        new = new[:, None]                           # (B, 1, H, 1, dh)
        for b, (page, off) in enumerate(at):
            pool = jax.lax.dynamic_update_slice(
                pool, jax.lax.slice_in_dim(new, b, b + 1),
                (page, layer, 0, off, 0), allow_negative_indices=False)
        return pool

    def _write_pages(self, pool, new, page_ids, layer):
        """Whole pages: ``new`` ``(T, H, dh)`` holds consecutive
        positions from a page boundary on, page ``n`` of it lands at
        ``pool[page_ids[n], layer]``; an id out of bounds drops its
        page."""
        import jax.numpy as jnp

        T, H, dh = new.shape
        new = jnp.pad(new, ((0, -T % self.block), (0, 0), (0, 0)))
        vals = new.reshape(-1, self.block, H, dh).transpose(0, 2, 1, 3)
        return pool.at[page_ids, layer].set(vals, mode="drop")

    # ------------------------------------------------------------ gathers
    def _gather(self, pool, bt):
        """(P, L, H, blk, dh)[bt (B, M)] -> contiguous (L, B, H, S, dh)."""
        d = self.dec
        t = pool[bt]                                 # (B, M, L, H, blk, dh)
        t = t.transpose(2, 0, 3, 1, 4, 5)            # (L, B, H, M, blk, dh)
        return t.reshape(d.L, bt.shape[0], d.H,
                         self.max_blocks * self.block, d.dh)

    # ---------------------------------------------------------------- step
    def _forward_step(self, p, pool_k, pool_v, bt, tokens, cursor):
        """One decode position for every slot: row ``b`` writes its new
        K/V at absolute cache position ``cursor[b]`` (page
        ``bt[b, cursor//block]``, offset ``cursor%block``) and attends
        over ``[0, cursor[b]]``.  Free rows ride along with
        ``bt[b]=0``/``cursor=0`` — their writes land in the scratch
        page the allocator never hands out."""
        import jax
        import jax.numpy as jnp

        from ..models.decode import NEG_INF, _fc, _ln

        d = self.dec
        B = tokens.shape[0]
        H, dh, D = d.H, d.dh, d.d_model
        S = self.max_blocks * self.block

        tok = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                       axis=0)                               # (B, D)
        pos_ids = jnp.clip(cursor, 0, d.max_len - 1)
        posv = jnp.take(p["pos_embed"][0], pos_ids, axis=0)  # (B, D)
        h = (tok + posv)[:, None]                            # (B, 1, D)
        s_idx = jnp.arange(S)
        valid = s_idx[None, :] <= cursor[:, None]            # (B, S)
        rows = jnp.arange(B)
        pages = jnp.take_along_axis(
            bt, (cursor // self.block)[:, None], axis=1)[:, 0]   # (B,)
        offs = cursor % self.block
        at = [(pages[b], offs[b]) for b in range(B)]
        sched = self.schedule
        if sched is None:
            kc = self._gather(pool_k, bt)
            vc = self._gather(pool_v, bt)
        else:
            from ..ops import paged_attention as _pa
        for i in range(d.L):
            name = f"layer{i}"
            with jax.named_scope(name):
                h2 = _ln(h, p[f"{name}_ln1_gamma"], p[f"{name}_ln1_beta"])
                q, k, v = d._block_qkv(p, i, h2)
                sh = lambda a: a.reshape(B, 1, H, dh).transpose(0, 2, 1, 3)
                qh, kh, vh = sh(q), sh(k), sh(v)             # (B, H, 1, dh)
                if sched is None:
                    kc = kc.at[i, rows, :, cursor].set(kh[:, :, 0])
                    vc = vc.at[i, rows, :, cursor].set(vh[:, :, 0])
                pool_k = self._write_rows(pool_k, kh, i, at)
                pool_v = self._write_rows(pool_v, vh, i, at)
                if sched is None:
                    scores = jnp.einsum("bhnd,bhsd->bhns", qh, kc[i]) \
                        / jnp.sqrt(jnp.asarray(dh, h.dtype))
                    scores = jnp.where(
                        valid[:, None, None, :], scores, NEG_INF)
                    att = jax.nn.softmax(scores, axis=-1)
                    ctx = jnp.einsum("bhns,bhsd->bhnd", att, vc[i])
                else:
                    # the kernel walks the block table over the pool the
                    # writes above just updated — same values the gathered
                    # table would hold, no materialization
                    ctx = _pa.paged_attention(
                        qh, pool_k, pool_v, bt, cursor, i,
                        block=self.block, schedule=sched)
                ctx = ctx.transpose(0, 2, 1, 3).reshape(B, 1, D)
                proj = _fc(ctx, p[f"{name}_proj_weight"],
                           p[f"{name}_proj_bias"])
                h = h + proj
                h2 = _ln(h, p[f"{name}_ln2_gamma"], p[f"{name}_ln2_beta"])
                f = _fc(h2, p[f"{name}_ffn_in_weight"],
                        p[f"{name}_ffn_in_bias"])
                f = jax.nn.gelu(f)
                f = _fc(f, p[f"{name}_ffn_out_weight"],
                        p[f"{name}_ffn_out_bias"])
                h = h + f
        h = _ln(h, p["final_ln_gamma"], p["final_ln_beta"])
        logits = _fc(h, p["lm_head_weight"], p["lm_head_bias"])
        return (pool_k, pool_v), logits[:, 0]                # (B, V)

    # ------------------------------------------------------------- prefill
    def _forward_prefill(self, p, pool_k, pool_v, bt_row, tokens, hist,
                         t):
        """Tail prefill behind a (possibly reused) history: ``tokens``
        (1, T) RIGHT-padded, the ``t`` real tokens sit at absolute
        positions ``hist .. hist+t-1``.  ``hist`` is a whole number of
        pages (only full blocks are shared), so the tail starts on a
        page boundary and its K/V go into the pool a PAGE at a time:
        every page that holds a real token is written whole, the pages
        of pad tokens alone aim out of bounds and are dropped.  The
        rows of the last, partial page beyond the prompt therefore hold
        the pad tokens' K/V: finite values at positions ``> cursor``,
        which both step lowerings mask to exact zero weight and which
        the decode write at each position replaces before the mask
        reaches it; only full blocks are promoted to the prefix index,
        so no shared page ever holds one.  The gathered table (for
        intra-prefill attention) takes the real tokens' rows only.
        ``hist``/``t`` ride as traced scalars, so the program count is
        one per padded bucket length."""
        import jax
        import jax.numpy as jnp

        from ..models.decode import NEG_INF, _fc, _ln

        d = self.dec
        T = tokens.shape[1]
        H, dh, D = d.H, d.dh, d.d_model
        S = self.max_blocks * self.block

        j = jnp.arange(T)
        real = j < t                                         # (T,)
        qpos = hist + j                                      # absolute
        tok = jnp.take(p["tok_embed_weight"], tokens.astype(jnp.int32),
                       axis=0)                               # (1, T, D)
        posv = jnp.take(p["pos_embed"][0],
                        jnp.clip(qpos, 0, d.max_len - 1), axis=0)[None]
        h = tok + posv
        # write targets: pad tokens go out of bounds -> dropped writes
        wpos = jnp.where(real, qpos, S)                      # table scatter
        n = jnp.arange(-(-T // self.block))                  # tail pages
        page_ids = jnp.where(
            n * self.block < t,
            bt_row[jnp.clip(hist // self.block + n, 0,
                            self.max_blocks - 1)],
            self.num_pages)                                  # pool scatter
        s_idx = jnp.arange(S)
        valid = s_idx[None, :] <= qpos[:, None]              # (T, S)
        kc = self._gather(pool_k, bt_row[None])              # (L, 1, H, S, dh)
        vc = self._gather(pool_v, bt_row[None])
        for i in range(d.L):
            name = f"layer{i}"
            with jax.named_scope(name):
                h2 = _ln(h, p[f"{name}_ln1_gamma"], p[f"{name}_ln1_beta"])
                q, k, v = d._block_qkv(p, i, h2)
                sh = lambda a: a.reshape(1, T, H, dh).transpose(0, 2, 1, 3)
                qh, kh, vh = sh(q), sh(k), sh(v)             # (1, H, T, dh)
                k_t = kh[0].transpose(1, 0, 2)                   # (T, H, dh)
                v_t = vh[0].transpose(1, 0, 2)
                kc = kc.at[i, 0, :, wpos].set(k_t)
                vc = vc.at[i, 0, :, wpos].set(v_t)
                pool_k = self._write_pages(pool_k, k_t, page_ids, i)
                pool_v = self._write_pages(pool_v, v_t, page_ids, i)
                scores = jnp.einsum("bhnd,bhsd->bhns", qh, kc[i]) \
                    / jnp.sqrt(jnp.asarray(dh, h.dtype))
                scores = jnp.where(valid[None, None], scores, NEG_INF)
                att = jax.nn.softmax(scores, axis=-1)
                ctx = jnp.einsum("bhns,bhsd->bhnd", att, vc[i])
                ctx = ctx.transpose(0, 2, 1, 3).reshape(1, T, D)
                proj = _fc(ctx, p[f"{name}_proj_weight"],
                           p[f"{name}_proj_bias"])
                h = h + proj
                h2 = _ln(h, p[f"{name}_ln2_gamma"], p[f"{name}_ln2_beta"])
                f = _fc(h2, p[f"{name}_ffn_in_weight"],
                        p[f"{name}_ffn_in_bias"])
                f = jax.nn.gelu(f)
                f = _fc(f, p[f"{name}_ffn_out_weight"],
                        p[f"{name}_ffn_out_bias"])
                h = h + f
        h = _ln(h, p["final_ln_gamma"], p["final_ln_beta"])
        logits = _fc(h, p["lm_head_weight"], p["lm_head_bias"])
        return (pool_k, pool_v), logits                      # (1, T, V)

    def prefill(self, bucket):
        if bucket not in self._prefill_cache:
            import jax

            from ..models.decode import _WeightProgram, _count_compiles

            self._prefill_cache[bucket] = _WeightProgram(
                self.dec, _count_compiles(self._forward_prefill,
                                          "decode_prefill_paged"),
                f"prefill_paged_b{bucket}", donate=_POOL_ARGS)
        return self._prefill_cache[bucket]


# the cache pytree among a declared program's arguments after the weights
_CACHE_ARG = (0,)
# the counters a declared program adds to (never donated: stats() reads
# them from another thread): assignments on held experts, on absent
# ones, distinct held experts hit
_N_COUNTERS = 3


class _CacheView:
    """What ``decoder.forward`` sees of the cache: the per-slot state,
    the pages, the positions of its tokens and which of them are real.
    ``step`` tells the decode step's view from a prefill's."""

    def __init__(self, programs, cache, positions, valid):
        self._pg = programs
        self.pages = dict(cache["pages"])
        self._state = dict(cache["state"])
        self.positions, self.valid = positions, valid
        self.counts = 0

    def count(self, counts):
        self.counts = self.counts + counts

    def cache(self):
        return {"pages": self.pages, "state": self._state}


class _StepView(_CacheView):
    """One token a slot: state rows are the slots themselves; a page row
    a slot is written at its cursor and every slot's table is gathered
    back for attention."""
    step = True

    def __init__(self, programs, cache, bt, cursor, occupied):
        super().__init__(programs, cache, cursor, occupied)
        self._bt, self._cursor = bt, cursor

    def state(self, name):
        return self._state[name]

    def set_state(self, name, value):
        self._state[name] = value.astype(self._state[name].dtype)

    def append(self, name, layer, rows):
        """``rows`` (B, W) land at each slot's cursor; returns the
        gathered ``(B, S, W)`` table and its ``(B, S)`` validity."""
        import jax
        import jax.numpy as jnp

        pg, bt, cursor = self._pg, self._bt, self._cursor
        pool = self.pages[name]
        pages = jnp.take_along_axis(
            bt, (cursor // pg.block)[:, None], axis=1)[:, 0]
        offs = cursor % pg.block
        W = rows.shape[-1]
        rows = rows.astype(pool.dtype)[None, :, None]        # (1, B, 1, W)
        # one dynamic_update_slice a slot, as the K/V pool's row writes
        # (a scatter of rows asks the TPU compiler for another layout)
        for b in range(rows.shape[1]):
            pool = jax.lax.dynamic_update_slice(
                pool, rows[:, b], (layer, pages[b], offs[b] * W),
                allow_negative_indices=False)
        self.pages[name] = pool
        # a page is one row of ``block * W`` numbers: gathering a slot's
        # table is a lookup of whole rows, in the layout they lie in
        S = pg.max_blocks * pg.block
        table = jnp.take(pool[layer], bt.reshape(-1), axis=0).reshape(
            bt.shape[0], S, W)
        return table, jnp.arange(S)[None, :] <= cursor[:, None]


class _PrefillView(_CacheView):
    """One sequence from position 0 into slot ``slot``: the state starts
    at zero and the state after token ``length - 1`` overwrites the
    slot's row; page rows go in a page at a time."""
    step = False

    def __init__(self, programs, cache, bt_row, slot, tokens, length):
        import jax.numpy as jnp

        j = jnp.arange(tokens.shape[0])
        super().__init__(programs, cache, j, j < length)
        self._bt_row, self._slot, self.length = bt_row, slot, length

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

    def append(self, name, layer, rows):
        """``rows`` (T, W), right-padded: every page that holds a real
        token is written whole (the rows beyond the prompt in the last
        page are masked by the step until its own writes replace them),
        pages of pad tokens alone aim out of bounds and are dropped."""
        import jax.numpy as jnp

        pg = self._pg
        pool = self.pages[name]
        T, W = rows.shape
        rows = jnp.pad(rows.astype(pool.dtype), ((0, -T % pg.block), (0, 0)))
        n = jnp.arange(rows.shape[0] // pg.block)
        page_ids = jnp.where(
            n * pg.block < self.length,
            self._bt_row[jnp.clip(n, 0, pg.max_blocks - 1)], pg.num_pages)
        self.pages[name] = pool.at[layer, page_ids].set(
            rows.reshape(-1, pg.block * W), mode="drop")
        return None


class _DeclaredPrograms:
    """The two jitted programs of a decoder that declares its cache
    (``decoder.paged_layout()``): ``jit_decode_step_<family>`` and
    ``jit_prefill_<family>_b<bucket>``.  Both call ``decoder.forward``
    over a cache view and nothing else; the cache, one pytree
    ``{"pages": {...}, "state": {...}}``, is donated to each call."""

    schedule = None

    def __init__(self, decoder, block, max_blocks, num_pages, num_slots):
        from ..models.decode import _WeightProgram, _count_compiles

        self.dec = decoder
        self.layout = decoder.paged_layout()
        self.block, self.max_blocks = int(block), int(max_blocks)
        self.num_pages, self.num_slots = int(num_pages), int(num_slots)
        self._WeightProgram, self._count = _WeightProgram, _count_compiles
        self._step_jit = _WeightProgram(
            decoder, _count_compiles(self._forward_step,
                                     "decode_step_paged"),
            f"decode_step_{decoder.family}", donate=_CACHE_ARG)
        self._prefill_cache = {}

    def pool_structs(self):
        """Shapes of the cache pytree, as the 1-tuple ``PagedSlots``
        keeps in ``pool``."""
        import jax

        pages = {n: jax.ShapeDtypeStruct(
            (layers, self.num_pages, self.block * width), dtype)
            for n, (layers, width, dtype) in self.layout["pages"].items()}
        state = {n: jax.ShapeDtypeStruct((self.num_slots,) + tuple(shape),
                                         dtype)
                 for n, (shape, dtype) in self.layout["state"].items()}
        return ({"pages": pages, "state": state},)

    def init_pool(self):
        import jax
        import jax.numpy as jnp

        return jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), self.pool_structs())

    def _forward_step(self, p, cache, counters, bt, tokens, cursor,
                      occupied):
        view = _StepView(self, cache, bt, cursor, occupied)
        logits = self.dec.forward(p, tokens, view)
        return (view.cache(),), (logits, counters + view.counts)

    def _forward_prefill(self, p, cache, counters, bt_row, tokens, slot,
                         t):
        view = _PrefillView(self, cache, bt_row, slot, tokens[0], t)
        logits = self.dec.forward(p, tokens[0], view)
        return (view.cache(),), (logits, counters + view.counts)

    def prefill(self, bucket):
        if bucket not in self._prefill_cache:
            self._prefill_cache[bucket] = self._WeightProgram(
                self.dec, self._count(self._forward_prefill,
                                      "decode_prefill_paged"),
                f"prefill_{self.dec.family}_b{bucket}", donate=_CACHE_ARG)
        return self._prefill_cache[bucket]


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
        # a decoder that declares its cache brings its own forward; the
        # K/V pool, its kernel and its schedule are the GPT-2 block's
        self.declared = hasattr(decoder, "paged_layout")
        if self.declared:
            self.kernel_mode, self.schedule = "none", None
            self.programs = _DeclaredPrograms(
                decoder, self.block, self.max_blocks, self.num_pages + 1,
                self.num_slots)
            # per-slot state cannot be picked up at a block boundary
            self.prefix_on = self.prefix_on \
                and self.programs.layout["prefix_reuse"]
        else:
            self.kernel_mode = (paged_kernel_mode() if kernel is None
                                else str(kernel).strip().lower())
            self.schedule = self._resolve_schedule()
            self.programs = _PagedPrograms(
                decoder, self.block, self.max_blocks, self.num_pages + 1,
                schedule=self.schedule)
        # device counters of a declared decoder's programs, and what
        # stats() has read of them so far (host side, never wraps)
        self._counters = None
        self._counted = np.zeros(_N_COUNTERS, np.int64)
        self._counters_seen = np.zeros(_N_COUNTERS, np.int64)
        self._reset_pool()
        # trace id of the admission currently allocating, so _alloc can
        # attribute its prefix evictions; None for step-time evictions
        self._trace_ctx = None
        # perf plane (telemetry/perf.py): one analytical cost row per
        # compiled paged program, captured at first dispatch
        self._cost_step_done = False
        self._cost_prefill_done = set()
        self._set_gauges()

    # ----------------------------------------------------------------- pool
    def _reset_pool(self):
        """A zeroed pool (and, where the decoder declares one, a zeroed
        state a slot) with every page free, no slot holding any and an
        empty prefix index."""
        self.pool = self.programs.init_pool()
        if self.declared:
            import jax.numpy as jnp

            self._read_counters()       # keep what the lost pool counted
            self._counters = jnp.zeros(_N_COUNTERS, jnp.int32)
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

        if self.declared:
            args = (self._counters,) + args
        try:
            self.pool, out = program(*self.pool, *args)
        except Exception:
            if any(a.is_deleted()
                   for a in jax.tree_util.tree_leaves(self.pool)):
                self._reset_pool()
                self._set_gauges()
            raise
        if self.declared:
            out, self._counters = out
        return out

    # ------------------------------------------------------------- schedule
    def _resolve_schedule(self):
        """The step-attention schedule for this pool's shape signature
        — decided ONCE, here at bind time, never per tick (the search's
        device syncs are the declared ``autotune.search.measure``
        boundary).  ``None`` means the PR-15 gather step verbatim."""
        import jax

        from .. import autotune as _autotune
        from ..ops import paged_attention as _pa

        mode = self.kernel_mode
        if mode == "gather":
            return None
        d = self.decoder
        B, M, blk = self.num_slots, self.max_blocks, self.block
        dtype = d._cache_dtype
        if mode in ("pallas", "interpret"):
            if not _pa.supports(blk, d.dh, dtype):
                return None         # shape gate even when forced
            return {"impl": "pallas", "interpret": mode == "interpret"}
        if mode == "pagewalk":
            return {"impl": "pagewalk", "chunk": 1}
        if mode != "auto":
            raise MXNetError(
                f"unknown MXTPU_PAGED_KERNEL mode {mode!r} (want auto, "
                "gather/0, pallas, interpret or pagewalk)")
        platform = jax.default_backend()
        default = _pa.default_schedule(platform, blk, d.dh, dtype)
        sched = _autotune.ensure(
            "paged_attention",
            _pa.keysig(B, d.H, M, blk, d.dh, dtype),
            default,
            _pa.candidate_schedules(platform, blk, d.dh, M, dtype),
            lambda c: _pa.make_bench_fn(c, B=B, H=d.H, M=M, block=blk,
                                        dh=d.dh, L=d.L, dtype=dtype))
        return None if sched.get("impl") == "gather" else dict(sched)

    # --------------------------------------------------------- bookkeeping
    def _set_gauges(self):
        _TM_PAGES.set(self.num_pages, state="total")
        _TM_PAGES.set(len(self._free), state="free")
        _TM_PAGES.set(len(self._prefix), state="prefix")
        if self.declared:
            _TM_STATE_SLOTS.set(self._slots_in_use())
            _TM_LATENT_PAGES.set(self.num_pages - len(self._free))

    def _slots_in_use(self):
        return sum(1 for pages in self._slot_pages if pages)

    def _read_counters(self):
        """Fold the device counters into the host's totals.  The device
        side is int32 and may wrap (after ~2**31 assignments, hours of
        traffic): the host adds the difference modulo 2**32 since its
        last read, so a reader that comes by now and then never sees
        the wrap.  The one device fetch of the counters; never called
        from a tick."""
        if self._counters is not None:
            now = np.asarray(self._counters).astype(np.int64)
            self._counted += (now - self._counters_seen) % (1 << 32)
            self._counters_seen = now
        return self._counted

    def stats(self):
        """The ``/healthz`` ``paged`` payload."""
        out = {"block": self.block,
               "pages_total": self.num_pages,
               "pages_free": len(self._free),
               "prefix_pages": len(self._prefix),
               "prefix_reuse": self.prefix_on,
               "kernel": "none" if self.declared
               else (self.schedule or {"impl": "gather"})["impl"]}
        if self.declared:
            held, absent, distinct = (int(n) for n in self._read_counters())
            out.update(
                family=self.decoder.family,
                state_slots_in_use=self._slots_in_use(),
                latent_pages_in_use=self.num_pages - len(self._free),
                expert_assignments_held=held,
                expert_assignments_absent=absent,
                expert_distinct_hits=distinct)
            for where, n in (("held", held), ("absent", absent),
                             ("distinct_hit", distinct)):
                _TM_EXPERT_PAIRS.set(n, where=where)
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
        return self.decoder.max_len

    # ------------------------------------------------------------ admission
    def admit(self, slot, prompt, trace=None):
        """Prefix lookup + page allocation + ONE bucketed tail prefill
        writing straight into the pool; returns the next-token logits
        row of the last prompt token.  ``trace``: the admitting
        request's trace id — kv_admit/kv_prefix_hit spans land under
        it, and prefix pages evicted to make room are attributed to it
        (ISSUE 16)."""
        import jax.numpy as jnp

        from ..models.decode import _snap

        t_kv0 = time.perf_counter()
        prompt = np.asarray(prompt, np.int64)
        p_len = int(prompt.size)
        blk = self.block
        n_full = p_len // blk
        hashes = self._block_hashes(prompt, n_full) if self.prefix_on \
            else []
        shared = []
        # reuse the longest cached chain, capped so >=1 tail token is
        # always prefilled (its logits seed the first sampled token) and
        # the cursor page stays fork-private
        for i in range((p_len - 1) // blk):
            pg = self._prefix.get(hashes[i]) if i < len(hashes) else None
            if pg is None:
                break
            shared.append(pg)
            self._prefix.move_to_end(hashes[i])
        n_shared = len(shared)
        hist = n_shared * blk
        tail = prompt[hist:]
        t = int(tail.size)
        # pin the matched chain BEFORE allocating: _alloc evicts ref==1
        # prefix pages, which would otherwise include this request's own
        # shared chain under pool pressure — the evicted page would come
        # back as an owned tail page and the prefill would overwrite the
        # shared prefix
        for pg in shared:
            self._ref[pg] += 1
        self._trace_ctx = trace
        try:
            owned = self._alloc((p_len + blk - 1) // blk - n_shared)
        except PoolExhausted:
            for pg in shared:
                self._ref[pg] -= 1
            raise
        finally:
            self._trace_ctx = None
        row = shared + owned
        self.bt[slot, :len(row)] = row
        self.bt[slot, len(row):] = 0
        self._slot_pages[slot] = list(row)
        if n_shared:
            _TM_PREFIX_HITS.inc(n_shared)
        bucket = next(b for b in self.prefill_buckets if b >= t)
        padded = np.zeros((1, bucket), np.int64)
        padded[0, :t] = tail
        # _snap: self.bt is mutated in place by later admits/steps while
        # this dispatch may still be executing — never alias it
        # the K/V programs prefill a tail behind ``hist`` shared tokens; a
        # declared decoder shares none and is told its slot instead
        args = (_snap(self.bt[slot]), jnp.asarray(padded),
                jnp.int32(slot if self.declared else hist), jnp.int32(t))
        logits = self._run(self.programs.prefill(bucket), *args)
        if bucket not in self._cost_prefill_done and _tm.perf.enabled():
            self._cost_prefill_done.add(bucket)
            _tm.perf.attach_cost_analysis(
                f"decode_prefill_paged[b{bucket}]",
                self.programs.prefill(bucket),
                *self._lowering_args(), *args)
        self.cursor[slot] = p_len
        # promote this prompt's full blocks: they are never written
        # again (writes happen at cursor >= p_len), so they are safe to
        # share with every later identical prefix
        if self.prefix_on:
            for i in range(n_full):
                if hashes[i] not in self._prefix:
                    pg = row[i]
                    self._prefix[hashes[i]] = pg
                    self._page_hash[pg] = hashes[i]
                    self._ref[pg] += 1
        self._set_gauges()
        if trace is not None and _tracing.trace_on():
            if n_shared:
                _tracing.record_span(
                    "kv_prefix_hit", "replica", trace, 0.0,
                    blocks=n_shared, tokens=hist)
            _tracing.record_span(
                "kv_admit", "replica", trace,
                time.perf_counter() - t_kv0, slot=slot,
                pages_shared=n_shared, pages_owned=len(owned),
                bucket=bucket)
        # a declared decoder's prefill returns the last real token's row
        return logits if self.declared else logits[0, t - 1]

    # ----------------------------------------------------------------- tick
    def step(self, tokens, occupied):
        """One jitted step over the pool (the paged allocator tick —
        declared in analysis/config.py:ENTRY_POINTS).  Rows crossing a
        block boundary get their next page here; a row the pool cannot
        feed is reported in ``starved`` for the scheduler to deliver
        truncated (its garbage write lands in the scratch page)."""
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
        args = (_snap(self.bt), _snap(tokens), _snap(self.cursor))
        if self.declared:
            args += (_snap(occupied, bool),)
        logits = self._run(self.programs._step_jit, *args)
        if not self._cost_step_done and _tm.perf.enabled():
            self._cost_step_done = True
            _tm.perf.attach_cost_analysis(
                "decode_step_paged", self.programs._step_jit,
                *self._lowering_args(), *args)
        adv = occupied.copy()
        adv[starved] = False
        self.cursor[adv] += 1
        if starved:
            self._set_gauges()
        return logits, starved

    def lower_step(self):
        """The paged step program lowered for this pool's shapes,
        without running it: ``.compile().as_text()`` shows whether the
        Pallas kernel (``tpu_custom_call``) is in it.  Inspection only:
        it is handed the pool's shape, never its buffers."""
        from ..models.decode import _snap

        args = (_snap(self.bt), _snap(np.zeros(self.num_slots, np.int64)),
                _snap(self.cursor))
        if self.declared:
            args += (_snap(np.zeros(self.num_slots), bool),)
        return self.programs._step_jit.lower(*self._lowering_args(), *args)

    def lower_prefill(self, bucket):
        """One prefill bucket's program, lowered the same way."""
        import jax.numpy as jnp

        from ..models.decode import _snap

        return self.programs.prefill(bucket).lower(
            *self._lowering_args(), _snap(self.bt[0]),
            jnp.asarray(np.zeros((1, bucket), np.int64)), jnp.int32(0),
            jnp.int32(1))

    def _lowering_args(self):
        """What stands for the pool (and the counters) when a program
        is lowered without being run: shapes, never the buffers."""
        import jax

        structs = self.programs.pool_structs()
        if self.declared:
            structs += (jax.ShapeDtypeStruct((_N_COUNTERS,), np.int32),)
        return structs

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
