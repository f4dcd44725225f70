"""How the paged programs write into the page pool (ISSUE 26).

The step program writes one row a slot with ``dynamic_update_slice``,
the prefill programs whole pages, and both take the pool's buffers over
(``donate``).  What the chip's compiler makes of that is
``tests/test_tpu_compile.py``'s; here, on the CPU: the values and where
they land are what the row scatter this replaced put there, paged
decode is still the contiguous one, a shared page is never written,
and a call that dies with the pool's buffers leaves a backend that
serves again.

A prefill attends over one layer's history pages and its tail's own
K/V beside them (ISSUE 30), where the contiguous decoder attends over
one table of ``max_len`` positions: the same softmax over the same
keys, summed in another order, so logits agree to the last bits and not
bitwise.  ``_TOL`` states what they are held to, with the
widest gap measured here beside it.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.serving import SlotScheduler
from mxnet_tpu.serving.paged_kv import (PagedSlots, _PrefillView,
                                       _StepView)
from mxnet_tpu.serving.scheduler import _ContiguousSlots
from mxnet_tpu.telemetry import perf

L, H, D, T, V = 2, 2, 32, 32, 17
BLOCK = 8
BUCKETS = (8, 12, 16, 32)       # 12: a bucket that ends inside a page


# paged against contiguous, as a share of the largest magnitude compared
# (logits, K/V rows): float32 read at most 1.9e-7 over 144 admissions
# and their steps, about one ulp; bfloat16 read 0 and is given two ulps
_TOL = {"float32": 2e-6, "bfloat16": 2e-2}


def _close(want, got, dtype="float32"):
    want, got = (np.asarray(a, np.float32) for a in (want, got))
    return np.abs(want - got).max() <= _TOL[dtype] * np.abs(want).max()


def _decoder(dtype, max_len=T):
    net = models.transformer.transformer_lm(
        num_layers=L, num_heads=H, d_model=D, seq_len=max_len,
        vocab_size=V)
    ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                         data=(1, max_len), softmax_label=(1, max_len))
    rs = np.random.RandomState(0)
    params = {}
    for name, arr in ex.arg_dict.items():
        if name in ("data", "softmax_label"):
            continue
        arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
        params[name] = arr
    return KVDecoder(params, num_layers=L, num_heads=H, max_len=max_len,
                     dtype=dtype)


@pytest.fixture(scope="module")
def decoder():
    return _decoder(jnp.float32)


def _paged(decoder, slots=3, **kw):
    kw.setdefault("kernel", "gather")
    return PagedSlots(decoder, slots, block=BLOCK, prefill_buckets=BUCKETS,
                      **kw)


class _RowScatterStep(_StepView):
    """The step's write as it was: one scatter of rows a layer."""

    def _write_rows(self, pool, new, layer):
        pages, offs = (jnp.stack(x) for x in zip(*self._at))
        return pool.at[pages, layer, :, offs].set(new[:, :, 0])


class _RowScatterPrefill(_PrefillView):
    """The prefill's write as it was: one scatter of rows a layer (its
    pad rows too, where their page is real; nothing live)."""

    def _write_pages(self, pool, new, layer):
        j = jnp.arange(new.shape[0])
        block = self._pg.block
        return pool.at[self._page_ids[j // block], layer, :,
                       j % block].set(new, mode="drop")


def _live_rows(pg, slot):
    """The pool's K and V at every position the slot has written:
    ``(2, cursor, L, H, dh)``."""
    n = int(pg.cursor[slot])
    pages = pg.bt[slot, :(n + BLOCK - 1) // BLOCK]
    out = []
    for side in pg.pool[0]["kv"]:
        rows = np.asarray(side)[pages]              # (n_pg, L, H, blk, dh)
        rows = rows.transpose(0, 3, 1, 2, 4).reshape(-1, L, H, D // H)
        out.append(rows[:n])
    return np.stack(out)


def test_pool_holds_what_the_row_scatter_wrote(decoder):
    """Admissions of every bucket, ticks across page boundaries, a slot
    released and taken again: every live position of every live page,
    and every logit on the way, is bitwise what the row scatter gave."""
    new = _paged(decoder)
    old = _paged(decoder)
    old.programs.step_view = _RowScatterStep
    old.programs.prefill_view = _RowScatterPrefill
    rs = np.random.RandomState(3)
    tok = np.zeros(3, np.int64)

    def admit(slot, plen):
        prompt = rs.randint(0, V, plen)
        ln, lo = (np.asarray(p.admit(slot, prompt)) for p in (new, old))
        assert np.array_equal(ln, lo)
        tok[slot] = int(ln.argmax())

    def tick(occupied, n):
        occ = np.asarray(occupied)
        for _ in range(n):
            ln, lo = (np.asarray(p.step(tok, occ)[0]) for p in (new, old))
            assert np.array_equal(ln[occ], lo[occ])
            tok[occ] = ln[occ].argmax(-1)

    def same_pool(slots):
        assert np.array_equal(new.bt, old.bt)
        for s in slots:
            assert np.array_equal(_live_rows(new, s), _live_rows(old, s))

    admit(0, 5)
    admit(1, 12)
    tick([True, True, False], 5)        # slot 0 crosses into page 2
    same_pool([0, 1])
    admit(2, 16)
    tick([True, True, True], 4)
    same_pool([0, 1, 2])
    for p in (new, old):
        p.release(1)
    admit(1, 9)                         # over pages another slot wrote
    tick([True, True, True], 3)
    same_pool([0, 1, 2])


@pytest.mark.parametrize("plen", [8, 12, 16])
def test_paged_vs_contiguous_bitwise_by_prompt_end(decoder, plen):
    """Paged decode is the contiguous one on prompts that end with a
    page (8, 16) and on one that ends inside a page (12, whose bucket
    is no whole number of pages either): the rows the page-wise prefill
    left beyond the prompt weigh exactly nothing.  Bitwise while the
    prefill attended over one gathered ``max_len`` table (until ISSUE
    30); now to ``_TOL``."""
    cont = _ContiguousSlots(decoder, 2, BUCKETS)
    pg = _paged(decoder, slots=2)
    prompt = np.random.RandomState(plen).randint(0, V, plen)
    lc = np.asarray(cont.admit(0, prompt), np.float32)
    lp = np.asarray(pg.admit(0, prompt), np.float32)
    assert _close(lc, lp)
    tok = np.array([int(lc.argmax()), 0])
    occ = np.array([True, False])
    for _ in range(T - plen - 1):
        lc = np.asarray(cont.step(tok, occ)[0], np.float32)
        lp = np.asarray(pg.step(tok, occ)[0], np.float32)
        assert _close(lc[0], lp[0])
        tok = np.array([int(lc[0].argmax()), 0])


# ------------------------------------ a prefill behind a history (ISSUE 30)
@pytest.fixture(scope="module")
def backend():
    """``backend(dtype, buckets, max_len)``: one decoder a dtype and
    length and one paged backend a bucket list, shared by the cases
    below (a program set is seconds of compile) and started anew for
    each: every page free, an empty prefix index."""
    made = {}

    def get(dtype, buckets, max_len=T):
        if (dtype, buckets, max_len) not in made:
            if (dtype, max_len) not in made:
                made[dtype, max_len] = _decoder(jnp.dtype(dtype), max_len)
            made[dtype, buckets, max_len] = PagedSlots(
                made[dtype, max_len], 3, block=BLOCK,
                prefill_buckets=buckets, kernel="gather", prefix_cache=True)
        made[dtype, buckets, max_len]._reset_pool()
        return made[dtype, buckets, max_len]

    return get


def _admission_is_the_contiguous_one(pg, hist_pages, tail, dtype):
    """Slot 1 leaves ``hist_pages`` shared pages behind; slot 0's
    prompt finds them and prefills ``tail`` tokens: against the
    contiguous decoder on the whole prompt, the first token's logits,
    every live row of the pool, and five decode steps."""
    hist = hist_pages * BLOCK
    cont = _ContiguousSlots(pg.decoder, 2, (hist + tail,))
    rs = np.random.RandomState(100 * hist_pages + tail)
    shared = rs.randint(0, V, hist)
    if hist:
        pg.admit(1, np.concatenate([shared, rs.randint(0, V, 3)]))
    prompt = np.concatenate([shared, rs.randint(0, V, tail)])
    close = functools.partial(_close, dtype=dtype)

    def window(n):          # (2, n, L, H, dh) of the contiguous cache
        return np.stack([np.asarray(side, np.float32)[:, 0, :, :n]
                         .transpose(2, 0, 1, 3) for side in cont.cache])

    lc, lp = cont.admit(0, prompt), pg.admit(0, prompt)
    assert list(pg.bt[0, :hist_pages]) == list(pg.bt[1, :hist_pages])
    assert hist_pages == 0 or pg.bt[0, hist_pages] != pg.bt[1, hist_pages]
    assert close(lc, lp)
    assert close(window(hist + tail), _live_rows(pg, 0))
    tok = np.array([int(np.asarray(lc).argmax()), 0, 0])
    occ = np.array([True, False, False])
    for _ in range(5):
        lc, lp = cont.step(tok[:2], occ[:2])[0], pg.step(tok, occ)[0]
        assert close(lc[0], lp[0])
        tok[0] = int(np.asarray(lc[0]).argmax())
    assert close(window(hist + tail + 5), _live_rows(pg, 0))


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("buckets", [BUCKETS, (T,)],
                         ids=["bucket_fits", "bucket_overshoots"])
@pytest.mark.parametrize("tail", [8, 5], ids=["ends_a_page", "inside_a_page"])
@pytest.mark.parametrize("hist_pages", [0, 1, 2])
def test_admission_behind_a_history_is_the_contiguous_one(
        backend, hist_pages, tail, buckets, dtype):
    """An admission that finds ``hist_pages`` pages of its prompt in
    the prefix index (none, one, several) and prefills a tail that ends
    with a page or inside one, in a bucket that fits or in ``max_len``'s
    (which, behind a history, reaches past ``max_len - hist``: those
    pad tokens' positions are clipped and their pages dropped)."""
    _admission_is_the_contiguous_one(backend(dtype, buckets), hist_pages,
                                     tail, dtype)


@pytest.mark.parametrize("hist_pages,dtype", [
    (16, "float32"), (17, "float32"), (17, "bfloat16"), (33, "float32")])
def test_admission_behind_a_long_history(backend, hist_pages, dtype):
    """Histories of 128 keys and more at ``max_len`` 288: the shared
    pages outnumber the tail's by 16, 17 and 33 to one."""
    _admission_is_the_contiguous_one(
        backend(dtype, (16, 288), max_len=288), hist_pages, 5, dtype)


def test_shared_page_is_never_written(decoder):
    """A page the prefix index shares is bit-identical before and after
    a second request prefills its tail behind it and both decode."""
    pg = _paged(decoder, prefix_cache=True)
    rs = np.random.RandomState(9)
    shared = rs.randint(0, V, BLOCK)
    pg.admit(0, np.concatenate([shared, rs.randint(0, V, 5)]))
    page = pg.bt[0, 0]
    assert page in pg._prefix.values()
    before = [np.asarray(side)[page].copy() for side in pg.pool[0]["kv"]]
    pg.admit(1, np.concatenate([shared, rs.randint(0, V, 7)]))
    assert pg.bt[1, 0] == page and pg.bt[1, 1] != pg.bt[0, 1]
    occ = np.array([True, True, False])
    for _ in range(6):
        pg.step(np.array([1, 2, 0]), occ)
    for side, was in zip(pg.pool[0]["kv"], before):
        assert np.array_equal(np.asarray(side)[page], was)


def _takes_the_pool_and_dies(cache, *_args):
    for side in cache["kv"]:
        side.delete()
    raise RuntimeError("planted: died holding the pool")


def _dies_before_the_pool(*_args):
    raise RuntimeError("planted: died before taking the pool")


@pytest.mark.parametrize("where", ["step", "prefill"])
def test_backend_starts_anew_after_losing_the_pool(decoder, where):
    """A donating call that raises after it took the buffers: the
    backend comes back with a fresh pool, every page free, no prefix,
    refuses to tick the slots that lost their pages, and serves the
    next request as a new backend would."""
    pg = _paged(decoder, prefix_cache=True)
    prompt = np.random.RandomState(4).randint(0, V, 12)
    pg.admit(0, prompt)
    assert pg.stats()["prefix_pages"] == 1
    occ = np.array([True, False, False])
    tok = np.zeros(3, np.int64)
    real = pg.programs._step_jit, pg.programs.prefill(12)
    if where == "step":
        pg.programs._step_jit = _takes_the_pool_and_dies
        with pytest.raises(RuntimeError, match="planted"):
            pg.step(tok, occ)
    else:
        pg.programs._prefill_cache[12] = _takes_the_pool_and_dies
        with pytest.raises(RuntimeError, match="planted"):
            pg.admit(1, (prompt + 1) % V)   # no shared block: bucket 12
    pg.programs._step_jit, pg.programs._prefill_cache[12] = real
    st = pg.stats()
    assert st["pages_free"] == st["pages_total"] and st["prefix_pages"] == 0
    assert not pg._ref.any() and not pg.bt.any() and not pg.cursor.any()
    assert not any(a.is_deleted() for a in pg.pool[0]["kv"])
    with pytest.raises(mx.MXNetError, match="holds no pages"):
        pg.step(tok, occ)               # slot 0's request cannot go on
    for slot in range(3):               # what the scheduler does next
        pg.release(slot)
    assert pg.stats()["pages_free"] == st["pages_total"]
    fresh = _paged(decoder, prefix_cache=True)
    la = np.asarray(pg.admit(0, prompt))
    assert np.array_equal(la, np.asarray(fresh.admit(0, prompt)))
    tok[0] = int(la.argmax())
    assert np.array_equal(np.asarray(pg.step(tok, occ)[0])[0],
                          np.asarray(fresh.step(tok, occ)[0])[0])


def test_failure_before_the_pool_is_taken_keeps_it(decoder):
    """A call that raises with the buffers still the caller's (a trace
    or compile error) costs nothing: pool, pages and prefix stay."""
    pg = _paged(decoder, prefix_cache=True)
    prompt = np.random.RandomState(4).randint(0, V, 12)
    tok = np.array([int(np.asarray(pg.admit(0, prompt)).argmax()), 0, 0])
    occ = np.array([True, False, False])
    twin = _paged(decoder, prefix_cache=True)
    twin.admit(0, prompt)
    real = pg.programs._step_jit
    pg.programs._step_jit = _dies_before_the_pool
    with pytest.raises(RuntimeError, match="planted"):
        pg.step(tok, occ)
    pg.programs._step_jit = real
    assert pg.stats() == twin.stats() and pg.cursor[0] == 12
    assert np.array_equal(np.asarray(pg.step(tok, occ)[0])[0],
                          np.asarray(twin.step(tok, occ)[0])[0])


def test_engine_outlives_a_lost_pool(decoder):
    """Through the scheduler: the tick that dies with the pool fails
    the requests that were live (outcome ``error``), the engine thread
    goes on, and the next request is served from the fresh pool with
    the tokens a plain decode gives."""
    sched = SlotScheduler(decoder, num_slots=2, queue_size=8, paged=True,
                          kv_block=BLOCK, paged_kernel="gather")
    try:
        pg = sched.backend
        rs = np.random.RandomState(12)
        warm = sched.generate(rs.randint(0, V, 6), max_new_tokens=3,
                              timeout=120)
        assert warm.outcome == "ok"
        real = pg.programs._step_jit
        pg.programs._step_jit = _takes_the_pool_and_dies
        lost = [sched.submit(rs.randint(0, V, 6), max_new_tokens=8)
                for _ in range(2)]
        for r in lost:
            r.wait(120)
        pg.programs._step_jit = real
        assert [r.outcome for r in lost] == ["error", "error"]
        assert "planted" in str(lost[0].error)
        st = sched.paged_stats()
        assert st["pages_free"] == st["pages_total"]
        assert st["prefix_pages"] == 0
        prompt = rs.randint(0, V, 9)
        req = sched.generate(prompt, max_new_tokens=5, timeout=120)
        assert req.outcome == "ok"
        ref = decoder.generate(prompt[None], 5, temperature=0)
        assert req.tokens == ref[0].tolist()
    finally:
        sched.close()


def test_lowering_takes_nothing(decoder):
    """``lower_step()`` and the perf plane's cost analysis only lower:
    the pool is the backend's afterwards, and the next tick runs."""
    pg = _paged(decoder)
    prompt = np.random.RandomState(2).randint(0, V, 12)
    was = perf.enabled()
    perf.enable()
    try:
        tok = np.array([int(np.asarray(pg.admit(0, prompt)).argmax()), 0, 0])
        occ = np.array([True, False, False])
        pg.step(tok, occ)               # first dispatch: cost analysis
        assert "input_output_alias" in pg.lower_step().compile().as_text()
        assert not any(a.is_deleted() for a in pg.pool[0]["kv"])
        rows = _live_rows(pg, 0)
        pg.step(tok, occ)
        assert np.array_equal(_live_rows(pg, 0)[:, :13], rows)
    finally:
        if not was:
            perf.disable()


# ------------------------------------------- a third decoder, defined here
# What the seam is for: a decoder that is neither models/decode.py's nor
# models/ling.py's declares K/V pages, brings one ``forward`` over the
# view, and is served -- page writes, prefix index, the kernel -- with
# no line of serving/paged_kv.py knowing it.
class _ToyDecoder:
    """A GPT-2-shaped block without biases: RMS norm, sinusoidal
    positions (so it never asks the view to ``embed``), one fused q/k/v
    projection.  128-wide heads and float32, so 8-row pages pass
    ``ops.paged_attention.supports``."""
    family, mesh = "toy", None
    L, H, DH, V = 2, 2, 128, 23

    def __init__(self, max_len, counters=()):
        D = self.H * self.DH
        rs = np.random.RandomState(5)
        shapes = {"embed": (self.V, D), "head": (self.V, D)}
        for i in range(self.L):
            shapes.update({f"l{i}_qkv": (3 * D, D), f"l{i}_o": (D, D),
                           f"l{i}_up": (2 * D, D), f"l{i}_down": (D, 2 * D)})
        self.p = {k: jnp.asarray(rs.normal(0, 0.06, s), jnp.float32)
                  for k, s in shapes.items()}
        self.max_len, self.vocab, self.counters = max_len, self.V, counters

    def paged_layout(self):
        return {"kv_pages": (self.L, self.H, self.DH, jnp.float32),
                "pages": {}, "state": {}, "counters": self.counters,
                "prefix_reuse": True}

    def forward(self, p, tokens, view):
        """``tokens`` (N,) at ``view.positions`` -> (B, V) in the step,
        (V,), the last real token's row, in a prefill."""
        H, DH = self.H, self.DH
        D = H * DH
        norm = lambda x: x * jax.lax.rsqrt(
            jnp.mean(x * x, -1, keepdims=True) + 1e-6)
        ang = view.positions[:, None] * (
            1e4 ** (-jnp.arange(0, D, 2) / D))[None, :]
        h = p["embed"][tokens] + jnp.concatenate(
            [jnp.sin(ang), jnp.cos(ang)], -1)
        h = h[:, None] if view.step else h[None]             # (B, n, D)
        B, n, _ = h.shape
        heads = lambda a: a.reshape(B, n, H, DH).transpose(0, 2, 1, 3)
        for i in range(self.L):
            q, k, v = jnp.split(norm(h) @ p[f"l{i}_qkv"].T, 3, -1)
            ctx = view.attend(i, heads(q), heads(k), heads(v))
            h = h + ctx.transpose(0, 2, 1, 3).reshape(B, n, D) \
                @ p[f"l{i}_o"].T
            h = h + jax.nn.gelu(norm(h) @ p[f"l{i}_up"].T) \
                @ p[f"l{i}_down"].T
        if self.counters:       # real tokens seen, program calls
            view.count(jnp.stack([jnp.sum(view.valid), 1]).astype(jnp.int32))
        logits = norm(h) @ p["head"].T
        return logits[:, 0] if view.step else logits[0, view.length - 1]


class _DenseView:
    """The toy's own reference: one whole sequence, no cache."""
    step = False

    def __init__(self, n):
        self.positions, self.length = jnp.arange(n), n

    def attend(self, layer, q, k, v):
        n = q.shape[2]
        scores = jnp.einsum("bhnd,bhsd->bhns", q, k) / np.sqrt(q.shape[-1])
        causal = jnp.arange(n)[:, None] >= jnp.arange(n)[None, :]
        att = jax.nn.softmax(jnp.where(causal, scores, -1e30), axis=-1)
        return jnp.einsum("bhns,bhsd->bhnd", att, v)


@pytest.mark.parametrize("kernel,impl", [("gather", "gather"),
                                         ("interpret", "pallas")])
def test_a_decoder_defined_here_is_served_over_kv_pages(kernel, impl):
    """Two requests (one prompt ends inside a page, the other shares its
    first page through the prefix index), then ``BLOCK + 2`` greedy
    steps each across page boundaries: every logits row is the dense
    forward's over the tokens so far, every token its choice."""
    dec = _ToyDecoder(max_len=T)
    pg = PagedSlots(dec, 3, block=BLOCK, prefill_buckets=BUCKETS,
                    kernel=kernel, prefix_cache=True)
    assert pg.stats()["kernel"] == impl and pg.stats()["family"] == "toy"

    def dense(tokens):
        return np.asarray(dec.forward(dec.p, jnp.asarray(tokens),
                                      _DenseView(len(tokens))))

    def same(got, tokens):
        want = dense(tokens)
        np.testing.assert_allclose(np.asarray(got), want, rtol=2e-4,
                                   atol=2e-5)
        assert int(np.argmax(got)) == int(want.argmax())
        return tokens + [int(want.argmax())]

    rs = np.random.RandomState(6)
    first = rs.randint(0, dec.V, 11).tolist()
    seqs = {0: same(pg.admit(0, np.array(first)), first)}
    fork = first[:BLOCK] + rs.randint(0, dec.V, 4).tolist()
    seqs[2] = same(pg.admit(2, np.array(fork)), fork)
    assert pg.bt[2, 0] == pg.bt[0, 0], "the first page is shared"
    occ = np.array([True, False, True])
    for _ in range(BLOCK + 2):
        tok = np.array([seqs[0][-1], 0, seqs[2][-1]])
        logits = np.asarray(pg.step(tok, occ)[0])
        for slot in (0, 2):
            seqs[slot] = same(logits[slot], seqs[slot])
    assert len(seqs[0]) == 11 + BLOCK + 3 <= T


def test_a_decoder_sees_its_counters_under_its_own_names():
    dec = _ToyDecoder(max_len=T, counters=("toy_real_tokens", "toy_calls"))
    pg = _paged(dec)
    assert pg.stats()["toy_real_tokens"] == pg.stats()["toy_calls"] == 0
    pg.admit(1, np.arange(11) % dec.V)
    occ = np.array([False, True, False])
    for _ in range(3):
        pg.step(np.array([0, 3, 0]), occ)
    st = pg.stats()
    assert (st["toy_real_tokens"], st["toy_calls"]) == (11 + 3, 4)
    assert "expert_assignments_held" not in st
