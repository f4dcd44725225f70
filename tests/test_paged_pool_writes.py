"""How the paged programs write into the page pool (ISSUE 26).

The step program writes one row a slot with ``dynamic_update_slice``,
the prefill programs whole pages, and both take the pool's buffers over
(``donate``).  What the chip's compiler makes of that is
``tests/test_tpu_compile.py``'s; here, on the CPU: the values and where
they land are what the row scatter this replaced put there, paged
decode is still bitwise the contiguous one, a shared page is never
written, and a call that dies with the pool's buffers leaves a backend
that serves again.
"""
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.serving import SlotScheduler
from mxnet_tpu.serving.paged_kv import PagedSlots, _PagedPrograms
from mxnet_tpu.serving.scheduler import _ContiguousSlots
from mxnet_tpu.telemetry import perf

L, H, D, T, V = 2, 2, 32, 32, 17
BLOCK = 8
BUCKETS = (8, 12, 16, 32)       # 12: a bucket that ends inside a page


@pytest.fixture(scope="module")
def decoder():
    net = models.transformer.transformer_lm(
        num_layers=L, num_heads=H, d_model=D, seq_len=T, vocab_size=V)
    ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                         data=(1, T), softmax_label=(1, T))
    rs = np.random.RandomState(0)
    params = {}
    for name, arr in ex.arg_dict.items():
        if name in ("data", "softmax_label"):
            continue
        arr[:] = rs.normal(0, 0.08, arr.shape).astype(np.float32)
        params[name] = arr
    return KVDecoder(params, num_layers=L, num_heads=H, max_len=T)


def _paged(decoder, slots=3, **kw):
    kw.setdefault("kernel", "gather")
    return PagedSlots(decoder, slots, block=BLOCK, prefill_buckets=BUCKETS,
                      **kw)


class _RowScatter(_PagedPrograms):
    """The writes as they were: one scatter of rows a layer, in the step
    and in the prefill (whose pad rows it writes too, where their page
    is real; nothing live)."""

    def _write_rows(self, pool, new, layer, at):
        pages, offs = (jnp.stack(x) for x in zip(*at))
        return pool.at[pages, layer, :, offs].set(new[:, :, 0])

    def _write_pages(self, pool, new, page_ids, layer):
        j = jnp.arange(new.shape[0])
        return pool.at[page_ids[j // self.block], layer, :,
                       j % self.block].set(new, mode="drop")


def _live_rows(pg, slot):
    """The pool's K and V at every position the slot has written:
    ``(2, cursor, L, H, dh)``."""
    n = int(pg.cursor[slot])
    pages = pg.bt[slot, :(n + BLOCK - 1) // BLOCK]
    out = []
    for side in pg.pool:
        rows = np.asarray(side)[pages]              # (n_pg, L, H, blk, dh)
        rows = rows.transpose(0, 3, 1, 2, 4).reshape(-1, L, H, D // H)
        out.append(rows[:n])
    return np.stack(out)


@pytest.mark.parametrize("kernel", ["gather", "pagewalk"])
def test_pool_holds_what_the_row_scatter_wrote(decoder, kernel):
    """Admissions of every bucket, ticks across page boundaries, a slot
    released and taken again: every live position of every live page,
    and every logit on the way, is bitwise what the row scatter gave."""
    new = _paged(decoder, kernel=kernel)
    old = _paged(decoder, kernel=kernel)
    old.programs = _RowScatter(decoder, BLOCK, old.max_blocks,
                               old.num_pages + 1, schedule=old.schedule)
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
    """Paged decode is bitwise the contiguous one on prompts that end
    with a page (8, 16) and on one that ends inside a page (12, whose
    bucket is no whole number of pages either): the rows the page-wise
    prefill left beyond the prompt weigh exactly nothing."""
    cont = _ContiguousSlots(decoder, 2, BUCKETS)
    pg = _paged(decoder, slots=2)
    prompt = np.random.RandomState(plen).randint(0, V, plen)
    lc = np.asarray(cont.admit(0, prompt), np.float32)
    lp = np.asarray(pg.admit(0, prompt), np.float32)
    assert np.array_equal(lc, lp)
    tok = np.array([int(lc.argmax()), 0])
    occ = np.array([True, False])
    for _ in range(T - plen - 1):
        lc = np.asarray(cont.step(tok, occ)[0], np.float32)
        lp = np.asarray(pg.step(tok, occ)[0], np.float32)
        assert np.array_equal(lc[0], lp[0])
        tok = np.array([int(lc[0].argmax()), 0])


def test_shared_page_is_never_written(decoder):
    """A page the prefix index shares is bit-identical before and after
    a second request prefills its tail behind it and both decode."""
    pg = _paged(decoder, prefix_cache=True)
    rs = np.random.RandomState(9)
    shared = rs.randint(0, V, BLOCK)
    pg.admit(0, np.concatenate([shared, rs.randint(0, V, 5)]))
    page = pg.bt[0, 0]
    assert page in pg._prefix.values()
    before = [np.asarray(side)[page].copy() for side in pg.pool]
    pg.admit(1, np.concatenate([shared, rs.randint(0, V, 7)]))
    assert pg.bt[1, 0] == page and pg.bt[1, 1] != pg.bt[0, 1]
    occ = np.array([True, True, False])
    for _ in range(6):
        pg.step(np.array([1, 2, 0]), occ)
    for side, was in zip(pg.pool, before):
        assert np.array_equal(np.asarray(side)[page], was)


def _takes_the_pool_and_dies(pool_k, pool_v, *_args):
    pool_k.delete()
    pool_v.delete()
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
    assert not any(a.is_deleted() for a in pg.pool)
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
        assert not any(a.is_deleted() for a in pg.pool)
        rows = _live_rows(pg, 0)
        pg.step(tok, occ)
        assert np.array_equal(_live_rows(pg, 0)[:, :13], rows)
    finally:
        if not was:
            perf.disable()
