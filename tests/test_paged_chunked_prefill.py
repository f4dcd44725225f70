"""Admission in chunks and prefix reuse over a layout's own page rows
(ISSUE 33), at tiny widths on the CPU.

A prompt whose tail is longer than the largest prefill bucket goes into
its slot as chunks of that bucket, each a prefill program whose ``hist``
is what the slot's pages already hold; a cached prefix is picked up the
same way.  Both rest on one thing: a prefill's tail attends over
``[history | own rows]``.  Here: chunks, one program and the plain
reference agree; a prefix hit gives a cold admission's logits; the
allocator never hands a pinned page out; the scheduler ticks its
occupied slots between two chunks; a K/V layout gets chunks too, and a
layout with per-slot state is still refused.

Float32 throughout; program against reference differs by summation
order alone (a few 1e-7 on logits of ~0.5; held to 2e-5).
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax.numpy as jnp  # noqa: E402

from benchmark import harness  # noqa: E402
from benchmark.families import kimi as fam, ling as ling_fam  # noqa: E402
from benchmark.reference import gpt2 as gpt2_ref, kimi as ref  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.models.decode import KVDecoder  # noqa: E402
from mxnet_tpu.serving import SlotScheduler  # noqa: E402
from mxnet_tpu.serving.paged_kv import PagedSlots, PoolExhausted  # noqa: E402
from mxnet_tpu.telemetry import tracing  # noqa: E402

LOGIT_TOL = 2e-5
SEED = 9
BLOCK = 16
MAX_LEN = 256


def _config(name):
    with open(os.path.join(ROOT, "benchmark", "configs", name)) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    return config


@pytest.fixture(scope="module")
def kimi():
    """(config, reference sizes, reference leaves, decoder) of the
    rehearsal's three layers in float32."""
    config = _config("kimi-k2-instruct-ep32-l6.json")
    decoder = fam.build_decoder(
        config, fam.serving_weights(config, SEED, jnp.float32), MAX_LEN,
        jnp.float32)
    return config, ref.sizes_of(config), fam.reference_params(config, SEED), \
        decoder


def _slots(decoder, buckets, **kw):
    return PagedSlots(decoder, num_slots=kw.pop("num_slots", 2), block=BLOCK,
                      prefill_buckets=buckets, **kw)


def _last_row(params, c, prompt):
    return np.asarray(ref.logits(params, jnp.asarray(prompt, jnp.int32),
                                 c))[-1]


# ------------------------------------------- chunks = one program = reference
@pytest.mark.parametrize("plen", [
    pytest.param(65, id="one_token_over_a_chunk"),
    pytest.param(100, id="a_chunk_and_a_part"),
    pytest.param(128, id="two_whole_chunks"),
    pytest.param(203, id="three_chunks_and_a_part")])
def test_a_prompt_in_chunks_is_the_prompt_in_one_program(kimi, plen):
    config, c, params, decoder = kimi
    prompt = np.random.default_rng(plen).integers(0, config["vocab_size"],
                                                  plen)
    chunked = _slots(decoder, (32, 64))
    whole = _slots(decoder, (32, 64, MAX_LEN))
    before = chunked.stats()["prefill_chunks"]
    got = np.asarray(chunked.admit(0, prompt), np.float32)
    assert chunked.stats()["prefill_chunks"] - before == -(-plen // 64)
    one = np.asarray(whole.admit(0, prompt), np.float32)
    assert whole.stats()["prefill_chunks"] == 1
    want = _last_row(params, c, prompt)
    assert np.max(np.abs(got - want)) < LOGIT_TOL
    assert np.max(np.abs(one - want)) < LOGIT_TOL
    # and decoding goes on from the chunks' pages as from the one
    # program's
    tok = np.array([int(np.argmax(want)), 0], np.int64)
    occupied = np.array([True, False])
    a = np.asarray(chunked.step(tok, occupied)[0], np.float32)[0]
    b = np.asarray(whole.step(tok, occupied)[0], np.float32)[0]
    want2 = _last_row(params, c, list(prompt) + [int(tok[0])])
    assert np.max(np.abs(a - want2)) < LOGIT_TOL
    assert np.max(np.abs(b - want2)) < LOGIT_TOL


# ----------------------------------------------------------- the prefix index
def test_a_prefix_hit_gives_the_logits_of_a_cold_admission(kimi):
    """The second ask of a document finds the document's whole pages in
    the index and prefills the question alone, over the document's
    latent rows: same logits as a backend that never saw the document,
    and ``stats()`` counts the tokens that came from shared pages."""
    config, c, params, decoder = kimi
    rng = np.random.default_rng(4)
    doc = rng.integers(0, config["vocab_size"], 150)
    asks = [np.concatenate([doc, rng.integers(0, config["vocab_size"], n)])
            for n in (9, 21, 5)]
    slots = _slots(decoder, (32, 64))
    for k, prompt in enumerate(asks):
        before = slots.stats()
        got = np.asarray(slots.admit(0, prompt), np.float32)
        after = slots.stats()
        slots.release(0)
        hit = after["prefix_tokens_hit"] - before["prefix_tokens_hit"]
        assert after["prompt_tokens"] - before["prompt_tokens"] == len(prompt)
        # the document's 9 whole pages, once the first ask has left them
        assert hit == (150 // BLOCK * BLOCK if k else 0)
        cold = np.asarray(_slots(decoder, (32, 64)).admit(0, prompt),
                          np.float32)
        want = _last_row(params, c, prompt)
        assert np.max(np.abs(got - want)) < LOGIT_TOL
        assert np.max(np.abs(cold - want)) < LOGIT_TOL
    assert slots.stats()["prefix_reuse"] is True


def test_the_index_needs_the_layouts_flag_and_nothing_else(kimi):
    """``paged_kv.py`` names no family: a decoder whose layout says
    ``prefix_reuse`` False gets neither hits nor chunks, whatever its
    pages are."""
    config, _, _, decoder = kimi

    class NoReuse:
        def __init__(self, inner):
            self.__dict__.update(inner.__dict__)
            self.forward = inner.forward
            self._layout = dict(inner.paged_layout(), prefix_reuse=False)

        family, mesh = "kimi", None

        def paged_layout(self):
            return self._layout

    slots = _slots(NoReuse(decoder), (32, 64))
    prompt = np.random.default_rng(1).integers(0, config["vocab_size"], 60)
    for _ in range(2):
        slots.admit(0, prompt)
        slots.release(0)
    assert slots.stats()["prefix_tokens_hit"] == 0
    assert slots.max_prompt == 64
    with pytest.raises(MXNetError, match="cannot go in chunks"):
        slots.begin_admit(0, np.zeros(65, np.int64))


def test_eviction_never_hands_out_a_pinned_chains_page(kimi):
    """A pool with room for little more than one request: an admission
    that hits a chain pins it before it allocates, so the pages the
    allocator evicts to feed its tail are never its own prefix -- the
    logits stay those of a cold admission -- and a pool whose pages are
    all pinned by live requests refuses and leaves them alone."""
    config, c, params, decoder = kimi
    rng = np.random.default_rng(12)
    slots = _slots(decoder, (32, 64), num_pages=MAX_LEN // BLOCK + 2)
    doc = rng.integers(0, config["vocab_size"], 130)
    other = rng.integers(0, config["vocab_size"], 150)
    slots.admit(0, doc)
    slots.release(0)                    # 8 pages stay, held by the index
    slots.admit(1, other)
    slots.release(1)                    # 9 more: one page is free
    assert (len(slots._free), len(slots._prefix)) == (1, 17)
    first = np.concatenate([doc, rng.integers(0, config["vocab_size"], 90)])
    adm = slots.begin_admit(0, first)   # 8 shared, 6 owned: 5 evictions
    assert adm.n_shared == 8 and len(slots._prefix) == 12
    shared = adm.row[:adm.n_shared]
    assert len(set(adm.row)) == len(adm.row)        # no page twice
    assert not set(shared) & set(adm.row[adm.n_shared:])
    assert all(slots._ref[pg] == 2 for pg in shared)
    while adm.pending:
        got = slots.admit_chunk(adm)
    want = _last_row(params, c, first)
    assert np.max(np.abs(np.asarray(got, np.float32) - want)) < LOGIT_TOL
    with pytest.raises(PoolExhausted):
        slots.begin_admit(1, rng.integers(0, config["vocab_size"], 200))
    # what the failed attempt took went back (index pages it evicted on
    # the way are free now); none of it is the live request's
    assert not slots._slot_pages[1]
    assert not set(slots._free) & set(adm.row)
    assert all(slots._ref[pg] >= 1 for pg in adm.row)
    slots.release(0)


# ------------------------------------------------------------ the scheduler
def test_decoding_slots_tick_between_an_admissions_chunks(kimi):
    """One short request decoding, one long prompt arriving: the engine
    runs one chunk, then one tick of the occupied slot, until the long
    prompt is whole; its slot is not occupied before that.  Read off
    the span ring: every ``engine.prefill_chunk`` but the last is
    followed by an ``engine.tick``, and both requests get the
    reference's tokens."""
    config, c, params, decoder = kimi
    rng = np.random.default_rng(2)
    short = rng.integers(0, config["vocab_size"], 20)
    long = rng.integers(0, config["vocab_size"], 180)     # 3 chunks of 64
    was = tracing.trace_on()
    tracing.enable_tracing(True)
    tracing.clear_spans()
    sched = SlotScheduler(decoder, num_slots=2, prefill_buckets=(32, 64),
                          kv_block=BLOCK)
    try:
        first = sched.submit(short, max_new_tokens=40)
        while not first.tokens:
            threading.Event().wait(0.01)
        second = sched.submit(long, max_new_tokens=4)
        for req in (first, second):
            req.wait(300)
            assert req.outcome == "ok"
    finally:
        sched.close()
        tracing.enable_tracing(was)
    spans = [s for s in tracing.spans()
             if s["name"] in ("engine.prefill_chunk", "engine.tick")]
    chunks = [i for i, s in enumerate(spans)
              if s["name"] == "engine.prefill_chunk"]
    assert len(chunks) == 3
    assert [spans[i]["hist"] for i in chunks] == [0, 64, 128]
    assert [spans[i]["tokens"] for i in chunks] == [64, 64, 52]
    assert [spans[i]["last"] for i in chunks] == [False, False, True]
    for i in chunks[:-1]:
        nxt = spans[i + 1]
        assert nxt["name"] == "engine.tick" and nxt["occupied"] == 1
    for prompt, req in ((short, first), (long, second)):
        lg = np.asarray(ref.logits(params, jnp.asarray(
            list(prompt) + req.tokens, jnp.int32), c))
        for j, tok in enumerate(req.tokens):
            row = lg[len(prompt) - 1 + j]
            assert row.max() - row[tok] < LOGIT_TOL
    stats = sched.paged_stats()
    assert stats["prefill_chunks"] == 1 + 3
    tracing.clear_spans()


def test_a_prompt_over_the_cache_window_is_refused_at_submit(kimi):
    decoder = kimi[3]
    sched = SlotScheduler(decoder, num_slots=1, prefill_buckets=(32, 64),
                          kv_block=BLOCK)
    try:
        assert sched.backend.max_prompt == MAX_LEN
        with pytest.raises(MXNetError, match="exceeds what an admission"):
            sched.submit(np.zeros(MAX_LEN + 1, np.int64))
    finally:
        sched.close()


# --------------------------------------------------- other layouts of pages
def test_a_kv_decoder_admits_a_long_prompt_in_chunks():
    """The GPT-2 block over K/V pages: a prompt over its largest bucket
    goes in chunks and gives its reference's logits, then decodes."""
    cell = harness.resolve("serve_batch", rehearse=True)
    gpt2 = harness.load_family(cell.config["family"])
    c = gpt2.sizes(cell.config)
    max_len = 128
    decoder = KVDecoder(
        gpt2.serving_weights(cell.config, SEED, jnp.float32), c["n_layer"],
        c["n_head"], max_len=max_len, dtype=jnp.float32)
    slots = PagedSlots(decoder, num_slots=1, block=BLOCK,
                       prefill_buckets=(16, 32), kernel="gather")
    assert slots.max_prompt == max_len
    prompt = np.random.default_rng(5).integers(0, c["vocab_size"], 77)
    got = np.asarray(slots.admit(0, prompt), np.float32)
    assert slots.stats()["prefill_chunks"] == 3
    params = gpt2.reference_params(cell.config, SEED, max_len=max_len)
    toks = list(prompt)
    want = np.asarray(gpt2_ref.logits(
        params, jnp.asarray(toks, jnp.int32)[None], c["n_head"]))[0]
    scale = np.abs(want[-1]).max()
    assert np.max(np.abs(got - want[-1])) < 1e-5 * scale
    tok = int(np.argmax(got))
    step = np.asarray(slots.step(np.array([tok]), np.array([True]))[0],
                      np.float32)[0]
    want = np.asarray(gpt2_ref.logits(
        params, jnp.asarray(toks + [tok], jnp.int32)[None], c["n_head"]))[0]
    assert np.max(np.abs(step - want[-1])) < 1e-5 * scale


def test_a_decoder_with_state_is_still_refused_over_its_largest_bucket():
    """Ling's layout holds a recurrent state a slot, which nothing
    snapshots at a chunk's end: no chunks, no hits, as before."""
    config = _config("ling-3.0-flash-ep4-l7.json")
    decoder = ling_fam.build_decoder(
        config, ling_fam.serving_weights(config, SEED, jnp.float32), 128,
        jnp.float32)
    sched = SlotScheduler(decoder, num_slots=1, prefill_buckets=(32, 64),
                          kv_block=BLOCK)
    try:
        assert sched.backend.max_prompt == 64
        with pytest.raises(MXNetError, match="exceeds what an admission"):
            sched.submit(np.zeros(65, np.int64))
        with pytest.raises(MXNetError, match="cannot go in chunks"):
            sched.backend.admit(0, np.zeros(65, np.int64))
        assert not sched.backend._slot_pages[0]
        ok = sched.generate(np.arange(64) % config["vocab_size"],
                            max_new_tokens=2)
        assert ok.outcome == "ok" and len(ok.tokens) == 2
    finally:
        sched.close()


@pytest.mark.parametrize("paged", [False, True])
def test_the_scheduler_drives_one_admission_interface(paged):
    """Both pools take a prompt as ``begin_admit`` then ``admit_chunk``
    until nothing is pending; the contiguous pool's admission is one
    program, never in chunks, and serves what the paged one serves."""
    cell = harness.resolve("serve_batch", rehearse=True)
    gpt2 = harness.load_family(cell.config["family"])
    c = gpt2.sizes(cell.config)
    decoder = KVDecoder(
        gpt2.serving_weights(cell.config, SEED, jnp.float32), c["n_layer"],
        c["n_head"], max_len=128, dtype=jnp.float32)
    sched = SlotScheduler(decoder, num_slots=2, prefill_buckets=(16, 32),
                          paged=paged, kv_block=BLOCK if paged else None)
    try:
        backend = sched.backend
        assert backend.paged is paged
        calls = []
        for name in ("begin_admit", "admit_chunk"):
            real = getattr(backend, name)
            setattr(backend, name, lambda *a, _n=name, _r=real, **kw: (
                calls.append(_n), _r(*a, **kw))[1])
        prompt = np.arange(20) % c["vocab_size"]
        done = sched.generate(prompt, max_new_tokens=3)
        assert done.outcome == "ok" and len(done.tokens) == 3
        assert calls == ["begin_admit", "admit_chunk"]
        adm = backend.begin_admit(1, prompt)
        assert adm.pending and not adm.chunked
        backend.admit_chunk(adm)
        assert not adm.pending
        backend.release(1)
        want = [int(t) for t in done.tokens]
    finally:
        sched.close()
    ref_logits = np.asarray(gpt2_ref.logits(
        gpt2.reference_params(cell.config, SEED),
        jnp.asarray(list(prompt), jnp.int32)[None], c["n_head"]))[0]
    assert want[0] == int(np.argmax(ref_logits[-1]))
