"""Autotuner tests (ISSUE 18): the schedule cache's roundtrip /
corruption / readonly / segregation contracts, the bounded search, the
paged-attention kernel's interpret-mode parity against the PR-15
gather path (every cursor and chunk edge; prefill + ragged steps +
fork-private divergence through the serving backend), the
shape-gate fallback, and zero steady-state recompiles with tuning on.
"""
import json
import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autotune as at, models, telemetry as tm
from mxnet_tpu.base import MXNetError
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.ops import residual_epilogue as repi
from mxnet_tpu.serving.paged_kv import PagedSlots
from mxnet_tpu.serving.scheduler import SlotScheduler

L, H, D, T, V = 2, 2, 32, 32, 17


@pytest.fixture(scope="module")
def lm_params():
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
    return params


@pytest.fixture(scope="module")
def decoder(lm_params):
    return KVDecoder(lm_params, num_layers=L, num_heads=H, max_len=T)


@pytest.fixture(scope="module")
def wide_decoder():
    """dh = 128: the narrowest head the Pallas gate admits (one page
    row fills whole 128-wide lanes) — the module's default D=32 model
    takes gather by the gate."""
    d_model = 128 * H
    net = models.transformer.transformer_lm(
        num_layers=L, num_heads=H, d_model=d_model, seq_len=T,
        vocab_size=V)
    ex = net.simple_bind(ctx=mx.cpu(), grad_req="null",
                         data=(1, T), softmax_label=(1, T))
    rs = np.random.RandomState(0)
    params = {}
    for name, arr in ex.arg_dict.items():
        if name in ("data", "softmax_label"):
            continue
        arr[:] = rs.normal(0, 0.04, arr.shape).astype(np.float32)
        params[name] = arr
    return KVDecoder(params, num_layers=L, num_heads=H, max_len=T)


@pytest.fixture()
def metrics():
    was = tm.enabled()
    tm.enable()
    yield tm.get_registry()
    if not was:
        tm.disable()


@pytest.fixture()
def no_cache(monkeypatch):
    """Autotuning off and in-memory winners forgotten — the default
    regime every non-cache test should run in."""
    monkeypatch.delenv("MXTPU_SCHEDULE_CACHE", raising=False)
    monkeypatch.delenv("MXTPU_PAGED_KERNEL", raising=False)
    at.reset()
    yield
    at.reset()


@pytest.fixture()
def sched_cache(tmp_path, monkeypatch):
    """A private search-mode schedule cache; state reset both sides."""
    path = str(tmp_path / "schedules.json")
    monkeypatch.setenv("MXTPU_SCHEDULE_CACHE", "search:" + path)
    monkeypatch.delenv("MXTPU_PAGED_KERNEL", raising=False)
    monkeypatch.delenv("MXTPU_AUTOTUNE_TRIALS", raising=False)
    at.reset()
    yield path
    at.reset()


def _const_bench(calls=None):
    """A bench_fn whose thunks do trivial device work; optionally
    records which candidates were measured."""
    def bench(cand):
        if calls is not None:
            calls.append(cand)
        return lambda: 0.0
    return bench


# ---------------------------------------------------------------------------
# cache plane
# ---------------------------------------------------------------------------
def test_cache_roundtrip_persists_and_reloads(sched_cache):
    won = at.ensure("k", "sig", {"impl": "a"},
                    [{"impl": "a"}, {"impl": "b"}], _const_bench(),
                    warmup=0, best_of=1)
    assert won["impl"] in ("a", "b")
    doc = json.load(open(sched_cache))
    assert doc["version"] == at.SCHEMA_VERSION
    ent = doc["entries"][at.device_kind()]["k|sig"]
    assert ent["schedule"] == won
    assert ent["trials"] == 2
    assert ent["best_us"] >= 0
    # a fresh process-state must reload the winner from disk with zero
    # new trials: reset the memo, prime through the bind path, look up
    at.reset()
    assert at.schedule_for("k", "sig", "DEFAULT") == "DEFAULT", \
        "unprimed lookup must stay a pure default read"
    at.fingerprint()                       # the executor-bind priming hook
    assert at.schedule_for("k", "sig", "DEFAULT") == won
    calls = []
    again = at.ensure("k", "sig", {"impl": "a"},
                      [{"impl": "a"}, {"impl": "b"}], _const_bench(calls),
                      warmup=0, best_of=1)
    assert again == won and calls == [], \
        "a persisted winner must be reused without re-measuring"


def test_corrupt_and_mismatched_files_fall_back(tmp_path, monkeypatch):
    good = {"version": at.SCHEMA_VERSION,
            "entries": {"cpu": {"k|s": {"schedule": {"impl": "x"}}}}}
    for name, text in [
        ("garbage.json", "{not json"),
        ("wrong_version.json", json.dumps(dict(good, version=999))),
        ("wrong_shape.json", json.dumps([1, 2, 3])),
    ]:
        p = tmp_path / name
        p.write_text(text)
        assert at.load_file(str(p)) == {}, name
    assert at.load_file(str(tmp_path / "missing.json")) == {}
    # end to end: a corrupt cache degrades to defaults, and a search
    # REPLACES it with a valid document instead of crashing
    p = tmp_path / "corrupt.json"
    p.write_text("{not json")
    monkeypatch.setenv("MXTPU_SCHEDULE_CACHE", "search:%s" % p)
    at.reset()
    at.fingerprint()
    assert at.schedule_for("k", "s", "DEFAULT") == "DEFAULT"
    at.ensure("k", "s", {"impl": "a"}, [{"impl": "a"}], _const_bench(),
              warmup=0, best_of=1)
    assert json.load(open(p))["version"] == at.SCHEMA_VERSION
    at.reset()


def test_readonly_never_writes(tmp_path, monkeypatch):
    path = tmp_path / "ro.json"
    monkeypatch.setenv("MXTPU_SCHEDULE_CACHE", "readonly:%s" % path)
    at.reset()
    calls = []
    got = at.ensure("k", "sig", {"impl": "default"},
                    [{"impl": "default"}, {"impl": "other"}],
                    _const_bench(calls), warmup=0, best_of=1)
    assert got == {"impl": "default"}
    assert calls == [], "readonly mode must never measure"
    assert not path.exists(), "readonly mode must never create the file"
    # an explicit record(persist=True) also refuses to touch disk
    at.record("k", "sig", {"impl": "other"}, 1.0, 1)
    assert not path.exists()
    # ...but a pre-existing file IS honored, byte-for-byte untouched
    doc = {"version": at.SCHEMA_VERSION,
           "entries": {at.device_kind(): {
               "k|sig": {"schedule": {"impl": "pinned"},
                         "best_us": 1.0, "trials": 1}}}}
    path.write_text(json.dumps(doc))
    before = path.read_bytes()
    at.reset()
    got = at.ensure("k", "sig", {"impl": "default"},
                    [{"impl": "default"}], _const_bench(calls),
                    warmup=0, best_of=1)
    assert got == {"impl": "pinned"} and calls == []
    assert path.read_bytes() == before
    at.reset()


def test_device_kind_segregation(tmp_path, monkeypatch):
    kind = at.device_kind()
    other = "TPU_v4" if kind != "TPU_v4" else "TPU_v5e"
    path = tmp_path / "mixed.json"
    path.write_text(json.dumps({
        "version": at.SCHEMA_VERSION,
        "entries": {
            kind: {"k|sig": {"schedule": {"impl": "mine"}}},
            other: {"k|sig": {"schedule": {"impl": "theirs"}},
                    "k2|sig": {"schedule": {"impl": "theirs"}}},
        }}))
    monkeypatch.setenv("MXTPU_SCHEDULE_CACHE", "search:%s" % path)
    at.reset()
    at.fingerprint()
    assert at.schedule_for("k", "sig", None) == {"impl": "mine"}
    assert at.schedule_for("k2", "sig", "DEFAULT") == "DEFAULT", \
        "another device kind's winners must not load here"
    # recording here must not clobber the other kind's entries
    at.ensure("k3", "sig", {"impl": "a"}, [{"impl": "a"}], _const_bench(),
              warmup=0, best_of=1)
    entries = json.load(open(path))["entries"]
    assert entries[other]["k|sig"]["schedule"] == {"impl": "theirs"}
    assert "k3|sig" in entries[kind]
    at.reset()


def test_trial_budget_and_telemetry(sched_cache, monkeypatch, metrics):
    monkeypatch.setenv("MXTPU_AUTOTUNE_TRIALS", "2")
    assert at.trials_budget() == 2
    trials = metrics.get("autotune_trials_total")
    cachec = metrics.get("autotune_cache_total")
    t0, h0, m0 = (trials.total(), cachec.value(result="hit"),
                  cachec.value(result="miss"))
    calls = []
    cands = [{"impl": "c%d" % i} for i in range(5)]
    won = at.ensure("budgeted", "sig", cands[0], cands,
                    _const_bench(calls), warmup=0, best_of=1)
    assert len(calls) == 2, "budget must cap measured candidates"
    assert won in cands[:2]
    assert trials.total() - t0 == 2
    assert cachec.value(result="miss") - m0 == 1
    # second call: the recorded winner hits, zero new trials
    calls.clear()
    again = at.ensure("budgeted", "sig", cands[0], cands,
                      _const_bench(calls), warmup=0, best_of=1)
    assert again == won and calls == []
    assert trials.total() - t0 == 2
    assert cachec.value(result="hit") - h0 == 1
    # budget 0: cached winners still honored, new searches disabled
    monkeypatch.setenv("MXTPU_AUTOTUNE_TRIALS", "0")
    assert at.ensure("budgeted", "sig", cands[0], cands,
                     _const_bench(calls), warmup=0, best_of=1) == won
    got = at.ensure("never_searched", "sig", {"impl": "d"}, cands,
                    _const_bench(calls), warmup=0, best_of=1)
    assert got == {"impl": "d"} and calls == []


def test_refused_candidate_is_counted_and_logged_on_tpu(
        sched_cache, metrics, monkeypatch, caplog):
    """A candidate whose build raises is skipped — on a TPU it is also
    counted and the first of the search logged with the compiler's
    message, so a kernel the chip refuses is not quietly replaced by
    the reference (ISSUE 21).  Off a TPU the skip stays silent: there
    the gate is doing its job."""
    from mxnet_tpu.autotune import search

    def bench(cand):
        if cand["impl"] != "gather":
            raise RuntimeError("Mosaic failed to compile TPU kernel: "
                               "Expected matmul acc to be 32-bit")
        return lambda: 0.0

    cands = [{"impl": "gather"}, {"impl": "pallas"},
             {"impl": "pallas", "interpret": True}]
    rejected = metrics.get("autotune_rejected_total")
    r0 = rejected.total()
    with caplog.at_level("WARNING", logger="mxnet_tpu.autotune"):
        won = at.ensure("paged_attention", "cpu_sig", {"impl": "gather"},
                        cands, bench, warmup=0, best_of=1)
    assert won == {"impl": "gather"}
    assert rejected.total() == r0 and not caplog.records
    monkeypatch.setattr(search, "_on_tpu", lambda: True)
    with caplog.at_level("WARNING", logger="mxnet_tpu.autotune"):
        won = at.ensure("paged_attention", "tpu_sig", {"impl": "gather"},
                        cands, bench, warmup=0, best_of=1)
    assert won == {"impl": "gather"}
    assert rejected.total() == r0 + 2
    assert len(caplog.records) == 1, "logged once per search"
    assert "Expected matmul acc to be 32-bit" in caplog.text


def test_fingerprint_epoch_invalidates_on_record(sched_cache):
    fp0 = at.fingerprint()
    at.record("k", "sig", {"impl": "a"}, 1.0, 1)
    fp1 = at.fingerprint()
    assert fp0 != fp1, \
        "a new winner must change the executor program-cache key"
    assert fp0[:2] == fp1[:2]              # same mode + path, new epoch


# ---------------------------------------------------------------------------
# paged-attention op parity
# ---------------------------------------------------------------------------
def _op_case(B=3, Hh=2, M=4, block=8, dh=128, Ll=2, seed=3,
             dtype="float32", cursors=None, scratch_slot=None):
    rs = np.random.RandomState(seed)
    P = B * M + 1
    import jax.numpy as jnp
    pool_k = jnp.asarray(rs.normal(size=(P, Ll, Hh, block, dh))
                         .astype(np.float32)).astype(dtype)
    pool_v = jnp.asarray(rs.normal(size=(P, Ll, Hh, block, dh))
                         .astype(np.float32)).astype(dtype)
    q = jnp.asarray(rs.normal(size=(B, Hh, 1, dh))
                    .astype(np.float32)).astype(dtype)
    bt = jnp.asarray(rs.permutation(np.arange(1, P))[:B * M]
                     .reshape(B, M).astype(np.int32))
    if scratch_slot is not None:
        # a slot nothing was admitted to: every entry the scratch page
        bt = bt.at[scratch_slot].set(0)
    if cursors is None:
        # ragged cursors: a nearly-empty, a mid, a nearly-full slot
        cursors = np.linspace(1, M * block - 1, B)
    cursor = jnp.asarray(np.asarray(cursors).astype(np.int32))
    return q, pool_k, pool_v, bt, cursor


def _edge_cursors(M, block, chunk):
    """One batch with every edge the kernel's loops have: the first
    row, the last row of a page, the first of the next, mid-page, either
    side of a chunk's end, the last row of the table."""
    edges = [0, block - 1, block, block + block // 2,
             chunk * block - 1, chunk * block, M * block - 1]
    return [min(c, M * block - 1) for c in edges]


def _run_op(sched, args, layer, block):
    """One jitted attention call — jitted because that is how serving
    invokes it (the bitwise contract is between compiled programs;
    eager dispatch fuses differently and drifts in the last bit)."""
    import jax

    f = jax.jit(lambda *a: pa.paged_attention(
        *a, layer, block=block, schedule=sched))
    return np.asarray(f(*args).astype("float32"))


# the kernel joins its chunks with a running max / sum / accumulator, so
# the sums over positions are reassociated against gather: f32 agrees
# to a few ulp of the output's scale
_F32_RTOL, _F32_ATOL = 2e-5, 2e-6

_PARITY = {
    # name: (heads, table width, forced chunk or None = from the budget)
    "one_chunk": (2, 4, None),           # the whole table in flight
    "h16_three_chunks": (16, 20, None),  # 64 KB pages: 8 in flight, 8+8+4
    "chunk_of_one_page": (2, 6, 1),
    "ragged_last_chunk": (2, 8, 3),      # 3+3+2
}


@pytest.mark.parametrize("case", sorted(_PARITY))
def test_pallas_interpret_f32_close_to_gather(no_cache, monkeypatch, case):
    """f32 pools with 8-row pages: the kernel against the PR-15 gather
    math at every cursor edge in one batch, with one slot whose table
    is all scratch page 0, on tables of one chunk, several, and one
    that is no multiple of the chunk."""
    Hh, M, forced = _PARITY[case]
    if forced is not None:
        monkeypatch.setattr(pa, "_MAX_CHUNK_PAGES", forced)
    chunk = pa.chunk_pages(Hh, 8, 128, "float32", M)
    assert chunk == (forced or min(M, 8))
    cursors = _edge_cursors(M, 8, chunk) + [5]
    args = _op_case(B=len(cursors), Hh=Hh, M=M, cursors=cursors,
                    scratch_slot=len(cursors) - 1)
    sched = {"impl": "pallas", "interpret": True}
    for layer in range(L):
        ref = _run_op(None, args, layer, 8)
        out = _run_op(sched, args, layer, 8)
        np.testing.assert_allclose(out, ref, rtol=_F32_RTOL,
                                   atol=_F32_ATOL, err_msg=case)


def test_pallas_old_schedule_keys_are_ignored(no_cache):
    """A winner read from a cache written before ISSUE 28 still carries
    ``grid`` / ``live_only``: it runs the one kernel there is, bit for
    bit the same program."""
    args = _op_case()
    new = _run_op({"impl": "pallas", "interpret": True}, args, 0, 8)
    for old in ({"grid": "bh", "live_only": True},
                {"grid": "flat", "live_only": False}):
        out = _run_op({"impl": "pallas", "interpret": True, **old},
                      args, 0, 8)
        assert np.array_equal(new, out), old


def test_pallas_never_reads_an_unfetched_buffer_row(no_cache, monkeypatch):
    """Pages past the cursor are never fetched, so their VMEM rows hold
    what was there before: under the TPU interpreter, which hands out
    NaN for memory never written and follows every DMA and semaphore,
    the output is finite and right, and no copy races a read."""
    import jax
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call \
        as ipc
    from jax.experimental.pallas import tpu as pltpu

    M, chunk = 6, 2
    monkeypatch.setattr(pa, "_MAX_CHUNK_PAGES", chunk)
    cursors = _edge_cursors(M, 8, chunk)
    args = _op_case(B=len(cursors), M=M, cursors=cursors)
    params = pltpu.InterpretParams(uninitialized_memory="nan",
                                   detect_races=True)
    out = np.asarray(jax.jit(lambda *a: pa._pallas_attention(
        *a, 1, 8, params))(*args))
    assert np.isfinite(out).all()
    np.testing.assert_allclose(out, _run_op(None, args, 1, 8),
                               rtol=_F32_RTOL, atol=_F32_ATOL)
    assert not ipc.races.races_found


@pytest.mark.parametrize("Hh", [2, 16])
def test_pallas_interpret_bf16_close_to_gather(no_cache, Hh):
    """bf16 pools with 16-row pages: the kernel accumulates both
    products and runs the softmax in f32 (Mosaic takes no bf16
    accumulator), gather does all of it in bf16 — so the two agree to
    bf16 rounding of the gather side.  Judged against the f32 math on
    the same (bf16-rounded) inputs: the kernel must be at least as
    close.  16 heads make a page 64 KB, so the table of 20 pages is
    walked in chunks of 8, 8 and 4."""
    M = 4 if Hh == 2 else 20
    chunk = pa.chunk_pages(Hh, 16, 128, "bfloat16", M)
    assert chunk == min(M, 8)
    cursors = _edge_cursors(M, 16, chunk)
    args = _op_case(B=len(cursors), Hh=Hh, M=M, block=16,
                    dtype="bfloat16", cursors=cursors)
    assert pa.supports(16, 128, "bfloat16")
    f32 = tuple(a.astype("float32") if a.dtype == "bfloat16" else a
                for a in args)
    sched = {"impl": "pallas", "interpret": True}
    for layer in range(L):
        exact = _run_op(None, f32, layer, 16)
        ref = _run_op(None, args, layer, 16)
        out = _run_op(sched, args, layer, 16)
        assert np.isfinite(out).all()
        scale = max(1.0, float(np.abs(exact).max()))
        assert np.abs(out - ref).max() < 4e-2 * scale, (Hh, layer)
        assert np.abs(out - exact).max() <= \
            np.abs(ref - exact).max() + 1e-2 * scale, (Hh, layer)


def test_chunk_pages_follows_the_page_not_the_table(no_cache):
    """Pages in flight come from the page's bytes and a fixed VMEM
    budget: serve_batch's 64 KB pages give 8 whatever ``max_len`` is;
    a short table is taken whole."""
    assert pa.chunk_pages(16, 16, 128, "bfloat16", 64) == 8
    assert pa.chunk_pages(16, 16, 128, "bfloat16", 256) == 8
    assert pa.chunk_pages(16, 16, 128, "float32", 256) == 4
    assert pa.chunk_pages(2, 8, 128, "float32", 4) == 4
    assert pa.chunk_pages(2, 8, 128, "float32", 4096) == \
        pa._MAX_CHUNK_PAGES
    assert pa.chunk_pages(64, 32, 256, "float32", 64) == 1


def test_shape_gate_falls_back_bit_identical(no_cache):
    """A shape the kernel cannot tile (block % 8 != 0, dh short of the
    128 lanes) takes the gather path by the gate even when the pallas
    schedule is forced — same array, bit for bit."""
    args = _op_case(block=4, dh=12)
    assert not pa.supports(4, 12, np.float32)
    # what the v5e compiler refused in rehearsal: a head narrower than
    # the 128-lane tiling, and half a packed bf16 sublane tile
    assert not pa.supports(16, 32, np.float32)
    assert not pa.supports(16, 64, "bfloat16")
    assert not pa.supports(8, 128, "bfloat16")
    assert pa.supports(8, 128, np.float32)
    assert pa.supports(16, 128, "bfloat16")
    ref = _run_op(None, args, 0, 4)
    out = _run_op({"impl": "pallas", "interpret": True}, args, 0, 4)
    assert np.array_equal(ref, out)


def test_candidate_schedules_and_keysig(no_cache):
    assert pa.candidate_schedules("cpu", 8, 128, np.float32) == \
        [{"impl": "gather"}], "compiled-pallas candidates are TPU-only"
    assert pa.candidate_schedules("tpu", 8, 128, np.float32) == \
        [{"impl": "gather"}, {"impl": "pallas"}], "one kernel, no knob"
    assert pa.candidate_schedules("tpu", 8, 32, np.float32) == \
        [{"impl": "gather"}], \
        "a head narrower than 128 lanes is gated off the kernel"
    assert pa.default_schedule("cpu", 8, 128, np.float32) == \
        {"impl": "gather"}
    assert pa.default_schedule("tpu", 8, 128, np.float32) == \
        {"impl": "pallas"}
    assert pa.default_schedule("tpu", 8, 32, np.float32) == \
        {"impl": "gather"}
    assert pa.keysig(2, 4, 8, 16, 64, np.float32) == \
        "b2h4m8k16d64_float32"


# ---------------------------------------------------------------------------
# end-to-end serving parity
# ---------------------------------------------------------------------------
def _drive(pg, seed=5):
    """Prefill + ragged steps + a mid-flight fork admission against the
    shared prefix block + dual-slot steps — the full paged life cycle,
    returning every logits array along the way."""
    rs = np.random.RandomState(seed)
    shared = rs.randint(0, V, 8).astype(np.int64)     # one full block
    fa = np.concatenate([shared, rs.randint(0, V, 8)])
    fb = np.concatenate([shared, rs.randint(0, V, 3)])  # ragged tail
    outs = [np.asarray(pg.admit(0, fa), np.float32)]
    occ = np.array([True, False, False])
    tok = np.array([int(outs[-1].argmax()), 0, 0])
    for _ in range(4):
        lg, _ = pg.step(tok, occ)
        outs.append(np.asarray(lg, np.float32))
        tok = np.array([int(outs[-1][0].argmax()), 0, 0])
    outs.append(np.asarray(pg.admit(1, fb), np.float32))
    occ = np.array([True, True, False])
    tok = np.array([tok[0], int(outs[-1].argmax()), 0])
    for _ in range(4):
        lg, _ = pg.step(tok, occ)
        outs.append(np.asarray(lg, np.float32))
        tok = np.array([int(outs[-1][0].argmax()),
                        int(outs[-1][1].argmax()), 0])
    return outs


def test_paged_slots_interpret_kernel_end_to_end(wide_decoder, no_cache):
    """The interpret-mode kernel drives the REAL serving backend —
    prefill, ragged decode steps, a fork admitting mid-flight behind
    the shared prefix block — against the gather backend: the same
    greedy token at every emission, logits within the kernel's f32
    tolerance (the prefills run no kernel: bitwise)."""
    decoder = wide_decoder
    buckets = (8, 16, 32)
    ref = _drive(PagedSlots(decoder, 3, block=8, prefill_buckets=buckets,
                            kernel="gather"))
    pg = PagedSlots(decoder, 3, block=8, prefill_buckets=buckets,
                    kernel="interpret")
    assert pg.schedule == {"impl": "pallas", "interpret": True}
    assert pg.stats()["kernel"] == "pallas"
    outs = _drive(pg)
    assert len(outs) == len(ref)
    for i, (a, b) in enumerate(zip(ref, outs)):
        assert np.array_equal(a.argmax(-1), b.argmax(-1)), \
            "interpret kernel chose another token at emission %d" % i
        np.testing.assert_allclose(
            b, a, rtol=_F32_RTOL, atol=_F32_ATOL * max(
                1.0, float(np.abs(a).max())),
            err_msg="emission %d" % i)
    assert np.array_equal(ref[0], outs[0]), "a prefill runs no kernel"


def test_paged_slots_auto(decoder, no_cache):
    """Auto with the cache off resolves to gather on a CPU host —
    bit-identical to MXTPU_PAGED_KERNEL=0."""
    buckets = (8, 16, 32)
    ref = _drive(PagedSlots(decoder, 3, block=8, prefill_buckets=buckets,
                            kernel="gather"))
    auto = PagedSlots(decoder, 3, block=8, prefill_buckets=buckets)
    assert auto.schedule is None and auto.stats()["kernel"] == "gather"
    for a, b in zip(ref, _drive(auto)):
        assert np.array_equal(a, b)


def test_paged_kernel_mode_env(decoder, no_cache, monkeypatch):
    monkeypatch.setenv("MXTPU_PAGED_KERNEL", "0")
    pg = PagedSlots(decoder, 2, block=8, prefill_buckets=(8, 16, 32))
    assert pg.schedule is None
    monkeypatch.setenv("MXTPU_PAGED_KERNEL", "bogus")
    with pytest.raises(MXNetError):
        PagedSlots(decoder, 2, block=8, prefill_buckets=(8, 16, 32))


def test_zero_recompiles_after_warmup_with_tuning_on(decoder, metrics,
                                                     sched_cache,
                                                     monkeypatch):
    """Tuning on (auto kernel, search-mode cache): the admit-time
    search picks a schedule ONCE, and warm serving traffic does zero
    traces per tick — the tuned program is as steady as the gather
    one."""
    monkeypatch.setenv("MXTPU_AUTOTUNE_TRIALS", "3")
    compiles = metrics.get("executor_compile_total")
    trials = metrics.get("autotune_trials_total")
    sched = SlotScheduler(decoder, num_slots=2, queue_size=16,
                          paged=True, kv_block=8)
    try:
        rs = np.random.RandomState(6)
        for plen in (3, 12, 20):           # warm every bucket + search
            sched.generate(rs.randint(0, V, plen), max_new_tokens=2,
                           timeout=120)
        assert os.path.exists(sched_cache), \
            "the admit-time search should have persisted a winner"
        c0, t0 = compiles.total(), trials.total()
        reqs = [sched.submit(rs.randint(0, V, ln), max_new_tokens=4)
                for ln in (3, 7, 5, 9, 4, 18)]
        for r in reqs:
            r.wait(120)
        assert all(r.outcome == "ok" for r in reqs), \
            [(r.outcome, r.error) for r in reqs]
        assert compiles.total() - c0 == 0, \
            "warm tuned serving traffic recompiled"
        assert trials.total() - t0 == 0, \
            "steady-state traffic must never re-search"
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# residual epilogue knob
# ---------------------------------------------------------------------------
def test_epilogue_tune_installs_winner_and_stays_bitwise(sched_cache):
    """tune() records a block_rows winner; the kernel's tiling is
    elementwise so EVERY block size is bitwise-identical — the knob
    can only change speed, never values."""
    import functools

    import jax
    import jax.numpy as jnp

    rows, channels = 64, 128
    won = repi.tune(rows, channels)
    assert won["block_rows"] > 0 and rows % won["block_rows"] == 0
    assert repi._block_rows_for(rows, channels, jnp.float32) == \
        won["block_rows"]
    ent = json.load(open(sched_cache))["entries"][at.device_kind()]
    assert "residual_epilogue|r64c128_float32" in ent
    rs = np.random.RandomState(1)
    x2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(np.float32))
    s2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(np.float32))
    sc = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))
    b = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))
    outs = [np.asarray(jax.jit(functools.partial(
        repi._pallas_fwd, interpret=True, block_rows=br))(x2, s2, sc, b))
        for br in (8, 16, 32, 64)]
    for o in outs[1:]:
        assert np.array_equal(outs[0], o)


def test_epilogue_unsupported_shape_keeps_default(no_cache):
    assert repi.tune(60, 100) == \
        {"block_rows": repi._default_block_rows(60)}
