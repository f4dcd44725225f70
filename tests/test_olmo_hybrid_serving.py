"""The Olmo-Hybrid decoder family on the serving path, against the plain
float32 reference (``benchmark/reference/olmo_hybrid.py``), at tiny
widths on the CPU with seeded weights: the chunked gated-DeltaNet form
against the token-by-token recurrence, prefill then decode against the
reference's full forward, a prompt in chunks with other slots' ticks
between them against the same prompt in one program, and no page of a
state decoder shared across requests.

Tolerances.  Program and reference both compute in float32 here, so
they differ by summation order and the chunked form alone: logits of
magnitude ~1 agree to ~1e-6; the limit of 2e-5 leaves an order of room
and is two orders below what the bfloat16 serving of the same weights
reads (``test_bfloat16_would_not_pass``).
"""
import json
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import ling as ling_fam  # noqa: E402
from benchmark.families import olmo_hybrid as fam  # noqa: E402
from benchmark.reference import olmo_hybrid as ref  # noqa: E402
from mxnet_tpu.models import delta_rule  # noqa: E402
from mxnet_tpu.ops import gdn_scan  # noqa: E402
from mxnet_tpu.ops import paged_attention  # noqa: E402
from mxnet_tpu.serving import serve_decoder  # noqa: E402
from mxnet_tpu.serving.paged_kv import PagedSlots  # noqa: E402

LOGIT_TOL = 2e-5
SEED = 7
BLOCK = 16
BUCKETS = (32, 64)
MAX_LEN = 256


def tiny_config(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "olmo-hybrid-7b-l8.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    config.update(over)
    return config


def _slots(decoder, buckets=BUCKETS, kernel="gather", **kw):
    return PagedSlots(decoder, num_slots=3, block=BLOCK,
                      prefill_buckets=buckets, kernel=kernel, **kw)


@pytest.fixture(scope="module")
def weights():
    config = tiny_config()
    return (config, ref.sizes_of(config),
            fam.serving_weights(config, SEED, jnp.float32),
            fam.reference_params(config, SEED))


def serve(slots, slot, prompt, n_new):
    """Admit ``prompt`` into ``slot`` and decode ``n_new`` tokens
    greedily; returns (all tokens, the logits row at each of the
    ``n_new + 1`` served positions)."""
    rows = [np.asarray(slots.admit(slot, np.asarray(prompt)), np.float32)]
    toks = list(prompt)
    occupied = np.zeros(slots.num_slots, bool)
    occupied[slot] = True
    nxt = np.zeros(slots.num_slots, np.int64)
    for _ in range(n_new):
        tok = int(np.argmax(rows[-1]))
        toks.append(tok)
        nxt[slot] = tok
        logits, starved = slots.step(nxt, occupied)
        assert not starved
        rows.append(np.asarray(logits, np.float32)[slot])
    slots.release(slot)
    return toks, np.stack(rows)


def reference_rows(params, c, toks, first):
    want = np.asarray(ref.logits(params, jnp.asarray(toks, jnp.int32), c))
    return want[first - 1:]


# ------------------------------------------------------- (a) the delta rule
def _recurrence(q, k, v, g, beta, S):
    """Token by token: the one-position step with the head's gate over
    ``d_k``."""
    outs = []
    for t in range(q.shape[0]):
        o, S = delta_rule.kda_recurrent_step(
            q[t][None], k[t][None], v[t][None],
            jnp.broadcast_to(g[t][None, :, None], q[t][None].shape),
            beta[t][None], S[None])
        S = S[0]
        outs.append(o[0])
    return jnp.stack(outs), S


def _scan_inputs(T, lowest, H=3, dk=6, dv=10):
    """``(q, k, v, g, beta, S0)`` in float32: L2-normed keys, ``beta``
    over (0, 2), gates from 0 down to ``lowest`` a token, the last 9
    positions right padding (``beta = g = 0``), a starting state of its
    own."""
    rng = np.random.default_rng(T)
    l2 = lambda a: a / np.linalg.norm(a, axis=-1, keepdims=True)
    q = l2(rng.normal(size=(T, H, dk))) * dk ** -0.5
    k = l2(rng.normal(size=(T, H, dk)))
    v = rng.normal(size=(T, H, dv))
    g = rng.uniform(lowest, 0.0, (T, H))
    g[::7] = 0.0
    beta = rng.uniform(0.0, 2.0, (T, H))
    g[-9:] = 0.0
    beta[-9:] = 0.0
    S0 = rng.normal(size=(H, dk, dv))
    return [jnp.asarray(a, jnp.float32) for a in (q, k, v, g, beta, S0)]


def _rel(got, want):
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _holds_the_recurrence(got_o, got_S, args):
    """Relative error <= 1e-5 in the outputs and the state against the
    token-by-token recurrence, nothing infinite, and the padding left
    the state as it was after the last real token."""
    want_o, want_S = _recurrence(*args)
    for got, want in ((got_o, want_o), (got_S, want_S)):
        assert np.isfinite(np.asarray(got)).all()
        assert _rel(got, want) <= 1e-5
    T = args[0].shape[0]
    _, S_real = _recurrence(*(a[:T - 9] for a in args[:5]), args[5])
    assert float(jnp.max(jnp.abs(got_S - S_real))) < 1e-5


SCAN_CASES = [
    pytest.param(128, -3.0, id="two_chunks"),
    pytest.param(100, -50.0, id="gates_to_-50_padded_tail"),
    pytest.param(70, -12.0, id="one_chunk_and_a_bit")]


@pytest.mark.parametrize("T,lowest", SCAN_CASES)
def test_gdn_chunked_is_the_recurrence(T, lowest):
    """``d_k`` 6 != ``d_v`` 10, ``beta`` over (0, 2), gates from 0 down
    to ``lowest`` a token (no inf or NaN anywhere), the last 9 positions
    right padding (``beta = g = 0``: the state stays), a starting state
    of its own; relative error <= 1e-5 in the outputs and the state."""
    args = _scan_inputs(T, lowest)
    _holds_the_recurrence(*delta_rule.gdn_chunked(*args, chunk=32), args)


@pytest.mark.parametrize("T,lowest,widths", [
    pytest.param(*case.values, (6, 10), id=case.id) for case in SCAN_CASES
] + [pytest.param(256, -3.0, (96, 192), id="the_cell_widths")])
def test_gdn_scan_kernel_is_the_recurrence(T, lowest, widths):
    """``ops/gdn_scan.py``'s kernel, interpreted, in its own chunks of
    64: the cases above, and the cell's widths (``d_k`` 96, ``d_v``
    192) over 256 positions, right-padded; relative error <= 1e-5
    against the recurrence and against ``gdn_chunked``."""
    args = _scan_inputs(T, lowest, 3, *widths)
    o, S = gdn_scan.gdn_scan(*args, interpret=True)
    _holds_the_recurrence(o, S, args)
    want_o, want_S = delta_rule.gdn_chunked(*args)
    assert _rel(o, want_o) <= 1e-5 and _rel(S, want_S) <= 1e-5


def _recurrence64(q, k, v, g, beta, S):
    """The recurrence token by token in float64 (numpy): the truth the
    float32 forms are measured from where they part."""
    q, k, v, g, beta, S = (np.asarray(a, np.float64)
                           for a in (q, k, v, g, beta, S))
    outs = []
    for t in range(q.shape[0]):
        S = np.exp(g[t])[:, None, None] * S
        u = beta[t][:, None] * (v[t] - np.einsum("hk,hkv->hv", k[t], S))
        S = S + k[t][:, :, None] * u[:, None, :]
        outs.append(np.einsum("hk,hkv->hv", q[t], S))
    return np.stack(outs), S


@pytest.mark.parametrize("chunk", [64, 128])
def test_the_inverse_by_products_is_the_solve(chunk):
    """The adversarial system: ``beta`` = 2 everywhere, keys nearly
    parallel, no decay (``g`` = 0), so that ``A``'s entries are all
    near 2 and ``(I + A)`` is far from the identity (condition ~7e3 at
    64, ~3e4 at 128).  The kernel's inverse from products against
    ``solve_triangular`` on the same right-hand side: relative error
    <= 1e-5.  Over two chunks of it both float32 forms part from the
    float64 recurrence by 1.3-5.3e-5 (the XLA form by 1.3-3.4e-5, the
    kernel by 2.3-5.3e-5, measured on the CPU): each is held to 1e-4
    of it."""
    rng = np.random.default_rng(chunk)
    H, dk, dv, T = 2, 96, 192, 2 * chunk
    k = np.ones((T, H, dk)) + 0.01 * rng.normal(size=(T, H, dk))
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    k = jnp.asarray(k, jnp.float32)
    kc = k[:chunk, 0]
    A = 2.0 * jnp.tril(jnp.dot(kc, kc.T, precision="highest"), -1)
    rhs = jnp.asarray(rng.normal(size=(chunk, dk + dv)), jnp.float32)
    want = jax.scipy.linalg.solve_triangular(
        A + jnp.eye(chunk), rhs, lower=True, unit_diagonal=True)
    got = jnp.dot(gdn_scan.unit_lower_inverse(A), rhs, precision="highest")
    assert _rel(got, want) <= 1e-5
    q = k * dk ** -0.5
    v = jnp.asarray(rng.normal(size=(T, H, dv)), jnp.float32)
    args = (q, k, v, jnp.zeros((T, H)), jnp.full((T, H), 2.0),
            jnp.asarray(rng.normal(size=(H, dk, dv)), jnp.float32))
    want_o, want_S = _recurrence64(*args)
    for o, S in (gdn_scan.gdn_scan(*args, interpret=True, chunk=chunk),
                 delta_rule.gdn_chunked(*args, chunk=chunk)):
        assert _rel(np.asarray(o, np.float64), want_o) <= 1e-4
        assert _rel(np.asarray(S, np.float64), want_S) <= 1e-4


def test_the_recurrence_has_negative_eigenvalues():
    """With ``beta`` = 2 the transition ``I - beta k k^T`` turns the
    state along ``k`` round: what a key held comes back negated."""
    H, dk, dv = 1, 4, 3
    k = jnp.zeros((1, H, dk)).at[0, 0, 0].set(1.0)
    S = jnp.ones((1, H, dk, dv))
    _, S2 = delta_rule.kda_recurrent_step(
        k, k, jnp.zeros((1, H, dv)), jnp.zeros((1, H, dk)),
        jnp.full((1, H), 2.0), S)
    assert np.allclose(np.asarray(S2[0, 0, 0]), -1.0)
    assert np.allclose(np.asarray(S2[0, 0, 1:]), 1.0)


# --------------------------------------------- (b) prefill, then decode
# the Pallas kernel takes pages of whole 128-wide lanes: two heads of 128
WIDE_HEADS = {"hidden_size": 256, "num_attention_heads": 2,
              "num_key_value_heads": 2}


@pytest.mark.parametrize("prompt_len,kernel,key_block", [
    pytest.param(20, "gather", 1024, id="one_program"),
    pytest.param(100, "gather", 1024, id="four_chunks"),
    pytest.param(100, "interpret", 32, id="four_chunks_kernel_key_blocks"),
    pytest.param(64, "gather", 16, id="chunks_end_on_a_page"),
    pytest.param(100, "gather", None, id="four_chunks_one_softmax")])
def test_prefill_then_decode_agrees_with_the_reference(
        weights, prompt_len, kernel, key_block, monkeypatch):
    """A prompt admitted whole or in chunks of 64 (each behind the
    chunks before it, the state carried in the slot's row), then six
    decoded tokens, under the step's gather and the interpreted Pallas
    kernel (at heads of 128), the prefill's history a key block of 16,
    32 or 1024 positions at a time (a window over
    ``paged_attention.DENSE_WINDOW``), or with the tail in one softmax
    (``None``: this window under it): every served logits row agrees
    with the reference's full forward."""
    if key_block is not None:
        monkeypatch.setattr(paged_attention, "DENSE_WINDOW", MAX_LEN - 1)
        monkeypatch.setattr(paged_attention, "HISTORY_KEY_BLOCK", key_block)
    else:
        assert MAX_LEN <= paged_attention.DENSE_WINDOW
    config, c, params, leaves = weights
    if kernel == "interpret":
        config = tiny_config(**WIDE_HEADS)
        c = ref.sizes_of(config)
        params = fam.serving_weights(config, SEED, jnp.float32)
        leaves = fam.reference_params(config, SEED)
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    slots = _slots(decoder, kernel=kernel)
    assert slots.stats()["kernel"] == ("pallas" if kernel == "interpret"
                                       else "gather")
    assert slots.max_prompt == MAX_LEN
    prompt = np.random.default_rng(prompt_len).integers(
        0, config["vocab_size"], prompt_len)
    toks, got = serve(slots, 1, prompt, 6)
    want = reference_rows(leaves, c, toks, prompt_len)
    assert np.max(np.abs(got - want)) < LOGIT_TOL
    chunks = -(-prompt_len // 64)
    assert slots.stats()["prefill_chunks"] == chunks
    assert slots.stats()["state_chunks_carried"] == chunks - 1
    # the interpreted kernels' mode puts the chunked scan on its kernel
    assert slots.stats()["gdn_scan_on_kernel"] == (
        chunks if kernel == "interpret" else 0)


def test_the_scheduler_serves_it_greedy_as_the_reference_would(weights):
    """Through ``serve_decoder``'s scheduler: a prompt of 150 tokens (3
    chunks, one tick between each) and a short one beside it; every
    token the reference's argmax."""
    config, c, params, leaves = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=4, kv_block=BLOCK,
                                  prefill_buckets=BUCKETS)
    try:
        rng = np.random.default_rng(3)
        prompts = [rng.integers(0, config["vocab_size"], n).tolist()
                   for n in (150, 9)]
        reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        for p, r in zip(prompts, reqs):
            r.wait(120)
            assert r.outcome == "ok" and len(r.tokens) == 5
            toks = list(p)
            for tok in r.tokens:
                want = np.asarray(ref.logits(
                    leaves, jnp.asarray(toks, jnp.int32), c))[-1]
                assert tok == int(np.argmax(want))
                toks.append(tok)
        assert sched.backend.stats()["state_chunks_carried"] == 2
    finally:
        server.shutdown()
        server.server_close()
        sched.close()


def test_bfloat16_would_not_pass(weights):
    """The same weights served in bfloat16 read gaps far over the
    tolerance: the check of this file sees the precision."""
    config, c, params, leaves = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.bfloat16)
    slots = _slots(decoder)
    prompt = np.random.default_rng(1).integers(0, config["vocab_size"], 40)
    toks, got = serve(slots, 0, prompt, 3)
    want = reference_rows(leaves, c, toks, 40)
    assert np.max(np.abs(got - want)) > 50 * LOGIT_TOL


# ------------------------------------ (c) chunks with other slots' ticks
def test_chunks_with_ticks_between_them_are_one_program(weights):
    """A prompt of 150 tokens in 3 chunks of 64 into slot 0, with a
    decoding slot 1 ticked between every two chunks, against the same
    prompt in one program of 256: the same logits and the same state
    rows (the step leaves a slot between chunks as it was)."""
    config, c, params, _ = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    chunked, whole = _slots(decoder), _slots(decoder, buckets=(256,))
    rng = np.random.default_rng(9)
    prompt = rng.integers(0, config["vocab_size"], 150)
    other = rng.integers(0, config["vocab_size"], 30)
    chunked.admit(1, other)
    busy = np.array([False, True, False])
    adm = chunked.begin_admit(0, prompt)
    while adm.pending:
        got = chunked.admit_chunk(adm)
        for _ in range(2):
            chunked.step(np.full(3, 5, np.int64), busy)
    want = whole.admit(0, prompt)
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < LOGIT_TOL
    state = lambda s: s.pool[0]["state"]
    for name in decoder.paged_layout()["state"]:
        a, b = (np.asarray(state(s)[name][0], np.float32)
                for s in (chunked, whole))
        assert np.max(np.abs(a - b)) < 1e-5 * max(1.0, np.abs(b).max()), \
            name
    assert chunked.stats()["state_chunks_carried"] == 2


def test_a_reused_slot_starts_from_a_zero_state(weights):
    """Two requests through one slot, one after the other: the second's
    first chunk must not see the first one's state."""
    config, c, params, leaves = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    slots = _slots(decoder)
    rng = np.random.default_rng(11)
    for n in (90, 19):
        prompt = rng.integers(0, config["vocab_size"], n)
        toks, got = serve(slots, 2, prompt, 3)
        want = reference_rows(leaves, c, toks, n)
        assert np.max(np.abs(got - want)) < LOGIT_TOL


# ------------------------------------------ (d) no prefix across requests
def test_a_state_decoder_shares_no_page_across_requests(weights):
    """Prefix caching asked for: the layout says no, so two requests
    with the same 80-token prompt hold pages of their own, none comes
    from the index, and both read the reference's logits."""
    config, c, params, leaves = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    slots = _slots(decoder, prefix_cache=True)
    assert slots.stats()["prefix_reuse"] is False
    prompt = np.random.default_rng(13).integers(0, config["vocab_size"], 80)
    a = slots.admit(0, prompt)
    b = slots.admit(1, prompt)
    assert not set(slots._slot_pages[0]) & set(slots._slot_pages[1])
    stats = slots.stats()
    assert stats["prefix_tokens_hit"] == 0 and stats["prefix_pages"] == 0
    want = reference_rows(leaves, c, list(prompt), 80)[0]
    for got in (a, b):
        assert np.max(np.abs(np.asarray(got) - want)) < LOGIT_TOL


def test_the_scan_kernel_counter_and_its_gate(weights):
    """``gdn_scan_on_kernel`` counts the prefill programs whose scan ran
    on the kernel: none for an Olmo prompt of two chunks off the chip
    (the XLA form), none for Ling even where the mode asks for kernels;
    and the kernel's gate takes the cell's shapes (30 heads of 96 and
    192) at every prefill bucket."""
    config, _c, params, _ = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    slots = _slots(decoder, kernel="auto")
    assert slots.state_schedule == {"impl": "xla"}
    slots.admit(0, np.arange(100) % config["vocab_size"])
    stats = slots.stats()
    assert stats["prefill_chunks"] == 2 and stats["gdn_scan_on_kernel"] == 0

    ling_config = json.load(open(os.path.join(
        ROOT, "benchmark", "configs", "ling-3.0-flash-ep4-l7.json")))
    ling_config.update(ling_config.pop("rehearse"))
    ling = ling_fam.build_decoder(
        ling_config, ling_fam.serving_weights(ling_config, SEED, jnp.float32),
        128, jnp.float32)
    slots = PagedSlots(ling, num_slots=2, block=BLOCK, prefill_buckets=(32,),
                       kernel="interpret")
    assert slots.state_schedule == {"impl": "pallas", "interpret": True}
    slots.admit(0, np.arange(20) % ling_config["vocab_size"])
    assert slots.stats()["gdn_scan_on_kernel"] == 0

    for bucket in (128, 256, 512, 1024, 2048):
        assert gdn_scan.supports(bucket, 30, 96, 192)
        assert gdn_scan.on_kernel(gdn_scan.default_schedule("tpu"),
                                  bucket, 30, 96, 192)
        assert not gdn_scan.on_kernel(gdn_scan.default_schedule("cpu"),
                                      bucket, 30, 96, 192)


def test_the_layout_declares_both_kinds_of_cache(weights):
    config = weights[0]
    decoder = fam.build_decoder(config, weights[2], MAX_LEN, jnp.float32)
    layout = decoder.paged_layout()
    c = decoder.cfg
    assert c.mixers == ("gdn", "gdn", "gdn", "attn")
    assert layout["kv_pages"][:3] == (1, c.heads, c.head_dim)
    assert layout["state"]["layer0.gdn_S"] == ((c.lin_heads, c.d_k, c.d_v),
                                               jnp.float32)
    assert layout["state"]["layer0.gdn_conv"][0] == (
        3, c.lin_heads * (2 * c.d_k + c.d_v))
    assert sorted(n for n in layout["state"] if n.endswith("gdn_S")) == [
        f"layer{i}.gdn_S" for i in (0, 1, 2)]
    assert layout["chunks"] is True and layout["prefix_reuse"] is False


def test_named_scopes_are_in_both_programs(weights):
    config, _c, params, _ = weights
    decoder = fam.build_decoder(config, params, MAX_LEN, jnp.float32)
    slots = _slots(decoder)
    for text in (slots.lower_step().compile().as_text(),
                 slots.lower_prefill(64).compile().as_text()):
        for scope in ("/gdn/", "/gdn.state/", "/attn.pages/",
                      "/mlp.dense/", "/head/"):
            assert scope in text, scope
    jax.clear_caches()
