"""The Ling-3.0-flash decoder family on the serving path, against the
plain float32 reference (``benchmark/reference/ling.py``), at tiny
widths on the CPU with seeded weights.

Tolerances.  Program and reference both compute in float32 here (the
package's default gives float32 arrays true float32 products), so they
differ by summation order alone: logits of magnitude ~0.5 agree to a
few 1e-7; the limit of 2e-5 leaves two orders of room and is two orders
below what serving the same weights in bfloat16 gives (~3e-3), which
``test_bfloat16_would_not_pass`` pins.
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

from benchmark.families import ling as fam  # noqa: E402
from benchmark.reference import ling as ref  # noqa: E402
from mxnet_tpu.models import ling as model  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402
from mxnet_tpu.serving.paged_kv import PagedSlots  # noqa: E402

LOGIT_TOL = 2e-5
SEED = 5
BLOCK = 16
BUCKETS = (32, 64)


def tiny_config(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ling-3.0-flash-ep4-l7.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    config.update(over)
    return config


# the step's latent attention under both of its lowerings
# (ops/latent_attention.py): the lookup by (layer, page), and the Pallas
# kernel interpreted on the CPU
LOWERINGS = {"gather": "gather", "interpret": "pallas"}


@pytest.fixture(scope="module", params=list(LOWERINGS))
def served(request):
    """(config, reference sizes, reference leaves, PagedSlots) of the
    rehearsal's three layers (KDA + dense, KDA + MoE, MLA + MoE) in
    float32, three slots, once for each lowering of the step's latent
    attention."""
    config = tiny_config()
    params = fam.serving_weights(config, SEED, jnp.float32)
    decoder = fam.build_decoder(config, params, 128, jnp.float32)
    slots = PagedSlots(decoder, num_slots=3, block=BLOCK,
                       prefill_buckets=BUCKETS, kernel=request.param)
    assert slots.stats()["latent_kernel"] == LOWERINGS[request.param]
    assert slots.stats()["kernel"] == "none"
    return config, ref.sizes_of(config), fam.reference_params(config, SEED), \
        slots


def serve(slots, slot, prompt, n_new, forced=None):
    """Admit ``prompt`` into ``slot`` and decode ``n_new`` tokens greedily
    (or ``forced``); returns (all tokens, the logits row at each of the
    ``n_new + 1`` served positions)."""
    rows = [np.asarray(slots.admit(slot, np.asarray(prompt)), np.float32)]
    toks = list(prompt)
    occupied = np.zeros(slots.num_slots, bool)
    occupied[slot] = True
    nxt = np.zeros(slots.num_slots, np.int64)
    for j in range(n_new):
        tok = int(np.argmax(rows[-1])) if forced is None else forced[j]
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


# ------------------------------------------------ (a) prefill, then decode
@pytest.mark.parametrize("prompt_len", [
    pytest.param(20, id="ends_inside_a_page"),
    pytest.param(32, id="ends_on_a_page_boundary"),
    pytest.param(37, id="second_bucket"),
    pytest.param(16, id="one_whole_page")])
def test_prefill_then_decode_agrees_with_the_reference(served, prompt_len):
    config, c, params, slots = served
    prompt = np.random.default_rng(prompt_len).integers(
        0, config["vocab_size"], prompt_len)
    toks, got = serve(slots, 1, prompt, 6)
    want = reference_rows(params, c, toks, prompt_len)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) < LOGIT_TOL


def test_a_reused_slot_starts_from_a_zero_state(served):
    """Two requests through one slot, one after the other: the second
    must not see the first one's recurrent state or latent rows."""
    config, c, params, slots = served
    rng = np.random.default_rng(11)
    for n in (45, 19):
        prompt = rng.integers(0, config["vocab_size"], n)
        toks, got = serve(slots, 2, prompt, 5)
        want = reference_rows(params, c, toks, n)
        assert np.max(np.abs(got - want)) < LOGIT_TOL


def test_slots_decode_side_by_side(served):
    """Three requests of different lengths in one step program: each row
    follows its own position, state and pages."""
    config, c, params, slots = served
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, config["vocab_size"], n) for n in (9, 32, 50)]
    rows = [[np.asarray(slots.admit(b, p), np.float32)]
            for b, p in enumerate(prompts)]
    toks = [list(p) for p in prompts]
    occupied = np.ones(3, bool)
    for _ in range(4):
        nxt = np.array([int(np.argmax(r[-1])) for r in rows], np.int64)
        for b in range(3):
            toks[b].append(int(nxt[b]))
        logits, _ = slots.step(nxt, occupied)
        logits = np.asarray(logits, np.float32)
        for b in range(3):
            rows[b].append(logits[b])
    for b in range(3):
        slots.release(b)
        want = reference_rows(params, c, toks[b], len(prompts[b]))
        assert np.max(np.abs(np.stack(rows[b]) - want)) < LOGIT_TOL


def test_bfloat16_would_not_pass(served):
    """The control of the tolerance: the same weights served in
    bfloat16 lie far outside it."""
    config, c, params, _ = served
    low = fam.build_decoder(
        config, fam.serving_weights(config, SEED, jnp.bfloat16), 128,
        jnp.bfloat16)
    slots = PagedSlots(low, num_slots=1, block=BLOCK, prefill_buckets=BUCKETS)
    prompt = np.random.default_rng(1).integers(0, config["vocab_size"], 24)
    toks, got = serve(slots, 0, prompt, 3)
    want = reference_rows(params, c, toks, len(prompt))
    assert np.max(np.abs(got - want)) > 20 * LOGIT_TOL


# ------------------------------------------- (b) chunked KDA = recurrence
def kda_inputs(T, H, d, seed, lowest=False):
    rng = np.random.default_rng(seed)
    q, k, v = (rng.standard_normal((T, H, d)).astype(np.float32)
               for _ in range(3))
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * np.sqrt(d)
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    # log decays down to the safe gate's lower bound of -5 a token
    g = -5.0 * (np.ones((T, H, d), np.float32) if lowest
                else rng.uniform(0, 1, (T, H, d)).astype(np.float32))
    beta = rng.uniform(0, 1, (T, H)).astype(np.float32)
    return q, k, v, g, beta


@pytest.mark.parametrize("T,lowest", [(16, False), (48, False), (64, True)],
                         ids=["one_chunk", "three_chunks",
                              "every_gate_at_its_lower_bound"])
def test_chunked_kda_is_the_recurrence(T, lowest):
    """Tolerance 5e-5 of the output's scale: float32 round-off through a
    16x16 triangular solve and, with every gate at its lower bound,
    ``exp(+-40)`` (an exponent's round-off times 40); a bfloat16 state
    reads 1e-2."""
    H, d = 2, 16
    q, k, v, g, beta = kda_inputs(T, H, d, T, lowest)
    S = jnp.asarray(np.random.default_rng(0).standard_normal(
        (H, d, d)).astype(np.float32))
    want, S_step = [], S[None]
    for t in range(T):
        o, S_step = model.kda_recurrent_step(
            q[t][None], k[t][None], v[t][None], g[t][None], beta[t][None],
            S_step)
        want.append(np.asarray(o[0]))
    got, S_chunk = model.kda_chunked(*(jnp.asarray(a) for a in
                                       (q, k, v, g, beta)), S)
    want = np.stack(want)
    scale = np.max(np.abs(want))
    assert np.max(np.abs(np.asarray(got) - want)) < 5e-5 * scale
    assert np.max(np.abs(np.asarray(S_chunk) - np.asarray(S_step[0]))) \
        < 5e-5 * max(1.0, float(np.max(np.abs(S_step))))


def test_a_padded_position_leaves_the_kda_state_as_it_is():
    H, d = 2, 16
    q, k, v, g, beta = kda_inputs(32, H, d, 7)
    g[20:], beta[20:] = 0.0, 0.0
    S0 = jnp.zeros((H, d, d), jnp.float32)
    args = [jnp.asarray(a) for a in (q, k, v, g, beta)]
    _, S_all = model.kda_chunked(*args, S0)
    # the state after 20 real tokens, from a run that stops at 16 + a
    # step-by-step tail
    _, S = model.kda_chunked(*(a[:16] for a in args), S0)
    S = S[None]
    for t in range(16, 20):
        _, S = model.kda_recurrent_step(*(a[t][None] for a in args), S)
    assert np.max(np.abs(np.asarray(S_all) - np.asarray(S[0]))) < 1e-6


# ---------------------------------------- (c) absorbed MLA = expanded MLA
def test_absorbed_mla_is_expanded_mla():
    """The decode form over the latent rows against the prefill form's
    last query; float32, tolerance 1e-5 of the output's scale."""
    c = model.LingConfig.from_dict(tiny_config())
    rng = np.random.default_rng(2)
    T, H = 40, c.heads
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    q_nope, q_rope = f(T, H, c.nope), f(T, H, c.rope)
    rows = f(T, c.kv_rank + c.rope)
    w_kvb = f(H * (c.nope + c.v_dim), c.kv_rank) * 0.1
    want = model.mla_expanded(q_nope, q_rope, rows, w_kvb, c, block=16)
    S = 48                                  # a table longer than the rows
    table = jnp.zeros((2, S, rows.shape[1])).at[:, :T].set(rows)
    at = jnp.array([T - 1, 12])
    valid = jnp.arange(S)[None, :] <= at[:, None]
    got = model.mla_absorbed(q_nope[at], q_rope[at], table, valid, w_kvb, c)
    scale = float(jnp.max(jnp.abs(want)))
    assert np.max(np.abs(np.asarray(got) - np.asarray(want[at]))) \
        < 1e-5 * scale


# ------------------------------------------------------ (d) the share test
def test_four_shares_add_up_to_the_uncut_layer():
    """32 experts over 4 chips of 8: the four chips' routed parts plus
    the shared expert counted once are the uncut layer of the
    reference.  Float32; 2e-5 of the output's scale."""
    E, held = 32, 8
    config = tiny_config(num_experts=E, published={"num_experts": E},
                         n_group=4, topk_group=2, num_experts_per_tok=4)
    whole = fam.reference_params(config, SEED, only="layer1_")
    w = ref.layer_leaves(whole, 1)
    c_whole = ref.sizes_of(config)
    x = jnp.asarray(np.random.default_rng(9).standard_normal(
        (24, config["hidden_size"])).astype(np.float32))
    want, _ = ref.moe(x, w, c_whole)
    shared = ref.swiglu(x, w["shared_gate_weight"], w["shared_up_weight"],
                        w["shared_down_weight"], "f32")
    total, pairs = shared, 0
    for chip in range(E // held):
        lo = chip * held
        part, counts = moe.moe_serve(
            x, w["router_weight"], w["router_bias"],
            w["experts_gate_weight"][lo:lo + held],
            w["experts_up_weight"][lo:lo + held],
            w["experts_down_weight"][lo:lo + held], expert_offset=lo,
            top_k=4, n_group=4, topk_group=2,
            scale=config["routed_scaling_factor"])
        total = total + part
        pairs += int(counts[0])
        assert int(counts[0]) + int(counts[1]) == 24 * 4
        # the reference given the same share agrees with the chip
        c_share = ref.sizes_of(dict(config, num_experts=held,
                                    expert_offset=lo))
        w_share = dict(w, **{k: w[k][lo:lo + held] for k in w
                             if k.startswith("experts_")})
        ref_part, _ = ref.moe(x, w_share, c_share)
        assert np.max(np.abs(np.asarray(part + shared - ref_part))) < 1e-6
    assert pairs == 24 * 4                  # every choice fell on one chip
    scale = float(jnp.max(jnp.abs(want)))
    assert np.max(np.abs(np.asarray(total - want))) < 2e-5 * scale


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_serve_with_the_kernel_forced_matches_the_fallback(
        monkeypatch, dtype):
    """One chip's share as ``serve_batch_ling`` has it -- experts 8..15
    of 32 held, so most pairs are absent and sort behind the last group,
    and some held experts get no row -- through the Pallas grouped
    matmul (interpreted) and through ``lax.ragged_dot``: the same rows,
    the same three counters, and the fourth says which ran."""
    E, held, lo, N, D, F, k = 32, 8, 8, 24, 128, 256, 4
    rng = np.random.default_rng(5)
    mk = lambda *s: jnp.asarray(
        0.2 * rng.standard_normal(s).astype(np.float32), jnp.dtype(dtype))
    args = (mk(N, D) * 5, mk(E, D), mk(E), mk(held, D, F), mk(held, D, F),
            mk(held, F, D))
    kw = dict(expert_offset=lo, top_k=k, n_group=4, topk_group=2,
              scale=2.5, valid=jnp.arange(N) < 20)
    want, counts = moe.moe_serve(*args, **kw)
    assert int(counts[3]) == 0 and 0 < int(counts[1]) and int(counts[0]) > 0
    monkeypatch.setattr(
        moe.gmm, "default_schedule",
        lambda *a, **k: {"impl": "pallas", "interpret": True})
    got, forced = moe.moe_serve(*args, **kw)
    assert list(np.asarray(forced)) == list(np.asarray(counts[:3])) + [1]
    tol = 1e-5 if dtype == "float32" else 2e-2
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    assert np.max(np.abs(np.asarray(got, np.float32)
                         - np.asarray(want, np.float32))) < tol * scale


# --------------------------------------------------------- (e) the router
def brute_force_choice(scores, bias, n_group, topk_group, top_k):
    E = scores.shape[0]
    sel = scores + bias
    size = E // n_group
    group_score = [np.sort(sel[g * size:(g + 1) * size])[-2:].sum()
                   for g in range(n_group)]
    kept = np.argsort(group_score)[-topk_group:]
    allowed = [e for e in range(E) if e // size in kept]
    return sorted(sorted(allowed, key=lambda e: sel[e])[-top_k:])


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_group_limited_top_k_against_a_brute_force_pick(seed):
    rng = np.random.default_rng(seed)
    N, E = 40, 64
    scores = rng.uniform(0, 1, (N, E)).astype(np.float32)
    bias = (0.2 * rng.standard_normal(E)).astype(np.float32)
    idx, wts = moe.route_group_limited(
        jnp.asarray(scores), jnp.asarray(bias), top_k=6, n_group=8,
        topk_group=3, scale=2.5)
    for n in range(N):
        assert sorted(np.asarray(idx[n]).tolist()) == brute_force_choice(
            scores[n], bias, 8, 3, 6)
    picked = np.take_along_axis(scores, np.asarray(idx), axis=1)
    want = picked / picked.sum(-1, keepdims=True) * 2.5
    assert np.allclose(np.asarray(wts), want, rtol=1e-6)


def test_the_expert_bias_changes_the_choice_and_not_the_weights():
    rng = np.random.default_rng(4)
    scores = jnp.asarray(rng.uniform(0.2, 0.8, (16, 32)).astype(np.float32))
    kw = dict(top_k=4, n_group=4, topk_group=2, scale=2.5)
    idx0, w0 = moe.route_group_limited(scores, jnp.zeros(32), **kw)
    bias = jnp.zeros(32).at[5].set(10.0)
    idx1, w1 = moe.route_group_limited(scores, bias, **kw)
    assert np.all(np.any(np.asarray(idx1) == 5, axis=1))
    assert not np.all(np.any(np.asarray(idx0) == 5, axis=1))
    # expert 5's weight is its score's share, untouched by the bias
    picked = np.take_along_axis(np.asarray(scores), np.asarray(idx1), axis=1)
    assert np.allclose(np.asarray(w1),
                       picked / picked.sum(-1, keepdims=True) * 2.5,
                       rtol=1e-6)
    # and the reference's router makes the same choices
    c = ref.sizes_of(tiny_config(num_experts=32,
                                 published={"num_experts": 32}))
    r_idx, r_w = ref.route(scores, bias, c)
    assert np.array_equal(np.sort(r_idx, 1), np.sort(np.asarray(idx1), 1))


# ---------------------------------------------- (f) no prefix reuse here
def test_a_decoder_with_per_slot_state_gets_no_prefix_hits(served):
    """Two requests that share a 32-token prefix: the second prefills
    all of its prompt (no page is shared, ``stats()`` says so), and both
    agree with the reference."""
    config, c, params, slots = served
    from mxnet_tpu import telemetry as tm

    assert slots.stats()["prefix_reuse"] is False
    hits = tm.counter("serve_prefix_hits_total", "")
    before = hits.value()
    rng = np.random.default_rng(21)
    prefix = rng.integers(0, config["vocab_size"], 32)
    for tail in (5, 9):
        prompt = np.concatenate(
            [prefix, rng.integers(0, config["vocab_size"], tail)])
        toks, got = serve(slots, 0, prompt, 4)
        want = reference_rows(params, c, toks, len(prompt))
        assert np.max(np.abs(got - want)) < LOGIT_TOL
    stats = slots.stats()
    assert stats["prefix_pages"] == 0 and hits.value() == before
    assert stats["state_slots_in_use"] == 0
    assert stats["latent_pages_in_use"] == 0


def test_stats_count_the_assignments_on_held_and_absent_experts(served):
    config, c, params, slots = served
    before = slots.stats()
    prompt = np.random.default_rng(8).integers(0, config["vocab_size"], 21)
    serve(slots, 0, prompt, 3)
    after = slots.stats()
    moe_layers = sum(m == "moe" for m in c.mlps)
    pairs = (21 + 3) * moe_layers * c.top_k     # pads and free rows not
    held = after["expert_assignments_held"] - before["expert_assignments_held"]
    absent = (after["expert_assignments_absent"]
              - before["expert_assignments_absent"])
    assert held + absent == pairs and 0 < held < pairs
    distinct = after["expert_distinct_hits"] - before["expert_distinct_hits"]
    # 4 program calls x 2 layers, at most the experts held in each
    assert 0 < distinct <= 4 * moe_layers * c.experts_held


def test_the_scheduler_serves_the_family_like_any_other():
    """``serve_decoder`` -> ``SlotScheduler`` -> ``PagedSlots``: the
    normal path, greedy, against the reference's own greedy choice."""
    from mxnet_tpu.serving import SlotScheduler

    config = tiny_config()
    decoder = fam.build_decoder(
        config, fam.serving_weights(config, SEED, jnp.float32), 128,
        jnp.float32)
    sched = SlotScheduler(decoder, num_slots=2, queue_size=4, kv_block=BLOCK,
                          prefill_buckets=BUCKETS)
    try:
        prompt = np.random.default_rng(31).integers(
            0, config["vocab_size"], 18)
        req = sched.generate(prompt, timeout=120, max_new_tokens=5,
                             temperature=0.0)
        assert req.outcome == "ok" and len(req.tokens) == 5
        assert sched.paged and sched.paged_stats()["family"] == "ling"
    finally:
        sched.close()
    c, params = ref.sizes_of(config), fam.reference_params(config, SEED)
    toks = list(prompt) + list(req.tokens)
    want = np.asarray(ref.logits(params, jnp.asarray(toks, jnp.int32), c))
    gaps, _ = ref.gaps_from_logits(want, jnp.asarray(toks, jnp.int32),
                                   len(prompt), 5)
    assert float(jnp.max(gaps)) < LOGIT_TOL
