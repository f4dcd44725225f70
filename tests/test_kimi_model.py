"""The Kimi-K2 decoder family (the DeepSeek-V3 block: MLA in every
layer, a low-rank query, YaRN, a sigmoid router with a correction bias
and a shared expert) on the serving path, against the plain float32
reference (``benchmark/reference/kimi.py``), at tiny widths on the CPU
with seeded weights.

Tolerances.  Program and reference both compute in float32 here, so
they differ by summation order alone: logits of magnitude ~0.5 agree
to a few 1e-7; the limit of 2e-5 leaves two orders of room and is two
orders below what serving the same weights in bfloat16 gives, which
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

import jax.numpy as jnp  # noqa: E402

from benchmark.families import kimi as fam  # noqa: E402
from benchmark.reference import kimi as ref  # noqa: E402
from mxnet_tpu.models import kimi as model, mla  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402
from mxnet_tpu.serving import SlotScheduler  # noqa: E402
from mxnet_tpu.serving.paged_kv import PagedSlots  # noqa: E402

LOGIT_TOL = 2e-5
SEED = 5
BLOCK = 16
BUCKETS = (32, 64)
CONFIG = os.path.join(ROOT, "benchmark", "configs",
                      "kimi-k2-instruct-ep32-l6.json")


def published_config():
    with open(CONFIG) as f:
        config = json.load(f)
    config.pop("rehearse")
    return config


def tiny_config(**over):
    with open(CONFIG) as f:
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
    rehearsal's three layers (one dense, two MoE) in float32, three
    slots, once for each lowering of the step's latent attention."""
    config = tiny_config()
    params = fam.serving_weights(config, SEED, jnp.float32)
    decoder = fam.build_decoder(config, params, 128, jnp.float32)
    slots = PagedSlots(decoder, num_slots=3, block=BLOCK,
                       prefill_buckets=BUCKETS, kernel=request.param)
    assert slots.stats()["latent_kernel"] == LOWERINGS[request.param]
    assert slots.stats()["kernel"] == "none"
    return config, ref.sizes_of(config), fam.reference_params(config, SEED), \
        slots


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


# ------------------------------------------------ (a) prefill, then decode
@pytest.mark.parametrize("prompt_len", [
    pytest.param(20, id="ends_inside_a_page"),
    pytest.param(32, id="ends_on_a_page_boundary"),
    pytest.param(37, id="second_bucket"),
    pytest.param(16, id="one_whole_page"),
    pytest.param(101, id="two_chunks")])
def test_prefill_then_decode_agrees_with_the_reference(served, prompt_len):
    config, c, params, slots = served
    prompt = np.random.default_rng(prompt_len).integers(
        0, config["vocab_size"], prompt_len)
    toks, got = serve(slots, 1, prompt, 6)
    want = reference_rows(params, c, toks, prompt_len)
    assert got.shape == want.shape
    assert np.max(np.abs(got - want)) < LOGIT_TOL


def test_slots_decode_side_by_side(served):
    """Three requests of different lengths in one step program: each row
    follows its own position and pages."""
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


def test_the_layout_is_latent_pages_and_nothing_else(served):
    layout = served[3].decoder.paged_layout()
    c = served[1]
    assert set(layout["pages"]) == {"latent"}
    assert layout["pages"]["latent"][:2] == (len(c.mlps),
                                             c.kv_rank + c.rope)
    assert layout["state"] == {} and layout["prefix_reuse"] is True
    assert "kv_pages" not in layout
    assert served[3].stats()["prefix_reuse"] is True


# --------------------------------------------------------------- (b) YaRN
def test_yarn_numbers_of_the_published_configuration():
    """``low``, ``high`` and ``m(1)`` as ISSUE 33 reckons them, in the
    program and in the reference alike."""
    config = published_config()
    c = model.KimiConfig.from_dict(config)
    r = ref.sizes_of(config)
    assert mla.yarn_correction_range(50000.0, 64, 4096, 1.0, 1.0) == (19, 20)
    assert ref.yarn_range(r) == (19, 20)
    assert c.attn_mscale == pytest.approx(1.34657, abs=1e-5)
    assert ref.mscale_of(32.0, 1.0) == pytest.approx(1.34657, abs=1e-5)
    assert ref.softmax_scale(r) == pytest.approx(
        192 ** -0.5 * 1.34657 ** 2, rel=1e-5)
    got, want = np.asarray(c.rope_freq()), np.asarray(ref.yarn_inv_freq(r))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    base = 50000.0 ** (-np.arange(0, 64, 2) / 64)
    # pairs below ``low`` keep their frequency, pairs from ``high`` on
    # turn 32 times slower
    np.testing.assert_allclose(got[:20], base[:20], rtol=1e-5)
    np.testing.assert_allclose(got[20:], base[20:] / 32, rtol=1e-5)


def test_rotary_positions_are_the_references():
    config = tiny_config()
    c, r = model.KimiConfig.from_dict(config), ref.sizes_of(config)
    x = jnp.asarray(np.random.default_rng(0).standard_normal(
        (9, 2, c.rope)).astype(np.float32))
    pos = jnp.asarray([0, 1, 5, 17, 33, 64, 65, 100, 127])
    got = mla.rope(x, pos, c.rope_freq())
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(ref.rope(x, pos, r)), atol=1e-6)


# ------------------------------------------- (c) the forms of the attention
def attention_inputs(c, T, seed=2):
    rng = np.random.default_rng(seed)
    f = lambda *s: jnp.asarray(rng.standard_normal(s).astype(np.float32))
    return (f(T, c.heads, c.nope), f(T, c.heads, c.rope),
            f(T, c.kv_rank + c.rope),
            f(c.heads * (c.nope + c.v_dim), c.kv_rank) * 0.1)


def test_absorbed_mla_is_expanded_mla_under_the_yarn_scale():
    """The decode form over the latent rows against the prefill form's
    queries, with ``attn_mscale`` in both; float32, 1e-5 of the
    output's scale.  The scale is really there: without it the output
    differs."""
    c = model.KimiConfig.from_dict(tiny_config())
    T = 40
    q_nope, q_rope, rows, w_kvb = attention_inputs(c, T)
    want = mla.mla_expanded(q_nope, q_rope, rows, w_kvb, c, block=16)
    S = 48                                  # a table longer than the rows
    table = jnp.zeros((2, S, rows.shape[1])).at[:, :T].set(rows)
    at = jnp.array([T - 1, 12])
    valid = jnp.arange(S)[None, :] <= at[:, None]
    got = mla.mla_absorbed(q_nope[at], q_rope[at], table, valid, w_kvb, c)
    scale = float(jnp.max(jnp.abs(want)))
    assert np.max(np.abs(np.asarray(got) - np.asarray(want[at]))) \
        < 1e-5 * scale
    import dataclasses
    plain = dataclasses.replace(c, attn_mscale=1.0)
    other = mla.mla_expanded(q_nope, q_rope, rows, w_kvb, plain, block=16)
    assert np.max(np.abs(np.asarray(other) - np.asarray(want))) \
        > 1e-3 * scale


@pytest.mark.parametrize("hist,tail", [(0, 40), (16, 24), (32, 8), (48, 21)])
def test_a_tail_behind_its_history_is_the_whole_sequence(hist, tail):
    """``mla_expanded(history=...)`` over a tail of ``tail`` rows behind
    ``hist`` rows of a table (key blocks of 16: one to three of them,
    the last partly masked) against the same function over the whole
    sequence from position 0."""
    c = model.KimiConfig.from_dict(tiny_config())
    T = hist + tail
    q_nope, q_rope, rows, w_kvb = attention_inputs(c, T, seed=hist)
    want = mla.mla_expanded(q_nope, q_rope, rows, w_kvb, c, block=16)[hist:]
    # what lies behind the history in the table is not the tail's to see
    table = jnp.full((80, rows.shape[1]), 7.0).at[:hist].set(rows[:hist])
    got = mla.mla_expanded(
        q_nope[hist:], q_rope[hist:], rows[hist:], w_kvb, c,
        history=(table, jnp.int32(hist)), block=16, key_block=16)
    scale = float(jnp.max(jnp.abs(want)))
    assert np.max(np.abs(np.asarray(got) - np.asarray(want))) < 1e-5 * scale


# ------------------------------------------------------ (d) the share test
def test_four_shares_add_up_to_the_uncut_layer():
    """16 experts over 4 chips of 4: the four chips' routed parts plus
    the shared expert counted once are the uncut layer of the
    reference.  Float32; 2e-5 of the output's scale."""
    E, held = 16, 4
    config = tiny_config(n_routed_experts=E,
                         published={"n_routed_experts": E})
    whole = fam.reference_params(config, SEED, only="layer1_")
    w = ref.layer_leaves(whole, 1)
    c_whole = ref.sizes_of(config)
    x = jnp.asarray(np.random.default_rng(4).standard_normal(
        (24, config["hidden_size"])).astype(np.float32))
    want, _ = ref.moe(x, w, c_whole)
    shared = ref.swiglu(x, w["shared_gate_weight"], w["shared_up_weight"],
                        w["shared_down_weight"], "f32")
    total = shared
    held_pairs = 0
    for share in range(E // held):
        lo = share * held
        y, counts = moe.moe_serve(
            x, w["router_weight"], w["router_bias"],
            w["experts_gate_weight"][lo:lo + held],
            w["experts_up_weight"][lo:lo + held],
            w["experts_down_weight"][lo:lo + held], expert_offset=lo,
            top_k=c_whole.top_k, n_group=c_whole.n_group,
            topk_group=c_whole.topk_group, scale=c_whole.scale)
        total = total + y
        held_pairs += int(counts[0])
    # every token's top_k choices fell on exactly one share each
    assert held_pairs == 24 * c_whole.top_k
    scale = float(jnp.max(jnp.abs(want)))
    assert np.max(np.abs(np.asarray(total) - np.asarray(want))) \
        < 2e-5 * scale
    # and the correction bias really steers the choice
    assert float(jnp.max(jnp.abs(w["router_bias"]))) > 0


# ------------------------------------------------------------ (e) serving
def test_stats_count_the_assignments_on_held_and_absent_experts(served):
    config, c, _, slots = served
    before = slots.stats()
    prompt = np.random.default_rng(8).integers(0, config["vocab_size"], 30)
    serve(slots, 0, prompt, 2)
    after = slots.stats()
    moe_layers = sum(m == "moe" for m in c.mlps)
    pairs = (30 + 2) * c.top_k * moe_layers
    moved = {k: after[k] - before[k] for k in (
        "expert_assignments_held", "expert_assignments_absent")}
    assert sum(moved.values()) == pairs and moved[
        "expert_assignments_held"] > 0
    assert after["prompt_tokens"] - before["prompt_tokens"] == 30


def test_the_scheduler_serves_the_family_like_any_other():
    config = tiny_config()
    params = fam.serving_weights(config, SEED, jnp.float32)
    decoder = fam.build_decoder(config, params, 128, jnp.float32)
    sched = SlotScheduler(decoder, num_slots=2, prefill_buckets=BUCKETS,
                          kv_block=BLOCK)
    try:
        rng = np.random.default_rng(6)
        prompts = [rng.integers(0, config["vocab_size"], n)
                   for n in (18, 90, 40)]
        reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        c = ref.sizes_of(config)
        ref_params = fam.reference_params(config, SEED)
        for p, r in zip(prompts, reqs):
            r.wait(120)
            assert r.outcome == "ok" and len(r.tokens) == 5
            toks = list(p) + r.tokens
            lg = np.asarray(ref.logits(
                ref_params, jnp.asarray(toks, jnp.int32), c))
            # every served token is the reference's own greedy choice,
            # to the tolerance of the logits
            for j, tok in enumerate(r.tokens):
                row = lg[len(p) - 1 + j]
                assert row.max() - row[tok] < LOGIT_TOL
        assert sched.paged_stats()["family"] == "kimi"
    finally:
        sched.close()
