"""The SDAR decoder family (generation by diffusion over blocks) on the
serving path, against the plain float32 reference
(``benchmark/reference/sdar.py``), at tiny widths on the CPU with seeded
weights.

Tolerances.  Program and reference both compute in float32 here (the
package's default gives float32 arrays true float32 products), so they
differ by summation order alone -- the program's softmax runs over the
history's pages and the block's own keys, the reference's over one
recomputed sequence: logits of magnitude ~1 agree to ~1e-6; the limit of
2e-5 leaves an order of room and is two orders below what serving the
same weights in bfloat16 gives (``test_bfloat16_would_not_pass``).
"""
import json
import os
import sys
import urllib.error
import urllib.request

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from benchmark.families import sdar as fam  # noqa: E402
from benchmark.reference import sdar as ref  # noqa: E402
from mxnet_tpu.base import MXNetError  # noqa: E402
from mxnet_tpu.models.sdar import SdarDecoder  # noqa: E402
from mxnet_tpu.ops import paged_attention as pa  # noqa: E402
from mxnet_tpu.parallel import moe  # noqa: E402
from mxnet_tpu.serving import SlotScheduler, serve_decoder  # noqa: E402
from mxnet_tpu.serving.paged_kv import PagedSlots  # noqa: E402

LOGIT_TOL = 2e-5
SEED = 5
BLOCK = 16
BUCKETS = (16, 48)
MAX_LEN = 64


def tiny_config(**over):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "sdar-30b-a3b-chat-l7.json")) as f:
        config = json.load(f)
    config.update(config.pop("rehearse"))
    # N(0, 0.02) at a hidden size of 64 leaves every product near
    # nought: weights of order one make each part of a layer count
    config.update({"initializer_range": 0.25}, **over)
    return config


class WithLogits(SdarDecoder):
    """The decoder with a head that hands the tests its logits."""

    def head(self, p, h, shape):
        x = ref.rms_norm(h.astype(jnp.float32),
                         p["final_norm_weight"].astype(jnp.float32),
                         self.cfg.eps)
        return jnp.dot(x, p["lm_head_weight"].astype(jnp.float32).T,
                       precision="highest").reshape(shape + (-1,))


@pytest.fixture(scope="module")
def model():
    """(config, reference sizes, reference leaves, serving leaves)."""
    config = tiny_config()
    return config, ref.sizes_of(config), \
        fam.reference_params(config, SEED), \
        fam.serving_weights(config, SEED, jnp.float32)


def paged(model, dtype=jnp.float32):
    config, _c, _rp, params = model
    decoder = WithLogits(params, config, max_len=MAX_LEN, dtype=dtype)
    return PagedSlots(decoder, num_slots=3, block=BLOCK,
                      prefill_buckets=BUCKETS)


@pytest.fixture(scope="module")
def slots(model):
    """One pool of three slots for the tests that drive it by hand (its
    programs compile once); each leaves every slot released."""
    return paged(model)


class Trajectory:
    """One request driven through ``PagedSlots`` by hand, a forward at a
    time, unmasking by the reference's own rule on the PROGRAM's logits:
    what ``SlotScheduler`` does, with the logits kept."""

    def __init__(self, slots, slot, prompt, n_new, steps, c):
        self.slots, self.slot, self.c, self.steps = slots, slot, c, steps
        self.n_new, n = n_new, c.block_length
        slots.admit(slot, np.asarray(prompt))
        r = len(prompt) % n
        self.block = list(prompt[len(prompt) - r:]) + [c.mask_id] * (n - r)
        self.fixed = [True] * r + [False] * (n - r)
        self.at, self.s, self.r = [-1] * n, 0, r
        self.tokens, self.unmask, self.logits, self.done = [], [], [], False

    @property
    def commits(self):
        return all(self.fixed)

    def after(self, lg):
        """Take in the logits ``(n, V)`` of the forward just made."""
        n = self.c.block_length
        if self.commits:
            self.block, self.fixed = [self.c.mask_id] * n, [False] * n
            self.at, self.s, self.r = [-1] * n, 0, 0
            return
        self.logits.append(lg)
        conf = np.max(np.asarray(jax.nn.log_softmax(lg, axis=-1)), axis=-1)
        masked = [j for j in range(n) if not self.fixed[j]]
        for j in ref.pick(conf, masked,
                          ref.unmask_count(n, self.steps, self.s)):
            self.block[j] = int(np.argmax(lg[j]))
            self.fixed[j], self.at[j] = True, self.s
        self.s += 1
        if all(self.fixed):
            self.tokens += self.block[self.r:]
            self.unmask += self.at[self.r:]
            self.done = len(self.tokens) >= self.n_new


def drive(slots, trajectories):
    """Forwards over all the trajectories' slots at once until each is
    done; a slot whose block is whole commits while the others denoise."""
    n = slots.block_n
    while not all(t.done for t in trajectories):
        live = [t for t in trajectories if not t.done]
        tokens = np.zeros((slots.num_slots, n), np.int64)
        occupied = np.zeros(slots.num_slots, bool)
        commit = np.zeros(slots.num_slots, bool)
        for t in live:
            tokens[t.slot], occupied[t.slot] = t.block, True
            commit[t.slot] = t.commits
        out, starved = slots.step(tokens, occupied, commit)
        assert not starved
        out = np.asarray(out, np.float32)
        for t in live:
            t.after(out[t.slot])
    for t in trajectories:
        slots.release(t.slot)
        t.tokens, t.unmask = t.tokens[:t.n_new], t.unmask[:t.n_new]


def same_as_generate(t, prompt, rp, c):
    """The trajectory against ``reference.generate``: the same tokens,
    the same ordinals, every denoising forward's logits within
    ``LOGIT_TOL``; returns the widest gap."""
    tokens, unmask, forwards = ref.generate(rp, prompt, t.n_new, c,
                                            steps=t.steps)
    assert t.tokens == tokens and t.unmask == unmask
    assert len(t.logits) == len(forwards)
    worst = max(float(np.max(np.abs(a - np.asarray(f[2]))))
                for a, f in zip(t.logits, forwards))
    assert worst < LOGIT_TOL
    return worst


def prompt_of(length, seed=0):
    return np.random.default_rng([SEED, seed, length]).integers(
        0, 255, length).tolist()


# prompts of 8, 9 and 11 tokens: remainder 0, 1 and 3 modulo the block;
# 6 and 7 tokens asked for are no multiple of 4 either way
@pytest.mark.parametrize("steps", [1, 2, 3, 4])
@pytest.mark.parametrize("p_len,n_new", [(8, 6), (9, 7), (11, 6)])
def test_block_decoding_agrees_with_generate(model, slots, p_len, n_new,
                                             steps):
    _config, c, rp, _p = model
    prompt = prompt_of(p_len)
    t = Trajectory(slots, 1, prompt, n_new, steps, c)
    drive(slots, [t])
    same_as_generate(t, prompt, rp, c)
    assert len(t.tokens) == n_new
    assert slots.cursor[1] == 0 and not slots._slot_pages[1]


def test_a_prompt_shorter_than_a_block_runs_no_prefill(model, slots):
    _config, c, rp, _p = model
    before = dict(slots.programs._prefill_cache)
    prompt = prompt_of(3)
    t = Trajectory(slots, 0, prompt, 5, 2, c)
    assert slots.programs._prefill_cache == before
    drive(slots, [t])
    same_as_generate(t, prompt, rp, c)


def test_slots_in_different_phases_share_a_forward(model, slots):
    """Three requests of different schedules in one pool: forwards in
    which one slot commits its block while the others denoise theirs
    give each what it gets alone."""
    _config, c, rp, _p = model
    specs = [(0, prompt_of(8, 1), 8, 1), (1, prompt_of(9, 2), 7, 4),
             (2, prompt_of(11, 3), 9, 2)]
    ts = [Trajectory(slots, slot, p, n, k, c) for slot, p, n, k in specs]
    mixed = []
    real_step = slots.step

    def watched(tokens, occupied, commit):
        mixed.append((int(commit.sum()), int(occupied.sum())))
        return real_step(tokens, occupied, commit)

    slots.step = watched
    try:
        drive(slots, ts)
    finally:
        del slots.step
    assert any(0 < c_ < o for c_, o in mixed), mixed
    for t, (_slot, p, _n, _k) in zip(ts, specs):
        same_as_generate(t, p, rp, c)


def test_bfloat16_would_not_pass(model):
    """The same weights served in bfloat16 leave ``LOGIT_TOL`` far
    behind at the first forward already (later ones may unmask another
    position): the tolerance tells the stated precision from the one
    below."""
    _config, c, rp, _p = model
    slots = paged(model, dtype=jnp.bfloat16)
    prompt = prompt_of(8)
    t = Trajectory(slots, 0, prompt, 4, 4, c)
    drive(slots, [t])
    first = ref.generate(rp, prompt, 4, c, steps=4)[2][0][2]
    gap = float(np.max(np.abs(t.logits[0] - np.asarray(first))))
    assert gap > 50 * LOGIT_TOL, gap


def test_a_prefix_hit_gives_the_logits_of_a_cold_prompt(model, slots):
    """36 tokens: two whole pages, then one block and no remainder.  The
    second admission finds both pages in the prefix index, prefills the
    last block alone behind them, and decodes what the cold one did."""
    _config, c, rp, _p = model
    assert slots.prefix_on
    prompt = prompt_of(36)
    cold = Trajectory(slots, 0, prompt, 8, 2, c)
    drive(slots, [cold])
    assert slots.stats()["prefix_pages"] == 2
    free = len(slots._free)
    warm = Trajectory(slots, 2, prompt, 8, 2, c)
    assert len(slots._free) == free - 1          # the tail's page alone
    drive(slots, [warm])
    assert warm.tokens == cold.tokens and warm.unmask == cold.unmask
    for a, b in zip(warm.logits, cold.logits):
        assert float(np.max(np.abs(a - b))) < LOGIT_TOL
    same_as_generate(warm, prompt, rp, c)


@pytest.mark.parametrize("p_len,n_new,steps", [(8, 8, 4), (9, 7, 2),
                                                (11, 9, 3)])
def test_the_streams_of_one_forward_are_generates_forwards(
        model, p_len, n_new, steps):
    """The identity the chip check rests on: one forward over the clean
    sequence and the noised copies of its generated blocks gives, at
    each copy, the logits ``generate`` saw in that forward."""
    _config, c, rp, _p = model
    prompt = prompt_of(p_len)
    tokens, unmask, forwards = ref.generate(rp, prompt, n_new, c,
                                            steps=steps)
    length, noised = 32, 16
    n_streams = max(unmask) + 1
    ids, pos, stream, fill, whole = ref.teacher_streams(
        prompt, tokens, unmask, c, length, noised, n_streams)
    assert (fill, whole) == (p_len // 4 * 4, (p_len + n_new) // 4 * 4)
    lg = np.asarray(ref.logits_of(rp, ids, pos, stream, c))
    seen = 0
    for start, s, want, _fixed in forwards:
        if start + c.block_length > whole:
            continue        # a last block the reply holds part of
        row = length + s * noised + start - fill
        got = lg[row:row + c.block_length]
        assert float(np.max(np.abs(got - np.asarray(want)))) < LOGIT_TOL
        seen += 1
    assert seen >= 2
    # and the comparison built on it finds nothing to object to
    final = np.zeros(n_streams * noised, np.int64)
    seq = np.array(prompt + tokens)
    for s in range(n_streams):
        final[s * noised:s * noised + whole - fill] = seq[fill:whole]
    gaps = ref.trajectory_gaps(*ref.reduce_rows(lg[length:], final), prompt,
                               tokens, unmask, c, noised)
    assert gaps[0] == 0.0 and gaps[1] == 0.0 and gaps[2] > 0


@pytest.mark.parametrize("fault", fam.FAULTS)
def test_a_planted_fault_opens_a_gap(model, fault):
    """The three faults of the benchmark's check on the reference's own
    trajectories (gap nought as they are): each opens one of the two
    gaps by orders more than ``LOGIT_TOL``."""
    config, c, rp, _p = model
    requests = []
    for p_len, n_new, steps in [(8, 12, 4), (9, 11, 2)]:
        prompt = prompt_of(p_len, 7)
        tokens, unmask, _f = ref.generate(rp, prompt, n_new, c, steps=steps)
        requests.append((prompt, tokens, unmask))
    config = dict(config, serving={"weights_dtype": "float32"})
    clean = fam.served(config, SEED, requests, length=32)
    assert max(clean["logit_gaps"] + clean["order_gaps"]) < LOGIT_TOL
    out = fam.served(config, SEED, requests, length=32, fault=fault)
    assert max(out["logit_gaps"] + out["order_gaps"]) > 100 * LOGIT_TOL, out


def test_softmax_top_k_routing_against_a_brute_force_pick():
    rng = np.random.default_rng(11)
    logits = rng.normal(size=(40, 16)).astype(np.float32)
    idx, wts = moe.route_softmax_topk(jnp.asarray(logits), top_k=4)
    prob = np.exp(logits - logits.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = np.argsort(-prob, axis=-1, kind="stable")[:, :4]
    assert np.array_equal(np.sort(np.asarray(idx), -1), np.sort(want, -1))
    picked = np.take_along_axis(prob, np.asarray(idx), axis=1)
    np.testing.assert_allclose(
        np.asarray(wts), picked / picked.sum(-1, keepdims=True), rtol=1e-6)
    # and through the expert layer: the dense sum over the chosen experts
    x = rng.normal(size=(40, 8)).astype(np.float32)
    router = rng.normal(size=(16, 8)).astype(np.float32)
    gate, up = (rng.normal(size=(16, 8, 6)).astype(np.float32)
                for _ in range(2))
    down = rng.normal(size=(16, 6, 8)).astype(np.float32)
    import functools
    y, counts = moe.moe_serve(
        jnp.asarray(x), jnp.asarray(router), None, jnp.asarray(gate),
        jnp.asarray(up), jnp.asarray(down), expert_offset=0, top_k=4,
        route=functools.partial(moe.route_softmax_topk, top_k=4))
    lg = x @ router.T
    prob = np.exp(lg - lg.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = np.zeros_like(x)
    for t in range(40):
        best = np.argsort(-prob[t], kind="stable")[:4]
        for e in best:
            h = x[t] @ gate[e]
            want[t] += prob[t, e] / prob[t, best].sum() * (
                (h / (1 + np.exp(-h)) * (x[t] @ up[e])) @ down[e])
    np.testing.assert_allclose(np.asarray(y), want, rtol=2e-4, atol=2e-4)
    assert list(np.asarray(counts)[:2]) == [160, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_moe_serve_with_the_kernel_forced_matches_the_fallback(
        monkeypatch, dtype):
    """The SDAR expert layer -- every expert held, softmax top-k, no
    absent pair -- through the Pallas grouped matmul (interpreted) and
    through ``lax.ragged_dot``: the same rows and counters, and the
    fourth counter says which ran."""
    import functools
    E, N, D, F, k = 16, 48, 128, 256, 4
    rng = np.random.default_rng(6)
    mk = lambda *s: jnp.asarray(
        0.2 * rng.standard_normal(s).astype(np.float32), jnp.dtype(dtype))
    args = (mk(N, D) * 5, mk(E, D), None, mk(E, D, F), mk(E, D, F),
            mk(E, F, D))
    kw = dict(expert_offset=0, top_k=k,
              route=functools.partial(moe.route_softmax_topk, top_k=k))
    want, counts = moe.moe_serve(*args, **kw)
    assert list(np.asarray(counts))[:2] + [int(counts[3])] == [N * k, 0, 0]
    monkeypatch.setattr(
        moe.gmm, "default_schedule",
        lambda *a, **k: {"impl": "pallas", "interpret": True})
    got, forced = moe.moe_serve(*args, **kw)
    assert list(np.asarray(forced)) == list(np.asarray(counts[:3])) + [1]
    tol = 1e-5 if dtype == "float32" else 2e-2
    scale = float(jnp.max(jnp.abs(want.astype(jnp.float32))))
    assert np.max(np.abs(np.asarray(got, np.float32)
                         - np.asarray(want, np.float32))) < tol * scale


@pytest.mark.parametrize("dtype,block", [("float32", 8), ("float32", 16),
                                         ("bfloat16", 16)])
def test_kernel_matches_gather_for_a_block_of_grouped_queries(dtype, block):
    """The Pallas kernel in interpret mode at ``R`` = 32 query rows on
    each of 4 K/V heads (8 query heads x a block of 4) against the
    gather lowering, limits inside a page, at its end and on the last."""
    B, Hkv, R, dh, M, L = 3, 4, 32, 128, 5, 2
    rng = np.random.default_rng(3)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.dtype(dtype))
    q = mk(B, Hkv, R, dh)
    pool_k, pool_v = mk(B * M + 1, L, Hkv, block, dh), \
        mk(B * M + 1, L, Hkv, block, dh)
    bt = jnp.asarray(rng.permutation(np.arange(1, B * M + 1)).reshape(B, M),
                     jnp.int32)
    limit = jnp.asarray([3, 2 * block - 1, M * block - 1], jnp.int32)
    assert pa.supports(block, dh, dtype)
    want = pa.paged_attention(q, pool_k, pool_v, bt, limit, 1, block=block)
    got = pa.paged_attention(q, pool_k, pool_v, bt, limit, 1, block=block,
                             schedule={"impl": "pallas"}, interpret=True)
    tol = 2e-5 if dtype == "float32" else 3e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               rtol=tol, atol=tol)


# ------------------------------------------------------- the scheduler
@pytest.fixture(scope="module")
def server(model):
    config, _c, _rp, params = model
    decoder = SdarDecoder(params, config, max_len=MAX_LEN, dtype=jnp.float32)
    srv, sched = serve_decoder(decoder, port=0, num_slots=3, kv_block=BLOCK,
                               prefill_buckets=BUCKETS)
    yield srv, sched
    srv.shutdown()
    srv.server_close()
    sched.close()


@pytest.mark.parametrize("p_len,n_new,steps", [
    (8, 8, 4), (9, 6, 2), (11, 7, 3), (3, 5, 1), (16, 9, None)])
def test_the_scheduler_serves_what_generate_gives(model, server, p_len,
                                                  n_new, steps):
    """Through ``SlotScheduler``: the head reduced on the device to a
    token and a probability a row, the schedule, the commit forwards;
    ``unmask_step`` rides in the reply.  (Ties aside, the probabilities
    order as the reference's log-probabilities do.)"""
    _config, c, rp, _p = model
    _srv, sched = server
    before = dict(sched.stats)
    prompt = prompt_of(p_len, 5)
    kw = {} if steps is None else {"denoising_steps": steps}
    req = sched.generate(prompt, max_new_tokens=n_new, **kw)
    tokens, unmask, forwards = ref.generate(rp, prompt, n_new, c,
                                            steps=steps)
    assert req.outcome == "ok", req.error
    assert req.tokens == tokens and req.unmask_step == unmask
    assert len(req.token_times) == n_new and req.ttft is not None
    # a block's tokens leave together, when it is whole
    first_block = 4 - p_len % 4
    assert len(set(req.token_times[:first_block])) == 1
    moved = {k: sched.stats[k] - before[k] for k in before}
    blocks = -(-(p_len % 4 + n_new) // 4)
    assert moved["commit_forwards"] == moved["blocks_committed"] \
        == blocks - 1               # the last block needs no commit
    assert moved["ticks"] == moved["slot_ticks"] \
        == len(forwards) + blocks - 1
    assert moved["tokens_unmasked"] == sum(len(f[3]) for f in forwards)


def test_a_block_decoders_admission_is_a_launch_alone(server):
    """Under a block decoder an admission fetches nothing: its
    ``engine.prefill`` holds ``engine.launch`` and no more (a prompt
    shorter than one block runs no program: not even that); a forward's
    ``engine.step`` holds launch, wait and the fetch of both ``(B, n)``
    arrays, int32 tokens and float32 probabilities."""
    from mxnet_tpu.telemetry import tracing

    _srv, sched = server
    was = tracing.trace_on()
    tracing.enable_tracing(True)
    tracing.clear_spans()
    try:
        for p_len in (9, 3):
            assert sched.generate(prompt_of(p_len, 5),
                                  max_new_tokens=5).outcome == "ok"
        spans = tracing.spans()
    finally:
        tracing.enable_tracing(was)
        tracing.clear_spans()

    def kids(parent):
        mine = [s for s in spans if s["parent"] == parent["sid"]]
        return [(s["name"], s.get("program"))
                for s in sorted(mine, key=lambda s: s["start_ns"])]

    long, short = [s for s in spans if s["name"] == "engine.prefill"]
    assert kids(long) == [("engine.launch", "prefill")]
    assert kids(short) == []
    steps = [s for s in spans if s["name"] == "engine.step"]
    assert steps
    for step in steps:
        assert kids(step) == [("engine.launch", "step"),
                              ("engine.wait", "step"),
                              ("engine.fetch", "step")]
    fetches = [s for s in spans if s["name"] == "engine.fetch"]
    # 3 slots of 4 positions, a token and a probability each
    assert {f["bytes"] for f in fetches} == {3 * 4 * (4 + 4)}
    assert len([s for s in spans if s["name"] == "engine.wait"]) \
        == len(steps)


def test_a_request_ends_at_the_block_that_holds_its_eos(model, server):
    _config, c, rp, _p = model
    _srv, sched = server
    prompt = prompt_of(8, 5)
    tokens, _u, _f = ref.generate(rp, prompt, 12, c)
    eos = tokens[5]
    want, unmask, _f = ref.generate(rp, prompt, 12, c, eos=eos)
    assert want == tokens[:tokens.index(eos) + 1]
    req = sched.generate(prompt, max_new_tokens=12, eos_id=eos)
    assert req.tokens == want and req.unmask_step == unmask


def test_a_temperature_is_refused(server):
    srv, sched = server
    with pytest.raises(MXNetError, match="temperature must be 0"):
        sched.submit([1, 2, 3, 4, 5], temperature=0.7)
    body = json.dumps({"prompt": [1, 2, 3, 4, 5], "max_tokens": 4,
                       "temperature": 0.5}).encode()
    with pytest.raises(urllib.error.HTTPError) as refused:
        urllib.request.urlopen(urllib.request.Request(
            "http://127.0.0.1:%d/generate" % srv.server_address[1],
            data=body, headers={"Content-Type": "application/json"}))
    assert refused.value.code == 400
    reply = json.loads(urllib.request.urlopen(urllib.request.Request(
        "http://127.0.0.1:%d/generate" % srv.server_address[1],
        data=json.dumps({"prompt": [1, 2, 3, 4, 5], "max_tokens": 4,
                         "denoising_steps": 2}).encode(),
        headers={"Content-Type": "application/json"})).read())
    assert reply["outcome"] == "ok" and len(reply["unmask_step"]) == 4
    assert set(reply["unmask_step"]) <= {0, 1}


def test_what_a_block_decoder_is_refused(model):
    config, _c, _rp, params = model
    decoder = SdarDecoder(params, config, max_len=MAX_LEN, dtype=jnp.float32)
    with pytest.raises(MXNetError, match="multiple of the decoder's"):
        PagedSlots(decoder, num_slots=2, block=2, prefill_buckets=BUCKETS)
    with pytest.raises(MXNetError, match="served paged"):
        SlotScheduler(decoder, num_slots=2, paged=False,
                      prefill_buckets=BUCKETS)
