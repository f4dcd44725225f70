"""Inference serving subsystem tests (mxnet_tpu/serving/ + the
KVDecoder slot-pool API): continuous batching must actually happen
(mid-flight slot reuse, zero per-tick recompiles after warmup),
backpressure must shed load (AdmissionQueueFull / HTTP 429), deadlines
must terminate requests, and the int8 predict path must stay within
logit-parity tolerance of fp32.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry as tm
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.serving import (AdmissionQueueFull, SlotScheduler,
                               serve_decoder, start_server)
from mxnet_tpu.serving.quantize import (QuantizedTensor,
                                        quantize_per_channel)

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


@pytest.fixture()
def metrics():
    was = tm.enabled()
    tm.enable()
    yield tm.get_registry()
    if not was:
        tm.disable()


def _post(port, body, timeout=60):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read())


# ---------------------------------------------------------------------------
# scheduler core
# ---------------------------------------------------------------------------
def test_scheduler_greedy_matches_generate(decoder):
    sched = SlotScheduler(decoder, num_slots=2, queue_size=4)
    try:
        rs = np.random.RandomState(1)
        prompt = rs.randint(0, V, 5)
        req = sched.generate(prompt, max_new_tokens=6, timeout=120)
        assert req.outcome == "ok"
        ref = decoder.generate(prompt[None], 6, temperature=0)
        assert req.tokens == ref[0].tolist()
        assert req.ttft is not None and req.ttft >= 0
    finally:
        sched.close()


def test_scheduler_cobatches_variable_lengths(decoder, metrics):
    """More concurrent requests than slots, different prompt lengths:
    every request completes with EXACTLY the tokens the per-request
    greedy decode produces, and at least one slot is reused mid-flight
    (continuous batching, not drain-and-refill)."""
    reuse = metrics.get("serve_slot_reuse_total")
    r0 = reuse.total()
    sched = SlotScheduler(decoder, num_slots=2, queue_size=16)
    try:
        rs = np.random.RandomState(2)
        prompts = [rs.randint(0, V, ln) for ln in (3, 7, 5, 9, 4, 6)]
        reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        for r in reqs:
            r.wait(120)
        assert all(r.outcome == "ok" for r in reqs)
        for p, r in zip(prompts, reqs):
            ref = decoder.generate(p[None], 5, temperature=0)
            assert r.tokens == ref[0].tolist(), (
                f"co-batched decode diverged for prompt len {len(p)}")
        assert reuse.total() - r0 > 0, "no slot was ever reused"
        assert sched.stats["slot_ticks"] > 0
    finally:
        sched.close()


def test_scheduler_sampled_requests_are_seeded(decoder):
    sched = SlotScheduler(decoder, num_slots=2, queue_size=4)
    try:
        prompt = np.array([1, 2, 3])
        a = sched.generate(prompt, max_new_tokens=6, temperature=0.8,
                           top_k=5, seed=7, timeout=120)
        b = sched.generate(prompt, max_new_tokens=6, temperature=0.8,
                           top_k=5, seed=7, timeout=120)
        assert a.outcome == b.outcome == "ok"
        assert a.tokens == b.tokens           # same seed, same stream
        assert all(0 <= t < V for t in a.tokens)
    finally:
        sched.close()


def test_scheduler_backpressure_and_validation(decoder, metrics):
    rejected = metrics.get("serve_requests_total")
    r0 = rejected.value(outcome="rejected")
    sched = SlotScheduler(decoder, num_slots=1, queue_size=1)
    try:
        blocker = sched.submit(np.array([1, 2, 3]), max_new_tokens=20)
        deadline = time.monotonic() + 30
        while sched.occupied == 0 and time.monotonic() < deadline:
            time.sleep(0.002)     # wait until the blocker owns the slot
        queued = sched.submit(np.array([4, 5]), max_new_tokens=2)
        with pytest.raises(AdmissionQueueFull):
            sched.submit(np.array([6]), max_new_tokens=2)
        assert rejected.value(outcome="rejected") - r0 >= 1
        # a prompt that can never fit any prefill bucket is rejected
        # outright, not queued
        with pytest.raises(mx.MXNetError):
            sched.submit(np.arange(T + 1), max_new_tokens=1)
        blocker.wait(120)
        queued.wait(120)
        assert blocker.outcome == "ok" and queued.outcome == "ok"
    finally:
        sched.close()


def test_scheduler_rejects_bad_sampling_params(decoder):
    """Malformed sampling params die at submit() with MXNetError — they
    must never reach the engine thread (one NaN temperature or
    oversized top_k used to kill it permanently)."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    try:
        prompt = np.array([1, 2, 3])
        for bad in ({"temperature": float("nan")},
                    {"temperature": -0.5},
                    {"top_k": 0},
                    {"top_k": V + 1},         # > vocab -> np.partition
                    {"seed": -1},
                    {"deadline_ms": float("inf")}):
            with pytest.raises(mx.MXNetError):
                sched.submit(prompt, max_new_tokens=2, **bad)
        # the engine is still alive and serving
        ok = sched.generate(prompt, max_new_tokens=2, timeout=120)
        assert ok.outcome == "ok"
    finally:
        sched.close()


def test_scheduler_explicit_zero_config(decoder):
    """Explicit zeros are validated/honored, not silently replaced by
    the env/default values."""
    with pytest.raises(mx.MXNetError):
        SlotScheduler(decoder, num_slots=0)
    with pytest.raises(mx.MXNetError):
        SlotScheduler(decoder, num_slots=1, queue_size=-1)
    sched = SlotScheduler(decoder, num_slots=1, queue_size=0)
    try:
        assert sched.queue_size == 0   # not the default 16
        with pytest.raises(AdmissionQueueFull):
            sched.submit(np.array([1]), max_new_tokens=1)
    finally:
        sched.close()


def test_engine_survives_admission_error(decoder, monkeypatch):
    """A request whose admission blows up inside the engine (injected
    prefill failure) terminates with outcome `error`; the engine thread
    survives and keeps serving."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    try:
        calls = {"n": 0}
        orig = decoder.prefill_padded

        def boom(*args, **kwargs):
            calls["n"] += 1
            if calls["n"] == 1:
                raise RuntimeError("injected prefill failure")
            return orig(*args, **kwargs)

        monkeypatch.setattr(decoder, "prefill_padded", boom)
        bad = sched.submit(np.array([1, 2]), max_new_tokens=2)
        assert bad.wait(120).outcome == "error"
        assert isinstance(bad.error, RuntimeError)
        good = sched.generate(np.array([1, 2]), max_new_tokens=2,
                              timeout=120)
        assert good.outcome == "ok"
    finally:
        sched.close()


def test_scheduler_deadline_times_out_queued_request(decoder):
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    try:
        blocker = sched.submit(np.array([1, 2, 3]), max_new_tokens=20)
        hopeless = sched.submit(np.array([4, 5]), max_new_tokens=2,
                                deadline_ms=1)
        hopeless.wait(120)
        assert hopeless.outcome == "timeout"
        blocker.wait(120)
        assert blocker.outcome == "ok"
    finally:
        sched.close()


def test_scheduler_close_terminates_requests(decoder):
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    req = sched.submit(np.array([1, 2]), max_new_tokens=25)
    sched.close()
    assert req.wait(10).outcome in ("shutdown", "ok")
    with pytest.raises(mx.MXNetError):
        sched.submit(np.array([1]), max_new_tokens=1)


def test_scheduler_capacity_truncates_at_cache_end(decoder):
    """A request whose budget exceeds the cache window is delivered
    truncated (outcome ok), never wedged: prompt bucketed to 16 leaves
    max_len-16 step positions + the prefill token."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=2)
    try:
        req = sched.generate(np.arange(9), max_new_tokens=500,
                             timeout=120)
        assert req.outcome == "ok"
        assert len(req.tokens) == T - 16 + 1
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# HTTP server end-to-end
# ---------------------------------------------------------------------------
def test_server_e2e_concurrent_zero_recompiles(decoder, metrics):
    """The acceptance path: concurrent client threads through /generate
    complete with mid-flight slot reuse and ZERO decode recompiles after
    warmup, /metrics exposes the serving families, /healthz answers."""
    server, sched = serve_decoder(decoder, port=0, num_slots=3,
                                  queue_size=16)
    port = server.server_address[1]
    try:
        rs = np.random.RandomState(3)
        # warmup: one request per prefill bucket this traffic will hit
        for plen in (3, 12):
            status, out = _post(port, {"prompt": rs.randint(0, V, plen)
                                       .tolist(), "max_tokens": 2})
            assert status == 200 and out["outcome"] == "ok"

        compiles = metrics.get("executor_compile_total")
        reuse = metrics.get("serve_slot_reuse_total")
        c0, r0 = compiles.total(), reuse.total()
        results, errors = [], []

        def client(i):
            try:
                prompt = rs.randint(0, V, 3 + i % 10).tolist()
                results.append(_post(port, {"prompt": prompt,
                                            "max_tokens": 6}))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(10)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(120)
        assert not errors, errors
        assert len(results) == 10
        assert all(s == 200 and o["outcome"] == "ok"
                   and o["n_tokens"] == 6 for s, o in results)
        assert compiles.total() - c0 == 0, \
            "serving traffic recompiled after warmup"
        assert reuse.total() - r0 > 0, "no mid-flight slot reuse"

        # ops endpoints
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=30).read().decode()
        for fam in ("serve_requests_total", "serve_ttft_seconds",
                    "serve_queue_depth", "serve_slot_occupancy",
                    "serve_tokens_total"):
            assert fam in text
        hz = json.loads(urllib.request.urlopen(
            f"http://127.0.0.1:{port}/healthz", timeout=30).read())
        assert hz["status"] == "ok" and hz["slots"] == 3
        assert hz["ticks"] > 0
    finally:
        server.shutdown()
        sched.close()


def test_server_generate_parity_and_validation(decoder):
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=4)
    port = server.server_address[1]
    try:
        prompt = [1, 5, 9, 2]
        status, out = _post(port, {"prompt": prompt, "max_tokens": 5})
        assert status == 200
        ref = decoder.generate(np.array(prompt)[None], 5, temperature=0)
        assert out["tokens"] == ref[0].tolist()
        assert out["ttft_ms"] is not None

        for bad in ({"prompt": []}, {"prompt": "hi"}, {"max_tokens": 3},
                    {"prompt": [1], "max_tokens": 0},
                    {"prompt": [1], "bogus": True},
                    # sampling params: wrong types, non-finite values
                    # (json.loads accepts NaN), and out-of-range values
                    # all get a 400 — never a dropped connection, never
                    # a dead engine thread
                    {"prompt": [1], "temperature": "hot"},
                    {"prompt": [1], "temperature": float("nan")},
                    {"prompt": [1], "temperature": -1},
                    {"prompt": [1], "top_k": 0},
                    {"prompt": [1], "top_k": 10 ** 9},
                    {"prompt": [1], "max_tokens": True},
                    {"prompt": [1], "seed": -1},
                    {"prompt": [1], "seed": 2 ** 40},
                    {"prompt": [1], "deadline_ms": -5},
                    {"prompt": [1], "eos_id": 1.5}):
            with pytest.raises(urllib.error.HTTPError) as ei:
                _post(port, bad)
            assert ei.value.code == 400, f"no 400 for {bad}"
        # after all that abuse the engine still serves
        status, out = _post(port, {"prompt": [1, 2], "max_tokens": 2})
        assert status == 200 and out["outcome"] == "ok"
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(f"http://127.0.0.1:{port}/nope",
                                   timeout=30)
        assert ei.value.code == 404
    finally:
        server.shutdown()
        sched.close()


def test_every_token_is_stamped_on_the_request_and_in_the_reply(decoder):
    """``Request.token_times``: one monotonic stamp a token, always on;
    the reply's ``token_ms`` are their offsets from receipt, so the
    first is the TTFT and the differences are the gaps between tokens;
    the terminal span carries the same gaps when someone is looking."""
    from mxnet_tpu.telemetry import tracing

    sched = SlotScheduler(decoder, num_slots=2, queue_size=8)
    try:
        reqs = [sched.submit([1, 2 + i, 3], max_new_tokens=n, temperature=0)
                for i, n in enumerate((5, 1, 3))]
        for r in reqs:
            assert r.wait(120).outcome == "ok"
    finally:
        sched.close()
    for r, n in zip(reqs, (5, 1, 3)):
        assert len(r.token_times) == len(r.tokens) == n
        assert all(b >= a for a, b in zip(r.token_times, r.token_times[1:]))
        assert r.token_times[0] - r.arrival == r.ttft
    assert tracing.spans() == []      # stamps need no tracing

    was = tracing.trace_on()
    tracing.enable_tracing(True)
    tracing.clear_spans()
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=4)
    port = server.server_address[1]
    try:
        status, out = _post(port, {"prompt": [1, 5, 9, 2], "max_tokens": 6})
        assert status == 200 and out["n_tokens"] == 6
        assert len(out["token_ms"]) == 6
        assert out["token_ms"][0] == out["ttft_ms"]
        assert out["token_ms"] == sorted(out["token_ms"])
        assert out["ttft_ms"] >= out["queue_wait_ms"]
        done = [s for s in tracing.spans() if s["name"] == "request"
                and s["request"] == out["id"]]
        assert len(done) == 1
        gaps = done[0]["gaps_ms"]
        assert len(gaps) == out["n_tokens"] - 1
        np.testing.assert_allclose(
            gaps, np.diff(out["token_ms"]), atol=2e-3)
        assert done[0]["ttft_ms"] == out["ttft_ms"]
        assert done[0]["queue_wait_ms"] == out["queue_wait_ms"]
    finally:
        server.shutdown()
        sched.close()
        tracing.enable_tracing(was)
        tracing.clear_spans()


def test_server_backpressure_returns_429(decoder):
    server, sched = serve_decoder(decoder, port=0, num_slots=1,
                                  queue_size=1)
    port = server.server_address[1]
    try:
        slow = threading.Thread(
            target=lambda: _post(port, {"prompt": [1, 2, 3],
                                        "max_tokens": 20}))
        slow.start()
        deadline = time.monotonic() + 30
        while sched.occupied == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        queued = threading.Thread(
            target=lambda: _post(port, {"prompt": [4], "max_tokens": 2}))
        queued.start()
        deadline = time.monotonic() + 30
        while sched.queue_depth == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [5], "max_tokens": 2})
        assert ei.value.code == 429
        assert ei.value.headers.get("Retry-After")
        slow.join(120)
        queued.join(120)
    finally:
        server.shutdown()
        sched.close()


def test_server_deadline_returns_504(decoder):
    server, sched = serve_decoder(decoder, port=0, num_slots=1,
                                  queue_size=4)
    port = server.server_address[1]
    try:
        blocker = threading.Thread(
            target=lambda: _post(port, {"prompt": [1, 2],
                                        "max_tokens": 20}))
        blocker.start()
        deadline = time.monotonic() + 30
        while sched.occupied == 0 and time.monotonic() < deadline:
            time.sleep(0.002)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(port, {"prompt": [3], "max_tokens": 2, "deadline_ms": 1})
        assert ei.value.code == 504
        body = json.loads(ei.value.read())
        assert body["outcome"] == "timeout"
        blocker.join(120)
    finally:
        server.shutdown()
        sched.close()


# ---------------------------------------------------------------------------
# int8 quantization
# ---------------------------------------------------------------------------
def test_quantize_per_channel_roundtrip():
    rs = np.random.RandomState(4)
    w = rs.normal(0, 0.3, (8, 16)).astype(np.float32)
    w[3] = 0.0                                 # all-zero channel
    q, scale = quantize_per_channel(w, axis=0)
    assert q.dtype == np.int8 and scale.shape == (8, 1)
    back = q.astype(np.float32) * scale
    # symmetric grid: per-channel error bounded by scale/2
    assert (np.abs(back - w) <= scale / 2 + 1e-8).all()
    assert (back[3] == 0).all() and scale[3] == 1.0  # zero row exact


def test_int8_decoder_logit_parity(lm_params, decoder):
    """int8 weights (per-channel symmetric, dequantize-in-compute) keep
    decode logits within a small fraction of the fp32 logit range, for
    prefill AND incremental steps."""
    dec8 = KVDecoder(lm_params, num_layers=L, num_heads=H, max_len=T,
                     quantize="int8")
    # int8 storage is real: the quantized entries hold int8 payloads
    # 6 matmul weights per layer + tok_embed + lm_head, all int8
    qs = [v for v in dec8.p.values() if isinstance(v, QuantizedTensor)]
    assert len(qs) == 6 * L + 2
    assert all(np.dtype(q.q.dtype) == np.int8 for q in qs)
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, V, (2, 8))
    _, ref = decoder.prefill(prompt)
    s8, got = dec8.prefill(prompt)
    ref, got = np.asarray(ref), np.asarray(got)
    tol = 0.05 * (ref.max() - ref.min())
    assert np.abs(got - ref).max() < tol
    # steps stay in tolerance too
    sref = decoder.prefill(prompt)[0]
    tokens = rs.randint(0, V, (2,))
    for _ in range(4):
        sref, lref = decoder.step(sref, tokens)
        s8, l8 = dec8.step(s8, tokens)
        assert np.abs(np.asarray(l8) - np.asarray(lref)).max() < tol
        tokens = np.asarray(lref).argmax(-1)


def test_int8_serving_end_to_end(lm_params):
    dec8 = KVDecoder(lm_params, num_layers=L, num_heads=H, max_len=T,
                     quantize="int8")
    server, sched = serve_decoder(dec8, port=0, num_slots=2,
                                  queue_size=4)
    port = server.server_address[1]
    try:
        status, out = _post(port, {"prompt": [2, 4, 6], "max_tokens": 5})
        assert status == 200 and out["outcome"] == "ok"
        ref = dec8.generate(np.array([[2, 4, 6]]), 5, temperature=0)
        assert out["tokens"] == ref[0].tolist()
    finally:
        server.shutdown()
        sched.close()


def test_int8_rejects_mesh_and_unknown_modes(lm_params):
    with pytest.raises(ValueError, match="quantize"):
        KVDecoder(lm_params, num_layers=L, num_heads=H, max_len=T,
                  quantize="int4")


# ---------------------------------------------------------------------------
# soak (excluded from tier-1: pytest -m 'not slow')
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_server_soak_poisson_load(decoder, metrics):
    """Longer continuous-batching soak: Poisson arrivals across many
    clients; everything completes, slots stay busy, no recompiles."""
    server, sched = serve_decoder(decoder, port=0, num_slots=4,
                                  queue_size=64)
    port = server.server_address[1]
    try:
        rs = np.random.RandomState(6)
        for plen in (3, 12, 20):   # warm the traffic's buckets
            _post(port, {"prompt": rs.randint(0, V, plen).tolist(),
                         "max_tokens": 2})
        compiles = metrics.get("executor_compile_total")
        c0 = compiles.total()
        results, errors = [], []

        def client(i):
            try:
                time.sleep(float(rs.exponential(0.01)))
                prompt = rs.randint(0, V, int(rs.randint(3, 24))).tolist()
                results.append(_post(port, {"prompt": prompt,
                                            "max_tokens": 8}))
            except Exception as exc:  # noqa: BLE001
                errors.append(exc)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(60)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(300)
        assert not errors, errors[:3]
        assert len(results) == 60
        assert all(s == 200 and o["outcome"] == "ok" for s, o in results)
        assert compiles.total() - c0 == 0
        assert sched.stats["slot_ticks"] / max(sched.stats["ticks"], 1) > 1
    finally:
        server.shutdown()
        sched.close()
