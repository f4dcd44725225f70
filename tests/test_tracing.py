"""Tracing + SLO plane tests (ISSUE 16): W3C traceparent grammar, the
bounded span ring, end-to-end propagation through a router retry (one
trace id across router and replica lanes, joined by fleetstat), TTFT
measured from request receipt (>= queue wait + prefill on a saturated
queue), queue-depth-derived Retry-After, SLO burn-rate math + the /slo
endpoint, the serve_slow fault site, and — the deployability bar —
bit-identical scheduler outputs with tracing off vs on.
"""
import importlib.util
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import faults, models, telemetry as tm
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.serving import (NoReplicaAvailable, ReplicaRouter,
                               SlotScheduler, serve_decoder,
                               start_router)
from mxnet_tpu.telemetry import tracing

L, H, D, T, V = 2, 2, 32, 32, 17
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


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


@pytest.fixture()
def traced(monkeypatch):
    """Tracing on, everything sampled, every tick recorded; restores."""
    monkeypatch.setenv("MXTPU_TRACE_SAMPLE", "1")
    monkeypatch.setattr(tracing, "TICK_EVERY", 1)
    was = tracing.trace_on()
    tracing.enable_tracing(True)
    tracing.clear_spans()
    yield
    tracing.enable_tracing(was)
    tracing.clear_spans()


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        "mxtpu_" + name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _post(port, body, timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/generate",
        data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def _get(port, path, timeout=30):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return json.loads(r.read())


# ---------------------------------------------------------------------------
# traceparent grammar
# ---------------------------------------------------------------------------
def test_traceparent_mint_parse_roundtrip():
    tp = tracing.mint_traceparent(sampled=True)
    ctx = tracing.parse_traceparent(tp)
    assert len(ctx["trace"]) == 32 and len(ctx["parent"]) == 16
    assert ctx["sampled"] is True
    assert tracing.parse_traceparent(
        tracing.mint_traceparent(sampled=False))["sampled"] is False
    # child: same trace, fresh parent span id
    child = tracing.child_traceparent(ctx["trace"], True)
    cctx = tracing.parse_traceparent(child)
    assert cctx["trace"] == ctx["trace"]
    assert cctx["parent"] != ctx["parent"]
    # the router records its attempt span under the SAME id it forwards
    sid = tracing.mint_span_id()
    reused = tracing.parse_traceparent(
        tracing.child_traceparent(ctx["trace"], False, sid))
    assert reused["parent"] == sid and reused["sampled"] is False


def test_traceparent_malformed_degrades_to_none():
    bad = [None, "", "garbage", "01-" + "a" * 32 + "-" + "b" * 16 + "-01",
           "00-" + "a" * 31 + "-" + "b" * 16 + "-01",
           "00-" + "a" * 32 + "-" + "b" * 15 + "-01",
           "00-" + "g" * 32 + "-" + "b" * 16 + "-01", 42]
    for header in bad:
        assert tracing.parse_traceparent(header) is None
    # case-insensitive per the W3C grammar
    up = ("00-" + "A" * 32 + "-" + "B" * 16 + "-01").upper()
    assert tracing.parse_traceparent(up)["trace"] == "a" * 32


def test_span_ring_is_bounded(monkeypatch, metrics):
    monkeypatch.setenv("MXTPU_SPAN_RING", "16")
    tracing.clear_spans()
    try:
        for i in range(40):
            tracing.record_span("s%d" % i, "replica", "t" * 32, 0.001)
        got = tracing.spans()
        assert len(got) == 16                       # oldest fell off
        assert got[-1]["name"] == "s39"
        assert len({s["sid"] for s in got}) == 16   # sids unique
        # per-trace filter
        tracing.record_span("x", "router", "u" * 32, 0.0)
        assert [s["name"] for s in tracing.spans("u" * 32)] == ["x"]
    finally:
        tracing.clear_spans()


# ---------------------------------------------------------------------------
# SLO plane math
# ---------------------------------------------------------------------------
def test_slo_plane_burn_math_and_exemplars(metrics):
    plane = tracing.SloPlane(ttft_ms=100, avail=0.9)   # budget = 0.1
    for _ in range(8):
        plane.record(True, ttft_s=0.01)
    plane.record(False)                                 # availability bad
    plane.record(True, ttft_s=0.2, trace="e" * 32)      # ttft bad
    snap = plane.snapshot()
    w = snap["windows"]["60s"]
    assert w["requests"] == 10
    assert w["bad_availability"] == 1 and w["bad_ttft"] == 1
    # bad fraction / budget: 1/10 / 0.1 = 1.0 exactly at the objective
    assert w["burn_rate"]["availability"] == pytest.approx(1.0)
    # ttft denominator is requests WITH a ttft observation (9 of 10);
    # the snapshot rounds burn rates to 4 decimals
    assert w["burn_rate"]["ttft"] == pytest.approx((1 / 9) / 0.1,
                                                   abs=1e-3)
    assert snap["violations_total"] == {"availability": 1, "ttft": 1}
    # the slowest TTFT carries its exemplar trace id
    assert snap["exemplars"][0]["trace"] == "e" * 32
    assert snap["exemplars"][0]["ttft_ms"] == pytest.approx(200.0)
    assert snap["error_budget"] == pytest.approx(0.1)


def test_slo_endpoint_and_metric_families(metrics):
    router = ReplicaRouter(replicas=["127.0.0.1:9"], scrape_s=30)
    rsrv = start_router(router, port=0)
    try:
        router.slo.record(True, ttft_s=0.001, trace="a" * 32)
        router.slo.record(False, trace="b" * 32)
        slo = _get(rsrv.server_address[1], "/slo")
        assert slo["objectives"]["availability"] == router.slo.avail
        assert slo["windows"]["5s"]["requests"] == 2
        assert slo["violations_total"]["availability"] == 1
        # snapshot() refreshed the gauges: families live in the registry
        text = tm.generate_text(tm.get_registry())
        assert "slo_burn_rate" in text
        assert "slo_violations_total" in text
        tracing.record_span("x", "router", "c" * 32, 0.0)
        assert "trace_spans_total" in tm.generate_text(tm.get_registry())
        tracing.clear_spans()
    finally:
        rsrv.shutdown()
        router.stop()


# ---------------------------------------------------------------------------
# e2e propagation: router retry -> replica -> finished
# ---------------------------------------------------------------------------
def _stub_shed_replica():
    """An HTTP replica that looks healthy (/healthz) but sheds every
    POST /generate with a 503 — the first routing choice that forces a
    traced re-route."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _H(BaseHTTPRequestHandler):
        def do_GET(self):
            body = json.dumps({"status": "ok", "slots": 2, "occupied": 0,
                               "queue_depth": 0, "queue_size": 16,
                               "ticks": 0}).encode()
            self.send_response(200)
            self.send_header("Content-Type", "application/json")
            self.send_header("Content-Length", str(len(body)))
            self.end_headers()
            self.wfile.write(body)

        def do_POST(self):
            n = int(self.headers.get("Content-Length", "0") or 0)
            self.rfile.read(n)
            self.send_response(503)
            self.send_header("Content-Length", "0")
            self.end_headers()

        def log_message(self, *args):
            pass

    class _S(ThreadingHTTPServer):
        daemon_threads = True

    srv = _S(("127.0.0.1", 0), _H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, "127.0.0.1:%d" % srv.server_address[1]


def test_e2e_trace_through_retry_and_fleetstat(decoder, metrics, traced,
                                               tmp_path, capsys):
    """One request bounces off a shedding replica, finishes on a real
    one, and the whole story — route, both attempts, queue wait,
    prefill, admit, decode ticks, terminal — lands under ONE trace id
    with router and replica lanes, joinable by `fleetstat.py trace`."""
    stub, stub_addr = _stub_shed_replica()
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=16)
    real_addr = "127.0.0.1:%d" % server.server_address[1]
    # the stub is listed FIRST: equal load ties keep dict order, so the
    # first attempt sheds and the retry carries the same trace onward
    router = ReplicaRouter(replicas=[stub_addr, real_addr], scrape_s=0.1)
    rsrv = start_router(router, port=0)
    rport = rsrv.server_address[1]
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            rows = router.replicas()
            if all(r["ok"] for r in rows.values()):
                break
            time.sleep(0.05)
        st, out, hdr = _post(rport, {"prompt": [1, 2, 3], "max_tokens": 4})
        assert st == 200
        tid = hdr["X-MXTPU-Trace"]
        assert len(tid) == 32
        assert out["trace"] == tid             # reply body names it too
        assert "queue_wait_ms" in out
        assert hdr["X-MXTPU-Replica"] == real_addr

        spans = tracing.spans(trace=tid)
        names = [s["name"] for s in spans]
        for need in ("route", "attempt", "queue_wait", "prefill",
                     "admit", "decode_tick", "request"):
            assert need in names, f"missing span {need!r} in {names}"
        # the shed attempt and the successful one, same trace
        attempts = [s for s in spans if s["name"] == "attempt"]
        assert sorted(str(a["status"]) for a in attempts) == ["200", "503"]
        assert {s["svc"] for s in spans} == {"router", "replica"}
        # parentage: attempts hang off the route span; the replica's
        # spans hang off the span id the router forwarded (= the
        # successful attempt's own sid)
        route = next(s for s in spans if s["name"] == "route")
        assert all(a["parent"] == route["sid"] for a in attempts)
        ok_att = next(a for a in attempts if str(a["status"]) == "200")
        qw = next(s for s in spans if s["name"] == "queue_wait")
        assert qw["parent"] == ok_att["sid"]

        # fleetstat joins router + replica buffers into one timeline
        fs = _load_tool("fleetstat")
        outj = str(tmp_path / "trace.json")
        rc = fs.main(["trace", tid, "--router", "127.0.0.1:%d" % rport,
                      "-o", outj])
        assert rc == 0
        listing = capsys.readouterr().out
        shown = [ln.split()[3] for ln in listing.splitlines()[2:]
                 if ln.strip() and "wrote" not in ln]
        assert len(shown) >= 5                 # >=5 named spans rendered
        # corrected start order: the queue wait starts before prefill,
        # prefill before the first decode tick (the terminal "request"
        # span starts at ARRIVAL, so it sorts near the queue wait)
        assert shown.index("queue_wait") < shown.index("prefill") \
            < shown.index("decode_tick")
        assert "request" in shown
        with open(outj) as f:
            doc = json.load(f)
        evs = doc["traceEvents"]
        lanes = {e["args"]["name"] for e in evs if e["ph"] == "M"}
        assert any("router" in ln for ln in lanes)
        assert any("replica" in ln for ln in lanes)
        assert sum(1 for e in evs if e["ph"] == "X") == len(spans)
    finally:
        rsrv.shutdown()
        router.stop()
        stub.shutdown()
        server.shutdown()
        sched.close()


def test_tracing_off_records_nothing_and_spans_json(decoder, metrics):
    """With MXTPU_TRACE off the fleet still mints/propagates trace ids
    (log correlation is free) but the span buffer stays empty, and
    /spans.json says so."""
    tracing.enable_tracing(False)
    tracing.clear_spans()
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=16)
    addr = "127.0.0.1:%d" % server.server_address[1]
    router = ReplicaRouter(replicas=[addr], scrape_s=0.1)
    rsrv = start_router(router, port=0)
    try:
        st, out, hdr = _post(rsrv.server_address[1],
                             {"prompt": [1, 2], "max_tokens": 3})
        assert st == 200 and len(hdr["X-MXTPU-Trace"]) == 32
        payload = _get(rsrv.server_address[1], "/spans.json")
        assert payload["trace_on"] is False
        assert payload["spans"] == []
        assert "offset_s" in payload["clock"]
    finally:
        rsrv.shutdown()
        router.stop()
        server.shutdown()
        sched.close()


# ---------------------------------------------------------------------------
# TTFT from request receipt (satellite a)
# ---------------------------------------------------------------------------
def test_ttft_includes_queue_wait_on_saturated_queue(decoder, metrics,
                                                     traced):
    """One slot, several requests: the queued request's TTFT must be
    measured from submission (receipt), so ttft >= queue_wait +
    prefill — queue time can never be hidden from the SLO."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=8)
    try:
        reqs = [sched.submit([1, 2, 3, 4], max_new_tokens=8, temperature=0,
                             trace="%032x" % i, sampled=True)
                for i in range(3)]
        for r in reqs:
            r.wait(120)
            assert r.outcome == "ok"
        last = reqs[-1]
        assert last.queue_wait > 0          # it genuinely queued
        assert last.ttft >= last.queue_wait
        pf = next(s for s in tracing.spans(trace=last.trace)
                  if s["name"] == "prefill")
        assert last.ttft >= last.queue_wait + pf["dur_s"] - 5e-3
        # the metric families observed both components
        text = tm.generate_text(tm.get_registry())
        assert "serve_queue_wait_seconds" in text
        assert "serve_ttft_seconds" in text
    finally:
        sched.close()


# ---------------------------------------------------------------------------
# Retry-After from fleet queue depth (satellite b)
# ---------------------------------------------------------------------------
def test_retry_after_tracks_fleet_queue_depth():
    router = ReplicaRouter(replicas=["h1:1", "h2:1"], scrape_s=30)

    def _load(qd, draining=False, ok=True):
        for a in router._replicas:
            router._replicas[a].update(
                ok=ok, draining=draining,
                health={"slots": 2, "occupied": 0, "queue_depth": qd,
                        "queue_size": 64})

    _load(0)
    shallow = router.retry_after_s()
    _load(16)
    deep = router.retry_after_s()
    _load(80)
    deeper = router.retry_after_s()
    assert shallow < deep < deeper           # deeper queue pushes out
    assert shallow == 1 and deep == 1 + 32 // 4
    _load(10 ** 6)
    assert router.retry_after_s() == 30      # clamped
    _load(0, draining=True)
    assert router.retry_after_s() == 10      # nothing routable: drain
    _load(0, ok=False)
    assert router.retry_after_s() == 10      # ...or restart timescale


def test_router_503_carries_derived_retry_after(metrics):
    """The HTTP 503 reply's Retry-After is retry_after_s(), not a
    constant — an empty/unroutable fleet answers the 10 s drain
    timescale, and the reply still names the trace."""
    router = ReplicaRouter(replicas=["127.0.0.1:9"], scrape_s=30)
    rsrv = start_router(router, port=0)
    try:
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(rsrv.server_address[1], {"prompt": [1]})
        err = ei.value
        assert err.code == 503
        assert err.headers["Retry-After"] == str(router.retry_after_s())
        assert int(err.headers["Retry-After"]) == 10
        assert len(err.headers["X-MXTPU-Trace"]) == 32
        body = json.loads(err.read())
        assert body["trace"] == err.headers["X-MXTPU-Trace"]
    finally:
        rsrv.shutdown()
        router.stop()


# ---------------------------------------------------------------------------
# serve_slow fault site: injectable TTFT pressure
# ---------------------------------------------------------------------------
@pytest.mark.slow
def test_serve_slow_fault_parks_the_engine(decoder, metrics, monkeypatch):
    """MXTPU_FAULT_PLAN=serve_slow:drop:1 parks the engine thread every
    tick, so decode genuinely slows — the injected-straggler knob the
    SLO/burn-rate demos ride."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    try:
        # warm the prefill/step programs so the baseline is decode, not
        # compile time
        sched.submit([1, 2, 3], max_new_tokens=6, temperature=0).wait(120)
        t0 = time.monotonic()
        sched.submit([1, 2, 3], max_new_tokens=6, temperature=0).wait(120)
        fast = time.monotonic() - t0
        monkeypatch.setenv("MXTPU_FAULT_PLAN", "serve_slow:drop:1")
        monkeypatch.setenv("MXTPU_FAULT_SLOW_S", "0.05")
        faults.reset()
        t0 = time.monotonic()
        req = sched.submit([1, 2, 3], max_new_tokens=6, temperature=0)
        req.wait(120)
        slow = time.monotonic() - t0
        assert req.outcome == "ok"
        assert slow > fast + 0.15            # >=5 parked decode ticks
    finally:
        monkeypatch.delenv("MXTPU_FAULT_PLAN", raising=False)
        faults.reset()
        sched.close()


# ---------------------------------------------------------------------------
# tracing-off bit-identity (satellite c)
# ---------------------------------------------------------------------------
def test_tracing_is_bit_identical_on_scheduler_outputs(decoder, metrics,
                                                       monkeypatch):
    """Tracing must be pure observation: the same prompts and seeds
    produce byte-identical token streams with tracing off vs on."""
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9, 10, 11], [12]]

    def run():
        sched = SlotScheduler(decoder, num_slots=2, queue_size=16)
        try:
            reqs = [sched.submit(p, max_new_tokens=6,
                                 temperature=(0 if i % 2 else 0.7),
                                 seed=i, trace="%032x" % i, sampled=True)
                    for i, p in enumerate(prompts)]
            return [list(r.wait(120).tokens) for r in reqs]
        finally:
            sched.close()

    tracing.enable_tracing(False)
    tracing.clear_spans()
    base = run()
    assert not tracing.spans()
    monkeypatch.setattr(tracing, "TICK_EVERY", 1)
    tracing.enable_tracing(True)
    try:
        on = run()
        assert tracing.spans()               # it really recorded
    finally:
        tracing.enable_tracing(False)
        tracing.clear_spans()
    assert on == base


# ---------------------------------------------------------------------------
# bench_trend direction tokens (satellite f)
# ---------------------------------------------------------------------------
def test_bench_trend_directions_for_trace_metrics():
    bt = _load_tool("bench_trend")
    assert bt.lower_is_better("slo_burn_rate_availability_60s")
    assert bt.lower_is_better("slo_violations_availability")
    assert bt.lower_is_better("trace_overhead_pct")
    assert not bt.lower_is_better("serve_trace_on_tokens_per_sec")
    assert not bt.lower_is_better("serve_trace_off_tokens_per_sec")


# ---------------------------------------------------------------------------
# phase(): the engine's and the trainer's own spans (ISSUE 25)
# ---------------------------------------------------------------------------
ENGINE_SPANS = {"engine.idle", "engine.admit", "engine.prefill",
                "engine.tick", "engine.step", "engine.sample"}


def _spans_total(reg):
    fam = tm.json_snapshot(reg)["metrics"].get("trace_spans_total")
    return sum(s["value"] for s in fam["samples"]) if fam else 0


def _serve_a_few(decoder):
    sched = SlotScheduler(decoder, num_slots=2, queue_size=16)
    try:
        reqs = [sched.submit([1 + i, 2, 3], max_new_tokens=4, temperature=0)
                for i in range(3)]
        for r in reqs:
            assert r.wait(120).outcome == "ok"
    finally:
        sched.close()
    return reqs


def test_phase_off_is_one_shared_object_and_records_nothing(metrics):
    tracing.enable_tracing(False)
    tracing.clear_spans()
    assert not tracing.recording()
    before = _spans_total(metrics)
    first = tracing.phase("engine.tick", "engine", tick=1)
    with first as ph:
        with tracing.phase("engine.step", "engine", tick=1) as inner:
            assert inner is ph is first      # nothing was allocated
    assert ph.t0 is None and ph.t1 is None   # and no clock was read
    assert tracing.spans() == []
    assert _spans_total(metrics) == before
    tracing.enable_tracing(True)
    try:
        with tracing.phase("engine.tick", "engine") as live:
            pass
        assert live is not first and live.t1 >= live.t0
        assert _spans_total(metrics) == before + 1
    finally:
        tracing.enable_tracing(False)
        tracing.clear_spans()


def test_phase_records_duration_parent_and_attributes(traced):
    assert tracing.recording()
    with tracing.phase("outer", "engine", tick=3) as outer:
        with tracing.phase("inner", "engine", parent="ignored") as inner:
            time.sleep(0.002)
        with tracing.phase("second", "engine"):
            pass
    done = threading.Event()

    def other_thread():
        # the stack of open phases is per thread: no parent here, so the
        # one handed in (a router's span id) stays
        with tracing.phase("elsewhere", "replica", trace="t" * 32,
                           parent="abcd"):
            pass
        done.set()

    threading.Thread(target=other_thread).start()
    assert done.wait(10)
    by = {s["name"]: s for s in tracing.spans()}
    assert by["inner"]["parent"] == by["outer"]["sid"] == outer.sid
    assert by["second"]["parent"] == outer.sid
    assert by["outer"]["parent"] is None and by["outer"]["tick"] == 3
    assert by["elsewhere"]["parent"] == "abcd"
    assert by["elsewhere"]["trace"] == "t" * 32
    assert by["inner"]["dur_s"] == inner.t1 - inner.t0 >= 0.002
    assert by["outer"]["dur_s"] >= by["inner"]["dur_s"]
    assert not any("prof" in s for s in by.values())


def test_engine_spans_nest_under_tracing_alone(decoder, traced):
    """MXTPU_TRACE / enable_tracing() and no profiler: the six engine
    spans land in the ring for requests no router sampled, nested, and
    without the ``prof`` flag."""
    _serve_a_few(decoder)
    spans = tracing.spans()
    assert ENGINE_SPANS <= {s["name"] for s in spans}
    assert not any(s.get("prof") for s in spans)
    by_sid = {s["sid"]: s for s in spans}
    for s in spans:
        if s["name"] in ("engine.step", "engine.sample"):
            up = by_sid[s["parent"]]
            assert up["name"] == "engine.tick" and up["tick"] == s["tick"]
        elif s["name"] == "engine.prefill":
            up = by_sid[s["parent"]]
            assert up["name"] == "engine.admit"
            assert up["request"] == s["request"]
            assert up["dur_s"] >= s["dur_s"]
        elif s["name"] in ("engine.tick", "engine.admit", "engine.idle"):
            assert s["parent"] is None and s["svc"] == "engine"
    admits = [s for s in spans if s["name"] == "engine.admit"]
    assert len(admits) == 3
    assert all({"request", "slot", "prompt_len", "bucket", "queue_wait_ms"}
               <= set(a) for a in admits)
    ticks = [s for s in spans if s["name"] == "engine.tick"]
    assert [t["tick"] for t in ticks] == list(range(len(ticks)))
    assert all(1 <= t["occupied"] <= 2 for t in ticks)
    # the terminal record of every request, sampled by a router or not
    done = [s for s in spans if s["name"] == "request"]
    assert len(done) == 3
    for r in done:
        assert r["trace"] is None and r["tokens"] == 4
        assert len(r["gaps_ms"]) == 3 and min(r["gaps_ms"]) >= 0
        assert r["ttft_ms"] >= r["queue_wait_ms"] >= 0


def test_engine_spans_reach_the_profilers_host_plane(decoder, tmp_path):
    """A jax.profiler session and no MXTPU_TRACE: the same names lie on
    the host plane of the .xplane.pb, and the ring's records say that a
    session was running as they closed."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracing.enable_tracing(False)
    tracing.clear_spans()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        assert tracing.recording()
        _serve_a_few(decoder)
    finally:
        jax.profiler.stop_trace()
    try:
        assert not tracing.recording()
        spans = tracing.spans()
        assert ENGINE_SPANS <= {s["name"] for s in spans}
        assert all(s["prof"] is True for s in spans
                   if s["name"] in ENGINE_SPANS | {"request"})
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        on_host = {}
        for plane in ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name in ENGINE_SPANS:
                            on_host.setdefault(ev.name, []).append(
                                dict(ev.stats))
        assert set(on_host) == ENGINE_SPANS
        assert len(on_host["engine.admit"]) == 3
        assert len(on_host["engine.tick"]) == sum(
            s["name"] == "engine.tick" for s in spans)
        assert {"request", "bucket"} <= set(on_host["engine.prefill"][0])
        assert "tick" in on_host["engine.step"][0]
    finally:
        tracing.clear_spans()


def test_prefill_span_holds_the_fetch(decoder, traced, monkeypatch):
    """``backend.admit_chunk`` returns without waiting for the device;
    the wait is the fetch of the logits, and it belongs to the prefill:
    both the engine's span and the sampled request's record hold it."""
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    real = sched.backend.admit_chunk

    class Slow:
        def __init__(self, row):
            self.row, self.size = row, row.size

        def __array__(self, dtype=None, copy=None):
            time.sleep(0.05)
            return np.asarray(self.row, dtype)

    monkeypatch.setattr(sched.backend, "admit_chunk",
                        lambda *a, **kw: Slow(real(*a, **kw)))
    try:
        req = sched.submit([1, 2, 3], max_new_tokens=2, temperature=0,
                           trace="%032x" % 5, sampled=True)
        assert req.wait(120).outcome == "ok"
    finally:
        sched.close()
    spans = tracing.spans()
    engine = next(s for s in spans if s["name"] == "engine.prefill")
    mine = next(s for s in spans if s["name"] == "prefill")
    assert engine["dur_s"] >= 0.05
    assert mine["dur_s"] == engine["dur_s"]       # one pair of stamps
    admit = next(s for s in spans if s["name"] == "admit")
    assert admit["dur_s"] == next(
        s for s in spans if s["name"] == "engine.admit")["dur_s"]


# ---------------------------------------------------------------------------
# the leaves under every program of the engine thread (ISSUE 35)
# ---------------------------------------------------------------------------
LEAVES = ["engine.launch", "engine.wait", "engine.fetch"]


def _end_ns(span):
    return span["start_ns"] + span["dur_s"] * 1e9


@pytest.mark.parametrize("parent,program,paged", [
    ("engine.step", "step", False), ("engine.prefill", "prefill", False),
    ("engine.prefill", "prefill", True),
    ("engine.prefill_chunk", "chunk", True)])
def test_a_program_span_holds_launch_wait_and_fetch(decoder, traced, parent,
                                                    program, paged):
    """Each program the engine thread runs is three leaves, one after
    the other and nothing else: the backend's call, the device
    finishing, the copy to the host.  On either backend; a prompt over
    the largest bucket of a paged one goes in chunks."""
    kw = dict(kv_block=4, prefill_buckets=(8,), paged_kernel="gather") \
        if paged else {}
    sched = SlotScheduler(decoder, num_slots=2, queue_size=8, **kw)
    try:
        for prompt in ([1, 2, 3], list(range(1, 15)) if paged else [4, 5]):
            assert sched.generate(prompt, max_new_tokens=3,
                                  temperature=0).outcome == "ok"
    finally:
        sched.close()
    spans = tracing.spans()
    whole = [s for s in spans if s["name"] == parent]
    assert whole
    by_sid = {s["sid"]: s for s in spans}
    # nothing nests under a leaf
    assert not any(by_sid.get(s["parent"], {}).get("name") in LEAVES
                   for s in spans)
    for p in whole:
        kids = sorted((s for s in spans if s["parent"] == p["sid"]),
                      key=lambda s: s["start_ns"])
        assert [k["name"] for k in kids] == LEAVES
        assert all(k["program"] == program for k in kids)
        launch, wait, fetch = kids
        # float32 logits: a row of the vocabulary a slot, or one row
        assert fetch["bytes"] == 4 * V * (2 if program == "step" else 1)
        if program != "step":
            assert launch["bucket"] == p["bucket"]
        # one open at a time, inside the parent, and all of it but the
        # parent's own clock reads
        assert p["start_ns"] <= launch["start_ns"]
        assert _end_ns(launch) <= wait["start_ns"] + 1e3
        assert _end_ns(wait) <= fetch["start_ns"] + 1e3
        assert _end_ns(fetch) <= _end_ns(p) + 1e3
        left = p["dur_s"] - sum(k["dur_s"] for k in kids)
        assert 0 <= left < 5e-3, (p, kids)


@pytest.mark.parametrize("looking", [False, True])
def test_the_wait_is_taken_only_while_someone_looks(decoder, monkeypatch,
                                                    looking):
    """With ``recording()`` false a tick and an admission make the calls
    they always made: one conversion of what the backend returned, no
    ``block_until_ready``, the ring untouched.  While recording the same
    sync is a wait and then the conversion."""
    calls = {"block": 0, "array": 0, "programs": 0}

    class Counted:
        def __init__(self, value):
            self.value, self.size = value, value.size
            calls["programs"] += 1

        def block_until_ready(self):
            calls["block"] += 1
            return self

        def __array__(self, dtype=None, copy=None):
            calls["array"] += 1
            return np.asarray(self.value, dtype)

    was = tracing.trace_on()
    tracing.enable_tracing(looking)
    tracing.clear_spans()
    sched = SlotScheduler(decoder, num_slots=1, queue_size=4)
    step, admit_chunk = sched.backend.step, sched.backend.admit_chunk

    def counted_step(*a, **kw):
        out, starved = step(*a, **kw)
        return Counted(out), starved

    monkeypatch.setattr(sched.backend, "step", counted_step)
    monkeypatch.setattr(sched.backend, "admit_chunk",
                        lambda *a, **kw: Counted(admit_chunk(*a, **kw)))
    try:
        assert tracing.recording() is looking
        req = sched.generate([1, 2, 3], max_new_tokens=4, temperature=0)
        assert req.outcome == "ok" and len(req.tokens) == 4
        assert calls["programs"] == 1 + sched.stats["ticks"] == 4
        assert calls["array"] == calls["programs"]
        assert calls["block"] == (calls["programs"] if looking else 0)
        names = [s["name"] for s in tracing.spans()]
        assert names.count("engine.wait") == calls["block"]
        if not looking:
            assert names == []
    finally:
        sched.close()
        tracing.enable_tracing(was)
        tracing.clear_spans()


def test_a_phase_starts_on_the_profilers_clock(tmp_path):
    """``start_ns`` is the wall clock in nanoseconds, which is what the
    profiler stamps a ``TraceAnnotation`` with: an event of the host
    plane stands at ``start_ns`` less the session's
    ``profile_start_time`` (a stat of the plane ``Task Environment``).
    The end stamp is on that clock too."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tracing.enable_tracing(False)
    tracing.clear_spans()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        for i in range(4):
            with tracing.phase("clock.outer", "engine", i=i):
                with tracing.phase("clock.inner", "engine", i=i):
                    time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    try:
        spans = [s for s in tracing.spans() if s["name"] == "clock.inner"]
        path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"),
                          recursive=True)
        events, origin = [], None
        for plane in ProfileData.from_file(path).planes:
            if plane.name == "Task Environment":
                origin = dict(plane.stats)["profile_start_time"]
            for line in plane.lines:
                events += [ev for ev in line.events
                           if ev.name == "clock.inner"]
        assert len(events) == len(spans) == 4 and origin
        events.sort(key=lambda ev: ev.start_ns)
        for ev, s in zip(events, spans):
            assert s["prof"] is True
            assert abs(s["start_ns"] - (origin + ev.start_ns)) < 1e6
            assert abs(s["dur_s"] * 1e9 - ev.duration_ns) < 1e6
            assert abs((s["t"] - s["dur_s"]) - s["start_ns"] * 1e-9) < 1e-6
        # a record written directly keeps its form
        direct = tracing.record_span("direct", "engine", None, 0.5)
        assert "start_ns" not in direct
    finally:
        tracing.clear_spans()
