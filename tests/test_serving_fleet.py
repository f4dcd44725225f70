"""Serving-fleet tests (ISSUE 15): the replica router (least-loaded
balancing, idempotent retries, draining rolling upgrades, SIGKILL'd
replica survival), the paged KV cache (last-bit parity vs contiguous,
prefix reuse with fork isolation, pool accounting), and the graceful
SIGTERM drain of tools/serve.py.
"""
import json
import os
import re
import signal
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import models, telemetry as tm
from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.serving import (NoReplicaAvailable, ReplicaDied,
                               ReplicaRouter, ReplicaTimeout,
                               RouterRetriesExhausted, SlotScheduler,
                               register_replica, serve_decoder,
                               start_router)
from mxnet_tpu.serving.paged_kv import PagedSlots, PoolExhausted
from mxnet_tpu.serving.scheduler import _ContiguousSlots

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


def _post(port, body, path="/generate", timeout=120):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return r.status, json.loads(r.read()), dict(r.headers)


def _get(port, path, timeout=30):
    with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                timeout=timeout) as r:
        return json.loads(r.read())


def _fleet(decoder, n=2, **kw):
    """n in-process replicas + a started router; caller cleans up."""
    servers, scheds = [], []
    for _ in range(n):
        s, sch = serve_decoder(decoder, port=0, num_slots=2,
                               queue_size=16)
        servers.append(s)
        scheds.append(sch)
    addrs = ["127.0.0.1:%d" % s.server_address[1] for s in servers]
    kw.setdefault("scrape_s", 0.1)
    router = ReplicaRouter(replicas=addrs, **kw)
    rsrv = start_router(router, port=0)
    return servers, scheds, addrs, router, rsrv


def _teardown(servers, scheds, router, rsrv):
    rsrv.shutdown()
    router.stop()
    for s in servers:
        s.shutdown()
    for sch in scheds:
        sch.close()


# ---------------------------------------------------------------------------
# router core
# ---------------------------------------------------------------------------
def test_router_relays_and_balances(decoder, metrics):
    """Requests through the router complete with decode parity, the
    answering replica is named in the header, load spreads over both
    replicas, and the router metric families are live."""
    servers, scheds, addrs, router, rsrv = _fleet(decoder)
    rport = rsrv.server_address[1]
    try:
        rs = np.random.RandomState(1)
        used = set()
        for i in range(8):
            prompt = rs.randint(0, V, 4 + i % 5).tolist()
            st, out, hdr = _post(rport, {"prompt": prompt,
                                         "max_tokens": 5})
            assert st == 200 and out["outcome"] == "ok"
            ref = decoder.generate(np.array(prompt)[None], 5,
                                   temperature=0)
            assert out["tokens"] == ref[0].tolist()
            used.add(hdr.get("X-MXTPU-Replica"))
        assert used <= set(addrs)
        hz = _get(rport, "/healthz")
        assert hz["status"] == "ok" and hz["healthy"] == 2
        assert set(hz["replicas"]) == set(addrs)
        text = urllib.request.urlopen(
            f"http://127.0.0.1:{rport}/metrics",
            timeout=30).read().decode()
        for fam in ("router_requests_total", "router_replicas",
                    "router_request_seconds"):
            assert fam in text
        fl = _get(rport, "/fleet")
        assert fl["healthy"] == 2 and len(fl["replicas"]) == 2
        # federation: replica metric families arrive host-labeled
        assert "serve_requests_total" in fl["metrics"]
        labels = {s["labels"].get("host")
                  for s in fl["metrics"]["serve_requests_total"]["samples"]}
        assert labels <= set(addrs) and labels
    finally:
        _teardown(servers, scheds, router, rsrv)


def test_router_retries_connect_failures(decoder, metrics):
    """A replica that looks healthy in the cache but is gone re-routes
    idempotently: the request succeeds on the next replica and the
    retry is counted with reason=connect; the dead row is marked.
    (No background scrape here — the test owns the cache so the forged
    healthy-but-gone row survives until routing.)"""
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=8)
    live = "127.0.0.1:%d" % server.server_address[1]
    dead = "127.0.0.1:1"
    router = ReplicaRouter(replicas=[dead, live], scrape_s=30,
                           retries=2)
    try:
        router.scrape_once()
        retr = metrics.get("router_retries_total")
        r0 = retr.value(reason="connect")
        # forge a fresh-looking healthy row so pick() prefers the dead
        # addr (tie on load, first insertion wins)
        router._replicas[dead].update(
            ok=True, health={"slots": 8, "occupied": 0,
                             "queue_depth": 0, "queue_size": 16})
        status, data, addr = router.route_generate(
            json.dumps({"prompt": [1, 2, 3], "max_tokens": 3}).encode())
        assert status == 200 and addr == live
        assert json.loads(data)["outcome"] == "ok"
        assert retr.value(reason="connect") - r0 >= 1
        assert router.replicas()[dead]["ok"] is False
    finally:
        router.stop()
        server.shutdown()
        sched.close()


def test_router_all_draining_returns_503(decoder):
    """503 + Retry-After ONLY when every replica is draining; undrain
    restores service."""
    servers, scheds, addrs, router, rsrv = _fleet(decoder)
    rport = rsrv.server_address[1]
    try:
        st, out, _ = _post(rport, {}, path="/admin/drain")
        assert st == 200 and set(out["replicas"]) == set(addrs)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(rport, {"prompt": [1], "max_tokens": 2})
        assert ei.value.code == 503
        assert ei.value.headers.get("Retry-After")
        st, out, _ = _post(rport, {}, path="/admin/undrain")
        assert st == 200
        router.scrape_once()
        st, out, _ = _post(rport, {"prompt": [1], "max_tokens": 2})
        assert st == 200 and out["outcome"] == "ok"
    finally:
        _teardown(servers, scheds, router, rsrv)


def test_router_exhaustion_is_named(decoder):
    """When every candidate was tried and failed, the router raises the
    named RouterRetriesExhausted (502 over HTTP), not a generic 500."""
    router = ReplicaRouter(replicas=["127.0.0.1:1"], scrape_s=30,
                           retries=1)
    router._replicas["127.0.0.1:1"].update(
        ok=True, health={"slots": 2, "occupied": 0, "queue_depth": 0,
                         "queue_size": 4})
    with pytest.raises(RouterRetriesExhausted, match="127.0.0.1:1"):
        router.route_generate(b'{"prompt": [1]}')
    # nothing routable at all -> the named unavailable error
    with pytest.raises(NoReplicaAvailable):
        router.route_generate(b'{"prompt": [1]}')


def _stub_replica(post_handler):
    """A bare HTTP server whose POST /generate is ``post_handler``;
    returns (server, "host:port")."""
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    class _H(BaseHTTPRequestHandler):
        def do_POST(self):
            n = int(self.headers.get("Content-Length", "0") or 0)
            self.rfile.read(n)
            post_handler(self)

        def log_message(self, *args):
            pass

    class _S(ThreadingHTTPServer):
        daemon_threads = True

    srv = _S(("127.0.0.1", 0), _H)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv, "127.0.0.1:%d" % srv.server_address[1]


def _routable(router, addr):
    router._replicas[addr].update(
        ok=True, health={"slots": 2, "occupied": 0, "queue_depth": 0,
                         "queue_size": 4})


def test_router_all_shed_keeps_backpressure_503():
    """When EVERY attempted replica answers a live 429/503 admission
    shed, the fleet is saturated, not broken: the router keeps the
    documented backpressure contract (NoReplicaAvailable -> 503 +
    Retry-After), not RouterRetriesExhausted's 502."""
    def shed(h):
        h.send_response(429)
        h.send_header("Content-Length", "0")
        h.end_headers()

    srvs, addrs = zip(*(_stub_replica(shed) for _ in range(2)))
    try:
        router = ReplicaRouter(replicas=list(addrs), scrape_s=30,
                               retries=2)
        for a in addrs:
            _routable(router, a)
        with pytest.raises(NoReplicaAvailable, match="429/503"):
            router.route_generate(b'{"prompt": [1]}')
        # a shed reply is not a death: both replicas stay routable
        assert all(r["ok"] for r in router.replicas().values())
    finally:
        for s in srvs:
            s.shutdown()


def test_router_slow_replica_is_timeout_not_dead():
    """A replica that merely exceeds generate_timeout_s raises the
    named ReplicaTimeout (504) and is NOT marked dead — a slow, healthy
    replica must not be reported as died mid-request nor dropped from
    routing."""
    def slow(h):
        time.sleep(3.0)
        h.send_response(200)
        h.send_header("Content-Length", "0")
        h.end_headers()

    srv, addr = _stub_replica(slow)
    try:
        router = ReplicaRouter(replicas=[addr], scrape_s=30, retries=1,
                               generate_timeout_s=0.3)
        _routable(router, addr)
        with pytest.raises(ReplicaTimeout, match="did not answer"):
            router.route_generate(b'{"prompt": [1]}')
        assert router.replicas()[addr]["ok"], \
            "slow replica was wrongly marked dead"
    finally:
        srv.shutdown()


def test_rolling_upgrade_under_live_traffic(decoder, metrics):
    """The acceptance bar: a full rolling upgrade (drain each replica,
    wait drained, undrain) completes under continuous client traffic
    with ZERO failed (non-retried) requests."""
    servers, scheds, addrs, router, rsrv = _fleet(decoder)
    rport = rsrv.server_address[1]
    try:
        rs = np.random.RandomState(3)
        stop = threading.Event()
        results, errors = [], []

        def client(i):
            r2 = np.random.RandomState(100 + i)
            while not stop.is_set():
                try:
                    st, out, _ = _post(
                        rport, {"prompt": r2.randint(0, V, 1 + i % 6)
                                .tolist(), "max_tokens": 4})
                    results.append((st, out["outcome"]))
                except Exception as exc:  # noqa: BLE001
                    errors.append(exc)
                    return
                time.sleep(0.01)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(4)]
        for t in threads:
            t.start()
        deadline = time.monotonic() + 60
        while not results and time.monotonic() < deadline:
            time.sleep(0.01)     # traffic is flowing before we upgrade
        upgraded = router.rolling_upgrade(drain_timeout=60)
        stop.set()
        for t in threads:
            t.join(120)
        assert [u["replica"] for u in upgraded] == sorted(addrs)
        assert not errors, errors[:3]
        assert results
        bad = [r for r in results if r != (200, "ok")]
        assert not bad, f"{len(bad)} failed requests during upgrade"
    finally:
        _teardown(servers, scheds, router, rsrv)


# ---------------------------------------------------------------------------
# coordinator self-registration
# ---------------------------------------------------------------------------
def test_replica_self_registration_via_coordinator(decoder, metrics):
    """A replica that register_replica()s with the PR-13 coordinator
    (role=serve) appears in the router's registry without any static
    list; leaving removes it at the next sweep."""
    from mxnet_tpu.parallel.coordinator import CoordinatorService

    svc = CoordinatorService(port=0, lease_s=2.0).start()
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=8)
    addr = "127.0.0.1:%d" % server.server_address[1]
    client = None
    router = None
    try:
        client = register_replica(addr, coordinator=svc.address)
        cl = svc.cluster()
        assert client.member in cl["members"]
        assert cl["members"][client.member]["role"] == "serve"
        router = ReplicaRouter(replicas=[], coordinator=svc.address,
                               scrape_s=0.1)
        router.scrape_once()
        rows = router.replicas()
        assert addr in rows and rows[addr]["ok"]
        assert rows[addr]["source"] == "coordinator"
        status, data, via = router.route_generate(
            json.dumps({"prompt": [2, 4], "max_tokens": 3}).encode())
        assert status == 200 and via == addr
        client.leave()
        client = None
        router.scrape_once()
        assert addr not in router.replicas()
    finally:
        if client is not None:
            client.leave()
        if router is not None:
            router.stop()
        svc.stop()
        server.shutdown()
        sched.close()


# ---------------------------------------------------------------------------
# subprocess chaos: SIGKILL'd replica, SIGTERM graceful drain
# ---------------------------------------------------------------------------
def _spawn_replica(extra_env=None, extra_flags=()):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("MXTPU_TELEMETRY_HTTP_PORT", None)
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, os.path.join(REPO, "tools", "serve.py"),
         "--demo", "--port", "0", "--num-layers", "1", "--num-heads",
         "1", "--d-model", "16", "--vocab-size", "32", "--max-len",
         "32", *extra_flags],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        cwd=REPO, env=env)
    addr, deadline = None, time.time() + 180
    lines = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        m = re.search(r"serving on http://([0-9.]+:[0-9]+)", line)
        if m:
            addr = m.group(1)
            break
    if addr is None:
        proc.kill()
        raise AssertionError("replica never came up:\n" + "".join(lines))
    return proc, addr


def test_router_survives_replica_sigkill_mid_request(decoder, metrics):
    """Fault site replica_kill (crash_after = a SIGKILL-shaped death
    mid-decode): the in-flight request gets the named 502, new work
    re-routes to the surviving replica, and the fleet converges (the
    dead replica is marked in the registry)."""
    proc, faulty = _spawn_replica(
        extra_env={"MXTPU_FAULT_PLAN": "replica_kill:crash_after:3"})
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=8)
    live = "127.0.0.1:%d" % server.server_address[1]
    router = ReplicaRouter(replicas=[faulty, live], scrape_s=0.1,
                           retries=2)
    rsrv = start_router(router, port=0)
    rport = rsrv.server_address[1]
    try:
        # force the doomed replica to take the request: drain the
        # healthy one, so the router's only candidate is the fault rig
        router.drain(live)
        with pytest.raises(urllib.error.HTTPError) as ei:
            _post(rport, {"prompt": [1, 2, 3], "max_tokens": 20})
        assert ei.value.code == 502
        body = json.loads(ei.value.read())
        assert body["router_error"] == "ReplicaDied"
        assert faulty in body["error"]
        assert proc.wait(timeout=60) == 137   # the crash_after exit
        # queued/new work re-routes: reopen the survivor and serve
        router.undrain(live)
        router.scrape_once()
        st, out, hdr = _post(rport, {"prompt": [4, 5], "max_tokens": 3})
        assert st == 200 and out["outcome"] == "ok"
        assert hdr.get("X-MXTPU-Replica") == live
        # convergence: the registry names the dead replica dead
        rows = router.replicas()
        assert rows[faulty]["ok"] is False
        assert rows[live]["ok"] is True
        hz = _get(rport, "/healthz")
        assert hz["healthy"] == 1
    finally:
        if proc.poll() is None:
            proc.kill()
        _teardown([server], [sched], router, rsrv)


def test_serve_sigterm_drains_then_exits(decoder):
    """ISSUE-15 satellite: SIGTERM on tools/serve.py == graceful
    rolling-restart step — the in-flight request finishes (not killed)
    and the process exits 0 after 'drained'."""
    proc, addr = _spawn_replica()
    port = int(addr.rsplit(":", 1)[1])
    try:
        result = {}

        def client():
            try:
                # 24 tokens fits the replica's cache window (max_len 32,
                # prompt bucket 8 -> 25 steps available): truncation can
                # never explain a short answer, only a broken drain can
                result["resp"] = _post(port, {"prompt": [1, 2],
                                              "max_tokens": 24})
            except Exception as exc:  # noqa: BLE001
                result["error"] = exc

        t = threading.Thread(target=client)
        t.start()
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            try:
                if _get(port, "/healthz", timeout=10)["occupied"] > 0:
                    break
            except OSError:
                pass
            time.sleep(0.01)
        proc.send_signal(signal.SIGTERM)
        t.join(120)
        assert proc.wait(timeout=120) == 0, "drain exit must be clean"
        assert "error" not in result, result.get("error")
        st, out, _ = result["resp"]
        assert st == 200 and out["outcome"] == "ok"
        assert out["n_tokens"] == 24   # the request was NOT cut short
    finally:
        if proc.poll() is None:
            proc.kill()


# ---------------------------------------------------------------------------
# paged KV cache
# ---------------------------------------------------------------------------
# paged against contiguous logits, float32, absolute: a paged prefill's
# keys are its history's pages and, beside them, its tail's own rows,
# where the contiguous one holds both in one max_len table (ISSUE 30):
# the same softmax summed in another order, so they agree to the last
# bits and no longer bitwise; measured <= 3.0e-8 on logits of scale 0.16
# (tests/test_paged_pool_writes.py holds bfloat16 and prefixes to it too)
_PAGED_TOL = 1e-6


def test_paged_vs_contiguous_bitwise(decoder):
    """On a block-aligned prompt the paged programs compute the
    contiguous decoder's softmax over the same keys: prefill logits and
    every step's logits agree to ``_PAGED_TOL`` between the two
    backends (bitwise while the prefill attended over a gathered
    ``max_len`` table), and the greedy tokens are the same."""
    buckets = (8, 16, 32)
    cont = _ContiguousSlots(decoder, 2, buckets)
    paged = PagedSlots(decoder, 2, block=8, prefill_buckets=buckets)
    rs = np.random.RandomState(5)
    prompt = rs.randint(0, V, 8).astype(np.int64)   # == bucket: start 0
    lc = np.asarray(cont.admit(0, prompt), np.float32)
    lp = np.asarray(paged.admit(0, prompt), np.float32)
    assert np.abs(lc - lp).max() <= _PAGED_TOL, "prefill logits diverged"
    assert lc.argmax() == lp.argmax()
    tok = np.array([int(lc.argmax()), 0])
    occ = np.array([True, False])
    for _ in range(6):
        slc, _n = cont.step(tok, occ)
        slp, _m = paged.step(tok, occ)
        slc = np.asarray(slc, np.float32)
        slp = np.asarray(slp, np.float32)
        assert np.abs(slc[0] - slp[0]).max() <= _PAGED_TOL, \
            "step logits diverged"
        assert slc[0].argmax() == slp[0].argmax()
        tok = np.array([int(slc[0].argmax()), 0])


def test_paged_scheduler_parity_and_zero_recompiles(decoder, metrics):
    """Mixed prompt lengths through the paged scheduler: every request
    matches its per-request greedy decode exactly, slots are reused
    mid-flight, and a WARM paged server does zero traces per tick."""
    reuse = metrics.get("serve_slot_reuse_total")
    compiles = metrics.get("executor_compile_total")
    sched = SlotScheduler(decoder, num_slots=2, queue_size=16,
                          paged=True, kv_block=8)
    try:
        rs = np.random.RandomState(6)
        # warmup: one request per tail bucket this traffic hits
        for plen in (3, 12, 20):
            sched.generate(rs.randint(0, V, plen), max_new_tokens=2,
                           timeout=120)
        c0, r0 = compiles.total(), reuse.total()
        prompts = [rs.randint(0, V, ln) for ln in (3, 7, 5, 9, 4, 18)]
        reqs = [sched.submit(p, max_new_tokens=5) for p in prompts]
        for r in reqs:
            r.wait(120)
        assert all(r.outcome == "ok" for r in reqs), \
            [(r.outcome, r.error) for r in reqs]
        for p, r in zip(prompts, reqs):
            ref = decoder.generate(p[None], 5, temperature=0)
            assert r.tokens == ref[0].tolist(), (
                f"paged co-batched decode diverged for len {len(p)}")
        assert compiles.total() - c0 == 0, \
            "warm paged serving traffic recompiled"
        assert reuse.total() - r0 > 0, "no mid-flight slot reuse"
    finally:
        sched.close()


def test_prefix_reuse_and_fork_isolation(decoder, metrics):
    """The prefix-cache correctness pin, driven at the backend level so
    the check is immune to greedy-argmax tie noise between different
    program structures: fork A decodes (mutating pages PAST the shared
    block), then fork B admits against the reused shared block — if
    A's writes corrupted the shared page, B's logits would be wrong by
    O(1); the legitimate full-prefill vs tail-reuse rounding difference
    is bounded at ~1e-5.  Steps feed both backends IDENTICAL forced
    tokens, so trajectories cannot drift apart."""
    hits = metrics.get("serve_prefix_hits_total")
    buckets = (8, 16, 32)
    cont = _ContiguousSlots(decoder, 3, buckets)
    pg = PagedSlots(decoder, 3, block=8, prefill_buckets=buckets)
    rs = np.random.RandomState(7)
    shared = rs.randint(0, V, 8).astype(np.int64)    # one full block
    fa = np.concatenate([shared, rs.randint(0, V, 8)])   # aligned p=16
    fb = np.concatenate([shared, rs.randint(0, V, 8)])
    tol = 1e-3

    h0 = hits.total()
    la_c = np.asarray(cont.admit(0, fa), np.float32)
    la_p = np.asarray(pg.admit(0, fa), np.float32)
    assert np.abs(la_c - la_p).max() <= _PAGED_TOL     # no prefix yet
    assert hits.total() - h0 == 0          # nothing cached yet
    # mutate fork A: 6 decode steps writing K/V past the shared block
    occ = np.array([True, False, False])
    tok = np.array([int(la_c.argmax()), 0, 0])
    for _ in range(6):
        lc, _ = cont.step(tok, occ)
        lp, _ = pg.step(tok, occ)
        lc = np.asarray(lc, np.float32)
        lp = np.asarray(lp, np.float32)
        assert np.abs(lc[0] - lp[0]).max() <= _PAGED_TOL
        tok = np.array([int(lc[0].argmax()), 0, 0])
    # fork B admits: the paged side prefills ONLY its tail behind the
    # reused shared page; corruption would blow past tol by orders of
    # magnitude
    lb_c = np.asarray(cont.admit(1, fb), np.float32)
    lb_p = np.asarray(pg.admit(1, fb), np.float32)
    assert hits.total() - h0 >= 1, "the shared block was not reused"
    scale = max(1.0, float(np.abs(lb_c).max()))
    assert np.abs(lb_c - lb_p).max() < tol * scale, \
        "fork B diverged — fork A's writes corrupted the shared prefix"
    occ2 = np.array([False, True, False])
    tok2 = np.array([0, int(lb_c.argmax()), 0])
    for _ in range(6):
        lc, _ = cont.step(tok2, occ2)
        lp, _ = pg.step(tok2, occ2)
        lc = np.asarray(lc, np.float32)
        lp = np.asarray(lp, np.float32)
        assert np.abs(lc[1] - lp[1]).max() < tol * scale
        tok2 = np.array([0, int(lc[1].argmax()), 0])
    # release both forks: private pages return to the pool, the shared
    # block stays pinned by the prefix index, and a third admission
    # still reuses the INTACT prefix
    pg.release(0)
    pg.release(1)
    st = pg.stats()
    assert st["prefix_pages"] >= 1
    assert st["pages_free"] == st["pages_total"] - st["prefix_pages"]
    h1 = hits.total()
    cont.release(0)
    lc3 = np.asarray(cont.admit(0, fa), np.float32)
    lp3 = np.asarray(pg.admit(0, fa), np.float32)
    assert hits.total() - h1 >= 1
    assert np.abs(lc3 - lp3).max() < tol * scale


def test_paged_healthz_and_env_selection(decoder, monkeypatch):
    """/healthz gains the paged pool block plus queue/drain signals;
    MXTPU_KV_BLOCK alone selects the paged backend."""
    monkeypatch.setenv("MXTPU_KV_BLOCK", "8")
    server, sched = serve_decoder(decoder, port=0, num_slots=2,
                                  queue_size=8)
    port = server.server_address[1]
    try:
        assert sched.paged and sched.backend.block == 8
        hz = _get(port, "/healthz")
        assert hz["paged"]["pages_total"] == 2 * (T // 8)
        assert hz["paged"]["block"] == 8
        assert hz["queue_size"] == 8 and hz["draining"] is False
        sched.drain()
        hz = _get(port, "/healthz")
        assert hz["draining"] is True
        assert hz["status"] in ("draining", "drained")
    finally:
        server.shutdown()
        sched.close()


def test_paged_pool_exhaustion_truncates(decoder):
    """Two slots contending for a pool smaller than their combined
    appetite: nobody hangs or errors — the starved request is delivered
    truncated with outcome ok (the paged cache-window analog)."""
    sched = SlotScheduler(decoder, num_slots=2, queue_size=4,
                          paged=True, kv_block=8, num_pages=4,
                          prefix_cache=False)
    try:
        rs = np.random.RandomState(8)
        a = sched.submit(rs.randint(0, V, 8), max_new_tokens=25)
        b = sched.submit(rs.randint(0, V, 8), max_new_tokens=25)
        a.wait(120)
        b.wait(120)
        assert a.outcome == "ok" and b.outcome == "ok"
        # 4 pages = 32 cache positions for 16 prompt tokens + budget 50:
        # at least one request must have been truncated by the pool
        assert len(a.tokens) + len(b.tokens) < 50
        assert min(len(a.tokens), len(b.tokens)) >= 1
        # the pool fully recovers for the next request
        c = sched.generate(rs.randint(0, V, 4), max_new_tokens=3,
                           timeout=120)
        assert c.outcome == "ok" and len(c.tokens) == 3
        assert sched.paged_stats()["pages_free"] == 4
    finally:
        sched.close()


def test_prefix_chain_pinned_against_own_eviction(decoder, metrics):
    """Admit-order regression pin: the shared chain must be pinned
    BEFORE tail allocation.  Unpinned, _alloc's LRU eviction reclaims
    this request's own ref==1 prefix page and hands it back as an owned
    tail page — one physical page mapped to two logical blocks, the
    tail prefill overwriting the shared prefix it is reusing.  Pinned,
    a pool that cannot feed the tail fails CLEANLY: PoolExhausted with
    refcounts and the prefix index intact, and the same admission
    succeeds uncorrupted once pages free up."""
    hits = metrics.get("serve_prefix_hits_total")
    buckets = (8, 16, 32)
    cont = _ContiguousSlots(decoder, 1, buckets)
    pg = PagedSlots(decoder, 3, block=8, num_pages=4,
                    prefix_cache=True, prefill_buckets=buckets)
    rs = np.random.RandomState(11)
    block_a = rs.randint(0, V, 8).astype(np.int64)
    pg.admit(0, block_a)                 # seed + promote chain A
    pg.release(0)
    pga = next(iter(pg._prefix.values()))
    pg.admit(1, rs.randint(0, V, 4))     # a live slot: 2 pages free
    # slot 0 matches chain A and needs 3 tail pages with 2 free; the
    # ONLY eviction candidate is chain A itself — unpinned, it would be
    # evicted into the owned tail (the page aliased to two blocks)
    long = np.concatenate([block_a, rs.randint(0, V, 24)])
    with pytest.raises(PoolExhausted):
        pg.admit(0, long)
    assert int(pg._ref[pga]) == 1          # the pin rolled back
    assert pga in pg._prefix.values()      # chain A survived
    assert pg._slot_pages[0] == []
    assert pg.stats()["pages_free"] == 2
    # release the contending slot: the SAME admission now succeeds,
    # reusing the intact chain behind a duplicate-free page row
    pg.release(1)
    h0 = hits.total()
    lp = np.asarray(pg.admit(0, long), np.float32)
    assert hits.total() - h0 >= 1, "chain A was not reused"
    row = pg._slot_pages[0]
    assert row[0] == pga and len(set(row)) == len(row) == 4
    lc = np.asarray(cont.admit(0, long), np.float32)
    scale = max(1.0, float(np.abs(lc).max()))
    assert np.abs(lc - lp).max() < 1e-3 * scale, \
        "tail prefill corrupted the shared prefix"


def test_paged_composes_with_int8(lm_params):
    """quantize='int8' weights decode through the paged programs too —
    the _DequantView dequantize-in-compute is backend-agnostic.  Parity
    is pinned where the two agree to the last bits (block-aligned
    prompt, paged vs contiguous scheduler over the SAME int8 decoder:
    the same greedy tokens): comparing two
    structurally different programs on near-tie int8 logits would pin
    floating-point rounding, not the quantize/paging contract."""
    dec8 = KVDecoder(lm_params, num_layers=L, num_heads=H, max_len=T,
                     quantize="int8")
    prompt = np.arange(1, 9)                   # len 8 == kv_block
    cont = SlotScheduler(dec8, num_slots=2, queue_size=4, paged=False)
    try:
        ref = cont.generate(prompt, max_new_tokens=5, timeout=120)
        assert ref.outcome == "ok"
    finally:
        cont.close()
    sched = SlotScheduler(dec8, num_slots=2, queue_size=4, paged=True,
                          kv_block=8)
    try:
        req = sched.generate(prompt, max_new_tokens=5, timeout=120)
        assert req.outcome == "ok"
        assert req.tokens == ref.tokens
    finally:
        sched.close()


def test_paged_validation():
    class _FakeDec:
        mesh = None
        max_len = 30

    with pytest.raises(mx.MXNetError, match="divide"):
        PagedSlots(_FakeDec(), 2, block=8)


# ---------------------------------------------------------------------------
# tooling satellites: fleetstat rows, bench_trend directions
# ---------------------------------------------------------------------------
def _load_tool(name):
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "mxtpu_" + name, os.path.join(REPO, "tools", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_fleetstat_router_rows_show_drain_and_paged():
    fleetstat = _load_tool("fleetstat")
    fleet = {
        "healthy": 1, "scrape_interval_s": 1.0,
        "replicas": {
            "10.0.0.1:9200": {"ok": True, "draining": True,
                              "health": {"status": "draining",
                                         "slots": 4, "occupied": 2,
                                         "queue_depth": 1, "ticks": 9,
                                         "paged": {"pages_total": 32,
                                                   "pages_free": 20,
                                                   "prefix_pages": 5}}},
            "10.0.0.2:9200": {"ok": False, "draining": False,
                              "health": None,
                              "error": "ConnectionRefusedError(61)"}},
        "metrics": {"serve_requests_total": {}},
    }
    out = fleetstat.render_router(fleet)
    assert "draining" in out                  # upgrade progress visible
    assert "DEAD" in out                      # dead replica named
    assert "20/32, 5 prefix" in out           # paged occupancy rendered
    assert "ConnectionRefused" in out


def test_bench_trend_directions_for_serve_metrics():
    """Round-19 direction table: retries/unavailable regress UP,
    throughput and the paged ratio regress DOWN."""
    bt = _load_tool("bench_trend")
    assert bt.lower_is_better("router_retry_total")
    assert bt.lower_is_better("router_retries_total")
    assert bt.lower_is_better("serve_fleet_ttft_p99_ms")
    assert not bt.lower_is_better("serve_fleet_tokens_per_sec")
    assert not bt.lower_is_better("paged_vs_contiguous_tokens_per_sec")
    assert not bt.lower_is_better("serve_paged_tokens_per_sec")


def test_bench_trend_directions_for_autotune_metrics():
    """Round-21 direction table: search wall cost and per-step kernel
    microseconds regress UP; the kernel speedup ratio regresses DOWN."""
    bt = _load_tool("bench_trend")
    assert bt.lower_is_better("autotune_search_ms")
    assert bt.lower_is_better("paged_attn_kernel_us_per_step")
    assert bt.lower_is_better("paged_attn_gather_us_per_step")
    assert bt.lower_is_better("epilogue_tuned_vs_default_us")
    assert not bt.lower_is_better("paged_attn_kernel_speedup")
    assert not bt.lower_is_better("autotune_cache_hit")
