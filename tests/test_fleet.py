"""Fleet observability plane (ISSUE 14): coordinator metrics
federation + ``/fleet``, heartbeat step-timing feed + straggler
detection, merge-trace clock alignment, the bench regression sentinel,
and the rank-aware telemetry satellites.

The whole plane is provable in-process: real HTTP servers on ephemeral
ports stand in for N hosts, the ``slow_step`` fault site (faults.py)
stands in for a sick one, and synthetic committed rounds stand in for
the bench trajectory.
"""
import importlib.util
import json
import os
import socket
import subprocess
import sys
import time
import urllib.request

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import telemetry as tm
from mxnet_tpu.telemetry import fleet, health
from mxnet_tpu.parallel.coordinator import (CoordinatorClient,
                                            CoordinatorService)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(REPO, "tools")


def _load_tool(name):
    """Import a tools/ script by path (tools/ is not a package)."""
    spec = importlib.util.spec_from_file_location(
        "fleet_test_" + name, os.path.join(TOOLS, name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(autouse=True)
def _fleet_isolation():
    tm.reset()
    tm.enable()
    health._ring.clear()
    yield
    health._ring.clear()
    tm.reset()
    tm.disable()


@pytest.fixture
def service():
    svc = CoordinatorService(port=0, lease_s=0.5).start()
    yield svc
    svc.stop()


# ---------------------------------------------------------------------------
# tentpole 1: metrics federation + GET /fleet
# ---------------------------------------------------------------------------
def test_federation_scrape_and_fleet_shape(service):
    """Two members with real /metrics endpoints: one scrape sweep
    federates both, and GET /fleet serves host-labeled merged families
    next to membership/liveness rows."""
    regs, servers = [], []
    try:
        for i in range(2):
            reg = tm.Registry()
            reg.get_or_create(tm.Counter, "trainer_samples_total",
                              "samples", ("loop",)).inc(64 * (i + 1),
                                                        loop="fused")
            regs.append(reg)
            servers.append(tm.start_http_server(0, registry=reg))
        for i, srv in enumerate(servers):
            service.join("h%d" % i, host="hostname%d" % i, rank=i,
                         telemetry_addr="127.0.0.1:%d"
                                        % srv.server_address[1])
        snap = service.scraper.scrape_once()
        assert set(snap) == {"h0", "h1"}
        assert all(s["ok"] for s in snap.values())

        with urllib.request.urlopen(
                "http://%s/fleet" % service.address, timeout=5) as resp:
            view = json.loads(resp.read())
        assert view["generation"] == 0
        assert view["hosts_alive"] == 2
        assert view["scrape_interval_s"] > 0
        assert set(view["hosts"]) == {"h0", "h1"}
        assert view["hosts"]["h1"]["rank"] == 1
        assert view["hosts"]["h0"]["scrape_ok"] is True
        # merged families carry a leading host label = member id
        fam = view["metrics"]["trainer_samples_total"]
        assert fam["labelnames"][0] == "host"
        got = {(s["labels"]["host"], s["labels"]["loop"]): s["value"]
               for s in fam["samples"]}
        assert got == {("h0", "fused"): 64.0, ("h1", "fused"): 128.0}
        # scrape accounting
        assert tm.get_registry().get("fleet_scrape_total").value(
            result="ok") >= 2
    finally:
        for srv in servers:
            srv.shutdown()


def test_fleet_scrape_survives_dead_member_endpoint(service):
    """A member whose telemetry endpoint died keeps an ok=False row with
    the error — the sweep must not raise or hang on it."""
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()  # nothing listens here
    service.join("dead", host="x", rank=0,
                 telemetry_addr="127.0.0.1:%d" % port)
    snap = service.scraper.scrape_once()
    assert snap["dead"]["ok"] is False
    assert "error" in snap["dead"]
    view = service.fleet()
    assert view["hosts"]["dead"]["scrape_ok"] is False
    assert view["metrics"] == {}


def test_fleetstat_cli_oneshot(service):
    """tools/fleetstat.py (stdlib-only) renders the /fleet view."""
    service.join("h0", host="alpha", rank=0)
    service.join("h1", host="beta", rank=1)
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "fleetstat.py"),
         "--coord", service.address],
        capture_output=True, text=True, timeout=60)
    assert r.returncode == 0, r.stderr
    assert "hosts_alive 2" in r.stdout
    assert "alpha" in r.stdout and "beta" in r.stdout


# ---------------------------------------------------------------------------
# tentpole 2: step-timing feed + straggler detection
# ---------------------------------------------------------------------------
def test_step_time_stats_from_ring():
    for i in range(6):
        health.record_step(loop="t", step=i, dispatch_s=0.002,
                           wall_s=0.01)
    stats = health.step_time_stats()
    assert stats["count"] == 6
    assert stats["step_wall_s"] == pytest.approx(0.01)
    assert stats["dispatch_s"] == pytest.approx(0.002)
    assert stats["last_step_t"] > 0


def test_straggler_named_under_injected_slow_host(service, monkeypatch):
    """ISSUE-14 acceptance: with an injected slow host (the faults.py
    ``slow_step`` site inflating this process's flight-ring walls), the
    coordinator names the straggler within the monitor cadence and
    publishes dist_step_skew_ratio / dist_straggler_host."""
    from mxnet_tpu import faults

    monkeypatch.setenv("MXTPU_FAULT_PLAN", "slow_step:drop:1")
    monkeypatch.setenv("MXTPU_FAULT_SLOW_S", "0.03")
    faults.reset()
    try:
        # the slow host is THIS process: its ring walls carry the
        # injected ~30ms park, and its client heartbeats report them
        for i in range(fleet.STRAGGLER_MIN_STEPS + 2):
            health.record_step(loop="t", step=i, dispatch_s=0.001)
        slow = CoordinatorClient(service.address, member="slow", rank=1)
        # the fast host is simulated: direct heartbeats with sub-ms steps
        service.join("fast", host="fast-host", rank=0)
        deadline = time.monotonic() + 15
        strag = None
        while time.monotonic() < deadline:
            service.heartbeat("fast", steps={"count": 32,
                                             "step_wall_s": 0.001,
                                             "dispatch_s": 0.0005})
            strag = service.cluster()["straggler"]
            if strag:
                break
            time.sleep(0.05)
        assert strag, "straggler never flagged"
        assert strag["member"] == "slow"
        assert strag["ratio"] >= fleet.straggler_ratio()
        assert service.cluster()["step_skew_ratio"] >= 2.0
        reg = tm.get_registry()
        assert reg.get("dist_step_skew_ratio").value() >= 2.0
        assert reg.get("dist_straggler_host").value(host="slow") == 1
        # /fleet carries the flag too
        assert service.fleet()["straggler"]["member"] == "slow"
        # recovery clears the flag: the slow host reports healthy walls
        slow.stop()
        deadline = time.monotonic() + 15
        while time.monotonic() < deadline:
            service.heartbeat("fast", steps={"count": 32,
                                             "step_wall_s": 0.001,
                                             "dispatch_s": 0.0005})
            service.heartbeat("slow", steps={"count": 32,
                                             "step_wall_s": 0.001,
                                             "dispatch_s": 0.0005})
            if not service.cluster()["straggler"]:
                break
            time.sleep(0.05)
        assert not service.cluster()["straggler"]
        assert reg.get("dist_straggler_host").value(host="slow") == 0
    finally:
        monkeypatch.delenv("MXTPU_FAULT_PLAN")
        faults.reset()
        try:
            slow.stop()
        except NameError:
            pass


def test_heartbeat_records_clock_offset(service):
    """Heartbeat replies carry the coordinator clock; the client must
    record an RTT-midpoint offset estimate for merge-trace."""
    c = CoordinatorClient(service.address, member="h0", rank=0)
    try:
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            clock = health.clock_offset()
            if clock["source"] == "coordinator":
                break
            time.sleep(0.05)
        assert clock["source"] == "coordinator"
        assert clock["rtt_s"] is not None and clock["rtt_s"] >= 0
        # same machine, same clock: the estimate is bounded by the RTT
        assert abs(clock["offset_s"]) <= max(clock["rtt_s"], 0.05)
    finally:
        c.stop()


def test_step_timing_feed_adds_no_per_batch_syncs(service, monkeypatch):
    """ISSUE-14 satellite: a fit loop with the coordinator armed (per-
    batch step_poll + background heartbeats carrying flight-ring step
    stats) must keep host syncs per-EPOCH, not per-batch."""
    from mxnet_tpu import engine
    from mxnet_tpu.parallel import coordinator as coord_mod

    monkeypatch.setenv("MXTPU_COORD_ADDR", service.address)
    coord_mod._default_client = None  # fresh client for this addr
    counts = {"n": 0}
    orig_asnumpy = nd.NDArray.asnumpy
    orig_wait = engine.wait_for_var

    def counted_asnumpy(self):
        counts["n"] += 1
        return orig_asnumpy(self)

    def counted_wait(arr):
        counts["n"] += 1
        return orig_wait(arr)

    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=8,
                           name="fleet_fc"), name="softmax")

    def run(nbatch):
        counts["n"] = 0
        rs = np.random.RandomState(7)
        x = rs.uniform(-1, 1, (16 * nbatch, 4)).astype(np.float32)
        y = rs.randint(0, 8, 16 * nbatch).astype(np.float32)
        train = mx.io.NDArrayIter(x, y, batch_size=16, shuffle=False)
        mod = mx.mod.Module(net, context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.1),), num_epoch=1)
        return counts["n"]

    monkeypatch.setattr(nd.NDArray, "asnumpy", counted_asnumpy)
    monkeypatch.setattr(engine, "wait_for_var", counted_wait)
    try:
        small = run(4)
        large = run(16)
        assert small == large, (small, large)
        # the feed actually ran: ring records carry wall_s for the
        # heartbeat's step stats
        recs = [r for r in health.flight_ring() if r.get("loop") == "module"]
        assert recs and all("wall_s" in r for r in recs)
        assert health.step_time_stats()["step_wall_s"] > 0
    finally:
        client = coord_mod._default_client
        if client is not None:
            client.stop()
            coord_mod._default_client = None


# ---------------------------------------------------------------------------
# tentpole 3: correlated distributed timeline (merge-trace)
# ---------------------------------------------------------------------------
def test_flight_dump_carries_identity_and_clock(tmp_path, monkeypatch):
    monkeypatch.setenv("MXTPU_RANK", "3")
    monkeypatch.setenv("MXTPU_DIST_GENERATION", "2")
    monkeypatch.setenv("MXTPU_COORD_ADDR", "10.0.0.9:8476")
    health.set_clock_offset(0.125, rtt_s=0.004)
    health.record_step(loop="t", step=1, wall_s=0.01)
    path = health.dump_flight_record(str(tmp_path / "f.json"))
    with open(path) as f:
        dump = json.load(f)
    ident = dump["identity"]
    assert ident["rank"] == 3 and ident["generation"] == 2
    assert ident["coordinator"] == "10.0.0.9:8476"
    assert ident["clock"]["offset_s"] == pytest.approx(0.125)
    assert dump["ring"][-1]["wall_s"] == pytest.approx(0.01)


def test_flight_dump_default_name_is_rank_aware(tmp_path, monkeypatch):
    """ISSUE-14 satellite: co-hosted workers must not clobber each
    other's black boxes — default dump names carry rank/generation."""
    monkeypatch.setenv("MXTPU_RANK", "5")
    monkeypatch.setenv("MXTPU_DIST_GENERATION", "7")
    path = health.dump_flight_record(str(tmp_path))  # directory mode
    name = os.path.basename(path)
    assert name.startswith("mxtpu_flight_record_r5_g7_")
    assert name.endswith(".json")


def test_merge_trace_lanes_and_clock_alignment(tmp_path):
    """Two synthetic dumps whose clocks disagree by 2.5s: the merged
    trace must put both hosts' step slices on ONE timebase (offset
    applied), one lane (pid) per host, with process_name metadata."""
    fleetstat = _load_tool("fleetstat")
    paths = []
    for i in range(2):
        skew = 0.0 if i == 0 else -2.5  # host b's clock runs behind
        ring = [{"seq": s, "step": s, "loop": "fused",
                 "t": 1000.0 + 0.01 * (s + 1) + skew,
                 "wall_s": 0.01, "dispatch_s": 0.004}
                for s in range(4)]
        dump = {"version": 2, "ring": ring,
                "identity": {"host": "host%d" % i, "rank": i,
                             "generation": 3,
                             "clock": {"offset_s": -skew}}}
        p = tmp_path / ("flight_h%d.json" % i)
        p.write_text(json.dumps(dump))
        paths.append(str(p))
    out, n_events = fleetstat.merge_trace(paths, str(tmp_path / "o.json"))
    assert n_events == 8
    with open(out) as f:
        trace = json.load(f)
    events = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    meta = [e for e in trace["traceEvents"] if e["ph"] == "M"]
    assert len({e["pid"] for e in events}) == 2
    labels = {e["args"]["name"] for e in meta}
    assert labels == {"host0 rank0 g3", "host1 rank1 g3"}
    # clock alignment: step s of both hosts happened at the SAME
    # coordinator time, so per-step ts must agree across lanes
    by_lane = {}
    for e in events:
        by_lane.setdefault(e["pid"], []).append(e["ts"])
    lanes = [sorted(v) for v in by_lane.values()]
    assert lanes[0] == pytest.approx(lanes[1], abs=1.0)  # µs
    # rebased onto a common origin, durations preserved
    assert min(lanes[0]) == pytest.approx(0.0, abs=1.0)
    assert events[0]["dur"] == pytest.approx(0.01 * 1e6)


# ---------------------------------------------------------------------------
# tentpole 4: bench regression sentinel
# ---------------------------------------------------------------------------
def _write_round(dirpath, n, metrics=None, error=None):
    parsed = {"metric": "resnet50_train_imgs_per_sec_per_chip",
              "unit": "img/s", "vs_baseline": 1.0}
    if error is not None:
        parsed["value"] = 0.0
        parsed["error"] = error
    else:
        parsed.update(metrics)
    path = os.path.join(dirpath, "BENCH_r%02d.json" % n)
    with open(path, "w") as f:
        json.dump({"n": n, "rc": 0, "parsed": parsed}, f)


def _run_trend(dirpath, *extra):
    return subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bench_trend.py"),
         "--dir", str(dirpath), *extra],
        capture_output=True, text=True, timeout=60)


def test_bench_trend_clean_trajectory_exits_zero(tmp_path):
    _write_round(tmp_path, 1, {"value": 100.0, "mfu": 0.15,
                               "dispatch_us_per_step": 50.0})
    _write_round(tmp_path, 2, {"value": 98.0, "mfu": 0.16,
                               "dispatch_us_per_step": 52.0})
    r = _run_trend(tmp_path)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "resnet50_train_imgs_per_sec_per_chip" in r.stdout
    assert "ok:" in r.stdout


def test_bench_trend_flags_throughput_regression(tmp_path):
    _write_round(tmp_path, 1, {"value": 100.0})
    _write_round(tmp_path, 2, {"value": 60.0})  # -40% > 15% tol
    r = _run_trend(tmp_path)
    assert r.returncode == 1
    assert "FAIL" in r.stdout and "regressed" in r.stdout


def test_bench_trend_flags_latency_regression_direction(tmp_path):
    # lower-is-better metric going UP is the regression; the headline
    # holding steady must not mask it
    _write_round(tmp_path, 1, {"value": 100.0, "dispatch_us_per_step": 50.0})
    _write_round(tmp_path, 2, {"value": 100.0, "dispatch_us_per_step": 90.0})
    r = _run_trend(tmp_path)
    assert r.returncode == 1
    assert "dispatch_us_per_step" in r.stdout


def test_bench_trend_fails_on_fallback_round_and_skips_its_metrics(
        tmp_path):
    _write_round(tmp_path, 1, {"value": 100.0})
    _write_round(tmp_path, 2, {"value": 101.0})
    _write_round(tmp_path, 3, error="backend init timed out")
    r = _run_trend(tmp_path)
    assert r.returncode == 1
    assert "ARTIFACT FALLBACK" in r.stdout
    # the fallback round's zeroed headline must NOT read as a live
    # regression (only the fallback failure is reported)
    assert "regressed" not in r.stdout


def test_bench_trend_current_fallback_flag(tmp_path):
    _write_round(tmp_path, 1, {"value": 100.0})
    r = _run_trend(tmp_path, "--current-fallback", "backend init timed out")
    assert r.returncode == 1
    assert "captured NOW" in r.stdout


def test_bench_trend_tolerance_env(tmp_path, monkeypatch):
    _write_round(tmp_path, 1, {"value": 100.0})
    _write_round(tmp_path, 2, {"value": 80.0})  # -20%
    assert _run_trend(tmp_path).returncode == 1  # default 15%
    assert _run_trend(tmp_path, "--tol", "0.3").returncode == 0
    monkeypatch.setenv("BENCH_TREND_TOL", "0.3")
    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "bench_trend.py"),
         "--dir", str(tmp_path)],
        capture_output=True, text=True, timeout=60,
        env=dict(os.environ, BENCH_TREND_TOL="0.3"))
    assert r.returncode == 0


def test_bench_trend_on_recorded_trajectory(tmp_path):
    """The shape of the trajectory the repo once recorded — a crash, ONE
    live round, then three backend-init fallbacks — must parse, name
    the live round, and read 'loud' (the committed records themselves
    were deleted in PR 21)."""
    _write_round(tmp_path, 1, error="backend init failed")
    _write_round(tmp_path, 2, {"value": 2251.15, "mfu": 0.1402})
    for n in (3, 4, 5):
        _write_round(tmp_path, n,
                     error="backend init timed out after 240s")
    r = _run_trend(tmp_path)
    assert r.returncode == 1
    assert "rounds: live" in r.stdout
    assert "r02" in r.stdout


# ---------------------------------------------------------------------------
# satellites: /healthz topology, http port auto-increment, log identity
# ---------------------------------------------------------------------------
def test_healthz_topology_fields(monkeypatch):
    monkeypatch.setenv("MXTPU_RANK", "2")
    monkeypatch.setenv("MXTPU_DIST_GENERATION", "4")
    monkeypatch.setenv("MXTPU_COORD_ADDR", "10.0.0.1:8476")
    srv = tm.start_http_server(0)
    try:
        port = srv.server_address[1]
        with urllib.request.urlopen(
                "http://127.0.0.1:%d/healthz" % port, timeout=5) as resp:
            payload = json.loads(resp.read())
        assert payload["rank"] == 2
        assert payload["generation"] == 4
        assert payload["coordinator_addr"] == "10.0.0.1:8476"
    finally:
        srv.shutdown()


def test_http_server_port_auto_increment():
    srv1 = tm.start_http_server(0)
    taken = srv1.server_address[1]
    try:
        srv2 = tm.start_http_server(taken, max_tries=8)
        try:
            assert taken < srv2.server_address[1] <= taken + 7
        finally:
            srv2.shutdown()
        # single-try keeps the old contract: taken port raises
        with pytest.raises(OSError):
            tm.start_http_server(taken, max_tries=1)
    finally:
        srv1.shutdown()


def test_log_lines_carry_rank_identity(monkeypatch, caplog):
    """ISSUE-14 satellite: Speedometer and LoggingReporter lines carry
    rank/size@generation when jax.distributed spans processes."""
    import logging

    from mxnet_tpu import callback
    from mxnet_tpu.parallel import dist

    monkeypatch.setattr(dist, "_log_identity", lambda: (1, 2, 3))
    assert dist.log_prefix() == "[1/2@g3] "

    spd = callback.Speedometer(batch_size=16, frequent=2)

    class P:
        epoch, nbatch, eval_metric = 0, 0, None

    with caplog.at_level(logging.INFO):
        P.nbatch = 1
        spd(P)          # opens the window
        P.nbatch = 2
        time.sleep(0.01)
        spd(P)          # reports
        tm.counter("fleet_test_total", "t").inc()
        tm.LoggingReporter().report_once()
    speed_lines = [r.message for r in caplog.records
                   if "samples/sec" in r.message]
    assert speed_lines and all(m.startswith("[1/2@g3] ")
                               for m in speed_lines)
    tele_lines = [r.message for r in caplog.records
                  if "telemetry:" in r.message]
    assert tele_lines and tele_lines[0].startswith("[1/2@g3] ")


def test_log_prefix_empty_single_process():
    from mxnet_tpu.parallel import dist

    assert dist.log_prefix() == ""


def test_join_advertises_import_time_telemetry_server(service, monkeypatch):
    """client_from_env-style joins advertise telemetry.http_address()."""
    srv = tm.start_http_server(0)
    addr = "127.0.0.1:%d" % srv.server_address[1]
    monkeypatch.setattr(tm, "_http_server", srv)
    try:
        assert tm.http_address() == addr
        c = CoordinatorClient(service.address, member="adv", rank=0)
        try:
            assert service.cluster()["members"]["adv"]["telemetry"] == addr
            assert service._scrape_targets() == {"adv": addr}
        finally:
            c.stop()
    finally:
        monkeypatch.setattr(tm, "_http_server", None)
        srv.shutdown()
