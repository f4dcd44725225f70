"""The edge metrics: ``benchmark/edge_reduce.py`` on hand-made records
(an idle stretch inside an edge, an admission between two ticks, a block
decoder's launch that no wait precedes, a ring that is full), each of
the five readers on a synthetic ring, and each as ``run.py`` calls it on
one traced rehearsal of ``serve_batch``."""
import os

import pytest

from bench_util import run_cell

from benchmark import edge_reduce, harness

NAMES = ["edge_ms.serve", "launch_ms.serve", "fetch_ms.serve",
         "sample_ms.serve", "edge_cover.serve"]
ORIGIN_NS = 1_791_000_000 * 10 ** 9      # a wall clock, as start_ns is


def rec(name, start, end, sid=None, parent=None, prof=True, **attrs):
    """A ``phase()`` record from ``start`` to ``end`` seconds after
    ``ORIGIN_NS``."""
    start_ns = ORIGIN_NS + int(round(start * 1e9))
    out = dict(attrs, name=name, sid=sid or f"{name}@{start}",
               parent=parent, start_ns=start_ns, dur_s=float(end - start),
               t=start_ns * 1e-9 + (end - start), svc="engine", trace=None)
    if prof:
        out["prof"] = True
    return out


def program(name, program, parent, launch, wait=None, fetch=None,
            prof=True):
    """The span ``name`` of one program with its leaves, each given as
    ``(start, end)``; a block decoder's admission has a launch alone."""
    leaves = [("engine.launch", launch), ("engine.wait", wait),
              ("engine.fetch", fetch)]
    leaves = [(n, at) for n, at in leaves if at]
    sid = f"{name}@{launch[0]}"
    return [rec(name, launch[0], leaves[-1][1][1], sid=sid, parent=parent,
                prof=prof)] \
        + [rec(n, *at, parent=sid, program=program, prof=prof)
           for n, at in leaves]


def synthetic_ring():
    """A tick, an admission, a tick, an idle stretch, an admission: three
    edges of 7, 3 and 5 seconds; and a tick before the session."""
    return (
        [rec("engine.tick", -9, -4, sid="t0", prof=False)]
        + program("engine.step", "step", "t0", (-9, -8), (-8, -6), (-6, -5),
                  prof=False)
        + [rec("engine.tick", 0, 10, sid="t1")]
        + program("engine.step", "step", "t1", (0, 1), (1, 5), (5, 6))
        + [rec("engine.sample", 6, 8, parent="t1")]
        + [rec("engine.admit", 10.5, 16, sid="a1")]
        + program("engine.prefill", "prefill", "a1", (11, 12), (12, 15),
                  (15, 15.5))
        + [rec("engine.tick", 16.5, 23.5, sid="t2")]
        + program("engine.step", "step", "t2", (16.5, 18), (18, 21), (21, 22))
        + [rec("engine.sample", 22, 23, parent="t2")]
        + [rec("engine.idle", 24, 30)]
        + [rec("engine.admit", 30.5, 34, sid="a2")]
        + program("engine.prefill", "prefill", "a2", (31, 32), (32, 33.5),
                  (33.5, 34)))


# ------------------------------------------------------------ arithmetic
def test_an_edge_runs_from_a_wait_to_the_next_launch():
    """An admission between two ticks makes two edges, each with what
    the host did in it; the stretch the engine sat idle is no edge's."""
    found = edge_reduce.edges([r for r in synthetic_ring() if r.get("prof")])
    assert [(e["after"], e["before"]) for e in found] == [
        ("step", "prefill"), ("prefill", "step"), ("step", "prefill")]
    assert [e["s"] for e in found] == pytest.approx([7, 3, 5])
    first, second, third = found
    assert (first["fetch"], first["sample"], first["launch"],
            first["rest"]) == pytest.approx((1, 2, 1, 3))
    assert (second["fetch"], second["sample"], second["launch"],
            second["rest"]) == pytest.approx((0.5, 0, 1.5, 1))
    # 11 seconds on the clock, 6 of them idle
    assert (third["fetch"], third["sample"], third["launch"],
            third["rest"]) == pytest.approx((1, 1, 1, 2))
    said = edge_reduce.describe(found)
    assert said.startswith("3 edges (prefill>step 1, step>prefill 2)")
    assert "launch 1166.667" in said


def test_a_launch_that_no_wait_precedes_closes_no_edge():
    """A block decoder's admission fetches nothing: the step's launch
    follows the prefill's with the device still busy."""
    records = (
        program("engine.prefill", "prefill", "a1", (0, 1))
        + program("engine.step", "step", "t1", (1, 2), (2, 5), (5, 6))
        + program("engine.prefill", "prefill", "a2", (7, 8))
        + program("engine.step", "step", "t2", (8, 9), (9, 12), (12, 13)))
    found = edge_reduce.edges(records)
    assert [(e["after"], e["before"], e["s"]) for e in found] == [
        ("step", "prefill", pytest.approx(3))]
    assert edge_reduce.leaf_cover(records) == pytest.approx(100.0)
    assert edge_reduce.by_program(records, "engine.launch") \
        == "prefill 2 x 1000.000 ms, step 2 x 1000.000 ms"


def test_records_without_the_leaves_have_no_edge():
    assert edge_reduce.edges([]) == []
    assert edge_reduce.edges([rec("engine.tick", 0, 1)]) == []
    assert edge_reduce.by_program([], "engine.launch") == ""
    assert edge_reduce.leaf_cover([rec("engine.tick", 0, 1)]) is None
    # an end stamp alone places a record that has no start_ns
    old = {"name": "engine.wait", "t": 5.0, "dur_s": 2.0}
    assert edge_reduce.interval(old) == pytest.approx((3.0, 5.0))


def test_the_leaves_share_of_their_parents():
    records = program("engine.step", "step", "t1", (0, 1), (1.5, 5), (5, 6))
    records[0]["dur_s"] = 8.0           # the parent closes two seconds on
    assert edge_reduce.leaf_cover(records) == pytest.approx(100 * 5.5 / 8)


# --------------------------------------------------------------- readers
TRACE = {"window_s": 40.0, "busy_s": 20.0}
WANT = {
    "edge_ms.serve": 1e3 * (7 + 3 + 5) / 3,
    "launch_ms.serve": 1e3 * (1 + 1 + 1.5 + 1) / 4,
    "fetch_ms.serve": 1e3 * (1 + 0.5 + 1 + 0.5) / 4,
    "sample_ms.serve": 1e3 * (2 + 1) / 2,
    "edge_cover.serve": 100.0 * 15 / 20,
}


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_on_a_synthetic_ring(name, monkeypatch, capsys):
    from mxnet_tpu.telemetry import tracing

    reader = harness.load_metric(name)
    monkeypatch.setattr(tracing, "spans", lambda trace=None: synthetic_ring())
    assert reader.read({"trace": TRACE}) == pytest.approx(WANT[name])
    said = capsys.readouterr().err
    assert said.startswith(name + ": ") and any(c.isdigit() for c in said)
    # the same ring when it is full: its first spans may be gone
    monkeypatch.setenv("MXTPU_SPAN_RING", str(len(synthetic_ring())))
    assert reader.read({"trace": TRACE}) is None
    assert "full" in capsys.readouterr().err


@pytest.mark.parametrize("name", NAMES)
def test_a_reader_without_its_spans_gives_no_value(name, monkeypatch):
    """The parent of the PR that brought the leaves writes the engine's
    other spans; a program may write none at all."""
    from mxnet_tpu.telemetry import tracing

    reader = harness.load_metric(name)
    monkeypatch.setattr(tracing, "spans", lambda trace=None: [
        rec("something.else", 0, 1)])
    assert reader.read({"trace": TRACE}) is None
    if name != "sample_ms.serve":       # its spans are older than the leaves
        monkeypatch.setattr(tracing, "spans", lambda trace=None: [
            r for r in synthetic_ring()
            if r["name"] not in edge_reduce.LEAVES])
        assert reader.read({"trace": TRACE}) is None
    monkeypatch.setattr(tracing, "spans", lambda trace=None: synthetic_ring())
    if name == "edge_cover.serve":      # no trace, or a device never idle
        assert reader.read({"trace": None}) is None
        assert reader.read({"trace": {"window_s": 5.0,
                                      "busy_s": 5.0}}) is None


# ------------------------------------------------------------- rehearsal
@pytest.fixture(scope="module")
def rehearsal():
    """One traced rehearsal of ``serve_batch``: its last line, what it
    said, and the ring it left."""
    from mxnet_tpu.telemetry import tracing

    had = os.environ.get("MXTPU_SPAN_RING")
    os.environ["MXTPU_SPAN_RING"] = "262144"
    tracing.enable_tracing(False)
    tracing.clear_spans()
    try:
        code, last, err = run_cell(
            "--workload", "serve_batch", "--seed", str(2 ** 31 + 35),
            "--seconds", "2", "--trace", "1", "--rehearse")
        spans = tracing.spans()
    finally:
        tracing.clear_spans()
        if had is None:
            del os.environ["MXTPU_SPAN_RING"]
        else:
            os.environ["MXTPU_SPAN_RING"] = had
    assert code == 0, err[-2000:]
    assert last["correct"] is True, last["compared"]
    return last, err, spans


@pytest.mark.parametrize("name", NAMES)
def test_a_traced_rehearsal_prints_the_edge_metric(name, rehearsal):
    last, err, _spans = rehearsal
    entry = next(m for m in harness.resolve("serve_batch").per_layer
                 if m["name"] == name)
    assert entry["layer"] == "serving scheduler"
    assert entry["moves"] == "serve_tok_s"
    assert name in last["metrics"], err[-1500:]
    assert last["metrics"][name]["value"] > 0
    assert last["metrics"][name]["unit"] == entry["unit"]
    assert f"{name}: " in err


def test_the_rehearsals_ring_holds_a_leaf_for_every_program(rehearsal):
    """Three leaves under each program that closed inside the session,
    and the span metrics that were there before still read."""
    last, _err, spans = rehearsal
    prof = [s for s in spans if s.get("prof")]
    programs = [s for s in prof if s["name"] in edge_reduce.PROGRAMS]
    assert len(programs) > 10
    for leaf in edge_reduce.LEAVES:         # one may be of a program
        #                                     still open as the session stops
        assert 0 <= sum(s["name"] == leaf for s in prof) - len(programs) <= 1
    assert all(isinstance(s["start_ns"], int) for s in prof
               if s["name"].startswith("engine."))
    assert edge_reduce.leaf_cover(prof) > 50
    assert len(edge_reduce.edges(prof)) >= len(programs) - 2
    for name in ("engine_host_ms.serve", "admit_share.serve",
                 "queue_wait_ms.serve", "itl_p95_ms.serve"):
        assert last["metrics"][name]["value"] > 0
