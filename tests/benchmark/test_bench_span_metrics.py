"""The span metrics: ``benchmark/span_reduce.py`` on hand-made records,
each of the five readers on a synthetic ring, and one traced rehearsal a
cell that prints them all -- while an untraced run leaves the program's
ring empty.

``shard_batch_ms.train`` has its reader and no entry in BENCHMARK.json
yet: ``test_bench_extend.py`` holds the ResNet-50 rehearsal to an exact
set of metrics, ``bench_util.add_resnet_cell`` gives that cell every
metric of ``lm_train``, and neither file is this PR's to edit.  Its
reader is called here as ``run.py`` would call it."""
import pytest

from bench_util import cells, run_cell

from benchmark import harness, span_reduce

SERVE = ["queue_wait_ms.serve", "itl_p95_ms.serve", "admit_share.serve",
         "engine_host_ms.serve"]
TRAIN = ["shard_batch_ms.train"]


def rec(name, start, end, sid=None, parent=None, prof=True, **attrs):
    out = dict(attrs, name=name, sid=sid or f"{name}@{start}", parent=parent,
               t=float(end), dur_s=float(end - start), svc="engine",
               trace=None)
    if prof:
        out["prof"] = True
    return out


# ------------------------------------------------------------ arithmetic
def test_self_time_leaves_out_what_the_children_cover():
    records = [
        rec("engine.tick", 0, 10, sid="t1"),
        rec("engine.step", 1, 7, parent="t1"),
        rec("engine.sample", 7, 9, parent="t1"),
        rec("engine.tick", 20, 30, sid="t2"),
        # children that overlap each other, one reaching past its parent
        rec("engine.step", 21, 26, parent="t2"),
        rec("engine.sample", 24, 33, parent="t2"),
        # a grandchild is its parent's business, not the tick's
        rec("kv_evict", 25, 25.5, parent="engine.step@21"),
        rec("engine.admit", 40, 44, sid="a1"),
    ]
    assert span_reduce.self_s(records, "engine.tick") == pytest.approx(
        (10 - 6 - 2) + (10 - 9))
    assert span_reduce.self_s(records, "engine.admit") == pytest.approx(4)
    assert span_reduce.self_s(
        records, "engine.tick", "engine.admit") == pytest.approx(7)
    assert span_reduce.self_s(records, "engine.step") == pytest.approx(
        6 + 4.5)
    assert span_reduce.total_s(records, "engine.tick") == pytest.approx(20)
    assert span_reduce.self_s(records, "no.such.span") == 0


@pytest.mark.parametrize("values,q,want", [
    (list(range(1, 101)), 95, 95), (list(range(1, 101)), 50, 50),
    ([3, 1, 2], 50, 2), ([3, 1, 2], 95, 3), ([7], 95, 7),
    (list(range(1, 21)), 95, 19), (list(range(1, 22)), 95, 20),
    ([5, 5, 1, 9], 50, 5)])
def test_percentile_is_the_benchmarks_rule(values, q, want):
    """The smallest value with at least that share at or below it, as
    ``drivers/serve_closed._percentile`` has it."""
    driver = harness.load_driver("serve_closed")
    assert span_reduce.percentile(values, q) == want
    assert driver._percentile(values, q) == want


def test_a_full_ring_gives_no_value(capsys):
    records = [rec("engine.tick", i, i + 1) for i in range(16)]
    assert span_reduce.window_records(records, 16, "x") is None
    assert "full" in capsys.readouterr().err
    assert len(span_reduce.window_records(records, 17, "x")) == 16


def test_a_ring_without_a_profiled_record_gives_no_value(capsys):
    records = [rec("engine.tick", i, i + 1, prof=False) for i in range(4)]
    assert span_reduce.window_records(records, 2048, "x") is None
    assert "no record of a profiler session" in capsys.readouterr().err
    assert span_reduce.window_records([], 2048, "x") is None
    mixed = records + [rec("engine.tick", 9, 10)]
    assert span_reduce.window_records(mixed, 2048, "x") == mixed[-1:]


# --------------------------------------------------------------- readers
def synthetic_ring():
    """Two ticks, two admissions, an idle stretch and three finished
    requests inside the session; one tick before it."""
    return [
        rec("engine.tick", -5, -1, prof=False),
        rec("request", -9, -1, prof=False, queue_wait_ms=999.0,
            gaps_ms=[999.0]),
        rec("engine.idle", 0, 2),
        rec("engine.admit", 2, 3, sid="a1"),
        rec("engine.prefill", 2.1, 2.9, parent="a1"),
        rec("engine.tick", 3, 7, sid="t1"),
        rec("engine.step", 3.5, 6, parent="t1"),
        rec("engine.sample", 6, 7, parent="t1"),
        rec("engine.admit", 7, 8, sid="a2"),
        rec("engine.prefill", 7, 7.5, parent="a2"),
        rec("engine.tick", 8, 12, sid="t2"),
        rec("engine.step", 8, 11, parent="t2"),
        rec("engine.sample", 11, 11.5, parent="t2"),
        rec("request", 1, 12, queue_wait_ms=10.0, ttft_ms=50.0,
            gaps_ms=[400.0, 410.0, 900.0]),
        rec("request", 2, 12, queue_wait_ms=300.0, ttft_ms=350.0,
            gaps_ms=[405.0]),
        rec("request", 3, 12, queue_wait_ms=20.0, ttft_ms=70.0, gaps_ms=[]),
        rec("train.shard_batch", 0, 0.002),
        rec("train.shard_batch", 1, 1.004),
    ]


WANT = {
    "queue_wait_ms.serve": 20.0,                 # median of 10, 20, 300
    "itl_p95_ms.serve": 900.0,                   # of 400, 405, 410, 900
    "admit_share.serve": 100.0 * 2 / (2 + 8 + 2),
    # ticks' self time 0.5 + 0.5, admissions' 0.2 + 0.5, sampling
    # 1 + 0.5 (host work too: only the device waits are left out), over
    # two ticks
    "engine_host_ms.serve": 1e3 * 3.2 / 2,
    "shard_batch_ms.train": 3.0,
}


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_a_reader_on_a_synthetic_ring(name, monkeypatch, capsys):
    from mxnet_tpu.telemetry import tracing

    reader = harness.load_metric(name)
    monkeypatch.setattr(tracing, "spans", lambda trace=None: synthetic_ring())
    assert reader.read({}) == pytest.approx(WANT[name])
    said = capsys.readouterr().err
    assert said.startswith(name + ": ") and any(c.isdigit() for c in said)
    # the same ring, seen without a profiler session: nothing to read
    monkeypatch.setattr(tracing, "spans", lambda trace=None: [
        {k: v for k, v in r.items() if k != "prof"}
        for r in synthetic_ring()])
    assert reader.read({}) is None
    # and full
    monkeypatch.setattr(tracing, "spans", lambda trace=None: synthetic_ring())
    monkeypatch.setenv("MXTPU_SPAN_RING", str(len(synthetic_ring())))
    assert reader.read({}) is None
    assert "full" in capsys.readouterr().err


@pytest.mark.parametrize("name", SERVE + TRAIN)
def test_a_reader_without_its_spans_gives_no_value(name, monkeypatch):
    """A program that writes other spans (the parent of the PR that
    brought these metrics, a later one that renames them)."""
    from mxnet_tpu.telemetry import tracing

    monkeypatch.setattr(tracing, "spans", lambda trace=None: [
        rec("something.else", 0, 1)])
    assert harness.load_metric(name).read({}) is None


# ------------------------------------------------------------ rehearsals
@pytest.mark.parametrize("cell,names", [
    (cells("serve_closed")[0], SERVE), (cells("train")[0], TRAIN)])
def test_a_traced_rehearsal_prints_the_span_metrics(cell, names,
                                                    monkeypatch):
    """A tiny model ticks in milliseconds: the ring is given the room a
    real window never needs, the full-ring rule stays as it is."""
    from mxnet_tpu.telemetry import tracing

    monkeypatch.setenv("MXTPU_SPAN_RING", "262144")
    tracing.enable_tracing(False)
    tracing.clear_spans()
    try:
        code, last, err = run_cell(
            "--workload", cell, "--seed", str(2 ** 31 + 25), "--seconds",
            "2", "--trace", "1", "--rehearse")
        assert code == 0, err[-2000:]
        assert last["correct"] is True, last["compared"]
        listed = {m["name"] for m in harness.resolve(cell).per_layer}
        for name in names:
            if name not in listed:        # shard_batch_ms.train, above
                assert harness.load_metric(name).read({}) > 0
                continue
            assert name in last["metrics"], (name, err[-1500:])
            assert last["metrics"][name]["value"] > 0
            assert f"{name}: " in err
        if cell in cells("serve_closed"):
            assert last["metrics"]["admit_share.serve"]["value"] < 100
            assert last["metrics"]["itl_p95_ms.serve"]["unit"] == "ms"
        # every record closed inside the session, but for the spans
        # that were open as it stopped (a tick or a step, with a child)
        spans = tracing.spans()
        assert len(spans) > 10
        assert sum(not s.get("prof") for s in spans) <= 3
    finally:
        tracing.clear_spans()


@pytest.mark.parametrize("cell", cells())
def test_an_untraced_run_leaves_the_ring_empty(cell):
    """Neither MXTPU_TRACE nor a profiler session: through a whole run,
    set-up and window, ``phase()`` records nothing."""
    from mxnet_tpu.telemetry import tracing

    tracing.enable_tracing(False)
    tracing.clear_spans()
    code, last, err = run_cell("--workload", cell, "--seed", "25",
                               "--seconds", "1", "--trace", "0",
                               "--rehearse")
    assert code == 0, err[-2000:]
    assert last["attempted"] > 0
    assert tracing.spans() == []
    assert not tracing.recording()
