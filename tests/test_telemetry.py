"""Unified telemetry runtime tests.

Covers: metric primitive semantics (counter/gauge/histogram, labels),
Prometheus text exposition validity, JSON snapshot, the span() ->
chrome-trace integration, subsystem instrumentation (executor, kvstore,
data iterators, trainer), the zero-metrics-when-disabled fast path, and
the round-5 satellite regressions (conv-precision warning + knob rename,
custom-op output-count cache invalidation, ImageIter epoch-end span).
"""
import json
import logging
import re
import urllib.request
import warnings

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu import telemetry as tm


@pytest.fixture(autouse=True)
def _telemetry_isolation():
    """Each test starts with a zeroed registry and telemetry ON."""
    tm.reset()
    tm.enable()
    yield
    tm.reset()
    tm.disable()


# ---------------------------------------------------------------------------
# primitives
# ---------------------------------------------------------------------------
def test_counter_semantics():
    c = tm.counter("t_counter_total", "help", labels=("kind",))
    c.inc(kind="a")
    c.inc(2.5, kind="a")
    c.inc(kind="b")
    assert c.value(kind="a") == 3.5
    assert c.value(kind="b") == 1.0
    assert c.total() == 4.5
    with pytest.raises(ValueError):
        c.inc(-1, kind="a")


def test_counter_label_schema_enforced():
    c = tm.counter("t_labeled_total", "help", labels=("kind",))
    with pytest.raises(ValueError):
        c.inc(wrong="x")
    with pytest.raises(ValueError):
        c.inc()  # missing label
    # unlabeled family rejects labels
    c2 = tm.counter("t_plain_total", "help")
    with pytest.raises(ValueError):
        c2.inc(kind="x")


def test_gauge_semantics():
    g = tm.gauge("t_gauge", "help")
    g.set(5)
    g.inc()
    g.dec(2)
    assert g.value() == 4.0
    g.set(-3)
    assert g.value() == -3.0


def test_histogram_semantics():
    h = tm.histogram("t_hist_seconds", "help", buckets=(1.0, 2.0, 4.0))
    for v in (0.5, 2.0, 9.0):  # bucket edges are inclusive (le)
        h.observe(v)
    assert h.count() == 3
    assert h.sum() == pytest.approx(11.5)
    text = tm.generate_text()
    assert 't_hist_seconds_bucket{le="1"} 1' in text
    assert 't_hist_seconds_bucket{le="2"} 2' in text
    assert 't_hist_seconds_bucket{le="4"} 2' in text
    assert 't_hist_seconds_bucket{le="+Inf"} 3' in text
    assert "t_hist_seconds_count 3" in text


def test_family_reregistration_idempotent_and_typechecked():
    c1 = tm.counter("t_same_total", "help", labels=("a",))
    c2 = tm.counter("t_same_total", "other help", labels=("a",))
    assert c1 is c2
    with pytest.raises(ValueError):
        tm.gauge("t_same_total")  # type conflict
    with pytest.raises(ValueError):
        tm.counter("t_same_total", labels=("b",))  # label-schema conflict
    with pytest.raises(ValueError):
        tm.counter("0bad name")


def test_disabled_is_noop():
    c = tm.counter("t_off_total", "help")
    g = tm.gauge("t_off_gauge", "help")
    h = tm.histogram("t_off_seconds", "help")
    tm.disable()
    c.inc()
    g.set(7)
    h.observe(1.0)
    assert c.value() == 0.0
    assert g.value() == 0.0
    assert h.count() == 0
    tm.enable()
    c.inc()
    assert c.value() == 1.0


def test_reset_clears_values_but_keeps_families():
    c = tm.counter("t_reset_total", "help")
    c.inc(3)
    tm.reset()
    assert c.value() == 0.0
    assert tm.get_registry().get("t_reset_total") is c


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------
_HELP_RE = re.compile(r"^# HELP [a-zA-Z_:][a-zA-Z0-9_:]* .*$")
_TYPE_RE = re.compile(
    r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* (counter|gauge|histogram)$")
_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'                          # metric name
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*"'      # first label
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="(?:[^"\\\n]|\\.)*")*\})?' # more labels
    r' (-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|[+-]Inf|NaN)$')


def _assert_valid_exposition(text):
    """Line-level validation of the Prometheus text format v0.0.4."""
    seen_type = {}
    for line in text.splitlines():
        if not line:
            continue
        if line.startswith("# HELP"):
            assert _HELP_RE.match(line), line
        elif line.startswith("# TYPE"):
            assert _TYPE_RE.match(line), line
            _, _, name, mtype = line.split(" ")
            assert name not in seen_type, f"duplicate TYPE for {name}"
            seen_type[name] = mtype
        else:
            assert _SAMPLE_RE.match(line), line
    # histogram families carry the full bucket/sum/count triple
    for name, mtype in seen_type.items():
        if mtype == "histogram" and (name + "_bucket") in text:
            assert f'{name}_bucket' in text
            assert 'le="+Inf"' in text
            assert f"{name}_sum" in text
            assert f"{name}_count" in text
    return seen_type


def test_generate_text_is_valid_exposition():
    c = tm.counter("t_exp_total", "a counter", labels=("kind",))
    c.inc(kind="x")
    c.inc(kind='we"ird\\lab\nel')  # escaping stress
    tm.gauge("t_exp_gauge", "a gauge").set(1.5)
    tm.histogram("t_exp_seconds", "a histogram").observe(0.01)
    text = tm.generate_text()
    types = _assert_valid_exposition(text)
    assert types["t_exp_total"] == "counter"
    assert types["t_exp_gauge"] == "gauge"
    assert types["t_exp_seconds"] == "histogram"
    assert '\\"' in text and "\\n" in text  # label escapes applied


def test_generate_text_serves_nonfinite_gauges():
    """A diverged run parks NaN in sentinel_grad_norm — the exposition
    must keep serving exactly then, not die on int(NaN)."""
    tm.gauge("t_exp_nan_gauge", "goes NaN on divergence").set(float("nan"))
    tm.gauge("t_exp_inf_gauge", "overflowed").set(float("inf"))
    text = tm.generate_text()
    _assert_valid_exposition(text)
    assert "t_exp_nan_gauge NaN" in text
    assert "t_exp_inf_gauge +Inf" in text


def test_json_snapshot_and_dump(tmp_path):
    c = tm.counter("t_json_total", "help", labels=("kind",))
    c.inc(2, kind="a")
    tm.histogram("t_json_seconds", "help").observe(0.5)
    snap = tm.json_snapshot()
    assert snap["metrics"]["t_json_total"]["samples"] == [
        {"labels": {"kind": "a"}, "value": 2.0}]
    hist = snap["metrics"]["t_json_seconds"]
    assert hist["samples"][0]["count"] == 1
    assert hist["samples"][0]["sum"] == pytest.approx(0.5)
    path = tm.dump_json(str(tmp_path / "snap.json"))
    with open(path) as f:
        assert json.load(f)["metrics"]["t_json_total"]["type"] == "counter"


def test_http_metrics_endpoint():
    tm.counter("t_http_total", "help").inc(5)
    srv = tm.start_http_server(0)
    try:
        port = srv.server_address[1]
        body = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10).read().decode()
        assert "t_http_total 5" in body
        _assert_valid_exposition(body)
        jbody = urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics.json", timeout=10).read()
        assert json.loads(jbody)["metrics"]["t_http_total"]["samples"]
    finally:
        srv.shutdown()


def test_logging_reporter(caplog):
    tm.counter("t_rep_total", "help").inc(3)
    tm.histogram("t_rep_seconds", "help").observe(0.25)
    rep = tm.LoggingReporter(interval=3600)
    with caplog.at_level(logging.INFO, logger="mxnet_tpu.telemetry"):
        rep.report_once()
    assert "t_rep_total=3" in caplog.text
    assert "t_rep_seconds n=1" in caplog.text


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------
def test_span_records_histogram_and_chrome_trace(tmp_path):
    from mxnet_tpu import profiler

    profiler.clear()
    profiler.profiler_set_state("run")
    try:
        with tm.span("unit_region", category="unit-test"):
            pass
    finally:
        profiler.profiler_set_state("stop")
    fname = str(tmp_path / "prof.json")
    profiler.dump_profile(fname)
    with open(fname) as f:
        events = json.load(f)["traceEvents"]
    ev = [e for e in events if e["name"] == "unit_region"]
    assert len(ev) == 1 and ev[0]["cat"] == "unit-test" and ev[0]["ph"] == "X"
    # ... and the same region landed in a latency histogram
    h = tm.get_registry().get("unit_region_seconds")
    assert h is not None and h.count() == 1


def test_span_histogram_name_and_labels():
    with tm.span("n", histogram_name="t_span_seconds", stage="x"):
        pass
    h = tm.get_registry().get("t_span_seconds")
    assert h.count(stage="x") == 1


def test_timed_decorator():
    @tm.timed("t_deco_fn")
    def f(x):
        return x + 1

    assert f(1) == 2
    assert tm.get_registry().get("t_deco_fn_seconds").count() == 1


def test_span_zero_cost_when_both_sinks_off():
    tm.disable()
    with tm.span("t_dark_region"):
        pass
    # family not even created: no label resolution on the disabled path
    assert tm.get_registry().get("t_dark_region_seconds") is None


def test_span_lands_on_the_jax_profilers_clock(tmp_path):
    """While a jax.profiler session runs -- and with telemetry and the
    chrome-trace profiler both off -- a ``span`` region is a
    ``TraceAnnotation`` on the host plane of the session's trace."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tm.disable()
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with tm.span("t_profiled_region", category="unit-test"):
            pass
    finally:
        jax.profiler.stop_trace()
    with tm.span("t_after_the_session"):
        pass
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = {ev.name for plane in ProfileData.from_file(path).planes
             if plane.name.startswith("/host:")
             for line in plane.lines for ev in line.events}
    assert "t_profiled_region" in names
    assert "t_after_the_session" not in names
    # its other sinks stayed off
    assert tm.get_registry().get("t_profiled_region_seconds") is None


# ---------------------------------------------------------------------------
# subsystem instrumentation
# ---------------------------------------------------------------------------
def test_executor_compile_and_cache_metrics():
    reg = tm.get_registry()
    a = sym.Variable("a")
    ex = (a * 2.0).simple_bind(mx.cpu(), a=(2,))
    ex.forward(is_train=False)
    assert reg.get("executor_compile_total").value(kind="fwd") >= 1
    assert reg.get("executor_graph_cache_total").value(result="miss") >= 1
    assert reg.get("executor_forward_seconds").count() >= 1
    # reshape reuses the donor's compiled fns -> cache hit
    ex2 = ex.reshape(a=(4,))
    assert reg.get("executor_graph_cache_total").value(result="hit") >= 1
    # backward path feeds the fwdbwd compile counter + latency histogram
    ex.forward(is_train=True)
    ex.backward([nd.ones((2,))])
    assert reg.get("executor_compile_total").value(kind="fwdbwd") >= 1
    assert reg.get("executor_backward_seconds").count() >= 1


def test_kvstore_metrics():
    reg = tm.get_registry()
    kv = mx.kv.create("local")
    kv.init("w", nd.ones((4,)))
    kv.push("w", nd.ones((4,)))
    out = nd.zeros((4,))
    kv.pull("w", out=out)
    assert reg.get("kvstore_push_total").value(store="local") == 1
    assert reg.get("kvstore_push_bytes_total").value(store="local") == 16
    assert reg.get("kvstore_pull_total").value(store="local") == 1
    assert reg.get("kvstore_pull_bytes_total").value(store="local") == 16
    assert reg.get("kvstore_push_seconds").count(store="local") == 1


def test_data_iterator_metrics():
    reg = tm.get_registry()
    data = np.zeros((8, 3), np.float32)
    it = mx.io.NDArrayIter(data, np.zeros((8,), np.float32), batch_size=4)
    n = len(list(it))
    assert n == 2
    assert reg.get("data_batches_total").value(iterator="NDArrayIter") == 2
    assert reg.get("data_batch_wait_seconds").count(iterator="NDArrayIter") == 2


def test_engine_metrics():
    reg = tm.get_registry()
    arr = nd.ones((3,))
    arr.wait_to_read()
    assert reg.get("engine_live_arrays").value() >= 1
    assert reg.get("engine_wait_seconds").count(call="wait_for_var") >= 1
    mx.engine.wait_for_all()
    assert reg.get("engine_wait_seconds").count(call="wait_for_all") >= 1
    assert reg.get("engine_naive_mode").value() == 0.0


def test_fused_trainer_metrics():
    from mxnet_tpu.trainer import FusedTrainer

    reg = tm.get_registry()
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=2),
        name="softmax")
    tr = FusedTrainer(net, optimizer="sgd")
    tr.init(data=(4, 6), softmax_label=(4,))
    tr.step(data=np.zeros((4, 6), np.float32),
            softmax_label=np.zeros((4,), np.float32))
    assert reg.get("trainer_samples_total").value(loop="fused") == 4
    assert reg.get("trainer_step_seconds").count(loop="fused") == 1


def _short_train_loop(epochs=2):
    """The acceptance-criteria loop: symbolic net, Module.fit over
    NDArrayIter, explicit local kvstore (single-device kvstore='local'
    legitimately bypasses the store, reference _create_kvstore parity)."""
    rs = np.random.RandomState(0)
    data = rs.rand(32, 10).astype(np.float32)
    label = (rs.rand(32) > 0.5).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=8)
    net = sym.SoftmaxOutput(
        sym.FullyConnected(sym.Variable("data"), num_hidden=2),
        name="softmax")
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.fit(it, num_epoch=epochs, kvstore=mx.kv.create("local"),
            batch_end_callback=mx.callback.Speedometer(8, frequent=2))


def test_train_loop_populates_required_metrics():
    reg = tm.get_registry()
    _short_train_loop()
    # the three acceptance-criteria metrics, all non-zero
    assert reg.get("executor_compile_total").total() > 0
    assert reg.get("kvstore_push_bytes_total").total() > 0
    assert reg.get("data_batches_total").total() > 0
    # Speedometer parity emitted through the registry
    assert reg.get("speedometer_samples_per_sec").value() > 0
    assert reg.get("trainer_samples_total").value(loop="module") > 0
    # ... and the whole registry renders as valid exposition format
    _assert_valid_exposition(tm.generate_text())


def test_train_loop_disabled_records_nothing():
    tm.reset()
    tm.disable()
    _short_train_loop(epochs=1)
    for fam in tm.get_registry().collect():
        assert not fam.samples(), f"{fam.name} recorded while disabled"


# ---------------------------------------------------------------------------
# docs drift
# ---------------------------------------------------------------------------
def test_metric_catalog_matches_registered_families():
    """ISSUE-5 satellite: docs/telemetry.md's catalog and the families
    the instrumented modules register at import must agree BOTH ways —
    a new metric without a docs row fails, and a catalog row for a
    removed metric fails.  Families are enumerated in a fresh
    subprocess so dynamically-created test families (spans, t_*) don't
    pollute the set."""
    import os
    import pathlib
    import subprocess
    import sys

    code = (
        "import os\n"
        "os.environ['JAX_PLATFORMS'] = 'cpu'\n"
        "os.environ.pop('MXTPU_TELEMETRY_HTTP_PORT', None)\n"
        "import mxnet_tpu\n"
        "import mxnet_tpu.trainer\n"
        "import mxnet_tpu.kvstore_fused\n"
        "import mxnet_tpu.mp_io\n"
        "import mxnet_tpu.module.base_module\n"
        "import mxnet_tpu.serving\n"
        "import mxnet_tpu.parallel.dist\n"
        "import mxnet_tpu.parallel.coordinator\n"
        "import mxnet_tpu.autotune\n"
        "for f in mxnet_tpu.telemetry.get_registry().collect():\n"
        "    print(f.name)\n")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=300,
                         cwd=os.path.dirname(os.path.dirname(
                             os.path.abspath(__file__))))
    assert res.returncode == 0, res.stderr[-2000:]
    registered = {l.strip() for l in res.stdout.splitlines() if l.strip()}
    assert "executor_compile_total" in registered  # enumeration sanity
    assert len(registered) > 20

    doc = pathlib.Path(__file__).resolve().parent.parent.joinpath(
        "docs", "telemetry.md").read_text()
    undocumented = sorted(n for n in registered if f"`{n}`" not in doc)
    assert not undocumented, (
        f"registered metric families missing from docs/telemetry.md: "
        f"{undocumented}")

    # vice versa: every family named in a catalog table's first column
    # must still be registered by the instrumented modules
    catalog = doc.split("## Metric catalog", 1)[1]
    in_catalog = set()
    for line in catalog.splitlines():
        if not line.startswith("|") or "---" in line:
            continue
        first_cell = line.split("|")[1]
        for name in re.findall(r"`([a-zA-Z_][a-zA-Z0-9_]*)`", first_cell):
            if "_" in name:
                in_catalog.add(name)
    assert len(in_catalog) > 20
    stale = sorted(n for n in in_catalog if n not in registered)
    assert not stale, (
        f"docs/telemetry.md catalogs families no module registers: "
        f"{stale}")


# ---------------------------------------------------------------------------
# satellite regressions
# ---------------------------------------------------------------------------
def test_conv_precision_warns_once_for_fp32(monkeypatch):
    from mxnet_tpu import base

    monkeypatch.delenv("MXTPU_CONV_PRECISION", raising=False)
    monkeypatch.delenv("MXNET_TPU_CONV_PRECISION", raising=False)
    monkeypatch.setattr(base, "_conv_precision_warned", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        base.conv_precision(np.zeros((1,), np.float32))
        base.conv_precision(np.zeros((1,), np.float32))  # second: silent
    msgs = [x for x in w if "MXTPU_CONV_PRECISION" in str(x.message)]
    assert len(msgs) == 1


def test_conv_precision_no_warning_for_low_precision_inputs(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu import base

    monkeypatch.delenv("MXTPU_CONV_PRECISION", raising=False)
    monkeypatch.delenv("MXNET_TPU_CONV_PRECISION", raising=False)
    monkeypatch.setattr(base, "_conv_precision_warned", False)
    with warnings.catch_warnings(record=True) as w:
        warnings.simplefilter("always")
        base.conv_precision(jnp.zeros((1,), jnp.bfloat16))
    assert not [x for x in w if "MXTPU_CONV_PRECISION" in str(x.message)]
    assert not base._conv_precision_warned


def test_conv_precision_knob_rename(monkeypatch):
    import jax

    from mxnet_tpu import base

    # old spelling still honored
    monkeypatch.delenv("MXTPU_CONV_PRECISION", raising=False)
    monkeypatch.setenv("MXNET_TPU_CONV_PRECISION", "float32")
    assert base.conv_precision() == jax.lax.Precision.HIGHEST
    # new spelling wins over the old one
    monkeypatch.setenv("MXTPU_CONV_PRECISION", "high")
    assert base.conv_precision() == jax.lax.Precision.HIGH


def test_conv_precision_warns_through_lowering(monkeypatch):
    from mxnet_tpu import base

    monkeypatch.delenv("MXTPU_CONV_PRECISION", raising=False)
    monkeypatch.delenv("MXNET_TPU_CONV_PRECISION", raising=False)
    monkeypatch.setattr(base, "_conv_precision_warned", False)
    net = sym.Convolution(sym.Variable("data"), kernel=(3, 3), num_filter=2)
    ex = net.simple_bind(mx.cpu(), data=(1, 3, 8, 8))
    ex.forward(is_train=False)  # fp32 conv traced -> one-time warning
    assert base._conv_precision_warned


def test_custom_op_reregistration_invalidates_output_cache():
    import mxnet_tpu.operator as op

    @op.register("tm_retest")
    class OneOut(op.CustomOpProp):
        def list_outputs(self):
            return ["output"]

    s1 = sym.Custom(sym.Variable("data"), op_type="tm_retest")
    assert len(s1.list_outputs()) == 1

    @op.register("tm_retest")
    class TwoOut(op.CustomOpProp):
        def list_outputs(self):
            return ["o1", "o2"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0], in_shape[0]], []

    s2 = sym.Custom(sym.Variable("data"), op_type="tm_retest")
    assert len(s2.list_outputs()) == 2


def test_imageiter_no_spurious_epoch_end_event(tmp_path):
    from PIL import Image

    from mxnet_tpu import profiler
    from mxnet_tpu.image import ImageIter

    rs = np.random.RandomState(5)
    files = []
    for i in range(8):
        fname = f"img{i}.png"
        Image.fromarray((rs.rand(20, 20, 3) * 255).astype(np.uint8)).save(
            str(tmp_path / fname))
        files.append((float(i % 2), fname))
    it = ImageIter(batch_size=4, data_shape=(3, 16, 16), imglist=files,
                   path_root=str(tmp_path))
    profiler.clear()
    profiler.profiler_set_state("run")
    try:
        nbatches = 0
        with pytest.raises(StopIteration):
            while True:
                it.next()
                nbatches += 1
    finally:
        profiler.profiler_set_state("stop")
    fname = str(tmp_path / "prof.json")
    profiler.dump_profile(fname)
    with open(fname) as f:
        events = [e for e in json.load(f)["traceEvents"]
                  if e["name"] == "ImageIter.next"]
    # epoch-end StopIteration must NOT record a spurious data-io event
    assert nbatches == 2
    assert len(events) == nbatches
