"""Async training-loop pipeline (round 8): device-resident fused
metrics, the bounded in-flight step window, and the device-side
step_multi feed.

Covers the ISSUE-4 acceptance criteria: fused metric values match the
eager numpy path, fit results are identical across MXTPU_ASYNC_DEPTH
settings, the steady-state Module.fit loop performs zero per-batch
host syncs with fused metrics on, and step_multi consumes per-step
device feeds without host re-stacking.
"""
import logging

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, sym
from mxnet_tpu.test_utils import get_synthetic_mnist


# ---------------------------------------------------------------------------
# fused metric parity
# ---------------------------------------------------------------------------

def _classification_batches(n_batches=3, b=16, c=10, seed=0):
    rs = np.random.RandomState(seed)
    out = []
    for _ in range(n_batches):
        logits = rs.uniform(0.01, 1.0, (b, c)).astype(np.float32)
        pred = logits / logits.sum(axis=1, keepdims=True)
        label = rs.randint(0, c, b).astype(np.float32)
        out.append((label, pred))
    return out


def _regression_batches(n_batches=3, b=16, seed=1):
    rs = np.random.RandomState(seed)
    return [(rs.uniform(-1, 1, (b, 4)).astype(np.float32),
             rs.uniform(-1, 1, (b, 4)).astype(np.float32))
            for _ in range(n_batches)]


_METRIC_CASES = [
    ("acc", lambda: mx.metric.Accuracy(), _classification_batches),
    ("acc-ignore", lambda: mx.metric.Accuracy(ignore_label=0),
     _classification_batches),
    ("top3", lambda: mx.metric.TopKAccuracy(top_k=3),
     _classification_batches),
    ("ce", lambda: mx.metric.CrossEntropy(), _classification_batches),
    ("perplexity", lambda: mx.metric.Perplexity(ignore_label=1),
     _classification_batches),
    ("mae", lambda: mx.metric.MAE(), _regression_batches),
    ("mse", lambda: mx.metric.MSE(), _regression_batches),
    ("rmse", lambda: mx.metric.RMSE(), _regression_batches),
    ("loss", lambda: mx.metric.Loss(), _regression_batches),
]


@pytest.mark.parametrize("name,make,data", _METRIC_CASES,
                         ids=[c[0] for c in _METRIC_CASES])
def test_fused_metric_matches_eager(name, make, data, monkeypatch):
    """Device-accumulated values must match the host-numpy path."""
    batches = data()

    fused = make()
    assert fused._fused_delta is not None  # the case list is fused-capable
    for label, pred in batches:
        fused.update([nd.array(label)], [nd.array(pred)])
    # nothing synced yet: the device window is still pending
    assert fused._dev_sum is not None
    fname, fval = fused.get()
    assert fused._dev_sum is None  # get() drained

    monkeypatch.setenv("MXTPU_FUSED_METRICS", "0")
    eager = make()
    for label, pred in batches:
        eager.update([nd.array(label)], [nd.array(pred)])
    assert eager._dev_sum is None  # opt-out really took the eager path
    ename, eval_ = eager.get()

    assert fname == ename
    np.testing.assert_allclose(fval, eval_, rtol=1e-5, atol=1e-7)
    assert fused.num_inst == eager.num_inst


def test_fused_and_eager_updates_interleave(monkeypatch):
    """The two paths share accumulators: flipping the gate mid-stream
    (or a non-device input) must not lose either window."""
    batches = _classification_batches(4)
    m = mx.metric.Accuracy()
    for i, (label, pred) in enumerate(batches):
        if i % 2:
            monkeypatch.setenv("MXTPU_FUSED_METRICS", "0")
        else:
            monkeypatch.delenv("MXTPU_FUSED_METRICS", raising=False)
        m.update([nd.array(label)], [nd.array(pred)])
    monkeypatch.setenv("MXTPU_FUSED_METRICS", "0")
    ref = mx.metric.Accuracy()
    for label, pred in batches:
        ref.update([nd.array(label)], [nd.array(pred)])
    assert m.get() == ref.get()
    assert m.num_inst == ref.num_inst


def test_fused_metric_local_global_split():
    """reset_local folds the pending device window into the carried
    totals (Speedometer auto_reset interval semantics)."""
    batches = _classification_batches(4)
    m = mx.metric.Accuracy()
    m.update([nd.array(batches[0][0])], [nd.array(batches[0][1])])
    m.update([nd.array(batches[1][0])], [nd.array(batches[1][1])])
    first_window = m.get()[1]
    m.reset_local()
    m.update([nd.array(batches[2][0])], [nd.array(batches[2][1])])
    second_window = m.get()[1]
    g = m.get_global()[1]
    exp = (first_window * 32 + second_window * 16) / 48
    np.testing.assert_allclose(g, exp, rtol=1e-6)


def test_fused_metric_follows_the_predictions_device():
    """A Module bound to an accelerator predicts there while its label
    batch sits where the host iterator put it; the fused accumulate
    moves the label (and its accumulators) to the prediction's device
    instead of handing jit two devices.  Found by the first Module.fit
    on a chip (PR 21); two CPU devices stand in for host and chip."""
    label, pred = _classification_batches(1)[0]
    m = mx.metric.Accuracy()
    ref = mx.metric.Accuracy()
    on_chip = nd.array(pred, ctx=mx.cpu(1))
    for _ in range(2):
        m.update([nd.array(label)], [on_chip])
        ref.update([nd.array(label)], [nd.array(pred)])
    assert m._dev_sum is not None, "took the eager path"
    assert m._dev_sum.devices() == on_chip._read().devices()
    assert m.get() == ref.get()
    # the same metric handed back to a host-bound module follows again
    m.update([nd.array(label)], [nd.array(pred)])
    ref.update([nd.array(label)], [nd.array(pred)])
    assert m.get() == ref.get()


def test_custom_and_f1_metrics_stay_eager():
    label = nd.array(np.array([1.0, 0.0]))
    pred = nd.array(np.array([[0.2, 0.8], [0.3, 0.7]]))
    cm = mx.metric.np(lambda l, p: float((p.argmax(1) == l).mean()))
    cm.update([label], [pred])
    assert cm._dev_sum is None
    f1 = mx.metric.F1()
    f1.update([label], [pred])
    assert f1._dev_sum is None
    assert mx.metric.create("loss").name == "loss"


# ---------------------------------------------------------------------------
# bounded in-flight window
# ---------------------------------------------------------------------------

def test_async_window_bounds_in_flight(monkeypatch):
    import jax.numpy as jnp

    from mxnet_tpu import engine

    monkeypatch.setenv("MXTPU_ASYNC_DEPTH", "3")
    assert engine.async_depth() == 3
    w = engine.AsyncWindow()
    for i in range(8):
        w.push(jnp.ones((4,)) * i)
        assert len(w) <= 3
    w.drain()
    assert len(w) == 0
    # explicit depth overrides the env; NDArray handles are unwrapped
    w2 = engine.AsyncWindow(depth=1)
    w2.push([nd.array([1.0]), nd.array([2.0])])
    w2.push(nd.array([3.0]))
    assert len(w2) == 1
    w2.drain()


def _mlp():
    data = sym.Variable("data")
    net = sym.FullyConnected(sym.Flatten(data), name="fc1", num_hidden=16)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="fc2", num_hidden=10)
    return sym.SoftmaxOutput(net, name="softmax")


def _fixed_params():
    rs = np.random.RandomState(3)
    return {
        "fc1_weight": nd.array(rs.uniform(-0.05, 0.05, (16, 784))),
        "fc1_bias": nd.array(np.zeros(16)),
        "fc2_weight": nd.array(rs.uniform(-0.05, 0.05, (10, 16))),
        "fc2_bias": nd.array(np.zeros(10)),
    }


def _fit_once(depth, monkeypatch, nbatch=8):
    monkeypatch.setenv("MXTPU_ASYNC_DEPTH", str(depth))
    (xtr, ytr), _ = get_synthetic_mnist(64 * nbatch, 16)
    train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=False)
    mod = mx.mod.Module(_mlp(), context=mx.cpu())
    metric = mx.metric.create("acc")
    mod.fit(train, eval_metric=metric, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.5),), num_epoch=2,
            arg_params=_fixed_params())
    args, _ = mod.get_params()
    return ({k: v.asnumpy() for k, v in args.items()},
            metric.get_global()[1])


def test_fit_identical_across_async_depths(monkeypatch):
    """MXTPU_ASYNC_DEPTH only changes WHEN the host waits, never the
    math: same seed/params/data must produce bit-identical results."""
    params1, acc1 = _fit_once(1, monkeypatch)
    params4, acc4 = _fit_once(4, monkeypatch)
    assert params1.keys() == params4.keys()
    for k in params1:
        np.testing.assert_array_equal(params1[k], params4[k], err_msg=k)
    assert acc1 == acc4


def test_steady_state_fit_has_zero_per_batch_syncs(monkeypatch):
    """ISSUE-4 acceptance: with fused metrics the epoch loop performs no
    per-batch asnumpy/wait — host syncs must NOT grow with batch count."""
    from mxnet_tpu import engine

    counts = {"asnumpy": 0, "wait": 0}
    orig_asnumpy = nd.NDArray.asnumpy
    orig_wait = engine.wait_for_var

    def counted_asnumpy(self):
        counts["asnumpy"] += 1
        return orig_asnumpy(self)

    def counted_wait(arr):
        counts["wait"] += 1
        return orig_wait(arr)

    def run(nbatch):
        counts["asnumpy"] = counts["wait"] = 0
        (xtr, ytr), _ = get_synthetic_mnist(64 * nbatch, 16)
        train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=False)
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.5),), num_epoch=1,
                arg_params=_fixed_params())
        return counts["asnumpy"] + counts["wait"]

    monkeypatch.setattr(nd.NDArray, "asnumpy", counted_asnumpy)
    monkeypatch.setattr(engine, "wait_for_var", counted_wait)

    small = run(4)
    large = run(16)
    # fused: whatever boundary syncs exist are per-EPOCH, not per-batch
    assert large == small, (small, large)

    monkeypatch.setenv("MXTPU_FUSED_METRICS", "0")
    small_eager = run(4)
    large_eager = run(16)
    # eager: every batch pays at least one device->host metric sync
    assert large_eager - small_eager >= 12
    assert large_eager > large


def test_monitor_does_not_serialize_async_window(monkeypatch):
    """ISSUE-5 satellite: Monitor.tic used to wait_to_read every arg
    array each interval, pinning the in-flight window at 0.  Stat
    dispatch is async (the sync lives in toc's _render), so an
    installed Monitor must keep engine_pipeline_depth > 0."""
    from mxnet_tpu import telemetry as tm

    monkeypatch.setenv("MXTPU_ASYNC_DEPTH", "2")
    # tic's old blocking loop is only observable as wait_to_read calls:
    # count them (the deque-length gauge alone stays full either way)
    waits = {"n": 0}
    orig_wait = nd.NDArray.wait_to_read

    def counted_wait(self):
        waits["n"] += 1
        return orig_wait(self)

    monkeypatch.setattr(nd.NDArray, "wait_to_read", counted_wait)
    tm.reset()
    tm.enable()
    try:
        (xtr, ytr), _ = get_synthetic_mnist(64 * 8, 16)
        train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=False)
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mon = mx.Monitor(interval=1, pattern=".*fc1.*")
        depth = tm.get_registry().get("engine_pipeline_depth")
        seen = []

        def watch(_param):
            seen.append(depth.value())

        mod.fit(train, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.5),), num_epoch=1,
                arg_params=_fixed_params(), monitor=mon,
                batch_end_callback=watch)
        # the monitor still produced stats (toc_print consumed them)...
        assert mon.step > 0
        # ...without the per-interval wait_to_read sweep over every arg
        # array (8 batches x 8 arrays would be >= 64 calls)
        assert waits["n"] == 0, waits
        # ...and the window stayed pipelined under it
        assert max(seen) > 0, seen
    finally:
        tm.reset()
        tm.disable()


def test_fused_metrics_with_data_parallel_module():
    """Sharded outputs (4-device data-parallel group) accumulate device-
    side too: replicated scalars + replicated host labels."""
    (xtr, ytr), (xte, yte) = get_synthetic_mnist(512, 128)
    train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=True)
    val = mx.io.NDArrayIter(xte, yte, batch_size=64)
    mod = mx.mod.Module(_mlp(), context=[mx.cpu(i) for i in range(4)])
    metric = mx.metric.create("acc")
    mod.fit(train, eval_metric=metric, optimizer="sgd",
            optimizer_params=(("learning_rate", 0.5),), num_epoch=3)
    assert mod.score(val, "acc")[0][1] > 0.9


# ---------------------------------------------------------------------------
# step_multi device feed
# ---------------------------------------------------------------------------

def _fc_sym():
    data = sym.Variable("data")
    net = sym.FullyConnected(data, name="fc", num_hidden=10)
    return sym.SoftmaxOutput(net, name="softmax")


def _make_trainer(b):
    from mxnet_tpu.trainer import FusedTrainer

    mx.random.seed(11)
    tr = FusedTrainer(_fc_sym(), optimizer="sgd",
                      optimizer_params={"lr": 0.1, "momentum": 0.9,
                                        "rescale_grad": 1.0 / b},
                      initializer=mx.init.Xavier())
    tr.init(data=(b, 32))
    return tr


def test_step_multi_tuple_feed_matches_sequential():
    """Per-step tuple feeds (the DevicePrefetchIter path) are stacked
    inside the compiled program and land on the same params as k
    sequential step() calls."""
    import jax

    rs = np.random.RandomState(5)
    k, b = 4, 8
    batches = [(rs.uniform(-1, 1, (b, 32)).astype(np.float32),
                rs.randint(0, 10, b).astype(np.float32))
               for _ in range(k)]

    seq = _make_trainer(b)
    for x, y in batches:
        seq.step(data=x, softmax_label=y)

    multi = _make_trainer(b)
    # device-resident per-step arrays, fed WITHOUT host re-stacking
    feed = {
        "data": tuple(jax.device_put(x) for x, _ in batches),
        "softmax_label": tuple(jax.device_put(y) for _, y in batches),
    }
    outs = multi.step_multi(_donate=True, **feed)
    assert np.asarray(outs[0]).shape[0] == k
    assert multi._step == seq._step == k
    for name in seq.params:
        np.testing.assert_allclose(np.asarray(seq.params[name]),
                                   np.asarray(multi.params[name]),
                                   rtol=1e-5, atol=1e-6, err_msg=name)


def test_step_multi_prestacked_jax_array_not_donated_by_default():
    """A caller-held pre-stacked device batch survives step_multi (the
    bench replays one stack), while _donate=True consumes it."""
    import jax
    import jax.numpy as jnp

    rs = np.random.RandomState(6)
    k, b = 3, 8
    tr = _make_trainer(b)
    stacked = {
        "data": jax.device_put(
            rs.uniform(-1, 1, (k, b, 32)).astype(np.float32)),
        "softmax_label": jax.device_put(
            rs.randint(0, 10, (k, b)).astype(np.float32)),
    }
    tr.step_multi(**stacked)
    # default: owned-by-caller arrays are NOT donated — still readable
    assert float(jnp.sum(stacked["data"])) == pytest.approx(
        float(np.sum(np.asarray(stacked["data"]))))
    tr.step_multi(**stacked)  # and replayable


def test_io_step_multi_feeds_groups_batches():
    from mxnet_tpu import io as io_mod

    rs = np.random.RandomState(9)
    x = rs.uniform(-1, 1, (64, 32)).astype(np.float32)
    y = rs.randint(0, 10, 64).astype(np.float32)
    it = mx.io.NDArrayIter(x, y, batch_size=8)
    feeds = list(io_mod.step_multi_feeds(it, 3))
    # 8 batches -> groups of 3, 3, 2 (short tail kept)
    assert [len(f["data"]) for f in feeds] == [3, 3, 2]
    assert set(feeds[0]) == {"data", "softmax_label"}
    assert feeds[0]["data"][0].shape == (8, 32)

    it.reset()
    tr = _make_trainer(8)
    for feed in io_mod.step_multi_feeds(it, 3):
        tr.step_multi(_donate=True, **feed)
    assert tr._step == 8

    it.reset()
    dropped = list(io_mod.step_multi_feeds(it, 3, drop_remainder=True))
    assert [len(f["data"]) for f in dropped] == [3, 3]


# ---------------------------------------------------------------------------
# Speedometer "values needed" guard
# ---------------------------------------------------------------------------

def test_speedometer_skips_sync_without_new_values(caplog):
    from mxnet_tpu.callback import Speedometer
    from mxnet_tpu.module.base_module import BatchEndParam

    metric = mx.metric.Accuracy()
    reads = {"n": 0}
    orig = metric.get_name_value

    def counted():
        reads["n"] += 1
        return orig()

    metric.get_name_value = counted
    spd = Speedometer(batch_size=4, frequent=1, auto_reset=False)
    lab = nd.array(np.array([1.0, 1.0]))
    pred = nd.array(np.array([[0.1, 0.9], [0.1, 0.9]]))

    import time

    with caplog.at_level(logging.INFO):
        metric.update([lab], [pred])
        spd(BatchEndParam(epoch=0, nbatch=0, eval_metric=metric,
                          locals=None))  # opens the window, no report
        time.sleep(0.01)  # non-degenerate window (elapsed > 0)
        spd(BatchEndParam(epoch=0, nbatch=1, eval_metric=metric,
                          locals=None))
        assert reads["n"] == 1  # new values -> synced and printed
        assert "Train-accuracy" in caplog.text
        caplog.clear()
        time.sleep(0.01)
        spd(BatchEndParam(epoch=0, nbatch=2, eval_metric=metric,
                          locals=None))
        assert reads["n"] == 1  # nothing new -> NO device->host sync
        assert "Speed" in caplog.text  # speed line still emitted
        assert "Train-accuracy" not in caplog.text
        metric.update([lab], [pred])
        time.sleep(0.01)
        spd(BatchEndParam(epoch=0, nbatch=3, eval_metric=metric,
                          locals=None))
        assert reads["n"] == 2  # new values -> synced again
        assert "Train-accuracy" in caplog.text


# ---------------------------------------------------------------------------
# CustomOpProp sequence-kwarg canonicalization
# ---------------------------------------------------------------------------

def test_custom_op_sequence_kwargs_stringify_as_tuples():
    from mxnet_tpu.base import frozen_attrs

    seen = []

    @mx.operator.register("attr_echo_r8")
    class _EchoProp(mx.operator.CustomOpProp):  # noqa: F841
        def __init__(self, kernel="()", scale="1"):
            seen.append((kernel, scale))
            super().__init__(need_top_grad=True)

        def list_arguments(self):
            return ["data"]

        def list_outputs(self):
            return ["output"]

        def infer_shape(self, in_shape):
            return in_shape, [in_shape[0]], []

    # both sequence spellings canonicalize to the reference's tuple text
    mx.operator.get_prop("attr_echo_r8", {"kernel": [3, 3], "scale": 2})
    mx.operator.get_prop("attr_echo_r8", {"kernel": (3, 3), "scale": 2})
    assert seen == [("(3, 3)", "2"), ("(3, 3)", "2")]
    # frozen_attrs round-trips both to the SAME tuple form, so the
    # imperative jit cache and the symbolic frontend agree
    assert frozen_attrs({"kernel": [3, 3]}) == frozen_attrs(
        {"kernel": (3, 3)})


# ---------------------------------------------------------------------------
# telemetry families
# ---------------------------------------------------------------------------

def test_pipeline_telemetry_families(monkeypatch):
    from mxnet_tpu import telemetry as tm

    tm.enable()
    try:
        tm.reset()
        (xtr, ytr), _ = get_synthetic_mnist(256, 16)
        train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=False)
        mod = mx.mod.Module(_mlp(), context=mx.cpu())
        mod.fit(train, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.5),), num_epoch=1,
                arg_params=_fixed_params())
        reg = tm.get_registry()
        fused = reg.get("metric_fused_update_total")
        assert fused is not None and fused.total() == 4  # one per batch
        syncs = reg.get("metric_host_sync_total")
        assert syncs is not None and syncs.total() >= 1  # epoch boundary
        stall = reg.get("trainer_host_stall_seconds")
        assert stall is not None and stall.count(site="window") >= 1
        text = tm.generate_text()
        assert "engine_pipeline_depth" in text
    finally:
        tm.reset()
        tm.disable()
