"""End-to-end convergence gates (parity: tests/python/train/ —
test_mlp.py / test_conv.py / test_dtype.py train small nets and assert
accuracy thresholds)."""
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import sym
from mxnet_tpu.test_utils import get_synthetic_mnist


def _conv_sym(num_classes=10):
    data = sym.Variable("data")
    net = sym.Convolution(data, name="conv1", kernel=(3, 3), num_filter=8)
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = sym.Convolution(net, name="conv2", kernel=(3, 3), num_filter=16)
    net = sym.Activation(net, act_type="relu")
    net = sym.Pooling(net, kernel=(2, 2), stride=(2, 2), pool_type="max")
    net = sym.FullyConnected(sym.Flatten(net), name="fc", num_hidden=num_classes)
    return sym.SoftmaxOutput(net, name="softmax")


def test_conv_converges():
    (xtr, ytr), (xte, yte) = get_synthetic_mnist(2048, 512)
    train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=True)
    val = mx.io.NDArrayIter(xte, yte, batch_size=64)
    mod = mx.mod.Module(_conv_sym())
    mod.fit(train, eval_data=val, num_epoch=2,
            initializer=mx.init.Xavier(),
            optimizer="sgd",
            optimizer_params={"learning_rate": 0.1, "momentum": 0.9})
    assert mod.score(val, "acc")[0][1] > 0.9


def test_fused_trainer_bf16_converges():
    """Parity: test_dtype.py — training in reduced precision (bf16
    compute, fp32 master weights) must still hit the accuracy gate."""
    import jax.numpy as jnp

    from mxnet_tpu.trainer import FusedTrainer

    (xtr, ytr), (xte, yte) = get_synthetic_mnist(2048, 512)
    tr = FusedTrainer(_conv_sym(), optimizer="sgd",
                      optimizer_params={"lr": 0.1, "momentum": 0.9,
                                        "rescale_grad": 1.0 / 64},
                      initializer=mx.init.Xavier(),
                      dtype=jnp.bfloat16)
    tr.init(data=(64, 1, 28, 28))
    for epoch in range(2):
        for i in range(0, len(xtr), 64):
            tr.step(data=xtr[i:i + 64], softmax_label=ytr[i:i + 64])
    preds = []
    for i in range(0, len(xte), 64):
        outs = tr.eval(data=xte[i:i + 64])
        preds.append(np.asarray(outs[0]).argmax(axis=1))
    acc = float((np.concatenate(preds) == yte).mean())
    assert acc > 0.9, acc


def test_adam_and_schedulers_converge():
    (xtr, ytr), (xte, yte) = get_synthetic_mnist(1024, 256)
    train = mx.io.NDArrayIter(xtr, ytr, batch_size=64, shuffle=True)
    val = mx.io.NDArrayIter(xte, yte, batch_size=64)
    data = sym.Variable("data")
    net = sym.FullyConnected(sym.Flatten(data), name="fc1", num_hidden=64)
    net = sym.Activation(net, act_type="relu")
    net = sym.FullyConnected(net, name="fc2", num_hidden=10)
    net = sym.SoftmaxOutput(net, name="softmax")
    sched = mx.lr_scheduler.FactorScheduler(step=20, factor=0.9)
    mod = mx.mod.Module(net)
    mod.fit(train, eval_data=val, num_epoch=3,
            initializer=mx.init.Xavier(),
            optimizer="adam",
            optimizer_params={"learning_rate": 1e-3, "lr_scheduler": sched})
    assert mod.score(val, "acc")[0][1] > 0.9


def test_fused_trainer_fixed_param_names():
    """Fixed params: unchanged by steps, no optimizer state, and the
    trainable subset still learns (Module fixed_param_names parity on the
    fused path)."""
    import jax.numpy as jnp

    from mxnet_tpu.trainer import FusedTrainer

    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(
            mx.sym.Activation(
                mx.sym.FullyConnected(mx.sym.Variable("data"), name="fc1",
                                      num_hidden=16),
                act_type="relu"),
            name="fc2", num_hidden=4),
        name="softmax")
    tr = FusedTrainer(net, optimizer="sgd",
                      optimizer_params={"lr": 0.5},
                      fixed_param_names=["fc1_weight", "fc1_bias"])
    tr.init(data=(8, 10))
    frozen_w = np.asarray(tr.params["fc1_weight"]).copy()
    live_w = np.asarray(tr.params["fc2_weight"]).copy()
    assert "fc1_weight" not in tr.opt_state
    rs = np.random.RandomState(0)
    for _ in range(3):
        tr.step(data=rs.uniform(size=(8, 10)).astype(np.float32),
                softmax_label=rs.randint(0, 4, 8).astype(np.float32))
    np.testing.assert_array_equal(np.asarray(tr.params["fc1_weight"]),
                                  frozen_w)
    assert not np.allclose(np.asarray(tr.params["fc2_weight"]), live_w)


def test_fused_trainer_bf16_cache_tracks_masters():
    """Mixed precision carries a DONATED bf16 compute copy updated
    inside the optimizer step; it must equal the f32 masters' bf16 cast
    after every step, and eval consumes it (same outputs as a fresh
    trainer loaded from the same masters)."""
    import jax.numpy as jnp

    from mxnet_tpu import sym
    from mxnet_tpu.trainer import FusedTrainer

    net = sym.SoftmaxOutput(sym.FullyConnected(
        sym.Variable("data"), num_hidden=4, name="fc"), name="softmax")
    tr = FusedTrainer(net, optimizer="adam", optimizer_params={"lr": 0.05},
                      dtype=jnp.bfloat16)
    tr.init(data=(8, 6))
    rs = np.random.RandomState(3)
    for i in range(5):
        tr.step(data=rs.rand(8, 6).astype(np.float32),
                softmax_label=rs.randint(0, 4, 8).astype(np.float32))
    for k, master in tr.params.items():
        assert master.dtype == jnp.float32
        cached = tr._cparams[k]
        assert cached.dtype == jnp.bfloat16
        np.testing.assert_array_equal(
            np.asarray(cached, np.float32),
            np.asarray(master.astype(jnp.bfloat16), np.float32),
            err_msg=k)
    # eval reads the carried cache: its outputs must match a fresh
    # trainer whose cache was rebuilt from these same masters
    x = rs.rand(8, 6).astype(np.float32)
    out_live = np.asarray(tr.eval(data=x)[0])
    tr2 = FusedTrainer(net, optimizer="adam", optimizer_params={"lr": 0.05},
                       dtype=jnp.bfloat16)
    tr2.init(data=(8, 6))
    tr2.params = dict(tr.params)
    tr2.aux = dict(tr.aux)
    tr2._refresh_compute_cache()
    np.testing.assert_array_equal(out_live, np.asarray(tr2.eval(data=x)[0]))


def test_fused_trainer_rmsprop_matches_module():
    """FusedTrainer's rmsprop rule == the Module/optimizer path after
    identical steps (the same oracle discipline the sgd/adam rules
    carry)."""
    from mxnet_tpu import nd, sym
    from mxnet_tpu.trainer import FusedTrainer

    rs = np.random.RandomState(5)
    x = rs.rand(32, 6).astype(np.float32)
    y = rs.randint(0, 3, 32).astype(np.float32)
    net = sym.SoftmaxOutput(sym.FullyConnected(
        sym.Variable("data"), num_hidden=3, name="fc"), name="softmax")

    np.random.seed(0)
    mx.random.seed(0)
    tr = FusedTrainer(net, optimizer="rmsprop",
                      optimizer_params={"lr": 0.01, "gamma1": 0.9})
    tr.init(data=(32, 6))
    start = {k: np.asarray(v).copy() for k, v in tr.params.items()}
    for _ in range(4):
        tr.step(data=x, softmax_label=y)

    np.random.seed(0)
    mx.random.seed(0)
    mod = mx.mod.Module(net, context=mx.cpu())
    mod.bind(data_shapes=[("data", (32, 6))],
             label_shapes=[("softmax_label", (32,))])
    mod.init_params(arg_params={k: nd.array(v) for k, v in start.items()},
                    aux_params={})
    mod.init_optimizer(optimizer="rmsprop",
                       optimizer_params={"learning_rate": 0.01,
                                         "gamma1": 0.9})
    for _ in range(4):
        mod.forward_backward(mx.io.DataBatch([nd.array(x)], [nd.array(y)]))
        mod.update()
    want, _ = mod.get_params()
    for k, v in tr.params.items():
        np.testing.assert_allclose(np.asarray(v), want[k].asnumpy(),
                                   rtol=2e-5, atol=2e-5, err_msg=k)

def test_step_multi_matches_sequential_steps():
    """step_multi(k stacked batches) must land on exactly the params that
    k sequential step() calls produce — same RNG folds, same lr
    schedule, same optimizer math — so the two are interchangeable
    mid-run (step_multi exists to amortize per-call dispatch latency,
    tools/probe_gap.py)."""
    import jax.numpy as jnp

    from mxnet_tpu.trainer import FusedTrainer

    (xtr, ytr), _ = get_synthetic_mnist(256, 16)
    k, b = 4, 32
    sched = mx.lr_scheduler.FactorScheduler(step=2, factor=0.5)

    def make():
        mx.random.seed(7)
        tr = FusedTrainer(_conv_sym(), optimizer="sgd",
                          optimizer_params={"lr": 0.1, "momentum": 0.9,
                                            "rescale_grad": 1.0 / b,
                                            "lr_scheduler": sched},
                          initializer=mx.init.Xavier(),
                          dtype=jnp.bfloat16)
        tr.init(data=(b, 1, 28, 28))
        return tr

    batches = [(xtr[i * b:(i + 1) * b], ytr[i * b:(i + 1) * b])
               for i in range(k)]

    seq = make()
    for x, y in batches:
        seq.step(data=x, softmax_label=y)

    multi = make()
    outs = multi.step_multi(
        data=np.stack([x for x, _ in batches]),
        softmax_label=np.stack([y for _, y in batches]))
    assert np.asarray(outs[0]).shape[0] == k
    assert multi._step == seq._step == k

    for name in seq.params:
        np.testing.assert_allclose(np.asarray(seq.params[name]),
                                   np.asarray(multi.params[name]),
                                   rtol=2e-5, atol=2e-5, err_msg=name)
    # and a further plain step() continues cleanly from the scanned state
    multi.step(data=batches[0][0], softmax_label=batches[0][1])
    seq.step(data=batches[0][0], softmax_label=batches[0][1])
    name = sorted(seq.params)[0]
    np.testing.assert_allclose(np.asarray(seq.params[name]),
                               np.asarray(multi.params[name]),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("call", ["step", "step_multi"])
def test_train_step_span_has_its_two_children(call):
    """``FusedTrainer.step`` and ``step_multi`` under tracing: one
    ``train_step`` span a call with ``train.shard_batch`` and
    ``train.dispatch`` inside it; with nobody looking, nothing."""
    from mxnet_tpu.telemetry import tracing
    from mxnet_tpu.trainer import FusedTrainer

    (xtr, ytr), _ = get_synthetic_mnist(64, 16)
    b = 16
    tr = FusedTrainer(_conv_sym(), optimizer="sgd",
                      optimizer_params={"lr": 0.1},
                      initializer=mx.init.Xavier())
    tr.init(data=(b, 1, 28, 28))

    def run():
        if call == "step":
            tr.step(data=xtr[:b], softmax_label=ytr[:b])
        else:
            tr.step_multi(data=np.stack([xtr[:b], xtr[b:2 * b]]),
                          softmax_label=np.stack([ytr[:b], ytr[b:2 * b]]))

    was = tracing.trace_on()
    tracing.enable_tracing(False)
    tracing.clear_spans()
    try:
        run()                                 # compiles; records nothing
        assert tracing.spans() == []
        tracing.enable_tracing(True)
        first = tr._step + 1
        run()
        run()
        spans = tracing.spans()
    finally:
        tracing.enable_tracing(was)
        tracing.clear_spans()
    assert [s["name"] for s in spans] == [
        "train.shard_batch", "train.dispatch", "train_step"] * 2
    assert {s["svc"] for s in spans} == {"trainer"}
    for shard, dispatch, whole in (spans[:3], spans[3:]):
        assert shard["parent"] == dispatch["parent"] == whole["sid"]
        assert whole["parent"] is None
        assert whole["dur_s"] >= shard["dur_s"] + dispatch["dur_s"]
    # the step the profiler's step view is told: the first one a call runs
    assert [spans[2]["step"], spans[5]["step"]] == [
        first, first + (1 if call == "step" else 2)]


def test_hwio_storage_excludes_multi_consumer_weights():
    """A conv weight with ANY consumer besides NHWC convs must stay in
    logical OIHW storage: the second reader (an in-graph weight norm
    here) would silently misread transposed axes otherwise."""
    import jax.numpy as jnp

    from mxnet_tpu.trainer import FusedTrainer

    data = sym.Variable("data")
    w = sym.Variable("c_weight")
    net = sym.Convolution(data, weight=w, kernel=(3, 3), num_filter=4,
                          pad=(1, 1), name="c")
    plain = sym.Convolution(net, kernel=(3, 3), num_filter=4, pad=(1, 1),
                            name="c2")
    pooled = sym.Pooling(plain, global_pool=True, pool_type="avg",
                         kernel=(1, 1))
    head = sym.SoftmaxOutput(sym.FullyConnected(sym.Flatten(pooled),
                                                num_hidden=3),
                             name="softmax")
    # second consumer of c_weight: an L2 penalty folded into the outputs
    penalty = sym.sum(sym.square(w))
    grouped = sym.Group([head, penalty])
    tr = FusedTrainer(grouped, optimizer="sgd",
                      optimizer_params={"lr": 0.01})
    tr.init(data=(2, 3, 8, 8))
    assert "c_weight" not in tr._hwio       # tied second use -> OIHW
    assert "c2_weight" in tr._hwio          # single-consumer -> HWIO
    rs = np.random.RandomState(0)
    outs = tr.step(data=rs.rand(2, 3, 8, 8).astype(np.float32),
                   softmax_label=rs.randint(0, 3, 2).astype(np.float32))
    assert all(np.isfinite(np.asarray(o)).all() for o in outs)
    # stored layouts match the discovery decision
    assert tr.params["c_weight"].shape == (4, 3, 3, 3)
    assert tr.params["c2_weight"].shape == (3, 3, 4, 4)


def test_hwio_states_checkpoint_is_layout_portable(tmp_path, monkeypatch):
    """Optimizer-state files are logical OIHW on disk: a checkpoint
    saved by an HWIO-storage trainer must load into a trainer with
    MXTPU_HWIO_STORAGE=0 (and vice versa) with identical slot values."""
    import jax.numpy as jnp

    from mxnet_tpu import models
    from mxnet_tpu.trainer import FusedTrainer

    net = models.get_symbol("resnet-18", num_classes=10,
                            image_shape=(3, 16, 16))

    def make():
        t = FusedTrainer(net, optimizer="sgd",
                         optimizer_params={"lr": 0.1, "momentum": 0.9})
        return t.init(data=(2, 3, 16, 16))

    tr = make()
    assert tr._hwio  # HWIO storage active by default
    rs = np.random.RandomState(0)
    for _ in range(2):
        tr.step(data=rs.rand(2, 3, 16, 16).astype(np.float32),
                softmax_label=rs.randint(0, 10, 2).astype(np.float32))
    prefix = str(tmp_path / "ck")
    tr.save_checkpoint(prefix, 1, save_optimizer_states=True)

    monkeypatch.setenv("MXTPU_HWIO_STORAGE", "0")
    tr2 = make()
    assert not tr2._hwio
    tr2.load_checkpoint(prefix, 1, load_optimizer_states=True)
    name = sorted(tr._hwio)[0]
    # params: tr stores HWIO, tr2 stores OIHW — logically equal
    np.testing.assert_allclose(
        np.transpose(np.asarray(tr.params[name]), (3, 2, 0, 1)),
        np.asarray(tr2.params[name]), rtol=0, atol=0)
    # momentum slots likewise
    np.testing.assert_allclose(
        np.transpose(np.asarray(tr.opt_state[name][0]), (3, 2, 0, 1)),
        np.asarray(tr2.opt_state[name][0]), rtol=0, atol=0)
    # and tr2 keeps training without shape errors
    tr2.step(data=rs.rand(2, 3, 16, 16).astype(np.float32),
             softmax_label=rs.randint(0, 10, 2).astype(np.float32))
