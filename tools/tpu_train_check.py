"""On-silicon training convergence check (parity: the reference's
tests/python/train suite — test_mlp/test_conv assert accuracy, not just
op numerics).  Trains two small models through the bf16 FusedTrainer on
the REAL chip and asserts accuracy above floor.

Run on the bench chip:  python tools/tpu_train_check.py
CPU smoke:  MXTPU_PLATFORM=cpu python tools/tpu_train_check.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def check_mlp():
    import jax.numpy as jnp

    from mxnet_tpu import sym
    from mxnet_tpu.initializer import Xavier
    from mxnet_tpu.trainer import FusedTrainer

    np.random.seed(0)  # the initializer draws from the global RNG
    rs = np.random.RandomState(0)
    x = rs.uniform(-1, 1, (512, 32)).astype(np.float32)
    y = ((x[:, :16].sum(1) - x[:, 16:].sum(1)) > 0).astype(np.float32)
    net = sym.SoftmaxOutput(sym.FullyConnected(sym.Activation(
        sym.FullyConnected(sym.Variable("data"), num_hidden=64, name="fc1"),
        act_type="relu"), num_hidden=2, name="fc2"), name="softmax")
    tr = FusedTrainer(net, optimizer="sgd",
                      optimizer_params={"lr": 0.1},
                      dtype=jnp.bfloat16, initializer=Xavier())
    tr.init(data=(128, 32))
    for epoch in range(15):
        for i in range(4):
            tr.step(data=x[i * 128:(i + 1) * 128],
                    softmax_label=y[i * 128:(i + 1) * 128])
    out = np.asarray(tr.eval(data=x[:128])[0])
    acc = float(((out[:, 1] > out[:, 0]) == (y[:128] > 0)).mean())
    print(f"mlp_train_acc: {acc:.3f}", flush=True)
    assert acc > 0.95, acc


def check_conv():
    import jax.numpy as jnp

    from mxnet_tpu import sym
    from mxnet_tpu.trainer import FusedTrainer

    np.random.seed(1)  # the initializer draws from the global RNG
    rs = np.random.RandomState(1)
    n = 512
    x = rs.uniform(0, 0.2, (n, 1, 16, 16)).astype(np.float32)
    y = rs.randint(0, 2, n)
    for i, c in enumerate(y):  # class lights the left or right half
        x[i, 0, :, (0 if c == 0 else 8):(8 if c == 0 else 16)] += 0.8
    y = y.astype(np.float32)
    net = sym.Variable("data")
    net = sym.Convolution(net, num_filter=8, kernel=(3, 3), pad=(1, 1),
                          name="c1")
    net = sym.BatchNorm(net, name="bn1")
    net = sym.Activation(net, act_type="relu")
    net = sym.SoftmaxOutput(sym.FullyConnected(sym.Flatten(net),
                                               num_hidden=2, name="fc"),
                            name="softmax")
    tr = FusedTrainer(net, optimizer="sgd", optimizer_params={"lr": 0.1},
                      dtype=jnp.bfloat16)  # default Uniform init: Xavier
    #                                        over-scales this shallow
    #                                        conv+BN stack (tested A/B)
    tr.init(data=(64, 1, 16, 16))
    for epoch in range(15):
        for i in range(8):
            tr.step(data=x[i * 64:(i + 1) * 64],
                    softmax_label=y[i * 64:(i + 1) * 64])
    out = np.asarray(tr.eval(data=x[:64])[0])
    acc = float(((out[:, 1] > out[:, 0]) == (y[:64] > 0)).mean())
    print(f"conv_bn_train_acc: {acc:.3f}", flush=True)
    assert acc > 0.95, acc


def main():
    if os.environ.get("MXTPU_PLATFORM") == "cpu":
        import jax

        jax.config.update("jax_platforms", "cpu")
    import jax

    from mxnet_tpu import compile_cache

    compile_cache.enable()
    print("devices:", jax.devices(), flush=True)
    tic = time.perf_counter()
    check_mlp()
    check_conv()
    print(f"TRAIN-ON-DEVICE OK ({time.perf_counter() - tic:.1f}s)",
          flush=True)


if __name__ == "__main__":
    main()
