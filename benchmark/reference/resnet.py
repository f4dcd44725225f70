"""Plain reference: a 50-layer residual network in float32
``jax.numpy``.

Written from the published descriptions: He et al., "Deep Residual
Learning" (arXiv:1512.03385, table 1: the 50-layer column -- a 7x7/2
stem, a 3x3/2 max pool, four stages of 3, 4, 6, 3 bottleneck units at
256, 512, 1024, 2048 channels, global average pool, a 1000-way
classifier), with the units in the pre-activation order of He et al.,
"Identity Mappings" (arXiv:1603.05027) that MXNet's
``example/image-classification/symbols/resnet.py`` builds and the
program follows: BN-ReLU-conv three times, the projection shortcut
taken from the first activation, stride on the 3x3 convolution, a batch
norm on the input image with its scale fixed at one, and a BN-ReLU
before the pool.  Batch norm uses the batch's own statistics (biased
variance), eps 2e-5.  No kernel; convolutions at ``highest`` precision.
It imports nothing of the program.

``compute="fp8"`` is the control: both operands of every convolution
and of the classifier rounded to float8 e4m3 and the gradient flowing
back into each rounded to e5m2, one scale a tensor (see
``reference/gpt2.py``).
"""
import functools

import jax
import jax.numpy as jnp

from benchmark.reference.gpt2 import _q8, _q8_grad

HIGHEST = jax.lax.Precision.HIGHEST
BN_EPS = 2e-5


def _conv(x, w, stride, pad, compute):
    if compute not in ("f32", "fp8"):
        raise ValueError(f"unknown compute {compute!r}")
    if compute == "fp8":
        x, w = _q8(x), _q8(w)
    y = jax.lax.conv_general_dilated(
        x, w, (stride, stride), ((pad, pad), (pad, pad)),
        dimension_numbers=("NCHW", "OIHW", "NCHW"), precision=HIGHEST)
    return _q8_grad(y) if compute == "fp8" else y


def _bn(x, gamma, beta):
    mean = jnp.mean(x, axis=(0, 2, 3), keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=(0, 2, 3), keepdims=True)
    shape = (1, -1, 1, 1)
    return (x - mean) * jax.lax.rsqrt(var + BN_EPS) * gamma.reshape(shape) \
        + beta.reshape(shape)


def _bn_relu(p, name, x):
    return jax.nn.relu(_bn(x, p[name + "_gamma"], p[name + "_beta"]))


def _unit(p, name, x, stride, match, compute):
    a1 = _bn_relu(p, name + "_bn1", x)
    y = _conv(a1, p[name + "_conv1_weight"], 1, 0, compute)
    y = _conv(_bn_relu(p, name + "_bn2", y), p[name + "_conv2_weight"],
              stride, 1, compute)
    y = _conv(_bn_relu(p, name + "_bn3", y), p[name + "_conv3_weight"],
              1, 0, compute)
    short = x if match else _conv(a1, p[name + "_sc_weight"], stride, 0,
                                  compute)
    return y + short


def logits(p, images, units, compute="f32"):
    """``images`` (N, 3, H, W) float32 -> class scores (N, classes)."""
    x = _bn(images, jax.lax.stop_gradient(jnp.ones_like(p["bn_data_gamma"])),
            p["bn_data_beta"])
    x = _conv(x, p["conv0_weight"], 2, 3, compute)
    x = _bn_relu(p, "bn0", x)
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, (1, 1, 3, 3),
                              (1, 1, 2, 2), ((0, 0), (0, 0), (1, 1), (1, 1)))
    for s, n in enumerate(units, start=1):
        for u in range(1, n + 1):
            name = f"stage{s}_unit{u}"
            stride = 2 if (u == 1 and s > 1) else 1
            # each unit recomputed in the backward pass: float32
            # activations of a whole batch would not fit otherwise
            unit = jax.checkpoint(functools.partial(
                _unit, name=name, stride=stride, match=u > 1,
                compute=compute))
            x = unit({k: v for k, v in p.items() if k.startswith(name)},
                     x=x)
    x = _bn_relu(p, "bn1", x)
    x = jnp.mean(x, axis=(2, 3))
    w, b = p["fc1_weight"], p["fc1_bias"]
    if compute == "fp8":
        x, w = _q8(x), _q8(w)
    y = jnp.einsum("nc,kc->nk", x, w, precision=HIGHEST)
    return (_q8_grad(y) if compute == "fp8" else y) + b


def _sum_ce(p, images, labels, units, compute):
    logp = jax.nn.log_softmax(logits(p, images, units, compute), axis=-1)
    return -jnp.sum(jnp.take_along_axis(logp, labels[:, None], axis=1))


@functools.partial(jax.jit, static_argnames=("units", "compute"))
def loss_and_grads(p, images, labels, units, compute="f32"):
    """(summed cross-entropy, its gradient) over the whole batch: batch
    norm ties the rows together, so there are no blocks of rows here."""
    return jax.value_and_grad(_sum_ce)(p, images, labels, units, compute)
