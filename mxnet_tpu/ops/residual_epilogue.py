"""Fused residual-epilogue kernel: (x + shortcut) * scale + bias -> ReLU.

The TVM argument (arXiv:1802.04799) in one op: the ``conv3 + shortcut``
tail of a ResNet bottleneck is a chain XLA leaves as several HBM-bound
elementwise kernels around the convolution — per-channel affine
(inference BatchNorm folded to scale/bias, or any affine), the residual
add, and the ReLU each re-read the activation.  This kernel computes
the whole epilogue in ONE NHWC Pallas pass over VMEM tiles: each
``(block_rows, C)`` tile of the ``(N*H*W, C)`` view is read once,
combined, and written once.

Three lowerings behind one ``custom_vjp`` function:

- **pallas**: the TPU kernel (``ctx.platform == "tpu"`` and the shape
  qualifies — C a lane multiple, rows tileable);
- **pallas interpret**: the same kernel interpreted on CPU (parity
  tests);
- **lax**: the plain jnp expression — CPU default and the fallback for
  shapes the kernel does not tile.  Same math, so tier-1 (CPU) runs
  identically whichever path a platform picks.

The row-block size is the autotuner's first tuned knob (ISSUE 18):
``_block_rows_for`` consults ``mxnet_tpu.autotune.schedule_for`` (the
pure lookup plane — safe at trace time) and :func:`tune` is the
bind-time search call site that installs a per-(rows, C, dtype) winner
in the ``MXTPU_SCHEDULE_CACHE``.

The backward is lax (elementwise selects + two per-channel reductions
— XLA fuses these fine; the win of the hand kernel is the forward,
which sits between two convolutions in the hot path).  The custom VJP
exists so autodiff never differentiates *through* the Pallas body.

Graph entry points (matched by passes/residual_epilogue.py so model
code does not change):

- ``_residual_epilogue(data, shortcut)``: plain ``relu(x + s)``.
- ``_residual_epilogue_bn(data, shortcut, gamma, beta | mean, var)``:
  ``relu(BatchNorm(x + s))``.  Train-mode batch statistics cannot fold
  into a per-channel affine, so with ``is_train`` (and no
  use_global_stats) the op REPLAYS the exact unfused composite —
  bit-identical math, aux updates included; inference folds the moving
  stats into (scale, bias) and runs the fused kernel.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ..base import parse_attr, parse_bool
from .registry import register

# row-block of the (rows, C) view each grid step processes; rows are
# N*H*W of an NHWC activation, so real batches divide 256 comfortably.
# The DEFAULT — the autotuner's first tuned knob (ISSUE 18) can
# override it per (rows, C, dtype) through the schedule cache.
_BLOCK_ROWS = 256
# the search space tune() measures: default included (a search can
# never lose to not searching), 512 gives headroom above the default
_CANDIDATE_BLOCK_ROWS = (512, 256, 128, 64, 32, 16, 8)


def supports(rows: int, channels: int) -> bool:
    """Can the Pallas kernel tile this (rows, C) view without padding?
    C must fill whole 128-wide lanes; rows must split into row blocks
    (a multiple of 8 sublanes).  ResNet-50's residual tails (C = 256 /
    512 / 1024 / 2048, rows = N*H*W) all qualify."""
    if channels % 128 != 0:
        return False
    return rows % _default_block_rows(rows) == 0 and rows >= 8


def _default_block_rows(rows: int) -> int:
    if rows % _BLOCK_ROWS == 0:
        return _BLOCK_ROWS
    for b in (128, 64, 32, 16, 8):
        if rows % b == 0:
            return b
    return rows  # not tileable; supports() returns False upstream


def _keysig(rows: int, channels: int, dtype) -> str:
    return "r%dc%d_%s" % (rows, channels, jnp.dtype(dtype).name)


def _block_rows_for(rows: int, channels: int, dtype) -> int:
    """The row block the kernel tiles with: the tuned winner for this
    (rows, C, dtype) when the schedule cache holds one, the static
    default otherwise.  ``schedule_for`` is the autotuner's PURE plane
    — safe here even though this runs at trace time inside the jitted
    graph."""
    from .. import autotune as _autotune

    default = _default_block_rows(rows)
    sched = _autotune.schedule_for(
        "residual_epilogue", _keysig(rows, channels, dtype),
        {"block_rows": default})
    br = int(sched.get("block_rows", default))
    return br if (br > 0 and rows % br == 0) else default


def _epilogue_kernel(x_ref, s_ref, sc_ref, b_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    s = s_ref[...].astype(jnp.float32)
    sc = sc_ref[...].astype(jnp.float32)   # (1, C), broadcasts over rows
    b = b_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.maximum((x + s) * sc + b, 0.0).astype(o_ref.dtype)


def _pallas_fwd(x2, s2, scale, bias, interpret, block_rows=None):
    rows, c = x2.shape
    br = (int(block_rows) if block_rows
          else _block_rows_for(rows, c, x2.dtype))
    sc2 = scale.reshape(1, c)
    b2 = bias.reshape(1, c)
    return pl.pallas_call(
        _epilogue_kernel,
        grid=(rows // br,),
        in_specs=[
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((br, c), lambda i: (i, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
            pl.BlockSpec((1, c), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((br, c), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, c), x2.dtype),
        interpret=interpret,
        name="residual_epilogue",
    )(x2, s2, sc2, b2)


def _lax_fwd(x, s, scale, bias, channel_axis):
    bshape = [1] * x.ndim
    bshape[channel_axis] = x.shape[channel_axis]
    t = ((x + s).astype(jnp.float32) * scale.reshape(bshape)
         + bias.reshape(bshape))
    return jnp.maximum(t, 0.0).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def _epilogue(x, s, scale, bias, channel_axis, use_pallas, interpret):
    out, _ = _epilogue_fwd(x, s, scale, bias, channel_axis, use_pallas,
                           interpret)
    return out


def _epilogue_fwd(x, s, scale, bias, channel_axis, use_pallas, interpret):
    # trace-ok: use_pallas/channel_axis/interpret are custom_vjp
    # nondiff_argnums — static Python values at trace time, never tracers
    if use_pallas and channel_axis == x.ndim - 1:
        c = x.shape[-1]
        rows = int(np.prod(x.shape[:-1]))
        x2 = x.reshape(rows, c)
        s2 = s.reshape(rows, c)
        out = _pallas_fwd(x2, s2, scale, bias, interpret).reshape(x.shape)
    else:
        out = _lax_fwd(x, s, scale, bias, channel_axis)
    return out, (x, s, scale, out)


def _epilogue_bwd(channel_axis, use_pallas, interpret, res, g):
    x, s, scale, out = res
    bshape = [1] * x.ndim
    bshape[channel_axis] = x.shape[channel_axis]
    axes = tuple(a for a in range(x.ndim) if a != channel_axis)
    mask = (out > 0)
    g32 = jnp.where(mask, g.astype(jnp.float32), 0.0)
    gs = g32 * scale.reshape(bshape).astype(jnp.float32)
    total32 = (x + s).astype(jnp.float32)
    dscale = jnp.sum(g32 * total32, axis=axes)
    # bias is not saved (its value never enters the backward); its grad
    # adopts the scale's dtype — the pair is always allocated together
    dbias = jnp.sum(g32, axis=axes)
    return (gs.astype(x.dtype), gs.astype(s.dtype),
            dscale.astype(scale.dtype), dbias.astype(scale.dtype))


_epilogue.defvjp(_epilogue_fwd, _epilogue_bwd)


def residual_epilogue(x, s, scale=None, bias=None, channel_axis=-1,
                      platform=None, impl="auto", interpret=False):
    """Functional entry: ``relu((x + s) * scale + bias)``.

    ``impl``: ``auto`` (Pallas on TPU when the shape tiles, lax
    otherwise), ``lax``, ``pallas``, ``pallas_interpret`` (the kernel
    interpreted on CPU — the parity-test hook)."""
    channel_axis = channel_axis % x.ndim
    c = x.shape[channel_axis]
    if scale is None:
        scale = jnp.ones((c,), jnp.float32)
    if bias is None:
        bias = jnp.zeros((c,), jnp.float32)
    rows = int(np.prod(x.shape)) // max(c, 1)
    if impl == "pallas_interpret":
        use_pallas, interpret = True, True
    elif impl == "pallas":
        use_pallas = True
    elif impl == "lax":
        use_pallas = False
    else:  # auto: hand kernel only where it wins and tiles
        use_pallas = (platform == "tpu" and channel_axis == x.ndim - 1
                      and supports(rows, c))
    if use_pallas and (channel_axis != x.ndim - 1 or not supports(rows, c)):
        use_pallas = False  # shape gate even when forced (ragged shapes)
    return _epilogue(x, s, scale, bias, channel_axis, use_pallas,
                     bool(interpret))


def tune(rows, channels, dtype=jnp.float32, interpret=None):
    """Search ``block_rows`` for the ``(rows, C)`` epilogue view and
    install the winner in the schedule cache (a bind-time call site —
    benches and tests call this; the traced kernel only ever does the
    pure ``schedule_for`` lookup).  On a host without a TPU the kernel
    is measured in interpret mode — tuning the parity tool honestly
    rather than pretending to time hardware it does not have.  Returns
    the winning schedule dict (``{"block_rows": N}``)."""
    from .. import autotune as _autotune

    rows, channels = int(rows), int(channels)
    if not supports(rows, channels):
        return {"block_rows": _default_block_rows(rows)}
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    name = jnp.dtype(dtype).name
    rs = np.random.RandomState(0)
    x2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(name))
    s2 = jnp.asarray(rs.normal(size=(rows, channels)).astype(name))
    scale = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))
    bias = jnp.asarray(rs.normal(size=(channels,)).astype(np.float32))

    def bench(cand):
        br = int(cand["block_rows"])
        if br <= 0 or rows % br:
            raise ValueError("block_rows %d does not tile %d rows"
                             % (br, rows))
        fn = jax.jit(functools.partial(
            _pallas_fwd, interpret=bool(interpret), block_rows=br))
        return lambda: fn(x2, s2, scale, bias)

    return _autotune.ensure(
        "residual_epilogue", _keysig(rows, channels, dtype),
        {"block_rows": _default_block_rows(rows)},
        [{"block_rows": b} for b in _CANDIDATE_BLOCK_ROWS
         if b <= rows and rows % b == 0],
        bench, warmup=1, best_of=3)


# ---------------------------------------------------------------------------
# op registrations (graph entry points for passes/residual_epilogue.py)
# ---------------------------------------------------------------------------
def _channel_axis(attrs, ndim):
    return ndim - 1 if attrs.get("__layout__") == "NHWC" else 1


@register("_residual_epilogue", arg_names=("data", "shortcut"))
def _residual_epilogue_op(ctx, data, shortcut, **attrs):
    """``relu(data + shortcut)`` as one fused epilogue (the affine is
    identity).  Lowering picked per ctx.platform; ``impl`` overrides."""
    ax = _channel_axis(attrs, data.ndim)
    return residual_epilogue(
        data, shortcut, channel_axis=ax, platform=ctx.platform,
        impl=str(attrs.get("impl", "auto")))


def _epi_bn_params(attrs, data_shape, *rest):
    if data_shape is None:
        raise TypeError("need data shape")
    ax = _channel_axis(attrs, len(data_shape))
    c = data_shape[ax]
    return {"gamma": (c,), "beta": (c,),
            "moving_mean": (c,), "moving_var": (c,)}


@register(
    "_residual_epilogue_bn",
    arg_names=("data", "shortcut", "gamma", "beta"),
    param_names=("gamma", "beta"),
    aux_names=("moving_mean", "moving_var"),
    infer_params=_epi_bn_params,
)
def _residual_epilogue_bn_op(ctx, data, shortcut, gamma, beta,
                             moving_mean, moving_var, **attrs):
    """``relu(BatchNorm(data + shortcut))``.

    Train mode (no use_global_stats) REPLAYS the exact unfused
    composite — the batch statistics cannot fold into a static affine,
    and replaying the same op fns keeps the rewrite bit-identical to
    the pass-off graph (the parity contract of passes/).  Inference
    folds the moving stats into (scale, bias) and runs the fused
    kernel; aux states pass through unchanged, like eval-mode
    BatchNorm."""
    from . import registry as _registry

    use_global = parse_bool(attrs.get("use_global_stats", False))
    if ctx.is_train and not use_global:
        total = data + shortcut
        out, aux_updates = _registry.get("BatchNorm").fn(
            ctx, total, gamma, beta, moving_mean, moving_var, **attrs)
        return jax.nn.relu(out), aux_updates
    eps = float(parse_attr(attrs.get("eps", 1e-3)))
    fix_gamma = parse_bool(attrs.get("fix_gamma", True))
    g32 = (jnp.ones_like(gamma) if fix_gamma else gamma).astype(jnp.float32)
    scale = g32 * jax.lax.rsqrt(moving_var.astype(jnp.float32) + eps)
    bias = beta.astype(jnp.float32) - moving_mean.astype(jnp.float32) * scale
    ax = _channel_axis(attrs, data.ndim)
    out = residual_epilogue(
        data, shortcut, scale, bias, channel_axis=ax,
        platform=ctx.platform, impl=str(attrs.get("impl", "auto")))
    return out, (moving_mean, moving_var)
