"""Pieces the served decoder families share (``models/ling.py``,
``models/sdar.py``): RMSNorm, a bias-free linear layer over weights
stored ``(out, in)``, SwiGLU.  Plain functions over arrays; a family's
own file holds what is its own."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import mxu_precision

__all__ = ["rms_norm", "lin", "swiglu"]


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def lin(x, w):
    return jnp.dot(x, w.T, precision=mxu_precision(x, w))


def swiglu(x, gate, up, down):
    return lin(jax.nn.silu(lin(x, gate)) * lin(x, up), down)
