"""Pieces the served decoder families share (``models/ling.py``,
``models/sdar.py``, ``models/kimi.py``): RMSNorm, a bias-free linear
layer over weights stored ``(out, in)``, SwiGLU, and the DeepSeek-V3
expert layer (sigmoid router with a correction bias, this chip's share
of the routed experts, one shared expert).  Plain functions over
arrays; a family's own file holds what is its own; latent attention is
in ``models/mla.py``."""
from __future__ import annotations

import jax
import jax.numpy as jnp

from ..base import mxu_precision
from ..parallel.moe import moe_serve

__all__ = ["rms_norm", "lin", "swiglu", "moe_block"]


def rms_norm(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), -1, keepdims=True)
                            + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def lin(x, w):
    return jnp.dot(x, w.T, precision=mxu_precision(x, w))


def swiglu(x, gate, up, down):
    return lin(jax.nn.silu(lin(x, gate)) * lin(x, up), down)


def moe_block(p, pre, x, view, c):
    """The expert layer of ``x`` (N, D) from the leaves ``p[pre +
    ...]``: the routed part of the experts held here
    (``parallel/moe.py:moe_serve``, whose four counts go to
    ``view.count``) plus the shared expert, in the named scopes
    ``moe.route``, ``moe.experts`` and ``moe.shared``.  ``c`` gives
    ``expert_offset``, ``top_k``, ``n_group``, ``topk_group`` and
    ``scale``."""
    y, counts = moe_serve(
        x, p[pre + "router_weight"], p[pre + "router_bias"],
        p[pre + "experts_gate_weight"], p[pre + "experts_up_weight"],
        p[pre + "experts_down_weight"], expert_offset=c.expert_offset,
        top_k=c.top_k, n_group=c.n_group, topk_group=c.topk_group,
        scale=c.scale, valid=view.valid)
    view.count(counts)
    with jax.named_scope("moe.shared"):
        shared = swiglu(x, p[pre + "shared_gate_weight"],
                        p[pre + "shared_up_weight"],
                        p[pre + "shared_down_weight"])
    return y + shared
