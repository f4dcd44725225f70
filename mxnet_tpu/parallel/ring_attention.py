"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO sequence parallelism — long sequences are handled by
bucketing + gradient mirroring (SURVEY.md §5.7).  On TPU, SP is first-class
(SURVEY.md §2.4 'Sequence/context parallelism' row): sequences shard over
the mesh's 'seq' axis and attention runs either as

- ring_attention: K/V blocks rotate around the ring via lax.ppermute while
  each device streams an online-softmax accumulation (blockwise attention;
  the ppermute rides ICI neighbor links, compute overlaps communication
  when XLA schedules the collective-permute asynchronously), or
- ulysses_attention: all-to-all re-shards (seq -> heads), each device runs
  full-sequence attention for its head slice, then all-to-all back.

Both are exact (not approximations) and differentiable (pure jnp/lax, so
jax.vjp handles the backward — the backward ppermutes run in the reverse
ring direction automatically).
"""
from __future__ import annotations


import jax
import jax.numpy as jnp

from ..base import mxu_precision
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P



def _stream_block(q, k, v, m, l, o, scale, mask=None):
    """One online-softmax accumulation step (blockwise attention inner op).

    q: (B, H, Tq, D), k/v: (B, H, Tk, D); m/l: (B, H, Tq); o accumulator.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=mxu_precision(q, k)) * scale
    if mask is not None:
        s = jnp.where(mask, s, -jnp.inf)
    m_new = jnp.maximum(m, jnp.max(s, axis=-1))
    # guard fully-masked rows (max = -inf)
    m_safe = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    corr = jnp.exp(jnp.where(jnp.isfinite(m), m - m_safe, -jnp.inf))
    p = jnp.exp(s - m_safe[..., None])
    if mask is not None:
        p = jnp.where(mask, p, 0.0)
    l_new = l * corr + jnp.sum(p, axis=-1)
    o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=mxu_precision(p, v))
    return m_new, l_new, o_new


def ring_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                   causal: bool = False, scale: float = None,
                   batch_axis: str = None):
    """Exact attention over sequence-sharded q/k/v.

    q, k, v: (B, H, T_global, D) arrays sharded over T on `axis_name`.
    Returns output with the same sharding.  ``batch_axis`` additionally
    shards B over a second mesh axis — the standard dp x sp long-context
    layout (each data-parallel replica runs its own ring).
    """
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    n = mesh.shape[axis_name]
    spec = P(batch_axis, None, axis_name, None)

    def local_fn(q, k, v):
        # q/k/v here are the local shards (B_local, H, T/n, D)
        b, h, t_local, _ = q.shape
        idx = jax.lax.axis_index(axis_name)
        m0 = jnp.full((b, h, t_local), -jnp.inf, q.dtype)
        l0 = jnp.zeros((b, h, t_local), q.dtype)
        o0 = jnp.zeros_like(q)

        q_pos = idx * t_local + jnp.arange(t_local)

        def body(step, carry):
            m, l, o, k_cur, v_cur = carry
            src_idx = (idx - step) % n  # whose K/V block we hold this step
            if causal:
                k_pos = src_idx * t_local + jnp.arange(t_local)
                mask = q_pos[:, None] >= k_pos[None, :]
                mask = jnp.broadcast_to(mask, (b, h, t_local, t_local))
            else:
                mask = None
            m, l, o = _stream_block(q, k_cur, v_cur, m, l, o, scale, mask)
            perm = [(i, (i + 1) % n) for i in range(n)]  # pass K/V to next rank
            k_next = jax.lax.ppermute(k_cur, axis_name, perm)
            v_next = jax.lax.ppermute(v_cur, axis_name, perm)
            return (m, l, o, k_next, v_next)

        m, l, o, _, _ = jax.lax.fori_loop(0, n, body, (m0, l0, o0, k, v))
        return o / jnp.maximum(l, 1e-20)[..., None]

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def ulysses_attention(q, k, v, mesh: Mesh, axis_name: str = "seq",
                      causal: bool = False, scale: float = None,
                      batch_axis: str = None):
    """DeepSpeed-Ulysses-style SP: all-to-all (seq->heads), full local
    attention, all-to-all back.  Requires H % mesh.shape[axis] == 0.
    ``batch_axis`` additionally shards B over a second mesh axis (dp x
    sp; the all-to-alls stay within each data replica's 'seq' group)."""
    h, d = q.shape[1], q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    n = mesh.shape[axis_name]
    if h % n != 0:
        raise ValueError(f"heads {h} not divisible by seq-par degree {n}")
    spec = P(batch_axis, None, axis_name, None)

    def local_fn(q, k, v):
        # local: (B, H, T/n, D) -> a2a -> (B, H/n, T, D)
        def a2a(x):
            return jax.lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2,
                                      tiled=True)

        ql, kl, vl = a2a(q), a2a(k), a2a(v)
        s = jnp.einsum("bhqd,bhkd->bhqk", ql, kl,
                       precision=mxu_precision(ql, kl)) * scale
        if causal:
            tq = s.shape[-2]
            mask = jnp.tril(jnp.ones((tq, tq), bool))
            s = jnp.where(mask, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        ol = jnp.einsum("bhqk,bhkd->bhqd", p, vl, precision=mxu_precision(p, vl))
        # back: (B, H/n, T, D) -> (B, H, T/n, D)
        return jax.lax.all_to_all(ol, axis_name, split_axis=2, concat_axis=1,
                                  tiled=True)

    return jax.shard_map(local_fn, mesh=mesh, in_specs=(spec, spec, spec),
                     out_specs=spec, check_vma=False)(q, k, v)


def full_attention(q, k, v, causal=False, scale=None):
    """Single-device reference attention (the oracle for SP tests) —
    materializes the (T, T) score matrix; use :func:`attention` for the
    memory-efficient dispatcher."""
    d = q.shape[-1]
    scale = scale if scale is not None else 1.0 / np.sqrt(d)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   precision=mxu_precision(q, k)) * scale
    if causal:
        t = s.shape[-1]
        mask = jnp.tril(jnp.ones((s.shape[-2], t), bool))
        s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, precision=mxu_precision(p, v))


def _kernel_spec(mesh, shape):
    """How the flash kernel's ``(B, H, T, dh)`` operands split over
    ``mesh``: batch over the data axis, heads over ``model`` (the
    megatron layout of the q/k/v projections), each only where it
    divides.  None when there is nothing to split over."""
    if mesh is None or mesh.size == 1:
        return None

    def axis(names, dim):
        for name in names:
            n = mesh.shape.get(name, 1)
            if n > 1 and dim % n == 0:
                return name
        return None

    return P(axis(("data", "batch"), shape[0]), axis(("model",), shape[1]),
             None, None)


def attention(q, k, v, causal=False, scale=None, impl="auto", platform=None,
              mesh=None):
    """Single-device attention dispatcher.

    impl='flash' (or 'auto' on TPU with block-compatible shapes) runs the
    Pallas flash kernels (ops/flash_attention.py) — O(T·D) memory, score
    tiles live only in VMEM.  Everything else falls back to the lax path
    (XLA still fuses well, but the (T, T) scores hit HBM).

    ``platform`` is the platform this call will lower FOR (threaded from
    OpCtx by the symbol-graph path); None falls back to the process
    default backend.  The distinction matters whenever a computation
    targets non-default devices — a CPU mesh on a TPU-attached host
    would otherwise pick the Pallas kernel and fail to lower.

    ``mesh`` is the device mesh the surrounding program is partitioned
    over.  GSPMD refuses a Mosaic kernel ("cannot be automatically
    partitioned"), so under a mesh the flash kernel runs inside a
    ``shard_map``: every device attends over its own batch rows and
    heads, no collective."""
    from ..ops import flash_attention as fa

    if impl == "auto":
        on_tpu = (platform or jax.default_backend()) == "tpu"
        impl = "flash" if on_tpu and fa.supports(q.shape, q.dtype) else "lax"
    if impl in ("flash", "flash_interpret"):
        # flash_interpret: the CPU test path for the kernels
        def kernel(q, k, v):
            return fa.flash_attention(q, k, v, causal, scale,
                                      interpret=impl == "flash_interpret")

        spec = _kernel_spec(mesh, q.shape)
        if spec is not None:
            kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(spec,) * 3,
                                   out_specs=spec, check_vma=False)
        return kernel(q, k, v)
    return full_attention(q, k, v, causal=causal, scale=scale)
