"""Flash attention — Pallas TPU kernels with full custom-VJP backward.

The reference has no attention kernels at all (its long-sequence story is
bucketing, SURVEY.md §5.7); this is the TPU-native hot-op the framework's
sequence stack builds on: blockwise online-softmax attention computed in
VMEM (never materializing the (T, T) score matrix in HBM), forward +
backward as Pallas kernels on the MXU.

Used by parallel/ring_attention.py for the per-device local attention
(the ring rotates K/V shards; each local block product runs here) and
directly via ``flash_attention`` for single-chip long sequences.

Layout: (B, H, T, D).  T must divide by the block sizes and D by 8
(lane padding covers D < 128; 128-multiples tile the MXU best) —
``supports`` reports whether a shape qualifies, the auto dispatcher
(parallel/ring_attention.attention) falls back to the pure-lax path
otherwise, and direct calls with ragged shapes raise.
``interpret=True`` runs the same kernels on CPU for tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free


def supports(q_shape, block_q=128, block_k=128):
    """True when the Pallas path handles this shape without padding."""
    b, h, t, d = q_shape
    return t % block_q == 0 and t % block_k == 0 and d % 8 == 0


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *, scale, causal,
                block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32) * scale            # (block_q, d)
    d = q.shape[-1]

    num_k = seq_len // block_k
    if causal:
        # only blocks with k_start <= q_end participate
        num_k_live = (qi * block_q + block_q + block_k - 1) // block_k
    else:
        num_k_live = num_k

    def body(ki, carry):
        m, l, acc = carry
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[:, None])
        l = l * corr + jnp.sum(p, axis=-1)
        acc = acc * corr[:, None] + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        return m_new, l, acc

    m0 = jnp.full((block_q,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((block_q,), jnp.float32)
    acc0 = jnp.zeros((block_q, d), jnp.float32)
    m, l, acc = jax.lax.fori_loop(0, num_k_live, body, (m0, l0, acc0))

    l_safe = jnp.maximum(l, 1e-20)
    o_ref[0] = (acc / l_safe[:, None]).astype(o_ref.dtype)
    lse_ref[0, :, 0] = m + jnp.log(l_safe)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   *, scale, causal, block_q, block_k, seq_len):
    qi = pl.program_id(1)
    q = q_ref[0].astype(jnp.float32)
    do = do_ref[0].astype(jnp.float32)
    lse = lse_ref[0, :, 0]
    delta = delta_ref[0, :, 0]
    d = q.shape[-1]

    num_k = seq_len // block_k
    num_k_live = ((qi * block_q + block_q + block_k - 1) // block_k
                  if causal else num_k)

    def body(ki, dq):
        k = k_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        v = v_ref[0, pl.ds(ki * block_k, block_k), :].astype(jnp.float32)
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        return dq + jnp.dot(ds, k, preferred_element_type=jnp.float32)

    dq = jax.lax.fori_loop(0, num_k_live, body, jnp.zeros((block_q, d), jnp.float32))
    dq_ref[0] = dq.astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, *, scale, causal, block_q, block_k,
                    seq_len):
    ki = pl.program_id(1)
    k = k_ref[0].astype(jnp.float32)                     # (block_k, d)
    v = v_ref[0].astype(jnp.float32)
    d = k.shape[-1]

    num_q = seq_len // block_q
    # causal: only q blocks with q_end >= k_start contribute
    q_start = (ki * block_k) // block_q if causal else 0

    def body(qi, carry):
        dk, dv = carry
        q = q_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        do = do_ref[0, pl.ds(qi * block_q, block_q), :].astype(jnp.float32)
        lse = lse_ref[0, pl.ds(qi * block_q, block_q), 0]
        delta = delta_ref[0, pl.ds(qi * block_q, block_q), 0]
        s = jnp.dot(q * scale, k.T, preferred_element_type=jnp.float32)
        if causal:
            q_pos = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            k_pos = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(q_pos >= k_pos, s, NEG_INF)
        p = jnp.exp(s - lse[:, None])                    # (block_q, block_k)
        dv = dv + jnp.dot(p.T, do, preferred_element_type=jnp.float32)
        dp = jnp.dot(do, v.T, preferred_element_type=jnp.float32)
        ds = p * (dp - delta[:, None]) * scale
        dk = dk + jnp.dot(ds.T, q, preferred_element_type=jnp.float32)
        return dk, dv

    z = jnp.zeros((block_k, d), jnp.float32)
    dk, dv = jax.lax.fori_loop(q_start, num_q, body, (z, z))
    dk_ref[0] = dk.astype(dk_ref.dtype)
    dv_ref[0] = dv.astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call plumbing
# --------------------------------------------------------------------------
def _fwd_impl(q, k, v, scale, causal, block_q, block_k, interpret):
    b, h, t, d = q.shape
    bh = b * h
    q3 = q.reshape(bh, t, d)
    k3 = k.reshape(bh, t, d)
    v3 = v.reshape(bh, t, d)
    grid = (bh, t // block_q)
    kern = functools.partial(_fwd_kernel, scale=scale, causal=causal,
                             block_q=block_q, block_k=block_k, seq_len=t)
    o, lse = pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            # trailing singleton lane dim: Mosaic requires the last two
            # block dims to be (8,128)-divisible or equal to the array
            # dims — a 2D (1, block_q) lse block violates that on real
            # TPUs (interpret mode never checks)
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, 1), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return o.reshape(b, h, t, d), lse.reshape(b, h, t)


def _bwd_impl(q, k, v, o, lse, do, scale, causal, block_q, block_k,
              interpret):
    b, h, t, d = q.shape
    bh = b * h
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                             # (b, h, t)
    q3, k3, v3 = (x.reshape(bh, t, d) for x in (q, k, v))
    do3 = do.reshape(bh, t, d)
    lse3 = lse.reshape(bh, t, 1)
    delta3 = delta.reshape(bh, t, 1)

    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k, seq_len=t)
    dq = pl.pallas_call(
        dq_kern,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_q, 1), lambda i, j: (i, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, lse3, delta3)

    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                 block_q=block_q, block_k=block_k, seq_len=t)
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, 1), lambda i, j: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, lse3, delta3)
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=128,
                    block_k=128, interpret=False):
    """Blockwise exact attention; returns (B, H, T, D).

    The (T, T) score matrix only ever exists one (block_q, block_k) tile
    at a time in VMEM; memory is O(T·D) instead of O(T²)."""
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _resolve_scale(scale, d):
    return scale if scale is not None else 1.0 / np.sqrt(d)


def _check_shape(shape, bq, bk):
    b, h, t, d = shape
    if t % bq or t % bk or d % 8:
        raise ValueError(
            f"flash_attention requires T divisible by block sizes "
            f"({bq}, {bk}) and D % 8 == 0; got T={t}, D={d}. "
            "Use parallel.ring_attention.attention(impl='auto') for "
            "automatic fallback.")


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    s = _resolve_scale(scale, q.shape[-1])
    bq = min(block_q, q.shape[2])
    bk = min(block_k, q.shape[2])
    _check_shape(q.shape, bq, bk)
    o, lse = _fwd_impl(q, k, v, s, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    s = _resolve_scale(scale, q.shape[-1])
    bq = min(block_q, q.shape[2])
    bk = min(block_k, q.shape[2])
    return _bwd_impl(q, k, v, o, lse, do, s, causal, bq, bk, interpret)


flash_attention.defvjp(_flash_fwd, _flash_bwd)


# --------------------------------------------------------------------------
# op registration: nd.FlashAttention / sym.FlashAttention
# --------------------------------------------------------------------------
def _register():
    from ..base import parse_attr, parse_bool
    from .registry import register

    @register("FlashAttention", arg_names=("query", "key", "value"))
    def _flash_attention_op(ctx, query, key, value, **attrs):
        """Exact blockwise attention over (B, H, T, D) inputs.

        No reference counterpart (SURVEY.md §5.7: the reference's
        long-sequence story is bucketing) — this is the TPU-native hot
        op behind the sequence stack.  impl: auto | flash |
        flash_interpret | lax."""
        causal = parse_bool(attrs.get("causal", False))
        scale = attrs.get("scale")
        scale = float(parse_attr(scale)) if scale is not None else None
        impl = str(attrs.get("impl", "auto"))
        from ..parallel.ring_attention import attention

        return attention(query, key, value, causal=causal, scale=scale,
                         impl=impl, platform=ctx.platform, mesh=ctx.mesh)


_register()
