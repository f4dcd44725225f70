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

What the inner loops feed the MXU (PERF.md section 6, PR 36): every
product takes its operands in the tensors' own dtype and accumulates in
float32.  The q, k, v and do tiles go in as they lie in VMEM; the two
tiles a kernel computes itself, p and ds, are rounded to that dtype
first, as every other matmul of a bf16 step rounds its inputs -- but
the forward's p goes in two such pieces (``_matmul(split=True)``), 16
bits of it: the loss reads that rounding, the gradients do not.
float32 tensors therefore still get float32 products: nothing is asked
but the dtype.  The scale multiplies the float32 scores, never q; the running
maximum, the sums, ``lse``, ``delta``, the exponentials and the
accumulators (VMEM scratch) are float32.

Tiles come from ``tiles(T, D, dtype)``, one pair a kernel (forward and
dq hold a tile of queries and walk keys, dkv holds a tile of keys and
walks queries).  Under a causal mask a kernel walks the tiles wholly
below the diagonal with no mask at all, builds the iota mask only for
the few the diagonal crosses, and of those multiplies only the rows
(dkv: the keys) on the near side of the diagonal.  The backward for k
and v works on the transposed scores ``k q^T``, so that no product
needs a transposed tile and the row statistics are read lane-dense;
``lse`` and ``delta`` travel as ``(B*H, T/block, 1, block)``, a row a
block of queries (a ``(T, 1)`` column is one value a 128-lane tile in
HBM: 67 MB an array at ``[128, 1024]``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import mxu_precision

NEG_INF = -1e30  # large-negative instead of -inf: keeps exp()/max() NaN-free
_NT = (((1,), (1,)), ((), ()))  # a @ b.T without building the transpose
_LADDER = (1024, 512, 256, 128)
# of the 16 MiB a Mosaic kernel may use unasked
_VMEM_BUDGET = 14 << 20
# (block_q, block_k) a kernel: the ladder's winners at bf16[128, 1024, 128]
# causal on a v5e, each kernel alone and then the step (PERF.md section 6,
# PR 36).  The forward wants few, large steps (each rescales its whole
# accumulator); dq and dkv want their fixed side whole and the walked
# side narrow, so that the cut at the diagonal leaves little masked work.
_BEST = {"fwd": (1024, 1024), "dq": (1024, 256), "dkv": (128, 1024)}


def _vmem_bytes(kernel, t, d, itemsize, block_q, block_k):
    """What a kernel asks of VMEM, by a model held against what Mosaic
    accepts for a described v5e (tests/test_tpu_compile.py): its inputs
    and outputs twice (the pipeline's two buffers), its float32 scratch,
    and the temporaries of its largest score tile -- ~10 B an element
    with one-pass bf16 products, up to ~36 B with float32 products,
    whose operands Mosaic splits in three."""
    whole, qb, kb = t * d * itemsize, block_q * d * itemsize, \
        block_k * d * itemsize
    column = block_q * 128 * 4   # a (block_q, 1) float32 column: a lane tile a row
    if kernel == "fwd":
        io, scratch = 2 * whole + 2 * qb, block_q * d * 4 + 2 * column
    elif kernel == "dq":
        io, scratch = 2 * whole + 3 * qb, block_q * d * 4 + 2 * column
    else:
        io, scratch = 2 * whole + 4 * kb + 2 * t * 8 * 4, 2 * block_k * d * 4
    return 2 * io + scratch + block_q * block_k * (10 if itemsize == 2 else 40)


def _sides(t, cap):
    """The tile sizes one side may take, largest first: the ladder's up
    to ``cap`` that divide T; T whole where none of the ladder does."""
    fits = [b for b in _LADDER if t % b == 0]
    if fits:
        return [b for b in fits if b <= cap]
    return [t] if t <= _LADDER[0] and t % 8 == 0 else []


def tiles(t, d, dtype):
    """``{"fwd" | "dq" | "dkv": (block_q, block_k)}`` for attention over
    T positions of D-wide heads, or None when no tile divides T or fits
    VMEM (the dispatcher then takes the lax path).  Each kernel starts
    from its measured best and steps its larger side down the ladder
    until the kernel fits."""
    itemsize = jnp.dtype(dtype).itemsize
    out = {}
    for kernel, (cap_q, cap_k) in _BEST.items():
        qs, ks = _sides(t, cap_q), _sides(t, cap_k)
        while qs and ks and _vmem_bytes(kernel, t, d, itemsize,
                                        qs[0], ks[0]) > _VMEM_BUDGET:
            if qs[0] >= ks[0]:
                qs = qs[1:]
            else:
                ks = ks[1:]
        if not (qs and ks):
            return None
        out[kernel] = (qs[0], ks[0])
    return out


def supports(q_shape, dtype=jnp.bfloat16):
    """True when the Pallas path handles this shape without padding."""
    b, h, t, d = q_shape
    return d % 8 == 0 and tiles(t, d, dtype) is not None


# --------------------------------------------------------------------------
# the loop over key (forward, dq) or query (dkv) tiles
# --------------------------------------------------------------------------
def _dot_nt(a, b):
    """``a @ b.T`` in float32, operands as they lie in VMEM: one pass of
    the MXU for bf16, the package's float32 products for float32."""
    return lax.dot_general(a, b, _NT, precision=mxu_precision(a, b),
                           preferred_element_type=jnp.float32)


def _matmul(p, x, split=False):
    """``p @ x`` with the float32 tile ``p`` rounded to ``x``'s dtype.
    ``split``: p goes in two pieces of that dtype, what the rounding
    keeps and what it drops, so that a bf16 product sees 16 bits of p
    for one more pass of the MXU (the forward's p @ v: a rounded p moved
    the first step's loss by ~1e-5 of itself, PERF.md section 6, PR 36)."""
    dot = functools.partial(jnp.dot, precision=mxu_precision(x),
                            preferred_element_type=jnp.float32)
    hi = p.astype(x.dtype)
    if not split or hi.dtype == p.dtype:
        return dot(hi, x)
    return dot(hi, x) + dot((p - hi.astype(p.dtype)).astype(x.dtype), x)


def _mask_above_diagonal(s, q_start, k_start, q_axis):
    """Scores of keys after their query set to NEG_INF; queries run
    along ``q_axis`` of the tile, keys along the other."""
    q_pos = q_start + lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    k_pos = k_start + lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(q_pos >= k_pos, s, NEG_INF)


def _crossing(fixed, walked, counted):
    """How many ``walked``-wide tiles the diagonal crosses inside one
    ``fixed``-wide tile of the other axis: a Python int where one size
    divides the other (they are then walked unrolled), else ``counted``,
    the kernel's own count from ``program_id``."""
    if fixed % walked == 0:
        return fixed // walked
    return 1 if walked % fixed == 0 else counted


def _plain(step, lo, n):
    """``step`` over ``n`` tiles from ``lo`` that need no mask."""
    lax.fori_loop(0, n, lambda i, _: step(lo + i, False, None), None)


def _walk(step, plain, first, n_cross, walked):
    """``step(tile, masked, cut)`` over a causal kernel's live tiles: a
    loop over the ``plain`` range (wholly below the diagonal, no mask)
    and the ``n_cross`` tiles from ``first`` that the diagonal crosses.
    Where their count is static and above one, the c-th of them starts
    ``c * walked`` into the fixed tile and what lies on the far side of
    it is masked whole: ``cut`` hands the step that static offset so
    that it multiplies only the rest (None: not known, or not crossing)."""
    _plain(step, *plain)
    if not isinstance(n_cross, int):
        lax.fori_loop(first, first + n_cross,
                      lambda i, _: step(i, True, None), None)
        return
    for c in range(n_cross):
        step(first + c, True, c * walked if n_cross > 1 else 0)


def _walk_keys(step, causal, q_start, block_q, block_k, seq_len):
    """The forward's and dq's walk: key tiles in order against the query
    tile at ``q_start``.  In order, because a row that one crossing tile
    masks whole has met its first key in an earlier tile, so the
    forward's running maximum is finite by then."""
    if not causal:
        return _plain(step, 0, seq_len // block_k)
    below = (q_start + 1) // block_k     # tiles wholly below the diagonal
    n_cross = _crossing(block_q, block_k,
                        pl.cdiv(q_start + block_q, block_k) - below)
    _walk(step, (0, below), below, n_cross, block_k)


def _tile(ref, i, block):
    return ref[0, pl.ds(pl.multiple_of(i * block, block), block), :]


def _as_row(col):
    """A ``(n, 1)`` column as a ``(1, n)`` row."""
    return col.reshape(1, col.shape[0])


def _as_col(row):
    return row.reshape(row.shape[1], 1)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------
def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, scale, causal, block_q, block_k, seq_len):
    q_start = pl.program_id(1) * block_q
    m_ref[...] = jnp.full(m_ref.shape, NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(ki, masked, cut):
        """Keys of tile ``ki`` against the query rows from ``cut`` on
        (the rows before it see none of them)."""
        cut = cut or 0
        s = _dot_nt(q_ref[0, cut:, :], _tile(k_ref, ki, block_k)) * scale
        if masked:
            s = _mask_above_diagonal(s, q_start + cut, ki * block_k, 0)
        m = m_ref[cut:, :]
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        corr = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        m_ref[cut:, :] = m_new
        l_ref[cut:, :] = l_ref[cut:, :] * corr + jnp.sum(
            p, axis=-1, keepdims=True)
        acc_ref[cut:, :] = acc_ref[cut:, :] * corr + _matmul(
            p, _tile(v_ref, ki, block_k), split=True)

    _walk_keys(step, causal, q_start, block_q, block_k, seq_len)
    l_safe = jnp.maximum(l_ref[...], 1e-20)
    o_ref[0] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
    lse_ref[0, 0] = _as_row(m_ref[...] + jnp.log(l_safe))


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------
def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
                   acc_ref, *, scale, causal, block_q, block_k, seq_len):
    q_start = pl.program_id(1) * block_q
    lse, delta = _as_col(lse_ref[0, 0]), _as_col(delta_ref[0, 0])
    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)

    def step(ki, masked, cut):
        cut = cut or 0
        k = _tile(k_ref, ki, block_k)
        s = _dot_nt(q_ref[0, cut:, :], k) * scale
        if masked:
            s = _mask_above_diagonal(s, q_start + cut, ki * block_k, 0)
        p = jnp.exp(s - lse[cut:])
        dp = _dot_nt(do_ref[0, cut:, :], _tile(v_ref, ki, block_k))
        acc_ref[cut:, :] += _matmul(p * (dp - delta[cut:]), k)

    _walk_keys(step, causal, q_start, block_q, block_k, seq_len)
    dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, scale, causal,
                    block_q, block_k, seq_len):
    """On the transposed tile: scores ``k q^T`` are (block_k, block_q),
    so ``lse`` and ``delta`` broadcast as rows and dv, dk take p^T and
    ds^T as they are."""
    k_start = pl.program_id(1) * block_k
    num_q = seq_len // block_q
    dk_acc[...] = jnp.zeros(dk_acc.shape, jnp.float32)
    dv_acc[...] = jnp.zeros(dv_acc.shape, jnp.float32)

    def step(qi, masked, cut):
        """Queries of tile ``qi`` against the keys up to ``keep`` (the
        keys after a crossing tile's last query are seen by none)."""
        keep = block_k if cut is None else min(cut + block_q, block_k)
        q, do = _tile(q_ref, qi, block_q), _tile(do_ref, qi, block_q)
        st = _dot_nt(k_ref[0, :keep, :], q) * scale
        if masked:
            st = _mask_above_diagonal(st, qi * block_q, k_start, 1)
        pt = jnp.exp(st - lse_ref[0, qi])
        dv_acc[:keep, :] += _matmul(pt, do)
        dpt = _dot_nt(v_ref[0, :keep, :], do)
        dk_acc[:keep, :] += _matmul(pt * (dpt - delta_ref[0, qi]), q)

    if causal:
        first = k_start // block_q     # the first query tile that sees a key
        n_cross = _crossing(block_k, block_q, jnp.minimum(
            pl.cdiv(k_start + block_k - 1, block_q), num_q) - first)
        above = first + n_cross        # query tiles wholly past the diagonal
        _walk(step, (above, num_q - above), first, n_cross, block_q)
    else:
        _plain(step, 0, num_q)
    dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
    dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


# --------------------------------------------------------------------------
# pallas_call plumbing
# --------------------------------------------------------------------------
def _rows(x, block):
    """A (B, H, T) row statistic as (B*H, T/block, 1, block): a lane-dense
    row a block of queries."""
    b, h, t = x.shape
    return x.reshape(b * h, t // block, 1, block)


# jitted for the lowering alone: a step's layers then share one lowered
# kernel where each call was turned into Mosaic's text anew (0.8 s of
# set-up for 8 layers; PERF.md section 6, PR 36)
@functools.partial(jax.jit, static_argnums=(3, 4, 5, 6, 7))
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
            # Mosaic requires the last two block dims to be (8,128)-
            # divisible or equal to the array dims (interpret mode never
            # checks): (1, block_q) is the whole of (1, block_q)
            pl.BlockSpec((1, 1, 1, block_q), lambda i, j: (i, j, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t // block_q, 1, block_q),
                                 jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_q, 1), jnp.float32),   # m
                        pltpu.VMEM((block_q, 1), jnp.float32),   # l
                        pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_fwd",
    )(q3, k3, v3)
    return o.reshape(b, h, t, d), lse.reshape(b, h, t)


@functools.partial(jax.jit, static_argnums=(6, 7, 8, 9, 10))
def _bwd_impl(q, k, v, o, lse, do, scale, causal, dq_blocks, dkv_blocks,
              interpret):
    b, h, t, d = q.shape
    bh = b * h
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32),
                    axis=-1)                             # (b, h, t)
    q3, k3, v3 = (x.reshape(bh, t, d) for x in (q, k, v))
    do3 = do.reshape(bh, t, d)

    block_q, block_k = dq_blocks
    dq_kern = functools.partial(_bwd_dq_kernel, scale=scale, causal=causal,
                                block_q=block_q, block_k=block_k, seq_len=t)
    stat = pl.BlockSpec((1, 1, 1, block_q), lambda i, j: (i, j, 0, 0))
    dq = pl.pallas_call(
        dq_kern,
        grid=(bh, t // block_q),
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
            stat, stat,
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, t, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dq",
    )(q3, k3, v3, do3, _rows(lse, block_q), _rows(delta, block_q))

    block_q, block_k = dkv_blocks
    dkv_kern = functools.partial(_bwd_dkv_kernel, scale=scale, causal=causal,
                                 block_q=block_q, block_k=block_k, seq_len=t)
    stat = pl.BlockSpec((1, t // block_q, 1, block_q),
                        lambda i, j: (i, 0, 0, 0))
    dk, dv = pl.pallas_call(
        dkv_kern,
        grid=(bh, t // block_k),
        in_specs=[
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, t, d), lambda i, j: (i, 0, 0)),
            stat, stat,
        ],
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda i, j: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bh, t, d), q.dtype),
            jax.ShapeDtypeStruct((bh, t, d), v.dtype),
        ],
        scratch_shapes=[pltpu.VMEM((block_k, d), jnp.float32),
                        pltpu.VMEM((block_k, d), jnp.float32)],
        interpret=interpret,
        name="flash_bwd_dkv",
    )(q3, k3, v3, do3, _rows(lse, block_q), _rows(delta, block_q))
    return (dq.reshape(b, h, t, d), dk.reshape(b, h, t, d),
            dv.reshape(b, h, t, d))


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=False):
    """Blockwise exact attention; returns (B, H, T, D).

    The (T, T) score matrix only ever exists one (block_q, block_k) tile
    at a time in VMEM; memory is O(T·D) instead of O(T²).  ``block_q``
    and ``block_k`` default to what ``tiles`` chooses for each kernel; a
    given pair (the tests' small tiles) is used by all three."""
    o, _ = _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret)
    return o


def _resolve_scale(scale, d):
    return scale if scale is not None else 1.0 / np.sqrt(d)


def _blocks(q, block_q, block_k):
    """The three kernels' tiles for this call; raises on a shape the
    kernels cannot tile."""
    b, h, t, d = q.shape
    if block_q is None and block_k is None:
        if not supports(q.shape, q.dtype):
            raise ValueError(
                f"flash_attention has no tiles for T={t}, D={d} "
                f"({q.dtype}): T must divide by one of {_LADDER[::-1]} "
                "or be a multiple of 8 no longer than the largest, "
                "D % 8 == 0, and K and V of one head must fit VMEM.  Use parallel.ring_attention."
                "attention(impl='auto') for automatic fallback.")
        return tiles(t, d, q.dtype)
    bq = min(block_q or block_k, t)
    bk = min(block_k or block_q, t)
    if t % bq or t % bk or d % 8:
        raise ValueError(
            f"flash_attention requires T divisible by block sizes "
            f"({bq}, {bk}) and D % 8 == 0; got T={t}, D={d}. "
            "Use parallel.ring_attention.attention(impl='auto') for "
            "automatic fallback.")
    return {"fwd": (bq, bk), "dq": (bq, bk), "dkv": (bq, bk)}


def _flash_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    s = _resolve_scale(scale, q.shape[-1])
    bq, bk = _blocks(q, block_q, block_k)["fwd"]
    o, lse = _fwd_impl(q, k, v, s, causal, bq, bk, interpret)
    return o, (q, k, v, o, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, res, do):
    q, k, v, o, lse = res
    s = _resolve_scale(scale, q.shape[-1])
    blocks = _blocks(q, block_q, block_k)
    return _bwd_impl(q, k, v, o, lse, do, s, causal, blocks["dq"],
                     blocks["dkv"], interpret)


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
