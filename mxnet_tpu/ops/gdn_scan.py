"""The gated delta rule's chunked scan as one Pallas kernel (a gate a
head: gated DeltaNet, ``models/olmo_hybrid.py``).

The recurrence is ``models/delta_rule.py``'s: ``S_t = (I - beta_t k_t
k_t^T) e^{g_t} S_{t-1} + beta_t k_t v_t^T``, ``o_t = S_t^T q_t``, a
``(d_k, d_v)`` float32 state a head.  Its XLA form, ``gdn_chunked``,
builds every chunk's ``(C, C)`` decay and score matrices in HBM, solves
the chunks' triangular systems in one ``solve_triangular`` and carries
the state through a ``lax.scan`` of small products; on the TPU the
solve alone was the prefill's largest operation (PERF.md, PR 41).  The
kernel does the same algebra a chunk at a time in VMEM:

- grid ``(head group, chunk)``, the chunk axis in order; a head's state
  stays in the output block, which is resident along the chunk axis:
  it is ``S0`` at the first chunk and goes back to HBM once, after the
  last;
- in a chunk, ``G`` the running sum of ``g`` (taken outside, as
  ``gdn_chunked`` takes it), ``D_ti = e^{G_t - G_i}`` for ``t >= i`` and
  0 above the diagonal (every exponent <= 0: no gate overflows),
  ``A = tril(K K^T * D, -1) * beta``, ``B = Q K^T * D``;
- ``(I + A)^{-1}`` from matrix products, no solve: the inverse of the
  unit diagonal's 1 x 1 blocks is 1, and blocks of ``s`` merge into
  blocks of ``2 s`` by ``[[X_1, 0], [-X_2 A_21 X_1, X_2]]``, the block
  form of forward substitution (:func:`unit_lower_inverse`);
- ``W = (I + A)^{-1} [beta V, beta e^G K]``, ``U = W_v - W_k S``, ``O =
  (e^G Q) S + B U``, ``S <- e^{G_C} S + (e^{G_C - G} K)^T U``.

Every product takes float32 operands and accumulates in float32 at
``HIGHEST`` precision, as the XLA form's do: nothing is rounded to
bfloat16.  ``interpret=True`` runs the same kernel on the CPU, the
hook the tests hold it to ``gdn_chunked`` and the recurrence by.

Schedules are plain dicts, as the paged kernels' are: ``{"impl":
"pallas"}`` (``"interpret": True`` to interpret it) puts a scan whose
shapes :func:`supports` takes on the kernel (:func:`on_kernel`); any
other runs the caller's XLA form.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["CHUNK", "supports", "default_schedule", "on_kernel",
           "head_group", "unit_lower_inverse", "gdn_scan"]

HIGHEST = jax.lax.Precision.HIGHEST
CHUNK = 64          # positions a chunk: every exponent <= 0, any is safe
_LANES = 128
# VMEM the pipelined blocks of a head group may take; the group is the
# largest divisor of the heads within it and _MAX_HEADS
_VMEM_BUDGET = 24 << 20
_MAX_HEADS = 10


def _lanes(n):
    return -(-int(n) // _LANES) * _LANES


def _head_vmem(C, d_k, d_v):
    """VMEM one head takes: its blocks twice each (the pipeline's two
    buffers) -- ``q, k, v``, the output, the gates, ``S0`` and ``S`` --
    and the chunk's ``(C, C)`` temporaries (``D``, ``A``, ``B`` and
    the inverse's), each row in whole lanes."""
    io = C * (2 * _lanes(d_k) + 2 * _lanes(d_v)) + 8 * _lanes(C)
    return 4 * (2 * (io + 2 * d_k * _lanes(d_v)) + 6 * C * _lanes(C))


def supports(T, H, d_k, d_v) -> bool:
    """Will Mosaic take the kernel for ``T`` positions of ``H`` heads
    of ``(d_k, d_v)``?  The state's rows lie on sublanes (``d_k`` and
    ``d_v`` whole tiles of 8) and one head's blocks must fit the VMEM
    budget; any ``T`` is padded to whole chunks."""
    return (T > 0 and H > 0 and d_k % 8 == 0 and d_v % 8 == 0
            and _head_vmem(CHUNK, d_k, d_v) <= _VMEM_BUDGET)


def default_schedule(platform: str) -> dict:
    """The kernel on a TPU, the XLA form everywhere else."""
    return {"impl": "pallas"} if platform == "tpu" else {"impl": "xla"}


def on_kernel(schedule, T, H, d_k, d_v) -> bool:
    """Does ``schedule`` put a scan of these shapes on the kernel?
    Where it asks for it and :func:`supports` passes; always when
    interpreted (no Mosaic in that)."""
    sched = schedule or {}
    if sched.get("impl") != "pallas":
        return False
    return bool(sched.get("interpret")) or supports(T, H, d_k, d_v)


def head_group(H, d_k, d_v, chunk=CHUNK) -> int:
    """Heads a grid step: the largest divisor of ``H`` up to
    :data:`_MAX_HEADS` whose blocks fit the VMEM budget."""
    fits = lambda g: g * _head_vmem(chunk, d_k, d_v) <= _VMEM_BUDGET
    return next((g for g in range(min(H, _MAX_HEADS), 1, -1)
                 if H % g == 0 and fits(g)), 1)


def _dot(a, b, dims):
    """A product over the last two axes; a leading axis of heads, where
    there is one, is a batch axis."""
    batch = ((), ())
    if a.ndim == 3:
        dims = tuple(tuple(d + 1 for d in ds) for ds in dims)
        batch = ((0,), (0,))
    return jax.lax.dot_general(a, b, (dims, batch), precision=HIGHEST,
                               preferred_element_type=jnp.float32)


def _nn(a, b):          # a @ b
    return _dot(a, b, ((1,), (0,)))


def _nt(a, b):          # a @ b^T
    return _dot(a, b, ((1,), (1,)))


def _tn(a, b):          # a^T @ b
    return _dot(a, b, ((0,), (0,)))


def unit_lower_inverse(A):
    """``(I + A)^{-1}`` for ``A`` ``(C, C)`` strictly lower triangular,
    ``C`` a power of two, by products alone: ``X`` starts as the
    inverse of the unit diagonal's 1 x 1 blocks, and at each level the
    blocks of ``s`` on the diagonal merge pairwise into blocks of ``2
    s``: ``X <- X - X A_s X``, ``A_s`` the part of ``A`` in the lower
    left ``s x s`` quarter of each ``2 s`` block, which is ``[[X_1, 0],
    [-X_2 A_21 X_1, X_2]]`` a block -- forward substitution in blocks,
    ``2 log2(C) - 2`` products of ``(C, C)``."""
    C = A.shape[-1]
    t = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)

    def quarter(s):     # row in the lower, column in the upper half
        return (t // (2 * s) == i // (2 * s)) & ((t // s) % 2 == 1) \
            & ((i // s) % 2 == 0)

    X = jnp.where(t == i, 1.0, 0.0) - jnp.where(quarter(1), A, 0.0)
    s = 2
    while s < C:
        X = X - _nn(_nn(X, jnp.where(quarter(s), A, 0.0)), X)
        s *= 2
    return X


def _kernel(q_ref, k_ref, v_ref, gb_ref, s0_ref, o_ref, s_ref):
    """One chunk of a head group: every product batched over the
    group's heads (the leading axis)."""
    C = q_ref.shape[1]

    @pl.when(pl.program_id(1) == 0)
    def _():
        s_ref[...] = s0_ref[...]

    t = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 1)
    i = jax.lax.broadcasted_iota(jnp.int32, (1, C, C), 2)
    causal = t >= i
    G_row = gb_ref[0, :, 0:1, :]                            # (h, 1, C)
    # a row to a column, exactly: (h, 1, C) -> (h, C, 1)
    column = lambda row: jnp.sum(jnp.where(t == i, row, 0.0), axis=2,
                                 keepdims=True)
    G, beta = column(G_row), column(gb_ref[0, :, 1:2, :])
    last = jax.lax.broadcasted_iota(jnp.int32, (1, 1, C), 2) == C - 1

    def at_end(rows):   # G at the chunk's last position, (h, rows, 1)
        return jnp.sum(jnp.where(
            last, jnp.broadcast_to(G_row, (G_row.shape[0], rows, C)), 0.0),
            axis=2, keepdims=True)

    q, k, v, S = q_ref[...], k_ref[...], v_ref[...], s_ref[...]
    D = jnp.where(causal, jnp.exp(jnp.where(causal, G - G_row, 0.0)), 0.0)
    A = jnp.where(t > i, _nt(k, k) * D, 0.0) * beta
    B = _nt(q, k) * D
    X = unit_lower_inverse(A)
    eG = jnp.exp(G)
    U = _nn(X, beta * v) - _nn(_nn(X, (beta * eG) * k), S)
    o_ref[...] = _nn(q * eG, S) + _nn(B, U)
    # the state's block stays resident along the chunk axis
    s_ref[...] = jnp.exp(at_end(S.shape[1])) * S \
        + _tn(k * jnp.exp(at_end(C) - G), U)


# a jit of its own: a program's six gated-DeltaNet layers trace and lower
# the kernel once, not six times (each call keeps its caller's scope)
@functools.partial(jax.jit, static_argnames=("interpret", "chunk"))
def gdn_scan(q, k, v, g, beta, S0, *, interpret=False, chunk=CHUNK):
    """``gdn_chunked``'s contract on the kernel: ``q, k`` (T, H, d_k),
    ``v`` (T, H, d_v), ``g, beta`` (T, H), ``S0`` (H, d_k, d_v), all
    float32.  Returns ``(o (T, H, d_v), S_T)``.  The tail is padded to
    whole chunks with ``beta = g = 0``, which leaves the state as it is
    (so does the caller's right padding)."""
    T, H, dk = q.shape
    dv = v.shape[-1]
    C = int(chunk)
    if C < 2 or C & (C - 1):
        raise ValueError(f"the scan's chunk must be a power of two: {C}")
    n = -(-T // C)
    G_ = head_group(H, dk, dv, C)
    pad = n * C - T

    def head_major(a):                      # (T, H, d) -> (H, n C, d)
        return jnp.pad(a.astype(jnp.float32),
                       ((0, pad), (0, 0), (0, 0))).transpose(1, 0, 2)

    def by_chunk(a):                        # (T, H) -> (n, H, C)
        return jnp.pad(a.astype(jnp.float32),
                       ((0, pad), (0, 0))).reshape(n, C, H).transpose(0, 2, 1)

    gb = jnp.stack([jnp.cumsum(by_chunk(g), axis=-1), by_chunk(beta)], 2)
    blk = lambda d: pl.BlockSpec((G_, C, d), lambda h, c: (h, c, 0))
    state = pl.BlockSpec((G_, dk, dv), lambda h, c: (h, 0, 0))
    o, S = pl.pallas_call(
        _kernel,
        out_shape=(jax.ShapeDtypeStruct((H, n * C, dv), jnp.float32),
                   jax.ShapeDtypeStruct((H, dk, dv), jnp.float32)),
        grid=(H // G_, n),
        in_specs=[blk(dk), blk(dk), blk(dv),
                  pl.BlockSpec((1, G_, 2, C), lambda h, c: (c, h, 0, 0)),
                  state],
        out_specs=(blk(dv), state),
        compiler_params=pltpu.CompilerParams(
            # a head group's chunks in order: the state goes from one to
            # the next in its output block
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=int(G_ * _head_vmem(C, dk, dv) + (16 << 20))),
        interpret=interpret,
        name="gdn_scan",
    )(head_major(q), head_major(k), head_major(v), gb,
      S0.astype(jnp.float32))
    return o[:, :T].transpose(1, 0, 2), S
