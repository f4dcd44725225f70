"""Grouped matmul for a served expert layer: stream each hit expert once.

``parallel/moe.py:moe_serve`` sorts its token-expert pairs by expert and
multiplies each expert's rows by that expert's matrices.  At serving
sizes every such call is weight-bound: an expert sees 2 to 128 rows, a
matrix is 3-4 MB, and the v5e needs 240 rows a matrix before the MXU
and not the memory sets the pace.  So the least a call can cost is one
read of each expert that has rows.  Two lowerings behind one
schedule-driven entry (the pattern of ``ops/paged_attention.py``):

- **pallas** -- the TPU kernel.  The rows are cut into tiles of
  :func:`row_tile` rows; a unit of work is one (expert, row tile) pair
  that share a row, in order of expert (:func:`work_items`: the list
  rides as scalar prefetch).  One grid step a unit: the expert's whole
  ``(k, n)`` matrix is one block, so it comes in as ONE contiguous DMA,
  and the pipeline starts the next unit's copy before this unit
  multiplies.  Consecutive units of one expert name the same block, so a
  matrix is read once however many tiles its rows span; an expert with
  no rows is in no unit and costs no copy; a tile behind the last group
  is in no unit and is neither read nor written.  A tile that holds
  rows of several experts is visited once for each, back to back, and
  each visit stores only its expert's rows, so any group may have any
  row count (dropless).  Given a pair of stacks ``(gate, up)`` the
  kernel computes ``silu(x gate) * (x up)`` from both float32
  accumulators and rounds once: the two copies fly together and the
  products never leave the chip's fast memory.  bf16 operands, one MXU
  pass, float32 accumulation.  A schedule with ``"interpret": True``
  runs the same kernel on the CPU: the parity-test hook.
- **ragged** -- ``lax.ragged_dot``, the TPU compiler's own grouped
  matmul: what runs for float32 operands, off the TPU, and for shapes
  :func:`supports` refuses.  On the v5e it costs 11.6 us a group that
  has rows, whatever their number: a third of what the memory allows
  (PERF.md, PR 32).

Rows that belong to no group (behind ``sum(group_sizes)``) hold
whatever was there in either lowering.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..base import mxu_precision

__all__ = ["supports", "default_schedule", "row_tile", "work_items",
           "grouped_matmul", "make_bench_fn"]

# rows a unit of work multiplies: the MXU's own height.  Below it the
# unit costs the same pass of the matrix through the MXU, above it more
_ROW_TILE = 128
# what the kernel may hold in VMEM: the v5e has 128 MiB, a kernel gets
# 16 MiB unless it asks (``vmem_limit_bytes``)
_VMEM_BUDGET = 48 << 20


def row_tile(rows: int) -> int:
    """Rows of one tile: the MXU's height, less for fewer rows (whole
    16-row tiles of packed bf16)."""
    return int(min(_ROW_TILE, -(-rows // 16) * 16))


def _vmem_bytes(rows, k, n, n_rhs):
    """Two buffers of each block the pipeline holds, plus the float32
    products of one unit."""
    tm = row_tile(rows)
    blocks = n_rhs * k * n * 2 + tm * k * 2 + tm * n * 2
    return 2 * blocks + (n_rhs + 1) * tm * n * 4


def supports(rows: int, k: int, n: int, dtype, n_rhs: int = 1) -> bool:
    """Will Mosaic take the kernel for ``(rows, k) x (E, k, n)``?  bf16
    alone (float32 operands are the reference paths': they keep the
    compiler's kernel); ``k`` and ``n`` whole 128-wide lanes; and
    ``n_rhs`` whole matrices, twice over, inside the VMEM budget."""
    if jnp.dtype(dtype) != jnp.dtype(jnp.bfloat16):
        return False
    if rows <= 0 or k <= 0 or n <= 0 or k % 128 or n % 128:
        return False
    return _vmem_bytes(rows, k, n, n_rhs) <= _VMEM_BUDGET


def default_schedule(platform: str, rows: int, k: int, n: int, dtype,
                     n_rhs: int = 1) -> dict:
    """The kernel on a TPU whose shapes qualify, ``lax.ragged_dot``
    everywhere else."""
    if platform == "tpu" and supports(rows, k, n, dtype, n_rhs):
        return {"impl": "pallas"}
    return {"impl": "ragged"}


def work_items(group_sizes, rows: int, tm: int):
    """The kernel's walk over ``group_sizes`` (E,) int32 on ``rows``
    rows in tiles of ``tm``: ``(group (W,), tile (W,), offsets (E + 1,),
    n (1,))``, all int32.  Unit ``j < n`` is ``(group[j], tile[j])``:
    the groups in order, each with the tiles its rows touch; a group
    without rows has none.  ``W = tiles + E - 1`` is the most there can
    be; the units past ``n`` repeat the last one, so that the pipeline
    moves nothing for them.  No loop: a few vector operations."""
    E = group_sizes.shape[0]
    tiles = -(-rows // tm)
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    first = (ends - sizes) // tm
    n_tiles = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    upto = jnp.cumsum(n_tiles)                # units of groups [0, g]
    n = upto[-1]
    j = jnp.minimum(jnp.arange(tiles + E - 1), jnp.maximum(n - 1, 0))
    group = jnp.minimum(jnp.sum(upto[None, :] <= j[:, None], axis=1), E - 1)
    tile = first[group] + j - (upto[group] - n_tiles[group])
    # inside the buffer even if the sizes break their contract (a block
    # index past it would be a copy from nowhere)
    tile = jnp.clip(tile, 0, tiles - 1)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    as_i32 = lambda a: a.astype(jnp.int32)
    return as_i32(group), as_i32(tile), as_i32(offsets), as_i32(n)[None]


# a jit of its own: the layers of a served program make the same call,
# and an inner jit is lowered (the kernel turned into Mosaic's text) once
# a shape and program, not once a layer: ~20 ms a call of set-up
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pallas_grouped(lhs, rhs, group_sizes, interpret):
    M, K = lhs.shape
    E, _, N = rhs[0].shape
    tm = row_tile(M)
    work = work_items(group_sizes, M, tm)
    prec = mxu_precision(lhs, *rhs)

    def kernel(group_ref, tile_ref, off_ref, n_ref, x_ref, *refs):
        w_refs, o_ref = refs[:-1], refs[-1]
        i = pl.program_id(0)

        @pl.when(i < n_ref[0])
        def _():
            x = x_ref[...]
            acc = [jnp.dot(x, w[...], precision=prec,
                           preferred_element_type=jnp.float32)
                   for w in w_refs]
            y = acc[0] if len(acc) == 1 else jax.nn.silu(acc[0]) * acc[1]
            g = group_ref[i]
            row = tile_ref[i] * tm + jax.lax.broadcasted_iota(
                jnp.int32, (tm, N), 0)
            mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
            # the rows of the tile's other groups stay as their own
            # visits left them (this tile's block stays in VMEM between
            # consecutive visits); a select, so nothing a row never
            # written holds can reach a live one
            o_ref[...] = jnp.where(
                mine, y, o_ref[...].astype(jnp.float32)).astype(o_ref.dtype)

    by_tile = lambda i, group, tile, off, n: (tile[i], 0)
    by_group = lambda i, group, tile, off, n: (group[i], 0, 0)
    gs = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(work[0].shape[0],),
        in_specs=[pl.BlockSpec((tm, K), by_tile)]
        + [pl.BlockSpec((None, K, N), by_group) for _ in rhs],
        out_specs=pl.BlockSpec((tm, N), by_tile))
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((M, N), lhs.dtype),
        grid_spec=gs,
        compiler_params=pltpu.CompilerParams(
            # in order: a tile's visits are consecutive
            dimension_semantics=("arbitrary",),
            # the blocks :func:`supports` counted and room for what
            # Mosaic keeps of its own
            vmem_limit_bytes=_VMEM_BUDGET + (8 << 20)),
        interpret=interpret,
        name="grouped_matmul",
    )(*work, lhs, *rhs)


def _ragged_grouped(lhs, rhs, group_sizes):
    # one MXU pass for low-precision operands whatever the package's
    # default says (the TPU's grouped matmul refuses bf16 operands at
    # "float32" precision); float32 operands keep the default
    grouped = functools.partial(jax.lax.ragged_dot, group_sizes=group_sizes,
                                precision=mxu_precision(lhs, *rhs))
    if len(rhs) == 1:
        return grouped(lhs, rhs[0])
    return jax.nn.silu(grouped(lhs, rhs[0])) * grouped(lhs, rhs[1])


def grouped_matmul(lhs, rhs, group_sizes, *, schedule=None):
    """``lhs`` (M, k) sorted by group times each group's own matrix.

    ``rhs``: one stack ``(E, k, n)`` -> ``lhs[rows of g] @ rhs[g]``, or
    a pair of stacks ``(gate, up)`` -> ``silu(lhs @ gate[g]) * (lhs @
    up[g])``; ``group_sizes`` (E,) int32 with ``sum <= M``, any of them
    0.  Returns ``(M, n)`` in ``lhs``'s dtype; rows behind the last
    group hold whatever was there.  ``schedule`` picks the lowering
    (``None`` = ``lax.ragged_dot``); ``{"impl": "pallas", "interpret":
    True}`` runs the kernel interpreted -- only when asked for (the CPU
    parity tool), never inferred from the backend: a forced kernel that
    cannot lower says so."""
    rhs = tuple(rhs) if isinstance(rhs, (tuple, list)) else (rhs,)
    sched = schedule or {"impl": "ragged"}
    if sched.get("impl") == "pallas":
        return _pallas_grouped(lhs, rhs, group_sizes,
                               bool(sched.get("interpret", False)))
    return _ragged_grouped(lhs, rhs, group_sizes)


# ------------------------------------------------------------- benchmark
def make_bench_fn(schedule, *, rows, d_model, d_ffn, experts, group_sizes,
                  layers=4, dtype=jnp.bfloat16):
    """A thunk timing ``layers`` expert layers' products (gate and up,
    then down: ``rows x d_model -> d_ffn -> d_model``) under
    ``schedule`` with ``group_sizes`` rows an expert, each layer fed
    the one before as in a served forward.  One stack of weights for
    every layer (the chip has no cache that would notice)."""
    keys = jax.random.split(jax.random.PRNGKey(0), 4)
    mk = lambda key, *s: (0.02 * jax.random.normal(key, s)).astype(dtype)
    x = mk(keys[0], rows, d_model) * 50.0
    w_gate = mk(keys[1], experts, d_model, d_ffn)
    w_up = mk(keys[2], experts, d_model, d_ffn)
    w_down = mk(keys[3], experts, d_ffn, d_model)
    sizes = jnp.asarray(group_sizes, jnp.int32)
    live = (jnp.arange(rows) < int(np.sum(group_sizes)))[:, None]

    # the arrays are jit ARGUMENTS, not closure captures (see
    # ops/paged_attention.make_bench_fn)
    def run(x, w_gate, w_up, w_down, sizes):
        for _ in range(layers):
            h = grouped_matmul(x, (w_gate, w_up), sizes, schedule=schedule)
            y = grouped_matmul(h, w_down, sizes, schedule=schedule)
            x = x + jnp.where(live, y, 0)
        return x

    jitted = jax.jit(run)
    return lambda: jitted(x, w_gate, w_up, w_down, sizes)
