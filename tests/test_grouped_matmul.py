"""The grouped-matmul kernel of the served expert layer
(``ops/grouped_matmul.py``), interpreted on the CPU, against
``lax.ragged_dot``: which rows go to which expert, groups without rows,
tiles shared by several groups, rows that belong to no group.  What
Mosaic makes of it is ``tests/test_tpu_compile.py``'s part; how fast it
is, the chip's."""
import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.ops import grouped_matmul as gmm

PALLAS = {"impl": "pallas", "interpret": True}

# (rows, k, n, group sizes): the cells' shapes scaled down -- k 2048 and
# 2560 become 256 and 384 (two and three lane tiles), n 768 becomes 128
CASES = {
    "every_group_equal": (256, 256, 128, [16] * 16),
    "empty_groups_between_hit_ones": (256, 256, 128,
                                      [0, 40, 0, 0, 100, 3, 0, 50, 0]),
    "all_rows_in_one_group": (256, 256, 128, [0, 0, 256, 0]),
    "groups_astride_the_row_tile": (384, 256, 128, [130, 1, 127, 126]),
    "rows_no_multiple_of_the_tile": (200, 256, 128, [7, 0, 150, 43]),
    "rows_behind_the_last_group": (256, 256, 128, [5, 0, 30, 0, 29, 0]),
    "no_rows_at_all": (128, 128, 128, [0, 0, 0]),
    "k2560_scaled": (128, 384, 128, [3, 0, 2, 2, 0, 4, 1, 3] * 2),
    "wide_out": (64, 128, 384, [20, 0, 44]),
}


def _operands(rows, k, n, sizes, dtype, seed=0):
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(
        0.1 * rng.standard_normal(s).astype(np.float32), jnp.dtype(dtype))
    live = int(np.sum(sizes))
    # rows of no group hold NaN: one read into a live row would show
    x = mk(rows, k).at[live:].set(jnp.nan)
    E = len(sizes)
    return (x, mk(E, k, n), mk(E, k, n), jnp.asarray(sizes, jnp.int32),
            live)


@pytest.mark.parametrize("pair", [False, True], ids=["one", "gate_up"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_matches_ragged_dot(case, dtype, pair):
    rows, k, n, sizes = CASES[case]
    x, w0, w1, gs, live = _operands(rows, k, n, sizes, dtype)
    rhs = (w0, w1) if pair else w0
    want = gmm.grouped_matmul(x, rhs, gs)
    got = gmm.grouped_matmul(x, rhs, gs, schedule=PALLAS)
    assert got.shape == want.shape == (rows, n) and got.dtype == x.dtype
    got = np.asarray(got[:live], np.float32)
    assert np.isfinite(got).all()
    # float32: the same products summed in another order; bfloat16: the
    # pair is rounded once where ragged_dot rounds both products first
    tol = 1e-5 if dtype == "float32" else 2e-2
    np.testing.assert_allclose(got, np.asarray(want[:live], np.float32),
                               rtol=tol, atol=tol * 0.05)


def test_against_a_dense_loop_over_the_groups():
    """Not only against the compiler's kernel: every group's rows times
    its own matrix, in numpy."""
    rows, k, n, sizes = 256, 128, 128, [9, 0, 120, 1, 70, 0, 0, 56]
    x, w0, w1, gs, live = _operands(rows, k, n, sizes, "float32", seed=4)
    got = np.asarray(gmm.grouped_matmul(x, (w0, w1), gs, schedule=PALLAS))
    lo = 0
    for e, size in enumerate(sizes):
        rows_e = np.asarray(x[lo:lo + size], np.float64)
        g, u = rows_e @ np.asarray(w0[e]), rows_e @ np.asarray(w1[e])
        np.testing.assert_allclose(got[lo:lo + size], g / (1 + np.exp(-g)) * u,
                                   rtol=1e-4, atol=1e-6)
        lo += size
    assert lo == live


@pytest.mark.parametrize("rows,tm,sizes", [
    (256, 128, [16] * 16), (256, 128, [0, 40, 0, 0, 100, 3, 0, 50]),
    (384, 128, [130, 1, 127, 126]), (256, 64, [0, 0, 0, 0]),
    (1024, 128, [0, 3, 250, 0, 3])])
def test_work_items_walk_groups_then_tiles(rows, tm, sizes):
    """Every (group, tile) pair that shares a row, once, the groups in
    order; tiles never go back (a tile's visits are consecutive); past
    the count the last unit repeats."""
    group, tile, offsets, n = (np.asarray(a) for a in gmm.work_items(
        jnp.asarray(sizes, jnp.int32), rows, tm))
    ends = np.cumsum(sizes)
    want = [(g, t) for g, (lo, hi) in enumerate(zip(ends - sizes, ends))
            for t in range(lo // tm, -(-hi // tm)) if hi > lo]
    n = int(n[0])
    assert n == len(want)
    assert len(group) == len(tile) == -(-rows // tm) + len(sizes) - 1
    assert list(zip(group[:n], tile[:n])) == want
    assert (np.diff(tile) >= 0).all() and (np.diff(group) >= 0).all()
    assert (group[n:] == group[max(n - 1, 0)]).all()
    assert (tile[n:] == tile[max(n - 1, 0)]).all()
    assert list(offsets) == [0] + list(ends)


@pytest.mark.parametrize("rows,k,n,dtype,n_rhs,ok", [
    (2048, 2048, 768, "bfloat16", 2, True),     # SDAR step, gate and up
    (1024, 768, 2560, "bfloat16", 1, True),     # Ling step, down
    (16384, 2560, 768, "bfloat16", 2, True),    # Ling prefill, 2048 tokens
    (2048, 2048, 768, "float32", 2, False),     # the reference paths
    (2048, 2048, 768, "float16", 1, False),
    (2048, 2000, 768, "bfloat16", 1, False),    # k off the lanes
    (2048, 2048, 100, "bfloat16", 1, False),    # n off the lanes
    (2048, 8192, 4096, "bfloat16", 2, False),   # 64 MB a matrix: no VMEM
    (0, 2048, 768, "bfloat16", 1, False)])
def test_gate(rows, k, n, dtype, n_rhs, ok):
    assert gmm.supports(rows, k, n, dtype, n_rhs) is ok
    want = "pallas" if ok else "ragged"
    assert gmm.default_schedule("tpu", rows, k, n, dtype, n_rhs) \
        == {"impl": want}
    assert gmm.default_schedule("cpu", rows, k, n, dtype, n_rhs) \
        == {"impl": "ragged"}


def test_bench_fn_runs_both_lowerings():
    """``make_bench_fn`` at a toy size: ``lax.ragged_dot`` and the
    interpreted kernel agree over two chained layers."""
    kw = dict(rows=64, d_model=128, d_ffn=128, experts=4,
              group_sizes=[10, 0, 30, 8], layers=2, dtype=jnp.float32)
    want = np.asarray(gmm.make_bench_fn(None, **kw)())
    got = np.asarray(gmm.make_bench_fn(PALLAS, **kw)())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
