"""The latent-attention kernel (``ops/latent_attention.py``) in interpret
mode on the CPU against ``models/mla.py:mla_absorbed`` over the table
gathered by hand: the same absorbed form, the sums over positions taken
a chunk of pages at a time.

Tolerances.  In float32 the two differ by summation order: outputs of
magnitude ~1 agree to a few 1e-6 (limit 2e-5).  In bfloat16 both round
the weights and the output to 8 bits of mantissa, so they agree to one
or two units in the last place of the output, 2^-7 at magnitude 1-2
(limit 0.05); ``test_a_position_past_the_cursor_weighs_nothing`` shows
that a wrong mask is two orders above either.
"""
import types

import jax.numpy as jnp
import numpy as np
import pytest

from mxnet_tpu.models import mla
from mxnet_tpu.ops import latent_attention as la

BLOCK, PAGES_A_SLOT = 16, 6
MAX_LEN = BLOCK * PAGES_A_SLOT
RANK, ROPE, NOPE, V = 512, 64, 128, 128
INTERPRET = {"impl": "pallas", "interpret": True}
# a free slot (block table and cursor zero: its row lands in the scratch
# page) between busy ones whose cursors stand at 0, on a page's last
# row, on a page's first row and at the end of the cache window
CURSORS = (0, BLOCK - 1, 0, BLOCK, MAX_LEN - 1, 37)
FREE = 2


def config(heads, mscale):
    c = types.SimpleNamespace(heads=heads, kv_rank=RANK, nope=NOPE,
                              rope=ROPE, v_dim=V)
    if mscale is not None:
        c.attn_mscale = mscale
    return c


def case(heads, dtype, seed=0, layers=2):
    """Queries, ``W_kvb``, a pool whose pages are dealt out at random,
    block tables and cursors."""
    rs = np.random.default_rng(seed)
    B, W = len(CURSORS), RANK + ROPE
    P = B * PAGES_A_SLOT + 1
    pool = np.zeros((layers, P, BLOCK, la.page_width(W)), np.float32)
    pool[..., :W] = rs.normal(size=(layers, P, BLOCK, W))
    bt = rs.permutation(np.arange(1, P))[:B * PAGES_A_SLOT].reshape(
        B, PAGES_A_SLOT).astype(np.int32)
    bt[FREE] = 0
    a = lambda x: jnp.asarray(x, dtype)
    return dict(
        q_nope=a(rs.normal(size=(B, heads, NOPE)) * 0.5),
        q_rope=a(rs.normal(size=(B, heads, ROPE)) * 0.5),
        w_kvb=a(rs.normal(size=(heads * (NOPE + V), RANK)) * RANK ** -0.5),
        pool=a(pool), bt=jnp.asarray(bt),
        cursor=jnp.asarray(np.array(CURSORS, np.int32)))


def by_hand(k, layer):
    """Each slot's table, gathered from the pool with numpy, and which
    of its rows the cursor has reached."""
    pool, bt = np.asarray(k["pool"].astype(jnp.float32)), np.asarray(k["bt"])
    table = pool[layer][bt].reshape(bt.shape[0], MAX_LEN, -1)[
        ..., :RANK + ROPE]
    valid = np.arange(MAX_LEN)[None, :] <= np.asarray(k["cursor"])[:, None]
    return jnp.asarray(table, k["pool"].dtype), jnp.asarray(valid)


def paged(k, c, layer, schedule):
    return mla._absorbed(
        k["q_nope"], k["q_rope"], k["w_kvb"], c,
        lambda q, rank, denominator: la.latent_attention(
            q, k["pool"], k["bt"], k["cursor"], layer, rank=rank,
            denominator=denominator, schedule=schedule))


@pytest.mark.parametrize("chunk", [None, 1, 4],
                         ids=["whole_table", "a_page", "four_pages"])
@pytest.mark.parametrize("mscale", [None, 1.3466],
                         ids=["plain", "yarn_mscale"])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.05)])
@pytest.mark.parametrize("heads", [64, 32], ids=["kimi_h64", "ling_h32"])
def test_kernel_is_mla_absorbed_over_the_gathered_table(heads, dtype, tol,
                                                        mscale, chunk):
    c, k = config(heads, mscale), case(heads, dtype)
    want = mla.mla_absorbed(k["q_nope"], k["q_rope"], *by_hand(k, 1),
                            k["w_kvb"], c)
    got = paged(k, c, 1, dict(INTERPRET, chunk=chunk))
    assert got.shape == want.shape == (len(CURSORS), heads * V)
    assert got.dtype == want.dtype
    gap = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert np.all(np.isfinite(np.asarray(got, np.float32)))
    assert gap.max() < tol, gap.max(axis=1)
    # and the other lowering, the lookup by (layer, page), is the same
    # function of the same pool
    other = paged(k, c, 1, {"impl": "gather"})
    assert np.abs(np.asarray(other, np.float32)
                  - np.asarray(want, np.float32)).max() < tol


@pytest.mark.parametrize("schedule", [INTERPRET, {"impl": "gather"}],
                         ids=["kernel", "gather"])
def test_a_position_past_the_cursor_weighs_nothing(schedule):
    """What stands behind a cursor -- the rest of its page, the slot's
    later pages -- may hold anything finite: the result does not move.
    The control moves a row the cursor HAS reached."""
    c, k = config(32, None), case(32, "float32", seed=3)
    before = np.asarray(paged(k, c, 0, schedule))
    pool, bt = np.array(k["pool"]), np.asarray(k["bt"])
    slot, cur = 5, CURSORS[5]                       # cursor 37: page 2, row 5
    pool[0, bt[slot, cur // BLOCK], cur % BLOCK + 1:] = 1e3
    pool[0, bt[slot, cur // BLOCK + 1:]] = -1e3
    k["pool"] = jnp.asarray(pool)
    after = np.asarray(paged(k, c, 0, schedule))
    assert np.array_equal(before, after)
    pool[0, bt[slot, 0], 3, :RANK] += 1.0
    k["pool"] = jnp.asarray(pool)
    moved = np.abs(np.asarray(paged(k, c, 0, schedule)) - before)
    assert moved[slot].max() > 1e-3 and moved[:slot].max() == 0.0


def test_the_gate_and_the_chunk_follow_the_page():
    """Pages of whole 8-row tiles in whole lanes, float32 or bfloat16,
    are what Mosaic takes (``tests/test_tpu_compile.py`` asks it); the
    pages in flight fill the VMEM budget twice over and never pass the
    table."""
    assert la.page_width(576) == 640 and la.page_width(40) == 128
    assert la.supports(16, 640, "bfloat16") and la.supports(8, 640, "float32")
    assert la.supports(8, 640, "bfloat16")
    assert not la.supports(4, 640, "bfloat16")      # half a tile of rows
    assert not la.supports(16, 576, "bfloat16")     # 4.5 lane tiles
    assert not la.supports(16, 640, "int8")
    assert la.default_schedule("tpu", 16, 640, "bfloat16") == \
        {"impl": "pallas"}
    assert la.default_schedule("cpu", 16, 640, "bfloat16") == \
        {"impl": "gather"}
    assert la.default_schedule("tpu", 16, 576, "bfloat16") == \
        {"impl": "gather"}
    assert la.chunk_pages(16, 640, "bfloat16", 1088) == 64   # Kimi
    assert la.chunk_pages(16, 640, "bfloat16", 144) == 64    # Ling
    assert la.chunk_pages(16, 640, "float32", 1088) == 51
    assert la.chunk_pages(16, 640, "bfloat16", 6) == 6
