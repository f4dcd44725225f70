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
import dataclasses
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


# ------------------------------------------------------------- the prefill
# ``latent_prefill`` against ``mla_expanded``'s XLA form over the slot's
# rows looked up (``PrefillHistory`` under gather): a tail of ``T`` rows
# (``length`` of them real) whose pages are in the pool already, behind
# ``hist`` positions, 4 heads at the step's widths.  Rows of pad queries
# are not compared: no caller reads them.
PF_HEADS, PF_PAGES = 4, 20
GATHER = {"impl": "gather"}
SMALL = {"query": 64, "sub": 32, "keys": 32, "heads": 2}


def prefill_case(dtype, hist, T, length, mscale=None, seed=0, layer=1):
    """Queries, ``W_kvb``, a pool of two layers whose pages are dealt out
    at random, and the tail's rows as the pool holds them."""
    rs = np.random.default_rng(seed)
    W = RANK + ROPE
    P = PF_PAGES + 5
    pool = np.zeros((2, P, BLOCK, la.page_width(W)), np.float32)
    pool[..., :W] = rs.normal(size=(2, P, BLOCK, W))
    bt_row = rs.permutation(np.arange(1, P))[:PF_PAGES].astype(np.int32)
    seq = pool[layer][bt_row].reshape(PF_PAGES * BLOCK, -1)[:, :W]
    a = lambda x: jnp.asarray(x, dtype)
    c = config(PF_HEADS, mscale)
    history = la.PrefillHistory(a(pool), jnp.asarray(bt_row), layer, W,
                                jnp.int32(hist), jnp.int32(length), GATHER)
    return c, history, dict(
        q_nope=a(rs.normal(size=(T, PF_HEADS, NOPE)) * 0.5),
        q_rope=a(rs.normal(size=(T, PF_HEADS, ROPE)) * 0.5),
        rows=a(seq[hist:hist + T]),
        w_kvb=a(rs.normal(size=(PF_HEADS * (NOPE + V), RANK)) * RANK ** -0.5))


def xla_form(c, history, k):
    return mla.mla_expanded(k["q_nope"], k["q_rope"], k["rows"], k["w_kvb"],
                            c, history=history, block=16, key_block=32)


def kernel_form(c, history, k, tiles):
    history = dataclasses.replace(history, schedule=INTERPRET)
    if tiles is None:       # the decoder's own call: the schedule decides
        return mla.mla_expanded(k["q_nope"], k["q_rope"], k["rows"],
                                k["w_kvb"], c, history=history)
    return la.latent_prefill(k["q_nope"], k["q_rope"], k["w_kvb"], history,
                             nope=NOPE, v_dim=V,
                             denominator=mla._denominator(c), tiles=tiles)


@pytest.mark.parametrize("hist,T,length,tiles,mscale", [
    pytest.param(0, 64, 64, SMALL, None, id="hist_0"),
    pytest.param(48, 64, 64, SMALL, None, id="hist_a_few_pages"),
    pytest.param(192, 64, 64, SMALL, None, id="hist_past_several_key_blocks"),
    pytest.param(80, 64, 37, SMALL, None, id="pad_rows"),
    pytest.param(32, 40, 40, dict(SMALL, query=16, sub=16), None,
                 id="tail_not_a_multiple_of_the_query_tile"),
    pytest.param(112, 64, 50, None, 1.3466, id="yarn_denominator")])
@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 0.05)])
def test_prefill_kernel_is_the_xla_form(dtype, tol, hist, T, length, tiles,
                                        mscale):
    c, history, k = prefill_case(dtype, hist, T, length, mscale)
    want = xla_form(c, history, k)
    got = kernel_form(c, history, k, tiles)
    assert got.shape == want.shape == (T, PF_HEADS * V)
    assert got.dtype == want.dtype
    got, want = (np.asarray(x, np.float32)[:length] for x in (got, want))
    assert np.all(np.isfinite(got))
    gap = np.abs(got - want)
    assert gap.max() < tol, gap.max(axis=1)


def test_the_prefill_kernel_reads_nothing_past_the_tail():
    """What stands at or past ``hist + length`` -- the pad rows of the
    tail's last page, the slot's later pages -- may hold anything
    finite: the real queries' rows do not move.  The control moves a
    history row every query sees."""
    hist, T, length = 48, 64, 29
    c, history, k = prefill_case("float32", hist, T, length, seed=5)
    before = np.asarray(kernel_form(c, history, k, SMALL))[:length]
    pool, bt = np.array(history.pool), np.asarray(history.bt_row)
    end = hist + length
    pool[1, bt[end // BLOCK], end % BLOCK:] = 1e3
    pool[1, bt[end // BLOCK + 1:]] = -1e3
    after = np.asarray(kernel_form(c, dataclasses.replace(
        history, pool=jnp.asarray(pool)), k, SMALL))[:length]
    assert np.array_equal(before, after)
    pool[1, bt[0], 3, :RANK] += 1.0
    moved = np.abs(np.asarray(kernel_form(c, dataclasses.replace(
        history, pool=jnp.asarray(pool)), k, SMALL))[:length] - before)
    assert moved.max(axis=1).min() > 0 and moved.max() > 1e-3


def test_the_prefill_tiles_follow_the_shape():
    """A chunk of 2,048 queries is one tile (each key block expanded
    once a head) in sub-tiles of 512, keys 512 at a time, and as many
    heads a program as the VMEM budget takes; a short tail takes more
    heads; a table shorter than a key block is one block."""
    kimi = dict(block=16, width=640, rank=512, nope=128, v_dim=128,
                dtype="bfloat16", M=1088)
    assert la.prefill_tiles(2048, 64, **kimi) == \
        {"query": 2048, "sub": 512, "keys": 512, "heads": 4}
    assert la.prefill_tiles(128, 64, **kimi) == \
        {"query": 128, "sub": 128, "keys": 512, "heads": 16}
    assert la.prefill_tiles(40, 4, **dict(kimi, M=2))["keys"] == 32
    assert la.prefill_tiles(40, 4, **kimi)["query"] == 48
    assert la.prefill_supports(16, 640, 512, 128, 128, "bfloat16")
    assert not la.prefill_supports(16, 128, 32, 16, 16, "float32")
