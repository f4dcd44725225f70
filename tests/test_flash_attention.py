"""Flash-attention Pallas kernel tests (interpret mode on the CPU mesh;
oracle = the dense lax attention used by the SP tests)."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops.flash_attention import flash_attention, supports, tiles
from mxnet_tpu.parallel.ring_attention import attention, full_attention


def _qkv(b=2, h=2, t=128, d=16, seed=0, dtype=jnp.float32):
    rs = np.random.RandomState(seed)
    mk = lambda: jnp.asarray(
        rs.normal(size=(b, h, t, d)).astype(np.float32)).astype(dtype)
    return mk(), mk(), mk()


def _grads(fn, q, k, v):
    """Gradients of a loss that weighs every output element otherwise."""
    w = v.astype(jnp.float32) + 1.0

    def loss(q, k, v):
        return (fn(q, k, v).astype(jnp.float32) * w).sum()

    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


# (T, block_q, block_k): the causal walk splits into tiles wholly below
# the diagonal and tiles it crosses; which is which, and how much of a
# crossing tile is cut off as masked whole, depends on how the two sizes
# divide each other
SPLIT_LOOP = [
    pytest.param(128, 64, 64, id="equal"),
    pytest.param(128, 64, 32, id="q-over-k"),       # forward, dq: two crossings
    pytest.param(128, 32, 64, id="k-over-q"),       # dkv: two crossings
    pytest.param(128, 128, 128, id="one-tile"),     # the whole of T
    pytest.param(128, 128, 32, id="all-q-four-k"),  # no plain tile, four cuts
    pytest.param(128, 32, 128, id="four-q-all-k"),
    pytest.param(192, 64, 96, id="neither-divides"),  # counted at run time
]


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_dense(causal):
    q, k, v = _qkv()
    ref = full_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal, None, 64, 64, True)
    assert jnp.abs(ref - out).max() < 1e-5


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_dense(causal):
    q, k, v = _qkv()
    g1 = _grads(lambda q, k, v: flash_attention(q, k, v, causal, None,
                                                64, 64, True), q, k, v)
    g2 = _grads(lambda q, k, v: full_attention(q, k, v, causal=causal),
                q, k, v)
    for a, b in zip(g1, g2):
        assert jnp.abs(a - b).max() < 2e-5


@pytest.mark.parametrize("t,block_q,block_k", SPLIT_LOOP)
@pytest.mark.parametrize("causal", [False, True])
def test_float32_split_loop_is_exact(causal, t, block_q, block_k):
    """float32 in, float32 products: whatever the tiles, the split walk
    reads what the dense reference reads, forward and backward."""
    q, k, v = _qkv(t=t)
    fl = lambda q, k, v: flash_attention(q, k, v, causal, None, block_q,
                                         block_k, True)
    de = lambda q, k, v: full_attention(q, k, v, causal=causal)
    assert jnp.abs(fl(q, k, v) - de(q, k, v)).max() < 1e-5
    for a, b in zip(_grads(fl, q, k, v), _grads(de, q, k, v)):
        assert jnp.abs(a - b).max() < 2e-5


@pytest.mark.parametrize("t,block_q,block_k", SPLIT_LOOP)
@pytest.mark.parametrize("causal", [False, True])
def test_bfloat16_forward_matches_float32_dense(causal, t, block_q, block_k):
    """bf16 tiles go to the MXU as they are; p goes to p @ v in two
    bf16 pieces, 16 bits of it.  bf16 keeps 8 bits: a rounding is at
    most 2^-9 of the value.  o carries its own rounding (2^-9 |o| <=
    2^-9 max|v|) and next to nothing of p's: 2^-9 max|v| is the bound,
    half of what a p rounded to bf16 would need (read: 0.45-0.7 of it;
    under the causal mask a row's first outputs are single v's)."""
    q, k, v = _qkv(t=t, dtype=jnp.bfloat16)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    out = flash_attention(q, k, v, causal, None, block_q, block_k, True)
    assert out.dtype == jnp.bfloat16
    ref = full_attention(q32, k32, v32, causal=causal)
    tol = 2.0 ** -9 * float(jnp.abs(v32).max())
    assert jnp.abs(out.astype(jnp.float32) - ref).max() < tol


@pytest.mark.parametrize("t,block_q,block_k", SPLIT_LOOP)
@pytest.mark.parametrize("causal", [False, True])
def test_bfloat16_backward_matches_float32_dense(causal, t, block_q, block_k):
    """A gradient is a sum over T terms, each of which carries up to
    three rounded factors (p, ds, and the bf16 o inside delta) and is
    itself rounded on the way out: four roundings of at most 2^-9.
    Their signs are random, so the sum's error grows like the sum
    itself, with sqrt(T), and stays a few 2^-9 of the largest gradient
    whatever T is: 4 x 2^-8 of it holds them with room (read: 1.3-1.5 x
    2^-8).  A tile skipped or masked wrongly moves a gradient by its
    share of the sum, a quarter or more here."""
    q, k, v = _qkv(t=t, dtype=jnp.bfloat16)
    q32, k32, v32 = (x.astype(jnp.float32) for x in (q, k, v))
    g1 = _grads(lambda q, k, v: flash_attention(
        q, k, v, causal, None, block_q, block_k, True), q, k, v)
    g2 = _grads(lambda q, k, v: full_attention(q, k, v, causal=causal),
                q32, k32, v32)
    for a, b in zip(g1, g2):
        assert a.dtype == jnp.bfloat16
        tol = 4 * 2.0 ** -8 * float(jnp.abs(b).max())
        assert jnp.abs(a.astype(jnp.float32) - b).max() < tol


@pytest.mark.parametrize("causal", [False, True])
def test_float32_with_the_chosen_tiles(causal):
    """No tile given: ``tiles`` chooses (here 256 x 256, one tile), and
    float32 inputs still read within 1e-5 / 2e-5 of the reference."""
    q, k, v = _qkv(t=256)
    fl = lambda q, k, v: flash_attention(q, k, v, causal, interpret=True)
    de = lambda q, k, v: full_attention(q, k, v, causal=causal)
    assert jnp.abs(fl(q, k, v) - de(q, k, v)).max() < 1e-5
    for a, b in zip(_grads(fl, q, k, v), _grads(de, q, k, v)):
        assert jnp.abs(a - b).max() < 2e-5


def test_flash_uneven_blocks():
    # block_q != block_k and T not a multiple of 128
    q, k, v = _qkv(t=192)
    ref = full_attention(q, k, v, causal=True)
    out = flash_attention(q, k, v, True, None, 64, 32, True)
    assert jnp.abs(ref - out).max() < 1e-5


@pytest.mark.parametrize("d", [128, 64, 16])
@pytest.mark.parametrize("t", [1024, 2048, 192, 64])
def test_tiles_divide_and_fit(t, d):
    chosen = tiles(t, d, jnp.bfloat16)
    assert set(chosen) == {"fwd", "dq", "dkv"}
    for kernel, (bq, bk) in chosen.items():
        assert t % bq == 0 and t % bk == 0
        # whole (8, 128) tiles of a block, or the whole of T
        assert all(b % 128 == 0 or b == t for b in (bq, bk))
        assert fa._vmem_bytes(kernel, t, d, 2, bq, bk) <= fa._VMEM_BUDGET
    assert supports((8, 16, t, d), jnp.bfloat16)


def test_tiles_at_the_benchmark_shape():
    """lm_train's attention, bf16[8, 16, 1024, 128] causal: the ladder's
    winners on the chip (PERF.md section 6, PR 36)."""
    assert tiles(1024, 128, jnp.bfloat16) == fa._BEST
    # the ladder steps down where K, V or the score tile outgrow VMEM
    long = tiles(16384, 128, jnp.float32)
    assert long is None or all(
        fa._vmem_bytes(kn, 16384, 128, 4, *b) <= fa._VMEM_BUDGET
        for kn, b in long.items())


def test_supports_predicate():
    assert supports((1, 2, 256, 64))
    assert not supports((1, 2, 250, 64))   # ragged T
    assert not supports((1, 2, 256, 63))   # ragged D
    assert supports((1, 2, 192, 64))       # no ladder tile: T whole
    with pytest.raises(ValueError, match="divisible by block sizes"):
        flash_attention(*_qkv(t=192), block_q=128, interpret=True)
    with pytest.raises(ValueError, match="no tiles"):
        flash_attention(*_qkv(t=1004), interpret=True)   # 1004 % 8


def test_attention_dispatcher_and_op():
    q, k, v = _qkv(t=64, d=8)
    ref = full_attention(q, k, v, causal=True)
    out = attention(q, k, v, causal=True, impl="flash_interpret")
    assert jnp.abs(ref - out).max() < 1e-5

    nd_out = mx.nd.FlashAttention(
        mx.nd.array(np.asarray(q)), mx.nd.array(np.asarray(k)),
        mx.nd.array(np.asarray(v)), causal=True, impl="lax")
    assert np.abs(nd_out.asnumpy() - np.asarray(ref)).max() < 1e-5

    # symbolic path: bind + forward + backward
    qs, ks, vs = (mx.sym.Variable(n) for n in "qkv")
    net = mx.sym.FlashAttention(qs, ks, vs, causal=True, impl="lax")
    ex = net.simple_bind(ctx=mx.cpu(), q=q.shape, k=k.shape, v=v.shape)
    ex.arg_dict["q"][:] = np.asarray(q)
    ex.arg_dict["k"][:] = np.asarray(k)
    ex.arg_dict["v"][:] = np.asarray(v)
    ex.forward(is_train=True)
    assert np.abs(ex.outputs[0].asnumpy() - np.asarray(ref)).max() < 1e-5
    ex.backward()
    assert ex.grad_dict["q"].asnumpy().shape == q.shape
