"""Compile the main path's Pallas kernels for a described TPU v5e.

The chip's compiler is installed here and compiles for a chip that is
described, not attached (guide `on-chip-measurement` section 2): what
Mosaic refuses on the chip it refuses here, at no chip time — a slice
off the 128-lane tiling, a bf16 accumulator, too much VMEM.  Interpret
mode checks none of that.  Nothing runs, so these tests say nothing
about results or speed; ``chip_smoke.py`` does.

The topology is described inside a module-scoped fixture, never at
import: under xdist every worker imports this file, and only one
process at a time may load the TPU's library.  The kernel functions are
called themselves with ``interpret=False`` — under a described topology
``jax.default_backend()`` is still ``cpu``, so the dispatchers
(``paged_attention``, ``attention``, ``residual_epilogue``) would take
their CPU branch.  All such tests live in this one file.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.ops import residual_epilogue as repi


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure to describe: skip
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    """Shapes placed on the first described chip.  A compile for a
    described chip is written to the persistent cache but cannot be
    read back without one, so the cache is off for this module."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    sharding = SingleDeviceSharding(topo.devices[0])

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                    sharding=sharding)

    yield sds
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _compile(fn, *shapes):
    """Raises what the chip's compiler would raise; returns the text."""
    text = jax.jit(fn).lower(*shapes).compile().as_text()
    assert "tpu_custom_call" in text, "no Mosaic kernel in the program"
    return text


# ------------------------------------------------------------------ flash
_QKV = (8, 16, 1024, 128)     # lm-560m: B8 H16 T1024 dh128


@pytest.mark.parametrize("tile", [128, 256])
def test_flash_fwd_compiles(one_chip, tile):
    assert fa.supports(_QKV, tile, tile)
    q = one_chip(_QKV, "bfloat16")
    _compile(lambda q, k, v: fa._fwd_impl(
        q, k, v, 1.0 / 128 ** 0.5, True, tile, tile, False), q, q, q)


@pytest.mark.parametrize("tile", [128, 256])
def test_flash_bwd_compiles(one_chip, tile):
    q = one_chip(_QKV, "bfloat16")
    lse = one_chip(_QKV[:3], "float32")
    _compile(lambda q, k, v, o, lse, do: fa._bwd_impl(
        q, k, v, o, lse, do, 1.0 / 128 ** 0.5, True, tile, tile, False),
        q, q, q, q, lse, q)


# ------------------------------------------------------------------ paged
@pytest.mark.parametrize("grid", ["bh", "flat"])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_attention_compiles(one_chip, dtype, grid):
    """The serving width of chip_smoke.py: 8 slots, 16 heads of 128,
    16-token pages, max_len 1024 (64 pages a slot)."""
    B, H, dh, block, M, L = 8, 16, 128, 16, 64, 2
    assert pa.supports(block, dh, dtype)
    pool = one_chip((B * M + 1, L, H, block, dh), dtype)
    _compile(
        lambda q, pk, pv, bt, cur: pa._pallas_attention(
            q, pk, pv, bt, cur, 1, block,
            {"grid": grid, "live_only": True}, False),
        one_chip((B, H, 1, dh), dtype), pool, pool,
        one_chip((B, M), "int32"), one_chip((B,), "int32"))


def test_paged_gate_rejects_what_mosaic_rejects(one_chip):
    """A head narrower than the 128-lane tiling: the compiler refuses
    the page slice, and ``supports()`` must have said so first."""
    B, H, dh, block, M, L = 8, 2, 32, 16, 8, 2
    assert not pa.supports(block, dh, "float32")
    pool = one_chip((B * M + 1, L, H, block, dh), "float32")
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(
            lambda q, pk, pv, bt, cur: pa._pallas_attention(
                q, pk, pv, bt, cur, 1, block,
                {"grid": "bh", "live_only": True}, False),
            one_chip((B, H, 1, dh), "float32"), pool, pool,
            one_chip((B, M), "int32"), one_chip((B,), "int32"))


# ------------------------------------------------------ residual epilogue
# ResNet-50's four residual tails at batch 32: (N*H*W, C)
_TAILS = [(32 * 56 * 56, 256), (32 * 28 * 28, 512),
          (32 * 14 * 14, 1024), (32 * 7 * 7, 2048)]


@pytest.mark.parametrize("rows,channels", _TAILS)
def test_residual_epilogue_fwd_compiles(one_chip, rows, channels):
    assert repi.supports(rows, channels)
    x = one_chip((rows, channels), "bfloat16")
    c = one_chip((channels,), "float32")
    _compile(functools.partial(repi._pallas_fwd, interpret=False),
             x, x, c, c)


def test_residual_epilogue_vjp_compiles(one_chip):
    """The custom-vjp pair around the kernel: forward through Pallas,
    backward through the saved output's mask."""
    shape = (32, 14, 14, 1024)

    def loss(x, s, scale, bias):
        out = repi._epilogue(x, s, scale, bias, 3, True, False)
        return jnp.sum(out.astype(jnp.float32))

    x = one_chip(shape, "bfloat16")
    c = one_chip((shape[-1],), "float32")
    _compile(jax.grad(loss, argnums=(0, 1, 2, 3)), x, x, c, c)
