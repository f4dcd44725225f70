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

The paged serving programs are compiled whole (abstract weights, a few
layers) for what the kernels alone cannot show: which layout the
compiler gives the page pool between them.  Any new program that takes
the pool is added to ``test_paged_program_keeps_pool_layout``
(docs/serving.md, "The pool's layout is the kernel's").  What a prefill
attends over is held the same way: no array of every layer's table, no
copy above one layer's (``test_paged_prefill_builds_no_table``, with
the gathered table it replaced as its control).  The same
programs over a decoder that declares page rows of its own and a
recurrent state a slot (``models/ling.py``) are compiled the same way
at the benchmark's real sizes, where a copy of a state array would cost
what the pool's copies cost before ISSUE 26:
``test_declared_program_copies_neither_state_nor_pages``.
"""
import functools
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from mxnet_tpu.models.decode import KVDecoder
from mxnet_tpu.ops import flash_attention as fa
from mxnet_tpu.ops import grouped_matmul as gmm
from mxnet_tpu.ops import latent_attention as la
from mxnet_tpu.ops import paged_attention as pa
from mxnet_tpu.ops import residual_epilogue as repi
from mxnet_tpu.serving.paged_kv import (_CachePrograms, _PrefillView,
                                        _StepView)


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
_QKV = (8, 16, 1024, 128)     # lm_train: B8 H16 T1024 dh128


def _flash_tiles(kernel, tile):
    """``tile`` a side, or what ``fa.tiles`` chooses for ``kernel`` at
    the benchmark's shape (the step's own kernels)."""
    if tile == "chosen":
        return fa.tiles(_QKV[2], _QKV[3], jnp.bfloat16)[kernel]
    return tile, tile


@pytest.mark.parametrize("tile", [128, 256, "chosen"])
def test_flash_fwd_compiles(one_chip, tile):
    bq, bk = _flash_tiles("fwd", tile)
    assert fa.supports(_QKV, jnp.bfloat16)
    q = one_chip(_QKV, "bfloat16")
    _compile(lambda q, k, v: fa._fwd_impl(
        q, k, v, 1.0 / 128 ** 0.5, True, bq, bk, False), q, q, q)


@pytest.mark.parametrize("tile", [128, 256, "chosen"])
def test_flash_bwd_compiles(one_chip, tile):
    q = one_chip(_QKV, "bfloat16")
    lse = one_chip(_QKV[:3], "float32")
    text = _compile(lambda q, k, v, o, lse, do: fa._bwd_impl(
        q, k, v, o, lse, do, 1.0 / 128 ** 0.5, True,
        _flash_tiles("dq", tile), _flash_tiles("dkv", tile), False),
        q, q, q, q, lse, q)
    # the row statistics travel lane-dense: a (T, 1) column is one value
    # a 128-lane tile, 67 MB an array at this shape
    assert "f32[128,1024,1]" not in text


@pytest.mark.parametrize("causal", [True, False])
def test_flash_fwd_compiles_in_float32(one_chip, causal):
    """float32 inputs keep float32 products (``contract_precision<fp32>``
    and tiles of 4 B): the chosen tiles must still fit."""
    bq, bk = fa.tiles(_QKV[2], _QKV[3], jnp.float32)["fwd"]
    q = one_chip(_QKV, "float32")
    _compile(lambda q, k, v: fa._fwd_impl(
        q, k, v, 1.0 / 128 ** 0.5, causal, bq, bk, False), q, q, q)


@pytest.mark.parametrize("t,d,dtype", [
    (2048, 128, "bfloat16"), (8192, 128, "bfloat16"), (16384, 64, "bfloat16"),
    (192, 64, "bfloat16"), (512, 64, "float32"), (2048, 128, "float32"),
    (4096, 128, "float32")])
def test_flash_chosen_tiles_fit_vmem(one_chip, t, d, dtype):
    """Who else runs the kernels: other lengths, narrow heads, float32.
    ``tiles`` steps down its ladder by a model of what the kernels ask
    of VMEM; Mosaic has the last word, here, for the whole custom_vjp."""
    assert fa.supports((1, 2, t, d), dtype)
    q = one_chip((1, 2, t, d), dtype)
    _compile(jax.grad(lambda q, k, v: jnp.sum(
        fa.flash_attention(q, k, v, True).astype(jnp.float32) ** 2),
        argnums=(0, 1, 2)), q, q, q)


# ------------------------------------------------------------------ paged
def _compile_paged_kernel(sds, B, H, dh, block, M, dtype, L=2, R=1):
    """``H`` K/V heads, ``R`` query rows each."""
    pool = sds((B * M + 1, L, H, block, dh), dtype)
    return _compile(
        lambda q, pk, pv, bt, cur: pa._pallas_attention(
            q, pk, pv, bt, cur, 1, block, False),
        sds((B, H, R, dh), dtype), pool, pool,
        sds((B, M), "int32"), sds((B,), "int32"))


@pytest.mark.parametrize("M", [64, 256, 1024])
@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_paged_attention_compiles(one_chip, dtype, M):
    """serve_batch's width: 16 slots, 16 heads of 128, 16-token pages,
    at its ``max_len`` 1024 (64 pages a slot) and at 4096 and 16384:
    the kernel holds two chunks of pages in VMEM whatever the table's
    length (the old one held the whole table, 2 x 4 MB at 16384)."""
    B, H, dh, block = 16, 16, 128, 16
    assert pa.supports(block, dh, dtype)
    chunk = pa.chunk_pages(H, block, dh, dtype, M)
    assert chunk == pa.chunk_pages(H, block, dh, dtype, 64)
    assert 4 * chunk * H * block * dh * jnp.dtype(dtype).itemsize \
        <= pa._VMEM_BUDGET
    _compile_paged_kernel(one_chip, B, H, dh, block, M, dtype)


@pytest.mark.parametrize("B,H,block,M,dtype", [
    (8, 16, 16, 64, "bfloat16"),     # chip_smoke.py's serve_paged_http
    (3, 2, 8, 4, "float32"),         # the CPU parity tests' small pages
    (4, 32, 32, 16, "bfloat16"),     # 256 KB pages: two in flight
])
def test_paged_attention_compiles_other_callers(one_chip, B, H, block, M,
                                                dtype):
    """One algorithm, its chunk read from the shapes: the other
    callers' sizes compile by the same path."""
    _compile_paged_kernel(one_chip, B, H, 128, block, M, dtype)


def test_paged_attention_compiles_for_a_block_of_grouped_queries(one_chip):
    """serve_block_sdar's width: 64 slots, 4 K/V heads of 128 that each
    serve 8 query heads x a block of 4 tokens = 32 rows, 16-token
    pages, 152 pages a slot, bfloat16."""
    _compile_paged_kernel(one_chip, 64, 4, 128, 16, 152, "bfloat16", L=7,
                          R=32)


def test_paged_gate_rejects_what_mosaic_rejects(one_chip):
    """A head narrower than the 128-lane tiling: the compiler refuses
    the page slice, and ``supports()`` must have said so first."""
    B, H, dh, block, M = 8, 2, 32, 16, 8
    assert not pa.supports(block, dh, "float32")
    with pytest.raises(Exception, match="aligned to tiling"):
        _compile_paged_kernel(one_chip, B, H, dh, block, M, "float32")


# ----------------------------------------------------------------- latent
def _compile_latent_kernel(sds, B, H, M, L, P, dtype, block=16, lanes=640,
                           width=576, rank=512):
    return _compile(
        lambda q, pool, bt, cur: la._pallas_attention(
            q, pool, bt, cur, L - 1, rank, 13.86, False),
        sds((B, H, lanes), dtype), sds((L, P, block, lanes), dtype),
        sds((B, M), "int32"), sds((B,), "int32"))


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("B,H,M,L,P", [
    pytest.param(16, 64, 1088, 6, 36161, id="serve_docqa_kimi"),
    pytest.param(128, 32, 144, 1, 18433, id="serve_batch_ling")])
def test_latent_attention_compiles(one_chip, B, H, M, L, P, dtype):
    """The two cells' steps: 16 slots x 64 heads over 1,088 pages a slot
    of a 6-layer pool of 36,161 pages, and 128 slots x 32 heads over 144
    pages a slot; rows of 576 in 640 lanes.  The block table rides whole
    as scalar prefetch (70 KB), two chunks of pages sit in VMEM whatever
    the table's length."""
    assert la.supports(16, 640, dtype)
    chunk = la.chunk_pages(16, 640, dtype, M)
    assert 2 * chunk * 16 * 640 * jnp.dtype(dtype).itemsize \
        <= la._VMEM_BUDGET
    text = _compile_latent_kernel(one_chip, B, H, M, L, P, dtype)
    assert "latent_attn" in text


def test_latent_attention_compiles_at_a_rank_off_the_lanes(one_chip):
    """A latent narrower than whole lanes (the rehearsal's 32 + 8 in
    128): the value product then covers the row and the caller cuts."""
    _compile_latent_kernel(one_chip, 3, 8, 8, 2, 40, "float32", block=8,
                           lanes=128, width=40, rank=32)


@pytest.mark.parametrize("block,lanes,why", [
    pytest.param(16, 576, "aligned to tiling", id="a_row_of_4.5_lane_tiles"),
    pytest.param(4, 640, "aligned to tiling", id="half_a_sublane_tile")])
def test_latent_gate_rejects_what_mosaic_rejects(one_chip, block, lanes, why):
    """What ``supports()`` refuses, the compiler refuses: a page row
    that is no whole number of 128-wide lanes (PR 33's rows of 576),
    and pages of 4 rows, half a tile of 8."""
    assert not la.supports(block, lanes, "bfloat16")
    with pytest.raises(Exception, match=why):
        _compile_latent_kernel(one_chip, 16, 64, 64, 2, 1025, "bfloat16",
                               block=block, lanes=lanes)


def test_a_page_that_is_one_row_of_a_matrix_cannot_be_copied(one_chip):
    """Why the pool changed shape in ISSUE 34: out of PR 33's ``(L, P,
    block * 576)`` a page is one row, strewn over 72 tiles of 8 pages x
    128 lanes, and the compiler refuses to copy it."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(pg_ref, pool_ref, o_ref, buf, sem):
        cp = pltpu.make_async_copy(pool_ref.at[1, pl.ds(pg_ref[0], 1)],
                                   buf, sem.at[0])
        cp.start()
        cp.wait()
        o_ref[...] = buf[...]

    def one_page(pg, pool):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((1, 9216), pool.dtype),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)],
                out_specs=pl.BlockSpec((1, 9216), lambda i, *_: (0, 0)),
                scratch_shapes=[pltpu.VMEM((1, 9216), pool.dtype),
                                pltpu.SemaphoreType.DMA((1,))]))(pg, pool)

    with pytest.raises(Exception, match="aligned to tiling"):
        _compile(one_page, one_chip((1,), "int32"),
                 one_chip((6, 36161, 9216), "bfloat16"))


# ------------------------------------------- paged programs and the pool
# serve_batch's pool: 16 slots x 64 pages of 16 tokens and the scratch
# page, 16 heads of 128; 3 of the 24 layers, and narrow where the pool
# does not see it (ffn, vocabulary), keep a compile to seconds
_SLOTS, _BLOCK, _MAX_LEN, _HEADS, _DH, _LAYERS = 16, 16, 1024, 16, 128, 3
_PAGES = _SLOTS * _MAX_LEN // _BLOCK + 1


def _pool_shape(layers):
    return "bf16[%d,%d,%d,%d,%d]" % (_PAGES, layers, _HEADS, _BLOCK, _DH)


_POOL = _pool_shape(_LAYERS)
_BUCKETS = (128, 256, 512, 1024)


def _paged_programs(sds, step_view=_StepView, layers=_LAYERS,
                    prefill_view=_PrefillView):
    """``serving/paged_kv.py``'s programs over a decoder that holds
    shapes for weights: nothing is allocated, everything lowers."""
    D, F, V = _HEADS * _DH, 512, 1024
    p = {"tok_embed_weight": (V, D), "pos_embed": (1, _MAX_LEN, D),
         "final_ln_gamma": (D,), "final_ln_beta": (D,),
         "lm_head_weight": (V, D), "lm_head_bias": (V,)}
    for i in range(layers):
        for w in ("q", "k", "v", "proj"):
            p[f"layer{i}_{w}_weight"] = (D, D)
            p[f"layer{i}_{w}_bias"] = (D,)
        for ln in ("ln1", "ln2"):
            p[f"layer{i}_{ln}_gamma"] = p[f"layer{i}_{ln}_beta"] = (D,)
        p[f"layer{i}_ffn_in_weight"] = (F, D)
        p[f"layer{i}_ffn_in_bias"] = (F,)
        p[f"layer{i}_ffn_out_weight"] = (D, F)
        p[f"layer{i}_ffn_out_bias"] = (D,)
    dec = KVDecoder.__new__(KVDecoder)
    dec.p = {k: sds(shape, "bfloat16") for k, shape in p.items()}
    dec.L, dec.H, dec.dh, dec.d_model = layers, _HEADS, _DH, D
    dec.max_len, dec.mesh = _MAX_LEN, None
    dec._cache_dtype = jnp.dtype("bfloat16")
    progs = _CachePrograms(
        dec, dec.paged_layout(), _BLOCK, _MAX_LEN // _BLOCK, _PAGES, _SLOTS,
        schedule=pa.default_schedule("tpu", _BLOCK, _DH, "bfloat16"))
    progs.step_view, progs.prefill_view = step_view, prefill_view
    return progs


def _compile_paged(progs, sds, which):
    """The step program (``which`` = "step") or one prefill bucket's,
    compiled with the pool handed over as ``PagedSlots`` does."""
    return _lower(progs, sds, which, _SLOTS, _MAX_LEN // _BLOCK)[0].compile()


def _lower(progs, sds, which, B, M):
    """The step program (``which`` = "step") or one prefill bucket's of
    ``progs``, lowered over the shapes of its cache and counters."""
    cache = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                   progs.pool_structs())
    counters = sds((len(progs.layout.get("counters", ())),), "int32")
    if which == "step":
        lowered = progs._step_jit.lower(
            *cache, counters, sds((B, M), "int32"), sds((B,), "int32"),
            sds((B,), "int32"), sds((B,), "bool"))
    else:
        lowered = progs.prefill(which).lower(
            *cache, counters, sds((M,), "int32"), sds((1, which), "int32"),
            sds((), "int32"), sds((), "int32"), sds((), "int32"))
    return lowered, cache


def _instructions(text):
    """``(name, result type, opcode)`` of each instruction of the
    optimized HLO."""
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*?) ([\w-]+)\(", line)
        if m is not None:
            yield m.groups()


def _is_copy(name, op):
    """A ``copy``, its asynchronous start, or a copy fusion."""
    return op in ("copy", "copy-start") or (op == "fusion"
                                            and "copy" in name)


def _copies_of(text, shapes):
    """Instructions of the optimized HLO that copy an array of one of
    ``shapes`` (``f32[128,32,128,128]``)."""
    return [name for name, result, op in _instructions(text)
            if _is_copy(name, op) and any(s in result for s in shapes)]


def _arrays_of(text, shapes):
    """Instructions of the optimized HLO, fused ones too, whose result
    is an array of one of ``shapes``."""
    return [name for name, result, _op in _instructions(text)
            if any(s in result for s in shapes)]


def _latent_schedules(lowering):
    """The page rows' schedule as ``PagedSlots`` resolves it on a TPU
    (``kernel``), or pinned to the lookup (``gather``)."""
    return {"latent": la.default_schedule(
        "tpu" if lowering == "kernel" else "cpu", _BLOCK, 640, "bfloat16")}


def _pool_copies(text, pool=_POOL):
    """Instructions of the optimized HLO that copy the whole pool."""
    return _copies_of(text, [pool])


@pytest.mark.parametrize("which", ("step",) + _BUCKETS)
def test_paged_program_keeps_pool_layout(one_chip, which):
    """No program reads or writes more of the pool than the pages it
    addresses: the pool stays in the row-major layout the Mosaic kernel
    demands from the entry parameter to the result, in place in the
    donated buffers."""
    compiled = _compile_paged(_paged_programs(one_chip), one_chip, which)
    text = compiled.as_text()
    assert _pool_copies(text) == []
    layouts = set(re.findall(re.escape(_POOL) + r"\{([\d,]+)", text))
    assert layouts == {"4,3,2,1,0"}, layouts
    assert text.count('custom_call_target="tpu_custom_call"') == (
        _LAYERS if which == "step" else 0)
    alias = re.search(r"input_output_alias=\{(.*?) \}", text)
    assert alias and re.findall(r"\{(\d)\}: \(\d+,", alias.group(1)) \
        == ["0", "1"], "the pools are not both aliased to the outputs"
    pool_bytes = 2 * _PAGES * _LAYERS * _HEADS * _BLOCK * _DH
    assert compiled.memory_analysis().alias_size_in_bytes == 2 * pool_bytes


def test_paged_step_at_full_depth_holds_one_kernel_a_layer(one_chip):
    """serve_batch's step at its 24 layers: 24 Mosaic calls (what
    ``kernels_in_step`` counts on the chip), no copy of the pool, both
    pools aliased, and temporaries that stay in the megabytes."""
    layers = 24
    compiled = _compile_paged(_paged_programs(one_chip, layers=layers),
                              one_chip, "step")
    text = compiled.as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == layers
    assert _pool_copies(text, _pool_shape(layers)) == []
    mem = compiled.memory_analysis()
    pool_bytes = 2 * _PAGES * layers * _HEADS * _BLOCK * _DH
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < 64 << 20


def test_row_scatter_relayouts_the_pool(one_chip):
    """The control: with the row scatter that the step program used to
    write with, compiled the same way, the compiler moves the scattered
    dimension out of the tiled pair and copies the whole pool into that
    layout and back for every kernel operand: 2 + 2 L copies.  So the
    test above is known to see them."""
    class RowScatter(_StepView):
        def _write_rows(self, pool, new, layer):
            pages, offs = (jnp.stack(x) for x in zip(*self._at))
            return pool.at[pages, layer, :, offs].set(new[:, :, 0])

    text = _compile_paged(_paged_programs(one_chip, RowScatter), one_chip,
                          "step").as_text()
    assert len(_pool_copies(text)) == 2 + 2 * _LAYERS


# ------------------------------------- what a paged prefill attends over
# one layer's table of one slot, (H, max_len, dh) bf16: 4 MB.  Until
# ISSUE 30 a prefill gathered every layer's for the slot, scattered each
# layer's new rows into that array and read the layer back out of it:
# the scatter wants one layout and the read another, so the compiler
# copied the whole array over and back in every layer (100 MB each way
# at serve_batch's 24 layers, 39% of the device's busy time)
_LAYER_TABLE_BYTES = 2 * _HEADS * _MAX_LEN * _DH
_ITEMSIZE = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "f16": 2, "s32": 4,
             "u32": 4, "f32": 4}


class GatheredTable(_PrefillView):
    """The prefill's attention as it was: the real tokens' rows go into
    the slot's gathered table, every layer's gathered once."""
    _tables = None

    def attend(self, layer, q, k, v):
        S = self._pg.max_blocks * self._pg.block
        if self._tables is None:
            def gathered(pool):                  # (L, 1, H, S, dh)
                t = pool[self._bt_row[None]].transpose(2, 0, 3, 1, 4, 5)
                return t.reshape(t.shape[:3] + (S, t.shape[-1]))
            self._tables = tuple(gathered(pool) for pool in self.kv)
            self._wpos = jnp.where(self.valid, self.positions, S)
            self._seen = jnp.arange(S)[None, :] <= self.positions[:, None]
        new = tuple(a[0].transpose(1, 0, 2) for a in (k, v))
        kc, vc = self._tables = tuple(
            table.at[layer, 0, :, self._wpos].set(rows)
            for table, rows in zip(self._tables, new))
        self.kv = tuple(self._write_pages(pool, rows, layer)
                        for pool, rows in zip(self.kv, new))
        return pa.dense_attention(q, kc[layer], vc[layer],
                                  self._seen[None, None])


def _tables_and_copies(text, layers):
    """Of the optimized HLO: the instructions whose result is an array
    of every layer's table (``bf16[L,1,H,S,dh]`` / ``bf16[L,H,S,dh]``),
    and the copies (``copy``, its asynchronous start, a copy fusion) of
    an array larger than one layer's table."""
    table = re.compile(r"bf16\[%d,(1,)?%d,%d,%d\]"
                       % (layers, _HEADS, _MAX_LEN, _DH))
    tables, copies = [], []
    for name, result, op in _instructions(text):
        m = re.match(r"(\w+)\[([\d,]*)\]", result)
        if m is None:                        # a tuple, a token
            continue
        if table.match(result):
            tables.append(name)
        nbytes = functools.reduce(
            lambda a, b: a * int(b), filter(None, m.group(2).split(",")),
            _ITEMSIZE.get(m.group(1), 0))
        if nbytes > _LAYER_TABLE_BYTES and _is_copy(name, op):
            copies.append(name)
    return tables, copies


@pytest.mark.parametrize("layers,bucket",
                         [(_LAYERS, b) for b in _BUCKETS] + [(24, 256)])
def test_paged_prefill_builds_no_table(one_chip, layers, bucket):
    """A prefill attends over one layer's pages of the slot and the
    tail's own K/V beside them: in no bucket's program is there an
    array of every layer's table, nor a copy of anything above one
    layer's; no control flow (a branch or a loop a layer loads slower:
    PERF.md, PR 30), temporaries no larger than the gathered table's
    program had (15.6 MB at 3 layers in bucket 1024, 337 MB at 24 in
    bucket 256), the pools in place as ever.  Once at serve_batch's 24
    layers."""
    compiled = _compile_paged(_paged_programs(one_chip, layers=layers),
                              one_chip, bucket)
    text = compiled.as_text()
    assert _tables_and_copies(text, layers) == ([], [])
    assert not re.search(r" (while|conditional)\(", text)
    assert _pool_copies(text, _pool_shape(layers)) == []
    mem = compiled.memory_analysis()
    pool_bytes = 2 * _PAGES * layers * _HEADS * _BLOCK * _DH
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.temp_size_in_bytes < (15_610_368 if layers == _LAYERS
                                     else 64 << 20)


@pytest.mark.parametrize("bucket", [128, 1024])
def test_gathered_table_is_copied_twice_a_layer(one_chip, bucket):
    """The control: the gathered table that a prefill used to attend
    over, compiled the same way, is there and is copied whole at least
    twice a layer.  So the test above is known to see both."""
    progs = _paged_programs(one_chip, prefill_view=GatheredTable)
    tables, copies = _tables_and_copies(
        _compile_paged(progs, one_chip, bucket).as_text(), _LAYERS)
    assert tables and len(copies) >= 2 * _LAYERS


# ------------------------------- declared cache: state beside the pages
# serve_batch_ling as the benchmark runs it: the configuration's own
# widths and layers, 128 slots of 2304 positions
_LING_SLOTS, _LING_MAX_LEN = 128, 2304


def _ling_programs(sds, donate=True, latent="gather"):
    """``_CachePrograms`` over a ``LingDecoder`` that holds shapes for
    weights, from the benchmark's configuration file."""
    import json

    from mxnet_tpu.models.ling import LingConfig, LingDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmark", "configs",
                           "ling-3.0-flash-ep4-l7.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    import sys
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.families import ling as fam

    dec = LingDecoder.__new__(LingDecoder)
    dec.cfg = LingConfig.from_dict(config)
    dec.p = {k: sds(tuple(v["shape"]),
                    "float32" if k.endswith(fam.KEPT_FLOAT32)
                    else "bfloat16")
             for k, v in fam.param_specs(config).items()}
    dec.max_len, dec.vocab = _LING_MAX_LEN, config["vocab_size"]
    dec._cache_dtype = jnp.dtype("bfloat16")
    M = _LING_MAX_LEN // _BLOCK
    progs = _CachePrograms(dec, dec.paged_layout(), _BLOCK, M,
                           _LING_SLOTS * M + 1, _LING_SLOTS,
                           page_schedules=_latent_schedules(latent))
    if not donate:
        from mxnet_tpu.models.decode import _WeightProgram
        progs._step_jit = _WeightProgram(
            dec, progs._step_program, "decode_step_ling_kept")
    return progs


def _compile_ling(progs, sds, which):
    lowered, cache = _lower(progs, sds, which, _LING_SLOTS,
                            _LING_MAX_LEN // _BLOCK)
    return lowered.compile(), cache


def _shape_text(s):
    short = {"float32": "f32", "bfloat16": "bf16"}[jnp.dtype(s.dtype).name]
    return "%s[%s]" % (short, ",".join(str(d) for d in s.shape))


# every slot's whole 2,304-row table of the one MLA layer, as the lookup
# builds it (and as PR 33's ``jnp.take`` did: the last)
_LING_TABLES = ["bf16[128,144,16,640]", "bf16[128,2304,640]",
                "bf16[18432,16,640]", "bf16[18432,9216]"]


@pytest.mark.parametrize("which,latent", [
    ("step", "kernel"), ("step", "gather"), (256, "gather"),
    (2048, "gather")])
def test_declared_program_copies_neither_state_nor_pages(one_chip, which,
                                                         latent):
    """The step and the prefills of the Ling decoder at the benchmark's
    sizes: no instruction copies a recurrent state (268 MB a KDA layer,
    1.61 GB in all) or the latent pool (377 MB), every leaf of the
    cache is written in place in the donated buffers, and the program
    fits the chip beside its 12.4 GB of arguments.  The convolution
    tails (9 MB a layer) are not held to this: the step's compiler
    stages them through its fast memory, which costs microseconds.
    The step's latent attention under the kernel builds no table of
    the slots' rows; under the other lowering it looks one up, which
    shows that the check can see a table."""
    compiled, cache = _compile_ling(_ling_programs(one_chip, latent=latent),
                                    one_chip, which)
    text = compiled.as_text()
    leaves = jax.tree_util.tree_leaves(cache)
    nbytes = lambda s: s.dtype.itemsize * functools.reduce(
        lambda a, b: a * b, s.shape)
    big = {_shape_text(s) for s in leaves if nbytes(s) > 64e6}
    assert big == {"f32[128,32,128,128]", "bf16[1,18433,16,640]"}
    assert _copies_of(text, big) == []
    cache_bytes = sum(nbytes(s) for s in leaves)
    assert 1.9e9 < cache_bytes < 2.1e9
    mem = compiled.memory_analysis()
    assert cache_bytes <= mem.alias_size_in_bytes < 1.001 * cache_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.0e9
    if which == "step":
        # the TPU compiler's own grouped-matmul kernels (lax.ragged_dot),
        # three a MoE layer and one for its group metadata, and under
        # the kernel this repo's latent attention for the one MLA layer
        ours = len(re.findall(r'custom-call\(.*latent_attn', text))
        assert ours == (latent == "kernel")
        assert text.count('custom_call_target="tpu_custom_call"') == 24 + ours
        assert "paged_attn" not in text
        assert bool(_arrays_of(text, _LING_TABLES)) == (latent == "gather")


def test_a_cache_that_is_not_donated_is_not_written_in_place(one_chip):
    """The control: compiled without ``donate``, nothing aliases, so the
    test above is known to see a state that is not carried in place."""
    compiled, _ = _compile_ling(_ling_programs(one_chip, donate=False),
                                one_chip, "step")
    assert compiled.memory_analysis().alias_size_in_bytes == 0


# ------------------------------------------- a block decoder's programs
# serve_block_sdar: benchmark/configs/sdar-30b-a3b-chat-l7.json at its
# published widths and 7 layers, 64 slots of 2432 positions
_SDAR_SLOTS, _SDAR_MAX_LEN = 64, 2432
_SDAR_PAGES = _SDAR_SLOTS * _SDAR_MAX_LEN // _BLOCK + 1
_SDAR_POOL = "bf16[%d,7,4,%d,128]" % (_SDAR_PAGES, _BLOCK)


def _sdar_programs(sds, step_view=_StepView):
    """``_CachePrograms`` over an ``SdarDecoder`` that holds shapes for
    weights, from the benchmark's configuration file."""
    import json
    import sys

    from mxnet_tpu.models.sdar import SdarConfig, SdarDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.families import sdar as fam

    with open(os.path.join(root, "benchmark", "configs",
                           "sdar-30b-a3b-chat-l7.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    dec = SdarDecoder.__new__(SdarDecoder)
    dec.cfg = c = SdarConfig.from_dict(config)
    dec.p = {k: sds(tuple(v["shape"]), "bfloat16")
             for k, v in fam.param_specs(config).items()}
    dec.max_len, dec.vocab = _SDAR_MAX_LEN, config["vocab_size"]
    dec.block_length, dec.mask_id = c.block_length, c.mask_id
    dec._cache_dtype = jnp.dtype("bfloat16")
    progs = _CachePrograms(
        dec, dec.paged_layout(), _BLOCK, _SDAR_MAX_LEN // _BLOCK,
        _SDAR_PAGES, _SDAR_SLOTS,
        schedule=pa.default_schedule("tpu", _BLOCK, c.head_dim, "bfloat16"))
    progs.step_view = step_view
    return progs


def _lower_sdar(progs, sds, which):
    B, M, n = _SDAR_SLOTS, _SDAR_MAX_LEN // _BLOCK, progs.block_n
    cache = jax.tree_util.tree_map(lambda s: sds(s.shape, s.dtype),
                                   progs.pool_structs())
    counters = sds((len(progs.layout["counters"]),), "int32")
    if which == "step":
        return progs._step_jit.lower(
            *cache, counters, sds((B, M), "int32"), sds((B, n), "int32"),
            sds((B,), "int32"), sds((B,), "bool"))
    return progs.prefill(which).lower(
        *cache, counters, sds((M,), "int32"), sds((1, which), "int32"),
        sds((), "int32"), sds((), "int32"), sds((), "int32"))


@pytest.mark.parametrize("which", ("step", 256, 2048))
def test_block_decoder_program_keeps_pool_layout(one_chip, which):
    """The SDAR step (a block of 4 tokens a slot, written as one
    ``dynamic_update_slice`` a slot and layer) and its prefills at the
    benchmark's sizes: no instruction copies a K/V pool (1.12 GB each),
    both are written in place in the donated buffers in the kernel's
    row-major layout, the step holds one paged-attention kernel a layer
    (beside the TPU compiler's own grouped-matmul calls, four a layer),
    and the program fits the chip beside its 12.2 GB of arguments."""
    compiled = _lower_sdar(_sdar_programs(one_chip), one_chip,
                           which).compile()
    text = compiled.as_text()
    assert _pool_copies(text, _SDAR_POOL) == []
    layouts = set(re.findall(re.escape(_SDAR_POOL) + r"\{([\d,]+)", text))
    assert layouts == {"4,3,2,1,0"}, layouts
    kernels = len(re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*paged_attn',
        text))
    assert kernels == (7 if which == "step" else 0)
    mem = compiled.memory_analysis()
    pool_bytes = 2 * _SDAR_PAGES * 7 * 4 * _BLOCK * 128
    assert mem.alias_size_in_bytes == 2 * pool_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


def test_block_row_scatter_relayouts_the_pool(one_chip):
    """The control: the block's rows written by a scatter and not by
    one ``dynamic_update_slice`` a slot make the compiler copy the
    whole pool, so the test above is known to see such copies."""
    class RowScatter(_StepView):
        def _write_rows(self, pool, new, layer):
            pages, offs = (jnp.stack(x) for x in zip(*self._at))
            rows = offs[:, None] + jnp.arange(new.shape[2])      # (B, n)
            return pool.at[pages[:, None], layer, :, rows].set(
                new.transpose(0, 2, 1, 3))

    text = _lower_sdar(_sdar_programs(one_chip, RowScatter), one_chip,
                       "step").compile().as_text()
    assert len(_pool_copies(text, _SDAR_POOL)) >= 2


# ------------------------------------------- latent pages and nothing else
# serve_docqa_kimi: benchmark/configs/kimi-k2-instruct-ep32-l6.json at
# its published widths and 6 layers, 16 slots of 17,408 positions over a
# pool of 36,160 pages (4.0 GB of latent rows)
_KIMI_SLOTS, _KIMI_MAX_LEN, _KIMI_PAGES = 16, 17408, 36160 + 1
_KIMI_POOL = "bf16[6,%d,%d,640]" % (_KIMI_PAGES, _BLOCK)
# one layer of the pool (666 MB in PR 33's rows of 9,216, which every
# gather copied out first), and every slot's whole 17,408-row table
_KIMI_SLABS = ["bf16[%d,%d,640]" % (_KIMI_PAGES, _BLOCK),
               "bf16[1,%d,%d,640]" % (_KIMI_PAGES, _BLOCK),
               "bf16[%d,9216]" % _KIMI_PAGES]
_KIMI_TABLES = ["bf16[16,1088,16,640]", "bf16[16,17408,640]",
                "bf16[17408,16,640]", "bf16[17408,9216]"]


def _kimi_programs(sds, latent="gather"):
    """``_CachePrograms`` over a ``KimiDecoder`` that holds shapes for
    weights, from the benchmark's configuration file."""
    import json
    import sys

    from mxnet_tpu.models.kimi import KimiConfig, KimiDecoder

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    from benchmark.families import kimi as fam

    with open(os.path.join(root, "benchmark", "configs",
                           "kimi-k2-instruct-ep32-l6.json")) as f:
        config = json.load(f)
    config.pop("rehearse")
    dec = KimiDecoder.__new__(KimiDecoder)
    dec.cfg = KimiConfig.from_dict(config)
    dec.p = {k: sds(tuple(v["shape"]),
                    "float32" if k.endswith("router_bias") else "bfloat16")
             for k, v in fam.param_specs(config).items()}
    dec.max_len, dec.vocab = _KIMI_MAX_LEN, config["vocab_size"]
    dec._cache_dtype = jnp.dtype("bfloat16")
    return _CachePrograms(dec, dec.paged_layout(), _BLOCK,
                          _KIMI_MAX_LEN // _BLOCK, _KIMI_PAGES, _KIMI_SLOTS,
                          page_schedules=_latent_schedules(latent))


@pytest.mark.parametrize("which,latent", [
    ("step", "kernel"), ("step", "gather"), (128, "gather"),
    (2048, "gather")])
def test_latent_page_program_copies_no_pool(one_chip, which, latent):
    """The Kimi step (a latent-attention kernel a layer over each slot's
    live pages; under the other lowering the slots' tables looked up by
    layer and page) and its prefills (the tail's pages written, this
    layer's rows of the slot looked up, a loop over the history's key
    blocks) at the benchmark's sizes: no instruction copies the 4.4 GB
    latent pool, it is written in place in the donated buffer, NO ARRAY
    OF ONE LAYER OF IT exists anywhere (PR 33's ``pool[layer]`` was a
    666 MB copy before each gather), and the program fits the chip
    beside its 12.8 GB of arguments -- the largest prefill's blocks of
    scores among the temporaries.  Under the kernel the step holds no
    table of the slots' rows either and next to no temporaries; under
    the other lowering it holds one a layer, which shows that the
    check can see a table."""
    lowered, _ = _lower(_kimi_programs(one_chip, latent), one_chip, which,
                        _KIMI_SLOTS, _KIMI_MAX_LEN // _BLOCK)
    compiled = lowered.compile()
    text = compiled.as_text()
    assert _copies_of(text, {_KIMI_POOL}) == []
    assert _arrays_of(text, _KIMI_SLABS) == []
    pool_bytes = 2 * 6 * _KIMI_PAGES * _BLOCK * 640
    mem = compiled.memory_analysis()
    assert 4.4e9 < pool_bytes <= mem.alias_size_in_bytes < 1.001 * pool_bytes
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9
    if which != "step":
        assert "while" in text      # the history's loop is a loop still
        return
    ours = len(re.findall(r'custom-call\(.*latent_attn', text))
    assert ours == (6 if latent == "kernel" else 0)
    assert bool(_arrays_of(text, _KIMI_TABLES)) == (latent == "gather")
    if latent == "kernel":
        assert mem.temp_size_in_bytes < 64e6
    else:
        # the lookup stages no fill: no select over a table's shape
        assert not [name for name, result, op in _instructions(text)
                    if op == "select" and "17408" in result
                    and "bf16" in result]


def test_grouped_matmul_refuses_an_expert_that_does_not_fit_vmem():
    """A Kimi-K2 expert matrix is 29 MB (7168 x 2048): the kernel holds
    an expert's WHOLE matrix as one block, two of each stack in flight,
    so gate and up need 117 MB and down 59 MB of the 48 MB it may use.
    ``supports`` says so and ``moe_serve`` keeps ``lax.ragged_dot``
    there, on the TPU too."""
    assert not gmm.supports(128, 7168, 2048, "bfloat16", 2)
    assert not gmm.supports(128, 2048, 7168, "bfloat16", 1)
    assert gmm.default_schedule("tpu", 128, 7168, 2048, "bfloat16",
                                2) == {"impl": "ragged"}


# ------------------------------------------ the experts' grouped matmul
# (rows, k, n, stacks): the step's two calls in serve_block_sdar and in
# serve_batch_ling, and the largest prefill buffer (bucket 2048, 8 pairs
# a token)
_GMM = [(2048, 2048, 768, 2), (2048, 768, 2048, 1),
        (1024, 2560, 768, 2), (1024, 768, 2560, 1),
        (16384, 2560, 768, 2), (16384, 768, 2560, 1)]


@pytest.mark.parametrize("rows,k,n,stacks", _GMM)
def test_grouped_matmul_compiles(one_chip, rows, k, n, stacks):
    """128 experts' matrices of 3.1-3.9 MB, two of each stack in VMEM:
    more than the 16 MiB a kernel gets unasked."""
    assert gmm.supports(rows, k, n, "bfloat16", stacks)
    text = _compile(
        lambda x, sizes, *w: gmm.grouped_matmul(
            x, w, sizes, schedule={"impl": "pallas"}),
        one_chip((rows, k), "bfloat16"), one_chip((128,), "int32"),
        *[one_chip((128, k, n), "bfloat16")] * stacks)
    assert "grouped_matmul" in text and "ragged-dot" not in text


_STACKS = ["bf16[128,%d,%d]" % s for s in
           ((2048, 768), (768, 2048), (2560, 768), (768, 2560))]


@pytest.mark.parametrize("lowering", ["kernel", "ragged"])
@pytest.mark.parametrize("family", ["sdar", "ling"])
def test_step_holds_the_grouped_matmul_kernel(one_chip, monkeypatch, family,
                                              lowering):
    """The SDAR and Ling step programs at the benchmark's sizes with
    ``moe_serve`` told it lowers for a TPU (under a described topology
    ``jax.default_backend()`` says cpu): two calls of this repo's kernel
    a MoE layer (gate and up together, down), none of the compiler's
    ``ragged-dot`` kernels, and no copy of a 403 / 503 MB weight stack
    (a layout the kernel and the program disagreed on would cost one).
    The control is the same program as it lowers off the TPU: the
    compiler's kernels and none of ours, so each check can fail."""
    if lowering == "kernel":
        on_cpu = gmm.default_schedule
        monkeypatch.setattr(gmm, "default_schedule",
                            lambda platform, *a, **k: on_cpu("tpu", *a, **k))
    if family == "sdar":
        compiled = _lower_sdar(_sdar_programs(one_chip), one_chip,
                               "step").compile()
        moe_layers, others = 7, 7               # a paged_attn a layer
    else:
        compiled, _ = _compile_ling(_ling_programs(one_chip), one_chip,
                                    "step")
        moe_layers, others = 6, 0
    text = compiled.as_text()
    ours = len(re.findall(
        r'custom-call\(.*custom_call_target="tpu_custom_call".*'
        r'grouped_matmul', text))
    mosaic = text.count('custom_call_target="tpu_custom_call"')
    assert _copies_of(text, _STACKS) == []
    if lowering == "kernel":
        assert ours == 2 * moe_layers and mosaic == ours + others
        assert "ragged-dot" not in text
    else:
        assert ours == 0 and mosaic == 4 * moe_layers + others
        assert "ragged-dot" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 15.5e9


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
