"""Mesh + sharding helpers.

The reference maps devices via ctx lists and ctx_group attrs
(kvstore/comm.h device placement, graph_executor.cc PlaceDevice).  Here a
jax.sharding.Mesh with named axes is the single source of truth:

- 'data'  : batch (data parallel — kvstore local/device parity)
- 'model' : tensor parallel (no reference analogue; SURVEY.md §2.4 marks
            TP as absent upstream — first-class here)
- 'pipe'  : pipeline stages (ctx_group parity)
- 'seq'   : sequence/context parallel (ring attention)
"""
from __future__ import annotations

import os
import re
import threading
from dataclasses import dataclass
from typing import Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..base import MXNetError
from .. import telemetry as _tm

# docs/telemetry.md — set whenever a process mesh is (re)built, one
# sample per axis; the scrapeable record of the topology a run used
_TM_AXIS = _tm.gauge(
    "mesh_axis_size",
    "size of each axis of the process-level device mesh "
    "(MXTPU_MESH_SHAPE; set at global_mesh build)", labels=("axis",))


def create_mesh(shape=None, axes=("data",), devices=None) -> Mesh:
    """Build a Mesh from the available devices.

    create_mesh() -> 1-D data mesh over all devices;
    create_mesh((4, 2), ("data", "model")) -> 2-D dp x tp mesh.

    One axis may be ``-1`` (inferred from the device count).  A shape
    the devices cannot fill raises :class:`MXNetError` naming the
    counts — the raw ``reshape`` error a bad ``MXTPU_MESH_SHAPE`` used
    to surface names neither the shape nor the device count.
    """
    devices = list(devices) if devices is not None else jax.devices()
    if shape is None:
        shape = (len(devices),)
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axes):
        raise MXNetError(
            f"mesh shape {shape} has {len(shape)} dims for "
            f"{len(axes)} axes {tuple(axes)}")
    if sum(1 for s in shape if s == -1) > 1:
        raise MXNetError(f"mesh shape {shape}: at most one -1 axis")
    if any(s == 0 or s < -1 for s in shape):
        raise MXNetError(f"mesh shape {shape}: axis sizes must be "
                         "positive (or one -1 to infer)")
    if -1 in shape:
        known = int(np.prod([s for s in shape if s != -1]))
        if known <= 0 or len(devices) % known != 0:
            raise MXNetError(
                f"mesh shape {shape}: cannot infer -1 axis — "
                f"{len(devices)} devices not divisible by {known}")
        shape = tuple(len(devices) // known if s == -1 else s
                      for s in shape)
    n = int(np.prod(shape))
    if n > len(devices):
        raise MXNetError(
            f"mesh shape {shape} needs {n} devices, have {len(devices)}")
    arr = np.array(devices[:n]).reshape(shape)
    return Mesh(arr, axes)


# ---------------------------------------------------------------------------
# Process-level mesh (the GSPMD backend's single source of device truth).
#
# One logical 2-D mesh ("batch", "model") covers the process's devices:
# the executor group shards input batches over "batch", group2ctx
# PartitionSpec annotations place parameters over "model", and the
# sharded fused optimizer update (kvstore_fused) splits every flat
# bucket across the whole mesh per arXiv:2004.13336.  MXTPU_MESH_SHAPE
# ("8,1", "4,2", "-1,2", ...) picks the factorization; the default is
# pure data parallel (n_devices, 1).  The same code runs from 8 chips
# to pod slices — only this env var changes.
# ---------------------------------------------------------------------------
GLOBAL_AXES = ("batch", "model")
_global_mesh_cache = {}
_global_mesh_lock = threading.Lock()


def mesh_shape_from_env(n_devices: int):
    """Resolved MXTPU_MESH_SHAPE as a tuple (default (n_devices, 1))."""
    raw = os.environ.get("MXTPU_MESH_SHAPE", "").strip()
    if not raw:
        return (n_devices, 1)
    parts = [p for p in re.split(r"[,x\s]+", raw.strip("()[]")) if p]
    try:
        shape = tuple(int(p) for p in parts)
    except ValueError:
        raise MXNetError(f"MXTPU_MESH_SHAPE={raw!r}: expected integers "
                         "like '8,1' or '4,2'")
    if len(shape) == 1:
        shape = (shape[0], 1)
    if len(shape) != 2:
        raise MXNetError(f"MXTPU_MESH_SHAPE={raw!r}: the process mesh "
                         f"is 2-D {GLOBAL_AXES}, got {len(shape)} dims")
    return shape


def global_mesh(devices=None) -> Mesh:
    """The process-level ("batch", "model") mesh over ``devices``
    (default: all devices).  Cached per (env shape, device list); the
    ``mesh_axis_size`` gauge records the axes of the last build."""
    devices = list(devices) if devices is not None else jax.devices()
    raw = os.environ.get("MXTPU_MESH_SHAPE", "").strip()
    key = (raw, tuple(id(d) for d in devices))
    with _global_mesh_lock:
        mesh = _global_mesh_cache.get(key)
    if mesh is not None:
        return mesh
    shape = mesh_shape_from_env(len(devices))
    n = int(np.prod([s for s in shape if s != -1]))
    if -1 not in shape and len(devices) % n != 0:
        raise MXNetError(
            f"MXTPU_MESH_SHAPE={shape} needs a multiple of {n} devices, "
            f"have {len(devices)}")
    mesh = create_mesh(shape, GLOBAL_AXES, devices=devices)
    with _global_mesh_lock:
        _global_mesh_cache[key] = mesh
    if _tm.enabled():
        for axis, size in zip(GLOBAL_AXES, mesh.devices.shape):
            _TM_AXIS.set(size, axis=axis)
    return mesh


def data_sharding(mesh: Mesh, ndim: int, axis: str = "data") -> NamedSharding:
    """Batch-dim sharding for an ndim array."""
    return NamedSharding(mesh, P(axis, *([None] * (ndim - 1))))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


@dataclass
class ShardingRule:
    """Regex -> PartitionSpec rule for parameter sharding (the TP analogue
    of the reference's ctx_group model-parallel annotations)."""

    pattern: str
    spec: tuple

    def matches(self, name: str) -> bool:
        return re.match(self.pattern, name) is not None


def param_shardings(mesh: Mesh, names, rules: Sequence[ShardingRule] = ()) -> dict:
    """{name: NamedSharding} from the first matching rule per name
    (default: replicated over ``mesh``)."""
    out = {}
    for name in names:
        spec = P()
        for rule in rules:
            if rule.matches(name):
                spec = P(*rule.spec)
                break
        out[name] = NamedSharding(mesh, spec)
    return out


def shard_params(mesh: Mesh, params: dict, rules: Sequence[ShardingRule] = ()) -> dict:
    """Place every param according to the first matching rule (default:
    replicated over ``mesh``).

    The whole dict moves through ONE batched ``jax.device_put`` (one
    transfer program instead of one dispatch per param); entries whose
    sharding already equals their target pass through untouched — the
    micro-assert below pins that re-sharding an already-correctly-
    sharded dict is a no-op, so callers may re-apply rules defensively
    (e.g. a rebind) without paying a transfer.
    """
    shardings = param_shardings(mesh, params.keys(), rules)
    done, todo = {}, {}
    for name, arr in params.items():
        if isinstance(arr, jax.Array) and arr.sharding == shardings[name]:
            done[name] = arr
        else:
            todo[name] = arr
    if todo:
        moved = jax.device_put(todo, {k: shardings[k] for k in todo})
        done.update(moved)
    out = {name: done[name] for name in params}
    for name, arr in params.items():
        if isinstance(arr, jax.Array) and arr.sharding == shardings[name]:
            assert out[name] is arr, (
                f"shard_params: re-sharding already-placed param {name!r} "
                "must be a no-op")
    return out


def megatron_rules(model_axis: str = "model", shard_embed: bool = True):
    """Megatron-style tensor-parallel sharding rules for the transformer
    zoo (models/transformer.py naming).

    The classic layout (Shoeybi et al.): attention qkv and FFN-in are
    *column*-parallel (split the output features: weight rows, since
    FullyConnected weights are (out, in)), their biases split with them;
    the attention out-projection and FFN-out are *row*-parallel (split
    the input features: weight columns) with replicated biases — GSPMD
    then inserts exactly one all-reduce after each row-parallel matmul,
    matching Megatron's f/g collectives.  The LM head and token embedding
    shard over the vocab dim.

    Returns a tuple of ShardingRule for FusedTrainer(sharding_rules=...)
    / shard_params.  No reference analogue: SURVEY.md §2.4 marks TP
    absent upstream.
    """
    rules = [
        # attention: q/k/v column-parallel, out-projection row-parallel
        # (separate projections so the shard boundary never cuts a packed
        # q|k|v layout — models/transformer.py)
        ShardingRule(r".*_(q|k|v)_weight$", (model_axis, None)),
        ShardingRule(r".*_(q|k|v)_bias$", (model_axis,)),
        ShardingRule(r".*_proj_weight$", (None, model_axis)),
        # FFN: in column-parallel, out row-parallel
        ShardingRule(r".*_ffn_in_weight$", (model_axis, None)),
        ShardingRule(r".*_ffn_in_bias$", (model_axis,)),
        ShardingRule(r".*_ffn_out_weight$", (None, model_axis)),
        # LM head: vocab-dim column-parallel
        ShardingRule(r"lm_head_weight$", (model_axis, None)),
        ShardingRule(r"lm_head_bias$", (model_axis,)),
    ]
    if shard_embed:
        rules.append(ShardingRule(r"tok_embed_weight$", (model_axis, None)))
    return tuple(rules)
