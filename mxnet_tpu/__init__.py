"""mxnet_tpu — a TPU-native framework with the capabilities of MXNet v0.9.4.

Not a port: the compute substrate is JAX/XLA (jit, vjp, sharding, Pallas),
the API surface is MXNet's (nd/sym/mod/kv/io) so reference user code maps
1:1.  See SURVEY.md at the repo root for the blueprint and per-module
docstrings for reference citations.
"""
import os as _os

import jax as _jax

# Platform selection must happen before ANY backend initializes.
# MXTPU_PLATFORM=cpu pins a process to host XLA — used by multi-process
# launches on a single-accelerator box (a chip belongs to one process);
# server-role processes (parameter server) are host-only and never touch
# the accelerator (parity: reference servers are CPU processes).
_platform = _os.environ.get("MXTPU_PLATFORM")
if _platform is None and _os.environ.get(
        "MXTPU_ROLE", _os.environ.get("DMLC_ROLE")) == "server":
    _platform = "cpu"
if _platform:
    _jax.config.update("jax_platforms", _platform)

from .base import MXNetError, AttrScope, NameManager, __version__, get_env as _get_env

# float32 arrays get true-fp32 matmuls (parity with the reference's fp32
# math); the fast path on TPU is explicit bfloat16 dtypes, which this
# setting does not affect.  Override with MXNET_TPU_MATMUL_PRECISION
# (e.g. "bfloat16" to trade accuracy for speed on fp32 data).
_jax.config.update(
    "jax_default_matmul_precision",
    _get_env("MXNET_TPU_MATMUL_PRECISION", "float32", str),
)
from .context import Context, cpu, cpu_pinned, gpu, tpu, current_context, num_devices
from . import engine
from . import random
from . import ops
from . import ndarray
from . import ndarray as nd
from .ndarray import NDArray
from . import sparse
from .sparse import RowSparseNDArray
ndarray.sparse = sparse  # reference surface: mx.nd.sparse.row_sparse_array
from . import symbol
from . import symbol as sym
from .symbol import Symbol, Variable, Group
from . import executor
from .executor import Executor
from . import amp
from . import passes
from . import initializer
from . import initializer as init
from .initializer import Initializer, Uniform, Normal, Xavier, Orthogonal, MSRAPrelu, Mixed, Load
from . import optimizer
from .optimizer import Optimizer
from . import metric
from . import lr_scheduler
from . import callback
from . import io
from . import recordio
from . import filesystem
from . import storage
from . import image
from . import kvstore as kv
from . import kvstore_server
from . import checkpoint
from . import faults
from . import model
from .model import FeedForward, save_checkpoint, load_checkpoint
from . import executor_manager
from . import predict
from . import module
from . import module as mod
from .module import Module, BucketingModule, SequentialModule, PythonModule
from . import monitor
from . import monitor as mon
from .monitor import Monitor
from . import resource
from .resource import ResourceRequest, ResourceManager
from . import rnn
from . import operator
from . import profiler
from . import telemetry
from . import rtc
from . import visualization
from . import visualization as viz
from . import test_utils

__all__ = [
    "MXNetError",
    "AttrScope",
    "NameManager",
    "Context",
    "cpu",
    "gpu",
    "tpu",
    "current_context",
    "nd",
    "NDArray",
    "engine",
    "random",
]

# Must be the LAST statement: server-role processes serve the parameter
# store here and exit without reaching user code (parity: reference
# mxnet/__init__.py importing kvstore_server at the bottom).
kvstore_server._init_kvstore_server_module()
