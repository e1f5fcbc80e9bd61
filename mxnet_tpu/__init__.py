"""mxnet_tpu — a TPU-native deep learning framework with the capabilities of
Apache MXNet 1.0.0 (reference: MaureenZOU/mxnet), rebuilt on JAX/XLA/Pallas.

Layer map (SURVEY.md §7.1): the reference's dependency engine, memory planner
and CUDA kernels are replaced by XLA compilation; NDArray wraps jax.Array;
Symbol graphs lower to single jitted XLA programs; KVStore data-parallelism
becomes in-program ICI collectives over a jax.sharding.Mesh.
"""
from time import perf_counter as _perf_counter

_import_start = _perf_counter()  # the mxnet_tpu.import span's start

__version__ = "1.0.0"

# MXNet supports float64 end-to-end (per-dtype test tolerances, fp64 ground
# truth in check_consistency — reference test_utils.py:1203); JAX needs x64
# opt-in. Weak typing keeps float32 as the working default on TPU.
import jax as _jax

_jax.config.update("jax_enable_x64", True)

# cpu() contexts — the default context among them — live on JAX's host
# backend, so it has to be loaded beside the accelerator. A platform list
# that names only the accelerator (JAX_PLATFORMS=tpu on a machine with a
# chip) gets ",cpu" appended; the accelerator stays first, so it stays the
# default backend, and a listed platform that cannot start still fails.
_platforms = _jax.config.jax_platforms
if _platforms and "cpu" not in _platforms.split(","):
    _jax.config.update("jax_platforms", _platforms + ",cpu")
del _platforms

from .base import MXNetError, AttrScope, NameManager, Prefix
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, num_gpus

from . import ndarray
from . import ndarray as nd
from . import random
from . import random as rnd  # reference alias (__init__.py:40)
from . import autograd
from . import symbol
from . import symbol as sym
from .symbol import Symbol
from . import executor
from .executor import Executor

from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from . import lr_scheduler
from . import metric
from . import callback
from . import io
from . import recordio
from . import image
from . import image as img  # reference alias (python/mxnet/__init__.py:75)  # reference alias (python/mxnet/__init__.py:75)
from . import config
from . import kvstore
from . import kvstore as kv
from . import kvstore_server
from . import model
from . import module
from . import module as mod
from . import monitor
from . import monitor as mon  # reference alias (__init__.py:63)
from .monitor import Monitor
from . import profiler
from . import observability
from . import autotune
from . import resilience
from . import rtc
from . import storage
from . import attribute
from . import name
from . import log
from . import libinfo
from . import engine
from . import executor_manager
from . import registry
from . import contrib
from . import visualization
from . import visualization as viz
from . import parallel
from . import runtime
from . import serving
from . import models
from . import gluon
from . import rnn
from . import test_utils
from . import operator
from .operator import _install_frontends as _iff

_iff()
del _iff

from .fluent import install as _install_fluent  # noqa: E402
from .fluent import NotImplementedForSymbol  # noqa: E402,F401

_install_fluent()
del _install_fluent


def __getattr__(attr):
    # kvstore_server is importable as mx.kvstore_server (reference module
    # layout) but loads lazily: an eager import would trip runpy's
    # double-import warning when the server role runs as
    # `python -m mxnet_tpu.kvstore_server` (tools/launch.py -s)
    if attr == "kvstore_server":
        import importlib

        mod = importlib.import_module(__name__ + ".kvstore_server")
        globals()[attr] = mod
        return mod
    raise AttributeError("module %r has no attribute %r"
                         % (__name__, attr))


observability.instruments.note_import(_import_start, _perf_counter())
del _import_start, _perf_counter
