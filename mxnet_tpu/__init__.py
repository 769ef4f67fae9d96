"""mxnet_tpu — a TPU-native deep-learning framework with the capabilities of
Apache MXNet v0.10.1 (the NNVM-era hybrid imperative/symbolic framework).

Not a port: the reference's async dependency engine + per-op CUDA kernels become
jax/XLA whole-graph compilation; its KVStore GPU-P2P/ps-lite communication becomes
ICI/DCN collectives over a jax device mesh; cuDNN kernels become XLA HLOs (+
Pallas where XLA lags). The user contract preserved: ``mx.nd``, ``mx.sym``,
``mx.mod.Module.fit``, ``mx.io``, ``mx.kv``, optimizer/metric/initializer/rnn
namespaces, and checkpoint formats. See SURVEY.md at the repo root for the full
layer map of the reference this framework re-implements.
"""
from . import base
from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context
from . import ops
from . import ndarray
from . import ndarray as nd
from . import symbol
from . import symbol as sym
from . import random
from .attribute import AttrScope
from .name import NameManager, Prefix
from .executor import Executor
from . import initializer
from . import initializer as init
from . import optimizer
from . import optimizer as opt
from .optimizer import Optimizer
from . import metric
from . import lr_scheduler
from . import callback
from . import monitor
from . import io
from . import io_image
from . import image_det
from . import recordio
from . import kvstore as kv
from .kvstore import KVStore, create as _kv_create
from . import module
from . import module as mod
from . import executor_manager
from . import model
from .model import FeedForward
from . import compileobs
from . import compile_cache
from . import graphpass
from . import fault
from . import guard
from . import telemetry
from . import rnn
from . import visualization
from . import visualization as viz
from . import profiler
from . import rtc
from . import torch_bridge
from . import torch_bridge as th
from . import torch_bridge as torch
from . import parallel
from . import contrib
from . import test_utils
from . import utils
from . import log
from . import notebook
from . import symbol_doc
from . import ndarray_doc
from . import kvstore_server
from . import random as rnd
from . import image as img
from . import monitor as mon

# later-MXNet convenience aliases: mx.nd.contrib.<op> / mx.sym.contrib.<op>
ndarray.contrib = contrib.ndarray
symbol.contrib = contrib.symbol

from . import engine
from . import operator
from . import export_artifact
from .export_artifact import export_predict_artifact, export_train_artifact

# Custom registers into the op registry after symbol/ndarray generated their
# functions at import — generate its wrappers explicitly
symbol.Custom = symbol._make_symbol_function("Custom")
ndarray.Custom = ndarray._make_ndarray_function("Custom")

# persistent cross-process compile cache (docs/compiler.md): wired at import
# when JAX_COMPILATION_CACHE_DIR or MXNET_COMPILE_CACHE_DIR is set — jax's
# persistent-cache config must land before the process's first compile
compile_cache.maybe_enable_from_env()

# server-role processes block here until the cluster shuts down
# (reference: python/mxnet/__init__.py → kvstore_server._init_kvstore_server_module)
if __import__("os").environ.get("DMLC_ROLE") in ("server", "scheduler"):
    from .kvstore_server import _init_kvstore_server_module

    _init_kvstore_server_module()

__version__ = "0.1.0"
