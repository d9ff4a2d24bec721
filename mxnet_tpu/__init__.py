"""mxnet_tpu — a TPU-native deep-learning framework with the MXNet-0.9.5
capability surface (see SURVEY.md for the blueprint).

Import as ``import mxnet_tpu as mx`` — the namespaces mirror the reference's
``python/mxnet/__init__.py``: ``mx.nd``, ``mx.sym``, ``mx.mod``, ``mx.io``,
``mx.kv``, ``mx.optimizer``, ``mx.init``, ``mx.metric``, ``mx.rnn``, …
"""

__version__ = "0.1.0"

import time as _time
# where the ``setup.import`` span starts: read before the first import
_IMPORT_T0_NS = _time.monotonic_ns()
del _time

from .base import MXNetError
from .context import Context, cpu, gpu, tpu, current_context, num_tpus

from . import telemetry
from . import perfdebug
from . import faults
from . import compile_cache
# arm the persistent XLA compile cache (JAX_COMPILATION_CACHE_DIR, else
# the fixed in-checkout default) before any executor build can compile;
# never raises, and initialises no backend
compile_cache._init_from_env()
from . import retry
from . import elastic

from . import ops
from . import ndarray
from . import ndarray as nd
from . import random
from . import random as rnd

from . import attribute
from .attribute import AttrScope
from . import name
from . import symbol
from . import symbol as sym
from .symbol import Symbol, Group, Variable
from . import executor
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
from . import monitor as mon
from .monitor import Monitor

from . import io
from . import kvstore
from . import kvstore as kv
from .kvstore import KVStore

from . import rnn

from . import module
from . import module as mod
from .module import Module

from . import model
from .model import FeedForward
from . import checkpoint
from .checkpoint import TrainingPreempted
from . import models

from . import log
from . import operator
from . import predict
from . import serving
from . import profiler
from . import rtc
from . import torch_bridge
from .torch_bridge import th
from . import visualization
from . import visualization as viz
from . import image
from . import recordio
from . import test_utils

# DMLC_ROLE=server processes become parameter servers on import (reference
# python/mxnet/kvstore_server.py _init_kvstore_server_module)
from .kvstore_server import _init_kvstore_server_module as _srv_init
_srv_init()
del _srv_init

# this import, first line to last, in the ring a start's account is read
# from (``tracing.setup_span``: recorded with tracing off)
from . import tracing
_sp = tracing.setup_span("setup.import")
_sp.t0_ns = _IMPORT_T0_NS
_sp.end()
del _sp
