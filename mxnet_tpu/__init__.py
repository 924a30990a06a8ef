"""mxnet_tpu: a TPU-native deep-learning framework with MXNet-0.9.5
capabilities (reference: aaronenyeshi/mxnet), rebuilt on JAX/XLA/Pallas.

Public surface mirrors ``python/mxnet/__init__.py``: nd/ndarray, sym/symbol,
Context helpers, io, module, optimizer, metric, initializer, kvstore, autograd,
random, callback, lr_scheduler, profiler.
"""
from . import base
from .base import MXNetError

# arm the happens-before race detector BEFORE any engine/serving module
# allocates locks or threads, so every make_lock seam and stdlib
# primitive created below is instrumented (no-op unless
# MXNET_RACE_CHECK=1)
from .analysis import racecheck as _racecheck
_racecheck.maybe_install()
from .context import Context, cpu, gpu, tpu, cpu_pinned, current_context, \
    num_devices
from . import engine
from . import ops
from . import ndarray
from . import ndarray as nd
from . import random
from . import autograd

ndarray._init_ndarray_module()

from . import symbol  # noqa: E402
from . import symbol as sym  # noqa: E402
from .symbol import Variable  # noqa: E402
from . import executor  # noqa: E402
from .attribute import AttrScope  # noqa: E402
from .name import NameManager, Prefix  # noqa: E402
from . import initializer
from . import initializer as init  # mx.init shorthand (reference __init__.py:28)  # noqa: E402
from .initializer import init_registry  # noqa: E402
from . import optimizer  # noqa: E402
from .optimizer import Optimizer  # noqa: E402
from . import lr_scheduler  # noqa: E402
from . import metric  # noqa: E402
from . import kvstore
from . import kvstore as kv  # mx.kv shorthand (reference __init__.py:36)
from .kvstore import KVStore, create as create_kvstore  # noqa: E402
from . import kvstore_server  # noqa: E402  (role hijack runs at kvstore
# creation, not import — see kvstore_server._init_kvstore_server_module)
from . import faultinject  # noqa: E402  (deterministic dist fault injection)
from . import io
from .io import recordio  # noqa: E402
from . import data  # noqa: E402  (checkpointable sharded streaming datasets)
from . import module
from . import module as mod  # mx.mod shorthand (reference __init__.py:53)  # noqa: E402
from .module import Module  # noqa: E402
from . import model  # noqa: E402
from .model import FeedForward  # noqa: E402
from . import callback  # noqa: E402
from . import monitor  # noqa: E402
from .monitor import Monitor  # noqa: E402
from . import profiler  # noqa: E402
from . import metrics  # noqa: E402  (process metrics registry)
from . import tracing  # noqa: E402  (request tracing + flight recorder)
from . import rnn  # noqa: E402
from . import visualization  # noqa: E402
from . import visualization as viz  # noqa: E402
from . import parallel  # noqa: E402
from . import models  # noqa: E402
from . import operator  # noqa: E402
from . import image  # noqa: E402
from . import rtc  # noqa: E402
from . import predictor  # noqa: E402
from .predictor import Predictor  # noqa: E402
from . import deploy  # noqa: E402
from . import serving  # noqa: E402  (AOT program store + continuous batcher)
from . import executor_manager  # noqa: E402
from . import pallas_ops  # noqa: E402
from . import test_utils  # noqa: E402
from . import contrib  # noqa: E402

__version__ = "0.1.0"
