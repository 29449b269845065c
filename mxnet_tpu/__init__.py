"""mxnet_tpu: a TPU-native deep-learning framework with the API surface of
Apache MXNet, built from scratch on JAX/XLA/Pallas/pjit.

Layer map (TPU-native redesign of the reference's, see SURVEY.md §1):

  user code / model zoo        mxnet_tpu.gluon.model_zoo, mxnet_tpu.models
  frontends                    nd / np / npx / sym / gluon / module / autograd
  eager runtime                ops.invoke (≙ Imperative::Invoke) + autograd tape
  compiled runtime             jax.jit tracing (≙ CachedOp/GraphExecutor+nnvm)
  ops                          ops/* → jax.numpy / lax / Pallas (≙ src/operator)
  distributed                  kvstore + parallel/* → XLA collectives over
                               ICI/DCN (≙ src/kvstore ps-lite/NCCL)
  memory/scheduling            XLA + PJRT (≙ src/engine, src/storage)
"""
__version__ = "2.0.0.tpu0"

from . import base
from .base import MXNetError
from .context import (Context, cpu, gpu, tpu, cpu_pinned, current_context,
                      num_gpus, num_tpus)
from . import ops
from . import autograd
from . import ndarray
from . import ndarray as nd
from . import _rng

# mx.random: module-level alias of nd.random plus seed()
from .ndarray import random  # noqa: F401
from .ndarray import NDArray  # noqa: F401

# Persistent XLA compilation cache: opt-in via MXNET_TPU_COMPILE_CACHE=1
# (+ MXNET_TPU_COMPILE_CACHE_DIR). Configured at import so the first
# compile of the process already reads/writes the cache.
from .runtime import _configure_compile_cache_from_env as _ccc

_ccc()
del _ccc


def _lazy(name):
    import importlib
    return importlib.import_module(f".{name}", __name__)


# Lazy subpackages (heavy or cyclic): accessed as attributes.
_LAZY_MODULES = ("numpy", "numpy_extension", "symbol", "gluon", "module",
                 "optimizer", "metric", "initializer", "io", "kvstore",
                 "image", "parallel", "profiler", "lr_scheduler",
                 "callback", "test_utils", "util", "runtime", "amp",
                 "recordio", "executor", "monitor", "model", "operator",
                 "contrib", "onnx", "native", "library", "visualization",
                 "error", "engine", "attribute", "name", "rtc", "deploy",
                 "rnn", "resilience", "serving", "observability", "jit")



_ALIAS = {"np": "numpy", "npx": "numpy_extension", "sym": "symbol", "viz": "visualization",
          "mod": "module", "kv": "kvstore"}


def __getattr__(name):
    target = _ALIAS.get(name, name)
    if target in _LAZY_MODULES:
        mod = _lazy(target)
        globals()[name] = mod
        return mod
    raise AttributeError(f"module 'mxnet_tpu' has no attribute {name!r}")


def waitall():
    nd.waitall()
