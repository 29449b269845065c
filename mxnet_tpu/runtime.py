"""mx.runtime — runtime feature detection + persistent compile cache.

Reference: python/mxnet/runtime.py (Features / feature_list /
Feature.is_enabled over libinfo's compile-time flags). The TPU build has
no compile-time feature matrix; features reflect the live jax runtime:
platform backends, device counts, and library capabilities.

This module also owns the persistent XLA compilation cache: XLA compiles
dominate warm-start time (a ResNet train step is seconds of compile), and
they are fully repeatable across process restarts, so
``enable_compile_cache`` turns on JAX's on-disk cache and every
subsequent process skips straight to the compiled executable. The
directory is the deployment's to place: ``JAX_COMPILATION_CACHE_DIR``,
which JAX reads itself, or else one fixed directory of the checkout.
``MXNET_TPU_COMPILE_CACHE=1`` turns the cache on at import; cache hits
land on the jaxmon bridge's ``mxtpu_xla_cache_hits_total`` counter,
which is how warm-start behavior is asserted.
"""
from __future__ import annotations

import collections
import os

__all__ = ["Feature", "Features", "feature_list", "enable_compile_cache"]

#: where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset.
#: The directory is part of the cache key, so it is a fixed path beside
#: the package, never a temporary name.
_CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def enable_compile_cache(cache_dir=None):
    """Enable JAX's persistent (on-disk) compilation cache.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX has already read it
    and the directory is left alone; otherwise the cache goes to
    ``cache_dir`` or, by default, ``<checkout>/.jax_cache``. Either way
    the entry-size/compile-time floors are dropped so every executable
    is cached — the cache exists to make warm starts cheap, not to
    ration disk. Returns the directory in use. Idempotent; safe to call
    before or after the backend initializes (config flags apply to
    subsequent compiles)."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    if cache_dir is None and os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        cache_dir = jax.config.jax_compilation_cache_dir
    else:
        cache_dir = os.path.abspath(os.path.expanduser(
            str(cache_dir or _CHECKOUT_CACHE_DIR)))
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # the process may already have compiled (and thereby initialized
    # the cache with the old dir/None); re-point the live instance
    compilation_cache.reset_cache()
    return cache_dir


def _configure_compile_cache_from_env():
    """Import-time hook: honor MXNET_TPU_COMPILE_CACHE=1. An unwritable
    cache dir must not take down interpreter startup."""
    if os.environ.get("MXNET_TPU_COMPILE_CACHE") != "1":
        return None
    try:
        return enable_compile_cache()
    except OSError:
        return None


Feature = collections.namedtuple("Feature", ["name", "enabled"])


def _probe():
    import jax

    feats = {}
    try:
        platform = jax.devices()[0].platform
    except RuntimeError:
        platform = "none"
    feats["TPU"] = platform not in ("cpu", "none")
    feats["CUDA"] = False            # CUDA never backs this build
    feats["CPU"] = True
    feats["INT64_TENSOR_SIZE"] = True
    feats["F16C"] = True             # bfloat16 native on TPU; emulated CPU
    feats["DIST_KVSTORE"] = True     # jax.distributed + KVStoreTPU
    feats["PALLAS"] = True           # flash-attention kernels
    try:
        feats["NUM_DEVICES_%d" % jax.device_count()] = True
    except RuntimeError:
        pass
    return feats


class Features(dict):
    """Mapping name -> Feature (reference: runtime.py Features)."""

    def __init__(self):
        super().__init__({k: Feature(k, v) for k, v in _probe().items()})

    def is_enabled(self, name):
        name = name.upper()
        return name in self and self[name].enabled

    def __repr__(self):
        return "[" + ", ".join(
            f"{f.name}: {'✔' if f.enabled else '✖'}"
            for f in self.values()) + "]"


def feature_list():
    return list(Features().values())
