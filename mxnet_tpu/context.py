"""Device context.

TPU-native re-design of the reference's ``Context`` (reference:
``python/mxnet/context.py``, ``include/mxnet/base.h`` DevType). The reference
carries a set of device kinds (cpu/gpu/cpu_pinned/cpu_shared) because its runtime
hand-manages memory per device kind; here a Context is a thin, hashable facade
over a ``jax.Device`` — PJRT owns allocation, XLA owns placement. ``gpu`` is
kept as an alias for "the accelerator" so reference user code ports unchanged.
"""
from __future__ import annotations

import threading
from typing import Optional

import jax

__all__ = [
    "Context", "cpu", "gpu", "tpu", "cpu_pinned", "current_context",
    "num_gpus", "num_tpus", "device",
]


class Context:
    """A device context: (device_type, device_id).

    Acts as a context manager that sets the default device for array
    creation, mirroring ``with mx.gpu(0):`` usage in the reference
    (``python/mxnet/context.py:228``).
    """

    # numeric codes kept for save/load compatibility with the reference's
    # NDArray binary format (include/mxnet/base.h: kCPU=1, kGPU=2, ...)
    devtype2mask = {"cpu": 1, "gpu": 2, "cpu_pinned": 3, "cpu_shared": 5, "tpu": 6}
    devmask2type = {v: k for k, v in devtype2mask.items()}

    _tls = threading.local()

    __slots__ = ("device_type", "device_id", "_old")

    def __init__(self, device_type: str, device_id: int = 0):
        if isinstance(device_type, Context):
            device_type, device_id = device_type.device_type, device_type.device_id
        if device_type not in self.devtype2mask:
            raise ValueError(f"unknown device type {device_type!r}")
        self.device_type = device_type
        self.device_id = int(device_id)
        self._old = None

    # -- identity ----------------------------------------------------------
    def __eq__(self, other):
        return (isinstance(other, Context)
                and self.device_type == other.device_type
                and self.device_id == other.device_id)

    def __hash__(self):
        return hash((self.device_type, self.device_id))

    def __repr__(self):
        return f"{self.device_type}({self.device_id})"

    __str__ = __repr__

    # -- jax mapping -------------------------------------------------------
    @property
    def jax_device(self) -> jax.Device:
        """The backing jax.Device. 'gpu' and 'tpu' both name the
        accelerator and raise when this process has no such chip; cpu
        maps to host (every cpu id shares the one host device).
        Only process-local devices are candidates: under
        jax.distributed, jax.devices() spans every process, and eager
        arrays can only live on addressable ones."""
        if self.device_type in ("cpu", "cpu_pinned", "cpu_shared"):
            devs = (jax.local_devices(backend="cpu")
                    if _has_platform("cpu") else jax.local_devices())
            return devs[min(self.device_id, len(devs) - 1)]
        devs = _accelerator_devices()
        if not 0 <= self.device_id < len(devs):
            raise ValueError(
                f"{self}: this process has {len(devs)} accelerator "
                f"device(s) ({jax.default_backend()} backend)")
        return devs[self.device_id]

    # -- scoping -----------------------------------------------------------
    def __enter__(self):
        if not hasattr(Context._tls, "stack"):
            Context._tls.stack = []
        Context._tls.stack.append(self)
        return self

    def __exit__(self, *exc):
        Context._tls.stack.pop()
        return False

    def empty_cache(self):
        """Reference API parity (MXStorageEmptyCache): PJRT owns the HBM
        pool, so this is a no-op provided for compatibility."""

    def memory_info(self):
        """(free, total) bytes on this context's device — the SURVEY §7
        memory-stats surface (reference: context.py:279 gpu_memory_info
        → MXGetGPUMemoryInformation64). Backed by PJRT's
        device.memory_stats(); returns (None, None) where the platform
        does not expose allocator stats (e.g. host CPU)."""
        try:
            stats = self.jax_device.memory_stats()
        except Exception:
            stats = None
        if not stats:
            return (None, None)
        total = stats.get("bytes_limit") or stats.get(
            "bytes_reservable_limit")
        in_use = stats.get("bytes_in_use", 0)
        free = total - in_use if total is not None else None
        return (free, total)

    @classmethod
    def default_ctx(cls) -> "Context":
        stack = getattr(cls._tls, "stack", None)
        if stack:
            return stack[-1]
        return _DEFAULT[0]


def _has_platform(name: str) -> bool:
    try:
        return bool(jax.devices(name))
    except RuntimeError:
        return False


def _accelerator_devices():
    """Process-local non-cpu jax devices."""
    return [d for d in jax.local_devices() if d.platform != "cpu"]


def cpu(device_id: int = 0) -> Context:
    return Context("cpu", device_id)


def cpu_pinned(device_id: int = 0) -> Context:
    return Context("cpu_pinned", device_id)


def gpu(device_id: int = 0) -> Context:
    """Accelerator context. On TPU hosts this is the TPU chip — kept so
    reference scripts written against mx.gpu() run unmodified."""
    return Context("gpu", device_id)


def tpu(device_id: int = 0) -> Context:
    return Context("tpu", device_id)


def device(dev: jax.Device) -> Context:
    """Wrap a raw jax.Device in a Context."""
    if dev.platform == "cpu":
        return Context("cpu", dev.id)
    return Context("tpu", _accelerator_devices().index(dev))


def num_gpus() -> int:
    """Number of accelerator devices (reference: mx.context.num_gpus)."""
    return len(_accelerator_devices())


def num_tpus() -> int:
    return len(_accelerator_devices())


def current_context() -> Context:
    return Context.default_ctx()


# Default: the accelerator if present, else cpu. Computed lazily on first
# array creation so that test harnesses can force JAX_PLATFORMS=cpu first.
class _DefaultCtx:
    def __init__(self):
        self._ctx: Optional[Context] = None

    def __getitem__(self, i) -> Context:
        if self._ctx is None:
            self._ctx = Context("tpu", 0) if _accelerator_devices() else Context("cpu", 0)
        return self._ctx


_DEFAULT = _DefaultCtx()


def gpu_memory_info(device_id=0):
    """(free, total) for an accelerator device (reference:
    context.py:279 gpu_memory_info). On this framework 'gpu' and 'tpu'
    name the same accelerator pool."""
    return gpu(device_id).memory_info()


def tpu_memory_info(device_id=0):
    return tpu(device_id).memory_info()
