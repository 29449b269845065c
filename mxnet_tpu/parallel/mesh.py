"""Device mesh construction.

Reference analogue: the kvstore/comm topology machinery
(src/kvstore/comm_tree.h:50 ComputeTrees builds reduction trees from the
PCIe/NVLink link matrix). On TPU none of that exists: the ICI fabric is a
torus XLA already knows; we only pick logical axis sizes.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import numpy as _np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = ["make_mesh", "local_mesh", "data_parallel_spec",
           "mesh_shard_info", "parse_mesh", "llm_mesh", "batch_spec",
           "leaf_spec", "round_up_to_dp", "spans_processes",
           "place_global", "to_host", "spmd_metrics", "note_mesh"]


def make_mesh(dp: Optional[int] = None, tp: int = 1, pp: int = 1,
              sp: int = 1, ep: int = 1, devices=None) -> Mesh:
    """Build a Mesh with named axes (dp, tp, pp, sp, ep); ``dp=None``
    absorbs all remaining devices.

    The axis order places dp outermost so data-parallel allreduce rides
    the widest rings, with tp innermost (finest-grained collectives on
    nearest neighbors) — the standard ICI layout recipe."""
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    fixed = tp * pp * sp * ep
    if dp is None:
        assert n % fixed == 0, \
            f"{n} devices not divisible by tp*pp*sp*ep={fixed}"
        dp = n // fixed
    total = dp * fixed
    assert total <= n, f"requested {total} devices, have {n}"
    arr = _np.array(devices[:total]).reshape(dp, pp, sp, ep, tp)
    return Mesh(arr, ("dp", "pp", "sp", "ep", "tp"))


def local_mesh(n: Optional[int] = None) -> Mesh:
    """1-axis dp mesh over local devices — the moral equivalent of
    kvstore 'device' (single-host data parallel)."""
    devices = jax.devices()
    if n is not None:
        devices = devices[:n]
    return Mesh(_np.array(devices), ("dp",))


def data_parallel_spec(ndim: int) -> PartitionSpec:
    """PartitionSpec sharding axis0 (batch) on dp, rest replicated."""
    return PartitionSpec("dp", *([None] * (ndim - 1)))


def parse_mesh(spec, devices=None) -> Mesh:
    """Build a Mesh from a compact string spec — the CLI/env spelling of
    :func:`make_mesh` (``MXNET_TPU_MESH``, ``chip_smoke.py``):

    - ``"8"``            → 1-axis dp mesh over 8 devices
    - ``"dp=4,tp=2"``    → named axis extents (unnamed axes default 1)
    - ``"dp=-1,tp=2"``   → dp absorbs the remaining devices
    """
    spec = str(spec).strip()
    if not spec:
        return local_mesh()
    if spec.isdigit():
        return local_mesh(int(spec))
    axes = {}
    for part in spec.split(","):
        k, _, v = part.partition("=")
        k, v = k.strip(), v.strip()
        if k not in ("dp", "tp", "pp", "sp", "ep"):
            raise ValueError(f"unknown mesh axis {k!r} in {spec!r} "
                             "(axes: dp, tp, pp, sp, ep)")
        axes[k] = int(v)
    dp = axes.pop("dp", None)
    if dp is not None and dp < 0:
        dp = None
    return make_mesh(dp=dp, devices=devices, **axes)


def llm_mesh(spec, devices=None) -> Mesh:
    """Build the serving mesh from a compact string spec — the
    CLI/env spelling for the LLM engine (``LLMServer(mesh=)``,
    ``MXNET_TPU_LLM_MESH``). Same axis grammar as :func:`parse_mesh`
    but with SERVING defaults: only ``dp``/``tp`` axes exist, a bare
    integer means tensor-parallel width, and ``dp`` defaults to 1
    instead of absorbing leftover devices (an engine that silently
    grew replica groups because the host had spare chips would break
    the one-scheduler accounting; ask for dp explicitly).

    - ``"tp=2"``       → 1x2 (dp, tp) mesh
    - ``"2"``          → tp=2
    - ``"dp=2,tp=2"``  → 2 replica groups of 2-way tensor parallel
    - ``"dp=-1,tp=2"`` → dp absorbs the remaining devices
    """
    spec = str("" if spec is None else spec).strip()
    axes = {"dp": 1, "tp": 1}
    if spec.isdigit():
        axes["tp"] = int(spec)
    elif spec:
        for part in spec.split(","):
            k, _, v = part.partition("=")
            k, v = k.strip(), v.strip()
            if k not in axes:
                raise ValueError(f"unknown llm mesh axis {k!r} in "
                                 f"{spec!r} (axes: dp, tp)")
            axes[k] = int(v)
    devices = list(devices if devices is not None else jax.devices())
    dp, tp = axes["dp"], axes["tp"]
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if dp < 0:
        if len(devices) % tp:
            raise ValueError(f"{len(devices)} devices not divisible "
                             f"by tp={tp}")
        dp = len(devices) // tp
    if dp < 1:
        raise ValueError(f"dp must be >= 1, got {dp}")
    total = dp * tp
    if total > len(devices):
        raise ValueError(f"llm mesh dp={dp},tp={tp} needs {total} "
                         f"devices, have {len(devices)}")
    arr = _np.array(devices[:total]).reshape(dp, tp)
    return Mesh(arr, ("dp", "tp"))


# ----------------------------------------------------------- placement --
# The SPMD train step (jit.CompiledTrainStep mesh mode / ShardedTrainer)
# places every program input through these helpers so single-process and
# multi-process meshes share one code path.

@functools.lru_cache(maxsize=64)
def spans_processes(mesh: Mesh) -> bool:
    """True when the mesh covers devices of more than this process.
    Cached: scanning ``mesh.devices.flat`` in Python on every step would
    cost thousands of attribute reads per step on big slices."""
    pid = jax.process_index()
    return any(d.process_index != pid for d in mesh.devices.flat)


def batch_spec(ndim: int, mesh: Mesh, rows: int,
               axis: str = "dp") -> PartitionSpec:
    """PartitionSpec for a batch-major program input: axis 0 sharded on
    ``dp`` when the mesh has a dp extent > 1 that divides ``rows``,
    replicated otherwise (an indivisible batch is still correct SPMD —
    every device just sees the full batch and no gradient psum is
    emitted)."""
    dp = dict(mesh.shape).get(axis, 1)
    if ndim == 0 or dp <= 1 or rows % dp:
        return PartitionSpec()
    return PartitionSpec(axis, *([None] * (ndim - 1)))


def leaf_spec(spec: PartitionSpec, shape, mesh: Mesh) -> PartitionSpec:
    """Clamp a parameter's PartitionSpec onto an array of ``shape`` —
    optimizer slots ride with their parameter's spec when they are
    weight-shaped, and fall back to replicated when they are not (scalar
    slots, per-row norms) or when a sharded dim is not divisible by its
    mesh axis extent."""
    spec = tuple(spec or ())
    if not spec or all(ax is None for ax in spec):
        return PartitionSpec()
    if len(spec) != len(shape):
        return PartitionSpec()
    extents = dict(mesh.shape)
    out = []
    for dim, ax in zip(shape, spec):
        if ax is None:
            out.append(None)
            continue
        size = 1
        for a in (ax if isinstance(ax, tuple) else (ax,)):
            size *= extents.get(a, 1)
        out.append(ax if size > 1 and dim % size == 0 else None)
    if all(ax is None for ax in out):
        return PartitionSpec()
    return PartitionSpec(*out)


def round_up_to_dp(bucket: int, mesh: Mesh, axis: str = "dp") -> int:
    """Round a batch bucket up to a multiple of the mesh's dp extent so
    the batch axis stays evenly shardable (pad rows are masked by the
    train step's traced real-row count)."""
    dp = dict(mesh.shape).get(axis, 1)
    if dp > 1 and bucket % dp:
        bucket += dp - (bucket % dp)
    return bucket


@functools.lru_cache(maxsize=4096)
def _named_sharding(mesh: Mesh, spec: PartitionSpec) -> NamedSharding:
    # the per-step placement sweep (jit._place_mesh/_place_nt,
    # ShardedTrainer.step) calls place_global for every weight and
    # optimizer slot on every step; caching the NamedSharding keeps
    # that steady-state no-op path at a dict hit + equality check per
    # leaf instead of an object construction
    return NamedSharding(mesh, spec)


def _placed_as(arr, sharding) -> bool:
    try:
        return arr.sharding == sharding
    except AttributeError:
        return False


def place_global(arr, mesh: Mesh, spec: PartitionSpec,
                 host_has: str = "full"):
    """Place a value onto ``mesh`` as one global array with ``spec``
    sharding; a no-op when it already lives there. Within one process
    this is a plain ``device_put``. Across processes the meaning of the
    host value matters (``host_has``):

    - ``"full"``: every process holds the whole (global-shape) value —
      parameters/optimizer state. Replicated specs broadcast rank 0's
      values (the reference dist_sync init semantics: kvstore_dist.h
      Init pushes rank-0 weights), so ranks cannot silently train on
      divergent 'replicated' parameters; sharded specs slice each
      process's addressable shards out of its full copy
      (make_array_from_callback) — NOT concatenation.
    - ``"local_shard"``: each process holds only its own piece —
      batches. The global array is the concatenation of every process's
      local array along the sharded axis
      (host_local_array_to_global_array), the reference's dist_sync
      data layout."""
    sharding = _named_sharding(mesh, spec)
    if _placed_as(arr, sharding):
        return arr
    if spans_processes(mesh):
        from jax.experimental import multihost_utils
        arr = _np.asarray(arr)
        replicated = all(ax is None for ax in (spec or ())) \
            or spec == PartitionSpec()
        if host_has == "full":
            if replicated:
                arr = multihost_utils.broadcast_one_to_all(arr)
            return jax.make_array_from_callback(
                arr.shape, sharding, lambda idx: arr[idx])
        return multihost_utils.host_local_array_to_global_array(
            arr, mesh, spec)
    return jax.device_put(arr, sharding)


def to_host(arr) -> _np.ndarray:
    """Full host value of a (possibly sharded) global array. Fully
    addressable arrays are a plain device_get; multi-process global
    arrays need the allgather (only the checkpoint writer pays it)."""
    try:
        addressable = arr.is_fully_addressable
    except AttributeError:
        addressable = True
    if addressable:
        return _np.asarray(jax.device_get(arr))
    from jax.experimental import multihost_utils
    return _np.asarray(multihost_utils.process_allgather(arr, tiled=True))


# ------------------------------------------------------------- metrics --

_SPMD_OBS = None


def spmd_metrics() -> dict:
    """The ``mxtpu_spmd_*`` series: evidence that multi-chip training is
    ONE program per step (dispatch count), what it moves over ICI
    (collective bytes), and what mesh it runs on (shape gauges)."""
    global _SPMD_OBS
    if _SPMD_OBS is None:
        from ..observability import get_registry
        reg = get_registry()
        _SPMD_OBS = {
            "dispatch": reg.counter(
                "mxtpu_spmd_step_dispatch_total",
                "SPMD whole-step program launches (steady state: exactly "
                "1 per training step at any device count)."),
            "programs": reg.counter(
                "mxtpu_spmd_program_compiles_total",
                "SPMD whole-step program builds, by (devices, bucket) — "
                "flat after warmup = zero steady-state recompiles.",
                ("devices", "bucket")),
            "bytes": reg.counter(
                "mxtpu_spmd_collective_bytes_total",
                "Logical in-program collective payload, by collective "
                "kind (grad_reduce = per-step gradient psum bytes over "
                "the dp axis; XLA may further shard/fuse the actual ICI "
                "transfers).", ("collective",)),
            "devices": reg.gauge(
                "mxtpu_spmd_mesh_devices",
                "Device count of the mesh the last SPMD step program "
                "was built for."),
            "axis": reg.gauge(
                "mxtpu_spmd_mesh_axis_extent",
                "Logical axis extents of the active SPMD mesh.",
                ("axis",)),
        }
    return _SPMD_OBS


def note_mesh(mesh: Mesh) -> None:
    """Publish the mesh shape on the ``mxtpu_spmd_mesh_*`` gauges."""
    obs = spmd_metrics()
    obs["devices"].set(int(mesh.devices.size))
    for ax, extent in dict(mesh.shape).items():
        obs["axis"].labels(axis=ax).set(int(extent))


def mesh_shard_info(mesh: Mesh) -> dict:
    """Checkpoint-facing shard layout metadata for a mesh: how many
    parallel checkpoint shards the mesh naturally supports (one per
    participating process), which shard this process owns, and the
    logical axis extents — recorded in sharded-checkpoint manifests so
    an elastic resume knows what world wrote the state it is reading
    (``resilience.sharded`` plans its row layout from this count when
    ``MXNET_TPU_CKPT_SHARDED=auto``)."""
    procs = sorted({d.process_index for d in mesh.devices.flat})
    me = jax.process_index()
    return {
        "num_shards": len(procs),
        "shard_id": procs.index(me) if me in procs else 0,
        "axes": {k: int(v) for k, v in dict(mesh.shape).items()},
        "num_devices": int(mesh.devices.size),
    }
