"""Eager op invocation.

TPU-native replacement of the reference's imperative invoke path
(reference: src/imperative/imperative.cc:98 ``Imperative::Invoke`` →
``SetShapeType`` → ``PushFCompute`` → engine). There is no dependency engine
here: JAX's async dispatch + XLA give the same "Python returns immediately,
device runs later" contract, and read/write ordering is inherent because
arrays are immutable values (mutation = rebinding the buffer).

``apply_op`` is the single chokepoint every generated ``nd.*`` function goes
through — the analogue of ``MXImperativeInvokeEx`` — and is also where
autograd taping happens (reference: ``Imperative::RecordOp``).
"""
from __future__ import annotations

import threading
from typing import Optional, Sequence

import jax
import jax.numpy as jnp

from .. import autograd, _rng
from .registry import Operator, get as get_op

__all__ = ["apply_op", "apply_fn", "wrap_out", "as_jax",
           "TRACED_HYPERPARAMS"]

import numpy as _np

# AMP hook state, mutated by mxnet_tpu.amp (the TPU-native analogue of the
# reference's amp_cast graph-rewrite insertion, python/mxnet/contrib/amp/
# amp.py:283 — here the cast happens at the op-invoke chokepoint instead
# of by patching every generated namespace function).
_AMP = {"active": False, "dtype": None, "lp_ops": frozenset(),
        "f32_ops": frozenset()}


def _amp_cast_inputs(op_name, inputs):
    import numpy as _onp
    NDArray = _ndarray_cls()
    if op_name in _AMP["lp_ops"]:
        target = _AMP["dtype"]
    elif op_name in _AMP["f32_ops"]:
        target = _onp.float32
    else:
        return inputs
    out = []
    for x in inputs:
        if isinstance(x, NDArray) and x.dtype in (_onp.float32,
                                                  _AMP["dtype"]) \
                and x.dtype != target:
            x = x.astype(target)
        out.append(x)
    return out


def _ndarray_cls():
    from ..ndarray.ndarray import NDArray
    return NDArray


def as_jax(x):
    """Unwrap NDArray / coerce array-likes to jax values."""
    NDArray = _ndarray_cls()
    if isinstance(x, NDArray):
        return x._data
    return x  # tracers, jnp arrays, numpy, scalars pass through


def wrap_out(data):
    NDArray = _ndarray_cls()
    return NDArray(data)


def _participating_slots(inputs):
    slots = []
    any_part = False
    for x in inputs:
        s = getattr(x, "_ag_slot", None)
        slots.append(s)
        any_part = any_part or (s is not None)
    return slots, any_part


def apply_fn(fn, inputs: Sequence, nout: int = 1, differentiable: bool = True,
             out=None):
    """Run a pure jax function over NDArray inputs with autograd taping.

    This is the generic path used both by registered ops and by ad-hoc
    differentiable closures (indexing, fused expressions).
    """
    NDArray = _ndarray_cls()
    xs = tuple(as_jax(i) for i in inputs)

    in_slots, any_part = _participating_slots(inputs)
    recorded = (differentiable and autograd.is_recording() and any_part)

    if recorded:
        outs, vjp_fn = jax.vjp(fn, *xs)
    else:
        outs = fn(*xs)

    single = not isinstance(outs, (tuple, list))
    outs_t = (outs,) if single else tuple(outs)

    results = []
    if out is not None:
        # write-to-output form (reference `out=` kwarg): rebind the
        # destination's buffer; not taped (matches reference kWriteTo refusal
        # to record in-place writes of graph arrays)
        targets = (out,) if isinstance(out, NDArray) else tuple(out)
        for t, o in zip(targets, outs_t):
            t._data = jnp.asarray(o, dtype=t.dtype) if o.dtype != t.dtype else o
            results.append(t)
    else:
        results = [NDArray(o) for o in outs_t]

    if recorded and out is None:
        out_slots = [autograd.new_slot() for _ in results]
        out_avals = [(r.shape, r._data.dtype) for r in results]
        for r, s in zip(results, out_slots):
            r._ag_slot = s
        # fn/xs allow create_graph=True to re-derive this vjp
        # differentiably (autograd._taped_vjp)
        autograd.record_node(vjp_fn, in_slots, out_slots, out_avals,
                             fn=fn, xs=xs)

    return results[0] if single else tuple(results)


def _embedding_sparse_grad(op, inputs, params):
    """Eager Embedding with ``sparse_grad=True``: record a tape node whose
    weight cotangent is a RowSparseNDArray of (looked-up row ids, output
    cotangents) — no (vocab, dim) dense scatter (reference:
    src/operator/tensor/indexing_op.cc EmbeddingOpBackward with
    kRowSparseStorage). Returns None under tracing (jit of a hybridized
    block): there the dense scatter-add vjp is the right XLA program.
    """
    NDArray = _ndarray_cls()
    data, weight = inputs[0], inputs[1]
    if any(isinstance(getattr(x, "_data", x), jax.core.Tracer)
           for x in (data, weight)):
        return None
    from ..ndarray.sparse import RowSparseNDArray

    in_slots, any_part = _participating_slots([data, weight])
    if not any_part:
        return None

    idx = as_jax(data).astype(jnp.int32)
    w = as_jax(weight)
    out_val = jnp.take(w, idx, axis=0)
    result = NDArray(out_val)

    def vjp_fn(dy):
        gw = RowSparseNDArray(dy.reshape(-1, w.shape[-1]), idx.ravel(),
                              w.shape)
        return (None, gw)

    out_slot = autograd.new_slot()
    result._ag_slot = out_slot
    autograd.record_node(vjp_fn, in_slots, [out_slot],
                         [(result.shape, out_val.dtype)])
    return result


# ---------------------------------------------------------------------------
# Single-dispatch optimizer-op path.
#
# Per-step hyperparameters that enter compiled update programs as TRACED
# scalars (weak-typed, exactly like an eager Python-float operand) so lr/wd/
# momentum schedules and LossScaler rescale changes never trigger a
# recompile. Floats OUTSIDE this set (clip_gradient, clip_weights, lower/
# upper bounds) stay static because the impls branch on them in Python.
TRACED_HYPERPARAMS = frozenset({"lr", "wd", "momentum", "rescale_grad"})

_MUTATES_JIT_CACHE = {}

# Set by optimizer.fused while it records an update program: apply_op hands
# mutates-op invocations to the recorder instead of executing them, so one
# host pass over the per-param updater yields the op sequence + scalar
# hyperparameter values that the fused single-dispatch program replays.
_FUSED_RECORDER = threading.local()


def _is_dynamic(v):
    return isinstance(v, jax.core.Tracer) or isinstance(v, jax.Array)


def _split_hyper(params):
    """(static kwargs, traced keys, traced values) for one mutates-op call.
    Only plain floats under TRACED_HYPERPARAMS become traced; everything
    else (bools, ints, None, structural floats) is baked into the compiled
    program and keys the jit cache."""
    static, tkeys, tvals = [], [], []
    for k in sorted(params):
        v = params[k]
        if k in TRACED_HYPERPARAMS and isinstance(v, (float, _np.floating)) \
                and not isinstance(v, bool):
            tkeys.append(k)
            tvals.append(float(v))
        else:
            static.append((k, v))
    return tuple(static), tuple(tkeys), tvals


def _mutates_jit(op, static_kw, traced_keys):
    key = (op.name, static_kw, traced_keys)
    fn = _MUTATES_JIT_CACHE.get(key)
    if fn is None:
        skw = dict(static_kw)
        impl, keys = op.impl, traced_keys

        def call(xs, tvals):
            kw = dict(skw)
            kw.update(zip(keys, tvals))
            return impl(*xs, **kw)

        fn = jax.jit(call)
        _MUTATES_JIT_CACHE[key] = fn
    return fn


def _run_mutates(op, xs, params):
    """Execute a mutates (optimizer update) op as ONE compiled dispatch.

    The impl runs under jax.jit with TRACED_HYPERPARAMS floats passed as
    weak-typed traced scalars: numerics are identical to handing the impl a
    Python float, there is one XLA execution instead of one per jnp
    primitive, and a changed lr/momentum/rescale value reuses the compiled
    program. Falls back to the direct eager impl when a hyperparameter is
    itself a tracer/array (op invoked under an outer trace with traced
    hyperparams, e.g. parallel.ShardedTrainer) or an int (lamb's ``t``
    would bake a new program every step)."""
    for v in params.values():
        if _is_dynamic(v) or (isinstance(v, int) and not isinstance(v, bool)):
            return op.impl(*xs, **params)
    static_kw, tkeys, tvals = _split_hyper(params)
    try:
        hash(static_kw)
    except TypeError:
        return op.impl(*xs, **params)
    return _mutates_jit(op, static_kw, tkeys)(xs, tuple(tvals))


def apply_op(op, inputs: Sequence, params: Optional[dict] = None, out=None):
    """Invoke a registered op on NDArray inputs."""
    if not isinstance(op, Operator):
        op = get_op(op)
    params = dict(params) if params else {}

    if _AMP["active"]:
        inputs = _amp_cast_inputs(op.name, inputs)

    if op.needs_rng and "rng" not in params:
        params["rng"] = _rng.next_key()
    if op.needs_train and "_training" not in params:
        params["_training"] = autograd.is_training()

    if op.mutates:
        recorder = getattr(_FUSED_RECORDER, "rec", None)
        if recorder is not None:
            return recorder.record(op, inputs, params)
        # optimizer-style in-place update: impl returns the new values of the
        # mutated inputs; rebind their buffers (reference: kWriteInplace ops
        # like sgd_update, src/operator/optimizer_op.cc)
        xs = tuple(as_jax(i) for i in inputs)
        outs = _run_mutates(op, xs, params) if not op.variadic \
            else op.impl(list(xs), **params)
        outs_t = (outs,) if not isinstance(outs, (tuple, list)) else tuple(outs)
        results = []
        for k, m in enumerate(op.mutates):
            tgt = inputs[m]
            tgt._data = outs_t[k]
            results.append(tgt)
        return results[0] if len(results) == 1 else tuple(results)

    if ((op.name == "Embedding" and params.get("sparse_grad"))
            or op.name == "_contrib_SparseEmbedding") \
            and autograd.is_recording():
        res = _embedding_sparse_grad(op, inputs, params)
        if res is not None:
            return res

    if op.variadic:
        arrs = list(inputs)
        fn = lambda *xs: op.impl(list(xs), **params)  # noqa: E731
        return apply_fn(fn, arrs, nout=op.nout,
                        differentiable=op.differentiable, out=out)

    fn = lambda *xs: op.impl(*xs, **params)  # noqa: E731
    return apply_fn(fn, inputs, nout=op.nout,
                    differentiable=op.differentiable, out=out)
