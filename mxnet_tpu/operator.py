"""mx.operator — Python custom operators (CustomOp / CustomOpProp).

Reference: python/mxnet/operator.py (CustomOp :71, CustomOpProp :524,
register :611) backed by src/operator/custom/custom.cc:70-119, which
runs user Python callbacks on a dedicated thread pool wired into the
dependency engine.

TPU-native design: the user-visible contract is identical — subclass
CustomOp (forward/backward with ``self.assign``), describe it with a
CustomOpProp, ``@register("name")``, invoke as ``nd.Custom(*data,
op_type="name")`` — but execution goes through ``jax.pure_callback``:
under ``jit`` the callback becomes a host call embedded in the XLA
program (the moral equivalent of the reference's engine-integrated
callback), and eagerly it just runs. The gradient is a ``jax.custom_vjp``
whose backward is a second pure_callback into the user's ``backward``.

Semantics notes (documented deviations):
- callbacks must be PURE functions of their inputs (no hidden state
  carried across calls) — jit may cache, reorder, or re-execute them;
- ``forward`` runs again in the backward callback to provide
  ``out_data`` (the reference keeps out_data alive between passes; a
  functional runtime recomputes instead);
- aux states are not supported (use regular params).
"""
from __future__ import annotations

import functools

import numpy as _np

from .base import dtype_np

__all__ = ["CustomOp", "CustomOpProp", "register", "get_all_registered"]

_CUSTOM_REGISTRY = {}


class CustomOp:
    """Base class for user ops (reference: operator.py:71)."""

    def forward(self, is_train, req, in_data, out_data, aux):
        raise NotImplementedError

    def backward(self, req, out_grad, in_data, out_data, in_grad, aux):
        raise NotImplementedError

    def assign(self, dst, req, src):
        """Write ``src`` into ``dst`` honoring the grad request
        (reference: operator.py:129)."""
        if req == "null":
            return
        if req in ("write", "inplace"):
            dst[:] = src
        elif req == "add":
            dst[:] = dst + src
        else:
            raise ValueError(f"unknown req {req!r}")


class CustomOpProp:
    """Describes a custom op (reference: operator.py:524)."""

    def __init__(self, need_top_grad=True):
        self.need_top_grad_ = need_top_grad

    def list_arguments(self):
        return ["data"]

    def list_outputs(self):
        return ["output"]

    def list_auxiliary_states(self):
        return []

    def infer_shape(self, in_shape):
        return in_shape, [in_shape[0]] * len(self.list_outputs()), []

    def infer_type(self, in_type):
        return (in_type, [in_type[0]] * len(self.list_outputs()),
                [in_type[0]] * len(self.list_auxiliary_states()))

    def declare_backward_dependency(self, out_grad, in_data, out_data):
        return out_grad + in_data + out_data

    def create_operator(self, ctx, in_shapes, in_dtypes):
        return CustomOp()


def register(reg_name):
    """Register a CustomOpProp subclass under ``op_type=reg_name``
    (reference: operator.py:611)."""

    def deco(prop_cls):
        _CUSTOM_REGISTRY[reg_name] = prop_cls
        return prop_cls

    return deco


def get_all_registered():
    return dict(_CUSTOM_REGISTRY)


class _HostBuf:
    """Numpy-backed buffer with the NDArray slice-assign surface the
    reference hands to CustomOp callbacks."""

    def __init__(self, arr):
        self._buf = _np.asarray(arr)

    def __getitem__(self, k):
        return self._buf[k]

    def __setitem__(self, k, v):
        self._buf[k] = _np.asarray(v, dtype=self._buf.dtype)

    @property
    def shape(self):
        return self._buf.shape

    @property
    def dtype(self):
        return self._buf.dtype

    def asnumpy(self):
        return self._buf

    def __array__(self, dtype=None):
        return self._buf if dtype is None else self._buf.astype(dtype)

    # arithmetic passthroughs so `dst + src` works inside assign('add')
    def __add__(self, other):
        return self._buf + _np.asarray(other)

    __radd__ = __add__


def _resolve(op_type, kwargs, in_shapes, in_dtypes):
    if op_type not in _CUSTOM_REGISTRY:
        raise ValueError(
            f"custom op {op_type!r} is not registered; known: "
            f"{sorted(_CUSTOM_REGISTRY)}")
    prop = _CUSTOM_REGISTRY[op_type](**kwargs)
    _, out_shapes, _ = prop.infer_shape([list(s) for s in in_shapes])
    in_t, out_t, _ = prop.infer_type(list(in_dtypes))
    op = prop.create_operator(None, in_shapes, in_dtypes)
    return prop, op, [tuple(s) for s in out_shapes], out_t


def custom(*inputs, op_type, **kwargs):
    """Functional entry: pure-jax implementation of nd.Custom (inputs are
    jnp arrays / tracers)."""
    import jax
    import jax.numpy as jnp

    str_kwargs = {k: v for k, v in kwargs.items() if k != "_training"}
    is_train = bool(kwargs.get("_training", False))
    in_shapes = [tuple(x.shape) for x in inputs]
    in_dtypes = [x.dtype for x in inputs]
    prop, op, out_shapes, out_dtypes = _resolve(
        op_type, str_kwargs, in_shapes, in_dtypes)
    n_out = len(out_shapes)
    out_sdt = [jax.ShapeDtypeStruct(s, dtype_np(d))
               for s, d in zip(out_shapes, out_dtypes)]
    in_sdt = [jax.ShapeDtypeStruct(s, dtype_np(d))
              for s, d in zip(in_shapes, in_dtypes)]

    def host_forward(*xs):
        ins = [_HostBuf(_np.asarray(x)) for x in xs]
        outs = [_HostBuf(_np.zeros(s.shape, s.dtype)) for s in out_sdt]
        op.forward(is_train, ["write"] * n_out, ins, outs, [])
        res = tuple(o._buf for o in outs)
        return res[0] if n_out == 1 else res

    def host_backward(*args):
        xs, gs = args[:len(inputs)], args[len(inputs):]
        ins = [_HostBuf(_np.asarray(x)) for x in xs]
        outs = [_HostBuf(_np.zeros(s.shape, s.dtype)) for s in out_sdt]
        op.forward(True, ["write"] * n_out, ins, outs, [])
        ograds = [_HostBuf(_np.asarray(g)) for g in gs]
        igrads = [_HostBuf(_np.zeros(s.shape, s.dtype)) for s in in_sdt]
        op.backward(["write"] * len(ins), ograds, ins, outs, igrads, [])
        res = tuple(g._buf for g in igrads)
        return res[0] if len(inputs) == 1 else res

    @jax.custom_vjp
    def run(*xs):
        out = jax.pure_callback(
            host_forward,
            out_sdt[0] if n_out == 1 else tuple(out_sdt), *xs)
        return out

    def run_fwd(*xs):
        return run(*xs), xs

    def run_bwd(res, g):
        gs = (g,) if n_out == 1 else tuple(g)
        grads = jax.pure_callback(
            host_backward,
            in_sdt[0] if len(inputs) == 1 else tuple(in_sdt),
            *res, *gs)
        return (grads,) if len(inputs) == 1 else tuple(grads)

    run.defvjp(run_fwd, run_bwd)
    return run(*inputs)


def _custom_op_entry(data, op_type=None, **kwargs):
    """Registered as op 'Custom' (variadic): nd.Custom(*data,
    op_type="name", **op_kwargs) — the reference invocation surface
    (python/mxnet/operator.py register_custom_op / nd.Custom)."""
    if op_type is None:
        raise ValueError("nd.Custom requires op_type=")
    return custom(*data, op_type=op_type, **kwargs)


def _register_framework_op():
    from .ops.registry import _REGISTRY, Operator
    _REGISTRY["Custom"] = Operator("Custom", _custom_op_entry,
                                   variadic=True, needs_train=True,
                                   host_op=True)


_register_framework_op()
