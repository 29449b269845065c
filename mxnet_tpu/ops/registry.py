"""Operator registry.

TPU-native replacement for the reference's NNVM op registry
(reference: include/mxnet/op_attr_types.h, src/operator/*). Where the
reference registers per-op FCompute<cpu>/FCompute<gpu> kernels plus separate
FInferShape/FInferType/FGradient attributes, here each op is ONE pure JAX
function: XLA provides the device kernels, ``jax.eval_shape`` provides
shape/type inference for free (used by Symbol), and ``jax.vjp`` provides the
gradient. The registry keys the autogenerated ``nd``/``np`` Python namespaces
the same way the reference's ``python/mxnet/ndarray/register.py`` codegen
keys off the C op registry.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Sequence

__all__ = ["Operator", "register", "get", "list_ops", "alias"]


class Operator:
    """A registered op.

    Attributes
    ----------
    name : canonical op name (matches the reference op name where one exists)
    impl : pure function ``impl(*jnp_arrays, **static_params) -> array | tuple``
    nout : number of outputs (int); tuple outputs must match
    differentiable : if False the op never lands on the autograd tape
        (integer/comparison outputs — reference marks these with
        MakeZeroGradNodes)
    variadic : if True the wrapper collects leading positional arrays into a
        single list argument (reference ops like Concat with num_args)
    mutates : index of the input that the op updates in place (optimizer
        update ops — reference kWriteInplace); the wrapper writes the first
        output back into that input's buffer
    """

    __slots__ = ("name", "impl", "nout", "differentiable", "variadic",
                 "mutates", "needs_rng", "needs_train", "host_op", "doc")

    def __init__(self, name: str, impl: Callable, nout: int = 1,
                 differentiable: bool = True, variadic: bool = False,
                 mutates: Optional[Sequence[int]] = None,
                 needs_rng: bool = False, needs_train: bool = False,
                 host_op: bool = False):
        self.name = name
        self.impl = impl
        self.nout = nout
        self.differentiable = differentiable
        self.variadic = variadic
        self.mutates = tuple(mutates) if mutates else ()
        self.needs_rng = needs_rng
        self.needs_train = needs_train
        # host_op: runs python host callbacks (jax.pure_callback)
        self.host_op = host_op
        self.doc = impl.__doc__

    def __repr__(self):
        return f"<Operator {self.name}>"


_REGISTRY: Dict[str, Operator] = {}


def register(name: Optional[str] = None, nout: int = 1,
             differentiable: bool = True, variadic: bool = False,
             mutates: Optional[Sequence[int]] = None,
             needs_rng: bool = False, needs_train: bool = False):
    """Decorator registering a pure jax function as a framework op."""

    def deco(fn: Callable) -> Callable:
        opname = name or fn.__name__
        if opname in _REGISTRY:
            raise ValueError(f"op {opname!r} already registered")
        _REGISTRY[opname] = Operator(opname, fn, nout=nout,
                                     differentiable=differentiable,
                                     variadic=variadic, mutates=mutates,
                                     needs_rng=needs_rng,
                                     needs_train=needs_train)
        return fn

    return deco


def alias(new: str, existing: str):
    """Register an alias name for an existing op (reference:
    NNVM_REGISTER_OP(...).add_alias)."""
    op = _REGISTRY[existing]
    if new not in _REGISTRY:
        _REGISTRY[new] = op


def get(name: str) -> Operator:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"operator {name!r} is not registered; see mxnet_tpu.ops") from None


def list_ops():
    return sorted(_REGISTRY)
