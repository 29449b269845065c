"""INT8 quantization operators.

Reference: src/operator/quantization/ (quantize.cc, quantize_v2.cc,
dequantize.cc, requantize.cc, quantized_conv.cc,
quantized_fully_connected.cc). Semantics kept:

- int8 is SYMMETRIC: scale = 127 / threshold with threshold =
  max(|min|, |max|); value v -> round(v * scale) in [-127, 127].
- uint8 is AFFINE over [min, max] with 255 steps.
- quantized_conv / quantized_fully_connected accumulate int8 x int8 into
  int32 on the MXU (lax preferred_element_type=int32) and return the
  int32 accumulator plus its float range, exactly like the reference's
  kernels; requantize folds int32 -> int8 given calibrated ranges.

Every op returns (out, out_min, out_max) like the reference so the
range bookkeeping composes.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl

from .flash_attention import _on_tpu
from .registry import _REGISTRY, Operator, alias, register


def _reg(name, fn, **kw):
    _REGISTRY[name] = Operator(name, fn, **kw)


def _thresh(mn, mx):
    return jnp.maximum(jnp.abs(mn), jnp.abs(mx))


def _quantize(data, min_range, max_range, out_type="int8"):
    """Reference: quantize.cc (_contrib_quantize)."""
    mn = jnp.asarray(min_range).reshape(())
    mx = jnp.asarray(max_range).reshape(())
    if out_type == "uint8":
        scale = 255.0 / jnp.maximum(mx - mn, 1e-30)
        q = jnp.clip(jnp.round((data - mn) * scale), 0, 255)\
            .astype(jnp.uint8)
        return q, mn, mx
    t = _thresh(mn, mx)
    scale = 127.0 / jnp.maximum(t, 1e-30)
    q = jnp.clip(jnp.round(data * scale), -127, 127).astype(jnp.int8)
    return q, -t, t


_reg("_contrib_quantize", _quantize, nout=3, differentiable=False)


def _quantize_v2(data, min_calib_range=None, max_calib_range=None,
                 out_type="int8"):
    """Reference: quantize_v2.cc — computes the range from the data when
    no calibrated range is given."""
    if min_calib_range is None or max_calib_range is None:
        mn = jnp.min(data)
        mx = jnp.max(data)
    else:
        mn = jnp.asarray(min_calib_range, jnp.float32)
        mx = jnp.asarray(max_calib_range, jnp.float32)
    return _quantize(data, mn, mx, out_type=out_type)


_reg("_contrib_quantize_v2", _quantize_v2, nout=3, differentiable=False)


def _dequantize(qdata, min_range, max_range, out_type="float32"):
    """Reference: dequantize.cc."""
    mn = jnp.asarray(min_range).reshape(())
    mx = jnp.asarray(max_range).reshape(())
    if qdata.dtype == jnp.uint8:
        scale = jnp.maximum(mx - mn, 1e-30) / 255.0
        return qdata.astype(jnp.float32) * scale + mn
    t = _thresh(mn, mx)
    return qdata.astype(jnp.float32) * (t / 127.0)


_reg("_contrib_dequantize", _dequantize, differentiable=False)


def _requantize(qdata, min_range, max_range, min_calib_range=None,
                max_calib_range=None):
    """int32 accumulator -> int8 (reference: requantize.cc). The int32
    range [min_range, max_range] is the product-range bookkeeping from
    the quantized op; the calibrated range decides the int8 scale."""
    real = _dequantize(qdata.astype(jnp.float32), min_range, max_range) \
        if qdata.dtype != jnp.int32 else \
        qdata.astype(jnp.float32) * (_thresh(
            jnp.asarray(min_range).reshape(()),
            jnp.asarray(max_range).reshape(())) / (127.0 * 127.0))
    if min_calib_range is None:
        mn, mx = jnp.min(real), jnp.max(real)
    else:
        mn = jnp.asarray(min_calib_range, jnp.float32)
        mx = jnp.asarray(max_calib_range, jnp.float32)
    return _quantize(real, mn, mx)


_reg("_contrib_requantize", _requantize, nout=3, differentiable=False)


def _quantized_fully_connected(qx, qw, x_scale=1.0, w_scale=1.0,
                               num_hidden=0):
    """int8 x int8 -> int32 dense (reference:
    quantized_fully_connected.cc). Returns the fp32 result scaled back:
    out = (qx @ qw.T) * (x_scale * w_scale); w_scale may be a per-row
    (per-output-channel) vector — finer than the reference's per-tensor
    scale."""
    acc = lax.dot_general(qx, qw, (((qx.ndim - 1,), (1,)), ((), ())),
                          preferred_element_type=jnp.int32)
    scale = jnp.asarray(x_scale) * jnp.asarray(w_scale)
    return acc.astype(jnp.float32) * scale


_reg("_contrib_quantized_fully_connected", _quantized_fully_connected,
     differentiable=False)


def _quantized_conv(qx, qw, kernel=None, stride=None, pad=None,
                    num_filter=0, layout="NHWC", x_scale=1.0, w_scale=1.0):
    """int8 conv with int32 accumulation (reference: quantized_conv.cc);
    NHWC/HWIO only (the TPU-native layout)."""
    nd = qx.ndim - 2
    stride = stride or (1,) * nd
    pad = pad or (0,) * nd
    acc = lax.conv_general_dilated(
        qx, qw, window_strides=tuple(stride),
        padding=[(p, p) for p in pad],
        dimension_numbers=("NHWC", "HWIO", "NHWC"),
        preferred_element_type=jnp.int32)
    scale = jnp.asarray(x_scale) * jnp.asarray(w_scale)
    return acc.astype(jnp.float32) * scale


_reg("_contrib_quantized_conv", _quantized_conv, differentiable=False)


# ------------------------------------------------ quantized op family --
# reference: src/operator/quantization/quantized_activation.cc,
# quantized_pooling.cc, quantized_flatten.cc, quantized_concat.cc,
# quantized_elemwise_add.cc / _mul.cc, quantized_batch_norm.cc,
# quantized_indexing_op.cc, calibrate.cc. Every op keeps the
# (values, min, max) triple contract.

def _quantized_act(data, min_data, max_data, act_type="relu"):
    assert act_type == "relu", "int8 activation supports relu"
    zero = jnp.zeros((), data.dtype)
    return jnp.maximum(data, zero), jnp.maximum(
        jnp.asarray(min_data).reshape(()), 0.0), \
        jnp.asarray(max_data).reshape(())


_reg("_contrib_quantized_act", _quantized_act, nout=3,
     differentiable=False)


def _quantized_pooling(data, min_data, max_data, kernel=None, stride=None,
                       pad=None, pool_type="max", global_pool=False,
                       layout="NCHW"):
    from .nn import _pooling
    out = _pooling(data.astype(jnp.float32), kernel=kernel, stride=stride,
                   pad=pad, pool_type=pool_type, global_pool=global_pool,
                   layout=layout)
    if pool_type == "max":
        out = out.astype(data.dtype)      # exact for max
    else:
        out = jnp.round(out).astype(data.dtype)
    return out, jnp.asarray(min_data).reshape(()), \
        jnp.asarray(max_data).reshape(())


_reg("_contrib_quantized_pooling", _quantized_pooling, nout=3,
     differentiable=False)


def _quantized_flatten(data, min_data, max_data):
    return data.reshape(data.shape[0], -1), \
        jnp.asarray(min_data).reshape(()), \
        jnp.asarray(max_data).reshape(())


_reg("_contrib_quantized_flatten", _quantized_flatten, nout=3,
     differentiable=False)


def _quantized_concat(arrays, num_args=1, dim=1):
    """Inputs: data0..dataN, min0..maxN interleaved per the reference
    (data..., min..., max...). Requantizes every part to the widest
    range, then concatenates."""
    n = len(arrays) // 3
    datas, mins, maxs = arrays[:n], arrays[n:2 * n], arrays[2 * n:]
    ts = [jnp.maximum(jnp.abs(mn.reshape(())), jnp.abs(mx.reshape(())))
          for mn, mx in zip(mins, maxs)]
    t_out = ts[0]
    for t in ts[1:]:
        t_out = jnp.maximum(t_out, t)
    parts = []
    for d, t in zip(datas, ts):
        real = d.astype(jnp.float32) * (t / 127.0)
        parts.append(jnp.clip(jnp.round(real / (t_out / 127.0)),
                              -127, 127).astype(jnp.int8))
    return jnp.concatenate(parts, axis=int(dim)), -t_out, t_out


_REGISTRY["_contrib_quantized_concat"] = Operator(
    "_contrib_quantized_concat", _quantized_concat, nout=3,
    variadic=True, differentiable=False)


def _quantized_elemwise(op):
    def impl(lhs, rhs, lhs_min, lhs_max, rhs_min, rhs_max):
        tl = jnp.maximum(jnp.abs(lhs_min.reshape(())),
                         jnp.abs(lhs_max.reshape(())))
        tr = jnp.maximum(jnp.abs(rhs_min.reshape(())),
                         jnp.abs(rhs_max.reshape(())))
        a = lhs.astype(jnp.float32) * (tl / 127.0)
        b = rhs.astype(jnp.float32) * (tr / 127.0)
        real = op(a, b)
        t = jnp.maximum(jnp.max(jnp.abs(real)), 1e-30)
        q = jnp.clip(jnp.round(real / (t / 127.0)), -127, 127)\
            .astype(jnp.int8)
        return q, -t, t
    return impl


_reg("_contrib_quantized_elemwise_add",
     _quantized_elemwise(lambda a, b: a + b), nout=3,
     differentiable=False)
_reg("_contrib_quantized_elemwise_mul",
     _quantized_elemwise(lambda a, b: a * b), nout=3,
     differentiable=False)


def _quantized_batch_norm(data, gamma, beta, moving_mean, moving_var,
                          min_data=None, max_data=None, eps=1e-3,
                          min_calib_range=None, max_calib_range=None,
                          **kw):
    t_in = jnp.maximum(jnp.abs(min_data.reshape(())),
                       jnp.abs(max_data.reshape(())))
    x = data.astype(jnp.float32) * (t_in / 127.0)
    inv = 1.0 / jnp.sqrt(moving_var + eps)
    shape = (1, -1) + (1,) * (data.ndim - 2)
    out = (x - moving_mean.reshape(shape)) * \
        (inv * gamma).reshape(shape) + beta.reshape(shape)
    if min_calib_range is not None:
        t = jnp.maximum(abs(float(min_calib_range)),
                        abs(float(max_calib_range)))
    else:
        t = jnp.maximum(jnp.max(jnp.abs(out)), 1e-30)
    q = jnp.clip(jnp.round(out / (t / 127.0)), -127, 127)\
        .astype(jnp.int8)
    return q, -t, t


_reg("_contrib_quantized_batch_norm", _quantized_batch_norm, nout=3,
     differentiable=False)


def _quantized_embedding(data, weight, min_weight, max_weight,
                         input_dim=0, output_dim=0, dtype="float32",
                         **kw):
    out = jnp.take(weight, data.astype(jnp.int32), axis=0)
    return out, jnp.asarray(min_weight).reshape(()), \
        jnp.asarray(max_weight).reshape(())


_reg("_contrib_quantized_embedding", _quantized_embedding, nout=3,
     differentiable=False)


# ------------------------------------- weight-only serving matmuls --
# ISSUE 20: the serving stack's per-output-channel WEIGHT-ONLY
# quantization (activations stay f32; weights are int8/fp8-e4m3 with
# an f32 scale per output column). Unlike the reference's int8×int8
# ops above, the contraction here runs in f32 on the MXU with the
# dequant FUSED into the matmul — ``(x @ W_q.astype(f32)) * s`` — so
# the f32 weight matrix never materializes in HBM. Gated exactly like
# ragged_attention: plain-jnp reference off-TPU (and as the oracle),
# Pallas kernel on TPU with the int8/fp8 tile dequantized in VMEM.


def quantized_matmul_reference(x, qw, w_scale):
    """Oracle: ``x [T, K] f32 @ qw [K, N] int8/fp8`` with per-output-
    channel ``w_scale [N]`` f32. The scale factors out of each output
    column's contraction, so scaling AFTER the accumulation is the
    same quantity with one multiply per output instead of per
    weight."""
    return (x @ qw.astype(jnp.float32)) * w_scale


def _wq_matmul_kernel(x_ref, qw_ref, s_ref, o_ref):
    # dequant fused in VMEM: the quantized tile and its channel scales
    # are widened to f32 right before the MXU contraction — HBM only
    # ever holds the 1-byte weights
    w = qw_ref[...].astype(jnp.float32) * s_ref[...]
    o_ref[...] = x_ref[...].astype(jnp.float32) @ w


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _wq_matmul_pallas(x, qw, w_scale, block_t, block_n, interpret):
    T, K = x.shape
    N = qw.shape[1]
    grid = (pl.cdiv(T, block_t), pl.cdiv(N, block_n))
    return pl.pallas_call(
        _wq_matmul_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((block_t, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, block_n), lambda i, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_t, block_n), lambda i, j: (i, j)),
        # inside a shard_map body the output varies over the mesh axes
        # either operand does (none outside one)
        out_shape=jax.ShapeDtypeStruct(
            (T, N), jnp.float32,
            vma=jax.typeof(x).vma | jax.typeof(qw).vma),
        interpret=interpret,
    )(x, qw, w_scale.reshape(1, N))


def quantized_matmul(x, qw, w_scale, use_pallas=None, interpret=None,
                     block_t=None, block_n=None):
    """Per-output-channel weight-only quantized matmul:
    ``out[t, c] = (sum_k x[t, k] * qw[k, c]) * w_scale[c]``.

    x: f32 ``[T, K]``; qw: int8 or fp8-e4m3 ``[K, N]``; w_scale: f32
    ``[N]`` (``serving.llm.quant.quantize_leaf`` scales). Gating as in
    :mod:`.ragged_attention`: ``use_pallas=None`` picks the Pallas
    kernel on TPU and the jnp reference elsewhere; ``interpret`` runs
    the kernel in interpret mode for off-TPU parity tests."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return quantized_matmul_reference(x, qw, w_scale)
    if interpret is None:
        interpret = not _on_tpu()
    T, K = x.shape
    N = qw.shape[1]
    bt = int(block_t) if block_t else min(T, 256)
    bn = int(block_n) if block_n else min(N, 256)
    return _wq_matmul_pallas(x, qw, w_scale, bt, bn, bool(interpret))


_reg("_contrib_quantized_matmul", quantized_matmul,
     differentiable=False)
alias("quantized_matmul", "_contrib_quantized_matmul")


def _calibrate_entropy(hist, hist_edges, num_quantized_bins=255):
    """KL-optimal threshold from a histogram (reference: calibrate.cc
    _contrib_calibrate_entropy); returns (min, max) calib range."""
    import numpy as _onp
    from ..contrib.quantization import optimal_threshold
    t = optimal_threshold(_onp.asarray(hist), _onp.asarray(hist_edges),
                          num_quantized_bins=int(num_quantized_bins))
    return jnp.asarray(-t, jnp.float32), jnp.asarray(t, jnp.float32)


_reg("_contrib_calibrate_entropy", _calibrate_entropy, nout=2,
     host_op=True, differentiable=False)
