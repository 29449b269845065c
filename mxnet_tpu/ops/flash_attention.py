"""Pallas flash attention for TPU — forward and backward kernels.

The reference framework has no fused attention kernel at all (SURVEY §5.7:
attention exists only as model-level example code), but the BERT-base
north-star config names "fused attention + AMP" — this module provides it
the TPU way: online-softmax (flash) kernels in Pallas that never
materialize the (T, T) score matrix in HBM, forward *and* backward.

Design (see /opt/skills/guides/pallas_guide.md):
- forward: grid = (B*H, Tq/bq, Tk/bk) with the key dimension innermost.
  Each program owns one query block; key/value blocks STREAM through VMEM
  via BlockSpec index maps (only one (bk, D) block resident at a time, so
  usable sequence length is not capped by K/V VMEM residency). Running
  max/denominator live in f32 scratch, which persists across the
  sequential TPU grid; the output block and the logsumexp row are written
  on the last key step. Matmuls hit the MXU with
  ``preferred_element_type=float32``.
- backward: two Pallas kernels recompute probabilities blockwise from the
  saved logsumexp (the standard flash backward):
    * dK/dV kernel, grid (B*H, Tk/bk, Tq/bq): owns one key block,
      streams query blocks, accumulates dK/dV (and the bias gradient) in
      f32 scratch using the transposed-score layout so the per-row
      logsumexp/delta enter as (1, bq) rows — no in-kernel transposes.
    * dQ kernel, grid (B*H, Tq/bq, Tk/bk): owns one query block, streams
      key blocks, accumulates dQ.
  Peak memory is O(T) end to end; tests pin both the gradients (vs
  ``jax.vjp`` of the XLA reference) and the O(T) memory scaling.
- causal masking skips the compute of fully-masked blocks (DMA still
  streams; a future refinement could prune the grid). Padding to block
  multiples is masked via the additive bias row (keys) and explicit
  position masks (queries) so padded rows contribute nothing to any
  gradient.
- off-TPU (CPU tests, virtual meshes) the same kernels run in interpret
  mode; ``attention_reference`` is the oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register

# Defaults tuned on TPU v5e (T=2048, D=64, causal fwd+bwd): small key
# blocks drown in per-grid-step overhead (128/128 ran 10x slower than
# 256/512); larger key blocks amortize it while staying well inside VMEM.
DEFAULT_BLOCK_Q = 256
DEFAULT_BLOCK_K = 512
_NEG_INF = -1e30


def _prec(dtype):
    # fp32 inputs get true-fp32 MXU passes (3-pass emulation); bf16 inputs
    # run at native MXU rate. Accumulation is always f32 via
    # preferred_element_type.
    return (jax.lax.Precision.HIGHEST if dtype == jnp.float32 else None)


def _dot(a, b, dims, precision):
    return jax.lax.dot_general(a, b, (dims, ((), ())),
                               preferred_element_type=jnp.float32,
                               precision=precision)


def attention_reference(q, k, v, bias=None, causal=False, scale=None):
    """Plain XLA attention, numerically the oracle for the kernels.

    q/k/v: (B, H, T, D); bias: (B, Tk) additive (0 keep / -inf drop).
    """
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / jnp.sqrt(d).astype(q.dtype)
    logits = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * s
    if bias is not None:
        logits = logits + bias[:, None, None, :].astype(jnp.float32)
    if causal:
        tq, tk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq)
        logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", probs, v)


# ---------------------------------------------------------------- forward --


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, causal, scale, num_k):
    qi, ki = pl.program_id(1), pl.program_id(2)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # causal: key blocks strictly above this query block's diagonal are
    # fully masked — skip their compute (their DMA still streams)
    run = (ki * block_k < (qi + 1) * block_q) if causal else True

    @pl.when(run)
    def _body():
        q, k_blk, v_blk = q_ref[...], k_ref[...], v_ref[...]
        prec = _prec(q.dtype)
        s = _dot(q, k_blk, ((1,), (1,)), prec) * scale  # (bq, bk) f32
        if bias_ref is not None:
            s = s + bias_ref[0, :][None, :]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = ki * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, _NEG_INF)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _dot(
            p.astype(v_blk.dtype) if v_blk.dtype != jnp.float32 else p,
            v_blk, ((1,), (0,)), prec)
        m_ref[...] = m_new

    @pl.when(ki == num_k - 1)
    def _finalize():
        l_safe = jnp.maximum(l_ref[...], 1e-30)
        o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)
        lse_ref[...] = m_ref[...] + jnp.log(l_safe)


def _pad_to(x, axis, mult):
    size = x.shape[axis]
    pad = (-size) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _prepare(q, k, v, bias, block_q, block_k):
    """Pad to block multiples; return flattened operands + a bias row that
    always masks padded keys (None only when nothing needs masking)."""
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    q = _pad_to(q, 2, block_q)
    k = _pad_to(k, 2, block_k)
    v = _pad_to(v, 2, block_k)
    Tq_p, Tk_p = q.shape[2], k.shape[2]
    if Tk_p != Tk or bias is not None:
        if bias is None:
            bias = jnp.zeros((B, Tk), jnp.float32)
        bias = jnp.pad(bias.astype(jnp.float32), ((0, 0), (0, Tk_p - Tk)),
                       constant_values=_NEG_INF)
    qf = q.reshape(B * H, Tq_p, D)
    kf = k.reshape(B * H, Tk_p, D)
    vf = v.reshape(B * H, Tk_p, D)
    return qf, kf, vf, bias, Tq_p, Tk_p


def _flash_forward(q, k, v, bias, causal, scale, block_q, block_k,
                   interpret, *, want_lse=False):
    B, H, Tq, D = q.shape
    s = scale if scale is not None else float(1.0 / (D ** 0.5))
    qf, kf, vf, bias_p, Tq_p, Tk_p = _prepare(q, k, v, bias, block_q,
                                              block_k)
    num_q, num_k = Tq_p // block_q, Tk_p // block_k
    grid = (B * H, num_q, num_k)

    in_specs = [
        pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, block_k, D), lambda b, i, j: (b, j, 0),
                     memory_space=pltpu.VMEM),
    ]
    args = [qf, kf, vf]
    if bias_p is not None:
        # (B, 1, Tk_p): the singleton sublane dim keeps the block shape
        # legal for TPU tiling (sublane must divide 8 or equal the array
        # dim)
        in_specs.append(pl.BlockSpec(
            (None, 1, block_k), lambda b, i, j: (b // H, 0, j),
            memory_space=pltpu.VMEM))
        args.append(bias_p[:, None, :])

        def kfn(qr, kr, vr, br, orf, lr, acc, m, l):
            _fwd_kernel(qr, kr, vr, br, orf, lr, acc, m, l, causal=causal,
                        scale=s, num_k=num_k)
    else:
        def kfn(qr, kr, vr, orf, lr, acc, m, l):
            _fwd_kernel(qr, kr, vr, None, orf, lr, acc, m, l,
                        causal=causal, scale=s, num_k=num_k)

    out, lse = pl.pallas_call(
        kfn,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((None, block_q, D), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((None, block_q, 1), lambda b, i, j: (b, i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
            jax.ShapeDtypeStruct((B * H, Tq_p, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, D), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        interpret=interpret,
    )(*args)
    out = out.reshape(B, H, Tq_p, D)[:, :, :Tq, :]
    if want_lse:
        return out, lse
    return out


# --------------------------------------------------------------- backward --
#
# Both kernels work in the transposed-score layout sT = (k @ q^T) * scale
# + bias, shape (bk, bq): the per-query-row logsumexp and delta enter as
# (1, bq) rows and the per-key bias as a (bk, 1) column, so no in-kernel
# transposes are needed. p^T = exp(sT - lse); dS^T = p^T * (v @ dO^T -
# delta); then dV += p^T @ dO, dK += scale * dS^T @ q (key-block kernel)
# and dQ += scale * (dS^T)^T-contraction @ k (query-block kernel).


def _bwd_scores(q_ref, k_ref, bias_ref, lse_ref, *, scale, causal,
                qi, ki, tq_real):
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]
    q, k_blk = q_ref[...], k_ref[...]
    sT = _dot(k_blk, q, ((1,), (1,)), _prec(q.dtype)) * scale  # (bk, bq)
    if bias_ref is not None:
        sT = sT + bias_ref[...]                            # (bk, 1) column
    pT = jnp.exp(sT - lse_ref[0, :][None, :])              # (1, bq) row
    qpos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_k, block_q), 1)
    valid = qpos < tq_real                 # padded query rows drop out
    if causal:
        kpos = ki * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (block_k, block_q), 0)
        valid = valid & (qpos >= kpos)
    return jnp.where(valid, pT, 0.0)


def _dkv_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref, bias_ref,
                dk_ref, dv_ref, dbias_ref, dk_acc, dv_acc, dbias_acc, *,
                causal, scale, num_q, tq_real):
    ki, qi = pl.program_id(1), pl.program_id(2)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if dbias_acc is not None:
            dbias_acc[...] = jnp.zeros_like(dbias_acc)

    run = (ki * block_k < (qi + 1) * block_q) if causal else True

    @pl.when(run)
    def _body():
        pT = _bwd_scores(q_ref, k_ref, bias_ref, lse_ref, scale=scale,
                         causal=causal, qi=qi, ki=ki, tq_real=tq_real)
        do, v_blk, q = do_ref[...], v_ref[...], q_ref[...]
        dt, prec = q.dtype, _prec(q.dtype)
        lp = (lambda a: a) if dt == jnp.float32 else (lambda a:
                                                      a.astype(dt))
        dv_acc[...] += _dot(lp(pT), do, ((1,), (0,)), prec)  # (bk, D)
        dpT = _dot(v_blk, do, ((1,), (1,)), prec)            # (bk, bq)
        dsT = pT * (dpT - delta_ref[0, :][None, :])
        if dbias_acc is not None:
            dbias_acc[...] += jnp.sum(dsT, axis=1, keepdims=True)
        dk_acc[...] += scale * _dot(lp(dsT), q, ((1,), (0,)), prec)

    @pl.when(qi == num_q - 1)
    def _finalize():
        dk_ref[...] = dk_acc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_acc[...].astype(dv_ref.dtype)
        if dbias_ref is not None:
            dbias_ref[...] = dbias_acc[...]


def _dq_kernel(q_ref, do_ref, k_ref, v_ref, lse_ref, delta_ref, bias_ref,
               dq_ref, dq_acc, *, causal, scale, num_k, tq_real):
    qi, ki = pl.program_id(1), pl.program_id(2)
    block_q, block_k = q_ref.shape[0], k_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    run = (ki * block_k < (qi + 1) * block_q) if causal else True

    @pl.when(run)
    def _body():
        pT = _bwd_scores(q_ref, k_ref, bias_ref, lse_ref, scale=scale,
                         causal=causal, qi=qi, ki=ki, tq_real=tq_real)
        do, v_blk, k_blk = do_ref[...], v_ref[...], k_ref[...]
        dt, prec = k_blk.dtype, _prec(k_blk.dtype)
        dpT = _dot(v_blk, do, ((1,), (1,)), prec)            # (bk, bq)
        dsT = pT * (dpT - delta_ref[0, :][None, :])
        if dt != jnp.float32:
            dsT = dsT.astype(dt)
        # contract the key dim of dsT (axis 0) with k: (bq, D)
        dq_acc[...] += scale * _dot(dsT, k_blk, ((0,), (0,)), prec)

    @pl.when(ki == num_k - 1)
    def _finalize():
        dq_ref[...] = dq_acc[...].astype(dq_ref.dtype)


def _flash_backward(q, k, v, bias, out, lse, g, causal, scale, block_q,
                    block_k, interpret):
    B, H, Tq, D = q.shape
    Tk = k.shape[2]
    # only compute the bias gradient when the caller actually passed a
    # bias; a bias row synthesized purely for key padding needs no grad
    want_dbias = bias is not None
    s = scale if scale is not None else float(1.0 / (D ** 0.5))
    qf, kf, vf, bias_p, Tq_p, Tk_p = _prepare(q, k, v, bias, block_q,
                                              block_k)
    gf = _pad_to(g, 2, block_q).reshape(B * H, Tq_p, D)
    of = _pad_to(out, 2, block_q).reshape(B * H, Tq_p, D)
    num_q, num_k = Tq_p // block_q, Tk_p // block_k

    # preprocess in plain XLA: delta = rowsum(dO * O); row layouts for the
    # kernels ((1, bq) rows, (bk, 1) bias column)
    delta = jnp.sum(gf.astype(jnp.float32) * of.astype(jnp.float32),
                    axis=-1)                               # (BH, Tq_p)
    delta_row = delta[:, None, :]                          # (BH, 1, Tq_p)
    lse_row = jnp.swapaxes(lse, 1, 2)                      # (BH, 1, Tq_p)
    bias_col = bias_p[:, :, None] if bias_p is not None else None

    def q_spec(fn):
        return pl.BlockSpec((None, block_q, D), fn, memory_space=pltpu.VMEM)

    def k_spec(fn):
        return pl.BlockSpec((None, block_k, D), fn, memory_space=pltpu.VMEM)

    def row_spec(fn):
        return pl.BlockSpec((None, 1, block_q), fn, memory_space=pltpu.VMEM)

    # ---- dK / dV (+ dbias): grid (BH, num_k, num_q), queries stream ----
    in_specs = [
        q_spec(lambda b, j, i: (b, i, 0)),
        q_spec(lambda b, j, i: (b, i, 0)),   # dO
        k_spec(lambda b, j, i: (b, j, 0)),
        k_spec(lambda b, j, i: (b, j, 0)),   # V
        row_spec(lambda b, j, i: (b, 0, i)),  # lse
        row_spec(lambda b, j, i: (b, 0, i)),  # delta
    ]
    args = [qf, gf, kf, vf, lse_row, delta_row]
    scratch = [pltpu.VMEM((block_k, D), jnp.float32),
               pltpu.VMEM((block_k, D), jnp.float32)]
    out_specs = [k_spec(lambda b, j, i: (b, j, 0)),
                 k_spec(lambda b, j, i: (b, j, 0))]
    out_shape = [jax.ShapeDtypeStruct((B * H, Tk_p, D), q.dtype),
                 jax.ShapeDtypeStruct((B * H, Tk_p, D), q.dtype)]
    if bias_p is not None:
        in_specs.append(pl.BlockSpec((None, block_k, 1),
                                     lambda b, j, i: (b // H, j, 0),
                                     memory_space=pltpu.VMEM))
        args.append(bias_col)
    if want_dbias:
        scratch.append(pltpu.VMEM((block_k, 1), jnp.float32))
        out_specs.append(pl.BlockSpec((None, block_k, 1),
                                      lambda b, j, i: (b, j, 0),
                                      memory_space=pltpu.VMEM))
        out_shape.append(
            jax.ShapeDtypeStruct((B * H, Tk_p, 1), jnp.float32))

        def dkv(qr, dor, kr, vr, lr, dr, br, dkr, dvr, dbr, dka, dva, dba):
            _dkv_kernel(qr, dor, kr, vr, lr, dr, br, dkr, dvr, dbr,
                        dka, dva, dba, causal=causal, scale=s,
                        num_q=num_q, tq_real=Tq)
    elif bias_p is not None:
        # bias row needed to recompute probabilities (key padding), but
        # its gradient is not
        def dkv(qr, dor, kr, vr, lr, dr, br, dkr, dvr, dka, dva):
            _dkv_kernel(qr, dor, kr, vr, lr, dr, br, dkr, dvr, None,
                        dka, dva, None, causal=causal, scale=s,
                        num_q=num_q, tq_real=Tq)
    else:
        def dkv(qr, dor, kr, vr, lr, dr, dkr, dvr, dka, dva):
            _dkv_kernel(qr, dor, kr, vr, lr, dr, None, dkr, dvr, None,
                        dka, dva, None, causal=causal, scale=s,
                        num_q=num_q, tq_real=Tq)

    res = pl.pallas_call(
        dkv, grid=(B * H, num_k, num_q), in_specs=in_specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=scratch, interpret=interpret)(*args)
    dk, dv = res[0], res[1]
    dbias = None
    if want_dbias:
        # per-(b,h,k) bias grads -> sum heads, drop key padding
        dbias = res[2].reshape(B, H, Tk_p)[:, :, :Tk].sum(axis=1)
        dbias = dbias.astype(bias.dtype)

    # ---- dQ: grid (BH, num_q, num_k), keys stream ----------------------
    in_specs = [
        q_spec(lambda b, i, j: (b, i, 0)),
        q_spec(lambda b, i, j: (b, i, 0)),   # dO
        k_spec(lambda b, i, j: (b, j, 0)),
        k_spec(lambda b, i, j: (b, j, 0)),   # V
        row_spec(lambda b, i, j: (b, 0, i)),  # lse
        row_spec(lambda b, i, j: (b, 0, i)),  # delta
    ]
    args = [qf, gf, kf, vf, lse_row, delta_row]
    if bias_p is not None:
        in_specs.append(pl.BlockSpec((None, block_k, 1),
                                     lambda b, i, j: (b // H, j, 0),
                                     memory_space=pltpu.VMEM))
        args.append(bias_col)

        def dqk(qr, dor, kr, vr, lr, dr, br, dqr, dqa):
            _dq_kernel(qr, dor, kr, vr, lr, dr, br, dqr, dqa,
                       causal=causal, scale=s, num_k=num_k, tq_real=Tq)
    else:
        def dqk(qr, dor, kr, vr, lr, dr, dqr, dqa):
            _dq_kernel(qr, dor, kr, vr, lr, dr, None, dqr, dqa,
                       causal=causal, scale=s, num_k=num_k, tq_real=Tq)

    dq = pl.pallas_call(
        dqk, grid=(B * H, num_q, num_k), in_specs=in_specs,
        out_specs=q_spec(lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Tq_p, D), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, D), jnp.float32)],
        interpret=interpret)(*args)

    dq = dq.reshape(B, H, Tq_p, D)[:, :, :Tq, :]
    dk = dk.reshape(B, H, Tk_p, D)[:, :, :Tk, :]
    dv = dv.reshape(B, H, Tk_p, D)[:, :, :Tk, :]
    return dq, dk, dv, dbias


def _on_tpu():
    return jax.default_backend() == "tpu"


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_attention(q, k, v, causal, scale, block_q, block_k, interpret):
    return _flash_forward(q, k, v, None, causal, scale, block_q, block_k,
                          interpret)


def _fa_fwd(q, k, v, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, None, causal, scale, block_q,
                              block_k, interpret, want_lse=True)
    return out, (q, k, v, out, lse)


def _fa_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, out, lse = res
    dq, dk, dv, _ = _flash_backward(q, k, v, None, out, lse, g, causal,
                                    scale, block_q, block_k, interpret)
    return dq, dk, dv


_flash_attention.defvjp(_fa_fwd, _fa_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash_attention_bias(q, k, v, bias, causal, scale, block_q, block_k,
                          interpret):
    return _flash_forward(q, k, v, bias, causal, scale, block_q, block_k,
                          interpret)


def _fab_fwd(q, k, v, bias, causal, scale, block_q, block_k, interpret):
    out, lse = _flash_forward(q, k, v, bias, causal, scale, block_q,
                              block_k, interpret, want_lse=True)
    return out, (q, k, v, bias, out, lse)


def _fab_bwd(causal, scale, block_q, block_k, interpret, res, g):
    q, k, v, bias, out, lse = res
    dq, dk, dv, dbias = _flash_backward(q, k, v, bias, out, lse, g,
                                        causal, scale, block_q, block_k,
                                        interpret)
    return dq, dk, dv, dbias


_flash_attention_bias.defvjp(_fab_fwd, _fab_bwd)


def flash_attention(q, k, v, bias=None, causal=False, scale=None,
                    block_q=DEFAULT_BLOCK_Q, block_k=DEFAULT_BLOCK_K,
                    interpret=None):
    """Flash attention entry point. q/k/v: (B, H, T, D); bias: (B, Tk)
    additive row (0 = keep, large-negative = drop)."""
    if interpret is None:
        interpret = not _on_tpu()
    block_q = min(block_q, max(q.shape[2], 8))
    block_k = min(block_k, max(k.shape[2], 8))
    if bias is None:
        return _flash_attention(q, k, v, causal, scale, block_q, block_k,
                                interpret)
    return _flash_attention_bias(q, k, v, bias, causal, scale, block_q,
                                 block_k, interpret)


@register("scaled_dot_product_attention")
def _sdpa_op(q, k, v, bias=None, *, causal=False, scale=None,
             flash=True):
    """Registered attention op: flash kernel on TPU, interpret/XLA
    reference elsewhere. Inputs (B, H, T, D)."""
    if not flash:
        return attention_reference(q, k, v, bias, causal, scale)
    return flash_attention(q, k, v, bias=bias, causal=causal, scale=scale)
