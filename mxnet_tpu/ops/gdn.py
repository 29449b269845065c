"""The gated delta rule (GatedDeltaNet linear attention) over the packed
serving step.

A GatedDeltaNet layer leaves no page a token. Each sequence owns one fixed
STATE a layer, ``S [Hv, Dk, Dv]`` (value heads x key size x value size),
that every token of the sequence overwrites, head by head:

    S_t = exp(g_t) S_{t-1}
    S_t = S_t + k_t (beta_t (v_t - S_t^T k_t))^T
    o_t = S_t^T q_t

with ``q_t``, ``k_t [Dk]`` (L2-normed; ``q`` also scaled), ``v_t [Dv]``,
``g_t <= 0`` the log of the decay and ``beta_t`` in ``(0, 1)`` the
write's strength. Unlike the state-space update of :mod:`.ssd` (a decay
and a rank-1 write), the write first takes away what the state already
predicts for the key, ``S^T k``: the delta rule. ``Hk`` key heads serve
``Hv`` value heads, key head ``j // (Hv / Hk)`` value head ``j``.

The states of a model live in ONE pool indexed by layer and by the
sequence's SLOT, the row of ``block_tables`` that ``seq_ids`` names: ``[L,
S, Hv, Dk, Dv]`` float32. A step hands over the packed batch of ``T``
tokens, as the flat attention kernels take it. The valid tokens of one
slot are a RUN, side by side and in position order (one token in decode,
a chunk in prefill). A run whose first token sits at position 0 starts
from a state of zeros, whatever the slot held before, and a token with
``valid == 0`` touches no state.

:func:`gdn_state_step` is the Pallas kernel on a TPU (``use_pallas=True``
elsewhere: interpret mode) and :func:`gdn_state_step_reference`, the
literal per-token recurrence in ``jnp``, off it; the tests pin one
against the other. Both take the WHOLE pool with ``layer`` and return it
updated; the kernel aliases it in place, reading and writing each run's
state once a step. The chunked (WY) form, which turns a long run's
tokens into matrix products, is not written: a run is advanced token by
token.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register
from .flash_attention import _on_tpu
from .ssd import slot_runs

__all__ = ["gdn_state_step", "gdn_state_step_reference"]

#: bytes of VMEM that Mosaic is handed for the state kernel
VMEM_LIMIT = 64 * 1024 * 1024
#: of which one grid step's blocks may take this much, each counted twice
VMEM_BUDGET = 48 * 1024 * 1024
#: the most value heads one grid step holds (an unrolled loop each)
MAX_HEAD_BLOCK = 16


def _delta_write(s, k, v, beta):
    """The delta rule's write into a decayed state ``s [..., Dk, Dv]``:
    ``k [..., Dk, 1]`` (a column), ``v [..., 1, Dv]``, ``beta [..., 1,
    1]``; what the state predicts for the key, ``S^T k``, is taken from
    the value first. One function for the kernel and the reference."""
    return s + k * (beta * (v - jnp.sum(s * k, axis=-2, keepdims=True)))


def _per_value_head(a, hv):
    """Key-head rows ``[..., Hk, D]`` repeated for the value heads that
    each serves: ``[..., Hv, D]``."""
    return jnp.repeat(a, hv // a.shape[-2], axis=-2)


def gdn_state_step_reference(q, k, v, g, beta, state, seq_ids, positions,
                             valid, layer):
    """The recurrence token by token, in batch order: the state of the
    token's slot is read (zeros at position 0), updated and written
    back unless the token is padding. ``q``/``k [T, Hk, Dk]``, ``v [T,
    Hv, Dv]``, ``g``/``beta [T, Hv]``, ``state [L, S, Hv, Dk, Dv]`` (all
    float32); returns ``(o [T, Hv, Dv], state)``."""
    hv = v.shape[1]
    qh, kh = _per_value_head(q, hv), _per_value_head(k, hv)

    def token(pool, t):
        slot = seq_ids[t]
        s0 = jnp.where(positions[t] == 0, 0.0, pool[layer, slot])
        s = _delta_write(jnp.exp(g[t])[:, None, None] * s0,
                         kh[t][:, :, None], v[t][:, None, :],
                         beta[t][:, None, None])
        o = jnp.einsum("hk,hkv->hv", qh[t], s,
                       precision=jax.lax.Precision.HIGHEST)
        keep = valid[t].astype(bool)
        return pool.at[layer, slot].set(
            jnp.where(keep, s, pool[layer, slot])), o

    state, o = jax.lax.scan(token, state, jnp.arange(q.shape[0]))
    return o, state


def _head_block(T, hv, hk, dk, dv):
    """Value heads a grid step holds: the most, of whole key-head groups
    that divide ``hv``, whose blocks (each held twice) fit
    ``VMEM_BUDGET``; as many rows as the step has tokens."""
    group = hv // hk

    def fits(hb):
        rows = T * 4 * (2 * hb // group * dk + 2 * hb * dv + 128)
        return 2 * (rows + 2 * hb * dk * dv * 4) <= VMEM_BUDGET
    blocks = [hb for hb in range(group, min(hv, MAX_HEAD_BLOCK) + 1, group)
              if hv % hb == 0]
    return max([hb for hb in blocks if fits(hb)], default=group)


def _gdn_kernel(layer_ref, order_ref, active_ref, start_ref, len_ref,
                reset_ref, q_ref, k_ref, v_ref, gb_ref, s_ref, o_ref,
                st_ref, *, heads, group, dk, dv):
    """Grid ``(blocks of value heads, runs)``: one grid step holds one
    slot's state for ``heads`` value heads, ``(heads, Dk, Dv)`` float32,
    brought into VMEM by the pipeline through ``order`` (the slots that
    have a run, first; the steps past the last of them name its block
    again, which costs no copy) and written back in place once. The run's
    tokens update it in turn: a key head's ``q`` and ``k`` rows become
    columns over ``Dk`` (a masked lane sum, once for the value heads it
    serves), ``S^T k`` and ``S^T q`` are sums over sublanes, the decay
    and ``beta`` of a head one lane of the token's row of ``gb``. The
    heads lead ``q``, ``k``, ``v`` and ``o``, so that a token's row of a
    head is whole lanes at a dynamic sublane; ``o`` rows go into the
    output block, which stays in VMEM across a head block's runs."""
    r = pl.program_id(1)
    hv = gb_ref.shape[1] // 2
    h0 = pl.program_id(0) * heads
    slot = order_ref[jax.lax.min(r, jax.lax.max(active_ref[0] - 1, 0))]

    @pl.when(r == 0)
    def _first():
        # rows of padding are never written: zeros, not what VMEM held
        o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

    @pl.when(jax.lax.bitwise_and(r == 0, active_ref[0] == 0))
    def _no_run():
        # a step of padding alone: the block goes back as it came
        st_ref[...] = s_ref[...]

    @pl.when(r < active_ref[0])
    def _run():
        @pl.when(reset_ref[slot] == 1)
        def _zero():
            st_ref[...] = jnp.zeros(st_ref.shape, jnp.float32)

        @pl.when(reset_ref[slot] == 0)
        def _carry():
            st_ref[...] = s_ref[...]

        eye = (jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (dk, dk), 1))
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 2 * hv), 1)

        def column(row):                # (1, Dk) -> (Dk, 1)
            return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

        def scalar(row, at):            # one lane of (1, 2 Hv), as (1, 1)
            return jnp.sum(jnp.where(lane == at, row, 0.0), axis=1,
                           keepdims=True)

        def token(i, carry):
            t = pl.ds(start_ref[slot] + i, 1)
            gb = gb_ref[t, :]
            for kh in range(heads // group):
                kcol = column(k_ref[kh, t, :])
                qcol = column(q_ref[kh, t, :])
                for h in range(kh * group, (kh + 1) * group):
                    decay = scalar(gb, h0 + h)
                    beta = scalar(gb, hv + h0 + h)
                    s = _delta_write(st_ref[0, 0, h] * decay, kcol,
                                     v_ref[h, t, :], beta)
                    st_ref[0, 0, h] = s
                    o_ref[h, t, :] = jnp.sum(s * qcol, axis=0,
                                             keepdims=True)
            return carry

        jax.lax.fori_loop(0, len_ref[slot], token, 0)


@functools.partial(jax.jit, static_argnums=(10,))
def _gdn_state_pallas(q, k, v, g, beta, state, seq_ids, positions, valid,
                      layer, interpret):
    T, hk, dk = q.shape
    hv, dv = v.shape[1:]
    S = state.shape[1]
    heads = _head_block(T, hv, hk, dk, dv)
    group = hv // hk
    start, length = slot_runs(seq_ids, valid, S)
    active = length > 0
    reset = jnp.logical_and(active, positions[start] == 0)
    # the slots that have a run first, in slot order
    order = jnp.argsort(jnp.logical_not(active), stable=True)
    # each head's decay, then each head's beta: one row a token
    gb = jnp.concatenate([jnp.exp(g), beta], axis=1)

    def heads_of(j, r, *_):
        return (j, 0, 0)

    def whole(j, r, *_):
        return (0, 0)

    def slot_state(j, r, lyr, order, n_active, *_):
        return (lyr[0],
                order[jax.lax.min(r, jax.lax.max(n_active[0] - 1, 0))],
                j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(hv // heads, S),
        in_specs=[pl.BlockSpec((heads // group, T, dk), heads_of),
                  pl.BlockSpec((heads // group, T, dk), heads_of),
                  pl.BlockSpec((heads, T, dv), heads_of),
                  pl.BlockSpec((T, 2 * hv), whole),
                  pl.BlockSpec((1, 1, heads, dk, dv), slot_state)],
        out_specs=[pl.BlockSpec((heads, T, dv), heads_of),
                   pl.BlockSpec((1, 1, heads, dk, dv), slot_state)],
    )
    kernel = functools.partial(_gdn_kernel, heads=heads, group=group,
                               dk=dk, dv=dv)
    o, state = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((hv, T, dv), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool is updated where it lies: operand 10 (after the six
        # scalar operands and q, k, v, gb) is output 1
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(layer.astype(jnp.int32).reshape(1), order.astype(jnp.int32),
      active.sum().astype(jnp.int32).reshape(1), start, length,
      reset.astype(jnp.int32), *(jnp.swapaxes(a, 0, 1) for a in (q, k, v)),
      gb, state)
    return jnp.swapaxes(o, 0, 1), state


def gdn_state_step(q, k, v, g, beta, state, seq_ids, positions, valid,
                   layer, use_pallas=None, interpret=None):
    """One step of the gated delta rule for a packed batch.

    ``q``/``k [T, Hk, Dk]`` (after the convolution, L2-normed, ``q``
    scaled), ``v [T, Hv, Dv]``, ``g [T, Hv]`` (the log of the decay),
    ``beta [T, Hv]``; ``state`` the model's pool ``[L, S, Hv, Dk, Dv]``
    float32, of which layer ``layer`` (a Python int or a traced int32
    scalar) is read and written, at the slots ``seq_ids [T]`` names
    among the tokens with ``valid [T]``; ``positions [T]`` say which
    runs start a sequence. Returns ``(o [T, Hv, Dv], state)``: ``o_t =
    S_t^T q_t``, rows of padding zeros on the kernel's path and
    unspecified on the reference's, the pool updated (in place on the
    kernel's path).

    The kernel (``_gdn_kernel``) takes each slot's run as a whole: its
    valid tokens must lie side by side in the batch, in position order,
    as the engine packs them. The reference takes any order."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    f32 = jnp.float32
    args = tuple(jnp.asarray(a, f32) for a in (q, k, v, g, beta)) + (
            state, jnp.asarray(seq_ids, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32),
            jnp.asarray(layer, jnp.int32))
    if not use_pallas:
        return gdn_state_step_reference(*args)
    if interpret is None:
        interpret = not _on_tpu()
    return _gdn_state_pallas(*args, bool(interpret))


@register("gdn_state_step", differentiable=False)
def _gdn_state_op(q, k, v, g, beta, state, seq_ids, positions, valid, *,
                  layer=0, use_pallas=None):
    """Registered gated delta-rule step: Pallas kernel on TPU, the
    per-token recurrence elsewhere."""
    return gdn_state_step(q, k, v, g, beta, state, seq_ids, positions,
                          valid, layer, use_pallas=use_pallas)
