"""The state-space (Mamba-2) recurrence over the packed serving step.

A Mamba-2 layer leaves no page a token. Each sequence owns one fixed
STATE a layer, ``S [H, P, N]`` (heads x head size x state size), that
every token of the sequence overwrites:

    S_t = exp(dt_t A)[:, None, None] S_{t-1}
          + (dt_t[:, None] x_t)[:, :, None] B_t[None, None, :]
    y_t = S_t C_t

with ``x_t [H, P]``, ``dt_t [H]`` (after its softplus), ``A [H]``
(negative), and ``B_t``, ``C_t [N]`` shared by all heads (one group).
The states of a model live in ONE pool indexed by layer and by the
sequence's SLOT, the row of ``block_tables`` that ``seq_ids`` names:
``[L, S, N, H * P]`` float32. ``N`` leads a slot's state and the
``H * P`` channels are its lanes: ``B_t`` and ``C_t`` then enter as
columns that every head shares, ``x_t`` and the decay as rows, and the
sum over ``N`` in ``y_t`` is a sum over sublanes, elementwise but for its
last step.

A step hands over the packed batch of ``T`` tokens, as the flat
attention kernels take it. The valid tokens of one slot are a RUN, side
by side and in position order (``LLMEngine._build_batch`` packs them
so): one token in decode, a chunk in prefill. A run whose first token
sits at position 0 starts from a state of zeros, whatever the slot held
before (a finished or preempted occupant's state needs no clearing), and
a token with ``valid == 0`` touches no state.

:func:`ssd_state_step` is the Pallas kernel on a TPU (``use_pallas=True``
elsewhere: interpret mode) and :func:`ssd_state_step_reference`, the
literal per-token recurrence in ``jnp``, off it; the tests pin one
against the other. Both take the WHOLE pool with ``layer`` (a
``pool[li]`` handed to a custom call is a copy, 2 MB a slot here) and
return it updated; the kernel aliases it in place.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register
from .flash_attention import _on_tpu

__all__ = ["ssd_state_step", "ssd_state_step_reference", "slot_runs"]

#: lanes of a slot's state that one grid step holds (a block of heads)
LANE_BLOCK = 1024
#: lanes updated at once inside a grid step: 32 vregs of state at N 128
LANE_CHUNK = 256


def slot_runs(seq_ids, valid, slots):
    """``(start [S], length [S])`` of each slot's run in the packed
    batch: the index of its first valid token (0 where it has none) and
    how many valid tokens name it."""
    mine = jnp.logical_and(
        seq_ids[:, None] == jnp.arange(slots, dtype=seq_ids.dtype)[None],
        valid.astype(bool)[:, None])                        # [T, S]
    return (jnp.argmax(mine, axis=0).astype(jnp.int32),
            mine.sum(axis=0).astype(jnp.int32))


def _channels(x, dt, A):
    """The decay ``exp(dt A)`` of each head over its ``P`` lanes, and
    ``dt x``: both ``[T, H * P]`` float32."""
    T, H, P = x.shape
    decay = jnp.exp(dt * A[None, :])                        # [T, H]
    return (jnp.repeat(decay, P, axis=1),
            (dt[:, :, None] * x).reshape(T, H * P))


def ssd_state_step_reference(x, dt, A, B, C, state, seq_ids, positions,
                             valid, layer):
    """The recurrence token by token, in batch order: the state of the
    token's slot is read (zeros at position 0), updated and written
    back unless the token is padding. ``x [T, H, P]``, ``dt [T, H]``,
    ``A [H]``, ``B``/``C [T, N]``, ``state [L, S, N, H * P]`` (all
    float32); returns ``(y [T, H, P], state)``."""
    T, H, P = x.shape
    decay, xdt = _channels(x, dt, A)

    def token(pool, t):
        slot = seq_ids[t]
        s0 = jnp.where(positions[t] == 0, 0.0, pool[layer, slot])
        s1 = s0 * decay[t][None, :] + B[t][:, None] * xdt[t][None, :]
        y = (s1 * C[t][:, None]).sum(axis=0)
        keep = valid[t].astype(bool)
        return pool.at[layer, slot].set(
            jnp.where(keep, s1, pool[layer, slot])), y

    state, y = jax.lax.scan(token, state, jnp.arange(T))
    return y.reshape(T, H, P), state


def _ssd_kernel(layer_ref, order_ref, active_ref, start_ref, len_ref,
                reset_ref, decay_ref, xdt_ref, b_ref, c_ref, s_ref, y_ref,
                o_ref):
    """Grid ``(blocks of lanes, runs)``: one grid step holds one slot's
    state for one block of heads, ``(N, WB)`` float32, brought into VMEM
    by the pipeline through ``order`` (the slots that have a run, first;
    the steps past the last of them name its block again, which costs no
    copy) and written back in place once. The run's tokens update it in
    turn: the token's row of decays and of ``dt x`` over the lanes, its
    ``B`` and ``C`` as columns over ``N`` (a row turned by a masked lane
    sum, once a token for all the block's heads), ``y`` a sum over
    sublanes into the token's row of the output block, which stays in
    VMEM across a lane block's runs."""
    r = pl.program_id(1)
    N, WB = o_ref.shape[2:]
    slot = order_ref[jax.lax.min(r, jax.lax.max(active_ref[0] - 1, 0))]

    @pl.when(r == 0)
    def _first():
        # rows of padding are never written: zeros, not what VMEM held
        y_ref[...] = jnp.zeros(y_ref.shape, jnp.float32)

    @pl.when(jax.lax.bitwise_and(r == 0, active_ref[0] == 0))
    def _no_run():
        # a step of padding alone: the block goes back as it came
        o_ref[...] = s_ref[...]

    @pl.when(r < active_ref[0])
    def _run():
        @pl.when(reset_ref[slot] == 1)
        def _zero():
            o_ref[...] = jnp.zeros(o_ref.shape, jnp.float32)

        @pl.when(reset_ref[slot] == 0)
        def _carry():
            o_ref[...] = s_ref[...]

        eye = (jax.lax.broadcasted_iota(jnp.int32, (N, N), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (N, N), 1))

        def column(row):                # (1, N) -> (N, 1)
            return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)

        def token(i, carry):
            t = pl.ds(start_ref[slot] + i, 1)
            b, c = column(b_ref[t, :]), column(c_ref[t, :])
            for w in range(0, WB, LANE_CHUNK):
                lanes = pl.ds(w, min(LANE_CHUNK, WB - w))
                s = o_ref[0, 0, :, lanes] * decay_ref[t, lanes] \
                    + b * xdt_ref[t, lanes]
                o_ref[0, 0, :, lanes] = s
                y_ref[t, lanes] = jnp.sum(s * c, axis=0, keepdims=True)
            return carry

        jax.lax.fori_loop(0, len_ref[slot], token, 0)


@functools.partial(jax.jit, static_argnums=(10,))
def _ssd_state_pallas(x, dt, A, B, C, state, seq_ids, positions, valid,
                      layer, interpret):
    T, H, P = x.shape
    S, N, W = state.shape[1:]
    wb = W if W <= LANE_BLOCK or W % LANE_BLOCK else LANE_BLOCK
    decay, xdt = _channels(x, dt, A)
    start, length = slot_runs(seq_ids, valid, S)
    active = length > 0
    reset = jnp.logical_and(active, positions[start] == 0)
    # the slots that have a run first, in slot order
    order = jnp.argsort(jnp.logical_not(active), stable=True)

    def lanes(j, r, *_):
        return (0, j)

    def whole(j, r, *_):
        return (0, 0)

    def slot_state(j, r, lyr, order, n_active, *_):
        return (lyr[0],
                order[jax.lax.min(r, jax.lax.max(n_active[0] - 1, 0))],
                0, j)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6,
        grid=(W // wb, S),
        in_specs=[pl.BlockSpec((T, wb), lanes),
                  pl.BlockSpec((T, wb), lanes),
                  pl.BlockSpec((T, N), whole),
                  pl.BlockSpec((T, N), whole),
                  pl.BlockSpec((1, 1, N, wb), slot_state)],
        out_specs=[pl.BlockSpec((T, wb), lanes),
                   pl.BlockSpec((1, 1, N, wb), slot_state)],
    )
    y, state = pl.pallas_call(
        _ssd_kernel, grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((T, W), jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # the pool is updated where it lies: operand 10 (after the six
        # scalar operands and decay, xdt, B, C) is output 1
        input_output_aliases={10: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 1024 * 1024),
        interpret=interpret,
    )(layer.astype(jnp.int32).reshape(1), order.astype(jnp.int32),
      active.sum().astype(jnp.int32).reshape(1), start, length,
      reset.astype(jnp.int32), decay, xdt, B, C, state)
    return y.reshape(T, H, P), state


def ssd_state_step(x, dt, A, B, C, state, seq_ids, positions, valid,
                   layer, use_pallas=None, interpret=None):
    """One step of the state-space recurrence for a packed batch.

    ``x [T, H, P]`` (the convolved, activated inputs as heads), ``dt [T,
    H]`` (after its bias and softplus), ``A [H]`` (``-exp(A_log)``),
    ``B``/``C [T, N]``, all float32; ``state`` the model's pool ``[L, S,
    N, H * P]`` float32, of which layer ``layer`` (a Python int or a
    traced int32 scalar) is read and written, at the slots ``seq_ids
    [T]`` names among the tokens with ``valid [T]``; ``positions [T]``
    say which runs start a sequence. Returns ``(y [T, H, P], state)``:
    ``y_t = S_t C_t`` (the skip ``D x_t`` is the caller's), rows of
    padding zeros on the kernel's path and unspecified on the
    reference's, the pool updated (in place on the kernel's path).

    The kernel (``_ssd_kernel``) takes each slot's run as a whole: its
    valid tokens must lie side by side in the batch, in position order,
    as the engine packs them. The reference takes any order."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    f32 = jnp.float32
    args = (x.astype(f32), dt.astype(f32), A.astype(f32), B.astype(f32),
            C.astype(f32), state, jnp.asarray(seq_ids, jnp.int32),
            jnp.asarray(positions, jnp.int32), jnp.asarray(valid, jnp.int32),
            jnp.asarray(layer, jnp.int32))
    if not use_pallas:
        return ssd_state_step_reference(*args)
    if interpret is None:
        interpret = not _on_tpu()
    return _ssd_state_pallas(*args, bool(interpret))


@register("ssd_state_step", differentiable=False)
def _ssd_state_op(x, dt, A, B, C, state, seq_ids, positions, valid, *,
                  layer=0, use_pallas=None):
    """Registered state-space step: Pallas kernel on TPU, the per-token
    recurrence elsewhere."""
    return ssd_state_step(x, dt, A, B, C, state, seq_ids, positions, valid,
                          layer, use_pallas=use_pallas)
