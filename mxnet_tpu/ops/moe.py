"""Routed experts for the flat serving step: the two routers (the
``noaux_tc`` sigmoid router of DeepSeek-V3-shaped models, and softmax
then top-``k`` then renormalise, the Qwen3-MoE family's) and the grouped
products over the experts that a step's tokens hit.

A step's ``T`` tokens each pick ``k`` of ``E`` experts: ``T * k`` (token,
expert) *pairs*. An expert is a SwiGLU, ``(silu(x Wg) * (x Wu)) Wd``, of
its own three matrices, so the layer's cost on a decoding step is the
bytes of the experts that were hit, not of the ``E`` that are held. The
products therefore run grouped:

- pairs are sorted by expert; each expert's rows are padded to whole
  tiles of ``TILE_ROWS`` rows, so every tile belongs to one expert and an
  expert's tiles lie one after the other;
- :func:`_grouped_swiglu_pallas` walks the tiles, one a grid step: the
  tile's expert is a scalar-prefetch operand that only the weights' index
  maps read, so a step's blocks are ONE expert's ``Wg``, ``Wu`` and ``Wd``
  out of the stacked ``[E, ...]`` weights, at the expert's WHOLE width
  (three contiguous transfers), and the tile's output is one SwiGLU with
  nothing to accumulate. The next tile of the same expert maps to the
  same blocks, which the pipeline does not fetch again: an expert's bytes
  are read once a call however many tiles its pairs fill, and an expert
  that no token hit is never read. Tiles past the last one in use are
  skipped and stay on the last expert's blocks, so they cost a grid step
  and no bytes;
- the block width follows from the shapes: the whole width where an
  expert's three matrices fit ``VMEM_BUDGET`` twice (one in use, the next
  in flight) beside the row tiles, else the widest block of whole
  128-lane tiles that divides the width and does
  (:func:`_column_block`). The grid is then ``(column blocks, tiles)``
  with the column block OUTSIDE the tiles, so that each (expert, column
  block) is still fetched once, and the column blocks' partial outputs
  are summed after the call;
- each pair's row is gathered back, scaled by its gate and summed into
  its token.

No capacity limit and no dropped pair: the number of tiles is sized for
the worst imbalance (every pair on one expert, or every pair on an
expert of its own). Rows of bucket padding (``valid == 0``) are given to
no expert and come back as zeros.

``moe_experts_reference`` is the plain ``jnp`` twin (every expert over
every token, weighted by a dense ``[T, E]`` matrix of gates) that runs
off the TPU and pins the kernel in tests.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register
from .flash_attention import _on_tpu

__all__ = ["route_noaux_tc", "route_softmax_topk", "moe_experts",
           "moe_experts_reference", "moe_load", "moe_load_sum"]

#: rows of one tile of the grouped products (a packed bf16 tile)
TILE_ROWS = 16
#: bytes of VMEM that Mosaic is handed for the grouped products
VMEM_LIMIT = 64 * 1024 * 1024
#: of which the blocks of one grid step may take this much, each counted
#: twice (the pipeline holds the block in use and the next one in flight);
#: the rest is for what the body keeps between its products
VMEM_BUDGET = VMEM_LIMIT * 3 // 4


def route_noaux_tc(x, router, bias, k, norm_topk_prob=True, scaling=1.0):
    """``(experts [T, k] int32, gates [T, k] float32)`` for ``x [T, d]``:
    scores ``sigmoid(x router)`` in float32, the ``k`` experts with the
    largest ``score + bias`` (the bias selects only; ``None``, a router
    without one: the largest scores), gates the selected scores,
    renormalised to sum to one if ``norm_topk_prob``, times ``scaling``.
    One group, so the published group limiting is the identity."""
    s = jax.nn.sigmoid(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(s if bias is None
                           else s + bias.astype(jnp.float32), k)
    gates = jnp.take_along_axis(s, sel, axis=1)
    if norm_topk_prob:
        gates = gates / (gates.sum(axis=1, keepdims=True) + 1e-20)
    return sel.astype(jnp.int32), gates * scaling


def route_softmax_topk(x, router, k, norm_topk_prob=True):
    """``(experts [T, k] int32, gates [T, k] float32)`` for ``x [T, d]``:
    scores ``softmax(x router)`` over ALL the experts in float32, the
    ``k`` largest, their scores as gates, divided by their sum if
    ``norm_topk_prob``. No selection bias, no scaling factor."""
    s = jax.nn.softmax(jnp.matmul(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST), axis=-1)
    gates, sel = jax.lax.top_k(s, k)
    if norm_topk_prob:
        gates = gates / gates.sum(axis=1, keepdims=True)
    return sel.astype(jnp.int32), gates


def _held_pairs(sel, live, num_experts, first):
    """``(experts as this layer numbers the ones it holds, which pairs it
    holds)``: every pair of a row with ``live`` where ``first`` is
    ``None`` (the layer holds every expert), else the pairs whose expert
    lies in ``[first, first + num_experts)``."""
    held = live[:, None]
    if first is None:
        return sel, held
    sel = sel - first
    return sel, held & (sel >= 0) & (sel < num_experts)


def moe_load(sel, valid, num_experts, first=None):
    """``int32 [4]``: routed pairs, experts hit, the largest load of one
    expert and the row tiles the pairs fill (the sum over the experts of
    ``ceil(load / TILE_ROWS)``: over the experts hit, what lies above 1
    is tiles that find their expert's weights already in VMEM), over the
    rows with ``valid``. With ``first``, of a layer that holds only the
    ``num_experts`` experts from ``first`` on (:func:`moe_experts`): the
    four over the pairs it holds, and ``int32 [5]``, the fifth the pairs
    routed to experts held elsewhere."""
    live = valid.astype(bool)
    local, held = _held_pairs(sel, live, num_experts, first)
    e = jnp.where(held, local, num_experts)
    counts = jnp.zeros((num_experts + 1,), jnp.int32).at[
        e.reshape(-1)].add(1)[:num_experts]
    load = [counts.sum(), (counts > 0).sum(), counts.max(),
            (-(-counts // TILE_ROWS)).sum()]
    if first is not None:
        load.append(live.sum() * sel.shape[1] - counts.sum())
    return jnp.stack(load).astype(jnp.int32)


def moe_load_sum(total, layer):
    """A step's load over its expert layers: pairs, experts hit and
    tiles summed, the largest load of one expert the largest (and the
    pairs held elsewhere summed, where the load has them)."""
    load = [total[0] + layer[0], total[1] + layer[1],
            jnp.maximum(total[2], layer[2]), total[3] + layer[3]]
    load += [total[4] + layer[4]] if total.shape[0] == 5 else []
    return jnp.stack(load)


def _swiglu(x, wg, wu, wd, limit=None):
    g = jnp.dot(x, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(x, wu, preferred_element_type=jnp.float32)
    if limit is not None:
        g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
    return jnp.dot((jax.nn.silu(g) * u).astype(x.dtype), wd,
                   preferred_element_type=jnp.float32)


def moe_experts_reference(x, sel, gates, valid, w_gate, w_up, w_down,
                          first=None, limit=None):
    """The expert layer's routed part, plainly: every expert held over
    every token, summed with each token's gate on it (0 where the token
    did not select it, and on rows without ``valid``). ``[T, d]``
    float32. ``first`` and ``limit`` as :func:`moe_experts` takes them."""
    T, E = x.shape[0], w_gate.shape[0]
    if first is None:
        weight = jnp.zeros((T, E), jnp.float32).at[
            jnp.arange(T)[:, None], sel].add(gates)
    else:
        # a pair held elsewhere adds its gate to a column that is dropped
        local, held = _held_pairs(sel, valid.astype(bool), E, first)
        weight = jnp.zeros((T, E + 1), jnp.float32).at[
            jnp.arange(T)[:, None], jnp.where(held, local, E)].add(
                gates)[:, :E]
    weight = jnp.where(valid.astype(bool)[:, None], weight, 0.0)

    def one(acc, e):
        wg, wu, wd, w = e
        return acc + w[:, None] * _swiglu(x, wg, wu, wd, limit), None
    out, _ = jax.lax.scan(one, jnp.zeros((T, w_down.shape[2]),
                                         jnp.float32),
                          (w_gate, w_up, w_down, weight.T))
    return out


def _column_block(d, m, itemsize, budget):
    """Columns of an expert's width that one grid step takes: all ``m``
    where ``Wg``, ``Wu`` and ``Wd`` of one expert, a tile of rows and its
    float32 output fit ``budget`` twice; else the widest block of whole
    128-lane tiles that divides ``m`` and does (one lane tile at the
    least). A width that is no multiple of 128 cannot be split."""
    def fits(tn):
        return 2 * (3 * d * tn * itemsize
                    + TILE_ROWS * d * (itemsize + 4)) <= budget
    lanes = m // 128
    if m % 128 or fits(m):
        return m
    return 128 * max([q for q in range(1, lanes) if lanes % q == 0
                      and fits(128 * q)], default=1)


def _grouped_kernel(te_ref, used_ref, x_ref, wg_ref, wu_ref, wd_ref,
                    o_ref, limit=None):
    """Grid (column blocks, tiles): one tile of rows, all of one expert,
    through one column block (as a rule the whole width) of that
    expert's SwiGLU."""
    @pl.when(pl.program_id(1) < used_ref[0])
    def _body():
        o_ref[...] = _swiglu(x_ref[...], wg_ref[...], wu_ref[...],
                             wd_ref[...], limit)


def _grouped_specs(d, tn):
    """The block shapes and index maps of :func:`_grouped_swiglu_pallas`
    for column blocks of ``tn``: ``(x, Wg, Wu, Wd, out)``, each map over
    ``(column block, tile, tile_expert, tiles_used)``. A tile past the
    last one in use carries the last expert in ``tile_expert``, so it
    stays on the blocks that are already there."""
    return (
        pl.BlockSpec((TILE_ROWS, d), lambda j, t, te, used: (t, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, d, tn), lambda j, t, te, used: (te[t], 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, d, tn), lambda j, t, te, used: (te[t], 0, j),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, tn, d), lambda j, t, te, used: (te[t], j, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((None, TILE_ROWS, d),
                     lambda j, t, te, used: (j, t, 0),
                     memory_space=pltpu.VMEM),
    )


@functools.partial(jax.jit, static_argnums=(6, 7),
                   static_argnames=("limit",))
def _grouped_swiglu_pallas(x_rows, tile_expert, tiles_used, w_gate, w_up,
                           w_down, interpret, budget=VMEM_BUDGET,
                           limit=None):
    R, d = x_rows.shape
    m = w_gate.shape[2]
    tn = _column_block(d, m, w_gate.dtype.itemsize, budget)
    nj = m // tn
    *in_specs, out_spec = _grouped_specs(d, tn)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(nj, R // TILE_ROWS),
        in_specs=in_specs, out_specs=out_spec)
    kernel = _grouped_kernel if limit is None else functools.partial(
        _grouped_kernel, limit=limit)
    parts = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((nj, R, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
    )(tile_expert, tiles_used, x_rows, w_gate, w_up, w_down)
    return parts[0] if nj == 1 else parts.sum(axis=0)


def moe_experts(x, sel, gates, valid, w_gate, w_up, w_down,
                use_pallas=None, interpret=None, first=None, limit=None):
    """The routed part of an expert layer for a packed batch: ``x [T,
    d]`` tokens, ``sel``/``gates`` ``[T, k]`` from the router, ``valid
    [T]`` (rows of padding are routed nowhere), the experts' stacked
    ``w_gate``/``w_up`` ``[E, d, m]`` and ``w_down [E, m, d]``. Returns
    ``sum_i gates[t, i] * E_sel[t, i](x[t])`` as ``[T, d]`` float32.
    Grouped Mosaic kernel on a TPU (only the experts hit are read), the
    plain twin elsewhere; see the module docstring.

    ``first``: the layer holds only the ``E`` experts from ``first`` on
    of the router's (one chip's share under expert parallelism); ``sel``
    names the router's experts, and a pair routed to one held elsewhere
    is given to no expert here, as a row of padding is, so it is neither
    computed nor read. ``None``: the ``E`` experts are all the router's.
    ``limit``: each expert's gate product is capped at ``limit`` and its
    up product clipped to ``[-limit, limit]`` before the SwiGLU's
    product (``swiglu_limit``); ``None``, neither."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if not use_pallas:
        return moe_experts_reference(x, sel, gates, valid, w_gate, w_up,
                                     w_down, first, limit)
    if interpret is None:
        interpret = not _on_tpu()
    T, k = sel.shape
    E, d = w_gate.shape[0], x.shape[1]
    P, tm = T * k, TILE_ROWS
    n_tiles = min(P, E) + P // tm
    R = n_tiles * tm
    live = valid.astype(bool)
    # pairs by expert; a pair of padding (or held elsewhere) goes to the
    # sentinel E, last
    if first is None:
        e = jnp.where(live[:, None], sel, E).reshape(P)
    else:
        sel, held = _held_pairs(sel, live, E, first)
        e = jnp.where(held, sel, E).reshape(P)
    counts = jnp.zeros((E + 1,), jnp.int32).at[e].add(1)
    tiles = (counts[:E] + tm - 1) // tm
    tile_end = jnp.cumsum(tiles)
    # first row of each expert's tiles; the sentinel's lies past the end
    row0 = jnp.concatenate([(tile_end - tiles) * tm,
                            jnp.full((1,), R, jnp.int32)])
    lead = jnp.cumsum(counts) - counts          # in the sorted order
    order = jnp.argsort(e, stable=True)
    e_sorted = e[order]
    row_sorted = row0[e_sorted] + jnp.arange(P, dtype=jnp.int32) \
        - lead[e_sorted]
    row_sorted = jnp.minimum(row_sorted, R)     # sentinel pairs: row R
    # row -> the token it holds (T: the zero row appended below)
    row_token = jnp.full((R + 1,), T, jnp.int32).at[row_sorted].set(
        (order // k).astype(jnp.int32))[:R]
    x_rows = jnp.concatenate([x, jnp.zeros((1, d), x.dtype)])[row_token]
    used = tile_end[-1:].astype(jnp.int32)
    tile_expert = jnp.searchsorted(
        tile_end, jnp.minimum(jnp.arange(n_tiles), used[0] - 1),
        side="right").astype(jnp.int32)
    tile_expert = jnp.minimum(tile_expert, E - 1)
    y_rows = _grouped_swiglu_pallas(
        x_rows, tile_expert, used, w_gate, w_up, w_down, bool(interpret),
        **({} if limit is None else {"limit": limit}))
    # each pair's row, in the pairs' own order
    row = jnp.zeros((P,), jnp.int32).at[order].set(row_sorted)
    y = jnp.take(y_rows, row, axis=0, mode="clip").reshape(T, k, d)
    y = jnp.where(live[:, None, None] if first is None else held[..., None],
                  y, 0.0)
    return (y * gates[..., None]).sum(axis=1)


@register("moe_experts", differentiable=False)
def _moe_experts_op(x, sel, gates, valid, w_gate, w_up, w_down, *,
                    use_pallas=None):
    """Registered routed-experts op: grouped Pallas kernel on TPU, the
    dense masked twin elsewhere."""
    return moe_experts(x, sel, gates, valid, w_gate, w_up, w_down,
                       use_pallas=use_pallas)
