"""Flat ragged attention over a paged cache: one kernel per cache kind.

A serving step hands attention ONE packed batch of query tokens,
``q [T, ...]``, drawn from many sequences: prefill chunks, decode
tokens and speculative verify positions side by side, no per-sequence
padding (the ``[total_q_tokens]`` shape of "Ragged Paged Attention",
PAPERS.md). Token ``t`` belongs to row ``seq_ids[t]`` of
``block_tables [S, MB]`` and sits at absolute position
``positions[t]``; the row names, in order, the pages of a fixed pool
that hold its sequence, so no sequence owns contiguous storage and the
batch's shape never depends on the length mix. The token's own key is
in its page before the call; it attends causally over positions ``<=
positions[t]`` of its own sequence. A model's pools are stacked over
layers and handed over whole with ``layer=`` (a ``pool[li]`` given to
a custom call is a copy).

Two kernels, three entries, each with the plain ``jnp`` reference it is
pinned against (tests/test_ragged_attention.py, test_llm_mla_moe.py)
and that runs in its place off the TPU; ``use_pallas=True`` off the
TPU runs the kernel in interpret mode:

- ``ragged_flat_attention`` (``_sharded`` for ``shard_map`` bodies,
  ``_reference``): a K and a V page per head, ``[L, N, bs, H, D]``,
  float or int8/fp8 with per-slot per-head scale pools. See
  ``_flat_kernel``.
- ``mla_flat_attention`` (``_reference``): one pool of latents ``[L,
  N, bs, W]`` that every head reads (MLA, absorbed form). See
  ``_mla_flat_kernel``.

- ``gqa_flat_attention`` (``_reference``): grouped queries at any head
  size over ONE pool of packed rows ``[L, N, bs, 2 x kv_heads x D]``
  (a token's value heads, then its key heads), through
  ``_mla_flat_kernel``'s walk.

Both kernels walk the same way. A grid over blocks of query tokens;
the tables and the per-token scalars are scalar-prefetch operands; in
a block each RUN of consecutive tokens of one sequence walks that
sequence's pages once, by manual DMA from the pool in HBM through
``block_tables[seq, j]`` (the gather never exists in HBM), double
buffered, and only as far as the page of the run's largest position;
online softmax in float32 scratch across a run's steps. A step costs
what its contexts hold, not ``T x MB``, and the cost follows the
layout of runs (``LLMEngine._build_batch`` packs a sequence's tokens
side by side and names the tail behind them so that it walks nothing).

The masking guarantee runs one way, and preemption, the prefix cache
and padded steps rely on it: data past a token's position, in blocks
its table row does not name, or in the null block (``NULL_BLOCK``,
where padded writes land) never reaches a valid row. A padded or stale
token (one past the step's own, whatever ``seq_ids`` and ``positions``
an earlier step left there) changes no valid row either; its own row
is unspecified and the caller discards it.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .registry import register
from .flash_attention import _NEG_INF, _on_tpu


def ragged_flat_attention_reference(q, k_pages, v_pages, block_tables,
                                    seq_ids, positions, scale=None,
                                    k_scales=None, v_scales=None,
                                    layer=None):
    """Gather-based oracle for the FLAT ragged layout: ``q`` is a
    packed ``[T, H, D]`` batch of query tokens from MANY sequences —
    token ``t`` belongs to row ``seq_ids[t]`` of ``block_tables`` and
    sits at absolute position ``positions[t]``, attending causally
    over positions ``<= positions[t]`` of ITS sequence's paged
    history. No per-sequence padding: the step computes exactly the
    tokens that exist (prefill chunks, decodes and verify positions
    packed together — the "[total_q_tokens]" shape of the Ragged
    Paged Attention paper). Invalid/padded tokens should carry
    ``seq_ids`` pointing at an all-null table row; their outputs are
    unspecified and must be discarded.

    Quantized pages (ISSUE 13): with ``k_scales``/``v_scales``
    ``(N, bs, H)`` f32, the int8 pages are dequantized per slot+head
    right after the gather — ``k = int8 * scale`` — and everything
    downstream runs in f32 exactly as the float path does.

    With ``layer`` the pools (and scale pools) are the STACKED
    ``(L, N, bs, H[, D])`` arrays and the gather itself picks the
    layer — the same values ``pool[layer]`` would hand over, without
    a layer-sized intermediate."""
    T, H, D = q.shape
    bs = k_pages.shape[-3]
    MB = block_tables.shape[1]
    s = scale if scale is not None else float(1.0 / (D ** 0.5))
    tbl = block_tables[seq_ids]                       # (T, MB)
    at = (tbl,) if layer is None else (layer, tbl)
    k = k_pages[at]
    v = v_pages[at]
    if k_scales is not None:
        k = k.astype(jnp.float32) * k_scales[at][..., None]
        v = v.astype(jnp.float32) * v_scales[at][..., None]
    k = k.reshape(T, MB * bs, H, D)
    v = v.reshape(T, MB * bs, H, D)
    logits = jnp.einsum("thd,tkhd->htk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * s
    pos = jnp.arange(MB * bs, dtype=jnp.int32)
    mask = pos[None, None, :] <= positions[None, :, None]
    logits = jnp.where(mask, logits, _NEG_INF)
    probs = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("htk,tkhd->thd", probs, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ----------------------------------------------------------- pallas --


def _block_scalars(seq_ids, positions, table_rows, total):
    """The per-token scalars a flat kernel prefetches: ids clipped to
    the table's rows and positions to zero, as the reference's gather
    and mask take them, and behind them up to ``total`` (whole query
    blocks) the last token's sequence at position 0: tokens that
    lengthen its run and cost no walk of their own, whose rows are
    never written back."""
    sid = jax.lax.clamp(0, seq_ids.astype(jnp.int32), table_rows - 1)
    pos = jax.lax.max(positions.astype(jnp.int32), 0)
    pad = total - sid.shape[0]
    if pad:
        sid = jnp.concatenate([sid, jnp.broadcast_to(sid[-1:], (pad,))])
        pos = jnp.concatenate([pos, jnp.zeros((pad,), jnp.int32)])
    return sid, pos


def _pages_sliceable(pages):
    """Whether Mosaic slices one ``(bs, H, D)`` page out of a pool in
    HBM for a DMA: it slices an operand by whole tiles, 128 lanes by
    one 32-bit sublane (refused at D 64, at two int8 heads and at a
    page of scales ``(bs, 16)``; jax 0.9.0, libtpu 0.0.34)."""
    H, D = pages.shape[-2:]
    return D % 128 == 0 and H * pages.dtype.itemsize % 4 == 0


def _flat_tiles(T, bs, MB):
    """``(query tokens a block, pages a loop step)`` of the flat K/V
    kernel, from the shapes alone.

    Query tokens: the whole packed batch up to 64 tokens, else blocks
    of 64. A run that crosses a block boundary is walked once a side,
    so a block should hold a step's runs whole (8 slots of a token or a
    chunk of 16 each: 8 to 128 tokens); the per-head products take all
    of a block's tokens as their rows, so a block far wider than its
    runs pays for rows it masks. Pages: as many as make 128 keys a loop
    step: an MXU-shaped minor dimension for ``q.k``, and the loop's
    fixed cost (DMA descriptors, rescaling the accumulator) once per
    128 keys; never more than the table is wide.

    VMEM: a page pair is ``2 x bs x H x D`` elements, 262 KB at 16 x 16
    x 128 float32 (a latent page: 20 KB). Two buffers of 128 keys for K
    and for V are 4 MB; the query and output blocks (double buffered by
    the pipeline) and the accumulator are 2.5 MB more at 64 tokens:
    under the 16 MB a kernel may take by default, so no
    ``vmem_limit_bytes``. 256 keys a step would be 8 MB of buffers."""
    qb = T if T <= 64 else 64
    return qb, max(1, min(MB, 128 // bs))


def _flat_kernel(layer_ref, sid_ref, pos_ref, bt_ref, q_ref, k_pool,
                 v_pool, *refs, scale, block_size, per_head):
    """Grid ``(query blocks,)``: one grid step holds ``QB`` packed query
    tokens ``(QB, H, D)``. The walk is ``_mla_flat_kernel``'s: the
    block's tokens are taken RUN by run, a run being consecutive tokens
    of one sequence; the run's K and V pages, and only up to the page
    of its largest position, come from the STACKED pools ``[L, N, bs,
    H, D]`` in HBM (whole: a ``pool[li]`` handed to a custom call is a
    copy; the layer is a scalar-prefetch operand) by explicit DMA
    through the scalar-prefetched block table, ``PP`` pages of each a
    loop step into one of two buffers ``(PP x bs, H, D)`` while the
    other is computed on. Online softmax in float32 scratch across a
    run's steps, a row per token and head.

    While a run's last step computes, the next run's first pages are
    already on their way; only a block's first copy is exposed.

    A page is ``(bs, H, D)``: a head's keys are every ``H``-th row of
    a buffer seen as ``(keys x H, D)``; queries, output and the
    softmax state are ``(QB x H, D)`` likewise, a row a token and head.
    Two inner forms read a buffer, chosen a loop step by how many of
    the run's tokens reach it:

    - ONE token alone (a decode row; the longest token of a run whose
      others stop short, such as a tail at position 0) takes the
      whole buffer at once on the VPU (multiply, then reduce lanes for
      ``q.k`` and the key axis for ``p.v``): a matrix-vector product a
      head, bound by the page bytes, exact in float32 (measured: 4 us
      a step of 128 keys, whose 2 MB take 2.6 us; a head at a time
      8.5 us);
    - several tokens (a prefill chunk, a verify window), where
      ``per_head``, take a head at a time on the MXU: ``(QB, D) x (D,
      keys)`` and ``(QB, keys) x (keys, D)``, the head's keys, queries
      and state rows by strided sublane loads (``ref[h::H, :]``). All
      the block's tokens are rows; a row takes keys only if it is one
      of the RUN's own (tokens ``[i, end)``; another run of the same
      sequence in the block walks for itself) and the key is not past
      its token's position, and ``p`` is zeroed by that mask, not by
      ``exp`` (a row that has met no key yet still has ``m ==
      _NEG_INF``). A chunk of 16 costs what one token costs this way,
      a third of sixteen passes of the first form.

    Without ``per_head`` (Mosaic loads strided only four-byte rows of
    exactly 128 lanes: quantized pages, two-byte floats, a head size
    other than 128 or heads no multiple of the 8 sublanes) every token
    of a run that reaches a step takes the first form in turn.
    ``refs`` is ``(o, k_buf, v_buf, sems, acc, m, l)`` for float pages
    and ``(k_scales, v_scales, o, k_buf, v_buf, ks_buf, vs_buf, sems,
    acc, m, l)`` for QUANTIZED pages: ONE layer's per-slot per-head
    float32 scales ``[N, bs, lanes]`` in HBM, copied page by page
    beside their pages, ``page x scale`` fused where the tile lands.
    Every step program traces and lowers this body anew (17 programs
    a warm-up, 0.3 s each on the chip's host in this kernel's first
    form), so it is kept small: the loops over tokens, pages and heads
    are ``fori_loop``s, and scalar and elementwise work is written in
    ``lax`` primitives (``jnp.where``, ``jnp.minimum`` and their kin
    each go through a jitted call's dispatch when traced)."""
    if len(refs) == 7:
        scale_pools = scale_bufs = ()
        o_ref, k_buf, v_buf, sems, acc_ref, m_ref, l_ref = refs
    else:
        (*scale_pools, o_ref, k_buf, v_buf, ks_buf, vs_buf, sems,
         acc_ref, m_ref, l_ref) = refs
        scale_bufs = (ks_buf, vs_buf)
    KB, H = k_buf.shape[1:3]
    QB = q_ref.shape[0] // H
    PP = KB // block_size
    MB = bt_ref.shape[1]
    t0 = pl.program_id(0) * QB
    lyr = layer_ref[0]
    last_key = MB * block_size - 1      # the reference's table ends here

    def neg_inf(shape):
        return jnp.full(shape, _NEG_INF, jnp.float32)

    acc_ref[...] = jnp.zeros(acc_ref.shape, jnp.float32)
    m_ref[...] = jnp.full(m_ref.shape, _NEG_INF, jnp.float32)
    l_ref[...] = jnp.zeros(l_ref.shape, jnp.float32)

    def pages(seq, k, slot, go):
        """Loop step ``k`` of sequence ``seq``: start (``go``) or await
        the DMA of its ``PP`` pages of K and of V (and of their scales)
        into buffer ``slot``. Past the table's width the last entry
        again; past the run's own pages whatever the table names (the
        null block in the engine's tables): masked either way."""
        def page(p, carry):
            blk = bt_ref[seq, jax.lax.min(k * PP + p, MB - 1)]
            into = pl.ds(pl.multiple_of(p * block_size, block_size),
                         block_size)
            copies = [(k_pool.at[lyr, blk], k_buf), (v_pool.at[lyr, blk],
                                                     v_buf)]
            copies += [(pool.at[blk], buf)
                       for pool, buf in zip(scale_pools, scale_bufs)]
            for n, (src, buf) in enumerate(copies):
                copy = pltpu.make_async_copy(src, buf.at[slot, into],
                                             sems.at[slot, n])
                if go:
                    copy.start()
                else:
                    copy.wait()
            return carry

        jax.lax.fori_loop(0, PP, page, 0)

    def one_token(t, k, slot):
        """Token ``t`` of the block against buffer ``slot``, every
        head at once on the VPU. Its first step holds key 0, so ``m``
        is finite from then on and ``exp`` alone zeroes what the mask
        hides."""
        rows = pl.ds(pl.multiple_of(t * H, H), H)
        q = q_ref[rows, :].astype(jnp.float32)            # (H, D)
        kk = k_buf[slot].astype(jnp.float32)              # (KB, H, D)
        vv = v_buf[slot].astype(jnp.float32)
        if scale_bufs:
            kk = kk * ks_buf[slot, :, :H][..., None]
            vv = vv * vs_buf[slot, :, :H][..., None]
        s = jnp.sum(q[None] * kk, axis=-1,
                    keepdims=True) * scale                # (KB, H, 1)
        kpos = k * KB + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jax.lax.select(kpos <= jax.lax.min(pos_ref[t0 + t], last_key),
                           s, neg_inf(s.shape))
        m_prev = m_ref[rows, :]                           # (H, 1)
        m_new = jax.lax.max(m_prev, jnp.max(s, axis=0))
        p = jax.lax.exp(s - m_new[None])
        alpha = jax.lax.exp(m_prev - m_new)
        l_ref[rows, :] = l_ref[rows, :] * alpha + jnp.sum(p, axis=0)
        acc_ref[rows, :] = acc_ref[rows, :] * alpha + jnp.sum(p * vv,
                                                              axis=0)
        m_ref[rows, :] = m_new

    def one_head(h, k, slot, limit):
        """Head ``h`` of every token of the block against its keys in
        buffer ``slot``, on the MXU; ``limit (QB, 1)`` is the last key
        each token may take in this run, -1 for tokens of other runs."""
        rows = pl.ds(h, QB, stride=H)   # the head's row of every token
        keys = pl.ds(h, KB, stride=H)
        acc_h, m_h, l_h = (r.at[rows, :] for r in (acc_ref, m_ref, l_ref))
        q = q_ref[rows, :].astype(jnp.float32)            # (QB, D)
        kk, vv = (buf.at[slot].reshape(KB * H, buf.shape[-1])[keys, :]
                  .astype(jnp.float32) for buf in (k_buf, v_buf))
        s = jax.lax.dot_general(
            q, kk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # (QB, KB)
        kpos = k * KB + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        mask = kpos <= limit
        s = jax.lax.select(mask, s, neg_inf(s.shape))
        m_prev = m_h[...]                                 # (QB, 1)
        m_new = jax.lax.max(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jax.lax.select(mask, jax.lax.exp(s - m_new),
                           jnp.zeros(s.shape, jnp.float32))
        alpha = jax.lax.exp(m_prev - m_new)
        l_h[...] = l_h[...] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_h[...] = acc_h[...] * alpha + jax.lax.dot_general(
            p, vv, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_h[...] = m_new

    tok = jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)

    def run(c):
        i, slot0 = c
        seq = sid_ref[t0 + i]

        def same_seq(c):
            return jax.lax.bitwise_and(
                c[0] < QB, sid_ref[t0 + jax.lax.min(c[0], QB - 1)] == seq)

        def take(c):
            j, last, second, top, limit = c
            pos = jax.lax.min(pos_ref[t0 + j], last_key)
            ahead = pos > last
            return (j + 1, jax.lax.max(last, pos),
                    jax.lax.select(ahead, last, jax.lax.max(second, pos)),
                    jax.lax.select(ahead, j, top),
                    jax.lax.select(tok == j,
                                   jax.lax.broadcast(pos, limit.shape),
                                   limit))

        # the run's end; its largest position, its second largest (-1:
        # none) and the token `top` that holds the largest; the last
        # key each of its tokens may take (-1: the tokens of other
        # runs, the same sequence's among them)
        end, last, second, top, limit = jax.lax.while_loop(
            same_seq, take,
            (i, -1, -1, i, jnp.full((QB, 1), -1, jnp.int32)))
        steps = jax.lax.div(last, KB) + 1
        # what follows this run's last step: the next run's first
        follows = sid_ref[t0 + jax.lax.min(end, QB - 1)]

        def step(k, carry):
            slot = jax.lax.rem(slot0 + k, 2)
            more = k + 1 < steps

            # the next step's pages, or the next run's first, while
            # this step computes
            @pl.when(jax.lax.bitwise_or(more, end < QB))
            def _next():
                pages(jax.lax.select(more, seq, follows),
                      jax.lax.select(more, k + 1, 0), 1 - slot, True)

            pages(seq, k, slot, False)

            if per_head:
                def heads():
                    jax.lax.fori_loop(
                        0, H,
                        lambda h, c: (one_head(h, k, slot, limit), c)[1], 0)

                # one token alone reaches this step (a decode row; a
                # run's tail at position 0 shares its first only)
                jax.lax.cond(second < k * KB,
                             lambda: one_token(top, k, slot), heads)
            else:
                def token(t, carry):    # if it reaches this step
                    pl.when(pos_ref[t0 + t] >= k * KB)(
                        lambda: one_token(t, k, slot))
                    return carry

                jax.lax.fori_loop(i, end, token, 0)
            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        return end, jax.lax.rem(slot0 + steps, 2)

    pages(sid_ref[t0], 0, 0, True)      # the block's first: nothing hides it
    jax.lax.while_loop(lambda c: c[0] < QB, run, (0, 0))
    l_safe = jax.lax.max(l_ref[...], jnp.float32(1e-30))
    o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnums=(9, 10))
def _ragged_flat_pallas(q, k_pages, v_pages, k_scales, v_scales,
                        block_tables, seq_ids, positions, layer, scale,
                        interpret):
    T, H, D = q.shape
    bs = k_pages.shape[2]
    S, MB = block_tables.shape
    qb, pp = _flat_tiles(T, bs, MB)
    blocks = -(-T // qb)
    sid, pos = _block_scalars(seq_ids, positions, S, blocks * qb)
    quantized = k_scales is not None
    if quantized:
        # a page's scales (bs, H) are no whole tile, and Mosaic slices
        # an operand in HBM by whole tiles only: heads padded to the
        # 128 lanes, as the (N, bs, H) operand is laid out anyway
        lanes = ((0, 0), (0, 0), (0, -H % 128))
        k_scales, v_scales = jnp.pad(k_scales, lanes), jnp.pad(v_scales,
                                                               lanes)
    # a head at a time on the MXU where Mosaic loads a head's rows
    # strided: four-byte pages, heads in whole sublane tiles, a head
    # exactly the 128 lanes wide
    per_head = (not quantized and k_pages.dtype == jnp.float32
                and H % 8 == 0 and D == 128)

    def rows(b, lyr, sid, pos, bt):
        return (b, 0)

    # the pools, in HBM; the scales by name: a layer's, 1 MB, the
    # compiler would else hold in VMEM, where a page's (bs, H) face is
    # no whole tile to slice
    pools = [pl.BlockSpec(memory_space=pl.ANY)] * 2
    if quantized:
        pools += [pl.BlockSpec(memory_space=pltpu.HBM)] * 2
    streams = len(pools)
    scratch = [pltpu.VMEM((2, pp * bs, H, D), k_pages.dtype),
               pltpu.VMEM((2, pp * bs, H, D), v_pages.dtype)]
    if quantized:
        scratch += [pltpu.VMEM((2, pp * bs, k_scales.shape[-1]),
                               jnp.float32)] * 2
    scratch += [pltpu.SemaphoreType.DMA((2, streams)),
                pltpu.VMEM((qb * H, D), jnp.float32),
                pltpu.VMEM((qb * H, 1), jnp.float32),
                pltpu.VMEM((qb * H, 1), jnp.float32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(blocks,),
        in_specs=[pl.BlockSpec((qb * H, D), rows, memory_space=pltpu.VMEM)]
        + pools,
        out_specs=pl.BlockSpec((qb * H, D), rows, memory_space=pltpu.VMEM),
        scratch_shapes=scratch,
    )
    kernel = functools.partial(_flat_kernel, scale=scale, block_size=bs,
                               per_head=per_head)
    args = [q.reshape(T * H, D), k_pages, v_pages]
    if quantized:
        # ONE layer's (N, bs, H) scales (see ragged_flat_attention for
        # why not the stacked pool)
        args += [k_scales, v_scales]
    # inside a shard_map body the output varies over the mesh axes
    # its heads do (none outside one)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * H, D), q.dtype,
                                       vma=jax.typeof(q).vma),
        interpret=interpret,
    )(layer.astype(jnp.int32).reshape(1), sid, pos,
      block_tables.astype(jnp.int32), *args)
    return out.reshape(T, H, D)


def ragged_flat_attention(q, k_pages, v_pages, block_tables, seq_ids,
                          positions, scale=None, use_pallas=None,
                          interpret=None, k_scales=None, v_scales=None,
                          layer=None):
    """Flat-ragged paged attention entry point (packed
    ``[total_q_tokens]`` batch, per-token sequence/position
    indirection): the Pallas kernel on a TPU, the gather reference
    elsewhere; ``use_pallas=True`` off the TPU is interpret mode.

    Pools: with ``layer`` (a Python int or a traced int32 scalar)
    ``k_pages``/``v_pages`` are the STACKED pools ``[L, N, bs, H, D]``
    of a whole model and the kernel reads layer ``layer`` of them in
    place. That is the form a multi-layer step must use: a
    ``pool[li]`` slice handed to the kernel's custom call is
    materialized (one layer's pages copied out of the pool, for K
    and again for V, on every call). Without ``layer`` the pools are
    one layer's ``[N, bs, H, D]`` — a stacked pool of one layer
    through the same kernel.

    ``k_scales``/``v_scales`` (``[L, N, bs, H]`` with ``layer``, else
    ``[N, bs, H]``; f32) select the QUANTIZED page variant: pages are
    int8/fp8 and are dequantized per slot+head inside the kernel
    (fused after the page DMA on the Pallas path, right after the
    gather on the reference path).

    What the kernel makes of the packed batch (``_flat_kernel``):
    blocks of query tokens (:func:`_flat_tiles`), and in a block every
    RUN of neighbouring tokens with one ``seq_ids`` value walks that
    sequence's K and V pages once, up to the page of the run's largest
    position. The result is the reference's for ANY ``seq_ids`` and
    ``positions`` (a row takes keys only from its own run's walk, up
    to its own token's position, so a sequence met again further on in
    a block, as a valid token beside a stale one, is two runs that do
    not touch; ids are clipped to the table's rows, positions to its
    width, as the reference's gather and mask do), but the cost
    follows the layout: ``LLMEngine._build_batch`` packs a sequence's
    tokens side by side, so a prefill chunk reads its pages once,
    while tokens of one sequence scattered over the batch are so many
    runs and walks. Nothing tells the kernel which tokens are padding:
    a tail that still holds an earlier step's ids and positions is
    walked like any token (rows finite, to be discarded); a tail that
    names the last token's sequence at position 0, as the engine
    writes it, lengthens the last run and walks nothing.

    On a TPU the kernel is taken where Mosaic can copy a page out of
    the pool by itself (a head size in whole 128-lane tiles, a page's
    heads in whole 32-bit sublanes: any count of float32 heads, four
    of int8); other shapes take the reference there."""
    if use_pallas is None:
        use_pallas = _on_tpu() and _pages_sliceable(k_pages)
    if scale is None:
        scale = float(1.0 / (q.shape[-1] ** 0.5))
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales or neither")
    if k_pages.ndim != (4 if layer is None else 5):
        raise ValueError(
            "pools are [L, N, bs, H, D] with `layer`, [N, bs, H, D] "
            f"without; got {k_pages.shape} and layer={layer!r}")
    if not use_pallas:
        return ragged_flat_attention_reference(
            q, k_pages, v_pages, jnp.asarray(block_tables),
            jnp.asarray(seq_ids), jnp.asarray(positions), scale,
            k_scales=k_scales, v_scales=v_scales, layer=layer)
    if interpret is None:
        interpret = not _on_tpu()
    if k_scales is not None:
        k_scales, v_scales = jnp.asarray(k_scales), jnp.asarray(v_scales)
        if layer is not None:
            # the kernel takes ONE layer's scales, sliced out here.
            # Unlike the pages, the stacked [L, N, bs, H] pool does
            # not sit row-major on the device (XLA:TPU lays an f32
            # array with a 16x16 minor face out N-minor), and the
            # custom call takes row-major operands only: handed
            # whole, the WHOLE scale pool is re-laid out on every
            # call (seen in the compiled step: 48 copies of
            # f32[24,1025,16,16], 201 MB each once padded). A
            # layer's slice is 1 MB, re-laid as it always was.
            k_scales, v_scales = k_scales[layer], v_scales[layer]
    if layer is None:
        layer = 0
        k_pages, v_pages = k_pages[None], v_pages[None]
    return _ragged_flat_pallas(q, k_pages, v_pages, k_scales, v_scales,
                               jnp.asarray(block_tables),
                               jnp.asarray(seq_ids),
                               jnp.asarray(positions),
                               jnp.asarray(layer),
                               float(scale), bool(interpret))


def ragged_flat_attention_sharded(q, k_pages, v_pages, block_tables,
                                  seq_ids, positions, axis_name=None,
                                  scale=None, use_pallas=None,
                                  interpret=None, k_scales=None,
                                  v_scales=None, layer=None):
    """Head-sharded flat variant for ``shard_map`` bodies (ISSUE 19),
    incl. the quantized-page form: ``q [T, H_local, D]``, pages
    ``([L,] N, bs, H_local, D)`` and scale pools
    ``([L,] N, bs, H_local)`` carry ONLY this shard's heads (stacked
    over layers with ``layer``, as in :func:`ragged_flat_attention`);
    ``block_tables/seq_ids/positions`` ride replicated (host-global
    block accounting).

    Attention is per-head independent and the softmax scale is
    ``1/sqrt(head_dim)`` — never head-count-dependent — so the local
    call IS this shard's full contribution: there is NO collective in
    here. The all-reduce that merges shards belongs to the caller's
    o-projection (fused into the one step program), which keeps this
    kernel dispatch per-shard and collective placement explicit.
    ``axis_name`` is accepted for symmetry/documentation; the scale
    default is pinned to head_dim explicitly so a future head-count
    -dependent rescale can't silently break shard independence."""
    del axis_name  # no collective here by design — see docstring
    if scale is None:
        scale = float(1.0 / (q.shape[-1] ** 0.5))  # head_dim only
    return ragged_flat_attention(
        q, k_pages, v_pages, block_tables, seq_ids, positions,
        scale=scale, use_pallas=use_pallas, interpret=interpret,
        k_scales=k_scales, v_scales=v_scales, layer=layer)


# ------------------------------------------- latent (MLA) flat form --
def mla_flat_attention_reference(q, pages, block_tables, seq_ids,
                                 positions, scale, v_dim, layer=None):
    """Gather-based oracle of :func:`mla_flat_attention`: every query
    token's latent pages gathered through its sequence's table row,
    one masked softmax, values the latents' first ``v_dim`` columns.
    Everything in float32."""
    T, H, W = q.shape
    bs = pages.shape[-2]
    MB = block_tables.shape[1]
    tbl = block_tables[seq_ids]                       # (T, MB)
    kv = pages[(tbl,) if layer is None else (layer, tbl)]
    kv = kv.reshape(T, MB * bs, W).astype(jnp.float32)
    logits = jnp.einsum("thw,tkw->htk", q.astype(jnp.float32), kv,
                        precision=jax.lax.Precision.HIGHEST) * scale
    pos = jnp.arange(MB * bs, dtype=jnp.int32)
    mask = pos[None, None, :] <= positions[None, :, None]
    probs = jax.nn.softmax(jnp.where(mask, logits, _NEG_INF), axis=-1)
    out = jnp.einsum("htk,tkv->thv", probs, kv[..., :v_dim],
                     precision=jax.lax.Precision.HIGHEST)
    return out.astype(q.dtype)


def _mla_tiles(T, H, bs, MB):
    """``(query tokens a block, pages a loop step)`` of the flat latent
    kernel, from the shapes alone.

    Query tokens: as many as put 256 rows (tokens x heads) before the
    MXU at once, two passes of its 128 rows and, at 16 heads, one
    prefill chunk of 16 tokens; the block's rows a multiple of the 8
    sublanes unless the block is the whole batch. Pages: as many as
    make 256 keys a loop step, so that both products have an
    MXU-shaped minor dimension and the loop's fixed cost (the DMA
    descriptors, the rescaling of the accumulator) is paid once per
    256 keys; never more than the table is wide."""
    qb = max(1, min(T, 256 // H))
    if qb < T:
        # tokens to a whole sublane tile
        per = next(n for n in (1, 2, 4, 8) if n * H % 8 == 0)
        qb = qb // per * per or T
    return qb, max(1, min(MB, 256 // bs))


def _mla_flat_kernel(layer_ref, sid_ref, pos_ref, bt_ref, q_ref, pool_ref,
                     o_ref, kv_buf, sems, acc_ref, m_ref, l_ref, qpos_ref,
                     *, scale, heads, block_size, v_dim):
    """Grid ``(query blocks,)``: one grid step holds ``QB`` packed query
    tokens, all their heads as the ``R = QB x H`` rows of one matrix
    (a latent page has no head axis). The block's tokens are taken RUN
    by run, a run being consecutive tokens of one sequence: the run's
    pages, and only up to the page of its largest position, come from
    the pool in HBM (``pool_ref``, whole, never sliced) by explicit
    DMA through the scalar-prefetched block table, ``PP`` pages a loop
    step into one of two buffers while the other is computed on. Each
    step is two products, ``(R, W) x (W, keys)`` and ``(R, keys) x
    (keys, v_dim)``, under one mask: a row sees a key if the row is one
    of the RUN's own (rows ``[i x H, end x H)``; another run of the
    same sequence in the block walks for itself) and the key's position
    is not past its token's. Online softmax in float32 scratch as in
    the flat kernel; rows of other runs pass through a step unchanged
    (their ``p`` is zeroed by the mask, not by ``exp``: a row that has
    met no key yet still has ``m == _NEG_INF``). The loops over a block's
    tokens and a step's pages are ``fori_loop``s, not Python loops:
    every step program traces and lowers this body anew, and unrolled
    it made a warm ``warmup()`` of 17 programs 8 s longer."""
    R = q_ref.shape[0]
    QB = R // heads
    KB = kv_buf.shape[1]
    PP = KB // block_size
    MB = bt_ref.shape[1]
    t0 = pl.program_id(0) * QB
    lyr = layer_ref[0]
    last_key = MB * block_size - 1      # the reference's table ends here

    acc_ref[...] = jnp.zeros_like(acc_ref)
    m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
    l_ref[...] = jnp.zeros_like(l_ref)

    def position_to_rows(i, carry):     # each token's position, per row
        rows = pl.ds(pl.multiple_of(i * heads, heads), heads)
        qpos_ref[rows, :] = jnp.full(
            (heads, 1), jnp.minimum(pos_ref[t0 + i], last_key), jnp.int32)
        return carry

    jax.lax.fori_loop(0, QB, position_to_rows, 0)
    row = jax.lax.broadcasted_iota(jnp.int32, (R, 1), 0)

    def pages(seq, k, slot, go):
        """Loop step ``k`` of sequence ``seq``: start (``go``) or await
        the DMA of its ``PP`` pages into buffer ``slot``. Past the
        table's width the last entry again; past the run's own pages
        whatever the table names (the null block in the engine's
        tables): masked either way."""
        def page(p, carry):
            copy = pltpu.make_async_copy(
                pool_ref.at[lyr,
                            bt_ref[seq, jnp.minimum(k * PP + p, MB - 1)]],
                kv_buf.at[slot, pl.ds(pl.multiple_of(p * block_size,
                                                     block_size),
                                      block_size)],
                sems.at[slot])
            if go:
                copy.start()
            else:
                copy.wait()
            return carry

        jax.lax.fori_loop(0, PP, page, 0)

    def run(i):
        seq = sid_ref[t0 + i]

        def same_seq(c):
            j, _ = c
            return jnp.logical_and(
                j < QB, sid_ref[t0 + jnp.minimum(j, QB - 1)] == seq)

        def take(c):
            j, last = c
            return j + 1, jnp.maximum(last, pos_ref[t0 + j])

        end, last = jax.lax.while_loop(same_seq, take,
                                       (i + 1, pos_ref[t0 + i]))
        steps = jnp.minimum(last, last_key) // KB + 1
        # the last key each row may see in this run: none for the rows
        # of other runs, the same sequence's among them
        mine = jnp.logical_and(row >= i * heads, row < end * heads)
        limit = jnp.where(mine, qpos_ref[...], -1)
        pages(seq, 0, 0, True)

        def step(k, carry):
            slot = jax.lax.rem(k, 2)

            @pl.when(k + 1 < steps)
            def _next():
                pages(seq, k + 1, 1 - slot, True)

            pages(seq, k, slot, False)
            q = q_ref[...].astype(jnp.float32)            # (R, W)
            kv = kv_buf[slot].astype(jnp.float32)         # (KB, W)
            s = jax.lax.dot_general(
                q, kv, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale   # (R, KB)
            kpos = k * KB + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            mask = kpos <= limit
            s = jnp.where(mask, s, _NEG_INF)
            m_prev = m_ref[...]                           # (R, 1)
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
            p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
            alpha = jnp.exp(m_prev - m_new)
            l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1,
                                                      keepdims=True)
            acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
                p, kv[:, :v_dim], preferred_element_type=jnp.float32)
            m_ref[...] = m_new
            return carry

        jax.lax.fori_loop(0, steps, step, 0)
        return end

    jax.lax.while_loop(lambda i: i < QB, run, 0)
    l_safe = jnp.maximum(l_ref[...], 1e-30)
    o_ref[...] = (acc_ref[...] / l_safe).astype(o_ref.dtype)


def _latent_walk(q, pages, block_tables, seq_ids, positions, layer, scale,
                 v_dim, interpret):
    """The call of ``_mla_flat_kernel``, for the two jitted entries that
    give it their names in a device trace."""
    T, H, W = q.shape
    bs = pages.shape[2]
    S, MB = block_tables.shape
    qb, pp = _mla_tiles(T, H, bs, MB)
    blocks = -(-T // qb)
    sid, pos = _block_scalars(seq_ids, positions, S, blocks * qb)
    R = qb * H

    def rows(b, lyr, sid, pos, bt):
        return (b, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(blocks,),
        in_specs=[
            pl.BlockSpec((R, W), rows, memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pl.ANY),        # the pool, in HBM
        ],
        out_specs=pl.BlockSpec((R, v_dim), rows, memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((2, pp * bs, W), pages.dtype),
            pltpu.SemaphoreType.DMA((2,)),
            pltpu.VMEM((R, v_dim), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.float32),
            pltpu.VMEM((R, 1), jnp.int32),
        ],
    )
    kernel = functools.partial(_mla_flat_kernel, scale=scale, heads=H,
                               block_size=bs, v_dim=v_dim)
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((T * H, v_dim), q.dtype),
        interpret=interpret,
    )(layer.astype(jnp.int32).reshape(1), sid, pos,
      block_tables.astype(jnp.int32), q.reshape(T * H, W), pages)
    return out.reshape(T, H, v_dim)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _mla_flat_pallas(q, pages, block_tables, seq_ids, positions, layer,
                     scale, v_dim, interpret):
    return _latent_walk(q, pages, block_tables, seq_ids, positions, layer,
                        scale, v_dim, interpret)


def mla_flat_attention(q, pages, block_tables, seq_ids, positions,
                       scale, v_dim, layer=None, use_pallas=None,
                       interpret=None):
    """Flat-ragged attention over a paged LATENT cache: multi-head
    latent attention (MLA) in its absorbed form.

    ``q [T, H, W]`` holds each head's query already in the cache's own
    space (``q_nope`` through the key half of the up-projection, then
    the rotary part); ``pages`` is the one pool of latents, ``[L, N,
    bs, W]`` with ``layer`` (stacked, read in place: hand the whole
    pool over, as :func:`ragged_flat_attention` says) or ``[N, bs,
    W]`` without; every head attends over the SAME page, whose first
    ``v_dim`` columns (the compressed part) are also the values.
    ``scale`` is the softmax scale of the expanded form
    (``(nope + rope) ** -0.5``), not of ``W``. Returns ``[T, H,
    v_dim]``, to go through the value half of the up-projection.
    Gated like :func:`ragged_flat_attention`: Mosaic on a TPU, the
    gather reference elsewhere, interpret mode on request.

    What the kernel makes of the packed batch (``_mla_flat_kernel``):
    blocks of query tokens (:func:`_mla_tiles`), and in a block every
    RUN of neighbouring tokens with one ``seq_ids`` value walks that
    sequence's pages once, up to the page of the run's largest
    position. The result is the reference's for ANY ``seq_ids`` and
    ``positions`` (a row takes keys only from its own run's walk, up
    to its own token's position, so a sequence met again further on in
    a block, as a valid token beside a stale one, is two runs that do
    not touch; ids are clipped to the table's rows, positions to its
    width, as the reference's gather and mask do), but the cost
    follows the layout: ``LLMEngine._build_batch`` packs a sequence's
    tokens side by side, so a prefill chunk reads its pages once, while
    tokens of one sequence scattered over the batch are so many runs
    and walks. Nothing tells the kernel which tokens are padding: a
    tail that still holds an earlier step's ids and positions is
    walked like any token (rows finite, to be discarded), a tail of
    zeros costs one loop step."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if pages.ndim != (3 if layer is None else 4):
        raise ValueError(
            "the latent pool is [L, N, bs, W] with `layer`, [N, bs, W] "
            f"without; got {pages.shape} and layer={layer!r}")
    block_tables = jnp.asarray(block_tables)
    seq_ids, positions = jnp.asarray(seq_ids), jnp.asarray(positions)
    if not use_pallas:
        return mla_flat_attention_reference(
            q, pages, block_tables, seq_ids, positions, float(scale),
            int(v_dim), layer=layer)
    if interpret is None:
        interpret = not _on_tpu()
    if layer is None:
        layer, pages = 0, pages[None]
    return _mla_flat_pallas(q, pages, block_tables, seq_ids, positions,
                            jnp.asarray(layer), float(scale), int(v_dim),
                            bool(interpret))


# ------------------------------- grouped queries over packed K/V rows --
def gqa_flat_attention_reference(q, pages, block_tables, seq_ids,
                                 positions, scale, kv_heads, layer=None):
    """Gather-based oracle of :func:`gqa_flat_attention`: every query
    token's pages gathered through its sequence's table row and split
    into the ``kv_heads`` value and key heads, query head ``j`` against
    kv head ``j // (H // kv_heads)``, one masked softmax. Everything in
    float32."""
    T, H, D = q.shape
    G = kv_heads
    bs = pages.shape[-2]
    MB = block_tables.shape[1]
    tbl = block_tables[seq_ids]                       # (T, MB)
    kv = pages[(tbl,) if layer is None else (layer, tbl)]
    kv = kv.reshape(T, MB * bs, 2, G, D).astype(jnp.float32)
    qg = q.astype(jnp.float32).reshape(T, G, H // G, D)
    logits = jnp.einsum("tgmd,tkgd->gmtk", qg, kv[:, :, 1],
                        precision=jax.lax.Precision.HIGHEST) * scale
    pos = jnp.arange(MB * bs, dtype=jnp.int32)
    mask = pos[None, None, None, :] <= positions[None, None, :, None]
    probs = jax.nn.softmax(jnp.where(mask, logits, _NEG_INF), axis=-1)
    out = jnp.einsum("gmtk,tkgd->tgmd", probs, kv[:, :, 0],
                     precision=jax.lax.Precision.HIGHEST)
    return out.reshape(T, H, D).astype(q.dtype)


@functools.partial(jax.jit, static_argnums=(6, 7, 8))
def _gqa_flat_pallas(q, pages, block_tables, seq_ids, positions, layer,
                     scale, kv_heads, interpret):
    """Grouped queries through the latent kernel's walk: a page row is
    ``[V | K]``, each the ``G`` kv heads side by side, and every query
    head is laid out over the row's width with zeros everywhere but
    under its own kv head's keys. The kernel's one product over the
    row's width is then ``q_j . k_(j // m)``, its values the row's
    first half, and head ``j`` keeps its kv head's ``D`` columns of
    what comes back. One page DMA serves all the heads; the products
    are ``G`` times wider than the group needs (MXU work on zeros)."""
    T, H, D = q.shape
    G = kv_heads
    eye = jnp.eye(G, dtype=q.dtype)
    wide = jnp.einsum("tgmd,gk->tgmkd", q.reshape(T, G, H // G, D), eye)
    wide = wide.reshape(T, H, G * D)
    out = _latent_walk(
        jnp.concatenate([jnp.zeros_like(wide), wide], axis=-1), pages,
        block_tables, seq_ids, positions, layer, scale, G * D, interpret)
    out = out.reshape(T, G, H // G, G, D)
    return jnp.einsum("tgmkd,gk->tgmd", out, eye).reshape(T, H, D)


def gqa_flat_attention(q, pages, block_tables, seq_ids, positions, scale,
                       kv_heads, layer=None, use_pallas=None,
                       interpret=None):
    """Flat-ragged attention of ``H`` query heads over ``kv_heads``
    shared K/V heads of any head size (grouped-query attention; ``H ==
    kv_heads`` is plain multi-head attention).

    ``q [T, H, D]``; ``pages`` ONE pool of rows ``[L, N, bs, 2 *
    kv_heads * D]`` with ``layer`` (stacked, read in place) or ``[N,
    bs, ...]`` without: a token's row is its value heads, then its key
    heads, each ``kv_heads * D`` wide, so that a page is whole lane
    tiles at any head size (Mosaic copies a page out of a pool by whole
    tiles, and refuses ``[bs, heads, 64]``) and one copy brings K and V
    of every head. Query head ``j`` attends kv head ``j // (H //
    kv_heads)`` causally over positions ``<= positions[t]`` of its
    sequence; ``scale`` is the model's to say. Returns ``[T, H, D]``.

    The packed batch is walked as :func:`mla_flat_attention` walks it
    (the same kernel body, ``_mla_flat_kernel``, under this entry's own
    name in a device trace): a run's pages once for all its query
    tokens and heads, only up to the page of its largest position, and
    the same guarantees for padded and stale tokens. Gated alike: Mosaic
    on a TPU, the gather reference elsewhere, interpret mode on
    request."""
    if use_pallas is None:
        use_pallas = _on_tpu()
    if pages.ndim != (3 if layer is None else 4) \
            or pages.shape[-1] != 2 * kv_heads * q.shape[-1] \
            or q.shape[1] % kv_heads:
        raise ValueError(
            "the pool is [L, N, bs, 2 * kv_heads * D] with `layer`, [N, "
            f"bs, ...] without, and kv_heads divides H; got q {q.shape}, "
            f"pages {pages.shape}, kv_heads={kv_heads}, layer={layer!r}")
    block_tables = jnp.asarray(block_tables)
    seq_ids, positions = jnp.asarray(seq_ids), jnp.asarray(positions)
    if not use_pallas:
        return gqa_flat_attention_reference(
            q, pages, block_tables, seq_ids, positions, float(scale),
            int(kv_heads), layer=layer)
    if interpret is None:
        interpret = not _on_tpu()
    if layer is None:
        layer, pages = 0, pages[None]
    return _gqa_flat_pallas(q, pages, block_tables, seq_ids, positions,
                            jnp.asarray(layer), float(scale),
                            int(kv_heads), bool(interpret))


@register("mla_flat_attention", differentiable=False)
def _mla_flat_op(q, pages, block_tables, seq_ids, positions, *, scale,
                 v_dim, layer=None, use_pallas=None):
    """Registered latent paged-attention op: Pallas kernel on TPU,
    gather reference elsewhere."""
    return mla_flat_attention(q, pages, block_tables, seq_ids, positions,
                              scale, v_dim, layer=layer,
                              use_pallas=use_pallas)
