"""Continuous-batching decode engine: admit, step, evict — every step.

The execution core of ``mxnet_tpu.serving.llm``. One engine iteration
(:meth:`LLMEngine.step`):

1. **admit** — while a decode slot is free and the pool can hold the
   prompt, pop the oldest waiting sequence into a slot. The prefix
   cache is consulted first (ISSUE 13): the longest registered chain
   of block-aligned prompt-prefix blocks is ref()'d into the
   sequence's table — those tokens' KV is SERVED, not recomputed, so
   the sequence starts prefilling at the first uncached token (always
   recomputing at least the last prompt token, whose logits emit the
   first generation). Admission no longer launches a dense bucketed
   prefill: the remaining prompt KV is written in CHUNKS scheduled
   into the regular step — a prefill chunk is just a multi-token
   decode, so long prompts never stall running decodes behind a
   monolithic prefill launch;
2. **plan + allocate** — a step has a TOKEN BUDGET, the longest
   packed batch the engine compiles (``max_seqs x q_tokens``: the top
   rung of ``_t_buckets``), and the scheduler deals it out. Decoding
   sequences take theirs first: one token in plain decode, or
   ``K + 1`` positions (last committed token + K draft proposals) in
   speculative decode. What is left goes to the sequences whose prompt
   is still being written, in admission order, each taking as much of
   its prompt as the budget still holds (first come, first finished);
   one the budget does not reach sits the step out, its slot
   untouched. ``prefill_chunk`` is the budget's tokens a slot, not a
   cap on a row. Blocks covering the step's KV writes are
   allocated up front (on the sequence, so every failure path frees
   them); under KV pressure the newest-admitted sequence is preempted
   (blocks freed, generation folded into its prompt, requeued — the
   position-keyed sampling PRNG resumes the exact stream);
3. **step** — ONE fixed-shape jitted launch for the whole mixed
   batch in the FLAT ragged layout: every row's query tokens packed
   into one ``[total_q_tokens]`` batch (tokens / positions / seq_ids
   / valid) + ``[max_seqs, max_blocks_per_seq]`` block tables in; the
   flat ragged kernel attends causally over the paged cache —
   per-token sequence indirection, NO per-sequence padding, so a
   mixed step computes exactly the tokens that exist; temperature /
   top-k / top-p sampling (per-sequence TRACED vectors,
   position-keyed PRNG) and the speculative accept rule run
   IN-PROGRAM on host-indexed per-row logit windows; committed
   tokens come out, KV pages are donated through. The packed length
   and the block-table width are bucketed on small warmed ladders
   (pure-decode, halvings of the budget, full) — so after
   :meth:`warmup` (every (t, mb, greedy|sampled) rung, plus the draft
   program's when speculation is on) steady state compiles NOTHING,
   no matter how the arrival/length/stop/sampling mix shifts
   (asserted via the ``backend_compile`` counter in tier-1).

Speculative decoding: a small DRAFT model proposes up to ``spec_k``
tokens per sequence (one fixed-shape draft dispatch each, its KV pages
indexed by the SAME block ids the target allocator handed out — one
strict accounting for both pools), then the chunked step scores all
``K + 1`` positions in one target dispatch and the standard accept
rule commits ``n_acc + 1`` tokens. Rejected draft KV entries are
rolled back by trimming the sequence's surplus blocks through the
strict :class:`~.kv_cache.BlockAllocator` (never bypassed); the draft
cache's committed-prefix watermark (``Sequence.draft_len``) rolls back
with them. A draft failure degrades that step to plain decode —
speculation is an optimization, never a correctness dependency.

One step in flight (:meth:`LLMEngine.step_ahead`, what the server runs):
step N+1 is scheduled, built and launched before step N is fetched and
committed, so the host's work and the fetch's round trip hide behind
the device's work. ``seq_len`` moves over what a step writes when it is
launched; a row of N+1 that feeds what N produced reads it from N's
output on the device. :meth:`LLMEngine.step` keeps the synchronous
order.

The engine is single-threaded by design (the serving worker
discipline): :class:`~.server.LLMServer` owns the thread, the queue
and the futures; the engine owns device state and determinism.
"""
from __future__ import annotations

import collections
import time

import numpy as np

from ..envutil import env_int as _env_int, env_str as _env_str
from ..adapters.bank import AdapterError, NULL_ADAPTER_PAGE
from .kv_cache import (PagedKVCache, KVCacheError, NULL_BLOCK,
                       prefix_block_hashes)
from .quant import (QuantizedWeights, quantize_weights,
                    resolve_weight_dtype, fp8_supported, FP8_NAME)
from .metrics import PhaseClock, hand_over
from .model import UnsupportedModelFeatureError
from .scheduler import Scheduler, Sequence, RUNNING, FINISHED, EVICTED
from .sampling import (TAG_SAMPLE, TAG_ACCEPT, TAG_DRAFT, row_keys,
                       sample_and_probs, spec_accept,
                       spec_accept_greedy)
from ...observability.tracing import get_tracer
from ...observability.flightrecorder import get_flightrecorder
from ...resilience import faults

__all__ = ["LLMEngine"]


class _Behind(Exception):
    """Planning ahead of the step in flight needs what only its commit
    gives (an allocation that has to preempt)."""


def _make_step_fn(model, spec_k, sampled, quantized=False, lora=False,
                  axis_name=None, wq=False, blockdiff=None):
    """Build the target step program body for (model, spec_k): ONE
    program covering chunked prefill + decode + speculative verify
    over the FLAT ragged layout — a packed ``[total_q_tokens]`` batch
    (no per-sequence padding: a mixed step computes exactly the
    tokens that exist).

    ``sampled`` selects the variant: greedy (raw argmax accept, no
    PRNG, no sorts — plain greedy traffic never pays sampling
    arithmetic) or sampled (position-keyed PRNG + the full accept
    rule). Inputs: tokens/positions/seq_ids/valid int32 [T] (packed);
    block_tables int32 [S, MB]; win_idx int32 [S, K+1] — the flat
    indices of each row's K+1 scored positions (host-computed);
    draft_tokens int32 [S, K]; draft_probs f32 [S, K, V]; n_draft
    int32 [S] (0 = plain row); sampling vectors [S] traced; counters
    int32 [S] = the ABSOLUTE index of the first token each row could
    emit (the PRNG anchor). Returns (tokens [S, K+1], n_accepted [S],
    aux, *pools): row i commits ``tokens[i, :n_accepted[i] + 1]`` —
    for plain rows that is one sampled/argmax token.

    The pools are the model's own (``model.cache_layout``): K and V
    pages, or ONE pool of latents or of packed K/V rows, then the slot
    state pools of a layout that has ``state`` (handed to the model as
    ``state=``); they follow the params, are donated and come back in
    their order. ``aux`` is whatever
    :meth:`model.decode_flat` returns after its pools (a model with
    expert layers: their load, int32 ``[4]``, or ``[5]`` from a model
    that holds a share of its experts), ``None`` (no output at
    all) for a model that returns nothing there; it sits ahead of the
    pools so that it comes back in the step's one fetch.

    ``quantized`` selects the int8-KV variant: the f32 scale pools
    ride the program right after the pages (donated with them) and
    :meth:`model.decode_flat` quantizes on write / the ragged kernel
    dequantizes on read.

    ``lora`` selects the multi-adapter variant: the AdapterBank's A/B
    factor pools enter right after the KV pools (NOT donated — they
    are shared with concurrently publishing threads) and the batch
    grows two trailing per-row vectors, a_tables int32 [S, P] (each
    row's adapter page list, NULL_ADAPTER_PAGE-padded) and a_scales
    f32 [S] (alpha/rank; 0.0 on adapter-less rows). Adapter selection
    is traced data: a mixed-adapter batch — including adapter-less
    rows through the all-zero null page — runs in this ONE fixed-shape
    program, so publish/evict/switch never compiles.

    ``axis_name`` (ISSUE 19) marks this body as the PER-SHARD half
    of a ``shard_map`` over a tensor-parallel mesh axis — it is
    threaded into :meth:`model.decode_flat`, which places the two
    in-program psums (o-projection, MLP down-projection); the accept
    rule below then runs on replicated logits, identically on every
    shard. ``None`` (the default) is the plain single-device body —
    the kwarg is only forwarded when set, so third-party models
    without SPMD support keep working unsharded.

    ``wq`` (ISSUE 20) selects the quantized-WEIGHTS variant: the
    traced ``params`` argument is the ``{"w": quantized tree, "s":
    flat scale dict}`` wrapper the engine builds from a
    :class:`~.quant.QuantizedWeights` checkpoint — unpacked here and
    forwarded as ``decode_flat(..., w_scales=...)``, so positional
    pool/batch signatures (and the donation indices) are identical to
    the f32 program and a quantized hot-swap reuses every warmed
    rung.

    ``blockdiff`` (the model's ``block_diffusion``) puts a REVEAL
    epilogue where the accept rule stands: the batch grows three
    trailing arrays, blk_idx int32 [S, B] (the flat indices of the
    block a row feeds), blk_masked int32 [S, B] (which of its positions
    are still masked) and n_reveal int32 [S] (how many this pass
    reveals; 0 for a settling pass and for a row that feeds no block),
    and the program returns, where tokens and n_accepted stand, the
    block after the reveal int32 [S, B] and which positions the pass
    revealed int32 [S, B]: each position's first choice, and by the
    model's ``remasking_strategy`` the leftmost masked ones, the most
    confident ones (by the first choice's probability), or every one
    above ``confidence_threshold`` and at least ``n_reveal``.

    Last of all come two arrays that let a step feed tokens the step
    before it produced and the host has not fetched: src int32 [T] (-1
    where the host's token stands, else a flat index into prev) and
    prev int32 [S, K+1] or [S, B] (that step's ``tokens`` output, on the
    device)."""
    import jax.numpy as jnp
    dkw = {} if axis_name is None else {"axis_name": axis_name}
    if wq:
        def _wparams(params):
            return params["w"], dict(dkw, w_scales=params["s"])
    else:
        def _wparams(params):
            return params, dkw

    def _accept(logits, win_idx, draft_tokens, draft_probs, n_draft,
                temperature, top_k, top_p, seeds, counters):
        S = win_idx.shape[0]
        K = spec_k
        win = logits[win_idx]                         # [S, K+1, V]
        if not sampled:
            return spec_accept_greedy(win, draft_tokens, n_draft)
        seeds2 = jnp.broadcast_to(seeds[:, None], (S, K + 1))
        ctr = counters[:, None] + jnp.arange(K + 1, dtype=jnp.int32)
        accept_keys = row_keys(seeds2[:, :K], ctr[:, :K], TAG_ACCEPT)
        sample_keys = row_keys(seeds2, ctr, TAG_SAMPLE)
        return spec_accept(
            win, draft_tokens, draft_probs, n_draft, temperature,
            top_k, top_p, accept_keys, sample_keys)

    def _reveal(logits, tokens, blk_idx, masked, n_reveal):
        strategy = blockdiff["remasking_strategy"]
        first = jnp.argmax(logits, axis=-1).astype(jnp.int32)[blk_idx]
        m = masked.astype(bool)
        want = n_reveal[:, None]
        if strategy == "sequential":
            reveal = m & (jnp.cumsum(masked, axis=1) <= want)
        else:
            # the first choice's probability, where a position is masked
            top = logits.max(axis=-1, keepdims=True)
            conf = 1.0 / jnp.exp(logits - top).sum(axis=-1)
            conf = jnp.where(m, conf[blk_idx], -1.0)
            order = jnp.argsort(-conf, axis=1, stable=True)
            rank = jnp.argsort(order, axis=1, stable=True)
            reveal = m & (rank < want)
            if strategy == "low_confidence_dynamic":
                high = m & (conf > blockdiff["confidence_threshold"])
                reveal = jnp.where(
                    high.sum(axis=1, keepdims=True) >= want, high, reveal)
        return (jnp.where(reveal, first, tokens[blk_idx]),
                reveal.astype(jnp.int32))

    layout = getattr(model, "cache_layout", None) or {"kind": "kv"}
    n_pages = 4 if quantized else 2 if layout["kind"] == "kv" else 1
    n_pools = n_pages + len(layout.get("state", ()))

    def step(params, *args):
        # params, the pools, [the bank's A/B pools,] the 14 batch
        # arrays [, a_tables, a_scales], src, prev
        pools, args = args[:n_pools], args[n_pools:]
        *args, src, prev = args
        p, mkw = _wparams(params)
        if lora:
            (a_pages, b_pages), args = args[:2], args[2:]
            *args, a_tables, a_scales = args
            mkw = dict(mkw, adapter=(a_pages, b_pages, a_tables,
                                     a_scales))
        if quantized:
            mkw = dict(mkw, k_scales=pools[2], v_scales=pools[3])
        if n_pools > n_pages:
            mkw = dict(mkw, state=pools[n_pages:])
        if blockdiff:
            *args, blk_idx, blk_masked, n_reveal = args
        tokens, positions, seq_ids, valid, block_tables, *accept = args
        # a token the step before produced, read where it stands
        tokens = jnp.where(src >= 0,
                           prev.reshape(-1)[jnp.maximum(src, 0)], tokens)
        # a pool of rows (latents, packed K/V) goes where the K pages
        # go, and has no V pool
        k_pages, v_pages = (*pools[:n_pages], None)[:2]
        logits, *pools2 = model.decode_flat(
            p, tokens, positions, seq_ids, valid, k_pages, v_pages,
            block_tables, **mkw)
        aux = pools2.pop() if len(pools2) > n_pools else None
        if blockdiff:
            toks, n_acc = _reveal(logits, tokens, blk_idx, blk_masked,
                                  n_reveal)
        else:
            toks, n_acc = _accept(logits, *accept)
        return (toks, n_acc, aux, *pools2)

    return step


def _make_draft_fn(model, sampled, quantized=False, axis_name=None,
                   wq=False):
    """Build the draft proposal program body: the same flat layout
    against the draft cache, returning one proposal per row plus
    (sampled variant) the full adjusted probability vector the accept
    rule needs. The greedy variant proposes by raw argmax — the
    greedy accept rule never reads probabilities, so it returns zeros
    there. ``last_idx`` int32 [S]: the flat index of each row's last
    fed token (0 for inactive rows; outputs discarded).
    ``axis_name``: see :func:`_make_step_fn` — the draft rides the
    same tensor-parallel mesh as the target (same block ids, same
    head split). ``wq``: quantized-weights draft (ISSUE 20's int8
    draft for speculative decoding) — same ``{"w", "s"}`` params
    wrapper as the target step."""
    import jax.numpy as jnp
    dkw = {} if axis_name is None else {"axis_name": axis_name}
    if wq:
        def _wparams(params):
            return params["w"], dict(dkw, w_scales=params["s"])
    else:
        def _wparams(params):
            return params, dkw

    def _propose(logits, last_idx, temperature, top_k, top_p, seeds,
                 counters):
        last_logits = logits[last_idx]                # [S, V]
        if not sampled:
            toks = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)
            return toks, jnp.zeros_like(last_logits)
        keys = row_keys(seeds, counters, TAG_DRAFT)
        return sample_and_probs(last_logits, temperature, top_k,
                                top_p, keys)

    if quantized:
        def draft(params, k_pages, v_pages, k_scales, v_scales,
                  tokens, positions, seq_ids, valid, block_tables,
                  last_idx, temperature, top_k, top_p, seeds,
                  counters):
            p, mkw = _wparams(params)
            logits, kp2, vp2, ks2, vs2 = model.decode_flat(
                p, tokens, positions, seq_ids, valid, k_pages,
                v_pages, block_tables, k_scales=k_scales,
                v_scales=v_scales, **mkw)
            toks, probs = _propose(logits, last_idx, temperature,
                                   top_k, top_p, seeds, counters)
            return toks, probs, kp2, vp2, ks2, vs2
        return draft

    def draft(params, k_pages, v_pages, tokens, positions, seq_ids,
              valid, block_tables, last_idx, temperature, top_k,
              top_p, seeds, counters):
        p, mkw = _wparams(params)
        logits, k_pages2, v_pages2 = model.decode_flat(
            p, tokens, positions, seq_ids, valid, k_pages,
            v_pages, block_tables, **mkw)
        toks, probs = _propose(logits, last_idx, temperature, top_k,
                               top_p, seeds, counters)
        return toks, probs, k_pages2, v_pages2

    return draft


def _make_copy_fn(n_arrays):
    """Build the copy-on-write program body: copy page row ``src`` of
    every pool array onto row ``dst`` (axis 1 — the block axis of the
    ``[L, N, ...]`` pools). src/dst enter as traced scalars, so one
    fixed-shape program (warmed once) serves every COW — a cache hit
    diverging from its shared prefix never compiles anything."""
    def copy(*args):
        arrs, src, dst = args[:-2], args[-2], args[-1]
        return tuple(a.at[:, dst].set(a[:, src]) for a in arrs)
    return copy


def _cached_program(model, kind, key, build):
    """One jitted program per (model, kind, key), cached ON the model
    object: engines sharing a model (server restart, test fixtures,
    fleet replicas) reuse compiled code instead of re-tracing — XLA
    already caches per shape inside the jit, this makes the jit
    object itself survive the engine."""
    progs = model.__dict__.setdefault("_mxtpu_llm_programs", {})
    full = (kind,) + key
    if full not in progs:
        progs[full] = build()
    return progs[full]


def _resolve_engine_mesh(mesh, model, draft_model):
    """Normalize the engine's ``mesh`` argument to a flat 1-axis
    ``("tp",)`` Mesh (or None = unsharded): accepts a Mesh, a spec
    string (:func:`~....parallel.mesh.llm_mesh` grammar), or None
    (falls back to ``MXNET_TPU_LLM_MESH``). Any axis other than
    ``tp`` must have extent 1 — dp replica groups belong to
    :class:`~.server.LLMServer`, which hands each engine its own tp
    row. Validates the head/d_ff splits for the target AND draft
    models up front so misconfiguration fails at construction, not
    at trace time. Returns ``(mesh_or_none, tp)``."""
    from jax.sharding import Mesh
    if mesh is None:
        spec = _env_str("MXNET_TPU_LLM_MESH", "").strip()
        if spec:
            mesh = spec
    if mesh is None:
        return None, 1
    if isinstance(mesh, str):
        from ...parallel.mesh import llm_mesh
        mesh = llm_mesh(mesh)
    extents = dict(mesh.shape)
    extra = {a: e for a, e in extents.items()
             if a != "tp" and int(e) != 1}
    if extra:
        raise ValueError(
            f"engine mesh must be tensor-parallel only; axes {extra} "
            f"have extent > 1 (dp replica groups are LLMServer's — "
            f"pass the dp mesh there, it hands each engine a tp row)")
    tp = int(extents.get("tp", 1))
    for which, m in (("model", model), ("draft_model", draft_model)):
        if m is None:
            continue
        if m.num_heads % tp:
            raise ValueError(
                f"{which} has {m.num_heads} heads, not divisible by "
                f"tp={tp}")
        d_ff = getattr(getattr(m, "config", None), "d_ff", None)
        if d_ff is not None and d_ff % tp:
            raise ValueError(
                f"{which} has d_ff {d_ff}, not divisible by tp={tp}")
    if tuple(mesh.axis_names) != ("tp",):
        devs = np.asarray(list(mesh.devices.flat))
        mesh = Mesh(devs, ("tp",))
    return mesh, tp


def _place_param_tree(params, model, mesh):
    """Place a param pytree onto ``mesh`` per the model's
    :meth:`param_specs` (column/row Megatron split, everything else
    replicated). Flattened against the PARAMS treedef so the spec
    tree only needs to be a tree prefix — and so a PartitionSpec
    never gets mistaken for a container by ``tree_map``."""
    import jax
    from ...parallel.mesh import place_global
    specs = model.param_specs(axis="tp")
    leaves, treedef = jax.tree_util.tree_flatten(params)
    spec_leaves = treedef.flatten_up_to(specs)
    placed = [place_global(a, mesh, s)
              for a, s in zip(leaves, spec_leaves)]
    return jax.tree_util.tree_unflatten(treedef, placed)


def _resolve_kv_dtype(name):
    """Map an ``fp8`` KV-dtype request onto the backend: returns
    ``(dtype_name, fell_back)`` — ``float8_e4m3fn`` where the stack
    carries the dtype, else ``int8`` with ``fell_back=True`` (the
    caller counts a warning; serving proceeds at the next-best
    quantized width instead of crashing a fleet config on an older
    backend). Non-fp8 names pass through untouched."""
    low = str(name).strip().lower()
    if low in ("fp8", "float8", "e4m3", "float8_e4m3", FP8_NAME):
        if fp8_supported():
            return FP8_NAME, False
        return "int8", True
    return name, False


def _place_scales(scales, model, mesh):
    """Place a flat per-channel weight-scale dict onto ``mesh``: each
    scale vector follows its weight's output axis per the model's
    :meth:`weight_scale_specs` (replicated when the model doesn't
    declare scale specs — correct, just not bandwidth-minimal)."""
    from jax.sharding import PartitionSpec as P
    from ...parallel.mesh import place_global
    specs = model.weight_scale_specs(axis="tp") \
        if hasattr(model, "weight_scale_specs") else {}
    return {k: place_global(v, mesh, specs.get(k, P()))
            for k, v in scales.items()}


def _spmd_wrap(fn, mesh, cache, param_specs, extra, aux=False):
    """Wrap a step/draft program body in ``shard_map`` over the
    engine's ``("tp",)`` mesh: params enter per ``param_specs``, the
    KV pools (and int8 scale pools) head-sharded, everything else —
    LoRA factor pools, the packed batch, block tables, sampling
    vectors — replicated. The first two outputs (tokens + accepts,
    or proposals + probs) come back replicated (the in-body psums
    make every shard compute identical logits), as does the step's
    ``aux`` slot (``aux=True``); the pools come back sharded as they
    went in. The collectives live INSIDE ``fn``
    (see ``TinyDecoder.decode_flat``), so jitting the wrapped fn
    yields the ONE donated whole-step program per (mesh, bucket,
    variant). ``extra`` = (replicated leading pool count, replicated
    batch arg count)."""
    from jax import shard_map
    from jax.sharding import PartitionSpec as P
    pool, scale = cache.pool_spec(), cache.scale_spec()
    pools = [pool, pool] + ([scale, scale] if cache.quantized else [])
    n_lora, n_batch = extra
    in_specs = tuple([param_specs] + pools
                     + [P()] * (n_lora + n_batch))
    out_specs = tuple([P(), P()] + [P()] * aux + pools)
    return shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs)


class LLMEngine:
    """Token-level scheduler + ONE fixed-shape jitted chunked step.

    The model protocol: everything the engine calls and reads of
    ``model`` (:class:`~.model.TinyDecoder` is the reference
    implementation; ``params`` is its pytree, opaque to the engine).

    - ``decode_flat(params, tokens, positions, seq_ids, valid,
      k_pages, v_pages, block_tables)``, a pure, jittable function:
      the packed step. ``tokens/positions/seq_ids/valid`` int32
      ``[T]`` (``valid[t] == 0`` is bucket padding, written to the
      null block, its output discarded; ``seq_ids[t]`` is a row of
      ``block_tables`` int32 ``[S, MB]``); the pools stacked over
      layers, ``[L, N, bs, ...]``, ``v_pages`` ``None`` for a latent
      cache. Returns ``(logits [T, V], *pools[, aux])``: the updated
      pools in the order they came (donated), then at most one more
      array, fetched with the step's tokens (expert load, see
      :func:`_make_step_fn`). Keywords are passed only with the
      feature that needs them: ``k_scales=, v_scales=`` (quantized
      pages; their updated scale pools come back after the pages),
      ``adapter=`` (an adapter bank), ``axis_name=`` (a tp mesh),
      ``w_scales=`` (quantized weights), ``state=`` (the slot state
      pools of a layout that has any, in the layout's order; they come
      back after the pages, in that order).
    - ``cache_layout``, what a token leaves in one layer of the cache:
      ``{"kind": "kv", "num_heads": H, "head_dim": D}``, ``{"kind":
      "latent", "latent_dim": W}`` or ``{"kind": "kv_packed",
      "num_heads": G, "head_dim": D}`` (one pool of rows of ``2 x G x
      D``); absent, K and V pools of ``[num_heads, head_dim]``. With
      ``"layers": n`` only ``n`` layers leave pages. With
      ``"window_layers"`` and ``"window": W`` those layers see the ``W``
      keys up to a token's own: a page is then one layer's, every layer
      draws on one pool, a window layer's pages behind the window go
      back to it before each step (:meth:`_allocate`), and the step is
      handed one table a layer, ``block_tables [L, S, MB]``. With
      ``"state": {name: (layers, *shape)}`` every SLOT (a row of
      ``block_tables``, what ``seq_ids`` names) also holds a fixed
      float32 state a layer that its tokens overwrite: the engine keeps
      the pools ``[layers, max_seqs, *shape]``, donates them through
      the step like the pages and never clears a slot. The model starts
      a run whose first token sits at position 0 from zeros (an
      admission, also after preemption: both restart at position 0)
      and lets a token with ``valid == 0`` touch nothing. Such state
      can neither be resumed from a prefix hit nor rolled back after a
      rejected draft: the engine keeps the prefix cache off for the
      model (``prefix_cache=None``) and its ``refused_features`` name
      ``prefix_cache`` and ``draft_model``.
    - ``block_diffusion`` (optional): ``None``, or how a model that
      generates by diffusion over blocks does it (``block_length`` B,
      ``mask_token_id``, ``denoising_steps``, ``remasking_strategy``,
      ``confidence_threshold``). Its rows are of a THIRD kind beside
      prefill and decode. A prompt's leading whole blocks are written
      in chunks that end on block boundaries and emit nothing; the
      rest of it sits, revealed, in the first generated block. A
      generating row then feeds its open block, B tokens at positions
      ``seq_len .. seq_len + B - 1`` (a masked position as the mask
      token), every pass: denoising passes that each reveal positions
      (the step's reveal epilogue, :func:`_make_step_fn`) and, once
      none is masked, one settling pass whose K/V stands; ``seq_len``
      moves by B at that pass alone, and the block's tokens are
      answered then (those past ``max_new_tokens`` or a stop token
      dropped). Rows in different passes share a step with each other
      and with prefill chunks. Which positions are masked is the row's
      own state (``Sequence.blk_masked``), never read off the tokens.
      The model masks block-causally and returns logits that are for
      the position they stand at. Preemption folds the settled blocks
      into the prompt and starts the open block again. Such a model's
      ``refused_features`` name ``draft_model``, ``prefix_cache`` and
      ``sampling`` (a request with a temperature is refused at
      submission).
    - ``vocab_size``, ``max_context``, ``num_layers``, ``num_heads``,
      ``head_dim``: ints.
    - Optional: ``param_specs(axis=)`` and ``weight_scale_specs(
      axis=)`` (PartitionSpec pytrees, read under a tp mesh);
      ``refused_features`` (names of engine arguments the model's
      block does not take, refused at construction).

    Nothing else: no dense ``forward``, no per-sequence decode.

    Config resolution: constructor arg > ``MXNET_TPU_LLM_*`` env var >
    default. ``max_context`` must be a multiple of ``block_size`` (a
    preempted near-full prompt must re-prefill whole); ``num_blocks``
    must leave room for at least one full-context sequence, which also
    guarantees a lone sequence can never deadlock on allocation.
    ``prefill_chunk`` (``MXNET_TPU_LLM_PREFILL_CHUNK``) is the step's
    token budget a slot: a step feeds at most ``max_seqs x
    max(prefill_chunk, spec_k + 1)`` tokens, the decoding rows' first
    and the rest to prefilling rows in admission order, however long a
    row's share comes out; ``draft_model``/``draft_params`` +
    ``spec_k`` (``MXNET_TPU_LLM_SPEC_K``) enable speculative decoding
    (the draft must share the target's vocab and cover its context).
    ``clock``: the :class:`~.metrics.PhaseClock` of the thread that
    steps this engine (``LLMServer`` hands its own to every engine it
    drives); left out, the engine makes one and marks its five phases.
    """

    def __init__(self, model, params, max_seqs=None, block_size=None,
                 num_blocks=None, max_context=None, prefill_chunk=None,
                 draft_model=None, draft_params=None, spec_k=None,
                 stats=None, dtype="float32", breaker=None,
                 prefix_cache=None, kv_dtype=None, adapter_bank=None,
                 mesh=None, weight_dtype=None, weight_calib=None,
                 draft_weight_dtype=None, clock=None):
        import jax
        import jax.numpy as jnp
        self.model = model
        # what the model's block does not take is refused here, by
        # the argument's name
        self._refused = tuple(getattr(model, "refused_features", ()))
        self._refuse_given("adapter_bank", adapter_bank is not None)
        self._refuse_given("draft_model", draft_model is not None)
        # SPMD decode (ISSUE 19): constructor arg (Mesh or spec
        # string) > MXNET_TPU_LLM_MESH env > unsharded. The ENGINE
        # mesh is tensor-parallel only — dp replica groups are
        # LLMServer's job (one engine per tp row behind one
        # scheduler), so a dp>1 mesh here is a config error, not a
        # silent absorb.
        self.mesh, self.tp = _resolve_engine_mesh(mesh, model,
                                                  draft_model)
        self._axis_name = "tp" if self.mesh is not None else None
        self._mesh_key = None if self.mesh is None else (
            tuple(self.mesh.axis_names),
            tuple(dict(self.mesh.shape).items()),
            tuple(d.id for d in self.mesh.devices.flat))
        self.spmd_dispatches = 0
        self._refuse_given("mesh", self.mesh is not None,
                           "a tensor-parallel (tp) mesh")
        if adapter_bank is not None:
            d_model = model.num_heads * model.head_dim
            if (adapter_bank.num_layers != model.num_layers
                    or adapter_bank.d_model != d_model):
                raise ValueError(
                    f"adapter bank shaped for {adapter_bank.num_layers}"
                    f" layers x d_model {adapter_bank.d_model}, model "
                    f"has {model.num_layers} x {d_model}")
        self.bank = adapter_bank
        if max_seqs is None:
            max_seqs = _env_int("MXNET_TPU_LLM_MAX_SEQS", 8)
        if block_size is None:
            block_size = _env_int("MXNET_TPU_LLM_BLOCK_SIZE", 16)
        if max_context is None:
            max_context = _env_int("MXNET_TPU_LLM_MAX_CONTEXT",
                                   model.max_context)
        if max_context > model.max_context:
            raise ValueError(
                f"max_context {max_context} exceeds the model's "
                f"{model.max_context}")
        if max_context % block_size:
            raise ValueError(
                f"max_context {max_context} must be a multiple of "
                f"block_size {block_size}")
        self.max_seqs = int(max_seqs)
        self.max_context = int(max_context)
        if prefill_chunk is None:
            prefill_chunk = _env_int("MXNET_TPU_LLM_PREFILL_CHUNK", 16)
        if prefill_chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1, got {prefill_chunk}")
        self.prefill_chunk = min(int(prefill_chunk), self.max_context)
        if spec_k is None:
            spec_k = _env_int("MXNET_TPU_LLM_SPEC_K",
                              3 if draft_model is not None else 0)
        if spec_k < 0:
            raise ValueError(f"spec_k must be >= 0, got {spec_k}")
        self.spec_k = int(spec_k) if draft_model is not None else 0
        self.draft_model = draft_model if self.spec_k > 0 else None
        # a model that generates by diffusion over blocks (the model
        # protocol): a generating row feeds one block a step
        self._blockdiff = getattr(model, "block_diffusion", None)
        self._block = 0
        if self._blockdiff:
            self._block = B = int(self._blockdiff["block_length"])
            if self.max_context % B:
                raise ValueError(
                    f"max_context {self.max_context} must be a multiple "
                    f"of the model's block_length {B}")
            # what each denoising pass of a block reveals
            steps = int(self._blockdiff["denoising_steps"])
            base, more = divmod(B, steps)
            self._reveal_plan = [base + (j < more) for j in range(steps)]
        # what a generating row feeds a step
        row_tokens = self._block or self.spec_k + 1
        # the step's token budget a slot: a slot's share of prompt
        # tokens or what a generating row feeds (a K+1-position
        # speculative verify, a block), whichever is wider
        self.q_tokens = max(self.prefill_chunk, row_tokens)
        # the FLAT step packs every row's query tokens into one
        # [total_q_tokens] batch — no per-sequence padding, so a
        # mixed step computes exactly the tokens that exist. The
        # packed length is bucketed on a ladder of at most four rungs
        # (all rows decode/verify, a half and a quarter of the
        # budget, the full budget), and the BLOCK-TABLE width on a
        # two-rung ladder (a dispatch whose longest row holds half
        # the table attends over half the pages). Every
        # (t, mb, variant) rung is warmed, so selection is
        # recompile-free.
        t_lo = self.max_seqs * row_tokens
        t_hi = max(t_lo, self.max_seqs * self.q_tokens)
        # the middle rungs are the budget's half and quarter (rounded
        # up), each where it lies above the all-decode rung: a prompt
        # goes into a step whole, so a step that admits one beside
        # the decoding rows needs any length up to the budget, and
        # one longer than a quarter of it then computes under twice
        # its tokens. At most S - 1 of them, so an engine of S slots
        # warms at most S + 1 rungs: one slot never mixes rows
        halvings = (-(-t_hi // 2), -(-t_hi // 4))[:self.max_seqs - 1]
        mids = {m for m in halvings if m > t_lo}
        self._t_buckets = sorted({t_lo, t_hi} | mids)
        # what one step may feed: the top rung, dealt out by _schedule
        # (the draft program's top rung is the same length)
        self._step_budget = t_hi
        # draft feeds are 1-2 tokens per row in steady state
        # (catch-up + proposal) and chunk-wide during prefill
        # mirroring
        d_lo = self.max_seqs * min(2, self.q_tokens)
        self._draft_t_buckets = sorted(
            {d_lo, t_hi} | {max(d_lo, m) for m in mids})
        mb = max_context // block_size
        self._mb_widths = sorted({max(1, -(-mb // 2)), mb})
        # the pages a full-context sequence holds: a block, or under
        # window layers (a page is then one layer's) every block of each
        # full layer and, of each window layer, what its window holds
        # over one step and the step in flight
        layout = getattr(model, "cache_layout", None) or {"kind": "kv"}
        seq_pages = mb
        if layout.get("window_layers"):
            seq_pages = PagedKVCache.seq_pages_for(
                layout.get("layers", model.num_layers),
                layout["window_layers"], layout["window"], block_size,
                t_hi, max_context)
        if num_blocks is None:
            num_blocks = _env_int("MXNET_TPU_LLM_NUM_BLOCKS",
                                  self.max_seqs * seq_pages + 1)
        if num_blocks - 1 < seq_pages:
            raise ValueError(
                f"num_blocks {num_blocks} cannot hold one full-context "
                f"sequence ({seq_pages} pages + the null block)")
        # cross-request prefix caching (ISSUE 13): constructor arg >
        # MXNET_TPU_LLM_PREFIX_CACHE env > on. Hits only rewrite host
        # state (block tables, start offsets) — cache hit vs miss can
        # never change a program shape.
        # A model whose slots hold state has nothing to resume a
        # prefix hit from: asked for, the cache is refused; left to
        # the default, it is off for that model (``stats()`` says so).
        self._refuse_given("prefix_cache", prefix_cache is True,
                           "a prefix cache (a hit has no slot state to "
                           "resume from)")
        if prefix_cache is None:
            prefix_cache = "prefix_cache" not in self._refused and bool(
                _env_int("MXNET_TPU_LLM_PREFIX_CACHE", 1))
        self.prefix_enabled = bool(prefix_cache)
        # quantized KV storage: constructor arg >
        # MXNET_TPU_LLM_KV_DTYPE env > the float `dtype` arg. "fp8"
        # resolves to float8_e4m3fn where the backend has it, else
        # int8 with a counted warning (ISSUE 20 availability guard).
        if kv_dtype is None:
            kv_dtype = _env_str("MXNET_TPU_LLM_KV_DTYPE", dtype)
        kv_dtype, kv_fell_back = _resolve_kv_dtype(kv_dtype)
        self.kv_dtype_fallbacks = 0
        if kv_fell_back:
            import warnings
            self.kv_dtype_fallbacks = 1
            if stats is not None:
                stats.record_quant_fallback()
            warnings.warn(
                "fp8 KV requested but float8_e4m3fn is unavailable on "
                "this backend; serving int8 KV instead", RuntimeWarning,
                stacklevel=2)
        # the pool's shape is the model's to say (cache_layout): K and
        # V pools of [H, D] a slot, or one pool of latents, which is
        # never quantized
        if (layout["kind"] != "kv"
                and kv_dtype in PagedKVCache.QUANTIZED_DTYPES):
            raise UnsupportedModelFeatureError(
                f"kv_dtype: a {layout['kind']} pool does not take "
                f"{kv_dtype} pages")
        self.cache = PagedKVCache.for_model(
            model, block_size, num_blocks, max_context,
            max_seqs=self.max_seqs, step_tokens=self._step_budget,
            dtype=kv_dtype, prefix_cache=self.prefix_enabled,
            mesh=self.mesh)
        # each layer's own table in a step, where layers hold different
        # pages (window and full layers); the cache's count of window
        # pages given back when the gauges were last recorded
        self._windowed = self.cache.windowed
        self._window_released = 0
        self.quantized = self.cache.quantized
        self.scheduler = Scheduler(self.max_seqs)
        self._stats = stats
        self._flight = get_flightrecorder()
        # the serving thread's phase clock: the server's, shared with
        # its loop and its other engines, or this engine's own when it
        # is stepped with no server around it
        self._served = clock is not None
        self._clock = clock if clock is not None else PhaseClock()
        self._launched = 0      # programs launched since the hand-over
        self._step_rung = 0     # the packed length the last one took
        if adapter_bank is not None and stats is not None:
            adapter_bank.attach_stats(stats)
        if self.prefix_enabled:
            self.cache.on_prefix_evict = self._on_prefix_evict
        # engine-local prefix counters (mirrored onto mxtpu_llm_* when
        # stats is attached; always available to tests/tools)
        self.prefix_lookups = 0
        self.prefix_hits = 0
        self.prefill_tokens_saved = 0
        # quantized weights (ISSUE 20): `params` may already be a
        # QuantizedWeights checkpoint (deploy/fleet hand-off), or a
        # f32 tree quantized here per weight_dtype arg >
        # MXNET_TPU_LLM_WEIGHT_DTYPE env > full precision. The engine
        # params become the {"w": tree, "s": scales} wrapper — ONE
        # traced argument, so every positional pool/batch index (and
        # the donation tuple) matches the f32 program exactly.
        qw = self._resolve_weight_input(params, weight_dtype,
                                        weight_calib,
                                        "MXNET_TPU_LLM_WEIGHT_DTYPE")
        self._refuse_given("weight_dtype", qw is not None,
                           "int8/fp8 weights")
        self.weight_dtype = "float32" if qw is None else qw.dtype
        self.weight_calib = None if qw is None else qw.method
        self.weight_quantized = qw is not None
        if qw is None:
            self._params = jax.tree_util.tree_map(jnp.asarray, params)
            if self.mesh is not None:
                self._params = _place_param_tree(self._params, model,
                                                 self.mesh)
            # of the placed leaves: asking the given ones would bring
            # every device-resident weight to the host to count it
            leaves = jax.tree_util.tree_leaves(self._params)
            self.weight_bytes = int(sum(a.nbytes for a in leaves))
            self.weight_params = int(sum(a.size for a in leaves))
            if leaves:
                # unquantized weights serve in their own dtype
                self.weight_dtype = str(
                    max(leaves, key=lambda a: a.size).dtype)
        else:
            self.weight_bytes = qw.nbytes()
            self.weight_params = qw.num_params()
            qp = jax.tree_util.tree_map(jnp.asarray, qw.params)
            sc = {k: jnp.asarray(v) for k, v in qw.scales.items()}
            if self.mesh is not None:
                qp = _place_param_tree(qp, model, self.mesh)
                sc = _place_scales(sc, model, self.mesh)
            self._params = {"w": qp, "s": sc}
        if self._stats is not None:
            self._stats.record_weight_quant(
                self.weight_dtype, self.weight_bytes,
                self.weight_params // max(1, self.tp))
        # donation is a TPU/HBM lever; CPU backends ignore it with a
        # warning per call site, so only request it where it works
        from ...ops.flash_attention import _on_tpu
        n_pools = len(self.cache.pools())
        donate = tuple(range(1, 1 + n_pools)) if _on_tpu() else ()
        # two VARIANTS (greedy / sampled) x two widths of the one
        # step program — all warmed, so variant+width selection at
        # dispatch time is recompile-free. Cached on the model object
        # so engines sharing a model reuse compiled programs. The
        # adapter-bank variant keys on the bank's pool geometry, so a
        # bank-less engine shares nothing with (and costs nothing of)
        # the multi-LoRA program set. The A/B pools themselves are
        # NEVER donated: publisher threads install into them while
        # steps are in flight, and donation positions (1..n_pools)
        # stay untouched because the factor pools enter after the KV
        # pools.
        lora = self.bank is not None
        lora_key = None if not lora else (
            self.bank.num_pages, self.bank.max_pages_per_adapter,
            self.bank.page_rank)

        def _build_step(s):
            fn = _make_step_fn(model, self.spec_k, s, self.quantized,
                               lora=lora, axis_name=self._axis_name,
                               wq=self.weight_quantized,
                               blockdiff=self._blockdiff)
            if self.mesh is not None:
                pspecs = model.param_specs(axis="tp")
                if self.weight_quantized:
                    pspecs = {"w": pspecs, "s": self._scale_spec_dict(
                        model, self._params["s"])}
                fn = _spmd_wrap(fn, self.mesh, self.cache, pspecs,
                                self._step_extra_specs(lora), aux=True)
            return jax.jit(fn, donate_argnums=donate)

        # a model that refuses sampling runs the greedy variant alone
        self._variants = (False,) if "sampling" in self._refused \
            else (False, True)
        self._step_jits = {
            sampled: _cached_program(
                model, "step",
                (self.spec_k, sampled, self.quantized, donate,
                 lora_key, self._mesh_key, self.weight_dtype),
                lambda s=sampled: _build_step(s))
            for sampled in self._variants}
        if self.draft_model is not None:
            if draft_model.vocab_size != model.vocab_size:
                raise ValueError(
                    f"draft vocab {draft_model.vocab_size} != target "
                    f"vocab {model.vocab_size}")
            if draft_model.max_context < self.max_context:
                raise ValueError(
                    f"draft max_context {draft_model.max_context} < "
                    f"engine max_context {self.max_context}")
            # the draft's pages are indexed by the SAME block ids the
            # target allocator hands out — its own allocator is never
            # touched, so there is exactly one strict accounting
            self.draft_cache = PagedKVCache.for_model(
                draft_model, block_size, num_blocks, max_context,
                dtype=kv_dtype, mesh=self.mesh)
            # int8 draft (ISSUE 20): the cheap-draft lever — draft
            # quality only moves the accept rate, never the committed
            # stream (the accept rule guarantees target-distribution
            # output), so the draft is the safest place to shed bytes
            dqw = self._resolve_weight_input(
                draft_params, draft_weight_dtype, weight_calib,
                "MXNET_TPU_LLM_DRAFT_WEIGHT_DTYPE")
            self.draft_weight_dtype = \
                "float32" if dqw is None else dqw.dtype
            self.draft_weight_quantized = dqw is not None
            if dqw is None:
                self._draft_params = jax.tree_util.tree_map(
                    jnp.asarray, draft_params)
                if self.mesh is not None:
                    self._draft_params = _place_param_tree(
                        self._draft_params, draft_model, self.mesh)
            else:
                dqp = jax.tree_util.tree_map(jnp.asarray, dqw.params)
                dsc = {k: jnp.asarray(v)
                       for k, v in dqw.scales.items()}
                if self.mesh is not None:
                    dqp = _place_param_tree(dqp, draft_model,
                                            self.mesh)
                    dsc = _place_scales(dsc, draft_model, self.mesh)
                self._draft_params = {"w": dqp, "s": dsc}

            def _build_draft(s):
                fn = _make_draft_fn(draft_model, s, self.quantized,
                                    axis_name=self._axis_name,
                                    wq=self.draft_weight_quantized)
                if self.mesh is not None:
                    dspecs = draft_model.param_specs(axis="tp")
                    if self.draft_weight_quantized:
                        dspecs = {"w": dspecs,
                                  "s": self._scale_spec_dict(
                                      draft_model,
                                      self._draft_params["s"])}
                    fn = _spmd_wrap(
                        fn, self.mesh, self.draft_cache,
                        dspecs, (0, 11))
                return jax.jit(fn, donate_argnums=donate)

            self._draft_jits = {
                sampled: _cached_program(
                    draft_model, "draft",
                    (sampled, self.quantized, donate,
                     self._mesh_key, self.draft_weight_dtype),
                    lambda s=sampled: _build_draft(s))
                for sampled in (False, True)}
        else:
            self.draft_cache = None
            self.draft_weight_dtype = None
            self.draft_weight_quantized = False
        # the copy-on-write program: one fixed-shape jitted copy of
        # block row src -> dst across every pool array (target K/V,
        # quant scales, draft pools) — warmed once, dispatched when a
        # sequence first writes into a block it still shares
        if self.prefix_enabled:
            n_arrs = len(self._cow_arrays())
            cow_donate = tuple(range(n_arrs)) if _on_tpu() else ()

            def _build_cow():
                fn = _make_copy_fn(n_arrs)
                if self.mesh is not None:
                    # the COW program must carry the pools' sharding
                    # through: an unconstrained jit would satisfy its
                    # default (single-device) placement by RESHARDING
                    # the pools on the first cache-hit divergence —
                    # silently unsharding the fleet's KV and
                    # recompiling every step program. shard_map pins
                    # in/out layouts to the head-sharded specs.
                    from jax import shard_map
                    from jax.sharding import PartitionSpec as P
                    pool = self.cache.pool_spec()
                    scale = self.cache.scale_spec()
                    per_cache = [pool, pool] + (
                        [scale, scale] if self.quantized else [])
                    arr_specs = per_cache * (n_arrs // len(per_cache))
                    fn = shard_map(
                        fn, mesh=self.mesh,
                        in_specs=tuple(arr_specs) + (P(), P()),
                        out_specs=tuple(arr_specs))
                return jax.jit(fn, donate_argnums=cow_donate)

            self._cow_jit = _cached_program(
                model, "cow", (n_arrs, self.quantized, cow_donate,
                               self.draft_model is not None,
                               self._mesh_key),
                _build_cow)
        else:
            self._cow_jit = None
        self._warmed = False
        # reusable per-width host batch buffers (target + draft) and
        # a shared position ramp — per-step host allocations compete
        # directly with XLA for the core on small hosts
        self._bufs = {}
        self._bufs_turn = 0
        self._draft_bufs = {}
        self._arange = np.arange(self._step_budget, dtype=np.int32)
        self._device_get = jax.device_get
        # one step in flight (step_ahead): its record until its results
        # are fetched and committed; the tokens output of the last step
        # launched, which the next one may read on the device; and the
        # last fetched one's, on the host
        self._inflight = None
        # (a device array, as a step's output is: a numpy one would key
        # the warmed programs' dispatch apart from serving's)
        self._feed = jnp.zeros((self.max_seqs,
                                self._block or self.spec_k + 1), jnp.int32)
        if self.mesh is not None:
            # placed as a step's output is, or the first step compiles
            from jax.sharding import PartitionSpec as P
            from ...parallel.mesh import place_global
            self._feed = place_global(self._feed, self.mesh, P())
        self._fed = None
        # the last dispatch's expert load (a model with expert
        # layers), host ints
        self._moe_load = None
        # a model whose slots hold state: rows whose state a step
        # advanced, and runs begun at position 0, over the engine's life
        self._has_state = bool(self.cache.state_pools)
        self.state_rows = 0
        self.state_starts = 0
        # a block-diffusion model: the last dispatch's rows in a
        # denoising pass, rows in a settling pass, positions revealed
        self._blk_step = [0, 0, 0]
        if self._has_state and self._stats is not None:
            self._stats.record_state_pool(
                self.max_seqs, self.cache.state_stats()["bytes"])
        # (source pools, replicated copies) — see _replicated_lora
        self._lora_placed = None
        if self.mesh is not None and self._stats is not None:
            self._stats.record_spmd_mesh(
                int(self.mesh.devices.size), {"tp": self.tp},
                self.cache.heads_per_shard)
        # circuit breaker (shared with the server): successful
        # step dispatches close it, failing ones trip it — the
        # server's submit path rejects while it is open
        self._breaker = breaker
        # sequences finished but not yet handed to the caller — kept
        # OUTSIDE step()'s local event list so a step that finishes A
        # and then raises on B cannot lose A (the server drains this
        # in its error path too)
        self._finished_pending = []
        # (seq, reason) whose deadline expired / cancel was requested —
        # the server resolves them with DeadlineExceededError
        self._dead_pending = []
        # (seq, exc) isolated out of a failing dispatch — the server
        # resolves them with the ORIGINAL exception
        self._poison_pending = []

    def _refuse_given(self, arg, given, what=None):
        if given and arg in self._refused:
            raise UnsupportedModelFeatureError(
                f"{arg}: the {self.model.config.model_type} block does "
                f"not take {what or arg.replace('_', ' ')}")

    # -------------------------------------------- pool call helpers --
    def _resolve_weight_input(self, params, weight_dtype, weight_calib,
                              env_name):
        """Normalize a params argument to its quantized form: a
        :class:`~.quant.QuantizedWeights` passes through (the
        deploy/fleet hand-off — already calibrated, dtype pinned in
        the artifact), a f32 tree is quantized here when
        ``weight_dtype`` arg or the ``env_name`` env var asks for it,
        and ``None`` means serve full precision. fp8 requests fall
        back to int8 with a counted warning on backends without the
        dtype."""
        if isinstance(params, QuantizedWeights):
            return params
        req = weight_dtype if weight_dtype is not None \
            else _env_str(env_name, "")
        wd, fell_back = resolve_weight_dtype(req)
        if fell_back:
            import warnings
            if self._stats is not None:
                self._stats.record_quant_fallback()
            warnings.warn(
                "fp8 weights requested but float8_e4m3fn is "
                "unavailable on this backend; quantizing to int8 "
                "instead", RuntimeWarning, stacklevel=3)
        if wd is None:
            return None
        calib = weight_calib if weight_calib is not None \
            else _env_str("MXNET_TPU_LLM_WEIGHT_CALIB", "absmax")
        pct = float(_env_str("MXNET_TPU_LLM_WEIGHT_PERCENTILE",
                             "99.9"))
        return quantize_weights(params, dtype=wd, method=calib,
                                percentile=pct)

    def _scale_spec_dict(self, m, scales):
        """Per-key PartitionSpecs for a flat scale dict, defaulting
        any key the model's :meth:`weight_scale_specs` doesn't cover
        to replicated — the spec tree must match the traced dict
        key-for-key under ``shard_map``."""
        from jax.sharding import PartitionSpec as P
        base = m.weight_scale_specs(axis="tp") \
            if hasattr(m, "weight_scale_specs") else {}
        return {k: base.get(k, P()) for k in scales}

    def _step_extra_specs(self, lora):
        """(leading replicated pool count, trailing replicated batch
        arg count) of the step program after params + KV pools: the
        two LoRA factor pools when a bank is attached, then the 14
        packed-batch/sampling args (+2 adapter-table args under
        lora) and the two that feed the step before's tokens. Keeps
        :func:`_spmd_wrap` in sync with
        :func:`_make_step_fn`'s signatures."""
        return (2, 18) if lora else (0, 16)

    def _cow_arrays(self):
        """Every device pool array a COW copy must cover, in the fixed
        order the copy program was built for."""
        arrs = list(self.cache.pools())
        if self.draft_cache is not None:
            arrs += list(self.draft_cache.pools())
        return arrs

    def _cow_install(self, outs):
        outs = list(outs)
        n = len(self.cache.pools())
        self.cache.swap(*outs[:n])
        if self.draft_cache is not None:
            self.draft_cache.swap(*outs[n:])

    def _call_step(self, sampled, batch):
        """Dispatch one step program against the target pool, swapping
        the donated page (and scale) buffers back in. With an adapter
        bank attached, the current A/B factor pool snapshot rides
        after the KV pools — reading it here (not caching it) is what
        makes a concurrent publish visible to the very next step."""
        jit = self._step_jits[sampled]
        lora = () if self.bank is None else self.bank.pools()
        if lora and self.mesh is not None:
            lora = self._replicated_lora(lora)
        if self.mesh is not None:
            self.spmd_dispatches += 1
            if self._stats:
                self._stats.record_spmd_dispatch()
        toks, n_acc, aux, *pools = jit(
            self._params, *self.cache.pools(), *lora, *batch)
        self.cache.swap(*pools)
        return toks, n_acc, aux

    def _replicated_lora(self, pools):
        """Mesh-replicated snapshot of the bank's A/B factor pools.
        The bank publishes single-device arrays; feeding those into a
        meshed program would re-place them on EVERY dispatch (a
        host-side copy per step — a latent single-device assumption).
        Cache the replicated copies keyed by pool identity: one
        device_put per publish, a tuple-compare no-op per step. The
        cache holds strong refs to the source pools, so the identity
        compare can never alias a collected array."""
        cached = self._lora_placed
        if (cached is not None and len(cached[0]) == len(pools)
                and all(a is b for a, b in zip(cached[0], pools))):
            return cached[1]
        from jax.sharding import PartitionSpec as P
        from ...parallel.mesh import place_global
        placed = tuple(place_global(p, self.mesh, P())
                       for p in pools)
        self._lora_placed = (tuple(pools), placed)
        return placed

    def _call_draft(self, sampled, batch):
        jit = self._draft_jits[sampled]
        if self.quantized:
            tok, probs, kp, vp, ks, vs = jit(
                self._draft_params, self.draft_cache.k_pages,
                self.draft_cache.v_pages, self.draft_cache.k_scales,
                self.draft_cache.v_scales, *batch)
            self.draft_cache.swap(kp, vp, ks, vs)
        else:
            tok, probs, kp, vp = jit(
                self._draft_params, self.draft_cache.k_pages,
                self.draft_cache.v_pages, *batch)
            self.draft_cache.swap(kp, vp)
        return tok, probs

    # ------------------------------------------------ prefix caching --
    def _prefix_salt(self, seq):
        """The sequence's prefix-cache namespace. Adapter KV is NOT
        base-model KV (the LoRA delta rides the K/V projections), so
        cached blocks are only reusable under the same adapter name
        AND version — the pinned handle's identity seeds the hash
        chain. Base-model sequences share the unsalted namespace."""
        h = seq.adapter_handle
        return b"" if h is None else f"{h.name}@{h.version}".encode()

    def _prefix_lookup(self, seq):
        """Longest chain of registered blocks matching the prompt's
        full-block prefix. Pure read — no refcounts move until the
        admission actually proceeds. Returns ``(block_ids,
        hit_tokens)`` with ``hit_tokens <= len(prompt) - 1``: at least
        one prompt token is always recomputed, because its logits must
        emit the first generated token. When the whole prompt is
        block-aligned and fully cached that last token's chunk rewrites
        the final SHARED block — the copy-on-write in
        :meth:`_allocate` gives the sequence its private copy first."""
        T = len(seq.prompt)
        bs = self.cache.block_size
        if seq.prefix_hashes is None:
            seq.prefix_hashes = prefix_block_hashes(
                seq.prompt, bs, salt=self._prefix_salt(seq))
        hit = []
        for h in seq.prefix_hashes:
            bid = self.cache.prefix_get(h)
            if bid is None:
                break
            hit.append(bid)
        hit_tokens = min(len(hit) * bs, T - 1)
        n_keep = -(-hit_tokens // bs) if hit_tokens > 0 else 0
        return hit[:n_keep], hit_tokens

    def _register_blocks(self, seq, length):
        """Register the sequence's FULL, immutable blocks in the
        prefix index (chained hashes over prompt + generated tokens,
        truncated to ``length``, the K/V that committed steps wrote).
        First registration of a
        hash wins; a block already registered (a hit this sequence is
        itself sharing) is skipped by :meth:`PagedKVCache.register`."""
        if not self.prefix_enabled:
            return
        bs = self.cache.block_size
        tokens = seq.prompt + seq.generated
        n_full = min(length, len(tokens)) // bs
        n_full = min(n_full, len(seq.block_ids))
        if n_full <= 0:
            return
        hashes = seq.prefix_hashes or []
        if len(hashes) < n_full:
            hashes = prefix_block_hashes(tokens[:n_full * bs], bs,
                                         salt=self._prefix_salt(seq))
            seq.prefix_hashes = hashes
        for k in range(n_full):
            self.cache.register(hashes[k], seq.block_ids[k])

    def _cow_block(self, seq, bi):
        """Copy-on-write block ``seq.block_ids[bi]``: allocate a
        private copy, device-copy the page row across every pool
        (target + scales + draft), repoint the sequence's table and
        drop one reference on the shared original. One fixed-shape
        dispatch — never a compile after warmup."""
        old = seq.block_ids[bi]
        new = None
        try:
            new = self.cache.allocator.alloc(1)[0]
            outs = self._cow_jit(*self._cow_arrays(), np.int32(old),
                                 np.int32(new))
            self._cow_install(outs)
            seq.block_ids[bi] = new
        except BaseException:
            # a failed copy dispatch must not leak the private block:
            # it is in no block table yet, so no cleanup path owns it
            if new is not None and seq.block_ids[bi] != new:
                self.cache.allocator.free([new])
            raise
        self.cache.allocator.free([old])
        self.cache.cow_count += 1
        fl = self._flight
        if fl.enabled:
            fl.event("kv.cow", req=f"llm:{seq.seq_id}",
                     tenant=seq.tenant,
                     attrs={"old": old, "new": new})

    def _table_shape(self, mb):
        """A step's block tables at width ``mb``: ``[S, mb]``, or ``[L, S,
        mb]``, a table a layer, under a cache with window layers."""
        if self._windowed:
            return (self.cache.num_layers, self.max_seqs, mb)
        return (self.max_seqs, mb)

    # ------------------------------------------------------- warmup --
    def _null_batch(self, T, MB):
        """An all-padding step batch of packed length ``T`` and table
        width ``MB`` (every row inactive on the null block): the real
        shapes with nothing to compute — what warmup dispatches."""
        S, K = self.max_seqs, self.spec_k
        V = self.model.vocab_size
        batch = (np.zeros(T, np.int32), np.zeros(T, np.int32),
                 np.zeros(T, np.int32), np.zeros(T, np.int32),
                 np.full(self._table_shape(MB), NULL_BLOCK, np.int32),
                 np.zeros((S, K + 1), np.int32),
                 np.zeros((S, K), np.int32),
                 np.zeros((S, K, V), np.float32),
                 np.zeros(S, np.int32), np.zeros(S, np.float32),
                 np.zeros(S, np.int32), np.ones(S, np.float32),
                 np.zeros(S, np.int32), np.zeros(S, np.int32))
        if self.bank is not None:
            batch += (np.full((S, self.bank.max_pages_per_adapter),
                              NULL_ADAPTER_PAGE, np.int32),
                      np.zeros(S, np.float32))
        if self._block:
            batch += (np.zeros((S, self._block), np.int32),
                      np.zeros((S, self._block), np.int32),
                      np.zeros(S, np.int32))
        return batch + (np.full(T, -1, np.int32), self._feed)

    def step_program_text(self, sampled=False):
        """StableHLO text of the step program at its narrowest bucket,
        lowered (not compiled, not run) against the live params and
        pools. A Mosaic-compiled Pallas kernel shows up in it as a
        ``tpu_custom_call``; the interpreted kernel and the ``jnp``
        reference do not — so this is where a caller reads which
        attention the engine's programs were built with."""
        pools = self.cache.pools()
        lora = () if self.bank is None else self.bank.pools()
        batch = self._null_batch(self._t_buckets[0], self._mb_widths[0])
        return self._step_jits[sampled].lower(
            self._params, *pools, *lora, *batch).as_text()

    def warmup(self):
        """Compile every program steady state can reach: the chunked
        step at each of its two widths (+ the draft program's widths
        when speculation is on). Returns {'step_qN'|'draft_qN':
        seconds}. After this, a mixed chunked-prefill / sampled-decode
        / speculative workload cannot recompile."""
        timings = {}
        S = self.max_seqs
        temp = np.zeros(S, np.float32)
        top_k = np.zeros(S, np.int32)
        top_p = np.ones(S, np.float32)
        seeds = np.zeros(S, np.int32)
        counters = np.zeros(S, np.int32)
        if self.bank is not None:
            # install the all-zero null adapter page (warms the
            # fixed-shape install program every later publish reuses)
            t0 = time.monotonic()
            self.bank.warmup()
            timings["adapter_install"] = time.monotonic() - t0
        if self.draft_model is not None:
            for T in self._draft_t_buckets:
                for MB in self._mb_widths:
                    tables = np.full((S, MB), NULL_BLOCK, np.int32)
                    for sampled in (False, True):
                        t0 = time.monotonic()
                        tok, probs = self._call_draft(sampled, (
                            np.zeros(T, np.int32),
                            np.zeros(T, np.int32),
                            np.zeros(T, np.int32),
                            np.zeros(T, np.int32), tables,
                            np.zeros(S, np.int32), temp, top_k,
                            top_p, seeds, counters))
                        np.asarray(tok)
                        tag = "sampled" if sampled else "greedy"
                        timings[f"draft_t{T}mb{MB}_{tag}"] = \
                            time.monotonic() - t0
        for T in self._t_buckets:
            for MB in self._mb_widths:
                batch = self._null_batch(T, MB)
                for sampled in self._variants:
                    t0 = time.monotonic()
                    toks = self._call_step(sampled, batch)[0]
                    np.asarray(toks)
                    tag = "sampled" if sampled else "greedy"
                    timings[f"step_t{T}mb{MB}_{tag}"] = \
                        time.monotonic() - t0
        if self._cow_jit is not None:
            # warm the copy-on-write program (src == dst == the null
            # block: a no-op copy with the real shapes)
            t0 = time.monotonic()
            outs = self._cow_jit(*self._cow_arrays(),
                                 np.int32(NULL_BLOCK),
                                 np.int32(NULL_BLOCK))
            self._cow_install(outs)
            timings["cow_copy"] = time.monotonic() - t0
        self._warmed = True
        return timings

    # ---------------------------------------------------- admission --
    def add_validate(self, seq):
        """Validate a sequence WITHOUT enqueueing it — the server runs
        this on the caller's thread so shape/vocab errors raise at
        submit time, not inside the engine loop."""
        if not isinstance(seq, Sequence):
            raise TypeError(f"add() wants a Sequence, got {type(seq)}")
        if len(seq.prompt) > self.max_context - 1:
            raise ValueError(
                f"prompt of {len(seq.prompt)} tokens leaves no room to "
                f"generate (max_context={self.max_context})")
        self._refuse_given("sampling", seq.sampling.temperature > 0,
                           "temperature sampling (greedy requests only)")
        vocab = self.model.vocab_size
        bad = [t for t in seq.prompt if not (0 <= t < vocab)]
        if bad:
            raise ValueError(
                f"prompt tokens {bad[:4]} out of vocab [0, {vocab})")
        return seq

    def add(self, seq):
        """Enqueue a WAITING sequence."""
        self.scheduler.add(self.add_validate(seq))

    def has_work(self):
        """A sequence to serve, or a step in flight to commit."""
        return self.scheduler.has_work() or self._inflight is not None

    def _record_block_gauges(self):
        if self._stats:
            a = self.cache.allocator
            self._stats.record_blocks(
                a.num_used, a.num_usable, cached=a.num_cached,
                shared=a.num_shared,
                free=a.num_free - a.num_cached)
            if self._windowed:
                w = self.cache.window_stats()
                self._stats.record_window_pages(
                    w["pages_held"], w["pages_full_layout"],
                    w["window_pages_released"] - self._window_released)
                self._window_released = w["window_pages_released"]
            self._stats.record_admission_state(
                self.scheduler.num_waiting, self.scheduler.num_running)

    def _on_prefix_evict(self, n=1):
        """Prefix-cache LRU reclaim observer: mirrors onto the metrics
        registry and drops a ``kv.reclaim`` decision into the flight
        ring (control-plane event — which cached blocks the allocator
        gave back under pressure)."""
        if self._stats:
            self._stats.record_prefix_evict(n)
        fl = self._flight
        if fl.enabled:
            fl.event("kv.reclaim", attrs={"blocks": n})

    def _admit(self, events):
        """Place waiting sequences into free slots. Conservative KV
        gate (the full prompt + one decode block must fit, prefix-hit
        blocks discounted — they are ref'd, not allocated) keeps FIFO
        admission from thrashing the preemption path; the UNCACHED
        remainder of the prompt is then written chunk-by-chunk by the
        regular step, so a hit sequence skips its hit tokens' prefill
        chunks entirely."""
        while self.scheduler.num_waiting:
            slot = self.scheduler.free_slot()
            if slot is None:
                break
            seq = self.scheduler.peek_waiting()
            if (self.bank is not None and seq.adapter is not None
                    and seq.adapter_handle is None):
                # pin the adapter version BEFORE the prefix lookup —
                # the pinned (name, version) namespaces the hash
                # chain, so adapter KV never aliases base-model or
                # other-version KV. A failed fault-in (unknown name,
                # bank full of in-use adapters) poisons the sequence
                # without touching cache state. A later KV gate break
                # leaves the pin on the waiting sequence — it is
                # reused on the next admission attempt and released
                # on terminal states like any other.
                try:
                    seq.adapter_handle = self.bank.acquire(
                        seq.adapter, tenant=seq.tenant)
                except AdapterError as exc:
                    self.scheduler.waiting.popleft()
                    self._poison(seq, exc, events)
                    continue
            T = len(seq.prompt)
            hit, hit_tokens = ([], 0)
            if self.prefix_enabled:
                hit, hit_tokens = self._prefix_lookup(seq)
            need = self.cache.blocks_for(T) - len(hit)
            if T % self.cache.block_size == 0:
                need += 1           # first decode opens a new page
            if self._windowed:
                # by kind: every block of the full layers, of the window
                # layers what a window holds (no prefix cache here)
                need = self.cache.pages_for(T + 1)
            if hit_tokens and hit_tokens < len(hit) * \
                    self.cache.block_size:
                # truncated (block-aligned full) hit: the recompute
                # chunk rewrites the FINAL hit block, which COWs when
                # shared — reserve its private copy up front so the
                # gate's promise ("admission never preempts to cover
                # its own growth") holds
                need += 1
            # hit blocks sitting in the cached LRU count toward
            # num_free but are about to be ref()'d by THIS admission —
            # gate on need + those, or a hit sequence could admit into
            # capacity it is itself consuming and then preempt healthy
            # running sequences to cover its decode growth
            cached_hits = sum(
                1 for bid in hit
                if self.cache.allocator.refcount(bid) == 0)
            if not self.cache.allocator.can_alloc(need + cached_hits):
                break               # FIFO: no head-of-line skipping
            for bid in hit:
                self.cache.allocator.ref(bid)
            self.scheduler.place(seq, slot)
            if seq.t_admit is None:
                # the queue wait ends here, once a request: a preempted
                # sequence's way back in counts under preemptions
                seq.t_admit = time.monotonic()
                if self._stats:
                    self._stats.record_admitted(
                        seq.t_admit - seq.t_submit)
            seq.block_ids = list(hit)
            if self._windowed:
                self.cache.start(seq)
            seq.seq_len = hit_tokens
            seq.draft_len = 0
            seq.prefill_started = False
            seq.cache_hit_tokens = hit_tokens
            if self.prefix_enabled:
                self.prefix_lookups += 1
                if hit_tokens > 0:
                    self.prefix_hits += 1
                    self.prefill_tokens_saved += hit_tokens
                if self._stats:
                    self._stats.record_prefix_lookup(
                        hit_tokens, tenant=seq.tenant)
            events.append(("admitted", seq))
            fl = self._flight
            if fl.enabled:
                fl.event("llm.admit", req=f"llm:{seq.seq_id}",
                         tenant=seq.tenant,
                         attrs={"slot": slot, "prompt": T,
                                "cache_hit": hit_tokens,
                                "adapter": seq.adapter})

    def _release_adapter(self, seq):
        """Drop the sequence's adapter pin on any TERMINAL release.
        Preemption deliberately keeps it: the pinned version is what
        makes a preempted sequence's re-prefill bit-identical even if
        the adapter was republished in between."""
        if seq.adapter_handle is not None and self.bank is not None:
            self.bank.release(seq.adapter_handle)
            seq.adapter_handle = None

    def _finish(self, seq, events, length):
        """``length``: the tokens whose K/V the committed steps wrote."""
        self._register_blocks(seq, length)
        self.cache.free_seq(seq)
        self._release_adapter(seq)
        reason = ("stop_token" if (seq.stop_token is not None
                                   and seq.generated
                                   and seq.generated[-1]
                                   == seq.stop_token)
                  else "length" if seq.num_generated
                  < seq.max_new_tokens else "max_new_tokens")
        self.scheduler.release(seq, FINISHED, reason)
        self._finished_pending.append(seq)
        events.append(("finished", seq))

    def _preempt(self, seq):
        self.cache.free_seq(seq)
        self.scheduler.preempt(seq)
        if self._stats:
            self._stats.record_preemption()
        fl = self._flight
        if fl.enabled:
            fl.event("llm.preempt", req=f"llm:{seq.seq_id}",
                     tenant=seq.tenant,
                     attrs={"preemptions": seq.preemptions,
                            "seq_len": seq.seq_len})

    def _poison(self, seq, exc, events):
        """Release ``seq`` as poison-isolated: blocks freed, slot
        freed, the ORIGINAL exception queued for the server."""
        self.cache.free_seq(seq)
        self._release_adapter(seq)
        self.scheduler.release(seq, EVICTED, "poison")
        self._poison_pending.append((seq, exc))
        if self._stats:
            self._stats.record_poison()
        events.append(("poisoned", seq))

    def _expire(self, events):
        """Lifecycle scan: release sequences whose end-to-end deadline
        expired or whose caller cancelled them (generate timeout).
        Waiting ones die before costing any prefill work; running ones
        free their KV blocks and decode slot immediately. The server
        turns the ``(seq, reason)`` records into typed
        ``DeadlineExceededError`` resolutions carrying partial tokens."""
        now = time.monotonic()
        if self.scheduler.waiting:
            keep = collections.deque()
            while self.scheduler.waiting:
                seq = self.scheduler.waiting.popleft()
                reason = ("timeout" if seq.cancelled
                          else "deadline" if seq.expired(now) else None)
                if reason is None:
                    keep.append(seq)
                    continue
                self.cache.free_seq(seq)  # waiting: normally no blocks
                self._release_adapter(seq)
                self.scheduler.release(seq, EVICTED, reason)
                self._dead_pending.append((seq, reason))
                events.append(("expired", seq))
            self.scheduler.waiting = keep
        for seq in self.scheduler.running():
            reason = ("timeout" if seq.cancelled
                      else "deadline" if seq.expired(now) else None)
            if reason is None:
                continue
            self.cache.free_seq(seq)
            self._release_adapter(seq)
            self.scheduler.release(seq, EVICTED, reason)
            self._dead_pending.append((seq, reason))
            events.append(("expired", seq))

    # ----------------------------------------------------- planning --
    def _plan(self, seq, budget, events):
        """This step's work for one running sequence: which committed
        tokens feed the chunk (a prefilling row takes ``budget`` of
        them at the most, what the step's token budget still holds),
        how many draft slots it gets, and the KV end position the
        allocator must cover. ``cost`` is what the row takes of the
        step's budget: its ``ntok``, or what its draft has to be fed,
        if that is more. Returns the plan dict or None (sequence was
        poison-isolated at prefill start, or a block-diffusion row
        the budget does not reach a whole block of, or a row of the step
        in flight whose sequence that step's commit ends).

        ``pos`` is where the row's tokens go: ``seq_len``, which moved
        over what the step in flight writes when it was launched. A row
        of that step that feeds what it produces (the token after a
        prompt or a decode token, a block after its reveal) takes it
        from that step's output on the device: ``src``, for each token
        fed, its flat index there (else the host's token stands)."""
        if self._prefilling(seq):
            # prefilling (fresh prompt or preemption-folded resume —
            # folding moves the generation INTO the prompt, so an
            # empty generation list is exactly "prompt not complete";
            # a 1-token prompt is a 1-token chunk that emits)
            committed = seq.prompt
            cl = len(committed)
            remaining = cl - seq.seq_len
            emits = True
            if self._block:
                # whole blocks, and only the prompt's leading ones: the
                # chunk ends on a block boundary and emits nothing
                B = self._block
                remaining = cl // B * B - seq.seq_len
                budget, emits = budget // B * B, False
                if not budget:
                    return None
            if not seq.prefill_started:
                seq.prefill_started = True
                try:
                    # chaos-harness site: scripted raises for "prefill
                    # fails on this prompt" — checked once per
                    # admission (a prefix-cache hit starts mid-prompt,
                    # so the flag, not seq_len == 0, marks the start),
                    # isolating exactly the poison sequence
                    faults.check("llm.prefill")
                except Exception as exc:
                    self._poison(seq, exc, events)
                    if self._breaker is not None:
                        self._breaker.record_failure(site="prefill")
                    return None
            ntok = min(budget, remaining)
            return {"kind": "prefill", "tokens":
                    committed[seq.seq_len:seq.seq_len + ntok],
                    "pos": seq.seq_len, "ntok": ntok, "cost": ntok,
                    "cl": cl,
                    "committed": committed,
                    "k": 0, "emit": emits and ntok == remaining,
                    "draft_tokens": [], "draft_probs": []}
        prev = self._ahead().get(seq)
        if self._block:
            return self._plan_block(seq, budget, prev)
        if prev is not None:
            # the step in flight commits one token, which this step
            # feeds from its output; it ends the sequence by count or by
            # context without a look at the token
            if (seq.num_generated + 1 >= seq.max_new_tokens
                    or seq.seq_len + 1 >= self.max_context):
                return None
            return {"kind": "decode", "tokens": [0],
                    "src": [seq.slot * (self.spec_k + 1)],
                    "pos": seq.seq_len, "ntok": 1, "cost": 1,
                    "cl": len(seq.prompt) + len(seq.generated) + 1,
                    "committed": None, "k": 0, "emit": True,
                    "draft_tokens": [], "draft_probs": []}
        # decode: one committed token outstanding. Speculate when the
        # draft's committed prefix can catch up within ONE chunk feed
        # (steady state: 1-2 tokens behind; a degraded draft recovers
        # over catch-up-only feeds first)
        cl = len(seq.prompt) + len(seq.generated)
        k = behind = 0
        committed = None
        if self.draft_model is not None:
            committed = seq.prompt + seq.generated
            behind = cl - seq.draft_len
            if behind <= self.q_tokens:
                rem_new = seq.max_new_tokens - seq.num_generated
                k = max(0, min(self.spec_k, rem_new - 1,
                               self.max_context - 1 - seq.seq_len))
        return {"kind": "decode", "tokens": [seq.last_token],
                "pos": seq.seq_len, "ntok": 1 + k,
                "cost": max(1 + k, min(behind, self.q_tokens)),
                "cl": cl, "committed": committed,
                "k": k, "emit": True,
                "draft_tokens": [], "draft_probs": []}

    def _prefilling(self, seq):
        """Whether ``seq`` is still writing its prompt. A row that
        decodes has generated (preemption folds the generation into
        the prompt); a block-diffusion row writes its prompt's leading
        whole blocks and generates from there."""
        if self._block:
            return seq.seq_len < len(seq.prompt) // self._block \
                * self._block
        # (a prompt whose last chunk is in flight decodes next)
        return not seq.generated and not self._ahead().get(
            seq, {}).get("emit")

    def _ahead(self):
        """The plans of the step in flight, by sequence."""
        return self._inflight["plans"] if self._inflight else {}

    def _plan_block(self, seq, budget, prev=None):
        """A generating row of a block-diffusion model: one pass over
        its open block, which is opened here if the row has none (what
        the prompt left over, revealed, then masked positions). A
        denoising pass while a position is masked, revealing what the
        pass's place in the block's plan says; else the settling
        pass. After a pass of the step in flight (``prev``, under the
        ``sequential`` strategy, which reveals the leftmost masked
        positions): after a denoising pass, the block that pass leaves
        on the device, whose masked positions the host knows; after a
        settling pass, a new block, all of it masked (``opens``)."""
        B = self._block
        if budget < B:
            return None
        mask = int(self._blockdiff["mask_token_id"])
        src = opens = None
        if prev is not None and prev["kind"] == "settle":
            if self._block_answer(seq, prev["pos"])[1]:
                return None
            tokens, masked, bpass = [mask] * B, [1] * B, 0
            opens = True
        elif prev is not None and prev["kind"] == "denoise":
            masked = list(prev["blk_masked"])
            left = prev["n_reveal"]
            for j in range(B):
                if masked[j] and left:
                    masked[j], left = 0, left - 1
            tokens, bpass = [0] * B, prev["blk_pass"] + 1
            src = [seq.slot * B + j for j in range(B)]
        else:
            if seq.blk_tokens is None:
                known = (seq.prompt + seq.generated)[seq.seq_len:]
                fill = B - len(known)
                seq.blk_tokens = known + [mask] * fill
                seq.blk_masked = [0] * len(known) + [1] * fill
                seq.blk_passes = [-1] * len(known) + [None] * fill
                seq.blk_pass = 0
            tokens, masked = list(seq.blk_tokens), list(seq.blk_masked)
            bpass = seq.blk_pass
        n_masked = sum(masked)
        n_reveal = 0
        if n_masked:
            # (the plan's passes sum to B, so a block that still has a
            # masked position has a pass of the plan left)
            n_reveal = min(n_masked, self._reveal_plan[bpass])
        return {"kind": "denoise" if n_masked else "settle",
                "tokens": tokens, "src": src, "opens": opens,
                "pos": seq.seq_len, "ntok": B, "cost": B,
                "cl": len(seq.prompt) + len(seq.generated),
                "committed": None, "k": 0, "emit": not n_masked,
                "n_reveal": n_reveal, "blk_masked": masked,
                "blk_pass": bpass,
                "draft_tokens": [], "draft_probs": []}

    def _block_answer(self, seq, pos):
        """What a settling pass over the open block at ``pos`` answers:
        how many of its tokens past those already answered go to the
        answer before ``max_new_tokens`` or a stop token ends it (the
        rest go to ``block_tail``), and whether the sequence ends there
        (by those or by the context). :meth:`_settle_block` commits by
        it, and the planner looks past a settling pass in flight by it
        (the block's tokens are on the host, committed with the pass
        before)."""
        n = seq.num_generated
        last = seq.generated[-1] if seq.generated else None
        kept = 0
        if seq.blk_tokens is not None:
            known = len(seq.prompt) + len(seq.generated) - pos
            for tok in seq.blk_tokens[known:]:
                if n >= seq.max_new_tokens or (
                        seq.stop_token is not None
                        and last == seq.stop_token):
                    break
                n, last, kept = n + 1, tok, kept + 1
        ends = (n >= seq.max_new_tokens
                or (seq.stop_token is not None and last == seq.stop_token)
                or pos + 2 * self._block > self.max_context)
        return kept, ends

    def _allocate(self, seq, plan, events):
        """Blocks covering this step's KV writes (positions
        ``seq_len .. seq_len + ntok - 1``), allocated ONTO the
        sequence before any dispatch so every failure path frees them;
        under pressure preempt newest-admitted first.

        Copy-on-write: a write-range block the sequence still SHARES
        (refcount > 1 — a prefix-cache hit whose final block the
        sequence is about to extend/rewrite) is copied to a private
        block first, so shared prefix KV is immutable for every other
        owner. COW capacity is reserved through the same
        preempt-under-pressure loop.

        Under a cache with window layers the pages are counted by kind
        (:meth:`~.kv_cache.PagedKVCache.grow`), and the window layers'
        pages wholly behind the window of the step's first position go
        back to the pool first (of the step in flight's, where it holds
        the sequence: a failed step is dispatched again from there)."""
        if self._windowed:
            ahead = self._ahead().get(seq)
            lowest = seq.seq_len if ahead is None \
                else min(seq.seq_len, ahead["pos"])
            self.cache.release_behind(seq, lowest)
            end = seq.seq_len + plan["ntok"]
            self._make_room(seq, self.cache.pages_to_grow(seq, end, lowest),
                            events)
            self.cache.grow(seq, end, lowest)
            return
        need = self.cache.blocks_for(seq.seq_len + plan["ntok"]) \
            - len(seq.block_ids)
        cow = []
        if self.prefix_enabled and seq.block_ids:
            bs = self.cache.block_size
            first = seq.seq_len // bs
            last = min((seq.seq_len + plan["ntok"] - 1) // bs,
                       len(seq.block_ids) - 1)
            cow = [bi for bi in range(first, last + 1)
                   if self.cache.allocator.refcount(
                       seq.block_ids[bi]) > 1]
        self._make_room(seq, max(need, 0) + len(cow), events)
        for bi in cow:
            # a victim preemption above may have dropped the share
            if self.cache.allocator.refcount(seq.block_ids[bi]) > 1:
                self._cow_block(seq, bi)
        if need > 0:
            seq.block_ids.extend(self.cache.allocator.alloc(need))

    def _make_room(self, seq, total, events):
        """``total`` free blocks for ``seq``, preempting newest-arrived
        sequences but ``seq`` until there are."""
        if (self._inflight is not None and total > 0
                and not self.cache.allocator.can_alloc(total)):
            # preemption folds committed tokens into the prompt: it
            # waits for the step in flight to be committed
            raise _Behind()
        while total > 0 and not self.cache.allocator.can_alloc(total):
            victim = self.scheduler.pick_victim(exclude=(seq,))
            if victim is None:
                raise KVCacheError(
                    "lone sequence cannot allocate — num_blocks too "
                    "small for max_context")
            self._preempt(victim)
            events.append(("preempted", victim))

    # -------------------------------------------------- draft phase --
    def _draft_dispatch(self, rows, feeds, counters_v):
        """One fixed-shape draft launch (narrow width for 1-2-token
        proposal feeds, chunk width while mirroring prefill).
        ``feeds``: {seq: (tokens, start_pos)}; rows not in it ride
        along inactive. Returns (tokens [S], probs [S, V]) as numpy."""
        S = self.max_seqs
        t_need = sum(len(t) for t, _ in feeds.values())
        T = next(w for w in self._draft_t_buckets if w >= t_need)
        mb_need = max(self.cache.blocks_for(start + len(t))
                      for t, start in feeds.values())
        MB = next(w for w in self._mb_widths if w >= mb_need)
        bufs = self._draft_bufs.get((T, MB))
        if bufs is None:
            bufs = (np.zeros(T, np.int32),            # tokens
                    np.zeros(T, np.int32),            # positions
                    np.zeros(T, np.int32),            # seq_ids
                    np.zeros(T, np.int32),            # valid
                    np.full((S, MB), NULL_BLOCK, np.int32),
                    np.zeros(S, np.int32),            # last_idx
                    np.zeros(S, np.float32), np.zeros(S, np.int32),
                    np.ones(S, np.float32), np.zeros(S, np.int32),
                    np.zeros(S, np.int32))
            self._draft_bufs[(T, MB)] = bufs
        (tokens, positions, seq_ids, valid, tables, last_idx, temp,
         top_k, top_p, seeds, counters) = bufs
        valid.fill(0)       # see _batch_buffers: never-stale writes
        off = 0
        for seq in rows:
            feed = feeds.get(seq)
            if feed is None:
                continue
            toks, start = feed
            i, n = seq.slot, len(toks)
            tokens[off:off + n] = toks
            positions[off:off + n] = start + self._arange[:n]
            seq_ids[off:off + n] = i
            valid[off:off + n] = 1
            last_idx[i] = off + n - 1
            nb = min(len(seq.block_ids), MB)
            tables[i, :nb] = seq.block_ids[:nb]
            tables[i, nb:] = NULL_BLOCK
            sp = seq.sampling
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seeds[i] = sp.seed
            counters[i] = counters_v.get(seq, 0)
            off += n
        self._name_tail(seq_ids, positions, off)
        # chaos-harness site: scripted raises / injected latency /
        # worker death mid-verify
        faults.check("llm.draft")
        sampled = any(s.sampling.temperature > 0 for s in feeds)
        tok, probs = self._call_draft(sampled, (
            tokens, positions, seq_ids, valid, tables, last_idx,
            temp, top_k, top_p, seeds, counters))
        return self._device_get((tok, probs))

    def _draft_propose(self, rows, plans):
        """Run the draft model: mirror prefill chunks into the draft
        cache, catch its committed prefix up, and propose up to K
        tokens per speculative row (stored on the row's plan). A
        failing draft dispatch DEGRADES the step to plain decode —
        never poisons, never leaks (draft pages share the target's
        block accounting).

        Prefix-cache interaction: catch-up feeds for a cache-hit
        sequence write DRAFT-pool KV into rows of blocks whose TARGET
        KV is shared (refcount > 1), without COW. This is safe by the
        same determinism the cache's bit-exact parity rests on: the
        draft KV of position p is a pure function of the committed
        token prefix, so every owner of a shared block writes
        byte-identical draft rows (pinned by the spec-parity suite).
        Only the TARGET pool is strictly immutable-under-sharing —
        its writes carry new per-sequence content and always COW
        first (:meth:`_allocate`)."""
        if self.draft_model is None:
            return
        feeds, counters, proposing = {}, {}, []
        # the draft program's own top rung, as long as the step's
        # budget: the decoding rows' feeds first (each within its
        # plan's ``cost``), the chunks' mirrors from what is left
        left = self._draft_t_buckets[-1]
        for seq in rows:
            plan = plans[seq]
            if plan["kind"] == "prefill":
                continue
            if plan["k"] > 0:
                # catch-up (bounded: <= 2 tokens in steady state) +
                # the proposal input
                feed = plan["committed"][seq.draft_len:plan["cl"]]
                feeds[seq] = (feed, seq.draft_len)
                plan["draft_fed"] = len(feed)
                counters[seq] = plan["cl"]
                proposing.append(seq)
            elif seq.draft_len < plan["cl"]:
                # a draft that fell behind (earlier degraded step):
                # catch-up-only feed, one chunk per step, until the
                # speculation gate in _plan re-opens
                end = min(plan["cl"], seq.draft_len + self.q_tokens)
                feeds[seq] = (plan["committed"][seq.draft_len:end],
                              seq.draft_len)
                plan["draft_fed"] = end - seq.draft_len
            left -= plan.get("draft_fed", 0)
        for seq in rows:
            plan = plans[seq]
            if plan["kind"] != "prefill":
                continue
            # mirror the target's chunk. Normally draft_len ==
            # seq_len and this IS the same chunk, which the step's
            # budget has room for; after a degraded draft step the
            # mirror restarts from the draft's own watermark so its
            # KV prefix never gaps, and takes what the rung has left
            end = min(seq.seq_len + plan["ntok"], seq.draft_len + left)
            if end > seq.draft_len:
                feeds[seq] = (plan["committed"][seq.draft_len:end],
                              seq.draft_len)
                plan["draft_fed"] = end - seq.draft_len
                left -= end - seq.draft_len
        if not feeds:
            return
        try:
            tok, probs = self._draft_dispatch(rows, feeds, counters)
            for seq in proposing:
                plans[seq]["draft_tokens"].append(int(tok[seq.slot]))
                plans[seq]["draft_probs"].append(probs[seq.slot])
            for r in range(1, self.spec_k):
                feeds, counters = {}, {}
                for seq in proposing:
                    plan = plans[seq]
                    if plan["k"] <= r:
                        continue
                    d_prev = plan["draft_tokens"][-1]
                    feeds[seq] = ([d_prev], plan["cl"] + r - 1)
                    counters[seq] = plan["cl"] + r
                    plan["draft_fed"] += 1
                if not feeds:
                    break
                tok, probs = self._draft_dispatch(rows, feeds,
                                                  counters)
                for seq in feeds:
                    plans[seq]["draft_tokens"].append(
                        int(tok[seq.slot]))
                    plans[seq]["draft_probs"].append(probs[seq.slot])
        except Exception:
            if self._pages_deleted():
                raise
            # degrade: this step decodes without speculation; the
            # draft prefix watermark is simply not advanced, so the
            # next step's catch-up re-feeds deterministically
            for seq in rows:
                plan = plans[seq]
                if plan["kind"] == "decode":
                    plan["k"] = 0
                    plan["ntok"] = 1
                    plan["tokens"] = [seq.last_token]
                    plan["draft_tokens"] = []
                    plan["draft_probs"] = []
                plan.pop("draft_fed", None)
            if self._stats:
                self._stats.record_spec_degraded()
        else:
            # proposals beyond what a row wanted never happen; trim
            # the committed-token budget trackers
            for seq in proposing:
                plan = plans[seq]
                plan["ntok"] = 1 + len(plan["draft_tokens"])
                plan["k"] = len(plan["draft_tokens"])
                plan["tokens"] = ([seq.last_token]
                                  + plan["draft_tokens"])

    # ------------------------------------------------- the one step --
    def _batch_buffers(self, t, mb):
        """Reusable host-side batch arrays for packed length ``t``
        and block-table width ``mb``, two sets that take turns (a
        step's set is written again two dispatches later, once the
        step has been fetched). ``valid``
        is reset EVERY dispatch — a stale valid flag would scatter
        garbage K/V through a stale (seq_id, position, table) combo
        into blocks another sequence may own now; everything else
        stale is masked or discarded."""
        # two sets a width, in turn: a numpy input may be read by the
        # device after the call returns, and one step stays in flight
        # while the next is built
        self._bufs_turn ^= 1
        key = (t, mb, self._bufs_turn)
        bufs = self._bufs.get(key)
        if bufs is None:
            S, K = self.max_seqs, self.spec_k
            V = self.model.vocab_size
            bufs = (np.zeros(t, np.int32),            # tokens
                    np.zeros(t, np.int32),            # positions
                    np.zeros(t, np.int32),            # seq_ids
                    np.zeros(t, np.int32),            # valid
                    np.full(self._table_shape(mb), NULL_BLOCK, np.int32),
                    np.zeros((S, K + 1), np.int32),   # win_idx
                    np.zeros((S, K), np.int32),       # draft tokens
                    np.zeros((S, K, V), np.float32),  # draft probs
                    np.zeros(S, np.int32),            # n_draft
                    np.zeros(S, np.float32),          # temperature
                    np.zeros(S, np.int32),            # top_k
                    np.ones(S, np.float32),           # top_p
                    np.zeros(S, np.int32),            # seeds
                    np.zeros(S, np.int32))            # counters
            if self.bank is not None:
                bufs += (np.full((S, self.bank.max_pages_per_adapter),
                                 NULL_ADAPTER_PAGE, np.int32),
                         np.zeros(S, np.float32))     # a_tables/scales
            if self._block:
                bufs += (np.zeros((S, self._block), np.int32),  # blk_idx
                         np.zeros((S, self._block), np.int32),  # masked
                         np.zeros(S, np.int32))                 # n_reveal
            bufs += (np.full(t, -1, np.int32),)                 # src
            self._bufs[key] = bufs
        return bufs

    @staticmethod
    def _name_tail(seq_ids, positions, off):
        """Behind a packed batch's ``off`` tokens: the last valid
        token's slot at position 0, not what an earlier step left in
        the buffers. The flat kernels take the batch run by run and
        walk a run's pages up to its largest position: this tail
        lengthens the last run and walks nothing (any tail gives the
        same valid rows; a stale one is walked like a token)."""
        if off:
            seq_ids[off:] = seq_ids[off - 1]
            positions[off:] = 0

    def _build_batch(self, rows, plans, t, mb):
        bufs = self._batch_buffers(t, mb)
        (tokens, positions, seq_ids, valid, tables, win_idx, d_toks,
         d_probs, n_draft, temp, top_k, top_p, seeds,
         counters) = bufs[:14]
        src = bufs[-1]
        valid.fill(0)
        n_draft.fill(0)
        src.fill(-1)
        if self._block:
            # a row that feeds no block reveals nothing
            blk_idx, blk_masked, n_reveal = bufs[-4:-1]
            blk_masked.fill(0)
            n_reveal.fill(0)
        off = 0
        K1 = self.spec_k + 1
        for seq in rows:
            plan = plans[seq]
            i, n = seq.slot, len(plan["tokens"])
            tokens[off:off + n] = plan["tokens"]
            if plan.get("src") is not None:
                src[off:off + n] = plan["src"]
            positions[off:off + n] = seq.seq_len + self._arange[:n]
            seq_ids[off:off + n] = i
            valid[off:off + n] = 1
            # the K+1 scored positions end at this row's last token
            start = off + n - 1 - plan["k"]
            win_idx[i] = np.clip(start + self._arange[:K1], 0, t - 1)
            # blocks past the sliced width only cover positions the
            # causal mask can never reach — truncation is invisible
            if self._windowed:
                self.cache.fill_tables(tables, i, seq)
            else:
                nb = min(len(seq.block_ids), mb)
                tables[i, :nb] = seq.block_ids[:nb]
                tables[i, nb:] = NULL_BLOCK
            k = plan["k"]
            n_draft[i] = k
            if k:
                d_toks[i, :k] = plan["draft_tokens"]
                d_probs[i, :k] = plan["draft_probs"]
            sp = seq.sampling
            temp[i] = sp.temperature
            top_k[i] = sp.top_k
            top_p[i] = sp.top_p
            seeds[i] = sp.seed
            counters[i] = plan["cl"]
            if self.bank is not None:
                # per-row adapter dispatch rides the batch exactly
                # like the sampling vectors: page list + scale for
                # adapted rows, the all-zero null page + scale 0.0
                # for plain rows (exact-zero delta — bit-identical
                # to a bank-less engine)
                a_tables, a_scales = bufs[14], bufs[15]
                h = seq.adapter_handle
                if h is None:
                    a_tables[i] = NULL_ADAPTER_PAGE
                    a_scales[i] = 0.0
                else:
                    a_tables[i] = h.pages_padded
                    a_scales[i] = h.scale
            if self._block and plan["kind"] != "prefill":
                blk_idx[i] = off + self._arange[:n]
                blk_masked[i] = plan["blk_masked"]
                n_reveal[i] = plan["n_reveal"]
            off += n
        self._name_tail(seq_ids, positions, off)
        return bufs

    def _dispatch(self, rows, plans):
        """ONE fixed-shape launch for ``rows`` (slots not in ``rows``
        ride along inactive on the null block — the shape, and
        therefore the compiled program, never changes). Returns the
        step's outputs on the device, unfetched (:meth:`_fetch`).
        Failures propagate to the isolation logic in :meth:`_land`.

        Two phases of the thread's clock, each under the span of its
        name: ``build`` and ``launch``."""
        clock, tracer = self._clock, get_tracer()
        clock.mark("build")
        with tracer.span("mxtpu.llm.build", "llm") as sp:
            if any(plans[s]["kind"] != "prefill" for s in rows):
                # chaos-harness site: scripted raises / injected latency
                faults.check("llm.decode")
            t_need = sum(len(plans[s]["tokens"]) for s in rows)
            t = next(w for w in self._t_buckets if w >= t_need)
            mb_need = max(self.cache.blocks_for(
                s.seq_len + plans[s]["ntok"]) for s in rows)
            mb = next(w for w in self._mb_widths if w >= mb_need)
            sampled = any(s.sampling.temperature > 0 for s in rows)
            # the last step launched's tokens, where this one reads
            # what that one produced
            batch = self._build_batch(rows, plans, t, mb) + (self._feed,)
            self._step_rung = t
            sp.set("tokens", t_need)
            sp.set("t_bucket", t)
            sp.set("mb", mb)
            if self._stats:
                self._stats.record_step_fill(t_need, t)
        clock.mark("launch")
        with tracer.span("mxtpu.llm.launch", "llm") as sp:
            sp.set("sampled", sampled)
            # the arrays handed over beside weights and pools
            sp.set("args", len(batch))
            self._launched += 1
            out = self._call_step(sampled, batch)
        self._feed = out[0]
        return out

    def _fetch(self, out):
        """The ``fetch`` phase: ONE copy of a launched step's outputs
        to the host, the wait for the device inside it; the expert
        layers' load, where the model has any, rides with the tokens.
        Returns ``(toks, n_acc)``."""
        self._clock.mark("fetch")
        with get_tracer().span("mxtpu.llm.fetch", "llm") as sp:
            toks, n_acc, load = self._device_get(out)
            self._moe_load = None
            if load is not None:
                self._moe_load = tuple(int(v) for v in load)
                if self._stats:
                    self._stats.record_moe(*self._moe_load)
            if sp.span_id is not None:
                sp.set("bytes", sum(a.nbytes for a in (toks, n_acc, load)
                                    if a is not None))
        self._fed = toks
        return toks, n_acc

    def _advance(self, rows, plans):
        """What a launched step writes, in ``seq_len`` at once: the
        next step is planned, and its contexts logged, from there. A
        verify row moves at its commit (the accept rule decides by how
        much), a denoising pass not at all."""
        for seq in rows:
            plan = plans[seq]
            kind = plan["kind"]
            if kind == "prefill" or kind == "settle":
                seq.seq_len = plan["pos"] + plan["ntok"]
            elif kind == "decode" and not plan["k"]:
                seq.seq_len = plan["pos"] + 1

    def _rewind(self, rows, plans):
        """Undo :meth:`_advance` for a step whose results never came."""
        for seq in rows:
            if seq.state == RUNNING:
                seq.seq_len = plans[seq]["pos"]

    def _resolve_fed(self, rows, plans):
        """Fill in from the host the tokens ``rows`` read on the device
        from the step before, once that step is fetched (``_fed``): the
        plans then stand alone, for an isolation's re-dispatches."""
        for seq in rows:
            plan = plans[seq]
            if plan.get("src") is not None:
                plan["tokens"] = [int(self._fed.flat[i])
                                  for i in plan["src"]]
                plan["src"] = None

    def _redispatch(self, rows, plans):
        """A synchronous dispatch from inside the commit phase (the
        halves of an isolation): whatever comes of it, the clock is
        back in ``commit`` after."""
        try:
            out = self._dispatch(rows, plans)
            self._advance(rows, plans)
            try:
                return self._fetch(out)
            except Exception:
                self._rewind(rows, plans)
                raise
        finally:
            self._clock.mark("commit")

    def _sites(self, rows, plans):
        sites = set()
        for s in rows:
            sites.add("prefill" if plans[s]["kind"] == "prefill"
                      else "decode")
        return sites

    def _record_breaker(self, rows, plans, ok):
        if self._breaker is None:
            return
        for site in self._sites(rows, plans):
            if ok:
                self._breaker.record_success(site=site)
            else:
                self._breaker.record_failure(site=site)

    def _commit(self, rows, plans, toks, n_acc, events):
        """Apply one successful dispatch's results to host state.
        Returns the number of committed decode/verify tokens (the
        throughput numerator; chunk-emitted first tokens are counted
        by the prefill metrics)."""
        decoded = 0
        for seq in rows:
            plan = plans[seq]
            cl = plan["cl"]
            if plan["kind"] == "denoise":
                if plan.get("opens"):
                    # a block planned while the one before it settled
                    seq.blk_tokens = list(plan["tokens"])
                    seq.blk_masked = list(plan["blk_masked"])
                    seq.blk_passes = [None] * self._block
                    seq.blk_pass = 0
                # toks: the block after the reveal; n_acc: which of its
                # positions this pass revealed
                for j in np.flatnonzero(n_acc[seq.slot]):
                    seq.blk_tokens[j] = int(toks[seq.slot, j])
                    seq.blk_masked[j] = 0
                    seq.blk_passes[j] = seq.blk_pass
                    self._blk_step[2] += 1
                seq.blk_pass += 1
                self._blk_step[0] += 1
                continue
            if plan["kind"] == "settle":
                self._blk_step[1] += 1
                decoded += self._settle_block(seq, events, plan["pos"])
                continue
            end = plan["pos"] + plan["ntok"]
            if plan["kind"] == "prefill":
                if "draft_fed" in plan:
                    seq.draft_len += plan["draft_fed"]
                if self._stats:
                    self._stats.record_prefill_chunk(plan["ntok"])
                fl = self._flight
                if fl.enabled:
                    fl.event("llm.prefill", req=f"llm:{seq.seq_id}",
                             tenant=seq.tenant,
                             attrs={"ntok": plan["ntok"],
                                    "seq_len": end,
                                    "emit": plan["emit"]})
                if self._block and self._stats and end >= len(
                        seq.prompt) // self._block * self._block:
                    self._stats.record_prefill(end)
                if not plan["emit"]:
                    continue
                # the prompt completed: register its full immutable
                # blocks in the prefix index (later identical prefixes
                # hit them), then commit the first generated token —
                # its logits came out of this chunk's last position
                self._register_blocks(seq, end)
                tok = int(toks[seq.slot, 0])
                seq.generated.append(tok)
                seq.last_token = tok
                events.append(("token", seq))
                if self._stats:
                    # prefill work actually PAID: hit tokens' KV was
                    # served from the cache, never written here
                    self._stats.record_prefill(
                        cl - seq.cache_hit_tokens)
                    self._stats.record_prefill_token()
                self._record_first_token(seq)
                if seq.done or end + 1 >= self.max_context:
                    self._finish(seq, events, end)
                continue
            # decode / speculative verify: commit the accepted drafts
            # plus the replacement/bonus token, truncating at stop /
            # max_new_tokens
            kept = 0
            for j in range(int(n_acc[seq.slot]) + 1):
                tok = int(toks[seq.slot, j])
                seq.generated.append(tok)
                seq.last_token = tok
                kept += 1
                events.append(("token", seq))
                if seq.done:
                    break
            end = plan["pos"] + kept
            decoded += kept
            if plan["k"]:
                seq.seq_len = end
                if self._stats:
                    self._stats.record_spec(plan["k"],
                                            int(n_acc[seq.slot]))
                # roll rejected draft KV back through the STRICT
                # allocator: blocks past the committed length return
                # to the pool (their garbage can never be read — the
                # kv_lens mask stops at seq_len, and a re-allocated
                # block is rewritten before any mask exposes it)
                seq.draft_len = min(cl + plan["k"] - 1, cl + kept - 1)
                keep_blocks = self.cache.blocks_for(
                    max(seq.seq_len, 1))
                if len(seq.block_ids) > keep_blocks:
                    self.cache.allocator.free(
                        seq.block_ids[keep_blocks:])
                    del seq.block_ids[keep_blocks:]
            elif "draft_fed" in plan:
                # catch-up-only feed advanced the draft prefix
                seq.draft_len += plan["draft_fed"]
            if seq.state == RUNNING and (
                    seq.done or end + 1 >= self.max_context):
                self._finish(seq, events, end)
        return decoded

    def _settle_block(self, seq, events, pos=None):
        """The host's half of a settling pass over the open block at
        ``pos``: its K/V stands, its tokens are answered (those past
        ``max_new_tokens`` or a stop token go to ``block_tail``) and the
        row has no open block. ``seq_len`` moved over the block when the
        pass was launched; with no ``pos`` (a block settled where no
        settling pass was launched, as ``benchmark/
        fault_readings_blockdiff.py`` plants it) it moves now. A block
        already answered answers nothing. Returns the tokens
        answered."""
        B = self._block
        if pos is None:
            pos = seq.seq_len
            seq.seq_len += B
        if seq.blk_tokens is None:
            return 0
        known = len(seq.prompt) + len(seq.generated) - pos
        kept, ends = self._block_answer(seq, pos)
        block = list(zip(seq.blk_tokens[known:], seq.blk_passes[known:]))
        for tok, at in block[:kept]:
            seq.generated.append(tok)
            seq.last_token = tok
            seq.reveal_pass.append(at)
            events.append(("token", seq))
        seq.block_tail.extend(block[kept:])
        seq.blk_tokens = seq.blk_masked = seq.blk_passes = None
        seq.blk_pass = 0
        self._record_first_token(seq)
        if ends:
            self._finish(seq, events, pos + B)
        return kept

    def _record_first_token(self, seq):
        """The moment a sequence's first token is answered, once."""
        if seq.t_first_token is not None:
            return
        seq.t_first_token = time.monotonic()
        if self._stats:
            # exemplar joins this TTFT observation back to the
            # request's flight timeline / trace span
            ex = None
            if self._flight.enabled:
                ex = (f"llm:{seq.seq_id}", seq.span.span_id
                      if seq.span is not None else None)
            self._stats.record_first_token(
                seq.t_first_token - seq.t_submit, exemplar=ex)

    def _isolate(self, rows, plans, events):
        """Bisect-retry a failing dispatch to isolate the poison
        row(s): halves re-dispatch through the SAME fixed-shape
        program (no recompiles); a failing singleton is evicted with
        its dispatch exception, everything else keeps its tokens.
        Returns the committed decode-token count."""
        if len(rows) == 1:
            try:
                toks, n_acc = self._redispatch(rows, plans)
            except Exception as exc:
                if self._pages_deleted():
                    raise       # KV pool gone mid-bisect: fatal
                self._poison(rows[0], exc, events)
                return 0
            # a successful sub-dispatch proves the backend is healthy:
            # recurring poison rows isolate forever without ever
            # accumulating into a breaker trip
            self._record_breaker(rows, plans, True)
            return self._commit(rows, plans, toks, n_acc, events)
        decoded = 0
        mid = len(rows) // 2
        for half in (rows[:mid], rows[mid:]):
            try:
                toks, n_acc = self._redispatch(half, plans)
            except Exception:
                if self._pages_deleted():
                    raise       # KV pool gone mid-bisect: fatal
                decoded += self._isolate(half, plans, events)
            else:
                self._record_breaker(half, plans, True)
                decoded += self._commit(half, plans, toks, n_acc,
                                        events)
        return decoded

    # --------------------------------------------------------- step --
    def _pages_deleted(self):
        """True when the KV page buffers were consumed by a FAILED
        donated dispatch (TPU: ``donate_argnums`` hands them to the
        runtime even when the launch errors). Retrying against deleted
        buffers would cascade every live sequence into a false poison
        verdict — so the isolation paths treat this as fatal engine
        state and re-raise instead, letting the server's worker-death
        cleanup resolve every Future typed."""
        for cache in (self.cache, self.draft_cache):
            if cache is None:
                continue
            is_del = getattr(cache.pools()[0], "is_deleted", None)
            try:
                if bool(is_del and is_del()):
                    return True
            except Exception:       # non-jax array backends
                pass
        return False

    def step(self):
        """One engine iteration, synchronous: schedule, build, launch,
        fetch and commit. Returns events: ``[("admitted"|"token"|
        "finished"|"preempted"|"expired"|"poisoned", Sequence)]``.

        The span ``mxtpu.llm.step`` covers the whole iteration, and its
        children ``schedule``, ``build``, ``launch``, ``fetch`` and
        ``commit`` cover it: no statement of the iteration stands
        between two of them. Each opens at a mark of the thread's phase
        clock (:class:`~.metrics.PhaseClock`), which is the one clock a
        step is timed by."""
        events = self._iterate(keep=False)
        if not self._served:
            # nobody's time until the next step
            self._clock.mark(None)
        return events

    def step_ahead(self):
        """One engine iteration with a step left in flight (what
        :class:`~.server.LLMServer` runs): schedule step N+1, build and
        launch it, then fetch and commit step N, so that the host's
        work and the fetch's round trip run while the device executes
        N+1. A row of N+1 that feeds what N produces reads it from N's
        output on the device.

        N+1 is fetched and committed at once, as :meth:`step` does,
        where its successor cannot be planned without its values on the
        host: a row with drafts (the accept rule decides its position)
        or in an engine with a draft model (whose feeds are committed
        tokens), a block pass under a confidence strategy, a step that
        failed. An allocation that has to preempt commits N first; an
        iteration with nothing to launch commits N alone.

        Spans: the iteration's ``mxtpu.llm.step`` carries N+1's rows,
        and its five children cover the iteration: N+1's ``schedule``,
        ``build`` and ``launch``, N's ``fetch`` and ``commit`` (empty
        where nothing was in flight). The span is recorded with N+1's
        commit, an iteration later, so that it carries what comes back
        of N+1 (its experts' load). An iteration that launches nothing
        records no step span."""
        return self._iterate(keep=True)

    def sync(self):
        """Fetch and commit the step in flight, if any. Returns its
        events."""
        events = []
        if self._inflight is not None:
            self._land(self._inflight, events)
            if not self._served:
                self._clock.mark(None)
        return events

    def _iterate(self, keep):
        clock, tracer = self._clock, get_tracer()
        events = []
        prev, landed = self._inflight, False
        clock.mark("schedule")
        sp = tracer.span("mxtpu.llm.step", "llm")
        try:
            rows, plans, prefilling = self._plan_step(tracer, sp, events)
        except _Behind:
            # preemption waits for the step in flight's commit
            self._land(prev, events)
            prev, landed = None, True
            clock.mark("schedule")
            rows, plans, prefilling = self._plan_step(tracer, sp, events)
        new = None
        if rows:
            new = self._launch(sp, rows, plans, prefilling,
                               overlapped=prev is not None)
        ahead = (keep and new is not None and new["ahead"]
                 and new["error"] is None)
        if prev is not None:
            self._land(prev, events, newer=new)
        elif ahead and not landed:
            self._land_nothing()
        if new is None:
            if prev is not None:
                # nothing launched: the iteration committed the step in
                # flight, and is no step's
                sp.discard()
            else:
                sp.finish()
        elif new.get("dropped"):
            sp.finish()
        elif ahead:
            sp.stop()
            self._inflight = new
        else:
            self._land(new, events)
        return events

    def _land_nothing(self):
        """The ``fetch`` and ``commit`` of an iteration that had no step
        in flight: empty, so that every step span has its five
        children."""
        tracer = get_tracer()
        self._clock.mark("fetch")
        with tracer.span("mxtpu.llm.fetch", "llm") as sp:
            sp.set("bytes", 0)
        self._clock.mark("commit")
        with tracer.span("mxtpu.llm.commit", "llm") as sp:
            sp.set("decoded", 0)

    def _plan_step(self, tracer, sp, events):
        """The ``schedule`` phase under its span: ``(rows, plans, how
        many rows prefill)``."""
        with tracer.span("mxtpu.llm.schedule", "llm") as ssp:
            rows, plans = self._schedule(events)
            if ssp.span_id is not None:
                ssp.set("admitted", sum(
                    1 for kind, _ in events if kind == "admitted"))
                ssp.set("waiting", self.scheduler.num_waiting)
            if rows:
                return rows, plans, self._describe_step(sp, rows, plans)
            self._record_block_gauges()
        return rows, plans, None

    def _lets_ahead(self, rows, plans):
        """Whether the step after this one can be planned before its
        values are on the host."""
        if self.draft_model is not None:
            return False
        strategy = (self._blockdiff or {}).get("remasking_strategy")
        return not any(
            plans[s]["k"] or (plans[s]["kind"] in ("denoise", "settle")
                              and strategy != "sequential")
            for s in rows)

    def _launch(self, sp, rows, plans, prefilling, overlapped):
        """Build and launch ``rows`` under the step span ``sp``; the
        record :meth:`_land` takes."""
        rec = {"rows": rows, "plans": plans, "span": sp,
               "prefilling": prefilling, "overlapped": overlapped,
               "ahead": self._lets_ahead(rows, plans), "out": None,
               "error": None}
        sp.set("overlapped", overlapped)
        try:
            rec["out"] = self._dispatch(rows, plans)
        except Exception as exc:
            if self._pages_deleted():
                raise       # KV pool gone: isolation impossible
            sp.set("error", repr(exc))
            rec["error"] = exc
            return rec
        self._advance(rows, plans)
        if overlapped and self._stats:
            self._stats.record_overlapped()
        return rec

    def _drop(self, rec):
        """A step launched after one that failed, whose plan stands on
        what that one was to produce: its results, if its launch gave
        any, are waited for and thrown away, its rows planned again."""
        if rec["error"] is None:
            try:
                self._device_get(rec["out"])
            except Exception:
                pass
            self._rewind(rec["rows"], rec["plans"])
        rec["dropped"] = True
        rec["span"].set("dropped", True)

    def _land(self, rec, events, newer=None):
        """Fetch a launched step and commit what comes back, under the
        span open (the iteration's); the step's bookkeeping. The step's
        own span, which carries its rows, takes what came back and is
        recorded then. ``newer``: the step launched after it, if any;
        where this one failed, ``newer`` is dropped."""
        tracer, fl = get_tracer(), self._flight
        rows, plans, sp = rec["rows"], rec["plans"], rec["span"]
        if self._inflight is rec:
            self._inflight = None
        self._blk_step = [0, 0, 0]
        out = None
        if rec["error"] is None:
            try:
                out = self._fetch(rec["out"])
            except Exception as exc:
                if self._pages_deleted():
                    raise   # KV pool gone: isolation impossible
                sp.set("error", repr(exc))
                if newer is not None:
                    self._drop(newer)
                self._rewind(rows, plans)
        # commit, and the step's bookkeeping once its results are in
        self._clock.mark("commit")
        with tracer.span("mxtpu.llm.commit", "llm") as csp:
            self._record_breaker(rows, plans, out is not None)
            # a row whose sequence ended before this step's commit (at
            # the step before it, or expired, cancelled, isolated since
            # its launch) has nothing to commit
            live = [s for s in rows if s.state == RUNNING]
            if len(live) < len(rows) and self._stats:
                self._stats.record_overlap_discarded(len(rows) - len(live))
            if out is None:
                self._resolve_fed(live, plans)
                with tracer.span("mxtpu.llm.isolate", "llm") as isp:
                    isp.set("n", len(live))
                    decoded = self._isolate(live, plans, events) \
                        if live else 0
            else:
                if self._has_state:
                    self._record_state(sp, rows, plans)
                decoded = self._commit(live, plans, *out, events)
                if self._moe_load is not None and sp.span_id is not None:
                    # over the step's expert layers: pairs and experts
                    # hit summed, the largest load of one expert, the
                    # row tiles the pairs fill summed
                    for key, v in zip(("moe_pairs", "moe_experts_hit",
                                       "moe_max_load", "moe_tiles"),
                                      self._moe_load):
                        sp.set(key, v)
                    if len(self._moe_load) == 5:
                        # a model that holds a share of its experts: the
                        # pairs routed to the others, summed
                        sp.set("moe_pairs_elsewhere", self._moe_load[4])
            csp.set("decoded", decoded)
            if self._block:
                # what the committed passes did (isolated halves among
                # them)
                if self._stats:
                    self._stats.record_blockdiff(*self._blk_step)
                for key, v in zip(("denoise_rows", "settle_rows",
                                   "revealed"), self._blk_step):
                    sp.set(key, v)
            # the step's one hand-over of the thread's clock: what it
            # holds is this iteration's phases so far, with the loop's
            # resolve and wait before it
            self._clock.mark("commit")
            ns = hand_over(self._clock, self._stats, fl, self._launched,
                           running=len(rows), rung=self._step_rung)
            self._launched = 0
            # dispatch and commit together, as ever
            step_s = (ns["build"] + ns["launch"] + ns["fetch"]
                      + ns["commit"]) / 1e9
            if self._stats and any(plans[s]["kind"] != "prefill"
                                   for s in rows):
                self._stats.record_decode_step(decoded, step_s)
            if fl.enabled:
                fl.event("llm.step",
                         attrs={"running": len(rows),
                                "prefilling": rec["prefilling"],
                                "decoded": decoded,
                                "overlapped": rec["overlapped"],
                                "step_ms": round(step_s * 1e3, 3),
                                "phases_ms": {
                                    p: round(v / 1e6, 3)
                                    for p, v in ns.items() if v}})
            self._record_block_gauges()
        sp.finish()

    def _describe_step(self, sp, rows, plans):
        """What the step span says of the rows about to be dispatched.
        Returns how many of them are prefilling, where someone asks."""
        if sp.span_id is None and not self._flight.enabled:
            return None
        prefilling = sum(1 for s in rows if plans[s]["kind"] == "prefill")
        if sp.span_id is not None:
            sp.set("running", len(rows))
            sp.set("prefilling", prefilling)
            if self._windowed:
                # the pages the step's sequences hold, and what an
                # all-full layout would hold for them
                w = self.cache.window_stats()
                sp.set("kv_pages_held", sum(w["pages_held"].values()))
                sp.set("kv_pages_full_layout", w["pages_full_layout"])
            # (tokens fed, context after) of each row
            sp.set("rows", [(len(plans[s]["tokens"]),
                             s.seq_len + len(plans[s]["tokens"]))
                            for s in rows])
        return prefilling

    def _schedule(self, events):
        """The host's half of an iteration before any launch: expire,
        admit, plan and allocate each running sequence, propose
        drafts. Returns ``(rows, plans)``, the rows in admission order;
        no rows, nothing to dispatch."""
        self._expire(events)
        self._admit(events)
        running = sorted(self.scheduler.running(),
                         key=lambda s: s.admit_index)
        plans = {}
        # the step's token budget: the rows that decode or verify take
        # theirs first (a slot's share each at the most), the rows
        # still writing their prompt what is left, in admission order.
        # One the budget does not reach is in no plan: it rides the
        # step inactive on the null block, as an empty slot does. (What
        # a row took before a later one's blocks preempted it is not
        # dealt again.)
        left = self._step_budget
        for seq in ([s for s in running if not self._prefilling(s)]
                    + [s for s in running if self._prefilling(s)]):
            if seq.state != RUNNING:
                continue            # preempted by an earlier victim
            if left <= 0:
                break
            plan = self._plan(seq, left, events)
            if plan is None:
                continue            # poison-isolated at prefill start
            self._allocate(seq, plan, events)
            plans[seq] = plan
            left -= plan["cost"]
        rows = [s for s in running
                if s.state == RUNNING and s in plans]
        if rows:
            self._draft_propose(rows, plans)
            rows = [s for s in rows if s.state == RUNNING]
        return rows, plans

    def state_pool_stats(self):
        """The slot state pools and what the steps did to them, for
        ``stats()`` and ``debug_status()``: slots, shapes and bytes
        (all zero or empty for a model without slot state), rows
        advanced and runs started over the engine's life."""
        return dict(self.cache.state_stats(), rows=self.state_rows,
                    starts=self.state_starts)

    def _record_state(self, sp, rows, plans):
        """What a dispatched step did to the slot state pools, before
        the commit: every row's state advanced, a row
        at position 0 began a run from zeros (an admission or a
        re-admission), a row of more than one token ran a prefill
        chunk through the recurrence."""
        starts = sum(1 for s in rows if plans[s]["pos"] == 0)
        self.state_rows += len(rows)
        self.state_starts += starts
        if self._stats:
            self._stats.record_state_step(len(rows), starts)
        if sp.span_id is not None:
            sp.set("state_rows", len(rows))
            sp.set("state_runs_prefill", sum(
                1 for s in rows if len(plans[s]["tokens"]) > 1))

    def pop_finished(self):
        """Drain the finished-but-unreported sequences. The server
        resolves Futures from THIS (not from step()'s event list) so a
        completion can survive an exception later in the same step."""
        out, self._finished_pending = self._finished_pending, []
        return out

    def pop_dead(self):
        """Drain the deadline-expired / cancelled ``(seq, reason)``
        records (the server resolves them with
        ``DeadlineExceededError`` carrying partial tokens)."""
        out, self._dead_pending = self._dead_pending, []
        return out

    def pop_poison(self):
        """Drain the poison-isolated ``(seq, exc)`` records (the
        server resolves them with the original dispatch exception)."""
        out, self._poison_pending = self._poison_pending, []
        return out

    # -------------------------------------------------------- drain --
    def evict_all(self, reason="evicted"):
        """Release every live sequence (running AND waiting) into the
        EVICTED state, freeing its blocks — including blocks a
        sequence dying mid-verify still holds for speculative
        positions (the draft cache shares them, so one free covers
        both pools). Returns the evicted sequences — the server turns
        them into ``SequenceEvictedError`` resolutions carrying
        partial tokens, never silent drops. The step in flight is
        committed first (what it finishes is in :meth:`pop_finished`);
        one whose results cannot be fetched is dropped."""
        rec = self._inflight
        if rec is not None:
            try:
                self.sync()
            except Exception:
                self._inflight = None
                rec["span"].discard()
        out = []
        for seq in self.scheduler.running():
            self.cache.free_seq(seq)
            self._release_adapter(seq)
            self.scheduler.release(seq, EVICTED, reason)
            out.append(seq)
        while self.scheduler.waiting:
            seq = self.scheduler.waiting.popleft()
            self.cache.free_seq(seq)  # waiting: normally no blocks
            self._release_adapter(seq)
            self.scheduler.release(seq, EVICTED, reason)
            out.append(seq)
        self._record_block_gauges()
        return out

    # ------------------------------------------------------ statusz --
    def debug_status(self):
        """Structured point-in-time engine state for the flight
        recorder's statusz surface (bundled into every post-mortem
        dump). Advisory read — called from the worker thread by the
        servers' ``debug_status()`` and best-effort from dump paths;
        every field is plain host state, so a torn read can misreport
        a count but never touch device state or recompile."""
        a = self.cache.allocator
        now = time.monotonic()
        seqs = []
        for seq in list(self.scheduler.running()) + \
                list(self.scheduler.waiting):
            seqs.append({
                "seq_id": seq.seq_id, "state": seq.state,
                "tenant": seq.tenant, "adapter": seq.adapter,
                "slot": seq.slot, "seq_len": seq.seq_len,
                "generated": len(seq.generated),
                "preemptions": seq.preemptions,
                "cache_hit_tokens": seq.cache_hit_tokens,
                "age_s": round(now - seq.t_submit, 3)})
        return {
            "waiting": self.scheduler.num_waiting,
            "running": self.scheduler.num_running,
            "kv_blocks": {"used": a.num_used, "usable": a.num_usable,
                          "cached": a.num_cached,
                          "shared": a.num_shared,
                          "free": a.num_free - a.num_cached,
                          "cow_count": self.cache.cow_count},
            "in_flight": (len(self._inflight["rows"])
                          if self._inflight else 0),
            "programs": {"t_buckets": list(self._t_buckets),
                         "mb_widths": list(self._mb_widths),
                         "warmed": self._warmed,
                         "step_variants": len(self._step_jits),
                         "spec_k": self.spec_k,
                         "prefill_chunk": self.prefill_chunk,
                         "block_diffusion": self._blockdiff},
            "state_pool": self.state_pool_stats(),
            "prefix_cache": {"enabled": self.prefix_enabled,
                             "lookups": self.prefix_lookups,
                             "hits": self.prefix_hits,
                             "tokens_saved": self.prefill_tokens_saved},
            "weights": {"dtype": self.weight_dtype,
                        "calib": self.weight_calib,
                        "bytes": self.weight_bytes,
                        "params": self.weight_params,
                        "params_per_chip":
                            self.weight_params // max(1, self.tp),
                        "draft_dtype": self.draft_weight_dtype,
                        "kv_dtype": self.cache.dtype.name,
                        "kv_dtype_fallbacks": self.kv_dtype_fallbacks},
            "adapters": self.bank.stats() if self.bank is not None
            else None,
            "mesh": None if self.mesh is None else {
                "devices": int(self.mesh.devices.size),
                "axes": {k: int(v)
                         for k, v in dict(self.mesh.shape).items()},
                "tp": self.tp,
                "spmd_step_dispatches": self.spmd_dispatches,
                "kv": self.cache.shard_info(),
            },
            "sequences": seqs,
        }
