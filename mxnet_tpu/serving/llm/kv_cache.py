"""Paged KV cache: a fixed pool of KV blocks + a refcounted allocator.

The memory model behind continuous batching (vLLM's PagedAttention,
and the TPU-side "Ragged Paged Attention" kernel shape): instead of one
contiguous ``[max_seqs, max_context, ...]`` KV tensor — whose worst-case
shape wastes almost all of it on short sequences — the cache is a pool
of ``num_blocks`` fixed-size blocks, ``[block_size]`` token slots each,
handed out on demand:

- a sequence owns ``ceil(seq_len / block_size)`` blocks, listed in its
  *block table* — an int32 row of page indices, padded with the
  reserved NULL block 0;
- the attention kernel indirects every KV read through the block
  table (:mod:`mxnet_tpu.ops.ragged_attention` — one multi-token
  chunk shape for prefill, decode and speculative verify), so blocks
  never need to be contiguous or ordered;
- block 0 is never allocated: padded table entries, whole padded tail
  BLOCKS of a bucketed prompt, and inactive batch rows all point at
  it. Note the protection boundary precisely: pad positions that land
  INSIDE a sequence's own last live block DO get written with garbage
  K/V — what keeps every output correct is the ``kv_lens`` mask (no
  read past the valid length, pinned by the garbage-invisibility
  test) plus decode overwriting each slot before ``kv_lens`` ever
  reaches it. Block 0's contents are scratch; inactive rows'
  attention outputs are discarded, never interpreted.

Cross-request prefix caching (ISSUE 13) extends the ownership model
from exclusive to REFCOUNTED: a full, immutable block of a prompt
prefix is content-addressed by a chained per-block hash
(:func:`prefix_block_hashes`) and can back many sequences at once —
each owner holds a normal entry in its block table, the allocator
holds one refcount per block. The lifecycle:

- ``alloc()`` hands out blocks at refcount 1 (exclusive, as before);
- a prefix-cache hit ``ref()``-s an existing block instead of
  allocating and prefilling it;
- ``free()`` DECREMENTS; a block only leaves circulation at zero;
- a zero-refcount block that is registered in the prefix index
  (:meth:`PagedKVCache.register`) is not returned to the free list —
  it parks in an LRU of CACHED blocks, its contents preserved for
  future hits, but remains fully reclaimable: ``alloc()`` evicts the
  oldest cached blocks (dropping their index entries) whenever the
  strict free list runs short. Cached blocks are spare capacity, so
  ``num_free``/``can_alloc`` count them — they can never read as a
  leak;
- TARGET-pool writes into a block whose refcount is above 1 are
  forbidden; the engine copy-on-writes the block first (the "first
  divergence" of two sequences sharing a prefix). Draft-pool catch-up
  writes are exempt: they recompute byte-identical rows from the
  shared committed prefix (see ``LLMEngine._draft_propose``).

The allocator stays strict by design: over-allocating raises
:class:`NoFreeBlocksError` (the scheduler's signal to evict), freeing a
block that is not currently allocated raises
:class:`BlockAccountingError` — a leak, double-free or refcount drift
is a bug worth crashing on, not a statistic (pinned by the 1k-schedule
fuzz tests in tests/test_ragged_attention.py, now covering
ref/cache/reclaim churn).

The block arrays themselves are jnp buffers ``[num_layers, num_blocks,
block_size, heads, head_dim]``, updated FUNCTIONALLY by the engine's
jitted programs (donated in, swapped back via :meth:`swap`), so the
decode hot path stays a fixed-shape, zero-recompile XLA program. With
``dtype="int8"`` the pages store per-slot-scale quantized K/V
(``k_scales``/``v_scales`` f32 ``[num_layers, num_blocks, block_size,
heads]`` ride along) and the ragged kernels dequantize in-kernel —
roughly 4x the blocks per byte of a float32 pool.
"""
from __future__ import annotations

import collections
import hashlib

import numpy as np

__all__ = ["KVCacheError", "NoFreeBlocksError", "BlockAccountingError",
           "BlockAllocator", "PagedKVCache", "LayerPages", "NULL_BLOCK",
           "prefix_block_hashes"]

# block 0 is reserved: the write/read sink for padding and inactive rows
NULL_BLOCK = 0


class KVCacheError(RuntimeError):
    """Base class for paged-KV-cache failures."""


class NoFreeBlocksError(KVCacheError):
    """alloc() could not satisfy the request; evict and retry."""


class BlockAccountingError(KVCacheError):
    """free() of a block that is not allocated (double-free / corrupt
    table), or a refcount/partition drift — always a caller bug."""


def prefix_block_hashes(tokens, block_size, salt=b""):
    """Chained content hashes of the FULL blocks of ``tokens``: hash k
    covers tokens ``[0, (k+1)*block_size)`` — block k's content chained
    onto hash k-1 — so equal hashes imply equal whole prefixes, not
    just equal blocks. The partial tail block is never hashed (it is
    mutable). ``salt`` seeds the chain: KV written under a LoRA
    adapter embeds that adapter's K/V deltas, so the engine namespaces
    the whole chain by the pinned adapter identity — equal tokens
    under different adapters (or versions) never share blocks.
    Returns a list of hex digests, one per full block."""
    out = []
    h = bytes(salt)
    n_full = len(tokens) // block_size
    for k in range(n_full):
        m = hashlib.blake2b(digest_size=16)
        m.update(h)
        m.update(np.asarray(tokens[k * block_size:(k + 1) * block_size],
                            np.int64).tobytes())
        h = m.digest()
        out.append(h.hex())
    return out


class BlockAllocator:
    """Refcounted free-list allocator over block ids ``1..num_blocks-1``.

    All-or-nothing ``alloc(n)``; strict double-free detection; O(1)
    occupancy accounting. Zero-refcount blocks marked *cacheable*
    (prefix-cache registration) park in an LRU instead of the free
    list and are reclaimed — oldest first, via ``reclaim_cb`` so the
    index can drop them — when a later ``alloc`` outgrows the strict
    free list. Not thread-safe — the engine loop is the only caller
    (one thread), matching the serving worker discipline.
    """

    def __init__(self, num_blocks, reclaim_cb=None):
        if num_blocks < 2:
            raise ValueError(
                f"need >= 2 blocks (1 usable + the reserved null block "
                f"{NULL_BLOCK}), got {num_blocks}")
        self.num_blocks = int(num_blocks)
        self._free = collections.deque(range(1, num_blocks))
        self._ref = {}                      # block id -> refcount >= 1
        # zero-refcount blocks with live cached contents, oldest first
        self._cached = collections.OrderedDict()
        self._cacheable = set()             # registered in a prefix index
        self._reclaim_cb = reclaim_cb
        # blocks at refcount > 1, maintained incrementally on the
        # 1<->2 crossings — the per-step metrics hook reads this every
        # engine iteration, so it must not rescan the refcount dict
        self._num_shared = 0

    @property
    def num_usable(self):
        """Total allocatable blocks (the pool minus the null block)."""
        return self.num_blocks - 1

    @property
    def num_free(self):
        """Blocks an ``alloc`` can draw on NOW: the strict free list
        plus the reclaimable cached LRU (cached blocks are spare
        capacity, never a leak)."""
        return len(self._free) + len(self._cached)

    @property
    def num_used(self):
        """Blocks with refcount >= 1 (owned by at least one sequence)."""
        return len(self._ref)

    @property
    def num_cached(self):
        """Zero-refcount blocks parked in the prefix-cache LRU."""
        return len(self._cached)

    @property
    def num_shared(self):
        """Blocks owned by MORE than one live sequence (refcount > 1)."""
        return self._num_shared

    def occupancy(self):
        """Fraction of usable blocks currently allocated."""
        return self.num_used / float(self.num_usable)

    def can_alloc(self, n):
        return n <= self.num_free

    def refcount(self, block):
        """Live owners of ``block`` (0 = free or cached)."""
        return self._ref.get(block, 0)

    def alloc(self, n=1):
        """Allocate ``n`` blocks at refcount 1; returns their ids.
        All-or-nothing: raises NoFreeBlocksError without touching the
        pool when fewer than ``n`` are free+cached. Draws the strict
        free list first, then reclaims cached blocks LRU-oldest-first
        (``reclaim_cb(block)`` fires per reclaim so the prefix index
        drops its entry before the block is rewritten)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > self.num_free:
            raise NoFreeBlocksError(
                f"need {n} blocks, {self.num_free} free "
                f"({self.num_used}/{self.num_usable} in use, "
                f"{self.num_cached} cached)")
        out = []
        for _ in range(n):
            if self._free:
                b = self._free.popleft()
            else:
                b, _ = self._cached.popitem(last=False)   # LRU evict
                self._cacheable.discard(b)
                if self._reclaim_cb is not None:
                    self._reclaim_cb(b)
            self._ref[b] = 1
            out.append(b)
        return out

    def ref(self, block):
        """Take one more reference on a live or cached block (a
        prefix-cache hit). A cached block revives at refcount 1 — its
        contents are live again, its index registration stands."""
        if block in self._cached:
            del self._cached[block]
            self._ref[block] = 1
        elif block in self._ref:
            if self._ref[block] == 1:
                self._num_shared += 1
            self._ref[block] += 1
        else:
            raise BlockAccountingError(
                f"ref() of block {block} which is neither allocated "
                "nor cached")

    def mark_cacheable(self, block):
        """Flag a LIVE block as prefix-index-registered: when its
        refcount drops to zero it parks in the cached LRU instead of
        the free list."""
        if block not in self._ref:
            raise BlockAccountingError(
                f"mark_cacheable() of unallocated block {block}")
        self._cacheable.add(block)

    def free(self, blocks):
        """Drop one reference per block. A block reaching refcount 0
        returns to the free list — or to the cached LRU when it is
        prefix-registered. Raises BlockAccountingError on the null
        block, an out-of-range id, a block with no live references
        (double-free), or a duplicate within one call (a sequence
        cannot own the same block twice)."""
        blocks = list(blocks)
        for b in blocks:                      # validate before mutating
            if b == NULL_BLOCK:
                raise BlockAccountingError(
                    f"block {NULL_BLOCK} is the reserved null block")
            if not (0 < b < self.num_blocks):
                raise BlockAccountingError(f"block {b} out of range")
            if b not in self._ref:
                raise BlockAccountingError(
                    f"block {b} is not allocated (double free?)")
        if len(set(blocks)) != len(blocks):
            raise BlockAccountingError(
                f"duplicate blocks in free(): {blocks}")
        for b in blocks:
            self._ref[b] -= 1
            if self._ref[b] == 1:
                self._num_shared -= 1
            elif self._ref[b] == 0:
                del self._ref[b]
                if b in self._cacheable:
                    self._cached[b] = None    # most-recently released
                else:
                    self._free.append(b)

    def check(self):
        """Invariant: every block is exactly one of {null, free,
        refcounted, cached}; refcounts are positive; every cached
        block is registered cacheable. Raises BlockAccountingError on
        violation; returns True."""
        free = set(self._free)
        if len(free) != len(self._free):
            raise BlockAccountingError("duplicate ids in free list")
        cached = set(self._cached)
        used = set(self._ref)
        if free & used or free & cached or used & cached:
            raise BlockAccountingError(
                "blocks in more than one of free/used/cached: "
                f"{sorted((free & used) | (free & cached) | (used & cached))}")
        if len(free) + len(used) + len(cached) != self.num_usable:
            raise BlockAccountingError(
                f"leak: {self.num_usable - len(free) - len(used) - len(cached)} "
                "blocks neither free, used nor cached")
        bad = [b for b, c in self._ref.items() if c < 1]
        if bad:
            raise BlockAccountingError(f"non-positive refcounts: {bad}")
        shared = sum(1 for c in self._ref.values() if c > 1)
        if shared != self._num_shared:
            raise BlockAccountingError(
                f"shared-block counter drift: {self._num_shared} "
                f"tracked, {shared} actual")
        if not cached <= self._cacheable:
            raise BlockAccountingError(
                f"cached blocks missing their cacheable flag: "
                f"{sorted(cached - self._cacheable)}")
        return True


class LayerPages:
    """One sequence's pages in a cache with window layers: ``table[l, b]``
    is the page of layer ``l`` that holds the sequence's block ``b`` (the
    null block where a window gave it back, and from ``blocks`` on), and
    ``window_from`` the first block whose window pages it still holds. The
    step's per-layer tables are copied from ``table`` a row at a time."""

    __slots__ = ("table", "blocks", "window_from")

    def __init__(self, num_layers, max_blocks):
        self.table = np.full((num_layers, max_blocks), NULL_BLOCK, np.int32)
        self.blocks = 0
        self.window_from = 0


class PagedKVCache:
    """The block pool's storage + allocator + block-table helpers +
    the cross-request prefix index.

    K and V pages are jnp arrays of shape ``[num_layers, num_blocks,
    block_size, num_heads, head_dim]``. The engine passes them into its
    donated jitted programs and swaps the returned buffers back in via
    :meth:`swap` — the cache object itself never mutates device memory.

    ``dtype="int8"`` selects quantized storage: pages hold int8 values
    and per-(layer, block, slot, head) f32 scales ride in
    ``k_scales``/``v_scales`` — the engine's programs quantize on
    write and the ragged kernels dequantize on read.
    ``dtype="float8_e4m3fn"`` (ISSUE 20; engines accept the ``fp8``
    alias with an availability guard) stores the same scale-per-slot
    layout at fp8 width — the write path scales into ±448 and lets
    the cast round, the dequant multiply is identical.

    ``latent_dim`` makes the pool ONE array of rows ``[num_layers,
    num_blocks, block_size, W]`` with no head axis and no V pool: a
    latent cache, or (``kind="kv_packed"``) a token's value heads and
    then its key heads. ``state`` adds, beside the pages, float32 pools
    ``[layers, state_slots, *shape]`` of what each SLOT (a sequence's
    row of the block tables) holds and overwrites token by token (a
    state-space layer's recurrent state, its convolution's window):
    :meth:`pools` lists them after the pages and :meth:`swap` takes them
    back; no block, table or hash knows of them.

    ``window_layers`` (with ``window``, ``W``, and ``step_tokens``, the
    most tokens one step writes for one sequence) makes a cache of TWO
    KINDS of layer in one pool. A page is then ONE layer's ``block_size``
    rows, the pool is ``[1, num_blocks, block_size, W]`` and every layer
    draws its pages from the one allocator: a sequence holds a full
    layer's pages for all its positions and a window layer's only from
    its window's first position on (:meth:`grow`; a page that falls
    wholly behind it goes back to the free list at once, for either kind
    to take), and the step is handed one table a layer
    (:meth:`fill_tables`). Nothing splits the pool between the kinds.
    Neither quantized, sharded nor prefix-cached.

    ``prefix_cache=True`` enables the content-addressed prefix index:
    :meth:`register` maps a chained block hash to a live block,
    :meth:`prefix_get` answers hit lookups, and LRU reclaims (the
    allocator outgrowing its strict free list) drop entries and count
    on ``prefix_evictions`` / fire ``on_prefix_evict``.
    """

    QUANTIZED_DTYPES = ("int8", "float8_e4m3fn")
    #: a latent pool's minor axis is padded to whole lanes of this many
    LANES = 128

    @classmethod
    def for_model(cls, model, block_size, num_blocks, max_context,
                  max_seqs=0, step_tokens=0, **kw):
        """The pools a model's layers write: shaped by its
        ``cache_layout`` (``TinyDecoder.cache_layout``), or K and V
        pools of ``[num_heads, head_dim]`` for a model without one.
        ``max_seqs`` is how many slots a layout's ``state`` is held
        for; ``step_tokens`` the most tokens a step writes for one
        sequence, where the layout has window layers."""
        layout = getattr(model, "cache_layout", None) or {
            "kind": "kv", "num_heads": model.num_heads,
            "head_dim": model.head_dim}
        layers = layout.get("layers", model.num_layers)
        if "state" in layout:
            kw.update(state=layout["state"], state_slots=max_seqs)
        if layout.get("window_layers"):
            kw.update(window_layers=layout["window_layers"],
                      window=layout["window"], step_tokens=step_tokens)
        if layout["kind"] == "latent":
            return cls(layers, 0, 0, block_size, num_blocks, max_context,
                       latent_dim=layout["latent_dim"], **kw)
        if layout["kind"] == "kv_packed":
            # one pool of rows, no head axis: the value heads, then the
            # key heads
            return cls(layers, 0, 0, block_size, num_blocks, max_context,
                       latent_dim=2 * layout["num_heads"]
                       * layout["head_dim"], kind="kv_packed", **kw)
        return cls(layers, layout["num_heads"], layout["head_dim"],
                   block_size, num_blocks, max_context, **kw)

    def __init__(self, num_layers, num_heads, head_dim, block_size,
                 num_blocks, max_context, dtype="float32",
                 prefix_cache=False, mesh=None, shard_axis="tp",
                 latent_dim=0, kind=None, state=None, state_slots=0,
                 window_layers=(), window=0, step_tokens=0):
        import jax.numpy as jnp
        if max_context < 1:
            raise ValueError(f"max_context must be >= 1, {max_context}")
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        # "kv": K and V pools of [num_heads, head_dim] a slot; "latent"
        # and "kv_packed": ONE pool of ``latent_dim`` values a slot, no
        # head axis
        self.latent_dim = int(latent_dim)
        self.kind = kind or ("latent" if self.latent_dim else "kv")
        self.block_size = int(block_size)
        self.num_blocks = int(num_blocks)
        self.max_context = int(max_context)
        self.dtype = np.dtype(dtype)
        self.quantized = self.dtype.name in self.QUANTIZED_DTYPES
        # every sequence's table has room for a full-context sequence
        self.max_blocks_per_seq = -(-self.max_context // self.block_size)
        # two kinds of layer in one pool of one-layer pages
        self.window_layers = tuple(int(i) for i in window_layers)
        self.windowed = bool(self.window_layers)
        self.window = int(window)
        self.step_tokens = 0
        # pages held now, by kind; the sequences' blocks (what an
        # all-full layout holds a layer); window pages given back
        self.pages_held = {"window": 0, "full": 0}
        self.blocks_covered = 0
        self.window_released = 0
        if self.windowed:
            if not (self.window >= 1 and step_tokens >= 1
                    and all(0 <= i < self.num_layers
                            for i in self.window_layers)):
                raise KVCacheError(
                    f"window layers {list(self.window_layers)} of "
                    f"{self.num_layers} need window >= 1 and step_tokens "
                    f">= 1, got {window} and {step_tokens}")
            if self.dtype.name in self.QUANTIZED_DTYPES or mesh is not None \
                    or prefix_cache or state or not latent_dim:
                raise KVCacheError(
                    "a cache with window layers is one pool of rows, "
                    "neither quantized, sharded, prefix-cached nor "
                    "holding slot state")
            self.step_tokens = int(step_tokens)
            self._window_rows = np.asarray(self.window_layers)[:, None]
        self.prefix_enabled = bool(prefix_cache)
        self.allocator = BlockAllocator(
            self.num_blocks,
            reclaim_cb=self._on_reclaim if self.prefix_enabled else None)
        self._hash_to_block = {}
        self._block_to_hash = {}
        self.prefix_evictions = 0
        self.cow_count = 0                 # engine-maintained
        self.on_prefix_evict = None        # optional stats hook
        self.mesh = mesh
        self.shard_axis = str(shard_axis)
        self.shards = 1
        if mesh is not None:
            self.shards = int(dict(mesh.shape).get(self.shard_axis, 1))
            if self.num_heads % self.shards:
                raise ValueError(
                    f"num_heads {self.num_heads} not divisible by "
                    f"{self.shard_axis}={self.shards} — head-sharded "
                    f"pools need an even head split")
        self.heads_per_shard = self.num_heads // self.shards
        self.k_pages = self.v_pages = self.latent_pages = None
        self.k_scales = self.v_scales = None
        # what a SLOT holds beside its pages (a state-space layer's
        # recurrent state, its convolution's window): float32 pools
        # ``[layers, state_slots, *shape]`` that a sequence's tokens
        # overwrite, carried through every token of a sequence and so
        # never in a narrower dtype. A run that starts at position 0
        # reads zeros, so no slot is ever cleared here.
        self.state_slots = int(state_slots)
        self.state_pools = {}
        if state:
            if self.state_slots < 1 or mesh is not None or prefix_cache:
                raise KVCacheError(
                    "slot state needs state_slots >= 1 and is neither "
                    "sharded nor shared by a prefix cache")
            self.state_pools = {
                name: jnp.zeros((shape[0], self.state_slots)
                                + tuple(shape[1:]), jnp.float32)
                for name, shape in state.items()}
        if self.latent_dim:
            # [L, N, bs, Wp]: no V pool (the values are the latent's own
            # first columns). ``latent_dim`` values a slot, zero-padded
            # to whole lanes: at a minor axis of 576 XLA:TPU lays a
            # pool this large out block-minor, and every kernel call,
            # which takes row-major operands only, copies it whole
            # (compiled off the chip: a 755 MB temporary a layer).
            # Row-major tiles pad 576 to 640 in any case, so the padded
            # pool is no larger than that.
            if self.quantized or mesh is not None:
                raise KVCacheError(
                    "a latent pool is neither quantized nor sharded")
            # (packed K/V rows are whole lanes at the widths served:
            # the attention op takes them unpadded)
            self.pool_width = self.latent_dim \
                if self.kind == "kv_packed" \
                else -(-self.latent_dim // self.LANES) * self.LANES
            self.latent_pages = jnp.zeros(
                (1 if self.windowed else self.num_layers, self.num_blocks,
                 self.block_size, self.pool_width),
                dtype=jnp.dtype(self.dtype))
            return
        shape = (self.num_layers, self.num_blocks, self.block_size,
                 self.num_heads, self.head_dim)
        self.k_pages = jnp.zeros(shape, dtype=jnp.dtype(self.dtype))
        self.v_pages = jnp.zeros(shape, dtype=jnp.dtype(self.dtype))
        if self.quantized:
            sshape = shape[:-1]            # [L, N, bs, H]
            self.k_scales = jnp.ones(sshape, dtype=jnp.float32)
            self.v_scales = jnp.ones(sshape, dtype=jnp.float32)
        if mesh is not None:
            # pools live head-sharded on the mesh from birth: every
            # chip holds [L, N, bs, H/shards, Dh] (scales
            # [L, N, bs, H/shards]) — the block axis is NOT sharded,
            # the ONE host-global BlockAllocator owns every block id
            # on every shard
            from ...parallel.mesh import place_global
            self.k_pages = place_global(self.k_pages, mesh,
                                        self.pool_spec())
            self.v_pages = place_global(self.v_pages, mesh,
                                        self.pool_spec())
            if self.quantized:
                self.k_scales = place_global(self.k_scales, mesh,
                                             self.scale_spec())
                self.v_scales = place_global(self.v_scales, mesh,
                                             self.scale_spec())

    # ----------------------------------------------------- sharding --
    def pool_spec(self):
        """PartitionSpec of a page pool ``[L, N, bs, H, Dh]``: heads
        sharded over ``shard_axis``, everything else replicated (the
        block axis stays global so block tables and the allocator are
        mesh-independent)."""
        from jax.sharding import PartitionSpec as P
        if self.mesh is None:
            return P()
        return P(None, None, None, self.shard_axis, None)

    def scale_spec(self):
        """PartitionSpec of an int8 scale pool ``[L, N, bs, H]``."""
        from jax.sharding import PartitionSpec as P
        if self.mesh is None:
            return P()
        return P(None, None, None, self.shard_axis)

    def shard_info(self):
        """Per-shard KV placement block for ``debug_status()`` /
        flight-recorder bundles: which heads live on which device.
        ``None`` for an unsharded pool."""
        if self.mesh is None:
            return None
        names = list(self.mesh.axis_names)
        k = names.index(self.shard_axis)
        devs = np.moveaxis(self.mesh.devices, k, 0).reshape(
            self.shards, -1)
        hps = self.heads_per_shard
        return {
            "axis": self.shard_axis,
            "shards": self.shards,
            "heads_per_shard": hps,
            "placement": [
                {"shard": i, "heads": [i * hps, (i + 1) * hps],
                 "devices": [str(d) for d in row]}
                for i, row in enumerate(devs)
            ],
        }

    # ------------------------------------------------------- tables --
    def blocks_for(self, num_tokens):
        """Blocks needed to hold ``num_tokens`` KV entries."""
        return -(-int(num_tokens) // self.block_size)

    def table_row(self, block_ids):
        """A sequence's padded block-table row: int32
        ``[max_blocks_per_seq]``, unused entries = the null block."""
        row = np.full(self.max_blocks_per_seq, NULL_BLOCK, np.int32)
        if len(block_ids) > self.max_blocks_per_seq:
            raise KVCacheError(
                f"{len(block_ids)} blocks exceed the "
                f"{self.max_blocks_per_seq}-block table "
                f"(max_context={self.max_context})")
        row[:len(block_ids)] = block_ids
        return row

    # ----------------------------------------- window and full layers --
    @staticmethod
    def window_blocks_for(window, block_size, step_tokens):
        """The most blocks of positions a window layer holds for one
        sequence: a step's tokens (at most ``step_tokens``) and the
        ``window - 1`` positions before the first of them, and, behind
        those, the tokens of the step in flight (which is dispatched
        again from its own position if it fails), over one more block
        where they do not start on a block's edge."""
        return -(-(window - 1 + 2 * step_tokens) // block_size) + 1

    @classmethod
    def seq_pages_for(cls, num_layers, window_layers, window, block_size,
                      step_tokens, num_tokens):
        """The most pages a sequence of ``num_tokens`` holds at once under
        a layout with ``window_layers`` of ``num_layers``: every block of
        each full layer, and of each window layer what its window holds."""
        blocks = -(-int(num_tokens) // block_size)
        held = min(blocks, cls.window_blocks_for(window, block_size,
                                                 step_tokens))
        n_win = len(window_layers)
        return (num_layers - n_win) * blocks + n_win * held

    def pages_for(self, num_tokens):
        """One-layer pages a sequence of ``num_tokens`` holds at the most
        in a cache with window layers, by kind (:meth:`seq_pages_for`)."""
        return self.seq_pages_for(self.num_layers, self.window_layers,
                                  self.window, self.block_size,
                                  self.step_tokens, num_tokens)

    def start(self, seq):
        """An admitted sequence holds no page yet: its per-layer
        record (:class:`LayerPages`)."""
        seq.layer_blocks = LayerPages(self.num_layers,
                                      self.max_blocks_per_seq)

    def _first_block(self, lowest):
        """The first block the window of position ``lowest`` reaches."""
        return max(0, lowest - self.window + 1) // self.block_size

    def release_behind(self, seq, lowest):
        """Give back the window layers' pages of ``seq`` that lie wholly
        behind the window of position ``lowest`` (the first position any
        query of it may still be computed at): every key they hold is
        before ``lowest - window + 1``. Returns how many went back."""
        lp = seq.layer_blocks
        below = min(self._first_block(lowest), lp.blocks)
        if below <= lp.window_from:
            return 0
        held = lp.table[self._window_rows, lp.window_from:below]
        gone = held[held != NULL_BLOCK].tolist()
        lp.table[self._window_rows, lp.window_from:below] = NULL_BLOCK
        lp.window_from = below
        if gone:
            self.allocator.free(gone)
            self.pages_held["window"] -= len(gone)
            self.window_released += len(gone)
        return len(gone)

    def pages_to_grow(self, seq, end, lowest):
        """Pages :meth:`grow` takes to cover positions below ``end``: a
        block a full layer lacks, and of those the window of ``lowest``
        reaches, a block a window layer lacks."""
        blocks = self.blocks_for(end)
        have = seq.layer_blocks.blocks
        n_win = len(self.window_layers)
        return (self.num_layers - n_win) * max(0, blocks - have) \
            + n_win * max(0, blocks - max(have, self._first_block(lowest)))

    def grow(self, seq, end, lowest):
        """Pages for positions below ``end`` onto ``seq``, after
        :meth:`release_behind`: each layer's next blocks, a window
        layer's only from the window of ``lowest`` on (the null block
        behind it, which :meth:`release_behind` then need not look at
        again). All-or-nothing (``NoFreeBlocksError``)."""
        need = self.pages_to_grow(seq, end, lowest)
        if not need:
            return
        lp = seq.layer_blocks
        blocks, have = self.blocks_for(end), lp.blocks
        first = min(max(have, self._first_block(lowest)), blocks)
        n_full = blocks - have
        pages = []
        try:
            pages = self.allocator.alloc(need)
            for layer in range(self.num_layers):
                start = first if layer in self.window_layers else have
                lp.table[layer, start:blocks] = pages[:blocks - start]
                pages = pages[blocks - start:]
                self.pages_held[self._kind(layer)] += blocks - start
        except BaseException:
            # the table is what free_seq gives back: what did not reach
            # it goes back here
            self.allocator.free(pages)
            raise
        if lp.window_from >= have:
            # every block before the new ones was given back already, and
            # the new ones behind the window hold none
            lp.window_from = first
        lp.blocks = blocks
        self.blocks_covered += n_full

    def _kind(self, layer):
        return "window" if layer in self.window_layers else "full"

    def seq_pages(self, seq):
        """The pages ``seq`` owns: its ``block_ids``, or under window
        layers the pages of its per-layer table."""
        lp = seq.layer_blocks
        if lp is None:
            return list(seq.block_ids)
        table = lp.table[:, :lp.blocks]
        return table[table != NULL_BLOCK].tolist()

    def free_seq(self, seq):
        """Every page ``seq`` owns back to the pool; it holds none after."""
        self.allocator.free(self.seq_pages(seq))
        lp = seq.layer_blocks
        if lp is not None:
            held = lp.table[:, :lp.blocks] != NULL_BLOCK
            for layer in range(self.num_layers):
                self.pages_held[self._kind(layer)] -= int(held[layer].sum())
            self.blocks_covered -= lp.blocks
        seq.block_ids = []
        seq.layer_blocks = None

    def fill_tables(self, tables, slot, seq):
        """Row ``slot`` of the step's per-layer tables ``[L, S, MB]``:
        each layer's pages by block (the null block where a window gave
        one back, and past the sequence's blocks)."""
        tables[:, slot] = seq.layer_blocks.table[:, :tables.shape[2]]

    def window_stats(self):
        """Pages held by kind, what an all-full layout would hold for the
        same sequences, and the window pages given back so far."""
        return {"window": self.window,
                "pages_held": dict(self.pages_held),
                "pages_full_layout": self.num_layers * self.blocks_covered,
                "window_pages_released": self.window_released}

    # ------------------------------------------------------ storage --
    def pools(self):
        """The device arrays a step program takes, donates and returns,
        in its order: the pools of pages, ``(k_pages, v_pages[,
        k_scales, v_scales])`` or ``(latent_pages,)``, then the slot
        state pools in the layout's order."""
        if self.latent_dim:
            pages = (self.latent_pages,)
        elif self.quantized:
            pages = (self.k_pages, self.v_pages, self.k_scales,
                     self.v_scales)
        else:
            pages = (self.k_pages, self.v_pages)
        return pages + tuple(self.state_pools.values())

    def layout(self):
        """The pool's layout and what a token costs in it, for
        ``stats()``: bytes over all layers, as allocated (a latent's
        lane padding and a quantized pool's scales included)."""
        itemsize = self.dtype.itemsize
        if self.windowed:
            return {"kind": self.kind,
                    "values_per_token": self.latent_dim,
                    "pool_shape": list(self.latent_pages.shape),
                    "window_layers": list(self.window_layers),
                    "window": self.window,
                    "bytes_per_page": self.block_size * self.pool_width
                    * itemsize}
        if self.latent_dim:
            return {"kind": self.kind,
                    "values_per_token": self.latent_dim,
                    "pool_shape": list(self.latent_pages.shape),
                    "bytes_per_token": self.num_layers * self.pool_width
                    * itemsize}
        per_layer = 2 * self.num_heads * (
            self.head_dim * itemsize + (4 if self.quantized else 0))
        return {"kind": "kv",
                "values_per_token": 2 * self.num_heads * self.head_dim,
                "pool_shape": list(self.k_pages.shape),
                "bytes_per_token": self.num_layers * per_layer}

    def swap(self, *pools):
        """Install the updated buffers a donated program returned, in
        the order of :meth:`pools` (the quantization scales with the
        pages when the pool is quantized)."""
        if self.state_pools:
            n = len(pools) - len(self.state_pools)
            self.state_pools = dict(zip(self.state_pools, pools[n:]))
            pools = pools[:n]
        if self.latent_dim:
            (self.latent_pages,) = pools
            return
        self.k_pages, self.v_pages = pools[:2]
        if self.quantized:
            if len(pools) != 4 or pools[2] is None or pools[3] is None:
                raise KVCacheError(
                    "quantized pool swap() requires k_scales/v_scales")
            self.k_scales, self.v_scales = pools[2:]

    # ------------------------------------------------- prefix index --
    def _on_reclaim(self, block):
        h = self._block_to_hash.pop(block, None)
        if h is not None:
            self._hash_to_block.pop(h, None)
        self.prefix_evictions += 1
        if self.on_prefix_evict is not None:
            self.on_prefix_evict()

    def prefix_get(self, h):
        """Block id registered for chained hash ``h`` (None = miss)."""
        return self._hash_to_block.get(h)

    def register(self, h, block):
        """Register a LIVE, FULL, immutable block under its chained
        hash. First registration wins (an identical block computed
        concurrently by another sequence stays private and is freed
        normally). Returns True when the entry was installed."""
        if not self.prefix_enabled:
            return False
        if h in self._hash_to_block or block in self._block_to_hash:
            return False
        self.allocator.mark_cacheable(block)
        self._hash_to_block[h] = block
        self._block_to_hash[block] = h
        return True

    @property
    def prefix_blocks(self):
        """Blocks currently registered in the prefix index."""
        return len(self._hash_to_block)

    # ---------------------------------------------------- invariants --
    def check(self, live_block_ids=None):
        """Pool-level invariant (the chaos-matrix gate): the allocator
        accounting is consistent, and — when ``live_block_ids`` (an
        iterable of per-sequence block-id lists) is given — the
        refcounts are EXACTLY the per-block owner counts over live
        sequences: no leaked blocks, no unaccounted sharing, no
        sequence owning one block twice. Cached (zero-refcount,
        prefix-registered) blocks are reclaimable capacity and never
        count as leaks. Raises :class:`BlockAccountingError`; returns
        True."""
        self.allocator.check()
        if live_block_ids is not None:
            owned = collections.Counter()
            for ids in live_block_ids:
                ids = list(ids)
                if len(set(ids)) != len(ids):
                    raise BlockAccountingError(
                        "a sequence owns the same KV block twice")
                owned.update(ids)
            if dict(owned) != self.allocator._ref:
                leaked = sorted(set(self.allocator._ref) - set(owned))
                phantom = sorted(set(owned) - set(self.allocator._ref))
                drift = sorted(
                    b for b in set(owned) & set(self.allocator._ref)
                    if owned[b] != self.allocator._ref[b])
                raise BlockAccountingError(
                    f"block accounting drift: leaked={leaked} "
                    f"unallocated-but-owned={phantom} "
                    f"refcount-drift={drift}")
        return True

    # -------------------------------------------------------- stats --
    def stats(self):
        a = self.allocator
        out = {
            "num_blocks": self.num_blocks,
            "block_size": self.block_size,
            "blocks_used": a.num_used,
            "blocks_shared": a.num_shared,
            "blocks_cached": a.num_cached,
            # strictly free (same definition as the
            # mxtpu_llm_kv_blocks_free gauge); cached LRU blocks are
            # counted separately and the sum is blocks_reclaimable
            "blocks_free": a.num_free - a.num_cached,
            "blocks_reclaimable": a.num_free,
            "occupancy": a.occupancy(),
            "max_blocks_per_seq": self.max_blocks_per_seq,
            "kv_dtype": self.dtype.name,
            "prefix_blocks": self.prefix_blocks,
            "prefix_evictions": self.prefix_evictions,
            "cow_copies": self.cow_count,
            "shards": self.shards,
            "heads_per_shard": self.heads_per_shard,
        }
        if self.windowed:
            # (blocks_used counts one-layer pages here)
            out.update(self.window_stats())
        return out

    def state_stats(self):
        """The slot state pools, for ``stats()``: how many slots, each
        pool's shape, and the bytes of all of them (what a deployment
        pays for its sessions whatever their contexts)."""
        return {"slots": self.state_slots if self.state_pools else 0,
                "pools": {k: list(v.shape)
                          for k, v in self.state_pools.items()},
                "bytes": int(sum(v.nbytes
                                 for v in self.state_pools.values()))}
