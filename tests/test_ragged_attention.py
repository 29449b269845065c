"""Flat ragged attention parity + KV block allocator accounting.

The kernel contract: ``ragged_flat_attention`` (the kernel every GPT-2
serving step runs) over block-table-indirected paged KV, for a packed
batch of tokens from different-length sequences, must match the dense
oracle ``ops/flash_attention.py:attention_reference`` run per token
over exactly its causal prefix — on every ragged length mix, including
block-boundary edges (len = block_size - 1, block_size, block_size +
1), fragmented (non-contiguous, shuffled) block tables, int8 pages, and
whatever garbage lies past a token's position, in blocks no table
names, or in the packed batch's tail; on tables of several loop steps
of the kernel's walk, whatever runs the batch's tokens form — on BOTH
paths (the gather-based
jnp reference and the Pallas kernel in interpret mode). The block
allocator must never leak or double-free across randomized admit/evict
schedules.
"""
import functools
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.flash_attention import attention_reference  # noqa: E402
from mxnet_tpu.ops.ragged_attention import (  # noqa: E402
    ragged_flat_attention)
from mxnet_tpu.serving.llm.kv_cache import (  # noqa: E402
    BlockAllocator, PagedKVCache, NoFreeBlocksError,
    BlockAccountingError, NULL_BLOCK)
from mxnet_tpu.serving.bucketing import (  # noqa: E402
    BucketSpec, pad_to_bucket)

BS = 8          # block size
H, D = 2, 16    # heads, head dim

both_paths = pytest.mark.parametrize("path", ["reference", "pallas"])


def _flat(path, q, kp, vp, bt, sids, poss, **kw):
    return np.asarray(ragged_flat_attention(
        q, kp, vp, bt, sids, poss, use_pallas=(path == "pallas"),
        interpret=True, **kw))


def _chunk_case(kv_lens, q_lens, num_blocks=64, seed=0, fragment=True,
                H=H, D=D):
    """A paged cache + a [S, Q, H, D] query chunk: queries are the
    LAST q_lens[i] positions of each sequence (the chunked-prefill /
    verify layout). Returns (q, k_pages, v_pages, block_tables,
    kv_lens, q_lens, per-seq dense k/v)."""
    rng = np.random.RandomState(seed)
    S = len(kv_lens)
    Q = max(q_lens)
    MB = max(-(-int(t) // BS) for t in kv_lens)
    k_pages = np.zeros((num_blocks, BS, H, D), np.float32)
    v_pages = np.zeros((num_blocks, BS, H, D), np.float32)
    tables = np.full((S, MB), NULL_BLOCK, np.int32)
    # fragmented, non-contiguous allocation: shuffle the pool so no
    # sequence's blocks are adjacent or ordered (dedicated RNG so the
    # q/k/v draws below are identical for fragment=True/False)
    pool = list(range(1, num_blocks))
    if fragment:
        np.random.RandomState(seed + 1000).shuffle(pool)
    it = iter(pool)
    dense = []
    q = rng.randn(S, Q, H, D).astype(np.float32)
    for i, t in enumerate(kv_lens):
        t = int(t)
        k_seq = rng.randn(t, H, D).astype(np.float32)
        v_seq = rng.randn(t, H, D).astype(np.float32)
        dense.append((k_seq, v_seq))
        for j in range(-(-t // BS)):
            b = next(it)
            tables[i, j] = b
            chunk = k_seq[j * BS:(j + 1) * BS]
            k_pages[b, :len(chunk)] = chunk
            chunk = v_seq[j * BS:(j + 1) * BS]
            v_pages[b, :len(chunk)] = chunk
    return (q, k_pages, v_pages, tables,
            np.asarray(kv_lens, np.int32),
            np.asarray(q_lens, np.int32), dense)


def _paged_case(lens, num_blocks=64, seed=0, fragment=True):
    """Decode rows, one query token a sequence: (q [S, H, D], k_pages,
    v_pages, block_tables, kv_lens, per-seq dense k/v)."""
    q, kp, vp, bt, kl, _, dense = _chunk_case(
        lens, [1] * len(lens), num_blocks, seed, fragment)
    return q[:, 0], kp, vp, bt, kl, dense


def _pack_flat(q, kv_lens, q_lens=None):
    """Pack the valid tokens of a chunk-shaped ``q [S, Q, H, D]`` flat:
    (flat_q [T, H, D], seq_ids [T], positions [T]), rows in order, a
    row's tokens its sequence's LAST ``q_lens[i]`` positions. Without
    ``q_lens`` ``q`` is ``[S, H, D]``, decode rows: one token a
    sequence, at ``kv_len - 1``."""
    if q_lens is None:
        q, q_lens = q[:, None], [1] * len(kv_lens)
    flat_q, sids, poss = [], [], []
    for i, qn in enumerate(q_lens):
        for t in range(qn):
            flat_q.append(q[i, t])
            sids.append(i)
            poss.append(kv_lens[i] - qn + t)
    return (np.stack(flat_q), np.asarray(sids, np.int32),
            np.asarray(poss, np.int32))


def _oracle(flat_q, sids, poss, dense):
    """Dense attention via the flash oracle, a packed token at a time
    over exactly its causal prefix of its own sequence."""
    outs = []
    for q, i, pos in zip(flat_q, sids, poss):
        k_seq, v_seq = (x[:pos + 1] for x in dense[i])
        o = attention_reference(
            jnp.asarray(q[None, :, None, :]),             # (1, H, 1, D)
            jnp.asarray(k_seq.transpose(1, 0, 2)[None]),  # (1, H, t, D)
            jnp.asarray(v_seq.transpose(1, 0, 2)[None]))
        outs.append(np.asarray(o)[0, :, 0, :])
    return np.stack(outs)


def _int8(x):
    """Symmetric int8 per slot and head, as the cache holds quantized
    pages: (int8 values, float32 scales ``x.shape[:-1]``)."""
    scale = np.abs(x).max(axis=-1) / 127.0
    safe = np.where(scale > 0, scale, 1.0)[..., None]
    return (np.clip(np.round(x / safe), -127, 127).astype(np.int8),
            scale.astype(np.float32))


def _int8_case(kp, vp, dense):
    """The case over int8 pages: (k_pages, v_pages, the scale pools as
    keyword arguments, the dense sequences dequantized as the kernel
    reads them)."""
    (kp, ks), (vp, vs) = _int8(kp), _int8(vp)
    dense = [tuple(xq * xs[..., None] for xq, xs in map(_int8, kv))
             for kv in dense]
    return kp, vp, dict(k_scales=ks, v_scales=vs), dense


def _poison_what_no_token_may_read(kp, vp, bt, kv_lens):
    """Copies of the pools with garbage in the null block, in every
    block no table names, and in the slots past each sequence's
    length in its last block."""
    kp, vp = kp.copy(), vp.copy()
    used = set(bt.ravel().tolist()) - {NULL_BLOCK}
    for b in range(kp.shape[0]):
        if b not in used:
            kp[b] = 1e6
            vp[b] = -1e6
    for i, t in enumerate(kv_lens):
        last = bt[i, (t - 1) // BS]
        kp[last, t % BS or BS:] = 1e6
        vp[last, t % BS or BS:] = -1e6
    return kp, vp


# block-boundary edges around BS plus interior/multi-block lengths
EDGE_MIXES = [
    [BS - 1, BS, BS + 1],
    [1, BS - 1, 2 * BS, 2 * BS + 1, 3 * BS - 1],
    [5, 11, 17, 24],
]


@pytest.mark.parametrize("lens,pages", [
    (EDGE_MIXES[0], "float32"), (EDGE_MIXES[1], "float32"),
    (EDGE_MIXES[2], "float32"), (EDGE_MIXES[1], "int8")],
    ids=["edges", "multi", "mix", "multi-int8"])
@both_paths
def test_parity_vs_dense_oracle(lens, pages, path):
    """Decode rows, one token a sequence at its last position. Int8
    pages are dequantized slot by slot and head by head where they are
    read: the oracle runs on the dequantized sequences."""
    q, kp, vp, bt, kl, dense = _paged_case(lens)
    kw = {}
    if pages == "int8":
        kp, vp, kw, dense = _int8_case(kp, vp, dense)
    flat_q, sids, poss = _pack_flat(q, kl)
    got = _flat(path, flat_q, kp, vp, bt, sids, poss, **kw)
    np.testing.assert_allclose(got, _oracle(flat_q, sids, poss, dense),
                               rtol=2e-5, atol=2e-6)


@both_paths
def test_fragmented_table_equals_contiguous(path):
    """A shuffled block table must read identically to a contiguous
    one — the kernel sees only the table, never block adjacency."""
    lens = [BS + 3, 2 * BS, 3]
    out = []
    for fragment in (True, False):
        q, kp, vp, bt, kl, _ = _paged_case(lens, fragment=fragment,
                                           seed=3)
        flat_q, sids, poss = _pack_flat(q, kl)
        out.append(_flat(path, flat_q, kp, vp, bt, sids, poss))
    np.testing.assert_array_equal(*out)


def test_pallas_matches_reference_path_bitwise_inputs():
    """Both paths over the SAME buffers: allclose at f32 ulp level."""
    lens = [2, BS, 19]
    q, kp, vp, bt, kl, _ = _paged_case(lens, seed=7)
    flat_q, sids, poss = _pack_flat(q, kl)
    ref = _flat("reference", flat_q, kp, vp, bt, sids, poss)
    pal = _flat("pallas", flat_q, kp, vp, bt, sids, poss)
    np.testing.assert_allclose(ref, pal, rtol=1e-5, atol=1e-6)


@both_paths
def test_garbage_in_unreferenced_blocks_is_invisible(path):
    """Stale KV beyond kv_len, in never-referenced blocks and in the
    null block must not leak into any output — the masking contract
    preemption relies on."""
    lens = [5, 9]
    q, kp, vp, bt, kl, _ = _paged_case(lens, seed=11)
    flat_q, sids, poss = _pack_flat(q, kl)
    base = _flat(path, flat_q, kp, vp, bt, sids, poss)
    kp2, vp2 = _poison_what_no_token_may_read(kp, vp, bt, lens)
    got = _flat(path, flat_q, kp2, vp2, bt, sids, poss)
    np.testing.assert_array_equal(base, got)


# ------------------------------------------------ multi-token runs --


@pytest.mark.parametrize("kv_lens,q_lens", [
    # verify-, decode- and prefill-like rows, block-boundary lengths
    ([13, 5, 2 * BS, BS + 1], [5, 2, 1, BS + 1]),
    # the engine's mixed step: a chunk, a verify window, a decode
    ([13, 5, 2 * BS], [5, 2, 1])], ids=["chunks", "mixed"])
@both_paths
def test_packed_runs_vs_dense_causal_oracle(kv_lens, q_lens, path):
    """Multi-token runs: token t of row i (absolute position kv_len -
    q_len + t) must equal single-query dense attention over exactly
    its causal prefix, though the keys of the run's later tokens are
    already in its pages — across block-boundary kv lengths, runs from
    1 token (decode) to a whole prompt, fragmented tables."""
    q, kp, vp, bt, kl, ql, dense = _chunk_case(kv_lens, q_lens, seed=21)
    flat_q, sids, poss = _pack_flat(q, kl, ql)
    got = _flat(path, flat_q, kp, vp, bt, sids, poss)
    np.testing.assert_allclose(got, _oracle(flat_q, sids, poss, dense),
                               rtol=2e-5, atol=2e-6)


@both_paths
def test_one_packed_run_matches_a_token_a_call(path):
    """A sequence's chunk fed as one packed run is the same tokens fed
    one a call: packing changes the layout, never the math."""
    q, kp, vp, bt, kl, ql, _ = _chunk_case([5, 2 * BS + 3], [2, 11],
                                           seed=13)
    flat_q, sids, poss = _pack_flat(q, kl, ql)
    run = _flat(path, flat_q, kp, vp, bt, sids, poss)
    one = np.concatenate([
        _flat(path, flat_q[t:t + 1], kp, vp, bt, sids[t:t + 1],
              poss[t:t + 1]) for t in range(len(sids))])
    np.testing.assert_allclose(run, one, rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("tail", ["padded", "stale"])
@both_paths
def test_tail_behind_the_valid_tokens_is_invisible(tail, path):
    """The packed batch is a bucket wider than the step's tokens. What
    lies behind them must not perturb any VALID row, bit for bit:
    ``padded``: garbage queries on sequence 0 at position 0, with KV
    garbage wherever the mask must hide it; ``stale``: an earlier
    step's ``seq_ids`` and ``positions`` as ``_build_batch`` leaves
    them (only ``valid`` is reset a step): a chunk of a sequence the
    step also holds, further on than it is now, and tokens of a slot
    whose table row is all null."""
    kv_lens, q_lens = [9, 17], [3, 5]
    q, kp, vp, bt, kl, ql, _ = _chunk_case(kv_lens, q_lens, seed=5)
    bt = np.concatenate([bt, np.full((1,) + bt.shape[1:], NULL_BLOCK,
                                     np.int32)])     # an idle slot
    flat_q, sids, poss = _pack_flat(q, kl, ql)
    n, T = len(sids), 16

    def bucket(x, behind):
        return np.concatenate([x, np.asarray(behind, x.dtype)])

    zeros = np.zeros(T - n, np.int32)
    base = _flat(path, bucket(flat_q, np.zeros((T - n, H, D))), kp, vp,
                 bt, bucket(sids, zeros), bucket(poss, zeros))
    if tail == "padded":
        kp, vp = _poison_what_no_token_may_read(kp, vp, bt[:2], kv_lens)
        behind = zeros, zeros
    else:
        behind = ([1] * 5 + [2] * 3,
                  [20, 21, 22, 23, 24, 3, 4, 5])
    got = _flat(path, bucket(flat_q, np.full((T - n, H, D), 1e6)), kp,
                vp, bt, bucket(sids, behind[0]), bucket(poss, behind[1]))
    np.testing.assert_array_equal(base[:n], got[:n])


# ------------------------------- the walk, run by run (ISSUE 31) --
# The kernel takes a block of the packed batch RUN by run (neighbouring
# tokens of one sequence) and walks a run's pages 128 keys a loop step.
# Tables of 55 pages of 8 are four steps, the last one past the table's
# width; only such tables tell a run's walk from another's. Three
# geometries, each through both inner forms' conditions: ``heads``
# (8 heads of 128, float32: a run of several tokens a head at a time,
# a run of one the whole buffer at once), ``tokens`` (2 heads of 16:
# every token in turn) and ``int8`` (8 heads of 128, quantized pages
# with their scale pools copied page by page).

WALK_LENS = [440, 37, 290, 130]                 # 55, 5, 37 and 17 pages
WALK_GEOMETRY = {"heads": (8, 128, False), "tokens": (2, 16, False),
                 "int8": (8, 128, True)}


def _run(seq, first, n=1):
    """``n`` packed tokens of ``seq`` at positions ``first...``."""
    return [(seq, first + t) for t in range(n)]


WALK_BATCHES = {
    # runs of 5, 1, 16 and 1 tokens in one block, walks of 4, 1, 3 and
    # 2 steps
    "mixed-runs": (_run(0, 435, 5) + _run(1, 36) + _run(2, 270, 16)
                   + _run(3, 129)),
    # a sequence twice in one block, apart: its valid tokens, then
    # another's, then tokens an earlier step left (shorter and longer
    # walks than the valid run's)
    "twice-apart": (_run(2, 130, 3) + _run(1, 20) + _run(2, 280, 2)
                    + _run(0, 300) + _run(2, 5, 2)),
    # 70 tokens are two blocks of 64: the second run crosses from one
    # into the other
    "crosses-a-block": _run(0, 380, 58) + _run(2, 200, 12),
    # 67 tokens: a last block of three, the runs whole on either side
    "partial-last-block": (_run(3, 60, 48) + _run(1, 0, 16)
                           + _run(0, 437, 3)),
}


@functools.lru_cache(maxsize=None)
def _walk_case(geometry):
    """The pools of one geometry, shared by its cases: (k_pages,
    v_pages, kwargs with the scale pools, tables [4, 55], dense k/v
    as the kernel reads them)."""
    heads, dim, quantized = WALK_GEOMETRY[geometry]
    _, kp, vp, bt, _, _, dense = _chunk_case(
        WALK_LENS, [1] * 4, num_blocks=160, seed=41, H=heads, D=dim)
    kw = {}
    if quantized:
        kp, vp, kw, dense = _int8_case(kp, vp, dense)
    return kp, vp, kw, bt, dense


def _dense_oracle(flat_q, sids, poss, dense):
    """``_oracle`` in numpy and float64 (seventy prefix lengths are
    seventy programs to ``attention_reference``): a packed token at a
    time, one softmax over exactly its causal prefix."""
    outs = []
    for q, i, pos in zip(flat_q.astype(np.float64), sids, poss):
        k_seq, v_seq = (x[:pos + 1].astype(np.float64) for x in dense[i])
        logits = np.einsum("hd,thd->ht", q, k_seq) / np.sqrt(q.shape[-1])
        p = np.exp(logits - logits.max(axis=1, keepdims=True))
        outs.append(np.einsum("ht,thd->hd",
                              p / p.sum(axis=1, keepdims=True), v_seq))
    return np.stack(outs)


def _walk_queries(geometry, batch):
    heads, dim, _ = WALK_GEOMETRY[geometry]
    sids, poss = (np.asarray(x, np.int32) for x in zip(*batch))
    q = np.random.RandomState(len(batch)).randn(
        len(batch), heads, dim).astype(np.float32)
    return q, sids, poss


@pytest.mark.parametrize("batch", list(WALK_BATCHES))
@pytest.mark.parametrize("geometry", list(WALK_GEOMETRY))
@both_paths
def test_walk_run_by_run_vs_dense_oracle(geometry, batch, path):
    """Every token of the packed batch, whatever run it stands in and
    however far that run walks, equals single-query dense attention
    over its own causal prefix."""
    kp, vp, kw, bt, dense = _walk_case(geometry)
    q, sids, poss = _walk_queries(geometry, WALK_BATCHES[batch])
    got = _flat(path, q, kp, vp, bt, sids, poss, **kw)
    np.testing.assert_allclose(got, _dense_oracle(q, sids, poss, dense),
                               rtol=2e-5, atol=5e-6)


@pytest.mark.parametrize("geometry", list(WALK_GEOMETRY))
@both_paths
def test_ids_and_positions_past_the_table_are_clipped(geometry, path):
    """A position past the table's width attends over the whole table
    and a sequence id past its rows reads the last row, as the
    reference's gather and mask do: the same rows as the clipped
    batch, which the oracle can follow."""
    kp, vp, kw, bt, dense = _walk_case(geometry)
    # sequence 0 holds 440 keys in a table of 55 x 8: the table's last
    # position, 439, is its last key
    wild = (_run(0, 439) + _run(0, 440) + _run(0, 10 ** 6)
            + _run(3, 100, 2) + _run(4, 129) + _run(99, 129))
    clipped = (_run(0, 439) * 3 + _run(3, 100, 2) + _run(3, 129) * 2)
    q, sids, poss = _walk_queries(geometry, wild)
    _, sids_in, poss_in = _walk_queries(geometry, clipped)
    got = _flat(path, q, kp, vp, bt, sids, poss, **kw)
    np.testing.assert_allclose(
        got, _dense_oracle(q, sids_in, poss_in, dense), rtol=2e-5,
        atol=5e-6)


def test_build_batch_names_the_tail_and_any_tail_serves_the_same(
        monkeypatch):
    """``LLMEngine._build_batch`` leaves behind a step's tokens the
    last valid token's slot at position 0 (a tail that lengthens the
    kernel's last run and walks nothing), and what is served does not
    depend on it: an engine whose tail keeps what earlier steps left,
    or holds garbage, serves the same tokens."""
    from mxnet_tpu.serving.llm import (DecoderConfig, LLMEngine, Sequence,
                                       TinyDecoder)
    model = TinyDecoder(DecoderConfig(
        vocab_size=17, d_model=16, num_layers=2, num_heads=2, d_ff=32,
        max_context=64))
    params = model.init_params(seed=0)
    rng = np.random.RandomState(4)
    cases = [(rng.randint(0, 17, size=n).tolist(), m)
             for n, m in ((21, 6), (3, 9), (9, 4), (30, 7), (1, 5))]

    def serve(name_tail):
        monkeypatch.setattr(LLMEngine, "_name_tail",
                            staticmethod(name_tail))
        eng = LLMEngine(model, params, max_seqs=4, block_size=BS,
                        max_context=64)
        seqs = [Sequence(prompt, n) for prompt, n in cases]
        tails = []
        for s in seqs:
            eng.add(s)
        while eng.has_work():
            eng.step()
            for _, positions, seq_ids, valid, *_ in eng._bufs.values():
                n = int(valid.sum())
                tails.append((n, seq_ids.copy(), positions.copy()))
        return [s.output_tokens() for s in seqs], tails

    def dirty(seq_ids, positions, off):
        seq_ids[off:] = rng.randint(0, 4, len(seq_ids) - off)
        positions[off:] = rng.randint(0, 64, len(seq_ids) - off)

    named = LLMEngine._name_tail
    want, tails = serve(named)
    assert any(0 < n < len(sids) for n, sids, _ in tails)
    for n, sids, poss in tails:
        if n:
            assert (sids[n:] == sids[n - 1]).all() and not poss[n:].any()
    assert serve(lambda *a: None)[0] == want        # what steps left
    assert serve(dirty)[0] == want


# -------------------------------------------- stacked pools (ISSUE 27) --

STACK_L = 4


def _flat_case(seed=31):
    """A packed flat batch over one layer's pages: (q, kp, vp, bt,
    seq_ids, positions)."""
    kv_lens = [13, 5, 2 * BS]
    q_lens = [5, 2, 1]
    q, kp, vp, bt, _, _, _ = _chunk_case(kv_lens, q_lens, seed=seed)
    flat_q, sids, poss = _pack_flat(q, kv_lens, q_lens)
    return flat_q, kp, vp, bt, sids, poss


def _stack_with_garbage(pages, layer, garbage=np.nan):
    """``[STACK_L, *pages.shape]`` holding ``pages`` at ``layer`` and
    ``garbage`` in every other layer: anything read from a wrong layer
    poisons the output."""
    out = np.full((STACK_L,) + pages.shape, garbage, pages.dtype)
    out[layer] = pages
    return out


@pytest.mark.parametrize("layer", [0, 2, STACK_L - 1],
                         ids=["first", "middle", "last"])
@pytest.mark.parametrize("path", ["reference", "pallas"])
def test_flat_stacked_pool_equals_layer_slice_bitwise(path, layer):
    """Handing the kernel the whole ``[L, N, bs, H, D]`` pool with a
    layer index is the per-layer-slice call, bit for bit: the layer
    only moves where each page is fetched from."""
    from mxnet_tpu.ops.ragged_attention import ragged_flat_attention
    q, kp, vp, bt, sid, pos = _flat_case()
    kw = dict(use_pallas=(path == "pallas"), interpret=True)
    want = np.asarray(ragged_flat_attention(q, kp, vp, bt, sid, pos,
                                            **kw))
    got = np.asarray(ragged_flat_attention(
        q, _stack_with_garbage(kp, layer),
        _stack_with_garbage(vp, layer), bt, sid, pos, layer=layer,
        **kw))
    assert np.isfinite(want).all()
    np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("path", ["reference", "pallas"])
def test_flat_stacked_pool_takes_a_traced_layer(path):
    """The layer is data (a scalar-prefetch operand of the kernel,
    an index of the reference's gather): one jitted program serves
    every layer."""
    import jax
    from mxnet_tpu.ops.ragged_attention import ragged_flat_attention
    q, kp, vp, bt, sid, pos = _flat_case(seed=32)
    kw = dict(use_pallas=(path == "pallas"), interpret=True)
    want = np.asarray(ragged_flat_attention(q, kp, vp, bt, sid, pos,
                                            **kw))
    fn = jax.jit(lambda ks, vs, lyr: ragged_flat_attention(
        q, ks, vs, bt, sid, pos, layer=lyr, **kw))
    for layer in (1, STACK_L - 1):
        got = fn(_stack_with_garbage(kp, layer),
                 _stack_with_garbage(vp, layer), jnp.int32(layer))
        np.testing.assert_array_equal(np.asarray(got), want)
    assert fn._cache_size() == 1


@pytest.mark.parametrize("layer", [None, 0],
                         ids=["stacked-without-layer",
                              "layer-without-stack"])
def test_flat_pool_rank_must_match_layer(layer):
    from mxnet_tpu.ops.ragged_attention import ragged_flat_attention
    q, kp, vp, bt, sid, pos = _flat_case()
    if layer is None:
        kp, vp = kp[None], vp[None]
    with pytest.raises(ValueError, match="layer"):
        ragged_flat_attention(q, kp, vp, bt, sid, pos, layer=layer)


def _all_eqns(jaxpr):
    """Every equation of a jaxpr and of the jaxprs nested in it (jit
    bodies, the Pallas kernel's body)."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in eqn.params.values():
            for j in (sub if isinstance(sub, (list, tuple)) else (sub,)):
                j = getattr(j, "jaxpr", j)
                if hasattr(j, "eqns"):
                    yield from _all_eqns(j)


@pytest.mark.parametrize("kv_dtype", ["float32", "int8"])
def test_decode_flat_hands_the_kernel_whole_pools(kv_dtype, monkeypatch):
    """The structure that keeps a layer's pages from being copied out
    of the pool (ISSUE 27): with the kernel on, every ``pallas_call``
    of ``decode_flat`` takes K and V at their full
    ``[L, N, bs, H, D]`` shape, and NO equation anywhere produces an
    array shaped like one layer's pages — a slice handed to a custom
    call is materialized. (Quantized pages: the scale pools are
    sliced per layer on purpose, see ``ragged_flat_attention``.) Four
    heads of 128: a page Mosaic can copy by itself, which is where the
    kernel is taken on a TPU."""
    import jax
    from mxnet_tpu.ops import ragged_attention as ra
    from mxnet_tpu.serving.llm.model import DecoderConfig, TinyDecoder
    monkeypatch.setattr(ra, "_on_tpu", lambda: True)
    L, N, T, S, MB = 3, 9, 6, 4, 4
    model = TinyDecoder(DecoderConfig(
        vocab_size=17, d_model=512, num_layers=L, num_heads=4, d_ff=32,
        max_context=MB * BS))
    params = model.init_params(seed=0)
    pool = jnp.zeros((L, N, BS, 4, 128), kv_dtype)
    scales = {}
    if kv_dtype != "float32":
        sc = jnp.ones((L, N, BS, 4), jnp.float32)
        scales = dict(k_scales=sc, v_scales=sc)
    ints = jnp.zeros((T,), jnp.int32)
    closed = jax.make_jaxpr(
        lambda kp, vp: model.decode_flat(
            params, ints, ints, ints, ints, kp, vp,
            jnp.zeros((S, MB), jnp.int32), **scales))(pool, pool)
    eqns = list(_all_eqns(closed.jaxpr))
    calls = [e for e in eqns if e.primitive.name == "pallas_call"]
    assert len(calls) == L
    for e in calls:
        whole = [v for v in e.invars
                 if getattr(v.aval, "shape", None) == pool.shape]
        assert len(whole) == 2, [v.aval for v in e.invars]
    sliced = [e for e in eqns for v in e.outvars
              if getattr(v.aval, "shape", None) == pool.shape[1:]]
    assert not sliced, sliced


# ------------------------------------------------------- allocator --


def test_allocator_alloc_free_roundtrip():
    a = BlockAllocator(9)           # 8 usable
    assert a.num_usable == 8 and a.num_free == 8
    blocks = a.alloc(3)
    assert len(blocks) == 3 and NULL_BLOCK not in blocks
    assert a.num_used == 3 and a.occupancy() == pytest.approx(3 / 8)
    a.free(blocks)
    assert a.num_used == 0 and a.num_free == 8
    a.check()


def test_allocator_oom_is_all_or_nothing():
    a = BlockAllocator(5)           # 4 usable
    a.alloc(3)
    with pytest.raises(NoFreeBlocksError):
        a.alloc(2)
    assert a.num_free == 1          # failed alloc touched nothing
    a.check()


def test_allocator_double_free_and_null_are_errors():
    a = BlockAllocator(5)
    b = a.alloc(2)
    a.free(b)
    with pytest.raises(BlockAccountingError):
        a.free(b)                   # double free
    with pytest.raises(BlockAccountingError):
        a.free([NULL_BLOCK])        # the reserved block
    with pytest.raises(BlockAccountingError):
        a.free([99])                # out of range
    c = a.alloc(1)
    with pytest.raises(BlockAccountingError):
        a.free(c + c)               # duplicates within one call
    a.check()


def test_allocator_fuzz_1k_schedules_never_leaks():
    """Property test: across 1k random admit/evict schedules the
    allocator's {free} ∪ {used} partition stays exact — no leaked, no
    double-counted, no vanished blocks."""
    rng = np.random.RandomState(0)
    a = BlockAllocator(33)          # 32 usable
    live = []                       # list of allocated block-id lists
    for step in range(1000):
        if live and (rng.rand() < 0.45 or a.num_free == 0):
            seq_blocks = live.pop(rng.randint(len(live)))
            a.free(seq_blocks)
        else:
            want = int(rng.randint(1, 6))
            if a.can_alloc(want):
                live.append(a.alloc(want))
            else:
                with pytest.raises(NoFreeBlocksError):
                    a.alloc(want)
        a.check()
        held = sum(len(b) for b in live)
        assert a.num_used == held
        assert a.num_free == a.num_usable - held
    for seq_blocks in live:
        a.free(seq_blocks)
    a.check()
    assert a.num_free == a.num_usable


def test_allocator_fuzz_1k_refcount_cow_lru_churn():
    """The ISSUE-13 extension of the schedule fuzz: 1k random steps of
    alloc / share (ref) / free / register-cacheable / LRU reclaim,
    with a shadow refcount model checked against ``check()`` and the
    partition counters every step. Sharing and caching must never
    break the exact {free, refcounted, cached} partition."""
    rng = np.random.RandomState(1)
    reclaimed = []
    a = BlockAllocator(33, reclaim_cb=reclaimed.append)
    live = []                       # per-"sequence" block-id lists
    shadow = {}                     # block -> expected refcount
    for step in range(1000):
        r = rng.rand()
        if live and r < 0.30:
            # retire one sequence: decref every block it owns
            seq_blocks = live.pop(rng.randint(len(live)))
            a.free(seq_blocks)
            for b in seq_blocks:
                shadow[b] -= 1
                if shadow[b] == 0:
                    del shadow[b]
        elif live and r < 0.45:
            # a "prefix hit": a new sequence refs an existing block
            donor = live[rng.randint(len(live))]
            b = donor[rng.randint(len(donor))]
            a.ref(b)
            shadow[b] += 1
            live.append([b])
        elif live and r < 0.55:
            # register a random live block in the "prefix index"
            donor = live[rng.randint(len(live))]
            a.mark_cacheable(donor[rng.randint(len(donor))])
        else:
            want = int(rng.randint(1, 6))
            if a.can_alloc(want):
                got = a.alloc(want)     # may reclaim LRU cached blocks
                live.append(got)
                for b in got:
                    assert b not in shadow      # reclaim gave it back
                    shadow[b] = 1
            else:
                with pytest.raises(NoFreeBlocksError):
                    a.alloc(want)
        a.check()
        assert a._ref == shadow
        assert a.num_used == len(shadow)
        assert (a.num_used + a.num_cached
                + (a.num_free - a.num_cached)) == a.num_usable
    # a reclaimed block must have been handed out again, never leaked
    for seq_blocks in live:
        a.free(seq_blocks)
    a.check()
    assert a.num_used == 0
    assert a.num_free == a.num_usable


def test_allocator_ref_and_cache_lifecycle():
    """Directed coverage of the sharing lifecycle: ref of free blocks
    is an error, cached blocks revive through ref(), reclaim fires the
    callback and drops LRU-oldest first."""
    dropped = []
    a = BlockAllocator(5, reclaim_cb=dropped.append)   # 4 usable
    b1, b2 = a.alloc(2)
    with pytest.raises(BlockAccountingError):
        a.ref(99)
    a.ref(b1)                       # shared
    assert a.refcount(b1) == 2 and a.num_shared == 1
    a.free([b1])
    assert a.refcount(b1) == 1 and a.num_shared == 0
    with pytest.raises(BlockAccountingError):
        a.mark_cacheable(77)        # not allocated
    a.mark_cacheable(b1)
    a.mark_cacheable(b2)
    a.free([b1])                    # -> cached LRU (oldest)
    a.free([b2])                    # -> cached LRU (newest)
    assert a.num_cached == 2 and a.num_used == 0
    assert a.num_free == a.num_usable    # cached = reclaimable
    a.ref(b2)                       # hit revives from the LRU
    assert a.refcount(b2) == 1 and a.num_cached == 1
    got = a.alloc(3)                # must reclaim b1 (LRU) + 2 free
    assert dropped == [b1]
    assert b1 in got
    a.check()
    with pytest.raises(BlockAccountingError):
        a.free([b1, b1])            # duplicate in one call


def test_paged_cache_check_refcount_aware():
    """check(live_block_ids) validates the refcounted ownership
    multiset exactly: legal sharing passes, drifted refcounts and
    leaks raise."""
    c = PagedKVCache(num_layers=1, num_heads=2, head_dim=4,
                     block_size=8, num_blocks=9, max_context=32,
                     prefix_cache=True)
    b1, b2 = c.allocator.alloc(2)
    c.allocator.ref(b1)
    assert c.check(live_block_ids=[[b1, b2], [b1]])
    with pytest.raises(BlockAccountingError):
        c.check(live_block_ids=[[b1, b2]])      # refcount drift
    with pytest.raises(BlockAccountingError):
        c.check(live_block_ids=[[b1, b1], [b1], [b2]])  # dup in one seq
    # registered + fully released blocks are CACHED capacity, not leaks
    c.register("h1", b1)
    c.allocator.free([b1])          # one reference per call: a
    c.allocator.free([b1])          # sequence never owns a block twice
    with pytest.raises(BlockAccountingError):
        c.allocator.free([b1])      # double free past zero
    c.allocator.free([b2])
    assert c.check(live_block_ids=[])
    assert c.stats()["blocks_cached"] == 1
    assert c.prefix_get("h1") == b1


def test_paged_cache_table_row_and_sizing():
    c = PagedKVCache(num_layers=2, num_heads=2, head_dim=4,
                     block_size=8, num_blocks=9, max_context=32)
    assert c.max_blocks_per_seq == 4
    assert c.blocks_for(1) == 1 and c.blocks_for(8) == 1
    assert c.blocks_for(9) == 2
    row = c.table_row([5, 3])
    assert row.tolist() == [5, 3, NULL_BLOCK, NULL_BLOCK]
    assert c.k_pages.shape == (2, 9, 8, 2, 4)
    st = c.stats()
    assert st["blocks_free"] == 8 and st["occupancy"] == 0.0


# ------------------------------------------- shared bucketing spec --


def test_bucket_spec_shared_pow2_discipline():
    """The refactored BucketSpec is the one bucket implementation both
    serving paths use: pow2 sizes, smallest-fit pick, zero-pad."""
    spec = BucketSpec.pow2(8)
    assert spec.buckets == [1, 2, 4, 8]
    assert spec.pick(3) == 4
    rows = np.ones((3, 5), np.float32)
    padded, bucket = spec.pad(rows)
    assert bucket == 4 and padded.shape == (4, 5)
    np.testing.assert_array_equal(padded[3:], 0)
    assert spec.waste(3) == pytest.approx(0.25)
    assert [b for b, _ in spec.warmup_shapes((5,))] == [1, 2, 4, 8]


def test_bucket_spec_page_aligned_length_axis():
    """The LLM prefill variant: pow2 buckets rounded up to block
    multiples, padding along the LENGTH axis."""
    spec = BucketSpec.pow2(64, multiple_of=16)
    assert spec.buckets == [16, 32, 64]
    toks = np.arange(21, dtype=np.int32)
    padded, bucket = spec.pad(toks)
    assert bucket == 32 and padded.shape == (32,)
    np.testing.assert_array_equal(padded[:21], toks)
    np.testing.assert_array_equal(padded[21:], 0)
    # axis-general padding (prefill pads axis 0 of a 1-D prompt; a
    # batched caller pads axis 1)
    x = np.ones((2, 3), np.float32)
    assert pad_to_bucket(x, 4, axis=1).shape == (2, 4)
    with pytest.raises(ValueError):
        pad_to_bucket(x, 2, axis=1)


# ------------- grouped queries over packed K/V rows (ISSUE 32) --
# ``gqa_flat_attention``: 32 query heads over 8 kv heads of 64, ONE pool
# of rows [V | K] of 1024 values, through the latent kernel's walk
# (blocks of 8 tokens at 32 heads, 256 keys a loop step). Tables of 48
# pages of 16 are 768 keys, three steps; only such tables tell a run's
# walk from another's.

from mxnet_tpu.ops.ragged_attention import (  # noqa: E402
    gqa_flat_attention, gqa_flat_attention_reference)

GQA_H, GQA_G, GQA_D, GQA_BS = 32, 8, 64, 16
GQA_LENS = [760, 37, 530, 290]                  # 48, 3, 34 and 19 pages
GQA_SCALE = 1.0 / 64

GQA_BATCHES = {
    # runs of 5, 1, 12 and 1 tokens, walks of 3, 1, 3 and 2 steps; the
    # run of 12 crosses from one block of 8 tokens into the next two
    "mixed-runs": (_run(0, 750, 5) + _run(1, 36) + _run(2, 515, 12)
                   + _run(3, 289)),
    # a sequence twice in one block, apart (PR 29's fault): its valid
    # tokens, then another's, then tokens an earlier step left
    "twice-apart": (_run(2, 300, 3) + _run(1, 20) + _run(2, 520, 2)
                    + _run(0, 600) + _run(2, 5, 1)),
    # a chunk of 16 from position 0 beside decode rows, and a tail that
    # names the last token's slot at position 0, as the engine writes it
    "chunk-and-tail": (_run(3, 0, 16) + _run(0, 759) + _run(1, 3)
                       + [(1, 0)] * 5),
}


@functools.lru_cache(maxsize=None)
def _gqa_case(dtype):
    """``(pages [2, N, bs, 1024] of dtype, tables [4, 48], dense k/v per
    sequence as float64, as the pool holds them)``: layer 1 of a stacked
    pool of two, layer 0 garbage."""
    rng = np.random.RandomState(7)
    MB = max(-(-t // GQA_BS) for t in GQA_LENS)
    N = 1 + sum(-(-t // GQA_BS) for t in GQA_LENS) + 9
    pages = rng.randn(2, N, GQA_BS, 2 * GQA_G * GQA_D).astype(np.float32)
    free = list(1 + rng.permutation(N - 1))     # fragmented tables
    bt = np.zeros((len(GQA_LENS), MB), np.int32)
    dense = []
    for i, t in enumerate(GQA_LENS):
        nb = -(-t // GQA_BS)
        bt[i, :nb] = [free.pop() for _ in range(nb)]
        rows = pages[1, bt[i, :nb]].reshape(nb * GQA_BS, 2, GQA_G, GQA_D)
        dense.append(rows)
    pages = jnp.asarray(pages).astype(dtype)
    dense = [np.asarray(jnp.asarray(r).astype(dtype).astype(jnp.float32),
                        np.float64) for r in dense]
    return pages, bt, dense


def _gqa_oracle(q, sids, poss, dense):
    """Single-query dense attention over each token's own causal prefix,
    query head ``j`` against kv head ``j // 4``, in float64."""
    outs = []
    m = GQA_H // GQA_G
    for qt, i, pos in zip(q.astype(np.float64), sids, poss):
        v, k = dense[i][:pos + 1, 0], dense[i][:pos + 1, 1]
        qg = qt.reshape(GQA_G, m, GQA_D)
        logits = np.einsum("gmd,tgd->gmt", qg, k) * GQA_SCALE
        p = np.exp(logits - logits.max(axis=-1, keepdims=True))
        outs.append(np.einsum("gmt,tgd->gmd",
                              p / p.sum(axis=-1, keepdims=True),
                              v).reshape(GQA_H, GQA_D))
    return np.stack(outs)


def _gqa(path, q, pages, bt, sids, poss, layer=1):
    return np.asarray(gqa_flat_attention(
        q, pages, bt, sids, poss, GQA_SCALE, GQA_G, layer=layer,
        use_pallas=(path == "pallas"), interpret=True))


@pytest.mark.parametrize("batch", list(GQA_BATCHES))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@both_paths
def test_grouped_walk_vs_dense_oracle(dtype, batch, path):
    """Every VALID token of the packed batch equals single-query dense
    grouped attention over its own causal prefix, through the stacked
    pool's layer 1. The tolerance is float32 round-off over 760 keys
    (the pages' rounding to bfloat16 is the oracle's too); queries four
    times unit scale, as the model's ``wq`` makes them."""
    pages, bt, dense = _gqa_case(dtype)
    sids, poss = (np.asarray(x, np.int32)
                  for x in zip(*GQA_BATCHES[batch]))
    q = 4 * np.random.RandomState(len(sids)).randn(
        len(sids), GQA_H, GQA_D).astype(np.float32)
    got = _gqa(path, q, pages, bt, sids, poss)
    valid = len(sids) - (5 if batch == "chunk-and-tail" else 0)
    np.testing.assert_allclose(
        got[:valid], _gqa_oracle(q, sids, poss, dense)[:valid],
        rtol=2e-5, atol=5e-6)


@both_paths
def test_grouped_heads_read_their_own_group(path):
    """With every kv head but one zeroed in V, only that group's four
    query heads bring anything back."""
    pages, bt, _ = _gqa_case("float32")
    v = np.asarray(pages).reshape(pages.shape[:3] + (2, GQA_G, GQA_D)).copy()
    v[..., 0, :5, :] = 0
    v[..., 0, 6:, :] = 0
    only5 = jnp.asarray(v.reshape(pages.shape))
    sids, poss = np.asarray([0, 2], np.int32), np.asarray([700, 40],
                                                          np.int32)
    q = np.random.RandomState(3).randn(2, GQA_H, GQA_D).astype(np.float32)
    got = _gqa(path, q, only5, bt, sids, poss)
    assert np.abs(got[:, 20:24]).min() > 0
    assert np.abs(got[:, :20]).max() == 0 and np.abs(got[:, 24:]).max() == 0


def test_grouped_kernel_equals_its_twin_and_one_layer_pool():
    """The kernel in interpret mode against the gather reference on the
    same inputs, and a pool of one layer without ``layer`` through the
    same kernel."""
    pages, bt, _ = _gqa_case("bfloat16")
    sids, poss = (np.asarray(x, np.int32)
                  for x in zip(*GQA_BATCHES["mixed-runs"]))
    q = np.random.RandomState(1).randn(len(sids), GQA_H,
                                       GQA_D).astype(np.float32)
    twin = np.asarray(gqa_flat_attention_reference(
        jnp.asarray(q), pages, jnp.asarray(bt), jnp.asarray(sids),
        jnp.asarray(poss), GQA_SCALE, GQA_G, layer=1))
    got = _gqa("pallas", q, pages, bt, sids, poss)
    np.testing.assert_allclose(got, twin, rtol=2e-5, atol=5e-6)
    np.testing.assert_array_equal(
        got, _gqa("pallas", q, pages[1], bt, sids, poss, layer=None))
    with pytest.raises(ValueError, match="kv_heads"):
        gqa_flat_attention(q, pages[1], bt, sids, poss, GQA_SCALE, GQA_G,
                           layer=0)
    with pytest.raises(ValueError, match="kv_heads"):
        gqa_flat_attention(q, pages, bt, sids, poss, GQA_SCALE, 5, layer=0)
