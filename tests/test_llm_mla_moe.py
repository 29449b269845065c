"""The DeepSeek-V3 block of ``TinyDecoder`` (latent attention over a paged
latent cache, routed and shared experts) at a toy size on the CPU: the
dense oracle and the paged path against the benchmark's plain reference,
the two new kernels in interpret mode against their ``jnp`` twins, and
what the engine refuses for this block.
"""
import importlib
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from mxnet_tpu.ops import moe, registry                 # noqa: E402
from mxnet_tpu.ops.ragged_attention import (            # noqa: E402
    mla_flat_attention, mla_flat_attention_reference)
from mxnet_tpu.serving.llm import (LLMEngine, LLMServer,  # noqa: E402
                                   PagedKVCache)
from mxnet_tpu.serving.llm.model import (               # noqa: E402
    DecoderConfig, TinyDecoder, UnsupportedModelFeatureError,
    greedy_decode_reference)

ref = importlib.import_module("benchmark.reference.deepseek_v3_decoder")

#: 2 expert layers after a dense one, 8 experts top-2, latent 16 + 8
TOY = dict(vocab_size=97, d_model=64, num_layers=3, num_heads=4, d_ff=96,
           max_context=64, model_type="deepseek_v3", kv_lora_rank=16,
           qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
           rope_theta=50000.0, rms_norm_eps=1e-5, first_k_dense_replace=1,
           n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
           moe_intermediate_size=32, routed_scaling_factor=2.446,
           norm_topk_prob=True)
TOKENS = np.random.RandomState(0).randint(0, 97, 40)


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(**TOY))


def paged_logits(model, params, tokens, dtype, chunk=8, block=4):
    """``tokens`` through ``decode_flat`` in chunks, one sequence whose
    blocks lie scattered in a pool of latents."""
    pool = PagedKVCache.for_model(model, block, 24, 64, dtype=dtype)
    assert pool.latent_pages.shape == (3, 24, block, 128)
    assert pool.kind == "latent" and pool.k_pages is None
    table = jnp.asarray([[3, 5, 7, 9, 11, 13, 15, 17, 2, 4] + [0] * 6],
                        jnp.int32)
    pages, out = pool.latent_pages, []
    for start in range(0, len(tokens), chunk):
        n = min(chunk, len(tokens) - start)
        logits, pages, load = model.decode_flat(
            params, jnp.asarray(tokens[start:start + n], jnp.int32),
            jnp.arange(start, start + n, dtype=jnp.int32),
            jnp.zeros(n, jnp.int32), jnp.ones(n, jnp.int32), pages, None,
            table)
        out.append(logits)
        # every token of a chunk routes to two experts in each of the
        # two expert layers; no expert can take more than the chunk
        assert int(load[0]) == n * 2 * 2 and 1 <= int(load[2]) <= n
        assert 2 <= int(load[1]) <= 16
    return jnp.concatenate(out)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_oracle_and_paged_path_against_the_reference(model, dtype):
    """(a), (b): ``forward`` attends in the expanded form and runs every
    expert densely; ``decode_flat`` keeps 24 values a token in pages and
    attends in the absorbed form. Both against the reference's logits."""
    params = ref.init_params(5, TOY, dtype=jnp.dtype(dtype))
    want = ref.forward(params, jnp.asarray(TOKENS, jnp.int32), TOY)
    dense, latents, none = model.forward(params,
                                         jnp.asarray(TOKENS[None]))
    assert none is None and latents.shape == (3, 1, 40, 24)
    paged = paged_logits(model, params, TOKENS, dtype)
    dense_err = np.abs(np.asarray(dense[0] - want)).max(axis=1)
    paged_err = np.abs(np.asarray(paged - want)).max(axis=1)
    if dtype == "float32":
        # the same arithmetic in float32, summed in another order
        # (absorbed against expanded, grouped against masked experts)
        assert dense_err.max() < 5e-5 and paged_err.max() < 5e-5
        # absorbed against expanded, directly
        assert np.abs(np.asarray(paged - dense[0])).max() < 5e-5
    else:
        # bf16 weights, products' operands and cache: 8 bits of mantissa
        # over logits of 3 give 0.004 to 0.007 here. A rounding can swap
        # the second of two experts at a near tie; under the reference's
        # residual-scaled weights that moves a position's logits by a
        # tenth or so, not by the 1 it does with branches as large as
        # the stream
        assert np.median(dense_err) < 0.02 and np.median(paged_err) < 0.02
        assert dense_err.max() < 0.3 and paged_err.max() < 0.3


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_served_through_llmserver_against_the_reference(model, dtype):
    """(a), the normal path: chunked prefill, then decoding through the
    paged latent cache behind ``LLMServer``; every served token against
    the reference's logits at its position."""
    params = ref.init_params(5, TOY, dtype=jnp.dtype(dtype))
    server = LLMServer(model, params, name="mla_moe_" + dtype,
                       block_size=4, prefill_chunk=8, dtype=dtype)
    server.start()
    try:
        rs = np.random.RandomState(1)
        asked = []
        for _ in range(8):
            prompt = rs.randint(0, 97, rs.randint(3, 40)).tolist()
            asked.append((prompt, server.submit(
                prompt, int(rs.randint(2, 20)))))
        worst = 0.0
        for prompt, fut in asked:
            served = list(fut.result(timeout=300).tokens)
            gaps = ref.served_token_gaps(params, prompt, served, TOY, 128)
            worst = max(worst, float(gaps.max()))
        stats = server.stats()
    finally:
        server.shutdown(drain=False)
    # float32: the served token is the reference's first choice but for
    # round-off; bf16: within the hundredth the logits differ by, or
    # what a swapped expert moves them by (see the test above)
    assert worst <= (1e-4 if dtype == "float32" else 0.3)
    layout = stats["cache_layout"]
    assert layout["kind"] == "latent" and layout["values_per_token"] == 24
    itemsize = 4 if dtype == "float32" else 2
    assert layout["bytes_per_token"] == 3 * 128 * itemsize
    assert stats["weight_dtype"] == dtype and stats["kv_dtype"] == dtype
    # counters of the router: two pairs a token a layer, 16 experts
    # held over the two expert layers of every dispatched step
    assert stats["moe_routed_pairs"] > 0 and model.expert_slots == 16
    assert 0 < stats["moe_experts_hit"] <= stats["moe_steps"] * 16
    # tiles of 16 rows: one an expert hit, and in the few steps that
    # feed a long prompt at once a second one
    assert stats["moe_experts_hit"] <= stats["moe_tiles"] <= (
        stats["moe_experts_hit"] + stats["moe_routed_pairs"] // 16)
    assert 1.0 <= stats["moe_tiles_per_expert_hit"] == (
        stats["moe_tiles"] / stats["moe_experts_hit"]) < 1.2
    from mxnet_tpu.observability import get_registry
    tiles = [c.value for c in
             get_registry().get("mxtpu_llm_moe_tiles_total").children()
             if c.labels_dict["server"] == "mla_moe_" + dtype]
    assert tiles == [stats["moe_tiles"]]


def test_step_span_carries_the_routers_load(model):
    """The expert load comes back in the step's one fetch and lands on
    ``mxtpu.llm.step``."""
    from mxnet_tpu.observability.tracing import get_tracer
    params = ref.init_params(6, TOY, dtype=jnp.float32)
    engine = LLMEngine(model, params, block_size=4, prefill_chunk=8)
    fetches = []
    real = engine._device_get
    engine._device_get = lambda out: (fetches.append(len(out)),
                                      real(out))[1]
    from mxnet_tpu.serving.llm import Sequence
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        engine.add(Sequence(list(range(1, 12)), 3))
        for _ in range(4):
            engine.step()
        spans = [s for s in tracer.snapshot()
                 if s["name"] == "mxtpu.llm.step"
                 and "moe_pairs" in s["attrs"]]
    finally:
        if not was_on:
            tracer.disable()
        tracer.clear()
    assert fetches and set(fetches) == {3}       # one fetch, three arrays
    assert len(spans) == len(fetches)
    first = spans[0]["attrs"]
    # the first step, an idle engine's whole budget (8 slots of 8): the
    # 11 prompt tokens at once x 2 experts x 2 expert layers
    assert first["moe_pairs"] == 44 and first["rows"] == [(11, 11)]
    assert 2 <= first["moe_experts_hit"] <= 16
    assert 2 <= first["moe_max_load"] <= 11
    # at most 11 pairs an expert: one tile of 16 rows each
    assert first["moe_tiles"] == first["moe_experts_hit"]


def test_long_chunks_through_both_kernels_beside_decoding_rows(monkeypatch):
    """The budget's long runs through the latent kernel and the grouped
    expert products themselves (interpret mode), contexts to 170: 2
    slots of 40, so a step of 80 tokens is two query blocks of 64. A
    140-token prompt takes 60 tokens beside the other row's prompt, then
    79 beside its decode token (a run astride the query blocks' edge);
    the pool is too small for both contexts, so it is preempted and
    writes its folded prompt again in chunks of 80 and 79. Every served
    token is the reference's first choice."""
    import functools
    from mxnet_tpu.serving.llm import Sequence, model as model_mod
    for name in ("mla_flat_attention", "moe_experts"):
        monkeypatch.setattr(model_mod, name, functools.partial(
            getattr(model_mod, name), use_pallas=True, interpret=True))
    toy = dict(TOY, max_context=192)
    # a model of its own: its programs trace the kernels, not the twins
    model = TinyDecoder(DecoderConfig(**toy))
    params = ref.init_params(5, toy, dtype=jnp.float32)
    engine = LLMEngine(model, params, max_seqs=2, block_size=8,
                       prefill_chunk=40, num_blocks=26)
    assert engine._t_buckets == [2, 40, 80]
    rs = np.random.RandomState(7)
    cases = [(rs.randint(0, 97, 20).tolist(), 60),
             (rs.randint(0, 97, 140).tolist(), 30)]
    seqs = [Sequence(p, n) for p, n in cases]
    chunks, inner = [], engine._dispatch

    def dispatch(rows, plans):
        chunks.extend((s is seqs[1], len(plans[s]["tokens"]), len(rows))
                      for s in rows if plans[s]["kind"] == "prefill")
        return inner(rows, plans)
    engine._dispatch = dispatch
    for s in seqs:
        engine.add(s)
    for _ in range(500):
        if not engine.has_work():
            break
        engine.step()
    assert not engine.has_work()
    # (the long prompt's?, tokens, rows in the step)
    assert chunks == [(False, 20, 2), (True, 60, 2), (True, 79, 2),
                      (True, 1, 2), (True, 80, 1), (True, 79, 1)]
    assert [s.preemptions for s in seqs] == [0, 1]
    for s, (prompt, n) in zip(seqs, cases):
        served = s.output_tokens()
        assert len(served) == n
        gaps = ref.served_token_gaps(params, prompt, served, toy, 192)
        assert float(gaps.max()) <= 1e-4


def test_latent_kernel_against_its_twin_with_garbage_invisible():
    """(c): the Pallas kernel in interpret mode against the gather
    reference; what lies past a token's position, in blocks no table
    names and in the null block changes nothing."""
    rs = np.random.RandomState(3)
    L, N, bs, W, H, V, T = 2, 12, 4, 128, 4, 16, 11
    pages = jnp.asarray(rs.randn(L, N, bs, W), jnp.float32)
    q = jnp.asarray(rs.randn(T, H, W), jnp.float32)
    tables = jnp.asarray([[3, 5, 7, 0], [9, 2, 0, 0], [4, 6, 8, 10]],
                         jnp.int32)
    seq_ids = jnp.asarray([0, 0, 0, 0, 1, 1, 2, 2, 2, 2, 2], jnp.int32)
    positions = jnp.asarray([6, 7, 8, 9, 4, 5, 10, 11, 12, 13, 14],
                            jnp.int32)
    args = (tables, seq_ids, positions, 0.25, V)
    want = mla_flat_attention_reference(q, pages, *args, layer=1)
    got = mla_flat_attention(q, pages, *args, layer=1, use_pallas=True,
                             interpret=True)
    assert got.shape == (T, H, V)
    np.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-5)
    # one layer's pool without `layer`: the same kernel
    single = mla_flat_attention(q, pages[1], *args, use_pallas=True,
                                interpret=True)
    np.testing.assert_array_equal(single, got)
    dirty = np.array(pages)
    dirty[:, 0] = 1e4                  # the null block
    dirty[:, 1] = -1e4                 # a block no table names
    dirty[0] = 1e4                     # another layer
    dirty[1, 7, 2:] = 1e4              # sequence 0 past position 9
    dirty[1, 2, 2:] = -1e4             # sequence 1 past position 5
    dirty[1, 10, 3:] = 1e4             # sequence 2 past position 14
    for fn in (lambda p: mla_flat_attention(
            q, p, *args, layer=1, use_pallas=True, interpret=True),
            lambda p: mla_flat_attention_reference(q, p, *args, layer=1)):
        np.testing.assert_allclose(fn(jnp.asarray(dirty)), want,
                                   rtol=2e-5, atol=2e-5)
    with pytest.raises(ValueError, match="latent pool"):
        mla_flat_attention(q, pages, *args)
    assert registry.get("mla_flat_attention") is not None


def _rows(*runs):
    """``(seq_ids, positions)`` of a packed batch from ``(sequence, first
    position, tokens)`` runs laid side by side."""
    sids = [s for s, _, n in runs for _ in range(n)]
    poss = [p + i for _, p, n in runs for i in range(n)]
    return np.asarray(sids, np.int32), np.asarray(poss, np.int32)


#: name -> (H, W, v_dim, block_size, MB, page dtype, runs, valid tokens).
#: At 4 heads a query block is the whole batch (or 64 tokens), at 16 heads
#: 16 tokens; a loop step takes 256 keys, never more than the table holds.
LATENT_CASES = {
    # two chunks of 16 and three decodes, one query block
    "mixed_step": (4, 128, 16, 4, 16, "float32",
                   [(0, 9, 16), (2, 40, 16), (1, 5, 1), (3, 63, 1),
                    (4, 0, 1)], 35),
    # 256 keys a step are 8 pages of 32: sequences of 11 pages (no
    # multiple), of 1 page, of exactly 8 and of 9
    "page_counts": (4, 128, 16, 32, 12, "float32",
                    [(0, 330, 3), (1, 2, 2), (2, 255, 1), (3, 256, 1)], 7),
    # 16 heads: blocks of 16 tokens, chunks astride their edges, a run
    # longer than a block, the last block partly past T
    "straddle": (16, 128, 16, 4, 16, "float32",
                 [(1, 3, 7), (0, 20, 16), (2, 0, 20), (3, 50, 1),
                  (1, 33, 2)], 46),
    # the first 10 tokens are the step; the tail is what an earlier,
    # longer step left in the buffers
    "stale_tail": (16, 128, 16, 4, 16, "float32",
                   [(0, 12, 8), (3, 30, 2), (2, 57, 6), (1, 11, 1),
                    (0, 40, 3), (3, 3, 3)], 10),
    # the cell's own tiles: pages of 16 in bf16, W 640, 16 heads, 512
    # values; a chunk, decodes, a second chunk astride the block edge
    "cell_tiles": (16, 640, 512, 16, 24, "bfloat16",
                   [(0, 100, 16), (1, 383, 1), (2, 15, 1), (3, 200, 16),
                    (4, 0, 4)], 38),
    # the same tokens scattered: no two neighbours of one sequence, so
    # every token is a run of its own
    "scattered": (4, 128, 16, 4, 16, "float32",
                  [(0, 9, 1), (2, 40, 1), (0, 10, 1), (1, 5, 1),
                   (2, 3, 1), (0, 2, 1), (1, 63, 1)], 7),
    # The three below at the cell's tiles (16 tokens a block, 256 keys a
    # loop step) over tables of 768 keys, so that two runs of one
    # sequence walk DIFFERENT numbers of loop steps: a row that took keys
    # from another run's walk would count its first keys twice.
    # one block holds A, B, A: A's runs end in the third and the first
    # loop step; the same again with the short run first
    "runs_apart": (16, 640, 512, 16, 48, "bfloat16",
                   [(0, 700, 5), (1, 300, 3), (0, 100, 4), (2, 20, 4),
                    (3, 90, 2), (1, 610, 1), (3, 520, 3)], 22),
    # T = 23: the last block is 7 tokens and 9 rows of the wrapper's own
    # padding; sequence 0 and the last token's sequence each have a
    # token past the first loop step there, away from the padding
    "padded_block": (16, 128, 16, 16, 48, "float32",
                     [(1, 50, 16), (0, 600, 1), (2, 400, 1), (3, 300, 1),
                      (4, 700, 3), (2, 10, 1)], 23),
    # three decode tokens are the step; the tail an earlier step left
    # holds chunks of the same sequences at other depths, another
    # sequence's token between
    "stale_tail_deep": (16, 128, 16, 16, 48, "float32",
                        [(0, 600, 1), (1, 20, 1), (2, 300, 1), (0, 280, 8),
                         (1, 500, 3), (2, 5, 4), (0, 40, 5)], 3),
}


@pytest.mark.parametrize("case", sorted(LATENT_CASES))
def test_latent_kernel_walks_runs_of_a_packed_step(case):
    """The kernel's iteration (query blocks, runs of one sequence, page
    steps to the run's own length) in interpret mode against the gather
    reference, every valid row; rows past the step's tokens finite."""
    H, W, V, bs, MB, dtype, runs, valid = LATENT_CASES[case]
    rs = np.random.RandomState(len(case))
    S, L = 5, 2
    N = S * MB + 1
    pages = jnp.asarray(rs.randn(L, N, bs, W), dtype)
    tables = jnp.asarray(
        1 + rs.permutation(N - 1).reshape(S, MB), jnp.int32)
    sids, poss = (jnp.asarray(a) for a in _rows(*runs))
    q = jnp.asarray(rs.randn(len(sids), H, W), jnp.float32)
    scale = W ** -0.5
    want = mla_flat_attention_reference(q, pages, tables, sids, poss,
                                        scale, V, layer=1)
    got = mla_flat_attention(q, pages, tables, sids, poss, scale, V,
                             layer=1, use_pallas=True, interpret=True)
    assert got.shape == (len(sids), H, V) and got.dtype == q.dtype
    np.testing.assert_allclose(got[:valid], want[:valid], rtol=2e-5,
                               atol=2e-5)
    assert np.isfinite(np.asarray(got)).all()
    # what the step's own tokens read is all that reaches their rows: a
    # tail of other sequences' tokens changes none of them
    if valid < len(sids):
        alone = mla_flat_attention(
            q[:valid], pages, tables, sids[:valid], poss[:valid], scale,
            V, layer=1, use_pallas=True, interpret=True)
        np.testing.assert_allclose(got[:valid], alone, rtol=1e-6,
                                   atol=1e-6)


def test_latent_tiles_follow_the_shapes():
    """No knob picks the tiles: 256 rows and 256 keys where the shapes
    allow them."""
    from mxnet_tpu.ops.ragged_attention import _mla_tiles
    # the cell: 16 heads, pages of 16
    assert _mla_tiles(8, 16, 16, 256) == (8, 16)
    assert _mla_tiles(23, 16, 16, 512) == (16, 16)
    assert _mla_tiles(128, 16, 16, 512) == (16, 16)
    # few heads: more tokens a block, in whole sublane tiles, or all
    assert _mla_tiles(11, 4, 4, 4) == (11, 4)
    assert _mla_tiles(100, 4, 4, 512) == (64, 64)
    assert _mla_tiles(100, 3, 16, 512) == (80, 16)
    assert _mla_tiles(2, 128, 256, 8) == (2, 1)
    assert _mla_tiles(40, 512, 16, 64) == (1, 16)


def per_token_loop(x, sel, gates, valid, wg, wu, wd):
    out = np.zeros((x.shape[0], wd.shape[2]), np.float64)
    for t in range(x.shape[0]):
        if not valid[t]:
            continue
        for e, g in zip(np.asarray(sel[t]), np.asarray(gates[t])):
            gate = np.asarray(x[t], np.float64) @ np.asarray(wg[e])
            up = np.asarray(x[t], np.float64) @ np.asarray(wu[e])
            out[t] += g * ((gate / (1 + np.exp(-gate)) * up)
                           @ np.asarray(wd[e]))
    return out


def _planted(loads, k):
    """``sel [T, k]`` that gives expert ``e`` exactly ``loads[e]`` pairs."""
    flat = np.repeat(np.arange(len(loads)), loads)
    assert flat.size % k == 0
    return jnp.asarray(flat.reshape(-1, k), jnp.int32)


#: plant, then what differs from T 37, d 64, E 8, m 32, k 2, float32
EXPERT_CASES = {
    "routed": {},
    "all_to_one": {},                  # every pair on expert 5
    "one_unused": {},                  # expert 3 gets no token
    "all_padding": {},
    # an expert's whole width is one block: three lane tiles, and a
    # width that is no multiple of 128
    "width_384": {"plant": "routed", "m": 384},
    "width_200": {"plant": "routed", "m": 200},
    "bf16": {"plant": "routed", "m": 256, "dtype": jnp.bfloat16},
    # experts of 2, 3 and 17 tiles of 16 rows, the rest unused
    "tiles_2_3_17": {"loads": [20, 0, 40, 0, 0, 260, 0, 0]},
    "one_tile_each": {"loads": [16, 1, 8, 16, 3, 16, 2, 16]},
    # the budget forced small: column blocks of 256 of 512, and of 128
    "fallback_2_blocks": {"plant": "routed", "m": 512, "budget": 500_000,
                          "tn": 256},
    "fallback_3_blocks": {"loads": [20, 0, 40, 0, 0, 260, 0, 0], "m": 384,
                          "budget": 300_000, "tn": 128},
}


@pytest.mark.parametrize("case", list(EXPERT_CASES))
def test_expert_layer_loses_no_pair_under_imbalance(case, monkeypatch):
    """(d): the grouped kernel (interpret mode) and the plain twin
    against a loop over each token's experts, under the router's own
    choice and under planted imbalance; at widths of several lane tiles
    and of no whole ones, in bf16, with experts of many tiles and of one
    each, and through the column blocks a small budget forces."""
    spec = EXPERT_CASES[case]
    plant = spec.get("plant", case)
    rs = np.random.RandomState(4)
    d, E, k = 64, 8, 2
    m, dtype = spec.get("m", 32), spec.get("dtype", jnp.float32)
    loads = spec.get("loads")
    T = sum(loads) // k if loads else 37
    x = jnp.asarray(rs.randn(T, d), dtype)
    wg = jnp.asarray(rs.randn(E, d, m) * d ** -0.5, dtype)
    wu = jnp.asarray(rs.randn(E, d, m) * d ** -0.5, dtype)
    wd = jnp.asarray(rs.randn(E, m, d) * m ** -0.5, dtype)
    router = jnp.asarray(rs.randn(d, E) * d ** -0.5, jnp.float32)
    bias = jnp.asarray(rs.randn(E) * 0.01, jnp.float32)
    sel, gates = moe.route_noaux_tc(x, router, bias, k, True, 2.446)
    np.testing.assert_allclose(gates.sum(axis=1), 2.446, rtol=1e-5)
    valid = np.arange(T) < T - 4           # four rows of bucket padding
    if plant == "all_to_one":
        sel = jnp.full_like(sel, 5)
    elif plant == "one_unused":
        sel = jnp.where(sel == 3, (sel + 1) % E, sel)
    elif plant == "all_padding":
        valid = np.zeros(T, bool)
    elif loads:
        sel, valid = _planted(loads, k), np.ones(T, bool)
    if "budget" in spec:
        assert moe._column_block(d, m, 4, spec["budget"]) == spec["tn"]
        kernel = moe._grouped_swiglu_pallas
        monkeypatch.setattr(moe, "_grouped_swiglu_pallas",
                            lambda *a: kernel(*a, spec["budget"]))
    else:
        assert moe._column_block(d, m, x.dtype.itemsize,
                                 moe.VMEM_BUDGET) == m
    f32 = [np.asarray(a, np.float32) for a in (x, wg, wu, wd)]
    want = per_token_loop(f32[0], sel, gates, valid, *f32[1:])
    args = (x, sel, gates, jnp.asarray(valid), wg, wu, wd)
    twin = moe.moe_experts_reference(*args)
    got = moe.moe_experts(*args, use_pallas=True, interpret=True)
    assert got.dtype == jnp.float32 and got.shape == (T, d)
    # bf16: silu(g) * u is rounded to the operands' dtype before the
    # down product, in the kernel and the twin alike
    tol = 1e-4 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(twin, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol)
    np.testing.assert_allclose(got, twin, rtol=tol / 4, atol=tol / 4)
    assert not np.asarray(got)[~valid].any()    # padding comes back 0
    pairs, hit, biggest, tiles = (int(v) for v in moe.moe_load(
        sel, jnp.asarray(valid), E))
    live = int(valid.sum())
    assert pairs == live * k and hit <= tiles <= hit + pairs // 16
    if plant == "all_to_one":
        assert (hit, biggest) == (1, live * k)
        assert tiles == -(-live * k // 16)
    elif plant == "one_unused":
        assert hit <= E - 1
    elif plant == "all_padding":
        assert (hit, biggest, tiles) == (0, 0, 0)
    elif case == "one_tile_each":
        assert (hit, biggest, tiles) == (8, 16, 8)
    elif loads:
        assert (hit, biggest, tiles) == (3, 260, 2 + 3 + 17)
    assert registry.get("moe_experts") is not None


def _kernel_call(monkeypatch, sel, valid, E, d=64, m=32):
    """What ``moe_experts`` hands the kernel for a routing: ``(x_rows,
    tile_expert, tiles_used)``, as NumPy arrays."""
    seen = []
    kernel = moe._grouped_swiglu_pallas
    monkeypatch.setattr(moe, "_grouped_swiglu_pallas",
                        lambda *a: seen.append(a) or kernel(*a))
    T, k = sel.shape
    w = jnp.zeros((E, d, m), jnp.float32)
    moe.moe_experts(jnp.ones((T, d), jnp.float32), sel,
                    jnp.ones((T, k), jnp.float32), jnp.asarray(valid), w,
                    w, w.transpose(0, 2, 1), use_pallas=True,
                    interpret=True)
    (x_rows, tile_expert, used, *_), = seen
    return np.asarray(x_rows), np.asarray(tile_expert), np.asarray(used)


@pytest.mark.parametrize("loads,blocks", [
    ([20, 0, 40, 0, 0, 260, 0, 0], 1), ([20, 0, 40, 0, 0, 260, 0, 0], 3),
    ([16, 1, 8, 16, 3, 16, 2, 16], 1), ([0, 0, 0, 0, 0, 0, 0, 2], 2),
    ([0] * 8, 1)])
def test_an_experts_weights_are_fetched_once(loads, blocks, monkeypatch):
    """The grid's own index maps over the tiles ``moe_experts`` lays out:
    walking the grid in its order, the weights' block index changes
    (a transfer) experts hit x column blocks times and no more, however
    many tiles an expert fills and however many lie unused behind the
    last; every row tile is read and written once a column block."""
    d, m, E, k = 64, 128 * blocks, len(loads), 2
    sel = _planted(loads, k) if sum(loads) else jnp.zeros((3, k), jnp.int32)
    valid = np.ones(sel.shape[0], bool) if sum(loads) \
        else np.zeros(sel.shape[0], bool)
    x_rows, te, used = _kernel_call(monkeypatch, sel, valid, E)
    n_tiles = x_rows.shape[0] // moe.TILE_ROWS
    hit = sum(n > 0 for n in loads)
    assert te.shape == (n_tiles,) and used[0] == sum(
        -(-n // moe.TILE_ROWS) for n in loads)
    assert n_tiles > used[0]            # the tail of unused tiles is there
    assert [int(e) for e in te[:used[0]]] == [
        e for e, n in enumerate(loads)
        for _ in range(-(-n // moe.TILE_ROWS))]
    x_spec, wg_spec, wu_spec, wd_spec, o_spec = moe._grouped_specs(d, 128)
    steps = [(j, t) for j in range(blocks) for t in range(n_tiles)]
    for spec in (wg_spec, wu_spec, wd_spec):
        at = [tuple(int(i) for i in spec.index_map(j, t, te, used))
              for j, t in steps]
        fetched = [at[0]] + [b for a, b in zip(at, at[1:]) if a != b]
        # an empty step still holds one block: the pipeline's first
        assert len(fetched) == max(hit, 1) * blocks
        assert len(set(fetched)) == len(fetched)    # none comes back
        assert {i[0] for i in fetched} == (
            {e for e, n in enumerate(loads) if n} or {0})
    rows, outs = ([tuple(int(i) for i in spec.index_map(j, t, te, used))
                   for j, t in steps] for spec in (x_spec, o_spec))
    assert rows == [(t, 0) for _, t in steps]
    assert outs == [(j, t, 0) for j, t in steps]
    assert wg_spec.block_shape == (None, d, 128) \
        and wd_spec.block_shape == (None, 128, d)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_load_counts_the_tiles_the_pairs_fill(seed):
    rs = np.random.RandomState(seed)
    T, k, E = 200, 6, 16
    sel = rs.randint(0, E, (T, k)) if seed else rs.zipf(1.5, (T, k)) % E
    valid = rs.rand(T) < 0.8
    counts = np.bincount(sel[valid].reshape(-1), minlength=E)
    want = [counts.sum(), (counts > 0).sum(), counts.max(),
            sum(-(-int(n) // moe.TILE_ROWS) for n in counts)]
    got = moe.moe_load(jnp.asarray(sel, jnp.int32), jnp.asarray(valid), E)
    assert got.dtype == jnp.int32 and got.tolist() == want
    twice = moe.moe_load_sum(got, got).tolist()
    assert twice == [2 * want[0], 2 * want[1], want[2], 2 * want[3]]


def test_selection_bias_selects_and_does_not_weigh():
    x = jnp.ones((1, 4), jnp.float32)
    router = jnp.asarray(np.diag([3.0, 2.0, 1.0, 0.5]), jnp.float32)
    sel, gates = moe.route_noaux_tc(x, router, jnp.zeros(4), 2, True, 1.0)
    assert sorted(np.asarray(sel[0]).tolist()) == [0, 1]
    biased, gates_b = moe.route_noaux_tc(
        x, router, jnp.asarray([0.0, -1.0, 0.0, 1.0]), 2, True, 1.0)
    assert sorted(np.asarray(biased[0]).tolist()) == [0, 3]
    s = 1 / (1 + np.exp(-np.asarray([3.0, 0.5])))
    np.testing.assert_allclose(sorted(np.asarray(gates_b[0])),
                               sorted(s / s.sum()), rtol=1e-5)


def test_default_config_still_builds_the_gpt2_program():
    """(f): the six widths alone describe PR 27's model: the same
    fields, tree, cache and step signature."""
    config = DecoderConfig()
    assert config.model_type == "gpt2" and config.head_dim == 16
    assert config.to_dict() == {"vocab_size": 32, "d_model": 32,
                                "num_layers": 2, "num_heads": 2,
                                "d_ff": 64, "max_context": 128}
    assert DecoderConfig.from_dict(config.to_dict()).to_dict() \
        == config.to_dict()
    toy = DecoderConfig(**TOY)
    assert DecoderConfig.from_dict(toy.to_dict()).to_dict() == toy.to_dict()
    assert toy.head_dim == 16 and toy.latent_dim == 24
    model = TinyDecoder(config)
    assert model.expert_slots == 0 and model.cache_layout == {
        "kind": "kv", "num_heads": 2, "head_dim": 16}
    params = model.init_params(0)
    assert set(params) == {"embed", "pos", "lnf_g", "lnf_b", "head",
                           "layers"}
    assert set(params["layers"][0]) == {
        "ln1_g", "ln1_b", "wq", "wk", "wv", "wo", "ln2_g", "ln2_b", "w1",
        "b1", "w2", "b2"}
    engine = LLMEngine(model, params, block_size=16)
    assert engine.cache.kind == "kv"
    assert [p.shape for p in engine.cache.pools()] == [(2, 65, 16, 2, 16)] * 2
    text = engine.step_program_text()
    # params' 29 leaves, the K and the V pool, the batch's 14 arrays
    assert text.count("%arg") >= 45
    assert engine.cache.layout() == {
        "kind": "kv", "values_per_token": 64,
        "pool_shape": [2, 65, 16, 2, 16], "bytes_per_token": 512}
    want = greedy_decode_reference(model, params, [1, 2, 3], 4)
    from mxnet_tpu.serving.llm import Sequence
    seq = Sequence([1, 2, 3], 4)
    engine.add(seq)
    while engine.has_work():
        engine.step()
    assert list(seq.generated) == want


def test_a_latent_cache_without_experts_serves():
    """The cache's layout and the router's load are separate facts: a
    dense MLA model (every layer's MLP dense) keeps the latent pool,
    returns no load and serves through the same step."""
    model = TinyDecoder(DecoderConfig(**dict(TOY, first_k_dense_replace=3)))
    assert model.expert_slots == 0
    assert model.cache_layout["kind"] == "latent"
    params = model.init_params(0)
    engine = LLMEngine(model, params, block_size=4)
    assert [p.shape for p in engine.cache.pools()] == [(3, 129, 4, 128)]
    from mxnet_tpu.serving.llm import Sequence
    seq = Sequence([int(t) for t in TOKENS[:9]], 3)
    engine.add(seq)
    while engine.has_work():
        engine.step()
    assert engine._moe_load is None
    tokens = jnp.asarray([(seq.prompt + seq.generated)[:12]], jnp.int32)
    logits = model.forward(params, tokens)[0][0]
    assert list(seq.generated) == [int(t) for t in
                                   jnp.argmax(logits[8:11], axis=-1)]


def _toy_draft():
    return TinyDecoder(DecoderConfig(vocab_size=97, max_context=64))


@pytest.mark.parametrize("kwargs,named", [
    (lambda: {"adapter_bank": object()}, "adapter_bank"),
    (lambda: {"weight_dtype": "int8"}, "weight_dtype"),
    (lambda: {"weight_dtype": "fp8"}, "weight_dtype"),
    (lambda: {"kv_dtype": "int8"}, "kv_dtype"),
    (lambda: {"kv_dtype": "fp8"}, "kv_dtype"),
    (lambda: {"mesh": "tp=2"}, "mesh"),
    (lambda: {"draft_model": _toy_draft(),
              "draft_params": _toy_draft().init_params(0)}, "draft_model"),
])
def test_engine_refuses_what_the_block_does_not_take(model, kwargs, named):
    params = model.init_params(0)
    with pytest.raises(UnsupportedModelFeatureError, match=named):
        LLMEngine(model, params, block_size=4, **kwargs())


def test_entry_points_no_engine_path_runs_raise(model):
    params = model.init_params(0)
    z = jnp.zeros((1, 1), jnp.int32)
    for call in (
            lambda: greedy_decode_reference(model, params, [1, 2], 2),
            lambda: model.param_specs()):
        with pytest.raises(UnsupportedModelFeatureError):
            call()
    with pytest.raises(ValueError, match="model_type"):
        DecoderConfig(model_type="mamba")
    with pytest.raises(ValueError, match="kv_lora_rank"):
        DecoderConfig(model_type="deepseek_v3")


# ------------------------------------------ one step in flight (PR 38) --

@pytest.mark.parametrize("slots", [2, 8])
def test_pipelined_server_answers_as_the_synchronous_step(model, slots):
    """The latent cache and the expert layers behind ``LLMServer``, one
    step in flight: the tokens of the synchronous ``engine.step()``, the
    prefix cache hitting a prompt served before, and each launched
    step's span carrying its own rows and its own routers' load."""
    from mxnet_tpu.observability.tracing import get_tracer
    from mxnet_tpu.serving.llm import Sequence
    params = ref.init_params(5, TOY, dtype=jnp.float32)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 97, rs.randint(3, 40)).tolist()
               for _ in range(6)]
    prompts.append(prompts[0][:17] + [5, 6])
    kw = dict(max_seqs=slots, block_size=4, prefill_chunk=8)
    engine = LLMEngine(model, params, **kw)
    seqs = [Sequence(p, 12) for p in prompts]
    for s in seqs:
        engine.add(s)
    while engine.has_work():
        engine.step()
    server = LLMServer(model, params, name=f"mla_moe_ahead_{slots}", **kw)
    server.warmup()
    server.start()
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        got = [f.result(timeout=300).tokens
               for f in [server.submit(p, 12) for p in prompts]]
        stats = server.stats()
        steps = [s for s in tracer.snapshot()
                 if s["name"] == "mxtpu.llm.step" and "rows" in s["attrs"]]
    finally:
        server.shutdown()
        if not was_on:
            tracer.disable()
        tracer.clear()
    assert got == [s.output_tokens() for s in seqs]
    assert stats["overlap_share"] > 0.8
    assert len(steps) == stats["steps_dispatched"]
    for step in steps:
        attrs = step["attrs"]
        assert {"moe_pairs", "moe_experts_hit", "overlapped"} <= set(attrs)
        # every token of the step through each of the 2 expert layers
        # to its 2 experts
        assert attrs["moe_pairs"] == 4 * sum(n for n, _ in attrs["rows"])
    assert sum(s["attrs"]["overlapped"] for s in steps) \
        == stats["steps_overlapped"]
