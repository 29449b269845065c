"""The Command-A+ block of ``TinyDecoder`` (``model_type`` ``cohere2_moe``:
parallel blocks of sliding-window and full grouped-query attention beside a
share of the routed experts and the mean of the shared ones) at a toy size
on the CPU: the paged path through a cache of two kinds of layer against
the benchmark's plain reference, the per-group windowed kernel in
interpret mode against its gather reference at the window's edges, the
cache manager under a fuzz of admissions, window releases, preemptions and
finishes, the expert shares against the uncut layer, and the other blocks'
step programs against their digests before window layers existed.
"""
import hashlib
import importlib
import os
import re
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import jax                                              # noqa: E402
import jax.numpy as jnp                                 # noqa: E402

from mxnet_tpu.ops import gdn, moe, ragged_attention, ssd  # noqa: E402
from mxnet_tpu.ops.ragged_attention import (            # noqa: E402
    gqa_window_attention, gqa_window_attention_reference)
from mxnet_tpu.serving.llm import (LLMEngine, LLMServer,  # noqa: E402
                                   PagedKVCache, Sequence)
from mxnet_tpu.serving.llm import model as model_mod    # noqa: E402
from mxnet_tpu.serving.llm.kv_cache import (            # noqa: E402
    NULL_BLOCK, BlockAccountingError, NoFreeBlocksError)
from mxnet_tpu.serving.llm.model import (               # noqa: E402
    DecoderConfig, TinyDecoder, UnsupportedModelFeatureError)

ref = importlib.import_module("benchmark.reference.command_a_plus_decoder")

#: three sliding layers of window 8 and a full one, 4 query heads over 2
#: kv heads of 16, 4 of 16 routed experts held (experts 4 to 7), top-4,
#: two shared experts averaged; pages of 4 tokens
TOY = dict(vocab_size=97, d_model=64, num_layers=4, num_heads=4, d_ff=64,
           max_context=64, model_type="cohere2_moe",
           layer_types=["sliding_attention"] * 3 + ["full_attention"],
           sliding_window=8, num_key_value_heads=2, head_dim=16,
           rope_theta=10000.0, layer_norm_eps=1e-5, router_experts=16,
           first_routed_expert=4, n_routed_experts=4, num_experts_per_tok=4,
           num_shared_experts=2, moe_intermediate_size=32,
           norm_topk_prob=True, logit_scale=1.0, torch_dtype="float32")
BS = 4
#: float32 against float32, both at ``highest``: the paged path sums its
#: attention over pages and the reference over one row, so logits of about
#: unit spread agree to a few ulps of their largest (1e-5 of 3); a bfloat16
#: product anywhere moves them by 1e-3 and more (checked below)
LOGIT_TOL = 2e-5


@pytest.fixture(scope="module")
def params():
    return ref.init_params(5, TOY)


def _paged_logits(model, params, tokens, chunk, use_pallas=False):
    """Every position's logits from the paged path: the prompt in chunks
    of ``chunk``, then the rest a token at a time, one sequence through a
    cache with window layers whose pages behind the window go back to the
    pool as the engine gives them back; the pages the window layers hold
    are checked against the window at every step."""
    cache = PagedKVCache.for_model(model, BS, 64, TOY["max_context"],
                                   step_tokens=chunk)
    seq = Sequence(list(tokens), 1)
    cache.start(seq)
    pool = cache.pools()[0]
    out, pos, n = [], 0, len(tokens)
    kw = {} if not use_pallas else {"use_pallas": True, "interpret": True}
    orig = model_mod.gqa_window_attention

    def attention(*a, **k):
        return orig(*a, **dict(k, **kw))
    model_mod.gqa_window_attention = attention
    try:
        with jax.default_matmul_precision("highest"):
            while pos < n:
                t = chunk if pos < 24 else 1
                t = min(t, n - pos)
                cache.release_behind(seq, pos)
                cache.grow(seq, pos + t, pos)
                first = max(0, pos - TOY["sliding_window"] + 1) // BS
                lp = seq.layer_blocks
                for layer, row in enumerate(lp.table[:, :lp.blocks]):
                    live = np.flatnonzero(row != NULL_BLOCK).tolist()
                    lo = first if layer in cache.window_layers else 0
                    assert live == list(range(lo, lp.blocks))
                    assert not lp.table[layer, lp.blocks:].any()
                tables = np.zeros((4, 1, cache.max_blocks_per_seq),
                                  np.int32)
                cache.fill_tables(tables, 0, seq)
                logits, pool, load = model.decode_flat(
                    params, jnp.asarray(tokens[pos:pos + t], jnp.int32),
                    jnp.arange(pos, pos + t, dtype=jnp.int32),
                    jnp.zeros(t, jnp.int32), jnp.ones(t, jnp.int32), pool,
                    None, jnp.asarray(tables))
                out.append(np.asarray(logits))
                pos += t
    finally:
        model_mod.gqa_window_attention = orig
    cache.free_seq(seq)
    assert cache.allocator.num_free == cache.allocator.num_usable
    return np.concatenate(out)


@pytest.mark.parametrize("use_pallas", [False, True],
                         ids=["reference", "kernel"])
def test_paged_path_against_the_plain_reference(params, use_pallas):
    """40 positions, 5 times the window: prefill chunks of 8 against the
    window's edge, then decoding through the cache, every position's
    logits against the reference's (the kernel in interpret mode or its
    gather twin). The same path with the weights rounded to bfloat16 does
    not pass the tolerance."""
    tokens = np.random.RandomState(0).randint(0, 97, 40)
    model = TinyDecoder(DecoderConfig(**TOY))
    want = np.asarray(ref.forward(params, jnp.asarray(tokens), TOY))
    got = _paged_logits(model, params, tokens, 8, use_pallas)
    assert np.abs(got - want).max() <= LOGIT_TOL
    assert want.std() > 0.3
    if not use_pallas:
        # the block's dense forward, the same masks without a cache
        with jax.default_matmul_precision("highest"):
            dense, _, _ = model.forward(params, jnp.asarray(tokens)[None])
        assert np.abs(np.asarray(dense[0]) - want).max() <= LOGIT_TOL
    rounded = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    got = _paged_logits(model, rounded, tokens, 8)
    assert np.abs(got - want).max() > 50 * LOGIT_TOL


def test_engine_serves_the_references_first_choices(params):
    """Prompts of 5 to 37 tokens through three slots, eight tokens each:
    every served token is the reference's first choice at its position,
    and the pool has every page back at the end."""
    model = TinyDecoder(DecoderConfig(**TOY))
    engine = LLMEngine(model, params, max_seqs=3, block_size=BS,
                       prefill_chunk=4)
    rs = np.random.RandomState(1)
    prompts = [rs.randint(0, 97, n).tolist() for n in (37, 5, 20, 12)]
    seqs = [Sequence(p, 8) for p in prompts]
    for s in seqs:
        engine.add(s)
    while engine.has_work():
        engine.step()
        engine.cache.check([engine.cache.seq_pages(s)
                            for s in engine.scheduler.running()])
    for s, p in zip(seqs, prompts):
        assert len(s.generated) == 8
        gaps = ref.served_token_gaps(params, p, s.generated, TOY, 128)
        assert float(gaps.max()) <= LOGIT_TOL
    stats = engine.cache.stats()
    assert stats["window_pages_released"] > 0
    assert stats["pages_held"] == {"window": 0, "full": 0}
    assert engine.cache.allocator.num_free \
        == engine.cache.allocator.num_usable


def test_server_step_in_flight_answers_as_the_synchronous_engine(params):
    """``LLMServer`` (one step in flight) serves what the synchronous
    engine serves, and its counters say what the window layers gave
    back."""
    from mxnet_tpu.observability import get_registry
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 97, n).tolist() for n in (30, 9, 17)]
    model = TinyDecoder(DecoderConfig(**TOY))
    engine = LLMEngine(model, params, max_seqs=2, block_size=BS,
                       prefill_chunk=4)
    seqs = [Sequence(p, 6) for p in prompts]
    for s in seqs:
        engine.add(s)
    while engine.has_work():
        engine.step()
    server = LLMServer(model, params, name="window_moe_toy", max_seqs=2,
                       block_size=BS, prefill_chunk=4)
    server.start()
    try:
        got = [server.submit(p, 6).result(timeout=300).tokens
               for p in prompts]
        stats = server.stats()
    finally:
        server.shutdown()
    assert [list(g) for g in got] == [s.generated for s in seqs]
    assert stats["kv_window_pages_released"] > 0
    assert stats["kv_cache"]["window"] == 8
    released = get_registry().get(
        "mxtpu_llm_kv_window_pages_released_total")
    assert sum(c.value for c in released.children()) > 0


def test_block_refuses_what_it_does_not_take(params):
    model = TinyDecoder(DecoderConfig(**TOY))
    for kw in ({"prefix_cache": True}, {"kv_dtype": "int8"}):
        with pytest.raises(UnsupportedModelFeatureError):
            LLMEngine(model, params, max_seqs=2, block_size=BS, **kw)
    with pytest.raises(UnsupportedModelFeatureError):
        LLMEngine(model, params, max_seqs=2, block_size=BS,
                  draft_model=model, draft_params=params)
    with pytest.raises(ValueError):
        DecoderConfig(**dict(TOY, sliding_window=0))
    with pytest.raises(ValueError):
        DecoderConfig(**dict(TOY, layer_types=["attention"] * 4))
    with pytest.raises(ValueError):
        DecoderConfig(**dict(TOY, first_routed_expert=13))
    config = DecoderConfig(**TOY)
    assert DecoderConfig.from_dict(config.to_dict()).to_dict() \
        == config.to_dict() == dict(TOY)
    assert model.expert_slots == 4 * 4
    assert model.cache_layout == {
        "kind": "kv_packed", "num_heads": 2, "head_dim": 16,
        "window_layers": [0, 1, 2], "window": 8}


# ------------------------------------------------- the windowed kernel --
def _kernel_case(seed, G=2, group=3, D=8, S=3, MB=12, L=2):
    rs = np.random.RandomState(seed)
    pages = jnp.asarray(rs.randn(L, 40, BS, 2 * G * D).astype(np.float32))
    tables = np.zeros((S, MB), np.int32)
    perm = rs.permutation(np.arange(1, 40))
    for s in range(S):
        tables[s, :10] = perm[s * 10:(s + 1) * 10]
    return pages, tables, rs


@pytest.mark.parametrize("window", [None, 1, 3, 4, 5, 8])
@pytest.mark.parametrize("layer", [0, 1])
def test_window_kernel_against_its_gather_reference(window, layer):
    """Runs across page and query-block edges (a chunk of 6 at positions
    18 to 23, decode tokens, one sequence met twice in a block), released
    pages behind a window (null entries), and a padded tail at position 0
    of the last run: every valid row as the gather reference computes it,
    every padded row zeros."""
    pages, tables, rs = _kernel_case(window or 0)
    if window is not None:
        tables[0, :(18 - window + 1) // BS] = NULL_BLOCK
    seq_ids = np.array([0] * 6 + [1, 2, 2, 1, 0, 2, 2, 2], np.int32)
    positions = np.array(list(range(18, 24)) + [5, 39, 38, 7, 24, 11,
                                                0, 0], np.int32)
    valid = np.array([1] * 12 + [0, 0], np.int32)
    q = jnp.asarray(rs.randn(len(seq_ids), 6, 8).astype(np.float32))
    want = gqa_window_attention_reference(q, pages, tables, seq_ids,
                                          positions, 0.3, 2, window, valid,
                                          layer=layer)
    got = gqa_window_attention(q, pages, tables, seq_ids, positions, 0.3, 2,
                               window=window, valid=valid, layer=layer,
                               use_pallas=True, interpret=True)
    np.testing.assert_allclose(np.asarray(got)[:12], np.asarray(want)[:12],
                               atol=2e-6)
    assert not np.asarray(got)[12:].any()
    assert not np.asarray(want)[12:].any()


def test_window_edges_are_p_minus_w_out_and_p_minus_w_plus_1_in():
    """One token at position 20 under a window of 8 against keys that all
    score alike but two: the key at 12 (``p - W``) and the key at 13 (``p
    - W + 1``) score far above the rest. The key at 13 is seen and takes
    the softmax; the one at 12 is not."""
    G, D = 1, 8
    pages = np.zeros((1, 10, BS, 2 * G * D), np.float32)
    tables = np.arange(1, 7, dtype=np.int32)[None]           # 24 keys
    for pos, value in ((12, 1.0), (13, 2.0)):
        page, slot = tables[0, pos // BS], pos % BS
        pages[0, page, slot, D:] = 10.0                       # its key
        pages[0, page, slot, :D] = value                      # its value
    q = jnp.ones((1, 1, D), jnp.float32)
    for use_pallas in (False, True):
        out = gqa_window_attention(
            q, jnp.asarray(pages), tables, np.zeros(1, np.int32),
            np.array([20], np.int32), 1.0, G, window=8, layer=0,
            use_pallas=use_pallas, interpret=True)
        np.testing.assert_allclose(np.asarray(out), 2.0, atol=1e-6)
        full = gqa_window_attention(
            q, jnp.asarray(pages), tables, np.zeros(1, np.int32),
            np.array([20], np.int32), 1.0, G, window=None, layer=0,
            use_pallas=use_pallas, interpret=True)
        np.testing.assert_allclose(np.asarray(full), 1.5, atol=1e-6)


# ------------------------------------------------ the cache by kind --
def test_cache_fuzz_keeps_pages_by_kind_and_never_leaks():
    """2,000 random operations over a pool too small for every sequence
    at once: admissions, steps that write a chunk or a token (window
    pages behind the step's first position given back first), a step in
    flight held back (its window kept), preemptions and finishes. After
    every one: the allocator's accounting, every owned page owned once,
    each window layer holding exactly its window's blocks and each full
    layer all of them, the counts by kind, and the all-full layout's;
    at the end every page is back."""
    rs = np.random.RandomState(11)
    W, step = 8, 6
    model = TinyDecoder(DecoderConfig(**TOY))
    cache = PagedKVCache.for_model(model, BS, 120, 64, step_tokens=step)
    seqs, live = [], []
    for _ in range(2000):
        op = rs.randint(4)
        if op == 0 and len(live) < 6:
            seq = Sequence([1], 1)
            cache.start(seq)
            seq.seq_len = 0
            live.append(seq)
            seqs.append(seq)
        elif op == 1 and live:
            seq = live[rs.randint(len(live))]
            n = rs.randint(1, step + 1)
            if seq.seq_len + n > 64:
                continue
            # the step in flight may hold this sequence a token back
            lowest = max(0, seq.seq_len - rs.randint(2))
            cache.release_behind(seq, lowest)
            need = cache.pages_to_grow(seq, seq.seq_len + n, lowest)
            try:
                cache.grow(seq, seq.seq_len + n, lowest)
            except NoFreeBlocksError:
                assert need > cache.allocator.num_free
                continue
            seq.seq_len += n
            first = max(0, lowest - W + 1) // BS
            lp = seq.layer_blocks
            for layer, row in enumerate(lp.table[:, :lp.blocks]):
                held = np.flatnonzero(row != NULL_BLOCK).tolist()
                lo = first if layer in cache.window_layers else 0
                assert held == list(range(min(lo, lp.blocks), lp.blocks))
        elif op in (2, 3) and live:
            # a preemption and a finish give every page back alike
            seq = live.pop(rs.randint(len(live)))
            cache.free_seq(seq)
            assert seq.block_ids == [] and seq.layer_blocks is None
        cache.check([cache.seq_pages(s) for s in live])
        by_kind = {"window": 0, "full": 0}
        for s in live:
            table = s.layer_blocks.table
            for layer, row in enumerate(table):
                kind = "window" if layer in cache.window_layers else "full"
                by_kind[kind] += int((row != NULL_BLOCK).sum())
            assert s.block_ids == []
        stats = cache.window_stats()
        assert stats["pages_held"] == by_kind
        assert stats["pages_full_layout"] == 4 * sum(
            s.layer_blocks.blocks for s in live)
        assert cache.allocator.num_used == sum(by_kind.values())
    assert stats["window_pages_released"] > 0
    for s in live:
        cache.free_seq(s)
    assert cache.allocator.num_free == cache.allocator.num_usable
    with pytest.raises(BlockAccountingError):
        cache.allocator.free([1])


def test_engine_preempts_under_a_small_pool_and_still_answers(params):
    """A pool of 43 pages for three slots (one full-context sequence's
    worth): admissions count pages by kind
    and the engine preempts the newest when a step's pages do not fit;
    every answer is still the reference's."""
    model = TinyDecoder(DecoderConfig(**TOY))
    engine = LLMEngine(model, params, max_seqs=3, block_size=BS,
                       prefill_chunk=4, num_blocks=44)
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 97, n).tolist() for n in (30, 28, 26)]
    seqs = [Sequence(p, 10) for p in prompts]
    for s in seqs:
        engine.add(s)
    preempted = 0
    while engine.has_work():
        preempted += sum(1 for kind, _ in engine.step()
                         if kind == "preempted")
    assert preempted > 0
    for s, p in zip(seqs, prompts):
        assert len(s.output_tokens()) == 10
        gaps = ref.served_token_gaps(params, p, s.output_tokens(), TOY,
                                     128)
        assert float(gaps.max()) <= LOGIT_TOL
    assert engine.cache.allocator.num_free \
        == engine.cache.allocator.num_usable
    # a pool that cannot hold one full-context sequence is refused
    with pytest.raises(ValueError):
        LLMEngine(model, params, max_seqs=3, block_size=BS, num_blocks=30)


# --------------------------------------------------- expert shares --
def test_expert_shares_add_up_to_the_uncut_layer():
    """A toy layer of 16 routed experts cut into the 8 chips' shares of 2:
    what each share adds beyond the attention and the shared experts
    (counted once) adds up, over the 8, to what the uncut layer adds."""
    uncut = dict(TOY, n_routed_experts=16, first_routed_expert=0)
    params = ref.init_params(9, uncut)
    lp = params["layers"][0]
    h = jax.random.normal(jax.random.PRNGKey(1), (24, 64), jnp.float32)
    shape = ref._frozen(uncut)

    def layer(model, p):
        return ref._layer_jit(p, h, ref._frozen(model), True, None)
    whole = layer(uncut, lp)
    # attention and the shared experts alone: a share whose experts add
    # nothing
    base = layer(dict(uncut, n_routed_experts=2), dict(
        lp, **{k: jnp.zeros_like(lp[k][:2]) for k in
               ("experts_gate", "experts_up", "experts_down")}))
    total = 0
    for first in range(0, 16, 2):
        share = dict(uncut, n_routed_experts=2, first_routed_expert=first)
        part = dict(lp, **{k: lp[k][first:first + 2] for k in
                           ("experts_gate", "experts_up", "experts_down")})
        total = total + (layer(share, part) - base)
    assert ref._frozen(uncut) == shape
    np.testing.assert_allclose(np.asarray(base + total), np.asarray(whole),
                               atol=2e-5)
    # the program's layer splits the pairs alike: held here plus held
    # elsewhere is every pair, on every share
    model = TinyDecoder(DecoderConfig(**dict(TOY, first_routed_expert=6,
                                             n_routed_experts=2)))
    part = dict(lp, **{k: lp[k][6:8] for k in
                       ("experts_gate", "experts_up", "experts_down")})
    _, load = model._experts_window(part, h, jnp.ones(24, bool))
    assert int(load[0]) + int(load[4]) == 24 * TOY["num_experts_per_tok"]


# ---------------------------------------------- the other blocks' steps --
def _normalised(text):
    """An exported module's text without source locations (and what is
    left of their references, numbered by how many the process has made)
    and without the Mosaic kernels' serialized bodies (which carry file
    names)."""
    text = re.sub(r'loc\(.*?\)', '', text)
    text = "\n".join(line for line in text.splitlines()
                     if not line.startswith("#loc"))
    text = re.sub(r'#loc[0-9]*', '', text)
    return re.sub(r'\\22body\\22: \\22[A-Za-z0-9+/=]*\\22', 'BODY', text)


YARN = {"factor": 8, "original_max_position_embeddings": 16,
        "beta_fast": 32, "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
        "type": "yarn"}
#: a toy of each of the five other blocks
OTHER_BLOCKS = {
    "gpt2": dict(vocab_size=97, d_model=256, num_layers=2, num_heads=2,
                 d_ff=96, max_context=64),
    "deepseek_v3": dict(
        vocab_size=97, d_model=64, num_layers=3, num_heads=4, d_ff=96,
        max_context=64, model_type="deepseek_v3", kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=8, v_head_dim=8,
        rope_theta=50000.0, rms_norm_eps=1e-5, first_k_dense_replace=1,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=2,
        moe_intermediate_size=32, routed_scaling_factor=2.446,
        norm_topk_prob=True),
    "granitemoehybrid": dict(
        vocab_size=97, d_model=64, num_layers=8, num_heads=4, d_ff=96,
        max_context=128, model_type="granitemoehybrid",
        layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
        num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
        mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1, mamba_expand=2,
        embedding_multiplier=12.0, attention_multiplier=1.0 / 16,
        residual_multiplier=0.22, logits_scaling=8.0,
        tie_word_embeddings=True, rms_norm_eps=1e-5, torch_dtype="float32"),
    "sdar_moe": dict(
        vocab_size=509, d_model=64, num_layers=2, num_heads=4, d_ff=96,
        max_context=64, model_type="sdar_moe", num_key_value_heads=2,
        head_dim=16, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=32, mask_token_id=508),
    "gigachat3_5": dict(
        vocab_size=97, d_model=64, num_layers=5, num_heads=4, d_ff=96,
        max_context=128, model_type="gigachat3_5", full_attention_layers=[4],
        q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=8, v_head_dim=8, rope_theta=10000.0,
        rope_scaling=YARN, rms_norm_eps=1e-6, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=16,
        linear_value_head_dim=16, linear_conv_kernel_dim=4,
        linear_sigmoid_gate_scale=2.0, layernorm_gating_weight=2.0,
        first_k_dense_replace=1, router_experts=16, first_routed_expert=4,
        n_routed_experts=4, num_experts_per_tok=4, n_shared_experts=1,
        moe_intermediate_size=32, routed_scaling_factor=2.5,
        norm_topk_prob=True, swiglu_limit=10.0, torch_dtype="float32"),
}


@pytest.mark.parametrize("block,digest", [
    ("gpt2", "2df47c3af83bd511"), ("deepseek_v3", "03861a61cbd670b0"),
    ("granitemoehybrid", "a8d1703ecf5005ac"), ("sdar_moe", "3a0bfe244efbeceb"),
    ("gigachat3_5", "e7e68ac743071054")])
def test_other_blocks_keep_their_step_programs(monkeypatch, block, digest):
    """A model without window layers runs exactly as before: each other
    block's toy engine step programs, every packed length, table width and
    variant, exported for the TPU with every Mosaic kernel, have the
    digest that the same export gave before a cache held two kinds of
    layer (locations and the kernels' serialized bodies left out)."""
    for mod in (moe, ragged_attention, ssd, gdn):
        monkeypatch.setattr(mod, "_on_tpu", lambda: True)
    m = TinyDecoder(DecoderConfig(**OTHER_BLOCKS[block]))
    engine = LLMEngine(m, m.init_params(0), max_seqs=2, block_size=16,
                       prefill_chunk=16)
    assert not engine.cache.windowed
    digests = []
    for T in engine._t_buckets:
        for MB in engine._mb_widths:
            for sampled in engine._variants:
                args = (engine._params, *engine.cache.pools(),
                        *engine._null_batch(T, MB))
                exported = jax.export.export(engine._step_jits[sampled],
                                             platforms=["tpu"])(*args)
                digests.append(hashlib.sha256(_normalised(
                    exported.mlir_module()).encode()).hexdigest()[:16])
    assert hashlib.sha256(" ".join(digests).encode()).hexdigest()[:16] \
        == digest
