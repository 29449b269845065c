"""The ``sdar_moe`` block of ``TinyDecoder`` (grouped-query attention with
q/k norms under a block-causal mask, a softmax top-k router over SwiGLU
experts) and the engine's THIRD kind of row, which generates by diffusion
over blocks, on the CPU at a toy size: two layers, blocks of 4 revealed in
4 passes.

- the block's dense ``forward`` and its router against the benchmark's
  plain reference;
- through ``LLMServer``: every ``P % 4`` and an ``n_out`` that is no
  multiple of 4 under each of the three strategies, the answer's length
  exact and every served token the reference's first choice at the pass
  that revealed it (the reference follows the served reveal passes);
- chunked against unchunked prefill, a prompt that holds the mask token's
  id, rows in different passes beside a prefill chunk in one step,
  preemption inside a block, a stop token inside a block, the context's
  end;
- the counters and span attributes, and what the block refuses, by name.

Tolerances: float32 against float32 everywhere, so round-off only: a
served token's logit lies at most ``GAP_TOL`` below the reference's best
(logits spread by about 1). The same test in bfloat16 reads a hundred
times that (``test_bfloat16_would_fail_the_tolerance``).
"""
import importlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.moe import route_softmax_topk  # noqa: E402
from mxnet_tpu.serving.llm import LLMEngine, LLMServer, Sequence  # noqa: E402
from mxnet_tpu.serving.llm.model import (  # noqa: E402
    DecoderConfig, TinyDecoder, UnsupportedModelFeatureError, REMASKING,
    greedy_decode_reference)
from mxnet_tpu.serving.llm.sampling import SamplingParams  # noqa: E402

ref = importlib.import_module("benchmark.reference.sdar_moe_decoder")

B = 4
TOY = dict(vocab_size=509, d_model=64, num_layers=2, num_heads=4, d_ff=96,
           max_context=128, model_type="sdar_moe", num_key_value_heads=2,
           head_dim=16, rope_theta=1e6, rms_norm_eps=1e-6, num_experts=8,
           num_experts_per_tok=2, moe_intermediate_size=32,
           norm_topk_prob=True, torch_dtype="float32", block_length=B,
           mask_token_id=508, denoising_steps=4,
           remasking_strategy="sequential", confidence_threshold=0.9)
#: float32 round-off of logits that spread by about 1
GAP_TOL = 1e-4


def _toy(**changed):
    return dict(TOY, **changed)


@pytest.fixture(scope="module")
def params():
    return ref.init_params(11, TOY, dtype=jnp.float32)


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(**TOY))


def _prompt(rs, n):
    # never the mask token's id, unless a test puts it there
    return [int(t) for t in rs.randint(0, TOY["mask_token_id"], n)]


def _drain(engine, seqs, stagger=0):
    """Add ``seqs`` (one every ``stagger`` steps, all at once at 0) and
    step the engine until it has no work."""
    waiting = list(seqs)
    steps = 0
    while waiting or engine.has_work():
        if waiting and (stagger == 0 or steps % stagger == 0):
            for seq in (waiting if stagger == 0 else waiting[:1]):
                engine.add(seq)
            waiting = [] if stagger == 0 else waiting[1:]
        engine.step()
        steps += 1
        assert steps < 5000
    return steps


def _gaps(params, shape, seq, prompt):
    """The widest gap of a finished sequence's tokens below the
    reference's first choice, the reference following its reveal passes
    and what its last block computed past the answer."""
    return float(ref.token_gaps(params, prompt, seq.output_tokens(), shape,
                                seq.reveal_pass, seq.block_tail).max())


# ------------------------------------------------------------ the block --

def test_config_round_trips_and_validates():
    config = DecoderConfig(**TOY)
    assert DecoderConfig.from_dict(config.to_dict()).to_dict() \
        == config.to_dict()
    assert set(config.to_dict()) == set(TOY)
    assert "block_length=4" in repr(config)
    for bad in ({"denoising_steps": 5}, {"denoising_steps": 0},
                {"remasking_strategy": "random"}, {"mask_token_id": 509},
                {"num_key_value_heads": 3}, {"head_dim": 15},
                {"num_experts_per_tok": 9}, {"max_context": 126}):
        with pytest.raises(ValueError):
            DecoderConfig(**_toy(**bad))
    assert REMASKING == ("sequential", "low_confidence_static",
                         "low_confidence_dynamic")


def test_init_params_has_the_references_leaves_and_scale(model, params):
    mine = model.init_params(3)
    assert set(mine) == set(params)
    for lp, lq in zip(mine["layers"], params["layers"]):
        assert set(lp) == set(lq)
        for k in lp:
            assert lp[k].shape == lq[k].shape, k
            if lp[k].ndim > 1:      # a matrix: the same scale
                np.testing.assert_allclose(np.std(lp[k]), np.std(lq[k]),
                                           rtol=0.15, err_msg=k)
            else:
                np.testing.assert_allclose(lp[k], lq[k], err_msg=k)
    np.testing.assert_allclose(np.std(mine["embed"]), ref.EMBED_SCALE,
                               rtol=0.05)
    assert model.cache_layout == {"kind": "kv_packed", "num_heads": 2,
                                  "head_dim": 16}
    assert model.expert_slots == 16
    assert model.block_diffusion["block_length"] == B
    assert TinyDecoder(DecoderConfig()).block_diffusion is None


def test_forward_matches_the_plain_reference(model, params):
    rs = np.random.RandomState(0)
    tokens = np.asarray(_prompt(rs, 27), np.int32)
    with jax.default_matmul_precision("highest"):
        logits, k, v = model.forward(params, jnp.asarray(tokens[None]))
    want = ref.forward(params, tokens, TOY)
    assert k is None and v is None and logits.shape == (1, 27, 509)
    np.testing.assert_allclose(logits[0], want, atol=2e-5)
    # block-causal, not causal: a change INSIDE a block reaches the
    # block's earlier positions, one in a later block does not
    changed = tokens.copy()
    changed[7] = (changed[7] + 1) % 500
    with jax.default_matmul_precision("highest"):
        other = model.forward(params, jnp.asarray(changed[None]))[0][0]
    moved = np.abs(np.asarray(other - logits[0])).max(axis=-1) > 1e-6
    assert moved[4:8].all() and not moved[:4].any() and moved[8:].all()


@pytest.mark.parametrize("norm_topk_prob", [True, False])
def test_softmax_router_matches_the_reference(params, norm_topk_prob):
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(33, 64), jnp.float32)
    router = params["layers"][0]["router"]
    sel, gates = route_softmax_topk(x, router, 2, norm_topk_prob)
    want_sel, want = ref.route(
        x, router, _toy(norm_topk_prob=norm_topk_prob),
        lambda a, b: jnp.matmul(a, b, precision="highest"))
    np.testing.assert_array_equal(sel, want_sel)
    np.testing.assert_allclose(gates, want, rtol=1e-5)
    sums = np.asarray(gates.sum(axis=1))
    if norm_topk_prob:
        np.testing.assert_allclose(sums, 1.0, rtol=1e-6)
    else:
        assert (sums < 1.0).all() and (sums > 2 / 8).all() \
            and sums.mean() < 0.95
    assert sel.dtype == jnp.int32 and gates.dtype == jnp.float32


# ------------------------------------------------- through the server --

@pytest.fixture(scope="module", params=REMASKING)
def served(request, params):
    """One server a strategy; the dynamic one at a threshold that some
    positions pass and some do not."""
    shape = _toy(remasking_strategy=request.param,
                 confidence_threshold=0.02)
    server = LLMServer(TinyDecoder(DecoderConfig(**shape)), params,
                       max_seqs=3, block_size=8, prefill_chunk=6,
                       name=f"blockdiff-{request.param}")
    server.warmup()
    server.start()
    yield server, shape
    server.shutdown()


@pytest.mark.parametrize("n_prompt,n_out", [(12, 7), (13, 6), (14, 9),
                                            (15, 5), (2, 3), (31, 14)])
def test_server_answers_as_the_reference_reveals(served, params, n_prompt,
                                                 n_out):
    """``P % 4`` of 0, 1, 2 and 3 (and a prompt shorter than a block),
    ``n_out % 4`` non-zero: exactly ``n_out`` tokens, each the
    reference's first choice in the pass that revealed it."""
    server, shape = served
    rs = np.random.RandomState(100 * n_prompt + n_out)
    prompt = _prompt(rs, n_prompt)
    res = server.submit(prompt, n_out).result(timeout=300)
    assert len(res.tokens) == n_out == len(res.reveal_pass)
    assert res.finish_reason == "max_new_tokens"
    # what the last block computed past the answer
    assert len(res.block_tail) == -(n_prompt + n_out) % B
    assert all(0 <= p < 4 for p in res.reveal_pass)
    if shape["remasking_strategy"] == "sequential":
        passes = ref.sequential_passes(n_prompt, n_out, shape)
        assert res.reveal_pass == passes[:n_out]
        assert [p for _, p in res.block_tail] == passes[n_out:]
    gaps = ref.token_gaps(params, prompt, res.tokens, shape,
                          res.reveal_pass, res.block_tail)
    assert float(gaps.max()) <= GAP_TOL


def test_strategies_differ_in_passes_not_in_rows(served):
    server, shape = served
    stats = server.stats()
    passes = stats["blockdiff_passes"]
    assert passes["settle"] == stats["blockdiff_blocks"] > 0
    assert stats["tokens_per_pass"] == pytest.approx(
        stats["blockdiff_revealed"] / (passes["denoise"] + passes["settle"]))
    if shape["remasking_strategy"] == "low_confidence_dynamic":
        # some passes revealed more than their one position
        assert stats["blockdiff_revealed"] > passes["denoise"]
    else:
        assert stats["blockdiff_revealed"] == passes["denoise"]
        assert stats["tokens_per_pass"] <= 0.8
    assert stats["prefix_cache"] is False and stats["prefix_hits"] == 0


def test_driver_reference_refuses_what_tokens_alone_do_not_fix(params):
    with pytest.raises(ValueError, match="sequential"):
        ref.served_token_gaps(
            params, [1, 2, 3], [4, 5],
            _toy(remasking_strategy="low_confidence_static"), 128)


def test_bfloat16_would_fail_the_tolerance():
    """The float32 test's tolerance has teeth: the same model in
    bfloat16 (weights, pages, activations into every product) reads far
    more against the float32 reference over the same weights."""
    shape = _toy(torch_dtype="bfloat16")
    half = ref.init_params(11, shape)
    engine = LLMEngine(TinyDecoder(DecoderConfig(**shape)), half,
                       max_seqs=3, block_size=8, dtype="bfloat16")
    rs = np.random.RandomState(5)
    prompts = [_prompt(rs, 21), _prompt(rs, 18), _prompt(rs, 30)]
    seqs = [Sequence(p, 90) for p in prompts]
    _drain(engine, seqs)
    worst = max(_gaps(half, shape, s, p) for s, p in zip(seqs, prompts))
    assert worst > 30 * GAP_TOL


def test_chunked_prefill_at_a_budget_of_no_whole_blocks_equals_unchunked(
        model, params):
    """One slot, a budget of 6: chunks of 4 (the whole blocks the budget
    holds), against the prompt in one chunk."""
    rs = np.random.RandomState(2)
    prompt = _prompt(rs, 27)
    got = []
    for chunk in (6, 64):
        engine = LLMEngine(model, params, max_seqs=1, block_size=8,
                           prefill_chunk=chunk)
        seq = Sequence(prompt, 10)
        fed = []
        inner = engine._dispatch
        engine._dispatch = lambda rows, plans, fed=fed, inner=inner: (
            fed.append([(plans[s]["kind"], plans[s]["ntok"]) for s in rows]),
            inner(rows, plans))[1]
        _drain(engine, [seq])
        chunks = [n for step in fed for kind, n in step
                  if kind == "prefill"]
        assert sum(chunks) == 24 and all(n % B == 0 for n in chunks)
        assert chunks == ([4] * 6 if chunk == 6 else [24])
        got.append((seq.output_tokens(), seq.reveal_pass))
        assert _gaps(params, TOY, seq, prompt) <= GAP_TOL
    assert got[0] == got[1] and len(got[0][0]) == 10


def test_a_prompt_token_that_equals_the_mask_id_is_a_prompt_token(model,
                                                                  params):
    """The mask token's id in the prompt's whole blocks AND among the
    tokens that sit in the first generated block: those positions are
    never revealed over, and the reference, which is told they are
    prompt, agrees."""
    mask = TOY["mask_token_id"]
    rs = np.random.RandomState(3)
    prompt = _prompt(rs, 14)
    prompt[5] = prompt[12] = prompt[13] = mask
    engine = LLMEngine(model, params, max_seqs=1, block_size=8)
    seq = Sequence(prompt, 9)
    _drain(engine, [seq])
    assert len(seq.output_tokens()) == 9
    # the first block had two positions to reveal, in passes 0 and 1
    assert seq.reveal_pass[:2] == [0, 1]
    assert _gaps(params, TOY, seq, prompt) <= GAP_TOL
    # had the engine read "masked" off the tokens, the block would have
    # had four positions to reveal and the answer another length of passes
    other = Sequence([t if t != mask else 7 for t in prompt], 9)
    _drain(engine, [other])
    assert other.reveal_pass == seq.reveal_pass


def test_rows_in_different_passes_beside_a_chunk_equal_the_rows_alone(
        model, params):
    rs = np.random.RandomState(4)
    prompts = [_prompt(rs, n) for n in (9, 14, 23, 30)]
    alone = []
    for prompt in prompts:
        engine = LLMEngine(model, params, max_seqs=1, block_size=8)
        seq = Sequence(prompt, 11)
        _drain(engine, [seq])
        alone.append((seq.output_tokens(), seq.reveal_pass, seq.block_tail))
    engine = LLMEngine(model, params, max_seqs=4, block_size=8,
                       prefill_chunk=4)
    held = []
    inner = engine._dispatch
    engine._dispatch = lambda rows, plans: (
        held.append([(plans[s]["kind"], s.blk_pass) for s in rows]),
        inner(rows, plans))[1]
    seqs = [Sequence(p, 11) for p in prompts]
    _drain(engine, seqs, stagger=3)
    assert [(s.output_tokens(), s.reveal_pass, s.block_tail)
            for s in seqs] == alone
    # some step held a prompt's chunk beside rows in two different passes
    # of their blocks, and some a settling row beside a denoising one
    assert any(
        "prefill" in [k for k, _ in step]
        and len({p for k, p in step if k == "denoise"}) >= 2
        for step in held)
    assert any({"settle", "denoise"} <= {k for k, _ in step}
               for step in held)


def test_preemption_inside_a_block_leaves_the_answer_unchanged(model,
                                                               params):
    """A pool too small for both contexts, pages of one block each: the
    younger sequence is preempted with a block open, its settled blocks
    folded into its prompt, the open block started again."""
    rs = np.random.RandomState(6)
    prompts = [_prompt(rs, 26), _prompt(rs, 21)]
    want = []
    for prompt in prompts:
        engine = LLMEngine(model, params, max_seqs=1, block_size=4)
        seq = Sequence(prompt, 60)
        _drain(engine, [seq])
        want.append((seq.output_tokens(), seq.reveal_pass))
    engine = LLMEngine(model, params, max_seqs=2, block_size=4,
                       num_blocks=33, max_context=128)
    open_blocks = []
    inner = engine._preempt
    engine._preempt = lambda seq: (
        open_blocks.append((seq.blk_pass, sum(seq.blk_masked or ()))),
        inner(seq))[1]
    seqs = [Sequence(p, 60) for p in prompts]
    _drain(engine, seqs, stagger=2)
    assert sum(s.preemptions for s in seqs) >= 1
    # a victim had had passes of its open block, positions still masked
    assert any(done and masked for done, masked in open_blocks)
    assert [(s.output_tokens(), s.reveal_pass) for s in seqs] == want
    for seq, prompt in zip(seqs, prompts):
        assert len(seq.output_tokens()) == 60
        assert seq.blk_tokens is None
        assert float(ref.token_gaps(
            params, prompt, seq.output_tokens(), TOY,
            seq.reveal_pass).max()) <= GAP_TOL
    assert engine.cache.allocator.num_used == 0


def test_a_stop_token_inside_a_block_ends_the_answer_there(model, params):
    rs = np.random.RandomState(7)
    prompt = _prompt(rs, 10)
    engine = LLMEngine(model, params, max_seqs=1, block_size=8)
    free = Sequence(prompt, 12)
    _drain(engine, [free])
    out = free.output_tokens()
    at = next(i for i in range(2, 12) if out[i] not in out[:i])
    seq = Sequence(prompt, 12, stop_token=out[at])
    _drain(engine, [seq])
    assert seq.output_tokens() == out[:at + 1]
    assert seq.finish_reason == "stop_token"
    assert len(seq.block_tail) == -(10 + at + 1) % B


def test_the_context_ends_on_a_block_and_the_answer_is_cut(model, params):
    rs = np.random.RandomState(8)
    prompt = _prompt(rs, 117)
    engine = LLMEngine(model, params, max_seqs=1, block_size=8)
    seq = Sequence(prompt, 40)
    _drain(engine, [seq])
    assert seq.seq_len == 128 and len(seq.output_tokens()) == 11
    assert seq.finish_reason == "length"
    assert _gaps(params, TOY, seq, prompt) <= GAP_TOL


# ------------------------------------------------- counters and spans --

def test_counters_and_step_span_attributes(model, params):
    from mxnet_tpu.observability import get_registry
    from mxnet_tpu.observability.tracing import get_tracer
    server = LLMServer(model, params, max_seqs=2, block_size=8,
                       name="blockdiff-counters")
    server.warmup()
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        server.start()
        rs = np.random.RandomState(9)
        futs = [server.submit(_prompt(rs, n), 8) for n in (9, 16)]
        for f in futs:
            assert len(f.result(timeout=300).tokens) == 8
        spans = [s["attrs"] for s in tracer.snapshot()
                 if s["name"] == "mxtpu.llm.step" and "rows" in s["attrs"]]
    finally:
        if not was_on:
            tracer.disable()
        tracer.clear()
    stats = server.stats()
    server.shutdown()
    # prompts of 9 and 16, 8 tokens each: blocks [8..12), [12..16),
    # [16..20) reveal 3, 4 and 4 positions for the first (3 of the last
    # past the answer); [16..20), [20..24) 4 and 4 for the second: 5
    # blocks, 19 positions in as many passes
    assert stats["blockdiff_blocks"] == 5
    assert stats["blockdiff_passes"] == {"denoise": 19, "settle": 5}
    assert stats["blockdiff_revealed"] == 19
    assert stats["tokens_per_pass"] == pytest.approx(19 / 24)
    assert stats["tokens_generated"] == 16
    assert stats["requests_completed"] == 2
    # a step that held a generating row is a decode step
    generating = [a for a in spans if a["denoise_rows"] + a["settle_rows"]]
    assert stats["decode_steps"] == len(generating)
    assert sum(a["denoise_rows"] for a in spans) == 19
    assert sum(a["settle_rows"] for a in spans) == 5
    assert sum(a["revealed"] for a in spans) == 19
    for a in generating:
        assert a["moe_pairs"] == 2 * 2 * sum(n for n, _ in a["rows"])
        assert 1 <= a["moe_experts_hit"] <= 16
        # a few rows of a block of 4: no expert fills a second tile
        assert a["moe_tiles"] == a["moe_experts_hit"]
        assert all(n % B == 0 for n, _ in a["rows"])
    assert stats["moe_steps"] == len(spans)
    reg = get_registry()
    label = {"server": server._stats.server_label}
    assert stats["moe_tiles"] == sum(a["moe_tiles"] for a in spans) \
        == reg.get("mxtpu_llm_moe_tiles_total").labels(**label).value
    assert reg.get("mxtpu_llm_blockdiff_revealed_total").labels(
        **label).value == 19
    assert reg.get("mxtpu_llm_blockdiff_blocks_total").labels(
        **label).value == 5
    by_kind = {kind: reg.get("mxtpu_llm_blockdiff_passes_total").labels(
        kind=kind, **label).value for kind in ("denoise", "settle")}
    assert by_kind == {"denoise": 19, "settle": 5}
    # another model's server has no pass to count
    plain = LLMServer(TinyDecoder(DecoderConfig()),
                      TinyDecoder(DecoderConfig()).init_params(0),
                      name="blockdiff-none")
    assert plain.stats()["tokens_per_pass"] == 0.0
    assert plain.stats()["blockdiff_passes"] == {}
    plain.shutdown()


def test_ladder_and_warmup_follow_the_block(model, params):
    engine = LLMEngine(model, params, max_seqs=4, block_size=8,
                       prefill_chunk=16)
    # four rows of a block; one or two prompts' shares beside the others'
    # blocks; the whole budget
    assert engine._t_buckets == [16, 28, 40, 64]
    assert engine.q_tokens == 16 and engine.spec_k == 0
    timings = engine.warmup()
    # the greedy variant alone: sampling is refused
    assert len(timings) == len(engine._t_buckets) * len(engine._mb_widths)
    assert all(name.endswith("greedy") for name in timings)
    assert engine.debug_status()["programs"]["block_diffusion"][
        "block_length"] == B


# ------------------------------------------------------------ refusals --

def _toy_draft():
    return TinyDecoder(DecoderConfig(vocab_size=509, max_context=128))


@pytest.mark.parametrize("kwargs,named", [
    (lambda: {"adapter_bank": object()}, "adapter_bank"),
    (lambda: {"weight_dtype": "int8"}, "weight_dtype"),
    (lambda: {"kv_dtype": "int8"}, "kv_dtype"),
    (lambda: {"kv_dtype": "fp8"}, "kv_dtype"),
    (lambda: {"mesh": "tp=2"}, "mesh"),
    (lambda: {"prefix_cache": True}, "prefix_cache"),
    (lambda: {"draft_model": _toy_draft(),
              "draft_params": _toy_draft().init_params(0)}, "draft_model"),
])
def test_engine_refuses_what_the_block_does_not_take(model, params, kwargs,
                                                     named):
    with pytest.raises(UnsupportedModelFeatureError, match=named):
        LLMEngine(model, params, block_size=4, **kwargs())


def test_a_request_with_a_temperature_is_refused_by_name(model, params):
    engine = LLMEngine(model, params, max_seqs=1, block_size=8)
    with pytest.raises(UnsupportedModelFeatureError, match="sampling"):
        engine.add(Sequence([1, 2, 3], 4,
                            sampling=SamplingParams(temperature=0.7)))
    assert not engine.has_work()
    engine.add(Sequence([1, 2, 3], 4, sampling=SamplingParams()))
    assert engine.has_work()


def test_entry_points_the_block_does_not_have_raise(model, params):
    for call in (
            lambda: greedy_decode_reference(model, params, [1, 2], 2),
            lambda: model.param_specs(),
            lambda: model.forward(params, jnp.zeros((1, 4), jnp.int32),
                                  lora=(None, None, 1.0)),
            lambda: model.decode_flat(
                params, *[jnp.zeros((4,), jnp.int32)] * 4,
                jnp.zeros((2, 3, 8, 64)), None, jnp.zeros((1, 1), jnp.int32),
                state=())):
        with pytest.raises(UnsupportedModelFeatureError):
            call()
