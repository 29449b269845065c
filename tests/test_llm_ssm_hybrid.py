"""The ``granitemoehybrid`` block of ``TinyDecoder`` (Mamba-2 state-space
layers over a per-slot state pool, grouped-query attention layers
without positions over packed K/V rows) behind ``LLMEngine``, on the CPU
at a toy size: two periods of ``mamba mamba attention mamba``.

- the block's dense ``forward`` against the benchmark's plain reference
  (logits);
- the engine's packed step against ``forward``: prompts over several
  chunks with the state carried, sequences side by side in one batch, a
  run shorter than the convolution's window, a slot used again,
  preemption and re-admission, padding rows, no prefix hit;
- the state-space kernel in interpret mode against its ``jnp`` twin, and
  the whole step through both kernels in interpret mode against the
  step through their twins;
- what the block refuses, by the argument's name; its counters and spans.

Tolerances: float32 against float32 everywhere, so round-off only; the
logits spread by 0.01 (the tied head's scale: ``init_params``), hence the
small absolute bounds.
"""
import importlib
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax.numpy as jnp  # noqa: E402

from mxnet_tpu.ops.ssd import (  # noqa: E402
    ssd_state_step, ssd_state_step_reference, slot_runs)
from mxnet_tpu.serving.llm import LLMEngine, LLMServer, Sequence  # noqa: E402
from mxnet_tpu.serving.llm import model as model_mod  # noqa: E402
from mxnet_tpu.serving.llm.model import (  # noqa: E402
    DecoderConfig, TinyDecoder, UnsupportedModelFeatureError,
    greedy_decode_reference)

ref = importlib.import_module("benchmark.reference.granite_hybrid_decoder")

TOY = dict(vocab_size=97, d_model=64, num_layers=8, num_heads=4, d_ff=96,
           max_context=128, model_type="granitemoehybrid",
           layer_types=["mamba", "mamba", "attention", "mamba"] * 2,
           num_key_value_heads=2, mamba_n_heads=8, mamba_d_head=16,
           mamba_d_state=16, mamba_d_conv=4, mamba_n_groups=1,
           mamba_expand=2, embedding_multiplier=12.0,
           attention_multiplier=1.0 / 16, residual_multiplier=0.22,
           logits_scaling=8.0, tie_word_embeddings=True,
           rms_norm_eps=1e-5, torch_dtype="float32")
#: float32 round-off of logits that spread by 0.01
LOGIT_TOL = 2e-6


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(**TOY))


@pytest.fixture(scope="module")
def params():
    return ref.init_params(11, TOY, dtype=jnp.float32)


def _prompt(rs, n):
    return [int(t) for t in rs.randint(0, TOY["vocab_size"], n)]


_FORWARD = {}


def _first_choices(model, params, tokens):
    """The dense forward's first choice after each position of
    ``tokens``, one jitted program for every length (the sequence padded
    to the context: what lies behind a position changes nothing before
    it)."""
    import jax
    fwd = _FORWARD.get(id(model))
    if fwd is None:
        fwd = _FORWARD[id(model)] = jax.jit(
            lambda p, t: jnp.argmax(model.forward(p, t)[0][0], axis=-1))
    padded = np.zeros((1, TOY["max_context"]), np.int32)
    padded[0, :len(tokens)] = tokens
    return [int(t) for t in np.asarray(fwd(params, jnp.asarray(padded)))]


def _is_greedy(model, params, prompt, served):
    """Whether ``served`` is what greedy decoding by the dense forward
    gives after ``prompt``: each served token the forward's first choice
    at the position before it."""
    choices = _first_choices(model, params, list(prompt) + list(served))
    return list(served) == choices[len(prompt) - 1:
                                   len(prompt) - 1 + len(served)]


def _drain(engine, seqs, limit=2000):
    for s in seqs:
        engine.add(s)
    for _ in range(limit):
        if not engine.has_work():
            return
        engine.step()
    raise AssertionError("the engine did not drain")


def _worst_gap(params, prompt, served):
    return float(ref.served_token_gaps(params, prompt, served, TOY,
                                       128).max())


# ------------------------------------------------ config and oracle --

def test_config_round_trips_and_validates():
    config = DecoderConfig(**TOY)
    assert DecoderConfig.from_dict(config.to_dict()).to_dict() \
        == config.to_dict() == dict(TOY)
    assert config.head_dim == 16 and config.mamba_inner == 128
    assert config.mamba_conv_dim == 160
    for change, named in (
            ({"layer_types": ["mamba"] * 7}, "layer_types"),
            ({"layer_types": ["mamba"] * 7 + ["conv"]}, "layer_types"),
            ({"num_key_value_heads": 3}, "num_key_value_heads"),
            ({"mamba_n_groups": 2}, "mamba_n_groups"),
            ({"mamba_expand": 3}, "mamba_expand"),
            ({"mamba_d_state": 0}, "mamba_d_state"),
            ({"tie_word_embeddings": False}, "tie_word_embeddings")):
        with pytest.raises(ValueError, match=named):
            DecoderConfig(**dict(TOY, **change))


def test_init_params_has_the_references_leaves_and_scale(model):
    ours, theirs = model.init_params(3), ref.init_params(3, TOY)
    assert set(ours) == set(theirs) == {"embed", "norm_f", "layers"}
    for lp, lq in zip(ours["layers"], theirs["layers"]):
        assert {k: v.shape for k, v in lp.items()} \
            == {k: v.shape for k, v in lq.items()}
        for k in lp:        # one rule of scale: the same spread
            a, b = np.asarray(lp[k], np.float32), np.asarray(lq[k],
                                                             np.float32)
            if a.size > 500:
                assert abs(a.std() / b.std() - 1) < 0.2, k
            else:
                np.testing.assert_allclose(a.mean(), b.mean(), atol=0.1)
    bf16 = TinyDecoder(DecoderConfig(**dict(TOY, torch_dtype="bfloat16")))
    assert {str(a.dtype) for a in
            __import__("jax").tree_util.tree_leaves(bf16.init_params(0))} \
        == {"bfloat16"}


def test_forward_matches_the_plain_reference(model, params):
    rs = np.random.RandomState(0)
    toks = jnp.asarray(_prompt(rs, 70), jnp.int32)
    want = np.asarray(ref.forward(params, toks, TOY))
    got = np.asarray(model.forward(params, toks[None])[0][0])
    assert want.std() > 5e-3                    # not a flat head
    np.testing.assert_allclose(got, want, atol=LOGIT_TOL, rtol=0)
    # bfloat16 weights: the reference upcasts them, the block reads them
    # with float32 accumulation and rounds activations to bfloat16
    # (2**-9 an operand): a hundredth of the logits' spread
    p16 = ref.init_params(11, dict(TOY, torch_dtype="bfloat16"))
    m16 = TinyDecoder(DecoderConfig(**dict(TOY, torch_dtype="bfloat16")))
    want = np.asarray(ref.forward(p16, toks, TOY))
    got = np.asarray(m16.forward(p16, toks[None])[0][0])
    assert np.abs(got - want).max() < 0.1 * want.std()


# ------------------------------------------------- engine vs forward --

def test_engine_serves_what_forward_computes(model, params):
    """Prompts of 37, 21 and 50 tokens (the step's budget of 48 dealt
    in admission order: 37 and 11, then 10, 3 and 2, ...: the state
    carried over chunks), one of 3 (exactly the convolution's window)
    and one of 2 (shorter than it), five requests on three slots, so
    that runs of several sequences lie side by side in one packed batch
    and two slots are used again."""
    rs = np.random.RandomState(1)
    prompts = [_prompt(rs, n) for n in (37, 21, 3, 2, 50)]
    n_new = [6, 9, 5, 7, 4]
    engine = LLMEngine(model, params, max_seqs=3, block_size=8,
                       prefill_chunk=16)
    assert engine.prefix_enabled is False
    seqs = [Sequence(p, n) for p, n in zip(prompts, n_new)]
    _drain(engine, seqs)
    for seq, prompt, n in zip(seqs, prompts, n_new):
        assert _is_greedy(model, params, prompt, seq.generated)
        assert _worst_gap(params, prompt, list(seq.generated)) <= LOGIT_TOL
    assert engine.state_starts == 5             # one a request
    assert engine.state_rows > 5


def test_runs_shorter_than_the_window_shift_it_through(model, params):
    """A budget of 2 (one slot of 2): every run is shorter than the
    window's 3 inputs, so each step's window is part old, part new."""
    rs = np.random.RandomState(2)
    prompt = _prompt(rs, 11)
    engine = LLMEngine(model, params, max_seqs=1, block_size=4,
                       prefill_chunk=2)
    assert engine._step_budget == 2
    seq = Sequence(prompt, 5)
    _drain(engine, [seq])
    assert len(seq.generated) == 5 \
        and _is_greedy(model, params, prompt, seq.generated)
    assert _worst_gap(params, prompt, list(seq.generated)) <= LOGIT_TOL


def test_a_slot_used_again_starts_from_zeros(model, params):
    """ONE slot, three requests one after the other, and the pools
    poisoned before the first: neither an earlier occupant's state nor
    garbage reaches a later one."""
    rs = np.random.RandomState(3)
    engine = LLMEngine(model, params, max_seqs=1, block_size=8)
    pools = engine.cache.pools()
    engine.cache.swap(pools[0], *(jnp.full_like(p, 1e3)
                                  for p in pools[1:]))
    for n_prompt, n in ((40, 5), (6, 6), (17, 4)):
        prompt = _prompt(rs, n_prompt)
        seq = Sequence(prompt, n)
        _drain(engine, [seq])
        assert seq.slot is None or seq.slot == 0
        assert len(seq.generated) == n \
            and _is_greedy(model, params, prompt, seq.generated)


def test_preemption_and_readmission_give_the_same_tokens(model, params):
    """A pool too small for both sequences' contexts: the younger one is
    preempted (its pages freed, its generation folded into its prompt)
    and re-admitted at position 0, where its state starts from zeros
    again."""
    rs = np.random.RandomState(4)
    prompts = [_prompt(rs, 30), _prompt(rs, 28)]
    engine = LLMEngine(model, params, max_seqs=2, block_size=4,
                       num_blocks=33, max_context=128)
    seqs = [Sequence(p, 40) for p in prompts]
    _drain(engine, seqs)
    assert sum(s.preemptions for s in seqs) >= 1
    for seq, prompt in zip(seqs, prompts):
        out = seq.output_tokens()
        assert len(out) == 40 and _is_greedy(model, params, prompt, out)
    assert engine.state_starts == 2 + sum(s.preemptions for s in seqs)


def test_a_row_the_budget_does_not_reach_keeps_its_slot_bit_for_bit(
        model, params):
    """2 slots of 4, a budget of 8, prompts of 20 and 10 tokens admitted
    together: the first takes the whole budget twice, and the second,
    in its slot all the while, is in no step's rows; its slot's state
    and window (garbage planted there before) come back bit for bit
    until the budget reaches it, and it then starts from zeros."""
    rs = np.random.RandomState(12)
    engine = LLMEngine(model, params, max_seqs=2, block_size=4,
                       prefill_chunk=4)
    assert engine._step_budget == 8
    pools = engine.cache.pools()
    engine.cache.swap(pools[0], *(
        jnp.asarray(rs.randn(*p.shape), jnp.float32) for p in pools[1:]))
    prompts = [_prompt(rs, 20), _prompt(rs, 10)]
    first, second = seqs = [Sequence(p, 6) for p in prompts]
    rows_of, inner = [], engine._dispatch
    engine._dispatch = lambda rows, plans: (
        rows_of.append({s: len(plans[s]["tokens"]) for s in rows}),
        inner(rows, plans))[1]
    for s in seqs:
        engine.add(s)
    for n in (8, 16):
        before = [np.asarray(p) for p in engine.cache.pools()[1:]]
        engine.step()
        assert rows_of[-1] == {first: 8} and first.seq_len == n
        assert second.slot == 1 and second.seq_len == 0 \
            and second.block_ids == []
        for was, now in zip(before, engine.cache.pools()[1:]):
            np.testing.assert_array_equal(np.asarray(now)[:, 1], was[:, 1])
            assert not np.array_equal(np.asarray(now)[:, 0], was[:, 0])
    engine.step()
    assert rows_of[-1] == {first: 4, second: 4}     # the remainder
    _drain(engine, [])
    for seq, prompt in zip(seqs, prompts):
        assert len(seq.generated) == 6 \
            and _is_greedy(model, params, prompt, seq.generated)
    assert engine.state_rows == sum(len(r) for r in rows_of)


def test_long_chunks_through_both_kernels_beside_decoding_rows(monkeypatch):
    """The budget's long runs through the state-space and the
    grouped-query kernels themselves (interpret mode), contexts to 170:
    2 slots of 40, so a step of 80 tokens is two query blocks of 64. A
    140-token prompt takes 60 tokens beside the other row's prompt, then
    79 beside its decode token (one run of 79 through the recurrence,
    astride the query blocks' edge); the pool is too small for both
    contexts, so it is preempted and writes its folded prompt again from
    a state of zeros in chunks of 80 and 79. Every served token is the
    reference's first choice."""
    import functools
    for name in ("ssd_state_step", "gqa_flat_attention"):
        monkeypatch.setattr(model_mod, name, functools.partial(
            getattr(model_mod, name), use_pallas=True, interpret=True))
    toy = dict(TOY, max_context=192)
    # a model of its own: its programs trace the kernels, not the twins
    model = TinyDecoder(DecoderConfig(**toy))
    params = ref.init_params(11, toy, dtype=jnp.float32)
    engine = LLMEngine(model, params, max_seqs=2, block_size=8,
                       prefill_chunk=40, num_blocks=26)
    assert engine._t_buckets == [2, 40, 80]
    rs = np.random.RandomState(7)
    cases = [(_prompt(rs, 20), 60), (_prompt(rs, 140), 30)]
    seqs = [Sequence(p, n) for p, n in cases]
    chunks, inner = [], engine._dispatch

    def dispatch(rows, plans):
        chunks.extend((s is seqs[1], len(plans[s]["tokens"]), len(rows))
                      for s in rows if plans[s]["kind"] == "prefill")
        return inner(rows, plans)
    engine._dispatch = dispatch
    _drain(engine, seqs)
    # (the long prompt's?, tokens, rows in the step)
    assert chunks == [(False, 20, 2), (True, 60, 2), (True, 79, 2),
                      (True, 1, 2), (True, 80, 1), (True, 79, 1)]
    assert [s.preemptions for s in seqs] == [0, 1]
    assert engine.state_starts == 3
    for s, (prompt, n) in zip(seqs, cases):
        served = s.output_tokens()
        assert len(served) == n
        gaps = ref.served_token_gaps(params, prompt, served, toy, 192)
        assert float(gaps.max()) <= LOGIT_TOL


def test_padding_rows_touch_no_state(model, params):
    """A packed step whose tail is padding that names a live slot, as
    ``_build_batch`` writes it (the last valid token's slot at position
    0, ``valid`` 0): every slot's state but the valid rows' comes back
    bit for bit, and a step of padding alone changes nothing at all."""
    engine = LLMEngine(model, params, max_seqs=3, block_size=8)
    rs = np.random.RandomState(5)
    _, ssm, conv = engine.cache.pools()
    ssm = jnp.asarray(rs.randn(*ssm.shape), jnp.float32)
    conv = jnp.asarray(rs.randn(*conv.shape), jnp.float32)
    pages = engine.cache.pools()[0]
    tables = jnp.asarray(np.arange(1, 1 + 3 * 16).reshape(3, 16),
                         jnp.int32)
    T = 12
    tokens = jnp.asarray(_prompt(rs, T), jnp.int32)
    # slot 1: 4 tokens from position 9; slot 2: one at 30; 7 of padding
    seq_ids = jnp.asarray([1] * 4 + [2] + [2] * 7, jnp.int32)
    positions = jnp.asarray([9, 10, 11, 12, 30] + [0] * 7, jnp.int32)
    valid = jnp.asarray([1] * 5 + [0] * 7, jnp.int32)
    _, _, ssm2, conv2 = model.decode_flat(
        params, tokens, positions, seq_ids, valid, pages, None, tables,
        state=(ssm, conv))
    np.testing.assert_array_equal(ssm2[:, 0], ssm[:, 0])
    np.testing.assert_array_equal(conv2[:, 0], conv[:, 0])
    assert not np.array_equal(ssm2[:, 2], ssm[:, 2])
    assert not np.array_equal(conv2[:, 1], conv[:, 1])
    # slot 2's window after one token: its old last two, then the new
    np.testing.assert_array_equal(conv2[:, 2, :2], conv[:, 2, 1:])
    _, _, ssm3, conv3 = model.decode_flat(
        params, tokens, positions, seq_ids, valid * 0, pages, None,
        tables, state=(ssm, conv))
    np.testing.assert_array_equal(ssm3, ssm)
    np.testing.assert_array_equal(conv3, conv)


def test_the_same_prompt_twice_hits_no_prefix(model, params):
    rs = np.random.RandomState(6)
    prompt = _prompt(rs, 40)
    server = LLMServer(model, params, max_seqs=1, block_size=8)
    server.warmup()
    server.start()
    try:
        first = server.submit(prompt, 4).result(timeout=300)
        again = server.submit(prompt, 4).result(timeout=300)
        stats = server.stats()
    finally:
        server.shutdown(drain=False)
    assert list(first.tokens) == list(again.tokens) \
        and _is_greedy(model, params, prompt, first.tokens)
    assert stats["prefix_cache"] is False and stats["prefix_hits"] == 0
    assert stats["compiles"] >= 0
    layout = stats["cache_layout"]
    assert layout["kind"] == "kv_packed" \
        and layout["values_per_token"] == 2 * 2 * 16 \
        and layout["pool_shape"][0] == 2          # attention layers only
    pool = stats["state_pool"]
    assert pool["slots"] == 1 and pool["starts"] == 2
    assert pool["pools"] == {"ssm": [6, 1, 16, 128],
                             "conv": [6, 1, 3, 160]}
    assert pool["bytes"] == 4 * 6 * (16 * 128 + 3 * 160)
    assert pool["rows"] == stats["state_rows"] >= stats["state_steps"] > 0


def test_step_span_carries_the_state_rows(model, params):
    from mxnet_tpu.observability.tracing import get_tracer
    engine = LLMEngine(model, params, max_seqs=2, block_size=8,
                       prefill_chunk=8)
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        _drain(engine, [Sequence(list(range(1, 12)), 3),
                        Sequence([5], 2)])
        spans = [s for s in tracer.snapshot()
                 if s["name"] == "mxtpu.llm.step" and "rows" in s["attrs"]]
    finally:
        if not was_on:
            tracer.disable()
        tracer.clear()
    assert spans
    first = spans[0]["attrs"]
    assert first["state_rows"] == len(first["rows"]) == 2
    assert first["state_runs_prefill"] == 1     # 11 tokens beside 1
    assert first["rows"] == [(11, 11), (1, 1)]
    assert all(s["attrs"]["state_rows"] == len(s["attrs"]["rows"])
               for s in spans)
    assert sum(s["attrs"]["state_rows"] for s in spans) \
        == engine.state_rows


# ----------------------------------------------------------- refusals --

def _toy_draft():
    return TinyDecoder(DecoderConfig(vocab_size=97, max_context=128))


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


def test_entry_points_the_block_does_not_have_raise(model, params):
    for call in (
            lambda: greedy_decode_reference(model, params, [1, 2], 2),
            lambda: model.param_specs(),
            lambda: model.forward(params, jnp.zeros((1, 2), jnp.int32),
                                  lora=(None, None, 1.0))):
        with pytest.raises(UnsupportedModelFeatureError):
            call()
    z = jnp.zeros((1,), jnp.int32)
    pages = jnp.zeros((2, 4, 4, 64), jnp.float32)
    for kw, what in (({"k_scales": pages}, "quantized pages"),
                     ({"axis_name": "tp"}, "tensor-parallel"),
                     ({"w_scales": {}}, "quantized weights")):
        with pytest.raises(UnsupportedModelFeatureError, match=what):
            model.decode_flat(params, z, z, z, z, pages, None,
                              jnp.zeros((1, 4), jnp.int32), **kw)
    gpt2 = TinyDecoder(DecoderConfig(vocab_size=97))
    with pytest.raises(UnsupportedModelFeatureError, match="slot state"):
        gpt2.decode_flat(gpt2.init_params(0), z, z, z, z, pages, pages,
                         jnp.zeros((1, 4), jnp.int32), state=(pages,))
    # the engine's default for the other blocks is unchanged
    assert LLMEngine(gpt2, gpt2.init_params(0)).prefix_enabled is True


# ------------------------------------ the state kernel and its twin --

def _ssd_case(seed, T, H=4, P=8, N=16, S=5, L=3):
    rs = np.random.RandomState(seed)
    f = jnp.float32
    x = jnp.asarray(rs.randn(T, H, P), f)
    dt = jnp.asarray(np.log1p(np.exp(rs.randn(T, H) - 2)), f)
    A = -jnp.exp(jnp.asarray(rs.randn(H) * 0.5, f))
    B, C = (jnp.asarray(rs.randn(T, N), f) for _ in range(2))
    state = jnp.asarray(rs.randn(L, S, N, H * P), f)
    return x, dt, A, B, C, state


SSD_BATCHES = {
    # slot 3 a chunk from position 0, slot 1 one token, slot 0 five from
    # 16 on; then padding that names slot 0 at position 0
    "mixed": ([3] * 6 + [1] + [0] * 5 + [0] * 8,
              list(range(6)) + [9] + list(range(16, 21)) + [0] * 8,
              [1] * 12 + [0] * 8),
    # every slot one token (a decode step), none fresh
    "decode": ([4, 2, 0, 1, 3], [7, 1, 30, 2, 5], [1] * 5),
    # one slot alone, a chunk that does not start a sequence, no padding
    "one-run": ([2] * 9, list(range(40, 49)), [1] * 9),
    # padding alone
    "nothing": ([1] * 6, [0] * 6, [0] * 6),
}


@pytest.mark.parametrize("batch", list(SSD_BATCHES))
def test_state_kernel_matches_its_twin(batch):
    """The Pallas kernel in interpret mode against the per-token
    recurrence: the same float32 operations but for the order of the sum
    over the state's columns in ``y`` (hence 1e-5, relative to outputs
    of order 1 to 10); the state itself bit for bit, every slot and
    layer the step did not name untouched."""
    sids, poss, valid = (np.asarray(a, np.int32)
                         for a in SSD_BATCHES[batch])
    T = len(sids)
    x, dt, A, B, C, state = _ssd_case(T, T)
    got_y, got = ssd_state_step(x, dt, A, B, C, state, sids, poss, valid,
                                1, use_pallas=True, interpret=True)
    want_y, want = ssd_state_step(x, dt, A, B, C, state, sids, poss,
                                  valid, 1, use_pallas=False)
    live = valid.astype(bool)
    np.testing.assert_allclose(np.asarray(got_y)[live],
                               np.asarray(want_y)[live], rtol=1e-5,
                               atol=1e-5)
    assert not np.asarray(got_y)[~live].any()   # padding rows: zeros
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    untouched = [s for s in range(5) if s not in set(sids[live])]
    np.testing.assert_array_equal(np.asarray(got)[1, untouched],
                                  np.asarray(state)[1, untouched])
    np.testing.assert_array_equal(np.asarray(got)[[0, 2]],
                                  np.asarray(state)[[0, 2]])


def test_state_twin_is_the_recurrence_written_out():
    """The twin against the equations in numpy, float64, one slot from
    position 0: ``S_t = exp(dt A) S + (dt x) B^T``, ``y_t = S_t C_t``,
    with the pool's layout ``[N, H * P]``."""
    T, H, P, N = 7, 4, 8, 16
    x, dt, A, B, C, state = _ssd_case(9, T)
    sids, poss = np.zeros(T, np.int32), np.arange(T, dtype=np.int32)
    y, new = ssd_state_step_reference(
        x, dt, A, B, C, state, jnp.asarray(sids), jnp.asarray(poss),
        jnp.ones(T, jnp.int32), 2)
    S = np.zeros((H, P, N))
    x64, dt64, A64, B64, C64 = (np.asarray(a, np.float64)
                                for a in (x, dt, A, B, C))
    for t in range(T):
        S = np.exp(dt64[t] * A64)[:, None, None] * S \
            + (dt64[t][:, None] * x64[t])[:, :, None] * B64[t]
        np.testing.assert_allclose(np.asarray(y[t]), S @ C64[t],
                                   rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(new[2, 0]), S.reshape(H * P, N).T, rtol=1e-5,
        atol=1e-6)
    start, length = slot_runs(jnp.asarray([2, 2, 0, 1, 1], jnp.int32),
                              jnp.asarray([1, 1, 1, 0, 1], jnp.int32), 4)
    assert list(np.asarray(length)) == [1, 1, 2, 0]
    assert list(np.asarray(start))[:3] == [2, 4, 0]


def test_step_through_both_kernels_equals_step_through_their_twins(
        model, params, monkeypatch):
    """``decode_flat`` with the state-space and the grouped-query
    kernels in interpret mode against the same step through their
    ``jnp`` twins: a chunk from position 0, a chunk further on, decode
    rows, padding."""
    import functools
    rs = np.random.RandomState(8)
    engine = LLMEngine(model, params, max_seqs=4, block_size=8)
    pages, ssm, conv = engine.cache.pools()
    pages = jnp.asarray(rs.randn(*pages.shape), jnp.float32)
    ssm = jnp.asarray(rs.randn(*ssm.shape), jnp.float32)
    conv = jnp.asarray(rs.randn(*conv.shape), jnp.float32)
    tables = jnp.asarray(1 + rs.permutation(64).reshape(4, 16), jnp.int32)
    seq_ids = jnp.asarray([2] * 16 + [0] * 5 + [3] + [1] + [1] * 4,
                          jnp.int32)
    positions = jnp.asarray(list(range(16)) + list(range(32, 37)) + [70]
                            + [3] + [0] * 4, jnp.int32)
    valid = jnp.asarray([1] * 23 + [0] * 4, jnp.int32)
    tokens = jnp.asarray(_prompt(rs, 27), jnp.int32)

    def step():
        return model.decode_flat(params, tokens, positions, seq_ids, valid,
                                 pages, None, tables, state=(ssm, conv))
    want = step()
    for name in ("ssd_state_step", "gqa_flat_attention"):
        monkeypatch.setattr(model_mod, name, functools.partial(
            getattr(model_mod, name), use_pallas=True, interpret=True))
    got = step()
    live = np.asarray(valid, bool)
    np.testing.assert_allclose(np.asarray(got[0])[live],
                               np.asarray(want[0])[live], atol=LOGIT_TOL,
                               rtol=0)
    # (block 0 is where the padding rows' writes land: scratch)
    np.testing.assert_allclose(np.asarray(got[1])[:, 1:],
                               np.asarray(want[1])[:, 1:], rtol=1e-5,
                               atol=1e-5)
    for a, b in zip(got[2:], want[2:]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-5, atol=1e-5)


# ------------------------------------------ one step in flight (PR 38) --

@pytest.mark.parametrize("num_blocks", [None, 20])
def test_pipelined_server_answers_as_the_synchronous_step(model, params,
                                                          num_blocks):
    """The slot state pools behind ``LLMServer`` with one step in
    flight: a slot freed at a step's commit is taken again only by a
    later step, whose first row starts from zeros; the tokens are the
    synchronous ``engine.step()``'s, with preemption (which waits for
    the step in flight's commit) where the pool is small."""
    rs = np.random.RandomState(9)
    prompts = [_prompt(rs, n) for n in (5, 23, 40, 9, 17, 31)]
    kw = dict(max_seqs=3, block_size=8, prefill_chunk=8)
    if num_blocks:
        kw["num_blocks"] = num_blocks
    engine = LLMEngine(model, params, **kw)
    seqs = [Sequence(p, 30) for p in prompts]
    _drain(engine, seqs)
    server = LLMServer(model, params, name=f"hybrid_ahead_{num_blocks}",
                       **kw)
    server.warmup()
    server.start()
    try:
        got = [f.result(timeout=300).tokens
               for f in [server.submit(p, 30) for p in prompts]]
        stats = server.stats()
    finally:
        server.shutdown()
    assert got == [s.output_tokens() for s in seqs]
    assert stats["overlap_share"] > 0.5
    if num_blocks:
        assert stats["preemptions"] >= 1
    assert server.engine.cache.allocator.num_used == 0
