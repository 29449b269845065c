"""The step's token budget (ISSUE 34): how ``LLMEngine._schedule`` deals
the longest packed batch the engine compiles, ``max_seqs x q_tokens``
(128 at the defaults), to the rows of a step. Counts only, on the CPU,
one warmed engine for the module.

- a step never feeds more than ``max(_t_buckets)`` tokens;
- rows that decode are always served, a token each;
- rows still writing their prompt take what is left in admission order,
  each ``min(its remaining prompt, what is left)``, so the last one
  reached takes the remainder and the ones behind it sit the step out;
- a 100-token prompt on the idle engine is one step's work;
- nothing of this compiles after ``warmup()``;
- with speculation on, the draft is fed the chunk the target takes;
- the counters ``mxtpu_llm_step_tokens_total`` and
  ``mxtpu_llm_step_rung_tokens_total``, and the benchmark's reader of the
  same share from the ``mxtpu.llm.build`` spans;
- the packed-length ladder: all rows decoding, the budget's half and
  quarter where they lie above that, the budget; a step that admits a
  prompt beside the decoding rows takes the least of them that holds it
  (``mxtpu_llm_steps_by_rung_total``).
"""
import os
import sys

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.observability.tracing import get_tracer  # noqa: E402
from mxnet_tpu.serving.llm import (  # noqa: E402
    DecoderConfig, LLMEngine, Sequence, TinyDecoder,
    greedy_decode_reference)
from mxnet_tpu.serving.llm.metrics import LLMStats  # noqa: E402

VOCAB, CTX, BS = 17, 256, 8


class DispatchLog:
    """Wraps ``engine._dispatch`` as the benchmark's ``StepLog`` does and
    keeps, for every dispatched step, what the engine held at that
    moment (before the commit moves ``seq_len``): ``(running, rows)``,
    ``running`` the sequences in their slots in admission order as
    ``(seq, decoding, prompt tokens still to write)``, ``rows`` the
    step's ``{seq: tokens fed}`` in batch order."""

    def __init__(self, engine):
        self.engine, self.steps = engine, []
        self._inner = engine._dispatch
        engine._dispatch = self

    def __call__(self, rows, plans):
        running = sorted(self.engine.scheduler.running(),
                         key=lambda s: s.admit_index)
        self.steps.append((
            [(s, bool(s.generated), len(s.prompt) - s.seq_len)
             for s in running],
            {s: len(plans[s]["tokens"]) for s in rows}))
        return self._inner(rows, plans)

    def since(self, mark):
        return self.steps[mark:]


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(
        vocab_size=VOCAB, d_model=16, num_layers=2, num_heads=2, d_ff=32,
        max_context=CTX))


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(seed=0)


@pytest.fixture(scope="module")
def warmed(model, params):
    """``(engine, log, stats)``: 8 slots at the program's defaults (a
    budget of 8 x 16), the prefix cache off so that every prompt token
    is fed, warmed."""
    stats = LLMStats("step_budget_t")
    engine = LLMEngine(model, params, max_seqs=8, block_size=BS,
                       max_context=CTX, prefix_cache=False, stats=stats)
    engine.warmup()
    return engine, DispatchLog(engine), stats


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=n).tolist()


def _drain(engine, limit=2000):
    for _ in range(limit):
        if not engine.has_work():
            return
        engine.step()
    raise AssertionError("the engine did not drain")


def test_the_budget_is_the_top_rung_and_no_program_is_new(warmed):
    engine = warmed[0]
    assert engine._t_buckets == [8, 32, 64, 128]
    assert engine._step_budget == max(engine._t_buckets) == 128
    assert engine._draft_t_buckets[-1] == engine._step_budget
    assert engine.debug_status()["programs"]["prefill_chunk"] == 16


def _block_model():
    """A block-diffusion model: a generating row feeds a block of 4."""
    return TinyDecoder(DecoderConfig(
        vocab_size=61, d_model=16, num_layers=1, num_heads=2, d_ff=32,
        max_context=128, model_type="sdar_moe", num_key_value_heads=1,
        head_dim=8, rope_theta=1e6, rms_norm_eps=1e-6, num_experts=4,
        num_experts_per_tok=2, moe_intermediate_size=16,
        norm_topk_prob=True, torch_dtype="float32", block_length=4,
        mask_token_id=60, denoising_steps=4,
        remasking_strategy="sequential", confidence_threshold=0.9))


def _exact_mix_ladder(slots, row, q):
    """A ladder whose middle rungs are the exact lengths of a step with
    one or two rows taking ``q`` tokens beside rows feeding ``row``:
    the most programs a ladder of this engine may warm."""
    lo, hi = slots * row, slots * q
    return {lo, hi} | {min(hi, i * q + (slots - i) * row)
                       for i in (1, 2) if i <= slots}


@pytest.mark.parametrize("slots,chunk,block,ladder", [
    (8, 16, False, [8, 32, 64, 128]),
    (32, 16, False, [32, 128, 256, 512]),
    (32, 16, True, [128, 256, 512]),
    (128, 16, False, [128, 512, 1024, 2048]),
    (2, 40, False, [2, 40, 80]),
], ids=["8x16", "32x16", "32-rows-of-block-4", "128x16", "2x40"])
def test_the_ladder_is_decode_halvings_of_the_budget_and_the_budget(
        model, params, slots, chunk, block, ladder):
    if block:
        model = _block_model()
        params = model.init_params(seed=0)
    engine = LLMEngine(model, params, max_seqs=slots, block_size=BS,
                       max_context=128, prefill_chunk=chunk,
                       prefix_cache=False)
    row = 4 if block else 1
    lo, hi = slots * row, slots * chunk
    assert engine._t_buckets == ladder
    # the all-decode rung, then the halvings above it, then the budget
    assert ladder[0] == lo and ladder[-1] == hi
    assert set(ladder[1:-1]) <= {-(-hi // 2), -(-hi // 4)}
    assert all(m > lo for m in ladder[1:-1])
    # the top rung is the step's budget, a slot's share of it the chunk
    assert engine._step_budget == hi == slots * engine.q_tokens
    assert len(ladder) <= len(_exact_mix_ladder(slots, row, chunk))


def test_an_admitted_prompt_beside_decoding_rows_takes_a_quarter_budget(
        model, params):
    """16 slots (a budget of 256): with 15 rows decoding, a prompt of 20
    tokens goes in whole and the step of 35 tokens takes the 64 rung,
    the budget's quarter, not the budget; nothing compiles after
    ``warmup()``, and the counter of steps by rung counts that step."""
    stats = LLMStats("step_budget_rungs_t")
    engine = LLMEngine(model, params, max_seqs=16, block_size=BS,
                       max_context=CTX, prefix_cache=False, stats=stats)
    assert engine._t_buckets == [16, 64, 128, 256]
    engine.warmup()
    log = DispatchLog(engine)
    rng = np.random.RandomState(5)
    with serving.CompileCounter() as cc:
        decoding = [Sequence(_prompt(rng, 4), 12) for _ in range(15)]
        for s in decoding:
            engine.add(s)
        engine.step()           # the 15 prompts, 60 tokens
        engine.step()           # 15 rows decoding
        assert all(s.generated for s in decoding)
        before = stats.snapshot()["steps_by_rung"]
        prompt = _prompt(rng, 20)
        admitted = Sequence(prompt, 4)
        engine.add(admitted)
        engine.step()
        _, rows = log.steps[-1]
        assert rows[admitted] == 20 and sum(rows.values()) == 35
        assert engine._step_rung == 64 == engine._step_budget // 4
        after = stats.snapshot()["steps_by_rung"]
        assert after[64] == before.get(64, 0) + 1
        assert sum(after.values()) == sum(before.values()) + 1
        _drain(engine)
    assert cc.count == 0
    assert admitted.output_tokens() == greedy_decode_reference(
        model, params, prompt, 4)
    snap = stats.snapshot()
    assert sum(snap["steps_by_rung"].values()) == len(log.steps)
    assert snap["step_rung_tokens"] == sum(
        t * n for t, n in snap["steps_by_rung"].items())


def test_a_100_token_prompt_on_the_idle_engine_is_one_step(warmed, model,
                                                           params):
    engine, log, _ = warmed
    mark = len(log.steps)
    prompt = _prompt(np.random.RandomState(1), 100)
    seq = Sequence(prompt, 4)
    engine.add(seq)
    engine.step()
    assert seq.seq_len == 100 and len(seq.generated) == 1
    (_, rows), = log.since(mark)
    assert rows == {seq: 100}
    _drain(engine)
    assert seq.output_tokens() == greedy_decode_reference(
        model, params, prompt, 4)
    # one chunk and three decode steps, not seven chunks
    assert len(log.since(mark)) == 4


def test_decoding_rows_first_then_prompts_in_admission_order(warmed, model,
                                                             params):
    """13 requests on 8 slots, prompts of 3 to 120 tokens: in every
    step the rows that decode are served, the prompts take the rest in
    admission order, and nothing compiles."""
    engine, log, _ = warmed
    budget = engine._step_budget
    rng = np.random.RandomState(2)
    lengths = (100, 90, 60, 5, 70, 40, 3, 120, 30, 9, 80, 50, 110)
    cases = [(_prompt(rng, n), int(rng.randint(3, 20))) for n in lengths]
    seqs = [Sequence(p, n) for p, n in cases]
    mark = len(log.steps)
    with serving.CompileCounter() as cc:
        for s in seqs:
            engine.add(s)
        _drain(engine)
    assert cc.count == 0
    steps = log.since(mark)
    waited = cut_short = 0
    for running, rows in steps:
        left = budget
        for seq, decoding, _ in running:
            if decoding:
                assert rows.get(seq) == 1       # always served
                left -= 1
        for seq, decoding, remaining in running:
            if decoding:
                continue
            share = min(remaining, left)
            # the budget's rest in admission order; none of it, no row
            assert rows.get(seq) == (share or None)
            waited += share == 0
            cut_short += 0 < share < remaining
            left -= share
        assert sum(rows.values()) == budget - left <= budget
        # the batch is packed in admission order
        assert list(rows) == [s for s, _, _ in running if s in rows]
    # the traffic did run the budget out: rows sat steps out, and rows
    # took a remainder shorter than their prompt
    assert waited > 0 and cut_short > 0
    assert max(sum(rows.values()) for _, rows in steps) == budget
    for seq, (prompt, n) in zip(seqs, cases):
        assert seq.output_tokens() == greedy_decode_reference(
            model, params, prompt, n)
    # 767 prompt tokens went through 13 admissions' chunks: far fewer
    # than the 54 chunks of 16 tokens a row
    chunks = sum(1 for running, rows in steps for seq, decoding, _
                 in running if not decoding and seq in rows)
    assert 13 <= chunks <= 26


def test_the_counters_hold_tokens_and_rungs_of_every_dispatch(warmed):
    engine, log, stats = warmed
    assert log.steps
    fed = [sum(rows.values()) for _, rows in log.steps]
    rungs = [next(w for w in engine._t_buckets if w >= n) for n in fed]
    snap = stats.snapshot()
    assert snap["step_tokens"] == sum(fed)
    assert snap["step_rung_tokens"] == sum(rungs)


def test_the_benchmarks_reader_takes_the_share_from_the_build_spans(
        warmed):
    """``scheduler.step_fill_share.serve``: 100 x tokens over rungs of
    the ring's ``mxtpu.llm.build`` spans; nothing from an empty ring or
    an untraced run."""
    from benchmark import run as run_mod
    read = run_mod.layer_reader("scheduler.step_fill_share.serve")
    engine, log, _ = warmed
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    try:
        mark = len(log.steps)
        rng = np.random.RandomState(3)
        for n in (70, 2, 33):
            engine.add(Sequence(_prompt(rng, n), 5))
        _drain(engine)
        fed = [sum(rows.values()) for _, rows in log.since(mark)]
        rungs = [next(w for w in engine._t_buckets if w >= n) for n in fed]
        assert len(fed) > 4 and sum(fed) < sum(rungs)
        assert read({"traced": {"steps": len(fed)}}) == pytest.approx(
            100.0 * sum(fed) / sum(rungs))
        assert read({"traced": None}) is None       # an untraced run
        tracer.clear()
        assert read({"traced": {"steps": 0}}) is None   # an empty ring
    finally:
        if not was_on:
            tracer.disable()
        tracer.clear()


def test_the_draft_is_fed_the_chunk_the_target_takes(model, params):
    """Speculation on (4 slots, ``spec_k`` 3: a budget of 64): after
    every step a row still writing its prompt has ``draft_len ==
    seq_len``, and the served tokens are the target's own."""
    draft = TinyDecoder(DecoderConfig(
        vocab_size=VOCAB, d_model=8, num_layers=1, num_heads=1, d_ff=16,
        max_context=CTX))
    engine = LLMEngine(model, params, max_seqs=4, block_size=BS,
                       max_context=CTX, draft_model=draft,
                       draft_params=draft.init_params(seed=1), spec_k=3)
    assert engine._step_budget == engine._draft_t_buckets[-1] == 64
    log = DispatchLog(engine)
    rng = np.random.RandomState(4)
    cases = [(_prompt(rng, n), m) for n, m in
             ((90, 12), (70, 9), (10, 15), (40, 6), (5, 10), (120, 7))]
    seqs = [Sequence(p, n) for p, n in cases]
    for s in seqs:
        engine.add(s)
    prefill_steps = 0
    for _ in range(2000):
        if not engine.has_work():
            break
        engine.step()
        for s in engine.scheduler.running():
            if not s.generated:
                assert s.draft_len == s.seq_len
                prefill_steps += 1
    assert not engine.has_work() and prefill_steps > 3
    assert all(sum(rows.values()) <= 64 for _, rows in log.steps)
    assert max(max(rows.values()) for _, rows in log.steps) > 16
    for seq, (prompt, n) in zip(seqs, cases):
        assert seq.output_tokens() == greedy_decode_reference(
            model, params, prompt, n)
