"""mxnet_tpu.serving.llm: continuous-batching decode engine.

The decode-serving contract pinned here (ISSUE 8 acceptance criteria):

- greedy continuous-batched decoding is BIT-IDENTICAL (token for
  token) to per-sequence eager decoding for a mixed batch of >= 8
  sequences with different prompt lengths and different stop steps,
  with sequences admitted and evicted mid-run;
- after ``warmup()`` a mixed prefill/decode workload (varying prompt
  lengths, staggered arrivals) triggers ZERO XLA recompiles (asserted
  via the jax.monitoring backend_compile counter);
- KV pressure preempts and resumes sequences without changing their
  token streams (restart-based recompute preemption);
- drain on shutdown/preemption runs in-flight sequences to completion
  within the deadline or rejects them with a typed
  ``SequenceEvictedError`` carrying the tokens generated so far —
  never a silent drop;
- ``mxtpu_llm_tokens_per_sec``, ``mxtpu_llm_ttft_seconds`` and
  ``mxtpu_llm_kv_blocks_in_use`` land in one Prometheus exposition.
"""
import os
import signal
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.serving import ServerClosed  # noqa: E402
from mxnet_tpu.serving.llm import (  # noqa: E402
    TinyDecoder, DecoderConfig, LLMEngine, LLMServer, Sequence,
    SequenceEvictedError, greedy_decode_reference)
from mxnet_tpu.resilience import PreemptionGuard  # noqa: E402

VOCAB = 17
BS = 8          # KV block size
CTX = 64


@pytest.fixture(scope="module")
def model():
    return TinyDecoder(DecoderConfig(
        vocab_size=VOCAB, d_model=16, num_layers=2, num_heads=2,
        d_ff=32, max_context=CTX))


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(seed=0)


def _prompts(rng, n, lo=1, hi=25):
    return [rng.randint(0, VOCAB, size=int(rng.randint(lo, hi))).tolist()
            for _ in range(n)]


# ------------------------------------------ (a) bit-identical decode --
def test_continuous_batching_bit_identical_mixed_batch(model, params):
    """>= 8 sequences, ragged prompt lengths (incl. block-boundary
    edges), different stop steps, fewer slots than sequences so
    admission/eviction churns mid-run: every token stream must equal
    per-sequence eager greedy decoding exactly."""
    eng = LLMEngine(model, params, max_seqs=4, block_size=BS,
                    max_context=CTX)
    eng.warmup()
    rng = np.random.RandomState(2)
    cases = []
    # block-boundary prompt lengths first, then a ragged mix
    for plen in (BS - 1, BS, BS + 1):
        cases.append((rng.randint(0, VOCAB, size=plen).tolist(),
                      int(rng.randint(1, 12))))
    for prompt in _prompts(rng, 6):
        cases.append((prompt, int(rng.randint(1, 12))))
    assert len(cases) >= 8
    seqs = []
    # staggered admission: half now, half injected mid-run
    for prompt, n in cases[:5]:
        s = Sequence(prompt, n)
        seqs.append(s)
        eng.add(s)
    steps = 0
    injected = 5
    while eng.has_work() or injected < len(cases):
        if injected < len(cases) and (steps % 2 == 0
                                      or not eng.has_work()):
            prompt, n = cases[injected]
            s = Sequence(prompt, n)
            seqs.append(s)
            eng.add(s)
            injected += 1
        eng.step()
        steps += 1
        assert steps < 1000
    for (prompt, n), s in zip(cases, seqs):
        assert s.state == "finished"
        ref = greedy_decode_reference(model, params, prompt, n)
        assert s.output_tokens() == ref, \
            f"seq {s.seq_id} (prompt {len(prompt)}, n={n}) diverged"
    assert eng.cache.allocator.num_used == 0
    eng.cache.allocator.check()


def test_stop_token_ends_generation_early(model, params):
    eng = LLMEngine(model, params, max_seqs=2, block_size=BS,
                    max_context=CTX)
    eng.warmup()
    prompt = [3, 1, 4, 1, 5]
    free_run = greedy_decode_reference(model, params, prompt, 20)
    stop = free_run[4]               # stop at the 5th generated token
    s = Sequence(prompt, 20, stop_token=stop)
    eng.add(s)
    while eng.has_work():
        eng.step()
    ref = greedy_decode_reference(model, params, prompt, 20,
                                  stop_token=stop)
    assert s.output_tokens() == ref
    assert s.output_tokens()[-1] == stop
    assert len(s.output_tokens()) < 20
    assert s.finish_reason == "stop_token"


# --------------------------------------------- (b) zero recompiles ---
def test_zero_recompiles_mixed_prefill_decode_staggered(model, params):
    """After warmup, staggered arrivals with varying prompt lengths mix
    prefill and decode launches every which way — and compile
    NOTHING (the backend_compile counter must not move)."""
    # same max_seqs as the bit-identical test above: the compiled
    # program set is shared, so this test's warmup compiles nothing
    eng = LLMEngine(model, params, max_seqs=4, block_size=BS,
                    max_context=CTX)
    eng.warmup()
    rng = np.random.RandomState(4)
    with serving.CompileCounter() as cc:
        pending = _prompts(rng, 9)
        live = []
        for prompt in pending[:3]:
            s = Sequence(prompt, int(rng.randint(1, 10)))
            live.append(s)
            eng.add(s)
        injected = 3
        steps = 0
        while eng.has_work() or injected < len(pending):
            if steps % 3 == 0 and injected < len(pending):
                s = Sequence(pending[injected],
                             int(rng.randint(1, 10)))
                live.append(s)
                eng.add(s)
                injected += 1
            eng.step()
            steps += 1
            assert steps < 1000
    assert cc.count == 0, \
        f"{cc.count} XLA recompiles after warmup (shape leak)"
    assert all(s.state == "finished" for s in live)


def test_warmup_covers_every_bucket_once(model, params):
    """A second warmup over the same engine compiles nothing: the ONE
    chunked-step program is everything steady state can reach."""
    eng = LLMEngine(model, params, max_seqs=2, block_size=BS,
                    max_context=CTX)
    first = eng.warmup()
    # every (packed length x table width) rung of the ONE flat
    # program, in its greedy and sampled variants, plus the prefix
    # cache's fixed-shape copy-on-write program — nothing else is
    # reachable in steady state
    expect = {f"step_t{t}mb{mb}_{v}" for t in eng._t_buckets
              for mb in eng._mb_widths for v in ("greedy", "sampled")}
    if eng.prefix_enabled:
        expect.add("cow_copy")
    assert set(first) == expect
    assert max(eng._t_buckets) == eng.max_seqs * eng.q_tokens
    assert eng.cache.max_blocks_per_seq in eng._mb_widths
    with serving.CompileCounter() as cc:
        eng.warmup()
    assert cc.count == 0


# ------------------------------------------------- (c) preemption ----
def test_kv_pressure_preempts_and_resumes_exact_stream(model, params):
    """A pool too small for all sequences at full length forces
    restart-based preemption; deterministic greedy decoding must
    resume the exact token stream."""
    eng = LLMEngine(model, params, max_seqs=3, block_size=BS,
                    max_context=CTX, num_blocks=11)   # 10 usable, 8/seq
    eng.warmup()
    rng = np.random.RandomState(5)
    seqs, orig = [], {}
    for prompt in _prompts(rng, 3, lo=4, hi=12):
        s = Sequence(prompt, 25)
        orig[s.seq_id] = list(prompt)
        seqs.append(s)
        eng.add(s)
    preempts = 0
    steps = 0
    while eng.has_work():
        preempts += sum(1 for k, _ in eng.step() if k == "preempted")
        steps += 1
        assert steps < 3000
    assert preempts >= 1, "pool was sized to force preemption"
    for s in seqs:
        ref = greedy_decode_reference(model, params, orig[s.seq_id],
                                      s.max_new_tokens)
        assert s.output_tokens() == ref
    assert eng.cache.allocator.num_used == 0
    eng.cache.allocator.check()


def test_long_chunks_through_the_kernel_beside_decoding_rows(monkeypatch):
    """The budget's long runs through the flat kernel itself (interpret
    mode): 2 slots of 40, so a step of 80 tokens is two query blocks of
    64. A 140-token prompt takes 60 tokens beside the other row's
    prompt, then 79 beside its decode token (a run astride the query
    blocks' edge); the pool is too small for both contexts, so it is
    preempted and writes its folded prompt again in chunks of 80 and 79.
    Every served token is the dense reference's."""
    import functools
    from mxnet_tpu.serving.llm import model as model_mod
    monkeypatch.setattr(model_mod, "ragged_flat_attention", functools.partial(
        model_mod.ragged_flat_attention, use_pallas=True, interpret=True))
    # a model of its own: its programs trace the kernel, not the twin
    model = TinyDecoder(DecoderConfig(
        vocab_size=VOCAB, d_model=16, num_layers=2, num_heads=2, d_ff=32,
        max_context=192))
    params = model.init_params(seed=0)
    eng = LLMEngine(model, params, max_seqs=2, block_size=BS,
                    max_context=192, prefill_chunk=40, num_blocks=26)
    assert eng._t_buckets == [2, 41, 80]
    rng = np.random.RandomState(7)
    cases = [(rng.randint(0, VOCAB, size=20).tolist(), 60),
             (rng.randint(0, VOCAB, size=140).tolist(), 30)]
    seqs = [Sequence(p, n) for p, n in cases]
    chunks, inner = [], eng._dispatch

    def dispatch(rows, plans):
        chunks.extend((s is seqs[1], len(plans[s]["tokens"]), len(rows))
                      for s in rows if plans[s]["kind"] == "prefill")
        return inner(rows, plans)
    eng._dispatch = dispatch
    for s in seqs:
        eng.add(s)
    steps = 0
    while eng.has_work():
        eng.step()
        steps += 1
        assert steps < 500
    # (the long prompt's?, tokens, rows in the step)
    assert chunks == [(False, 20, 2), (True, 60, 2), (True, 79, 2),
                      (True, 1, 2), (True, 80, 1), (True, 79, 1)]
    assert [s.preemptions for s in seqs] == [0, 1]
    for s, (prompt, n) in zip(seqs, cases):
        assert s.output_tokens() == greedy_decode_reference(
            model, params, prompt, n)
    assert eng.cache.allocator.num_used == 0
    eng.cache.allocator.check()


# ------------------------------------------------------ (d) drain ----
def test_drain_deadline_evicts_with_partial_tokens(model, params):
    """Shutdown under a deadline: sequences that cannot finish resolve
    with SequenceEvictedError CARRYING their tokens so far.

    Deterministic (no wall-clock race): generations are sized near the
    context cap (~56 tokens each), we POLL until real decode progress
    exists, then shut down with an explicit ``deadline_ms=0`` — the
    worker's next loop iteration is already past the deadline, so no
    amount of CPU speed can run the remaining ~50 steps per sequence
    to completion first."""
    srv = LLMServer(model, params, name="drain_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    want = CTX - 8                       # far more than can ever finish
    futs = [srv.submit([1, 2, 3], want) for _ in range(4)]
    deadline = time.monotonic() + 30
    while (srv.stats()["tokens_generated"] < 4
           and time.monotonic() < deadline):
        time.sleep(0.005)                # wait for partial progress
    assert srv.stats()["tokens_generated"] >= 4
    srv.shutdown(drain=True, deadline_ms=0.0)   # evict now, typed
    done = evicted = partial = 0
    for f in futs:
        try:
            r = f.result(timeout=10)
            done += 1
            assert len(r.tokens) == want
        except SequenceEvictedError as e:
            evicted += 1
            assert e.reason == "drain_deadline"
            assert isinstance(e.tokens, list)
            if e.tokens:
                partial += 1
    assert done + evicted == 4          # nothing silently dropped
    assert evicted >= 1                 # deadline actually bound
    assert partial >= 1                 # tokens-so-far really carried
    with pytest.raises(ServerClosed):
        srv.submit([1], 1)


def test_drain_without_deadline_completes_everything(model, params):
    srv = LLMServer(model, params, name="drain_full", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    futs = [srv.submit([i + 1, 2], 6) for i in range(5)]
    srv.shutdown(drain=True)            # unbounded: run all to the end
    for f in futs:
        assert len(f.result(timeout=10).tokens) == 6


def test_shutdown_without_drain_rejects_live_sequences(model, params):
    srv = LLMServer(model, params, name="nodrain", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    futs = [srv.submit([1, 2], 40) for _ in range(3)]
    srv.shutdown(drain=False)
    for f in futs:
        with pytest.raises(SequenceEvictedError) as ei:
            f.result(timeout=10)
        assert ei.value.reason == "shutdown"


def test_preemption_guard_drains_decode_sequences(model, params):
    """SIGUSR1 through PreemptionGuard: admission closes and every
    in-flight decode sequence either completes within the deadline or
    resolves with a typed eviction — never lost."""
    guard = PreemptionGuard(signals=(signal.SIGUSR1,)).install()
    try:
        srv = LLMServer(model, params, name="guard_t", max_seqs=2,
                        block_size=BS, max_context=CTX)
        srv.warmup()
        srv.start()
        srv.attach_preemption_guard(guard, poll_s=0.01,
                                    deadline_ms=2000.0)
        futs = [srv.submit([1, 2, 3], 5) for _ in range(4)]
        os.kill(os.getpid(), signal.SIGUSR1)
        resolved = 0
        for f in futs:
            try:
                r = f.result(timeout=30)
                assert len(r.tokens) == 5
            except SequenceEvictedError:
                pass
            resolved += 1
        assert resolved == 4
        deadline = time.monotonic() + 10
        while srv.running and time.monotonic() < deadline:
            time.sleep(0.01)
        with pytest.raises(ServerClosed):
            srv.submit([1], 1)
    finally:
        guard.uninstall()


def test_model_server_drain_deadline_env(monkeypatch):
    """Satellite: the single-shot ModelServer honors
    MXNET_TPU_SERVE_DRAIN_DEADLINE_MS — a drain that cannot finish in
    time fails the remaining queue with ServerClosed instead of
    serving it; every Future still resolves."""
    monkeypatch.setenv("MXNET_TPU_SERVE_DRAIN_DEADLINE_MS", "250")

    def slow(batch):
        time.sleep(0.2)
        return batch * 2.0

    srv = serving.ModelServer(slow, buckets=[1], max_delay_ms=0.1,
                              item_shape=(2,), dtype="float32",
                              name="slow_t").start()
    futs = [srv.submit(np.full(2, i, np.float32)) for i in range(8)]
    t0 = time.monotonic()
    srv.shutdown(drain=True)            # env deadline binds
    assert time.monotonic() - t0 < 5.0  # not 8 * 0.2s + slack
    served = failed = 0
    for f in futs:
        try:
            f.result(timeout=10)
            served += 1
        except ServerClosed:
            failed += 1
    assert served + failed == 8         # nothing silently dropped
    assert failed >= 1                  # the deadline actually cut in


def test_engine_error_closes_admission_and_resolves_futures(model,
                                                            params):
    """A dying engine loop must not leave the server half-alive: every
    live Future resolves with the error and later submits raise
    ServerClosed instead of enqueueing onto a dead worker."""
    srv = LLMServer(model, params, name="err_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    boom = RuntimeError("injected engine failure")

    def bad_step():
        raise boom

    srv.engine.step = bad_step
    fut = srv.submit([1, 2, 3], 5)
    with pytest.raises(RuntimeError, match="injected engine failure"):
        fut.result(timeout=10)
    deadline = time.monotonic() + 10
    while srv.running and time.monotonic() < deadline:
        time.sleep(0.01)
    with pytest.raises(ServerClosed):
        srv.submit([1], 1)


# ---------------------------------------------------- (e) metrics ----
def test_llm_metrics_in_one_exposition(model, params):
    from mxnet_tpu.observability import get_registry
    srv = LLMServer(model, params, name="metrics_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    futs = [srv.submit([1 + i, 2], 4) for i in range(3)]
    for f in futs:
        f.result(timeout=30)
    st = srv.stats()
    srv.shutdown()
    assert st["requests_completed"] == 3
    assert st["tokens_generated"] == 12
    assert st["tokens_per_sec"] > 0
    assert st["ttft_ms"]["p50"] <= st["ttft_ms"]["p99"]
    text = get_registry().expose()
    # how full the steps ran: 3 prompts of 2 tokens and 9 decode tokens
    assert st["step_tokens"] == 15 < st["step_rung_tokens"]
    assert st["step_fill_share"] == pytest.approx(
        st["step_tokens"] / st["step_rung_tokens"])
    for needed in ("mxtpu_llm_tokens_per_sec", "mxtpu_llm_ttft_seconds",
                   "mxtpu_llm_kv_blocks_in_use",
                   "mxtpu_llm_requests_completed_total",
                   "mxtpu_llm_decode_steps_total",
                   "mxtpu_llm_step_tokens_total",
                   "mxtpu_llm_step_rung_tokens_total"):
        assert needed in text, f"{needed} missing from exposition"
    # the tools-side checker must accept the exposition wholesale
    sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools"))
    try:
        from metrics_dump import parse_exposition
    finally:
        sys.path.pop(0)
    samples = parse_exposition(text)
    key = ("mxtpu_llm_requests_completed_total",
           (("server", "metrics_t"),))
    assert samples[key] == 3


# ----------------------------------------------- (f) deploy/export ---
def test_decoder_artifact_round_trips_through_server(model, params,
                                                     tmp_path):
    path = str(tmp_path / "decoder.mxtpu")
    mx.deploy.export_decoder(model, params, path)
    m2, p2 = mx.deploy.load_decoder(path)
    assert m2.config.to_dict() == model.config.to_dict()
    prompt = [2, 7, 1]
    ref = greedy_decode_reference(model, params, prompt, 6)
    srv = LLMServer(m2, p2, name="artifact_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    res = srv.generate(prompt, 6, timeout=30)
    srv.shutdown()
    assert res.tokens == ref


def test_bad_artifact_rejected(tmp_path):
    with pytest.raises(ValueError):
        mx.deploy.load_decoder(b"NOTANARTIFACT")


# ------------------------------------------------- (g) validation ----
def test_submit_validation(model, params):
    srv = LLMServer(model, params, name="valid_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    srv.warmup()
    srv.start()
    with pytest.raises(ValueError):
        srv.submit(list(range(1, CTX + 2))[:CTX], 1)   # no room left
    with pytest.raises(ValueError):
        srv.submit([VOCAB + 5], 1)                     # out of vocab
    with pytest.raises(ValueError):
        srv.submit([1], 0)                             # nothing to gen
    with pytest.raises(ValueError):
        srv.submit([], 1)                              # empty prompt
    srv.shutdown()


def test_submit_deadline_and_queue_knobs(model, params, monkeypatch):
    """Overload knobs on the decode path: env-var resolution plus the
    fail-fast submit behaviors (expired budget, bounded queue)."""
    monkeypatch.setenv("MXNET_TPU_SERVE_MAX_QUEUE", "7")
    monkeypatch.setenv("MXNET_TPU_SERVE_DEADLINE_MS", "500")
    srv = LLMServer(model, params, name="knobs_t", max_seqs=2,
                    block_size=BS, max_context=CTX)
    assert srv.max_queue == 7
    assert srv.default_deadline_ms == 500.0
    srv.warmup()
    srv.start()
    with pytest.raises(serving.DeadlineExceededError):
        srv.submit([1, 2], 4, deadline_ms=0)    # budget already gone
    # a deadline generous enough never to bind: serves normally
    res = srv.submit([1, 2], 3, deadline_ms=60000).result(timeout=30)
    assert len(res.tokens) == 3
    srv.shutdown()
    assert srv.stats()["deadline_expired"] == 1
    # typed-hierarchy satellite: eviction/deadline errors share the
    # exported base
    assert issubclass(SequenceEvictedError, serving.ServingError)
    assert issubclass(serving.DeadlineExceededError,
                      serving.ServingError)


def test_engine_sizing_guards(model, params):
    with pytest.raises(ValueError):
        LLMEngine(model, params, max_seqs=2, block_size=BS,
                  max_context=CTX - 1)                 # not page-aligned
    with pytest.raises(ValueError):
        LLMEngine(model, params, max_seqs=2, block_size=BS,
                  max_context=CTX, num_blocks=4)       # < 1 full seq
    with pytest.raises(ValueError):
        LLMEngine(model, params, max_seqs=2, block_size=BS,
                  max_context=CTX, prefill_chunk=0)    # no chunk
    with pytest.raises(ValueError):
        LLMEngine(model, params, max_seqs=2, block_size=BS,
                  max_context=CTX, spec_k=-1,
                  draft_model=model, draft_params=params)
