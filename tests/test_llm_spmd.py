"""SPMD LLM serving gates (ISSUE 19): the unified decode step —
chunked prefill + decode + speculative verify in ONE donated program —
sharded tensor-parallel over a ``tp`` mesh axis, with dp replica
groups of engines behind one server.

What this module pins:

- **bit-exactness at tp=1** — wrapping the step in shard_map over a
  one-device mesh changes NOTHING: greedy AND sampled token streams
  match the unsharded engine token-for-token (same programs modulo
  the wrapper, same float order);
- **greedy parity at tp>1** — per-shard ragged attention + psum'd
  o/MLP projections reproduce the eager single-device reference on
  virtual CPU devices (structure evidence; ICI collectives on real
  hardware run the same program);
- **zero steady-state recompiles, ONE dispatch per step** — mixed
  chunked-prefill + sampled + speculative + adapter traffic on a
  warmed tp=2 engine never re-enters XLA, and every ``step()`` lands
  exactly one launch of the sharded unified program;
- **ONE strict BlockAllocator** — the draft pool rides the target
  allocator's block ids under sharding too; block accounting stays
  exact under randomized admission/completion traffic;
- **prefix-cache elastic resume** — block hashes are pure token
  chains (no mesh salt), so a cache warmed at one mesh size hits at
  another after restart;
- **COW under sharding** — copy-on-write flows through a
  shard_map'd program, so the donated pools come back with their
  sharding intact (the latent single-device assumption fixed in the
  engine: an unconstrained jit would have resharded the pools on the
  first shared-prefix rewrite);
- **kill-one-shard chaos** — a tp engine's worker dying resolves
  every in-flight Future typed, settles KV blocks and adapter-page
  refcounts clean, and a fresh engine at a DIFFERENT mesh size
  resumes the prefix-hash namespace;
- **dp replica groups** — ``mesh="dp=2"`` runs two engines behind
  one scheduler thread with least-loaded routing, one warmup, one
  drain contract.

Budget note (tier-1): every fast tp=2 test shares the ONE
module-scoped warmed ``world`` engine; the tp=4 and dp×tp sweep is
``slow``-marked with the tp=2 tests as its fast gate.
"""
import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import jax  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from mxnet_tpu import serving  # noqa: E402
from mxnet_tpu.serving import ServerClosed  # noqa: E402
from mxnet_tpu.serving.llm import (  # noqa: E402
    TinyDecoder, LLMServer, greedy_decode_reference,
    prefix_block_hashes)
from mxnet_tpu.serving.llm.engine import LLMEngine  # noqa: E402
from mxnet_tpu.serving.llm.metrics import LLMStats  # noqa: E402
from mxnet_tpu.serving.llm.scheduler import Sequence  # noqa: E402
from mxnet_tpu.serving.llm.sampling import SamplingParams  # noqa: E402
from mxnet_tpu.serving.adapters.bank import AdapterBank  # noqa: E402
from mxnet_tpu.parallel.mesh import llm_mesh  # noqa: E402
from mxnet_tpu.resilience import faults  # noqa: E402

VOCAB, BS, CTX = 23, 8, 64


@pytest.fixture(autouse=True)
def _reset_faults():
    faults.reset()
    yield
    faults.reset()


@pytest.fixture(scope="module")
def model():
    # 4 heads so the same model shards at tp=1/2/4
    return TinyDecoder(vocab_size=VOCAB, d_model=16, num_layers=2,
                       num_heads=4, d_ff=32, max_context=CTX)


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(0)


@pytest.fixture(scope="module")
def draft():
    return TinyDecoder(vocab_size=VOCAB, d_model=16, num_layers=1,
                       num_heads=4, d_ff=32, max_context=CTX)


@pytest.fixture(scope="module")
def dparams(draft):
    return draft.init_params(1)


def _tiny_bank():
    bank = AdapterBank(num_layers=2, d_model=16, max_adapters=4,
                       page_rank=2, max_pages_per_adapter=2)
    rs = np.random.RandomState(3)
    bank.publish("tiny",
                 (rs.randn(2, 4, 16, 2) * 0.1).astype(np.float32),
                 (rs.randn(2, 4, 2, 16) * 0.1).astype(np.float32))
    return bank


@pytest.fixture(scope="module")
def bank():
    return _tiny_bank()


@pytest.fixture(scope="module")
def world(model, params, draft, dparams, bank):
    """The ONE warmed tp=2 engine every fast SPMD test shares:
    speculative draft, adapter bank, prefix cache — the full unified
    step, sharded. Tests drain it completely before returning."""
    eng = LLMEngine(model, params, mesh="tp=2", max_seqs=4,
                    block_size=BS, num_blocks=41, max_context=CTX,
                    prefill_chunk=8, draft_model=draft,
                    draft_params=dparams, spec_k=2,
                    adapter_bank=bank, prefix_cache=True,
                    stats=LLMStats(server="spmd_world"))
    eng.warmup()
    return eng


@pytest.fixture(scope="module")
def tp1(model, params):
    """Small tp=1 engine (shared): the shard_map-over-one-device
    wrapper whose streams must be bit-exact vs unsharded."""
    eng = LLMEngine(model, params, mesh="tp=1", max_seqs=2,
                    block_size=BS, num_blocks=17, max_context=32,
                    prefill_chunk=8, prefix_cache=True)
    eng.warmup()
    return eng


def _serve(engine, jobs, max_new=8):
    """Run jobs (prompt, sampling, adapter) to completion; returns
    generated streams in submit order. Asserts nothing died."""
    seqs = []
    for prompt, samp, ad in jobs:
        s = Sequence(list(prompt), max_new, sampling=samp, adapter=ad)
        engine.add(s)
        seqs.append(s)
    outs = {}
    for _ in range(600):
        if not engine.has_work():
            break
        engine.step()
        for s in engine.pop_finished():
            outs[s.seq_id] = list(s.generated)
    assert not engine.has_work(), "engine did not drain"
    dead = engine.pop_dead()
    assert not dead, f"sequences died: {dead}"
    return [outs[s.seq_id] for s in seqs]


# ------------------------------------------------------- mesh parsing --
def test_llm_mesh_spec_parsing():
    """llm_mesh: bare int = tp, dp defaults to 1 (never silently
    absorbs spare devices), dp=-1 absorbs explicitly."""
    m = llm_mesh("2")
    assert dict(zip(m.axis_names, m.devices.shape)) == {"dp": 1,
                                                        "tp": 2}
    m = llm_mesh("dp=2,tp=2")
    assert dict(zip(m.axis_names, m.devices.shape)) == {"dp": 2,
                                                        "tp": 2}
    n = len(jax.devices())
    m = llm_mesh("dp=-1,tp=2")
    assert dict(zip(m.axis_names, m.devices.shape)) == {"dp": n // 2,
                                                        "tp": 2}
    with pytest.raises(ValueError):
        llm_mesh("pp=2")
    with pytest.raises(ValueError):
        llm_mesh(f"tp={2 * n}")


def test_engine_rejects_dp_mesh(model, params):
    """The ENGINE owns only tp; a dp>1 mesh is a config error
    pointing at LLMServer, not a silent absorb."""
    with pytest.raises(ValueError, match="LLMServer"):
        LLMEngine(model, params, mesh="dp=2,tp=2", max_seqs=2,
                  block_size=BS, num_blocks=17, max_context=32)
    with pytest.raises(ValueError, match="not divisible by tp"):
        LLMEngine(model, params, mesh="tp=8", max_seqs=2,
                  block_size=BS, num_blocks=17, max_context=32)


# ------------------------------------------------- tp=1 bit-exactness --
def test_tp1_bitexact_greedy_and_sampled(model, params, tp1):
    """Acceptance gate: tp=1 is BIT-EXACT vs the unsharded engine —
    greedy AND sampled streams, token for token."""
    e0 = LLMEngine(model, params, max_seqs=2, block_size=BS,
                   num_blocks=17, max_context=32, prefill_chunk=8,
                   prefix_cache=True)
    e0.warmup()
    jobs = [
        ([1, 2, 3], None, None),
        ([4, 5, 6, 7, 8, 9, 10, 11, 12, 13], None, None),
        ([14, 15], SamplingParams(temperature=0.9, top_k=5, seed=7),
         None),
        ([3, 3, 3], SamplingParams(temperature=1.2, top_p=0.9,
                                   seed=11), None),
    ]
    base = _serve(e0, jobs)
    sharded = _serve(tp1, jobs)
    assert sharded == base
    for (prompt, samp, _), toks in zip(jobs, base):
        if samp is None:
            assert toks == greedy_decode_reference(model, params,
                                                   prompt, 8)


# ------------------------------------- tp=2: the mixed-traffic gate --
def test_tp2_mixed_traffic_zero_recompiles_one_dispatch(world, model,
                                                        params, bank):
    """Acceptance gate: mixed chunked-prefill + sampled + speculative
    + adapter traffic on the warmed tp=2 engine runs with ZERO
    recompiles and exactly ONE launch of the sharded unified step per
    ``engine.step()`` — and greedy rows match the eager reference."""
    jobs = [
        # 14-token prompt: a chunk longer than ``prefill_chunk``
        # (8), what the step's budget of 8 a slot has room for
        (list(range(1, 15)), None, None),
        ([4, 5, 6], SamplingParams(temperature=0.8, top_k=5, seed=7),
         None),
        ([13, 2, 1], None, "tiny"),
        ([3, 3, 3, 3], SamplingParams(temperature=1.1, top_p=0.9,
                                      seed=11), "tiny"),
    ]
    seqs = []
    for prompt, samp, ad in jobs:
        s = Sequence(list(prompt), 8, sampling=samp, adapter=ad)
        world.add(s)
        seqs.append(s)
    outs = {}
    steps = 0
    with serving.CompileCounter() as cc:
        while world.has_work():
            before = world.spmd_dispatches
            world.step()
            steps += 1
            assert world.spmd_dispatches == before + 1, \
                "unified step must be ONE device dispatch"
            for s in world.pop_finished():
                outs[s.seq_id] = list(s.generated)
            assert steps < 600
    assert cc.count == 0, f"{cc.count} steady-state recompiles"
    assert not world.pop_dead()
    res = [outs[s.seq_id] for s in seqs]
    assert res[0] == greedy_decode_reference(model, params,
                                             jobs[0][0], 8)
    assert res[2] == greedy_decode_reference(
        model, params, jobs[2][0], 8,
        lora=bank.adapter_arrays("tiny"))
    world.cache.check([])


def test_tp2_replicated_lora_pools_cached(world):
    """Regression (latent single-device assumption): the bank's A/B
    factor pools are replicated onto the mesh ONCE per publish, not
    re-placed per step — the memoized placement survives across
    steps while the pool identity is unchanged."""
    _serve(world, [([5, 6, 7], None, "tiny")], max_new=4)
    first = world._lora_placed
    assert first is not None
    _serve(world, [([6, 7, 8], None, "tiny")], max_new=4)
    assert world._lora_placed is first


def test_tp2_statusz_and_metrics_mesh_block(world):
    """Satellite: the flight-recorder statusz surface and the
    ``mxtpu_llm_spmd_*`` series expose mesh shape and per-shard KV
    placement."""
    ds = world.debug_status()
    mesh = ds["mesh"]
    assert mesh["devices"] == 2 and mesh["tp"] == 2
    kv = mesh["kv"]
    assert kv["axis"] == "tp" and kv["shards"] == 2
    assert kv["heads_per_shard"] == 2
    heads = sorted(tuple(p["heads"]) for p in kv["placement"])
    assert heads == [(0, 2), (2, 4)]        # every head exactly once
    assert all(len(p["devices"]) == 1 for p in kv["placement"])
    snap = world._stats.snapshot()
    assert snap["spmd_mesh_devices"] == 2
    assert snap["spmd_mesh_axes"] == {"tp": 2}
    assert snap["spmd_kv_heads_per_shard"] == 2
    assert snap["spmd_step_dispatches"] == world.spmd_dispatches > 0


def test_tp2_cow_preserves_sharding_one_allocator(world, model, params):
    """Regression (the COW single-device fix): a shared-prefix
    rewrite flows through the shard_map'd copy program, so the
    donated pools come back with their sharding INTACT — and the
    draft pool still rides the target allocator (ONE strict
    accounting)."""
    from jax.sharding import NamedSharding
    expected = NamedSharding(world.mesh, world.cache.pool_spec())

    def _sharded(pool):
        return pool.sharding.is_equivalent_to(expected, pool.ndim)

    assert world.cache.pool_spec() != P()
    assert _sharded(world.cache.k_pages)
    cow0 = world.cache.cow_count
    prompt = [17] * (2 * BS)                # two full blocks, aligned
    a = Sequence(prompt, 8)                 # long-lived first owner
    world.add(a)
    guard = 0
    while not a.generated:                  # A's blocks registered
        world.step()
        guard += 1
        assert guard < 50
    b = Sequence(prompt, 3)                 # hits all but last token
    world.add(b)
    while world.has_work():
        world.step()
    assert b.cache_hit_tokens == 2 * BS - 1
    assert world.cache.cow_count > cow0, \
        "block-aligned prefix hit must copy-on-write the last block"
    ref = greedy_decode_reference(model, params, prompt, 8)
    assert a.output_tokens() == ref
    assert b.output_tokens() == ref[:3]
    for pool in (world.cache.k_pages, world.cache.v_pages,
                 world.draft_cache.k_pages, world.draft_cache.v_pages):
        assert _sharded(pool), \
            "COW must hand the pools back with their sharding intact"
    # ONE allocator: the draft cache's own allocator is never touched
    assert world.draft_cache.allocator.num_used == 0
    world.cache.check([])


def test_tp2_allocator_fuzz_under_churn(world):
    """ONE-BlockAllocator invariant under randomized admission /
    completion churn on the sharded engine: exact per-block owner
    counts at EVERY step (leaks, double-owns and refcount drift all
    raise)."""
    rng = np.random.default_rng(0)
    live = []
    steps = 0
    while steps < 120:
        if len(live) < 4 and rng.random() < 0.5:
            prompt = list(rng.integers(1, VOCAB,
                                       size=int(rng.integers(1, 20))))
            s = Sequence(prompt, int(rng.integers(1, 8)),
                         adapter="tiny" if rng.random() < 0.3
                         else None)
            world.add(s)
            live.append(s)
        if not world.has_work():
            break
        world.step()
        steps += 1
        done = world.pop_finished()
        assert not world.pop_dead()
        live = [s for s in live if s not in done]
        world.cache.check([s.block_ids for s in live])
    while world.has_work():                 # drain the tail
        world.step()
        world.pop_finished()
    world.cache.check([])


# -------------------------------------- prefix cache: elastic resume --
def test_prefix_hashes_elastic_across_mesh_sizes(tp1, world):
    """Satellite invariant: prefix-cache hashes are pure token
    chains — NO mesh salt — so the hash a tp=1 engine registered is
    the hash a restarted tp=2 engine computes for the same prompt.
    Restart at a different mesh size resumes the namespace."""
    prefix = [19] * BS                      # one full block
    hashes = prefix_block_hashes(prefix, BS)
    _serve(tp1, [(prefix + [1], None, None)], max_new=2)
    assert tp1.cache.prefix_get(hashes[0]) is not None
    hits0 = tp1.prefix_hits
    _serve(tp1, [(prefix + [2], None, None)], max_new=2)
    assert tp1.prefix_hits > hits0
    # "restart" at tp=2: same tokens -> same hash -> a hit, and the
    # shared stream still matches the eager reference
    _serve(world, [(prefix + [1], None, None)], max_new=2)
    assert world.cache.prefix_get(hashes[0]) is not None
    hits0 = world.prefix_hits
    _serve(world, [(prefix + [2], None, None)], max_new=2)
    assert world.prefix_hits > hits0


# --------------------------------------------- kill-one-shard chaos --
def test_kill_one_shard_resolves_and_resumes(model, params):
    """Chaos satellite: a tp=2 server's worker dying mid-loop
    resolves EVERY in-flight Future typed, settles KV blocks and
    adapter-page refcounts clean, and a fresh engine at a DIFFERENT
    mesh size (tp=1) resumes the prefix-hash namespace."""
    bank2 = _tiny_bank()
    srv = LLMServer(model, params, name="spmd_chaos", mesh="tp=2",
                    max_seqs=2, block_size=BS, num_blocks=17,
                    max_context=32, prefill_chunk=8,
                    adapter_bank=bank2, prefix_cache=True)
    srv.warmup()
    srv.start()
    prefix = [21] * BS
    srv.submit(prefix + [1], 2).result(timeout=30)   # register prefix
    faults.crash_at_point("llm.worker", nth=2)
    futs = [srv.submit(prefix + [2 + i], 8,
                       adapter="tiny" if i == 0 else None)
            for i in range(3)]
    for f in futs:
        try:
            f.result(timeout=30)
        except BaseException:
            pass                            # typed outcome either way
    assert all(f.done() for f in futs)
    faults.reset()
    deadline_ok = False
    import time
    t0 = time.monotonic()
    while time.monotonic() - t0 < 10:
        if not srv.running:
            deadline_ok = True
            break
        time.sleep(0.01)
    assert deadline_ok
    with pytest.raises(ServerClosed):
        srv.submit([1], 1)
    eng = srv.engine
    assert eng.cache.allocator.num_used == 0
    eng.cache.check([])
    bank2.check()                           # adapter refcounts settled
    # elastic resume: a FRESH engine at tp=1 recomputes the same
    # hashes for the same tokens and rebuilds the shared namespace
    e1 = LLMEngine(model, params, mesh="tp=1", max_seqs=2,
                   block_size=BS, num_blocks=17, max_context=32,
                   prefill_chunk=8, prefix_cache=True)
    e1.warmup()
    _serve(e1, [(prefix + [1], None, None)], max_new=2)
    assert e1.cache.prefix_get(
        prefix_block_hashes(prefix, BS)[0]) is not None
    hits0 = e1.prefix_hits
    out = _serve(e1, [(prefix + [2], None, None)], max_new=4)
    assert e1.prefix_hits > hits0
    assert out[0] == greedy_decode_reference(model, params,
                                             prefix + [2], 4)


# --------------------------------------------------- dp replica groups --
def test_dp_replicas_behind_one_scheduler(model, params):
    """dp=2 replica groups: one server front end, two engines, ONE
    worker thread — least-loaded routing spreads sequences over both
    replicas and every generation matches the eager reference."""
    srv = LLMServer(model, params, name="spmd_dp", mesh="dp=2",
                    max_seqs=2, block_size=BS, num_blocks=17,
                    max_context=32, prefill_chunk=8)
    assert srv.dp == 2
    timings = srv.warmup()
    assert any(k.startswith("dp1.") for k in timings)
    srv.start()
    prompts = [[1 + i, 2, 3] for i in range(6)]
    futs = [srv.submit(p, 5) for p in prompts]
    for p, f in zip(prompts, futs):
        assert f.result(timeout=60).tokens == \
            greedy_decode_reference(model, params, p, 5)
    assert all(e.spmd_dispatches > 0 for e in srv._engines), \
        "least-loaded routing must feed BOTH replicas"
    st = srv.stats()
    assert st["dp"] == 2 and st["mesh"]["devices"] == 2
    ds = srv.debug_status()
    assert ds["dp"] == 2 and len(ds["engines"]) == 1
    srv.shutdown()
    for e in srv._engines:
        assert e.cache.allocator.num_used == 0
        e.cache.check([])


# ------------------------------------------------ slow: bigger meshes --
@pytest.mark.slow
def test_tp4_and_dp2tp2_sweep(model, params):
    """Structural sweep past the fast gate: tp=4 sharding and the
    dp=2 x tp=2 product mesh both reproduce the eager reference."""
    prompts = [[1, 2, 3], [4, 5, 6, 7, 8, 9], [10, 11]]
    refs = [greedy_decode_reference(model, params, p, 6)
            for p in prompts]
    e4 = LLMEngine(model, params, mesh="tp=4", max_seqs=2,
                   block_size=BS, num_blocks=17, max_context=32,
                   prefill_chunk=8)
    e4.warmup()
    out = _serve(e4, [(p, None, None) for p in prompts], max_new=6)
    assert out == refs
    srv = LLMServer(model, params, name="spmd_dp2tp2",
                    mesh="dp=2,tp=2", max_seqs=2, block_size=BS,
                    num_blocks=17, max_context=32, prefill_chunk=8)
    srv.warmup()
    srv.start()
    futs = [srv.submit(p, 6) for p in prompts]
    assert [f.result(timeout=60).tokens for f in futs] == refs
    srv.shutdown()
