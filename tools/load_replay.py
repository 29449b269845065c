#!/usr/bin/env python
"""Deterministic trace-driven load replay -> SLO status -> capacity.

The million-user proof harness (ROADMAP item 6, per-server half):
generate a SEEDED synthetic-but-realistic request trace — diurnal +
bursty arrivals (non-homogeneous Poisson by thinning), heavy-tail
bounded-Pareto prompt/output length mix, Zipf-skewed tenants — and
replay it open-loop (arrivals land at their scheduled wall times, the
server keeps up or sheds — the mode that measures capacity) or
closed-loop (N clients, next request only after the last answer — the
mode that measures latency under a fixed concurrency) against BOTH
serving front ends:

- ``ModelServer`` (single-shot, jitted matmul backend or any
  ``--model`` predictor artifact);
- ``LLMServer`` (continuous-batching decode, built-in TinyDecoder).

While traffic runs, a :class:`~mxnet_tpu.observability.timeseries.
TimeSeriesRing` records periodic registry snapshots; afterwards the
:class:`~mxnet_tpu.observability.slo.SLOEngine` evaluates declared
SLOs (availability = served/(served+shed+expired), latency-percentile
bound, TTFT bound for decode) with multi-window burn-rate status, and
:mod:`mxnet_tpu.observability.capacity` derives sustainable QPS/chip,
tokens/sec/chip and chips-per-M-users — every number read back out of
registry snapshots, never hand-entered — written as a
``CAPACITY_rNN.json`` where ``--out`` says via
``tools/perf_capture.emit_capacity_snapshot`` (an unhealthy replay is
written with ``"skipped"`` set and no value).

Determinism contract: a fixed ``--seed`` produces a BIT-IDENTICAL
request schedule (asserted by ``tests/test_slo_capacity.py`` and
re-checked in ``--smoke``); replay against warmed servers performs
ZERO steady-state XLA compiles (backend_compile-counter pinned), and
every replayed request resolves TYPED — the
served/shed/expired/evicted/failed partition sums exactly to the
number submitted, or the capacity report refuses itself.

    python tools/load_replay.py --smoke              # tiny CI gate
    python tools/load_replay.py --duration 30 --base-rps 50 \
        --frontend both --out .                      # committed run
    python tools/load_replay.py --fleet --duration 12 \
        --out .     # FleetRouter replay + mid-replay weight hot-swap

``--fleet`` (ISSUE 16) routes the same schedule through a
:class:`~mxnet_tpu.serving.fleet.FleetRouter` hosting both front ends
as named models (tenant-parity target map, interactive/batch lanes)
and hot-swaps the LLM's weights MID-REPLAY from a sharded checkpoint;
the aggregated report carries per-model and fleet-total
chips-per-M-users and refuses itself if the swap recompiled, dropped a
request, or failed to commit.
"""
import argparse
import datetime
import hashlib
import json
import math
import os
import sys
import tempfile
import threading
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


# Trace generation is deliberately import-light (numpy + stdlib): the
# schedule must be computable — and testable bit-identical — without
# touching jax or the serving stack.
class TraceSpec:
    """Parameters of one synthetic workload trace. Everything that
    influences the schedule lives here, so (spec, seed) -> schedule is
    a pure function and the spec block in the capacity report fully
    reproduces the run."""

    FIELDS = ("seed", "duration_s", "base_rps", "diurnal_period_s",
              "diurnal_amp", "burst_rate", "burst_mean_s", "burst_mult",
              "tenants", "tenant_skew", "prompt_min", "prompt_max",
              "prompt_alpha", "out_min", "out_max", "out_alpha",
              "deadline_ms")

    def __init__(self, seed=0, duration_s=10.0, base_rps=20.0,
                 diurnal_period_s=None, diurnal_amp=0.5,
                 burst_rate=0.2, burst_mean_s=0.5, burst_mult=3.0,
                 tenants=4, tenant_skew=1.2, prompt_min=2,
                 prompt_max=48, prompt_alpha=1.5, out_min=1,
                 out_max=16, out_alpha=1.3, deadline_ms=None):
        self.seed = int(seed)
        self.duration_s = float(duration_s)
        self.base_rps = float(base_rps)
        # one "day" defaults to the trace length: the replay sweeps a
        # full peak/trough cycle however short the run is
        self.diurnal_period_s = float(diurnal_period_s
                                      if diurnal_period_s
                                      else duration_s)
        self.diurnal_amp = float(diurnal_amp)
        if not (0.0 <= self.diurnal_amp < 1.0):
            raise ValueError("diurnal_amp must be in [0, 1)")
        self.burst_rate = float(burst_rate)      # burst starts / sec
        self.burst_mean_s = float(burst_mean_s)  # mean burst length
        self.burst_mult = float(burst_mult)      # rate multiplier
        self.tenants = int(tenants)
        self.tenant_skew = float(tenant_skew)    # zipf exponent
        self.prompt_min = int(prompt_min)
        self.prompt_max = int(prompt_max)
        self.prompt_alpha = float(prompt_alpha)  # bounded-pareto tail
        self.out_min = int(out_min)
        self.out_max = int(out_max)
        self.out_alpha = float(out_alpha)
        self.deadline_ms = deadline_ms
        if self.base_rps <= 0 or self.duration_s <= 0:
            raise ValueError("base_rps and duration_s must be > 0")

    def to_dict(self):
        return {k: getattr(self, k) for k in self.FIELDS}


def _bounded_pareto(u, lo, hi, alpha):
    """Inverse-CDF sample of a bounded Pareto(lo, hi, alpha) from one
    uniform draw — the heavy-tail length distribution (most requests
    short, a fat tail of long ones) real prompt/output mixes show."""
    lo, hi = float(lo), float(hi)
    if hi <= lo:
        return int(lo)
    ratio = (lo / hi) ** alpha
    x = lo / (1.0 - u * (1.0 - ratio)) ** (1.0 / alpha)
    return int(min(max(x, lo), hi))


def _tenant_weights(spec):
    """Zipf-ish share per tenant: w_k ~ 1/(k+1)^skew, normalized —
    tenant t00 dominates, the tail splits the rest."""
    w = np.array([1.0 / (k + 1) ** spec.tenant_skew
                  for k in range(spec.tenants)])
    return w / w.sum()


def generate_trace(spec):
    """The deterministic schedule: a list of request dicts
    ``{i, at_us, tenant, prompt_len, new_tokens}`` sorted by arrival.

    Arrivals are a non-homogeneous Poisson process sampled by
    thinning: rate(t) = base * (1 + amp*sin(2pi t/period)) *
    (burst_mult inside a burst window). Burst windows are drawn first
    (their own exponential process), then arrivals, then per-request
    attributes — all from ONE ``np.random.RandomState(seed)``, so the
    draw order is fixed and the schedule is bit-identical for a fixed
    spec (arrival times are quantized to integer microseconds to keep
    the artifact platform-stable)."""
    rng = np.random.RandomState(spec.seed)
    bursts = []
    if spec.burst_rate > 0 and spec.burst_mult > 1.0:
        t = 0.0
        while True:
            t += rng.exponential(1.0 / spec.burst_rate)
            if t >= spec.duration_s:
                break
            end = t + rng.exponential(spec.burst_mean_s)
            bursts.append((t, min(end, spec.duration_s)))
            t = end

    def in_burst(t):
        return any(a <= t < b for a, b in bursts)

    def rate_at(t):
        r = spec.base_rps * (1.0 + spec.diurnal_amp * math.sin(
            2.0 * math.pi * t / spec.diurnal_period_s))
        if in_burst(t):
            r *= spec.burst_mult
        return max(r, 0.0)

    rate_max = spec.base_rps * (1.0 + spec.diurnal_amp) \
        * max(spec.burst_mult, 1.0)
    arrivals = []
    t = 0.0
    while True:
        t += rng.exponential(1.0 / rate_max)
        if t >= spec.duration_s:
            break
        if rng.uniform() * rate_max <= rate_at(t):
            arrivals.append(t)

    weights = _tenant_weights(spec)
    trace = []
    for i, at in enumerate(arrivals):
        tenant = int(rng.choice(spec.tenants, p=weights))
        p_len = _bounded_pareto(rng.uniform(), spec.prompt_min,
                                spec.prompt_max, spec.prompt_alpha)
        n_out = _bounded_pareto(rng.uniform(), spec.out_min,
                                spec.out_max, spec.out_alpha)
        trace.append({
            "i": i,
            "at_us": int(round(at * 1e6)),
            "tenant": f"t{tenant:02d}",
            "prompt_len": p_len,
            "new_tokens": n_out,
        })
    return trace


def schedule_digest(trace):
    """SHA-256 over the canonical JSON schedule — the bit-identity
    witness the tests and the capacity report's audit block carry."""
    blob = json.dumps(trace, sort_keys=True,
                      separators=(",", ":")).encode()
    return hashlib.sha256(blob).hexdigest()


def request_rng(spec, req):
    """The per-request RNG: (seed, i) -> RandomState, the one
    derivation every replayed input (prompt tokens, feature vectors)
    draws from, so replay payloads are reproducible alongside the
    schedule."""
    return np.random.RandomState((spec.seed * 1000003 + req["i"])
                                 % (2 ** 31 - 1))


def prompt_tokens(spec, req, vocab):
    """The request's actual prompt tokens, derived deterministically
    from (seed, i) so the trace stays lengths-only but the replayed
    tokens are reproducible too."""
    return request_rng(spec, req).randint(
        0, vocab, size=req["prompt_len"]).tolist()


def tenant_prefix_tokens(spec, tenant, vocab, block_size):
    """Tenant ``t``'s deterministic shared system-prompt prefix
    (ISSUE 13): every one of the tenant's requests opens with these
    tokens, so Zipf-skewed replay traffic actually exercises the
    cross-request prefix cache the way production system prompts do.
    Seeded per (trace seed, tenant); length from the same
    bounded-Pareto family as the prompt mix, floored at one KV block
    so a prefix can be cached at all."""
    idx = int(str(tenant).lstrip("t"))
    rng = np.random.RandomState((spec.seed * 7919 + idx + 1)
                                % (2 ** 31 - 1))
    length = max(int(block_size),
                 _bounded_pareto(rng.uniform(), spec.prompt_min,
                                 spec.prompt_max, spec.prompt_alpha))
    return rng.randint(0, vocab, size=length).tolist()


def tenant_adapter(tenant):
    """Tenant ``t``'s deterministic LoRA adapter id (ISSUE 17): every
    third tenant — including the dominant Zipf head t00 — rides the
    base model (so replay batches mix adapter and adapter-less rows),
    the rest each get a per-tenant fine-tuned variant. A pure function
    of the tenant index, so the assignment is part of the trace's
    bit-identity."""
    idx = int(str(tenant).lstrip("t"))
    return None if idx % 3 == 0 else f"lora-t{idx:02d}"


def tenant_adapter_factors(spec, name, num_layers, d_model, rank=4):
    """Adapter ``name``'s deterministic A/B factors, seeded from
    (trace seed, name) — what the replay writes into the adapter
    registry so fault-in serves reproducible weights."""
    h = int(hashlib.sha256(f"{spec.seed}:{name}".encode())
            .hexdigest()[:8], 16)
    rng = np.random.RandomState(h % (2 ** 31 - 1))
    a = (rng.randn(num_layers, 4, d_model, rank) * 0.05
         ).astype(np.float32)
    b = (rng.randn(num_layers, 4, rank, d_model) * 0.05
         ).astype(np.float32)
    return a, b


# ------------------------------------------------------------ replay --

OUTCOMES = ("served", "shed", "expired", "evicted", "failed")


def _classify(exc):
    from mxnet_tpu.serving import (DeadlineExceededError, Overloaded,
                                   SequenceEvictedError)
    if isinstance(exc, DeadlineExceededError):
        return "expired"
    if isinstance(exc, Overloaded):          # incl. CircuitOpenError
        return "shed"
    if isinstance(exc, SequenceEvictedError):
        return "evicted"
    return "failed"


def _drain_futures(futs, outcomes, timeout=600):
    ttfts = []
    for fut in futs:
        try:
            res = fut.result(timeout=timeout)
            outcomes["served"] += 1
            ttft = getattr(res, "ttft_s", None)
            if ttft is not None:
                ttfts.append(ttft)
        except Exception as exc:
            outcomes[_classify(exc)] += 1
    return ttfts


def replay(server, trace, spec, submit_fn, *, open_loop=True,
           closed_workers=4, speed=1.0, result_timeout=600):
    """Drive one front end through the schedule.

    ``submit_fn(req) -> Future`` adapts the request dict to the
    server (typed submit-time sheds are classified here). Open loop:
    arrivals land at ``at_us/speed`` past replay start regardless of
    completions. Closed loop: ``closed_workers`` clients walk the
    schedule in order, each submitting its next request only after
    its previous one resolved (arrival times ignored).

    Returns ``(outcomes, ttfts, elapsed_s)`` where outcomes is the
    typed partition over the WHOLE schedule — it must sum to
    ``len(trace)`` or the run is unaccountable."""
    outcomes = {k: 0 for k in OUTCOMES}
    ttfts = []
    t0 = time.monotonic()
    if open_loop:
        futs = []
        for req in trace:
            lag = t0 + req["at_us"] / 1e6 / speed - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            try:
                futs.append(submit_fn(req))
            except Exception as exc:
                outcomes[_classify(exc)] += 1
        ttfts = _drain_futures(futs, outcomes, timeout=result_timeout)
    else:
        lock = threading.Lock()
        it = iter(trace)

        def client():
            while True:
                with lock:
                    req = next(it, None)
                if req is None:
                    return
                try:
                    fut = submit_fn(req)
                    res = fut.result(timeout=result_timeout)
                except Exception as exc:
                    with lock:
                        outcomes[_classify(exc)] += 1
                    continue
                with lock:
                    outcomes["served"] += 1
                    ttft = getattr(res, "ttft_s", None)
                    if ttft is not None:
                        ttfts.append(ttft)

        threads = [threading.Thread(target=client, daemon=True)
                   for _ in range(max(1, closed_workers))]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
    return outcomes, ttfts, time.monotonic() - t0


# --------------------------------------------------------- frontends --

def _serving_backend(dim, seed=7):
    """Jitted matmul backend: real XLA programs per bucket, so the
    zero-recompile pin means what it says on the single-shot path."""
    import jax
    import jax.numpy as jnp
    w = np.random.RandomState(seed).randn(dim, dim).astype(np.float32)

    def _fwd(b):
        return jnp.tanh(b @ w)

    jfn = jax.jit(_fwd)

    def fn(batch):
        return np.asarray(jfn(batch))
    return fn


def run_serving(args, spec, trace, ring):
    """Replay the schedule against a warmed ModelServer; returns the
    per-frontend result block."""
    from mxnet_tpu import serving
    dim = args.feature_dim
    if args.model:
        import mxnet_tpu as mx
        backend = mx.deploy.load_predictor(args.model)
        srv = serving.ModelServer(backend, name="replay",
                                  max_queue=args.max_queue)
    else:
        srv = serving.ModelServer(
            _serving_backend(dim), buckets=[1, 2, 4, 8],
            max_delay_ms=1.0, item_shape=(dim,), dtype="float32",
            name="replay", max_queue=args.max_queue)
    srv.start()
    srv.warmup()

    def submit(req):
        x = request_rng(spec, req).randn(dim).astype(np.float32)
        return srv.submit(x, deadline_ms=spec.deadline_ms,
                          tenant=req["tenant"])

    ring.record()
    interval = max(0.05, spec.duration_s / 40.0)
    ring.start(interval)
    with serving.CompileCounter() as cc:
        outcomes, _, elapsed = replay(
            srv, trace, spec, submit, open_loop=not args.closed,
            closed_workers=args.closed, speed=args.speed)
    ring.stop()
    ring.record()
    stats = srv.stats()
    server_label = srv._stats.server_label
    srv.shutdown()
    return {
        "frontend": "serving",
        "server": server_label,
        "outcomes": outcomes,
        "submitted": len(trace),
        "elapsed_s": round(elapsed, 3),
        "compiles_during_replay": cc.count,
        "tenants": stats["tenants"],
        "latency_ms": stats["latency_ms"],
    }


def run_llm(args, spec, trace, ring):
    """Replay the schedule against a warmed LLMServer; returns the
    per-frontend result block."""
    from mxnet_tpu import serving
    from mxnet_tpu.serving.llm import (TinyDecoder, DecoderConfig,
                                       LLMServer)
    from mxnet_tpu.serving.adapters import AdapterBank, AdapterRegistry
    model = TinyDecoder(DecoderConfig(
        vocab_size=32, d_model=32, num_layers=2, num_heads=2,
        d_ff=64, max_context=args.max_context))
    block_size = 16
    # per-Zipf-tenant LoRA adapters (ISSUE 17): traffic adapters live
    # in a registry only — the replay's acquires FAULT them in — and
    # never-acquired decoys pre-fill every pool page so each fault-in
    # must run the cold-LRU capacity eviction path. Sized so every
    # traffic adapter fits once the decoys are gone: no acquire can
    # ever fail.
    adapters = {f"t{k:02d}": tenant_adapter(f"t{k:02d}")
                for k in range(spec.tenants)}
    names = sorted({a for a in adapters.values() if a})
    bank = None
    if names:
        reg = AdapterRegistry(
            tempfile.mkdtemp(prefix="replay_adapters_"), num_shards=2)
        for nm in names:
            a, b = tenant_adapter_factors(spec, nm, model.num_layers,
                                          32)
            reg.save(nm, a, b, version=1)
        bank = AdapterBank(model.num_layers, 32,
                           max_adapters=len(names), page_rank=4,
                           registry=reg)
        j = 0
        while bank.stats()["pages_free"] > 0:
            da, db = tenant_adapter_factors(
                spec, f"replay-decoy-{j}", model.num_layers, 32)
            bank.publish(f"replay-decoy-{j}", da, db, persist=False)
            j += 1
    # prefix_cache pinned ON: the tenant system-prompt workload (and
    # the smoke's hit-rate gate) exists to exercise it, regardless of
    # the ambient MXNET_TPU_LLM_PREFIX_CACHE value
    # --weight-dtype int8/fp8 (ISSUE 20): serve the replay from a
    # per-channel quantized checkpoint — the engine quantizes at
    # construction and the capacity report gains the models-per-chip
    # column the smaller resident footprint buys
    wkw = {}
    if args.weight_dtype and args.weight_dtype != "float32":
        wkw["weight_dtype"] = args.weight_dtype
    srv = LLMServer(model, model.init_params(0), name="replay_llm",
                    max_seqs=args.max_seqs, block_size=block_size,
                    max_context=args.max_context,
                    max_queue=args.max_queue, prefix_cache=True,
                    adapter_bank=bank, **wkw)
    srv.warmup()
    srv.start()
    max_prompt = max(2, args.max_context // 2)
    # each Zipf tenant's requests share one deterministic system
    # prompt — the reuse pattern the prefix cache monetizes
    prefixes = {f"t{k:02d}": tenant_prefix_tokens(
        spec, f"t{k:02d}", model.vocab_size, block_size)
        for k in range(spec.tenants)}

    def submit(req):
        body = prompt_tokens(spec, req, model.vocab_size)
        toks = (prefixes[req["tenant"]] + body)[:max_prompt]
        return srv.submit(toks, req["new_tokens"],
                          deadline_ms=spec.deadline_ms,
                          tenant=req["tenant"],
                          adapter=adapters[req["tenant"]]
                          if bank is not None else None)

    ring.record()
    interval = max(0.05, spec.duration_s / 40.0)
    ring.start(interval)
    with serving.CompileCounter() as cc:
        outcomes, ttfts, elapsed = replay(
            srv, trace, spec, submit, open_loop=not args.closed,
            closed_workers=args.closed, speed=args.speed)
    ring.stop()
    ring.record()
    stats = srv.stats()
    srv.shutdown()
    ttfts.sort()

    def pct(p):
        if not ttfts:
            return None
        return ttfts[min(len(ttfts) - 1,
                         int(round(p / 100.0 * (len(ttfts) - 1))))]

    return {
        "frontend": "llm",
        "server": srv._stats.server_label,
        "outcomes": outcomes,
        "submitted": len(trace),
        "elapsed_s": round(elapsed, 3),
        "compiles_during_replay": cc.count,
        "tenants": stats["tenants"],
        "tokens_generated": stats["tokens_generated"],
        "ttft_ms": {"p50": round((pct(50) or 0) * 1e3, 3),
                    "p99": round((pct(99) or 0) * 1e3, 3)},
        # cross-request KV reuse over the tenant system prompts: the
        # hit rate belongs in the capacity report — saved prefill is
        # saved chip time
        "prefix": {
            "lookups": stats["prefix_lookups"],
            "hits": stats["prefix_hits"],
            "hit_rate": round(stats["prefix_hit_rate"], 4),
            "prefill_tokens_saved": stats["prefill_tokens_saved"],
            "evictions": stats["prefix_evictions"],
        },
        # quantized-weight footprint (ISSUE 20): measured device-
        # resident weight bytes + dtype — the models-per-chip input
        # the capacity model derives against its declared HBM budget
        "weights": {
            "dtype": stats["weight_dtype"],
            "bytes": stats["weight_bytes"],
            "params_per_chip": stats["weight_params_per_chip"],
        },
        # per-tenant LoRA economics: residency hits vs registry
        # fault-ins and the capacity evictions the fault-ins forced —
        # saved fault-ins are saved publish bandwidth, like saved
        # prefill is saved chip time
        "adapters": None if bank is None else {
            "per_tenant": adapters,
            "names": names,
            "pool": len(names),
            "bank": stats.get("adapters"),
        },
    }


# ------------------------------------------------------- fleet mode --
#
# ``--fleet`` replays the SAME seeded Zipf-tenant schedule against a
# FleetRouter hosting two named models — "chat" (LLMServer, TinyDecoder)
# and "rank" (ModelServer, jitted matmul) — with a weight hot-swap of
# "chat" fired mid-replay from a pre-written SHARDED checkpoint. The
# whole window (replay + publish + warmup of the v2 replica) runs under
# ONE CompileCounter: the zero-recompile pin covers the swap, because
# the chat builder reuses the same decoder model object (published
# weights enter the cached programs as traced arguments) and the rank
# builder reuses one shared jitted function. Outcomes are partitioned
# PER MODEL and the capacity report aggregates per-model and
# fleet-total chips-per-M-users under the same refusal contract.

FLEET_MODELS = ("chat", "rank")


def _fleet_target(req):
    """Tenant-parity target map: even tenants chat, odd tenants rank —
    deterministic from the schedule, so the per-model split is part of
    the trace's bit-identity."""
    return "chat" if int(req["tenant"].lstrip("t")) % 2 == 0 else "rank"


def _fleet_lane(req):
    """Every 4th request rides the batch lane; the rest are
    interactive — enough traffic on both lanes to exercise the
    router's lane accounting without starving either."""
    return "batch" if req["i"] % 4 == 3 else "interactive"


def run_fleet(args, spec, trace, ring):
    """Replay the schedule through a FleetRouter (open loop only),
    hot-swapping "chat" to v2 weights halfway through; returns the
    fleet result block with per-model typed partitions."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu import deploy, serving
    from mxnet_tpu.resilience.checkpoint import write_checkpoint
    from mxnet_tpu.serving.llm import (TinyDecoder, DecoderConfig,
                                       LLMServer)

    dim = args.feature_dim
    model = TinyDecoder(DecoderConfig(
        vocab_size=32, d_model=32, num_layers=2, num_heads=2,
        d_ff=64, max_context=args.max_context))
    block_size = 16

    def chat_builder(arrays):
        # same decoder object every build: the engine's programs are
        # cached ON the model, so the v2 replica warms compile-free
        return LLMServer(model, deploy.unflatten_params(arrays),
                         name="replay_fleet_chat",
                         max_seqs=args.max_seqs, block_size=block_size,
                         max_context=args.max_context,
                         max_queue=args.max_queue, prefix_cache=True)

    rank_jit = jax.jit(lambda w, b: jnp.tanh(b @ w))

    def rank_builder(arrays):
        w = arrays["w"]
        return serving.ModelServer(
            lambda batch: np.asarray(rank_jit(w, batch)),
            buckets=[1, 2, 4, 8], max_delay_ms=1.0, item_shape=(dim,),
            dtype="float32", name="replay_fleet_rank",
            max_queue=args.max_queue)

    # v2 weights go through the PR 7 sharded-manifest path BEFORE the
    # clock starts: publish() must find a committed checkpoint, and
    # writing it is not part of the serving window being measured
    ckpt_run = tempfile.mkdtemp(prefix="fleet_ckpt_")
    write_checkpoint(ckpt_run,
                     deploy.flatten_params(model.init_params(1)),
                     step=2, num_shards=2)

    router = serving.FleetRouter(name="replay_fleet")
    for name, builder, arrays in (
            ("chat", chat_builder,
             deploy.flatten_params(model.init_params(0))),
            ("rank", rank_builder,
             {"w": np.random.RandomState(7).randn(dim, dim)
              .astype(np.float32)})):
        srv = builder(arrays)
        srv.warmup()
        srv.start()
        router.add_model(name, srv, version=1, builder=builder)

    max_prompt = max(2, args.max_context // 2)
    prefixes = {f"t{k:02d}": tenant_prefix_tokens(
        spec, f"t{k:02d}", model.vocab_size, block_size)
        for k in range(spec.tenants)}

    def submit(req):
        lane = _fleet_lane(req)
        if _fleet_target(req) == "chat":
            body = prompt_tokens(spec, req, model.vocab_size)
            toks = (prefixes[req["tenant"]] + body)[:max_prompt]
            return router.submit("chat", toks, req["new_tokens"],
                                 deadline_ms=spec.deadline_ms,
                                 tenant=req["tenant"], lane=lane)
        x = request_rng(spec, req).randn(dim).astype(np.float32)
        return router.submit("rank", x, deadline_ms=spec.deadline_ms,
                             tenant=req["tenant"], lane=lane)

    outcomes = {m: {k: 0 for k in OUTCOMES} for m in FLEET_MODELS}
    submitted = dict.fromkeys(FLEET_MODELS, 0)
    swap = {"published": None, "error": None}

    def publisher():
        try:
            swap["published"] = router.publish("chat", 2,
                                               run_dir=ckpt_run)
        except Exception as exc:          # surfaced as a refusal gate
            swap["error"] = repr(exc)

    ring.record()
    ring.start(max(0.05, spec.duration_s / 40.0))
    with serving.CompileCounter() as cc:
        # the swap fires mid-replay, while both models carry live
        # traffic — that concurrency IS the thing being proven
        timer = threading.Timer(spec.duration_s / args.speed / 2.0,
                                publisher)
        timer.daemon = True
        timer.start()
        t0 = time.monotonic()
        futs, ttfts = [], []
        for req in trace:
            m = _fleet_target(req)
            submitted[m] += 1
            lag = t0 + req["at_us"] / 1e6 / args.speed \
                - time.monotonic()
            if lag > 0:
                time.sleep(lag)
            try:
                futs.append((m, submit(req)))
            except Exception as exc:
                outcomes[m][_classify(exc)] += 1
        for m, fut in futs:
            try:
                res = fut.result(timeout=600)
                outcomes[m]["served"] += 1
                ttft = getattr(res, "ttft_s", None)
                if ttft is not None:
                    ttfts.append(ttft)
            except Exception as exc:
                outcomes[m][_classify(exc)] += 1
        elapsed = time.monotonic() - t0
        timer.join(timeout=600)
    ring.stop()
    ring.record()

    # chat's decode-token total spans BOTH replicas (the v1 server
    # retired mid-window and its v2 replacement), so read it from the
    # registry summed across their server labels, not from one
    # server's stats()
    from mxnet_tpu.observability import get_registry
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from metrics_dump import parse_exposition
    finally:
        sys.path.pop(0)
    samples = parse_exposition(get_registry().expose())
    chat_tokens = sum(
        v for (n, lbls), v in samples.items()
        if n == "mxtpu_llm_tokens_generated_total"
        and dict(lbls).get("server", "").startswith("replay_fleet_chat"))
    chat_servers = sorted(
        dict(lbls)["server"] for (n, lbls), v in samples.items()
        if n == "mxtpu_llm_requests_submitted_total"
        and dict(lbls).get("server", "").startswith("replay_fleet_chat"))
    routed = {
        lane: int(sum(v for (n, lbls), v in samples.items()
                      if n == "mxtpu_fleet_routed_total"
                      and dict(lbls).get("lane") == lane))
        for lane in ("interactive", "batch")}

    # per-tenant attribution likewise spans the swap: sum the tenant
    # outcome counters across every server label the model used
    def _tenant_counts(metric, prefix):
        out = {}
        for (n, lbls), v in samples.items():
            if n != metric:
                continue
            d = dict(lbls)
            if not d.get("server", "").startswith(prefix) \
                    or d.get("outcome") not in ("submitted", "served"):
                continue
            t = out.setdefault(d["tenant"],
                               {"submitted": 0, "served": 0})
            t[d["outcome"]] += int(v)
        return out

    tenants = {
        "chat": _tenant_counts("mxtpu_llm_tenant_requests_total",
                               "replay_fleet_chat"),
        "rank": _tenant_counts("mxtpu_serving_tenant_requests_total",
                               "replay_fleet_rank"),
    }
    final_version = router.active_version("chat")
    router.shutdown()
    ttfts.sort()

    def pct(p):
        if not ttfts:
            return None
        return ttfts[min(len(ttfts) - 1,
                         int(round(p / 100.0 * (len(ttfts) - 1))))]

    return {
        "frontend": "fleet",
        "fleet": "replay_fleet",
        "models": {
            "chat": {"kind": "llm", "servers": chat_servers,
                     "submitted": submitted["chat"],
                     "outcomes": outcomes["chat"],
                     "tokens_generated": int(chat_tokens)},
            "rank": {"kind": "serving",
                     "servers": ["replay_fleet_rank"],
                     "submitted": submitted["rank"],
                     "outcomes": outcomes["rank"]},
        },
        "submitted": len(trace),
        "elapsed_s": round(elapsed, 3),
        "compiles_during_replay": cc.count,
        "swap": {"model": "chat", "to_version": 2,
                 "published": swap["published"],
                 "error": swap["error"],
                 "final_active_version": final_version,
                 "sharded_checkpoint": True},
        "lanes_routed": routed,
        "tenants": tenants,
        "ttft_ms": {"p50": round((pct(50) or 0) * 1e3, 3),
                    "p99": round((pct(99) or 0) * 1e3, 3)},
    }


def evaluate_and_report_fleet(args, spec, trace, blk, out_dir,
                              rings=None, flight_bundle=None):
    """Fleet capacity derivation + committed artifact.

    Per-model chips-per-M-users from the model's own typed partition
    over the replay window (chat is token-based like the llm front
    end, rank request-based like serving), summed into the fleet
    headline. ``build_report`` is deliberately NOT reused here: its
    per-server registry rates would split chat's traffic across the
    v1/v2 server labels the hot-swap creates — the per-model outcome
    partition is the accounting that stays whole across a swap."""
    from mxnet_tpu.observability import get_registry
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import perf_capture
    finally:
        sys.path.pop(0)

    chips = 1
    try:
        import jax
        chips = max(1, jax.local_device_count())
    except Exception:
        pass
    user_model = {"requests_per_user_per_s": args.rpu,
                  "tokens_per_user_per_s": args.tpu}
    elapsed = blk["elapsed_s"] or 1.0

    frontends, total = [], 0.0
    for name in FLEET_MODELS:
        m = blk["models"][name]
        oc = m["outcomes"]
        fe = {"kind": m["kind"], "model": name,
              "server": ",".join(m["servers"]),
              "window_s": elapsed,
              "submitted_qps": round(m["submitted"] / elapsed, 3),
              "served_qps": round(oc["served"] / elapsed, 3)}
        denom = oc["served"] + oc["shed"] + oc["expired"]
        fe["availability"] = round(oc["served"] / denom, 5) \
            if denom else None
        if m["kind"] == "llm":
            tps = m["tokens_generated"] / elapsed
            fe["tokens_per_sec"] = round(tps, 3)
            fe["tokens_per_sec_per_chip"] = round(tps / chips, 3)
            per_chip, demand = tps / chips, args.tpu
        else:
            fe["qps_per_chip"] = round(oc["served"] / elapsed / chips,
                                       3)
            per_chip, demand = oc["served"] / elapsed / chips, args.rpu
        if per_chip > 0:
            fe["chips_per_m_users"] = round(1e6 * demand / per_chip, 3)
            total += fe["chips_per_m_users"]
        frontends.append(fe)

    avails = [fe["availability"] for fe in frontends
              if fe["availability"] is not None]
    rec = {
        "metric": "fleet_chips_per_m_users",
        "unit": "chips / 1M users",
        "value": round(total, 3) if total > 0 else None,
        "frontends": frontends,
        "chips": chips,
        "user_model": user_model,
        "window_s": elapsed,
        "trace": {"spec": spec.to_dict(), "requests": len(trace),
                  "schedule_sha256": schedule_digest(trace)},
        "tenants": blk["tenants"],
        "outcomes": {m: blk["models"][m]["outcomes"]
                     for m in FLEET_MODELS},
        "compiles_during_replay": blk["compiles_during_replay"],
        "slo_attained": bool(avails) and all(
            a >= args.availability_target for a in avails),
        "detail": {"fleet": blk["fleet"], "swap": blk["swap"],
                   "lanes_routed": blk["lanes_routed"],
                   "ttft_ms": blk["ttft_ms"]},
    }

    # refusal gates: a swap that recompiled, dropped accounting, threw
    # untyped, or never landed cannot headline fleet capacity
    reasons = []
    if blk["compiles_during_replay"]:
        reasons.append(f"{blk['compiles_during_replay']} XLA "
                       "recompiles during the measured window "
                       "(hot-swap included)")
    for name in FLEET_MODELS:
        m = blk["models"][name]
        if sum(m["outcomes"].values()) != m["submitted"]:
            reasons.append(
                f"{name}: accounting drift — "
                f"{sum(m['outcomes'].values())} outcomes for "
                f"{m['submitted']} submissions")
        if m["outcomes"]["failed"]:
            reasons.append(f"{name}: {m['outcomes']['failed']} "
                           "untyped/unexpected failures")
    if blk["swap"]["error"]:
        reasons.append(f"hot-swap failed: {blk['swap']['error']}")
    elif blk["swap"]["published"] != blk["swap"]["to_version"] \
            or blk["swap"]["final_active_version"] \
            != blk["swap"]["to_version"]:
        reasons.append("hot-swap did not commit within the window")
    if reasons:
        rec["skipped"] = "; ".join(reasons)

    os.makedirs(out_dir, exist_ok=True)
    metrics_log = os.path.join(out_dir, "load_replay_metrics.jsonl")
    get_registry().write_snapshot(metrics_log)
    ts_log = persist_timeseries(
        rings or {},
        os.path.join(out_dir, "load_replay_timeseries.jsonl"))
    rec["_capture"] = {
        "tag": f"load_replay_fleet_seed{spec.seed}",
        "metrics_log": metrics_log,
        "timeseries_log": ts_log,
        "flight_bundle": flight_bundle,
        "captured_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }
    path = perf_capture.emit_capacity_snapshot(rec, out_dir=out_dir)
    return rec, path


# -------------------------------------------------- flight probe ----
#
# ISSUE 18: the replay doubles as the flight recorder's chaos proof.
# The recorder runs over the WHOLE window (every submit/admit/step/
# served event from both front ends lands in the ring), and a timer
# thread fires ONE InjectedCrash at a probe site mid-replay. The crash
# is caught in the probe thread and handed to ``crash_dump`` — the
# replay itself never notices, no future fails, the typed-partition
# and CompileCounter==0 refusal gates stay exactly as strict — but
# what lands on disk is a genuine crash-triggered post-mortem bundle
# captured while both servers carried live traffic.

def arm_flight_probe(args, spec, out_dir):
    """Enable the recorder and schedule the mid-replay crash probe;
    returns the probe state dict (``finish_flight_probe`` reaps it)."""
    from mxnet_tpu.observability import get_flightrecorder
    from mxnet_tpu.resilience import InjectedCrash, faults
    fl = get_flightrecorder()
    fl.enable(out_dir=out_dir)
    faults.crash_at_point("flight.replay_probe", nth=1)
    state = {"bundle": None, "timer": None, "recorder": fl}

    def probe():
        try:
            faults.point("flight.replay_probe")
        except InjectedCrash as exc:
            state["bundle"] = fl.crash_dump(exc, server="replay_probe")
        finally:
            # disarm: the injector must not stay hot past the probe
            # (an armed injector slows every check() in the hot path)
            faults.reset()

    timer = threading.Timer(spec.duration_s / args.speed / 2.0, probe)
    timer.daemon = True
    timer.start()
    state["timer"] = timer
    return state


def finish_flight_probe(state):
    """Join the probe timer; returns the bundle path (or None if the
    dump failed — the smoke treats that as a hard problem)."""
    if state is None:
        return None
    state["timer"].join(timeout=60)
    return state["bundle"]


def persist_timeseries(rings, path):
    """Write every frontend ring's raw snapshot records as JSONL —
    the same records the SLO engine and capacity model read, committed
    alongside the report so the derivation is auditable after the
    fact (and diffable against a flight bundle's metrics pair)."""
    with open(path, "w") as f:
        for frontend in sorted(rings):
            for rec in rings[frontend].records():
                f.write(json.dumps(
                    {"frontend": frontend, "ts": rec["ts"],
                     "metrics": rec["metrics"]},
                    sort_keys=True, default=repr) + "\n")
    return path


# ------------------------------------------------- SLO + capacity ----

def _replay_windows(duration_s):
    """Burn-rate windows scaled to the replay length (the env-driven
    default window LENGTHS assume a long-lived server; a bounded
    replay needs its windows inside the measured span). The burn
    THRESHOLDS still honor MXNET_TPU_SLO_{FAST,SLOW}_BURN."""
    from mxnet_tpu.observability import slo as slo_mod
    fast, slow = slo_mod.burn_thresholds()
    d = max(duration_s, 1.0)
    return [(d / 2.0, d / 12.0, fast, slo_mod.STATUS_PAGE),
            (d, d / 5.0, slow, slo_mod.STATUS_WARN)]


def _env_float(name, default):
    v = os.environ.get(name)
    try:
        return float(v) if v else default
    except ValueError:
        return default


def evaluate_and_report(args, spec, trace, results, rings, out_dir,
                        flight_bundle=None):
    """SLO evaluation + capacity derivation + committed artifact."""
    from mxnet_tpu.observability import SLO, SLOEngine, get_registry
    from mxnet_tpu.observability import capacity as cap_mod
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        import perf_capture
    finally:
        sys.path.pop(0)

    windows = _replay_windows(spec.duration_s)
    slo_reports, frontends, tenants = {}, [], {}
    for blk in results:
        ring = rings[blk["frontend"]]
        server = blk["server"]
        if blk["frontend"] == "serving":
            lat = SLO.latency("serving_latency", args.slo_latency_ms,
                              target=args.slo_target,
                              labels={"server": server})
            slos = [SLO.serving_availability(
                        "serving_availability", server,
                        target=args.availability_target), lat]
            frontends.append(("serving", server, lat, ring))
        else:
            lat = SLO.ttft("llm_ttft", args.slo_ttft_ms,
                           target=args.slo_target,
                           labels={"server": server})
            slos = [SLO.llm_availability(
                        "llm_availability", server,
                        target=args.availability_target), lat]
            frontends.append(("llm", server, lat, ring))
        engine = SLOEngine(slos, ring, windows=windows)
        slo_reports.update(engine.evaluate())
        tenants[blk["frontend"]] = blk["tenants"]

    chips = 1
    try:
        import jax
        chips = max(1, jax.local_device_count())
    except Exception:
        pass

    llm_weights = next((b.get("weights") for b in results
                        if b["frontend"] == "llm"), None)
    rec = cap_mod.build_report(
        rings[results[0]["frontend"]], slo_reports, frontends,
        chips=chips,
        user_model={"requests_per_user_per_s": args.rpu,
                    "tokens_per_user_per_s": args.tpu},
        trace={"spec": spec.to_dict(), "requests": len(trace),
               "schedule_sha256": schedule_digest(trace)},
        llm_weights=llm_weights)
    rec["tenants"] = tenants
    rec["outcomes"] = {b["frontend"]: b["outcomes"] for b in results}
    rec["compiles_during_replay"] = sum(b["compiles_during_replay"]
                                        for b in results)
    for blk in results:
        if blk["frontend"] == "llm" and "prefix" in blk:
            rec["llm_prefix"] = blk["prefix"]
        if blk["frontend"] == "llm" and blk.get("adapters"):
            rec["llm_adapters"] = blk["adapters"]

    # refusal gates: an unhealthy replay cannot headline capacity
    reasons = []
    if rec["compiles_during_replay"]:
        reasons.append(f"{rec['compiles_during_replay']} XLA "
                       "recompiles during the measured window")
    for blk in results:
        total = sum(blk["outcomes"].values())
        if total != blk["submitted"]:
            reasons.append(
                f"{blk['frontend']}: accounting drift — {total} "
                f"outcomes for {blk['submitted']} submissions")
        if blk["outcomes"]["failed"]:
            reasons.append(f"{blk['frontend']}: "
                           f"{blk['outcomes']['failed']} untyped/"
                           "unexpected failures")
    if reasons:
        rec["skipped"] = "; ".join(reasons)

    os.makedirs(out_dir, exist_ok=True)
    metrics_log = os.path.join(out_dir, "load_replay_metrics.jsonl")
    get_registry().write_snapshot(metrics_log)
    ts_log = persist_timeseries(
        rings, os.path.join(out_dir, "load_replay_timeseries.jsonl"))
    rec["_capture"] = {
        "tag": f"load_replay_seed{spec.seed}",
        "metrics_log": metrics_log,
        "timeseries_log": ts_log,
        "flight_bundle": flight_bundle,
        "captured_at": datetime.datetime.now(
            datetime.timezone.utc).isoformat(),
    }
    path = perf_capture.emit_capacity_snapshot(rec, out_dir=out_dir)
    return rec, path


# -------------------------------------------------------------- main --

def _smoke_check(args, spec, trace, results, rec, cap_path,
                 flight_bundle=None):
    """The CI gate: determinism, zero recompiles, exact typed
    partition, a well-formed committed capacity report, a clean
    exposition, a verified crash-triggered flight bundle, and the
    persisted time-series records."""
    from mxnet_tpu.observability import get_registry
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    try:
        from metrics_dump import parse_exposition
        from flight_inspect import check as flight_check
    finally:
        sys.path.pop(0)
    probs = []
    if schedule_digest(generate_trace(spec)) != schedule_digest(trace):
        probs.append("schedule not bit-identical across generations")
    for blk in results:
        if blk["compiles_during_replay"]:
            probs.append(f"{blk['frontend']}: "
                         f"{blk['compiles_during_replay']} recompiles")
        if sum(blk["outcomes"].values()) != blk["submitted"]:
            probs.append(f"{blk['frontend']}: partition "
                         f"{blk['outcomes']} != {blk['submitted']}")
        if blk["outcomes"]["failed"]:
            probs.append(f"{blk['frontend']}: unexpected failures")
        if not blk["tenants"]:
            probs.append(f"{blk['frontend']}: no tenant attribution")
        if blk["frontend"] == "llm":
            pf = blk.get("prefix", {})
            if not pf.get("hits"):
                probs.append("llm: tenant system prompts produced no "
                             "prefix-cache hits")
            if ("llm_prefix" not in rec
                    or rec["llm_prefix"].get("hit_rate") is None):
                probs.append("capacity report carries no llm_prefix "
                             "hit-rate block")
            ad = (blk.get("adapters") or {}).get("bank") or {}
            if not ad.get("acquire_hits"):
                probs.append("llm: tenant adapters produced no "
                             "residency hits")
            if ad.get("registry_loads", 0) \
                    < len((blk.get("adapters") or {}).get("names", [])):
                probs.append("llm: not every tenant adapter was "
                             "faulted in from the registry")
            if not (ad.get("evictions") or {}).get("capacity"):
                probs.append("llm: fault-ins forced no cold-LRU "
                             "capacity eviction (decoy survived)")
            if ("llm_adapters" not in rec
                    or (rec["llm_adapters"].get("bank") or {})
                    .get("acquires") is None):
                probs.append("capacity report carries no llm_adapters "
                             "hit/evict block")
    with open(cap_path) as f:
        cap = json.load(f)
    if cap.get("skipped"):
        probs.append(f"capacity report skipped: {cap['skipped']}")
    if cap.get("value") is None:
        probs.append("capacity report has no headline value")
    for fe in cap.get("frontends") or []:
        if fe.get("chips_per_m_users") is None:
            probs.append(f"{fe.get('kind')}: no chips_per_m_users")
    if not cap.get("slo"):
        probs.append("capacity report carries no SLO block")
    else:
        for name, rep in cap["slo"].items():
            if rep.get("status_name") not in ("ok", "warn", "page",
                                              "breach"):
                probs.append(f"SLO {name}: no status")
    try:
        samples = parse_exposition(get_registry().expose())
    except ValueError as exc:
        samples = {}
        probs.append(f"exposition malformed after replay: {exc}")
    for prefix in ("mxtpu_slo_attainment", "mxtpu_slo_status",
                   "mxtpu_slo_burn_rate", "mxtpu_ts_snapshots_total",
                   "mxtpu_serving_tenant_requests_total",
                   "mxtpu_llm_tenant_requests_total",
                   "mxtpu_flight_events_total",
                   "mxtpu_flight_dumps_total"):
        if not any(n.startswith(prefix) for n, _ in samples):
            probs.append(f"no {prefix}* series in exposition")
    # flight recorder (ISSUE 18): the mid-replay probe must have
    # produced a complete, CRC-verified crash bundle
    if not flight_bundle:
        probs.append("mid-replay probe produced no flight bundle")
    else:
        for p in flight_check(flight_bundle):
            probs.append(f"flight bundle: {p}")
        try:
            with open(os.path.join(flight_bundle,
                                   "MANIFEST.json")) as f:
                man = json.load(f)
            if man.get("trigger") != "crash":
                probs.append("flight bundle trigger is "
                             f"{man.get('trigger')!r}, not 'crash'")
            if not man.get("stats", {}).get("recorded"):
                probs.append("flight bundle recorded no events")
        except Exception as exc:
            probs.append(f"flight manifest unreadable: {exc!r}")
    ts_log = (rec.get("_capture") or {}).get("timeseries_log")
    if not ts_log or not os.path.exists(ts_log):
        probs.append("no persisted time-series snapshots")
    else:
        with open(ts_log) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        if len(lines) < 4:
            probs.append(f"time-series log holds only {len(lines)} "
                         "snapshots")
        if any("metrics" not in ln or "frontend" not in ln
               for ln in lines):
            probs.append("time-series records missing frontend/"
                         "metrics fields")
    return probs


def main():
    ap = argparse.ArgumentParser(
        description=__doc__.splitlines()[0],
        formatter_class=argparse.ArgumentDefaultsHelpFormatter)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--duration", type=float, default=10.0,
                    help="trace length in seconds")
    ap.add_argument("--base-rps", type=float, default=20.0)
    ap.add_argument("--diurnal-amp", type=float, default=0.5)
    ap.add_argument("--diurnal-period", type=float, default=0.0,
                    help="seconds per diurnal cycle (0 = one cycle "
                         "over the whole trace)")
    ap.add_argument("--burst-rate", type=float, default=0.2,
                    help="expected burst windows per second")
    ap.add_argument("--burst-mult", type=float, default=3.0)
    ap.add_argument("--tenants", type=int, default=4)
    ap.add_argument("--tenant-skew", type=float, default=1.2)
    ap.add_argument("--deadline-ms", type=float, default=0.0,
                    help="per-request end-to-end deadline (0 = none)")
    ap.add_argument("--frontend", choices=("serving", "llm", "both"),
                    default="both")
    ap.add_argument("--fleet", action="store_true",
                    help="replay through a FleetRouter (chat=LLM + "
                         "rank=single-shot, tenant-parity target map, "
                         "lanes) with a chat weight hot-swap from a "
                         "sharded checkpoint fired mid-replay; emits "
                         "an aggregated fleet capacity report and "
                         "exits nonzero if it refused itself")
    ap.add_argument("--closed", type=int, default=0,
                    help="closed-loop client count (0 = open loop at "
                         "scheduled arrival times)")
    ap.add_argument("--speed", type=float, default=1.0,
                    help="open-loop time compression (2 = replay the "
                         "trace twice as fast as scheduled)")
    ap.add_argument("--model", default=None,
                    help="predictor artifact for the serving front "
                         "end (default: built-in jitted matmul)")
    ap.add_argument("--feature-dim", type=int, default=16)
    ap.add_argument("--max-seqs", type=int, default=4)
    ap.add_argument("--max-context", type=int, default=64)
    ap.add_argument("--max-queue", type=int, default=512)
    ap.add_argument("--weight-dtype",
                    choices=("float32", "int8", "fp8"),
                    default="float32",
                    help="LLM front-end weight storage dtype: "
                         "int8/fp8 serves the replay from a per-"
                         "channel quantized checkpoint, and the "
                         "capacity report derives the models-per-chip "
                         "delta from the measured weight bytes")
    ap.add_argument("--slo-latency-ms", type=float,
                    default=_env_float("MXNET_TPU_SLO_LATENCY_MS",
                                       250.0))
    ap.add_argument("--slo-ttft-ms", type=float,
                    default=_env_float("MXNET_TPU_SLO_TTFT_MS", 2500.0))
    ap.add_argument("--slo-target", type=float,
                    default=_env_float("MXNET_TPU_SLO_TARGET", 0.99),
                    help="latency/TTFT SLO target fraction")
    ap.add_argument("--availability-target", type=float, default=0.99)
    ap.add_argument("--rpu", type=float, default=0.005,
                    help="assumed requests/sec per active user")
    ap.add_argument("--tpu", type=float, default=1.5,
                    help="assumed decode tokens/sec per active user")
    ap.add_argument("--out", default=None,
                    help="directory for CAPACITY_rNN.json (default: "
                         "a temp dir, printed)")
    ap.add_argument("--trace-only", action="store_true",
                    help="print the schedule digest + first requests "
                         "and exit (no servers)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny CI run against BOTH front ends; fail "
                         "on recompiles, accounting drift, a "
                         "malformed capacity report, or a dirty "
                         "exposition")
    args = ap.parse_args()

    if args.smoke:
        args.duration = min(args.duration, 2.5)
        args.base_rps = min(args.base_rps, 16.0)
        args.frontend = "both"
        args.max_context = min(args.max_context, 64)
        args.max_seqs = min(args.max_seqs, 4)

    spec = TraceSpec(
        seed=args.seed, duration_s=args.duration,
        base_rps=args.base_rps, diurnal_amp=args.diurnal_amp,
        diurnal_period_s=args.diurnal_period or None,
        burst_rate=args.burst_rate, burst_mult=args.burst_mult,
        tenants=args.tenants, tenant_skew=args.tenant_skew,
        prompt_max=max(2, args.max_context // 2),
        deadline_ms=args.deadline_ms if args.deadline_ms > 0 else None)
    trace = generate_trace(spec)
    digest = schedule_digest(trace)
    print(f"trace: {len(trace)} requests over {spec.duration_s}s "
          f"(seed {spec.seed}, sha256 {digest[:16]}...)")
    if args.trace_only:
        print(json.dumps(trace[:10], indent=1))
        return 0

    from mxnet_tpu.observability import TimeSeriesRing, get_registry
    out_dir = args.out or tempfile.mkdtemp(prefix="load_replay_")
    os.makedirs(out_dir, exist_ok=True)
    flight = arm_flight_probe(args, spec, out_dir)
    if args.fleet:
        if args.closed:
            print("--fleet is open-loop only (the swap must land "
                  "against scheduled arrivals)", file=sys.stderr)
            return 2
        ring = TimeSeriesRing(get_registry())
        blk = run_fleet(args, spec, trace, ring)
        print(json.dumps(blk, indent=1))
        bundle = finish_flight_probe(flight)
        if bundle:
            print(f"FLIGHT bundle -> {bundle}")
        rec, cap_path = evaluate_and_report_fleet(
            args, spec, trace, blk, out_dir, rings={"fleet": ring},
            flight_bundle=bundle)
        print(f"CAPACITY json -> {cap_path}")
        print(json.dumps({k: rec[k] for k in
                          ("value", "unit", "slo_attained", "chips",
                           "window_s") if k in rec}, indent=1))
        if rec.get("skipped"):
            print(f"FLEET REFUSED: {rec['skipped']}")
            return 1
        print("FLEET OK")
        return 0

    results, rings = [], {}
    if args.frontend in ("serving", "both"):
        rings["serving"] = TimeSeriesRing(get_registry())
        results.append(run_serving(args, spec, trace,
                                   rings["serving"]))
        print(json.dumps(results[-1], indent=1))
    if args.frontend in ("llm", "both"):
        rings["llm"] = TimeSeriesRing(get_registry())
        results.append(run_llm(args, spec, trace, rings["llm"]))
        print(json.dumps(results[-1], indent=1))

    bundle = finish_flight_probe(flight)
    if bundle:
        print(f"FLIGHT bundle -> {bundle}")
    rec, cap_path = evaluate_and_report(args, spec, trace, results,
                                        rings, out_dir,
                                        flight_bundle=bundle)
    print(f"CAPACITY json -> {cap_path}")
    print(json.dumps({k: rec[k] for k in
                      ("value", "unit", "slo_attained", "slo_statuses",
                       "chips", "window_s") if k in rec}, indent=1))

    if args.smoke:
        probs = _smoke_check(args, spec, trace, results, rec, cap_path,
                             flight_bundle=bundle)
        if probs:
            for p in probs:
                print(f"SMOKE problem: {p}")
            print("SMOKE FAIL")
            return 1
        print("SMOKE PASS")
    return 0


if __name__ == "__main__":
    sys.exit(main())
