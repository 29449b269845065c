"""LLMServer: the request-facing front end of the decode engine.

Reuses the ``ModelServer`` plumbing contracts (PR 2) on top of
:class:`~.engine.LLMEngine`: many threads submit prompts and get
Futures; ONE worker thread drives the engine loop (admit → step →
retire, every iteration); ``warmup()`` pre-compiles every reachable
program so steady state never hits XLA; drain on shutdown or
preemption resolves EVERY Future.

What decode adds over single-shot serving is drain *semantics*: an
in-flight sequence is minutes of state, not one forward pass. So drain
runs the engine until every live sequence completes OR a deadline
(``deadline_ms`` arg > ``MXNET_TPU_SERVE_DRAIN_DEADLINE_MS`` env >
unbounded) expires — past it, live sequences are rejected with a typed
:class:`SequenceEvictedError` CARRYING the tokens generated so far.
A caller always gets either its full generation or a partial one
under a typed error; nothing is silently dropped.

Overload & failure semantics (docs/SERVING.md):

- ``submit(..., deadline_ms=)`` (env ``MXNET_TPU_SERVE_DEADLINE_MS``)
  puts an END-TO-END deadline on the generation: expired while
  waiting → failed before any prefill; expired mid-decode → evicted
  with partial tokens; both resolve with a typed
  :class:`~..errors.DeadlineExceededError`;
- admission is bounded (``MXNET_TPU_SERVE_MAX_QUEUE`` counts
  pending + waiting sequences — the backlog that holds no KV blocks
  yet); past the bound ``submit`` sheds with a typed
  :class:`~..errors.Overloaded` instead of growing the queue;
- ``generate(..., timeout=)`` CANCELS the underlying sequence on
  timeout: its KV blocks and decode slot are released and the Future
  resolves typed — an abandoned caller cannot leak pool blocks;
- poison prompts (prefill raises) and poison decode rows (bisect
  isolation in the engine) fail ONLY their own Future, with the
  original exception; persistent dispatch failures trip the shared
  :class:`~..overload.CircuitBreaker` and submits fail fast with
  :class:`~..errors.CircuitOpenError` until a half-open probe heals;
- a dying worker (chaos point ``llm.worker``) resolves every live
  Future and frees every KV block before the thread exits.

Observability: per-request hand-off spans (``mxtpu.llm.request``
opened under the caller's context, finished by the worker with
queue-wait/ttft/token counts); on the worker thread one
``mxtpu.llm.step`` an engine iteration with the children
``mxtpu.llm.schedule``, ``.build``, ``.launch``, ``.fetch`` and
``.commit`` (``mxtpu.llm.isolate`` under it on the failure path) and
its siblings ``mxtpu.llm.wait`` around this loop's head and
``mxtpu.llm.resolve`` around its flush, so that the leaves cover the
worker's time between them; the same boundaries mark the worker's
:class:`~.metrics.PhaseClock`, always on, which feeds
``mxtpu_llm_step_phase_seconds_total`` and the stall series; the
``mxtpu_llm_*`` registry series (:class:`~.metrics.LLMStats`) and the
shared ``mxtpu_serving_{shed,deadline_expired,poison_isolated,
breaker_state}`` overload series. Spans record while the tracer is on
or any jax profiler capture runs (docs/OBSERVABILITY.md).
"""
from __future__ import annotations

import os
import threading
import time

import numpy as np

from ..errors import (DeadlineExceededError, Overloaded,
                      SequenceEvictedError, ServerClosed)
from ..adapters.bank import UnknownAdapterError
from ..envutil import env_float as _env_float
from ..overload import (CircuitBreaker, resolve_deadline,
                        resolve_overload_knobs, shed_if_breaker_open)
from .engine import LLMEngine
from .metrics import LLMStats, PhaseClock, hand_over
from .sampling import SamplingParams
from .scheduler import Sequence
from ..telemetry import compile_count
from ...observability.tracing import get_tracer
from ...observability.flightrecorder import get_flightrecorder
from ...resilience import faults

__all__ = ["LLMServer", "SequenceEvictedError", "GenerationResult"]


def _resolve_server_mesh(mesh):
    """Split the server-level decode mesh into per-replica tp rows.

    The SERVER owns the ``dp`` axis (replica groups of engines behind
    one scheduler thread); each :class:`~.engine.LLMEngine` owns only
    ``tp`` (tensor-parallel shards fused into its one step program).
    Accepts a ``jax.sharding.Mesh``, a spec string for
    :func:`~...parallel.mesh.llm_mesh` (``"tp=2"``, ``"dp=2,tp=2"``,
    bare ``"4"`` = tp), or ``None`` with the ``MXNET_TPU_LLM_MESH``
    env var as fallback. Returns ``(info, submeshes)`` where ``info``
    is ``{"devices", "dp", "tp"}`` and ``submeshes`` is one flat
    tp-only Mesh per dp replica — or ``(None, None)`` unsharded."""
    if mesh is None:
        mesh = os.environ.get("MXNET_TPU_LLM_MESH", "").strip() or None
    if mesh is None:
        return None, None
    if isinstance(mesh, str):
        from ...parallel.mesh import llm_mesh
        mesh = llm_mesh(mesh)
    axes = dict(zip(mesh.axis_names, mesh.devices.shape))
    for ax, n in axes.items():
        if ax not in ("dp", "tp") and n != 1:
            raise ValueError(
                f"LLMServer shards over dp/tp only; mesh axis "
                f"{ax!r} has extent {n}")
    dp = int(axes.get("dp", 1))
    tp = int(axes.get("tp", 1))
    arr = np.asarray(mesh.devices)
    names = list(mesh.axis_names)
    if "dp" in names:
        arr = np.moveaxis(arr, names.index("dp"), 0)
    else:
        arr = arr[None, ...]
    arr = arr.reshape(dp, tp)
    from jax.sharding import Mesh
    subs = [Mesh(arr[i], ("tp",)) for i in range(dp)]
    info = {"devices": int(arr.size), "dp": dp, "tp": tp}
    return info, subs


class GenerationResult:
    """A completed generation: ``tokens`` (ints, prompt excluded),
    ``seq_id``, ``ttft_s``, ``finish_reason``. From a model that
    generates by diffusion over blocks also ``reveal_pass``, for each
    token the denoising pass of its block that revealed it (0 the
    first), and ``block_tail``, ``(token, pass)`` of the positions its
    last block computed past the answer's length; both empty
    otherwise."""

    __slots__ = ("tokens", "seq_id", "ttft_s", "finish_reason",
                 "reveal_pass", "block_tail")

    def __init__(self, tokens, seq_id, ttft_s, finish_reason,
                 reveal_pass=(), block_tail=()):
        self.tokens = tokens
        self.seq_id = seq_id
        self.ttft_s = ttft_s
        self.finish_reason = finish_reason
        self.reveal_pass = list(reveal_pass)
        self.block_tail = list(block_tail)

    def __repr__(self):
        return (f"GenerationResult(seq={self.seq_id}, "
                f"tokens={len(self.tokens)}, "
                f"reason={self.finish_reason!r})")


class LLMServer:
    """Serve autoregressive decoding (greedy or sampled) with
    continuous batching.

    ``model``/``params``: a decoder in paged form (see
    :class:`~.model.TinyDecoder`) and its parameter pytree. Engine
    kwargs (``max_seqs``, ``block_size``, ``num_blocks``,
    ``max_context``, ``prefill_chunk``, and ``draft_model`` /
    ``draft_params`` / ``spec_k`` for speculative decoding) pass
    through to :class:`~.engine.LLMEngine`, each defaulting to its
    ``MXNET_TPU_LLM_*`` env var.

    ``mesh`` (optional; env ``MXNET_TPU_LLM_MESH``): a decode mesh —
    a ``jax.sharding.Mesh`` or an :func:`~...parallel.mesh.llm_mesh`
    spec string (``"tp=2"``, ``"dp=2,tp=2"``, bare ``"4"`` = tp).
    The server consumes the ``dp`` axis as replica groups: one
    :class:`~.engine.LLMEngine` per dp row (each on its own flat
    tp-only submesh, tensor-parallel via shard_map), all behind this
    one front end — submit routes each sequence to the least-loaded
    replica; drain/failure semantics cover every replica's Futures.

    Overload knobs: ``max_queue``
    (``MXNET_TPU_SERVE_MAX_QUEUE``), ``deadline_ms``
    (``MXNET_TPU_SERVE_DEADLINE_MS``), ``breaker_threshold`` /
    ``breaker_cooldown_ms`` (``MXNET_TPU_SERVE_BREAKER_*``).
    """

    def __init__(self, model, params, name="llm", max_queue=None,
                 deadline_ms=None, breaker_threshold=None,
                 breaker_cooldown_ms=None, mesh=None, **engine_kw):
        self.name = name
        self._stats = LLMStats(server=name)
        self._flight = get_flightrecorder()
        # the worker thread's phase clock: one thread, so one clock,
        # however many engines it steps
        self._clock = PhaseClock()
        self._breaker = CircuitBreaker(
            threshold=breaker_threshold,
            cooldown_ms=breaker_cooldown_ms,
            on_state=self._on_breaker_state)
        # dp replica groups: the server consumes the dp axis (one
        # engine per replica row, all driven by the ONE worker thread
        # below); each engine gets a flat tp-only submesh and fuses
        # its shards into its single step program
        self._mesh_info, submeshes = _resolve_server_mesh(mesh)
        if submeshes is None:
            self._engines = [LLMEngine(model, params,
                                       stats=self._stats,
                                       breaker=self._breaker,
                                       clock=self._clock,
                                       **engine_kw)]
        else:
            self._engines = [LLMEngine(model, params,
                                       stats=self._stats,
                                       breaker=self._breaker,
                                       clock=self._clock,
                                       mesh=sub, **engine_kw)
                             for sub in submeshes]
            # engines published their tp-submesh shape; overwrite
            # with the full fleet view (dp included) once
            self._stats.record_spmd_mesh(
                self._mesh_info["devices"],
                {"dp": self._mesh_info["dp"],
                 "tp": self._mesh_info["tp"]},
                self._engines[0].cache.heads_per_shard)
        self._engine = self._engines[0]
        self.dp = len(self._engines)
        self.max_queue, self.default_deadline_ms = \
            resolve_overload_knobs(max_queue, deadline_ms)
        self._cv = threading.Condition()
        self._pending = []            # guarded-by: _cv
        self._closed = False          # guarded-by: _cv
        self._drain = True            # guarded-by: _cv
        self._deadline = None         # guarded-by: _cv
        # quiesce/resume (fleet hot-swap drain): admission gate +
        # exact live-Future count via done-callbacks — quiesce() waits
        # on _live, not on engine polling, so the gap between popping
        # _pending and engine.add() can never look "drained"
        self._quiesced = False        # guarded-by: _cv
        self._live = 0                # guarded-by: _cv
        self._worker = None
        self._started = False
        self._guard_watcher = None
        self._guard_stop = threading.Event()
        self._flight.register(f"llm:{name}", self)

    def _on_breaker_state(self, state):
        """Breaker transition observer: the gauge plus one flight
        control-plane event (the recorder names the moment the fleet
        degraded to rejection)."""
        self._stats.record_breaker_state(state)
        fl = self._flight
        if fl.enabled:
            fl.event("breaker", attrs={"server": self.name,
                                       "state": state})

    # -------------------------------------------------------- sizing --
    @property
    def engine(self):
        return self._engine

    @property
    def max_context(self):
        return self._engine.max_context

    # ----------------------------------------------------- lifecycle --
    def start(self):
        if self._started:
            return self
        self._started = True
        self._worker = threading.Thread(
            target=self._run_loop, name=f"mxtpu-{self.name}-engine",
            daemon=True)
        self._worker.start()
        return self

    def __enter__(self):
        return self.start()

    def __exit__(self, *exc):
        self.shutdown()
        return False

    @property
    def running(self):
        with self._cv:
            return self._started and not self._closed

    def warmup(self):
        """Pre-compile every prefill bucket + the decode program.
        Must run BEFORE ``start()`` — enforced, because warmup and the
        engine thread would otherwise race on the shared KV pages
        (concurrent ``cache.swap`` loses updates; on TPU both would
        donate the same buffer). Returns {program: seconds}."""
        if self._started:
            raise RuntimeError(
                "warmup() must run before start(): the engine thread "
                "owns the KV cache once serving begins")
        out = dict(self._engines[0].warmup())
        # dp replicas: identical programs per replica, but each
        # submesh's device set keys its own executable — warm them
        # all so steady state never compiles on ANY replica
        for i, eng in enumerate(self._engines[1:], start=1):
            for key, secs in eng.warmup().items():
                out[f"dp{i}.{key}"] = secs
        return out

    # -------------------------------------------------------- submit --
    def _queue_depth(self):   # guarded-by: caller
        """Admission backlog: sequences holding NO KV blocks yet."""
        return len(self._pending) + sum(
            e.scheduler.num_waiting for e in self._engines)

    def submit(self, prompt_tokens, max_new_tokens, stop_token=None,
               deadline_ms=None, tenant=None, sampling=None,
               adapter=None):
        """Enqueue one prompt; returns a Future resolving to a
        :class:`GenerationResult` (or raising a typed
        :class:`~..errors.ServingError` subclass:
        :class:`SequenceEvictedError`, :class:`DeadlineExceededError`,
        :class:`ServerClosed`; at submit time: :class:`Overloaded` /
        :class:`CircuitOpenError`).

        ``sampling`` (optional): a
        :class:`~.sampling.SamplingParams` — or a dict of its kwargs —
        selecting temperature / top-k / top-p / seed for THIS
        generation (default greedy). Per-sequence params ride the
        fixed decode program as traced vectors: changing them never
        recompiles.

        ``tenant`` (optional) attributes this generation's outcome —
        and its generated tokens — on the per-tenant series
        ``mxtpu_llm_tenant_requests_total`` /
        ``mxtpu_llm_tenant_tokens_total``; untagged requests create
        no tenant series.

        ``adapter`` (optional): the name of a published LoRA adapter
        to decode under (``None`` = base model). Requires an
        :class:`~..adapters.AdapterBank` on the engine
        (``adapter_bank=`` engine kwarg); the name must be resident or
        in the bank's registry — checked HERE on the caller's thread,
        so a typo raises at submit, not mid-batch. Adapter selection
        is traced batch data: mixed-adapter batches (and base-model
        rows) run in the one warmed program, never recompiling."""
        if isinstance(sampling, dict):
            sampling = SamplingParams(**sampling)
        if not self._started:
            raise RuntimeError("server not started; call start()")
        if adapter is not None:
            bank = self._engine.bank
            if bank is None:
                raise ValueError(
                    f"adapter={adapter!r} but the engine has no "
                    "AdapterBank (pass adapter_bank= at construction)")
            if not bank.known(adapter):
                raise UnknownAdapterError(
                    f"adapter {adapter!r} is neither resident nor in "
                    "the registry")
        fl = self._flight
        try:
            shed_if_breaker_open(self._breaker, self._stats)
            deadline = resolve_deadline(deadline_ms,
                                        self.default_deadline_ms,
                                        self._stats)
        except Overloaded:              # breaker_open shed
            self._stats.record_tenant(tenant, "shed")
            if fl.enabled:
                fl.event("llm.shed", tenant=tenant,
                         attrs={"server": self.name,
                                "reason": "breaker_open"})
            raise
        except DeadlineExceededError:   # budget spent at submit
            self._stats.record_tenant(tenant, "expired")
            if fl.enabled:
                fl.event("llm.shed", tenant=tenant,
                         attrs={"server": self.name,
                                "reason": "deadline_at_submit"})
            raise
        prompt = [int(t) for t in np.asarray(prompt_tokens).ravel()]
        seq = Sequence(prompt, max_new_tokens, stop_token=stop_token,
                       deadline=deadline, tenant=tenant,
                       sampling=sampling, adapter=adapter)
        # validate shape/vocab NOW, on the caller's thread
        self._engine.add_validate(seq)
        from concurrent.futures import Future
        seq.future = Future()
        seq.future._mxtpu_seq = seq        # generate-timeout cancel hook
        tracer = get_tracer()
        span = tracer.begin("mxtpu.llm.request", "llm",
                            tracer.current())
        if span.span_id is not None:
            seq.span = span
            span.set("seq_id", seq.seq_id)
            span.set("prompt", len(prompt))
        with self._cv:
            if self._closed:
                if seq.span is not None:
                    seq.span.set("error", "ServerClosed")
                    seq.span.finish()
                if fl.enabled:
                    fl.event("llm.shed", tenant=tenant,
                             attrs={"server": self.name,
                                    "reason": "closed"})
                raise ServerClosed(
                    "server is draining; no new sequences admitted")
            if self._quiesced:
                if seq.span is not None:
                    seq.span.set("error", "ServerClosed")
                    seq.span.finish()
                if fl.enabled:
                    fl.event("llm.shed", tenant=tenant,
                             attrs={"server": self.name,
                                    "reason": "quiesced"})
                raise ServerClosed(
                    "server is quiesced; admission paused "
                    "(resume() re-opens)")
            if (self.max_queue is not None
                    and self._queue_depth() >= self.max_queue):
                depth = self._queue_depth()
                self._stats.record_shed("queue_full")
                self._stats.record_tenant(tenant, "shed")
                if seq.span is not None:
                    seq.span.set("error", "Overloaded")
                    seq.span.finish()
                if fl.enabled:
                    fl.event("llm.shed", tenant=tenant,
                             attrs={"server": self.name,
                                    "reason": "queue_full",
                                    "depth": depth})
                raise Overloaded(
                    f"admission queue full ({depth} >= max_queue "
                    f"{self.max_queue}); request shed",
                    reason="queue_full", depth=depth)
            self._pending.append(seq)
            self._live += 1
            self._cv.notify_all()
        seq.future.add_done_callback(self._live_dec)
        self._stats.record_submit()
        self._stats.record_tenant(tenant, "submitted")
        if fl.enabled:
            fl.event("llm.submit", req=f"llm:{seq.seq_id}",
                     tenant=tenant,
                     attrs={"server": self.name, "prompt": len(prompt),
                            "adapter": adapter,
                            "span_id": seq.span.span_id
                            if seq.span is not None else None})
        return seq.future

    def cancel(self, future):
        """Cancel the sequence behind a Future returned by
        :meth:`submit`: the engine releases its KV blocks and decode
        slot at the next iteration and the Future resolves with a
        typed :class:`DeadlineExceededError` (``reason="timeout"``)
        carrying the tokens generated so far. No-op if the Future is
        already resolved."""
        seq = getattr(future, "_mxtpu_seq", None)
        if seq is None or future.done():
            return False
        with self._cv:
            seq.cancelled = True
            self._cv.notify_all()
        return True

    def generate(self, prompt_tokens, max_new_tokens, stop_token=None,
                 timeout=None, deadline_ms=None, reap_timeout=5.0,
                 tenant=None, sampling=None, adapter=None):
        """Blocking single-prompt decode through the batcher.

        On ``timeout`` the underlying sequence is CANCELLED — its KV
        blocks and decode slot are released, so an abandoned request
        cannot leak pool capacity — and the typed
        :class:`DeadlineExceededError` (with partial tokens) is raised
        instead of a bare ``TimeoutError``. ``reap_timeout`` bounds
        how long the cancel waits for the engine's next iteration to
        resolve it (normally one loop tick; a wedged dispatch raises
        the typed error after this window instead)."""
        fut = self.submit(prompt_tokens, max_new_tokens,
                          stop_token=stop_token, deadline_ms=deadline_ms,
                          tenant=tenant, sampling=sampling,
                          adapter=adapter)
        from concurrent.futures import TimeoutError as FuturesTimeout
        try:
            return fut.result(timeout=timeout)
        except FuturesTimeout:
            self.cancel(fut)
            try:
                # the engine resolves the cancelled sequence on its
                # next iteration (a dead worker resolves everything in
                # its own cleanup)
                return fut.result(timeout=reap_timeout)
            except FuturesTimeout:
                # engine wedged past the reap window (e.g. a dispatch
                # stuck on-device): keep the typed-error contract —
                # callers catching ServingError must see this too
                seq = getattr(fut, "_mxtpu_seq", None)
                raise DeadlineExceededError(
                    "generation cancelled on timeout but not yet "
                    "reaped by the engine",
                    tokens=seq.output_tokens() if seq else (),
                    seq_id=seq.seq_id if seq else None,
                    reason="timeout") from None

    # --------------------------------------------------------- stats --
    def stats(self):
        snap = self._stats.snapshot()
        snap["compiles"] = compile_count()
        snap["kv_cache"] = self._engine.cache.stats()
        snap["prefill_chunk"] = self._engine.prefill_chunk
        snap["spec_k"] = self._engine.spec_k
        snap["q_tokens"] = self._engine.q_tokens
        snap["max_seqs"] = self._engine.max_seqs
        snap["prefix_cache"] = self._engine.prefix_enabled
        snap["kv_dtype"] = self._engine.cache.dtype.name
        # K and V pools of [H, D] or one pool of latents, and the
        # bytes a token takes in it over all layers
        snap["cache_layout"] = self._engine.cache.layout()
        # what the slots hold beside pages (empty for most models), and
        # what the steps did to it
        snap["state_pool"] = self._engine.state_pool_stats()
        snap["weight_dtype"] = self._engine.weight_dtype
        snap["weight_bytes"] = self._engine.weight_bytes
        snap["weight_params_per_chip"] = (
            self._engine.weight_params // max(1, self._engine.tp))
        snap["draft_weight_dtype"] = self._engine.draft_weight_dtype
        # how full the steps ran: tokens fed over the rungs taken
        rung = snap.get("step_rung_tokens", 0)
        snap["step_fill_share"] = (snap.get("step_tokens", 0) / rung
                                   if rung else 0.0)
        # expert layers: row tiles over experts hit; what lies above 1
        # is tiles that found their expert's weights already in VMEM
        hit = snap.get("moe_experts_hit", 0)
        snap["moe_tiles_per_expert_hit"] = (snap.get("moe_tiles", 0) / hit
                                            if hit else 0.0)
        # a model that generates by diffusion over blocks: positions
        # revealed over row passes, settling passes among them
        passes = sum(snap.get("blockdiff_passes", {}).values())
        snap["tokens_per_pass"] = (snap.get("blockdiff_revealed", 0)
                                   / passes if passes else 0.0)
        # of the worker's time outside the loop's wait, the share that
        # is not the wait for the device (the fetch): what the chip
        # stands idle for, seen from inside
        phases = snap["step_phases_s"]
        busy = sum(phases.values()) - phases["wait"]
        snap["host_share"] = ((busy - phases["fetch"]) / busy
                              if busy else 0.0)
        lookups = snap.get("prefix_lookups", 0)
        snap["prefix_hit_rate"] = (snap.get("prefix_hits", 0) / lookups
                                   if lookups else 0.0)
        snap["dp"] = self.dp
        if self._mesh_info is not None:
            snap["mesh"] = dict(self._mesh_info)
        if self._engine.bank is not None:
            snap["adapters"] = self._engine.bank.stats()
        return snap

    def debug_status(self):
        """Structured live-state snapshot for the flight recorder's
        statusz surface: admission/lifecycle flags read under the
        server lock, plus the engine's advisory state (queue depths,
        KV partition, program warmth, in-flight sequences). JSON-ready
        and side-effect free — safe to call from a dump while the
        worker is dying."""
        with self._cv:
            pending = len(self._pending)
            closed, quiesced = self._closed, self._quiesced
            live = self._live
        out = {
            "kind": "llm",
            "server": self.name,
            "started": self._started,
            "closed": closed,
            "quiesced": quiesced,
            "live_futures": live,
            "pending": pending,
            "queue_depth": pending + sum(
                e.scheduler.num_waiting for e in self._engines),
            "max_queue": self.max_queue,
            "breaker_state": self._breaker.state,
            "dp": self.dp,
            "mesh": (dict(self._mesh_info)
                     if self._mesh_info is not None else None),
            "engine": self._engine.debug_status(),
        }
        if self.dp > 1:
            out["engines"] = [e.debug_status()
                              for e in self._engines[1:]]
        return out

    # --------------------------------------------------------- drain --
    def shutdown(self, drain=True, deadline_ms=None):
        """Stop admitting. With ``drain``, run every live sequence to
        completion within the deadline (explicit ``deadline_ms`` arg >
        ``MXNET_TPU_SERVE_DRAIN_DEADLINE_MS`` env > unbounded); past it
        — or immediately with ``drain=False`` — live sequences resolve
        with :class:`SequenceEvictedError` carrying their tokens so
        far. An EXPLICIT ``deadline_ms=0`` means "evict now, typed"
        (the ``ModelServer.shutdown(timeout=0)`` analogue); an unset/0
        env var means unbounded. Idempotent; every Future resolves
        either way."""
        if not self._started:
            return
        if deadline_ms is None:
            env_ms = _env_float("MXNET_TPU_SERVE_DRAIN_DEADLINE_MS", 0.0)
            deadline_ms = env_ms if env_ms > 0 else None
        with self._cv:
            if not self._closed:
                self._closed = True
                self._drain = bool(drain)
                if not drain:
                    self._deadline = time.monotonic()
                elif deadline_ms is None:
                    self._deadline = None
                else:
                    self._deadline = (time.monotonic()
                                      + deadline_ms / 1e3)
            self._cv.notify_all()
        if self._worker is not None:
            self._worker.join()
        self._guard_stop.set()

    close = shutdown

    # ------------------------------------------------------- quiesce --
    def _live_dec(self, _fut=None):
        """Done-callback: one admitted generation Future resolved."""
        with self._cv:
            self._live -= 1
            self._cv.notify_all()

    def quiesce(self, timeout=None):
        """Stop admitting NEW sequences and wait until every admitted
        Future has resolved (completion, eviction, deadline — any
        typed outcome). Unlike :meth:`shutdown` the engine thread, KV
        pools, and compiled programs stay warm: :meth:`resume`
        re-opens admission without rebuilding anything (the fleet
        hot-swap drain runs on exactly this). While quiesced,
        ``submit`` raises a typed :class:`ServerClosed`.

        Returns True once drained; False if ``timeout`` (seconds)
        expired with sequences still live — the server STAYS quiesced
        and the caller picks resume() or shutdown() (whose drain path
        evicts stragglers typed, with their partial tokens)."""
        deadline = (time.monotonic() + timeout
                    if timeout is not None else None)
        with self._cv:
            self._quiesced = True
            while self._live > 0:
                rem = (None if deadline is None
                       else deadline - time.monotonic())
                if rem is not None and rem <= 0:
                    return False
                self._cv.wait(rem if rem is not None else 0.5)
            return True

    def resume(self):
        """Re-open admission after :meth:`quiesce`. Idempotent."""
        with self._cv:
            self._quiesced = False

    @property
    def admitting(self):
        with self._cv:
            return not self._quiesced and not self._closed

    def attach_preemption_guard(self, guard, poll_s=0.05,
                                deadline_ms=None):
        """Drain on preemption (``resilience.PreemptionGuard``): once
        the guard trips, stop admitting and drain under the deadline —
        sequences that cannot finish in time are evicted WITH their
        partial tokens, never lost silently."""
        if self._guard_watcher is not None:
            return self

        def _watch():
            while not self._guard_stop.is_set():
                if guard.wait(poll_s):
                    self.shutdown(drain=True, deadline_ms=deadline_ms)
                    return

        self._guard_watcher = threading.Thread(
            target=_watch, name=f"mxtpu-{self.name}-preempt-watch",
            daemon=True)
        self._guard_watcher.start()
        return self

    # --------------------------------------------------- worker loop --
    def _close_span(self, seq, **attrs):
        if seq.span is not None:
            for k, v in attrs.items():
                seq.span.set(k, v)
            seq.span.finish()
            seq.span = None

    def _resolve_finished(self, seq):
        ttft = (seq.t_first_token - seq.t_submit
                if seq.t_first_token else None)
        res = GenerationResult(seq.output_tokens(), seq.seq_id, ttft,
                               seq.finish_reason, seq.reveal_pass,
                               seq.block_tail)
        latency = time.monotonic() - seq.t_submit
        ex = None
        fl = self._flight
        if fl.enabled:
            key = f"llm:{seq.seq_id}"
            ex = (key, seq.span.span_id
                  if seq.span is not None else None)
            fl.event("llm.served", req=key, tenant=seq.tenant,
                     attrs={"server": self.name,
                            "tokens": len(res.tokens),
                            "finish": seq.finish_reason,
                            "latency_ms": round(latency * 1e3, 3),
                            "ttft_ms": round(ttft * 1e3, 3)
                            if ttft is not None else None})
        self._stats.record_completed(latency, exemplar=ex)
        self._stats.record_tenant(seq.tenant, "served")
        self._stats.record_tenant_tokens(seq.tenant, len(res.tokens))
        if seq.span is not None:
            seq.span.set("tokens", len(res.tokens))
            if seq.t_admit is not None:
                seq.span.set("queue_wait_ms", round(
                    (seq.t_admit - seq.t_submit) * 1e3, 3))
            if ttft is not None:
                seq.span.set("ttft_ms", round(ttft * 1e3, 3))
            seq.span.set("finish", seq.finish_reason)
            seq.span.finish()
            seq.span = None
        seq.future.set_result(res)

    def _resolve_evicted(self, seq, reason):
        toks = seq.output_tokens()
        err = SequenceEvictedError(
            f"sequence {seq.seq_id} evicted ({reason}) after "
            f"{len(toks)} tokens", tokens=toks, seq_id=seq.seq_id,
            reason=reason)
        self._stats.record_evicted(reason)
        self._stats.record_tenant(seq.tenant, "evicted")
        self._stats.record_tenant_tokens(seq.tenant, len(toks))
        fl = self._flight
        if fl.enabled:
            fl.event("llm.evicted", req=f"llm:{seq.seq_id}",
                     tenant=seq.tenant,
                     attrs={"server": self.name, "reason": reason,
                            "tokens": len(toks)})
        self._close_span(seq, error=reason, tokens=len(toks))
        seq.future.set_exception(err)

    def _resolve_dead(self, seq, reason):
        """A deadline-expired ("deadline") or cancelled ("timeout")
        sequence: typed DeadlineExceededError with partial tokens."""
        toks = seq.output_tokens()
        err = DeadlineExceededError(
            f"sequence {seq.seq_id} {reason} after {len(toks)} tokens",
            tokens=toks, seq_id=seq.seq_id, reason=reason)
        # exactly one counter per outcome: the dedicated deadline
        # series for queue/decode expiry, the eviction series (by
        # reason) for caller-cancelled timeouts
        if reason == "deadline":
            self._stats.record_deadline_expired()
        else:
            self._stats.record_evicted(reason)
        self._stats.record_tenant(seq.tenant, "expired")
        self._stats.record_tenant_tokens(seq.tenant, len(toks))
        fl = self._flight
        if fl.enabled:
            fl.event("llm.expired", req=f"llm:{seq.seq_id}",
                     tenant=seq.tenant,
                     attrs={"server": self.name, "reason": reason,
                            "tokens": len(toks)})
        self._close_span(seq, error=reason, tokens=len(toks))
        seq.future.set_exception(err)

    def _resolve_poison(self, seq, exc):
        """A poison-isolated sequence fails with the ORIGINAL dispatch
        exception (the serving layer isolates, it does not mask)."""
        self._stats.record_failure()
        self._stats.record_tenant(seq.tenant, "failed")
        fl = self._flight
        if fl.enabled:
            fl.event("llm.poisoned", req=f"llm:{seq.seq_id}",
                     tenant=seq.tenant,
                     attrs={"server": self.name, "error": repr(exc)})
        self._close_span(seq, error=repr(exc))
        seq.future.set_exception(exc)

    def _flush_engine(self):
        """Resolve everything every engine retired since the last
        call: completions, deadline/cancel expiries, poison
        isolations. Returns how many it resolved."""
        resolved = 0
        for eng in self._engines:
            for seq in eng.pop_finished():
                self._resolve_finished(seq)
                resolved += 1
            for seq, reason in eng.pop_dead():
                self._resolve_dead(seq, reason)
                resolved += 1
            for seq, exc in eng.pop_poison():
                self._resolve_poison(seq, exc)
                resolved += 1
        return resolved

    def _fail_everything(self, exc):
        """Worker-death cleanup: resolve EVERY live Future (engine +
        still-pending) and free every KV block, so no caller hangs on
        a dead engine thread and the pool stays leak-free. Futures
        resolve with a TYPED ServerClosed chaining the original death
        (same contract as ModelServer's worker-death path — a caller
        catching ServingError sees every outcome, even an
        InjectedCrash BaseException)."""
        with self._cv:
            self._closed = True
            self._drain = False
            orphans, self._pending = self._pending, []
        self._flush_engine()
        err = ServerClosed(f"llm engine worker died: {exc!r}")
        err.__cause__ = exc
        evicted = []
        for eng in self._engines:
            evicted.extend(eng.evict_all("engine_error"))
        for seq in orphans + evicted:
            if seq.future.done():       # defensive: never double-set
                continue
            self._stats.record_failure()
            self._stats.record_tenant(seq.tenant, "failed")
            self._close_span(seq, error=repr(exc))
            seq.future.set_exception(err)

    def _run_loop(self):
        try:
            self._run_loop_inner()
        except BaseException as exc:
            # flight bundle FIRST, while the dying state is still
            # visible (queue depths, in-flight sequences, KV
            # partition); crash_dump never raises, so cleanup and the
            # re-raise below are unconditional
            self._flight.crash_dump(exc, server=self.name)
            # InjectedCrash (chaos harness) or an engine bug the
            # isolation layer could not contain: close admission FIRST
            # so no future submit can enqueue onto a dead loop, then
            # resolve every live Future
            self._fail_everything(exc)
            raise
        finally:
            # what the clock holds since the last step's hand-over
            self._clock.mark(None)
            hand_over(self._clock, self._stats, self._flight)

    def _route(self, seq):
        """Pick the replica for one admitted sequence: least loaded
        by live sequences (waiting + running), first replica winning
        ties — deterministic, and exact because the ONE worker thread
        is the only writer of every engine's scheduler."""
        return min(self._engines,
                   key=lambda e: (e.scheduler.num_waiting
                                  + e.scheduler.num_running))

    def _run_loop_inner(self):
        engines = self._engines
        tracer = get_tracer()
        clock = self._clock
        while True:
            # the loop's head, the sibling of mxtpu.llm.step before it:
            # the condition variable, routing, the scans for work
            clock.mark("wait")
            with tracer.span("mxtpu.llm.wait", "llm") as wsp:
                blocked_ns = 0
                with self._cv:
                    while (not self._pending
                           and not any(e.has_work() for e in engines)
                           and not self._closed):
                        t0 = time.monotonic_ns()
                        self._cv.wait(timeout=0.05)
                        blocked_ns += time.monotonic_ns() - t0
                    pending, self._pending = self._pending, []
                    closed, drain = self._closed, self._drain
                    deadline = self._deadline
                for seq in pending:
                    self._route(seq).add(seq)
                # chaos-harness point: crash_at_point("llm.worker")
                # simulates the engine thread dying mid-loop
                faults.point("llm.worker")
                ready = [e for e in engines if e.has_work()]
                wsp.set("blocked_us", blocked_ns // 1000)
                wsp.set("routed", len(pending))
                if closed:
                    expired = (deadline is not None
                               and time.monotonic() >= deadline)
                    if not drain or expired:
                        reason = ("shutdown" if not drain
                                  else "drain_deadline")
                        self._flush_engine()
                        for eng in engines:
                            for seq in eng.evict_all(reason):
                                self._resolve_evicted(seq, reason)
                        return
                    if not ready:
                        self._flush_engine()
                        return
            for eng in ready:
                eng.step()
            # the sibling of mxtpu.llm.step after it: Futures resolve
            # here, with their callbacks
            clock.mark("resolve")
            with tracer.span("mxtpu.llm.resolve", "llm") as sp:
                resolved = self._flush_engine()
                sp.set("resolved", resolved)
