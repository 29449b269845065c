"""LLM serving telemetry: the ``mxtpu_llm_*`` series.

Same discipline as :mod:`mxnet_tpu.serving.telemetry`: every series
lives on the process-wide observability registry, labeled
``{server="<name>"}`` via the shared claim protocol (a restarted server
re-claims its label; a live duplicate gets ``#N``), so LLM decode
telemetry lands in the same Prometheus exposition as training,
checkpoint and single-shot serving metrics.

The serving-economics headline numbers ("Fine-Tuning and Serving Gemma
4 31B on Google Cloud TPU", PAPERS.md) are first-class:

- ``mxtpu_llm_tokens_per_sec`` — decode throughput gauge (smoothed
  per-launch rate, EMA over decode steps — a lifetime average would
  decay across idle gaps; use ``rate(`` on the token counter for
  precise windows);
- ``mxtpu_llm_ttft_seconds`` — time-to-first-token histogram (submit →
  first generated token, i.e. queue wait + prefill);
- ``mxtpu_llm_queue_wait_seconds`` — the queue wait alone (submit →
  first placement into a decode slot);
- ``mxtpu_llm_kv_blocks_in_use`` / ``_total`` — paged-cache occupancy.

The serving thread's own time is :class:`PhaseClock`'s: every moment of
it under one of :data:`PHASES`, handed to :class:`LLMStats` once a step
(``mxtpu_llm_step_phase_seconds_total`` and the stall families).
"""
from __future__ import annotations

import threading
import time

from ...observability import get_registry
from ..telemetry import (OverloadStats, TenantStats,
                         _claim_server_label, _LATENCY_BUCKETS)

__all__ = ["LLMStats", "PhaseClock", "PHASES", "STALL_S", "hand_over"]

#: the serving thread's phases, in the order a loop iteration takes
#: them: the loop's head (the condition variable, routing, the scans for
#: work), the engine's five, the loop's flush of Futures
PHASES = ("wait", "schedule", "build", "launch", "fetch", "commit",
          "resolve")
#: one stay in a phase longer than this is a stall (PERF.md section 7)
STALL_S = 0.1
_STALL_NS = int(STALL_S * 1e9)


class PhaseClock:
    """The serving thread's time as a chain of phases with no gap
    between them. :meth:`mark` is the one operation: ONE read of the
    monotonic clock, which charges the time since the last mark to the
    phase that was open and opens the next, so the phases sum to the
    thread's wall time by construction. A stay is the time between two
    marks; a stay of more than :data:`STALL_S` in any phase but
    ``wait`` (a server without a request waits by right) is a stall.

    One thread marks, so there is no lock here; the sums are plain
    Python numbers until :func:`hand_over` gives them to
    :class:`LLMStats`, once a step. ``LLMServer`` holds one clock for
    its worker thread and all its engines; an engine stepped with no
    server around it makes its own and marks its five phases alone."""

    __slots__ = ("_open", "_t_ns", "ns", "longest_ns", "stalls")

    def __init__(self):
        self._open = None       # the phase time is charged to, if any
        self._t_ns = 0
        self._fresh()

    def _fresh(self):
        self.ns = dict.fromkeys(PHASES, 0)
        self.longest_ns = dict.fromkeys(PHASES, 0)
        self.stalls = []        # (phase, ns) of each stalled stay

    def mark(self, phase):
        """Close the open phase and open ``phase`` (``None``: none, the
        thread's time is nobody's until the next mark). Marking the
        open phase again charges it so far and ends that stay. Returns
        the clock's reading, nanoseconds."""
        now = time.monotonic_ns()
        was = self._open
        if was is not None:
            stay = now - self._t_ns
            self.ns[was] += stay
            if stay > self.longest_ns[was]:
                self.longest_ns[was] = stay
            if stay > _STALL_NS and was != "wait":
                self.stalls.append((was, stay))
        self._open, self._t_ns = phase, now
        return now

    def take(self):
        """``(ns by phase, longest stay by phase, stalls)`` since the
        last take, and start them anew; the open phase stays open."""
        out = self.ns, self.longest_ns, self.stalls
        self._fresh()
        return out


def hand_over(clock, stats, flight, dispatched=0, **step):
    """Give ``stats`` what ``clock`` gathered since its last hand-over
    (``dispatched``: the programs launched meanwhile). With the flight
    recorder on, each stalled stay leaves an event ``llm.stall`` with
    its phase, its milliseconds and ``step``, what the caller knows of
    the step at hand (for a stall in ``resolve``, the step after it).
    Returns the nanoseconds by phase."""
    ns, longest, stalls = clock.take()
    if stats is not None:
        stats.record_step_phases(ns, longest, stalls, dispatched)
    if stalls and flight.enabled:
        for phase, stay in stalls:
            flight.event("llm.stall", attrs={
                "phase": phase, "ms": round(stay / 1e6, 3), **step})
    return ns


class LLMStats:
    """Thread-safe LLM serving counters on the shared registry."""

    def __init__(self, server="llm", registry=None):
        self._reg = registry if registry is not None else get_registry()
        self._server = _claim_server_label(str(server), self)
        r, lbl = self._reg, ("server",)
        s = {"server": self._server}
        self._submitted = r.counter(
            "mxtpu_llm_requests_submitted_total",
            "Decode requests accepted.", lbl).labels(**s)
        self._completed = r.counter(
            "mxtpu_llm_requests_completed_total",
            "Decode requests finished with a full generation.",
            lbl).labels(**s)
        self._evicted = r.counter(
            "mxtpu_llm_requests_evicted_total",
            "Decode requests rejected mid-flight "
            "(drain deadline, shutdown).", ("server", "reason"))
        self._failed = r.counter(
            "mxtpu_llm_requests_failed_total",
            "Decode requests resolved with an error.", lbl).labels(**s)
        self._tokens = r.counter(
            "mxtpu_llm_tokens_generated_total",
            "Tokens produced by decode steps and prefill.",
            lbl).labels(**s)
        self._prefill_tokens = r.counter(
            "mxtpu_llm_prefill_tokens_total",
            "Prompt tokens whose KV was written by prefill "
            "(pad excluded).", lbl).labels(**s)
        self._prefills = r.counter(
            "mxtpu_llm_prefills_total",
            "Prefill launches (admissions incl. preemption resumes).",
            lbl).labels(**s)
        self._decode_steps = r.counter(
            "mxtpu_llm_decode_steps_total",
            "Fixed-shape decode batch launches.", lbl).labels(**s)
        self._preemptions = r.counter(
            "mxtpu_llm_preemptions_total",
            "Sequences evicted for KV pressure and requeued "
            "(restart-based preemption).", lbl).labels(**s)
        self._queue_depth = r.gauge(
            "mxtpu_llm_queue_depth",
            "Sequences waiting for admission.", lbl).labels(**s)
        self._running = r.gauge(
            "mxtpu_llm_running_seqs",
            "Sequences in the decode batch.", lbl).labels(**s)
        self._blocks_in_use = r.gauge(
            "mxtpu_llm_kv_blocks_in_use",
            "Allocated KV cache blocks (refcount >= 1).",
            lbl).labels(**s)
        self._blocks_total = r.gauge(
            "mxtpu_llm_kv_blocks_total",
            "Usable KV cache blocks (pool minus the null block).",
            lbl).labels(**s)
        self._blocks_cached = r.gauge(
            "mxtpu_llm_kv_blocks_cached",
            "Zero-refcount blocks parked in the prefix-cache LRU "
            "(reclaimable capacity holding reusable prefix KV).",
            lbl).labels(**s)
        self._blocks_shared = r.gauge(
            "mxtpu_llm_kv_blocks_shared",
            "Blocks owned by more than one live sequence "
            "(refcount > 1).", lbl).labels(**s)
        self._blocks_free = r.gauge(
            "mxtpu_llm_kv_blocks_free",
            "Strictly free blocks (not allocated, not cached).",
            lbl).labels(**s)
        self._pages_held = r.gauge(
            "mxtpu_llm_kv_pages_held",
            "One-layer KV pages live sequences hold, by the kind of layer "
            "(a cache with window layers: sliding-window layers hold a "
            "window's pages, full layers all of theirs).",
            ("server", "kind"))
        self._pages_held_children = {
            kind: self._pages_held.labels(server=s["server"], kind=kind)
            for kind in ("window", "full")}
        self._pages_full_layout = r.gauge(
            "mxtpu_llm_kv_pages_full_layout",
            "One-layer pages the same sequences would hold if every "
            "layer kept all of its pages (a cache with window layers): "
            "what mxtpu_llm_kv_pages_held is a share of.",
            lbl).labels(**s)
        self._window_released = r.counter(
            "mxtpu_llm_kv_window_pages_released_total",
            "Window layers' pages given back to the pool as they fell "
            "wholly behind their sequence's window.", lbl).labels(**s)
        self._prefix_lookups = r.counter(
            "mxtpu_llm_prefix_lookup_total",
            "Prefix-cache lookups (one per admission while the cache "
            "is enabled).", lbl).labels(**s)
        self._prefix_hits = r.counter(
            "mxtpu_llm_prefix_hit_total",
            "Admissions whose prompt prefix was served from cached "
            "blocks.", lbl).labels(**s)
        self._prefix_evicts = r.counter(
            "mxtpu_llm_prefix_evict_total",
            "Cached prefix blocks reclaimed LRU-oldest-first under KV "
            "pressure.", lbl).labels(**s)
        self._prefill_saved = r.counter(
            "mxtpu_llm_prefill_tokens_saved_total",
            "Prompt tokens whose prefill was skipped because their KV "
            "was served from the prefix cache.", lbl).labels(**s)
        self._tenant_saved = r.counter(
            "mxtpu_llm_tenant_prefill_tokens_saved_total",
            "Prefill tokens saved by prefix-cache hits, attributed "
            "per tenant (tagged requests only).", ("server", "tenant"))
        self._tenant_saved_children = {}
        self._prefill_chunks = r.counter(
            "mxtpu_llm_prefill_chunk_total",
            "Prompt chunks written through the unified step (chunked "
            "prefill).", lbl).labels(**s)
        self._prefill_chunk_tokens = r.counter(
            "mxtpu_llm_prefill_chunk_tokens_total",
            "Prompt tokens written by prefill chunks (pad excluded).",
            lbl).labels(**s)
        self._step_tokens = r.counter(
            "mxtpu_llm_step_tokens_total",
            "Tokens fed by dispatched steps (decode, verify and "
            "prefill rows together, pad excluded).", lbl).labels(**s)
        self._step_rung_tokens = r.counter(
            "mxtpu_llm_step_rung_tokens_total",
            "Packed length of the step programs dispatched (the "
            "rung each step took): what mxtpu_llm_step_tokens_total "
            "is a share of.", lbl).labels(**s)
        self._steps_by_rung = r.counter(
            "mxtpu_llm_steps_by_rung_total",
            "Step programs dispatched at each packed length (the rung "
            "each step took).", ("server", "rung"))
        self._rung_children = {}    # rung -> child, engine thread
        self._spec_proposed = r.counter(
            "mxtpu_llm_spec_proposed_total",
            "Draft tokens proposed for speculative verification.",
            lbl).labels(**s)
        self._spec_accepted = r.counter(
            "mxtpu_llm_spec_accept_total",
            "Draft tokens accepted by the target verify step.",
            lbl).labels(**s)
        self._spec_degraded = r.counter(
            "mxtpu_llm_spec_degraded_total",
            "Steps that fell back to plain decode after a draft "
            "dispatch failure.", lbl).labels(**s)
        self._spec_accept_rate = r.gauge(
            "mxtpu_llm_spec_accept_rate",
            "Cumulative draft-token acceptance rate "
            "(accepted / proposed).", lbl).labels(**s)
        self._tps = r.gauge(
            "mxtpu_llm_tokens_per_sec",
            "Decode throughput: smoothed per-step rate (EMA over "
            "decode launches). For precise windows use "
            "rate(mxtpu_llm_tokens_generated_total).",
            lbl).labels(**s)
        self._ttft = r.histogram(
            "mxtpu_llm_ttft_seconds",
            "Time to first token: submit -> first generated token "
            "(queue wait + prefill).", lbl,
            buckets=_LATENCY_BUCKETS).labels(**s)
        self._queue_wait = r.histogram(
            "mxtpu_llm_queue_wait_seconds",
            "Admission queue wait: submit -> first placement into a "
            "decode slot (a preempted sequence's re-admission counts "
            "under preemptions, not here).", lbl,
            buckets=_LATENCY_BUCKETS).labels(**s)
        self._latency = r.histogram(
            "mxtpu_llm_request_seconds",
            "Per-request end-to-end latency (submit -> last token).",
            lbl, buckets=_LATENCY_BUCKETS).labels(**s)
        self._step_time = r.histogram(
            "mxtpu_llm_decode_step_seconds",
            "Wall time of one decode batch launch.", lbl,
            buckets=_LATENCY_BUCKETS).labels(**s)
        self._adapters_resident = r.gauge(
            "mxtpu_llm_adapters_resident",
            "LoRA adapters currently installed in the device-resident "
            "AdapterBank (in use + cold).", lbl).labels(**s)
        self._adapter_evictions = r.counter(
            "mxtpu_llm_adapter_evictions_total",
            "Adapters retired from the bank, by reason (capacity = "
            "LRU reclaim for a fault-in, republish = replaced by a "
            "newer version, explicit = operator evict).",
            ("server", "reason"))
        self._adapter_evict_children = {}
        self._adapter_requests = r.counter(
            "mxtpu_llm_adapter_requests_total",
            "Generations admitted under each LoRA adapter (base-model "
            "requests create no series).", ("server", "adapter"))
        self._adapter_req_children = {}
        self._adapter_publishes = r.counter(
            "mxtpu_llm_adapter_publish_total",
            "Adapter versions hot-published into the bank (fine-tune "
            "loop or direct publish).", lbl).labels(**s)
        self._tenant_adapter_requests = r.counter(
            "mxtpu_llm_tenant_adapter_requests_total",
            "Adapter-tagged generations attributed per tenant (tagged "
            "requests only).", ("server", "tenant", "adapter"))
        self._tenant_adapter_children = {}
        self._spmd_dispatches = r.counter(
            "mxtpu_llm_spmd_step_dispatch_total",
            "Unified-step launches of the shard_map (SPMD) program — "
            "exactly one device dispatch per engine step when a mesh "
            "is attached (unsharded engines create no series).",
            lbl).labels(**s)
        self._spmd_devices = r.gauge(
            "mxtpu_llm_spmd_mesh_devices",
            "Devices in the engine's decode mesh (0/absent = "
            "unsharded).", lbl).labels(**s)
        self._spmd_axis = r.gauge(
            "mxtpu_llm_spmd_mesh_axis_extent",
            "Extent of each mesh axis the decode step is sharded "
            "over (one series per axis; set at engine construction).",
            ("server", "axis"))
        self._spmd_axis_children = {}
        self._spmd_heads_per_shard = r.gauge(
            "mxtpu_llm_spmd_kv_heads_per_shard",
            "KV heads resident on each tp shard of the paged pool "
            "(num_heads / tp).", lbl).labels(**s)
        self._weight_dtype = r.gauge(
            "mxtpu_llm_weight_dtype",
            "Serving weight storage dtype (one series per dtype, "
            "value 1 on the active one; set at engine construction).",
            ("server", "dtype"))
        self._weight_dtype_children = {}
        self._weight_bytes = r.gauge(
            "mxtpu_llm_weight_bytes",
            "Device-resident bytes of the serving weight tree "
            "(quantized leaves + f32 scales + untouched leaves).",
            lbl).labels(**s)
        self._weight_params_per_chip = r.gauge(
            "mxtpu_llm_weight_params_per_chip",
            "Model parameters resident per chip (total params / tp) — "
            "with mxtpu_llm_weight_bytes this prices params-per-chip "
            "at each weight dtype.", lbl).labels(**s)
        self._quant_fallbacks = r.counter(
            "mxtpu_llm_quant_fallback_total",
            "fp8 weight/KV requests served as int8 because the "
            "backend lacks float8_e4m3fn (availability-guard "
            "fallbacks).", lbl).labels(**s)
        self._moe_pairs = r.counter(
            "mxtpu_llm_moe_routed_pairs_total",
            "(token, expert) pairs routed by the step's expert "
            "layers (valid rows only).", lbl).labels(**s)
        self._moe_hit = r.counter(
            "mxtpu_llm_moe_experts_hit_total",
            "Experts that at least one token of a step hit, summed "
            "over the step's expert layers: the experts whose weights "
            "the step had to read.", lbl).labels(**s)
        self._moe_max_load = r.histogram(
            "mxtpu_llm_moe_max_expert_load",
            "Largest number of pairs one expert took in one expert "
            "layer of a step.", lbl,
            buckets=(1, 2, 4, 8, 16, 32, 64, 128, 256, 512,
                     1024)).labels(**s)
        self._moe_tiles = r.counter(
            "mxtpu_llm_moe_tiles_total",
            "Row tiles of the grouped expert products that a step's "
            "pairs fill, summed over the step's expert layers; over "
            "the experts hit, what lies above 1 is tiles that found "
            "their expert's weights already in VMEM.", lbl).labels(**s)
        self._moe_elsewhere = r.counter(
            "mxtpu_llm_moe_pairs_elsewhere_total",
            "(token, expert) pairs that the step's expert layers routed "
            "to experts this chip does not hold (a model that holds a "
            "share of its router's experts); neither computed nor read "
            "here.", lbl).labels(**s)
        self._state_rows = r.counter(
            "mxtpu_llm_state_rows_total",
            "Rows (sequences) whose slot state a dispatched step "
            "advanced (models whose slots hold state).",
            lbl).labels(**s)
        self._state_steps = r.counter(
            "mxtpu_llm_state_steps_total",
            "Dispatched steps of a model whose slots hold state: what "
            "mxtpu_llm_state_rows_total is a mean over.",
            lbl).labels(**s)
        self._state_starts = r.counter(
            "mxtpu_llm_state_starts_total",
            "Runs begun at position 0, from a state of zeros: "
            "admissions and re-admissions after preemption.",
            lbl).labels(**s)
        self._state_slots = r.gauge(
            "mxtpu_llm_state_slots",
            "Slots the state pools hold a state for (max_seqs; 0 for "
            "a model without slot state).", lbl).labels(**s)
        self._state_bytes = r.gauge(
            "mxtpu_llm_state_pool_bytes",
            "Device bytes of the slot state pools, all layers and "
            "slots.", lbl).labels(**s)
        self._blockdiff_passes = r.counter(
            "mxtpu_llm_blockdiff_passes_total",
            "Row passes of a model that generates by diffusion over "
            "blocks: a row fed one block in one step, by kind "
            "(denoise: a pass that reveals positions; settle: the pass "
            "over the revealed block whose K/V stands).",
            ("server", "kind"))
        self._blockdiff_pass_children = {}
        self._blockdiff_revealed = r.counter(
            "mxtpu_llm_blockdiff_revealed_total",
            "Positions revealed by denoising passes (those a last "
            "block computes past the answer's length among them).",
            lbl).labels(**s)
        self._blockdiff_blocks = r.counter(
            "mxtpu_llm_blockdiff_blocks_total",
            "Blocks settled: their K/V written for good, their tokens "
            "answered.", lbl).labels(**s)
        # the serving thread's time by phase (PhaseClock), one child a
        # phase made here: a hand-over looks nothing up
        by_phase = ("server", "phase")

        def phase_children(metric, phases=PHASES):
            return {p: metric.labels(server=self._server, phase=p)
                    for p in phases}
        self._phase_seconds = phase_children(r.counter(
            "mxtpu_llm_step_phase_seconds_total",
            "Seconds the serving thread spent in each phase of its loop "
            "(wait, schedule, build, launch, fetch, commit, resolve); "
            "the phases sum to the thread's wall time.", by_phase))
        self._phase_longest = phase_children(r.gauge(
            "mxtpu_llm_step_phase_longest_seconds",
            "The longest single stay in each phase over the server's "
            "life.", by_phase))
        self._longest_ns = dict.fromkeys(PHASES, 0)     # guarded-by: _lock
        stalled = tuple(p for p in PHASES if p != "wait")
        self._stalls = phase_children(r.counter(
            "mxtpu_llm_step_stalls_total",
            "Stays in a phase longer than 0.1 s (wait left out: a "
            "server without a request waits by right).", by_phase),
            stalled)
        self._stall_seconds = phase_children(r.counter(
            "mxtpu_llm_step_stall_seconds_total",
            "Seconds of the stays counted by "
            "mxtpu_llm_step_stalls_total.", by_phase), stalled)
        self._steps_dispatched = r.counter(
            "mxtpu_llm_steps_dispatched_total",
            "Step programs launched, of any kind of row (prefill "
            "chunks, decode, verify, block passes; the halves of an "
            "isolation among them).", lbl).labels(**s)
        self._steps_overlapped = r.counter(
            "mxtpu_llm_steps_overlapped_total",
            "Step programs launched while another step of the engine "
            "was in flight (its results not yet fetched).",
            lbl).labels(**s)
        self._overlap_discarded = r.counter(
            "mxtpu_llm_overlap_discarded_rows_total",
            "Rows of a step launched ahead whose sequence ended at the "
            "step before it (a stop token, the context, an expiry or a "
            "cancel): their results are dropped.", lbl).labels(**s)
        # the overload/failure series share the single-shot server's
        # mxtpu_serving_* catalog (one dashboard for both front ends)
        self._overload = OverloadStats(r, self._server)
        self._tenants = TenantStats(
            r, "mxtpu_llm_tenant_requests_total", self._server,
            tokens_metric="mxtpu_llm_tenant_tokens_total")
        self._evict_children = {}
        self._lock = threading.Lock()
        self._gen_count = 0

    @property
    def server_label(self):
        return self._server

    # ---------------------------------------------------- recording --
    def _labeled_child(self, counter, cache, **labels):
        """Memoized per-label child lookup (engine-thread only — the
        lock-free twin of TenantStats' guarded cache); one copy so the
        eviction-reason and tenant-saved series cannot drift."""
        key = tuple(sorted(labels.items()))
        child = cache.get(key)
        if child is None:
            child = counter.labels(server=self._server, **labels)
            cache[key] = child
        return child

    def record_submit(self):
        self._submitted.inc()

    def record_admission_state(self, waiting, running):
        self._queue_depth.set(waiting)
        self._running.set(running)

    def record_blocks(self, in_use, total, cached=0, shared=0,
                      free=None):
        self._blocks_in_use.set(in_use)
        self._blocks_total.set(total)
        self._blocks_cached.set(cached)
        self._blocks_shared.set(shared)
        self._blocks_free.set(total - in_use - cached
                              if free is None else free)

    def record_window_pages(self, held, full_layout, released=0):
        """A cache with window layers: the pages held by kind (``{"window":
        n, "full": n}``), what an all-full layout would hold for the same
        sequences, and window pages given back since the last record."""
        for kind, n in held.items():
            self._pages_held_children[kind].set(n)
        self._pages_full_layout.set(full_layout)
        if released:
            self._window_released.inc(released)

    def record_prefix_lookup(self, hit_tokens, tenant=None):
        """One admission-time prefix-cache lookup: counts the lookup,
        the hit (when any tokens were served from cache) and the
        prefill tokens saved — attributed per tenant when tagged."""
        self._prefix_lookups.inc()
        if hit_tokens > 0:
            self._prefix_hits.inc()
            self._prefill_saved.inc(hit_tokens)
            if tenant is not None:
                self._labeled_child(
                    self._tenant_saved, self._tenant_saved_children,
                    tenant=str(tenant)).inc(hit_tokens)

    def record_prefix_evict(self, n=1):
        self._prefix_evicts.inc(n)

    def record_prefill(self, prompt_tokens):
        self._prefills.inc()
        self._prefill_tokens.inc(prompt_tokens)

    def record_admitted(self, wait_s):
        """A request's first admission, ``wait_s`` after its submit."""
        self._queue_wait.observe(wait_s)

    def record_first_token(self, ttft_s, exemplar=None):
        """``exemplar`` (optional ``(req, span_id)``): keep this
        observation in its TTFT bucket's bounded reservoir — built by
        call sites only while the flight recorder is on."""
        self._ttft.observe(ttft_s, exemplar=exemplar)

    # smoothing factor for the per-step throughput EMA: heavy enough
    # to damp single-launch jitter, light enough that the gauge tracks
    # a load change within a few steps. A lifetime average would decay
    # toward zero across idle gaps and misreport healthy bursts.
    _TPS_ALPHA = 0.2

    def record_decode_step(self, new_tokens, step_s):
        with self._lock:
            self._decode_steps.inc()
            self._step_time.observe(step_s)
            self._tokens.inc(new_tokens)
            self._gen_count += new_tokens
            inst = new_tokens / max(step_s, 1e-9)
            prev = self._tps.value
            self._tps.set(inst if prev == 0
                          else prev + self._TPS_ALPHA * (inst - prev))

    def record_prefill_token(self):
        """The first generated token comes out of prefill, not a
        decode step — count it so the token counter sees every
        token (the throughput EMA tracks decode launches only)."""
        with self._lock:
            self._tokens.inc()
            self._gen_count += 1

    def record_prefill_chunk(self, tokens):
        self._prefill_chunks.inc()
        self._prefill_chunk_tokens.inc(tokens)

    def record_step_fill(self, tokens, rung):
        """One dispatched step: ``tokens`` fed to a program shaped
        for ``rung``."""
        self._step_tokens.inc(tokens)
        self._step_rung_tokens.inc(rung)
        child = self._rung_children.get(rung)
        if child is None:
            child = self._rung_children[rung] = self._steps_by_rung.labels(
                server=self._server, rung=rung)
        child.inc()

    def record_step_phases(self, ns, longest_ns, stalls, dispatched):
        """One hand-over of the serving thread's clock
        (:func:`hand_over`): nanoseconds and longest stay by phase,
        the stalled stays ``[(phase, ns)]``, the programs launched."""
        with self._lock:
            for phase, v in ns.items():
                if v:
                    self._phase_seconds[phase].inc(v / 1e9)
            for phase, v in longest_ns.items():
                if v > self._longest_ns[phase]:
                    self._longest_ns[phase] = v
                    self._phase_longest[phase].set(v / 1e9)
            for phase, v in stalls:
                self._stalls[phase].inc()
                self._stall_seconds[phase].inc(v / 1e9)
            if dispatched:
                self._steps_dispatched.inc(dispatched)

    def record_overlapped(self):
        """One step launched while another was in flight."""
        self._steps_overlapped.inc()

    def record_overlap_discarded(self, rows):
        self._overlap_discarded.inc(rows)

    def record_spec(self, proposed, accepted):
        self._spec_proposed.inc(proposed)
        self._spec_accepted.inc(accepted)
        total = self._spec_proposed.value
        if total > 0:
            self._spec_accept_rate.set(
                self._spec_accepted.value / total)

    def record_spec_degraded(self):
        self._spec_degraded.inc()

    def record_preemption(self):
        self._preemptions.inc()

    def record_completed(self, latency_s, exemplar=None):
        self._completed.inc()
        self._latency.observe(latency_s, exemplar=exemplar)

    def record_evicted(self, reason):
        self._labeled_child(self._evicted, self._evict_children,
                            reason=reason).inc()

    def record_failure(self, n=1):
        self._failed.inc(n)

    # ------------------------------------------------ adapter series --
    def record_adapters_resident(self, n):
        self._adapters_resident.set(n)

    def record_adapter_evicted(self, reason, n=1):
        self._labeled_child(self._adapter_evictions,
                            self._adapter_evict_children,
                            reason=str(reason)).inc(n)

    def record_adapter_request(self, adapter, tenant=None):
        """One generation admitted under ``adapter`` — attributed per
        tenant too when the request is tenant-tagged."""
        self._labeled_child(self._adapter_requests,
                            self._adapter_req_children,
                            adapter=str(adapter)).inc()
        if tenant is not None:
            self._labeled_child(self._tenant_adapter_requests,
                                self._tenant_adapter_children,
                                tenant=str(tenant),
                                adapter=str(adapter)).inc()

    def record_adapter_publish(self, n=1):
        self._adapter_publishes.inc(n)

    # --------------------------------------------------- SPMD series --
    def record_spmd_mesh(self, devices, axes, heads_per_shard):
        """Engine construction under a mesh: publish its shape (total
        devices, per-axis extents) and the per-shard KV-head count so
        dashboards can tell a tp=4 fleet from four tp=1 replicas."""
        self._spmd_devices.set(int(devices))
        for axis, extent in axes.items():
            self._labeled_child(self._spmd_axis,
                                self._spmd_axis_children,
                                axis=str(axis)).set(int(extent))
        self._spmd_heads_per_shard.set(int(heads_per_shard))

    def record_spmd_dispatch(self, n=1):
        self._spmd_dispatches.inc(n)

    def record_moe(self, pairs, experts_hit, max_load, tiles,
                   elsewhere=None):
        """One dispatched step of a model with expert layers (the
        histogram's count is the number of such steps: times the
        experts the model holds, the denominator of the hit share).
        ``elsewhere``: pairs routed to experts held on other chips,
        from a model that holds a share of them."""
        self._moe_pairs.inc(pairs)
        self._moe_hit.inc(experts_hit)
        self._moe_max_load.observe(max_load)
        self._moe_tiles.inc(tiles)
        if elsewhere is not None:
            self._moe_elsewhere.inc(elsewhere)

    def record_state_pool(self, slots, nbytes):
        """Engine construction, a model whose slots hold state."""
        self._state_slots.set(int(slots))
        self._state_bytes.set(int(nbytes))

    def record_state_step(self, rows, starts):
        """One dispatched step of such a model: the rows whose state it
        advanced, and those of them that began at position 0."""
        self._state_rows.inc(rows)
        self._state_steps.inc()
        self._state_starts.inc(starts)

    def record_blockdiff(self, denoise_rows, settle_rows, revealed):
        """One dispatched step of a model that generates by diffusion
        over blocks: its rows in a denoising pass, its rows in a
        settling pass (each settles one block), the positions
        revealed."""
        for kind, n in (("denoise", denoise_rows),
                        ("settle", settle_rows)):
            if n:
                self._labeled_child(self._blockdiff_passes,
                                    self._blockdiff_pass_children,
                                    kind=kind).inc(n)
        self._blockdiff_revealed.inc(revealed)
        self._blockdiff_blocks.inc(settle_rows)

    # --------------------------------------------- quantized weights --
    def record_weight_quant(self, dtype, weight_bytes,
                            params_per_chip):
        """Engine construction: publish the serving weight dtype (a
        1-valued series per dtype label — float32 engines publish too,
        so dashboards can diff a mixed fleet), resident weight bytes
        and the params-per-chip headline."""
        self._labeled_child(self._weight_dtype,
                            self._weight_dtype_children,
                            dtype=str(dtype)).set(1)
        self._weight_bytes.set(int(weight_bytes))
        self._weight_params_per_chip.set(int(params_per_chip))

    def record_quant_fallback(self, n=1):
        """One fp8→int8 availability-guard fallback (weights or KV)."""
        self._quant_fallbacks.inc(n)

    # ------------------------------------------------- tenant series --
    def record_tenant(self, tenant, outcome, n=1):
        """Per-tenant outcome attribution (no-op for tenant None)."""
        self._tenants.record(tenant, outcome, n)

    def record_tenant_tokens(self, tenant, n):
        self._tenants.record_tokens(tenant, n)

    # ------------------------------------------------ overload series --
    def record_shed(self, reason):
        self._overload.record_shed(reason)

    def record_deadline_expired(self, n=1):
        self._overload.record_deadline_expired(n)

    def record_poison(self, n=1):
        self._overload.record_poison(n)

    def record_breaker_state(self, state):
        self._overload.record_breaker_state(state)

    # -------------------------------------------------------- stats --
    def snapshot(self):
        with self._lock:
            return self._overload.snapshot_into({
                "requests_submitted": int(self._submitted.value),
                "requests_completed": int(self._completed.value),
                "requests_evicted": int(sum(
                    c.value for c in self._evict_children.values())),
                "requests_failed": int(self._failed.value),
                "tokens_generated": int(self._tokens.value),
                "prefill_tokens": int(self._prefill_tokens.value),
                "prefills": int(self._prefills.value),
                "prefill_chunks": int(self._prefill_chunks.value),
                "prefill_chunk_tokens": int(
                    self._prefill_chunk_tokens.value),
                "step_tokens": int(self._step_tokens.value),
                "step_rung_tokens": int(self._step_rung_tokens.value),
                "steps_by_rung": {t: int(c.value) for t, c in sorted(
                    self._rung_children.items())},
                "steps_dispatched": int(self._steps_dispatched.value),
                "steps_overlapped": int(self._steps_overlapped.value),
                "overlap_discarded_rows": int(
                    self._overlap_discarded.value),
                "step_phases_s": {p: c.value for p, c in
                                  self._phase_seconds.items()},
                "step_phase_longest_s": {
                    p: c.value for p, c in self._phase_longest.items()},
                "stalls": {p: {"count": int(c.value),
                               "seconds": self._stall_seconds[p].value}
                           for p, c in self._stalls.items() if c.value},
                "spec_proposed": int(self._spec_proposed.value),
                "spec_accepted": int(self._spec_accepted.value),
                "spec_degraded": int(self._spec_degraded.value),
                "spec_accept_rate": self._spec_accept_rate.value,
                "decode_steps": int(self._decode_steps.value),
                "preemptions": int(self._preemptions.value),
                "queue_depth": int(self._queue_depth.value),
                "running_seqs": int(self._running.value),
                "kv_blocks_in_use": int(self._blocks_in_use.value),
                "kv_blocks_total": int(self._blocks_total.value),
                "kv_blocks_cached": int(self._blocks_cached.value),
                "kv_blocks_shared": int(self._blocks_shared.value),
                "kv_blocks_free": int(self._blocks_free.value),
                "prefix_lookups": int(self._prefix_lookups.value),
                "prefix_hits": int(self._prefix_hits.value),
                "prefix_evictions": int(self._prefix_evicts.value),
                "prefill_tokens_saved": int(
                    self._prefill_saved.value),
                "tokens_per_sec": self._tps.value,
                "admissions": int(self._queue_wait.count),
                "queue_wait_s_sum": self._queue_wait.sum,
                "ttft_ms": {
                    "p50": self._ttft.percentile(50) * 1e3,
                    "p99": self._ttft.percentile(99) * 1e3,
                },
                "request_ms": {
                    "p50": self._latency.percentile(50) * 1e3,
                    "p99": self._latency.percentile(99) * 1e3,
                },
                "spmd_step_dispatches": int(
                    self._spmd_dispatches.value),
                "spmd_mesh_devices": int(self._spmd_devices.value),
                "spmd_mesh_axes": {
                    k[0][1]: int(c.value) for k, c in
                    self._spmd_axis_children.items()},
                "spmd_kv_heads_per_shard": int(
                    self._spmd_heads_per_shard.value),
                "weight_dtype": {
                    k[0][1]: int(c.value) for k, c in
                    self._weight_dtype_children.items()},
                "weight_bytes": int(self._weight_bytes.value),
                "weight_params_per_chip": int(
                    self._weight_params_per_chip.value),
                "quant_fallbacks": int(self._quant_fallbacks.value),
                "moe_routed_pairs": int(self._moe_pairs.value),
                "moe_experts_hit": int(self._moe_hit.value),
                "moe_steps": int(self._moe_max_load.count),
                "moe_tiles": int(self._moe_tiles.value),
                "moe_pairs_elsewhere": int(self._moe_elsewhere.value),
                "kv_pages_held": {
                    k: int(c.value)
                    for k, c in self._pages_held_children.items()},
                "kv_pages_full_layout": int(
                    self._pages_full_layout.value),
                "kv_window_pages_released": int(
                    self._window_released.value),
                "state_rows": int(self._state_rows.value),
                "state_starts": int(self._state_starts.value),
                "state_steps": int(self._state_steps.value),
                "blockdiff_passes": {
                    k[0][1]: int(c.value) for k, c in
                    self._blockdiff_pass_children.items()},
                "blockdiff_revealed": int(
                    self._blockdiff_revealed.value),
                "blockdiff_blocks": int(self._blockdiff_blocks.value),
                "adapters_resident": int(
                    self._adapters_resident.value),
                "adapter_publishes": int(
                    self._adapter_publishes.value),
                "adapter_evictions": {
                    k[0][1]: int(c.value) for k, c in
                    self._adapter_evict_children.items()},
                "adapter_requests": {
                    k[0][1]: int(c.value) for k, c in
                    self._adapter_req_children.items()},
                "tenants": self._tenants.snapshot(),
            })
