"""Whole-step compilation: ONE donated XLA launch per training step.

The eager training loop pays per-op dispatch three times per step — the
recorded forward, the tape walk of ``backward()`` (one XLA execution per
recorded op), and the optimizer apply (collapsed to one dispatch by
``optimizer.fused`` in the previous round). The reference gets its speed
from compiling the whole computation (Symbol/CachedOp executor), and the
TPU literature is unambiguous that end-to-end step compilation — not
per-op dispatch — is what unlocks MFU ("Automatic Full Compilation of
Julia Programs and ML Models to Cloud TPUs"; the MLPerf TPU-v3 scaling
reports, PAPERS.md). :class:`CompiledTrainStep` closes the remaining gap:

- the user's ``loss_fn`` (arbitrary Python calling gluon blocks — the
  eager ops are trace-transparent) is traced ONCE per input signature;
- the backward comes from ``jax.value_and_grad`` over the parameter
  pytree instead of the tape walk;
- the cross-context gradient reduce and the recorded fused optimizer
  apply (``optimizer.fused`` record/replay, including its value-deduped
  traced-scalar hyperparameter split, so lr/wd/momentum/LossScaler
  rescale never recompile) fold into the same program;
- weights and optimizer slots are donated, so the step updates HBM in
  place and steady-state training is a single device dispatch per step
  with zero host round-trips (one scalar fetch only while float16 loss
  scaling is engaged — the overflow-skip decision is host state).

Batch-tail bucketing: XLA compiles one program per input shape, so the
ragged final batch of an epoch would recompile the whole step. Training
batches are therefore padded up to a power-of-two bucket (the serving
bucketer's pad discipline, ``serving.bucketing``); a mask built from the
traced real-row count zeroes the padded rows' loss so they contribute
exactly ``+0.0`` to every gradient, and the traced row count feeds
``rescale_grad`` so the mean semantics are those of the REAL rows.
``MXNET_TPU_STEP_BUCKETS`` tunes or disables the bucket set. (Batch-
statistics ops — BatchNorm in training mode — see the padded rows; for
those nets a tail batch is shape-stable but not numerically identical
to an unpadded step. See docs/PERFORMANCE.md.)

SPMD mesh mode (``compile_step(mesh=...)`` / ``MXNET_TPU_MESH``): the
same step program partitions over a ``parallel.make_mesh`` device mesh —
weights/optimizer slots placed per ``param_spec`` (replicated by
default, megatron splits via ``parallel.auto_spec``), batches sharded
over ``dp``, and XLA's GSPMD emits the in-program gradient psum. No
per-context Python loop, no host-side allreduce: one donated dispatch
per step at any device count, with the bucket-tail masking and AMP
overflow-skip semantics unchanged under sharding. Evidence rides the
``mxtpu_spmd_*`` series (docs/OBSERVABILITY.md).

Guarded fallback: anything the trace cannot express — sparse gradients,
host-sync/host-state optimizers, data-dependent Python control flow
(detected at trace time), ``grad_req='add'`` accumulation, kvstores
whose reduce is not a plain sum — routes the step through the eager
record/backward path, counted by reason on the shared metrics registry
(``mxtpu_train_step_fallback_total``). ``MXNET_TPU_COMPILED_STEP=0``
disables the compiled path globally.
"""
from __future__ import annotations

import os
import threading
import warnings

import numpy as _np

__all__ = ["CompiledTrainStep", "step_buckets_config", "pick_train_bucket",
           "pad_rows"]

# trace-time fallback reasons that are deterministic for this trainer /
# loss_fn — retrying them every step would re-pay a failed trace
_STICKY_REASONS = ("trace_failed", "unrecordable", "state_leaf",
                   "exec_failed", "mesh_multictx")


class _Fallback(Exception):
    """Raised when the step cannot be compiled; carries the reason."""

    def __init__(self, reason):
        super().__init__(reason)
        self.reason = reason


class _TraceFrame(dict):
    """Trace-capture frame pushed on ``parameter._TRACE_STACK``: dict of
    parameter writes (``Parameter -> traced NDArray``, the contract
    CachedOp's aux frame established) plus the set of parameters READ
    with concrete (non-input) values — those get promoted to program
    inputs on the rebuild pass instead of baking stale constants."""

    __slots__ = ("reads",)

    def __init__(self):
        super().__init__()
        self.reads = set()


def step_buckets_config(override=None):
    """Resolve the training bucket policy: ``None`` = bucketing off
    (exact shapes; ragged tails recompile), ``"auto"`` = powers of two
    up to the largest batch seen, or an explicit sorted list of sizes.
    ``override`` (the ``buckets=`` argument) wins over the
    ``MXNET_TPU_STEP_BUCKETS`` env: False/0 = off, a list = explicit."""
    if override is not None:
        if override is False or override == 0:
            return None
        if override is True or override == "auto":
            return "auto"
        return sorted(int(b) for b in override)
    v = os.environ.get("MXNET_TPU_STEP_BUCKETS", "1").strip().lower()
    if v in ("0", "off", "false", "none"):
        return None
    if v in ("1", "auto", "on", ""):
        return "auto"
    return sorted(int(t) for t in v.split(","))


def pick_train_bucket(n, buckets, max_batch):
    """Bucket for a batch of ``n`` rows under a policy resolved by
    :func:`step_buckets_config` — the ONE training bucket policy,
    shared by :class:`CompiledTrainStep` and ``parallel.ShardedTrainer``
    (which rounds the result up to its mesh's dp extent)."""
    from .serving.bucketing import bucket_sizes, pick_bucket
    if buckets is None:
        return n
    if buckets == "auto":
        return pick_bucket(n, bucket_sizes(max_batch))
    return pick_bucket(n, buckets) if n <= buckets[-1] else n


def pad_rows(v, bucket):
    """Zero-pad ``v`` (array or NDArray, batch on axis 0) up to
    ``bucket`` rows; returns ``v`` itself when already full. Host
    arrays pad through the serving bucketer, device arrays with one
    concatenate — the single pad discipline for every training path."""
    import jax.numpy as jnp
    from .ndarray import NDArray
    from .serving.bucketing import pad_batch
    arr = v._data if isinstance(v, NDArray) else v
    n = arr.shape[0]
    if n == bucket:
        return v
    if isinstance(arr, _np.ndarray):
        return pad_batch(arr, bucket)
    return jnp.concatenate(
        [arr, jnp.zeros((bucket - n,) + tuple(arr.shape[1:]), arr.dtype)],
        axis=0)


def _tracer():
    """The process tracer (lazy import keeps `import mxnet_tpu` light;
    get_tracer itself is one lock-free global read after first use)."""
    from .observability.tracing import get_tracer
    return get_tracer()


def _metrics():
    from .observability import get_registry
    reg = get_registry()
    return {
        "dispatch": reg.counter(
            "mxtpu_train_step_dispatch_total",
            "Compiled whole-step program launches (steady state: exactly "
            "1 per training step)."),
        "compiled": reg.counter(
            "mxtpu_train_step_compiled_total",
            "Training steps executed as one compiled forward+backward+"
            "reduce+update program."),
        "fallback": reg.counter(
            "mxtpu_train_step_fallback_total",
            "Training steps that fell back to the eager record/backward "
            "path, by reason.", ("reason",)),
        "bucket_compiles": reg.counter(
            "mxtpu_train_step_bucket_compiles_total",
            "Whole-step program builds, by batch bucket (flat after "
            "warmup = zero steady-state recompiles).", ("bucket",)),
        "padded_rows": reg.counter(
            "mxtpu_train_step_padded_rows_total",
            "Zero rows added to ragged batch tails to hit a pre-compiled "
            "bucket (the FLOP cost of never recompiling)."),
    }


class CompiledTrainStep:
    """One buffer-donating XLA program per (structure, bucketed shape,
    dtype) covering forward + loss + backward + cross-context gradient
    reduce + optimizer update. Build via
    ``gluon.Trainer.compile_step(loss_fn)``.

    ``loss_fn(*batch)`` is arbitrary Python calling the net through the
    eager API; it must return the per-sample loss (any shape with the
    batch on axis 0, or a scalar), or a tuple whose FIRST element is the
    loss — the remaining elements (predictions etc.) ride along as
    program outputs. Calling the step returns exactly what ``loss_fn``
    returned, with padded rows sliced off.

    Semantics mirror ``loss.backward(); trainer.step(batch_rows)``: the
    gradient is of the loss SUM (a backward seeded with ones) and the
    optimizer's ``rescale_grad`` divides by the real row count. BN aux
    states (running stats) update inside the program. ``param.grad()``
    buffers are NOT written — readers of raw gradients belong on the
    eager path (``MXNET_TPU_COMPILED_STEP=0``).
    """

    # consecutive dispatch failures tolerated before the compiled path is
    # disabled for this step object (trace failures disable immediately)
    MAX_EXEC_FAILURES = 3

    def __init__(self, trainer, loss_fn, buckets=None, donate=True,
                 remat=None, mesh=None, param_spec=None):
        if remat not in (None, "", "full", "dots"):
            raise ValueError(
                f"remat must be None, 'full' or 'dots', got {remat!r}")
        self._trainer = trainer
        self._loss_fn = loss_fn
        self._donate = donate
        self._remat = remat or None
        if isinstance(mesh, str):
            from .parallel.mesh import parse_mesh
            mesh = parse_mesh(mesh)
        # SPMD mesh mode: every program input is placed onto `mesh` as
        # one global array (weights/slots per `param_spec`, batches
        # dp-sharded) and the SAME step program partitions over it —
        # XLA's GSPMD emits the in-program gradient psum, so multi-chip
        # training keeps the 1-dispatch/zero-host-round-trip contract.
        self._mesh = mesh
        self._param_spec = param_spec
        self._wspecs = {}        # param name -> clamped PartitionSpec
        self._buckets = step_buckets_config(buckets)
        self._max_batch = 0
        # mid-run resume: a trainer restored from a checkpoint carries
        # the saved run's bucket warmth — seed it so resumed tails pad
        # to the same buckets (identical numerics, no cold recompiles);
        # registration lets a restore_state() AFTER compile_step reach
        # live step objects the same way
        registry = getattr(trainer, "_compiled_steps", None)
        if registry is not None:
            registry.add(self)
        restored = getattr(trainer, "_restored_step_state", None) or {}
        self.seed_bucket_state(restored.get("max_batch", 0))
        self._cache = {}      # signature key -> (compiled, meta)
        self._disabled = None
        self._exec_failures = 0
        self._obs = None
        self._lock = threading.Lock()
        self.last_reason = None      # fallback reason of the last call

    # ------------------------------------------------------ eligibility --
    def _why_ineligible(self):
        """None when this call can take the compiled path, else the
        fallback-reason label (host-sync optimizers, sparse grads,
        non-foldable kvstores, gradient accumulation, env gate)."""
        if os.environ.get("MXNET_TPU_COMPILED_STEP", "1") == "0":
            return "env_disabled"
        if self._disabled is not None:
            return self._disabled
        tr = self._trainer
        from .optimizer.fused import fusable
        if tr._update_on_kvstore:
            return "kvstore"
        if tr._kvstore is not None and not getattr(
                tr._kvstore, "fused_reduce_compatible", False):
            return "kvstore"
        if not fusable(tr._optimizer):
            return "optimizer"
        for p in tr._params:
            if p.grad_req == "add":
                return "grad_req_add"
            if p.grad_req != "null" and (p.stype == "row_sparse"
                                         or p.grad_stype == "row_sparse"):
                return "sparse_grad"
        if self._mesh is not None:
            # the mesh IS the multi-device story: per-context replicas
            # and a device mesh are two incompatible placements for the
            # same weight — a trainer built over replicated contexts
            # keeps the replica path (deterministic for this trainer,
            # hence sticky)
            if any(p._data is not None and len(p._data) > 1
                   for p in tr._params):
                return "mesh_multictx"
        return None

    def _obs_metrics(self):
        if self._obs is None:
            self._obs = _metrics()
        return self._obs

    # -------------------------------------------------------- bucketing --
    def seed_bucket_state(self, max_batch):
        """Adopt bucket warmth from a restored checkpoint (monotonic —
        never shrinks what this step already saw)."""
        self._max_batch = max(self._max_batch, int(max_batch or 0))

    def _pick_bucket(self, n):
        if self._buckets == "auto":
            self._max_batch = max(self._max_batch, n)
        bucket = pick_train_bucket(n, self._buckets, self._max_batch)
        if self._mesh is not None and self._buckets is not None:
            # keep the batch axis evenly shardable over dp; the extra
            # pad rows are masked like any other tail padding. (With
            # bucketing off the batch stays unpadded — an indivisible
            # batch then simply replicates, still correct SPMD.)
            from .parallel.mesh import round_up_to_dp
            bucket = round_up_to_dp(bucket, self._mesh)
        return bucket

    # ------------------------------------------------------------- call --
    def __call__(self, *args):
        import time as _time
        tr = self._trainer
        if not tr._kv_initialized:
            tr._init_kvstore()
        obs = self._obs_metrics()
        t0 = _time.monotonic()
        # the step span (no kwargs, no attrs: the disabled path must
        # allocate nothing per step); trace/compile/dispatch/fallback
        # appear as children via contextvar nesting
        with _tracer().span("mxtpu.train_step", "step", None, None,
                            tr._step_count):
            reason = self._why_ineligible()
            if reason is not None:
                return self._eager_step(args, reason)
            try:
                return self._compiled_step(args, obs, t0)
            except _Fallback as e:
                if e.reason == "scalar_loss_bucketed":
                    # a pre-reduced loss cannot be pad-corrected: drop
                    # the bucketing (exact shapes still compile whole-
                    # step) and retry once
                    self._buckets = None
                    try:
                        return self._compiled_step(args, obs, t0)
                    except _Fallback as e2:
                        e = e2
                if e.reason in _STICKY_REASONS:
                    self._disabled = e.reason
                return self._eager_step(args, e.reason)

    # ---------------------------------------------------- the fast path --
    def _compiled_step(self, args, obs, t0):
        import time as _time
        import jax
        from . import _rng
        from .gluon.block import _flatten_arrays, _flat_flags
        from .optimizer import fused as _fused

        # from entry to the cache lookup: key, weights, states, rng draw
        with _tracer().span("mxtpu.train_step.prepare", "step"):
            tr = self._trainer
            opt, upd = tr._optimizer, tr._updaters[0]
            scaler = getattr(tr, "_amp_loss_scaler", None)
            engaged = scaler is not None and scaler.loss_scale != 1.0

            flat_in, in_fmt = _flatten_arrays(args)
            flags = _flat_flags(in_fmt)
            arrays = [v for v, f in zip(flat_in, flags) if f]
            opaque = tuple(v for v, f in zip(flat_in, flags) if not f)
            if not arrays or getattr(arrays[0], "ndim", 0) == 0:
                raise _Fallback("no_batch_axis")
            n = int(arrays[0].shape[0])

            # deferred parameter shapes resolve through one eager predict
            # pass (no aux writes — CachedOp's warm-up discipline)
            if any(p._data is None for p in tr._params):
                from . import autograd
                with autograd.pause(train_mode=False):
                    self._loss_fn(*args)

            work = [(i, p) for i, p in enumerate(tr._params)
                    if p.grad_req != "null" and p._data is not None]
            if not work:
                raise _Fallback("no_trainable")
            bucket = self._pick_bucket(n)

            # ---- phase A: record the optimizer apply on host ----------------
            # All host bookkeeping (update counts, schedulers, Adam bias
            # correction, AMP rescale) advances exactly as in the eager loop;
            # a fallback from here on must roll the counts back.
            scale = tr._scale / (scaler.loss_scale if engaged else 1.0)
            opt.rescale_grad = scale / n
            _fused.prepare_states(opt, upd, work)
            try:
                roles, weight_nds, grad_nds, state_nds, state_defs = \
                    _fused.build_roles(upd, work)
            except ValueError:
                raise _Fallback("state_leaf") from None
            rec = _fused.record_program(upd, work, grad_nds, weight_nds, roles)
            if not rec.ok:
                _fused.rollback_counts(opt, work)
                raise _Fallback("unrecordable")

            mesh_pin = None
            if self._mesh is not None:
                # place weights + optimizer slots onto the mesh as global
                # arrays (no-op once placed: donation hands back outputs
                # with the same, constraint-pinned shardings)
                mesh_pin = self._place_mesh(work, weight_nds, state_nds,
                                            state_defs)

            nts = [p for p in tr._params
                   if p.grad_req == "null" and p._data is not None]
            key = (in_fmt, opaque, bucket, engaged, self._mesh,
                   self._buckets is not None, type(opt), tuple(rec.program),
                   tuple(state_defs),
                   tuple((tuple(a.shape[1:]) if a.shape[:1] == (n,)
                          else ("F",) + tuple(a.shape),
                          str(_np.dtype(_dtype_of(a)))) for a in arrays),
                   tuple((tuple(w.shape), str(w.dtype)) for w in weight_nds),
                   tuple((tuple(s.shape), str(s.dtype)) for s in state_nds))
            try:
                hash(key)
            except TypeError:
                _fused.rollback_counts(opt, work)
                raise _Fallback("unhashable_signature") from None

            batch_vals = self._stage_batch(arrays, n, bucket)
            if self._mesh is not None:
                batch_vals = self._place_batch(batch_vals, bucket)
            weights = [w._data for w in weight_nds]
            states = [s._data for s in state_nds]
            scalars = tuple(rec.slot_values)
            ls = float(scaler.loss_scale) if engaged else 1.0
            rng_base = _rng.base_key()
            rng_draw = _rng.reserve_draw()

        entry = self._cache.get(key)
        if entry is None:
            with self._lock:
                entry = self._cache.get(key)
                if entry is None:
                    with _tracer().span("mxtpu.train_step.compile",
                                        "step") as _sp:
                        _sp.set("bucket", bucket)
                        try:
                            entry = self._build(
                                rec.program, work, nts, in_fmt, flags,
                                opaque, bucket, engaged,
                                (weights, states, scalars, ls, n,
                                 rng_base, rng_draw, batch_vals),
                                mesh_pin=mesh_pin)
                        except _Fallback:
                            _fused.rollback_counts(opt, work)
                            raise
                    self._cache[key] = entry
                    obs["bucket_compiles"].labels(bucket=str(bucket)).inc()
                    if self._mesh is not None:
                        from .parallel.mesh import note_mesh, spmd_metrics
                        sobs = spmd_metrics()
                        sobs["programs"].labels(
                            devices=str(self._mesh.devices.size),
                            bucket=str(bucket)).inc()
                        note_mesh(self._mesh)
        compiled, meta = entry

        nt_all = meta["nt_params"]
        if self._mesh is not None:
            nt_vals = self._place_nt(nt_all)
        else:
            nt_vals = [p._get_primary()._data for p in nt_all]
        try:
            with _tracer().span("mxtpu.train_step.dispatch", "step"):
                outs = compiled(weights, nt_vals, states, scalars, ls, n,
                                rng_base, rng_draw, batch_vals)
        except Exception:
            if any(w.is_deleted() for w in weights) or \
                    any(s.is_deleted() for s in states):
                raise       # donation consumed the inputs: nothing to
                            # fall back onto — surface the real failure
            warnings.warn("compiled train step failed; falling back to "
                          "the eager record/backward path", stacklevel=4)
            with self._lock:
                self._cache.pop(key, None)
            self._exec_failures += 1
            reason = "exec_failed" if \
                self._exec_failures >= self.MAX_EXEC_FAILURES else \
                "exec_retry"
            _fused.rollback_counts(opt, work)
            raise _Fallback(reason) from None
        # from the program's return to the end: overflow, write-back, loss
        with _tracer().span("mxtpu.train_step.rebind", "step"):
            self._exec_failures = 0
            new_w, new_s, aux_out, loss_out, extras, flag = outs

            overflow = False
            if engaged:
                overflow = not bool(_np.asarray(flag))  # the ONE host sync
            if overflow:
                # the program kept the pre-step weights/slots (in-program
                # where()); mirror the eager amp_step skip exactly: no count
                # advance, no step tick, scale halves
                _fused.rollback_counts(opt, work)
                scaler.update_scale(overflow=True)
                warnings.warn(
                    f"AMP: gradient overflow, skipping update and reducing "
                    f"loss scale to {scaler.loss_scale}", stacklevel=3)
            else:
                if engaged:
                    scaler.update_scale(overflow=False)
                tr._step_count += 1

            for k, (i, param) in enumerate(work):
                replicas = param.list_data()
                replicas[0]._data = new_w[k]
                for other in replicas[1:]:
                    other._data = jax.device_put(new_w[k],
                                                 other.context.jax_device)
            for leaf, data in zip(state_nds, new_s):
                leaf._data = data
            for p, v in zip(meta["aux_params"], aux_out):
                ctxs = list(p._data)
                p._data[ctxs[0]]._data = v
                for c in ctxs[1:]:
                    p._data[c]._data = jax.device_put(v, c.jax_device)

            obs["dispatch"].inc()
            obs["compiled"].inc()
            if self._mesh is not None:
                from .parallel.mesh import spmd_metrics
                sobs = spmd_metrics()
                sobs["dispatch"].inc()
                if meta.get("spmd_grad_bytes"):
                    sobs["bytes"].labels(collective="grad_reduce").inc(
                        meta["spmd_grad_bytes"])
            if bucket != n:
                obs["padded_rows"].inc(bucket - n)
            tobs = tr._obs_metrics()
            if not overflow:
                # an overflow-skip records nothing, mirroring the eager
                # amp_step early return — secs samples stay 1:1 with steps
                tobs["secs"].observe(_time.monotonic() - t0)
                tobs["steps"].inc()
                tobs["examples"].inc(n)
                from .resilience import faults
                from .resilience import async_writer as _aw
                _aw.note_step_overlap()
                faults.on_step(tr._step_count)
            self.last_reason = None
            return self._package(meta, loss_out, extras, n, bucket)

    # --------------------------------------------------- mesh placement --
    def _leaf_spec_of(self, name, shape):
        """Clamped PartitionSpec for a named parameter on this mesh
        (cached: the clamp result is a pure function of name+shape)."""
        from jax.sharding import PartitionSpec
        from .parallel.mesh import leaf_spec
        spec = self._wspecs.get(name)
        if spec is None:
            raw = self._param_spec(name, tuple(shape)) \
                if self._param_spec else PartitionSpec()
            spec = leaf_spec(raw, tuple(shape), self._mesh)
            self._wspecs[name] = spec
        return spec

    def _place_mesh(self, work, weight_nds, state_nds, state_defs):
        """Move weights and optimizer slots onto the mesh (no-op when
        already placed). Slots ride their parameter's spec when
        weight-shaped, else replicate. Returns (wspecs, sspecs) aligned
        with weight_nds/state_nds for the build's output pinning."""
        from .parallel.mesh import leaf_spec, place_global
        mesh = self._mesh
        wspecs, sspecs = [], []
        si = 0
        for k, (i, param) in enumerate(work):
            w = weight_nds[k]
            spec = self._leaf_spec_of(param.name, w.shape)
            wspecs.append(spec)
            w._data = place_global(w._data, mesh, spec)
            for _ in range(state_defs[k].num_leaves):
                leaf = state_nds[si]
                lspec = leaf_spec(spec, tuple(leaf.shape), mesh)
                leaf._data = place_global(leaf._data, mesh, lspec)
                sspecs.append(lspec)
                si += 1
        return wspecs, sspecs

    def _place_nt(self, nt_params):
        """Place non-trainable program inputs (frozen weights, BN
        stats) on the mesh; returns their values in input order."""
        from .parallel.mesh import place_global
        vals = []
        for p in nt_params:
            nd = p._get_primary()
            spec = self._leaf_spec_of(p.name, nd.shape)
            nd._data = place_global(nd._data, self._mesh, spec)
            vals.append(nd._data)
        return vals

    def _place_batch(self, batch_vals, bucket):
        """Shard padded batch inputs over dp (replicate anything not
        batch-major). Multi-process meshes treat each process's value as
        its local shard of the global batch — the dist_sync layout."""
        from jax.sharding import PartitionSpec
        from .parallel.mesh import batch_spec, place_global
        mesh = self._mesh
        out = []
        for v in batch_vals:
            nd = getattr(v, "ndim", 0)
            if nd and getattr(v, "shape", ())[:1] == (bucket,):
                spec = batch_spec(nd, mesh, bucket)
            else:
                spec = PartitionSpec()
            host_has = "local_shard" if len(spec) else "full"
            out.append(place_global(v, mesh, spec, host_has=host_has))
        return out

    # ------------------------------------------------------------ build --
    def _build(self, program, work, nts, in_fmt, flags, opaque, bucket,
               engaged, sample_inputs, mesh_pin=None):
        """Trace + AOT-compile the whole-step program for one signature.
        Two passes: the first lowering discovers parameters the loss
        reads or writes outside the Trainer's set; those are promoted to
        program inputs and the step re-lowered, so e.g. frozen-backbone
        BN stats never bake stale constants. AOT (lower/compile): a
        trace failure and a backend compile failure are told apart, and
        each takes its own way back to the eager path.
        Returns (compiled, meta)."""
        import jax
        from .optimizer.fused import bind_entries
        entries = bind_entries(program)
        trainables = [p for _, p in work]
        w, s, sc, ls, n, rb, rd, bv = sample_inputs
        extra = []
        for attempt in (0, 1):
            nt_all = nts + extra
            meta = {"nt_params": nt_all, "aux_params": None,
                    "single": True, "loss_scalar": False,
                    "reads": set(), "writes": set()}
            if self._mesh is not None:
                dp = dict(self._mesh.shape).get("dp", 1)
                if dp > 1 and bucket and bucket % dp == 0:
                    # logical per-step gradient-psum payload over dp:
                    # one grad the size of every trainable weight
                    meta["spmd_grad_bytes"] = sum(
                        int(a.size) * a.dtype.itemsize for a in w)
            fn = self._make_fn(entries, trainables, nt_all, in_fmt, flags,
                               opaque, bucket, engaged, meta,
                               mesh_pin=mesh_pin)
            jitted = jax.jit(fn, donate_argnums=(0, 2) if self._donate
                             else ())
            nt_vals = self._place_nt(nt_all) if self._mesh is not None \
                else [p._get_primary()._data for p in nt_all]
            try:
                lowered = jitted.lower(w, nt_vals, s, sc, ls, n, rb, rd,
                                       bv)
            except _Fallback:
                raise
            except Exception as e:
                # data-dependent Python control flow, host syncs inside
                # the loss, structures the trace cannot carry —
                # deterministic for this signature
                warnings.warn(
                    "whole-step trace failed "
                    f"({type(e).__name__}: {e}); training continues on "
                    "the eager path", stacklevel=5)
                raise _Fallback("trace_failed") from None
            if meta["loss_scalar"] and self._buckets is not None:
                raise _Fallback("scalar_loss_bucketed")
            discovered = sorted(
                (meta["reads"] | meta["writes"]) - set(trainables)
                - set(nt_all),
                key=lambda p: p.name)
            discovered = [p for p in discovered if p._data is not None]
            if discovered and attempt == 0:
                extra = extra + discovered
                continue
            if discovered:
                raise _Fallback("trace_failed")  # nondeterministic trace
            break
        try:
            compiled = lowered.compile()
        except Exception as e:
            # backend compile failure (e.g. XLA OOM): the caller
            # must see a _Fallback so phase-A counters roll back and the
            # step still runs eagerly. Counts against the same breaker as
            # execution failures — a deterministic compile failure would
            # otherwise re-pay the full trace+compile every step forever.
            warnings.warn(
                f"whole-step compile failed ({type(e).__name__}: {e}); "
                "training continues on the eager path", stacklevel=4)
            self._exec_failures += 1
            reason = "exec_failed" if \
                self._exec_failures >= self.MAX_EXEC_FAILURES else \
                "exec_retry"
            raise _Fallback(reason) from None
        return compiled, meta

    def _make_fn(self, entries, trainables, nts, in_fmt, flags, opaque,
                 bucket, engaged, meta, mesh_pin=None):
        import jax
        import jax.numpy as jnp
        if mesh_pin is not None:
            # pin the updated weights/slots to their input shardings so
            # XLA aliases the donated buffers and the next step's
            # placement check is a no-op — without the constraint GSPMD
            # may propagate a different output layout and every step
            # would pay a reshard
            from jax.sharding import NamedSharding
            _w_sh = [NamedSharding(self._mesh, s) for s in mesh_pin[0]]
            _s_sh = [NamedSharding(self._mesh, s) for s in mesh_pin[1]]
        from . import _rng, autograd
        from .gluon.block import _regroup
        from .gluon.parameter import _TRACE_STACK
        from .ndarray import NDArray
        from .optimizer.fused import apply_entries
        loss_fn = self._loss_fn
        masked = self._buckets is not None
        remat = self._remat

        def run_loss(ws, nt_vals, xvals, key, mask):
            """One forward+loss over (a slice of) the batch; returns
            (differentiable head, (loss value, extras, writes))."""
            frame = _TraceFrame()
            _TRACE_STACK.append(frame)
            old = _rng.push_trace_key(key)
            touched = []
            try:
                for p, v in zip(trainables, ws):
                    p._trace_data = NDArray(v)
                    touched.append(p)
                for p, v in zip(nts, nt_vals):
                    p._trace_data = NDArray(v)
                    touched.append(p)
                merged, ai, oi = [], 0, 0
                for is_arr in flags:
                    if is_arr:
                        merged.append(NDArray(xvals[ai]))
                        ai += 1
                    else:
                        merged.append(opaque[oi])
                        oi += 1
                with autograd.pause(train_mode=True):
                    out = loss_fn(*_regroup(merged, in_fmt))
            finally:
                for p in touched:
                    p._trace_data = None
                for p in frame:
                    p._trace_data = None
                _TRACE_STACK.pop()
                _rng.pop_trace_key(old)
            single = not isinstance(out, tuple)
            outs = (out,) if single else tuple(out)
            loss = outs[0]
            extras = tuple(o._data if isinstance(o, NDArray) else o
                           for o in outs[1:])
            meta["single"] = single
            meta["reads"] |= frame.reads
            meta["writes"] |= set(frame)
            # aux writes flow out as a name-ordered TUPLE (a Parameter-
            # keyed dict would need sortable pytree keys); the order is
            # pinned on meta during the (deterministic) trace
            worder = sorted(frame, key=lambda p: p.name)
            meta["aux_params"] = worder
            wvals = tuple(
                frame[p]._data if isinstance(frame[p], NDArray)
                else frame[p] for p in worder)
            lv = loss._data if isinstance(loss, NDArray) \
                else jnp.asarray(loss)
            if lv.ndim == 0:
                meta["loss_scalar"] = True
                head = lv
            elif mask is not None:
                factor = mask.reshape(
                    mask.shape + (1,) * (lv.ndim - 1)).astype(lv.dtype)
                head = (lv * factor).sum()
            else:
                # the eager gradient seed is ones == grad of the SUM
                head = lv.sum()
            return head, (lv, extras, wvals)

        def step_fn(weights, nt_vals, states, scalars, loss_scale, n_real,
                    rng_base, rng_draw, xvals):
            key = jax.random.fold_in(rng_base, rng_draw)
            mask = (jnp.arange(bucket) < n_real) if masked else None

            def head_of(h):
                # with scaling engaged the eager head is loss*loss_scale;
                # scaling the summed head by the traced scale produces
                # cotangents that match element-for-element
                return h * loss_scale if engaged else h

            # one forward over the whole batch on the primary context —
            # per-context gradient partials never materialize, so the
            # cross-context reduce is subsumed (the updated weights are
            # broadcast to every replica after the dispatch)
            def objective(ws):
                h, aux = run_loss(ws, nt_vals, xvals, key, mask)
                return head_of(h), aux
            if remat == "full":
                objective = jax.checkpoint(objective)
            elif remat == "dots":
                objective = jax.checkpoint(
                    objective,
                    policy=jax.checkpoint_policies.dots_saveable)
            (_, (loss_v, extras, aux_vals)), grads = jax.value_and_grad(
                objective, has_aux=True)(list(weights))

            bufs = {}
            for k, w in enumerate(weights):
                bufs[("w", k)] = w
            for k, g in enumerate(grads):
                bufs[("g", k)] = g
            for j, st in enumerate(states):
                bufs[("s", j)] = st
            flag = jnp.asarray(True)
            if engaged:
                fin = [jnp.isfinite(g).all() for g in grads]
                flag = jnp.all(jnp.stack(fin)) if fin else flag
            apply_entries(entries, bufs, scalars)
            new_w = [bufs[("w", k)] for k in range(len(weights))]
            new_s = [bufs[("s", j)] for j in range(len(states))]
            if engaged:
                # overflow => keep the pre-step weights and slots (the
                # eager amp_step update skip, decided in-program)
                new_w = [jnp.where(flag, nw, ow)
                         for nw, ow in zip(new_w, weights)]
                new_s = [jnp.where(flag, ns, os_)
                         for ns, os_ in zip(new_s, states)]
            if mesh_pin is not None:
                new_w = [jax.lax.with_sharding_constraint(v, sh)
                         for v, sh in zip(new_w, _w_sh)]
                new_s = [jax.lax.with_sharding_constraint(v, sh)
                         for v, sh in zip(new_s, _s_sh)]
            return new_w, new_s, aux_vals, loss_v, extras, flag

        return step_fn

    # --------------------------------------------------------- plumbing --
    def _stage_batch(self, arrays, n, bucket):
        """Padded program-input values. Only arrays whose leading axis is
        the batch axis are padded; host arrays pad on host, device arrays
        with one tiny concatenate (ragged tails only — full buckets copy
        nothing). The pad-row metric is charged by the CALLER after the
        padded program actually dispatched (a step that falls back runs
        unpadded)."""
        from .ndarray import NDArray
        out = []
        for a in arrays:
            v = a._data if isinstance(a, NDArray) else a
            if hasattr(v, "shape") and v.shape[:1] == (n,) and bucket != n:
                v = pad_rows(v, bucket)
            out.append(v)
        return out

    def _package(self, meta, loss_out, extras, n, bucket):
        from .ndarray import NDArray

        def trim(v):
            if hasattr(v, "shape") and v.shape[:1] == (bucket,) \
                    and n != bucket:
                v = v[:n]
            return NDArray(v)
        loss = trim(loss_out)
        if meta["single"]:
            return loss
        return (loss,) + tuple(trim(e) for e in extras)

    # ------------------------------------------------------- eager path --
    def _eager_step(self, args, reason):
        """The guarded fallback: the plain record/backward/step loop
        (which itself runs the fused one-dispatch update when it can).
        Counted by reason; semantics identical to hand-written eager
        training, including the AMP wrapper's overflow skip."""
        from . import autograd
        from .gluon.block import _flatten_arrays, _flat_flags
        obs = self._obs_metrics()
        obs["fallback"].labels(reason=reason).inc()
        self.last_reason = reason
        tr = self._trainer
        scaler = getattr(tr, "_amp_loss_scaler", None)
        flat_in, fmt = _flatten_arrays(args)
        n = 1
        for v, f in zip(flat_in, _flat_flags(fmt)):
            if f and getattr(v, "ndim", 0):
                n = int(v.shape[0])
                break
        with _tracer().span("mxtpu.train_step.fallback", "step") as sp:
            sp.set("reason", reason)
            with autograd.record():
                out = self._loss_fn(*args)
                loss = out[0] if isinstance(out, tuple) else out
                head = loss * scaler.loss_scale \
                    if scaler is not None and scaler.loss_scale != 1.0 \
                    else loss
            head.backward()
            tr.step(n)
        return out

    # ------------------------------------------------------- introspect --
    def cache_size(self):
        return len(self._cache)


def _dtype_of(a):
    d = getattr(a, "dtype", None)
    return d if d is not None else _np.asarray(a).dtype
