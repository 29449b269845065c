"""chip_smoke.py: the quickest proof that the system still starts on the chip.

One process on a machine with a TPU drives both hot paths once through
the entry points a user calls, at the full width of a model the repo
supports, with random weights made from a seed:

- **train**   ResNet-50 v1 (NHWC, space-to-depth stem, bf16, batch 128,
              224x224) stepped through ``gluon.Trainer.compile_step``;
- **serve**   ``LLMServer`` over ``TinyDecoder`` at head_dim 128 answering
              eight requests whose steps mix prefill chunks with decodes;
- **kernels** every Pallas kernel the package dispatches to by default on
              a TPU, compiled by Mosaic and compared with its ``jnp``
              reference on the chip;
- **latent**  the flat latent (MLA) attention kernel alone, at the widths
              and the three packed shapes of the ``moonlight_16b_a3b``
              cell, against its reference on a few rows, timed a call;
- **ragged**  the flat K/V attention kernel alone, at the widths and the
              four packed shapes of the ``cerebras_gpt_1b3`` cells, over
              float32 and int8 pages, likewise; then grouped queries (32
              heads over 8 of 64, bf16 packed rows) at the
              ``granite_4_0_h_micro`` cell's;
- **ssd**     the state-space step kernel alone over that cell's state
              pool (36 layers x 32 slots x 2 MB), against the per-token
              recurrence, timed a call;
- **blockdiff** grouped queries under the block-causal mask and one
              layer's 128 experts at the ``sdar_30b_a3b_chat`` cell's
              shapes, likewise;
- **experts** the grouped expert products alone at one layer of the
              ``moonlight_16b_a3b`` cell (64 experts of ``[2048, 1408]``,
              6 a token), a decode step of 8 rows and a chunk step of 128.

Every phase checks what came out and prints one JSON line; a failed check
raises, so the exit code is non-zero. The last line of standard output is
``{"ok": true, "device": {...}}``. Times printed here are information for
the builder, not metrics.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the same two hot paths over a mesh
    python chip_smoke.py --phases latent   # only the phases named

It refuses to run where JAX finds no TPU. The phases are plain functions
that take their sizes as arguments, so ``tests/test_chip_smoke.py``
rehearses the control flow on the CPU at toy sizes; only :func:`main`
fixes the full sizes.
"""
import argparse
import json
import sys
import time

PHASES = ("train", "serve", "kernels", "latent", "ragged", "ssd",
          "blockdiff", "experts")

#: kernel-vs-reference bounds, fixed from the dtype before any chip run.
#: bf16 flash attention: the repo's own bf16 bound (tests/test_attention.py).
#: f32 ragged attention: a run of several tokens takes both products through
#: the MXU at the backend's default precision, one bf16 pass over float32
#: operands (2**-9 an operand, a few of them a logit; the latent kernel reads
#: 0.003); a run of one token and int8 pages stay on the VPU, exact in float32.
#: int8-weight matmul: bounded by a bf16 pass over f32 activations, which is
#: below the 1/127 step of the weights themselves.
#: latent attention: float32 operands through the MXU at the backend's
#: default precision, one bf16 pass like the flash kernel's.
FLASH_BF16_TOL = 0.05
RAGGED_F32_TOL = 0.02
WQ_MATMUL_TOL = 1e-2
LATENT_TOL = 0.05
#: the state-space step: the same float32 operations in kernel and
#: recurrence, but for the order of the sum over the state's 128 columns
SSD_TOL = 1e-5


def check(ok, what):
    """A phase check. Raises (never ``assert``: -O must not skip it)."""
    if not ok:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def _counters():
    """(XLA compiles, persistent-cache hits, persistent-cache misses) from
    the jax.monitoring bridge. A compile request counts as a compile
    whether or not the persistent cache answered it."""
    from mxnet_tpu.observability import (get_registry,
                                         install_jax_monitoring_bridge)
    install_jax_monitoring_bridge()
    reg = get_registry()
    events = reg.counter("mxtpu_xla_events_total", labelnames=("event",))
    return (int(reg.counter("mxtpu_xla_compile_total").value),
            int(reg.counter("mxtpu_xla_cache_hits_total").value),
            int(events.labels(
                event="/jax/compilation_cache/cache_misses").value))


def _report(phase, started, counters0, setup_s, run_s, **facts):
    """Print the phase's one JSON line."""
    import jax
    dev = jax.devices()[0]
    compiles, hits, misses = (b - a for a, b in
                              zip(counters0, _counters()))
    line = {"phase": phase, "platform": dev.platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()), "jax": jax.__version__,
            "setup_s": round(setup_s, 2), "run_s": round(run_s, 3),
            "phase_s": round(time.monotonic() - started, 2),
            "xla_compiles": compiles, "cache_hits": hits,
            "cache_misses": misses}
    line.update(facts)
    print(json.dumps(line), flush=True)
    return line


def _on_platform(arr, platform):
    return all(d.platform == platform for d in arr.devices())


def _check_spread(arrays, n_devices, what):
    """Several chips: every array must really span the mesh, and every
    device must hold bytes — a mesh that put everything on the first
    device would pass a sharding check on paper only."""
    import jax
    for a in arrays:
        check(len(a.sharding.device_set) == n_devices,
              f"{what}: array spans {len(a.sharding.device_set)} of "
              f"{n_devices} devices")
    for d in jax.devices()[:n_devices]:
        stats = d.memory_stats()
        if stats is not None:       # the CPU backend reports none
            check(stats["bytes_in_use"] > 0,
                  f"{what}: nothing resident on {d}")


# ------------------------------------------------------------------ train --

def train_phase(make_net, image, batch, dtype="bfloat16", warmup=2,
                steps=10, mesh=None):
    """Step ``make_net()`` through ``Trainer.compile_step`` on batches
    already on the device. ``mesh`` (e.g. ``"dp=4"``) shards the
    step; ``batch`` is then the global batch."""
    import jax
    import numpy as np
    import mxnet_tpu as mx
    import mxnet_tpu.autograd as ag
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.observability import get_registry

    started, c0 = time.monotonic(), _counters()
    dev = jax.devices()[0]
    if mesh is not None:
        mesh = parallel.parse_mesh(mesh)
    mx.random.seed(0)
    net = make_net()
    net.initialize(init=mx.initializer.Xavier())
    in_shape = (1, image, image, 3)
    with ag.pause(train_mode=False):
        net(nd.NDArray(np.ones(in_shape, np.float32)))
    net.cast(dtype)

    def stage(a):
        a = nd.NDArray(jax.device_put(a, dev))
        return a if mesh is None else parallel.shard_batch(a, mesh)

    n_batches = min(4, warmup + steps)
    xs = [stage(np.random.RandomState(100 + i).randn(
        batch, *in_shape[1:]).astype(dtype)) for i in range(n_batches)]
    ys = [stage(np.random.RandomState(200 + i).randint(
        0, 1000, (batch,)).astype(np.int32)) for i in range(n_batches)]

    def loss_fn(x, y):
        logp = mx.nd.log_softmax(net(x).astype("float32"), axis=-1)
        return -mx.nd.pick(logp, y, axis=1)

    params = net.collect_params()
    trainer = Trainer(params, "sgd",
                      {"learning_rate": 1e-3, "momentum": 0.9})
    step = trainer.compile_step(loss_fn, mesh=mesh)
    fallback = get_registry().counter("mxtpu_train_step_fallback_total",
                                      labelnames=("reason",))

    def fallbacks():
        return sum(c.value for c in fallback.children())

    fallbacks0 = fallbacks()
    probe = [p for p in params.values() if p.grad_req != "null"][0]
    before = probe.data().asnumpy().astype(np.float32)

    losses = []

    def run(first, n):
        for i in range(first, first + n):
            losses.append(step(xs[i % n_batches], ys[i % n_batches]))
            check(step.last_reason is None,
                  f"step {i} fell back to eager: {step.last_reason}")
        losses[-1]._data.block_until_ready()

    run(0, warmup)
    setup_s = time.monotonic() - started
    compiles_warm = _counters()[0]
    t0 = time.monotonic()
    run(warmup, steps)
    run_s = time.monotonic() - t0
    # whether block_until_ready waited: if it had returned early, this
    # fetch of the same value would have to wait for the device instead
    t0 = time.monotonic()
    last = losses[-1].asnumpy()
    fetch_s = time.monotonic() - t0

    check(_counters()[0] == compiles_warm,
          "XLA compiled again after the warm-up steps")
    check(step.cache_size() == 1,
          f"{step.cache_size()} step programs compiled, expected 1")
    check(fallbacks() == fallbacks0,
          "mxtpu_train_step_fallback_total moved: a step ran eagerly")
    check(all(np.isfinite(l.asnumpy()).all() for l in losses),
          "loss is not finite")
    check(last.shape == (batch,), f"loss shape {last.shape}")
    check(not np.array_equal(
        before, probe.data().asnumpy().astype(np.float32)),
        "weights did not change")
    live = [p.data()._data for p in params.values()] \
        + [a._data for a in xs + ys]
    check(all(_on_platform(a, dev.platform) for a in live),
          f"parameters or batches are not on a {dev.platform} device")
    if mesh is not None:
        _check_spread(live, mesh.devices.size, "train")
    return _report(
        "train", started, c0, setup_s, run_s, batch=batch, image=image,
        dtype=dtype, mesh=None if mesh is None else dict(mesh.shape),
        steps=steps, step_programs=step.cache_size(),
        final_loss=round(float(last.mean()), 4),
        fetch_after_block_s=round(fetch_s, 4),
        block_until_ready_waits=bool(fetch_s < 0.05 * run_s))


# ------------------------------------------------------------------ serve --

def serve_phase(config, prompt_lens, new_tokens, n_reference=2,
                max_seqs=8, block_size=16, mesh=None, precision=None):
    """Answer ``len(prompt_lens)`` requests submitted together through
    ``LLMServer`` and compare ``n_reference`` of the generations with
    ``greedy_decode_reference`` on the same device. ``precision`` pins
    ``jax_default_matmul_precision`` for the server's programs and the
    reference alike (None leaves the backend's default)."""
    import jax
    import numpy as np
    from mxnet_tpu.serving.llm import LLMServer
    from mxnet_tpu.serving.llm.model import (TinyDecoder,
                                             greedy_decode_reference)

    started, c0 = time.monotonic(), _counters()
    platform = jax.devices()[0].platform
    # the engine's worker thread must see it, so the global, not the
    # thread-local context manager
    prev_precision = jax.config.jax_default_matmul_precision
    jax.config.update("jax_default_matmul_precision", precision)
    try:
        model = TinyDecoder(config)
        params = jax.device_put(model.init_params(seed=0))
        rs = np.random.RandomState(7)
        prompts = [rs.randint(0, config.vocab_size, n).tolist()
                   for n in prompt_lens]
        server = LLMServer(model, params, name="chip_smoke", mesh=mesh,
                           max_seqs=max_seqs, block_size=block_size)
        engine = server.engine
        # the dispatch decision, read off the program the engine built:
        # only a Mosaic-compiled kernel lowers to a tpu_custom_call
        kernels = engine.step_program_text().count("tpu_custom_call")
        check((kernels > 0) == (platform == "tpu"),
              f"{kernels} compiled Pallas kernels in the step program "
              f"on {platform}")
        warm = server.warmup()
        setup_s = time.monotonic() - started
        compiles_warm = _counters()[0]
        before = server.stats()
        server.start()
        t0 = time.monotonic()
        futures = [server.submit(p, new_tokens) for p in prompts]
        results = [f.result(timeout=600) for f in futures]
        run_s = time.monotonic() - t0
        compiles_load = _counters()[0] - compiles_warm
        server.shutdown()
        stats = {k: v - before[k] for k, v in server.stats().items()
                 if k in ("requests_failed", "requests_completed",
                          "prefill_chunks", "decode_steps")}

        check(compiles_load == 0,
              f"{compiles_load} XLA compiles between warmup() and the "
              "last token")
        check(stats["requests_failed"] == 0
              and stats["requests_completed"] == len(prompts),
              f"requests: {stats['requests_completed']} completed, "
              f"{stats['requests_failed']} failed")
        for r in results:
            check(len(r.tokens) == new_tokens
                  and all(0 <= t < config.vocab_size for t in r.tokens),
                  f"request {r.seq_id}: bad generation {r.tokens[:8]}")
        check(stats["prefill_chunks"] > len(prompts)
              and stats["decode_steps"] > 0,
              "steps did not mix prefill chunks with decodes")
        pools = [engine.cache.k_pages, engine.cache.v_pages]
        check(all(_on_platform(a, platform) for a in pools),
              f"KV pools are not on a {platform} device")
        if mesh is not None:
            sharded = pools + [engine._params["layers"][0]["wq"]]
            _check_spread(sharded, engine.mesh.devices.size, "serve")
        for i in range(n_reference):
            want = greedy_decode_reference(model, params, prompts[i],
                                           new_tokens)
            check(results[i].tokens == want,
                  f"request {i} differs from greedy_decode_reference: "
                  f"{results[i].tokens} != {want}")
    finally:
        jax.config.update("jax_default_matmul_precision", prev_precision)
    return _report(
        "serve", started, c0, setup_s, run_s, model=config.to_dict(),
        head_dim=config.head_dim, max_seqs=max_seqs,
        block_size=block_size, mesh=mesh, prompt_lens=list(prompt_lens),
        new_tokens=new_tokens, warmed_programs=len(warm),
        attention="pallas_compiled" if kernels else "jnp_reference",
        compiles_during_load=compiles_load,
        decode_steps=stats["decode_steps"],
        prefill_chunks=stats["prefill_chunks"],
        parity={"compared": f"greedy tokens of {n_reference} requests "
                            "== greedy_decode_reference, token for token",
                "matmul_precision": precision or "backend default"})


# ---------------------------------------------------------------- kernels --

def _rel_err(got, want):
    import numpy as np
    got, want = (np.asarray(a, np.float32) for a in (got, want))
    return float(np.abs(got - want).max() / max(np.abs(want).max(), 1e-30))


def kernel_cases(attn_shape, ragged_shape, matmul_shape, interpret=False):
    """``(name, kernel, reference, args, bound)`` for each Pallas kernel
    the package dispatches to by default on a TPU."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops.flash_attention import (attention_reference,
                                               flash_attention)
    from mxnet_tpu.ops.quantization import (quantized_matmul,
                                            quantized_matmul_reference)
    from mxnet_tpu.ops.ragged_attention import (
        ragged_flat_attention, ragged_flat_attention_reference)

    rs = np.random.RandomState(3)

    # flash attention forward and backward, with and without bias
    B, H, T, D = attn_shape
    q, k, v, g = (jnp.asarray(rs.randn(B, H, T, D), jnp.bfloat16)
                  for _ in range(4))
    bias = jnp.asarray(np.where(rs.rand(B, T) < 0.9, 0.0, -1e9),
                       jnp.float32)

    def flash(q, k, v, *b):
        return flash_attention(q, k, v, *b, interpret=interpret)

    def grads(fn):
        return jax.grad(lambda q, k, v, *b: jnp.sum(
            fn(q, k, v, *b).astype(jnp.float32) * g), argnums=(0, 1, 2))

    def in_f32(fn):
        return lambda *a: fn(*(x.astype(jnp.float32) for x in a))

    for tag, args in (("", (q, k, v)), ("_bias", (q, k, v, bias))):
        yield ("flash_fwd" + tag, flash, in_f32(attention_reference), args,
               FLASH_BF16_TOL)
        yield ("flash_bwd" + tag, grads(flash),
               in_f32(grads(attention_reference)), args, FLASH_BF16_TOL)

    # ragged flat attention over float and int8 pages
    Tq, Hr, Dr, bs, N, MB = ragged_shape
    S = 4
    qr = jnp.asarray(rs.randn(Tq, Hr, Dr), jnp.float32)
    pages = rs.randn(2, N, bs, Hr, Dr).astype(np.float32)
    tables = jnp.asarray(rs.permutation(N - 1)[:S * MB].reshape(S, MB) + 1,
                         jnp.int32)
    sids = jnp.asarray(rs.randint(0, S, Tq), jnp.int32)
    poss = jnp.asarray(rs.randint(0, MB * bs, Tq), jnp.int32)
    scales = np.abs(pages).max(axis=-1) / 127.0          # [2, N, bs, H]
    qpages = np.clip(np.round(pages / scales[..., None]), -127,
                     127).astype(np.int8)
    for name, (kp, vp), kw in (
            ("ragged_flat_f32", pages, {}),
            ("ragged_flat_int8", qpages,
             {"k_scales": jnp.asarray(scales[0]),
              "v_scales": jnp.asarray(scales[1])})):
        yield (name,
               lambda q, kp, vp, kw=kw: ragged_flat_attention(
                   q, kp, vp, tables, sids, poss, use_pallas=True,
                   interpret=interpret, **kw),
               lambda q, kp, vp, kw=kw: ragged_flat_attention_reference(
                   q, kp, vp, tables, sids, poss, **kw),
               (qr, jnp.asarray(kp), jnp.asarray(vp)), RAGGED_F32_TOL)

    # weight-only int8 matmul
    Tm, K, Nm = matmul_shape
    x = jnp.asarray(rs.randn(Tm, K), jnp.float32)
    w = rs.randn(K, Nm).astype(np.float32) / np.sqrt(K)
    ws = np.abs(w).max(axis=0) / 127.0
    wq = jnp.asarray(np.clip(np.round(w / ws), -127, 127), jnp.int8)
    yield ("quantized_matmul_int8",
           lambda x, wq, ws: quantized_matmul(
               x, wq, ws, use_pallas=True, interpret=interpret),
           quantized_matmul_reference, (x, wq, jnp.asarray(ws)),
           WQ_MATMUL_TOL)


def kernels_phase(attn_shape, ragged_shape, matmul_shape, interpret=False):
    """Run every :func:`kernel_cases` kernel against its ``jnp``
    reference (evaluated at ``highest`` matmul precision) on the default
    device. ``interpret=True`` is the CPU rehearsal."""
    import jax
    import numpy as np

    started, c0 = time.monotonic(), _counters()
    errs = {}
    run_s = 0.0
    for name, kernel, reference, args, tol in kernel_cases(
            attn_shape, ragged_shape, matmul_shape, interpret):
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(reference)(*args))
        fn = jax.jit(kernel)
        jax.block_until_ready(fn(*args))        # compile
        t0 = time.monotonic()
        got = jax.block_until_ready(fn(*args))
        run_s += time.monotonic() - t0
        pairs = list(zip(jax.tree.leaves(got), jax.tree.leaves(want)))
        check(all(np.isfinite(np.asarray(g, np.float32)).all()
                  and g.shape == w.shape for g, w in pairs),
              f"{name}: non-finite or mis-shaped output")
        errs[name] = max(_rel_err(g, w) for g, w in pairs)
        check(errs[name] <= tol,
              f"{name}: error {errs[name]:.3g} over bound {tol}")
    return _report(
        "kernels", started, c0, time.monotonic() - started - run_s, run_s,
        interpret=interpret, attn_shape=list(attn_shape),
        ragged_shape=list(ragged_shape), matmul_shape=list(matmul_shape),
        rel_err={k: float(f"{e:.3g}") for k, e in errs.items()})


# ------------------------------------------------ the flat kernels alone --

def latent_batch(rs, T, MB, block_size, slots=8, chunk=16, median=1046.0):
    """A packed step of ``T`` query tokens over ``slots`` sequences as
    ``LLMEngine._build_batch`` lays it out: each sequence's tokens side
    by side at ascending positions, as many whole prefill chunks of
    ``chunk`` tokens as fit while the other slots decode one token
    each (T=8: eight decodes; 23: a chunk and seven; 128: eight
    chunks). Contexts are drawn as the conversation cells' traffic
    draws its prompts (lognormal about ``median`` tokens, sigma 0.9),
    cut to the table, and a chunk stands somewhere inside its prompt:
    most tables are far from full. The engine keeps one pair of buffers a shape and
    never clears them, so the step is drawn twice: an earlier one that
    fills the buffers, then the step itself with slot 0 idle and the
    others in another admit order, written over the front. What is left
    of the earlier step is the stale tail, a token or a chunk of a
    sequence that the step also holds further up. Returns ``(seq_ids,
    positions, valid)``, ``valid`` the step's own tokens; ``T`` is 2
    or more."""
    import numpy as np
    chunks = min(slots, max(0, T - slots) // (chunk - 1))
    lens = [chunk] * chunks + [1] * min(slots - chunks, T)
    limit = MB * block_size

    def step(order):
        sids, poss = [], []
        for slot, n in zip(order, lens):
            ctx = int(np.clip(rs.lognormal(np.log(median), 0.9), n, limit))
            if n > 1:                   # a chunk inside the prompt
                ctx = int(rs.randint(n, ctx + 1))
            sids += [slot] * n
            poss += list(range(ctx - n, ctx))
        return sids, poss

    sids, poss = step(range(len(lens)))
    pad = T - len(sids)                 # a first step's tail: zeros
    sids, poss = sids + [0] * pad, poss + [0] * pad
    order = 1 + rs.permutation(len(lens) - 1)
    if order[-1] == len(lens) - 1:      # the tail's sequence: not beside it
        order = order[::-1]
    now = step(order)
    valid = len(now[0])
    sids[:valid], poss[:valid] = now
    return np.asarray(sids, np.int32), np.asarray(poss, np.int32), valid


def _flat_kernel_alone(phase, cases, q_shape, pools, attend, reference, tol,
                       reps, **facts):
    """A flat attention kernel alone over ``pools`` (the first
    ``[layers, num_blocks, block_size, ...]``): for each ``(T, MB,
    median context)`` of ``cases`` a batch from :func:`latent_batch`
    over 8 tables of ``MB`` pages; ``attend(q, tables, seq_ids,
    positions, layer, *pools)`` on it, every row of the step's own tokens against
    ``reference`` (same arguments; the stale tail's rows only finite);
    then ``reps`` calls timed twice: with that stale tail, as the engine
    left it before PR 31, and with the tail it writes since (the last
    valid token's slot at position 0)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    started, c0 = time.monotonic(), _counters()
    layers, num_blocks, block_size = pools[0].shape[:3]
    layer, slots = layers // 2, 8
    ms, ms_clean, errs, ctx = {}, {}, {}, {}
    run_s = 0.0
    for T, MB, median in cases:
        name = f"T{T}_MB{MB}"
        rs = np.random.RandomState(1000 * T + MB)
        q = jax.random.normal(jax.random.PRNGKey(T), (T,) + q_shape,
                              jnp.float32)
        check(slots * MB < num_blocks, f"{phase} {name}: the pool is too "
              f"small for {slots} tables of {MB} pages")
        tables = jnp.asarray(1 + rs.permutation(num_blocks - 1)[
            :slots * MB].reshape(slots, MB), jnp.int32)
        sids, poss, valid = latent_batch(rs, T, MB, block_size, slots,
                                         median=median)
        clean = sids.copy(), poss.copy()
        clean[0][valid:], clean[1][valid:] = sids[valid - 1], 0
        sids, poss = jnp.asarray(sids), jnp.asarray(poss)
        got = jax.block_until_ready(
            jax.jit(attend)(q, tables, sids, poss, layer, *pools))
        # `reps` calls inside ONE program, a layer after the other as a
        # step makes them: a call from the host costs 0.2 ms before the
        # kernel starts, more than the kernel takes
        many = jax.jit(lambda *a: jax.lax.fori_loop(
            0, reps, lambda i, _: attend(*a[:4], i % layers, *a[4:]),
            jnp.zeros_like(got)))
        jax.block_until_ready(many(q, tables, sids, poss, *pools))  # compile
        for into, tail in ((ms, (sids, poss)), (ms_clean, clean)):
            tail = [jnp.asarray(x) for x in tail]
            t0 = time.monotonic()
            jax.block_until_ready(many(q, tables, *tail, *pools))
            dt = time.monotonic() - t0
            run_s += dt
            into[name] = round(dt / reps * 1e3, 4)
        ctx[name] = round(float(poss[:valid].mean()) + 1, 1)
        # the reference gathers a whole table a row: 16 rows a time
        with jax.default_matmul_precision("highest"):
            want = jnp.concatenate([jax.jit(reference)(
                q[r:r + 16], tables, sids[r:r + 16], poss[r:r + 16], layer,
                *pools) for r in range(0, valid, 16)])
        check(got.shape[:2] == q.shape[:2]
              and bool(np.isfinite(np.asarray(got)).all()),
              f"{phase} {name}: non-finite or mis-shaped output")
        errs[name] = _rel_err(got[:valid], want[:valid])
        check(errs[name] <= tol,
              f"{phase} {name}: error {errs[name]:.3g} over bound {tol}")
    return _report(
        phase, started, c0, time.monotonic() - started - run_s, run_s,
        pool=list(pools[0].shape), dtype=str(pools[0].dtype), reps=reps,
        ms_per_call=ms, ms_per_call_clean_tail=ms_clean, mean_context=ctx,
        rel_err={k: float(f"{e:.3g}") for k, e in errs.items()}, **facts)


def latent_phase(cases, layers, num_blocks, block_size, width, heads, v_dim,
                 dtype="bfloat16", reps=50, interpret=False):
    """The flat latent kernel alone over a pool ``[layers, num_blocks,
    block_size, width]`` (see :func:`_flat_kernel_alone`); contexts
    about 1,046 tokens, as the Moonlight cell draws them."""
    import jax
    from mxnet_tpu.ops.ragged_attention import (
        mla_flat_attention, mla_flat_attention_reference)

    scale = 192 ** -0.5
    pool = jax.random.normal(
        jax.random.PRNGKey(5),
        (layers, num_blocks, block_size, width)).astype(dtype)

    def attend(q, tables, sids, poss, layer, pool):
        return mla_flat_attention(
            q, pool, tables, sids, poss, scale, v_dim, layer=layer,
            use_pallas=True, interpret=interpret)

    def reference(q, tables, sids, poss, layer, pool):
        return mla_flat_attention_reference(
            q, pool, tables, sids, poss, scale, v_dim, layer=layer)

    return _flat_kernel_alone(
        "latent", [(T, MB, 1046.0) for T, MB in cases], (heads, width),
        (pool,), attend, reference, LATENT_TOL, reps, interpret=interpret,
        heads=heads, v_dim=v_dim)


def ragged_phase(cases, layers, num_blocks, block_size, heads, head_dim,
                 dtype="float32", reps=50, interpret=False):
    """The flat K/V kernel alone over pools ``[layers, num_blocks,
    block_size, heads, head_dim]`` of ``dtype`` (``int8``: with
    per-slot per-head scale pools), ``cases`` being ``(T, MB, median
    context)`` (see :func:`_flat_kernel_alone`)."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops.ragged_attention import (
        ragged_flat_attention, ragged_flat_attention_reference)

    shape = (layers, num_blocks, block_size, heads, head_dim)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    if dtype == "int8":
        pools = tuple(jax.random.randint(k, shape, -127, 128, jnp.int8)
                      for k in keys[:2])
        pools += tuple(jax.random.uniform(k, shape[:-1], jnp.float32, 0.5,
                                          1.5) / 127.0 for k in keys[2:])
    else:
        pools = tuple(jax.random.normal(k, shape, jnp.dtype(dtype))
                      for k in keys[:2])

    def attend(q, tables, sids, poss, layer, kp, vp, *scales):
        return ragged_flat_attention(
            q, kp, vp, tables, sids, poss, layer=layer, use_pallas=True,
            interpret=interpret, **dict(zip(("k_scales", "v_scales"),
                                            scales)))

    def reference(q, tables, sids, poss, layer, kp, vp, *scales):
        return ragged_flat_attention_reference(
            q, kp, vp, tables, sids, poss, layer=layer,
            **dict(zip(("k_scales", "v_scales"), scales)))

    return _flat_kernel_alone(
        "ragged", cases, (heads, head_dim), pools, attend, reference,
        RAGGED_F32_TOL, reps, interpret=interpret)


def gqa_phase(cases, layers, num_blocks, block_size, heads, kv_heads,
              head_dim, dtype="bfloat16", reps=50, interpret=False):
    """Grouped queries alone over ONE pool of packed rows ``[layers,
    num_blocks, block_size, 2 x kv_heads x head_dim]`` (see
    :func:`_flat_kernel_alone`), contexts as the conversation cells draw
    them, the scale Granite's 1/64."""
    import jax
    from mxnet_tpu.ops.ragged_attention import (
        gqa_flat_attention, gqa_flat_attention_reference)

    scale = 1.0 / 64
    pool = jax.random.normal(
        jax.random.PRNGKey(9), (layers, num_blocks, block_size,
                                2 * kv_heads * head_dim)).astype(dtype)

    def attend(q, tables, sids, poss, layer, pool):
        # queries as large as the cell's, whose wq is four times fan-in
        return gqa_flat_attention(
            4 * q, pool, tables, sids, poss, scale, kv_heads, layer=layer,
            use_pallas=True, interpret=interpret)

    def reference(q, tables, sids, poss, layer, pool):
        return gqa_flat_attention_reference(
            4 * q, pool, tables, sids, poss, scale, kv_heads, layer=layer)

    return _flat_kernel_alone(
        "ragged_gqa", [(T, MB, 1046.0) for T, MB in cases],
        (heads, head_dim), (pool,), attend, reference, LATENT_TOL, reps,
        interpret=interpret, heads=heads, kv_heads=kv_heads)


def ssd_phase(cases, layers, slots, heads, head_dim, state, chunk=16,
              reps=36, interpret=False):
    """The state-space step kernel alone over a pool ``[layers, slots,
    state, heads x head_dim]``: for each ``T`` of ``cases`` a packed step
    as the engine lays it out (whole prefill chunks of ``chunk`` tokens
    in the first slots, some from position 0, one decode token in each
    of the others, the rest padding), the kernel against the per-token
    recurrence on one layer (outputs of the step's own rows, that layer
    of the pool, and every other layer untouched), then ``reps`` calls
    in one program, a layer after the other, timed."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops.ssd import ssd_state_step

    started, c0 = time.monotonic(), _counters()
    width = heads * head_dim
    pool = 0.1 * jax.random.normal(jax.random.PRNGKey(3),
                                   (layers, slots, state, width),
                                   jnp.float32)
    layer = layers // 2
    ms, errs, run_s = {}, {}, 0.0
    for T in cases:
        rs = np.random.RandomState(T)
        chunks = min(slots, max(0, T - slots) // (chunk - 1))
        lens = [chunk] * chunks + [1] * min(slots - chunks, T)
        sids, poss = [], []
        for slot, n in zip(rs.permutation(slots), lens):
            at = 0 if n > 1 and rs.rand() < 0.5 else int(rs.randint(1, 4000))
            sids += [int(slot)] * n
            poss += list(range(at, at + n))
        valid = len(sids)
        pad = T - valid
        sids, poss = sids + [sids[-1]] * pad, poss + [0] * pad
        live = jnp.asarray([1] * valid + [0] * pad, jnp.int32)
        sids, poss = jnp.asarray(sids, jnp.int32), jnp.asarray(poss,
                                                               jnp.int32)
        ks = jax.random.split(jax.random.PRNGKey(T), 4)
        x = jax.random.normal(ks[0], (T, heads, head_dim), jnp.float32)
        dt = jax.nn.softplus(jax.random.normal(ks[1], (T, heads)) - 4.0)
        A = -jnp.linspace(1.0, 16.0, heads)
        B, C = (jax.random.normal(k, (T, state), jnp.float32)
                for k in ks[2:])

        def step(pool, lyr, use_pallas):
            return ssd_state_step(x, dt, A, B, C, pool, sids, poss, live,
                                  lyr, use_pallas=use_pallas,
                                  interpret=interpret)

        got_y, got = jax.jit(lambda p: step(p, layer, True))(pool)
        want_y, want = jax.jit(lambda p: step(p, layer, False))(pool)
        name = f"T{T}"
        errs[name] = max(_rel_err(got_y[:valid], want_y[:valid]),
                         _rel_err(got[layer], want[layer]))
        check(errs[name] <= SSD_TOL,
              f"ssd {name}: error {errs[name]:.3g} over bound {SSD_TOL}")
        others = [i for i in range(layers) if i != layer]
        check(bool(jnp.array_equal(got[jnp.asarray(others)],
                                   pool[jnp.asarray(others)])),
              f"ssd {name}: a layer that the step did not name changed")
        del got, want
        many = jax.jit(lambda p: jax.lax.fori_loop(
            0, reps, lambda i, c: (step(c[0], i % layers, True)[1],
                                   c[1] + 1), (p, 0))[0],
            donate_argnums=(0,))
        pool = jax.block_until_ready(many(pool))            # compile
        t0 = time.monotonic()
        pool = jax.block_until_ready(many(pool))
        dt_s = time.monotonic() - t0
        run_s += dt_s
        ms[name] = round(dt_s / reps * 1e3, 4)
    return _report(
        "ssd", started, c0, time.monotonic() - started - run_s, run_s,
        pool=list(pool.shape), reps=reps, ms_per_call=ms,
        rel_err={k: float(f"{e:.3g}") for k, e in errs.items()},
        interpret=interpret)


def _expert_weights(experts, d_model, width, dtype):
    """``(router [d, E] float32, (Wg, Wu [E, d, m], Wd [E, m, d]))`` of one
    expert layer, drawn on the device by fan-in (the router three times
    that, as the block-diffusion cell's)."""
    import jax
    import jax.numpy as jnp
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    router = 3 * d_model ** -0.5 * jax.random.normal(
        ks[0], (d_model, experts), jnp.float32)
    w_gate, w_up = (
        (d_model ** -0.5 * jax.random.normal(
            k, (experts, d_model, width), jnp.float32)).astype(dtype)
        for k in ks[1:3])
    w_down = (width ** -0.5 * jax.random.normal(
        ks[3], (experts, width, d_model), jnp.float32)).astype(dtype)
    return router, (w_gate, w_up, w_down)


def _expert_products_alone(cases, router, weights, top_k, dtype, reps,
                           interpret):
    """The grouped expert products of one layer alone, at each ``T`` of
    ``cases`` tokens routed ``top_k`` ways by the softmax router, against
    the plain twin: ``(ms a call, relative error, experts hit, row tiles
    the pairs fill, seconds timed)``, the first four by case. The
    tiles are counted here from the routing, so that the phase runs on a
    tree whose ``moe_load`` does not count them."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops.moe import (TILE_ROWS, moe_experts,
                                   moe_experts_reference, route_softmax_topk)

    ms, errs, hit, tiles, run_s = {}, {}, {}, {}, 0.0
    for T in cases:
        name = f"T{T}"
        x = jax.random.normal(jax.random.PRNGKey(T + 1),
                              (T, router.shape[0]), jnp.float32)
        live = jnp.ones((T,), jnp.int32)
        sel, gates = route_softmax_topk(x, router, top_k)
        counts = np.bincount(np.asarray(sel).reshape(-1),
                             minlength=router.shape[1])
        hit[name] = int((counts > 0).sum())
        tiles[name] = int((-(-counts // TILE_ROWS)).sum())

        # every large array goes in as an argument: one that a jitted
        # function closes over becomes a constant of its program
        def products(x, wg, wu, wd):
            return moe_experts(x.astype(dtype), sel, gates, live, wg, wu,
                               wd, use_pallas=True, interpret=interpret)

        got = jax.block_until_ready(jax.jit(products)(x, *weights))
        want = jax.jit(lambda x, wg, wu, wd: moe_experts_reference(
            x.astype(dtype), sel, gates, live, wg, wu, wd))(x, *weights)
        errs[name] = _rel_err(got, want)
        # each call's input leans on the last one's output, so that no
        # call is folded into another
        many = jax.jit(lambda x, *w: jax.lax.fori_loop(
            0, reps, lambda i, c: x + 1e-6 * products(c, *w), x))
        jax.block_until_ready(many(x, *weights))                # compile
        t0 = time.monotonic()
        jax.block_until_ready(many(x, *weights))
        dt = time.monotonic() - t0
        run_s += dt
        ms[name] = round(dt / reps * 1e3, 4)
    return ms, errs, hit, tiles, run_s


def experts_phase(cases, experts, top_k, d_model, width, dtype="bfloat16",
                  reps=28, interpret=False):
    """One layer's grouped expert products alone (``experts`` stacked
    SwiGLUs of ``[d_model, width]``, ``top_k`` a token) at each ``T`` of
    ``cases``, against the plain twin; timed over ``reps`` calls in one
    program."""
    started, c0 = time.monotonic(), _counters()
    router, weights = _expert_weights(experts, d_model, width, dtype)
    ms, errs, hit, tiles, run_s = _expert_products_alone(
        cases, router, weights, top_k, dtype, reps, interpret)
    for name, e in errs.items():
        check(e <= LATENT_TOL,
              f"experts {name}: error {e:.3g} over bound {LATENT_TOL}")
    return _report(
        "experts", started, c0, time.monotonic() - started - run_s, run_s,
        experts=[experts, d_model, width], top_k=top_k, dtype=dtype,
        reps=reps, ms_per_call=ms, experts_hit=hit, tiles=tiles,
        rel_err={k: float(f"{e:.3g}") for k, e in errs.items()},
        interpret=interpret)


def blockdiff_phase(cases, layers, num_blocks, block_size, heads, kv_heads,
                    head_dim, block_length, experts, top_k, d_model, width,
                    slots=32, median=330.0, dtype="bfloat16", reps=28,
                    interpret=False):
    """The two kernels of a block-diffusion expert decoder's step alone,
    at a step as the engine lays it out: ``slots`` rows that each feed
    one block of ``block_length`` positions (``T = slots x
    block_length``), or, at a larger ``T``, one row's block replaced by a
    prompt chunk of whole blocks that takes the rest; contexts lognormal
    about ``median`` (the chat cell's prompt and half its answer), every
    block aligned. Grouped queries over ONE pool of packed rows
    ``[layers, num_blocks, block_size, 2 x kv_heads x head_dim]`` under
    the BLOCK-CAUSAL mask (the kernel is given each token's block's last
    position) against the gather reference; the grouped expert products
    over one layer's ``experts`` stacked SwiGLUs of ``width``, ``top_k``
    a token by the softmax router, against the plain twin. Each timed
    over ``reps`` calls in one program."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from mxnet_tpu.ops.ragged_attention import (
        gqa_flat_attention, gqa_flat_attention_reference)

    started, c0 = time.monotonic(), _counters()
    B, MB = block_length, (num_blocks - 1) // slots
    pool = jax.random.normal(
        jax.random.PRNGKey(9), (layers, num_blocks, block_size,
                                2 * kv_heads * head_dim)).astype(dtype)
    router, weights = _expert_weights(experts, d_model, width, dtype)
    layer, scale = layers // 2, head_dim ** -0.5
    ms_attn, errs, run_s = {}, {}, 0.0
    for T in cases:
        name = f"T{T}"
        rs = np.random.RandomState(T)
        lens = [B] * slots
        lens[0] = T - (slots - 1) * B
        check(lens[0] >= B and lens[0] % B == 0,
              f"blockdiff {name}: T is {slots} blocks of {B}, or more by "
              "whole blocks")
        tables = jnp.asarray(1 + rs.permutation(num_blocks - 1)[
            :slots * MB].reshape(slots, MB), jnp.int32)
        ends = [max(n, int(np.clip(rs.lognormal(np.log(median), 0.7), n,
                                   MB * block_size)) // B * B)
                for n in lens]
        sids = jnp.asarray(np.repeat(np.arange(slots), lens), jnp.int32)
        poss = jnp.asarray(np.concatenate(
            [np.arange(end - n, end) for n, end in zip(lens, ends)]),
            jnp.int32)
        sees = (poss // B + 1) * B - 1
        q = jax.random.normal(jax.random.PRNGKey(T), (T, heads, head_dim),
                              jnp.float32)

        # every large array goes in as an argument: one that a jitted
        # function closes over becomes a constant of its program
        def attend(q, lyr, pool):
            return gqa_flat_attention(q, pool, tables, sids, sees, scale,
                                      kv_heads, layer=lyr, use_pallas=True,
                                      interpret=interpret)

        got = jax.block_until_ready(jax.jit(attend)(q, layer, pool))
        reference = jax.jit(
            lambda q, s, p, pool: gqa_flat_attention_reference(
                q, pool, tables, s, p, scale, kv_heads, layer=layer))
        with jax.default_matmul_precision("highest"):
            want = jnp.concatenate([
                reference(q[r:r + 16], sids[r:r + 16], sees[r:r + 16], pool)
                for r in range(0, T, 16)])
        errs[name + "_attn"] = _rel_err(got, want)
        many = jax.jit(lambda q, pool: jax.lax.fori_loop(
            0, reps, lambda i, _: attend(q, i % layers, pool),
            jnp.zeros_like(got)))
        jax.block_until_ready(many(q, pool))                    # compile
        t0 = time.monotonic()
        jax.block_until_ready(many(q, pool))
        dt = time.monotonic() - t0
        run_s += dt
        ms_attn[name] = round(dt / reps * 1e3, 4)

    ms_experts, e_experts, hit, tiles, dt = _expert_products_alone(
        cases, router, weights, top_k, dtype, reps, interpret)
    run_s += dt
    errs.update({name + "_experts": e for name, e in e_experts.items()})
    for name, e in errs.items():
        check(e <= LATENT_TOL,
              f"blockdiff {name}: error {e:.3g} over bound {LATENT_TOL}")
    return _report(
        "blockdiff", started, c0, time.monotonic() - started - run_s, run_s,
        pool=list(pool.shape), experts=[experts, d_model, width],
        dtype=dtype, reps=reps, ms_per_call_attn=ms_attn,
        ms_per_call_experts=ms_experts, experts_hit=hit, tiles=tiles,
        rel_err={k: float(f"{e:.3g}") for k, e in errs.items()},
        interpret=interpret)


# ------------------------------------------------------------------- main --

def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4 runs train (dp=4) and serve (tp=4) over a "
                         "four-chip mesh in this one process")
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="the phases to run, comma separated, of "
                         + ", ".join(PHASES) + " (kernels, latent, ragged, "
                         "ssd, blockdiff and experts: one chip only)")
    args = ap.parse_args(argv)
    phases = args.phases.split(",")
    if set(phases) - set(PHASES):
        ap.error(f"--phases takes {PHASES}, got {args.phases!r}")

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU; jax found "
                 f"{devices[0].platform!r}")
    if len(devices) < args.chips:
        sys.exit(f"--chips {args.chips} but jax found {len(devices)}")

    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.runtime import enable_compile_cache
    from mxnet_tpu.serving.llm.model import DecoderConfig
    print(json.dumps({"compile_cache": enable_compile_cache()}),
          flush=True)

    many = args.chips > 1
    if "train" in phases:
        train_phase(
            lambda: vision.resnet50_v1(layout="NHWC", stem_s2d=True),
            image=224, batch=128 * args.chips,
            mesh=f"dp={args.chips}" if many else None)
    if "serve" in phases:
        serve_phase(
            DecoderConfig(vocab_size=32000, d_model=1024, num_layers=8,
                          num_heads=8, d_ff=4096, max_context=2048),
            prompt_lens=(16, 48, 96, 160, 240, 320, 416, 512),
            new_tokens=32, mesh=f"tp={args.chips}" if many else None,
            # the TPU's default float32 matmul rounds operands to bf16,
            # and the paged step and the dense reference round different
            # intermediates; pinned so that equal means equal
            precision="highest")
    if "kernels" in phases and not many:
        kernels_phase(attn_shape=(8, 12, 512, 64),
                      ragged_shape=(64, 8, 128, 16, 257, 32),
                      matmul_shape=(256, 1024, 4096))
    if "latent" in phases and not many:
        # the moonlight_16b_a3b cell's pool and widths; its steps pack 8,
        # 23, 38 or 128 tokens over tables of 256 or 512 pages
        latent_phase(cases=((8, 256), (23, 512), (128, 512)), layers=9,
                     num_blocks=4097, block_size=16, width=640, heads=16,
                     v_dim=512)
    if "ragged" in phases and not many:
        # the cerebras_gpt_1b3 cells' pools and widths; their steps pack
        # 8, 23, 38 or 128 tokens over tables of 64 (Alpaca: contexts
        # about 75) or 128 pages (history: about 1,020). One dtype at a
        # time: the float32 pools are 6.4 GB
        cases = ((8, 64, 75.0), (23, 128, 1020.0), (38, 128, 1020.0),
                 (128, 128, 1020.0))
        for dtype in ("float32", "int8"):
            ragged_phase(cases, layers=24, num_blocks=1025, block_size=16,
                         heads=16, head_dim=128, dtype=dtype)
        # the granite_4_0_h_micro cell's pool of packed rows; its steps
        # pack 32, 47, 62 or 512 tokens over tables of 256 or 512 pages
        gqa_phase(cases=((32, 256), (47, 512), (512, 512)), layers=4,
                  num_blocks=16385, block_size=16, heads=32, kv_heads=8,
                  head_dim=64)
    if "ssd" in phases and not many:
        # the same cell's state pool: 32 decode rows, one chunk beside 31
        # decodes, 32 chunks
        ssd_phase(cases=(32, 47, 512), layers=36, slots=32, heads=64,
                  head_dim=64, state=128)
    if "experts" in phases and not many:
        # one expert layer of the moonlight_16b_a3b cell: a decode step of
        # 8 rows and a full chunk step of 128
        experts_phase(cases=(8, 128), experts=64, top_k=6, d_model=2048,
                      width=1408)
    if "blockdiff" in phases and not many:
        # the sdar_30b_a3b_chat cell's pool, heads and one layer's
        # experts: 32 rows of a block of 4 (T 128), and 31 beside a
        # prompt chunk of 388 (T 512)
        blockdiff_phase(cases=(128, 512), layers=7, num_blocks=8193,
                        block_size=16, heads=32, kv_heads=4, head_dim=128,
                        block_length=4, experts=128, top_k=8, d_model=2048,
                        width=768)
    dev = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
