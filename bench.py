"""Benchmark: ResNet-50 training + inference throughput on one chip.

Mirrors the reference's two benchmark protocols:
  - training:  example/image-classification/train_imagenet.py
               (baseline 363.69 img/s, ResNet-50 bs=128 fp32 V100,
               perf.md:243-256) — the headline protocol, since the
               north star (BASELINE.md) is a *training* number.
  - inference: example/image-classification/benchmark_score.py
               (baseline 1233.15 img/s, bs=128 fp32 V100, perf.md:185-198)
               — reported in "extra".

The headline *metric* is MFU (model FLOP utilisation vs the chip's bf16
peak), computed from XLA's own cost analysis of the compiled step and
cross-checked against an analytic FLOP count — "value" is the MFU
percentage and "extra.train_img_s" the throughput behind it.

Timing notes: JAX dispatch is asynchronous, so every timed loop here
  (a) chains iterations through data dependencies (the train step reuses
      its own outputs; the inference step threads `x + 1e-30*logit`
      through so XLA cannot sever the dependency), and
  (b) ends with a device->host scalar fetch, which waits for the chain.
(`block_until_ready()` waits too on this machine: chip_smoke.py's train
phase checks it on every run.) Two self-checks guard the result: the
XLA-counted FLOPs must agree with an analytic ResNet-50 count within 2x,
and the implied FLOP/s must not exceed the chip's bf16 peak — otherwise
"suspect": true is emitted and the run cannot be read as a valid result.

It measures a TPU and raises where JAX finds none.

Config via env: BENCH_BATCH (128), BENCH_DTYPE (bfloat16), BENCH_LAYOUT
(NHWC), BENCH_FP32_PARITY=1 adds the reference-protocol fp32/NCHW run.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "% of bf16 peak", ...}
"""
import json
import os
import time

import numpy as np

TRAIN_BASELINE_IMG_S = 363.69   # ResNet-50 train bs=128 fp32 V100
INFER_BASELINE_IMG_S = 1233.15  # ResNet-50 infer bs=128 fp32 V100

# Analytic ResNet-50 cost at 224x224: ~4.1e9 MACs forward => 8.2 GFLOP/img
# forward (mul+add), ~3x that for fwd+bwd. Used only as a sanity band for
# the XLA-counted number, which is what MFU is computed from.
ANALYTIC_FWD_FLOPS_IMG = 8.2e9
ANALYTIC_TRAIN_FLOPS_IMG = 3 * ANALYTIC_FWD_FLOPS_IMG

# Peak bf16 matmul FLOP/s per chip, by device_kind substring (public
# spec-sheet numbers); MFU is reported against the bf16 peak regardless
# of benchmark dtype so the denominator is well-defined.
_PEAK_BF16 = [
    ("v6", 918e12), ("v5p", 459e12), ("v5", 197e12), ("v4", 275e12),
    ("v3", 123e12), ("v2", 45e12),
]


def _peak_flops(device):
    kind = getattr(device, "device_kind", "").lower()
    for key, peak in _PEAK_BF16:
        if key in kind:
            return peak
    return None


def _cost_flops(compiled):
    try:
        cost = compiled.cost_analysis()
        c = cost[0] if isinstance(cost, (list, tuple)) else cost
        return float(c.get("flops", 0)) or None
    except Exception:
        return None


def _timed_reps(run_n, n, reps=3, step_timer=None, examples_per_rep=None):
    """run_n(n) executes n chained steps and ends with a host fetch;
    returns the median per-step time across reps. ``step_timer`` (an
    observability.StepTimer) brackets each rep — the timed unit is one
    whole n-step rep — with ``examples_per_rep`` (= batch * n) feeding
    the examples/sec gauge, so the bench reports the same data-wait/
    compute split production training does."""
    times = []
    for _ in range(reps):
        if step_timer is not None:
            step_timer.begin_step()
        t0 = time.perf_counter()
        run_n(n)
        times.append((time.perf_counter() - t0) / n)
        if step_timer is not None:
            step_timer.end_step(batch_size=examples_per_rep)
    return sorted(times)[len(times) // 2]


def bench_train_compiled(dtype, layout, batch, train_iters,
                         stem_s2d=False, remat=""):
    """Train-side benchmark through the PRODUCTION runtime path: a gluon
    Trainer driving ``CompiledTrainStep`` — per-step Python dispatch of
    ONE donated program, exactly what a user training loop pays. The
    scan-based protocol (``bench_resnet``) amortizes dispatch over
    ``train_iters`` steps inside one launch and is kept as the
    A/B control (``BENCH_COMPILED_STEP=0``).

    Honest timing: step i+1's program consumes step i's donated weights
    (a real dependency chain), batches are pre-staged on device, and the
    timed unit ends with a host fetch of the last step's loss, which
    synchronises the whole chain."""
    import jax
    import mxnet_tpu as mx
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Trainer
    from mxnet_tpu.gluon.model_zoo import vision
    import mxnet_tpu.autograd as ag

    dev = jax.devices()[0]
    in_shape = (1, 3, 224, 224) if layout == "NCHW" else (1, 224, 224, 3)
    mx.random.seed(0)
    net = vision.resnet50_v1(layout=layout, stem_s2d=stem_s2d)
    net.initialize(init=mx.initializer.Xavier())
    with ag.pause(train_mode=False):
        net(mx.nd.NDArray(np.ones(in_shape, np.float32)))
    if dtype != "float32":
        net.cast(dtype)

    x_shape = (batch,) + in_shape[1:]
    xs = [nd.NDArray(jax.device_put(
              np.random.RandomState(100 + i).randn(*x_shape).astype(dtype),
              dev)) for i in range(train_iters)]
    ys = [nd.NDArray(jax.device_put(
              np.random.RandomState(200 + i).randint(0, 1000, (batch,))
              .astype(np.int32), dev)) for i in range(train_iters)]

    def loss_fn(x, y):
        logits = net(x)
        logp = mx.nd.log_softmax(logits.astype("float32"), axis=-1)
        # per-sample NLL; CompiledTrainStep's rescale_grad /batch makes
        # the update the gradient of the MEAN loss (scan-path parity)
        return -mx.nd.pick(logp, y, axis=1)

    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 1e-3, "momentum": 0.9})
    step = trainer.compile_step(loss_fn, remat=remat or None)

    loss = None

    def run_train(n):
        nonlocal loss
        for i in range(n):
            loss = step(xs[i], ys[i])
        float(loss.asnumpy()[0])   # host fetch == real synchronisation

    run_train(train_iters)          # warmup: compiles the step program
    if step.last_reason is not None:
        raise RuntimeError(
            f"compiled-step bench fell back to eager: {step.last_reason}")
    try:
        from mxnet_tpu.observability import StepTimer
        timer = StepTimer(subsystem="bench_loop")
    except Exception:
        timer = None
    train_dt = _timed_reps(run_train, train_iters, step_timer=timer,
                           examples_per_rep=batch * train_iters)
    final_loss = float(loss.asnumpy().mean())
    assert np.isfinite(final_loss), "training diverged"
    train_flops = _cost_flops(step)

    prof_dir = os.environ.get("BENCH_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            run_train(train_iters)

    return {
        "train_img_s": batch / train_dt, "train_flops": train_flops,
        "train_dt": train_dt, "final_loss": final_loss, "dev": dev,
        # --mesh lever: which SPMD mesh the step compiled over (None =
        # single-device replica path)
        "mesh": os.environ.get("MXNET_TPU_MESH") or None,
    }


def bench_resnet(dtype, layout, batch, train_iters, infer_iters,
                 stem_s2d=False, train=True):
    import jax
    import jax.numpy as jnp
    import mxnet_tpu as mx
    from mxnet_tpu.gluon.model_zoo import vision
    from mxnet_tpu.parallel import functional_call, extract_params
    import mxnet_tpu.autograd as ag

    dev = jax.devices()[0]
    try:
        host = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        host = dev

    # ---- build + init + shape warmup, all on host -----------------------
    in_shape = (1, 3, 224, 224) if layout == "NCHW" else (1, 224, 224, 3)
    with jax.default_device(host):
        mx.random.seed(0)
        net = vision.resnet50_v1(layout=layout, stem_s2d=stem_s2d)
        net.initialize(init=mx.initializer.Xavier())
        with ag.pause():
            net(mx.nd.NDArray(jnp.ones(in_shape, jnp.float32)))
        params_host = {k: np.asarray(v)
                       for k, v in extract_params(net).items()}

    def cast(p):
        return p.astype(dtype) if p.dtype == np.float32 else p

    # single batched transfer to the accelerator
    params = jax.device_put({k: cast(v) for k, v in params_host.items()},
                            dev)
    x_shape = (batch,) + in_shape[1:]
    x = jax.device_put(np.random.RandomState(0).randn(*x_shape)
                       .astype(dtype), dev)
    y = jax.device_put((np.arange(batch) % 1000).astype(np.int32), dev)

    # ---- inference ------------------------------------------------------
    # The timed unit is ONE jitted program scanning `infer_iters` batches:
    # per-step host dispatch (pytree flatten of 100+ params) otherwise
    # dominates at this step time. Each iteration threads a negligible-but-
    # nonzero function of its output into the next input, so XLA cannot
    # sever the chain, and timing ends with a device->host fetch.
    xs_inf = jax.device_put(
        np.random.RandomState(1).randn(infer_iters, *x_shape).astype(dtype),
        dev)

    def infer_n(params, x0, xs):
        def body(s, xi):
            # scalar chain: batch i's input depends on batch i-1's output,
            # so XLA cannot reorder or elide any iteration
            out, _ = functional_call(net, params, xi + s, training=False)
            return (out[0, 0] * 1e-30).astype(xi.dtype), out[0, 0]
        _, outs = jax.lax.scan(body, jnp.zeros((), dtype), xs)
        return outs[-1]

    cinfer = jax.jit(infer_n).lower(params, x, xs_inf).compile()
    # NB: XLA cost analysis counts a while/scan body ONCE, so this is
    # already the per-iteration figure.
    infer_flops = _cost_flops(cinfer)

    def run_infer(n):
        out = cinfer(params, x, xs_inf)
        float(out)  # host fetch == real synchronisation

    run_infer(infer_iters)  # warmup past the post-compile slow window
    infer_dt = _timed_reps(run_infer, infer_iters)
    infer_img_s = batch / infer_dt

    if not train:
        # inference-only invocation (the compiled-step mode benches
        # training through the runtime path instead of the scan)
        return {"infer_img_s": infer_img_s, "infer_flops": infer_flops,
                "dev": dev}

    # ---- training step (fwd+bwd+SGD-momentum, donated buffers) ----------
    def loss_fn(params, x, y):
        logits, aux = functional_call(net, params, x, training=True)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        loss = -jnp.take_along_axis(logp, y[:, None], axis=1).mean()
        return loss, aux

    # BENCH_REMAT: explicit rematerialisation policy for the backward.
    #   full — save nothing, recompute the whole forward (max memory
    #          headroom, ~+33% flops; unlocks larger BENCH_BATCH)
    #   dots — save matmul outputs only (the policy knob XLA can't pick
    #          on its own)
    remat = os.environ.get("BENCH_REMAT", "")
    if remat == "full":
        loss_fn = jax.checkpoint(loss_fn)
    elif remat == "dots":
        loss_fn = jax.checkpoint(
            loss_fn, policy=jax.checkpoint_policies.dots_saveable)

    def train_step(params, mom, x, y):
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, x, y)
        mom = jax.tree.map(lambda m, g: 0.9 * m + g.astype(m.dtype),
                           mom, grads)
        # lr kept small: the bench runs ~100 steps on random labels and the
        # final-loss finiteness assert must not trip on a divergence
        params = jax.tree.map(lambda p, m: p - (1e-3 * m).astype(p.dtype),
                              params, mom)
        for k, v in aux.items():  # BatchNorm running stats thread through
            if k in params:
                params[k] = v.astype(params[k].dtype)
        return params, mom, loss

    mom = jax.device_put({k: np.zeros(v.shape, np.float32)
                          for k, v in params_host.items()}, dev)

    # Same scan treatment as inference: `train_iters` optimizer steps in
    # one program over distinct pre-staged batches. Each step consumes the
    # previous step's params/momentum (a real dependency chain by
    # construction), and timing ends with a loss + post-update-param fetch.
    xs_tr = jax.device_put(
        np.random.RandomState(2).randn(train_iters, *x_shape).astype(dtype),
        dev)
    ys_tr = jax.device_put(
        np.random.RandomState(3).randint(0, 1000, (train_iters, batch))
        .astype(np.int32), dev)

    def train_n(params, mom, xs, ys):
        def body(carry, xy):
            p, m, _ = carry
            p, m, loss = train_step(p, m, *xy)
            return (p, m, loss), None
        (params, mom, loss), _ = jax.lax.scan(
            body, (params, mom, jnp.float32(0)), (xs, ys))
        # one host fetch of `probe` waits for the loss AND the last
        # param update (the loss of step n only depends on step-(n-1)
        # params, so it alone would not wait for the final update)
        probe = loss + (jax.tree.leaves(params)[0].ravel()[0]
                        .astype(jnp.float32) * 1e-30)
        return params, mom, loss, probe

    ctrain = jax.jit(train_n, donate_argnums=(0, 1)).lower(
        params, mom, xs_tr, ys_tr).compile()
    # XLA cost analysis counts the scan body once == per-step flops.
    train_flops = _cost_flops(ctrain)

    loss = None

    def run_train(n):
        nonlocal params, mom, loss
        params, mom, loss, probe = ctrain(params, mom, xs_tr, ys_tr)
        float(probe)  # single host fetch == real synchronisation

    run_train(train_iters)  # warmup
    try:
        from mxnet_tpu.observability import StepTimer
        # subsystem bench_loop: mxtpu_bench_step_seconds is already a
        # gauge (headline mirror below), the timer needs histograms
        timer = StepTimer(subsystem="bench_loop")
    except Exception:
        timer = None
    train_dt = _timed_reps(run_train, train_iters, step_timer=timer,
                           examples_per_rep=batch * train_iters)
    train_img_s = batch / train_dt
    final_loss = float(loss)
    assert np.isfinite(final_loss), "training diverged"

    # BENCH_PROFILE=<dir>: capture a device trace of one timed scan so
    # the HBM/MXU split of the step is inspectable (feeds docs/PERF.md)
    prof_dir = os.environ.get("BENCH_PROFILE")
    if prof_dir:
        with jax.profiler.trace(prof_dir):
            run_train(train_iters)

    return {
        "train_img_s": train_img_s, "infer_img_s": infer_img_s,
        "train_flops": train_flops, "infer_flops": infer_flops,
        "train_dt": train_dt, "final_loss": final_loss, "dev": dev,
    }


def _parse_flags():
    """CLI flags for the MFU levers; each maps onto its env var (flags
    win): ``--batch 256 --bn-fused-bwd --remat dots``."""
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--batch", type=int, help="env BENCH_BATCH")
    ap.add_argument("--dtype", help="env BENCH_DTYPE")
    ap.add_argument("--layout", help="env BENCH_LAYOUT")
    ap.add_argument("--remat", choices=["", "full", "dots"],
                    help="backward rematerialisation (env BENCH_REMAT)")
    ap.add_argument("--bn-fused-bwd", dest="bn_fused_bwd", nargs="?",
                    const="1", choices=["0", "1"],
                    help="fused BatchNorm backward: bare flag or 1 = on, "
                         "0 = off (env MXNET_TPU_BN_FUSED_BWD)")
    ap.add_argument("--compiled-step", dest="compiled_step",
                    choices=["0", "1"],
                    help="train via gluon CompiledTrainStep (1, default) "
                         "or the jax-scan control loop (0) "
                         "(env BENCH_COMPILED_STEP)")
    ap.add_argument("--mesh",
                    help="SPMD device mesh for the compiled train step "
                         "('8', 'dp=4,tp=2', ... — parallel.parse_mesh "
                         "spelling; env MXNET_TPU_MESH). The whole "
                         "step then runs as ONE donated SPMD program "
                         "with in-program gradient reduce; see "
                         "tools/multichip_bench.py for the 1..N-device "
                         "scaling protocol")
    ap.add_argument("--iters", type=int, help="env BENCH_ITERS")
    ap.add_argument("--train-iters", type=int,
                    help="env BENCH_TRAIN_ITERS")
    args = ap.parse_args()
    for flag, env in (("batch", "BENCH_BATCH"), ("dtype", "BENCH_DTYPE"),
                      ("layout", "BENCH_LAYOUT"), ("remat", "BENCH_REMAT"),
                      ("mesh", "MXNET_TPU_MESH"),
                      ("compiled_step", "BENCH_COMPILED_STEP"),
                      ("bn_fused_bwd", "MXNET_TPU_BN_FUSED_BWD"),
                      ("iters", "BENCH_ITERS"),
                      ("train_iters", "BENCH_TRAIN_ITERS")):
        v = getattr(args, flag)
        if v is not None:
            os.environ[env] = str(v)


def main():
    _parse_flags()
    batch = int(os.environ.get("BENCH_BATCH", 128))
    dtype = os.environ.get("BENCH_DTYPE", "bfloat16")
    layout = os.environ.get("BENCH_LAYOUT", "NHWC")
    infer_iters = int(os.environ.get("BENCH_ITERS", 50))
    train_iters = int(os.environ.get("BENCH_TRAIN_ITERS", 50))
    compiled_mode = os.environ.get("BENCH_COMPILED_STEP", "1") != "0"
    # MLPerf-style space-to-depth stem (numerically identical to the plain
    # 7x7/s2 stem — tests/test_layout.py); BENCH_S2D=0 opts out.
    stem_s2d = os.environ.get("BENCH_S2D", "1") != "0" and layout == "NHWC"

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU; jax found platform {platform!r}")

    from mxnet_tpu.runtime import enable_compile_cache
    enable_compile_cache()

    # Count XLA compiles for the registry snapshot; install before any
    # tracing happens.
    from mxnet_tpu.observability import install_jax_monitoring_bridge
    install_jax_monitoring_bridge()

    if compiled_mode:
        r = bench_resnet(dtype, layout, batch, train_iters,
                         infer_iters, stem_s2d=stem_s2d, train=False)
        r.update(bench_train_compiled(
            dtype, layout, batch, train_iters, stem_s2d=stem_s2d,
            remat=os.environ.get("BENCH_REMAT", "")))
    else:
        r = bench_resnet(dtype, layout, batch, train_iters,
                         infer_iters, stem_s2d=stem_s2d)
    dev = r["dev"]
    peak = _peak_flops(dev)

    # ---- self-checks ----------------------------------------------------
    suspect = False
    notes = []
    flops = r["train_flops"]
    flops_source = "xla_cost_analysis"
    if flops:
        ratio = flops / (ANALYTIC_TRAIN_FLOPS_IMG * batch)
        if not (0.5 <= ratio <= 2.0):
            suspect = True
            notes.append(f"XLA flop count {flops:.3g} is {ratio:.2f}x the "
                         "analytic ResNet-50 estimate (expected 0.5-2x)")
    else:
        notes.append("no XLA cost analysis available; MFU from analytic "
                     "FLOP estimate")
        flops = ANALYTIC_TRAIN_FLOPS_IMG * batch
        flops_source = "analytic_estimate"
    implied = flops / r["train_dt"]
    if peak and implied > 1.15 * peak:
        suspect = True
        notes.append(f"implied {implied/1e12:.1f} TFLOP/s exceeds chip "
                     f"bf16 peak {peak/1e12:.0f} TFLOP/s — timing is wrong")
    mfu = round(100 * implied / peak, 2) if peak else None

    extra = {
        "train_img_s": round(r["train_img_s"], 2),
        "train_vs_baseline": round(r["train_img_s"] / TRAIN_BASELINE_IMG_S,
                                   3),
        "infer_img_s": round(r["infer_img_s"], 2),
        "infer_vs_baseline": round(r["infer_img_s"] / INFER_BASELINE_IMG_S,
                                   3),
        "dtype": dtype, "layout": layout, "stem_s2d": stem_s2d,
        "flops_per_step": flops, "flops_source": flops_source,
        "implied_tflops": round(implied / 1e12, 2),
        "device_kind": getattr(dev, "device_kind", str(dev)),
        "final_loss": round(r["final_loss"], 4),
        "timing": "chained-deps+host-fetch, median of 3 reps",
    }
    # Step-time split + dispatch accounting (same registry series the
    # training StepTimer feeds): data_fraction ~0 here because batches
    # are pre-staged — the number production loops should converge to
    # with DevicePrefetchIter; the timed train unit is ONE compiled scan
    # over all steps, so host dispatches per optimizer step is 1/iters.
    try:
        from mxnet_tpu.observability import get_registry
        _reg = get_registry()
        extra["data_fraction"] = round(
            float(_reg.gauge("mxtpu_bench_loop_data_fraction").value), 6)
        # compiled-vs-eager dispatch accounting, from the same
        # mxtpu_train_step_* series production training reports on: the
        # compiled-step protocol is 1 launch per optimizer step; the
        # scan control amortizes to 1/train_iters. Any eager fallback
        # steps are itemized by reason — a nonzero fallback count means
        # the headline did NOT measure the compiled path.
        fallback = _reg.counter("mxtpu_train_step_fallback_total",
                                labelnames=("reason",))
        fb = {c.labelvalues[0]: int(c.value)
              for c in fallback.children() if c.value}
        extra["dispatch"] = {
            "protocol": "compiled_step" if compiled_mode else "jax_scan",
            "train_dispatches_per_step":
                1 if compiled_mode else round(1.0 / train_iters, 6),
            "update_dispatches_per_step": 0,  # folded into the step
            "train_step_compiled": int(_reg.counter(
                "mxtpu_train_step_compiled_total").value),
            "train_step_fallback": fb,
            "xla_compiles": int(
                _reg.counter("mxtpu_xla_compile_total").value),
            "xla_cache_hits": int(
                _reg.counter("mxtpu_xla_cache_hits_total").value),
        }
    except Exception:
        pass
    if notes:
        extra["notes"] = notes

    # optional reference-protocol parity run (fp32, NCHW)
    if os.environ.get("BENCH_FP32_PARITY"):
        p = bench_resnet("float32", "NCHW", batch, train_iters, infer_iters)
        extra["fp32_nchw_train_img_s"] = round(p["train_img_s"], 2)
        extra["fp32_nchw_infer_img_s"] = round(p["infer_img_s"], 2)

    out = {
        "metric": f"resnet50_v1_train_bs{batch}_{dtype}_{layout}_mfu",
        "value": mfu,
        "unit": "% of bf16 peak",
        "vs_baseline": round(r["train_img_s"] / TRAIN_BASELINE_IMG_S, 3),
    }
    if suspect:
        out["suspect"] = True
    out["extra"] = extra

    # Mirror the headline numbers into the observability registry and
    # flush the MXNET_TPU_METRICS_LOG snapshot (if enabled): step time /
    # examples-per-sec / compile count land in the same source every
    # other subsystem reports to.
    try:
        from mxnet_tpu.observability import get_registry
        reg = get_registry()
        reg.gauge("mxtpu_bench_step_seconds",
                  "Per-step train time of the last bench run.").set(
            r["train_dt"])
        reg.gauge("mxtpu_bench_examples_per_sec",
                  "Train throughput of the last bench run.").set(
            r["train_img_s"])
        reg.gauge("mxtpu_bench_infer_examples_per_sec",
                  "Inference throughput of the last bench run.").set(
            r["infer_img_s"])
        if mfu is not None:
            reg.gauge("mxtpu_bench_mfu_percent",
                      "Model FLOP utilization of the last bench run."
                      ).set(mfu)
        reg.write_snapshot()
    except Exception:
        pass
    print(json.dumps(out))


if __name__ == "__main__":
    main()
