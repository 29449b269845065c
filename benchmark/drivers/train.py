"""Driver for training cells: one compiled training step, stepped for the
window over batches that are already on the device.

The construction follows ``chip_smoke.py``'s ``train_phase`` (a Gluon net
through ``Trainer.compile_step``); the timing is a training loop's: steps
chained, the loss fetched now and then and after the last step. What a
configuration and a traffic mix may set is in ``benchmark/README.md``.

Set-up builds the one step object, drives it through its first steps on
the window's own batches and keeps what the comparison needs: each step's
loss, the first gradient as the optimizer got it (worked out from the
momentum after step 1) and the weights' change after the checked steps.
The same object then runs the window. Once the window has closed and the
program's state is freed, the configuration's plain reference follows the
same steps from the same seed, and ``benchmark/compare.py`` sets the two
side by side.
"""
import gc
import importlib
import time

from benchmark import compare, trace as trace_mod, traffic as traffic_mod


def _resolve(entry):
    module, _, attr = entry.partition(":")
    return getattr(importlib.import_module(module), attr)


def _fallbacks():
    from mxnet_tpu.observability import get_registry
    counter = get_registry().counter("mxtpu_train_step_fallback_total",
                                     labelnames=("reason",))
    return int(sum(c.value for c in counter.children()))


def _spread_ms(spans):
    """Mean, median and 90th percentile of host spans, in milliseconds."""
    if not spans:
        return None
    ordered = sorted(spans)
    return {"mean": 1e3 * sum(ordered) / len(ordered),
            "p50": 1e3 * ordered[len(ordered) // 2],
            "p90": 1e3 * ordered[int(0.9 * len(ordered))]}


def build(config, init, specs):
    """The net with the seed's weights loaded, its trainer and the compiled
    step: ``(net, trainer, step, by_spec)`` where ``by_spec`` maps a
    reference leaf's name to the program's parameter."""
    import numpy as np
    import mxnet_tpu as mx
    import mxnet_tpu.autograd as ag
    from mxnet_tpu import nd
    from mxnet_tpu.gluon import Trainer

    model = config["model"]
    net = _resolve(config["net"]["entry"])(**config["net"]["kwargs"])
    net.initialize()
    with ag.pause(train_mode=False):      # resolves the deferred shapes
        net(nd.NDArray(np.ones((1, model["image"], model["image"], 3),
                               np.float32)))
    params = net.collect_params()
    names = list(params.keys())
    if len(names) != len(specs):
        raise RuntimeError(f"the program has {len(names)} parameters, the "
                           f"reference {len(specs)}")
    by_spec = {}
    for pname, (sname, shape, _) in zip(names, specs):
        p = params[pname]
        if tuple(p.shape) != tuple(shape):
            raise RuntimeError(f"{pname} is {p.shape}, the reference's "
                               f"{sname} is {shape}")
        p.set_data(nd.NDArray(init[sname]))
        by_spec[sname] = p
    net.cast(config["dtype"])

    def loss_fn(x, y):
        logp = mx.nd.log_softmax(net(x).astype("float32"), axis=-1)
        return -mx.nd.pick(logp, y, axis=1)

    opt = dict(config["optimizer"])
    trainer = Trainer(params, opt.pop("name"), opt)
    return net, trainer, trainer.compile_step(loss_fn), by_spec


def optimizer_state(trainer, by_spec, names):
    """``{leaf: (momentum, float32 master weights or None)}`` as jax
    arrays, read from the trainer's updater."""
    states = trainer._updaters[0].states
    index = {p.name: i for i, p in enumerate(trainer._params)}
    out = {}
    for n in names:
        st = states[index[by_spec[n].name]]
        mom, master = st if isinstance(st, tuple) else (st, None)
        out[n] = (mom._data, None if master is None else master._data)
    return out


def first_gradient_norms(state, init, opt):
    """Norm by leaf of the gradient step 1's optimizer got: with zero
    momentum before it, ``mom1 = -lr * (g + wd * w0)``."""
    import jax
    import jax.numpy as jnp
    lr, wd = opt["learning_rate"], opt.get("wd", 0.0)

    @jax.jit
    def norms(moms, w0):
        return {k: jnp.linalg.norm(-moms[k].astype(jnp.float32) / lr
                                   - wd * w0[k]) for k in moms}
    got = norms({k: v[0] for k, v in state.items()},
                {k: init[k] for k in state})
    return {k: float(v) for k, v in got.items()}


def update_norms(state, by_spec, init):
    """Norm by leaf of the weights' change since the seed's values, from
    the float32 master weights where the optimizer keeps them."""
    import jax
    import jax.numpy as jnp
    now = {k: (v[1] if v[1] is not None else by_spec[k].data()._data)
           for k, v in state.items()}

    @jax.jit
    def norms(now, w0):
        return {k: jnp.linalg.norm(now[k].astype(jnp.float32) - w0[k])
                for k in now}
    got = norms(now, {k: init[k] for k in now})
    return {k: float(v) for k, v in got.items()}


def run(ctx):
    import jax
    import numpy as np
    from mxnet_tpu import nd

    config, traffic, seed = ctx["config"], ctx["traffic"], ctx["seed"]
    model, opt = config["model"], config["optimizer"]
    ref = importlib.import_module("benchmark.reference."
                                  + config["reference"])
    batch, n_batches = traffic["batch"], traffic["batches"]
    fetch_every, check_steps = traffic["fetch_every"], traffic["check_steps"]

    # ------------------------------------------------------------ set-up --
    marks = {"imports_s": time.monotonic() - ctx["t_start"]}

    def mark(name):
        marks[name] = time.monotonic() - ctx["t_start"]
    specs = ref.param_specs(model)
    names = ref.trainable(specs)
    init = ref.init_params(seed, model)
    xs_all, ys_all = traffic_mod.train_batches(seed, traffic, model,
                                               config["dtype"])
    xs = [nd.NDArray(xs_all[i]) for i in range(n_batches)]
    ys = [nd.NDArray(ys_all[i]) for i in range(n_batches)]
    jax.block_until_ready((xs_all, ys_all, init))
    mark("inputs_and_weights_s")
    net, trainer, step, by_spec = build(config, init, specs)
    mark("net_built_s")

    failed = 0
    spans = {"dispatch": [], "dispatch_traced": []}

    def one(i, span=None):
        nonlocal failed
        t0 = time.monotonic()
        loss = step(xs[i % n_batches], ys[i % n_batches])
        if span:
            spans[span].append(time.monotonic() - t0)
        if step.last_reason is not None:
            failed += 1
        return loss

    def fetch(loss, rows=False):
        nonlocal failed
        value = loss.asnumpy().astype(np.float64)
        if not np.isfinite(value).all():
            failed += 1
        return value.tolist() if rows else float(value.mean())

    # the first steps, through the window's own call and feed
    seen = {"losses": []}
    for i in range(check_steps):
        by_row = fetch(one(i), rows=True)
        seen["losses"].append(sum(by_row) / len(by_row))
        if i == 0:
            seen["row_losses"] = by_row
            mark("first_step_s")
            seen["grad_norms"] = first_gradient_norms(
                optimizer_state(trainer, by_spec, names), init, opt)
    seen["update_norms"] = update_norms(
        optimizer_state(trainer, by_spec, names), by_spec, init)
    done = check_steps
    fetch(one(done))                  # one more: the fetch path is warm
    done += 1
    setup_failed = failed
    compiles0, fallbacks0 = ctx["xla_compiles"](), _fallbacks()
    setup_s = time.monotonic() - ctx["t_start"]

    # ------------------------------------------------------------ window --
    traced, trace_data = None, None
    t_open = time.monotonic()
    first = done
    if ctx["trace"]:
        with trace_mod.recording() as recorded:
            # every traced step and the fetch that waits for the last lie
            # under a bench.* annotation: trace.py takes the window from
            # them, on the trace's own clock
            loss = None
            for _ in range(traffic["trace_steps"]):
                with jax.profiler.TraceAnnotation("bench.dispatch"):
                    loss = one(done, span="dispatch_traced")
                done += 1
            with jax.profiler.TraceAnnotation("bench.fetch_loss"):
                fetch(loss)
        traced = {"steps": done - first, "images": (done - first) * batch}
        trace_data = recorded["trace"]
    while time.monotonic() - t_open < ctx["seconds"]:
        loss = None
        for _ in range(fetch_every):
            # with the profiler off: under it a call takes some 16 ms
            loss = one(done, span="dispatch" if ctx["trace"] else None)
            done += 1
        fetch(loss)
    window_s = time.monotonic() - t_open
    steps = done - first
    compiles = ctx["xla_compiles"]() - compiles0
    fallbacks = _fallbacks() - fallbacks0
    memory_peak = ctx["memory_peak_bytes"]()
    memory_stats = ctx["devices"][0].memory_stats() or {}
    programs = step.cache_size()

    # ------------------------------------- free the program, then compare --
    del net, trainer, step, by_spec, xs, ys, loss
    gc.collect()
    jax.clear_caches()
    t0 = time.monotonic()
    want = dict(zip(compare.TRAINING_READINGS,
                    ref.train_steps(init, xs_all, ys_all, model, opt,
                                    steps=check_steps)))
    reference_s = time.monotonic() - t0
    numbers = compare.training(seen, want, ref.matrices(specs))
    numbers["compiles_in_window"] = compiles
    limits = config["limits"]
    checks = {k: {"value": numbers.get(k), "limit": limits[k]}
              for k in limits}
    notes = {"steps": steps, "window_s": window_s, "setup_s": setup_s,
             "reference_s": reference_s, "step_programs": programs,
             "eager_fallbacks": fallbacks, "failed_in_setup": setup_failed,
             "setup_marks": marks,
             "dispatch_ms": {k: _spread_ms(v) for k, v in spans.items()},
             "losses": seen["losses"], "reference_losses": want["losses"],
             "memory_stats": {k: v for k, v in memory_stats.items()
                              if "bytes" in k},
             "not_compared": {k: v for k, v in numbers.items()
                              if k not in limits}}
    return {"attempted": steps, "failed": failed - setup_failed,
            "end_to_end": {"train.images_per_s": steps * batch / window_s,
                           "setup_s": setup_s},
            "memory_peak_bytes": memory_peak, "checks": checks,
            "trace": trace_data, "traced": traced, "spans": spans,
            "counters": {"xla_compiles": compiles,
                         "eager_fallbacks": fallbacks},
            "notes": notes}
