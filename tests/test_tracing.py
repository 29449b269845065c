"""End-to-end runtime tracing (mxnet_tpu.observability.tracing).

Pins the span-tracer contracts every perf PR's evidence rides on:

- span mechanics: contextvar nesting, attrs, hand-off spans, and the
  explicit cross-thread propagation primitives (``current``/``attach``/
  ``parent=``) across the two real thread hops — DevicePrefetchIter's
  staging worker and the serving MicroBatchQueue batch former — with
  parent linkage preserved and zero spans left open after drain;
- off = free: with tracing disabled the hot paths return the shared
  no-op singleton and the ``mxtpu_trace_spans_started_total`` counter
  stays exactly flat over real training steps (counter-asserted);
- bounded memory: a 10k-span burst leaves the ring at capacity with
  every eviction counted (the PR 3 memory-flat discipline);
- the acceptance criterion: one ``Estimator.fit`` epoch with tracing on
  exports valid Chrome-trace JSON whose step spans nest compile/
  dispatch children, and serving request spans decompose into
  queue/pad/compute.
"""
import json
import os
import sys
import threading

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
import mxnet_tpu as mx
from mxnet_tpu import gluon, nd
from mxnet_tpu.gluon import nn, Trainer
from mxnet_tpu.observability import MetricsRegistry, get_registry
from mxnet_tpu.observability.tracing import (Tracer, get_tracer,
                                             validate_chrome_trace)
from mxnet_tpu.observability import tracing as tracing_mod

LOSS = gluon.loss.SoftmaxCrossEntropyLoss()


@pytest.fixture
def tracer():
    """The process tracer, enabled and emptied for one test; always
    disabled + drained again afterwards so tracing never leaks into the
    rest of the tier-1 run."""
    tr = get_tracer()
    tr.clear()
    tr.enable()
    yield tr
    tr.disable()
    tr.clear()


def _spans_by_name(tr):
    out = {}
    for s in tr.snapshot():
        out.setdefault(s["name"], []).append(s)
    return out


def _build(seed=0):
    mx.random.seed(seed)
    net = nn.HybridSequential()
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"), nn.Dense(4))
    net.initialize(init=mx.initializer.Xavier())
    return net


def _batches(n=4, batch=16):
    rng = np.random.RandomState(0)
    return [(nd.array(rng.randn(batch, 6).astype(np.float32)),
             nd.array((rng.permutation(batch) % 4).astype(np.float32)))
            for _ in range(n)]


# ---------------------------------------------------- span mechanics --

def test_span_nesting_attrs_and_linkage(tracer):
    with tracer.span("outer", "host", attrs={"k": 1}) as outer:
        with tracer.span("inner") as inner:
            inner.set("x", "y")
        assert tracer.current() is outer
    assert tracer.current() is None
    by = _spans_by_name(tracer)
    assert by["inner"][0]["parent_id"] == by["outer"][0]["span_id"]
    assert by["inner"][0]["attrs"] == {"x": "y"}
    assert by["outer"][0]["attrs"] == {"k": 1}
    # inner finished first, so the ring holds it first (oldest first)
    assert [s["name"] for s in tracer.snapshot()] == ["inner", "outer"]
    assert tracer.stats()["open"] == 0


def test_disabled_span_is_shared_noop_singleton():
    tr = get_tracer()
    assert not tr.enabled
    a, b = tr.span("hot"), tr.span("other", "step", step=3)
    assert a is b, "disabled tracing must not allocate per call"
    with a as sp:
        sp.set("k", "v")          # all no-ops, never raises
    assert a.finish() is None
    assert tr.begin("handoff") is a


def test_ring_bounded_under_10k_spans():
    """Memory stays flat under load: the ring never exceeds capacity
    and every eviction is counted (PR 3 histogram discipline)."""
    reg = MetricsRegistry()
    tr = Tracer(ring=256, registry=reg).enable()
    for i in range(10000):
        tr.span(f"s{i % 7}").finish()
    st = tr.stats()
    assert st["buffered"] == 256
    assert st["capacity"] == 256
    assert st["started"] == 10000
    assert st["dropped"] == 10000 - 256
    assert st["open"] == 0
    assert len(tr.snapshot()) == 256


def test_attach_propagates_context_to_plain_thread(tracer):
    recorded = []

    def worker(parent):
        with tracer.attach(parent):
            assert tracer.current() is parent
            with tracer.span("work") as sp:
                recorded.append(sp.span_id)
        assert tracer.current() is None

    with tracer.span("producer") as parent:
        t = threading.Thread(target=worker, args=(tracer.current(),))
        t.start()
        t.join()
    by = _spans_by_name(tracer)
    work = by["work"][0]
    assert work["span_id"] == recorded[0]
    assert work["parent_id"] == parent.span_id
    assert work["tid"] != by["producer"][0]["tid"]


@pytest.fixture
def capture(tmp_path):
    """A real ``jax.profiler`` capture on the CPU backend, started the
    way an operator or the benchmark starts one: through jax, not
    through ``mx.profiler``."""
    import jax
    jax.profiler.start_trace(str(tmp_path / "capture"))
    try:
        yield
    finally:
        if jax.profiler.TraceAnnotation.is_enabled():
            jax.profiler.stop_trace()


@pytest.fixture
def tracer_off():
    """The process tracer off and emptied, as a run without
    ``MXNET_TPU_TRACE`` has it; emptied again afterwards."""
    tr = get_tracer()
    assert not tr.enabled
    tr.clear()
    yield tr
    tr.clear()


def test_step_annotation_goes_to_innermost_step_span(tracer, capture):
    """XLA step markers do not nest: while a profiler capture runs, only
    the OUTERMOST-at-open step-category span becomes a
    jax.profiler.StepTraceAnnotation — an enclosing epoch span or a
    trainer.step wrapped by CompiledTrainStep's fallback must not garble
    per-step device attribution."""
    import jax
    Step = jax.profiler.StepTraceAnnotation
    with tracer.span("epoch", "epoch", attrs={"epoch": 0}) as ep:
        assert not isinstance(ep._ann, Step)
        with tracer.span("step", "step", step=3) as outer:
            assert isinstance(outer._ann, Step)
            with tracer.span("fallback.step", "step", step=3) as inner:
                assert not isinstance(inner._ann, Step), \
                    "nested step span must degrade to a plain annotation"
        with tracer.span("step2", "step", step=4) as nxt:
            assert isinstance(nxt._ann, Step), \
                "depth must unwind when the outer step span finishes"
    # with the tracer off the capture alone makes spans real, and they
    # obey the same rule
    tracer.disable()
    outer = tracer.span("step", "step", step=5)
    with outer:
        assert isinstance(outer._ann, Step)
        inner = tracer.span("inner", "step", step=5)
        with inner:
            assert not isinstance(inner._ann, Step)
    tracer.enable()


def test_capture_records_spans_with_the_tracer_off(tracer_off, capture):
    """Tracer off and a capture that jax itself started: spans land in
    the ring with parents, durations and attributes, same-thread spans
    are annotations in the capture, hand-off spans are recorded and not
    annotated; once the capture has stopped the shared no-op is back."""
    import jax
    tr = tracer_off
    with tr.span("outer", "llm") as outer:
        assert outer.span_id is not None and outer._ann is not None
        with tr.span("inner", attrs={"k": 1}) as inner:
            inner.set("x", "y")
        handoff = tr.begin("handoff", parent=outer)
        assert handoff.span_id is not None and handoff._ann is None
    worker = threading.Thread(target=handoff.finish)
    worker.start()
    worker.join()
    jax.profiler.stop_trace()
    assert tr.span("after") is tr.begin("after")       # the shared _NULL
    assert tr.span("after").span_id is None
    by = _spans_by_name(tr)
    assert set(by) == {"outer", "inner", "handoff"}
    assert by["inner"][0]["parent_id"] == by["outer"][0]["span_id"]
    assert by["handoff"][0]["parent_id"] == by["outer"][0]["span_id"]
    assert by["outer"][0]["parent_id"] is None
    assert by["inner"][0]["attrs"] == {"k": 1, "x": "y"}
    assert by["outer"][0]["dur_us"] >= by["inner"][0]["dur_us"] >= 0
    assert not tr.enabled


def test_tracer_module_imports_without_jax():
    """The off path asks ``sys.modules`` for jax and never imports it."""
    import subprocess
    code = ("import importlib.util, sys\n"
            "spec = importlib.util.spec_from_file_location('t', sys.argv[1])\n"
            "m = importlib.util.module_from_spec(spec)\n"
            "spec.loader.exec_module(m)\n"
            "assert m._capture_running() is False\n"
            "assert 'jax' not in sys.modules\n")
    subprocess.run([sys.executable, "-c", code, tracing_mod.__file__],
                   check=True)


def test_validator_rejects_malformed_documents():
    ok = {"traceEvents": [{"ph": "X", "name": "a", "pid": 1, "tid": 1,
                           "ts": 0, "dur": 5}]}
    assert validate_chrome_trace(ok) == 1
    assert validate_chrome_trace(json.dumps(ok)) == 1
    for bad in (
            [],                                              # not a dict
            {"traceEvents": {}},                             # not a list
            {"traceEvents": [{"ph": "X", "name": "a"}]},     # no pid/tid
            {"traceEvents": [{"ph": "X", "name": "a", "pid": 1,
                              "tid": 1, "ts": -1, "dur": 2}]},
            {"traceEvents": [{"ph": "s", "name": "f", "pid": 1,
                              "tid": 1}]},                   # flow w/o id
    ):
        with pytest.raises(ValueError):
            validate_chrome_trace(bad)


def test_export_cross_thread_parent_draws_flow_arrows(tracer, tmp_path):
    def worker(parent):
        with tracer.span("child", parent=parent):
            pass

    with tracer.span("parent") as p:
        t = threading.Thread(target=worker, args=(p,))
        t.start()
        t.join()
    path = tracer.export(str(tmp_path / "t.json"))
    n = validate_chrome_trace(path)
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    assert n == 2
    phases = {e["ph"] for e in events}
    assert {"s", "f"} <= phases, "cross-thread hand-off needs flow arrows"
    # export accounting on the registry
    reg = get_registry()
    assert reg.counter("mxtpu_trace_exports_total").value > 0
    assert reg.counter("mxtpu_trace_export_bytes_total").value >= \
        os.path.getsize(path)


# ----------------------------------------- thread-hop instrumentation --

def test_prefetch_worker_spans_parent_under_consumer(tracer):
    """DevicePrefetchIter's staging thread: every stage span links back
    to the consumer's span that started the iteration, and the drain
    leaves nothing open."""
    from mxnet_tpu.gluon.data.prefetch import DevicePrefetchIter
    src = _batches(3)
    with tracer.span("train_loop") as loop:
        out = list(DevicePrefetchIter(src, depth=2))
    assert len(out) == 3
    by = _spans_by_name(tracer)
    stages = by["mxtpu.data_prefetch.stage"]
    assert len(stages) == 3
    for s in stages:
        assert s["parent_id"] == loop.span_id
        assert s["tid"] != by["train_loop"][0]["tid"], \
            "stage spans must come from the worker thread"
        assert s["cat"] == "data"
    assert tracer.stats()["open"] == 0


def test_serving_request_spans_cross_batch_former(tracer):
    """One serving request reads end to end: the hand-off span opens
    under the caller's span, is finished by the MicroBatchQueue worker,
    and decomposes into queue/pad/compute with its request id."""
    from mxnet_tpu import serving
    srv = serving.ModelServer(lambda b: b * 2.0, buckets=[1, 2, 4],
                              max_delay_ms=2.0, item_shape=(3,),
                              name="tsrv").start()
    try:
        with tracer.span("client") as client:
            y = srv.predict(np.ones(3, np.float32))
        assert np.allclose(y, 2.0)
    finally:
        srv.shutdown(drain=True)
    by = _spans_by_name(tracer)
    req = by["mxtpu.serving.request"][0]
    assert req["parent_id"] == client.span_id
    for key in ("req_id", "queue_ms", "pad_ms", "compute_ms", "bucket"):
        assert key in req["attrs"], f"request span lacks {key}"
    assert req["attrs"]["queue_ms"] >= 0
    assert req["attrs"]["compute_ms"] >= 0
    # the worker's batch span nests the pad -> dispatch -> reply stages
    batch = by["mxtpu.serving.batch"][0]
    for stage in ("mxtpu.serving.pad", "mxtpu.serving.dispatch",
                  "mxtpu.serving.reply"):
        assert by[stage][0]["parent_id"] == batch["span_id"]
    assert batch["tid"] != by["client"][0]["tid"]
    assert tracer.stats()["open"] == 0, "drained server leaked spans"


def test_serving_closed_request_span_is_finished(tracer):
    from mxnet_tpu import serving
    from mxnet_tpu.serving import ServerClosed
    srv = serving.ModelServer(lambda b: b, buckets=[1],
                              item_shape=(2,)).start()
    srv.shutdown(drain=True)
    with pytest.raises((ServerClosed, RuntimeError)):
        srv.submit(np.ones(2, np.float32))
    assert tracer.stats()["open"] == 0


def test_checkpoint_write_restore_spans(tracer, tmp_path):
    from mxnet_tpu import resilience as rz
    run = str(tmp_path / "run")
    rz.write_checkpoint(run, {"w": nd.array([1.0, 2.0])}, step=3)
    ckpt, manifest = rz.latest_checkpoint(run)
    rz.read_arrays(ckpt, manifest)
    by = _spans_by_name(tracer)
    w = by["mxtpu.ckpt.write"][0]
    assert w["attrs"]["step"] == 3 and w["attrs"]["bytes"] > 0
    r = by["mxtpu.ckpt.restore"][0]
    assert r["attrs"]["bytes"] > 0
    assert tracer.stats()["open"] == 0


def test_host_scope_is_a_tracer_span_too(tracer):
    """profiler.host_scope: one API, two sinks — existing call sites
    appear in tracer exports without re-instrumentation."""
    from mxnet_tpu import profiler
    with profiler.host_scope("legacy/site"):
        pass
    assert "legacy/site" in _spans_by_name(tracer)


# ----------------------------------------------------- off = free --

def test_tracing_off_training_hot_path_allocates_no_spans():
    """Counter-asserted zero-overhead contract: real compiled training
    steps with tracing off start exactly zero spans."""
    tr = get_tracer()
    assert not tr.enabled
    started = get_registry().counter("mxtpu_trace_spans_started_total")
    net = _build(21)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05})
    step = trainer.compile_step(lambda x, y: LOSS(net(x), y))
    data = _batches(3)
    step(*data[0])                      # compile outside the meter
    c0 = started.value
    for b in data:
        step(*b)
    assert started.value - c0 == 0, \
        "disabled tracing must not start spans on the step hot path"


def test_tracing_off_engine_steps_allocate_no_spans():
    """The same contract over the LLM engine: with the tracer off and
    no capture, scheduling, dispatching and committing whole requests
    start zero spans, and the queue wait is still counted."""
    from mxnet_tpu.serving.llm import (DecoderConfig, LLMServer,
                                       TinyDecoder)
    tr = get_tracer()
    assert not tr.enabled
    started = get_registry().counter("mxtpu_trace_spans_started_total")
    model = TinyDecoder(DecoderConfig(vocab_size=17, d_model=16,
                                      num_layers=2, num_heads=2, d_ff=32,
                                      max_context=64))
    srv = LLMServer(model, model.init_params(seed=0), name="trace_off",
                    max_seqs=2)
    srv.warmup()
    srv.start()
    c0 = started.value
    futures = [srv.submit([1, 2, 3, 4 + i], 6) for i in range(4)]
    assert all(len(f.result(timeout=60).tokens) == 6 for f in futures)
    stats = srv.stats()
    srv.shutdown(drain=False)
    assert started.value - c0 == 0, \
        "disabled tracing must not start spans on the serving hot path"
    assert stats["decode_steps"] > 0 and stats["admissions"] == 4
    assert stats["queue_wait_s_sum"] > 0


def test_capture_splits_the_compiled_training_step(tracer_off, capture):
    """Tracer off, a capture running: every compiled step leaves
    ``mxtpu.train_step`` with prepare, dispatch and rebind under it, in
    that order, and the first one its compile."""
    import jax
    net = _build(22)
    trainer = Trainer(net.collect_params(), "sgd",
                      {"learning_rate": 0.05})
    step = trainer.compile_step(lambda x, y: LOSS(net(x), y))
    for b in _batches(3):
        step(*b)
    jax.profiler.stop_trace()
    spans = tracer_off.snapshot()
    steps = [s for s in spans if s["name"] == "mxtpu.train_step"]
    assert [s["attrs"]["step"] for s in steps] == [0, 1, 2]
    for i, st in enumerate(steps):
        kids = sorted((s for s in spans
                       if s["parent_id"] == st["span_id"]),
                      key=lambda s: s["ts_us"])
        want = ["prepare", "dispatch", "rebind"]
        if i == 0:
            want.insert(1, "compile")
        assert [k["name"] for k in kids] == \
            ["mxtpu.train_step." + w for w in want]
        assert sum(k["dur_us"] for k in kids) <= st["dur_us"]
        assert all(k["tid"] == st["tid"] for k in kids)


# ------------------------------------------------------- acceptance --

def test_estimator_fit_epoch_exports_attributable_trace(tracer, tmp_path):
    """One Estimator.fit epoch with tracing on -> a valid Chrome-trace
    export whose step spans nest compile/dispatch children under the
    epoch span."""
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    net = _build(7)
    est = Estimator(net, LOSS,
                    trainer=Trainer(net.collect_params(), "sgd",
                                    {"learning_rate": 0.05}))
    est.fit(_batches(4), epochs=1, compiled_step=True)

    by = _spans_by_name(tracer)
    epoch = by["mxtpu.estimator.epoch"][0]
    steps = by["mxtpu.train_step"]
    assert len(steps) == 4
    step_ids = {s["span_id"] for s in steps}
    for s in steps:
        assert s["parent_id"] == epoch["span_id"]
        assert s["cat"] == "step"
    # first step compiled; every step dispatched — as children
    assert len(by["mxtpu.train_step.compile"]) == 1
    assert by["mxtpu.train_step.compile"][0]["parent_id"] in step_ids
    dispatches = by["mxtpu.train_step.dispatch"]
    assert len(dispatches) == 4
    assert all(d["parent_id"] in step_ids for d in dispatches)

    path = tracer.export(str(tmp_path / "fit.json"))
    n_events = validate_chrome_trace(path)
    assert n_events >= 4 + 4 + 1 + 1
    with open(path) as f:
        names = {e["name"] for e in json.load(f)["traceEvents"]
                 if e["ph"] == "X"}
    assert {"mxtpu.estimator.epoch", "mxtpu.train_step",
            "mxtpu.train_step.compile",
            "mxtpu.train_step.dispatch"} <= names
    assert tracer.stats()["open"] == 0
