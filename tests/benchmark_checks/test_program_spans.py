"""The program's spans as the benchmark reads them: a toy ``LLMServer``
under a real ``jax.profiler`` capture with the tracer off (what a
``--trace 1`` run is), set beside what the serve driver's ``StepLog``
logs from outside; and each reader of ``benchmark/layer_metrics/`` that
reads the ring, on rings recorded on the chip, against numbers worked out
by hand.
"""
import gzip
import json
import os
import statistics
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import program_spans, run as run_mod  # noqa: E402
from benchmark.drivers.serve import StepLog  # noqa: E402
from mxnet_tpu.observability.tracing import get_tracer  # noqa: E402

CHILDREN = ("mxtpu.llm.schedule", "mxtpu.llm.build", "mxtpu.llm.launch",
            "mxtpu.llm.fetch", "mxtpu.llm.commit")
SERVE_READERS = ("scheduler.schedule_ms.serve", "engine.build_ms.serve",
                 "engine.launch_ms.serve", "engine.commit_ms.serve",
                 "server.queue_wait_ms.serve")
TRAIN_READERS = ("trainer.launch_ms.train", "trainer.host_self_ms.train")
TRACED = {"traced": {"steps": 1}}


def recorded(name):
    path = os.path.join(ROOT, "benchmark", "testdata", name)
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


@pytest.fixture
def ring(monkeypatch):
    """Hands the readers a recorded ring in the tracer's place."""
    def put(spans):
        monkeypatch.setattr(get_tracer(), "snapshot", lambda: spans)
    return put


# ------------------------------------ a toy server under a real capture --

@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """4 clients on 2 slots under a capture that jax started, the tracer
    off: ``(spans, what StepLog logged, the server's stats)``."""
    import jax
    import numpy as np
    from mxnet_tpu.serving.llm import DecoderConfig, LLMServer, TinyDecoder
    tracer = get_tracer()
    assert not tracer.enabled
    # wide enough that a step takes milliseconds on the CPU: the share
    # that the children cover is then about the spans, not their cost
    model = TinyDecoder(DecoderConfig(vocab_size=64, d_model=512,
                                      num_layers=4, num_heads=4, d_ff=2048,
                                      max_context=128))
    server = LLMServer(model, model.init_params(seed=0),
                       name="program_spans", max_seqs=2)
    server.warmup()
    server.start()
    steplog = StepLog(server.engine)
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 64, size=n).tolist() for n in (5, 19, 33, 8)]
    tracer.clear()
    options = jax.profiler.ProfileOptions()     # as trace.recording() does
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("capture")),
                             profiler_options=options)
    try:
        steplog.on = True
        futures = [server.submit(p, 12) for p in prompts]
        for f in futures:
            f.result(timeout=120)
        steplog.on = False
    finally:
        jax.profiler.stop_trace()
    spans = tracer.snapshot()
    stats = server.stats()
    server.shutdown(drain=False)
    tracer.clear()
    return spans, steplog.steps, stats


def dispatched_steps(spans):
    by_parent = {}
    for s in spans:
        by_parent.setdefault(s["parent_id"], []).append(s)
    steps = [s for s in program_spans.named(spans, "mxtpu.llm.step")
             if any(c["name"] == "mxtpu.llm.build"
                    for c in by_parent.get(s["span_id"], []))]
    return steps, by_parent


def test_every_dispatched_step_has_the_five_children(captured):
    spans, logged, _ = captured
    steps, by_parent = dispatched_steps(spans)
    assert len(steps) == len(logged) > 10
    shares = []
    for step in steps:
        children = by_parent[step["span_id"]]
        assert sorted(c["name"] for c in children) == sorted(CHILDREN)
        assert all(c["tid"] == step["tid"] for c in children)
        shares.append(sum(c["dur_us"] for c in children)
                      / max(step["dur_us"], 1))
    assert statistics.median(shares) >= 0.95
    # the server's flush is the step's sibling on the same thread
    resolves = program_spans.named(spans, "mxtpu.llm.resolve")
    assert {r["tid"] for r in resolves} == {steps[0]["tid"]}
    assert all(r["parent_id"] is None for r in resolves)
    assert sum(r["attrs"]["resolved"] for r in resolves) == 4


def test_rows_of_the_step_span_are_what_steplog_logs(captured):
    spans, logged, _ = captured
    steps, by_parent = dispatched_steps(spans)
    steps.sort(key=lambda s: s["ts_us"])
    assert [[tuple(r) for r in s["attrs"]["rows"]] for s in steps] == logged
    for step in steps:
        attrs = step["attrs"]
        assert attrs["running"] == len(attrs["rows"]) <= 2
        assert 0 <= attrs["prefilling"] <= attrs["running"]
        build = next(c for c in by_parent[step["span_id"]]
                     if c["name"] == "mxtpu.llm.build")
        assert build["attrs"]["tokens"] == sum(n for n, _ in attrs["rows"])
        assert build["attrs"]["t_bucket"] >= build["attrs"]["tokens"]
    admitted = sum(s["attrs"]["admitted"] for s in
                   program_spans.named(spans, "mxtpu.llm.schedule"))
    assert admitted == 4


def test_queue_wait_is_counted_where_it_ends(captured):
    spans, _, stats = captured
    # 4 clients on 2 slots: two requests wait for a slot
    assert stats["admissions"] == 4 and stats["queue_wait_s_sum"] > 0
    requests = program_spans.named(spans, "mxtpu.llm.request")
    assert len(requests) == 4
    waits = sorted(r["attrs"]["queue_wait_ms"] for r in requests)
    assert waits[0] >= 0 and waits[-1] > waits[0]
    assert sum(waits) / 1e3 == pytest.approx(stats["queue_wait_s_sum"],
                                             abs=1e-4)
    for r in requests:
        assert r["attrs"]["ttft_ms"] >= r["attrs"]["queue_wait_ms"]


def test_serving_readers_read_the_captured_ring(captured, ring):
    ring(captured[0])
    for name in SERVE_READERS:
        assert run_mod.layer_reader(name)(TRACED) > 0, name


# --------------------------------------------- the readers, by hand --

def span(name, span_id, parent_id, dur_us, **attrs):
    return {"name": name, "cat": "llm", "ts_us": 0, "dur_us": dur_us,
            "tid": 1, "thread": "t", "span_id": span_id,
            "parent_id": parent_id, "parent_tid": 1, "attrs": attrs}


def test_self_time_and_medians_by_hand(ring):
    spans = [span("mxtpu.train_step", 1, None, 1000),
             span("mxtpu.train_step.prepare", 2, 1, 200),
             span("mxtpu.train_step.dispatch", 3, 1, 600),
             span("mxtpu.train_step.rebind", 4, 1, 150),
             span("mxtpu.train_step", 5, None, 5000),
             span("mxtpu.train_step.compile", 6, 5, 3000),
             span("mxtpu.train_step.dispatch", 7, 5, 1500),
             span("mxtpu.train_step", 8, None, 900),
             span("mxtpu.train_step.dispatch", 9, 8, 700),
             # a dispatch under no step counts for no step's self time
             span("mxtpu.train_step.dispatch", 10, None, 50)]
    assert program_spans.self_ms(
        spans, "mxtpu.train_step", ("mxtpu.train_step.dispatch",
                                    "mxtpu.train_step.compile")) == 0.4
    assert program_spans.median_ms(program_spans.named(
        spans, "mxtpu.train_step.dispatch")) == 0.65
    ring(spans)
    assert run_mod.layer_reader("trainer.host_self_ms.train")(TRACED) == 0.4
    assert run_mod.layer_reader("trainer.launch_ms.train")(TRACED) == 0.65


def test_steps_that_did_not_dispatch_are_left_out(ring):
    spans = [span("mxtpu.llm.step", 1, None, 100),
             span("mxtpu.llm.schedule", 2, 1, 90),      # found no row
             span("mxtpu.llm.step", 3, None, 1000),
             span("mxtpu.llm.schedule", 4, 3, 30),
             span("mxtpu.llm.build", 5, 3, 50),
             span("mxtpu.llm.commit", 6, 3, 20),
             span("mxtpu.llm.resolve", 7, None, 4),
             span("mxtpu.llm.resolve", 8, None, 6)]
    ring(spans)
    assert run_mod.layer_reader("scheduler.schedule_ms.serve")(TRACED) \
        == 0.03
    assert run_mod.layer_reader("engine.build_ms.serve")(TRACED) == 0.05
    assert run_mod.layer_reader("engine.commit_ms.serve")(TRACED) \
        == pytest.approx(0.02 + 0.005)
    assert run_mod.layer_reader("engine.launch_ms.serve")(TRACED) is None


def test_serving_readers_on_the_ring_recorded_on_the_chip(ring, monkeypatch):
    """Five traced steps of ``cgpt1b3-alpaca-closed16`` on the v5e. In
    microseconds: schedule 71 259 45 39 41, build 145 171 135 149 132,
    launch 2024 2023 2337 2114 2010, commit 119 64 69 47 98, resolve
    6 4 4 3 95; two requests waited 2047.89 and 1965.848 ms."""
    import mxnet_tpu.observability as obs
    spans = recorded("cgpt1b3_serve_ring_5steps.json.gz")["spans"]
    ring(spans)
    want = {"scheduler.schedule_ms.serve": 0.045,
            "engine.build_ms.serve": 0.145,
            "engine.launch_ms.serve": 2.024,
            "engine.commit_ms.serve": 0.069 + 0.004}
    for name, value in want.items():
        assert run_mod.layer_reader(name)(TRACED) == pytest.approx(value)
    registry = obs.MetricsRegistry()
    waits = registry.histogram("mxtpu_llm_queue_wait_seconds", "",
                               ("server",))
    for i, r in enumerate(program_spans.named(spans, "mxtpu.llm.request")):
        waits.labels(server=f"s{i}").observe(
            r["attrs"]["queue_wait_ms"] / 1e3)
    monkeypatch.setattr(obs, "get_registry", lambda: registry)
    assert run_mod.layer_reader("server.queue_wait_ms.serve")(TRACED) == \
        pytest.approx((2047.89 + 1965.848) / 2)
    # on the chip too the five children cover the step
    steps, by_parent = dispatched_steps(spans)
    assert len(steps) == 5
    for step in steps:
        covered = sum(c["dur_us"] for c in by_parent[step["span_id"]])
        assert 0.99 < covered / step["dur_us"] <= 1


def test_training_readers_on_the_ring_recorded_on_the_chip(ring):
    """Five traced steps of ``resnet50-train-bs128`` on the v5e. In
    microseconds: step 16507 19608 18591 13905 16838, of which dispatch
    8964 9992 11182 7402 8864, which leaves 7543 9616 7409 6503 7974."""
    ring(recorded("resnet50_train_ring_5steps.json.gz")["spans"])
    assert run_mod.layer_reader("trainer.launch_ms.train")(TRACED) == 8.964
    assert run_mod.layer_reader("trainer.host_self_ms.train")(TRACED) == \
        pytest.approx(7.543)


@pytest.mark.parametrize("name", SERVE_READERS + TRAIN_READERS)
def test_reader_finds_nothing_to_read(name, ring, monkeypatch):
    """An empty ring and a registry without the histogram are what a
    program without this PR's spans and counter leaves: the reader
    returns nothing and does not raise."""
    import mxnet_tpu.observability as obs
    registry = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: registry)
    ring([])
    read = run_mod.layer_reader(name)
    assert read(TRACED) is None
    # the histogram there and nothing observed
    registry.histogram("mxtpu_llm_queue_wait_seconds", "", ("server",))
    assert read(TRACED) is None
    # and whatever the ring holds, nothing of a run that traced nothing
    ring([span("mxtpu.llm.step", 1, None, 10)])
    assert read({"traced": None}) is None
