"""The four readers of the serving thread's period
(``engine.fetch_ms.serve``, ``server.loop_ms.serve``,
``engine.host_ms_per_step.serve``, ``engine.host_share.serve``): on a toy
``LLMServer`` under a real ``jax.profiler`` capture with the tracer off
(what a ``--trace 1`` run is), on a ring written by hand, on a ring and a
counter snapshot recorded on the chip, and on what a program without the
spans or the counter leaves.
"""
import gzip
import json
import os
import statistics
import sys
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmark import phase_spans, program_spans, run as run_mod  # noqa: E402
from mxnet_tpu.observability.tracing import get_tracer  # noqa: E402

READERS = ("engine.fetch_ms.serve", "server.loop_ms.serve",
           "engine.host_ms_per_step.serve", "engine.host_share.serve")
TRACED = {"traced": {"steps": 1}}


def recorded(name):
    path = os.path.join(ROOT, "benchmark", "testdata", name)
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


@pytest.fixture
def ring(monkeypatch):
    """Hands the readers a ring in the tracer's place."""
    def put(spans):
        monkeypatch.setattr(get_tracer(), "snapshot", lambda: spans)
    return put


@pytest.fixture
def registry(monkeypatch):
    """Hands the readers a registry of the test's own, with the phase
    seconds of ``{server: {phase: seconds}}`` counted in it."""
    import mxnet_tpu.observability as obs
    reg = obs.MetricsRegistry()
    monkeypatch.setattr(obs, "get_registry", lambda: reg)

    def count(by_server):
        counter = reg.counter(phase_spans.PHASE_SECONDS, "",
                              ("server", "phase"))
        for server, phases in by_server.items():
            for phase, seconds in phases.items():
                counter.labels(server=server, phase=phase).inc(seconds)
        return reg
    return count


# ------------------------------------ a toy server under a real capture --

@pytest.fixture(scope="module")
def captured(tmp_path_factory):
    """6 requests on 2 slots under a capture that jax started, the tracer
    off, the last two sent once the server had run dry: ``(spans, the
    server's stats)``."""
    import jax
    from mxnet_tpu.serving.llm import DecoderConfig, LLMServer, TinyDecoder
    tracer = get_tracer()
    assert not tracer.enabled
    model = TinyDecoder(DecoderConfig(vocab_size=64, d_model=512,
                                      num_layers=4, num_heads=4, d_ff=2048,
                                      max_context=128))
    server = LLMServer(model, model.init_params(seed=0),
                       name="phase_readers", max_seqs=2)
    server.warmup()
    server.start()
    tracer.clear()
    options = jax.profiler.ProfileOptions()     # as trace.recording() does
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path_factory.mktemp("capture")),
                             profiler_options=options)
    try:
        for wave in (4, 2):
            futures = [server.submit(list(range(1, 7 + 5 * i)), 10)
                       for i in range(wave)]
            for f in futures:
                f.result(timeout=120)
            time.sleep(0.12)        # the server waits, blocked
    finally:
        jax.profiler.stop_trace()
    spans = tracer.snapshot()
    server.shutdown()
    stats = server.stats()
    tracer.clear()
    return spans, stats


def test_readers_read_the_captured_ring_and_the_servers_counter(
        captured, ring, registry):
    spans, stats = captured
    ring(spans)
    registry({"phase_readers": stats["step_phases_s"]})
    got = {name: run_mod.layer_reader(name)(TRACED) for name in READERS}
    assert all(v is not None and v > 0 for v in got.values()), got
    fetches = program_spans.in_dispatched_steps(spans, "mxtpu.llm.fetch")
    assert len(fetches) == stats["steps_dispatched"] > 20
    assert got["engine.fetch_ms.serve"] == statistics.median(
        s["dur_us"] for s in fetches) / 1e3
    # the loop's own work is microseconds, though one wait stood 0.12 s
    waits = program_spans.named(spans, "mxtpu.llm.wait")
    assert max(w["attrs"]["blocked_us"] for w in waits) > 100_000
    assert got["server.loop_ms.serve"] < 1.0
    # the period less its fetch and less the blocked wait: the host's own
    # time a step, which on a CPU is a small part of a step, and more
    # than the loop alone
    steps = program_spans.named(spans, "mxtpu.llm.step")
    assert got["server.loop_ms.serve"] \
        < got["engine.host_ms_per_step.serve"] \
        < statistics.median(s["dur_us"] for s in steps) / 1e3
    assert len(phase_spans.host_us(spans)) == len(fetches) - 1
    # the counter's share is what the server's own stats() say
    assert got["engine.host_share.serve"] == pytest.approx(
        100 * stats["host_share"])
    # and near the span's: host time a step over the period less the wait
    period = statistics.median(
        b["ts_us"] - a["ts_us"] for a, b in zip(steps, steps[1:])) / 1e3
    assert got["engine.host_share.serve"] == pytest.approx(
        100 * got["engine.host_ms_per_step.serve"] / period, rel=0.5)


# --------------------------------------------- the readers, by hand --

def span(name, span_id, parent_id, ts_us, dur_us, tid=1, **attrs):
    return {"name": name, "cat": "llm", "ts_us": ts_us, "dur_us": dur_us,
            "tid": tid, "thread": "t", "span_id": span_id,
            "parent_id": parent_id, "parent_tid": tid, "attrs": attrs}


def step(span_id, ts_us, dur_us, fetch_us=None, tid=1):
    """A step and its children; one that found no row has a schedule
    child alone."""
    out = [span("mxtpu.llm.step", span_id, None, ts_us, dur_us, tid),
           span("mxtpu.llm.schedule", span_id + 1, span_id, ts_us, 50, tid)]
    if fetch_us is not None:
        out += [span("mxtpu.llm.build", span_id + 2, span_id, ts_us + 50,
                     150, tid),
                span("mxtpu.llm.launch", span_id + 3, span_id, ts_us + 200,
                     2000, tid),
                span("mxtpu.llm.fetch", span_id + 4, span_id, ts_us + 2200,
                     fetch_us, tid),
                span("mxtpu.llm.commit", span_id + 5, span_id,
                     ts_us + 2200 + fetch_us, 100, tid)]
    return out


def wait(span_id, ts_us, dur_us, blocked_us=0, routed=0, tid=1):
    return span("mxtpu.llm.wait", span_id, None, ts_us, dur_us, tid,
                blocked_us=blocked_us, routed=routed)


BY_HAND = (
    # a wait that blocked 50 ms, then a dispatched step: the loop took 30
    [wait(1, 0, 50_030, blocked_us=50_000, routed=2)]
    + step(10, 50_040, 10_000, fetch_us=7_000)
    + [span("mxtpu.llm.resolve", 20, None, 60_050, 10, resolved=0)]
    # a step that found no row follows this wait: left out everywhere
    + [wait(21, 60_070, 20)] + step(30, 60_100, 90)
    + [wait(40, 60_200, 40)] + step(50, 60_250, 12_000, fetch_us=9_000)
    # between two dispatched steps a wait that blocked 5 ms
    + [wait(60, 72_300, 5_060, blocked_us=5_000, routed=1)]
    + step(70, 77_400, 11_000, fetch_us=8_000)
    + [wait(80, 88_450, 50)] + step(90, 88_500, 9_000, fetch_us=6_000)
    # the last wait has no step after it, the last step no successor
    + [wait(100, 97_600, 70_000, blocked_us=69_990)]
    # an engine stepped with no server around it, on a thread of its own:
    # no wait between its steps, so no period can be told
    + step(200, 1_000, 5_000, fetch_us=4_000, tid=2)
    + step(210, 9_000, 5_000, fetch_us=4_500, tid=2))


def test_readers_on_a_ring_written_by_hand(ring):
    ring(BY_HAND)
    # the fetches of the six steps that dispatched, both threads
    assert run_mod.layer_reader("engine.fetch_ms.serve")(TRACED) == \
        statistics.median([7.0, 9.0, 8.0, 6.0, 4.0, 4.5])
    # waits 1, 40, 60 and 80 precede a dispatched step: 30, 40, 60, 50 us
    assert phase_spans.loop_us(BY_HAND) == [30, 40, 60, 50]
    assert run_mod.layer_reader("server.loop_ms.serve")(TRACED) == 0.045
    # steps 50 -> 70: 17,150 less the fetch's 9,000 and 5,000 blocked;
    # steps 70 -> 90: 11,100 less 8,000; 10 -> 30 and 30 -> 50 hold the
    # step that did not dispatch; 90 has no successor
    assert phase_spans.host_us(BY_HAND) == [3_150, 3_100]
    assert run_mod.layer_reader("engine.host_ms_per_step.serve")(TRACED) \
        == 3.125


def test_host_share_by_hand(registry):
    registry({"a": dict(wait=10.0, schedule=1.0, build=1.0, launch=2.0,
                        fetch=14.0, commit=1.0, resolve=1.0),
              "b#2": dict(wait=90.0, launch=1.0, fetch=19.0)})
    # outside the wait 40 s, of which the fetches 33
    assert run_mod.layer_reader("engine.host_share.serve")(TRACED) == \
        pytest.approx(100 * 7 / 40)


# ------------------------------------------- recorded on the chip --

def test_readers_on_the_ring_and_counter_recorded_on_the_chip(ring,
                                                              registry):
    """Six traced steps of ``cgpt1b3-alpaca-closed16`` on the v5e (PR 37).
    In microseconds: fetch 8945 8665 8790 8768 8574 8685; the waits
    before them 9 10 7 25 15 9, none blocked, the fourth routing two
    requests; from one step's start to the next 11157 11861 11962 11381
    11104, which less the fetch leaves 2212 3196 3172 2613 2530. Over the
    whole run the thread spent 17.85 s in wait (``stop_trace`` held the
    client loop), 33.71 in fetch and 8.49 in the five other phases."""
    data = recorded("cgpt1b3_serve_phase_ring_6steps.json.gz")
    ring(data["spans"])
    registry({"benchmark": data["phase_seconds"]})
    want = {"engine.fetch_ms.serve": 8.7265,
            "server.loop_ms.serve": 0.0095,
            "engine.host_ms_per_step.serve": 2.613,
            "engine.host_share.serve": 100 * 8.485238 / 42.191069}
    for name, value in want.items():
        assert run_mod.layer_reader(name)(TRACED) == pytest.approx(
            value, rel=1e-6), name
    # on the chip too the leaves cover the period: between the first
    # step's start and the last one's, all but the spans' own cost
    leaves = [s for s in data["spans"] if s["name"].rsplit(".", 1)[1] in
              data["phase_seconds"]]
    steps = program_spans.named(data["spans"], "mxtpu.llm.step")
    first, last = steps[0]["ts_us"], steps[-1]["ts_us"]
    covered = sum(s["dur_us"] for s in leaves
                  if first <= s["ts_us"] < last)
    assert 0.98 < covered / (last - first) <= 1
    # and the counter of dispatched steps is the whole run's
    assert data["steps_dispatched"] == 3902


# ------------------------------------------------- nothing to read --

@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_to_read(name, ring, registry):
    """An empty ring and a registry without the counter are what a
    program without this PR's span and counter leaves: the reader returns
    nothing and does not raise."""
    reg = registry({})
    ring([])
    read = run_mod.layer_reader(name)
    assert read(TRACED) is None
    # the counter there and nothing counted
    reg.counter(phase_spans.PHASE_SECONDS, "", ("server", "phase")).labels(
        server="s", phase="wait")
    assert read(TRACED) is None
    # a server that only ever waited has no share to give
    registry({"s": {"wait": 3.0}})
    assert read(TRACED) is None
    # and whatever the ring and the registry hold, nothing of a run that
    # traced nothing
    ring(BY_HAND)
    registry({"s": {"fetch": 3.0, "launch": 1.0}})
    assert read({"traced": None}) is None


def test_the_parents_ring_gives_the_fetch_alone(ring):
    """The program before this PR recorded ``mxtpu.llm.fetch`` and no
    ``mxtpu.llm.wait``: its ring (five steps on the v5e, PR 26; fetch
    31214 31845 31725 28513 28490 us) gives the fetch and no period."""
    ring(recorded("cgpt1b3_serve_ring_5steps.json.gz")["spans"])
    assert run_mod.layer_reader("engine.fetch_ms.serve")(TRACED) == 31.214
    assert run_mod.layer_reader("server.loop_ms.serve")(TRACED) is None
    assert run_mod.layer_reader(
        "engine.host_ms_per_step.serve")(TRACED) is None
