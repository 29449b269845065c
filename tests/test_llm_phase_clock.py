"""The serving thread's whole period under the program's own phases.

``serving.llm.metrics.PhaseClock`` is marked at every boundary of
``LLMServer``'s loop and ``LLMEngine.step``: wait, schedule, build,
launch, fetch, commit, resolve, with no gap between them. Pinned here:

- the seven sums add up to the worker thread's wall time, and the leaf
  spans of a captured run cover it (the spans open where the clock is
  marked);
- a capture's xplane holds the seven leaves by name on the worker's
  thread, one of each a dispatched step;
- a stay of more than 0.1 s in a phase is counted as a stall under that
  phase, sets its longest stay and leaves an ``llm.stall`` flight event;
- warm-up is in no phase, an engine stepped with no server marks its own
  five, and with the tracer off no span is opened while the phase
  counters move.
"""
import collections
import glob
import os
import sys
import time

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from mxnet_tpu.observability import (  # noqa: E402
    get_flightrecorder, get_registry)
from mxnet_tpu.observability.flightrecorder import (  # noqa: E402
    flight_ring_capacity)
from mxnet_tpu.observability.tracing import get_tracer  # noqa: E402
from mxnet_tpu.serving.llm import (  # noqa: E402
    DecoderConfig, LLMEngine, LLMServer, Sequence, TinyDecoder)
from mxnet_tpu.serving.llm.metrics import (  # noqa: E402
    PHASES, STALL_S, LLMStats, PhaseClock, hand_over)

LEAVES = tuple("mxtpu.llm." + p for p in PHASES)
ENGINE_PHASES = ("schedule", "build", "launch", "fetch", "commit")


@pytest.fixture(scope="module")
def model():
    # wide enough that a step takes ten milliseconds and more on the
    # CPU: what the spans do not cover is their own opening and closing,
    # a hundred microseconds a step here
    return TinyDecoder(DecoderConfig(vocab_size=64, d_model=1024,
                                     num_layers=4, num_heads=8, d_ff=4096,
                                     max_context=128))


@pytest.fixture(scope="module")
def params(model):
    return model.init_params(seed=0)


@pytest.fixture(scope="module")
def small():
    """A model whose step takes well under a millisecond, and its
    parameters: nothing but what a test plants comes near 0.1 s."""
    model = TinyDecoder(DecoderConfig(vocab_size=17, d_model=16,
                                      num_layers=2, num_heads=2, d_ff=32,
                                      max_context=64))
    return model, model.init_params(seed=0)


def toy_server(model, params, name, **kw):
    server = LLMServer(model, params, name=name, max_seqs=2, **kw)
    server.warmup()
    return server


def submit(server, n, new_tokens):
    return [server.submit(list(range(1, 6 + 3 * i)), new_tokens)
            for i in range(n)]


def serve(server, n=4, new_tokens=12):
    return [f.result(timeout=120) for f in submit(server, n, new_tokens)]


# ------------------------------------------------------- the clock --

def test_marks_charge_the_open_phase_and_leave_no_gap():
    clock = PhaseClock()
    t0 = clock.mark("wait")
    clock.mark("schedule")
    clock.mark("build")
    clock.mark("build")             # the open phase again: charged so far
    t1 = clock.mark(None)           # nobody's time from here
    time.sleep(0.002)
    t2 = clock.mark("schedule")
    t3 = clock.mark(None)
    ns, longest, stalls = clock.take()
    assert sum(ns.values()) == (t1 - t0) + (t3 - t2)
    assert set(ns) == set(PHASES) and ns["fetch"] == 0
    assert 0 < longest["build"] <= ns["build"] and not stalls
    # taken is gone; the clock starts anew
    assert not any(clock.take()[0].values())


def test_a_long_stay_is_a_stall_except_in_wait(monkeypatch):
    from mxnet_tpu.serving.llm import metrics
    ticks = iter([0, 150_000_000, 400_000_000, 400_001_000,
                  500_000_000, 500_002_000])
    monkeypatch.setattr(metrics.time, "monotonic_ns", lambda: next(ticks))
    clock = PhaseClock()
    clock.mark("wait")              # 150 ms in wait: waits by right
    clock.mark("fetch")             # 250 ms
    clock.mark("commit")            # 1 us
    clock.mark("fetch")             # 99.999 ms: under the threshold
    clock.mark("commit")
    clock.mark(None)
    ns, longest, stalls = clock.take()
    assert stalls == [("fetch", 250_000_000)]
    assert longest["wait"] == 150_000_000
    assert longest["fetch"] == 250_000_000
    assert ns["fetch"] == 250_000_000 + 99_999_000
    assert STALL_S == 0.1


def test_hand_over_feeds_the_five_families():
    import mxnet_tpu.observability as obs
    registry = obs.MetricsRegistry()
    stats = LLMStats(server="handed", registry=registry)
    clock = PhaseClock()
    clock.ns.update(wait=2_000_000_000, build=300_000_000,
                    fetch=700_000_000)
    clock.longest_ns.update(wait=1_500_000_000, build=250_000_000)
    clock.stalls.append(("build", 250_000_000))
    ns = hand_over(clock, stats, get_flightrecorder(), dispatched=3)
    assert ns["fetch"] == 700_000_000
    snap = stats.snapshot()
    assert snap["step_phases_s"] == dict.fromkeys(PHASES, 0.0) | {
        "wait": 2.0, "build": 0.3, "fetch": 0.7}
    assert snap["steps_dispatched"] == 3
    assert snap["stalls"] == {"build": {"count": 1, "seconds": 0.25}}
    assert snap["step_phase_longest_s"]["wait"] == 1.5
    # a shorter stay later does not lower the longest
    clock.longest_ns.update(wait=1_000_000)
    hand_over(clock, stats, get_flightrecorder())
    assert stats.snapshot()["step_phase_longest_s"]["wait"] == 1.5
    text = registry.expose()
    for family in ("mxtpu_llm_step_phase_seconds_total",
                   "mxtpu_llm_step_phase_longest_seconds",
                   "mxtpu_llm_step_stalls_total",
                   "mxtpu_llm_step_stall_seconds_total",
                   "mxtpu_llm_steps_dispatched_total"):
        assert family in text
    # wait is counted in the first two families and has no stall series
    assert 'mxtpu_llm_step_stalls_total{phase="wait"' not in text.replace(
        'server="handed",', "")


# ---------------------------------- the sums and the thread's wall time --

def test_phase_sums_add_up_to_the_worker_threads_wall_time(model, params,
                                                           monkeypatch):
    server = toy_server(model, params, "phase_sums")
    # warm-up is not the loop: it runs in no phase and dispatches nothing
    assert server.stats()["step_phases_s"] == dict.fromkeys(PHASES, 0.0)
    assert server.stats()["steps_dispatched"] == 0
    wall, launched = {}, []
    inner = server._run_loop_inner

    def timed():
        wall["t0"] = time.monotonic_ns()
        try:
            inner()
        finally:
            wall["t1"] = time.monotonic_ns()
    monkeypatch.setattr(server, "_run_loop_inner", timed)
    call_step = server.engine._call_step
    monkeypatch.setattr(
        server.engine, "_call_step",
        lambda *a: launched.append(1) or call_step(*a))
    server.start()
    assert all(len(r.tokens) == 16
               for r in serve(server, n=8, new_tokens=16))
    time.sleep(0.12)                # an idle server waits, and counts it
    served = server.stats()
    server.shutdown()
    stats = server.stats()
    assert stats["steps_dispatched"] == len(launched) >= 50
    assert served["steps_dispatched"] == len(launched)
    phases = stats["step_phases_s"]
    assert tuple(phases) == PHASES and all(v > 0 for v in phases.values())
    thread_s = (wall["t1"] - wall["t0"]) / 1e9
    assert sum(phases.values()) == pytest.approx(thread_s, rel=0.01)
    assert phases["wait"] > 0.1
    # what the chip would stand idle for, seen from inside: everything
    # outside the loop's wait that is not the fetch
    busy = sum(phases.values()) - phases["wait"]
    assert stats["host_share"] == pytest.approx(
        (busy - phases["fetch"]) / busy)
    assert 0 < stats["host_share"] < 1
    # the one clock of a step: the histogram's sum is the four phases of
    # the steps that held a generating row, so no more than all of them
    step_s = get_registry().get("mxtpu_llm_decode_step_seconds").labels(
        server=server._stats.server_label).sum
    assert 0 < step_s <= (phases["build"] + phases["launch"]
                          + phases["fetch"] + phases["commit"]) * 1.0001


def test_one_thread_one_clock(model, params):
    server = LLMServer(model, params, name="one_clock", max_seqs=2)
    assert all(e._clock is server._clock for e in server._engines)


def test_engine_with_no_server_marks_its_own_five(model, params):
    import mxnet_tpu.observability as obs
    stats = LLMStats(server="alone", registry=obs.MetricsRegistry())
    engine = LLMEngine(model, params, max_seqs=2, stats=stats)
    engine.warmup()
    engine.add(Sequence([1, 2, 3, 4, 5], 6))
    steps = 0
    while engine.has_work():
        engine.step()
        steps += 1
        time.sleep(0.001)           # between two steps: nobody's time
    snap = stats.snapshot()
    assert snap["steps_dispatched"] == steps == 6
    phases = snap["step_phases_s"]
    assert phases["wait"] == phases["resolve"] == 0.0
    assert all(phases[p] > 0 for p in ENGINE_PHASES)
    assert engine._clock._open is None


# ------------------------------------------------ under a capture --

@pytest.fixture(scope="module")
def captured(model, params, tmp_path_factory):
    """Eight requests on two slots under a capture that jax started, the
    tracer off: ``(the ring's spans, the xplane's mxtpu.* events by host
    line)``."""
    import jax
    from jax.profiler import ProfileData
    tracer = get_tracer()
    assert not tracer.enabled
    server = toy_server(model, params, "phase_capture")
    server.start()
    serve(server, n=1, new_tokens=2)
    tracer.clear()
    options = jax.profiler.ProfileOptions()     # as trace.recording() does
    options.python_tracer_level = 0
    trace_dir = str(tmp_path_factory.mktemp("capture"))
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    try:
        futures = submit(server, 4, 16)
        # the worker is stepping by now: what comes next is routed by a
        # wait that the ring holds (the first began before the capture)
        time.sleep(0.05)
        futures += submit(server, 4, 16)
        for f in futures:
            f.result(timeout=120)
    finally:
        jax.profiler.stop_trace()
    spans = tracer.snapshot()
    server.shutdown(drain=False)
    tracer.clear()
    path = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                  "*.xplane.pb"))[-1]
    lines = {}
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            events = [(e.name, int(e.start_ns), int(e.duration_ns))
                      for e in line.events if e.name.startswith("mxtpu.")]
            if events:
                lines[(plane.name, i, line.name)] = events
    return spans, lines


def steps_of(spans):
    built = {s["parent_id"] for s in spans if s["name"] == "mxtpu.llm.build"}
    return sorted((s for s in spans if s["name"] == "mxtpu.llm.step"
                   and s["span_id"] in built), key=lambda s: s["ts_us"])


def test_leaf_spans_cover_the_period_of_a_captured_run(captured):
    spans, _ = captured
    steps = steps_of(spans)
    assert len(steps) >= 50
    first, last = steps[0]["ts_us"], steps[-1]["ts_us"]
    tid = steps[0]["tid"]
    leaves = [s for s in spans if s["name"] in LEAVES and s["tid"] == tid
              and first <= s["ts_us"] < last]
    # one after another, none inside another
    leaves.sort(key=lambda s: s["ts_us"])
    for a, b in zip(leaves, leaves[1:]):
        assert a["ts_us"] + a["dur_us"] <= b["ts_us"] + 1, (a, b)
    covered = sum(min(s["ts_us"] + s["dur_us"], last) - s["ts_us"]
                  for s in leaves)
    assert covered / (last - first) >= 0.98
    # in the order the loop takes them
    names = [s["name"].rsplit(".", 1)[1] for s in leaves]
    period = list(PHASES[1:]) + ["wait"]
    assert names[:len(period) * 3] == period * 3
    for s in leaves:
        if s["name"] == "mxtpu.llm.wait":
            assert s["parent_id"] is None
            assert 0 <= s["attrs"]["blocked_us"] <= s["dur_us"]
            assert s["attrs"]["routed"] >= 0
        if s["name"] == "mxtpu.llm.fetch":
            assert s["attrs"]["bytes"] > 0
        if s["name"] == "mxtpu.llm.launch":
            assert s["attrs"]["args"] == 14
    routed = sum(s["attrs"]["routed"] for s in spans
                 if s["name"] == "mxtpu.llm.wait")
    assert 4 <= routed <= 8


def test_xplane_holds_the_seven_leaves_on_the_workers_thread(captured):
    spans, lines = captured
    # every annotation of the worker stands on one host line
    (key, events), = [(k, v) for k, v in lines.items()
                      if any(n == "mxtpu.llm.step" for n, _, _ in v)]
    assert key[0].startswith("/host:")
    count = collections.Counter(n for n, _, _ in events)
    ring = collections.Counter(s["name"] for s in spans
                               if s["name"] != "mxtpu.llm.request")
    # a span still open when the ring was read is in the xplane alone
    for name in LEAVES + ("mxtpu.llm.step",):
        assert 0 <= count[name] - ring[name] <= 1, name
    # one of each leaf a dispatched step, on the capture's clock: between
    # the first step's start and the last one's every step dispatched
    starts = sorted(t for n, t, _ in events if n == "mxtpu.llm.step")
    assert len(starts) == len(steps_of(spans)) >= 50
    inside = [(n, t, d) for n, t, d in events
              if n in LEAVES and starts[0] <= t < starts[-1]]
    assert collections.Counter(n for n, _, _ in inside) == dict.fromkeys(
        LEAVES, len(starts) - 1)
    assert sum(d for _, _, d in inside) / (starts[-1] - starts[0]) >= 0.97


# ----------------------------------------------------------- stalls --

@pytest.fixture
def flight():
    fl = get_flightrecorder()
    fl.disable()
    fl.clear()
    yield fl
    fl.enable(ring=flight_ring_capacity())
    fl.disable()
    fl.clear()


@pytest.mark.parametrize("method,phase", [("_build_batch", "build"),
                                          ("_device_get", "fetch")])
def test_a_planted_delay_is_one_stall_under_its_phase(small, flight,
                                                      monkeypatch, method,
                                                      phase):
    flight.enable(ring=4096)
    server = toy_server(*small, "stall_" + phase)
    inner, calls = getattr(server.engine, method), []

    def slow(*args):
        calls.append(1)
        if len(calls) == 5:
            time.sleep(0.15)
        return inner(*args)
    monkeypatch.setattr(server.engine, method, slow)
    server.start()
    serve(server, n=2, new_tokens=8)
    stats = server.stats()
    server.shutdown()
    assert list(stats["stalls"]) == [phase]
    assert stats["stalls"][phase]["count"] == 1
    assert 0.15 <= stats["stalls"][phase]["seconds"] < 0.5
    assert stats["step_phase_longest_s"][phase] == pytest.approx(
        stats["stalls"][phase]["seconds"])
    for other in set(ENGINE_PHASES) - {phase}:
        assert stats["step_phase_longest_s"][other] < STALL_S
    label = {"server": server._stats.server_label, "phase": phase}
    registry = get_registry()
    assert registry.get("mxtpu_llm_step_stalls_total").labels(
        **label).value == 1
    assert registry.get("mxtpu_llm_step_stall_seconds_total").labels(
        **label).value == pytest.approx(stats["stalls"][phase]["seconds"])
    events = flight.snapshot()
    stall, = [e for e in events if e["kind"] == "llm.stall"]
    assert stall["attrs"]["phase"] == phase
    assert stall["attrs"]["ms"] == pytest.approx(
        1e3 * stats["stalls"][phase]["seconds"], abs=1e-3)
    assert 1 <= stall["attrs"]["running"] <= 2
    assert stall["attrs"]["rung"] in server.engine._t_buckets
    # the step's own event carries the phases the stall sits among
    step = max((e for e in events if e["kind"] == "llm.step"),
               key=lambda e: e["attrs"]["step_ms"])
    assert step["attrs"]["phases_ms"][phase] >= 150
    assert step["attrs"]["step_ms"] == pytest.approx(
        sum(step["attrs"]["phases_ms"][p]
            for p in ("build", "launch", "fetch", "commit")), abs=0.01)


def test_the_chaos_gate_at_llm_decode_stalls_the_build(small):
    """The harness's own latency injection sits inside the build phase,
    where the gate is checked."""
    from mxnet_tpu.resilience import faults
    server = toy_server(*small, "stall_gate")
    server.start()
    faults.reset()
    try:
        faults.delay_at("llm.decode", 0.15, times=1)
        serve(server, n=1, new_tokens=6)
    finally:
        faults.reset()
    stats = server.stats()
    server.shutdown()
    assert stats["stalls"]["build"]["count"] == 1
    assert list(stats["stalls"]) == ["build"]


# ------------------------------------------------ the tracer off --

def test_tracer_off_opens_no_span_while_the_phase_counters_move(small):
    tracer = get_tracer()
    assert not tracer.enabled
    started = get_registry().counter("mxtpu_trace_spans_started_total")
    server = toy_server(*small, "phase_trace_off")
    server.start()
    serve(server, n=1, new_tokens=2)
    c0, before = started.value, server.stats()
    serve(server)
    after = server.stats()
    server.shutdown(drain=False)
    assert started.value - c0 == 0
    assert after["steps_dispatched"] - before["steps_dispatched"] >= 20
    for phase in PHASES:
        assert after["step_phases_s"][phase] > \
            before["step_phases_s"][phase], phase
