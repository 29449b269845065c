"""The serving thread's period as the per-layer readers see it: its
spans in the order the thread took them, and its phase seconds.

On the worker's thread ``mxtpu.llm.wait`` (the loop's head),
``mxtpu.llm.step`` with its five children, and ``mxtpu.llm.resolve``
follow one another, so the time from one step's start to the next is
what the thread does a step. Two parts of it are waiting and not work:
the step's ``mxtpu.llm.fetch`` (for the device) and, of the wait before
the next step, its ``blocked_us`` (for a client). A program without
these spans or counters leaves every function here with nothing to give.
"""
import importlib

STEP, WAIT, FETCH = "mxtpu.llm.step", "mxtpu.llm.wait", "mxtpu.llm.fetch"
PHASE_SECONDS = "mxtpu_llm_step_phase_seconds_total"


def _by_thread(spans):
    """``[(the thread's steps and waits in time order, the ids of the
    steps that dispatched, fetch_us by step id)]``, one a thread that
    stepped."""
    ps = importlib.import_module("benchmark.program_spans")
    dispatched = {s["parent_id"] for s in ps.named(spans,
                                                   "mxtpu.llm.build")}
    fetch_us = {}
    for s in ps.named(spans, FETCH):
        fetch_us[s["parent_id"]] = fetch_us.get(s["parent_id"], 0) \
            + s["dur_us"]
    out = []
    for tid in sorted({s["tid"] for s in ps.named(spans, STEP)}):
        out.append((sorted((s for s in spans if s["tid"] == tid
                            and s["name"] in (STEP, WAIT)),
                           key=lambda s: s["ts_us"]),
                    dispatched, fetch_us))
    return out


def loop_us(spans):
    """Of each wait that a dispatched step follows next on its thread,
    the microseconds it was not blocked: the loop's own work between two
    steps. The waits of a server with nothing to do (a step that found
    no row follows, or another wait, or nothing) are left out."""
    out = []
    for ordered, dispatched, _ in _by_thread(spans):
        wait = None
        for s in ordered:
            if s["name"] == WAIT:
                wait = s
                continue
            if wait is not None and s["span_id"] in dispatched:
                out.append(wait["dur_us"]
                           - wait["attrs"].get("blocked_us", 0))
            wait = None
    return out


def host_us(spans):
    """Over each two consecutive steps of a thread that both dispatched:
    the microseconds from the first's start to the second's, less the
    first's fetch and less ``blocked_us`` of the waits between. A step
    that did not dispatch ends a pair and starts none; the last step has
    no successor and gives nothing. Nothing at all without a wait
    between: the period cannot then be told from a server that idles."""
    out = []
    for ordered, dispatched, fetch_us in _by_thread(spans):
        first, blocked, waited = None, 0, False
        for s in ordered:
            if s["name"] == WAIT:
                blocked += s["attrs"].get("blocked_us", 0)
                waited = True
                continue
            if first is not None and waited and s["span_id"] in dispatched:
                out.append(s["ts_us"] - first["ts_us"]
                           - fetch_us.get(first["span_id"], 0) - blocked)
            first = s if s["span_id"] in dispatched else None
            blocked, waited = 0, False
    return out


def phase_seconds(seen):
    """``{phase: seconds}`` of the serving threads over the whole run,
    all label children of the program's counter summed by ``phase``; or
    nothing (no traced run, no such counter, nothing counted)."""
    if not seen.get("traced"):
        return None
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(PHASE_SECONDS)
    if metric is None:
        return None
    out = {}
    for child in metric.children():
        phase = child.labels_dict.get("phase")
        out[phase] = out.get(phase, 0.0) + child.value
    return out if any(out.values()) else None
