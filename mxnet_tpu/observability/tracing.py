"""Process-wide span tracer: causal timelines over the metrics registry.

PR 3's :class:`MetricsRegistry` answers "how much / how often"; this
module answers "and in what order, caused by what": nested host spans
with contextvar propagation that survives thread hops (the
``DevicePrefetchIter`` staging worker, the serving ``MicroBatchQueue``
batch former, checkpoint writers), buffered in a bounded ring and
exportable as Chrome trace-event JSON that Perfetto / ``chrome://
tracing`` open directly. The design follows the per-op timeline
attribution that the MLPerf TPU-pod scaling work leans on: an aggregate
(30% MFU) is not actionable until one step / one request can be read
end to end.

Three integration rules keep the tracer honest:

- **off = free.** With tracing disabled and no profiler capture
  running, every ``tracer.span(...)`` call on a hot path returns the
  same ``_NULL`` singleton — no object, dict or closure is allocated
  per step (asserted in tier-1 via the ``mxtpu_trace_*`` counters).
  Call sites therefore never need their own ``if enabled`` guards;
  one that builds an attribute value asks ``span.span_id is not None``
  first.
- **bounded memory.** Completed spans land in a ring of
  ``MXNET_TPU_TRACE_RING`` entries (drops counted on
  ``mxtpu_trace_spans_dropped_total``), so a week-long serving process
  with tracing on cannot leak.
- **one timeline with XLA.** While a jax profiler capture is running
  — whoever started it: ``mx.profiler``, ``jax.profiler.start_trace``,
  TensorBoard — spans record even with the tracer off, and every
  same-thread span also enters a ``jax.profiler.TraceAnnotation``
  (outermost step-category spans a ``StepTraceAnnotation``; XLA step
  markers do not nest, so an enclosing epoch or wrapped fallback span
  never claims one). The ring then holds the spans of the captured
  window with their host durations and parents, and the same spans
  stand in the xplane beside the device's events on the capture's
  clock. The chip benchmark's per-layer readers take the ring
  (``benchmark/program_spans.py``); its trace reduction
  (``benchmark/trace.py``) keeps the device's events and, of the
  host's annotations, only its own ``bench.*``, so the ``mxtpu.*``
  annotations in the xplane are read by hand so far (ROADMAP B4).

Cross-thread propagation is explicit: contextvars do not follow work
onto other threads, so producers capture ``tracer.current()`` at
hand-off and workers either pass it as ``parent=`` or wrap their work
in ``tracer.attach(parent)``.

Env vars: ``MXNET_TPU_TRACE`` (truthy enables at first use; a value
containing a path separator or ending in ``.json`` is also the at-exit
export path), ``MXNET_TPU_TRACE_RING`` (ring capacity, default 32768),
``MXNET_TPU_TRACE_DIR`` (directory for at-exit export,
``trace_<pid>.json``). See docs/OBSERVABILITY.md.
"""
from __future__ import annotations

import collections
import contextvars
import itertools
import json
import os
import sys
import threading
import time

__all__ = ["Span", "Tracer", "get_tracer", "trace_ring_capacity",
           "validate_chrome_trace"]

DEFAULT_RING = 32768

# The active span of the current execution context. Threads started
# before a span opened (or plain worker threads) see None and must be
# handed a parent explicitly (tracer.current() at submit time).
_CURRENT = contextvars.ContextVar("mxtpu_trace_span", default=None)

# How many jax StepTraceAnnotations are open in this context: XLA step
# markers are not nestable, so only the innermost step-category span
# (depth 0 at open) becomes a StepTraceAnnotation — an enclosing epoch
# span or a wrapped fallback step must not garble device attribution.
_STEP_DEPTH = contextvars.ContextVar("mxtpu_trace_step_depth", default=0)

_ids = itertools.count(1)


def trace_ring_capacity():
    """Ring capacity: ``MXNET_TPU_TRACE_RING`` or the default."""
    try:
        n = int(os.environ.get("MXNET_TPU_TRACE_RING",
                               DEFAULT_RING) or DEFAULT_RING)
    except ValueError:
        return DEFAULT_RING
    return max(16, n)


# jax.profiler.TraceAnnotation.is_enabled, held after the first lookup
_capture_signal = None


def _capture_running():
    """True while a jax profiler capture is active, whoever started it
    (``TraceAnnotation.is_enabled()`` reads the runtime's own switch).
    Importing the tracer never drags jax in: where ``jax`` is not in
    ``sys.modules`` no capture can run."""
    global _capture_signal
    signal = _capture_signal
    if signal is None:
        jax = sys.modules.get("jax")
        if jax is None:
            return False
        try:
            signal = jax.profiler.TraceAnnotation.is_enabled
        except AttributeError:      # jax still importing, or too old
            return False
        _capture_signal = signal
    return signal()


def _jax_annotation(name, cat, step):
    """``(annotation, is_step)``: the jax context bridging one span onto
    the device timeline. Only an OUTERMOST step-category span becomes a
    ``StepTraceAnnotation`` (jax/XProf step markers do not nest); any
    span already under one gets a plain ``TraceAnnotation``."""
    import jax
    if cat == "step" and step is not None and _STEP_DEPTH.get() == 0:
        return (jax.profiler.StepTraceAnnotation(name, step_num=int(step)),
                True)
    return jax.profiler.TraceAnnotation(name), False


class _NullSpan:
    """The shared no-op span. Context-manageable, settable, finishable —
    every method free of allocation, so disabled tracing costs a method
    call and nothing else on the hot path."""

    __slots__ = ()
    span_id = None
    name = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, key, value):
        return self

    def finish(self):
        return None


_NULL = _NullSpan()


class Span:
    """One host span: created open, recorded into the tracer's ring on
    :meth:`finish` (or context-manager exit).

    ``activate=True`` (the default for ``tracer.span``) installs the
    span as the current contextvar value for its dynamic extent, so
    spans opened underneath nest automatically. Hand-off spans
    (``tracer.begin``) stay un-activated: they are created on one
    thread and finished on another (a serving request), where a
    contextvar token could not be reset correctly.
    """

    __slots__ = ("_tracer", "name", "cat", "span_id", "parent_id",
                 "parent_tid", "tid", "thread_name", "t0_ns", "attrs",
                 "_token", "_ann", "_step_token", "_done")

    def __init__(self, tracer, name, cat, parent, attrs, step, activate):
        self._tracer = tracer
        self.name = name
        self.cat = cat
        self.span_id = next(_ids)
        if parent is None and activate:
            parent = _CURRENT.get()
        if parent is not None and parent.span_id is not None:
            self.parent_id = parent.span_id
            self.parent_tid = parent.tid
        else:
            self.parent_id = None
            self.parent_tid = None
        t = threading.current_thread()
        self.tid = t.ident or 0
        self.thread_name = t.name
        self.attrs = dict(attrs) if attrs else None
        if step is not None:
            self.set("step", int(step))
        self._token = _CURRENT.set(self) if activate else None
        self._ann = None
        self._step_token = None
        # hand-off spans (activate=False) open on one thread and finish
        # on another; jax TraceMe begin/end pairs are thread-scoped, so
        # only activated (same-thread) spans bridge to the device
        # timeline
        if activate and _capture_running():
            try:
                ann, is_step = _jax_annotation(name, cat, step)
                ann.__enter__()
                self._ann = ann
                if is_step:
                    self._step_token = _STEP_DEPTH.set(
                        _STEP_DEPTH.get() + 1)
            except Exception:
                self._ann = None
        self._done = False
        tracer._on_start()
        self.t0_ns = time.monotonic_ns()

    # ------------------------------------------------------------- api --
    def set(self, key, value):
        """Attach one attribute (rendered into the trace event args)."""
        if self.attrs is None:
            self.attrs = {}
        self.attrs[key] = value
        return self

    def finish(self):
        if self._done:
            return
        dur_ns = time.monotonic_ns() - self.t0_ns
        self._done = True
        if self._step_token is not None:
            try:
                _STEP_DEPTH.reset(self._step_token)
            except ValueError:
                _STEP_DEPTH.set(0)
            self._step_token = None
        if self._ann is not None:
            try:
                self._ann.__exit__(None, None, None)
            except Exception:
                pass
            self._ann = None
        if self._token is not None:
            try:
                _CURRENT.reset(self._token)
            except ValueError:
                # finished from a different context than it was opened
                # in (generator teardown); clearing beats leaking
                _CURRENT.set(None)
            self._token = None
        self._tracer._record(self, dur_ns)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.finish()
        return False


class Tracer:
    """Bounded process tracer. Use the module singleton
    (:func:`get_tracer`); fresh instances exist for tests."""

    def __init__(self, ring=None, registry=None):
        self._lock = threading.Lock()
        self._ring = collections.deque(
            maxlen=ring if ring else trace_ring_capacity())
        self._registry = registry
        self._enabled = False
        self._open = 0
        self._epoch_ns = time.monotonic_ns()
        self._obs = None

    # -------------------------------------------------------- lifecycle --
    @property
    def enabled(self):
        return self._enabled

    def enable(self, ring=None):
        """Turn span recording on (idempotent); ``ring`` resizes the
        buffer, dropping whatever an old smaller ring held."""
        with self._lock:
            if ring and ring != self._ring.maxlen:
                self._ring = collections.deque(self._ring, maxlen=ring)
            self._enabled = True
            self._metrics()
        return self

    def disable(self):
        self._enabled = False
        return self

    def clear(self):
        with self._lock:
            self._ring.clear()

    def _metrics(self):
        if self._obs is None:
            if self._registry is None:
                from .registry import get_registry
                self._registry = get_registry()
            reg = self._registry
            self._obs = {
                "started": reg.counter(
                    "mxtpu_trace_spans_started_total",
                    "Tracer spans opened (0 while tracing is off — the "
                    "zero-overhead contract)."),
                "dropped": reg.counter(
                    "mxtpu_trace_spans_dropped_total",
                    "Completed spans evicted from the bounded ring "
                    "before an export read them."),
                "exports": reg.counter(
                    "mxtpu_trace_exports_total",
                    "Chrome-trace exports written."),
                "export_bytes": reg.counter(
                    "mxtpu_trace_export_bytes_total",
                    "Bytes of Chrome-trace JSON written by exports."),
            }
        return self._obs

    # ------------------------------------------------------------ spans --
    def span(self, name, cat="host", parent=None, attrs=None, step=None):
        """Open a nested, context-activated span. Returns the ``_NULL``
        singleton when tracing is off and no profiler capture is
        running, so hot paths call this unconditionally."""
        if not self._enabled and not _capture_running():
            return _NULL
        return Span(self, name, cat, parent, attrs, step, True)

    def begin(self, name, cat="host", parent=None, attrs=None):
        """Open a hand-off span: NOT installed as the current context
        (it will be finished on another thread — serving requests,
        background writers). Pair with ``span.finish()``. Recorded
        under a capture like any span, but never annotated."""
        if not self._enabled and not _capture_running():
            return _NULL
        return Span(self, name, cat, parent, attrs, None, False)

    def current(self):
        """The active span of this execution context (None outside any
        span, or on a thread no span was propagated to)."""
        return _CURRENT.get()

    def attach(self, parent):
        """Context manager adopting ``parent`` as this thread's current
        span — the explicit cross-thread propagation primitive::

            parent = tracer.current()        # producer side
            ...
            with tracer.attach(parent):      # worker thread
                with tracer.span("work"):    # nests under parent
        """
        return _Attach(parent)

    # --------------------------------------------------------- recording --
    def _on_start(self):
        with self._lock:
            self._open += 1
        self._metrics()["started"].inc()

    def _record(self, span, dur_ns):
        rec = (span.name, span.cat,
               (span.t0_ns - self._epoch_ns) // 1000, dur_ns // 1000,
               span.tid, span.thread_name, span.span_id, span.parent_id,
               span.parent_tid, span.attrs)
        with self._lock:
            self._open -= 1
            if len(self._ring) == self._ring.maxlen:
                self._metrics()["dropped"].inc()
            self._ring.append(rec)

    # ------------------------------------------------------- introspection --
    def stats(self):
        with self._lock:
            obs = self._metrics()
            return {"enabled": self._enabled,
                    "buffered": len(self._ring),
                    "capacity": self._ring.maxlen,
                    "open": self._open,
                    "started": int(obs["started"].value),
                    "dropped": int(obs["dropped"].value)}

    def snapshot(self):
        """Completed spans currently buffered, oldest first, as dicts
        (test/debug surface; export() is the production path)."""
        with self._lock:
            ring = list(self._ring)
        return [{"name": n, "cat": c, "ts_us": ts, "dur_us": dur,
                 "tid": tid, "thread": tname, "span_id": sid,
                 "parent_id": pid, "parent_tid": ptid,
                 "attrs": attrs or {}}
                for (n, c, ts, dur, tid, tname, sid, pid, ptid, attrs)
                in ring]

    # ---------------------------------------------------------- exporting --
    def export(self, path=None):
        """Write the buffered spans as Chrome trace-event JSON (one
        ``traceEvents`` array Perfetto / chrome://tracing load as-is):
        per-thread lanes with thread-name metadata, one complete ("X")
        event per span carrying span/parent ids in ``args``, and flow
        arrows ("s"/"f") wherever a child ran on a different thread
        than its parent — the rendering of a propagated context.

        ``path`` defaults to the at-exit destination
        (:func:`default_export_path`). Returns the path written."""
        if path is None:
            path = default_export_path()
        if path is None:
            raise ValueError(
                "no export path: pass one, or set MXNET_TPU_TRACE_DIR "
                "(or MXNET_TPU_TRACE=<file.json>)")
        data = self.to_chrome_trace()
        payload = json.dumps(data)
        d = os.path.dirname(os.path.abspath(path))
        os.makedirs(d, exist_ok=True)
        with open(path, "w") as f:
            f.write(payload)
        obs = self._metrics()
        obs["exports"].inc()
        obs["export_bytes"].inc(len(payload))
        return path

    def to_chrome_trace(self):
        """The export as a dict (``{"traceEvents": [...]}``)."""
        spans = self.snapshot()
        pid = os.getpid()
        events = [{"ph": "M", "name": "process_name", "pid": pid,
                   "tid": 0, "args": {"name": f"mxnet_tpu host {pid}"}}]
        threads = {}
        for s in spans:
            threads.setdefault(s["tid"], s["thread"])
        for tid, tname in sorted(threads.items()):
            events.append({"ph": "M", "name": "thread_name", "pid": pid,
                           "tid": tid, "args": {"name": tname}})
        by_id = {s["span_id"]: s for s in spans}
        for s in spans:
            args = {"span_id": s["span_id"]}
            if s["parent_id"] is not None:
                args["parent_id"] = s["parent_id"]
            args.update(s["attrs"])
            events.append({"ph": "X", "name": s["name"], "cat": s["cat"],
                           "pid": pid, "tid": s["tid"], "ts": s["ts_us"],
                           "dur": max(s["dur_us"], 1), "args": args})
            # a cross-thread parent cannot nest by timestamp containment;
            # a flow arrow draws the causal hand-off instead
            parent = by_id.get(s["parent_id"])
            if parent is not None and parent["tid"] != s["tid"]:
                fid = s["span_id"]
                events.append({"ph": "s", "id": fid, "pid": pid,
                               "name": "ctx", "cat": "ctx",
                               "tid": parent["tid"],
                               "ts": parent["ts_us"]})
                events.append({"ph": "f", "bp": "e", "id": fid,
                               "pid": pid, "name": "ctx", "cat": "ctx",
                               "tid": s["tid"], "ts": s["ts_us"]})
        return {"traceEvents": events, "displayTimeUnit": "ms"}


class _Attach:
    __slots__ = ("_parent", "_token")

    def __init__(self, parent):
        self._parent = parent
        self._token = None

    def __enter__(self):
        self._token = _CURRENT.set(self._parent)
        return self._parent

    def __exit__(self, *exc):
        if self._token is not None:
            _CURRENT.reset(self._token)
            self._token = None
        return False


# ------------------------------------------------------------ validation --

def validate_chrome_trace(data):
    """Assert ``data`` (dict, JSON text, or a path to a JSON file) is a
    well-formed Chrome trace-event document Perfetto will load: a
    ``traceEvents`` list whose members carry the per-phase required
    fields. Raises ``ValueError`` with the first offence; returns the
    number of "X" (complete) events. This is the checker
    ``tools/metrics_dump.py --smoke`` and the tier-1 tracing tests run
    against every export."""
    if isinstance(data, (str, bytes, os.PathLike)) and \
            os.path.exists(os.fspath(data)):
        with open(data) as f:
            data = f.read()
    if isinstance(data, (str, bytes)):
        data = json.loads(data)
    if not isinstance(data, dict) or \
            not isinstance(data.get("traceEvents"), list):
        raise ValueError("trace document must be an object with a "
                         "'traceEvents' list")
    n_complete = 0
    for i, e in enumerate(data["traceEvents"]):
        if not isinstance(e, dict):
            raise ValueError(f"traceEvents[{i}]: not an object")
        ph = e.get("ph")
        if not isinstance(ph, str) or not ph:
            raise ValueError(f"traceEvents[{i}]: missing 'ph'")
        if not isinstance(e.get("name"), str):
            raise ValueError(f"traceEvents[{i}]: missing 'name'")
        for key in ("pid", "tid"):
            if not isinstance(e.get(key), int):
                raise ValueError(f"traceEvents[{i}]: missing '{key}'")
        if ph == "X":
            n_complete += 1
            for key in ("ts", "dur"):
                v = e.get(key)
                if not isinstance(v, (int, float)) or v < 0:
                    raise ValueError(
                        f"traceEvents[{i}]: bad '{key}': {v!r}")
        elif ph in ("s", "t", "f"):
            if "id" not in e or not isinstance(e.get("ts"), (int, float)):
                raise ValueError(f"traceEvents[{i}]: flow event needs "
                                 "'id' and 'ts'")
        elif ph == "M":
            if not isinstance(e.get("args"), dict):
                raise ValueError(f"traceEvents[{i}]: metadata event "
                                 "needs 'args'")
    return n_complete


# ------------------------------------------------------------- singleton --

def _env_truthy(v):
    return bool(v) and v.strip().lower() not in ("0", "off", "false",
                                                 "no", "")


def _env_export_file(v):
    """A MXNET_TPU_TRACE value that names a file doubles as the at-exit
    export path (`MXNET_TPU_TRACE=run/trace.json`)."""
    if v and (os.sep in v or v.endswith(".json")):
        return v
    return None


def default_export_path():
    """Where an argument-less export lands: the file named by
    ``MXNET_TPU_TRACE`` (if it names one), else
    ``MXNET_TPU_TRACE_DIR/trace_<pid>.json``, else None."""
    f = _env_export_file(os.environ.get("MXNET_TPU_TRACE", ""))
    if f:
        return f
    d = os.environ.get("MXNET_TPU_TRACE_DIR")
    if d:
        return os.path.join(d, f"trace_{os.getpid()}.json")
    return None


_global = None
_global_lock = threading.Lock()


def get_tracer():
    """The process tracer. First call reads ``MXNET_TPU_TRACE`` — a
    truthy value enables recording immediately and, when an export path
    is derivable (:func:`default_export_path`), registers an at-exit
    export so instrumented processes need zero tracing code. Cheap to
    call per request/step: after the first call it is one global read,
    no lock."""
    global _global
    if _global is not None:
        return _global
    with _global_lock:
        if _global is None:
            _global = Tracer()
            env = os.environ.get("MXNET_TPU_TRACE", "")
            if _env_truthy(env):
                _global.enable()
                if default_export_path():
                    import atexit
                    atexit.register(_safe_export, _global)
        return _global


def _safe_export(tracer):
    try:
        tracer.export()
    except Exception:
        pass
