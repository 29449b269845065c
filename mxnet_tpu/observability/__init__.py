"""mxnet_tpu.observability — unified runtime observability.

One process-wide :class:`MetricsRegistry` (counters, gauges, fixed-edge
histograms — mergeable across hosts) that every subsystem reports
through under the ``mxtpu_<subsystem>_<metric>`` naming scheme:

================  ====================================================
subsystem         instrumented where
================  ====================================================
``training``      :class:`StepTimer` (step wall time, data-wait vs
                  compute split, examples/sec) wired into
                  ``gluon.Trainer`` / the estimator's
                  ``StepTimerHandler``; optimizer-step timing and the
                  optional grad-norm gauge in ``Trainer.step``
``xla``           compile count/duration + cache hits via the
                  :mod:`jax.monitoring` bridge (:mod:`.jaxmon`)
``resilience``    checkpoint write/restore duration, bytes, retry
                  counts (``mxnet_tpu.resilience``)
``kvstore``       allreduce count/bytes/duration
                  (``mxnet_tpu.kvstore``)
``serving``       request/batch counters, wait/service/latency
                  histograms, queue depth
                  (``mxnet_tpu.serving.telemetry``)
``llm``           decode serving: tokens/sec, time-to-first-token,
                  KV-block occupancy, preemptions/evictions
                  (``mxnet_tpu.serving.llm.metrics``)
================  ====================================================

Exporters (both zero-dependency):

- ``get_registry().expose()`` — Prometheus text exposition;
- ``get_registry().write_snapshot()`` — JSONL snapshot, gated by
  ``MXNET_TPU_METRICS_LOG`` (+ periodic via
  ``MXNET_TPU_METRICS_INTERVAL``); rendered by
  ``tools/metrics_dump.py``.

Derived layers (all reading the same snapshots, never their own
sampling paths): :mod:`.timeseries` (:class:`TimeSeriesRing`) adds the
time axis — a bounded ring of periodic snapshots with in-process
``rate()``/windowed-percentile queries; :mod:`.slo` evaluates
declarative latency/availability/TTFT objectives with multi-window
burn-rate status off that ring (``mxtpu_slo_*``); :mod:`.capacity`
turns a replay window into the committed chips-per-M-users report
(``tools/load_replay.py`` drives all three).

Incident capture: :mod:`.flightrecorder` (:func:`get_flightrecorder`)
keeps a bounded black-box ring of per-request lifecycle events and
control-plane decisions, dumps atomic post-mortem bundles on SLO
page/breach transitions, worker crashes, or manual request
(``mxtpu_flight_*``; rendered by ``tools/flight_inspect.py``), and
serves ``debug_status()`` snapshots of registered servers;
:mod:`.exemplars` attaches opt-in (req id, span id) exemplars to
histogram buckets so a breach names its offending requests.

Causality lives next door: :mod:`.tracing` (:func:`get_tracer`) records
nested host spans across the same subsystems — one step / one serving
request readable end to end, exported as Chrome-trace/Perfetto JSON and
bridged onto the XLA device timeline while a profiler capture runs.

See docs/OBSERVABILITY.md for the metric catalog and the tracing guide.
"""
from .registry import (Counter, Gauge, Histogram, MetricsRegistry,
                       DEFAULT_TIME_BUCKETS, get_registry)
from .steptimer import StepTimer
from .jaxmon import compile_count, install_jax_monitoring_bridge
from .tracing import Span, Tracer, get_tracer, validate_chrome_trace
from .timeseries import TimeSeriesRing
from .slo import (SLO, SLOEngine, STATUS_OK, STATUS_WARN, STATUS_PAGE,
                  STATUS_BREACH)
from .flightrecorder import (FlightRecorder, get_flightrecorder,
                             flight_ring_capacity, flight_triggers)
from .exemplars import EXEMPLARS_PER_BUCKET

__all__ = ["Counter", "Gauge", "Histogram", "MetricsRegistry",
           "DEFAULT_TIME_BUCKETS", "get_registry", "StepTimer",
           "compile_count", "install_jax_monitoring_bridge",
           "Span", "Tracer", "get_tracer", "validate_chrome_trace",
           "TimeSeriesRing", "SLO", "SLOEngine", "STATUS_OK",
           "STATUS_WARN", "STATUS_PAGE", "STATUS_BREACH",
           "FlightRecorder", "get_flightrecorder",
           "flight_ring_capacity", "flight_triggers",
           "EXEMPLARS_PER_BUCKET"]
