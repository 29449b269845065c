"""The program's own spans and counters, as the per-layer readers see
them: spans by name from the tracer's ring, a span's self time, medians,
a histogram's mean.

The readers run in the process that ran the cell. While a profiler
capture runs the program's tracer records every span into its ring
(``mxnet_tpu/observability/tracing.py``), so after a ``--trace 1`` run
the ring holds the spans of the traced window with their host durations,
parents and attributes. A program without that (the tracer off and no
record under a capture) leaves the ring empty, and every function here
then returns nothing.

The ring also holds the steps that ran while the profiler was starting
and stopping with the client loop held, and a traced training call that
finds the runtime's queue full waits there for the device: hence medians.
"""
import statistics


def ring(seen):
    """The ring's spans as dicts, oldest first; none where the run traced
    nothing (what the ring holds then is not this run's window)."""
    if not seen.get("traced"):
        return []
    from mxnet_tpu.observability.tracing import get_tracer
    return get_tracer().snapshot()


def named(spans, name):
    return [s for s in spans if s["name"] == name]


def in_dispatched_steps(spans, name):
    """The spans called ``name`` of the serving steps that dispatched:
    those whose parent also has a ``mxtpu.llm.build`` child (an iteration
    that found no row to run has none)."""
    steps = {s["parent_id"] for s in named(spans, "mxtpu.llm.build")}
    return [s for s in named(spans, name) if s["parent_id"] in steps]


def median_ms(spans):
    """Median duration in milliseconds, or nothing."""
    if not spans:
        return None
    return statistics.median(s["dur_us"] for s in spans) / 1e3


def self_ms(spans, parent, children):
    """Median over the ``parent`` spans of their duration less what
    their direct children named in ``children`` cover, in milliseconds."""
    covered = {}
    for s in spans:
        if s["name"] in children and s["parent_id"] is not None:
            covered[s["parent_id"]] = covered.get(s["parent_id"], 0) \
                + s["dur_us"]
    own = [s["dur_us"] - covered.get(s["span_id"], 0)
           for s in named(spans, parent)]
    return statistics.median(own) / 1e3 if own else None


def histogram_mean_ms(seen, name):
    """Sum over count of a histogram of seconds in the program's registry,
    over all its label children and the whole run, in milliseconds."""
    if not seen.get("traced"):
        return None
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    if metric is None:
        return None
    children = metric.children()
    count = sum(c.count for c in children)
    if not count:
        return None
    return 1e3 * sum(c.sum for c in children) / count
