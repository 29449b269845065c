"""Host time after a step's tokens are fetched and before the next step
is scheduled: committing them to the sequences (``_commit``, with the
step's bookkeeping) and resolving the finished requests' Futures
(``_flush_engine``). The median of the program's span
``mxtpu.llm.commit`` plus the median of its sibling ``mxtpu.llm.resolve``
over the traced steps, on the host's monotonic clock, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    spans = ps.ring(seen)
    commit = ps.median_ms(ps.in_dispatched_steps(spans,
                                                 "mxtpu.llm.commit"))
    resolve = ps.median_ms(ps.named(spans, "mxtpu.llm.resolve"))
    if commit is None:
        return None
    return commit + (resolve or 0.0)
