"""Everything the serving thread does a step that is not waiting, for the
device or for a client: the median, over consecutive dispatched steps of
the recording, of the time from one ``mxtpu.llm.step``'s start to the
next one's, less the first step's ``mxtpu.llm.fetch`` and less
``blocked_us`` of the ``mxtpu.llm.wait`` between them. It is scheduling,
building, the launch, the commit, resolving Futures and the loop's head
together, with whatever lies between them: the host time a step that the
device cannot start the next step without, and so the number that
launching a step before reading the last one has to hide. On the host's
monotonic clock, in milliseconds."""
import importlib
import statistics


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    phases = importlib.import_module("benchmark.phase_spans")
    host = phases.host_us(ps.ring(seen))
    return statistics.median(host) / 1e3 if host else None
