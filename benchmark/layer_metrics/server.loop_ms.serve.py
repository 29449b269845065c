"""Host time of the server's loop between two steps, outside the engine:
taking what is pending under the lock, routing it to an engine, the scans
for work. The median of the program's span ``mxtpu.llm.wait`` less its
``blocked_us`` (the time inside the condition variable, when the server
had nothing to do) over the waits that a dispatched step follows, on the
host's monotonic clock, in milliseconds."""
import importlib
import statistics


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    phases = importlib.import_module("benchmark.phase_spans")
    loop = phases.loop_us(ps.ring(seen))
    return statistics.median(loop) / 1e3 if loop else None
