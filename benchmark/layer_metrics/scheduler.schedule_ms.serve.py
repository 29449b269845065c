"""Host time a step spends before anything is built or launched: expiring,
admitting, planning and allocating each running sequence. The median of
the program's span ``mxtpu.llm.schedule`` over the traced steps that
dispatched, on the host's monotonic clock, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.median_ms(ps.in_dispatched_steps(ps.ring(seen),
                                                "mxtpu.llm.schedule"))
