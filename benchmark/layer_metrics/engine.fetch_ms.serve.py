"""Host time inside a step's one fetch (``_device_get``): the wait for
the device to finish the step and the copy of its tokens back; the one
phase of the serving thread that is the chip's time and not the host's.
The median of the program's span ``mxtpu.llm.fetch`` over the traced
steps that dispatched, on the host's monotonic clock, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.median_ms(ps.in_dispatched_steps(ps.ring(seen),
                                                "mxtpu.llm.fetch"))
