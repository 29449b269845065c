"""Host time a step spends choosing the bucket and filling the batch's
host buffers (``_build_batch``). The median of the program's span
``mxtpu.llm.build`` over the traced steps, on the host's monotonic clock,
in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.median_ms(ps.named(ps.ring(seen), "mxtpu.llm.build"))
