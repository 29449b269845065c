"""Host time of the jitted call of a step (``_call_step``): handing over
the parameters, uploading the batch's host arrays, the launch. The call is
asynchronous, so the device starts before it returns. The median of the
program's span ``mxtpu.llm.launch`` over the traced steps, on the host's
monotonic clock, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.median_ms(ps.named(ps.ring(seen), "mxtpu.llm.launch"))
