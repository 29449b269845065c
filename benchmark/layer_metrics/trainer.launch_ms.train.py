"""Host time of the compiled program's call in a training step (the PJRT
call that donates the weights and the optimizer's state). The median of
the program's span ``mxtpu.train_step.dispatch`` over the traced steps, on
the host's monotonic clock, in milliseconds; a call that finds the
runtime's queue full waits there for the device, hence the median."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.median_ms(ps.named(ps.ring(seen),
                                 "mxtpu.train_step.dispatch"))
