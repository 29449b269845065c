"""Host time of a training step outside the compiled call: the program's
span ``mxtpu.train_step`` less its children ``mxtpu.train_step.dispatch``
and ``mxtpu.train_step.compile``, which leaves ``prepare`` (the structure
key, gathering weights and states, the rng draw) and ``rebind`` (writing
the results back into the Parameters). The median over the traced steps,
on the host's monotonic clock, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.self_ms(ps.ring(seen), "mxtpu.train_step",
                      ("mxtpu.train_step.dispatch",
                       "mxtpu.train_step.compile"))
