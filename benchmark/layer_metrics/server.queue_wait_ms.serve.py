"""Mean time a request waits from its submit to its first placement into
a decode slot: the program's histogram ``mxtpu_llm_queue_wait_seconds``,
sum over count, all label children, the whole run, in milliseconds."""
import importlib


def read(seen):
    ps = importlib.import_module("benchmark.program_spans")
    return ps.histogram_mean_ms(seen, "mxtpu_llm_queue_wait_seconds")
