"""Of the serving thread's time outside its loop's wait, the share that is
not the wait for the device: 100 x (the phase seconds but ``fetch`` and
``wait``) over (the phase seconds but ``wait``), from the program's
counter ``mxtpu_llm_step_phase_seconds_total`` over the whole run, all
label children. It is what an operator without a profiler has in the
place of the device's idle share: while the thread is in any phase but
``fetch`` the device has at most the step just launched to work on.
Leaving ``wait`` out keeps it whole over the time for which a traced run
holds the client loop. In per cent."""
import importlib


def read(seen):
    phases = importlib.import_module("benchmark.phase_spans")
    seconds = phases.phase_seconds(seen)
    if not seconds:
        return None
    busy = sum(seconds.values()) - seconds.get("wait", 0.0)
    if busy <= 0:
        return None
    return 100.0 * (busy - seconds.get("fetch", 0.0)) / busy
