"""Host time of one call of the compiled step: the driver's span around
each ``step(x, y)`` of a traced run's window after the profiler has
stopped, in milliseconds. The median, not the mean: the runtime lets some
thirty steps wait in its queue, and a call that finds the queue full waits
there for the device, which is the device's time and not the host's."""
import statistics


def read(seen):
    spans = seen["spans"].get("dispatch")
    if not spans:
        return None
    return 1e3 * statistics.median(spans)
