"""The rehearsals of ``test_benchmark.py`` switch the profiler off and
read a recorded trace in its place (the CPU has no device lane), so no
capture runs there and the program's tracer, which records under a
capture, would record nothing for the per-layer readers that read its
ring. For that module the tracer's own switch stands in for the capture.
"""
import pytest


@pytest.fixture(scope="module", autouse=True)
def tracer_stands_in_for_the_capture(request):
    if request.module.__name__.rpartition(".")[2] != "test_benchmark":
        yield
        return
    from mxnet_tpu.observability.tracing import get_tracer
    tracer = get_tracer()
    was_on = tracer.enabled
    tracer.clear()
    tracer.enable()
    yield
    if not was_on:
        tracer.disable()
    tracer.clear()
