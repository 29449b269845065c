"""Rows whose slot state a dispatched step advanced, over the run: the
program's counter ``mxtpu_llm_state_rows_total`` over its
``mxtpu_llm_state_steps_total`` (all label children). It is how many of
the engine's slots a step's state traffic is paid for, and the rows the
state kernel's roofline counts; read beside
``scheduler.decode_rows_per_step.serve``. A program without the series
gives nothing to read."""


def series(name):
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    return [] if metric is None else metric.children()


def read(seen):
    if not seen.get("traced"):
        return None
    rows = sum(c.value for c in series("mxtpu_llm_state_rows_total"))
    steps = sum(c.value for c in series("mxtpu_llm_state_steps_total"))
    if not rows or not steps:
        return None
    return rows / steps
