"""Experts hit over experts held, over every dispatched step and expert
layer of the run: the program's counter
``mxtpu_llm_moe_experts_hit_total`` over the steps it dispatched (the
count of its histogram ``mxtpu_llm_moe_max_expert_load``, one
observation a step) times the routed experts the configuration holds over
its expert layers (all label children). It is the share of the expert
bytes that a step has to stream, and describes the routing under this
traffic: more rows a step raise it, so it is read beside
``scheduler.decode_rows_per_step.serve``. A program without the series
gives nothing to read. In per cent."""
import importlib


def series(name):
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    return [] if metric is None else metric.children()


def read(seen):
    if not seen.get("traced"):
        return None
    work = importlib.import_module("benchmark.work_mla_moe")
    model = seen["config"]["model"]
    hit = sum(c.value for c in series("mxtpu_llm_moe_experts_hit_total"))
    steps = sum(c.count for c in series("mxtpu_llm_moe_max_expert_load"))
    held = steps * model.get("n_routed_experts", 0) \
        * work.expert_layers(model)
    if not hit or not held:
        return None
    return 100.0 * hit / held
