"""Experts hit over experts held, over every dispatched step and layer of
the run, for a block-diffusion expert decoder: the program's counter
``mxtpu_llm_moe_experts_hit_total`` over the steps it dispatched (the
count of its histogram ``mxtpu_llm_moe_max_expert_load``, one observation
a step) times the experts the configuration holds over its layers
(``work_blockdiff_moe.py``; all label children). It is the share of the
expert bytes that a step has to stream: with 32 rows of a block each a
step feeds 128 tokens or more and hits nearly every expert. A program
without the series gives nothing to read. In per cent."""
import importlib


def series(name):
    from mxnet_tpu.observability import get_registry
    metric = get_registry().get(name)
    return [] if metric is None else metric.children()


def read(seen):
    model = seen["config"]["model"]
    if not seen.get("traced") or "block_length" not in model:
        return None
    work = importlib.import_module("benchmark.work_blockdiff_moe")
    hit = sum(c.value for c in series("mxtpu_llm_moe_experts_hit_total"))
    steps = sum(c.count for c in series("mxtpu_llm_moe_max_expert_load"))
    held = steps * work.experts_held(model)
    if not hit or not held:
        return None
    return 100.0 * hit / held
