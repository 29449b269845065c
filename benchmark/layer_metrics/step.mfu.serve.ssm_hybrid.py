"""The whole step's share of the chips' bf16 peak over the traced window,
for a Granite-4.0-H-shaped decoder: the operations each processed token
requires at its context (``work_ssm_hybrid.py``: the state-space layers'
projections, convolution and recurrence, grouped-query attention over the
context, the MLPs, the head) over the peak. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    if not traced or not peak or not traced["steps"] \
            or "layer_types" not in model:
        return None
    work = importlib.import_module("benchmark.work_ssm_hybrid")
    flops = 0
    for step in traced["steps"]:
        for n_tokens, context in step:
            # the tokens of a chunk attend over contexts that end
            # between context - n_tokens + 1 and context
            flops += n_tokens * work.decoder_flops_per_token(
                model, context - (n_tokens - 1) / 2)
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
