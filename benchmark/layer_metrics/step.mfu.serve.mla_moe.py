"""The whole step's share of the chips' bf16 peak over the traced window,
for a DeepSeek-V3-shaped decoder: the operations each processed token
requires at its context (``work_mla_moe.py``: expanded-form latent
attention, the projections, the routed and shared experts or the dense
MLP, the head) over the peak. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    if not traced or not peak or not traced["steps"] \
            or "kv_lora_rank" not in model:
        return None
    work = importlib.import_module("benchmark.work_mla_moe")
    flops = 0
    for step in traced["steps"]:
        for n_tokens, context in step:
            # the tokens of a chunk attend over contexts that end
            # between context - n_tokens + 1 and context
            flops += n_tokens * work.decoder_flops_per_token(
                model, context - (n_tokens - 1) / 2)
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
