"""The whole step's share of the chips' bf16 peak over the traced window:
the operations each processed token requires at its context (``work.py``,
from the model's shapes) over the peak. Float32 matrix products at the
backend's default precision are one bf16 pass, so the bf16 peak is the
yardstick. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    if not traced or not peak or not traced["steps"]:
        return None
    work = importlib.import_module("benchmark.work")
    model = seen["config"]["model"]
    flops = 0
    for step in traced["steps"]:
        for n_tokens, context in step:
            # the tokens of a chunk attend over contexts that end
            # between context - n_tokens + 1 and context
            flops += n_tokens * work.decoder_flops_per_token(
                model, context - (n_tokens - 1) / 2)
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
