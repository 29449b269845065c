"""The whole step's share of the chips' bf16 peak over the traced window,
for a block-diffusion expert decoder: the operations each token FED
requires at the keys it sees (``work_blockdiff_moe.py``: the projections,
scores and values, the router, the experts a token takes, the head) over
the peak. A denoising pass's tokens count each time they are fed, so a
change that answers the same with fewer passes lowers this while the rate
rises. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    if not traced or not peak or not traced["steps"] \
            or "block_length" not in model:
        return None
    work = importlib.import_module("benchmark.work_blockdiff_moe")
    flops = sum(n_tokens * work.decoder_flops_per_token(
        model, work.mean_context(model, n_tokens, context))
        for step in traced["steps"] for n_tokens, context in step)
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
