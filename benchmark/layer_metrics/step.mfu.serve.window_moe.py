"""The whole step's share of the chips' bf16 peak over the traced window, for
a Command-A+-shaped decoder: the operations each processed token requires
(``work_window_moe.py``: every layer's projections, the router over all its
experts, the shared experts, the head, and attention over the keys each
token sees, the window's on a sliding layer), and the routed pairs this
chip computed (``moe_pairs`` of the program's ``mxtpu.llm.step`` spans,
matched to the traced steps by their ``rows`` as
``kernel.moe_experts_roofline.serve`` matches them: ``work_window_moe.py``
reads its function from its file), over the peak. A program without the
spans' routing attributes gives nothing to read. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    if not traced or not peak or not traced["steps"] \
            or "sliding_window" not in model:
        return None
    work = importlib.import_module("benchmark.work_window_moe")
    spans = [s["attrs"] for s in work.traced_step_spans(seen)
             if "moe_pairs" in s["attrs"]]
    if not spans:
        return None
    bs = seen["config"]["engine"]["block_size"]
    flops = sum(a["moe_pairs"] for a in spans) * work.expert_pair_flops(
        model)
    dense = work.dense_flops_per_token(model)
    for step in traced["steps"]:
        for n_tokens, context in step:
            flops += n_tokens * dense + work.attention_row(
                model, bs, n_tokens, context)[1]
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
