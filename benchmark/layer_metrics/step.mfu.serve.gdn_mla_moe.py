"""The whole step's share of the chips' bf16 peak over the traced window,
for a GigaChat-3.5-shaped decoder: the operations each processed token
requires at its context (``work_gdn_mla_moe.py``: GatedDeltaNet
projections, convolution and delta rule; the query latent, the output
gate and the expanded-form scores and values of latent attention; the
dense MLP; the router over all its experts and the shared expert; the
head), and the routed pairs this chip computed (``moe_pairs`` of the
program's ``mxtpu.llm.step`` spans, matched to the traced steps by their
``rows`` as ``kernel.moe_experts_roofline.serve`` matches them, its own
function read from its file), over the peak. A program without the spans'
routing attributes gives nothing to read. In per cent."""
import importlib
import importlib.util
import os


def _accepted_reader():
    """The module of ``kernel.moe_experts_roofline.serve.py``, for its
    ``traced_step_spans``."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "kernel.moe_experts_roofline.serve.py")
    spec = importlib.util.spec_from_file_location(
        "layer_metric_kernel_moe_experts_roofline_serve", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    if not traced or not peak or not traced["steps"] \
            or "linear_num_value_heads" not in model:
        return None
    spans = [s["attrs"] for s in _accepted_reader().traced_step_spans(seen)
             if "moe_pairs" in s["attrs"]]
    if not spans:
        return None
    work = importlib.import_module("benchmark.work_gdn_mla_moe")
    flops = sum(a["moe_pairs"] for a in spans) * work.expert_pair_flops(
        model)
    for step in traced["steps"]:
        for n_tokens, context in step:
            # the tokens of a chunk attend over contexts that end
            # between context - n_tokens + 1 and context
            flops += n_tokens * work.decoder_flops_per_token(
                model, context - (n_tokens - 1) / 2)
    return 100.0 * flops / seen["trace"]["window_s"] \
        / (peak["bf16_flops_per_s"] * seen["chips"])
