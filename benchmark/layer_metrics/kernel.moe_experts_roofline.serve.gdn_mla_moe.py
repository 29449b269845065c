"""The grouped expert products' share of their roofline under a decoder
that holds a share of its experts (GigaChat-3.5-shaped): for each traced
step the least time its routing allows, the larger of reading the held
experts that were hit once (``moe_experts_hit`` x one expert's bytes over
the HBM bandwidth) and the held pairs' arithmetic (``moe_pairs`` x one
pair's operations over the bf16 peak), both summed over the step's expert
layers as the program's ``mxtpu.llm.step`` spans carry them
(``work_gdn_mla_moe.py`` for an expert's bytes and a pair's operations),
over the device time of the kernel in the trace, by its family name
(``_grouped_swiglu_pallas``). The spans are matched to the traced steps
by their ``rows``, as ``kernel.moe_experts_roofline.serve`` matches them
(its own function, read from its file); a program without the attributes
or the kernel gives nothing to read. In per cent."""
import importlib
import importlib.util
import os

KERNEL = "_grouped_swiglu_pallas"


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
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "linear_num_value_heads" not in model:
        return None
    steps = [s["attrs"] for s in _accepted_reader().traced_step_spans(seen)
             if "moe_pairs" in s["attrs"]]
    if not steps:
        return None
    work = importlib.import_module("benchmark.work_gdn_mla_moe")
    read_s = work.expert_bytes(model) / peak["hbm_bytes_per_s"]
    pair_s = work.expert_pair_flops(model) / peak["bf16_flops_per_s"]
    least = sum(max(a["moe_experts_hit"] * read_s, a["moe_pairs"] * pair_s)
                for a in steps)
    return 100.0 * least / seen["chips"] / device_s
