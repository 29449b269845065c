"""The grouped expert products' share of their roofline under a
Command-A+-shaped decoder that holds a share of its experts: for each
traced step the least time its routing allows, the larger of reading the
held experts that were hit once (``moe_experts_hit`` x one expert's bytes
over the HBM bandwidth) and the held pairs' arithmetic (``moe_pairs`` x one
pair's operations over the bf16 peak), both summed over the step's expert
layers as the program's ``mxtpu.llm.step`` spans carry them
(``work_window_moe.py`` for an expert's bytes and a pair's operations),
over the device time of the kernel in the trace, by its family name
(``_grouped_swiglu_pallas``). The spans are matched to the traced steps by
their ``rows``, as ``kernel.moe_experts_roofline.serve`` matches them
(``work_window_moe.py`` reads its function from its file); a program
without the attributes or the kernel gives nothing to read. In per cent."""
import importlib

KERNEL = "_grouped_swiglu_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "sliding_window" not in model:
        return None
    work = importlib.import_module("benchmark.work_window_moe")
    steps = [s["attrs"] for s in work.traced_step_spans(seen)
             if "moe_pairs" in s["attrs"]]
    if not steps:
        return None
    read_s = work.expert_bytes(model) / peak["hbm_bytes_per_s"]
    pair_s = work.expert_pair_flops(model) / peak["bf16_flops_per_s"]
    least = sum(max(a["moe_experts_hit"] * read_s, a["moe_pairs"] * pair_s)
                for a in steps)
    return 100.0 * least / seen["chips"] / device_s
