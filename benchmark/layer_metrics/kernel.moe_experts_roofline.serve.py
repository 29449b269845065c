"""The grouped expert products' share of their roofline: for each traced
step the least time its routing allows, the larger of reading the experts
that were hit once (``moe_experts_hit`` x one expert's bytes over the HBM
bandwidth) and the pairs' arithmetic (``moe_pairs`` x one pair's
operations over the bf16 peak), both summed over the step's expert layers
as the program's ``mxtpu.llm.step`` spans carry them, over the device time
of the kernel in the trace, by its family name
(``_grouped_swiglu_pallas``). The spans are matched to the traced steps by
their ``rows``; a program without the attributes or the kernel gives
nothing to read. In per cent."""
import importlib

KERNEL = "_grouped_swiglu_pallas"


def traced_step_spans(seen):
    """The ``mxtpu.llm.step`` spans of the traced steps, in order: the
    ring also holds the steps that ran while the profiler started and
    stopped, so the run of spans is found whose ``rows`` are the traced
    steps'."""
    ps = importlib.import_module("benchmark.program_spans")
    spans = [s for s in ps.named(ps.ring(seen), "mxtpu.llm.step")
             if "rows" in s["attrs"]]
    want = [[list(r) for r in step] for step in seen["traced"]["steps"]]
    have = [[list(r) for r in s["attrs"]["rows"]] for s in spans]
    for k in range(len(have) - len(want) + 1):
        if have[k:k + len(want)] == want:
            return spans[k:k + len(want)]
    return []


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0:
        return None
    steps = [s["attrs"] for s in traced_step_spans(seen)
             if "moe_pairs" in s["attrs"]]
    if not steps:
        return None
    work = importlib.import_module("benchmark.work_mla_moe")
    model = seen["config"]["model"]
    read_s = work.expert_bytes(model) / peak["hbm_bytes_per_s"]
    pair_s = work.expert_pair_flops(model) / peak["bf16_flops_per_s"]
    least = sum(max(a["moe_experts_hit"] * read_s, a["moe_pairs"] * pair_s)
                for a in steps)
    return 100.0 * least / seen["chips"] / device_s
