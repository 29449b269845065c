"""The per-group windowed attention kernel's share of its roofline, both
kinds of layer: for each traced step the least time its rows allow, the
larger of reading the pages their keys lie in once a layer (a sliding
layer's from the page of a row's first token's first visible key, a full
layer's all of them: ``work_window_moe.attention_row``) over the HBM
bandwidth and the scores and values of the tokens fed over the keys each
sees over the bf16 peak, summed, over the device time of the kernel in
the trace, by its family name (``_gqa_window_pallas``). A program without
that kernel leaves no such events and the reader returns nothing. In per
cent."""
import importlib

KERNEL = "_gqa_window_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "sliding_window" not in model:
        return None
    work = importlib.import_module("benchmark.work_window_moe")
    bs = seen["config"]["engine"]["block_size"]
    least = 0.0
    for step in traced["steps"]:
        rows = [work.attention_row(model, bs, n, ctx) for n, ctx in step]
        least += max(sum(b for b, _ in rows) / peak["hbm_bytes_per_s"],
                     sum(f for _, f in rows) / peak["bf16_flops_per_s"])
    return 100.0 * least / seen["chips"] / device_s
