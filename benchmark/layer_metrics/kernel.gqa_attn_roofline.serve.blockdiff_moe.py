"""The grouped-query attention kernel's share of its roofline under a
block-diffusion expert decoder: for each traced step the least time the
chip could take, the larger of reading the K and V pages that each
sequence's context requires (every layer; ``work_blockdiff_moe.py``) over
the HBM bandwidth and the scores and values of the tokens fed over the
bf16 peak, over the device time of the kernel in the trace, by its family
name (``_gqa_flat_pallas``). A program without that kernel leaves no such
events and the reader returns nothing. In per cent."""
import importlib

KERNEL = "_gqa_flat_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "block_length" not in model:
        return None
    work = importlib.import_module("benchmark.work_blockdiff_moe")
    block = seen["config"]["observed_defaults"]["block_size"]
    least = sum(max(
        work.kv_cache_bytes(model, [ctx for _, ctx in step], block)
        / peak["hbm_bytes_per_s"],
        sum(work.attention_flops(model, n, ctx) for n, ctx in step)
        / peak["bf16_flops_per_s"]) for step in traced["steps"])
    return 100.0 * least / seen["chips"] / device_s
