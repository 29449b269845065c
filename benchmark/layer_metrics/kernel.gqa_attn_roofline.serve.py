"""The grouped-query attention kernel's share of its roofline: the least
time the chip could take to read the K and V pages that each sequence's
context requires in each traced step (``work_ssm_hybrid.py``: pages of
``block_size`` tokens of 2 x 8 x 64 bf16 values, the attention layers
alone; the kernel is bound by memory) over the device time of the kernel
in the trace, by its family name (``_gqa_flat_pallas``). A program without
that kernel leaves no such events and the reader returns nothing. In per
cent."""
import importlib

KERNEL = "_gqa_flat_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "layer_types" not in model:
        return None
    work = importlib.import_module("benchmark.work_ssm_hybrid")
    block = seen["config"]["observed_defaults"]["block_size"]
    total = sum(work.kv_cache_bytes(
        model, [ctx for _, ctx in step], block) for step in traced["steps"])
    return 100.0 * total / seen["chips"] / peak["hbm_bytes_per_s"] / device_s
