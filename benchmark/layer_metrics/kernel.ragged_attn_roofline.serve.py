"""The ragged attention kernel's share of its roofline: the least time the
chip could take to read the K and V pages that each sequence's context
requires in each traced step (``work.py``; the kernel is bound by memory)
over the device time of the Mosaic kernels in the trace
(``custom-call.tpu_custom_call``: the flat attention kernel is the only
one the float32 step holds). In per cent."""
import importlib

KERNEL = "custom-call.tpu_custom_call"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    device_s = seen["trace"]["by_category"].get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0:
        return None
    work = importlib.import_module("benchmark.work")
    block = seen["config"]["observed_defaults"]["block_size"]
    total = sum(work.ragged_attention_bytes(
        seen["config"]["model"], [ctx for _, ctx in step], block)
        for step in traced["steps"])
    return 100.0 * total / seen["chips"] / peak["hbm_bytes_per_s"] / device_s
