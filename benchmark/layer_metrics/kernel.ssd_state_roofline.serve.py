"""The state-space step kernel's share of its roofline: the least time the
chip could take to read once and write once the recurrent state of every
row of each traced step, in every state-space layer
(``work_ssm_hybrid.py``: rows x layers x 2 x ``[H, P, N]`` float32; a
decode row's update is bound by those bytes), over the device time of the
kernel in the trace, by its family name (``_ssd_state_pallas``). A program
without that kernel leaves no such events and the reader returns nothing.
In per cent."""
import importlib

KERNEL = "_ssd_state_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "layer_types" not in model:
        return None
    work = importlib.import_module("benchmark.work_ssm_hybrid")
    total = sum(work.state_step_bytes(model, len(step))
                for step in traced["steps"])
    return 100.0 * total / seen["chips"] / peak["hbm_bytes_per_s"] / device_s
