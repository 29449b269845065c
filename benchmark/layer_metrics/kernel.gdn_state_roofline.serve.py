"""The gated delta-rule kernel's share of its roofline: for each traced
step the least time the chip could take, the larger of moving every
row's state once in and once out in every GatedDeltaNet layer (rows x
layers x 2 x ``[value heads, Dk, Dv]`` float32 over the HBM bandwidth)
and the delta rule's operations for the tokens fed (``work_gdn_mla_moe.py``
over the bf16 peak), summed, over the device time of the kernel in the
trace, by its family name (``_gdn_state_pallas``). The convolution's
window is moved by the step's other operations, not by the kernel, and is
not counted. A program without that kernel leaves no such events and the
reader returns nothing. In per cent."""
import importlib

KERNEL = "_gdn_state_pallas"


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    model = seen["config"]["model"]
    device_s = seen["trace"].get("by_family", {}).get(KERNEL, 0.0)
    if not traced or not peak or not traced["steps"] or device_s <= 0 \
            or "linear_num_value_heads" not in model:
        return None
    work = importlib.import_module("benchmark.work_gdn_mla_moe")
    least = sum(max(
        work.state_step_bytes(model, len(step)) / peak["hbm_bytes_per_s"],
        work.delta_rule_step_flops(model, sum(n for n, _ in step))
        / peak["bf16_flops_per_s"]) for step in traced["steps"])
    return 100.0 * least / seen["chips"] / device_s
