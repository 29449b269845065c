"""The convolutions' share of their roofline: the least time the chip
could take for the operations the convolutions require in the traced
steps (forward and backward, at the bf16 peak: at these shapes they are
bound by compute), over the device time of the trace's convolution
fusions (``fusion.Output`` and bare ``convolution``). XLA fuses BatchNorm
statistics and elementwise work into those fusions, and that time counts
against the share. In per cent."""
import importlib

CONV = ("fusion.Output", "convolution")


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    by_cat = seen["trace"]["by_category"]
    device_s = sum(by_cat.get(c, 0.0) for c in CONV)
    if not traced or not peak or device_s <= 0:
        return None
    work = importlib.import_module("benchmark.work")
    flops = work.resnet_conv_train_flops_per_image(seen["config"]["model"])
    least_s = flops * traced["images"] / seen["chips"] \
        / peak["bf16_flops_per_s"]
    return 100.0 * least_s / device_s
