"""The whole step's share of the chips' bf16 peak: the operations that the
forward and backward passes require per image (``work.py``, from the
model's shapes; recomputation does not count) times the images a second
of the traced window, over the peak. In per cent."""
import importlib


def read(seen):
    traced, peak = seen["traced"], seen["peak"]
    if not traced or not peak:
        return None
    work = importlib.import_module("benchmark.work")
    flops = work.resnet_train_flops_per_image(seen["config"]["model"])
    rate = traced["images"] / seen["trace"]["window_s"]
    return 100.0 * flops * rate / (peak["bf16_flops_per_s"] * seen["chips"])
