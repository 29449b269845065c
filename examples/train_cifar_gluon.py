#!/usr/bin/env python
"""Train a model-zoo ResNet with Gluon, TPU-first knobs included.

Reference example: example/image-classification/train_cifar10.py. Data
is synthetic CIFAR-shaped (no egress); the flags show the TPU path:
--layout NHWC --dtype bfloat16 --stem-s2d run the configuration the
benchmark's training cell measures (BENCHMARK.json).

  python examples/train_cifar_gluon.py --steps 20 --layout NHWC
"""
import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import gluon, nd  # noqa: E402
import mxnet_tpu.autograd as ag  # noqa: E402
from mxnet_tpu.gluon.model_zoo import vision  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet18_v1")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--layout", default="NHWC", choices=["NCHW", "NHWC"])
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--stem-s2d", action="store_true",
                    help="MLPerf space-to-depth stem (NHWC only)")
    args = ap.parse_args()

    mx.random.seed(0)
    kwargs = {"layout": args.layout}
    if args.stem_s2d:
        kwargs["stem_s2d"] = True
    net = vision.get_model(args.model, classes=10, **kwargs) \
        if hasattr(vision, "get_model") else \
        getattr(vision, args.model)(classes=10, **kwargs)
    net.initialize(init=mx.initializer.Xavier())
    if args.dtype != "float32":
        net.cast(args.dtype)
    net.hybridize()

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.05, "momentum": 0.9})
    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()

    rng = np.random.RandomState(0)
    shape = (args.batch_size, 3, 32, 32) if args.layout == "NCHW" \
        else (args.batch_size, 32, 32, 3)
    x = nd.array(rng.randn(*shape).astype(args.dtype))
    y = nd.array((np.arange(args.batch_size) % 10).astype(np.float32))

    for step in range(args.steps):
        with ag.record():
            loss = loss_fn(net(x), y).mean()
        loss.backward()
        trainer.step(args.batch_size)
        if step % 5 == 0 or step == args.steps - 1:
            print(f"step {step}: loss {float(loss.asnumpy()):.4f}")


if __name__ == "__main__":
    main()
