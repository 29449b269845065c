"""Input-pipeline throughput: can the decode/augment path outrun the
device? (reference protocol: the C++ ImageRecordIter is benchmarked by
tools/bandwidth checks; here the bar is the device-side train img/s
of the benchmark's training cell, ``train.images_per_s`` in
PERF_LEDGER.jsonl — the pipeline must exceed it or it becomes the
bottleneck on real data.)

Packs synthetic 480x480 JPEGs (ImageNet-scale decode cost) into a .rec,
then times ImageRecordIterNative and, for comparison, the pure-Python
ImageIter, with the standard train augmentation (resize-short 256,
random 224 crop, mirror).

Usage: python tools/bench_input_pipeline.py [n_images] [batch]
Prints one JSON line.
"""
import json
import os
import sys
import tempfile
import time

import numpy as np


def make_rec(prefix, n, hw=480):
    from mxnet_tpu import recordio
    rng = np.random.RandomState(0)
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "w")
    # smooth-ish images compress/decode like photos, not noise
    for i in range(n):
        base = rng.randint(0, 255, (hw // 8, hw // 8, 3), dtype=np.uint8)
        import cv2
        img = cv2.resize(base, (hw, hw), interpolation=cv2.INTER_CUBIC)
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack_img(header, img, quality=90,
                                           img_fmt=".jpg"))
    rec.close()


def time_iter(it, warm_batches=2, min_seconds=5.0):
    for _ in range(warm_batches):
        next(it)
    n = 0
    t0 = time.perf_counter()
    while time.perf_counter() - t0 < min_seconds:
        try:
            b = next(it)
        except StopIteration:
            it.reset()
            continue
        np.asarray(b.data[0].asnumpy()[0, 0])  # touch the data
        n += it.batch_size
    return n / (time.perf_counter() - t0)


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 512
    batch = int(sys.argv[2]) if len(sys.argv) > 2 else 128
    from mxnet_tpu.image import (ImageIter, ImageRecordIterNative,
                                 native_pipeline_available)
    with tempfile.TemporaryDirectory() as d:
        prefix = os.path.join(d, "bench")
        make_rec(prefix, n)
        out = {"batch": batch, "n_images": n,
               "threads": os.cpu_count()}
        if native_pipeline_available():
            it = ImageRecordIterNative(
                path_imgrec=prefix + ".rec", data_shape=(3, 224, 224),
                batch_size=batch, shuffle=True, rand_crop=True,
                rand_mirror=True, resize=256)
            out["native_img_s"] = round(time_iter(it), 1)
            it.close()
        py_it = ImageIter(
            batch_size=batch, data_shape=(3, 224, 224),
            path_imgrec=prefix + ".rec", shuffle=True,
            aug_list=None, resize=256, rand_crop=True, rand_mirror=True)
        out["python_img_s"] = round(time_iter(py_it), 1)
        if "native_img_s" in out:
            out["native_speedup"] = round(
                out["native_img_s"] / out["python_img_s"], 2)
        print(json.dumps(out))


if __name__ == "__main__":
    main()
