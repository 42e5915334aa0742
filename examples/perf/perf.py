"""Inference perf harness — ref examples/vnni/bigdl/Perf.scala:61-68 (the
imgs/sec loop over a catalog model, f32 vs INT8). An example for a user's
own device; the repo's numbers come from benchmark/run.py (PERF.md).

Measures steady-state predict throughput of a catalog image classifier,
optionally through InferenceModel.do_quantize (weight-only int8) and/or
do_calibrate (the full VNNI-INT8 analogue: calibrated activation int8
with integer matmuls/convs) — printing imgs/sec and the speed ratios.
"""

from __future__ import annotations

import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))


def _measure(fn, x, iters, warmup=2):
    for _ in range(warmup):
        out = fn(x)
    np.asarray(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    np.asarray(out)                      # materialize = barrier
    dt = time.perf_counter() - t0
    return len(x) * iters / dt


def main(argv=None):
    p = argparse.ArgumentParser(description="Catalog-model inference perf")
    p.add_argument("--model", default="squeezenet")
    p.add_argument("--image-size", type=int, default=128)
    p.add_argument("--batch-size", "-b", type=int, default=32)
    p.add_argument("--iters", type=int, default=10)
    p.add_argument("--quantize", action="store_true",
                   help="also measure the int8-weight path")
    p.add_argument("--calibrate", action="store_true",
                   help="also measure calibrated activation-int8 (integer "
                        "matmuls/convs — the full doCalibrateTF story)")
    args = p.parse_args(argv)

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.image.imageclassification import (
        ImageClassifier,
    )

    ctx = zoo.init_nncontext()
    print(f"{args.model} @ {args.image_size}px, batch {args.batch_size}, "
          f"{ctx.num_devices} x {ctx.devices[0].device_kind}")

    clf = ImageClassifier(args.model, num_classes=1000,
                          input_shape=(args.image_size, args.image_size, 3))
    inf = InferenceModel()
    inf.do_load_keras(clf.model)
    x = np.random.default_rng(0).normal(
        size=(args.batch_size, args.image_size, args.image_size, 3)
    ).astype(np.float32)

    f32 = _measure(inf.do_predict, x, args.iters)
    print(f"f32:  {f32:8.1f} imgs/s")
    result = {"f32_imgs_per_sec": f32}

    if args.quantize:
        inf.do_quantize()
        q8 = _measure(inf.do_predict, x, args.iters)
        print(f"int8: {q8:8.1f} imgs/s  ({q8 / f32:.2f}x)")
        result.update({"int8_imgs_per_sec": q8, "speedup": q8 / f32})

    if args.calibrate:
        # fresh InferenceModel: calibration refuses on an already-quantized
        # one, and the comparison should be f32-load -> calibrate
        inf2 = InferenceModel()
        inf2.do_load_keras(clf.model)
        inf2.do_calibrate([x])            # representative batch
        c8 = _measure(inf2.do_predict, x, args.iters)
        print(f"calibrated int8: {c8:8.1f} imgs/s  ({c8 / f32:.2f}x)")
        result.update({"calibrated_imgs_per_sec": c8,
                       "calibrated_speedup": c8 / f32})
    return result


if __name__ == "__main__":
    main()
