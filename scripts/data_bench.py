"""Input-pipeline overlap bench: synchronous ``FeatureSet.train_batches``
vs the streaming ``Pipeline`` with parallel map workers + async device
prefetch, on a transform-heavy workload. Emits BENCH_DATA.json.

    python scripts/data_bench.py [--samples 256] [--batch 32]
        [--workers 4] [--epochs 4] [--out BENCH_DATA.json]

What it measures (docs/data-pipeline.md "is my run input-bound?"):

- ``input_only_ms`` — per-batch host cost of the transform chain alone
  (blur-resize-crop-flip-normalize in cv2/numpy, no device work),
- two step models, reported side by side and clearly labeled:

  * ``simulated_device`` — the step is a host-idle wait calibrated to
    the MEASURED XLA step time of a real jitted train step on this
    machine. This models an accelerator step faithfully: a TPU computes
    without consuming host CPU, so host-side input work genuinely
    proceeds underneath it. The overlap numbers that matter for the
    TPU deployment story come from this mode.
  * ``xla_cpu_inline`` — the same jitted step executed inline on the
    host CPU. On a multi-core host this also shows overlap (input
    workers run on cores XLA isn't using); on a single-core container
    input threads and XLA contend for the same core and overlap is
    physically impossible — the mode is kept, honestly, as the floor.

For each mode: ``sync_step_ms`` (transforms on the train-loop thread —
the pre-pipeline shape), ``pipeline_step_ms`` (``.map(aug, workers)``
+ ``.prefetch(k)`` device stream), and
``overlap_fraction`` = (sync - pipeline) / min(input, device): the share
of the hideable cost the pipeline actually hid.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

_IMG = 96      # stored image side
_CROP = 56     # augmented crop side


def _augment_one(img: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """The transform-heavy per-sample chain (cv2 + numpy): blur, upscale,
    blur, random crop, flip, brightness, normalize — the ImageSet
    augmentation shape without file I/O, so the bench isolates host
    transform cost."""
    import cv2

    a = img
    for _ in range(3):  # transform-HEAVY: repeated blur-resize rounds
        a = cv2.GaussianBlur(a, (7, 7), 1.5)
        a = cv2.resize(a, (128, 128))
    a = cv2.GaussianBlur(a, (7, 7), 1.5)
    y0 = int(rng.integers(0, 128 - _CROP + 1))
    x0 = int(rng.integers(0, 128 - _CROP + 1))
    a = a[y0:y0 + _CROP, x0:x0 + _CROP]
    if rng.random() < 0.5:
        a = a[:, ::-1]
    a = a.astype(np.float32) + float(rng.uniform(-12, 12))
    return np.ascontiguousarray((a - 128.0) / 64.0)


def _make_step(tx):
    import jax
    import jax.numpy as jnp
    import optax

    def loss_fn(p, x, y):
        h = jnp.maximum(x.reshape(x.shape[0], -1) @ p["w1"] + p["b1"], 0.0)
        logits = h @ p["w2"] + p["b2"]
        onehot = jax.nn.one_hot(y, logits.shape[-1])
        return -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits), -1))

    @jax.jit
    def step(p, opt_state, x, y):
        loss, grads = jax.value_and_grad(loss_fn)(p, x, y)
        updates, opt_state = tx.update(grads, opt_state, p)
        return optax.apply_updates(p, updates), opt_state, loss

    return step


def run_bench(samples: int, batch: int, workers: int, epochs: int,
              prefetch: int = 2, seed: int = 0):
    import jax
    import optax

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.common.runtime import device_info
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.data.pipeline import Pipeline
    from analytics_zoo_tpu.data.sources import ArraySource
    from analytics_zoo_tpu.parallel.sharding import shard_batch

    ctx = zoo.init_nncontext()
    mesh = ctx.mesh
    rng = np.random.default_rng(seed)
    raw = rng.integers(0, 255, size=(samples, _IMG, _IMG, 3)).astype(np.uint8)
    labels = rng.integers(0, 10, samples).astype(np.int32)
    steps_per_epoch = -(-samples // batch)
    n_steps = epochs * steps_per_epoch

    dim = _CROP * _CROP * 3
    p0 = {
        "w1": rng.normal(0, 0.05, (dim, 48)).astype(np.float32),
        "b1": np.zeros(48, np.float32),
        "w2": rng.normal(0, 0.05, (48, 10)).astype(np.float32),
        "b2": np.zeros(10, np.float32),
    }
    tx = optax.adam(1e-3)
    xla_step = _make_step(tx)
    params = jax.device_put(p0)
    opt_state = tx.init(params)

    def pipe(n_workers):
        def aug(rec, r):
            x, y = rec
            return _augment_one(x, r), y

        return (Pipeline(ArraySource(raw, labels), seed=seed)
                .map(aug, num_workers=n_workers)
                .batch(batch).prefetch(prefetch))

    # the synchronous baseline: the SAME per-sample chain as a per-batch
    # TransformedFeatureSet transform, run on the train-loop thread
    def batch_aug(x, y):
        r = np.random.default_rng(seed)
        return np.stack([_augment_one(a, r) for a in x]), y

    sync_fs = ArrayFeatureSet(raw, labels).transform(batch_aug)

    # -- input-only: host transform cost, no device work -----------------
    t0 = time.perf_counter()
    n_b = 0
    for _ in range(epochs):
        for _b in pipe(0).train_batches(batch, shuffle=True, seed=seed):
            n_b += 1
    input_only_ms = (time.perf_counter() - t0) / n_b * 1e3

    # -- calibrate the device model: the real jitted step, warm ----------
    xb = shard_batch(mesh, np.zeros((batch, _CROP, _CROP, 3), np.float32))
    yb = shard_batch(mesh, np.zeros(batch, np.int32))
    params, opt_state, loss = xla_step(params, opt_state, xb, yb)  # compile
    jax.block_until_ready(loss)
    t0 = time.perf_counter()
    for _ in range(n_steps):
        params, opt_state, loss = xla_step(params, opt_state, xb, yb)
    jax.block_until_ready(loss)
    device_ms = (time.perf_counter() - t0) / n_steps * 1e3

    def timed(loop):
        t0 = time.perf_counter()
        n = loop()
        return (time.perf_counter() - t0) / n * 1e3

    def mode(step_fn, drain):
        """sync + pipeline wall time per step under one step model."""
        def sync_loop():
            n = 0
            for e in range(epochs):
                for x, y, _m in sync_fs.train_batches(batch, shuffle=True,
                                                      seed=e):
                    step_fn(shard_batch(mesh, x), shard_batch(mesh, y))
                    n += 1
            drain()
            return n

        def pipe_loop():
            n = 0
            streaming = pipe(workers)
            for e in range(epochs):
                for x, y, _m in streaming.device_batches(batch, shuffle=True,
                                                         seed=e):
                    step_fn(x, y)
                    n += 1
            drain()
            return n

        sync_ms = timed(sync_loop)
        pipe_ms = timed(pipe_loop)
        hideable = min(input_only_ms, device_ms)
        overlap = max(0.0, min(1.0, (sync_ms - pipe_ms) / max(hideable, 1e-9)))
        return {
            "sync_step_ms": round(sync_ms, 3),
            "pipeline_step_ms": round(pipe_ms, 3),
            "speedup_vs_sync": round(sync_ms / pipe_ms, 3),
            "overlap_fraction": round(overlap, 3),
            "sync_samples_per_sec": round(batch / sync_ms * 1e3, 1),
            "pipeline_samples_per_sec": round(batch / pipe_ms * 1e3, 1),
        }

    # simulated accelerator: host-idle wait of the calibrated step time
    # (time.sleep releases the GIL — input workers genuinely run under it,
    # exactly like host threads under an in-flight TPU step)
    sim = mode(lambda x, y: time.sleep(device_ms / 1e3), lambda: None)
    sim["device_step_ms"] = round(device_ms, 3)
    sim["note"] = (
        "step = host-idle wait calibrated to the measured XLA-CPU step "
        f"({device_ms:.2f} ms): models an accelerator step, which does not "
        "consume host CPU — the TPU-deployment overlap number")

    # inline XLA-CPU: the real step executed on the host
    state = {"p": params, "o": opt_state, "l": loss}

    def inline_step(x, y):
        state["p"], state["o"], state["l"] = xla_step(state["p"], state["o"],
                                                      x, y)

    xla = mode(inline_step,
               lambda: jax.block_until_ready(state["l"]))
    xla["note"] = (
        "step = the same jitted step run inline on the host CPU; input "
        "workers and XLA share this machine's cores, so on a 1-core "
        "container overlap is physically impossible (floor), while "
        "multi-core hosts show real overlap here too")

    from analytics_zoo_tpu.common.observability import get_registry

    starvation = None
    for line in get_registry().render().splitlines():
        if line.startswith("zoo_data_starvation_ratio "):
            starvation = float(line.split()[-1])

    return {
        "metric": "input_pipeline_overlap",
        "host_cpus": os.cpu_count(),
        "device": device_info(),
        "samples": samples,
        "image_shape": [_IMG, _IMG, 3],
        "crop": _CROP,
        "batch_size": batch,
        "map_workers": workers,
        "prefetch_depth": prefetch,
        "epochs_timed": epochs,
        "steps_per_epoch": steps_per_epoch,
        "input_only_ms": round(input_only_ms, 3),
        "device_step_ms": round(device_ms, 3),
        "simulated_device": sim,
        "xla_cpu_inline": xla,
        "starvation_ratio_end": starvation,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(description="Input-pipeline overlap bench")
    ap.add_argument("--samples", type=int, default=256)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--epochs", type=int, default=4)
    ap.add_argument("--prefetch", type=int, default=2)
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "BENCH_DATA.json"))
    args = ap.parse_args(argv)

    rec = run_bench(args.samples, args.batch, args.workers, args.epochs,
                    prefetch=args.prefetch)
    print(json.dumps(rec, indent=2))
    for name in ("simulated_device", "xla_cpu_inline"):
        m = rec[name]
        print(f"\n[{name}]")
        print(f"  sync      {m['sync_step_ms']:8.2f} ms/step "
              f"({m['sync_samples_per_sec']:8.1f} samples/s)")
        print(f"  pipeline  {m['pipeline_step_ms']:8.2f} ms/step "
              f"({m['pipeline_samples_per_sec']:8.1f} samples/s)")
        print(f"  overlap   {m['overlap_fraction']:.0%} of the hideable "
              f"{min(rec['input_only_ms'], rec['device_step_ms']):.2f} ms")
    with open(args.out, "w") as f:
        json.dump(rec, f, indent=2)
        f.write("\n")
    print(f"\nwrote {os.path.abspath(args.out)}")
    return rec


if __name__ == "__main__":
    main()
