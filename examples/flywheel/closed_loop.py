"""Online-learning flywheel end-to-end: serve a model, sample its live
traffic into capture segments, retrain incrementally from the incumbent
checkpoint, and promote the candidate through the canary ladder — the
full capture → replay → retrain → promote cycle in one process
(docs/flywheel.md).

    python examples/flywheel/closed_loop.py [--requests 120] [--cycles 2]

The engine carries a RolloutConfig, so each cycle's candidate enters as
a canary and is promoted by the ladder's gates against real traffic —
clients see zero errors throughout. Uses ``fraction=1.0`` so a short run
captures enough rows; production taps run at ~1%.
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", ".."))

IN_DIM, OUT_DIM = 4, 2


def main(argv=None):
    p = argparse.ArgumentParser(description="flywheel closed-loop demo")
    p.add_argument("--requests", type=int, default=120,
                   help="live requests to capture per cycle")
    p.add_argument("--cycles", type=int, default=2)
    p.add_argument("--fraction", type=float, default=1.0)
    p.add_argument("--timeout-s", type=float, default=60.0)
    args = p.parse_args(argv)

    import optax

    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.flywheel import (
        CaptureConfig,
        CaptureTap,
        FlywheelController,
        FlywheelTrainer,
        RetrainConfig,
    )
    from analytics_zoo_tpu.ft import atomic
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense
    from analytics_zoo_tpu.serving import (
        BatcherConfig,
        RolloutConfig,
        ServingEngine,
    )

    zoo.init_nncontext()
    root = tempfile.mkdtemp(prefix="flywheel_demo_")
    cap_root = os.path.join(root, "capture")
    ckpt_dir = os.path.join(root, "ckpts")

    def build_est():
        return Estimator(
            Sequential([Dense(OUT_DIM, input_shape=(IN_DIM,))]),
            optax.sgd(0.05))

    # seed the incumbent: one conventional training pass so there is a
    # committed checkpoint to serve and warm-start from
    rng = np.random.default_rng(0)
    est = build_est()
    est.set_checkpoint(ckpt_dir, keep_last=6, asynchronous=False)
    est.train(ArrayFeatureSet(
        rng.normal(size=(32, IN_DIM)).astype(np.float32),
        rng.normal(size=(32, OUT_DIM)).astype(np.float32)),
        objectives.mean_squared_error, batch_size=8)

    class Lin:
        """Servable rebuilt from a committed checkpoint's params."""

        def __init__(self, w, b):
            self.w, self.b = w, b

        def do_predict(self, x):
            return np.asarray(x, np.float32) @ self.w + self.b

    def build_model(path):
        flat, _ = atomic.read_checkpoint(path)
        params = dict(flat)
        # layer auto-naming counts up per Estimator construction, so
        # match the Dense kernel/bias by rank, not by key
        w = next(v for v in params.values() if getattr(v, "ndim", 0) == 2)
        b = next(v for v in params.values() if getattr(v, "ndim", 0) == 1)
        return Lin(np.asarray(w), np.asarray(b))

    engine = ServingEngine(rollout=RolloutConfig(
        ladder=(0.25, 1.0), min_requests=4, auto_evaluate=False))
    tap = CaptureTap(CaptureConfig(
        directory=cap_root, fraction=args.fraction, rows_per_shard=32,
        roll_interval_s=0.1, idle_poll_s=0.02))
    engine.set_capture(tap)

    trainer = FlywheelTrainer(
        build_est, objectives.mean_squared_error,
        RetrainConfig(capture_dir=os.path.join(cap_root, "m"),
                      checkpoint_dir=ckpt_dir, batch_size=8,
                      checkpoint_every=4, min_rows=8))
    ctrl = FlywheelController(
        engine, "m", tap, trainer, build_model,
        example_input=np.ones((1, IN_DIM), np.float32),
        config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0))

    x_pool = rng.normal(size=(256, IN_DIM)).astype(np.float32)
    errors = [0]

    def traffic():
        for i in range(8):
            try:
                engine.predict("m", x_pool[int(rng.integers(256))][None, :])
            except Exception:
                errors[0] += 1

    reports = []
    for cycle in range(args.cycles):
        for i in range(args.requests):
            try:
                engine.predict("m", x_pool[i % 256][None, :])
            except Exception:
                errors[0] += 1
        t0 = time.perf_counter()
        report = ctrl.run_cycle(traffic_fn=traffic,
                                timeout_s=args.timeout_s)
        print(f"cycle {cycle + 1}: {report.outcome} "
              f"(candidate step {report.candidate_step}, "
              f"{len(report.consumed_segments)} segment(s), "
              f"{time.perf_counter() - t0:.2f}s)")
        reports.append(report)

    latest = engine.stats()["m"]["latest"]
    sampled = int(tap.metrics["sampled"].value)
    ctrl.close()
    tap.close()
    engine.shutdown()
    print(f"served version now {latest!r}; {sampled} requests sampled, "
          f"{errors[0]} client errors")
    return {
        "outcomes": [r.outcome for r in reports],
        "final_candidate_step": reports[-1].candidate_step,
        "served_latest": latest,
        "sampled": sampled,
        "client_errors": errors[0],
    }


if __name__ == "__main__":
    main()
