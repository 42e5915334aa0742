"""Batch scoring bench → BENCH_BATCH.json: cold vs warm-AOT vs resumed
throughput, restart compile counts, and dispatch/fetch overlap.

Two experiments (docs/batch-scoring.md has the measuring protocol):

1. **Restart economics** (real XLA, tiny Keras classifier): the same
   job runs three ways against one persistent AOT executable cache —
   ``cold`` (empty cache: every bucket compiles), ``warm_aot`` (a fresh
   ``InferenceModel``, i.e. a restarted process, same cache: the
   acceptance bar is **zero** ``zoo_compile_total`` compiles), and
   ``resumed`` (the job is killed mid-run at the ``batch_mid_job_kill``
   chaos site, then resumed by another fresh model: zero compiles, only
   the uncommitted tail re-scored, output bitwise identical to the
   uninterrupted reference).

2. **Overlap** (simulated device): scoring is host input work +
   device work per batch. A synchronous loop pays
   ``input + device`` per batch; the pipelined dispatch/fetch loop
   (+ host prefetch) pays ``max(input, device)``. The simulated model's
   ``do_fetch`` sleeps out the device time (releasing the GIL — host
   work proceeds), which data_bench.py showed matches real-XLA overlap
   behaviour while keeping the floor deterministic. Reported
   ``overlap_fraction`` = hidden time / min(input, device) — 1.0 is
   perfect overlap; the acceptance bar is the pipelined loop beating
   the synchronous one.

::

    JAX_PLATFORMS=cpu python scripts/batch_bench.py
"""

from __future__ import annotations

import argparse
import glob as glob_lib
import hashlib
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402


def build_model(feature_dim: int, weights_path=None):
    """The bench classifier (serving_bench's shape) behind a fresh
    ``InferenceModel``; with ``weights_path`` the weights load from disk,
    so every phase's model is bitwise the same net (fresh executables,
    identical math — a restarted process)."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    m = Sequential(name="batchbench")
    # explicit layer names: parameter-dict keys are part of the AOT
    # cache key, so they must be restart-stable
    m.add(Dense(32, activation="relu", input_shape=(feature_dim,),
                name="bb_dense_1"))
    m.add(Dense(8, activation="softmax", name="bb_dense_2"))
    if weights_path is not None:
        m.load_weights(weights_path)
    return m, InferenceModel().do_load_keras(m)


def _digest(directory: str) -> str:
    h = hashlib.sha256()
    for f in sorted(glob_lib.glob(os.path.join(directory, "shard_*.npy"))):
        with open(f, "rb") as fh:
            h.update(fh.read())
    return h.hexdigest()


def run_restart_bench(rows: int, feature_dim: int, batch: int,
                      buckets, rows_per_shard: int, work_dir: str):
    """cold / warm_aot / resumed phases against one AOT cache dir."""
    from analytics_zoo_tpu.batch import (
        BatchJobRunner,
        BatchPredictJob,
        OutputSpec,
    )
    from analytics_zoo_tpu.common.observability import (
        get_registry,
        install_compile_listener,
    )
    from analytics_zoo_tpu.data.sources import ArraySource
    from analytics_zoo_tpu.ft import chaos

    install_compile_listener()
    compiles = get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()

    rng = np.random.default_rng(7)
    X = rng.standard_normal((rows, feature_dim)).astype(np.float32)
    aot_dir = os.path.join(work_dir, "aot")
    weights = os.path.join(work_dir, "weights.npz")
    _net, _ = build_model(feature_dim)
    _net.save_weights(weights)

    def phase(name: str, out: str, resume=False, kill_after=None):
        _, inf = build_model(feature_dim, weights_path=weights)
        job = BatchPredictJob(inf, ArraySource(X), batch_size=batch,
                              pad_to_bucket=buckets, pipeline_depth=2,
                              aot_cache_dir=aot_dir)
        runner = BatchJobRunner(job, OutputSpec(out,
                                                rows_per_shard=rows_per_shard))
        c0 = compiles.value
        t0 = time.perf_counter()
        killed = False
        if kill_after is not None:
            # in-process stand-in for the subprocess kill: raise at the
            # chaos site instead of os._exit, leaving kill-identical
            # committed state behind (test_ft.py's chaos_raise idiom)
            class _Boom(BaseException):
                pass

            orig_fail = chaos.fail
            os.environ["AZOO_FT_CHAOS"] = "batch_mid_job_kill"
            os.environ["AZOO_FT_CHAOS_SKIP"] = str(kill_after)
            chaos.reset()
            chaos.fail = lambda p: (_ for _ in ()).throw(_Boom(p))
            try:
                runner.run()
            except _Boom:
                killed = True
            finally:
                chaos.fail = orig_fail
                os.environ.pop("AZOO_FT_CHAOS")
                os.environ.pop("AZOO_FT_CHAOS_SKIP")
                chaos.reset()
            report = {"killed_after_shards": kill_after}
        else:
            report = runner.run(resume=resume)
        wall = time.perf_counter() - t0
        rec = {"wall_s": round(wall, 3),
               "compiles": int(compiles.value - c0)}
        if not killed and kill_after is None:
            rec["rows_per_sec"] = round(report["rows"] / wall, 1)
            rec["skipped_shards"] = report["skipped_shards"]
        return rec

    ref_out = os.path.join(work_dir, "out_cold")
    warm_out = os.path.join(work_dir, "out_warm")
    resumed_out = os.path.join(work_dir, "out_resumed")

    record = {"metric": "batch_restart",
              "rows": rows, "batch_size": batch,
              "buckets": list(buckets), "rows_per_shard": rows_per_shard}
    record["cold"] = phase("cold", ref_out)
    record["warm_aot"] = phase("warm_aot", warm_out, resume=False)
    phase("kill", resumed_out, kill_after=2)
    record["resumed"] = phase("resumed", resumed_out, resume=True)
    record["resumed"]["bitwise_identical_to_cold"] = (
        _digest(resumed_out) == _digest(ref_out))
    return record


class SimulatedDeviceModel:
    """A device that takes exactly ``device_ms`` per batch, with a truly
    async dispatch: ``do_dispatch`` stamps when the result will be ready
    and returns immediately; ``do_fetch`` sleeps out whatever remains
    (``time.sleep`` releases the GIL, so host-side input work overlaps —
    the same simulated-device floor data_bench.py uses)."""

    def __init__(self, device_ms: float):
        self.device_s = device_ms / 1e3
        self._free_at = 0.0  # one device queue: batches serialize

    def do_dispatch(self, x):
        start = max(time.perf_counter(), self._free_at)
        self._free_at = start + self.device_s
        return self._free_at, np.asarray(x) * 2.0

    def do_fetch(self, out):
        ready_at, payload = out
        delay = ready_at - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        return payload

    def do_predict(self, x):
        time.sleep(self.device_s)
        return np.asarray(x) * 2.0


def run_overlap_bench(rows: int, feature_dim: int, batch: int,
                      input_ms: float, device_ms: float, work_dir: str):
    """Synchronous loop vs pipelined dispatch/fetch on the simulated
    device, identical input cost (a per-sample ``map`` sleep)."""
    from analytics_zoo_tpu.batch import (
        BatchJobRunner,
        BatchPredictJob,
        OutputSpec,
    )
    from analytics_zoo_tpu.data.pipeline import Pipeline
    from analytics_zoo_tpu.data.sources import ArraySource

    rng = np.random.default_rng(3)
    X = rng.standard_normal((rows, feature_dim)).astype(np.float32)
    per_sample_s = input_ms / 1e3 / batch

    def slow_input(rec):
        time.sleep(per_sample_s)  # the simulated decode/transform cost
        return rec

    def run(depth: int, out: str):
        # the synchronous baseline is fully serial — no host prefetch, no
        # dispatch depth — so it pays input + device per batch; the
        # pipelined run overlaps both stages
        pipe = (Pipeline(ArraySource(X))
                .map(slow_input)
                .batch(batch, pad_to_bucket=(batch,)))
        if depth:
            pipe = pipe.prefetch(2)
        job = BatchPredictJob(SimulatedDeviceModel(device_ms), pipe,
                              prefetch=0, pipeline_depth=depth)
        t0 = time.perf_counter()
        report = BatchJobRunner(
            job, OutputSpec(out, rows_per_shard=rows)).run()
        wall = time.perf_counter() - t0
        return wall, report["rows"] / wall

    sync_wall, sync_rps = run(0, os.path.join(work_dir, "ov_sync"))
    pipe_wall, pipe_rps = run(2, os.path.join(work_dir, "ov_pipe"))
    n_batches = -(-rows // batch)
    hideable_s = n_batches * min(input_ms, device_ms) / 1e3
    overlap = (sync_wall - pipe_wall) / hideable_s if hideable_s else 0.0
    return {
        "metric": "batch_overlap",
        "rows": rows, "batch_size": batch,
        "input_ms_per_batch": input_ms, "device_ms_per_batch": device_ms,
        "sync": {"wall_s": round(sync_wall, 3),
                 "rows_per_sec": round(sync_rps, 1)},
        "pipelined": {"wall_s": round(pipe_wall, 3),
                      "rows_per_sec": round(pipe_rps, 1),
                      "depth": 2},
        "speedup": round(sync_wall / pipe_wall, 3),
        "overlap_fraction": round(min(1.0, max(0.0, overlap)), 3),
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--rows", type=int, default=600)
    p.add_argument("--feature-dim", type=int, default=16)
    p.add_argument("--batch", type=int, default=32)
    p.add_argument("--rows-per-shard", type=int, default=100)
    p.add_argument("--overlap-rows", type=int, default=512)
    p.add_argument("--input-ms", type=float, default=6.0,
                   help="simulated host input cost per batch")
    p.add_argument("--device-ms", type=float, default=6.0,
                   help="simulated device cost per batch")
    p.add_argument("--out", default="BENCH_BATCH.json")
    args = p.parse_args(argv)

    with tempfile.TemporaryDirectory(prefix="azoo-batch-bench-") as work:
        restart = run_restart_bench(
            args.rows, args.feature_dim, args.batch,
            buckets=(8, 16, args.batch), rows_per_shard=args.rows_per_shard,
            work_dir=work)
        overlap = run_overlap_bench(
            args.overlap_rows, args.feature_dim, args.batch,
            args.input_ms, args.device_ms, work_dir=work)

    record = {"bench": "batch_scoring", "restart": restart,
              "overlap": overlap,
              "device": device_info()}
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(json.dumps(record, indent=2))
    return record


if __name__ == "__main__":
    main()
