"""Flywheel bench: capture-tap overhead on the serving hot path, plus
one real closed-loop cycle's latency. Emits BENCH_FLYWHEEL.json.

    python scripts/flywheel_bench.py [--clients 8] [--requests 150]
        [--fraction 0.01] [--trials 3] [--outcomes]
        [--out BENCH_FLYWHEEL.json]

Two claims under test (docs/flywheel.md):

1. **Capture is free at serving time.** The tap's hot-path cost is one
   sampler decision plus one queue put on a done-callback — encoding and
   shard writes happen on the writer thread. Closed-loop clients hammer
   a numpy model through the ServingEngine with capture off, then with
   capture on at the production default 1% sampling; the acceptance bar
   is <2% req/s regression (best-of-``--trials`` on both sides, so
   scheduler noise cancels rather than accumulates).

2. **The cycle is fast enough to run continuously.** One real
   serve → capture → rotate → warm-start retrain → canary-ladder
   promotion cycle end to end, timed. This is the latency floor between
   "data observed" and "model updated" the flywheel can sustain.

Runs anywhere (``JAX_PLATFORMS=cpu`` works). No outer timeout — see the
measuring protocol in docs/performance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402


class MatmulModel:
    """Duck-typed servable: a real (non-sleeping) numpy forward so the
    bench measures the tap's overhead against actual work, not against
    an empty function where any fixed cost looks enormous."""

    def __init__(self, dim: int = 64, seed: int = 0):
        rng = np.random.default_rng(seed)
        self.w = rng.normal(size=(dim, dim)).astype(np.float32)

    def do_predict(self, x):
        return np.asarray(x, np.float32) @ self.w


def run_load(engine, name: str, clients: int, requests: int,
             dim: int) -> dict:
    """Closed-loop: ``clients`` threads each issue ``requests``
    sequential predicts; returns req/s and latency percentiles."""
    x = np.ones((1, dim), np.float32)
    lat = [[] for _ in range(clients)]
    errors = [0]
    start = threading.Barrier(clients + 1)

    def client(slot):
        start.wait()
        for _ in range(requests):
            t0 = time.perf_counter()
            try:
                engine.predict(name, x)
            except Exception:
                errors[0] += 1
            lat[slot].append(time.perf_counter() - t0)

    threads = [threading.Thread(target=client, args=(i,), daemon=True)
               for i in range(clients)]
    for t in threads:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    flat = sorted(v for slot in lat for v in slot)
    total = clients * requests
    return {
        "req_per_s": round(total / wall, 1),
        "latency_p50_ms": round(flat[len(flat) // 2] * 1e3, 3),
        "latency_p99_ms": round(flat[int(len(flat) * 0.99)] * 1e3, 3),
        "errors": errors[0],
        "wall_s": round(wall, 3),
    }


def bench_capture_overhead(clients: int, requests: int, fraction: float,
                           trials: int, dim: int = 64) -> dict:
    """Best-of-``trials`` req/s with the tap off vs on at ``fraction``."""
    from analytics_zoo_tpu.flywheel import CaptureConfig, CaptureTap
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    cfg = BatcherConfig(max_batch_size=32, max_wait_ms=1.0)
    results = {"off": [], "on": []}
    cap_root = tempfile.mkdtemp(prefix="fly_bench_cap_")
    sampled = 0
    for trial in range(trials):
        for mode in ("off", "on"):
            engine = ServingEngine()
            engine.register("m", MatmulModel(dim),
                            np.ones((1, dim), np.float32), config=cfg)
            tap = None
            if mode == "on":
                tap = CaptureTap(CaptureConfig(
                    directory=os.path.join(cap_root, f"t{trial}"),
                    fraction=fraction))
                tap.enable("m")
                engine.set_capture(tap)
            # warmup outside the timed window
            for _ in range(20):
                engine.predict("m", np.ones((1, dim), np.float32))
            # the metrics registry is process-global: count this run's
            # samples as a delta, not the accumulated total
            s0 = tap.metrics["sampled"].value if tap is not None else 0
            cell = run_load(engine, "m", clients, requests, dim)
            results[mode].append(cell)
            if tap is not None:
                tap.flush()
                sampled = tap.metrics["sampled"].value - s0
                tap.close()
            engine.shutdown()
    best_off = max(results["off"], key=lambda c: c["req_per_s"])
    best_on = max(results["on"], key=lambda c: c["req_per_s"])
    overhead = (best_off["req_per_s"] - best_on["req_per_s"]) \
        / best_off["req_per_s"] * 100.0
    return {
        "clients": clients,
        "requests_per_client": requests,
        "sampling_fraction": fraction,
        "trials": trials,
        "capture_off": best_off,
        "capture_on": best_on,
        "capture_on_sampled_rows": int(sampled),
        "overhead_pct": round(overhead, 2),
        "all_off_rps": [c["req_per_s"] for c in results["off"]],
        "all_on_rps": [c["req_per_s"] for c in results["on"]],
    }


def bench_outcomes(clients: int, requests: int, trials: int,
                   dim: int = 64) -> dict:
    """Outcome-plane smoke (ISSUE 19), two claims from docs/flywheel.md:

    1. **Label ingestion doesn't tax serving.** Same best-of-trials
       protocol as the capture bench, but the "on" side runs two
       labeler threads POSTing 16-record ``:outcome`` batches over HTTP
       (~320 labels/s — ~7x the label rate the joiner needs at the
       production 1% sampling fraction) against the same engine the
       predict clients hammer. Acceptance: <2% req/s regression.
    2. **Every captured trace joins.** Capture at fraction 1.0, label
       every captured trace id, rotate, and read the joiner's stats.
       Acceptance: completeness == 1.0 (no row the trainer would see
       in outcome mode goes unlabeled when its label exists).
    """
    import http.client

    from analytics_zoo_tpu.batch import writers
    from analytics_zoo_tpu.flywheel import (
        CaptureConfig, CaptureTap, LabelStore,
    )
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine
    from analytics_zoo_tpu.serving.http import serve as serve_http

    cfg = BatcherConfig(max_batch_size=32, max_wait_ms=1.0)
    root = tempfile.mkdtemp(prefix="fly_bench_outcome_")
    results = {"off": [], "on": []}
    posted = [0]
    post_errors = [0]
    for trial in range(trials):
        # alternate which side runs first so slow positional drift
        # (page cache, CPU frequency) cancels instead of accumulating
        # against whichever side always runs second
        for mode in (("off", "on") if trial % 2 == 0 else ("on", "off")):
            engine = ServingEngine()
            engine.register("m", MatmulModel(dim),
                            np.ones((1, dim), np.float32), config=cfg)
            cap_dir = os.path.join(root, f"{mode}{trial}")
            tap = CaptureTap(CaptureConfig(directory=cap_dir,
                                           fraction=0.01))
            tap.enable("m")
            engine.set_capture(tap)
            store = LabelStore(cap_dir, rows_per_shard=256)
            engine.set_label_store(store)
            srv, _ = serve_http(engine, port=0)
            stop = threading.Event()
            labelers = []
            if mode == "on":
                def labeler(seed, port=srv.server_port):
                    conn = http.client.HTTPConnection("127.0.0.1", port)
                    j = 0
                    while not stop.is_set():
                        batch = json.dumps({"outcomes": [
                            {"trace_id": f"bench-{seed}-{j}-{k}",
                             "label": [float(j + k)],
                             "ts": 1700000000.0 + j}
                            for k in range(16)]})
                        try:
                            conn.request(
                                "POST", "/v1/models/m:outcome", batch,
                                {"Content-Type": "application/json"})
                            resp = conn.getresponse()
                            resp.read()
                            if resp.status == 200:
                                posted[0] += 16
                            else:
                                post_errors[0] += 1
                        except Exception:
                            post_errors[0] += 1
                            conn = http.client.HTTPConnection(
                                "127.0.0.1", port)
                        j += 1
                        # ~160 labels/s per labeler: ~7x the rate the
                        # joiner needs at 1% sampling — taxing ingestion
                        # at delivery line-rate would measure the GIL,
                        # not the plane
                        time.sleep(0.1)
                    conn.close()
                labelers = [threading.Thread(target=labeler, args=(i,),
                                             daemon=True)
                            for i in range(2)]
                for t in labelers:
                    t.start()
            for _ in range(20):
                engine.predict("m", np.ones((1, dim), np.float32))
            cell = run_load(engine, "m", clients, requests, dim)
            stop.set()
            for t in labelers:
                t.join(timeout=10)
            srv.shutdown()
            tap.close()
            store.close()
            engine.shutdown()
            results[mode].append(cell)
    best_off = max(results["off"], key=lambda c: c["req_per_s"])
    best_on = max(results["on"], key=lambda c: c["req_per_s"])
    overhead = (best_off["req_per_s"] - best_on["req_per_s"]) \
        / best_off["req_per_s"] * 100.0

    # -- join completeness: capture everything, label everything --------
    engine = ServingEngine()
    engine.register("m", MatmulModel(dim),
                    np.ones((1, dim), np.float32), config=cfg)
    cap_dir = os.path.join(root, "join")
    tap = CaptureTap(CaptureConfig(directory=cap_dir, fraction=1.0,
                                   rows_per_shard=64))
    tap.enable("m")
    engine.set_capture(tap)
    store = LabelStore(cap_dir, rows_per_shard=64)
    for i in range(200):
        engine.predict("m", np.ones((1, dim), np.float32))
    tap.flush()
    seg = tap.rotate("m")
    traces = [row["t"] for row in writers.iter_output_rows(seg)]
    store.ingest("m", [{"trace_id": t, "label": [float(i)],
                        "ts": 1700000000.0 + i}
                       for i, t in enumerate(traces)])
    store.rotate("m")
    desc = store.describe("m")
    tap.close()
    store.close()
    engine.shutdown()
    return {
        "clients": clients,
        "requests_per_client": requests,
        "trials": trials,
        "labelers": 2,
        "label_batch_size": 16,
        "ingest_off": best_off,
        "ingest_on": best_on,
        "labels_posted_http": posted[0],
        "label_post_errors": post_errors[0],
        "ingest_overhead_pct": round(overhead, 2),
        "all_off_rps": [c["req_per_s"] for c in results["off"]],
        "all_on_rps": [c["req_per_s"] for c in results["on"]],
        "join": {
            "captured_rows": desc["captured_rows"],
            "matched_rows": desc["matched_rows"],
            "labels_unique": desc["labels_unique"],
            "completeness": desc["completeness"],
        },
    }


def bench_cycle() -> dict:
    """One real closed-loop cycle on a tiny model: seed an incumbent,
    capture live traffic at fraction 1.0, then time
    rotate → retrain → canary promotion."""
    import optax

    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.flywheel import (
        CaptureConfig, CaptureTap, FlywheelController, FlywheelTrainer,
        RetrainConfig,
    )
    from analytics_zoo_tpu.ft import atomic
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense
    from analytics_zoo_tpu.serving import (
        BatcherConfig, RolloutConfig, ServingEngine,
    )

    root = tempfile.mkdtemp(prefix="fly_bench_cycle_")
    cap_root = os.path.join(root, "capture")
    ckpt_dir = os.path.join(root, "ckpts")
    in_dim, out_dim = 4, 2

    def build_est():
        return Estimator(Sequential([Dense(out_dim, input_shape=(in_dim,))]),
                         optax.sgd(0.05))

    rng = np.random.default_rng(0)
    est = build_est()
    est.set_checkpoint(ckpt_dir, keep_last=4, asynchronous=False)
    est.train(ArrayFeatureSet(
        rng.normal(size=(32, in_dim)).astype(np.float32),
        rng.normal(size=(32, out_dim)).astype(np.float32)),
        objectives.mean_squared_error, batch_size=8)

    class Lin:
        def __init__(self, w, b):
            self.w, self.b = w, b

        def do_predict(self, x):
            return np.asarray(x, np.float32) @ self.w + self.b

    def build_model(path):
        flat, _ = atomic.read_checkpoint(path)
        d = dict(flat)
        w = next(v for v in d.values() if getattr(v, "ndim", 0) == 2)
        b = next(v for v in d.values() if getattr(v, "ndim", 0) == 1)
        return Lin(np.asarray(w), np.asarray(b))

    engine = ServingEngine(rollout=RolloutConfig(
        ladder=(0.25, 1.0), min_requests=4, auto_evaluate=False))
    tap = CaptureTap(CaptureConfig(directory=cap_root, fraction=1.0,
                                   rows_per_shard=32, roll_interval_s=0.1,
                                   idle_poll_s=0.02))
    engine.set_capture(tap)
    trainer = FlywheelTrainer(build_est, objectives.mean_squared_error,
                              RetrainConfig(
                                  capture_dir=os.path.join(cap_root, "m"),
                                  checkpoint_dir=ckpt_dir, batch_size=8,
                                  checkpoint_every=4, min_rows=8))
    ctrl = FlywheelController(
        engine, "m", tap, trainer, build_model,
        example_input=np.ones((1, in_dim), np.float32),
        config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0))

    x_pool = rng.normal(size=(64, in_dim)).astype(np.float32)
    t_cap0 = time.perf_counter()
    for i in range(96):
        engine.predict("m", x_pool[i % 64][None, :])
    capture_s = time.perf_counter() - t_cap0

    errors = [0]

    def traffic():
        for i in range(8):
            try:
                engine.predict("m", x_pool[i % 64][None, :])
            except Exception:
                errors[0] += 1

    report = ctrl.run_cycle(traffic_fn=traffic, timeout_s=60)
    ctrl.close()
    tap.close()
    engine.shutdown()
    return {
        "outcome": report.outcome,
        "candidate_step": report.candidate_step,
        "consumed_segments": len(report.consumed_segments),
        "capture_96_requests_s": round(capture_s, 3),
        "cycle_s": round(report.duration_s, 3),
        "client_errors_during_rollout": errors[0],
    }


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="flywheel capture-overhead + cycle-latency bench")
    parser.add_argument("--clients", type=int, default=8)
    parser.add_argument("--requests", type=int, default=200,
                        help="requests per client per trial")
    parser.add_argument("--fraction", type=float, default=0.01)
    parser.add_argument("--trials", type=int, default=5,
                        help="best-of trials per side; single-core "
                             "hosts need >=5 for scheduler noise to "
                             "cancel")
    parser.add_argument("--skip-cycle", action="store_true",
                        help="capture-overhead phase only (CI smoke)")
    parser.add_argument("--outcomes", action="store_true",
                        help="also run the outcome-plane smoke: label "
                             "ingestion overhead under concurrent HTTP "
                             "POSTs + join completeness (ISSUE 19)")
    parser.add_argument("--out", default=None,
                        help="write BENCH_FLYWHEEL.json here")
    args = parser.parse_args(argv)

    overhead = bench_capture_overhead(args.clients, args.requests,
                                      args.fraction, args.trials)
    print(f"capture off: {overhead['capture_off']['req_per_s']} req/s   "
          f"on({args.fraction:.0%}): "
          f"{overhead['capture_on']['req_per_s']} req/s   "
          f"overhead: {overhead['overhead_pct']}%")
    doc = {
        "metric": "flywheel_capture_overhead_and_cycle_latency",
        "capture_overhead": overhead,
        "device": device_info(),
        "methodology": (
            "closed-loop clients against a numpy matmul servable through "
            "the ServingEngine, best-of-trials req/s capture-off vs "
            "capture-on; cycle phase runs one real serve->capture->"
            "retrain->canary-promotion loop on a tiny Dense model"),
    }
    if not args.skip_cycle:
        cycle = bench_cycle()
        print(f"cycle: {cycle['outcome']} in {cycle['cycle_s']}s "
              f"(candidate step {cycle['candidate_step']}, "
              f"{cycle['client_errors_during_rollout']} client errors)")
        doc["cycle"] = cycle
    if args.outcomes:
        outcomes = bench_outcomes(args.clients, args.requests,
                                  args.trials)
        print(f"outcome ingest off: "
              f"{outcomes['ingest_off']['req_per_s']} req/s   "
              f"on({outcomes['labels_posted_http']} labels posted): "
              f"{outcomes['ingest_on']['req_per_s']} req/s   "
              f"overhead: {outcomes['ingest_overhead_pct']}%")
        print(f"join: {outcomes['join']['matched_rows']}/"
              f"{outcomes['join']['captured_rows']} rows matched "
              f"(completeness {outcomes['join']['completeness']})")
        doc["outcomes"] = outcomes
    doc["acceptance"] = {
        "overhead_pct": overhead["overhead_pct"],
        "overhead_target_pct": 2.0,
        "overhead_ok": overhead["overhead_pct"] < 2.0,
    }
    if not args.skip_cycle:
        doc["acceptance"]["cycle_promoted"] = doc["cycle"][
            "outcome"] == "promoted"
    if args.outcomes:
        doc["acceptance"].update({
            "outcome_overhead_pct": outcomes["ingest_overhead_pct"],
            "outcome_overhead_ok":
                outcomes["ingest_overhead_pct"] < 2.0,
            "outcome_join_completeness":
                outcomes["join"]["completeness"],
            "outcome_join_ok":
                outcomes["join"]["completeness"] == 1.0,
        })
    if args.out:
        with open(args.out, "w") as f:
            json.dump(doc, f, indent=2)
            f.write("\n")
        print(f"wrote {args.out}")
    return doc


if __name__ == "__main__":
    main()
