"""Rollout bench: goodput through a live canary rollout — one healthy
canary auto-promoting through the full ladder, one chaos-broken canary
auto-rolling back. Emits BENCH_ROLLOUT.json.

    python scripts/rollout_bench.py [--service-ms 2] [--rps 400]
        [--out BENCH_ROLLOUT.json]

The model is a synthetic sleeper (exact capacity, hardware-independent),
traffic is open-loop at ``rps`` version-less requests/s, and the rollout
evaluator runs on its own thread exactly as in production. The claims
under test (docs/rollouts.md): a healthy canary reaches 100% with no
goodput dip beyond noise, and a canary that fails every request is
rolled back automatically with the client-visible error fraction bounded
by the ladder's early rungs — the blast radius the ladder exists to
bound. CPU-only by construction: the sleeper does no device work, so the
script pins ``JAX_PLATFORMS=cpu`` itself and its numbers say nothing about
a chip.
"""

from __future__ import annotations

import argparse
import concurrent.futures
import json
import os
import sys
import threading
import time

import numpy as np

# CPU-only by construction (see the module docstring): pinned before jax
# is imported, and inherited by every worker this script spawns.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402


class SleepModel:
    """Fixed service time per batch; the scale distinguishes versions."""

    def __init__(self, service_s: float, scale: float):
        self.service_s = service_s
        self.scale = scale

    def do_predict(self, x):
        time.sleep(self.service_s)
        return np.asarray(x, np.float32) * self.scale


def run_cell(chaos_canary: bool, service_ms: float, rps: float,
             max_s: float = 20.0):
    """One cell: steady load, register a canary, run until the rollout
    resolves; returns goodput windows + outcome + timings."""
    from analytics_zoo_tpu.ft import chaos
    from analytics_zoo_tpu.serving import (
        BatcherConfig,
        ResilienceConfig,
        RolloutConfig,
        ServingEngine,
    )

    service_s = service_ms / 1e3
    engine = ServingEngine(
        resilience=ResilienceConfig(admission=False, watchdog=False),
        rollout=RolloutConfig(ladder=(0.05, 0.25, 1.0), min_requests=25,
                              evaluate_interval_s=0.05))
    cfg = BatcherConfig(max_batch_size=16, max_wait_ms=2.0,
                        max_queue_size=4096)
    x = np.ones((1, 4), np.float32)
    engine.register("bench", SleepModel(service_s, 2.0),
                    example_input=x, config=cfg, version="1")

    lock = threading.Lock()
    ok_times, err_times = [], []
    futures = []

    def on_done(f):
        t = time.monotonic()
        with lock:
            (ok_times if f.exception() is None else err_times).append(t)

    def pump(stop):
        tick_s = 0.005
        per_tick = max(1, round(rps * tick_s))
        next_tick = time.monotonic()
        while not stop():
            for _ in range(per_tick):
                try:
                    f = engine.predict_async("bench", x)
                except Exception:  # noqa: BLE001 — breaker/queue reject
                    with lock:
                        err_times.append(time.monotonic())
                else:
                    f.add_done_callback(on_done)
                    futures.append(f)
            next_tick += tick_s
            pause = next_tick - time.monotonic()
            if pause > 0:
                time.sleep(pause)

    # steady-state baseline on the incumbent alone
    t_start = time.monotonic()
    pump(lambda: time.monotonic() >= t_start + 1.0)
    baseline_ok = len(ok_times)

    # the canary lands (auto-begins the rollout); chaos breaks it or not
    if chaos_canary:
        chaos.arm_serving("canary_errors", tag="bench@2")
    t_canary = time.monotonic()
    engine.register("bench", SleepModel(service_s, 3.0),
                    example_input=x, config=cfg, version="2")
    ctrl = engine.rollout_controller()
    deadline = t_canary + max_s
    pump(lambda: (ctrl.active("bench") is None
                  or time.monotonic() >= deadline))
    state = ctrl.describe("bench")
    t_resolved = time.monotonic()
    # tail: 0.5 s of post-resolution traffic proves the survivor serves
    pump(lambda: time.monotonic() >= t_resolved + 0.5)
    concurrent.futures.wait(futures, timeout=30)
    chaos.reset()

    with lock:
        oks = sorted(ok_times)
        errs = sorted(err_times)
    rollout_ok = sum(1 for t in oks if t_canary <= t < t_resolved)
    rollout_err = sum(1 for t in errs if t_canary <= t < t_resolved)
    tail_err = sum(1 for t in errs if t >= t_resolved)
    # windowed goodput across the rollout: the dip is min window / the
    # pre-canary baseline rate
    win_s = 0.25
    windows = []
    t = t_canary
    while t < t_resolved:
        windows.append(sum(1 for u in oks if t <= u < t + win_s) / win_s)
        t += win_s
    baseline_rps = baseline_ok / 1.0
    dip = (min(windows) / baseline_rps if windows and baseline_rps else
           None)
    engine.shutdown()
    return {
        "chaos_canary": chaos_canary,
        "outcome": state["outcome"] if state else None,
        "reason": state["reason"] if state else None,
        "time_to_resolve_s": round(t_resolved - t_canary, 3),
        "baseline_goodput_rps": round(baseline_rps, 1),
        "min_window_goodput_rps": (round(min(windows), 1) if windows
                                   else None),
        "goodput_dip_ratio": round(dip, 3) if dip is not None else None,
        "rollout_ok": rollout_ok,
        "rollout_errors": rollout_err,
        "rollout_error_fraction": (
            round(rollout_err / max(1, rollout_ok + rollout_err), 4)),
        "post_resolution_errors": tail_err,
    }


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--service-ms", type=float, default=2.0)
    p.add_argument("--rps", type=float, default=400.0)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_ROLLOUT.json"))
    args = p.parse_args(argv)

    cells = []
    for chaos_canary in (False, True):
        cell = run_cell(chaos_canary, args.service_ms, args.rps)
        print(json.dumps(cell))
        cells.append(cell)
    healthy, broken = cells

    record = {
        "metric": "serving_canary_rollout",
        "ladder": [0.05, 0.25, 1.0],
        "service_ms": args.service_ms,
        "offered_rps": args.rps,
        "cells": cells,
        # the acceptance bar: healthy promotes, broken rolls back, the
        # broken canary's client-visible error fraction stays within the
        # ladder's early rungs (blast radius), nothing fails after
        # resolution
        "acceptance": {
            "healthy_promoted": healthy["outcome"] == "promoted",
            "broken_rolled_back": broken["outcome"] == "rolled_back",
            "time_to_rollback_s": broken["time_to_resolve_s"],
            "broken_error_fraction": broken["rollout_error_fraction"],
            "error_fraction_within_ladder":
                broken["rollout_error_fraction"] <= 0.30,
            "clean_after_resolution":
                healthy["post_resolution_errors"] == 0
                and broken["post_resolution_errors"] == 0,
        },
        "device": device_info(),
    }
    print(json.dumps(record["acceptance"]))
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return record


if __name__ == "__main__":
    main()
