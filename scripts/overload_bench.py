"""Overload bench: goodput and accepted-latency p99 under 1x/2x/4x
offered load, with admission-control shedding ON vs OFF, through the
ServingEngine. Emits BENCH_OVERLOAD.json.

    python scripts/overload_bench.py [--duration 2.0] [--deadline-ms 150]
        [--service-ms 10] [--max-batch 8] [--out BENCH_OVERLOAD.json]

The model is a synthetic sleeper (``service_ms`` per batch regardless of
batch size), so capacity is exact — ``max_batch / service_ms`` rows/s —
and the cells measure the resilience layer, not the hardware. The claim
under test (docs/resilience.md): past saturation, shedding the unmeetable
requests at submit keeps goodput at capacity and accepted-request latency
inside the deadline, while the no-shedding baseline queues everything and
collapses into 504s. CPU-only by construction: the sleeper does no device
work, so the script pins ``JAX_PLATFORMS=cpu`` itself (its front-door
workers inherit the pin) and its numbers say nothing about a chip.

Front-door mode (ISSUE 14) — ``--workers N`` — benches the horizontal
tier instead: closed-loop HTTP clients through a
:class:`~analytics_zoo_tpu.serving.frontdoor.FrontDoor` over 1, 2, ...,
N preforked sleeper workers (same synthetic model, booted from
scripts/_frontdoor_bench_spec.py), plus one mid-load worker-SIGKILL
cell. Emits BENCH_FRONTDOOR.json: the req/s scaling curve and the
kill-cell error classification (the bar: ~linear scaling, zero
non-quota / non-retryable client errors while a worker dies and is
respawned). Because the sleeper releases the GIL, per-worker capacity
is scheduler-bound — the scaling curve measures the front door's
fan-out and stays meaningful on a small host; ``host_cores`` is
recorded so readers can judge the CPU-bound generalization.
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
    """Fixed service time per batch — exact, hardware-independent
    capacity of max_batch/service_s rows per second."""

    def __init__(self, service_s: float):
        self.service_s = service_s

    def do_predict(self, x):
        time.sleep(self.service_s)
        return np.asarray(x, np.float32) * 2.0


def run_cell(load_mult: float, shedding: bool, duration_s: float,
             deadline_ms: float, service_ms: float, max_batch: int):
    """One bench cell: open-loop 1-row submits at ``load_mult`` x capacity
    for ``duration_s``; returns the cell record."""
    from analytics_zoo_tpu.serving import (
        BatcherConfig,
        DeadlineExceededError,
        QueueFullError,
        ResilienceConfig,
        ServingEngine,
        ShedError,
    )

    service_s = service_ms / 1e3
    capacity_rps = max_batch / service_s
    offered_rps = capacity_rps * load_mult
    engine = ServingEngine(resilience=ResilienceConfig(
        admission=shedding, breaker=None, watchdog=False))
    engine.register(
        "bench", SleepModel(service_s),
        example_input=np.zeros((1, 4), np.float32),
        config=BatcherConfig(max_batch_size=max_batch, max_wait_ms=2.0,
                             max_queue_size=1024, timeout_ms=deadline_ms))

    results = {"ok": 0, "shed": 0, "full": 0, "timeout": 0, "other": 0}
    latencies = []
    lock = threading.Lock()
    x = np.ones((1, 4), np.float32)
    futures = []

    def on_done(t0):
        def cb(f):
            dt = time.monotonic() - t0
            exc = f.exception()
            with lock:
                if exc is None:
                    results["ok"] += 1
                    latencies.append(dt)
                elif isinstance(exc, DeadlineExceededError):
                    results["timeout"] += 1
                else:
                    results["other"] += 1
        return cb

    tick_s = 0.005
    per_tick = max(1, round(offered_rps * tick_s))
    submitted = 0
    t_start = time.monotonic()
    next_tick = t_start
    while time.monotonic() - t_start < duration_s:
        for _ in range(per_tick):
            t0 = time.monotonic()
            try:
                f = engine.predict_async("bench", x)
            except ShedError:
                with lock:
                    results["shed"] += 1
            except QueueFullError:
                with lock:
                    results["full"] += 1
            else:
                f.add_done_callback(on_done(t0))
                futures.append(f)
            submitted += 1
        next_tick += tick_s
        pause = next_tick - time.monotonic()
        if pause > 0:
            time.sleep(pause)
    concurrent.futures.wait(futures, timeout=60)
    wall = time.monotonic() - t_start
    engine.shutdown()

    lat = np.asarray(sorted(latencies), np.float64)
    p99_ms = (round(float(lat[max(0, int(lat.size * 0.99) - 1)]) * 1e3, 2)
              if lat.size else None)
    return {
        "load_mult": load_mult,
        "shedding": shedding,
        "offered_rps": round(submitted / wall, 1),
        "goodput_rps": round(results["ok"] / wall, 1),
        "accepted_p99_ms": p99_ms,
        "ok": results["ok"],
        "shed_429": results["shed"],
        "queue_full_429": results["full"],
        "deadline_504": results["timeout"],
        "other_errors": results["other"],
    }


def run_frontdoor_cell(workers: int, duration_s: float, service_ms: float,
                       max_batch: int, clients_per_worker: int = 6,
                       kill_mid_run: bool = False):
    """One front-door cell: ``clients_per_worker * workers`` closed-loop
    HTTP clients for ``duration_s``; optionally SIGKILL one worker at
    ~40% of the run. Closed-loop clients adapt to capacity, so the cell
    reports achieved req/s (the scaling curve) rather than shed rates."""
    import signal
    import urllib.error
    import urllib.request

    from analytics_zoo_tpu.serving.frontdoor import FrontDoor, FrontDoorConfig

    spec = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "_frontdoor_bench_spec.py") + ":build_engine"
    fd = FrontDoor(FrontDoorConfig(
        spec=spec, workers=workers, heartbeat_interval_s=0.1,
        worker_boot_timeout_s=120,
        worker_env={"AZOO_BENCH_SERVICE_MS": str(service_ms),
                    "AZOO_BENCH_MAX_BATCH": str(max_batch)})).start()
    counts = {"ok": 0, "quota_429": 0, "backpressure_429": 0,
              "retryable_503": 0, "deadline_504": 0, "other_errors": 0}
    latencies = []
    lock = threading.Lock()
    stop = threading.Event()
    body = json.dumps({"instances": [[1.0, 2.0, 3.0, 4.0]]}).encode()
    url = fd.url + "/v1/models/bench:predict"

    def client():
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                req = urllib.request.Request(
                    url, data=body,
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                with lock:
                    counts["ok"] += 1
                    latencies.append(time.monotonic() - t0)
            except urllib.error.HTTPError as e:
                key = {429: "backpressure_429", 503: "retryable_503",
                       504: "deadline_504"}.get(e.code, "other_errors")
                with lock:
                    counts[key] += 1
            except Exception:  # noqa: BLE001 — a bench records, not raises
                with lock:
                    counts["other_errors"] += 1

    threads = [threading.Thread(target=client)
               for _ in range(clients_per_worker * workers)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    killed_pid = None
    try:
        if kill_mid_run:
            time.sleep(duration_s * 0.4)
            killed_pid = fd.worker_pids()["0"]
            os.kill(killed_pid, signal.SIGKILL)
            time.sleep(duration_s * 0.6)
        else:
            time.sleep(duration_s)
    finally:
        stop.set()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start
        respawned = (kill_mid_run
                     and fd.worker_pids().get("0") not in (None, killed_pid)
                     and fd.health()["live_workers"] == workers)
        fd.shutdown()

    lat = np.asarray(sorted(latencies), np.float64)
    cell = {
        "workers": workers,
        "clients": clients_per_worker * workers,
        "kill_mid_run": kill_mid_run,
        "req_per_s": round(counts["ok"] / wall, 1),
        "latency_p50_ms": (round(float(np.percentile(lat, 50)) * 1e3, 2)
                           if lat.size else None),
        "latency_p99_ms": (round(float(np.percentile(lat, 99)) * 1e3, 2)
                           if lat.size else None),
        **counts,
        "non_quota_client_errors": (counts["backpressure_429"]
                                    + counts["retryable_503"]
                                    + counts["deadline_504"]
                                    + counts["other_errors"]),
    }
    if kill_mid_run:
        cell["killed_pid"] = killed_pid
        cell["worker_respawned_and_rejoined"] = respawned
    return cell


def run_frontdoor_suite(args):
    """The ``--workers`` mode: scaling ladder 1, 2, ..., N plus a
    mid-load SIGKILL cell; writes BENCH_FRONTDOOR.json."""
    ladder = []
    n = 1
    while n < args.workers:
        ladder.append(n)
        n *= 2
    ladder.append(args.workers)

    cells = []
    for n in ladder:
        cell = run_frontdoor_cell(n, args.duration, args.fd_service_ms,
                                  args.fd_max_batch)
        print(json.dumps(cell))
        cells.append(cell)
    kill_cell = run_frontdoor_cell(min(2, args.workers), args.duration,
                                   args.fd_service_ms, args.fd_max_batch,
                                   kill_mid_run=True)
    print(json.dumps(kill_cell))

    by_n = {c["workers"]: c["req_per_s"] for c in cells}
    base = by_n.get(1) or 1.0
    record = {
        "metric": "frontdoor_horizontal_scaling",
        "per_worker_capacity_rps": round(
            args.fd_max_batch / (args.fd_service_ms / 1e3), 1),
        "service_ms": args.fd_service_ms,
        "max_batch_size": args.fd_max_batch,
        "duration_s": args.duration,
        "host_cores": os.cpu_count(),
        "methodology": (
            "closed-loop HTTP clients (6 per worker) against a preforked "
            "front door; the sleeper model releases the GIL during its "
            "fixed service time, so per-worker capacity is scheduler-"
            "bound and the scaling curve isolates the fan-out layer "
            "rather than host core count"),
        "cells": cells,
        "kill_cell": kill_cell,
        "acceptance": {
            "scaling_1_to_2": (round(by_n[2] / base, 2)
                               if 2 in by_n else None),
            "scaling_1_to_4": (round(by_n[4] / base, 2)
                               if 4 in by_n else None),
            "kill_non_quota_client_errors":
                kill_cell["non_quota_client_errors"],
            "kill_worker_respawned":
                kill_cell.get("worker_respawned_and_rejoined", False),
        },
        "device": device_info(),
    }
    print(json.dumps(record["acceptance"]))
    with open(args.out_frontdoor, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return record


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--duration", type=float, default=2.0,
                   help="seconds of offered load per cell")
    p.add_argument("--deadline-ms", type=float, default=150.0)
    p.add_argument("--service-ms", type=float, default=10.0,
                   help="synthetic per-batch service time")
    p.add_argument("--max-batch", type=int, default=8)
    p.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_OVERLOAD.json"))
    p.add_argument("--workers", type=int, default=0,
                   help="front-door mode: bench the horizontal tier over "
                        "1, 2, ..., N preforked workers plus a mid-load "
                        "worker-SIGKILL cell (0 = classic overload bench)")
    p.add_argument("--fd-service-ms", type=float, default=50.0,
                   help="front-door mode: sleeper service time per batch")
    p.add_argument("--fd-max-batch", type=int, default=2,
                   help="front-door mode: worker max batch size")
    p.add_argument("--out-frontdoor", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_FRONTDOOR.json"))
    args = p.parse_args(argv)

    if args.workers > 0:
        return run_frontdoor_suite(args)

    cells = []
    for load_mult in (1.0, 2.0, 4.0):
        for shedding in (True, False):
            cell = run_cell(load_mult, shedding, args.duration,
                            args.deadline_ms, args.service_ms,
                            args.max_batch)
            print(json.dumps(cell))
            cells.append(cell)

    def cell_at(mult, shedding):
        return next(c for c in cells
                    if c["load_mult"] == mult and c["shedding"] == shedding)

    on2, off2 = cell_at(2.0, True), cell_at(2.0, False)
    record = {
        "metric": "serving_overload_shedding",
        "capacity_rps": round(args.max_batch / (args.service_ms / 1e3), 1),
        "deadline_ms": args.deadline_ms,
        "service_ms": args.service_ms,
        "max_batch_size": args.max_batch,
        "duration_s": args.duration,
        "cells": cells,
        # the acceptance bar: at 2x load, shedding must not cost goodput
        # and accepted requests must hold their deadline
        "acceptance": {
            "shedding_goodput_2x": on2["goodput_rps"],
            "baseline_goodput_2x": off2["goodput_rps"],
            "shedding_goodput_ge_baseline":
                on2["goodput_rps"] >= off2["goodput_rps"],
            "accepted_p99_ms_2x": on2["accepted_p99_ms"],
            "accepted_p99_le_deadline":
                (on2["accepted_p99_ms"] is not None
                 and on2["accepted_p99_ms"] <= args.deadline_ms),
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
