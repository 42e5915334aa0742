"""Ops-plane overhead bench: what the always-on flight recorder costs.

The ISSUE 17 flight recorder records every request — a ring append and
a handful of timestamp stamps on the hot path — so its cost must be
measured, not assumed. This bench drives the same concurrent load
through one ServingEngine twice:

- **recorder on** — the stock path: every request enters the bounded
  ring via :meth:`FlightRecorder.begin`, gets its seven lifecycle
  stamps, and closes via :meth:`FlightRecorder.finish` (which checks
  the latency threshold and bumps the per-outcome counter);
- **recorder bypassed** — ``engine.flight`` swapped for a null recorder
  whose ``begin`` hands back a bare :class:`RequestRecord` that never
  touches the ring, lock, or counters (the record object itself stays,
  so the batcher's stamp writes — plain attribute stores — are charged
  to the baseline; they are the floor the design cannot go below).

Each side runs ``--trials`` times interleaved (on/off/on/off…, so drift
hits both equally) and the **median** requests/sec is compared:
``overhead_pct = (off - on) / off * 100``. The budget the ops plane
ships under is **< 2%** (docs/observability.md); CI gates looser (see
``--gate-pct``) because shared runners are noisy, but the committed
BENCH_OBS.json number is the honest one. Exit is 1 when the gate
fails, so the tier-1 "Ops plane" step turns red instead of drifting.

    python scripts/obs_bench.py [--clients 8] [--requests 40]
        [--trials 3] [--gate-pct 2.0] [--out BENCH_OBS.json]
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import threading
import time

import numpy as np

_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(_HERE, ".."))
sys.path.insert(0, _HERE)  # sibling import: serving_bench's build_model

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402
from analytics_zoo_tpu.common.flight_recorder import (  # noqa: E402
    RequestRecord,
)


class _NullRecorder:
    """begin/finish/trigger that never touch the ring — the bypassed
    baseline. Returns real records so the serving path is unchanged."""

    def begin(self, model, trace_id=None, kind="predict", tenant=None):
        return RequestRecord(model, trace_id=trace_id, kind=kind,
                             tenant=tenant)

    def finish(self, rec, outcome, error=None):
        pass

    def trigger(self, reason):
        return None


def build_engine(clients: int, feature_dim: int = 16):
    """One engine + registered bench model, the serving_bench shape."""
    from serving_bench import build_model  # same demo trunk

    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    inf = build_model(feature_dim)
    engine = ServingEngine()
    cfg = BatcherConfig(max_batch_size=32, max_wait_ms=2.0,
                        max_queue_size=max(256, clients * 4))
    engine.register("bench", inf,
                    example_input=np.zeros((1, feature_dim), np.float32),
                    config=cfg)
    return engine


def drive(engine, clients: int, requests: int,
          feature_dim: int = 16) -> float:
    """``clients`` threads of ``requests`` single-row predicts each;
    returns requests/sec (single-row so req/s == rows/s — the recorder
    cost is per *request*, which is what the gate protects)."""
    ok = [0]
    lock = threading.Lock()

    def client(seed: int):
        rng = np.random.default_rng(seed)
        mine = 0
        for _ in range(requests):
            x = rng.normal(size=(1, feature_dim)).astype(np.float32)
            try:
                engine.predict("bench", x)
            except Exception:  # noqa: BLE001 — count sheds, keep driving
                continue
            mine += 1
        with lock:
            ok[0] += mine

    threads = [threading.Thread(target=client, args=(s,))
               for s in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return ok[0] / wall if wall > 0 else 0.0


def run_bench(clients: int, requests: int, trials: int,
              feature_dim: int = 16) -> dict:
    """Interleaved on/off trials over one engine; the JSON record."""
    engine = build_engine(clients, feature_dim)
    real = engine.flight
    null = _NullRecorder()
    try:
        # one throwaway pass compiles the bucket executables so neither
        # side pays XLA warmup
        drive(engine, clients, max(4, requests // 4), feature_dim)
        rps_on, rps_off = [], []
        for _ in range(trials):
            engine.flight = real
            rps_on.append(drive(engine, clients, requests, feature_dim))
            engine.flight = null
            rps_off.append(drive(engine, clients, requests, feature_dim))
    finally:
        engine.flight = real
        engine.shutdown()
    on = statistics.median(rps_on)
    off = statistics.median(rps_off)
    overhead = (off - on) / off * 100.0 if off > 0 else 0.0
    return {
        "metric": "ops_plane_overhead",
        "clients": clients,
        "requests_per_client": requests,
        "trials": trials,
        "requests_per_sec_recorder_on": round(on, 1),
        "requests_per_sec_recorder_off": round(off, 1),
        "trials_on": [round(r, 1) for r in rps_on],
        "trials_off": [round(r, 1) for r in rps_off],
        "overhead_pct": round(overhead, 2),
        "budget_pct": 2.0,
        "device": device_info(),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--clients", type=int, default=8)
    p.add_argument("--requests", type=int, default=40,
                   help="requests per client per trial")
    p.add_argument("--trials", type=int, default=3,
                   help="interleaved on/off trial pairs; medians compared")
    p.add_argument("--gate-pct", type=float, default=None,
                   help="exit 1 when overhead_pct exceeds this (CI uses "
                        "a looser value than the committed 2%% budget — "
                        "shared runners are noisy)")
    p.add_argument("--out", default=None,
                   help="also write the record to this JSON file")
    args = p.parse_args(argv)
    record = run_bench(args.clients, args.requests, args.trials)
    print(json.dumps(record))
    if args.out:
        with open(args.out, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
    if args.gate_pct is not None and record["overhead_pct"] > args.gate_pct:
        print(f"FAIL: recorder overhead {record['overhead_pct']}% > "
              f"gate {args.gate_pct}%", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
