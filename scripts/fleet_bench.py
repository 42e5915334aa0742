"""Fleet bench: multi-host scaling, whole-host-death resilience and
cooperative-cache effectiveness through the fleet fabric (ISSUE 18).
Emits BENCH_FLEET.json.

    python scripts/fleet_bench.py [--duration 3.0] [--service-ms 20]
        [--max-batch 4] [--workers 2] [--out BENCH_FLEET.json] [--smoke]

Three phases, all against simulated hosts (in-process
:class:`~analytics_zoo_tpu.serving.fabric.door.FleetDoor` instances,
each prefork-spawning REAL worker subprocesses from
scripts/_frontdoor_bench_spec.py — the same GIL-releasing sleeper model
as the front-door bench, so per-worker capacity is exact and
scheduler-bound, and the curves measure the fabric, not the hardware):

1. **Scaling** — closed-loop sticky-keyed clients against 1 host vs 2
   hosts (same workers per host; keys partition over the roster, so the
   2-host cell pays real cross-host forwards for ~half its traffic).
   The bar: >= 1.7x req/s.
2. **Whole-host kill** — every client enters through host a; half the
   keys are owned by host b. At ~40% of the run host b dies whole
   (SIGKILL to all of its workers, HTTP plane down, no heartbeat
   leave). The bar: zero non-quota client errors, and host a absorbing
   the dead host's sticky keys.
3. **Cooperative cache** — distinct payloads warmed through host a
   only, then requested through host b. The bar: host b answers from
   the peer cache (hit rate ~1.0) without ever computing them.

``--smoke`` shortens every cell for CI; the acceptance record is
printed last either way and the "Fleet fabric" tier-1 step gates on
``kill_non_quota_client_errors == 0``. See docs/fleet.md.

CPU-only by construction: the sleeper does no device work, so the script
pins ``JAX_PLATFORMS=cpu`` itself (every worker inherits the pin) and its
numbers say nothing about a chip.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

import numpy as np

# CPU-only by construction (see the module docstring): pinned before jax
# is imported, and inherited by every worker this script spawns.
os.environ["JAX_PLATFORMS"] = "cpu"

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))


SPEC = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "_frontdoor_bench_spec.py") + ":build_engine"
PREDICT = "/v1/models/bench:predict"


def _boot_fleet(host_ids, workers, service_ms, max_batch, *,
                result_cache=False):
    """Boot one FleetDoor per host id against a fresh shared fleet dir;
    returns (doors, fleet_dir)."""
    from analytics_zoo_tpu.serving.fabric import FleetConfig, FleetDoor

    fleet_dir = tempfile.mkdtemp(prefix="azoo-fleet-bench-")
    env = {"AZOO_BENCH_SERVICE_MS": str(service_ms),
           "AZOO_BENCH_MAX_BATCH": str(max_batch)}
    if result_cache:
        env["AZOO_BENCH_RESULT_CACHE"] = "1"
    doors = [FleetDoor(FleetConfig(
        spec=SPEC, fleet_dir=fleet_dir, host_id=hid, workers=workers,
        heartbeat_interval_s=0.1, worker_boot_timeout_s=120,
        worker_env=dict(env))).start() for hid in host_ids]
    deadline = time.monotonic() + 15
    want = set(host_ids)
    while time.monotonic() < deadline:
        if all(set(d.membership.poll().live) == want for d in doors):
            return doors, fleet_dir
        time.sleep(0.05)
    raise RuntimeError(f"fleet never converged to {sorted(want)}")


def _teardown(doors, fleet_dir):
    for d in doors:
        try:
            d.shutdown()
        except Exception:  # noqa: BLE001 — bench teardown is best-effort
            pass
    shutil.rmtree(fleet_dir, ignore_errors=True)


def _keys_owned_by(owner, roster, n, prefix):
    """``n`` route keys whose roster interval belongs to ``owner``."""
    from analytics_zoo_tpu.serving.fabric import fleet_pick

    keys, i = [], 0
    while len(keys) < n:
        key = f"{prefix}-{i}"
        if fleet_pick(roster, roster, roster[0], key) == owner:
            keys.append(key)
        i += 1
        if i > 100_000:
            raise RuntimeError(f"cannot find {n} keys for {owner}")
    return keys


def run_load_cell(doors, duration_s, clients_per_worker, workers, *,
                  kill_door=None, entry_doors=None):
    """Closed-loop sticky-keyed clients for ``duration_s``. Each client
    owns one route key and enters through one door (round-robin over
    ``entry_doors`` or all doors). With ``kill_door``, that host dies
    whole at ~40% of the run. Returns the cell record."""
    entries = entry_doors or doors
    n_clients = clients_per_worker * workers * len(doors)
    counts = {"ok": 0, "quota_429": 0, "backpressure_429": 0,
              "retryable_503": 0, "deadline_504": 0, "other_errors": 0}
    served_by = {}          # key -> last X-Zoo-Host that answered it
    latencies = []
    lock = threading.Lock()
    stop = threading.Event()
    body = json.dumps({"instances": [[1.0, 2.0, 3.0, 4.0]]}).encode()

    def client(idx):
        base = entries[idx % len(entries)].url
        key = f"bench-key-{idx}"
        req_headers = {"Content-Type": "application/json",
                       "X-Zoo-Route-Key": key}
        while not stop.is_set():
            t0 = time.monotonic()
            try:
                req = urllib.request.Request(base + PREDICT, data=body,
                                             headers=req_headers)
                with urllib.request.urlopen(req, timeout=60) as resp:
                    resp.read()
                    host = resp.headers.get("X-Zoo-Host")
                with lock:
                    counts["ok"] += 1
                    latencies.append(time.monotonic() - t0)
                    served_by[key] = host
            except urllib.error.HTTPError as e:
                code = {429: "backpressure_429", 503: "retryable_503",
                        504: "deadline_504"}.get(e.code, "other_errors")
                with lock:
                    counts[code] += 1
            except Exception:  # noqa: BLE001 — a bench records, not raises
                with lock:
                    counts["other_errors"] += 1

    threads = [threading.Thread(target=client, args=(i,))
               for i in range(n_clients)]
    t_start = time.monotonic()
    for t in threads:
        t.start()
    try:
        if kill_door is not None:
            time.sleep(duration_s * 0.4)
            kill_door.simulate_host_kill()
            time.sleep(duration_s * 0.6)
        else:
            time.sleep(duration_s)
    finally:
        stop.set()
        for t in threads:
            t.join()
        wall = time.monotonic() - t_start

    lat = np.asarray(sorted(latencies), np.float64)
    return {
        "hosts": len(doors),
        "workers_per_host": workers,
        "clients": n_clients,
        "killed_host": kill_door.host_id if kill_door else None,
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
        "_served_by": served_by,
    }


def run_scaling(args):
    """Phase 1: the same sticky closed-loop workload against 1 host and
    against 2; the 2-host cell forwards ~half its traffic."""
    cells = []
    for host_ids in (["a"], ["a", "b"]):
        doors, fdir = _boot_fleet(host_ids, args.workers,
                                  args.service_ms, args.max_batch)
        try:
            cell = run_load_cell(doors, args.duration,
                                 args.clients_per_worker, args.workers)
        finally:
            _teardown(doors, fdir)
        del cell["_served_by"]
        print(json.dumps(cell))
        cells.append(cell)
    return cells


def run_kill(args):
    """Phase 2: whole-host SIGKILL mid-load, all clients entering
    through the survivor."""
    doors, fdir = _boot_fleet(["a", "b"], args.workers,
                              args.service_ms, args.max_batch)
    a, b = doors
    try:
        cell = run_load_cell(doors, args.duration * 2,
                             args.clients_per_worker, args.workers,
                             kill_door=b, entry_doors=[a])
        served_by = cell.pop("_served_by")
        # b is dead and every key's LAST answer must come from a —
        # the survivor absorbed the dead host's intervals
        cell["keys_total"] = len(served_by)
        cell["keys_absorbed_by_survivor"] = sum(
            1 for h in served_by.values() if h == "a")
        cell["survivor_absorbed_all_keys"] = (
            cell["keys_total"] > 0
            and cell["keys_absorbed_by_survivor"] == cell["keys_total"])
        view = a.membership.poll()
        cell["survivor_view"] = {"live": sorted(view.live),
                                 "roster": list(view.roster)}
    finally:
        _teardown(doors, fdir)
    print(json.dumps(cell))
    return cell


def run_coop_cache(args):
    """Phase 3: warm N distinct payloads through host a, request them
    through host b — count b's peer-cache hits."""
    doors, fdir = _boot_fleet(["a", "b"], args.workers,
                              args.service_ms, args.max_batch,
                              result_cache=True)
    a, b = doors
    n = args.coop_keys
    hits = misses = 0
    warm_s = serve_s = 0.0
    try:
        bodies = [json.dumps(
            {"instances": [[float(i), 1.0, 2.0, 3.0]]}).encode()
            for i in range(n)]

        def post(door, payload):
            req = urllib.request.Request(
                door.url + PREDICT, data=payload,
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.headers.get("X-Zoo-Cache"), resp.read()

        t0 = time.monotonic()
        warmed = [post(a, p)[1] for p in bodies]
        warm_s = time.monotonic() - t0
        t0 = time.monotonic()
        for payload, expect in zip(bodies, warmed):
            status, data = post(b, payload)
            if status == "hit" and data == expect:
                hits += 1
            else:
                misses += 1
        serve_s = time.monotonic() - t0
    finally:
        _teardown(doors, fdir)
    cell = {
        "keys_warmed_on_a": n,
        "peer_hits_on_b": hits,
        "peer_misses_on_b": misses,
        "hit_rate_on_b": round(hits / n, 3) if n else None,
        "warm_wall_s": round(warm_s, 3),
        "serve_wall_s": round(serve_s, 3),
    }
    print(json.dumps(cell))
    return cell


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--duration", type=float, default=3.0,
                   help="seconds per load cell (the kill cell runs 2x)")
    # defaults keep per-host capacity (workers * max_batch / service)
    # well under what one Python process can proxy: the doors and the
    # closed-loop clients share this process's GIL, and the cell must
    # measure fleet capacity, not interpreter contention
    p.add_argument("--service-ms", type=float, default=40.0)
    p.add_argument("--max-batch", type=int, default=2)
    p.add_argument("--workers", type=int, default=2,
                   help="workers per simulated host")
    p.add_argument("--clients-per-worker", type=int, default=6)
    p.add_argument("--coop-keys", type=int, default=32)
    p.add_argument("--smoke", action="store_true",
                   help="short cells for CI (the acceptance record "
                        "still gates)")
    p.add_argument("--out", default="BENCH_FLEET.json")
    args = p.parse_args(argv)
    if args.smoke:
        args.duration = min(args.duration, 1.5)
        args.coop_keys = min(args.coop_keys, 12)

    scale_cells = run_scaling(args)
    kill_cell = run_kill(args)
    coop_cell = run_coop_cache(args)

    one, two = scale_cells[0]["req_per_s"], scale_cells[1]["req_per_s"]
    scaling_x = round(two / one, 2) if one else None
    record = {
        "bench": "fleet",
        "mode": "smoke" if args.smoke else "full",
        "claim": ("2 simulated hosts scale near-linearly over 1 with "
                  "sticky cross-host routing; a whole-host SIGKILL "
                  "costs zero non-quota client errors (survivor "
                  "absorbs the dead intervals); results warmed on one "
                  "host are peer-cache hits on the other"),
        "host_cores": os.cpu_count(),
        "params": {"duration_s": args.duration,
                   "service_ms": args.service_ms,
                   "max_batch": args.max_batch,
                   "workers_per_host": args.workers,
                   "clients_per_worker": args.clients_per_worker},
        "scaling": scale_cells,
        "whole_host_kill": kill_cell,
        "cooperative_cache": coop_cell,
        "acceptance": {
            "scaling_2host_over_1host_x": scaling_x,
            "scaling_bar_1_7x": (scaling_x is not None
                                 and scaling_x >= 1.7),
            "kill_non_quota_client_errors":
                kill_cell["non_quota_client_errors"],
            "survivor_absorbed_all_keys":
                kill_cell["survivor_absorbed_all_keys"],
            "coop_cache_hit_rate_on_b": coop_cell["hit_rate_on_b"],
        },
    }
    with open(args.out, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    print(json.dumps(record["acceptance"]))
    return 0


if __name__ == "__main__":
    sys.exit(main())
