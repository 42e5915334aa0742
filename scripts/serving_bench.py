"""Online-serving load bench: N concurrent synthetic clients through the
ServingEngine; reports throughput, latency percentiles, batch-fill ratio
and the executable-cache counters, and emits BENCH_SERVING.json alongside
the BENCH_*.json trajectory records.

    python scripts/serving_bench.py [--clients 16] [--requests 50]
        [--max-batch 32] [--max-wait-ms 4] [--out BENCH_SERVING.json]

Mesh-parallel mode (``--mesh data=8``) benches the sharded inference
path instead: bitwise parity vs the single-device executables for every
bucket, pipelined throughput for both paths, and a warm-restart compile
count under the mesh — written to BENCH_SHARDED.json. On CPU the script
forces ``--xla_force_host_platform_device_count`` to the mesh size
before the first jax import (docs/sharded-inference.md).

Zipfian mode (``--zipf 1.1``) benches the content-addressed result cache
(docs/result-cache.md): hot-key traffic over a fixed payload pool,
cache-off baseline vs cache-on, a hit-rate→latency/goodput curve across
skews, and a hit-vs-miss bitwise check — merged into BENCH_SERVING.json
under the ``result_cache`` key.

Runs anywhere (`JAX_PLATFORMS=cpu` works); on-chip numbers come from
running the same script on the TPU interpreter. No outer timeout — see the
measuring protocol in docs/performance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.join(
    os.path.dirname(os.path.abspath(__file__)), ".."))

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402


def build_model(feature_dim: int, hidden=(64,)):
    """The web-service demo classifier shape: Dense trunk + softmax
    head, loaded into an InferenceModel (no fit — serving cares about
    the forward). ``hidden`` sets the trunk widths: the plain load bench
    keeps the demo's single 64-unit layer, the result-cache bench uses a
    wider/deeper trunk so a forward pass costs what real inference costs
    (a result cache is pointless when execution is free)."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    m = Sequential(name="bench")
    # explicit layer names: auto-naming counts up process-globally, and
    # the parameter dict keys must be restart-stable for the AOT
    # executable cache (the pytree structure is part of the cache key)
    for i, width in enumerate(hidden):
        m.add(Dense(width, activation="relu",
                    input_shape=(feature_dim,) if i == 0 else None,
                    name=f"bench_dense_{i + 1}"))
    m.add(Dense(8, activation="softmax",
                name=f"bench_dense_{len(hidden) + 1}"))
    return InferenceModel().do_load_keras(m)


def _latency_ms(lat: np.ndarray) -> dict:
    """The BENCH_SERVING latency block: p50/p95/p99/mean milliseconds
    (p99 is what the result-cache hit-rate→latency curve plots — a cache
    only helps the tail if the tail is recorded)."""
    if not lat.size:
        return {}
    return {
        "p50": round(float(np.percentile(lat, 50)), 3),
        "p95": round(float(np.percentile(lat, 95)), 3),
        "p99": round(float(np.percentile(lat, 99)), 3),
        "mean": round(float(lat.mean()), 3),
    }


def run_bench(clients: int, requests: int, max_batch: int,
              max_wait_ms: float, feature_dim: int = 16,
              max_rows: int = 4, eager_flush_quiesce_ms=0.25):
    """Drive the engine with ``clients`` threads of ``requests`` each
    (random 1..max_rows-row requests); returns the JSON record."""
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    inf = build_model(feature_dim)
    engine = ServingEngine()
    cfg = BatcherConfig(max_batch_size=max_batch, max_wait_ms=max_wait_ms,
                        max_queue_size=max(256, clients * 4),
                        eager_flush_quiesce_ms=eager_flush_quiesce_ms)
    t0 = time.perf_counter()
    engine.register("bench", inf,
                    example_input=np.zeros((1, feature_dim), np.float32),
                    config=cfg)
    warmup_s = time.perf_counter() - t0

    latencies_ms = []
    lat_lock = threading.Lock()
    rows_sent = [0]
    rejected = [0]

    def client(seed: int):
        rng = np.random.default_rng(seed)
        mine, sent = [], 0
        for _ in range(requests):
            x = rng.normal(size=(int(rng.integers(1, max_rows + 1)),
                                 feature_dim)).astype(np.float32)
            t = time.perf_counter()
            try:
                engine.predict("bench", x)
            except Exception:  # noqa: BLE001 — count sheds, keep driving
                with lat_lock:
                    rejected[0] += 1
                continue
            mine.append((time.perf_counter() - t) * 1e3)
            sent += len(x)
        with lat_lock:
            latencies_ms.extend(mine)
            rows_sent[0] += sent

    threads = [threading.Thread(target=client, args=(s,))
               for s in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    engine.shutdown()

    lat = np.asarray(latencies_ms, np.float64)
    m = engine.metrics.for_model("bench")
    from analytics_zoo_tpu.common.observability import get_tracer
    record = {
        "metric": "serving_engine_load",
        "tracing_enabled": get_tracer().enabled,
        "clients": clients,
        "requests_per_client": requests,
        "max_batch_size": max_batch,
        "max_wait_ms": max_wait_ms,
        "eager_flush_quiesce_ms": eager_flush_quiesce_ms,
        "buckets": list(cfg.ladder()),
        "warmup_s": round(warmup_s, 3),
        "wall_s": round(wall, 3),
        "requests_ok": int(lat.size),
        "requests_rejected": rejected[0],
        "rows_per_sec": round(rows_sent[0] / wall, 1),
        "requests_per_sec": round(lat.size / wall, 1),
        "latency_ms": _latency_ms(lat),
        "batch_fill_mean": round(m.batch_fill.mean, 4),
        "flushes": m.flushes.value,
        "padded_rows": m.padded_rows.value,
        "executable_cache": dict(inf.cache_stats),
        "device": device_info(),
    }
    return record


def _zipf_probs(pool: int, s: float) -> np.ndarray:
    """Bounded Zipf(s) over ``pool`` ranks: p(k) ∝ 1/k^s (s=0 → uniform)."""
    w = 1.0 / np.arange(1, pool + 1, dtype=np.float64) ** s
    return w / w.sum()


def _drive_zipf(engine, name: str, pool_inputs, probs, clients: int,
                requests: int):
    """Closed-loop Zipfian clients: each request draws one of the pool's
    fixed payloads by rank probability — the hot-key traffic shape the
    result cache exists for. Returns (wall_s, latencies_ms, rejected)."""
    latencies_ms = []
    lat_lock = threading.Lock()
    rejected = [0]

    def client(seed: int):
        rng = np.random.default_rng(seed)
        idxs = rng.choice(len(pool_inputs), size=requests, p=probs)
        mine = []
        for i in idxs:
            t = time.perf_counter()
            try:
                engine.predict(name, pool_inputs[int(i)])
            except Exception:  # noqa: BLE001 — count sheds, keep driving
                with lat_lock:
                    rejected[0] += 1
                continue
            mine.append((time.perf_counter() - t) * 1e3)
        with lat_lock:
            latencies_ms.extend(mine)

    threads = [threading.Thread(target=client, args=(s,))
               for s in range(clients)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    wall = time.perf_counter() - t0
    return wall, np.asarray(latencies_ms, np.float64), rejected[0]


def run_zipf_bench(s: float, clients: int, requests: int, max_batch: int,
                   max_wait_ms: float, feature_dim: int = 256,
                   hidden=(2048, 2048, 2048, 2048),
                   pool: int = 256, rows: int = 2, repeats: int = 3,
                   eager_flush_quiesce_ms=0.25):
    """The result-cache record (ISSUE 12): Zipfian(s) hot-key traffic
    over a fixed payload pool, cache-off baseline vs cache-on (each the
    best of ``repeats`` runs — the plain bench's noise protocol), plus a
    hit-rate→latency/goodput curve across skews (more skew → higher hit
    rate → lower latency, same engine otherwise). Bitwise check: on the
    cache-on engine, every pool payload's cached response must equal a
    ``Cache-Control: no-cache``-style fresh execution byte for byte."""
    from analytics_zoo_tpu.serving import (BatcherConfig, ResultCacheConfig,
                                           ServingEngine)

    rng = np.random.default_rng(7)
    pool_inputs = [rng.normal(size=(rows, feature_dim)).astype(np.float32)
                   for _ in range(pool)]

    def fresh_engine(cached: bool):
        inf = build_model(feature_dim, hidden=hidden)
        engine = ServingEngine(
            result_cache=ResultCacheConfig() if cached else None)
        engine.register(
            "bench", inf,
            example_input=np.zeros((1, feature_dim), np.float32),
            config=BatcherConfig(
                max_batch_size=max_batch, max_wait_ms=max_wait_ms,
                max_queue_size=max(256, clients * 4),
                eager_flush_quiesce_ms=eager_flush_quiesce_ms))
        return engine

    def measure(cached: bool, skew: float):
        engine = fresh_engine(cached)
        try:
            wall, lat, rej = _drive_zipf(
                engine, "bench", pool_inputs, _zipf_probs(pool, skew),
                clients, requests)
            point = {
                "zipf_s": skew,
                "requests_ok": int(lat.size),
                "requests_rejected": rej,
                "requests_per_sec": round(lat.size / wall, 1),
                "rows_per_sec": round(lat.size * rows / wall, 1),
                "latency_ms": _latency_ms(lat),
            }
            bitwise = None
            if cached:
                stats = engine.result_cache.stats()
                total = stats["hits"] + stats["misses"] + stats["coalesced"]
                point["hit_rate"] = round(
                    (stats["hits"] + stats["coalesced"]) / max(1, total), 4)
                point["cache"] = stats
                # hit path vs miss path, byte for byte: a cached reply
                # must be indistinguishable from a fresh execution
                bitwise = all(
                    np.array_equal(
                        np.asarray(engine.predict("bench", x)),
                        np.asarray(engine.predict("bench", x,
                                                  bypass_cache=True)))
                    for x in pool_inputs)
                point["bitwise_identical"] = bitwise
                scrape = engine.metrics_text()
                point["metrics_families_in_scrape"] = all(
                    f"zoo_serving_result_cache_{fam}" in scrape
                    for fam in ("hits", "misses", "coalesced",
                                "evictions", "bytes"))
            return point
        finally:
            engine.shutdown()

    def best_of(cached: bool, skew: float, n: int):
        points = [measure(cached, skew) for _ in range(max(1, n))]
        best = max(points, key=lambda p: p["requests_per_sec"])
        best["repeats_requests_per_sec"] = sorted(
            p["requests_per_sec"] for p in points)
        return best

    # one throwaway pass warms XLA dispatch + the adaptive interpreter
    # (same reasoning as the plain bench's priming)
    measure(cached=False, skew=s)
    no_cache = best_of(cached=False, skew=s, n=repeats)
    with_cache = best_of(cached=True, skew=s, n=repeats)
    # hit-rate→latency/goodput curve: sweep skew on the cache-on path
    # (uniform → heavy-tailed); each point is a fresh engine+cache
    skews = sorted({0.0, 0.6, float(s), 1.5})
    curve = [measure(cached=True, skew=k) for k in skews]
    return {
        "metric": "serving_result_cache_zipf",
        "zipf_s": float(s),
        "pool": pool,
        "feature_dim": feature_dim,
        "hidden": list(hidden),
        "rows": rows,
        "clients": clients,
        "requests_per_client": requests,
        "max_batch_size": max_batch,
        "max_wait_ms": max_wait_ms,
        "no_cache": no_cache,
        "with_cache": with_cache,
        "speedup_requests_per_sec": round(
            with_cache["requests_per_sec"]
            / max(1e-9, no_cache["requests_per_sec"]), 4),
        "bitwise_identical": with_cache["bitwise_identical"],
        "curve": curve,
        "device": device_info(),
    }


def _ensure_host_devices(mesh_spec: str) -> None:
    """Force enough XLA host devices for ``mesh_spec`` (the SNIPPETS.md
    [2] CI trick). Must run before the FIRST jax import — a no-op when
    jax is already loaded or the flag is already set."""
    total = 1
    for part in mesh_spec.split(","):
        if "=" in part:
            total *= int(part.split("=", 1)[1])
    flags = os.environ.get("XLA_FLAGS", "")
    if "jax" in sys.modules or \
            "xla_force_host_platform_device_count" in flags:
        return
    os.environ["XLA_FLAGS"] = (
        flags + f" --xla_force_host_platform_device_count={total}").strip()


def run_mesh_bench(mesh_spec: str, feature_dim: int = 16,
                   iters: int = 200, pipeline_depth: int = 2,
                   cache_dir=None):
    """The sharded-inference record (ISSUE 11): for every bucket in a
    ladder sized to the mesh (>= 2 rows per data slice — single-row
    slices hit XLA CPU's gemv kernels, which are not bitwise identical
    to the batched ones), compare the mesh-partitioned executable's
    output byte-for-byte against the single-device executable's, then
    measure pipelined dispatch/fetch throughput for both paths and a
    warm-restart compile count under the mesh."""
    import tempfile
    from collections import deque

    from analytics_zoo_tpu.common.observability import (
        get_registry,
        install_compile_listener,
    )
    from analytics_zoo_tpu.mesh import MeshConfig, ShardingPlan
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    install_compile_listener()
    compiles = get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()

    def plan():
        return ShardingPlan(MeshConfig.from_spec(mesh_spec))

    d = plan().data_axis_length
    buckets = (2 * d, 4 * d, 8 * d)
    rng = np.random.default_rng(0)

    ref = build_model(feature_dim)
    sharded = build_model(feature_dim)
    sharded.params, sharded.model_state = ref.params, ref.model_state
    sharded.set_sharding_plan(plan())

    parity = {}
    for b in buckets:
        x = rng.normal(size=(b, feature_dim)).astype(np.float32)
        want = ref.do_predict(x)
        got = sharded.do_predict(x)
        parity[str(b)] = {
            "bitwise": bool((want == got).all()),
            "max_abs_diff": float(np.max(np.abs(want - got))),
        }

    def throughput(im, rows):
        x = rng.normal(size=(rows, feature_dim)).astype(np.float32)
        im.do_optimize(x)
        q = deque()
        t0 = time.perf_counter()
        for _ in range(iters):
            q.append(im.do_dispatch(x))
            if len(q) > pipeline_depth:
                im.do_fetch(q.popleft())
        while q:
            im.do_fetch(q.popleft())
        return rows * iters / (time.perf_counter() - t0)

    rows = buckets[-1]
    single_rps = throughput(ref, rows)
    sharded_rps = throughput(sharded, rows)

    # warm-restart proof under the mesh: two fresh-model engine
    # lifetimes against one AOT cache dir; the second must compile zero
    cache_dir = cache_dir or tempfile.mkdtemp(prefix="azoo-mesh-bench-")
    restart = {}
    for phase in ("cold_restart", "warm_restart"):
        inf = build_model(feature_dim)
        inf.set_aot_cache(cache_dir)
        engine = ServingEngine()
        c0 = compiles.value
        t0 = time.perf_counter()
        engine.register(
            "bench", inf,
            example_input=np.zeros((1, feature_dim), np.float32),
            config=BatcherConfig(max_batch_size=buckets[-1],
                                 buckets=buckets),
            sharding_plan=plan())
        engine.predict("bench",
                       np.zeros((buckets[0], feature_dim), np.float32))
        restart[phase] = {
            "register_to_first_predict_s": round(
                time.perf_counter() - t0, 3),
            "compiles": int(compiles.value - c0),
        }
        engine.shutdown()

    return {
        "metric": "serving_sharded_inference",
        "mesh": plan().mesh_config.describe(),
        "devices": plan().mesh_config.total_devices,
        "buckets": list(buckets),
        "feature_dim": feature_dim,
        "parity": parity,
        "all_bitwise": all(p["bitwise"] for p in parity.values()),
        "rows_per_sec": {
            "single_device": round(single_rps, 1),
            "sharded": round(sharded_rps, 1),
            "ratio": round(sharded_rps / single_rps, 4),
        },
        "restart": restart,
        "aot_cache_dir": cache_dir,
        "device": device_info(),
    }


def run_restart_compiles(max_batch: int, feature_dim: int = 16,
                         cache_dir=None):
    """Simulate a serving-process restart against a persistent AOT
    executable cache (``AZOO_AOT_CACHE_DIR`` /
    ``InferenceModel(aot_cache_dir=...)``): register the bench model
    twice against the same cache directory, each time with a *fresh*
    ``InferenceModel`` (fresh executables — exactly a restarted
    process's state), and report XLA backend-compile counts
    (``zoo_compile_total``) and AOT-cache events per phase. A healthy
    cache shows the warm phase at zero compiles with one hit per
    bucket."""
    import tempfile

    from analytics_zoo_tpu.common.observability import (
        aot_cache_counters,
        get_registry,
        install_compile_listener,
    )
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    install_compile_listener()
    compiles = get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()
    cache_events = aot_cache_counters()
    cache_dir = cache_dir or tempfile.mkdtemp(prefix="azoo-aot-bench-")
    record = {"metric": "serving_restart_compiles",
              "max_batch_size": max_batch,
              "aot_cache_dir": cache_dir}
    for phase in ("cold_restart", "warm_restart"):
        inf = build_model(feature_dim)
        inf.set_aot_cache(cache_dir)
        engine = ServingEngine()
        c0 = compiles.value
        ev0 = {k: c.value for k, c in cache_events.items()}
        t0 = time.perf_counter()
        engine.register(
            "bench", inf,
            example_input=np.zeros((1, feature_dim), np.float32),
            config=BatcherConfig(max_batch_size=max_batch))
        engine.predict("bench", np.zeros((2, feature_dim), np.float32))
        elapsed = time.perf_counter() - t0
        engine.shutdown()
        record[phase] = {
            "register_to_first_predict_s": round(elapsed, 3),
            "compiles": int(compiles.value - c0),
            "aot_cache_events": {k: int(cache_events[k].value - ev0[k])
                                 for k in cache_events},
        }
    return record


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--clients", type=int, default=16)
    p.add_argument("--requests", type=int, default=50,
                   help="requests per client")
    p.add_argument("--max-batch", type=int, default=32)
    p.add_argument("--max-wait-ms", type=float, default=4.0)
    p.add_argument("--repeats", type=int, default=3,
                   help="timed runs after priming; the reported record is "
                        "the best run (OS-scheduling noise on a shared "
                        "host is strictly subtractive, so max is the "
                        "honest capability estimate — all repeats' req/s "
                        "are recorded alongside)")
    p.add_argument("--eager-flush-quiesce-ms", type=float, default=0.25,
                   help="flush a partial batch once the pipeline is idle "
                        "and no request arrived for this long; <= 0 keeps "
                        "the strict max-wait window")
    p.add_argument("--trace-overhead", action="store_true",
                   help="also run with the global tracer ENABLED and "
                        "report the traced/untraced throughput ratio")
    p.add_argument("--restart-compiles", action="store_true",
                   help="instead of the load bench: simulate a serving "
                        "restart twice against one AOT executable cache "
                        "dir and report compile counts per phase (prints "
                        "JSON to stdout, does not write --out)")
    p.add_argument("--aot-cache-dir", default=None,
                   help="cache dir for --restart-compiles (default: a "
                        "fresh temp dir, i.e. a guaranteed-cold first "
                        "phase)")
    p.add_argument("--zipf", type=float, default=None, metavar="S",
                   help="instead of the load bench: Zipfian(S) hot-key "
                        "traffic over a fixed payload pool, cache-off "
                        "baseline vs result-cache-on, a hit-rate→latency/"
                        "goodput curve across skews, and a hit-vs-miss "
                        "bitwise check — merged into BENCH_SERVING.json "
                        "under 'result_cache'")
    p.add_argument("--zipf-pool", type=int, default=256,
                   help="distinct payloads in the Zipf pool (large enough "
                        "that hit rate actually varies with skew)")
    p.add_argument("--mesh", default=None, metavar="SPEC",
                   help="instead of the load bench: run the sharded-"
                        "inference bench over this mesh (e.g. 'data=8') "
                        "— per-bucket bitwise parity vs single-device, "
                        "pipelined throughput for both paths, and a "
                        "warm-restart compile count; writes "
                        "BENCH_SHARDED.json unless --out is given")
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    default_out = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..",
        "BENCH_SHARDED.json" if args.mesh else "BENCH_SERVING.json")
    out_path = args.out or default_out
    eager = (args.eager_flush_quiesce_ms
             if args.eager_flush_quiesce_ms > 0 else None)
    if args.mesh:
        _ensure_host_devices(args.mesh)  # before the first jax import
        record = run_mesh_bench(args.mesh,
                                cache_dir=args.aot_cache_dir)
        print(json.dumps(record))
        with open(out_path, "w") as f:
            json.dump(record, f, indent=2)
            f.write("\n")
        return record
    if args.restart_compiles:
        record = run_restart_compiles(args.max_batch,
                                      cache_dir=args.aot_cache_dir)
        print(json.dumps(record))
        return record
    if args.zipf is not None:
        record = run_zipf_bench(args.zipf, args.clients, args.requests,
                                args.max_batch, args.max_wait_ms,
                                pool=args.zipf_pool,
                                eager_flush_quiesce_ms=eager)
        # merge under "result_cache" so the plain load-bench record and
        # the zipf record coexist in one BENCH_SERVING.json
        content = {}
        if os.path.exists(out_path):
            try:
                with open(out_path) as f:
                    content = json.load(f)
            except (OSError, ValueError):
                content = {}
        content["result_cache"] = record
        print(json.dumps(record))
        with open(out_path, "w") as f:
            json.dump(content, f, indent=2)
            f.write("\n")
        return record
    # Prior committed record: the tracing-disabled-overhead guard — the
    # instrumented request path (span hooks compiled in, tracer off) must
    # hold throughput within 5% of the last recorded run on comparable
    # hardware, or the "disabled tracing is free" claim is broken.
    prev_rps = None
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev_rps = json.load(f).get("requests_per_sec")
        except (OSError, ValueError):
            pass
    # Throwaway priming passes: the bench measures steady-state serving
    # throughput, not process cold-start. The first run in a process is
    # up to ~2x slower for reasons that have nothing to do with the
    # serving path's design — XLA's dispatch machinery and thread pools
    # spin up lazily, and CPython's adaptive interpreter needs thousands
    # of iterations before the hot loops run specialized bytecode. Two
    # full-shape passes get all of that out of the way (and keep the
    # trace-overhead A/B below warm for both of its runs).
    for _ in range(2):
        run_bench(args.clients, args.requests, args.max_batch,
                  args.max_wait_ms, eager_flush_quiesce_ms=eager)
    # best of --repeats timed runs: the workload is deterministic, so
    # run-to-run spread is host scheduling noise (strictly subtractive);
    # the max is the capability estimate, the full list is kept for the
    # spread
    runs = [run_bench(args.clients, args.requests, args.max_batch,
                      args.max_wait_ms, eager_flush_quiesce_ms=eager)
            for _ in range(max(1, args.repeats))]
    record = max(runs, key=lambda r: r["requests_per_sec"])
    record["repeats_requests_per_sec"] = sorted(
        r["requests_per_sec"] for r in runs)
    # keep a previously benched result-cache section alive across plain
    # load-bench rewrites of the file
    if os.path.exists(out_path):
        try:
            with open(out_path) as f:
                prev_cache = json.load(f).get("result_cache")
            if prev_cache is not None:
                record["result_cache"] = prev_cache
        except (OSError, ValueError):
            pass
    if prev_rps:
        record["vs_previous_requests_per_sec"] = round(
            record["requests_per_sec"] / prev_rps, 4)
    if args.trace_overhead:
        from analytics_zoo_tpu.common.observability import get_tracer

        tracer = get_tracer().enable()
        try:
            traced = run_bench(args.clients, args.requests, args.max_batch,
                               args.max_wait_ms,
                               eager_flush_quiesce_ms=eager)
        finally:
            tracer.disable()
            tracer.clear()
        record["traced"] = {
            "requests_per_sec": traced["requests_per_sec"],
            "latency_ms": traced["latency_ms"],
            "vs_untraced": round(traced["requests_per_sec"]
                                 / record["requests_per_sec"], 4),
        }
    print(json.dumps(record))
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return record


if __name__ == "__main__":
    main()
