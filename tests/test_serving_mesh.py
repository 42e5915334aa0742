"""Mesh-parallel serving e2e (ISSUE 11), all under the 8 fake XLA host
devices conftest.py forces:

- parity, like with like: for every bucket in the ladder, a
  ``data=8``-sharded engine returns byte-identical results to the
  single-device model run on each device's slice of the bucket (the
  program shape a device really ran), and stays within a stated few
  units in the last place of the unsharded engine, whose program has
  another shape — and the batch-scoring engine does the same over a full
  dataset;
- zero post-warmup compiles: after register's bucket warmup, concurrent
  HTTP predicts and a hot-reload to a new version never touch the XLA
  compiler again for warmed shapes (``zoo_compile_total``);
- warm restarts: a fresh process-equivalent (new model, new engine, same
  AOT cache dir) under a ``data=8`` mesh compiles zero times;
- isolation: single-device and sharded cache entries for the same model
  never cross-hit — each topology compiles its own entries once, then
  both run warm from one shared cache directory.
"""

import json
import threading
import urllib.request

import numpy as np

import analytics_zoo_tpu as zoo
from conftest import bucket_that_served, pad_rows, record_flushes
from analytics_zoo_tpu.common.observability import (
    get_registry,
    install_compile_listener,
)
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.mesh import MeshConfig, ShardingPlan
from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

# Every bucket gives each of the 8 data slices >= 2 rows (the plan warns
# about a bucket that would put a single row on a slice:
# docs/sharded-inference.md, "Caveats").
BUCKETS = (16, 32, 64)
FEATURES = 6
SLICES = 8


def _plan():
    return ShardingPlan(MeshConfig.from_spec("data=8"))


def _build_net(names=("mesh_e1", "mesh_e2")):
    """EXPLICIT layer names (the test_inference_aot_cache.py idiom):
    auto-naming counts up process-globally and the parameter dict keys
    are part of the AOT cache key, so restart simulation pins them."""
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    m = Sequential(name="meshe")
    m.add(Dense(4, activation="relu", input_shape=(FEATURES,),
                name=names[0]))
    m.add(Dense(2, name=names[1]))
    return m


def _compile_counter():
    install_compile_listener()
    return get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()


def _cfg():
    return BatcherConfig(max_batch_size=BUCKETS[-1], buckets=BUCKETS,
                         max_wait_ms=1.0)


def _bucket(rows, ladder=BUCKETS):
    return next(b for b in ladder if b >= rows)


def _per_slice(model, x, bucket):
    """What a ``data=8`` program of ``bucket`` rows computes for ``x``,
    from a single-device ``model``: ``x`` zero-padded to the bucket, cut
    into the eight devices' slices, each slice predicted at its own shape
    — the shape the partitioned program has on every device."""
    xb = pad_rows(x, bucket)
    per = bucket // SLICES
    out = [np.asarray(model.do_predict(xb[lo:lo + per]))
           for lo in range(0, bucket, per)]
    return np.concatenate(out, axis=0)[:x.shape[0]]


def _assert_close_to_unsharded(out, ref, what=""):
    """Sharded against the unsharded call on the same bucket is NOT
    bitwise on XLA:CPU: a device's slice is a 2- or 4-row program where
    the unsharded one has 16 or 32 rows, and the dot for those shapes sums
    its K = 6 and K = 4 products in another association (the two agree
    bitwise from 8 rows a slice). Each sum errs by half a unit in the last
    place of its partial sums, so the bound is 4 such units at the
    output's scale (measured: 1.1); an element near zero may differ by
    many units of its own."""
    bound = 4 * np.finfo(np.float32).eps * float(np.max(np.abs(ref)))
    assert float(np.max(np.abs(out - ref))) <= bound, what


def test_sharded_engine_bitwise_parity_every_bucket():
    net = _build_net()  # ONE net → identical weights in all three models
    one = InferenceModel().do_load_keras(net)
    ref_engine, sh_engine = ServingEngine(), ServingEngine()
    compiles = _compile_counter()
    try:
        ref_engine.register(
            "m", InferenceModel().do_load_keras(net),
            example_input=np.zeros((1, FEATURES), np.float32),
            config=_cfg())
        sh_engine.register(
            "m", InferenceModel().do_load_keras(net),
            example_input=np.zeros((1, FEATURES), np.float32),
            config=_cfg(), sharding_plan=_plan())
        rng = np.random.RandomState(7)
        c0 = compiles.value
        served = []
        for rows in BUCKETS + (5, 13):  # off-ladder sizes pad to a bucket
            x = rng.randn(rows, FEATURES).astype(np.float32)
            served.append((x, np.asarray(ref_engine.predict("m", x)),
                           np.asarray(sh_engine.predict("m", x))))
        assert compiles.value - c0 == 0, (
            "post-warmup predicts recompiled — warmup did not cover the "
            "ladder under the mesh")
        # (the slice-shaped reference programs compile outside the window)
        for x, ref, out in served:
            rows = x.shape[0]
            np.testing.assert_array_equal(
                out, _per_slice(one, x, _bucket(rows)),
                err_msg=f"sharded != its slices on one device, rows={rows}")
            _assert_close_to_unsharded(out, ref, f"rows={rows}")
    finally:
        ref_engine.shutdown()
        sh_engine.shutdown()


def test_concurrent_http_predicts_and_hot_reload_stay_bitwise():
    from analytics_zoo_tpu.serving.http import serve

    net_v1, net_v2 = _build_net(("mh_a1", "mh_a2")), \
        _build_net(("mh_b1", "mh_b2"))
    ref = InferenceModel().do_load_keras(net_v1)
    ref2 = InferenceModel().do_load_keras(net_v2)
    engine = ServingEngine()
    compiles = _compile_counter()
    srv = None
    sharded_v1 = InferenceModel().do_load_keras(net_v1)
    # how six concurrent requests of 16 rows coalesce (buckets 16, 32 or
    # 64) is the batcher's; the record says which shape served each
    flushed = record_flushes(sharded_v1)
    try:
        engine.register(
            "m", sharded_v1,
            example_input=np.zeros((1, FEATURES), np.float32),
            config=_cfg(), sharding_plan=_plan())
        srv, _t = serve(engine, port=0)
        base = f"http://127.0.0.1:{srv.server_port}"
        rng = np.random.RandomState(11)
        xs = [rng.randn(16, FEATURES).astype(np.float32)
              for _ in range(6)]
        expected = [np.asarray(ref.do_predict(x)) for x in xs]

        c0 = compiles.value
        results, errors = [None] * len(xs), []

        def hit(i):
            try:
                req = urllib.request.Request(
                    f"{base}/v1/models/m:predict",
                    data=json.dumps(
                        {"instances": xs[i].tolist()}).encode(),
                    headers={"Content-Type": "application/json"})
                with urllib.request.urlopen(req, timeout=30) as resp:
                    assert resp.status == 200
                    results[i] = np.asarray(
                        json.loads(resp.read())["predictions"],
                        np.float32)
            except Exception as e:  # noqa: BLE001 — surfaced below
                errors.append((i, e))

        threads = [threading.Thread(target=hit, args=(i,))
                   for i in range(len(xs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not errors, f"concurrent HTTP predicts failed: {errors}"
        assert compiles.value - c0 == 0
        for x, got, want in zip(xs, results, expected):
            # a request's 16 rows sit at a multiple of 16 in its flush, so
            # a slice never straddles two requests: bitwise what one
            # device computes for these rows alone under the flush's shape
            np.testing.assert_array_equal(
                got, _per_slice(ref, x, bucket_that_served(flushed, x)))
            _assert_close_to_unsharded(got, want)

        # hot-reload: a new version under the same mesh takes over the
        # version-less route; its warmup compiles, its traffic does not
        engine.register(
            "m", InferenceModel().do_load_keras(net_v2),
            example_input=np.zeros((1, FEATURES), np.float32),
            config=_cfg(), sharding_plan=_plan())
        x = xs[0]
        # reference compiles outside the window; one request alone is
        # served by the 16-row bucket
        want2 = np.asarray(ref2.do_predict(x))
        want2_sliced = _per_slice(ref2, x, BUCKETS[0])
        c1 = compiles.value
        out = np.asarray(engine.predict("m", x))
        assert compiles.value - c1 == 0
        np.testing.assert_array_equal(out, want2_sliced)
        _assert_close_to_unsharded(out, want2)
        # really the new model: far from v1, not a few units in the last place
        assert np.max(np.abs(out - expected[0])) > 1e-3
    finally:
        if srv is not None:
            srv.shutdown()
        engine.shutdown()


def test_batch_job_sharded_bitwise_parity():
    from analytics_zoo_tpu.batch import BatchPredictJob
    from analytics_zoo_tpu.data.sources import ArraySource

    net = _build_net(("mb_c1", "mb_c2"))
    X = np.random.RandomState(3).randn(72, FEATURES).astype(np.float32)
    ladder = (16, 32)

    def run(sharded):
        job = BatchPredictJob(
            InferenceModel().do_load_keras(net), ArraySource(X),
            batch_size=32, pad_to_bucket=ladder,
            sharding_plan=_plan() if sharded else None)
        return np.concatenate([np.asarray(b)
                               for b in job.scored_blocks()], axis=0)

    ref, out = run(sharded=False), run(sharded=True)
    assert ref.shape[0] == X.shape[0]
    # batches of 32, 32 and a tail of 8 padded to the 16-row bucket
    one = InferenceModel().do_load_keras(net)
    batches = [X[lo:lo + 32] for lo in range(0, X.shape[0], 32)]
    sliced = np.concatenate(
        [_per_slice(one, b, _bucket(b.shape[0], ladder)) for b in batches],
        axis=0)
    np.testing.assert_array_equal(out, sliced)
    _assert_close_to_unsharded(out, ref)


def _lifetime(cache_dir, sharded, names, warm_buckets=(16, 32)):
    """One simulated serving-process lifetime: fresh model + engine
    against ``cache_dir``, register (bucket warmup), one predict."""
    inf = InferenceModel().do_load_keras(_build_net(names=names))
    inf.set_aot_cache(cache_dir)
    engine = ServingEngine()
    try:
        engine.register(
            "m", inf, example_input=np.zeros((1, FEATURES), np.float32),
            config=BatcherConfig(max_batch_size=warm_buckets[-1],
                                 buckets=warm_buckets, max_wait_ms=1.0),
            sharding_plan=_plan() if sharded else None)
        out = engine.predict("m", np.ones((8, FEATURES), np.float32))
    finally:
        engine.shutdown()
    return np.asarray(out)


def test_warm_restart_under_data8_mesh_compiles_zero_times(tmp_path):
    compiles = _compile_counter()
    cache_dir = str(tmp_path / "aot")
    names = ("mw_d1", "mw_d2")

    c0 = compiles.value
    cold = _lifetime(cache_dir, sharded=True, names=names)
    assert compiles.value - c0 >= 2  # one per bucket

    c1 = compiles.value
    warm = _lifetime(cache_dir, sharded=True, names=names)
    assert compiles.value - c1 == 0, (
        "warm restart recompiled under the data=8 mesh — the AOT key "
        "is unstable across processes for sharded executables")
    assert warm.shape == cold.shape


def test_single_device_and_sharded_entries_never_cross_hit(tmp_path):
    import os

    compiles = _compile_counter()
    cache_dir = str(tmp_path / "aot")
    names = ("mx_e1", "mx_e2")

    _lifetime(cache_dir, sharded=False, names=names)
    n_single = len(os.listdir(cache_dir))
    assert n_single >= 2

    # same model, same HLO source — the sharded topology must MISS the
    # single-device entries and compile its own
    c0 = compiles.value
    _lifetime(cache_dir, sharded=True, names=names)
    assert compiles.value - c0 >= 2, (
        "a data=8 lifetime hit single-device cache entries")
    assert len(os.listdir(cache_dir)) >= n_single + 2  # new entries stored

    # and both topologies now run warm from the shared directory
    for sharded in (False, True):
        c = compiles.value
        _lifetime(cache_dir, sharded=sharded, names=names)
        assert compiles.value - c == 0, (
            f"sharded={sharded} lifetime recompiled against a warm cache")
