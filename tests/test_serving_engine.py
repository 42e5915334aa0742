"""ServingEngine acceptance (ISSUE 1): AOT bucket warmup means zero
serve-time recompiles (asserted via the executable-cache counters),
multi-threaded batched results are bitwise-identical to direct
``do_predict`` under the flush's own bucket shape, batch fill exceeds 0.5
at saturation, backpressure rejects with a distinct error, and the LRU
executable-cache cap holds."""

import threading

import numpy as np
import pytest

import analytics_zoo_tpu as zoo
from conftest import bucket_that_served, max_ulp, pad_rows, record_flushes
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.serving import (
    BatcherConfig,
    DeadlineExceededError,
    ModelNotFoundError,
    QueueFullError,
    ServingEngine,
)


def _make_inference_model(**kw):
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    m = Sequential()
    m.add(Dense(8, activation="tanh", input_shape=(4,)))
    m.add(Dense(3, activation="softmax"))
    return InferenceModel(**kw).do_load_keras(m)


class FakeModel:
    """do_predict duck-type for engine logic tests — no XLA, can block."""

    def __init__(self):
        self.gate = None
        self.optimized = []
        self.cache_stats = {"hits": 0, "misses": 0, "evictions": 0}

    def do_optimize(self, x):
        self.optimized.append(np.asarray(x).shape)
        return self

    def do_predict(self, x):
        if self.gate is not None:
            self.gate.wait(timeout=10)
        return np.asarray(x, np.float32) * 2.0


def test_register_warms_every_bucket_and_serving_never_recompiles():
    inf = _make_inference_model()
    engine = ServingEngine()
    cfg = BatcherConfig(max_batch_size=8, max_wait_ms=4.0,
                        buckets=(1, 2, 4, 8))
    # every bucket-shaped batch the flush thread hands the model, so that
    # each request can be compared under the program shape that served it
    # (how 24 concurrent clients coalesce is not the test's to fix)
    flushed = record_flushes(inf)
    try:
        engine.register("mlp", inf, example_input=np.zeros((1, 4), np.float32),
                        config=cfg)
        # warmup compiled exactly one executable per bucket
        assert inf.cache_stats["misses"] == len(cfg.ladder())
        misses_after_warmup = inf.cache_stats["misses"]
        hits_before = inf.cache_stats["hits"]

        rng = np.random.default_rng(0)
        results = {}
        errors = []

        def client(i):
            try:
                x = rng_rows[i]
                results[i] = engine.predict("mlp", x)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        rng_rows = {i: rng.normal(size=(1 + i % 3, 4)).astype(np.float32)
                    for i in range(24)}
        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(24)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not errors, errors

        # acceptance: no recompiles after warmup — every flush hit the
        # cache. (Checked BEFORE the direct-predict loop below, which
        # legitimately compiles the non-bucket shapes it asks for.)
        assert inf.cache_stats["misses"] == misses_after_warmup, \
            inf.cache_stats
        assert inf.cache_stats["hits"] > hits_before
        assert flushed and {b.shape[0] for b in flushed} <= set(cfg.ladder())

        for i, x in rng_rows.items():
            n = x.shape[0]
            bucket = bucket_that_served(flushed, x)
            # acceptance: a padded bucket changes nothing. The served rows
            # are bitwise what the same program gives for these rows alone
            # — whatever shared the flush, wherever in it they sat.
            np.testing.assert_array_equal(
                results[i], inf.do_predict(pad_rows(x, bucket))[:n])
            # Against do_predict at the request's own 1-3 rows the bar is a
            # few units in the last place, not bitwise: that is another
            # XLA:CPU program (another shape), whose dot and softmax sums
            # may associate differently. Measured <= 5 over 200 random
            # requests against every bucket of this ladder.
            assert max_ulp(results[i], inf.do_predict(x)) <= 8

        # acceptance: batch-fill ratio > 0.5 at saturation
        fill = engine.metrics.for_model("mlp").batch_fill
        assert fill.count > 0
        assert fill.mean > 0.5, fill.mean
    finally:
        engine.shutdown()


def test_backpressure_distinct_error_and_no_blocking():
    fake = FakeModel()
    fake.gate = threading.Event()
    engine = ServingEngine()
    try:
        engine.register("fake", fake, example_input=np.zeros((1, 2)),
                        config=BatcherConfig(max_batch_size=1,
                                             max_wait_ms=1.0,
                                             max_queue_size=2))
        x = np.ones((1, 2), np.float32)
        futs = [engine.predict_async("fake", x)]
        import time
        time.sleep(0.05)                      # worker picks up #1, blocks
        futs += [engine.predict_async("fake", x) for _ in range(2)]
        with pytest.raises(QueueFullError):
            engine.predict("fake", x)
        assert engine.metrics.for_model("fake").rejected.value >= 1
        fake.gate.set()
        fake.gate = None
        for f in futs:
            np.testing.assert_array_equal(f.result(timeout=5), x * 2.0)
    finally:
        fake.gate = None
        engine.shutdown()


def test_deadline_through_engine():
    fake = FakeModel()
    fake.gate = threading.Event()
    engine = ServingEngine()
    try:
        engine.register("fake", fake, example_input=np.zeros((1, 2)),
                        config=BatcherConfig(max_batch_size=1,
                                             max_wait_ms=1.0))
        x = np.ones((1, 2), np.float32)
        blocked = engine.predict_async("fake", x)
        import time
        time.sleep(0.05)
        doomed = engine.predict_async("fake", x, timeout_ms=1.0)
        time.sleep(0.05)
        fake.gate.set()
        fake.gate = None
        np.testing.assert_array_equal(blocked.result(timeout=5), x * 2.0)
        with pytest.raises(DeadlineExceededError):
            doomed.result(timeout=5)
        assert engine.metrics.for_model("fake").timeouts.value == 1
        # loop survives: next request serves
        np.testing.assert_array_equal(engine.predict("fake", x), x * 2.0)
    finally:
        fake.gate = None
        engine.shutdown()


def test_versioning_and_unregister():
    a, b = FakeModel(), FakeModel()
    engine = ServingEngine()
    try:
        e1 = engine.register("m", a, example_input=np.zeros((1, 2)))
        e2 = engine.register("m", b, example_input=np.zeros((1, 2)))
        assert (e1.version, e2.version) == ("1", "2")
        assert engine.entry("m").version == "2"        # latest wins
        assert engine.entry("m", "1").model is a
        x = np.ones((2, 2), np.float32)
        np.testing.assert_array_equal(engine.predict("m", x), x * 2.0)
        engine.unregister("m", "2")
        assert engine.entry("m").version == "1"        # latest repointed
        with pytest.raises(KeyError):
            engine.predict("m", x, version="2")
        with pytest.raises(KeyError):
            engine.predict("nope", x)
        engine.unregister("m")
        assert engine.model_names() == []
    finally:
        engine.shutdown()


def test_auto_version_never_reused_after_unregister():
    """register→'1', register→'2', unregister '1', register(auto) mints
    '3' — the freed number is never reissued (regression: len+1 collided
    on '2')."""
    engine = ServingEngine()
    try:
        engine.register("m", FakeModel(), example_input=np.zeros((1, 2)))
        engine.register("m", FakeModel(), example_input=np.zeros((1, 2)))
        engine.unregister("m", "1")
        e3 = engine.register("m", FakeModel(),
                             example_input=np.zeros((1, 2)))
        assert e3.version == "3"
        assert engine.entry("m").version == "3"
    finally:
        engine.shutdown()


def test_latest_repoints_numerically():
    """After unregistering the newest version, '10' outranks '9' (numeric
    compare, not lexicographic sorted()[-1])."""
    engine = ServingEngine()
    try:
        for v in ("9", "10", "11"):
            engine.register("m", FakeModel(),
                            example_input=np.zeros((1, 2)), version=v)
        engine.unregister("m", "11")
        assert engine.entry("m").version == "10"
    finally:
        engine.shutdown()


def test_unknown_lookups_raise_model_not_found():
    """Registry misses raise ModelNotFoundError (the only 404-mapped
    KeyError); still a KeyError subclass for existing callers."""
    engine = ServingEngine()
    try:
        with pytest.raises(ModelNotFoundError):
            engine.entry("ghost")
        engine.register("m", FakeModel(), example_input=np.zeros((1, 2)))
        with pytest.raises(ModelNotFoundError):
            engine.entry("m", "7")
        with pytest.raises(ModelNotFoundError):
            engine.unregister("m", "7")
        assert issubclass(ModelNotFoundError, KeyError)
    finally:
        engine.shutdown()


def test_engine_signature_rejects_malformed_requests():
    """The engine derives an InputSignature from example_input, so a
    trailing-dim mismatch raises synchronously at predict — it can no
    longer land in a batch with well-formed requests and take them (and
    the flush thread) down."""
    engine = ServingEngine()
    try:
        engine.register("m", FakeModel(), example_input=np.zeros((1, 3)),
                        config=BatcherConfig(max_batch_size=8,
                                             max_wait_ms=1.0))
        with pytest.raises(ValueError):
            engine.predict("m", np.ones((2, 4), np.float32))
        with pytest.raises(ValueError):
            engine.predict("m", [np.ones((2, 3), np.float32)] * 2)
        x = np.ones((2, 3), np.float32)
        np.testing.assert_array_equal(engine.predict("m", x), x * 2.0)
    finally:
        engine.shutdown()


def test_warmup_shapes_cover_ladder():
    fake = FakeModel()
    engine = ServingEngine()
    try:
        engine.register("f", fake, example_input=np.zeros((5, 3), np.int32),
                        config=BatcherConfig(max_batch_size=8,
                                             buckets=(2, 8)))
        assert fake.optimized == [(2, 3), (8, 3)]
    finally:
        engine.shutdown()


def test_metrics_exposition_families():
    fake = FakeModel()
    engine = ServingEngine()
    try:
        engine.register("expo", fake, example_input=np.zeros((1, 2)),
                        config=BatcherConfig(max_batch_size=4,
                                             max_wait_ms=1.0))
        engine.predict("expo", np.ones((2, 2), np.float32))
        text = engine.metrics_text()
        for family in ("zoo_serving_requests_total",
                       "zoo_serving_rejected_total",
                       "zoo_serving_queue_depth",
                       "zoo_serving_batch_fill_ratio",
                       "zoo_serving_latency_seconds",
                       "zoo_serving_executable_cache"):
            assert family in text, family
        assert 'zoo_serving_requests_total{model="expo"} 1' in text
        assert 'quantile="0.95"' in text
        stats = engine.stats()
        assert stats["expo"]["metrics"]["requests"] == 1
        assert stats["expo"]["versions"]["1"]["buckets"] == [1, 2, 4]
    finally:
        engine.shutdown()


def test_executable_cache_lru_cap_and_counters():
    """ISSUE 1 satellite: the per-shape executable cache is LRU-bounded and
    evicted shapes recompile correctly."""
    inf = _make_inference_model(executable_cache_size=2)
    xs = [np.ones((n, 4), np.float32) for n in (1, 2, 3)]
    direct = [inf.do_predict(x) for x in xs]          # 3 compiles, cap 2
    assert len(inf._compiled) == 2
    assert inf.cache_stats["misses"] == 3
    assert inf.cache_stats["evictions"] == 1
    # the evicted shape (batch 1, LRU) recompiles and still serves exactly
    misses = inf.cache_stats["misses"]
    np.testing.assert_array_equal(inf.do_predict(xs[0]), direct[0])
    assert inf.cache_stats["misses"] == misses + 1
    # cached shapes are hits, not recompiles
    np.testing.assert_array_equal(inf.do_predict(xs[2]), direct[2])
    assert inf.cache_stats["misses"] == misses + 1
    assert inf.cache_stats["hits"] >= 1


def test_executable_cache_unbounded_when_none():
    inf = _make_inference_model(executable_cache_size=None)
    for n in (1, 2, 3, 4, 5):
        inf.do_predict(np.ones((n, 4), np.float32))
    assert len(inf._compiled) == 5
    assert inf.cache_stats["evictions"] == 0
