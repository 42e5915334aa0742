"""Content-addressed result cache (ISSUE 12): keying, LRU/TTL/byte
budget, single-flight coalescing, copy-on-write mutation safety (the
PR 7 staging-buffer discipline applied to cache hits), invalidation
riding the control plane (unregister / hot-reload trim / rollout
rollback), quota-before-cache ordering, hits feeding rollout health
windows, the HTTP ``X-Zoo-Cache`` header and ``Cache-Control:
no-cache`` bypass, and the metrics exposition families."""

import io
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from analytics_zoo_tpu.ft import atomic, chaos
from analytics_zoo_tpu.ft.hot_reload import CheckpointWatcher
from analytics_zoo_tpu.ft.manager import CheckpointManager
from analytics_zoo_tpu.serving import (
    BatcherConfig,
    CowView,
    QuotaConfig,
    QuotaExceededError,
    ResultCache,
    ResultCacheConfig,
    RolloutConfig,
    ServingEngine,
    TenantQuota,
)
from analytics_zoo_tpu.serving.http import serve
from analytics_zoo_tpu.serving.quota import QuotaManager


@pytest.fixture(autouse=True)
def _disarm_chaos():
    yield
    chaos.reset()


class Doubler:
    def do_predict(self, x):
        return np.asarray(x, np.float32) * 2.0


class _ScaleModel:
    def __init__(self, scale):
        self.scale = np.asarray(scale, np.float32)

    def do_predict(self, x):
        return np.asarray(x, np.float32) * self.scale


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


CFG = BatcherConfig(max_batch_size=8, max_wait_ms=1.0)
X = np.ones((1, 3), np.float32)


def _wait_until(cond, timeout=5.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _put(cache, key, arr, model="m", version="1"):
    """Insert through the public flight protocol (what the engine does)."""
    leader, _ = cache.begin_flight(key)
    assert leader
    cache.complete_flight(key, model, version, arr)


# ---------------------------------------------------------------------------
# cache core: config, keying, LRU, TTL, byte budget
# ---------------------------------------------------------------------------


def test_config_validation():
    with pytest.raises(ValueError):
        ResultCacheConfig(max_entries=0)
    with pytest.raises(ValueError):
        ResultCacheConfig(max_bytes=0)
    with pytest.raises(ValueError):
        ResultCacheConfig(ttl_s=0.0)
    assert ResultCacheConfig(ttl_s=None).ttl_s is None  # expiry disabled


def test_key_covers_model_version_dtype_shape_and_bytes():
    a = np.arange(6, dtype=np.float32).reshape(2, 3)
    k = ResultCache.key("m", "1", [a])
    # deterministic, and equal bytes hash equal
    assert k == ResultCache.key("m", "1", [a.copy()])
    # model, version, dtype, shape and content all key distinctly
    assert k != ResultCache.key("other", "1", [a])
    assert k != ResultCache.key("m", "2", [a])
    assert k != ResultCache.key("m", "1", [a.astype(np.float64)])
    assert k != ResultCache.key("m", "1", [a.reshape(3, 2)])
    assert k != ResultCache.key("m", "1", [a + 1])
    # non-contiguous input hashes like its contiguous twin
    assert ResultCache.key("m", "1", [a.T]) == ResultCache.key(
        "m", "1", [np.ascontiguousarray(a.T)])


def test_lru_eviction_and_recency_touch():
    cache = ResultCache(ResultCacheConfig(max_entries=2, ttl_s=None))
    _put(cache, "k1", np.ones(4, np.float32))
    _put(cache, "k2", np.ones(4, np.float32) * 2)
    assert cache.get("k1") is not None  # touch: k1 is now most recent
    _put(cache, "k3", np.ones(4, np.float32) * 3)
    assert cache.get("k2") is None      # k2 was least recent → evicted
    assert cache.get("k1") is not None
    assert cache.get("k3") is not None
    s = cache.stats()
    assert s["evictions"] == 1 and s["entries"] == 2


def test_ttl_expiry_with_injected_clock():
    clk = _FakeClock()
    cache = ResultCache(ResultCacheConfig(ttl_s=10.0), clock=clk)
    _put(cache, "k", np.ones(4, np.float32))
    clk.advance(9.9)
    assert cache.get("k") is not None
    clk.advance(0.2)                     # past expires_at
    assert cache.get("k") is None
    s = cache.stats()
    assert s["evictions"] == 1 and s["entries"] == 0 and s["bytes"] == 0


def test_byte_budget_bounds_residency_and_oversized_never_cached():
    cache = ResultCache(ResultCacheConfig(max_bytes=64, ttl_s=None))
    _put(cache, "big", np.ones(32, np.float32))   # 128 B > budget
    assert cache.get("big") is None and cache.stats()["entries"] == 0
    _put(cache, "a", np.ones(10, np.float32))     # 40 B
    _put(cache, "b", np.ones(10, np.float32))     # 40 B → over 64: drop a
    s = cache.stats()
    assert s["entries"] == 1 and s["bytes"] == 40 and s["evictions"] == 1
    assert cache.get("a") is None and cache.get("b") is not None


# ---------------------------------------------------------------------------
# single-flight coalescing
# ---------------------------------------------------------------------------


def test_coalescing_one_execution_resolves_the_flight():
    cache = ResultCache(ResultCacheConfig())
    leader, none = cache.begin_flight("k")
    assert leader and none is None
    is_leader2, waiter = cache.begin_flight("k")
    assert not is_leader2 and waiter is not None
    cache.complete_flight("k", "m", "1", np.ones(4, np.float32) * 7)
    got = waiter.result(timeout=5)
    np.testing.assert_array_equal(got, np.ones(4, np.float32) * 7)
    assert isinstance(got, CowView)      # zero-copy view of the master
    assert np.shares_memory(got, cache.get("k"))
    s = cache.stats()
    assert s["misses"] == 1 and s["coalesced"] == 1 and s["hits"] == 1


def test_leader_failure_fails_flight_and_errors_never_cached():
    cache = ResultCache(ResultCacheConfig())
    cache.begin_flight("k")
    _l, waiter = cache.begin_flight("k")
    boom = RuntimeError("device on fire")
    cache.fail_flight("k", boom)
    with pytest.raises(RuntimeError, match="device on fire"):
        waiter.result(timeout=5)
    assert cache.get("k") is None        # nothing cached
    leader, _ = cache.begin_flight("k")  # next request retries for real
    assert leader


def test_coalesce_off_every_caller_leads():
    cache = ResultCache(ResultCacheConfig(coalesce=False))
    assert cache.begin_flight("k") == (True, None)
    assert cache.begin_flight("k") == (True, None)
    assert cache.stats()["coalesced"] == 0


# ---------------------------------------------------------------------------
# invalidation
# ---------------------------------------------------------------------------


def test_invalidate_version_counts_separately_from_evictions():
    cache = ResultCache(ResultCacheConfig(ttl_s=None))
    _put(cache, "k1", np.ones(4, np.float32), version="1")
    _put(cache, "k2", np.ones(4, np.float32), version="2")
    _put(cache, "k3", np.ones(4, np.float32), version="2")
    assert cache.invalidate_version("m", "2") == 2
    s = cache.stats()
    assert s["invalidations"] == 2 and s["evictions"] == 0
    assert s["entries"] == 1 and cache.get("k1") is not None
    assert cache.invalidate_model("m") == 1
    assert cache.stats()["entries"] == 0


# ---------------------------------------------------------------------------
# copy-on-write mutation safety (the PR 7 staging discipline for hits)
# ---------------------------------------------------------------------------


def test_cow_setitem_raises_and_master_stays_bitwise_intact():
    cache = ResultCache(ResultCacheConfig(ttl_s=None))
    _put(cache, "k", np.arange(4, dtype=np.float32))
    v = cache.get("k")
    with pytest.raises(ValueError, match=r"arr\.copy\(\)"):
        v[0] = 99.0
    with pytest.raises(ValueError):
        v[:] = 0.0
    np.testing.assert_array_equal(cache.get("k"),
                                  np.arange(4, dtype=np.float32))


def test_cow_augmented_assignment_materializes_private_copy():
    cache = ResultCache(ResultCacheConfig(ttl_s=None))
    _put(cache, "k", np.arange(4, dtype=np.float32))
    v = cache.get("k")
    master = cache.get("k")
    assert np.shares_memory(v, master)   # hits are zero-copy
    v += 1                               # COW: rebinds v to a private copy
    np.testing.assert_array_equal(v, np.arange(4, dtype=np.float32) + 1)
    assert not np.shares_memory(v, master)
    assert v.flags.writeable
    # nothing a caller does to a hit changes what the next hit sees
    np.testing.assert_array_equal(cache.get("k"),
                                  np.arange(4, dtype=np.float32))


def test_cow_copy_and_npy_serialization_from_the_view():
    cache = ResultCache(ResultCacheConfig(ttl_s=None))
    _put(cache, "k", np.arange(6, dtype=np.float32).reshape(2, 3))
    v = cache.get("k")
    c = v.copy()
    assert type(c) is np.ndarray and c.flags.writeable
    c[0, 0] = -1.0                       # private: master untouched
    np.testing.assert_array_equal(
        cache.get("k"), np.arange(6, dtype=np.float32).reshape(2, 3))
    # the zero-copy npy path: np.save streams straight from the view and
    # produces bytes identical to saving a plain private array
    buf_view, buf_plain = io.BytesIO(), io.BytesIO()
    np.save(buf_view, v, allow_pickle=False)
    np.save(buf_plain, np.asarray(v).copy(), allow_pickle=False)
    assert buf_view.getvalue() == buf_plain.getvalue()


# ---------------------------------------------------------------------------
# engine integration: dispositions, one-execution hits, quota ordering
# ---------------------------------------------------------------------------


class _CountingModel:
    def __init__(self):
        self.calls = 0

    def do_predict(self, x):
        self.calls += 1
        return np.asarray(x, np.float32) * 2.0


def test_engine_dispositions_and_hit_skips_execution():
    model = _CountingModel()
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", model, example_input=X, config=CFG)
        warm_calls = model.calls         # register-time bucket warmup
        f1 = engine.predict_async("m", X)
        r1 = f1.result(timeout=10)
        assert f1.cache_status == "miss"
        f2 = engine.predict_async("m", X)
        r2 = f2.result(timeout=10)
        assert f2.cache_status == "hit"
        assert isinstance(r2, CowView)
        np.testing.assert_array_equal(np.asarray(r1), np.asarray(r2))
        assert model.calls == warm_calls + 1   # the hit executed nothing
        # explicit version and per-request opt-out both bypass
        f3 = engine.predict_async("m", X, version="1")
        f3.result(timeout=10)
        assert f3.cache_status == "bypass"
        f4 = engine.predict_async("m", X, bypass_cache=True)
        f4.result(timeout=10)
        assert f4.cache_status == "bypass"
        assert model.calls == warm_calls + 3   # bypasses executed
        s = engine.result_cache.stats()
        assert s["hits"] == 1 and s["misses"] == 1
        # a different payload is a different key
        f5 = engine.predict_async("m", X * 3)
        f5.result(timeout=10)
        assert f5.cache_status == "miss"
    finally:
        engine.shutdown()


def test_engine_without_cache_has_no_disposition():
    engine = ServingEngine()
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG)
        fut = engine.predict_async("m", X)
        fut.result(timeout=10)
        assert not hasattr(fut, "cache_status")
        assert engine.result_cache is None
    finally:
        engine.shutdown()


class _GatedModel:
    """Blocks inside do_predict once armed — pins a flight open so a
    second identical request deterministically coalesces onto it."""

    def __init__(self):
        self.gate = threading.Event()
        self.armed = False
        self.entered = threading.Event()
        self.calls = 0

    def do_predict(self, x):
        self.calls += 1
        if self.armed:
            self.entered.set()
            assert self.gate.wait(10)
        return np.asarray(x, np.float32) * 2.0


def test_engine_coalesces_concurrent_identical_requests():
    model = _GatedModel()
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", model, example_input=X, config=CFG)
        model.armed = True
        f1 = engine.predict_async("m", X)
        assert f1.cache_status == "miss"
        assert model.entered.wait(10)    # leader is executing right now
        executed = model.calls
        f2 = engine.predict_async("m", X)
        assert f2.cache_status == "coalesced"
        model.gate.set()
        np.testing.assert_array_equal(np.asarray(f1.result(timeout=10)),
                                      X * 2.0)
        np.testing.assert_array_equal(np.asarray(f2.result(timeout=10)),
                                      X * 2.0)
        assert model.calls == executed   # one execution, whole flight
        assert isinstance(f2.result(), CowView)
        assert engine.result_cache.stats()["coalesced"] == 1
    finally:
        model.gate.set()
        engine.shutdown()


class _FailOnceModel:
    def __init__(self):
        self.fail = False
        self.calls = 0

    def do_predict(self, x):
        self.calls += 1
        if self.fail:
            raise RuntimeError("transient device error")
        return np.asarray(x, np.float32) * 2.0


def test_engine_never_caches_errors_and_retries_for_real():
    model = _FailOnceModel()
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", model, example_input=X, config=CFG)
        model.fail = True
        with pytest.raises(RuntimeError):
            engine.predict("m", X)
        assert engine.result_cache.stats()["entries"] == 0
        model.fail = False
        fut = engine.predict_async("m", X)
        np.testing.assert_array_equal(np.asarray(fut.result(timeout=10)),
                                      X * 2.0)
        assert fut.cache_status == "miss"   # re-executed, then cached
        assert engine.predict_async("m", X).cache_status == "hit"
    finally:
        engine.shutdown()


def test_cache_hit_never_skips_quota():
    """The ordering the ISSUE pins: quota is checked before the cache, so
    an over-budget tenant 429s even on a red-hot key."""
    clk = _FakeClock()
    engine = ServingEngine(result_cache=ResultCacheConfig())
    engine.quota = QuotaManager(QuotaConfig(
        tenants={"paid": TenantQuota(rate=1.0, burst=2.0)}), clock=clk)
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG)
        f1 = engine.predict_async("m", X, tenant="paid")
        f1.result(timeout=10)
        assert f1.cache_status == "miss"
        f2 = engine.predict_async("m", X, tenant="paid")
        f2.result(timeout=10)
        assert f2.cache_status == "hit"     # hit — but it paid a token
        with pytest.raises(QuotaExceededError):
            engine.predict_async("m", X, tenant="paid")
    finally:
        engine.shutdown()


def test_cache_hits_feed_rollout_health_windows():
    """A hit still records into the version's health window — under
    hot-key traffic a canary must reach min_requests and promote."""
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG)
        for _ in range(6):
            engine.predict("m", X)
        assert _wait_until(lambda: engine.version_health("m", "1").total >= 6)
        assert engine.result_cache.stats()["hits"] >= 5
    finally:
        engine.shutdown()

    # the promotion version of the same pin: one hot key end to end
    engine = ServingEngine(
        result_cache=ResultCacheConfig(),
        rollout=RolloutConfig(ladder=(0.25, 1.0), min_requests=4,
                              auto_evaluate=False))
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG,
                        version="1")
        engine.register("m", _ScaleModel(3.0), example_input=X, config=CFG,
                        version="2")
        ctrl = engine.rollout_controller()
        assert ctrl.active("m") is not None
        deadline = time.monotonic() + 30
        while ctrl.active("m") is not None and time.monotonic() < deadline:
            for _ in range(8):
                engine.predict("m", X)   # one payload: pure hot-key mix
            time.sleep(0.01)
            ctrl.tick()
        state = ctrl.describe("m")
        assert state["done"] and state["outcome"] == "promoted"
        np.testing.assert_array_equal(np.asarray(engine.predict("m", X)),
                                      X * 3.0)
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# invalidation rides the control plane
# ---------------------------------------------------------------------------


def test_unregister_drops_version_entries():
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG,
                        version="1")
        engine.predict("m", X)
        assert engine.result_cache.stats()["entries"] == 1
        engine.unregister("m", "1")
        s = engine.result_cache.stats()
        assert s["entries"] == 0 and s["invalidations"] == 1
    finally:
        engine.shutdown()


def test_hot_reload_trim_drops_retired_versions_entries(tmp_path):
    """keep_versions trimming retires old checkpoints; their cached
    results must die with them — a re-registered version number must
    never serve the old version's bytes."""
    mgr = CheckpointManager(str(tmp_path), asynchronous=False)
    mgr.save(1, {"scale": np.asarray(2.0, np.float32)})

    def build_model(path):
        flat, _meta = atomic.read_checkpoint(path)
        return _ScaleModel(dict(flat)["scale"])

    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        watcher = CheckpointWatcher(
            engine, "m", str(tmp_path), build_model, example_input=X,
            config=CFG, keep_versions=1)
        assert watcher.poll_once() == 1
        np.testing.assert_array_equal(np.asarray(engine.predict("m", X)),
                                      X * 2.0)
        assert engine.result_cache.stats()["entries"] == 1
        mgr.save(2, {"scale": np.asarray(3.0, np.float32)})
        assert watcher.poll_once() == 2      # registers "2", trims "1"
        s = engine.result_cache.stats()
        assert s["invalidations"] >= 1
        # no stale hit after the repoint: fresh execution, fresh bytes
        out = np.asarray(engine.predict("m", X))
        np.testing.assert_array_equal(out, X * 3.0)
        np.testing.assert_array_equal(
            out, np.asarray(engine.predict("m", X, bypass_cache=True)))
    finally:
        engine.shutdown()


def test_rollout_rollback_drops_canary_entries_no_stale_reuse():
    """Rollback retires the canary and its cache entries; a later canary
    minted under the SAME version string must execute fresh — the
    scenario where version-in-the-key alone is not enough."""
    engine = ServingEngine(
        result_cache=ResultCacheConfig(),
        rollout=RolloutConfig(ladder=(0.5, 1.0), min_requests=4,
                              auto_evaluate=False))
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG,
                        version="1")
        engine.register("m", _ScaleModel(3.0), example_input=X, config=CFG,
                        version="2")
        # drive the hot key until BOTH versions' results are cached
        routed = set()
        assert _wait_until(lambda: (
            routed.update(float(np.asarray(engine.predict("m", X))[0, 0])
                          for _ in range(8))
            or routed >= {2.0, 3.0}), timeout=10)
        before = engine.result_cache.stats()
        assert before["entries"] >= 2        # both versions cached
        engine.rollout_controller().rollback("m", "manual")
        s = engine.result_cache.stats()
        assert s["invalidations"] >= 1
        assert sorted(engine.describe_model("m")["versions"]) == ["1"]
        # re-mint version "2" with different weights: routed traffic must
        # see 2x (incumbent) or 4x (new canary) — never the stale 3x
        engine.register("m", _ScaleModel(4.0), example_input=X, config=CFG,
                        version="2")
        seen = set()
        for _ in range(64):
            seen.add(float(np.asarray(engine.predict("m", X))[0, 0]))
        assert 3.0 not in seen, seen
        assert 4.0 in seen and 2.0 in seen, seen
    finally:
        engine.shutdown()


def test_rollout_auto_rollback_drops_canary_entries():
    """The chaos acceptance scenario with a cache in the path: distinct
    payloads miss and record the canary's errors (hot-key hits would
    mask them), auto-rollback retires the canary, and its cached entry
    dies with it."""
    engine = ServingEngine(
        result_cache=ResultCacheConfig(),
        rollout=RolloutConfig(ladder=(0.25, 1.0), min_requests=8,
                              auto_evaluate=False))
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG,
                        version="1")
        for _ in range(8):
            engine.predict("m", X * 5)       # incumbent health baseline
        engine.register("m", _ScaleModel(3.0), example_input=X, config=CFG,
                        version="2")
        assert _wait_until(lambda: any(
            np.asarray(engine.predict("m", X))[0, 0] == 3.0
            for _ in range(8)), timeout=10)  # canary result now cached
        chaos.arm_serving("canary_errors", tag="m@2")
        rng = np.random.default_rng(3)
        for _ in range(40):                  # unique payloads: all misses
            try:
                engine.predict(
                    "m", rng.normal(size=(1, 3)).astype(np.float32))
            except Exception:  # noqa: BLE001 — canary-routed request
                pass
        assert _wait_until(
            lambda: engine.version_health("m", "2").total >= 8)
        engine.rollout_controller().tick()
        state = engine.rollout_controller().describe("m")
        assert state["done"] and state["outcome"] == "rolled_back"
        assert engine.result_cache.stats()["invalidations"] >= 1
        assert sorted(engine.describe_model("m")["versions"]) == ["1"]
        # the hot key now serves the incumbent — bitwise vs fresh
        out = np.asarray(engine.predict("m", X))
        np.testing.assert_array_equal(out, X * 2.0)
        np.testing.assert_array_equal(
            out, np.asarray(engine.predict("m", X, bypass_cache=True)))
    finally:
        engine.shutdown()


# ---------------------------------------------------------------------------
# HTTP surface: X-Zoo-Cache header, Cache-Control bypass, quota 429
# ---------------------------------------------------------------------------


@pytest.fixture
def server():
    engine = ServingEngine(result_cache=ResultCacheConfig())
    engine.register("dbl", Doubler(), example_input=np.zeros((1, 3)),
                    config=CFG)
    srv, _t = serve(engine, port=0)
    yield f"http://127.0.0.1:{srv.server_port}", engine
    srv.shutdown()
    engine.shutdown()


def _post(url, body: bytes, headers=None):
    req = urllib.request.Request(url, data=body, headers=headers or {})
    with urllib.request.urlopen(req, timeout=10) as resp:
        return resp.status, resp.headers, resp.read()


def test_http_cache_header_json(server):
    base, _ = server
    body = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
    code, headers, raw = _post(f"{base}/v1/models/dbl:predict", body)
    assert code == 200 and headers["X-Zoo-Cache"] == "miss"
    code, headers, raw2 = _post(f"{base}/v1/models/dbl:predict", body)
    assert code == 200 and headers["X-Zoo-Cache"] == "hit"
    assert raw == raw2                       # hit is byte-identical
    # Cache-Control: no-cache is the per-request opt-out
    code, headers, raw3 = _post(f"{base}/v1/models/dbl:predict", body,
                                {"Cache-Control": "no-cache"})
    assert code == 200 and headers["X-Zoo-Cache"] == "bypass"
    assert raw == raw3
    # explicit-version routes bypass too
    code, headers, _ = _post(f"{base}/v1/models/dbl/versions/1:predict",
                             body)
    assert code == 200 and headers["X-Zoo-Cache"] == "bypass"


def test_http_cache_header_npy_zero_copy_path(server):
    base, _ = server
    x = np.arange(6, dtype=np.float32).reshape(2, 3)
    buf = io.BytesIO()
    np.save(buf, x)
    hdrs = {"Content-Type": "application/x-npy",
            "Accept": "application/x-npy"}
    code, headers, raw = _post(f"{base}/v1/models/dbl:predict",
                               buf.getvalue(), hdrs)
    assert code == 200 and headers["X-Zoo-Cache"] == "miss"
    code, headers, raw2 = _post(f"{base}/v1/models/dbl:predict",
                                buf.getvalue(), hdrs)
    assert code == 200 and headers["X-Zoo-Cache"] == "hit"
    assert raw == raw2                       # npy streams from the view
    np.testing.assert_array_equal(np.load(io.BytesIO(raw2)), x * 2.0)
    code, headers, raw3 = _post(
        f"{base}/v1/models/dbl:predict", buf.getvalue(),
        dict(hdrs, **{"Cache-Control": "no-cache"}))
    assert code == 200 and headers["X-Zoo-Cache"] == "bypass"
    assert raw == raw3


def test_http_no_cache_engine_has_no_header():
    engine = ServingEngine()
    engine.register("dbl", Doubler(), example_input=np.zeros((1, 3)),
                    config=CFG)
    srv, _t = serve(engine, port=0)
    try:
        base = f"http://127.0.0.1:{srv.server_port}"
        body = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
        code, headers, _ = _post(f"{base}/v1/models/dbl:predict", body)
        assert code == 200 and headers.get("X-Zoo-Cache") is None
    finally:
        srv.shutdown()
        engine.shutdown()


def test_http_hot_key_still_429s_over_quota():
    clk = _FakeClock()
    engine = ServingEngine(result_cache=ResultCacheConfig())
    engine.quota = QuotaManager(QuotaConfig(
        tenants={"paid": TenantQuota(rate=1.0, burst=2.0)}), clock=clk)
    engine.register("dbl", Doubler(), example_input=np.zeros((1, 3)),
                    config=CFG)
    srv, _t = serve(engine, port=0)
    try:
        base = f"http://127.0.0.1:{srv.server_port}"
        body = json.dumps({"instances": [[1.0, 2.0, 3.0]]}).encode()
        hdrs = {"X-Zoo-Tenant": "paid"}
        code, headers, _ = _post(f"{base}/v1/models/dbl:predict", body,
                                 hdrs)
        assert code == 200 and headers["X-Zoo-Cache"] == "miss"
        code, headers, _ = _post(f"{base}/v1/models/dbl:predict", body,
                                 hdrs)
        assert code == 200 and headers["X-Zoo-Cache"] == "hit"
        with pytest.raises(urllib.error.HTTPError) as e:
            _post(f"{base}/v1/models/dbl:predict", body, hdrs)
        assert e.value.code == 429           # the hit above paid a token
        assert e.value.headers["Retry-After"] is not None
    finally:
        srv.shutdown()
        engine.shutdown()


# ---------------------------------------------------------------------------
# metrics exposition
# ---------------------------------------------------------------------------

_FAMILIES = ("zoo_serving_result_cache_hits_total",
             "zoo_serving_result_cache_misses_total",
             "zoo_serving_result_cache_coalesced_total",
             "zoo_serving_result_cache_evictions_total",
             "zoo_serving_result_cache_invalidations_total",
             "zoo_serving_result_cache_bytes",
             "zoo_serving_result_cache_entries")


def test_metrics_families_in_one_scrape():
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG)
        engine.predict("m", X)
        engine.predict("m", X)
        text = engine.metrics_text()
        for fam in _FAMILIES:
            assert f"# TYPE {fam}" in text, fam
        assert "zoo_serving_result_cache_hits_total 1" in text
        assert "zoo_serving_result_cache_misses_total 1" in text
        assert "zoo_serving_result_cache_entries 1" in text
    finally:
        engine.shutdown()


def test_hot_keys_served_from_cache_are_bitwise_a_fresh_execution():
    """Skewed traffic over a small pool through a real ``InferenceModel``:
    every repeat is a hit, a hit is byte for byte what a fresh execution of
    the same request gives, and one scrape carries every family. One
    request at a time, so hit, miss and bypass all ran the 2-row bucket."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    net = Sequential()
    net.add(Dense(16, activation="relu", input_shape=(6,)))
    net.add(Dense(4, activation="softmax"))
    engine = ServingEngine(result_cache=ResultCacheConfig())
    try:
        engine.register("m", InferenceModel().do_load_keras(net),
                        example_input=np.zeros((1, 6), np.float32),
                        config=CFG)
        rng = np.random.default_rng(7)
        pool = [rng.normal(size=(2, 6)).astype(np.float32)
                for _ in range(8)]
        weights = 1.0 / np.arange(1, len(pool) + 1) ** 1.1   # Zipf(1.1)
        draws = rng.choice(len(pool), size=64, p=weights / weights.sum())
        first = {}
        for k in draws:
            fut = engine.predict_async("m", pool[k])
            out = np.asarray(fut.result(timeout=10))
            assert fut.cache_status == ("hit" if k in first else "miss")
            np.testing.assert_array_equal(out, first.setdefault(k, out))
        stats = engine.result_cache.stats()
        assert stats["misses"] == len(first)
        assert stats["hits"] == len(draws) - len(first)
        for k, cached in first.items():
            fresh = engine.predict("m", pool[k], bypass_cache=True)
            np.testing.assert_array_equal(cached, np.asarray(fresh))
        text = engine.metrics_text()
        for fam in _FAMILIES:
            assert f"# TYPE {fam}" in text, fam
        assert f"zoo_serving_result_cache_hits_total {stats['hits']}" in text
    finally:
        engine.shutdown()


def test_metrics_families_render_zero_without_cache():
    engine = ServingEngine()
    try:
        engine.register("m", Doubler(), example_input=X, config=CFG)
        text = engine.metrics_text()
        for fam in _FAMILIES:             # stable family set for scrapers
            assert f"# TYPE {fam}" in text, fam
        assert "zoo_serving_result_cache_hits_total 0" in text
    finally:
        engine.shutdown()
