"""Persistent AOT executable cache (ISSUE 7): a simulated serving-process
restart against a warm cache performs ZERO backend compiles (asserted via
``zoo_compile_total``), a corrupted entry degrades to recompilation
without failing a single request, structurally different models never
share an entry, and warmup overflow past ``executable_cache_size`` is
detected and counted."""

import os

import numpy as np

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.common.observability import (
    aot_cache_counters,
    get_registry,
    inference_cache_counters,
    install_compile_listener,
)
from analytics_zoo_tpu.inference.aot_cache import (
    _SUFFIX,
    AotExecutableCache,
)
from analytics_zoo_tpu.inference.inference_model import InferenceModel
from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

def _build(names=("aot_dense_1", "aot_dense_2"), **kw):
    """A small classifier with EXPLICIT layer names: auto-naming counts
    up process-globally, and the parameter dict keys are part of the
    cache key (the serialized executable embeds the input pytree) — a
    real restarted process starts its counters fresh, so in-process
    restart simulation must pin the names."""
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    zoo.init_nncontext()
    m = Sequential(name="aotm")
    m.add(Dense(4, activation="relu", input_shape=(6,), name=names[0]))
    m.add(Dense(2, name=names[1]))
    return InferenceModel(**kw).do_load_keras(m)


def _compile_counter():
    install_compile_listener()
    return get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()


def _register_and_predict(cache_dir, buckets=(1, 2, 4),
                          names=("aot_dense_1", "aot_dense_2")):
    """One simulated serving-process lifetime: fresh model + engine
    against ``cache_dir``, register (bucket warmup), one predict."""
    inf = _build(names=names)
    inf.set_aot_cache(cache_dir)
    engine = ServingEngine()
    try:
        engine.register(
            "m", inf, example_input=np.zeros((1, 6), np.float32),
            config=BatcherConfig(max_batch_size=buckets[-1],
                                 buckets=buckets, max_wait_ms=1.0))
        out = engine.predict("m", np.ones((2, 6), np.float32))
    finally:
        engine.shutdown()
    return np.asarray(out)


def test_warm_restart_performs_zero_compiles(tmp_path):
    compiles = _compile_counter()
    events = aot_cache_counters()
    cache_dir = str(tmp_path / "aot")

    c0, h0, s0 = (compiles.value, events["hits"].value,
                  events["stores"].value)
    cold = _register_and_predict(cache_dir)
    cold_compiles = compiles.value - c0
    assert cold_compiles >= 3  # one per bucket
    assert events["stores"].value - s0 >= 3
    stored = [f for f in os.listdir(cache_dir) if f.endswith(_SUFFIX)]
    assert len(stored) >= 3

    # "restart": fresh InferenceModel (empty in-memory executable cache),
    # fresh engine, same disk cache — the compile storm must vanish
    c1, h1 = compiles.value, events["hits"].value
    warm = _register_and_predict(cache_dir)
    assert compiles.value - c1 == 0, (
        "warm restart recompiled — the AOT disk cache is not being hit")
    assert events["hits"].value - h1 >= 3
    assert warm.shape == cold.shape


def test_corrupted_cache_entry_falls_back_without_failing_requests(
        tmp_path):
    compiles = _compile_counter()
    events = aot_cache_counters()
    cache_dir = str(tmp_path / "aot")
    _register_and_predict(cache_dir)

    for f in os.listdir(cache_dir):
        if f.endswith(_SUFFIX):
            with open(os.path.join(cache_dir, f), "wb") as fh:
                fh.write(b"this is not a serialized executable")

    c0, e0 = compiles.value, events["errors"].value
    out = _register_and_predict(cache_dir)  # every request must succeed
    assert out.shape == (2, 2)
    assert compiles.value - c0 >= 3  # fell back to compiling
    assert events["errors"].value - e0 >= 3  # ... and said so


def test_structurally_different_models_never_share_an_entry(tmp_path):
    # same architecture → byte-identical HLO, but different layer names →
    # different parameter pytrees. The serialized executable embeds the
    # input pytree, so a cross-hit would fail at call time; the argument
    # structure is salted into the key to make this a clean miss.
    compiles = _compile_counter()
    cache_dir = str(tmp_path / "aot")
    _register_and_predict(cache_dir, names=("alpha_1", "alpha_2"))
    c0 = compiles.value
    out = _register_and_predict(cache_dir, names=("beta_1", "beta_2"))
    assert out.shape == (2, 2)
    assert compiles.value - c0 >= 3, (
        "a model with a different parameter pytree hit the other "
        "model's cache entries")


def test_key_includes_args_structure(tmp_path):
    class _Lowered:
        def as_text(self):
            return "HloModule m"

    k1 = AotExecutableCache.key_for(_Lowered(), "PyTreeDef(a)")
    k2 = AotExecutableCache.key_for(_Lowered(), "PyTreeDef(b)")
    k3 = AotExecutableCache.key_for(_Lowered(), "PyTreeDef(a)")
    assert k1 != k2
    assert k1 == k3


def test_cache_load_of_missing_key_is_a_miss(tmp_path):
    events = aot_cache_counters()
    cache = AotExecutableCache(str(tmp_path / "aot"))
    m0 = events["misses"].value
    assert cache.load("0" * 64) is None
    assert events["misses"].value - m0 == 1


def test_warmup_overflow_is_detected_and_counted():
    # 3 bucket warmups through a 2-entry LRU: the third warmup evicts a
    # just-warmed executable — serve-time recompiles are back, which is
    # exactly what the overflow counter exists to surface
    overflow = inference_cache_counters()["warmup_overflow"]
    o0 = overflow.value
    inf = _build(names=("ovf_dense_1", "ovf_dense_2"),
                 executable_cache_size=2)
    for rows in (1, 2, 4):
        inf.do_optimize(np.zeros((rows, 6), np.float32))
    assert inf.warmup_overflows >= 1
    assert overflow.value - o0 >= 1


def test_no_overflow_when_cache_fits_the_ladder():
    inf = _build(names=("fit_dense_1", "fit_dense_2"),
                 executable_cache_size=8)
    for rows in (1, 2, 4):
        inf.do_optimize(np.zeros((rows, 6), np.float32))
    assert inf.warmup_overflows == 0
