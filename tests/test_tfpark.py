"""TFPark facade tests (ref pyzoo/test/zoo/tfpark patterns)."""

import numpy as np
import pytest

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.keras.engine.topology import Sequential
from analytics_zoo_tpu.keras.layers import Dense
from analytics_zoo_tpu.keras.optimizers import Adam


@pytest.fixture(autouse=True)
def _ctx():
    zoo.init_nncontext()


def test_tfdataset_batch_contract():
    from analytics_zoo_tpu.tfpark import TFDataset

    x = np.zeros((32, 4), np.float32)
    with pytest.raises(ValueError, match="multiple of the"):
        TFDataset.from_ndarrays((x, np.zeros(32)), batch_size=12)  # 12 % 8 != 0
    ds = TFDataset.from_ndarrays((x, np.zeros(32)), batch_size=16)
    assert ds.batch_size == 16
    ds2 = TFDataset.from_ndarrays((x, np.zeros(32)), batch_per_thread=2)
    assert ds2.batch_size == 16  # 2 * 8 devices


def test_tfpark_keras_model_fit_predict():
    from analytics_zoo_tpu.tfpark import KerasModel, TFDataset

    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)
    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer=Adam(lr=0.02), loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    km = KerasModel(m)
    ds = TFDataset.from_ndarrays((x, y), batch_size=32)
    km.fit(ds, epochs=15)
    res = km.evaluate(ds)
    assert res["accuracy"] > 0.9
    preds = km.predict(TFDataset.from_ndarrays(x, batch_size=32))
    assert preds.shape == (64, 2)


def test_tf_optimizer_from_keras_and_from_loss():
    """TFOptimizer facade (ref tf_optimizer.py:57,229,238,388): from_keras
    reads the compiled attributes, from_loss binds an explicit (model,
    criterion), optimize() drives the engine, and the optimizer translation
    table accepts names/objects/optax transforms."""
    import optax

    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.tfpark import (
        TFDataset, TFOptimizer, to_optax_optim_method,
    )

    rng = np.random.default_rng(3)
    x = rng.normal(size=(64, 4)).astype(np.float32)
    y = (x.sum(1) > 0).astype(np.int32)

    m = Sequential()
    m.add(Dense(8, activation="relu", input_shape=(4,)))
    m.add(Dense(2, activation="softmax"))
    m.compile(optimizer=Adam(lr=0.02), loss="sparse_categorical_crossentropy",
              metrics=["accuracy"])
    ds = TFDataset.from_ndarrays((x, y), batch_size=32)
    opt = TFOptimizer.from_keras(m, ds)
    from analytics_zoo_tpu.engine.triggers import MaxEpoch
    opt.optimize(end_trigger=MaxEpoch(12))
    assert m.evaluate(x, y, batch_size=32)["accuracy"] > 0.9

    # from_loss: explicit (model, criterion) — uncompiled model whose
    # estimator already holds state (predict first): the optimizer must be
    # RESET into it, not assigned over a stale empty opt_state
    m2 = Sequential()
    m2.add(Dense(8, activation="relu", input_shape=(4,)))
    m2.add(Dense(2, activation="softmax"))
    m2.predict(x[:8], batch_size=8)
    opt2 = TFOptimizer.from_loss(
        objectives.sparse_categorical_crossentropy, optax.adam(0.02),
        model=m2, dataset=ds)
    opt2.set_gradient_clipping_by_l2_norm(5.0)
    opt2.optimize(end_trigger=MaxEpoch(12))
    acc2 = opt2._ensure_estimator().evaluate(
        ds.feature_set, ["accuracy"], batch_size=32)["accuracy"]
    assert acc2 > 0.9, acc2

    # val_spilt (ref misspelling kept): held-out validation actually runs
    m3 = Sequential()
    m3.add(Dense(8, activation="relu", input_shape=(4,)))
    m3.add(Dense(2, activation="softmax"))
    m3.compile(optimizer=Adam(lr=0.02), loss="sparse_categorical_crossentropy",
               metrics=["accuracy"])
    opt3 = TFOptimizer.from_keras(m3, ds, val_spilt=0.25)
    opt3.optimize(end_trigger=MaxEpoch(10))
    assert opt3._ensure_estimator().run_state.score is not None

    # translation table (ref to_bigdl_optim_method:276-373)
    assert isinstance(to_optax_optim_method("rmsprop"),
                      optax.GradientTransformation)
    assert isinstance(to_optax_optim_method(optax.sgd(0.1)),
                      optax.GradientTransformation)
    assert isinstance(to_optax_optim_method(Adam(lr=0.1)),
                      optax.GradientTransformation)
    with pytest.raises(ValueError, match="Unknown optimizer"):
        to_optax_optim_method("nope")


def test_tfestimator_model_fn_protocol(tmp_path):
    from analytics_zoo_tpu.tfpark import EstimatorSpec, TFDataset, TFEstimator

    rng = np.random.default_rng(1)
    x = rng.normal(size=(64, 3)).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)

    def model_fn(mode, params):
        m = Sequential()
        m.add(Dense(params["hidden"], activation="relu", input_shape=(3,)))
        m.add(Dense(2, activation="softmax"))
        return EstimatorSpec(mode=mode, model=m,
                             loss="sparse_categorical_crossentropy",
                             optimizer=Adam(lr=0.05))

    est = TFEstimator(model_fn, params={"hidden": 8})
    input_fn = lambda: TFDataset.from_ndarrays((x, y), batch_size=32)
    est.train(input_fn, steps=40)
    res = est.evaluate(input_fn, eval_methods=["loss", "accuracy"])
    assert res["accuracy"] > 0.9
    preds = est.predict(lambda: TFDataset.from_ndarrays(x, batch_size=32))
    assert preds.shape == (64, 2)


def test_bert_classifier_tiny():
    from analytics_zoo_tpu.tfpark import BERTClassifier, TFDataset

    rng = np.random.default_rng(2)
    n, seq = 64, 16
    ids = rng.integers(1, 30, size=(n, seq))
    types = np.zeros((n, seq), np.int32)
    mask = np.ones((n, seq), np.float32)
    y = (ids[:, 0] > 15).astype(np.int32)  # signal in first token

    est = BERTClassifier(
        num_classes=2,
        bert_config=dict(vocab=30, hidden_size=32, n_block=1, n_head=2,
                         seq_len=seq, intermediate_size=64,
                         hidden_drop=0.0, attn_drop=0.0),
        optimizer=Adam(lr=0.01))
    input_fn = lambda: TFDataset.from_ndarrays(([ids, types, mask], y),
                                               batch_size=32)
    est.train(input_fn, steps=60)
    res = est.evaluate(input_fn, eval_methods=["loss", "accuracy"])
    assert res["accuracy"] > 0.85, res


def test_tf_predictor_over_dataset():
    """TFPredictor (ref tf_predictor.py:28): batch prediction of a model —
    or a bare callable graph like an imported TFNet — over a TFDataset."""
    from analytics_zoo_tpu.keras.engine.base import reset_name_counts
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense
    from analytics_zoo_tpu.tfpark import TFDataset, TFPredictor

    rng = np.random.default_rng(5)
    x = rng.normal(size=(70, 6)).astype(np.float32)  # 70: exercises masking

    reset_name_counts()
    m = Sequential(name="tfpred")
    m.add(Dense(3, activation="softmax", input_shape=(6,)))
    m.compile(optimizer=Adam(lr=0.01), loss="sparse_categorical_crossentropy")
    ds = TFDataset.from_ndarrays(x, batch_per_thread=4)
    preds = TFPredictor.from_keras(m, ds).predict()
    assert preds.shape == (70, 3)

    # bare-callable path (what Net.load_tf returns behaves like)
    import jax.numpy as jnp

    fn = lambda t: jnp.tanh(jnp.asarray(t) @ jnp.ones((6, 2), jnp.float32))
    preds2 = TFPredictor.from_tfnet(fn, ds).predict()
    assert preds2.shape == (70, 2)
    np.testing.assert_allclose(preds2, np.tanh(x @ np.ones((6, 2))), atol=1e-5)


def test_tf_predictor_with_real_tfnet(tmp_path):
    """The primary TFPredictor use case: an imported foreign TF graph
    (TFNet, ref TFNet.scala:52) predicted over a TFDataset."""
    tf = __import__("pytest").importorskip("tensorflow")

    from analytics_zoo_tpu.tfnet import TFNet
    from analytics_zoo_tpu.tfpark import TFDataset, TFPredictor

    km = tf.keras.Sequential([
        tf.keras.layers.Input(shape=(5,)),
        tf.keras.layers.Dense(4, activation="relu"),
        tf.keras.layers.Dense(3, activation="softmax"),
    ])
    net = TFNet.from_keras(km)

    rng = np.random.default_rng(3)
    x = rng.normal(size=(37, 5)).astype(np.float32)  # 37: masked tail
    ds = TFDataset.from_ndarrays(x, batch_per_thread=2)
    preds = TFPredictor.from_tfnet(net, ds).predict()
    assert preds.shape == (37, 3)
    np.testing.assert_allclose(preds, km.predict(x, verbose=0), atol=1e-5)


def test_keras_model_fit_with_tfdataset_validation():
    """fit(validation_data=TFDataset) unwraps to the validation FeatureSet
    (the reference's KerasModel accepts dataset-form validation too)."""
    from analytics_zoo_tpu.tfpark import KerasModel, TFDataset

    rng = np.random.RandomState(0)
    x = rng.randn(64, 6).astype(np.float32)
    y = (x[:, 0] > 0).astype(np.int32)
    m = Sequential([Dense(2, activation="softmax", input_shape=(6,))])
    m.compile("adam", "sparse_categorical_crossentropy", metrics=["accuracy"])
    wrapped = KerasModel(m)
    train = TFDataset.from_ndarrays((x, y), batch_size=32)
    # the validation dataset's OWN batch geometry must be honored
    val = TFDataset.from_ndarrays((x[:16], y[:16]), batch_size=16)
    from analytics_zoo_tpu.engine.estimator import Estimator
    seen = []
    orig_eval = Estimator.evaluate

    def spy(self, validation_set, validation_method, batch_size=32):
        seen.append(batch_size)
        return orig_eval(self, validation_set, validation_method, batch_size)

    Estimator.evaluate = spy
    try:
        wrapped.fit(train, epochs=2, validation_data=val)
    finally:
        Estimator.evaluate = orig_eval
    assert seen and all(b == 16 for b in seen), seen  # val batch, not train
    res = wrapped.evaluate(val)
    assert "loss" in res


def test_bert_trains_through_public_fit_over_device_cache():
    """The bench's ``bert_fit_path`` machinery (VERDICT r3 #2): BERT
    through the PUBLIC Estimator.train over an HBM-cached multi-input
    token set — must engage the cached gather path and train."""
    import optax

    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.engine.triggers import MaxEpoch
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.tfpark.bert import BERTClassifierNet

    model = BERTClassifierNet(num_classes=2, hidden_drop=0.0, attn_drop=0.0,
                              n_block=2, hidden_size=32, n_head=2,
                              seq_len=16, intermediate_size=64, vocab=100)
    est = Estimator(model, optax.adam(0.01))
    n, batch = 64, 16
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 100, (n, 16)).astype(np.int32)
    types = np.zeros((n, 16), np.int32)
    amask = np.ones((n, 16), np.float32)
    y = (ids[:, 0] > 50).astype(np.int32)
    fs = ArrayFeatureSet([ids, types, amask], y).cache_device()
    assert fs.device_shuffle  # epoch-in-one-dispatch eligible

    for _ in range(4):
        est.train(fs, objectives.sparse_categorical_crossentropy,
                  end_trigger=MaxEpoch(est.run_state.epoch + 1),
                  batch_size=batch)
    assert np.isfinite(est.run_state.loss)
    # the cached path really engaged: the training-step cache is keyed on
    # the dataset identity only when the gather is in the loop
    assert any(k[0] in ("train_epoch", "train_scan")
               for k in est._jit_cache.keys()), est._jit_cache.keys()


def test_bert_fit_path_bench_rehearsal():
    """Dress rehearsal of a fused-fit measurement's call pattern (a warm
    call, then the timed one, as any benchmark of the fused path makes):
    train(MaxEpoch(E)) then train(MaxEpoch(2E)) must BOTH take the
    fused-fit dispatch with the SAME compiled executable — a retrace or
    recompile inside the timed region would corrupt the on-chip number
    (caught one: eager optax init left TP-pspec'd moments replicated
    while the step emitted them model-sharded)."""
    import optax

    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.engine.triggers import MaxEpoch
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.tfpark.bert import BERTClassifierNet

    model = BERTClassifierNet(num_classes=2, hidden_drop=0.0, attn_drop=0.0,
                              n_block=2, hidden_size=32, n_head=2,
                              seq_len=16, intermediate_size=64, vocab=100)
    est = Estimator(model, optax.adam(0.01))
    n, batch, epochs = 64, 16, 2
    rng = np.random.default_rng(6)
    ids = rng.integers(0, 100, (n, 16)).astype(np.int32)
    types = np.zeros((n, 16), np.int32)
    amask = np.ones((n, 16), np.float32)
    y = (ids[:, 0] > 50).astype(np.int32)
    fs = ArrayFeatureSet([ids, types, amask], y).cache_device()

    crit = objectives.sparse_categorical_crossentropy
    est.train(fs, crit, end_trigger=MaxEpoch(epochs), batch_size=batch)
    fit_keys = [k for k in est._jit_cache if k[0] == "train_fit"]
    assert fit_keys, "bench warmup did not take the fused-fit path"
    n_compiles = est._jit_cache[fit_keys[0]]._cache_size()
    assert n_compiles == 1

    est.train(fs, crit, end_trigger=MaxEpoch(2 * epochs), batch_size=batch)
    # same E -> same token -> same executable AND same trace: nothing
    # recompiled in the region the bench clock covers
    assert [k for k in est._jit_cache if k[0] == "train_fit"] == fit_keys
    assert est._jit_cache[fit_keys[0]]._cache_size() == n_compiles
    assert est.run_state.epoch == 2 * epochs
    assert np.isfinite(est.run_state.loss)
