"""Engine-builder spec with a real jax-backed InferenceModel — the
warm-restart front-door test (slow tier) boots workers from this.

The front door exports ``AZOO_AOT_CACHE_DIR`` into the worker
environment, so the InferenceModel built here persists its compiled
executables automatically; a respawned worker (or a whole warm
front-door restart) must compile zero times. Layer names are explicit
because the parameter-dict keys are part of the AOT cache key — they
must be restart-stable (the test_inference_aot_cache.py idiom).
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np

FEATURES = 8


def build_engine():
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    zoo.init_nncontext()
    m = Sequential(name="fd")
    m.add(Dense(16, activation="relu", input_shape=(FEATURES,),
                name="fd_dense_1"))
    m.add(Dense(4, activation="softmax", name="fd_dense_2"))
    inf = InferenceModel().do_load_keras(m)

    engine = ServingEngine()
    engine.register("fd", inf, example_input=np.zeros((1, FEATURES)),
                    config=BatcherConfig(max_batch_size=4, max_wait_ms=1.0))
    return engine
