"""Test bootstrap: multi-chip logic on a virtual CPU mesh.

Mirrors the reference's ``local[N]`` Spark-context trick (SURVEY.md §4 item 4:
DistriEstimatorSpec simulates a cluster with executor threads). Here the
simulated cluster is 8 XLA host devices; the same shardings that run on a TPU
slice compile and execute on them.

Must set the env vars before jax initializes its backends — hence this file
does it at import time, before any test module imports jax.
"""

import os

# The tests are CPU-only by construction: pin the platform before jax is
# imported (the env var suffices, and worker subprocesses inherit it) and
# force 8 host devices through XLA_FLAGS, read at CPU-backend init.
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# No persistent compile cache under test: the tier-1 run is cut off by a
# wall-clock budget, so a warm <checkout>/.jax_cache would change how far
# the suite gets — cold and warm runs must behave the same. The env var
# reaches the subprocesses the tests spawn; the cache DIRECTORY the package
# configures is unaffected (tests/test_chip_smoke.py checks it).
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _reset_context():
    """Fresh global NNContext + layer-name counters per test."""
    yield
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.keras.engine import base

    nncontext.stop_nncontext()
    base.reset_name_counts()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running integration test (still run in CI)")


def load_script(base: str, relpath: str, prefix: str = "script"):
    """Import a CLI script (examples/ or apps/) as a module — shared by the
    e2e smoke suites."""
    import importlib.util
    import os
    import sys

    path = os.path.abspath(os.path.join(
        os.path.dirname(__file__), "..", base, relpath))
    name = prefix + "_" + relpath.replace("/", "_").replace("-", "_")         .removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


# -- serving parity: compare like with like ---------------------------------
# A request served through a bucket ladder ran under the flush's bucket
# shape; a direct ``do_predict`` at the request's own row count is another
# XLA:CPU program, equal only to a few units in the last place. These let a
# test find the shape that served a request and build the same one directly.

def record_flushes(model):
    """Record every bucket-shaped batch the batcher dispatches to ``model``
    (call before ``register``). Returns the list it fills."""
    flushed = []
    dispatch = model.do_dispatch

    def recording_dispatch(x):
        flushed.append(np.array(x))
        return dispatch(x)

    model.do_dispatch = recording_dispatch
    return flushed


def bucket_that_served(flushed, x):
    """Row count of the recorded flush that carried the rows ``x``."""
    n = x.shape[0]
    return next(b.shape[0] for b in flushed
                if any(np.array_equal(b[o:o + n], x)
                       for o in range(b.shape[0] - n + 1)))


def pad_rows(x, bucket):
    """``x`` as the batcher assembles it alone in a flush: zeros below."""
    pad = np.zeros((bucket - x.shape[0],) + x.shape[1:], x.dtype)
    return np.concatenate([x, pad], axis=0)


def max_ulp(a, b):
    """Largest distance between two float32 arrays in units in the last
    place (elementwise of one sign: softmax outputs, trained weights)."""
    ia = np.asarray(a, np.float32).view(np.int32).astype(np.int64)
    ib = np.asarray(b, np.float32).view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib)))


def inner_jaxprs(eqn):
    """The jaxprs an equation holds (a kernel's body aside)."""
    from jax._src import core

    if eqn.primitive.name == "pallas_call":
        return
    for v in eqn.params.values():
        for u in (v if isinstance(v, (tuple, list)) else (v,)):
            if isinstance(u, (core.ClosedJaxpr, core.Jaxpr)):
                yield getattr(u, "jaxpr", u)
