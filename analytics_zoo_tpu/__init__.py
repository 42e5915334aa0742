"""analytics_zoo_tpu — a TPU-native analytics + AI framework.

A ground-up JAX/XLA re-design of the capabilities of Analytics Zoo
(reference: MeghComputing/analytics-zoo). Where the reference layers a
Keras-style API, feature pipelines, a model zoo, Spark-ML integration and a
serving runtime on top of BigDL's MKL tensor engine and a Spark-block-manager
AllReduce, this framework is Python/JAX-native:

    user API -> JAX pytrees/functions -> jit/pjit + XLA -> TPU ICI collectives

There is no JVM, no py4j mirror layer, no frozen-graph export. Distributed
training is a single jitted SPMD program over a ``jax.sharding.Mesh``; gradient
aggregation is XLA's implicit psum over the data axis (replacing BigDL's
parameter-sharded AllReduce, ref docs/docs/wp-bigdl.md:113-160).

Top-level namespaces mirror the reference package layout
(``com.intel.analytics.zoo.*`` / ``pyzoo/zoo/*``):

- :mod:`analytics_zoo_tpu.common`    — NNContext equivalent (mesh bring-up, config)
- :mod:`analytics_zoo_tpu.keras`     — Keras-1-style layer/model API (ref pipeline/api/keras)
- :mod:`analytics_zoo_tpu.autograd`  — Variable/AutoGrad sugar (ref pipeline/api/autograd)
- :mod:`analytics_zoo_tpu.engine`    — training engine (ref InternalDistriOptimizer/Estimator)
- :mod:`analytics_zoo_tpu.data`      — FeatureSet/ImageSet/TextSet (ref zoo/feature)
- :mod:`analytics_zoo_tpu.models`    — model zoo (ref zoo/models)
- :mod:`analytics_zoo_tpu.parallel`  — mesh/sharding/collectives (replaces Spark comms)
- :mod:`analytics_zoo_tpu.inference` — serving runtime (ref pipeline/inference)
- :mod:`analytics_zoo_tpu.serving`   — online engine: dynamic batching, bucket
  ladder, backpressure, metrics (ref Cluster Serving)
- :mod:`analytics_zoo_tpu.ops`       — Pallas TPU kernels
"""

__version__ = "0.1.0"

from analytics_zoo_tpu.common.runtime import configure_compile_cache

# before any compile, on every entry point: importing the package is the
# one step they all share
configure_compile_cache()

from analytics_zoo_tpu.common.nncontext import init_nncontext, get_nncontext

__all__ = ["init_nncontext", "get_nncontext", "__version__"]
