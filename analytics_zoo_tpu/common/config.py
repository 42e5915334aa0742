"""Typed configuration for the runtime.

The reference scatters configuration over three tiers: a packaged Spark conf
(``spark-analytics-zoo.conf``, zoo/src/main/resources:30-38 — shuffle-locality
off, nio transfer, KMP/OMP pinning), ``spark.analytics.zoo.versionCheck``
properties (NNContext.scala:138-143) and scopt CLI case-classes in examples.
None of those concepts survive on TPU — there is no shuffle service and no OMP
pinning — so the rebuild collapses configuration into one typed dataclass with
versioned defaults (SURVEY.md §5 "Config / flag system").
"""

from __future__ import annotations

import dataclasses
import os
from typing import Optional, Sequence


@dataclasses.dataclass
class ZooConfig:
    """Global runtime configuration (analogue of NNContext's SparkConf tier).

    Attributes:
      mesh_shape: devices per mesh axis. ``None`` → all visible devices on one
        data axis (pure DP, matching the reference's only strategy,
        SURVEY.md §2.4).
      mesh_axis_names: logical axis names. Convention: ``data`` (batch/DP),
        ``model`` (TP), ``seq`` (SP/CP). Collectives ride ICI along these axes.
      default_dtype: compute dtype. bfloat16 keeps matmuls on the MXU's native
        path; params stay float32 unless ``param_dtype`` overrides.
      seed: root RNG seed; all layer init / dropout keys derive from it.
    """

    mesh_shape: Optional[Sequence[int]] = None
    mesh_axis_names: Sequence[str] = ("data", "model")
    default_dtype: str = "float32"
    param_dtype: str = "float32"
    seed: int = 0
    log_level: str = "INFO"
    # Input pipeline: number of host-side prefetched batches kept in flight so
    # the mesh is never starved (SURVEY.md §7 hard-part #1).
    prefetch_depth: int = 2
    # Multi-host runtime (the reference's defining capability: BigDL
    # DistriOptimizer over a Spark cluster, wp-bigdl.md:113-160; here:
    # jax.distributed over ICI/DCN). Opt-in: when ``distributed`` is true (or
    # the ZOO_COORDINATOR env var is set), init_nncontext calls
    # jax.distributed.initialize and the mesh spans every process's devices;
    # each process feeds only its local shard of the global batch.
    distributed: bool = False
    coordinator_address: Optional[str] = None   # e.g. "10.0.0.1:8476"
    num_processes: Optional[int] = None
    process_id: Optional[int] = None

    def __post_init__(self):
        # Env tier (the analogue of the reference's executor-env conf,
        # NNContext.scala:132-178 reading executor/node counts): a launcher
        # (mpirun/k8s/GCE metadata script) exports these per process.
        if not self.distributed and os.environ.get("ZOO_COORDINATOR"):
            self.distributed = True
        if self.distributed:
            if self.coordinator_address is None:
                self.coordinator_address = os.environ.get("ZOO_COORDINATOR")
            if self.num_processes is None and os.environ.get("ZOO_NUM_PROCESSES"):
                self.num_processes = int(os.environ["ZOO_NUM_PROCESSES"])
            if self.process_id is None and os.environ.get("ZOO_PROCESS_ID"):
                self.process_id = int(os.environ["ZOO_PROCESS_ID"])

    def replace(self, **kw) -> "ZooConfig":
        """dataclasses.replace-style copy with overrides."""
        return dataclasses.replace(self, **kw)
