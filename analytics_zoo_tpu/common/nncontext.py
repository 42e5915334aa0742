"""Runtime bring-up: the TPU-native ``init_nncontext()``.

Reference semantics (pyzoo/zoo/common/nncontext.py:21-98 and
NNContext.scala:132-178): one global context, created idempotently under a
lock, that (1) assembles mandatory engine configuration, (2) optionally
verifies versions, (3) initialises the compute engine (BigDL ``Engine.init``
thread pools per executor).

TPU-native inversion (SURVEY.md §3.1): there is no Spark cluster to configure
— "init the engine" means discovering ``jax.devices()``, building the
``jax.sharding.Mesh`` that every subsequent ``fit``/``predict`` is pjit-ted
over, and rooting the deterministic RNG. The Spark conf hacks (shuffle
locality, serializers, KMP pinning) have no analogue and are dropped.
"""

from __future__ import annotations

import logging
import os
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np

from analytics_zoo_tpu.common.config import ZooConfig

logger = logging.getLogger("analytics_zoo_tpu")

_CONTEXT_LOCK = threading.Lock()  # mirrors SparkContext._lock use, nncontext.py:50
_GLOBAL_CONTEXT: Optional["NNContext"] = None


class NNContext:
    """Global runtime context: device mesh + config + root RNG.

    Replaces the (SparkContext, BigDL Engine) pair. Everything downstream —
    the training engine, predictors, the serving runtime — asks this object
    for the mesh and for RNG keys instead of asking Spark for executors.
    """

    def __init__(self, conf: Optional[ZooConfig] = None):
        self.conf = conf or ZooConfig()
        self._configure_logging()
        if self.conf.distributed:
            self._init_distributed()

        # In distributed mode jax.devices() is the GLOBAL device list (every
        # process's chips); the mesh spans all of them and each process
        # executes the same program on its addressable shard — multi-host
        # SPMD, the analogue of BigDL's one-task-per-executor layout
        # (wp-bigdl.md:113-160) with XLA collectives in place of the
        # block-manager AllReduce.
        self.devices = jax.devices()
        self.mesh = self._build_mesh(self.conf.mesh_shape, self.conf.mesh_axis_names)
        self._rng_seed = self.conf.seed
        self._rng_counter = 0
        self._rng_lock = threading.Lock()
        logger.info(
            "Initialized NNContext: %d device(s) [%s], mesh axes %s shape %s"
            "%s",
            len(self.devices),
            self.devices[0].platform,
            self.mesh.axis_names,
            dict(zip(self.mesh.axis_names, self.mesh.devices.shape)),
            (f", process {self.process_index}/{self.process_count}"
             if self.process_count > 1 else ""),
        )

    def _init_distributed(self):
        """Join the multi-process runtime (ref NNContext.scala:132-178 reads
        executor/node counts from the cluster manager; here the coordinator
        address + process rank come from config/env and
        ``jax.distributed.initialize`` wires the processes together)."""
        if jax.distributed.is_initialized():
            logger.info("jax.distributed already initialized; reusing")
            return
        kw = {}
        if self.conf.coordinator_address:
            kw["coordinator_address"] = self.conf.coordinator_address
        if self.conf.num_processes is not None:
            kw["num_processes"] = self.conf.num_processes
        if self.conf.process_id is not None:
            kw["process_id"] = self.conf.process_id
        logger.info("Joining distributed runtime: %s", kw or "(auto-detect)")
        jax.distributed.initialize(**kw)

    # -- engine bring-up -------------------------------------------------

    def _build_mesh(self, mesh_shape, axis_names) -> jax.sharding.Mesh:
        n = len(self.devices)
        if mesh_shape is None:
            # Default: every chip on the data axis; trailing axes size-1 so
            # shardings written for (data, model) meshes work unchanged.
            mesh_shape = (n,) + (1,) * (len(axis_names) - 1)
        mesh_shape = tuple(mesh_shape)
        if int(np.prod(mesh_shape)) != n:
            raise ValueError(
                f"mesh_shape {mesh_shape} needs {np.prod(mesh_shape)} devices, "
                f"have {n}"
            )
        dev_array = np.asarray(self.devices).reshape(mesh_shape)
        return jax.sharding.Mesh(dev_array, tuple(axis_names))

    def _configure_logging(self):
        # Analogue of LoggerFilter.redirectSparkInfoLogs (Topology.scala:132):
        # keep framework logs readable by default.
        level = getattr(logging, self.conf.log_level.upper(), logging.INFO)
        logger.setLevel(level)
        if not logger.handlers:
            h = logging.StreamHandler()
            h.setFormatter(
                logging.Formatter("%(asctime)s %(name)s %(levelname)s %(message)s")
            )
            logger.addHandler(h)

    # -- properties ------------------------------------------------------

    @property
    def num_devices(self) -> int:
        """Global device count across all processes."""
        return len(self.devices)

    @property
    def data_axis(self) -> str:
        """Name of the mesh axis batches shard over (first axis name)."""
        return self.mesh.axis_names[0]

    @property
    def platform(self) -> str:
        """Backend platform string (tpu / cpu / gpu)."""
        return self.devices[0].platform

    # -- multi-host topology ---------------------------------------------

    @property
    def process_count(self) -> int:
        """Number of host processes in the cluster (1 single-host)."""
        return jax.process_count()

    @property
    def process_index(self) -> int:
        """This process's rank in the cluster."""
        return jax.process_index()

    @property
    def local_devices(self):
        """Devices addressable by THIS process."""
        return jax.local_devices()

    def local_batch_window(self, batch_size: int):
        """This process's contiguous row range [lo, hi) of a global batch.

        The global batch contract becomes per-process in multi-host mode:
        every process computes the same deterministic batch order (a function
        of seed and dataset size), then materializes only these rows — its
        addressable shard of the batch-sharded global array. Returns None in
        single-process mode (feed the whole batch).
        """
        pc = self.process_count
        if pc <= 1:
            return None
        if batch_size % pc != 0:
            raise ValueError(
                f"global batch {batch_size} must divide across {pc} processes")
        per = batch_size // pc
        lo = self.process_index * per
        return (lo, lo + per)

    # -- RNG -------------------------------------------------------------

    def next_rng_key(self) -> jax.Array:
        """Deterministic stream of fresh keys (root seed + fold-in counter)."""
        with self._rng_lock:
            self._rng_counter += 1
            c = self._rng_counter
        return jax.random.fold_in(jax.random.PRNGKey(self._rng_seed), c)

    def next_rng_keys(self, k: int) -> jax.Array:
        """``k`` consecutive stream keys as one ``(k, ...)`` array —
        value-identical to ``k`` ``next_rng_key()`` calls (same counters,
        same fold-in) but computed in ONE vmapped dispatch instead of ``k``
        serialized ones (the chunked train path feeds hundreds per epoch;
        pinned equal in tests/test_scan_dispatch.py)."""
        import jax.numpy as jnp

        with self._rng_lock:
            start = self._rng_counter + 1
            self._rng_counter += k
        root = jax.random.PRNGKey(self._rng_seed)
        return jax.vmap(lambda c: jax.random.fold_in(root, c))(
            jnp.arange(start, start + k))

    def rng_state(self) -> Tuple[int, int]:
        """``(seed, counter)`` — the full position of the deterministic key
        stream. Checkpointed so a resumed run's dropout/shuffle keys
        continue EXACTLY where the interrupted run's stopped (the bitwise
        kill/resume contract, docs/fault-tolerance.md)."""
        with self._rng_lock:
            return (self._rng_seed, self._rng_counter)

    def set_rng_state(self, seed: int, counter: int) -> None:
        """Restore a :meth:`rng_state` snapshot (checkpoint resume)."""
        with self._rng_lock:
            self._rng_seed = int(seed)
            self._rng_counter = int(counter)


def init_nncontext(
    conf: Optional[ZooConfig] = None,
    cluster_mode: str = "local",
    **kwargs,
) -> NNContext:
    """Create (or fetch) the global :class:`NNContext`.

    Mirrors ``zoo.common.nncontext.init_nncontext`` (nncontext.py:21-40):
    idempotent, lock-guarded, returns the one global context. ``cluster_mode``
    is accepted for API parity; on TPU, topology comes from the runtime
    (``jax.devices()``), not from a resource manager.

    Extra ``kwargs`` override :class:`ZooConfig` fields, e.g.
    ``init_nncontext(mesh_shape=(4, 2))``.
    """
    global _GLOBAL_CONTEXT
    with _CONTEXT_LOCK:
        if _GLOBAL_CONTEXT is not None:
            if conf is not None or kwargs:
                logger.warning(
                    "init_nncontext called again; returning existing context "
                    "(new conf ignored)"
                )
            return _GLOBAL_CONTEXT
        if conf is None:
            conf = ZooConfig(**kwargs)
        elif kwargs:
            conf = conf.replace(**kwargs)
        _GLOBAL_CONTEXT = NNContext(conf)
        return _GLOBAL_CONTEXT


def get_nncontext() -> NNContext:
    """Return the global context, creating a default one if needed."""
    if _GLOBAL_CONTEXT is None:
        return init_nncontext()
    return _GLOBAL_CONTEXT


def stop_nncontext() -> None:
    """Drop the global context (mainly for tests)."""
    global _GLOBAL_CONTEXT
    with _CONTEXT_LOCK:
        _GLOBAL_CONTEXT = None
