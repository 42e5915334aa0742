"""Persistent AOT executable cache — warm restarts skip the compile storm.

Serving warmup AOT-compiles one executable per bucket shape
(:meth:`~analytics_zoo_tpu.inference.inference_model.InferenceModel
.do_optimize`); on every process restart and every
:mod:`~analytics_zoo_tpu.ft.hot_reload` version swap that work is redone
from scratch, and for real models the compile storm dominates
time-to-first-predict. XLA executables are serializable
(``jax.experimental.serialize_executable`` — the orbax-export / AOT
persistence line of work in PAPERS.md), so this module keeps them on
disk:

- **Key**: SHA-256 over the *lowered HLO text* plus the jax / jaxlib
  versions, the backend platform and the mesh fingerprint (device
  count + axis names/lengths + sharding declarations for mesh-parallel
  executables; a single-device sentinel otherwise — see
  :meth:`AotExecutableCache.key_for`). The HLO is weight-independent
  (parameters are runtime arguments), so a hot-reloaded checkpoint with
  identical architecture and shapes hits the same entry — exactly the
  case where recompiling is pure waste. Any change to the model
  structure, input shapes/dtypes, quantization mode, mesh topology or
  toolchain versions changes the HLO or a salt and therefore the key:
  a mismatch is a clean miss, never a wrong executable.
- **Write**: atomic (``tmp`` + ``os.replace``) so a crash mid-store can
  never leave a torn entry that poisons later loads.
- **Read**: *any* failure — unpicklable bytes, a truncated file, a
  deserialization error from a different runtime — is caught, counted
  (``zoo_serving_aot_cache_events_total{event="errors"}``) and treated
  as a miss; the caller recompiles. A corrupted cache can cost time,
  never correctness.

Metrics: ``zoo_serving_aot_cache_events_total{event}`` with events
``hits`` / ``misses`` / ``stores`` / ``errors`` in the process-global
registry (scraped through ``GET /metrics``). Paired with
``zoo_compile_total``, a warm restart is provable: cache hits go up,
backend compiles stay at zero.

Enable per model (``InferenceModel(aot_cache_dir=...)`` /
``set_aot_cache``) or process-wide via the ``AZOO_AOT_CACHE_DIR``
environment variable. See docs/serving.md ("Performance tuning").
"""

from __future__ import annotations

import hashlib
import json
import logging
import os
import pickle
import tempfile
from typing import Any, Dict, List, Optional

logger = logging.getLogger("analytics_zoo_tpu")

__all__ = ["AotExecutableCache"]

#: Environment variable naming a process-wide cache directory picked up
#: by every ``InferenceModel`` constructed without an explicit dir.
ENV_VAR = "AZOO_AOT_CACHE_DIR"

_SUFFIX = ".zxc"  # zoo xla executable, pickled (payload, in_tree, out_tree)
_META_SUFFIX = ".meta.json"  # optional human-readable sidecar per entry


class AotExecutableCache:
    """Disk cache of serialized XLA executables under ``directory``.

    One file per entry, named ``<sha256 key>.zxc``. Thread-safe by
    construction: keys are content-addressed and writes are atomic
    renames, so concurrent warmups of the same model race benignly
    (last writer wins with identical bytes)."""

    def __init__(self, directory: str):
        self.directory = str(directory)
        os.makedirs(self.directory, exist_ok=True)

    # -- keying -----------------------------------------------------------

    @staticmethod
    def key_for(lowered, args_structure: str = "",
                mesh_fingerprint: str = "", variant: str = "",
                stage: str = "") -> str:
        """Content key for a ``jax.stages.Lowered``: HLO text + jax /
        jaxlib versions + backend platform + the caller's argument
        pytree structure + the mesh fingerprint. Weight values do not
        enter the key (they are arguments), so hot-reloaded checkpoints
        of the same architecture share the entry. ``args_structure`` (a
        ``tree_structure`` repr) must be part of the key because the
        serialized executable embeds the input pytree: two models can
        lower to byte-identical HLO yet flatten their parameters under
        different dict keys, and feeding one the other's executable
        fails at call time — with the structure salted in, that pair is
        a clean miss instead.

        ``mesh_fingerprint`` names the device topology the executable
        was partitioned for — device count, axis names/lengths and the
        in/out sharding declarations (a
        :meth:`~analytics_zoo_tpu.mesh.plan.ShardingPlan.fingerprint`
        string). A serialized executable embeds concrete device
        assignments, so a 1-device and an 8-device build of the *same*
        HLO are different artifacts and must never cross-hit. Callers
        lowering without shardings pass the default ``""``, hashed as a
        distinct single-device sentinel (deliberately NOT
        ``jax.device_count()`` — an unsharded jit compiles for one
        device regardless of how many the host exposes, and salting the
        host's device count in would turn identical single-device
        entries into spurious cross-environment misses).

        ``variant`` is an explicit execution-variant salt (ISSUE 16):
        the int8 weight-quantized build of a bucket passes ``"int8"``
        here so its entries can never cross-hit the f32 build's, even
        if a future lowering folded the dequantize ops into HLO the two
        variants share. The default ``""`` (the f32/unquantized build)
        hashes to exactly the pre-ISSUE-16 key, so existing caches stay
        warm across the upgrade.

        ``stage`` is the pipeline-stage salt: a stage-split serving
        model compiles one executable per (bucket, mesh, stage) cell,
        and two stages of one model can lower to identical HLO over the
        identical argument structure (equal-width segments see the same
        shapes) — without the salt they would cross-hit and one stage
        would serve another's program. Like ``variant``, the default
        ``""`` (unstaged) hashes to exactly the prior key, keeping
        existing caches warm."""
        import jax
        import jaxlib

        h = hashlib.sha256()
        h.update(jax.__version__.encode())
        h.update(jaxlib.__version__.encode())
        h.update(jax.default_backend().encode())
        h.update(args_structure.encode())
        h.update((mesh_fingerprint or "single-device").encode())
        if variant:
            h.update(b"variant:" + variant.encode())
        if stage != "":
            h.update(b"stage:" + str(stage).encode())
        h.update(lowered.as_text().encode())
        return h.hexdigest()

    def _path(self, key: str) -> str:
        return os.path.join(self.directory, key + _SUFFIX)

    # -- load / store -----------------------------------------------------

    def load(self, key: str) -> Optional[Any]:
        """Deserialize and load the executable for ``key``, or None on a
        miss or *any* failure (corrupt bytes, incompatible runtime — the
        caller recompiles; counted under ``event="errors"``)."""
        from analytics_zoo_tpu.common.observability import (
            aot_cache_counters,
        )

        counters = aot_cache_counters()
        path = self._path(key)
        if not os.path.exists(path):
            counters["misses"].inc()
            return None
        try:
            from jax.experimental import serialize_executable

            with open(path, "rb") as f:
                payload, in_tree, out_tree = pickle.load(f)
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree)
        except Exception as e:  # noqa: BLE001 — a bad entry is a miss
            counters["errors"].inc()
            logger.warning(
                "AOT cache entry %s unusable (%s: %s) — recompiling",
                path, type(e).__name__, e)
            return None
        counters["hits"].inc()
        return compiled

    def store(self, key: str, compiled,
              meta: Optional[Dict[str, Any]] = None) -> bool:
        """Serialize ``compiled`` to the cache (atomic write). Returns
        True on success; failures are logged + counted, never raised —
        an unwritable cache degrades to cold-start behavior.

        ``meta`` (optional, JSON-able) is written to a ``<key>.meta.json``
        sidecar — purely descriptive (bucket shapes, mesh fingerprint,
        quantization variant) so ``scripts/aot_inspect.py --list`` can
        name entries without reading SHA-256s. Sidecars never affect
        load: a missing or torn sidecar costs a ``-`` in the listing,
        never a cache miss."""
        from analytics_zoo_tpu.common.observability import (
            aot_cache_counters,
        )

        counters = aot_cache_counters()
        try:
            from jax.experimental import serialize_executable

            payload, in_tree, out_tree = serialize_executable.serialize(
                compiled)
            blob = pickle.dumps((payload, in_tree, out_tree),
                                protocol=pickle.HIGHEST_PROTOCOL)
            fd, tmp = tempfile.mkstemp(dir=self.directory,
                                       suffix=_SUFFIX + ".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._path(key))
            except BaseException:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:  # noqa: BLE001 — caching is best-effort
            counters["errors"].inc()
            logger.warning(
                "failed to persist AOT executable %s (%s: %s)",
                key[:12], type(e).__name__, e)
            return False
        if meta is not None:
            try:
                fd, tmp = tempfile.mkstemp(dir=self.directory,
                                           suffix=_META_SUFFIX + ".tmp")
                with os.fdopen(fd, "w") as f:
                    json.dump(meta, f, sort_keys=True)
                os.replace(tmp,
                           os.path.join(self.directory, key + _META_SUFFIX))
            except Exception as e:  # noqa: BLE001 — sidecars are cosmetic
                logger.debug("failed to write AOT meta sidecar for %s "
                             "(%s: %s)", key[:12], type(e).__name__, e)
        counters["stores"].inc()
        return True

    # -- introspection -----------------------------------------------------

    def entries(self) -> List[Dict[str, Any]]:
        """Describe every cached executable: ``{"key", "bytes", "meta"}``
        per ``.zxc`` file, sorted by key. ``meta`` is the parsed sidecar
        dict or None for legacy entries without one (or with a torn
        sidecar — introspection never raises). The read surface behind
        ``scripts/aot_inspect.py``."""
        out: List[Dict[str, Any]] = []
        try:
            names = sorted(os.listdir(self.directory))
        except OSError:
            return out
        for fname in names:
            if not fname.endswith(_SUFFIX):
                continue
            key = fname[:-len(_SUFFIX)]
            path = os.path.join(self.directory, fname)
            try:
                size = os.path.getsize(path)
            except OSError:
                continue  # raced a concurrent eviction/replace
            meta = None
            try:
                with open(os.path.join(self.directory,
                                       key + _META_SUFFIX)) as f:
                    meta = json.load(f)
            except (OSError, ValueError):
                meta = None
            out.append({"key": key, "bytes": size, "meta": meta})
        return out
