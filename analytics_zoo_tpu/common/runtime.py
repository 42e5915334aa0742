"""What the process runs on: the compile cache's place, the device's
identity and its published peaks.

Three things every entry point (trainer, server, benchmark, smoke run)
must agree on, kept in one module so none of them grows its own copy:

- :func:`configure_compile_cache` — JAX's persistent compilation cache
  is keyed by its directory, so the directory is either what the
  operator placed in ``JAX_COMPILATION_CACHE_DIR`` or one fixed path
  under the checkout. Called at package import, before any compile.
- :func:`device_info` — platform, ``device_kind`` and device count as
  ``jax.devices()`` reports them; the only source of a ``platform``
  label in any printed result.
- :data:`PEAKS` / :func:`device_peaks` — the one peaks table, keyed by
  ``device_kind``. A device that is not in it is an error, not a default.
"""

from __future__ import annotations

import os
from typing import Dict

import jax

#: Fixed cache directory of a checkout: ``<checkout>/.jax_cache``,
#: derived from this file's location (never a temp dir, a pid or a time —
#: a directory that moves never hits).
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> None:
    """Place JAX's persistent compilation cache
    (``jax.config.jax_compilation_cache_dir`` says where it ended up).

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX has already read it into
    that option and nothing is touched. Otherwise the cache goes to
    :data:`CHECKOUT_CACHE_DIR`. Initialises no backend."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)


def device_info() -> Dict[str, object]:
    """``{"platform", "kind", "count"}`` of the devices JAX runs on —
    ``jax.devices()[0].platform``, ``.device_kind`` and
    ``len(jax.devices())``. Initialises the backend if none is up."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


#: Published per-chip peaks, keyed by ``device_kind`` exactly as JAX
#: reports it. Source: Google Cloud documentation, "TPU v5e" system
#: architecture page (197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at
#: 819 GB/s per chip).
PEAKS: Dict[str, Dict[str, float]] = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def device_peaks(device_kind: str) -> Dict[str, float]:
    """The :data:`PEAKS` row for ``device_kind``; raises ``KeyError``
    naming the kind when the table has no entry for it."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}; known: "
            f"{sorted(PEAKS)} — add a sourced row to "
            "analytics_zoo_tpu.common.runtime.PEAKS") from None
