"""ctypes bindings for the native host-data-path runtime (native/zoo_native.cpp).

Ref parity (SURVEY.md §2.3 item 4): the reference's PersistentMemoryAllocator
JNI façade (initialize/allocate/free/copy) backing PmemFeatureSet. Here the
native library provides the arena/store/prefetcher trio; pybind11 is not in
the image, so the ABI is plain C consumed via ctypes.

In a checkout the library always goes through ``make -C native`` the first
time a process needs it: make is incremental (free when the ``.so`` is
current), so a git-ignored binary can never be older than the tracked source
it was built from. An installed wheel ships the ``.so`` without the sources
and loads it as is. When the build or the load fails, ``available()`` is
False, the failure is logged as an error with the compiler's output, and the
pure-Python data paths keep working.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import List, Optional, Sequence

import numpy as np

log = logging.getLogger("analytics_zoo_tpu")

_LIB_NAME = "libzoo_native.so"
_lib = None
_lib_lock = threading.Lock()
_load_failed = False


def _repo_native_dir() -> str:
    # analytics_zoo_tpu/native/ -> repo root /native
    pkg = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    return os.path.join(os.path.dirname(pkg), "native")


def _bind(lib) -> None:
    u64, i64, p = ctypes.c_uint64, ctypes.c_int64, ctypes.c_void_p
    lib.zoo_arena_create.restype = p
    lib.zoo_arena_create.argtypes = [u64, ctypes.c_char_p]
    lib.zoo_arena_alloc.restype = u64
    lib.zoo_arena_alloc.argtypes = [p, u64]
    lib.zoo_arena_base.restype = p
    lib.zoo_arena_base.argtypes = [p]
    lib.zoo_arena_used.restype = u64
    lib.zoo_arena_used.argtypes = [p]
    lib.zoo_arena_capacity.restype = u64
    lib.zoo_arena_capacity.argtypes = [p]
    lib.zoo_arena_destroy.argtypes = [p]
    lib.zoo_copy.argtypes = [p, p, u64]
    lib.zoo_store_create.restype = p
    lib.zoo_store_create.argtypes = [p]
    lib.zoo_store_put.restype = u64
    lib.zoo_store_put.argtypes = [p, p, u64]
    lib.zoo_store_count.restype = u64
    lib.zoo_store_count.argtypes = [p]
    lib.zoo_store_get.restype = p
    lib.zoo_store_get.argtypes = [p, u64, ctypes.POINTER(u64)]
    lib.zoo_store_destroy.argtypes = [p]
    lib.zoo_prefetcher_create.restype = p
    lib.zoo_prefetcher_create.argtypes = [
        p, ctypes.POINTER(u64), ctypes.c_int, u64, ctypes.c_int, ctypes.c_int]
    lib.zoo_prefetcher_start_epoch.argtypes = [p, ctypes.POINTER(u64), u64, i64]
    lib.zoo_prefetcher_next.restype = ctypes.c_int
    lib.zoo_prefetcher_next.argtypes = [p]
    lib.zoo_prefetcher_slot_ptr.restype = p
    lib.zoo_prefetcher_slot_ptr.argtypes = [p, ctypes.c_int]
    lib.zoo_prefetcher_release.argtypes = [p]
    lib.zoo_prefetcher_destroy.argtypes = [p]
    lib.zoo_native_version.restype = ctypes.c_int


def _build() -> None:
    """``make -C native`` where the sources are present (a checkout);
    callers hold ``_lib_lock``."""
    src = _repo_native_dir()
    if os.path.isdir(src):
        subprocess.run(["make", "-C", src], check=True, capture_output=True,
                       text=True, timeout=120)


def ensure_lib(lib_name: str) -> str:
    """Bring ``lib_name`` up to date with its source (see module docstring)
    and return its path inside the package — shared by all native
    components. Raises if the build fails or does not produce the library."""
    so = os.path.join(os.path.dirname(os.path.abspath(__file__)), lib_name)
    with _lib_lock:
        _build()
    if not os.path.exists(so):
        raise FileNotFoundError(
            f"{lib_name} not found and not produced by make — is "
            f"native/Makefile's target list current?")
    return so


def _load():
    global _lib, _load_failed
    if _lib is not None or _load_failed:
        return _lib
    with _lib_lock:
        if _lib is not None or _load_failed:
            return _lib
        so = os.path.join(os.path.dirname(os.path.abspath(__file__)), _LIB_NAME)
        try:
            _build()
            lib = ctypes.CDLL(so)
            _bind(lib)
            ver = lib.zoo_native_version()
            if ver != 1:  # not assert: must survive python -O
                raise OSError(f"libzoo_native ABI {ver} != expected 1")
            _lib = lib
        except (OSError, subprocess.SubprocessError) as e:
            log.error("native runtime unavailable (%s)%s; the pure-Python "
                      "data path will feed instead", e,
                      f"\n{e.stderr[-2000:]}"
                      if getattr(e, "stderr", None) else "")
            _load_failed = True
    return _lib


def available() -> bool:
    return _load() is not None


class NativeArena:
    """mmap arena — anonymous (DRAM) or file-backed ("PMEM" analogue)."""

    def __init__(self, capacity: int, path: Optional[str] = None):
        lib = _load()
        if lib is None:
            raise RuntimeError("native runtime unavailable")
        self._lib = lib
        self._h = lib.zoo_arena_create(
            int(capacity), path.encode() if path else None)
        if not self._h:
            raise MemoryError(f"arena create failed (capacity={capacity})")

    @property
    def used(self) -> int:
        return self._lib.zoo_arena_used(self._h)

    @property
    def capacity(self) -> int:
        return self._lib.zoo_arena_capacity(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.zoo_arena_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativeSampleStore:
    """Variable-size sample records indexed in an arena."""

    def __init__(self, arena: NativeArena):
        self._lib = arena._lib
        self.arena = arena
        self._h = self._lib.zoo_store_create(arena._h)

    def put(self, data: np.ndarray) -> int:
        data = np.ascontiguousarray(data)
        sid = self._lib.zoo_store_put(
            self._h, data.ctypes.data_as(ctypes.c_void_p), data.nbytes)
        if sid == 2 ** 64 - 1:
            raise MemoryError("sample store arena full")
        return sid

    def __len__(self) -> int:
        return self._lib.zoo_store_count(self._h)

    def get(self, sid: int) -> np.ndarray:
        size = ctypes.c_uint64()
        ptr = self._lib.zoo_store_get(self._h, int(sid), ctypes.byref(size))
        if not ptr:
            raise IndexError(sid)
        buf = (ctypes.c_uint8 * size.value).from_address(ptr)
        return np.frombuffer(buf, dtype=np.uint8).copy()

    def close(self) -> None:
        if self._h:
            self._lib.zoo_store_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


class NativePrefetcher:
    """Background batch assembly: C++ worker threads gather samples into a
    bounded ring of batch slots; iteration yields per-component numpy views.

    The store must be frozen (no further ``put``) while a prefetcher built
    on it is live — workers read the index without locks.
    """

    def __init__(self, store: NativeSampleStore,
                 comp_shapes: Sequence[tuple], comp_dtypes: Sequence,
                 batch_size: int, n_slots: int = 3, n_threads: int = 2):
        self._lib = store._lib
        self.store = store
        self.comp_shapes = [tuple(int(d) for d in s) for s in comp_shapes]
        self.comp_dtypes = [np.dtype(d) for d in comp_dtypes]
        self.comp_bytes = [
            int(np.prod(s)) * d.itemsize
            for s, d in zip(self.comp_shapes, self.comp_dtypes)]
        self.batch_size = int(batch_size)
        sizes = (ctypes.c_uint64 * len(self.comp_bytes))(*self.comp_bytes)
        self._h = self._lib.zoo_prefetcher_create(
            store._h, sizes, len(self.comp_bytes), self.batch_size,
            int(n_slots), int(n_threads))
        if not self._h:
            raise MemoryError("prefetcher create failed")

    def epoch(self, order: np.ndarray, drop_remainder: bool = False):
        """Iterate one epoch of batches over ``order`` (sample ids).

        Yields a list of per-component numpy arrays (views into the slot —
        valid until the next iteration step)."""
        order = np.ascontiguousarray(order, dtype=np.uint64)
        n = len(order)
        if drop_remainder:
            n_batches = n // self.batch_size
        else:
            n_batches = (n + self.batch_size - 1) // self.batch_size
        self._lib.zoo_prefetcher_start_epoch(
            self._h, order.ctypes.data_as(ctypes.POINTER(ctypes.c_uint64)),
            n, n_batches)
        while True:
            slot = self._lib.zoo_prefetcher_next(self._h)
            if slot < 0:
                return
            ptr = self._lib.zoo_prefetcher_slot_ptr(self._h, slot)
            comps, off = [], 0
            for shape, dtype, nbytes in zip(self.comp_shapes, self.comp_dtypes,
                                            self.comp_bytes):
                block = (ctypes.c_uint8 * (nbytes * self.batch_size)
                         ).from_address(ptr + off)
                arr = np.frombuffer(block, dtype=dtype).reshape(
                    (self.batch_size,) + shape)
                comps.append(arr)
                off += nbytes * self.batch_size
            yield comps
            self._lib.zoo_prefetcher_release(self._h)

    def close(self) -> None:
        if self._h:
            self._lib.zoo_prefetcher_destroy(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
