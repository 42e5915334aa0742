"""Native-cached FeatureSet — ref feature/pmem (PmemFeatureSet,
pmem/FeatureSet.scala:171) and the memory-type switch of
FeatureSet.rdd(memoryType) (feature/FeatureSet.scala:308).

The reference caches the training set in Optane persistent memory via a JNI
allocator to hold datasets larger than DRAM. TPU-native analogue: samples
live in ONE native mmap arena — anonymous for ``DRAM``, file-backed for
``PMEM``/``DISK`` (page cache spills to disk) — and fixed-shape batches are
assembled by C++ worker threads (native/zoo_native.cpp) into a bounded ring
that stays ahead of the device step loop ("the input pipeline must not
starve the mesh", SURVEY.md §7 hard-part #1).
"""

from __future__ import annotations

import logging
from typing import List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet, FeatureSet

log = logging.getLogger("analytics_zoo_tpu")


class NativeCachedFeatureSet(FeatureSet):
    """Samples cached in a native arena; batches assembled off-thread.

    Components = the flattened list of x arrays then y arrays; each sample's
    record is the concatenation of its components' bytes.
    """

    def __init__(self, x, y=None, memory_type: str = "DRAM",
                 path: Optional[str] = None, n_slots: int = 3,
                 n_threads: int = 2, headroom: float = 1.05):
        from analytics_zoo_tpu import native

        xs = [np.ascontiguousarray(a) for a in (x if isinstance(x, (list, tuple)) else [x])]
        self._multi_x = isinstance(x, (list, tuple))
        ys = ([np.ascontiguousarray(a) for a in (y if isinstance(y, (list, tuple)) else [y])]
              if y is not None else [])
        self._multi_y = isinstance(y, (list, tuple))
        self._n_x = len(xs)
        comps = xs + ys
        n = len(comps[0])
        if any(len(c) != n for c in comps):
            raise ValueError("all components must share dim 0")
        self.comp_shapes = [c.shape[1:] for c in comps]
        self.comp_dtypes = [c.dtype for c in comps]

        mt = memory_type.upper()
        if mt not in ("DRAM", "PMEM", "DISK"):
            raise ValueError(f"memory_type must be DRAM/PMEM/DISK, got {memory_type}")
        self._owned_path = None
        if mt in ("PMEM", "DISK") and path is None:
            import tempfile

            path = tempfile.NamedTemporaryFile(
                prefix="zoo_pmem_", suffix=".bin", delete=False).name
            self._owned_path = path  # unlinked in close()
        total = sum(int(np.prod(c.shape[1:])) * c.dtype.itemsize for c in comps)
        # 64B-per-sample alignment overhead + slack
        cap = int((total + 64) * n * headroom) + (1 << 20)
        self.arena = native.NativeArena(cap, path if mt != "DRAM" else None)
        self.store = native.NativeSampleStore(self.arena)
        rec = np.empty(total, np.uint8)
        for i in range(n):
            off = 0
            for c in comps:
                b = c[i].tobytes()
                rec[off:off + len(b)] = np.frombuffer(b, np.uint8)
                off += len(b)
            self.store.put(rec)
        self._n = n
        self._prefetchers = {}
        self._pf_args = (n_slots, n_threads)
        self.memory_type = mt

    @property
    def num_samples(self) -> int:
        return self._n

    def _split(self, comps: List[np.ndarray]):
        xs, ys = comps[:self._n_x], comps[self._n_x:]
        x = xs if self._multi_x else xs[0]
        if not ys:
            return x, None
        y = ys if self._multi_y else ys[0]
        return x, y

    def take(self, indices: np.ndarray):
        """Random-access gather (eval path) — decode records in Python."""
        outs = [np.empty((len(indices),) + s, d)
                for s, d in zip(self.comp_shapes, self.comp_dtypes)]
        for row, sid in enumerate(indices):
            raw = self.store.get(int(sid))
            off = 0
            for c, (s, d) in enumerate(zip(self.comp_shapes, self.comp_dtypes)):
                nb = int(np.prod(s)) * d.itemsize
                outs[c][row] = np.frombuffer(raw[off:off + nb], d).reshape(s)
                off += nb
        return self._split(outs)

    def _ring_batches(self, batch_size: int, shuffle: bool, seed: int,
                      drop_remainder: bool = False):
        """One epoch out of the native prefetch ring: each batch's components
        as views into the ring's slot, valid until the next step."""
        from analytics_zoo_tpu import native

        pf = self._prefetchers.get(batch_size)
        if pf is None:
            pf = native.NativePrefetcher(
                self.store, self.comp_shapes, self.comp_dtypes, batch_size,
                n_slots=self._pf_args[0], n_threads=self._pf_args[1])
            self._prefetchers[batch_size] = pf
        order = np.arange(self._n, dtype=np.uint64)
        if shuffle:
            np.random.default_rng(seed).shuffle(order)
        yield from pf.epoch(order, drop_remainder=drop_remainder)

    def batches(self, batch_size: int, shuffle: bool = True, seed: int = 0,
                drop_remainder: bool = False):
        """Hot path: batches come out of the native prefetch ring, each the
        caller's own copy to keep (one straight memcpy; the scatter-gather
        assembly stays on the C++ threads)."""
        for comps in self._ring_batches(batch_size, shuffle, seed,
                                        drop_remainder):
            yield self._split([np.array(c) for c in comps])

    def train_batches(self, batch_size: int, shuffle: bool = True, seed: int = 0,
                      borrowed: bool = False):
        """Masked variant on top of the native ring: the C++ assembler
        wrap-pads the tail batch (zoo_native.cpp, same contract as
        FeatureSet.batches), so only the last batch's mask differs.

        ``borrowed=True`` is the lending form: the same batches in the same
        order without the copy, ``x`` and ``y`` as views into the ring's
        slot. The contract is ``NativePrefetcher.epoch``'s: a batch is valid
        until the iterator's next step, which hands its slot back to the C++
        workers to refill. The borrower must be done reading it by then: a
        host-to-device transfer started from it must have finished, and
        where the placed array can alias host memory (``device_put`` on the
        CPU backend does: the slots are 64-byte aligned) the batch has to be
        copied first. ``Estimator.train`` borrows; a caller that may keep
        what it is given leaves the default."""
        tail = self._n % batch_size
        n_batches = -(-self._n // batch_size)
        batches = (map(self._split,
                       self._ring_batches(batch_size, shuffle, seed))
                   if borrowed else self.batches(batch_size, shuffle, seed))
        for b, (x, y) in enumerate(batches):
            mask = np.ones(batch_size, np.float32)
            if tail and b == n_batches - 1:
                mask[tail:] = 0.0
            yield x, y, mask

    def close(self) -> None:
        for pf in self._prefetchers.values():
            pf.close()
        self._prefetchers.clear()
        self.store.close()
        self.arena.close()
        if self._owned_path:
            import contextlib
            import os

            with contextlib.suppress(OSError):
                os.unlink(self._owned_path)
            self._owned_path = None


def cached_feature_set(x, y=None, memory_type: str = "DRAM",
                       **kw) -> FeatureSet:
    """Factory with graceful fallback — ref FeatureSet.rdd(memoryType).

    ``memory_type``: ``DRAM``/``PMEM``/``DISK`` pick the host cache level
    (native arena store when available); ``DEVICE`` caches in accelerator
    HBM with on-device per-batch gather (DeviceCachedFeatureSet) — the
    TPU-native level the reference's hierarchy stops short of.

    Returns a :class:`NativeCachedFeatureSet` when the native runtime is
    available, else a plain :class:`ArrayFeatureSet` (pure Python).
    """
    from analytics_zoo_tpu import native

    if memory_type.upper() == "DEVICE":
        if kw:
            raise TypeError(
                f"memory_type='DEVICE' takes no extra options, got {sorted(kw)} "
                "(n_slots/path/n_threads apply to the native host cache only)")
        return ArrayFeatureSet(x, y).cache_device()
    if native.available():
        try:
            return NativeCachedFeatureSet(x, y, memory_type=memory_type, **kw)
        except MemoryError as e:  # arena sizing problems fall back too
            log.warning("native cache unavailable (%s); using DRAM arrays", e)
    return ArrayFeatureSet(x, y)
