"""FeatureSet — host-side dataset abstraction feeding the device mesh.

Ref: feature/FeatureSet.scala (DistributedFeatureSet:103,
CachedDistributedFeatureSet:216, DRAMFeatureSet:298) — a cached RDD with a
memory-type choice (DRAM vs PMEM) iterated by the optimizer. TPU-native
inversion: the dataset is host memory (optionally memory-mapped — the PMEM
analogue, SURVEY.md §2.3 item 4) producing *statically-shaped* per-step
batches sharded over the mesh's data axis.

Batching contract (ref tf_dataset.py:134-139: batch must divide by total
cores): here batches are wrap-padded up to ``batch_size`` so every XLA
program sees one shape; training shuffles each epoch with a deterministic
per-epoch seed; eval carries a validity mask so padding never biases metrics.
"""

from __future__ import annotations

from typing import Any, Callable, Iterator, List, Optional, Sequence, Tuple, Union

import numpy as np

ArrayLike = Union[np.ndarray, Sequence[np.ndarray]]


def _as_arrays(x) -> List[np.ndarray]:
    if isinstance(x, (list, tuple)):
        return [np.asarray(a) for a in x]
    return [np.asarray(x)]


class FeatureSet:
    """Base interface: ``batches`` for training, ``eval_batches`` for
    evaluation/prediction. Subclasses provide indexing into samples.

    ``device_transform`` (optional) is a jittable per-batch function applied
    to ``x`` ON DEVICE, inside the training/eval/predict step. Host batches
    then travel the host→device link in their raw dtype — e.g. uint8 images
    at 1/4 the bytes of pre-normalized f32 — and the transform (cast +
    normalize) fuses into the compiled step. This is the TPU-first inversion
    of the reference's host-side ChannelNormalize (feature/image/
    ChannelNormalize.scala): on TPU the infeed link is the scarce resource,
    the VPU cast is free. See ImageSet.to_feature_set(device_normalize=True).
    """

    device_transform = None

    @property
    def num_samples(self) -> int:
        """Number of samples in the dataset."""
        raise NotImplementedError

    def take(self, indices: np.ndarray) -> Tuple[Any, Any]:
        """Gather (x, y) for integer indices; x may be a list of arrays."""
        raise NotImplementedError

    # -- index-batch generators (shared batching/wrap-pad/mask logic) ----

    def steps_per_epoch(self, batch_size: int) -> int:
        """How many batches one epoch yields (row-sharded caches override:
        their epoch length is per-shard, not global)."""
        return -(-self.num_samples // batch_size)

    def train_index_batches(self, batch_size: int, shuffle: bool = True,
                            seed: int = 0, start_step: int = 0
                            ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Yield (indices, mask) per training batch. The tail batch is
        wrap-padded (modulo) to keep the jitted step's shapes static; the
        mask zero-weights the duplicates (the reference instead requires
        exact division, tf_dataset.py:134-139).

        ``start_step`` skips the first N batches WITHOUT materializing
        them — the crash-recovery iterator offset: the epoch order is a
        pure function of ``(seed, num_samples)``, so a resumed run
        re-derives the interrupted epoch's order and continues at exactly
        the batch the checkpoint recorded (docs/fault-tolerance.md)."""
        n = self.num_samples
        order = np.arange(n)
        if shuffle:
            np.random.default_rng(seed).shuffle(order)
        full_mask = np.ones(batch_size, dtype=np.float32)
        for start in range(start_step * batch_size, n, batch_size):
            idx = order[start:start + batch_size]
            valid = len(idx)
            if valid == 0:
                return
            mask = full_mask
            if valid < batch_size:
                idx = np.concatenate(
                    [idx, order[np.arange(batch_size - valid) % n]])
                mask = np.zeros(batch_size, dtype=np.float32)
                mask[:valid] = 1.0
            yield idx, mask

    def eval_index_batches(self, batch_size: int
                           ) -> Iterator[Tuple[np.ndarray, np.ndarray]]:
        """Deterministic-order (indices, mask) with wrap-padding masked out."""
        n = self.num_samples
        for start in range(0, n, batch_size):
            idx = np.arange(start, min(start + batch_size, n))
            valid = len(idx)
            if valid < batch_size:
                idx = np.concatenate([idx, np.arange(batch_size - valid) % n])
            mask = np.zeros(batch_size, dtype=np.float32)
            mask[:valid] = 1.0
            yield idx, mask

    def batches(self, batch_size: int, shuffle: bool = True,
                seed: int = 0, drop_remainder: bool = False,
                window: Optional[Tuple[int, int]] = None,
                start_step: int = 0
                ) -> Iterator[Tuple[Any, Any]]:
        """``window=(lo, hi)`` keeps only those rows of each global batch —
        the multi-host contract: every process iterates the same
        deterministic global batch order (a function of seed and n) but
        materializes/decodes ONLY its local rows
        (``NNContext.local_batch_window``). ``start_step`` skips the first
        N batches without materializing them (mid-epoch resume)."""
        n = self.num_samples
        order = np.arange(n)
        if shuffle:
            np.random.default_rng(seed).shuffle(order)
        for start in range(start_step * batch_size, n, batch_size):
            idx = order[start:start + batch_size]
            if len(idx) < batch_size:
                if drop_remainder or len(idx) == 0:
                    return
                # wrap-pad (modulo, so tiny datasets still fill the batch)
                # to keep the jitted step's shapes static
                pad = order[np.arange(batch_size - len(idx)) % n]
                idx = np.concatenate([idx, pad])
            if window is not None:
                idx = idx[window[0]:window[1]]
            yield self.take(idx)

    def train_batches(self, batch_size: int, shuffle: bool = True,
                      seed: int = 0,
                      window: Optional[Tuple[int, int]] = None,
                      start_step: int = 0
                      ) -> Iterator[Tuple[Any, Any, np.ndarray]]:
        """Training batches WITH a validity mask over the wrap-padding.
        ``window`` slices each global batch to this process's rows BEFORE
        ``take`` (no host loads rows it doesn't own); ``start_step`` skips
        already-consumed batches on a mid-epoch resume (no ``take`` for
        the skipped ones)."""
        for idx, mask in self.train_index_batches(batch_size, shuffle, seed,
                                                  start_step=start_step):
            if window is not None:
                idx, mask = idx[window[0]:window[1]], mask[window[0]:window[1]]
            x, y = self.take(idx)
            yield x, y, mask

    def eval_batches(self, batch_size: int,
                     window: Optional[Tuple[int, int]] = None
                     ) -> Iterator[Tuple[Any, Any, np.ndarray]]:
        """Deterministic order; yields (x, y, mask) with wrap-padding masked out."""
        for idx, mask in self.eval_index_batches(batch_size):
            if window is not None:
                idx, mask = idx[window[0]:window[1]], mask[window[0]:window[1]]
            x, y = self.take(idx)
            yield x, y, mask

    # -- transforms (ref Preprocessing `->` chaining) --------------------

    def transform(self, fn: Callable) -> "TransformedFeatureSet":
        """Chain a jittable per-batch transform; returns a TransformedFeatureSet.
        """
        return TransformedFeatureSet(self, fn)

    __rshift__ = transform


class ArrayFeatureSet(FeatureSet):
    """In-memory ndarray-backed dataset (the ``DRAMFeatureSet`` analogue).

    ``x`` may be one array or a list (multi-input models); ``y`` may be None
    for prediction-only sets.
    """

    def __init__(self, x: ArrayLike, y: Optional[ArrayLike] = None):
        self.xs = _as_arrays(x)
        self._multi_x = isinstance(x, (list, tuple))
        self.ys = _as_arrays(y) if y is not None else None
        self._multi_y = isinstance(y, (list, tuple)) if y is not None else False
        n = len(self.xs[0])
        for a in self.xs + (self.ys or []):
            if len(a) != n:
                raise ValueError("All arrays must share dim 0 "
                                 f"({len(a)} vs {n})")

    @property
    def num_samples(self) -> int:
        return len(self.xs[0])

    def take(self, indices: np.ndarray):
        xs = [a[indices] for a in self.xs]
        x = xs if self._multi_x else xs[0]
        if self.ys is None:
            return x, None
        ys = [a[indices] for a in self.ys]
        y = ys if self._multi_y else ys[0]
        return x, y

    @staticmethod
    def from_ndarrays(x, y=None) -> "ArrayFeatureSet":
        """Build from (x, y) ndarrays / lists of ndarrays."""
        return ArrayFeatureSet(x, y)

    def cache_device(self, shard_rows: Optional[bool] = None
                     ) -> "DeviceCachedFeatureSet":
        """Move the whole dataset into device memory (HBM) — see
        DeviceCachedFeatureSet. ``shard_rows=True`` shards the cache rows
        across the data axis instead of replicating (automatic in
        multi-host runs)."""
        fs = DeviceCachedFeatureSet(self.xs if self._multi_x else self.xs[0],
                                    (self.ys if self._multi_y else self.ys[0])
                                    if self.ys is not None else None,
                                    shard_rows=shard_rows)
        fs.device_transform = self.device_transform
        return fs


class DeviceCachedFeatureSet(ArrayFeatureSet):
    """Dataset cached in device HBM; per-batch gather runs ON DEVICE.

    The reference's FeatureSet picks a cache memory type per executor —
    DRAM or Optane PMem (feature/FeatureSet.scala:216,298, feature/pmem/).
    The TPU-native memory hierarchy adds a level above both: HBM. The
    host↔device link is orders of magnitude slower than HBM (819 GB/s on a
    v5e chip), and PCIe/DMA infeed is the classic input-pipeline ceiling: a
    256×224² f32 batch is 154 MB on the wire but a gather when it already
    lives in HBM. Keep the dataset resident (uint8 pixels stay
    uint8 — pair with ``device_transform`` for on-device normalize) and only
    a ~KB index vector crosses the wire per step.

    Two cache layouts:

    - **Replicated** (single-host default): every device holds the full
      dataset and gathers its batch shard from its replica. Fastest per
      step, but the dataset must fit one device's HBM.
    - **Row-sharded** (``shard_rows=True``; automatic in multi-host runs):
      device ``k`` of the ``d``-way data axis holds rows
      ``[k·R, (k+1)·R)`` (R = ceil(n/d), wrap-padded) and each step
      gathers its batch shard FROM ITS OWN ROWS via a ``shard_map`` local
      gather — no cross-device collective, no host materializing rows it
      doesn't own. This is the TPU-native form of the reference's
      per-executor cache (feature/FeatureSet.scala:216,298): samples live
      where they train, and the shuffle is per-shard (each device permutes
      its own rows per epoch), exactly like the reference sampling within
      each executor's cached partition. Capacity scales with the device
      count instead of being bounded by one device.

    ``take`` returns device arrays (replicated mode) or host gathers
    (sharded mode — the host copy is kept for order-preserving predict).
    """

    #: When True (default) the engine may run whole epochs in one compiled
    #: dispatch with the shuffle computed ON DEVICE (one RNG-key upload per
    #: epoch instead of an index matrix per chunk). The permutation is
    #: still seed-deterministic but its batch order differs from the host
    #: shuffle; set False to keep the host-identical order.
    device_shuffle = True

    def __init__(self, x: ArrayLike, y: Optional[ArrayLike] = None,
                 shard_rows: Optional[bool] = None):
        super().__init__(x, y)
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        from analytics_zoo_tpu.common.nncontext import get_nncontext

        explicit = shard_rows is not None
        if shard_rows is None:
            # Multi-host: a replicated device_put would span non-addressable
            # devices — shard the rows per host instead (the reference's
            # per-executor cache). Single-host defaults to the replicated
            # layout (measured fastest; docs/performance.md).
            shard_rows = jax.process_count() > 1
        self.shard_rows = bool(shard_rows)
        self._host_fallback = False
        ctx = get_nncontext()
        mesh = ctx.mesh
        if not self.shard_rows:
            if jax.process_count() > 1:
                # explicit shard_rows=False on multi-host: keep host arrays;
                # the engine streams each process's local batch shard
                self._host_fallback = True
                return
            replicated = NamedSharding(mesh, PartitionSpec())
            self.xs = [jax.device_put(a, replicated) for a in self.xs]
            if self.ys is not None:
                self.ys = [jax.device_put(a, replicated) for a in self.ys]
            return
        # -- row-sharded layout: device k holds rows [k*R, (k+1)*R) -------
        # per-shard epoch plans build ON DEVICE too (device_epoch_plan), so
        # the sharded cache keeps the class-default device_shuffle=True and
        # is epoch-/fit-in-one-dispatch eligible like the replicated one;
        # per-step host paths keep the numpy plans
        self._data_axis = ctx.data_axis
        d = int(mesh.shape[self._data_axis])
        n = self.num_samples
        self.rows_per_shard = -(-n // d)
        self._n_shards = d
        # data-axis coordinates whose devices THIS process addresses (the
        # contiguous slab contract of make_array_from_process_local_data)
        axis_pos = mesh.axis_names.index(self._data_axis)
        pi = jax.process_index()
        coords = sorted({c[axis_pos] for c, dev in np.ndenumerate(mesh.devices)
                         if dev.process_index == pi})
        pc = jax.process_count()
        msg = None
        if coords != list(range(coords[0], coords[-1] + 1)):
            msg = ("row-sharded device cache needs each process's devices "
                   f"to be contiguous along the data axis; got coords "
                   f"{coords}")
        elif pc > 1 and len(coords) * pc != d:
            # Unequal per-process coord counts would make the per-step local
            # index batches unequal too, and the global-shape assembly in
            # sharding.shard_batch (local*process_count) wrong. Balanced
            # slabs only.
            msg = ("row-sharded device cache needs every process to own the "
                   f"same number of data-axis coords; process {pi} owns "
                   f"{len(coords)} of {d} across {pc} processes")
        if msg is not None:
            if explicit:
                raise ValueError(msg)
            import logging

            logging.getLogger("analytics_zoo_tpu").warning(
                "%s — falling back to host streaming", msg)
            self.shard_rows = False
            self._host_fallback = True
            return
        self._local_coords = coords
        R = self.rows_per_shard

        def _place(a):
            # materialize ONLY this process's row slab (wrap-padding the
            # dataset tail in the same indexing pass — no full-copy concat)
            a = np.asarray(a)
            sh = NamedSharding(mesh, PartitionSpec(
                self._data_axis, *([None] * (a.ndim - 1))))
            lo, hi = coords[0] * R, (coords[-1] + 1) * R
            gids = np.arange(lo, hi)
            local = np.ascontiguousarray(a[np.where(gids < n, gids,
                                                    gids % n)])
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(
                    sh, local, (R * d,) + a.shape[1:])
            return jax.device_put(local, sh)

        # keep host copies: take()/predict stream in dataset order from them
        self._dev_xs = [_place(a) for a in self.xs]
        self._dev_ys = ([_place(a) for a in self.ys]
                        if self.ys is not None else None)

    @property
    def device_cache(self):
        """The HBM-resident arrays, passed to the compiled step as ARGUMENTS
        every call. Same buffer objects each step → stable runtime handles,
        no per-step infeed. They must not be
        closed over instead: jit bakes closed-over concrete arrays into the
        program as literal constants — megabytes of HLO."""
        if self.shard_rows:
            return (self._dev_xs, self._dev_ys)
        return (self.xs, self.ys)

    def gather_from(self, cache, idx):
        """Jit-traceable gather of batch ``idx`` out of ``cache`` (the
        ``device_cache`` pytree); runs INSIDE the compiled step.

        Replicated mode: ``idx`` holds dataset row ids; each device gathers
        its batch shard from its full replica. Sharded mode: ``idx`` holds
        SHARD-LOCAL row ids in ``[0, rows_per_shard)`` (built by
        ``train_index_batches``) and the gather runs under ``shard_map`` so
        every device reads only its own rows — no collective."""
        xs_arrays, ys_arrays = cache
        if self.shard_rows:
            return self._sharded_gather(xs_arrays, ys_arrays, idx)
        xs = [a[idx] for a in xs_arrays]
        x = xs if self._multi_x else xs[0]
        if ys_arrays is None:
            return x, None
        ys = [a[idx] for a in ys_arrays]
        y = ys if self._multi_y else ys[0]
        return x, y

    def _sharded_gather(self, xs_arrays, ys_arrays, idx):
        from jax import shard_map
        from jax.sharding import PartitionSpec

        from analytics_zoo_tpu.common.nncontext import get_nncontext

        mesh = get_nncontext().mesh
        da = self._data_axis

        def spec(a):
            return PartitionSpec(da, *([None] * (a.ndim - 1)))

        ys_list = tuple(ys_arrays) if ys_arrays is not None else ()

        def local(xs_shards, ys_shards, idx_local):
            return (tuple(a[idx_local] for a in xs_shards),
                    tuple(a[idx_local] for a in ys_shards))

        xs_t, ys_t = shard_map(
            local, mesh=mesh,
            in_specs=(tuple(spec(a) for a in xs_arrays),
                      tuple(spec(a) for a in ys_list),
                      PartitionSpec(da)),
            out_specs=(tuple(spec(a) for a in xs_arrays),
                       tuple(spec(a) for a in ys_list)),
            check_vma=False,
        )(tuple(xs_arrays), ys_list, idx)
        x = list(xs_t) if self._multi_x else xs_t[0]
        if ys_arrays is None:
            return x, None
        return x, (list(ys_t) if self._multi_y else ys_t[0])

    # -- sharded per-epoch index plans -----------------------------------

    def steps_per_epoch(self, batch_size: int) -> int:
        if not self.shard_rows:
            return super().steps_per_epoch(batch_size)
        self._check_shard_batch(batch_size)
        return -(-self.rows_per_shard // (batch_size // self._n_shards))

    def device_epoch_plan(self, perm_key, batch_size: int):
        """In-graph (traced) epoch index plan — the fused/epoch-dispatch
        analogue of ``gather_train_index_batches``: returns
        ``(idxs, masks)`` of shape ``(steps, batch)`` computed ON DEVICE
        from one key, so a whole epoch (or a whole fit) needs no host
        index upload.

        Mirrors ``_shard_epoch_plan`` semantics exactly — shard ``k``
        gets an independent permutation of its R local rows (key
        ``fold_in(perm_key, k)``), rows past the dataset tail and
        per-epoch wrap-padding masked 0 — but with jax's permutation
        instead of numpy's, so the batch ORDER differs from the host
        path (the same documented divergence as ``device_shuffle``
        everywhere else). Replicated caches use the engine's global
        in-graph plan directly (the engine only consults this method for
        ``shard_rows`` sets).
        """
        import jax
        import jax.numpy as jnp

        if not self.shard_rows:
            raise ValueError(
                "device_epoch_plan is the row-sharded plan; replicated "
                "caches use the engine's global in-graph plan")
        self._check_shard_batch(batch_size)
        d, R = self._n_shards, self.rows_per_shard
        b = batch_size // d
        steps = -(-R // b)
        total = steps * b
        n = self.num_samples

        def shard_plan(k):
            perm = jax.random.permutation(jax.random.fold_in(perm_key, k), R)
            valid = jnp.clip(n - k * R, 0, R)
            pos = jnp.arange(total)
            idx = perm[pos % R]
            mask = ((idx < valid) & (pos < R)).astype(jnp.float32)
            return idx.astype(jnp.int32), mask

        idxs, masks = jax.vmap(shard_plan)(jnp.arange(d))  # (d, total)
        return (self._interleave_shards(idxs, d, steps, b),
                self._interleave_shards(masks, d, steps, b))

    @staticmethod
    def _interleave_shards(arr, d: int, steps: int, b: int):
        """(d, steps*b) per-shard plans -> (steps, d*b): column block k
        holds shard k's local ids, so the data-axis split hands every
        device exactly its own rows — THE layout contract
        ``_sharded_gather`` depends on (one definition for the train and
        eval plans)."""
        return arr.reshape(d, steps, b).transpose(1, 0, 2).reshape(steps, -1)

    def _check_shard_batch(self, batch_size: int) -> None:
        d = self._n_shards
        if batch_size < d or batch_size % d:
            raise ValueError(
                f"batch {batch_size} must divide across the {d}-way data "
                "axis for a row-sharded cache")

    def _shard_epoch_plan(self, batch_size: int, shuffle: bool, seed: int):
        """Per data-axis shard: a permutation of its R rows cut into
        per-step slices of B/d rows. Rows past the dataset tail (global
        wrap-padding) and per-epoch tail wrap-padding get mask 0, so an
        epoch weights every real sample exactly once — the same exactness
        contract as ``train_index_batches``."""
        self._check_shard_batch(batch_size)
        d, R = self._n_shards, self.rows_per_shard
        b = batch_size // d
        steps = -(-R // b)
        total = steps * b
        n = self.num_samples
        plans = []
        for k in range(d):
            valid = min(max(n - k * R, 0), R)
            perm = (np.random.default_rng((seed, k)).permutation(R)
                    if shuffle else np.arange(R))
            mask = (perm < valid).astype(np.float32)
            if total > R:
                perm = np.concatenate([perm, perm[np.arange(total - R) % R]])
                mask = np.concatenate(
                    [mask, np.zeros(total - R, np.float32)])
            plans.append((perm.reshape(steps, b).astype(np.int32),
                          mask.reshape(steps, b)))
        return plans, steps

    def _sharded_index_batches(self, batch_size: int, shuffle: bool,
                               seed: int, start_step: int = 0):
        """Yield (idx, mask) of THIS PROCESS's shard-local rows per step —
        the multi-host contract of ``shard_batch`` (local rows in, global
        array out). Single-process yields the full concatenation."""
        plans, steps = self._shard_epoch_plan(batch_size, shuffle, seed)
        coords = self._local_coords
        for s in range(start_step, steps):
            yield (np.concatenate([plans[k][0][s] for k in coords]),
                   np.concatenate([plans[k][1][s] for k in coords]))

    def gather_train_index_batches(self, batch_size: int,
                                   shuffle: bool = True, seed: int = 0,
                                   start_step: int = 0):
        """Index batches for the IN-STEP gather path. Sharded mode yields
        shard-local row ids in shard order (``train_index_batches`` keeps
        dataset order for the streaming paths — predict depends on it)."""
        if not self.shard_rows:
            yield from self.train_index_batches(batch_size, shuffle, seed,
                                                start_step=start_step)
            return
        yield from self._sharded_index_batches(batch_size, shuffle, seed,
                                               start_step=start_step)

    def device_eval_plan(self, batch_size: int):
        """In-graph dataset-order eval plan for the fused (one-dispatch)
        evaluation — the traced analogue of ``gather_eval_index_batches``
        with identical mask semantics; shard k's column block walks its
        R local rows in order. Replicated caches use the engine's global
        plan directly (the engine only consults this for ``shard_rows``
        sets, like ``device_epoch_plan``)."""
        import jax.numpy as jnp

        if not self.shard_rows:
            raise ValueError(
                "device_eval_plan is the row-sharded plan; replicated "
                "caches use the engine's global in-graph plan")
        self._check_shard_batch(batch_size)
        d, R = self._n_shards, self.rows_per_shard
        b = batch_size // d
        steps = -(-R // b)
        total = steps * b
        n = self.num_samples
        pos = jnp.arange(total)
        idx = (pos % R).astype(jnp.int32)                      # (total,)
        valid = jnp.clip(n - jnp.arange(d) * R, 0, R)          # (d,)
        mask = ((idx[None, :] < valid[:, None])
                & (pos[None, :] < R)).astype(jnp.float32)      # (d, total)
        idxs = jnp.broadcast_to(idx, (d, total))
        return (self._interleave_shards(idxs, d, steps, b),
                self._interleave_shards(mask, d, steps, b))

    def gather_eval_index_batches(self, batch_size: int):
        """Dataset-order (indices, mask) batches for the in-step eval gather.
        """
        if not self.shard_rows:
            yield from self.eval_index_batches(batch_size)
            return
        yield from self._sharded_index_batches(batch_size, shuffle=False,
                                               seed=0)

    def take(self, indices: np.ndarray):
        import jax.numpy as jnp

        if self.shard_rows or self._host_fallback:
            # host copies kept (sharded: for order-preserving streaming;
            # fallback: the arrays never left the host) — numpy gather
            return ArrayFeatureSet.take(self, indices)
        return self.gather_from(self.device_cache,
                                jnp.asarray(np.ascontiguousarray(indices)))


class PairFeatureSet(ArrayFeatureSet):
    """Pairwise-ranking dataset: rows are (pos, neg) interleaved — even index
    positive, odd negative — as produced by Relations.generate_relation_pairs
    (ref feature/common/Relations.scala:92, consumed by RankHinge).

    Shuffling and batching operate on PAIR units so the interleaving that
    RankHinge depends on survives (the reference achieves this by packing
    both members into one Sample, TextSet.scala:398).
    """

    def __init__(self, x, y=None):
        super().__init__(x, y)
        if self.num_samples % 2 != 0:
            raise ValueError("PairFeatureSet needs an even number of rows "
                             "(pos, neg interleaved)")

    @staticmethod
    def _check_window(window):
        """Multi-host row windows must respect the (pos, neg) interleaving:
        both bounds even so no pair is split across processes."""
        if window is not None and (window[0] % 2 or window[1] % 2):
            raise ValueError(
                f"PairFeatureSet process window {window} splits a (pos, neg) "
                "pair; use an even per-process batch share")
        return window

    def batches(self, batch_size: int, shuffle: bool = True, seed: int = 0,
                drop_remainder: bool = False, window=None):
        if batch_size % 2 != 0:
            raise ValueError("batch_size must be even for pair batches")
        self._check_window(window)
        pairs = self.num_samples // 2
        per_batch = batch_size // 2
        order = np.arange(pairs)
        if shuffle:
            np.random.default_rng(seed).shuffle(order)
        for start in range(0, pairs, per_batch):
            p = order[start:start + per_batch]
            if len(p) < per_batch:
                if drop_remainder or len(p) == 0:
                    return
                p = np.concatenate(
                    [p, order[np.arange(per_batch - len(p)) % pairs]])
            idx = np.empty(2 * len(p), dtype=np.int64)
            idx[0::2], idx[1::2] = 2 * p, 2 * p + 1
            if window is not None:
                idx = idx[window[0]:window[1]]
            yield self.take(idx)

    def cache_device(self):
        raise NotImplementedError(
            "PairFeatureSet cannot be device-cached: the engine's index-batch "
            "gather path shuffles single rows, which would destroy the "
            "(pos, neg) interleaving RankHinge depends on")

    def train_batches(self, batch_size: int, shuffle: bool = True, seed: int = 0,
                      window=None):
        """Pair-unit masking: a padded pair masks BOTH interleaved members,
        matching the per-pair loss convention (_ps_rank_hinge)."""
        if batch_size % 2 != 0:
            raise ValueError("batch_size must be even for pair batches")
        self._check_window(window)
        pairs = self.num_samples // 2
        per_batch = batch_size // 2
        order = np.arange(pairs)
        if shuffle:
            np.random.default_rng(seed).shuffle(order)
        for start in range(0, pairs, per_batch):
            p = order[start:start + per_batch]
            valid = len(p)
            if valid == 0:
                return
            mask = np.ones(batch_size, dtype=np.float32)
            if valid < per_batch:
                p = np.concatenate(
                    [p, order[np.arange(per_batch - valid) % pairs]])
                mask[2 * valid:] = 0.0
            idx = np.empty(2 * len(p), dtype=np.int64)
            idx[0::2], idx[1::2] = 2 * p, 2 * p + 1
            if window is not None:
                idx, mask = (idx[window[0]:window[1]],
                             mask[window[0]:window[1]])
            x, y = self.take(idx)
            yield x, y, mask


class TransformedFeatureSet(FeatureSet):
    """Lazily applies a per-batch transform (ref Preprocessing chain)."""

    def __init__(self, base: FeatureSet, fn: Callable):
        self.base = base
        self.fn = fn
        self.device_transform = base.device_transform

    @property
    def num_samples(self) -> int:
        return self.base.num_samples

    def take(self, indices: np.ndarray):
        return self.fn(*self.base.take(indices))
