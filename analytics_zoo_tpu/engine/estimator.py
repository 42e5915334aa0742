"""The training core: Estimator over a jitted SPMD train step.

This module replaces the reference's whole training-engine stack —
``InternalDistriOptimizer`` (Topology.scala:952-1145), BigDL's
``DistriOptimizer`` (parameter-sharded AllReduce over the Spark block
manager, wp-bigdl.md:113-160) and the ``Estimator`` facade
(pipeline/estimator/Estimator.scala:33-103) — with one coherent design:

    train_step = jit( grad(loss) -> clip -> optax update )   over a Mesh

The batch is sharded on the ``data`` mesh axis; parameters stay replicated,
so XLA inserts the gradient all-reduce over ICI automatically. The driver's
only per-iteration job is feeding the next host batch (no task scheduling —
the overhead BigDL measured at >10% near 500 tasks/iter, wp-bigdl.md:171-173,
is gone by construction).

Model protocol (duck-typed; KerasNet and nnframes both implement it):
  init(rng) -> (params, model_state)
  apply(params, model_state, x, training, rng) -> (y, new_model_state)
  regularization(params) -> scalar
  auxiliary_loss(model_state) -> scalar   (optional: a term of the training
      loss that the forward pass computes, e.g. an expert layer's balance
      term, from the state ``apply`` returned; the reported loss stays the
      criterion's)
"""

from __future__ import annotations

import contextlib
import hashlib
import inspect
import itertools
import logging
import os
import queue as queue_lib
import sys
import threading
import time
from collections import OrderedDict, deque
from typing import Any, Callable, Dict, List, NamedTuple, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from analytics_zoo_tpu.common.nncontext import get_nncontext
from analytics_zoo_tpu.common.observability import (
    Phase,
    data_metrics,
    get_tracer,
    monotonic_s,
    training_metrics,
)
from analytics_zoo_tpu.engine import checkpoint as ckpt_lib
from analytics_zoo_tpu.ft.atomic import CheckpointCorruptError, CheckpointError
from analytics_zoo_tpu.engine.summary import TrainSummary, ValidationSummary
from analytics_zoo_tpu.engine.triggers import EveryEpoch, MaxEpoch, MinLoss, RunState, Trigger
from analytics_zoo_tpu.keras import metrics as metrics_lib
from analytics_zoo_tpu.parallel.sharding import replicated, shard_batch

logger = logging.getLogger("analytics_zoo_tpu")


# Upper bound on steps fused into one dispatch by the chunked scan path
# (_make_train_scan). Compile cost is K-independent (lax.scan), so the cap
# only bounds how stale the host's view of the loss/iteration counter gets
# and the size of the per-epoch index upload ((K, batch) int32 — trivial).
_MAX_SCAN_CHUNK = 256


def _epoch_index_plan(perm_key, num_samples: int, batch_size: int):
    """In-graph mirror of ``FeatureSet.train_index_batches``: a shuffled
    epoch's ``(steps, batch)`` index matrix and wrap-pad mask, computed on
    device from one key. Every sample appears exactly once with mask 1; the
    tail batch wraps to the permutation's head with mask 0 on duplicates."""
    steps = -(-num_samples // batch_size)
    total = steps * batch_size
    perm = jax.random.permutation(perm_key, num_samples)
    pos = jnp.arange(total)
    idxs = perm[pos % num_samples].reshape(steps, batch_size)
    masks = (pos < num_samples).astype(jnp.float32).reshape(steps, batch_size)
    return idxs, masks


def _eval_index_plan(num_samples: int, batch_size: int):
    """In-graph mirror of ``FeatureSet.eval_index_batches``: dataset-order
    ``(steps, batch)`` indices with wrap-padding masked 0 — the fused
    evaluation's no-upload plan."""
    steps = -(-num_samples // batch_size)
    pos = jnp.arange(steps * batch_size)
    idxs = (pos % num_samples).astype(jnp.int32).reshape(steps, batch_size)
    masks = (pos < num_samples).astype(jnp.float32).reshape(steps, batch_size)
    return idxs, masks


def _uses_loss(trigger) -> bool:
    """True if the trigger may read RunState.loss — those runs need the loss
    fetched synchronously each step. Built-in iteration/epoch triggers are
    known loss-free; UNKNOWN custom triggers conservatively count as
    loss-reading (sync drain) unless they set ``reads_loss = False``."""
    from analytics_zoo_tpu.engine import triggers as trig

    reads = getattr(trigger, "reads_loss", None)
    if reads is not None:
        return bool(reads)
    if isinstance(trigger, MinLoss):
        return True
    subs = getattr(trigger, "triggers", None)
    if subs is not None:
        return any(_uses_loss(t) for t in subs)
    return not isinstance(trigger, (trig.MaxEpoch, trig.MaxIteration,
                                    trig.EveryEpoch, trig.SeveralIteration,
                                    trig.MaxScore))


class _AccumTx(NamedTuple):
    """init/update pair for count-weighted gradient accumulation (the
    ``update`` takes the micro-batch's valid-sample count as an extra arg,
    so it is not a drop-in optax.GradientTransformation)."""
    init: Callable
    update: Callable


def count_weighted_accumulation(tx: optax.GradientTransformation,
                                k: int) -> _AccumTx:
    """Gradient accumulation over K micro-batches, weighting each micro-batch
    gradient by its number of *valid* (non-wrap-pad) samples.

    optax.MultiSteps averages the K micro-gradients with equal weight, which
    over-weights the real samples of a masked tail micro-batch at an epoch
    boundary relative to a true K*batch_size batch. Carrying the mask sum
    through the accumulator makes every window — tail included — apply
    exactly ``sum_i(n_i * g_i) / sum_i(n_i)``, the gradient of the
    concatenated big batch (same exactness bar as the per-sample masked loss,
    ref tf_dataset.py:134-139).
    """
    def init(params):
        acc = jax.tree_util.tree_map(jnp.zeros_like, params)
        return (tx.init(params), acc, jnp.zeros((), jnp.float32),
                jnp.zeros((), jnp.int32))

    def update(grads, state, params, count):
        inner, acc, acc_n, mini = state
        count = jnp.asarray(count, jnp.float32)
        acc = jax.tree_util.tree_map(lambda a, g: a + count * g, acc, grads)
        acc_n = acc_n + count
        mini = mini + 1

        def apply(_):
            mean = jax.tree_util.tree_map(
                lambda a: a / jnp.maximum(acc_n, 1.0), acc)
            updates, new_inner = tx.update(mean, inner, params)
            return updates, (new_inner,
                             jax.tree_util.tree_map(jnp.zeros_like, acc),
                             jnp.zeros((), jnp.float32),
                             jnp.zeros((), jnp.int32))

        def skip(_):
            return (jax.tree_util.tree_map(jnp.zeros_like, grads),
                    (inner, acc, acc_n, mini))

        return jax.lax.cond(mini >= k, apply, skip, None)

    return _AccumTx(init, update)


class _StepWatchdog:
    """Daemon thread asserting the train loop's iteration counter advances
    at least every ``timeout_s`` — the stall detector behind
    ``Estimator.set_step_watchdog``. Fires once per stall episode (re-arms
    when progress resumes): CRITICAL log + faulthandler thread dump (shows
    the Python frame blocked on the hung call) + optional callback."""

    def __init__(self, run_state: "RunState", timeout_s: float,
                 on_stall: Optional[Callable]):
        self.run_state = run_state
        self.timeout_s = timeout_s
        self.on_stall = on_stall
        self._stop = threading.Event()
        self._paused = threading.Event()
        self._thread: Optional[threading.Thread] = None

    def start(self):
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="azoo-step-watchdog")
        self._thread.start()
        return self

    def pause(self):
        """Suspend stall detection around legitimate non-stepping phases
        (validation epochs, checkpoint writes/allgathers) — the iteration
        counter doesn't advance there and must not alarm."""
        self._paused.set()

    def resume(self):
        self._paused.clear()

    def stop(self):
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def _run(self):
        last_it = self.run_state.iteration
        last_t = time.monotonic()
        fired = False
        poll = max(0.5, self.timeout_s / 4.0)
        while not self._stop.wait(poll):
            if self._paused.is_set():
                last_t = time.monotonic()  # re-arm the window on resume
                continue
            it = self.run_state.iteration
            if it != last_it:
                last_it, last_t, fired = it, time.monotonic(), False
                continue
            if fired or time.monotonic() - last_t < self.timeout_s:
                continue
            fired = True
            logger.critical(
                "training stalled: no step completed for %.0fs (iteration "
                "stuck at %d) — likely a hung device/backend call; thread "
                "dump follows", self.timeout_s, it)
            try:
                import faulthandler

                faulthandler.dump_traceback(file=sys.stderr)
            except Exception:  # pragma: no cover
                pass
            if self.on_stall is not None:
                try:
                    self.on_stall(self.run_state)
                except Exception:  # noqa: BLE001 — detector must not die
                    logger.exception("step-watchdog on_stall callback failed")


_SENTINEL = object()
_UNTIMED = contextlib.nullcontext()


def _device_prefetch(host_iter, transfer: Callable, depth: int = 2,
                     on_dequeue: Optional[Callable] = None,
                     wait=_UNTIMED, assemble=_UNTIMED, put=_UNTIMED,
                     borrowed: bool = False):
    """Run host batch assembly + device_put in a background thread, ``depth``
    batches ahead of the consumer (the double-buffer that keeps the jitted
    step from ever waiting on input — SURVEY.md §7 hard-part #1; the
    reference gets this from Spark task pipelining).

    ``transfer`` maps a host item to its device-resident form. JAX transfers
    are async (device_put returns immediately), so the thread mostly hides
    the *host-side* gather/augment cost; the bounded queue caps device-memory
    pressure at ``depth`` in-flight batches.

    The lending contract. By default an item is the iterator's to give: the
    thread keeps it alive until it takes the next one and never waits for
    the transfer. ``borrowed`` says the items are lent (views into a buffer
    the iterator refills on its next step: ``train_batches(borrowed=True)``
    of ``data/pmem.py``). The thread then blocks until the placed batch is
    ready (``jax.block_until_ready``) before it steps the iterator, or
    leaves it. Where the queue has room, as at an epoch's start, the batch
    goes on it before that wait, so the consumer's dispatch overlaps the
    copy; where it is full the consumer has batches in hand and the wait
    comes first. That suffices where the transfer is a copy; where the
    placed array can alias the host's (``_aliases_host``) it is ``transfer``
    that has to copy a lent item first.

    ``wait``, ``assemble`` and ``put`` are the caller's clocks
    (:class:`~analytics_zoo_tpu.common.observability.Phase`): ``wait`` around
    each take of the consumer from the queue, and on the infeed thread
    ``assemble`` around each ``next(host_iter)`` and ``put`` around each
    ``transfer(item)`` and, for a borrowed item, the wait for its copy to
    finish (the thread's time blocked on a full queue is in neither).
    ``on_dequeue(queue_depth)`` fires once per take with the ready-queue
    depth right after it — the hook behind the ``zoo_data_*`` queue-depth
    and starvation gauges of ``Estimator.train``.
    """
    q: queue_lib.Queue = queue_lib.Queue(maxsize=depth)
    stop = threading.Event()  # set when the consumer abandons the epoch early

    def _put(item) -> bool:
        while not stop.is_set():
            try:
                q.put(item, timeout=0.1)
                return True
            except queue_lib.Full:
                continue
        return False

    def _offer(item) -> bool:
        try:
            q.put_nowait(item)
            return True
        except queue_lib.Full:
            return False

    def worker():
        try:
            it = iter(host_iter)
            while True:
                with assemble:
                    item = next(it, _SENTINEL)
                if item is _SENTINEL:
                    break
                with put:
                    placed = transfer(item)
                    sent = borrowed and _offer(("ok", placed))
                    if borrowed:    # the lender gets its buffer back after this
                        jax.block_until_ready(placed)
                # an `item` that is not lent lives on until the next one is
                # taken, as it did in a plain for-loop: the transfer it feeds
                # is asynchronous
                if not sent and not _put(("ok", placed)):
                    return
        except BaseException as e:  # noqa: BLE001 — re-raised in consumer
            _put(("err", e))
            return
        _put(_SENTINEL)

    t = threading.Thread(target=worker, daemon=True, name="zoo-infeed")
    t.start()
    try:
        while True:
            with wait:
                item = q.get()
            if on_dequeue is not None:
                on_dequeue(q.qsize())
            if item is _SENTINEL:
                return
            tag, payload = item
            if tag == "err":
                raise payload
            yield payload
    finally:
        stop.set()


class TrainState(NamedTuple):
    params: Any
    model_state: Any
    opt_state: Any
    step: jnp.ndarray


def _as_list(x):
    if isinstance(x, (list, tuple)):
        return list(x)
    return [x]


def _shard(mesh, v):
    """Shard a host batch element onto the data axis; lists/tuples (multi
    input or multi target) shard leaf-wise."""
    if isinstance(v, (list, tuple)):
        return tuple(shard_batch(mesh, t) for t in v)
    return shard_batch(mesh, v)


def _aliases_host(mesh) -> bool:
    """Whether an array placed on ``mesh`` can share memory with the host
    array it was put from: the CPU backend's ``device_put`` aliases an
    aligned buffer instead of copying it, so "ready" never means "copied"
    there. Every other backend's transfer is a copy to another memory."""
    return any(d.platform == "cpu" for d in mesh.devices.flat)


def _names_parameter(fn, name: str) -> bool:
    """Whether ``fn`` (or ``None``) declares a parameter called ``name``."""
    try:
        return name in inspect.signature(fn).parameters
    except (TypeError, ValueError):     # not callable, or no signature
        return False


def _skip_steps(make_iter, k: int):
    """Resume-offset a batch-iterator factory: ask the dataset to skip the
    first ``k`` batches itself (the ``start_step`` kwarg — skipped batches
    are never materialized), falling back to ``islice`` for duck-typed
    datasets without the kwarg (they then produce and discard them)."""
    if k <= 0:
        return make_iter()
    try:
        return make_iter(start_step=k)
    except TypeError:
        return itertools.islice(make_iter(), k, None)


def _windowed_iter(make_iter, window):
    """Call a dataset's batch-iterator factory with the process-local row
    window, falling back to post-take slicing for duck-typed datasets whose
    generators don't take a ``window`` kwarg (they then materialize the
    global batch and keep only the local rows)."""
    if window is None:
        return make_iter()
    try:
        return make_iter(window=window)
    except TypeError:
        lo, hi = window
        return (jax.tree_util.tree_map(lambda a: np.asarray(a)[lo:hi], item)
                for item in make_iter())


def _metric_fingerprint(m) -> tuple:
    """Hashable snapshot of a metric's full configuration for the compiled-
    step cache: every instance attribute participates (thresholds, k,
    num_thresholds, wrapped loss fns by identity, ...), so two metrics
    producing different compiled stats can never share a cache entry."""
    parts = [type(m).__name__, getattr(m, "name", "")]
    for k, v in sorted(vars(m).items()):
        if callable(v):
            parts.append((k, id(v)))
        elif isinstance(v, (int, float, str, bool, tuple, frozenset, type(None))):
            parts.append((k, v))
        elif isinstance(v, (np.ndarray, jax.Array)):
            # repr() truncates large arrays to '...' — hash the contents
            a_ = np.asarray(v)
            parts.append((k, a_.shape, str(a_.dtype),
                          hashlib.sha1(a_.tobytes()).hexdigest()))
        else:
            parts.append((k, repr(v)))
    return tuple(parts)


def _round_batch(batch_size: int, n_data: int) -> int:
    """The sharded-batch contract: dim 0 must divide across the data axis
    (ref tf_dataset.py:134-139 requires batch % total cores == 0 and errors;
    we round up instead — FeatureSet wrap-pads and masks the remainder)."""
    rounded = -(-batch_size // n_data) * n_data
    if rounded != batch_size:
        logger.info("batch_size %d rounded up to %d (data axis = %d shards)",
                    batch_size, rounded, n_data)
    return rounded


class Estimator:
    """Uniform train/evaluate facade (ref AbstractEstimator, Estimator.scala:33-45).

    Gradient-clipping setters mirror Estimator.scala:78-103; checkpoint and
    TensorBoard wiring mirror KerasNet (Topology.scala:102-118).
    """

    def __init__(self, model, optim_method: Optional[optax.GradientTransformation] = None,
                 model_dir: Optional[str] = None, zero1: bool = False,
                 gradient_accumulation: int = 1):
        self.model = model
        self.optim_method = optim_method
        self.model_dir = model_dir
        # K>1: accumulate gradients over K micro-batch steps and apply the
        # optimizer every Kth (count_weighted_accumulation) — the standard
        # way to reach a large effective batch when activations for the full
        # batch don't fit in HBM. Each micro-batch still counts as one
        # iteration for triggers/summaries; the effective batch is
        # K * batch_size. Micro-gradients are weighted by their valid-sample
        # counts, so even the final (wrap-pad-masked) window of an epoch
        # equals the true K*batch_size gradient exactly.
        self.gradient_accumulation = int(gradient_accumulation)
        if self.gradient_accumulation < 1:
            raise ValueError(
                f"gradient_accumulation must be >= 1, got {gradient_accumulation}")
        # ZeRO-1: shard optimizer moments over the data axis — XLA turns the
        # gradient psum into reduce-scatter + all-gather around the update
        # (cf. PAPERS.md "Automatic Cross-Replica Sharding of Weight Update";
        # the TPU-native form of BigDL's parameter-sharded AllReduce,
        # wp-bigdl.md:140-160, where each node owns one shard of the update).
        self.zero1 = zero1
        self.ctx = get_nncontext()
        self._clip_constant: Optional[Tuple[float, float]] = None
        self._clip_l2norm: Optional[float] = None
        self._checkpoint_path: Optional[str] = model_dir
        self._checkpoint_overwrite = True
        self._ckpt_keep_last: Optional[int] = None
        self._ckpt_keep_every: Optional[int] = None
        self._ckpt_async = True
        self._ckpt_manager = None  # lazy ft.CheckpointManager
        self._preemption = None    # armed ft.PreemptionHandler
        # streaming-pipeline state: the Pipeline train() is consuming (its
        # stream position rides along in checkpoint metadata), and a
        # restored position waiting for the next train() to validate/arm
        self._active_train_set = None
        self._restored_data_state = None
        self._profile: Optional[Tuple[str, int, int]] = None
        self._watchdog: Optional[Tuple[float, Optional[Callable]]] = None
        self.train_summary: Optional[TrainSummary] = None
        self.val_summary: Optional[ValidationSummary] = None
        self.tstate: Optional[TrainState] = None
        self.run_state = RunState()
        # Compiled-step cache: repeated train()/evaluate()/predict() calls
        # (epoch continuation is a core reference semantic — fit() resumes,
        # Topology.scala:366-379) must NOT rebuild the jitted step, or every
        # call re-traces and pays a compile (or a persistent-cache load) for
        # a program it already holds. Keyed on everything the closure bakes
        # in; LRU-bounded because a cached step pins its dataset's gather
        # closure (and thereby an HBM-resident cache) alive — unbounded
        # growth would
        # leak one full device dataset per fold in K-fold-style workflows.
        self._jit_cache: "OrderedDict[Any, Callable]" = OrderedDict()

    _JIT_CACHE_MAX = 8

    def _jit_cache_get(self, token):
        fn = self._jit_cache.get(token)
        if fn is not None:
            self._jit_cache.move_to_end(token)
        return fn

    def _jit_cache_put(self, token, fn):
        self._jit_cache[token] = fn
        self._jit_cache.move_to_end(token)
        while len(self._jit_cache) > self._JIT_CACHE_MAX:
            self._jit_cache.popitem(last=False)
        return fn

    def _cache_token(self, kind: str, *parts) -> tuple:
        return (kind, id(self.optim_method),
                str(getattr(self.model, "compute_dtype", None)),
                self._clip_constant, self._clip_l2norm,
                self.gradient_accumulation,
                self._trainable_fingerprint(), *parts)

    def _trainable_fingerprint(self):
        """Hashable snapshot of layer/weight trainability — freeze/unfreeze
        between fit() calls changes the baked-in update mask, so it must
        invalidate the compiled-step cache."""
        if not hasattr(self.model, "layers"):
            return None
        out = []
        for l in self.model.layers():
            specs = tuple((s.name, s.trainable)
                          for s in getattr(l, "weight_specs", ()))
            out.append((l.name, getattr(l, "trainable", True), specs))
        return tuple(out)

    # -- configuration (ref Estimator.scala:78-103) ----------------------

    def set_constant_gradient_clipping(self, min_value: float, max_value: float):
        """Clip every gradient coordinate to [min_value, max_value]."""
        self._clip_constant = (float(min_value), float(max_value))
        self._clip_l2norm = None
        return self

    def set_l2_norm_gradient_clipping(self, clip_norm: float):
        """Scale gradients so the global L2 norm stays under ``clip_norm``."""
        self._clip_l2norm = float(clip_norm)
        self._clip_constant = None
        return self

    def clear_gradient_clipping(self):
        """Remove any configured gradient clipping (ref clearGradientClipping).
        """
        self._clip_constant = None
        self._clip_l2norm = None
        return self

    def set_checkpoint(self, path: str, overwrite: bool = True,
                       keep_last: Optional[int] = None,
                       keep_every: Optional[int] = None,
                       asynchronous: bool = True):
        """Write ckpt_N checkpoints under ``path`` (every epoch by default).

        Saves go through the fault-tolerance subsystem
        (:class:`~analytics_zoo_tpu.ft.manager.CheckpointManager`): the
        device-to-host snapshot happens at the trigger point, but
        serialization and I/O run on a background writer thread
        (``asynchronous=False`` blocks instead), and every checkpoint is
        committed atomically — a crash mid-save can never strand a
        half-checkpoint that resume would read. ``keep_last``/
        ``keep_every`` enable retention sweeps (keep the N newest, plus
        every checkpoint whose iteration is a multiple of M); the default
        keeps everything, matching the legacy behavior."""
        if self._ckpt_manager is not None:
            self._ckpt_manager.close()
            self._ckpt_manager = None
        self._checkpoint_path = path
        self._checkpoint_overwrite = overwrite
        self._ckpt_keep_last = keep_last
        self._ckpt_keep_every = keep_every
        self._ckpt_async = asynchronous
        return self

    def set_preemption_handler(self, handler=None):
        """Arm save-then-exit preemption handling: install (or adopt) a
        :class:`~analytics_zoo_tpu.ft.preemption.PreemptionHandler` whose
        SIGTERM/SIGINT flag ``train()`` checks at every step boundary. On
        a flagged preemption the loop writes a checkpoint (if
        ``set_checkpoint`` is configured), waits for it to be durably
        committed, and raises
        :class:`~analytics_zoo_tpu.ft.preemption.PreemptedError` — the
        restarted process resumes via ``train(..., auto_resume=True)``.
        Pass ``handler=None`` to create+install one (main thread only)."""
        from analytics_zoo_tpu.ft.preemption import PreemptionHandler

        if handler is None:
            handler = PreemptionHandler().install()
        self._preemption = handler
        return self

    def set_tensorboard(self, log_dir: str, app_name: str):
        """Attach TrainSummary/ValidationSummary writers under ``log_dir``."""
        self.train_summary = TrainSummary(log_dir, app_name)
        self.val_summary = ValidationSummary(log_dir, app_name)
        return self

    def set_step_watchdog(self, timeout_s: float,
                          on_stall: Optional[Callable] = None):
        """Arm a training-loop stall detector (the failure-detection
        subsystem the reference delegates to Spark task retry, SURVEY.md §5
        — here the failure mode is a hung device/backend, which can block
        the host loop in native code indefinitely). While ``train()``
        runs, a daemon thread
        checks that the iteration counter advances at least every
        ``timeout_s`` seconds; on a stall it logs CRITICAL with a full
        thread dump (faulthandler) showing the Python frame the loop is
        blocked in, and
        calls ``on_stall(run_state)`` if given — e.g. to alert, checkpoint
        elsewhere, or ``os._exit`` for a supervisor restart. Detection
        only: the stuck native call cannot be interrupted from Python.
        ``timeout_s=0`` disarms."""
        self._watchdog = (float(timeout_s), on_stall) if timeout_s else None
        return self

    def set_profile(self, log_dir: str, start_iteration: int = 2,
                    num_iterations: int = 3):
        """Collect a jax.profiler device trace for ``num_iterations`` steps
        beginning at ``start_iteration`` of the next train() (skipping the
        compile step by default). View with TensorBoard/XProf."""
        self._profile = (log_dir, int(start_iteration), int(num_iterations))
        return self

    def _tx(self) -> optax.GradientTransformation:
        if self.optim_method is None:
            raise RuntimeError(
                "No optimizer set — call compile(optimizer, loss) before training")
        chain = []
        if self._clip_constant is not None:
            lo, hi = self._clip_constant
            chain.append(optax.stateless(
                lambda upd, params=None: jax.tree_util.tree_map(
                    lambda g: jnp.clip(g, lo, hi), upd)))
        if self._clip_l2norm is not None:
            chain.append(optax.clip_by_global_norm(self._clip_l2norm))
        chain.append(self.optim_method)
        tx = optax.chain(*chain) if len(chain) > 1 else self.optim_method
        if self.gradient_accumulation > 1:
            # clipping applies to the (count-weighted) window-average gradient
            # at the Kth micro-step, matching the big-batch trajectory
            tx = count_weighted_accumulation(tx, self.gradient_accumulation)
        return tx

    # -- state -----------------------------------------------------------

    def _pspecs(self):
        return self.model.param_pspecs() if hasattr(self.model, "param_pspecs") else {}

    def place_params(self, params):
        """Place a params tree per the central layout policy (TP pspecs)."""
        from analytics_zoo_tpu.parallel.sharding import place_params

        return place_params(self.ctx.mesh, params, self._pspecs())

    def _opt_state_shardings(self, opt_state):
        """ZeRO-1 layout: shard each moment leaf on its first dim divisible by
        the data-axis size; scalars/indivisible leaves replicate."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        mesh = self.ctx.mesh
        n = mesh.shape[self.ctx.data_axis]

        def leaf_sharding(leaf):
            shape = getattr(leaf, "shape", ())
            for d, size in enumerate(shape):
                if size >= n and size % n == 0:
                    spec = [None] * len(shape)
                    spec[d] = self.ctx.data_axis
                    return NamedSharding(mesh, P(*spec))
            return NamedSharding(mesh, P())

        return jax.tree_util.tree_map(leaf_sharding, opt_state)

    def _ensure_state(self):
        if self.tstate is None:
            params, model_state = self.model.init(self.ctx.next_rng_key())
            params = self.place_params(params)
            # Optimizer moments are created with zeros_like and inherit each
            # parameter's sharding; counters/state scalars replicate. A model
            # used for inference only (e.g. loaded from disk) has no
            # optimizer — opt_state stays empty until reset_optimizer.
            opt_state = (self._init_opt_state(params)
                         if self.optim_method is not None else ())
            rest = jax.device_put(
                (model_state, jnp.asarray(0, jnp.int32)), replicated(self.ctx.mesh))
            self.tstate = TrainState(params, rest[0], opt_state, rest[1])

    def _init_opt_state(self, params):
        """Optimizer-state init with the MESH-PLACED layout every tstate
        writer must produce (see _train_out_shardings): init runs UNDER JIT
        so GSPMD propagates each param's sharding to its moments the same
        way the train step's outputs will — an eagerly-init'd state left
        TP-pspec'd moments replicated while the step emitted them
        model-sharded, and the flipped signature re-traced the executable
        right after warmup; eager init also left scalar counters
        UNCOMMITTED (a full second compile, 2x 14.5s on NCF's epoch
        executable). ZeRO-1 then re-places moments on the data axis."""
        if self.zero1:
            # explicit ZeRO layout replaces whatever init produces — no
            # point paying the jitted-init compile first
            opt_state = self._tx().init(params)
            if opt_state != ():
                opt_state = jax.tree_util.tree_map(
                    jax.device_put, opt_state,
                    self._opt_state_shardings(opt_state))
            return opt_state
        opt_state = jax.jit(self._tx().init)(params)
        if opt_state != ():
            # input-independent leaves (optimizer step counters are jnp
            # constants inside init) come out of jit UNCOMMITTED on the
            # default device — pin them replicated or their
            # SingleDeviceSharding poisons _train_out_shardings
            rep = replicated(self.ctx.mesh)
            opt_state = jax.tree_util.tree_map(
                lambda a: a if (isinstance(a, jax.Array)
                                and a.committed) else jax.device_put(a, rep),
                opt_state)
        return opt_state

    def reset_optimizer(self, optim_method: optax.GradientTransformation) -> None:
        """Swap/instate the optimizer, rebuilding opt_state for current params
        (used when compile() follows load_weights)."""
        if self.run_state.iteration > 0:
            logger.warning(
                "reset_optimizer after %d iterations: optimizer state is "
                "reinitialized (a compile() after resume_from_checkpoint "
                "discards the restored moments — compile first, then resume)",
                self.run_state.iteration)
        self.optim_method = optim_method
        # the compiled steps bake the old tx in; id() of a freed optimizer
        # can be reused by a new one, so invalidate rather than rely on keys
        self._jit_cache.clear()
        if self.tstate is not None:
            self.tstate = self.tstate._replace(
                opt_state=self._init_opt_state(self.tstate.params))

    def resume_from_checkpoint(self, directory: Optional[str] = None) -> bool:
        """Restore the LATEST checkpoint under ``directory`` (default: the
        ``set_checkpoint`` dir). Returns False when none exists — so cold
        starts and restarts share one call site. This is the
        process-restart form of the reference's resume story (repeated
        ``fit()`` continues epoch numbering via getFinishedEpoch,
        Topology.scala:366-379); counters live in the checkpoint, so
        training picks up at the recorded epoch/iteration."""
        d = directory or self._checkpoint_path
        if not d:
            raise ValueError(
                "no checkpoint directory: pass one or call set_checkpoint")
        if self.optim_method is None:
            # a later compile()/reset_optimizer would re-init opt_state and
            # silently discard the restored moments — force the safe order
            raise RuntimeError(
                "resume_from_checkpoint before an optimizer is set: call "
                "compile()/set the optimizer FIRST, then resume (compiling "
                "afterwards would reinitialize the restored optimizer state)")
        candidates = ckpt_lib.committed_checkpoints(d)
        if not candidates:
            return False
        # newest first; a corrupt checkpoint (external damage — the commit
        # protocol cannot produce one) falls back to the previous committed
        last_err = None
        for _step, latest in reversed(candidates):
            try:
                self.load_checkpoint(
                    latest[:-4] if latest.endswith(".npz") else latest)
            except CheckpointCorruptError as e:
                logger.warning("checkpoint %s is corrupt (%s) — trying the "
                               "previous committed one", latest, e)
                last_err = e
                continue
            logger.info("Resumed from %s (epoch %d, iteration %d, "
                        "epoch_step %d)", latest, self.run_state.epoch,
                        self.run_state.iteration, self.run_state.epoch_step)
            return True
        raise CheckpointError(
            f"every checkpoint under {d!r} is corrupt") from last_err

    def load_checkpoint(self, path: str):
        """Restore params/opt-state/counters from a ckpt_N directory."""
        self._ensure_state()
        # Reject a gradient_accumulation mismatch up front: K=1 vs K>1 differ
        # in opt_state *structure* (count_weighted_accumulation wraps it), and
        # two different K>1 values share a structure but not semantics — a
        # mid-window accumulator saved under K=4 must not resume under K=2.
        saved_k = ckpt_lib.peek_metadata(path).get("gradient_accumulation")
        if saved_k is not None and int(saved_k) != self.gradient_accumulation:
            raise ValueError(
                f"Checkpoint at {path!r} was saved with "
                f"gradient_accumulation={saved_k}, but this Estimator was "
                f"built with gradient_accumulation={self.gradient_accumulation}; "
                "the optimizer states are incompatible. Rebuild the Estimator "
                f"with gradient_accumulation={saved_k} to restore it.")
        restored, meta = ckpt_lib.load_checkpoint(path, self.tstate)
        # Re-apply the central layout: params keep their TP shardings;
        # opt-state leaves take the CURRENT tstate's layout (the jit-init /
        # ZeRO placement _ensure_state built) — replicating them here would
        # be frozen in by the steps' pinned out_shardings, permanently
        # resharding ZeRO moments to full per-device replicas; the rest of
        # the state replicates.
        rest = jax.device_put(
            (restored.model_state, restored.step), replicated(self.ctx.mesh))
        opt_state = restored.opt_state
        if opt_state != ():
            opt_state = jax.tree_util.tree_map(
                lambda a, cur: jax.device_put(
                    a, cur.sharding if isinstance(cur, jax.Array)
                    else replicated(self.ctx.mesh)),
                opt_state, self.tstate.opt_state)
        self.tstate = TrainState(self.place_params(restored.params),
                                 rest[0], opt_state, rest[1])
        self.run_state.epoch = int(meta.get("epoch", 0))
        self.run_state.iteration = int(meta.get("iteration", 0))
        # Full resumable state (docs/fault-tolerance.md): the data-iterator
        # offset within the interrupted epoch, and the RNG stream position —
        # with both restored, the resumed trajectory (shuffle order, dropout
        # keys, optimizer updates) is bitwise the uninterrupted one.
        self.run_state.epoch_step = int(meta.get("epoch_step", 0))
        if "rng_counter" in meta:
            seed = int(meta.get("rng_seed", self.ctx.rng_state()[0]))
            if seed != self.ctx.rng_state()[0]:
                logger.warning(
                    "checkpoint was written under RNG seed %d; this context "
                    "uses %d — restoring the saved seed so the key stream "
                    "continues identically", seed, self.ctx.rng_state()[0])
            self.ctx.set_rng_state(seed, int(meta["rng_counter"]))
        # a streamed run's checkpoint carries the pipeline's stream position
        # — held until the next train() has the Pipeline object to validate
        # it against (load_state_dict rejects a stream-shape mismatch)
        self._restored_data_state = meta.get("pipeline")
        return self

    # -- jitted steps ----------------------------------------------------

    def _cast_for_compute(self, tree):
        """Mixed-precision policy: cast f32 leaves to the model's compute
        dtype (master weights stay f32 in the optimizer; the cast is inside
        grad, so gradients come back f32)."""
        cd = getattr(self.model, "compute_dtype", None)
        if not cd:
            return tree
        dtype = jnp.dtype(cd)
        return jax.tree_util.tree_map(
            lambda a: a.astype(dtype)
            if hasattr(a, "dtype") and a.dtype == jnp.float32 else a, tree)

    def _update_mask(self, params):
        """Pytree of bools matching ``params``: False = frozen (layer- or
        weight-level ``trainable``; e.g. GraphNet.freeze, WordEmbedding's
        always-frozen GloVe table). None when everything is trainable."""
        if not hasattr(self.model, "layers"):
            return None
        layer_by_name = {l.name: l for l in self.model.layers()}

        def mask_layer(lname, sub):
            layer = layer_by_name.get(lname)
            if layer is None:
                return jax.tree_util.tree_map(lambda _: True, sub)
            if not getattr(layer, "trainable", True):
                return jax.tree_util.tree_map(lambda _: False, sub)
            spec_tr = {s.name: s.trainable for s in layer.weight_specs}
            return {
                k: (jax.tree_util.tree_map(lambda _: spec_tr.get(k, True), v)
                    if isinstance(v, dict) else spec_tr.get(k, True))
                for k, v in sub.items()
            }

        mask = {lname: mask_layer(lname, sub) for lname, sub in params.items()}
        if all(jax.tree_util.tree_leaves(mask)):
            return None
        return mask

    def _train_out_shardings(self):
        """(TrainState, loss) output shardings pinned to the CURRENT
        TrainState leaf shardings. GSPMD is free to emit e.g. an optimizer
        moment with a different (equivalent-on-this-mesh) spec than it
        came in with; the flipped signature then re-traces the executable
        on the call AFTER warmup — i.e. inside a timed window
        (caught by test_bert_fit_path_bench_rehearsal). Pinning outputs
        to inputs makes every later call signature-identical."""
        assert self.tstate is not None
        rep = replicated(self.ctx.mesh)
        ts_sh = jax.tree_util.tree_map(
            lambda a: a.sharding if isinstance(a, jax.Array) else rep,
            self.tstate)
        return ts_sh, rep

    def _make_train_step(self, criterion: Callable,
                         device_transform: Optional[Callable] = None,
                         device_gather: Optional[Callable] = None) -> Callable:
        return jax.jit(self._train_step_body(
            criterion, device_transform, device_gather), donate_argnums=(0,),
            out_shardings=self._train_out_shardings())

    def _make_train_scan(self, criterion: Callable,
                         device_transform: Optional[Callable] = None,
                         device_gather: Optional[Callable] = None) -> Callable:
        """K train steps in ONE dispatch (``lax.scan`` over the step body).

        Built for HBM-cached datasets, where per-step infeed is an index
        vector: every dispatch has a fixed host cost (launch, argument
        handling, loss fetch), so a model whose step computes in a few ms —
        NCF above all — can spend most of its wall-clock on it. Scanning K
        steps inside the executable amortizes that to one dispatch, one
        chunked index upload and one loss-vector fetch per K steps. How
        large the per-dispatch cost is on the current machine is not
        measured (docs/performance.md).
        Args: ``(tstate, idxs (K,B), masks (K,B), rngs (K,·), cache)``
        → ``(tstate, losses (K,))``.
        """
        body = self._train_step_body(criterion, device_transform,
                                     device_gather)

        def train_scan(tstate: TrainState, idxs, masks, rngs, cache=None):
            def step(ts, inp):
                idx, mask, rng = inp
                ts, loss = body(ts, (idx, mask), rng, cache)
                return ts, loss

            return jax.lax.scan(step, tstate, (idxs, masks, rngs))

        return jax.jit(train_scan, donate_argnums=(0,),
                       out_shardings=self._train_out_shardings())

    def _make_train_epoch(self, criterion: Callable, num_samples: int,
                          batch_size: int,
                          device_transform: Optional[Callable] = None,
                          device_gather: Optional[Callable] = None,
                          plan_fn: Optional[Callable] = None,
                          steps: Optional[int] = None) -> Callable:
        """A FULL epoch in one dispatch, with the shuffle on device.

        The chunked scan still uploads a fresh ``(K, batch)`` index matrix
        per chunk, each a new device buffer with its own host-to-device
        transfer. Here the epoch permutation is computed IN-GRAPH
        (``jax.random.permutation``) from one uploaded key, wrap-padded and
        masked exactly like ``FeatureSet.train_index_batches``, so per epoch
        the host sends two RNG keys and fetches a single loss vector.
        ``perm_key`` is derived from ``rs.epoch`` (the same contract as the
        host paths' ``seed=rs.epoch``), so a resumed run reshuffles epochs
        exactly like the uninterrupted one; ``step_key`` feeds the per-step
        dropout stream. Batch order differs from the host shuffle (a
        different — still epoch-seed-deterministic — permutation
        algorithm); datasets can set ``device_shuffle = False`` to keep the
        host-identical order.
        """
        one_epoch = self._one_epoch_scan(
            self._train_step_body(criterion, device_transform, device_gather),
            num_samples, batch_size, plan_fn, steps)

        def train_epoch(tstate: TrainState, perm_key, step_key, cache=None):
            return one_epoch(tstate, perm_key, step_key, cache)

        return jax.jit(train_epoch, donate_argnums=(0,),
                       out_shardings=self._train_out_shardings())

    def _one_epoch_scan(self, body: Callable, num_samples: int,
                        batch_size: int,
                        plan_fn: Optional[Callable] = None,
                        steps: Optional[int] = None) -> Callable:
        """The single-epoch scan shared by ``_make_train_epoch`` and
        ``_make_train_fit`` — ONE definition of the in-graph index plan,
        sharding constraints and per-step key split, so the fused and
        per-epoch paths cannot drift apart (their trajectory equality is
        the kill/resume contract pinned in tests/test_scan_dispatch.py).

        ``plan_fn(perm_key) -> (idxs, masks)`` lets a dataset supply its
        own traced plan (the row-sharded cache's per-shard permutations,
        ``DeviceCachedFeatureSet.device_epoch_plan``); the default is the
        global-shuffle plan."""
        steps = steps if steps is not None else -(-num_samples // batch_size)
        data_axis = self.ctx.data_axis
        mesh = self.ctx.mesh

        def one_epoch(ts, perm_key, step_key, cache):
            idxs, masks = (plan_fn(perm_key) if plan_fn is not None else
                           _epoch_index_plan(perm_key, num_samples,
                                             batch_size))
            # keep the SPMD batch split explicit: each device gathers only
            # its shard's rows from its cache replica
            sharding = NamedSharding(mesh, P(None, data_axis))
            idxs = jax.lax.with_sharding_constraint(idxs, sharding)
            masks = jax.lax.with_sharding_constraint(masks, sharding)
            rngs = jax.random.split(step_key, steps)

            def step(ts2, inp):
                idx, mask, rng = inp
                ts2, loss = body(ts2, (idx, mask), rng, cache)
                return ts2, loss

            return jax.lax.scan(step, ts, (idxs, masks, rngs))

        return one_epoch

    def _make_train_fit(self, criterion: Callable, num_samples: int,
                        batch_size: int,
                        device_transform: Optional[Callable] = None,
                        device_gather: Optional[Callable] = None,
                        plan_fn: Optional[Callable] = None,
                        steps: Optional[int] = None) -> Callable:
        """E epochs in ONE dispatch (``lax.scan`` over whole epochs).

        The epoch path still pays per-epoch host round-trips: two fresh
        key uploads, one dispatch, one blocking loss fetch, with the device
        idle in between. Here a whole ``train(MaxEpoch(k))``
        call is one executable: the host uploads an ``(E,)`` epoch-id
        vector and the ``(E, 2)`` step-key block, dispatches once and
        fetches one ``(E, steps)`` loss matrix.

        Trajectory contract: ``PRNGKey(epoch_id)`` computed IN-GRAPH equals
        the per-epoch path's host-side ``PRNGKey(rs.epoch)`` and the step
        keys come from the same ``next_rng_keys`` stream, so a fused run,
        a per-epoch run and a kill/resume run shuffle and drop out
        identically (pinned in tests/test_scan_dispatch.py).
        """
        one_epoch = self._one_epoch_scan(
            self._train_step_body(criterion, device_transform, device_gather),
            num_samples, batch_size, plan_fn, steps)

        def train_fit(tstate: TrainState, epoch_ids, step_keys, cache=None):
            def epoch(ts, inp):
                e, skey = inp
                # in-graph PRNGKey(e) == the per-epoch path's host-side
                # PRNGKey(rs.epoch) for the same integer
                return one_epoch(ts, jax.random.PRNGKey(e), skey, cache)

            return jax.lax.scan(epoch, tstate, (epoch_ids, step_keys))

        return jax.jit(train_fit, donate_argnums=(0,),
                       out_shardings=self._train_out_shardings())

    def _train_step_body(self, criterion: Callable,
                         device_transform: Optional[Callable] = None,
                         device_gather: Optional[Callable] = None) -> Callable:
        """The raw (unjitted) train step — fwd + bwd + update. Shared by the
        per-step path (`_make_train_step`) and the chunked scan path."""
        from analytics_zoo_tpu.keras import objectives as objectives_lib

        tx = self._tx()
        k_accum = self.gradient_accumulation
        model = self.model
        cast = self._cast_for_compute
        ps_criterion = objectives_lib.get_per_sample(criterion)
        aux_fn = getattr(model, "auxiliary_loss", None)

        def _reduce_rows(ps, mask):
            """Masked/unmasked mean of a per-sample loss vector, plus the
            valid-sample count the mean covers (the grad-accum weight)."""
            if mask is None:
                return jnp.mean(ps), jnp.asarray(ps.shape[0], jnp.float32)
            count = jnp.sum(mask).astype(jnp.float32)
            return jnp.sum(ps * mask) / jnp.maximum(count, 1.0), count

        def loss_fn(params, model_state, xs, y, mask, rng):
            if device_transform is not None:
                xs = device_transform(xs)
            pred, new_state = model.apply(cast(params), model_state, cast(xs),
                                          training=True, rng=rng)
            if hasattr(pred, "astype") and not getattr(
                    criterion, "takes_compute_dtype", False):
                # (a loss over [rows, tokens, vocabulary] logits upcasts a
                # block of tokens at a time itself: a float32 copy of the
                # whole batch's logits is 1.6 GB at 16 384 x 25 024)
                pred = pred.astype(jnp.float32)
            if mask is not None and ps_criterion is not None:
                # exact tail-batch semantics: wrap-pad duplicates get zero
                # loss weight, so no sample ever counts twice per epoch
                loss, count = _reduce_rows(ps_criterion(y, pred), mask)
            else:
                raw = criterion(y, pred)
                if getattr(raw, "ndim", 0):
                    # reference-style per-sample criterion (BigDL criterions
                    # and autograd CustomLoss return one value per row):
                    # reduce here, honoring the tail mask exactly
                    loss, count = _reduce_rows(
                        raw.reshape(raw.shape[0], -1).mean(axis=-1), mask)
                else:
                    loss = raw
                    count = jnp.asarray(
                        jax.tree_util.tree_leaves(y)[0].shape[0], jnp.float32)
            reg = model.regularization(params)
            if aux_fn is not None:
                # a term the forward pass computed (a balance term of the
                # experts): in the gradient, not in the reported loss
                reg = reg + aux_fn(new_state)
            return loss + reg, (new_state, loss, count)

        opt_shardings = None
        if self.zero1 and self.tstate is not None and self.tstate.opt_state != ():
            opt_shardings = self._opt_state_shardings(self.tstate.opt_state)
        update_mask = (self._update_mask(self.tstate.params)
                       if self.tstate is not None else None)
        stats_fn = getattr(model, "train_stats", None)

        def train_step(tstate: TrainState, batch, rng, cache=None):
            if device_gather is not None:
                # HBM-resident dataset: batch is (indices, mask); the gather
                # runs inside this compiled step, and the cache arrays come
                # in as arguments with stable buffer handles (see
                # DeviceCachedFeatureSet.device_cache)
                idx, mask = batch
                xs, y = device_gather(cache, idx)
            else:
                xs, y, *rest = batch
                mask = rest[0] if rest else None
            grads_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (total, (new_mstate, data_loss, count)), grads = grads_fn(
                tstate.params, tstate.model_state, xs, y, mask, rng)
            if update_mask is not None:
                # zero frozen grads BEFORE the transform: frozen params must
                # not inflate the global clip norm or accumulate Adam moments
                grads = jax.tree_util.tree_map(
                    lambda g, m: g if m else jnp.zeros_like(g),
                    grads, update_mask)
            with jax.named_scope("optimizer"):
                if k_accum > 1:
                    # count-weighted accumulation: loss_fn reports how many
                    # valid samples its gradient averages over (sum(mask) on
                    # any masked per-sample path, the full batch dim
                    # otherwise), so the K-window mean equals the true
                    # K x batch gradient
                    updates, new_opt = tx.update(
                        grads, tstate.opt_state, tstate.params, count)
                else:
                    updates, new_opt = tx.update(
                        grads, tstate.opt_state, tstate.params)
                if update_mask is not None:
                    # and zero the *updates* too, so decoupled weight decay
                    # (AdamWeightDecay) can't drift frozen parameters
                    updates = jax.tree_util.tree_map(
                        lambda u, m: u if m else jnp.zeros_like(u),
                        updates, update_mask)
                if opt_shardings is not None:
                    # pin the ZeRO-1 layout across steps so XLA keeps moments
                    # sharded (reduce-scatter grads, all-gather updated
                    # params)
                    new_opt = jax.lax.with_sharding_constraint(
                        new_opt, opt_shardings)
                new_params = optax.apply_updates(tstate.params, updates)
            new_ts = TrainState(new_params, new_mstate, new_opt,
                                tstate.step + 1)
            if stats_fn is not None:
                # small per-step statistics the model keeps in its state
                # (routing counts, tokens) ride out beside the loss: one
                # result, one fetch at the drain, no sync of their own
                return new_ts, (data_loss, stats_fn(new_mstate))
            return new_ts, data_loss

        return train_step

    def _make_eval_step(self, metric_objs: Sequence[metrics_lib.Metric],
                        device_transform: Optional[Callable] = None,
                        device_gather: Optional[Callable] = None) -> Callable:
        model = self.model
        cast = self._cast_for_compute

        def eval_step(tstate: TrainState, batch, cache=None):
            if device_gather is not None:
                idx, mask = batch
                xs, y = device_gather(cache, idx)
            else:
                xs, y, mask = batch
            if device_transform is not None:
                xs = device_transform(xs)
            pred, _ = model.apply(cast(tstate.params), tstate.model_state, cast(xs),
                                  training=False, rng=None)
            if hasattr(pred, "astype"):
                pred = pred.astype(jnp.float32)
            stats = []
            for m in metric_objs:
                s, c = m.batch_stats(y, pred, mask=mask)
                stats.append((s, c))
            return stats

        return jax.jit(eval_step)

    def _make_eval_scan(self, metric_objs: Sequence[metrics_lib.Metric],
                        num_samples: int, batch_size: int,
                        device_transform: Optional[Callable] = None,
                        device_gather: Optional[Callable] = None,
                        eval_plan: Optional[Callable] = None) -> Callable:
        """A WHOLE evaluation epoch in one dispatch over an HBM-cached set:
        the dataset-order index plan builds in-graph (no host uploads at
        all — eval takes only tstate and the cache's stable handles), the
        per-batch metric partial sums accumulate in the scan carry, and
        the host fetches one small stats tuple. The per-batch partials
        are identical to ``_make_eval_step``'s, so the result is
        bit-comparable to the streaming path (pinned in
        tests/test_train_loop.py)."""
        model = self.model
        cast = self._cast_for_compute
        data_axis = self.ctx.data_axis
        mesh = self.ctx.mesh

        def eval_scan(tstate: TrainState, cache=None):
            idxs, masks = (eval_plan() if eval_plan is not None else
                           _eval_index_plan(num_samples, batch_size))
            sharding = NamedSharding(mesh, P(None, data_axis))
            idxs = jax.lax.with_sharding_constraint(idxs, sharding)
            masks = jax.lax.with_sharding_constraint(masks, sharding)

            def batch_stats(idx, mask):
                xs, y = device_gather(cache, idx)
                if device_transform is not None:
                    xs = device_transform(xs)
                pred, _ = model.apply(cast(tstate.params), tstate.model_state,
                                      cast(xs), training=False, rng=None)
                if hasattr(pred, "astype"):
                    pred = pred.astype(jnp.float32)
                return tuple(m.batch_stats(y, pred, mask=mask)
                             for m in metric_objs)

            shapes = jax.eval_shape(batch_stats, idxs[0], masks[0])
            init = jax.tree_util.tree_map(
                lambda s: jnp.zeros(s.shape, s.dtype), shapes)

            def step(carry, inp):
                s = batch_stats(*inp)
                return jax.tree_util.tree_map(jnp.add, carry, s), None

            totals, _ = jax.lax.scan(step, init, (idxs, masks))
            return totals

        return jax.jit(eval_scan)

    # -- training loop ---------------------------------------------------

    def train(self, train_set, criterion: Callable,
              end_trigger: Optional[Trigger] = None,
              checkpoint_trigger: Optional[Trigger] = None,
              validation_set=None,
              validation_method: Optional[Sequence] = None,
              batch_size: int = 32,
              validation_batch_size: Optional[int] = None,
              auto_resume: bool = False) -> "Estimator":
        """Train until ``end_trigger`` (default: one more epoch).

        ``train_set`` is anything exposing
        ``batches(batch_size, shuffle=True, seed=int) -> iterable of (x, y)``
        and ``num_samples`` — see :mod:`analytics_zoo_tpu.data.feature_set`.
        A streaming :class:`~analytics_zoo_tpu.data.pipeline.Pipeline` is
        accepted directly: the infeed thread adopts its ``.prefetch(k)``
        depth, consumer wait time feeds the ``zoo_data_*`` starvation
        gauges, and checkpoints carry the iterator's resumable stream
        position (docs/data-pipeline.md).

        ``auto_resume=True`` restores the latest COMMITTED checkpoint
        under the ``set_checkpoint`` directory before training (no-op when
        none exists, so cold starts and process restarts share one call
        site). Resume is full-state — params, optimizer moments,
        epoch/iteration counters, RNG stream position and the
        data-iterator offset within an interrupted epoch — so the resumed
        trajectory is bitwise the uninterrupted one
        (docs/fault-tolerance.md).

        The call keeps its own clock (docs/observability.md): its seconds
        go to ``zoo_train_call_seconds_total`` and are split, always, into
        the loop's waits on the infeed queue (``zoo_data_wait_seconds``),
        its waits on the device (``zoo_train_drain_seconds_total``) and
        the rest, the host's own work (``zoo_train_host_seconds_total``);
        with the tracer on the same intervals are ``train.*`` spans under
        one ``train.call``.
        """
        obs = training_metrics()
        call = Phase("train.call", obs["call_seconds"].inc)
        wait = Phase("train.infeed_wait",
                     data_metrics()["wait_seconds"].observe)
        drain = Phase("train.drain", obs["drain_seconds"].inc)
        try:
            with call:
                self._train(obs, wait, drain, train_set, criterion,
                            end_trigger, checkpoint_trigger, validation_set,
                            validation_method, batch_size,
                            validation_batch_size, auto_resume)
        finally:
            # the waits and drains lie inside the call, so what is left is
            # the host's; max() only guards the float's last bit
            obs["host_seconds"].inc(
                max(0.0, call.last - wait.seconds - drain.seconds))
        return self

    def _train(self, obs, wait: Phase, drain: Phase, train_set, criterion,
               end_trigger, checkpoint_trigger, validation_set,
               validation_method, batch_size, validation_batch_size,
               auto_resume) -> None:
        """The body of :meth:`train`, inside its ``train.call`` phase;
        ``wait`` and ``drain`` are the clocks of its two kinds of wait."""
        if (auto_resume and self._checkpoint_path is not None
                and self.run_state.iteration == 0):
            # process-restart entry: a warm estimator (iteration > 0) is
            # already ahead of its own checkpoints — never rewind it
            self.resume_from_checkpoint()
        self._ensure_state()
        batch_size = _round_batch(batch_size, self.ctx.mesh.shape[self.ctx.data_axis])
        end_trigger = end_trigger or MaxEpoch(self.run_state.epoch + 1)
        checkpoint_trigger = checkpoint_trigger or EveryEpoch()
        gather = getattr(train_set, "gather_from", None)
        window = self.ctx.local_batch_window(batch_size)
        if (gather is not None and window is not None
                and not getattr(train_set, "shard_rows", False)):
            # A replicated HBM cache lives on ONE process's devices; across
            # processes the in-step global gather only applies to row-sharded
            # caches (DeviceCachedFeatureSet shards automatically multi-host;
            # this guards duck-typed device sets without that layout).
            logger.info("multi-host run: device cache is not row-sharded; "
                        "streaming the process-local batch shard")
            gather = None
        cache = train_set.device_cache if gather is not None else None
        dt = getattr(train_set, "device_transform", None)
        # bound methods get a fresh id per access — key on the dataset object
        token = self._cache_token("train", criterion,
                                  id(dt) if dt is not None else None,
                                  id(train_set) if gather is not None else None)
        step_fn = self._jit_cache_get(token)
        if step_fn is None:
            step_fn = self._jit_cache_put(
                token, self._make_train_step(criterion, dt, gather))
        mesh = self.ctx.mesh
        rs = self.run_state
        profile = self._profile
        prof_started = prof_done = False
        prof_t0 = 0.0
        steps_this_call = 0
        watchdog = None
        tracer = get_tracer()
        data_obs = data_metrics()
        fill = Phase("train.fill", obs["fill_seconds"].inc, tracer)

        # Streaming-pipeline integration (data/pipeline.py). A Pipeline is
        # consumed through the same duck-typed train_batches protocol as any
        # FeatureSet, but two contracts upgrade when one is passed:
        # the infeed thread adopts the pipeline's .prefetch(k) depth, and
        # every checkpoint carries the resumable stream position
        # (state_dict -> ft metadata; see _write_checkpoint).
        is_stream = hasattr(train_set, "note_queue_depth")
        infeed_depth = int(getattr(train_set, "prefetch_depth", 0) or 2)
        # A set whose train_batches can lend (`borrowed=True`: views into its
        # own ring, valid until the iterator's next step) is fed straight
        # from them: no host copy between the set and the device
        # (_device_prefetch has the contract). An override that does not name
        # the parameter is fed its own batches, as before.
        lends = gather is None and _names_parameter(
            getattr(train_set, "train_batches", None), "borrowed")
        copy_lent = lends and _aliases_host(self.ctx.mesh)
        lend_kw = {"borrowed": True} if lends else {}
        if self._restored_data_state is not None:
            if int(self._restored_data_state.get("position_batches", 0)) == 0:
                # epoch-boundary checkpoint: there is no mid-epoch offset
                # to restore, and the next epoch's order is a pure
                # function of rs.epoch — so a DIFFERENT stream here is a
                # legitimate warm start on new data (the flywheel's
                # incremental-retrain case), not a corrupted resume
                pass
            elif hasattr(train_set, "load_state_dict"):
                # raises on a stream-shape mismatch: a saved position must
                # never silently index into a different stream
                train_set.load_state_dict(self._restored_data_state)
            else:
                logger.warning(
                    "checkpoint carries a streaming-pipeline position but "
                    "this train_set (%s) is not a Pipeline — the position "
                    "is ignored (epoch_step still resumes the batch "
                    "offset)", type(train_set).__name__)
            self._restored_data_state = None
        note_depth = getattr(train_set, "note_queue_depth", None)
        call_t0 = time.perf_counter()

        def on_dequeue(qdepth):
            # every host-fed set: the zoo_data_* gauges say whether the
            # input path keeps up (the waits themselves are `wait`'s)
            if note_depth is not None:
                note_depth(qdepth + 1)
            data_obs["queue_depth"].set(qdepth)
            elapsed = time.perf_counter() - call_t0
            if elapsed > 0:
                data_obs["starvation_ratio"].set(
                    min(1.0, wait.seconds / elapsed))
        self._active_train_set = train_set if is_stream else None

        # Chunked dispatch (see _make_train_scan): K steps per call when the
        # dataset is HBM-cached and nothing demands per-step host control —
        # profiling wants per-step traces, loss-reading triggers need the
        # loss every step, and an iteration-granular checkpoint trigger must
        # observe every counter value. Epoch-granular training (the common
        # fit() shape) qualifies.
        chunk = 0
        if (gather is not None and profile is None
                and isinstance(checkpoint_trigger, EveryEpoch)
                and not _uses_loss(end_trigger)
                and isinstance(end_trigger, (MaxEpoch,))
                and not self._watchdog):
            # (an armed step watchdog needs per-step iteration progress;
            # a K-step dispatch would freeze the counter for K step-times
            # and false-alarm — per-step dispatch keeps it meaningful)
            steps_per_epoch = (
                train_set.steps_per_epoch(batch_size)
                if hasattr(train_set, "steps_per_epoch")
                else -(-train_set.num_samples // batch_size))
            chunk = min(steps_per_epoch, _MAX_SCAN_CHUNK)
        elif gather is not None and self._watchdog:
            logger.info("step watchdog armed: chunked dispatch disabled "
                        "(per-step iteration progress required)")
        scan_fn = epoch_fn = fit_fn = None
        fit_epochs = 0
        if chunk > 1:
            if (getattr(train_set, "device_shuffle", False)
                    and steps_per_epoch <= _MAX_SCAN_CHUNK
                    and rs.epoch_step == 0):
                # (a mid-epoch resume needs a partial first epoch — the
                # fused whole-epoch/whole-fit dispatches can't skip into
                # an epoch; the chunked scan path below slices its index
                # list instead)
                # whole epoch in one dispatch, shuffle on device: the host
                # uploads one RNG key per epoch instead of an index matrix
                if (self._checkpoint_path is None and validation_set is None):
                    # nothing demands per-epoch host control -> fuse ALL
                    # remaining epochs into one dispatch (no per-epoch
                    # upload/dispatch/fetch round-trip, no idle device
                    # between epochs)
                    fit_epochs = end_trigger.max_epoch - rs.epoch
                dev_plan = (getattr(train_set, "device_epoch_plan", None)
                            if getattr(train_set, "shard_rows", False)
                            else None)
                plan_fn = ((lambda k, _p=dev_plan, _b=batch_size: _p(k, _b))
                           if dev_plan is not None else None)
                if fit_epochs > 1:
                    fit_token = self._cache_token(
                        "train_fit", criterion,
                        id(dt) if dt is not None else None,
                        id(train_set), train_set.num_samples, batch_size,
                        fit_epochs)
                    fit_fn = self._jit_cache_get(fit_token)
                    if fit_fn is None:
                        fit_fn = self._jit_cache_put(
                            fit_token, self._make_train_fit(
                                criterion, train_set.num_samples, batch_size,
                                dt, gather, plan_fn, steps_per_epoch))
                else:
                    epoch_token = self._cache_token(
                        "train_epoch", criterion,
                        id(dt) if dt is not None else None,
                        id(train_set), train_set.num_samples, batch_size)
                    epoch_fn = self._jit_cache_get(epoch_token)
                    if epoch_fn is None:
                        epoch_fn = self._jit_cache_put(
                            epoch_token, self._make_train_epoch(
                                criterion, train_set.num_samples, batch_size,
                                dt, gather, plan_fn, steps_per_epoch))
            else:
                scan_token = self._cache_token(
                    "train_scan", criterion,
                    id(dt) if dt is not None else None,
                    id(train_set), chunk)
                scan_fn = self._jit_cache_get(scan_token)
                if scan_fn is None:
                    scan_fn = self._jit_cache_put(
                        scan_token, self._make_train_scan(criterion, dt, gather))
                chunk_sharding = NamedSharding(
                    mesh, P(None, self.ctx.data_axis))  # (K, B): K = scan dim

        from analytics_zoo_tpu.keras import objectives as objectives_lib

        has_mask = hasattr(train_set, "train_batches") or gather is not None
        if (has_mask and objectives_lib.get_per_sample(criterion) is None
                and train_set.num_samples % batch_size != 0):
            logger.warning(
                "criterion %s has no per-sample form: the wrap-padded tail "
                "batch weights duplicated samples twice",
                getattr(criterion, "__name__", criterion))

        # Loss fetch policy: float(loss) blocks until the step completes, so
        # fetching every step serializes host batch prep against device
        # compute. Instead keep <=2 steps in flight and drain the oldest —
        # the host stays a step ahead (double-buffered with the infeed
        # thread). Loss-reading triggers (MinLoss) force sync draining.
        max_outstanding = 0 if (_uses_loss(end_trigger)
                                or _uses_loss(checkpoint_trigger)) else 2

        def _profiler_tick():
            # trace a window of steps relative to this train() call
            nonlocal prof_started, prof_done, prof_t0
            if profile is None or prof_done:
                return
            import jax as _jax
            log_dir, start, num = profile
            # dispatch is asynchronous: wait for the steps in flight at both
            # edges, so the window holds exactly `num` whole steps (on a v5e
            # an unsynchronized stop caught one of two BERT steps)
            if not prof_started and steps_this_call >= start:
                _jax.block_until_ready(self.tstate)
                _jax.profiler.start_trace(log_dir)
                prof_started = True
                prof_t0 = monotonic_s()
            elif prof_started and steps_this_call >= start + num:
                _jax.block_until_ready(self.tstate)
                _jax.profiler.stop_trace()
                if tracer.enabled:
                    # the device-trace window as one host span, so the
                    # Perfetto view shows where the XProf dump sits in
                    # the run
                    tracer.record_span(
                        "train.profiler_window",
                        tracer.current_trace_id() or "train",
                        prof_t0, monotonic_s(), log_dir=log_dir)
                prof_done = True
                logger.info("Profiler trace written to %s", log_dir)
                try:  # diagnostics only — never fail training over a parse
                    from analytics_zoo_tpu.common.trace_tools import top_ops
                    # the device plane of the platform the step ran on; a
                    # TPU trace without device ops is reported, not
                    # papered over with the host's python line
                    if self.ctx.platform == "tpu":
                        rows = top_ops(log_dir, plane_substr="TPU", n=5)
                        if not rows:
                            logger.warning(
                                "profiler trace under %s holds no TPU "
                                "device ops", log_dir)
                    else:
                        rows = top_ops(log_dir, line="python",
                                       plane_substr="CPU", n=5)
                    for name, ms, count in rows:
                        logger.info("  top op %8.2f ms x%-5d %s",
                                    ms, count, name[:80])
                except Exception as e:  # noqa: BLE001
                    logger.warning("trace summary unavailable: %s", e)

        def _transfer(host_batch):
            if gather is not None:  # (indices, mask): tiny per-step infeed
                idx, mask = host_batch
                return shard_batch(mesh, idx), shard_batch(mesh, mask)
            if copy_lent:
                host_batch = jax.tree_util.tree_map(np.array, host_batch)
            elif lends:
                data_obs["borrowed_batches"].inc()
            if len(host_batch) == 3:
                xs, y, mask = host_batch
                return (_shard(mesh, xs), _shard(mesh, y),
                        shard_batch(mesh, mask))
            xs, y = host_batch
            return (_shard(mesh, xs), _shard(mesh, y))

        # the paths that take their batches from the infeed thread, one
        # dispatch a step (the fused ones upload an index plan or a key)
        host_fed = fit_fn is None and epoch_fn is None and scan_fn is None
        epoch_ctx = None
        try:
            # started inside the try so any raise is guaranteed to reach
            # the finally-stop (a leaked daemon would alarm on a dead run)
            if self._watchdog:
                watchdog = _StepWatchdog(rs, *self._watchdog).start()
            while not end_trigger(rs):
                epoch_ctx = tracer.span("train.epoch", epoch=rs.epoch)
                epoch_span = epoch_ctx.__enter__()
                if host_fed:
                    fill.start()      # until the first batch is out
                rs.epoch_finished = False
                # >0 only right after a mid-epoch resume: the number of
                # this epoch's batches the interrupted run already consumed
                # (epoch order is a pure function of seed=rs.epoch, so
                # skipping exactly that many continues the trajectory)
                resume_skip = rs.epoch_step
                epoch_start = time.perf_counter()
                epoch_loss, epoch_batches = 0.0, 0
                # (first_iteration, device losses) — a scalar loss for the
                # per-step path, a (K,) vector for one scan/epoch dispatch
                pending: deque = deque()
                last_drain_t = epoch_start

                def _drain_one():
                    nonlocal epoch_loss, epoch_batches, last_drain_t
                    first_it, dev_losses = pending.popleft()
                    # ONE fetch; ravel: the fused-fit path yields (E, steps)
                    with drain:     # the host waits for the device here
                        if isinstance(dev_losses, tuple):
                            # (loss, the model's step statistics): fetched
                            # together, so the statistics wait for nothing
                            vals, stats = jax.device_get(dev_losses)
                        else:
                            vals, stats = np.asarray(dev_losses), None
                    lead = np.ndim(vals)   # a fused dispatch stacks steps
                    vals = np.atleast_1d(vals).ravel()
                    if stats is not None:
                        stats = {name: np.reshape(v, (len(vals),)
                                                  + np.shape(v)[lead:])
                                 for name, v in stats.items()}
                        for j in range(len(vals)):     # a step at a time
                            self.model.record_train_stats(
                                {name: v[j] for name, v in stats.items()})
                    rs.loss = float(vals[-1])
                    epoch_loss += float(vals.sum())
                    epoch_batches += len(vals)
                    now = time.perf_counter()
                    dt = now - last_drain_t
                    last_drain_t = now
                    # training metric families (drain granularity: a fused
                    # dispatch contributes its mean per-step time once)
                    obs["steps"].inc(len(vals))
                    if dt > 0:
                        obs["step_seconds"].observe(dt / len(vals))
                        obs["items_per_sec"].set(
                            len(vals) * batch_size / dt)
                    if self.train_summary is not None:
                        for j, lv in enumerate(vals):
                            self.train_summary.add_scalar(
                                "Loss", float(lv), first_it + j)
                        if dt > 0:
                            self.train_summary.add_scalar(
                                "Throughput", len(vals) * batch_size / dt,
                                first_it + len(vals) - 1)

                if fit_fn is not None:
                    # ALL remaining epochs in one dispatch: upload the
                    # epoch-id vector + step-key block, fetch one (E, steps)
                    # loss matrix. Keys/ids reproduce the per-epoch path's
                    # streams exactly (see _make_train_fit docstring).
                    epoch_ids = np.arange(rs.epoch, rs.epoch + fit_epochs,
                                          dtype=np.int32)
                    step_keys = self.ctx.next_rng_keys(fit_epochs)
                    with tracer.span("train.dispatch", kind="fused_fit",
                                     steps=steps_per_epoch * fit_epochs):
                        self.tstate, losses = fit_fn(
                            self.tstate, epoch_ids, step_keys, cache)
                    first_it = rs.iteration + 1
                    rs.iteration += steps_per_epoch * fit_epochs
                    steps_this_call += steps_per_epoch * fit_epochs
                    pending.append((first_it, losses))
                    while pending:
                        _drain_one()
                    # the loop tail accounts for ONE epoch; own the rest
                    rs.epoch += fit_epochs - 1
                    obs["epochs"].inc(fit_epochs - 1)
                    logger.info(
                        "Epochs %d-%d fused into one dispatch (%d steps)",
                        rs.epoch - fit_epochs + 2, rs.epoch + 1,
                        steps_per_epoch * fit_epochs)
                    host_iter = iter(())
                elif epoch_fn is not None:
                    # Epoch-in-one-dispatch: upload two keys, fetch one loss
                    # vector (the fetch doubles as the epoch barrier). The
                    # shuffle key derives from rs.epoch — the same contract
                    # as the host paths' seed=rs.epoch, so resumed runs
                    # reshuffle identically; the dropout stream stays on the
                    # session counter like every other path.
                    perm_key = jax.random.PRNGKey(rs.epoch)
                    step_key = self.ctx.next_rng_key()
                    with tracer.span("train.dispatch", kind="epoch",
                                     steps=steps_per_epoch):
                        self.tstate, losses = epoch_fn(
                            self.tstate, perm_key, step_key, cache)
                    first_it = rs.iteration + 1
                    rs.iteration += steps_per_epoch
                    steps_this_call += steps_per_epoch
                    pending.append((first_it, losses))
                    while pending:
                        _drain_one()
                    host_iter = iter(())
                elif scan_fn is not None:
                    # Chunked path: K steps per dispatch. Host-side work per
                    # chunk is one index stack + three uploads (idx, mask and
                    # the vmapped key block); chunks are double-buffered like
                    # single steps. Group sizes are balanced (at most two
                    # distinct sizes -> at most two compiled shapes) so no
                    # epoch tail ever falls back to per-step dispatch.
                    idx_batches = list(_skip_steps(
                        lambda **kw: getattr(
                            train_set, "gather_train_index_batches",
                            train_set.train_index_batches)(
                            batch_size, shuffle=True, seed=rs.epoch, **kw),
                        resume_skip))
                    # empty only when a resume landed exactly on the epoch
                    # boundary (epoch_step == steps_per_epoch): nothing left
                    # of this epoch — fall through to the tail bookkeeping
                    n_groups = -(-len(idx_batches) // chunk) if idx_batches else 0
                    base, rem = divmod(len(idx_batches), max(n_groups, 1))
                    start = 0
                    for gi in range(n_groups):
                        size = base + (1 if gi < rem else 0)
                        group = idx_batches[start:start + size]
                        start += size

                        def _put_chunk(stack2d):
                            # multi-host: each process stacked only its local
                            # rows of each batch; assemble the global (K, B)
                            if self.ctx.process_count > 1:
                                return jax.make_array_from_process_local_data(
                                    chunk_sharding,
                                    np.ascontiguousarray(stack2d),
                                    (stack2d.shape[0], batch_size))
                            return jax.device_put(stack2d, chunk_sharding)

                        idxs = _put_chunk(np.stack([g[0] for g in group]))
                        masks = _put_chunk(np.stack([g[1] for g in group]))
                        rngs = self.ctx.next_rng_keys(size)
                        with tracer.span("train.dispatch", kind="scan",
                                         steps=size):
                            self.tstate, losses = scan_fn(
                                self.tstate, idxs, masks, rngs, cache)
                        first_it = rs.iteration + 1
                        rs.iteration += size
                        rs.epoch_step += size
                        steps_this_call += size
                        pending.append((first_it, losses))
                        while len(pending) > 1:
                            _drain_one()
                        self._check_preemption(watchdog)
                    while pending:
                        _drain_one()
                    host_iter = iter(())
                elif gather is not None:
                    host_iter = _skip_steps(
                        lambda **kw: getattr(
                            train_set, "gather_train_index_batches",
                            train_set.train_index_batches)(
                            batch_size, shuffle=True, seed=rs.epoch, **kw),
                        resume_skip)
                elif hasattr(train_set, "train_batches"):
                    host_iter = _skip_steps(
                        lambda **skip_kw: _windowed_iter(
                            lambda **kw: train_set.train_batches(
                                batch_size, shuffle=True, seed=rs.epoch,
                                **lend_kw, **skip_kw, **kw),
                            window),
                        resume_skip)
                else:
                    host_iter = _skip_steps(
                        lambda **skip_kw: _windowed_iter(
                            lambda **kw: train_set.batches(
                                batch_size, shuffle=True, seed=rs.epoch,
                                **skip_kw, **kw),
                            window),
                        resume_skip)
                for batch in _device_prefetch(
                        host_iter, _transfer, depth=infeed_depth,
                        on_dequeue=on_dequeue, wait=wait, borrowed=lends,
                        assemble=Phase("infeed.assemble",
                                       data_obs["assemble_seconds"].inc,
                                       tracer, parent=epoch_span),
                        put=Phase("infeed.transfer",
                                  data_obs["transfer_seconds"].inc,
                                  tracer, parent=epoch_span)):
                    fill.stop()
                    rng = self.ctx.next_rng_key()
                    _profiler_tick()
                    with tracer.span("train.dispatch", kind="step"):
                        self.tstate, loss = step_fn(
                            self.tstate, batch, rng, cache)
                    rs.iteration += 1
                    rs.epoch_step += 1
                    steps_this_call += 1
                    pending.append((rs.iteration, loss))
                    while len(pending) > max_outstanding:
                        _drain_one()
                    self._check_preemption(watchdog)
                    if end_trigger(rs):
                        break
                    if checkpoint_trigger(rs) and not isinstance(checkpoint_trigger, EveryEpoch):
                        self._maybe_checkpoint()
                fill.stop()     # an epoch with no batch left to take
                while pending:
                    _drain_one()
                rs.epoch += 1
                obs["epochs"].inc()
                rs.epoch_step = 0
                rs.epoch_finished = True
                logger.info(
                    "Epoch %d done in %.2fs — mean loss %.5f",
                    rs.epoch, time.perf_counter() - epoch_start,
                    epoch_loss / max(epoch_batches, 1))
                # non-stepping phases: the iteration counter legitimately
                # stalls here (checkpoint write/allgather, a whole
                # validation epoch) — don't let the watchdog alarm
                if watchdog is not None:
                    watchdog.pause()
                if checkpoint_trigger(rs):
                    self._maybe_checkpoint()
                if validation_set is not None and validation_method:
                    with tracer.span("train.validation", epoch=rs.epoch):
                        results = self.evaluate(
                            validation_set, validation_method,
                            validation_batch_size or batch_size)
                    for name, value in results.items():
                        rs.score = value
                        if self.val_summary is not None:
                            self.val_summary.add_scalar(name, value, rs.iteration)
                    logger.info("Validation @ epoch %d: %s", rs.epoch, results)
                if watchdog is not None:
                    watchdog.resume()
                # epoch boundary: the fused/epoch dispatch paths check here
                # (per-step paths already checked every iteration)
                self._check_preemption(watchdog)
                epoch_ctx.__exit__(None, None, None)
                epoch_ctx = None
            # surface async checkpoint-writer failures to the caller, and
            # guarantee every triggered save is durable before returning
            self._drain_checkpoints()
        finally:
            if epoch_ctx is not None:   # an epoch that raised: close its spans
                fill.stop(*sys.exc_info())
                epoch_ctx.__exit__(*sys.exc_info())
            self._active_train_set = None
            if watchdog is not None:
                watchdog.stop()
            self._drain_checkpoints(raising=False)
            # close an open trace even when a step raises, or the
            # process-global profiler stays active and the dump is lost
            if prof_started and not prof_done:
                import jax as _jax
                _jax.profiler.stop_trace()
                logger.info("Profiler trace written to %s", profile[0])
            if prof_started or prof_done:
                # one-shot semantics: "during the next train()" — re-arm
                # explicitly via set_profile for another trace
                self._profile = None
        return self

    # -- multi-host data-parallel training (ft/distributed.py) -----------

    def _make_dist_step_single(self, criterion: Callable, tx):
        """The N==1 step of ``train_distributed``: the plain train step's
        loss/grad/update math VERBATIM in one jit — tree-shaped grads and
        optimizer state, frozen-grad zeroing before AND after
        ``tx.update`` — so the single-host distributed trajectory is
        bitwise today's ``train()`` path (pinned by
        tests/test_dist_training.py; the optimizer update must run on the
        SAME leaf shapes, since XLA's per-shape codegen makes a
        flat-vector Adam wobble the stored moments by 1 ulp). The tree
        state is converted to the canonical sharded layout only at
        checkpoint time (:meth:`ShardedUpdater.tree_to_flat` — pure data
        movement). Returns ``(jitted (params, model_state, opt_state, xs,
        y, mask, rng) -> (new_params, new_opt, new_mstate, loss) fn,
        update_mask)``."""
        from analytics_zoo_tpu.keras import objectives as objectives_lib

        model = self.model
        cast = self._cast_for_compute
        ps_criterion = objectives_lib.get_per_sample(criterion)
        update_mask = self._update_mask(self.tstate.params)
        aux_fn = getattr(model, "auxiliary_loss", None)

        def _reduce_rows(ps, mask):
            if mask is None:
                return jnp.mean(ps), jnp.asarray(ps.shape[0], jnp.float32)
            count = jnp.sum(mask).astype(jnp.float32)
            return jnp.sum(ps * mask) / jnp.maximum(count, 1.0), count

        def loss_fn(params, model_state, xs, y, mask, rng):
            pred, new_state = model.apply(cast(params), model_state,
                                          cast(xs), training=True, rng=rng)
            if hasattr(pred, "astype"):
                pred = pred.astype(jnp.float32)
            if mask is not None and ps_criterion is not None:
                loss, count = _reduce_rows(ps_criterion(y, pred), mask)
            else:
                raw = criterion(y, pred)
                if getattr(raw, "ndim", 0):
                    loss, count = _reduce_rows(
                        raw.reshape(raw.shape[0], -1).mean(axis=-1), mask)
                else:
                    loss = raw
                    count = jnp.asarray(
                        jax.tree_util.tree_leaves(y)[0].shape[0],
                        jnp.float32)
            reg = model.regularization(params)
            if aux_fn is not None:
                reg = reg + aux_fn(new_state)
            return loss + reg, (new_state, loss, count)

        def step(params, model_state, opt_state, xs, y, mask, rng):
            grads_fn = jax.value_and_grad(loss_fn, has_aux=True)
            (_total, (new_mstate, data_loss, _count)), grads = grads_fn(
                params, model_state, xs, y, mask, rng)
            if update_mask is not None:
                grads = jax.tree_util.tree_map(
                    lambda g, m: g if m else jnp.zeros_like(g),
                    grads, update_mask)
            updates, new_opt = tx.update(grads, opt_state, params)
            if update_mask is not None:
                updates = jax.tree_util.tree_map(
                    lambda u, m: u if m else jnp.zeros_like(u),
                    updates, update_mask)
            new_params = optax.apply_updates(params, updates)
            return new_params, new_opt, new_mstate, data_loss

        return jax.jit(step), update_mask

    def _make_dist_grad_psum(self, criterion: Callable, mesh_config,
                             num_hosts: int, host_id: int = 0):
        """The N>1 gradient step: a real ``shard_map``/``psum`` over this
        host's local data axis computing the gradient of the SUM of
        per-sample losses plus the valid-sample count — the cross-host
        combine is then ``(Σ gsum) / (Σ count) + greg`` in fixed host
        order, identical on every host. Each global device folds its
        global data-axis index into the shared per-step rng, so dropout
        is drawn independently per shard instead of replicated. The
        regularization gradient is computed once outside the shard_map on
        the (replicated) params. Returns ``(jitted fn, update_mask)``
        where the fn maps ``(params, model_state, xs, y, mask, rng)`` to
        ``(gsum_vec, greg_vec, loss_sum, count, new_mstate)``."""
        from analytics_zoo_tpu.keras import objectives as objectives_lib
        from jax import shard_map
        from jax.flatten_util import ravel_pytree
        from jax.sharding import PartitionSpec as SP

        model = self.model
        cast = self._cast_for_compute
        ps_criterion = objectives_lib.get_per_sample(criterion)
        update_mask = self._update_mask(self.tstate.params)
        mesh = mesh_config.build()
        dev_offset = int(host_id) * int(mesh_config.axis_length("data"))

        def loss_sum_fn(params, model_state, xs, y, mask, rng):
            pred, new_state = model.apply(cast(params), model_state,
                                          cast(xs), training=True, rng=rng)
            if hasattr(pred, "astype"):
                pred = pred.astype(jnp.float32)
            rows = jnp.asarray(
                jax.tree_util.tree_leaves(y)[0].shape[0], jnp.float32)
            if ps_criterion is not None:
                ps = ps_criterion(y, pred)
                loss_sum = jnp.sum(ps * mask)
                count = jnp.sum(mask).astype(jnp.float32)
            else:
                raw = criterion(y, pred)
                if getattr(raw, "ndim", 0):
                    ps = raw.reshape(raw.shape[0], -1).mean(axis=-1)
                    loss_sum = jnp.sum(ps * mask)
                    count = jnp.sum(mask).astype(jnp.float32)
                else:
                    # scalar-only criterion: treat the batch mean as exact
                    # (the plain path warns about wrap-pad duplicates too)
                    loss_sum = raw * rows
                    count = rows
            return loss_sum, (new_state, count)

        def shard_body(params, model_state, rng, xs, y, mask):
            rng = jax.random.fold_in(
                rng, jax.lax.axis_index("data") + dev_offset)
            (ls, (new_ms, cnt)), grads = jax.value_and_grad(
                loss_sum_fn, has_aux=True)(params, model_state, xs, y,
                                           mask, rng)
            grads = jax.lax.psum(grads, "data")
            ls = jax.lax.psum(ls, "data")
            cnt = jax.lax.psum(cnt, "data")
            return grads, ls, cnt, new_ms

        wrapped = shard_map(
            shard_body, mesh=mesh,
            in_specs=(SP(), SP(), SP(), SP("data"), SP("data"), SP("data")),
            out_specs=(SP(), SP(), SP(), SP()), check_vma=False)

        def grad_step(params, model_state, xs, y, mask, rng):
            grads, ls, cnt, new_ms = wrapped(params, model_state, rng,
                                             xs, y, mask)
            if update_mask is not None:
                grads = jax.tree_util.tree_map(
                    lambda g, m: g if m else jnp.zeros_like(g),
                    grads, update_mask)
            gsum_vec, _ = ravel_pytree(grads)
            greg = jax.grad(model.regularization)(params)
            if update_mask is not None:
                greg = jax.tree_util.tree_map(
                    lambda g, m: g if m else jnp.zeros_like(g),
                    greg, update_mask)
            greg_vec, _ = ravel_pytree(greg)
            # each host contributes greg/num_hosts; the host-order sum of
            # num_hosts identical addends is deterministic and equal on
            # every host
            return (gsum_vec, greg_vec / num_hosts, ls, cnt, new_ms)

        return jax.jit(grad_step), update_mask

    def _dist_checkpoint_steps(self, prefix: str = "ckpt"):
        from analytics_zoo_tpu.ft import atomic

        return atomic.committed_checkpoints(self._checkpoint_path, prefix)

    def _dist_keep_steps(self, steps):
        """Retention policy of ``set_checkpoint`` applied to a sharded
        checkpoint directory: the ``keep_last`` newest plus every
        ``keep_every`` multiple; None disables the sweep entirely."""
        if self._ckpt_keep_last is None and self._ckpt_keep_every is None:
            return None
        keep = set(steps[-self._ckpt_keep_last:]
                   if self._ckpt_keep_last else steps)
        if self._ckpt_keep_every:
            keep |= {s for s in steps if s % self._ckpt_keep_every == 0}
        return keep

    def _write_dist_checkpoint(self, dist, updater, opt_shard):
        """Synchronous two-phase sharded save of the current state: every
        host stages its round-robin share of the flattened
        params/model_state/step tree plus its own optimizer shard; the
        coordinator validates, merges and commits
        (:func:`analytics_zoo_tpu.ft.distributed
        .commit_sharded_checkpoint`). Raises DistTimeoutError /
        DistCommitError on failure — callers decide whether that is fatal
        (preemption save) or surfaced later like an async-writer error
        (periodic trigger)."""
        from analytics_zoo_tpu.ft import atomic
        from analytics_zoo_tpu.ft import distributed as dist_lib

        rs = self.run_state
        shared = {"params": self.tstate.params,
                  "model_state": self.tstate.model_state,
                  "step": self.tstate.step}
        shared_flat = ckpt_lib._flatten(shared)
        if dist.num_hosts == 1:
            # the single-host loop trains the per-leaf tree state —
            # checkpoint in the canonical flat layout so any host count
            # can restore it
            opt_shard = updater.tree_to_flat(opt_shard)
        mine = (dist_lib.split_round_robin(shared_flat, dist.host_id,
                                           dist.num_hosts)
                + updater.opt_flat(opt_shard))
        expected = ({k for k, _ in shared_flat}
                    | updater.expected_opt_keys())
        seed, counter = self.ctx.rng_state()
        metadata = {"epoch": rs.epoch,
                    "iteration": rs.iteration,
                    "epoch_step": rs.epoch_step,
                    "gradient_accumulation": self.gradient_accumulation,
                    "rng_seed": seed,
                    "rng_counter": counter,
                    "dist": {"num_hosts": dist.num_hosts,
                             "flat_size": updater.flat_size,
                             "slice_len": updater.slice_len,
                             "opt_leaves": updater.opt_leaf_count}}
        path = os.path.join(self._checkpoint_path, f"ckpt_{rs.iteration}")
        with get_tracer().span("train.checkpoint", iteration=rs.iteration,
                               dist=True):
            dist_lib.commit_sharded_checkpoint(
                path, mine, host_id=dist.host_id,
                num_hosts=dist.num_hosts, expected_keys=expected,
                metadata=metadata, commit_id=dist.commit_id(rs.iteration),
                timeout_s=dist.timeout_s,
                overwrite=self._checkpoint_overwrite)
        if dist.is_coordinator:
            steps = [s for s, _ in self._dist_checkpoint_steps()]
            keep = self._dist_keep_steps(steps)
            if keep is not None:
                atomic.sweep_stale(self._checkpoint_path, keep_steps=keep)
        return path

    def _resume_distributed(self, dist, updater):
        """Restore the newest committed checkpoint for a distributed run:
        rebuild the shared params/model_state/step tree by KEY (sharded
        manifests order leaves by owning host, never positionally),
        reshard the optimizer slices for this run's host count, and
        restore counters + the RNG stream. Falls back over corrupt
        checkpoints exactly like :meth:`resume_from_checkpoint`. Returns
        ``(opt_shard_or_None, resumed_bool)``."""
        from analytics_zoo_tpu.ft import atomic

        if dist.is_coordinator:
            atomic.sweep_stale(self._checkpoint_path)
        dist.barrier()  # nobody lists the dir until the sweep is done
        candidates = self._dist_checkpoint_steps()
        if not candidates:
            return None, False
        shared_tpl = {"params": self.tstate.params,
                      "model_state": self.tstate.model_state,
                      "step": self.tstate.step}
        tpl_keys = [k for k, _ in ckpt_lib._flatten(shared_tpl)]
        tpl_leaves, treedef = jax.tree_util.tree_flatten(shared_tpl)
        last_err = None
        for _step, path in reversed(candidates):
            try:
                flat, meta = atomic.read_checkpoint(path)
                fm = dict(flat)
                leaves = []
                for key, like in zip(tpl_keys, tpl_leaves):
                    if key not in fm:
                        raise CheckpointCorruptError(
                            f"checkpoint {path!r}: leaf {key!r} missing")
                    arr = fm[key]
                    if tuple(arr.shape) != tuple(like.shape):
                        raise ValueError(
                            f"Checkpoint {path!r}: leaf {key!r} has shape "
                            f"{tuple(arr.shape)}, target expects "
                            f"{tuple(like.shape)}")
                    leaves.append(arr)
                restored = jax.tree_util.tree_unflatten(treedef, leaves)
                dist_meta = (meta or {}).get("dist")
                if dist_meta is None:
                    raise CheckpointCorruptError(
                        f"checkpoint {path!r} carries no 'dist' metadata — "
                        "not a distributed checkpoint")
                opt_shard = updater.restore_opt(fm, dist_meta)
            except CheckpointCorruptError as e:
                logger.warning("checkpoint %s is corrupt (%s) — trying the "
                               "previous committed one", path, e)
                last_err = e
                continue
            rest = jax.device_put(
                (restored["model_state"], restored["step"]),
                replicated(self.ctx.mesh))
            self.tstate = TrainState(
                self.place_params(restored["params"]), rest[0], (), rest[1])
            meta = meta or {}
            self.run_state.epoch = int(meta.get("epoch", 0))
            self.run_state.iteration = int(meta.get("iteration", 0))
            self.run_state.epoch_step = int(meta.get("epoch_step", 0))
            if "rng_counter" in meta:
                seed = int(meta.get("rng_seed", self.ctx.rng_state()[0]))
                self.ctx.set_rng_state(seed, int(meta["rng_counter"]))
            logger.info("host %d resumed from %s (epoch %d, iteration %d, "
                        "written by %d host(s))", dist.host_id, path,
                        self.run_state.epoch, self.run_state.iteration,
                        int(dist_meta["num_hosts"]))
            return opt_shard, True
        raise CheckpointError(
            f"every checkpoint under {self._checkpoint_path!r} is corrupt"
        ) from last_err

    def train_distributed(self, train_set, criterion: Callable,
                          end_trigger: Optional[Trigger] = None,
                          checkpoint_trigger: Optional[Trigger] = None,
                          batch_size: int = 32,
                          auto_resume: bool = False,
                          dist=None, mesh_config=None) -> "Estimator":
        """Multi-host data-parallel training with sharded optimizer
        updates and two-phase sharded checkpoints
        (docs/distributed-training.md).

        ``dist`` is this host's
        :class:`~analytics_zoo_tpu.ft.distributed.DistContext` (default: a
        single-host context, in which case the trajectory is bitwise
        identical to :meth:`train`). ``batch_size`` is the GLOBAL batch —
        rounded up to divide ``num_hosts × local data axis``, each host
        consuming its contiguous row window of every batch. Per step,
        each host computes the gradient of the sum of its window's
        per-sample losses under a ``shard_map``/``psum`` over its local
        device mesh, the hosts all-gather ``(grad-sum, loss-sum, count)``
        through the rendezvous and combine them in fixed host order, and
        the optimizer update runs sharded — host k updates the k-th
        window of the flattened parameter vector
        (:class:`~analytics_zoo_tpu.ft.distributed.ShardedUpdater`, 1/N
        optimizer memory per host), then the updated windows are
        exchanged and reassembled.

        Checkpoints (``set_checkpoint``) are synchronous two-phase
        sharded commits; a failed save (peer death → timeout, validation
        abort) is recorded and re-raised at the next save attempt or
        train end — training itself continues, like an async-writer
        failure in :meth:`train`. A preemption flagged on ANY host
        (``set_preemption_handler``) propagates in-band through the next
        exchange round: every host then saves coordinately and raises
        :class:`~analytics_zoo_tpu.ft.preemption.PreemptedError`.
        ``auto_resume=True`` restores the newest committed checkpoint —
        including one written by a different host count (optimizer shards
        reshard deterministically).

        Not supported here: ``gradient_accumulation > 1``, L2-norm
        clipping (needs the global norm before slicing) and ``zero1``
        (superseded by the cross-host sharded update). ``model_state``
        must be replicated-stable (e.g. no cross-host batch-norm
        reduction — each host keeps its local copy)."""
        from analytics_zoo_tpu.common.observability import (
            distributed_metrics)
        from analytics_zoo_tpu.ft import distributed as dist_lib
        from analytics_zoo_tpu.ft.preemption import PreemptedError
        from analytics_zoo_tpu.mesh.config import MeshConfig

        if self.gradient_accumulation > 1:
            raise NotImplementedError(
                "train_distributed does not support gradient_accumulation "
                "> 1 (the accumulator state is not shard-partitionable)")
        if self._clip_l2norm is not None:
            raise NotImplementedError(
                "train_distributed does not support L2-norm clipping: the "
                "global norm needs every gradient before the update is "
                "sliced — use constant clipping")
        if self.zero1:
            raise NotImplementedError(
                "zero1 is superseded by the sharded update in "
                "train_distributed (optimizer state is already 1/N per "
                "host)")
        if dist is None:
            dist = dist_lib.DistContext(0, 1)
        self._ensure_state()
        # the replicated full optimizer state is dead weight here — the
        # ShardedUpdater owns the (1/N) live state
        if self.tstate.opt_state != ():
            self.tstate = self.tstate._replace(opt_state=())
        mesh_cfg = mesh_config or MeshConfig.host_local_data()
        n_data = mesh_cfg.axis_length("data")
        global_batch = _round_batch(batch_size, dist.num_hosts * n_data)
        per_host = global_batch // dist.num_hosts
        tx = self._tx()
        updater = dist_lib.ShardedUpdater(
            tx, self.tstate.params, dist.host_id, dist.num_hosts, mesh_cfg)
        single = dist.num_hosts == 1
        opt_shard = None
        resumed = False
        if (auto_resume and self._checkpoint_path is not None
                and self.run_state.iteration == 0):
            opt_shard, resumed = self._resume_distributed(dist, updater)
            if opt_shard is not None and single:
                # the single-host loop runs the plain per-leaf step — keep
                # the live state in the tree layout it trains with
                opt_shard = updater.to_tree_state(opt_shard)
        if opt_shard is None:
            opt_shard = (tx.init(self.tstate.params) if single
                         else updater.init_opt(self.tstate.params))

        rs = self.run_state
        end_trigger = end_trigger or MaxEpoch(rs.epoch + 1)
        checkpoint_trigger = checkpoint_trigger or EveryEpoch()
        if single:
            step_fn, update_mask = self._make_dist_step_single(criterion, tx)
        else:
            step_fn, update_mask = self._make_dist_grad_psum(
                criterion, mesh_cfg, dist.num_hosts, dist.host_id)
        mask_vec = (None if single
                    else updater.mask_vector(self.tstate.params,
                                             update_mask))
        window = (None if single else
                  (dist.host_id * per_host, (dist.host_id + 1) * per_host))
        dm = distributed_metrics()
        dm["hosts"].set(dist.num_hosts)
        obs = training_metrics()
        tracer = get_tracer()
        save_error: List[Optional[BaseException]] = [None]
        # the just-resumed iteration is already durably committed — an
        # immediate trigger/epoch-end firing at the same step must dedupe,
        # not re-commit over the checkpoint we restored from
        last_saved = [rs.iteration if resumed else -1]
        # in-band preemption bit: set by the signal listener, exchanged
        # with the gradients so ALL hosts agree to save-then-exit on the
        # same step (docs/fault-tolerance.md)
        preempt_flag = [False]
        if self._preemption is not None:
            self._preemption.add_listener(
                lambda: preempt_flag.__setitem__(0, True))

        def _save(coordinated_exit=False):
            if save_error[0] is not None:
                err, save_error[0] = save_error[0], None
                raise err
            if self._checkpoint_path is None:
                return None
            if last_saved[0] == rs.iteration:
                return os.path.join(self._checkpoint_path,
                                    f"ckpt_{rs.iteration}")
            try:
                path = self._write_dist_checkpoint(dist, updater, opt_shard)
            except (dist_lib.DistTimeoutError,
                    dist_lib.DistCommitError) as e:
                if coordinated_exit:
                    raise
                logger.error("distributed checkpoint at iteration %d "
                             "failed (%s) — training continues; the error "
                             "re-raises at the next save attempt",
                             rs.iteration, e)
                save_error[0] = e
                return None
            last_saved[0] = rs.iteration
            return path

        def _coordinated_preempt():
            path = _save(coordinated_exit=True)
            logger.warning("preemption: distributed checkpoint %s "
                           "committed at iteration %d — exiting", path,
                           rs.iteration)
            raise PreemptedError(
                f"training preempted at iteration {rs.iteration}"
                + (f"; checkpoint committed at {path}" if path else
                   " (no checkpoint directory configured — state NOT "
                   "saved)"),
                checkpoint_path=path)

        while not end_trigger(rs):
            rs.epoch_finished = False
            resume_skip = rs.epoch_step
            epoch_start = time.time()
            epoch_loss, epoch_batches = 0.0, 0
            if hasattr(train_set, "train_batches"):
                host_iter = _skip_steps(
                    lambda **skip_kw: _windowed_iter(
                        lambda **kw: train_set.train_batches(
                            global_batch, shuffle=True, seed=rs.epoch,
                            **skip_kw, **kw),
                        window),
                    resume_skip)
            else:
                host_iter = _skip_steps(
                    lambda **skip_kw: _windowed_iter(
                        lambda **kw: train_set.batches(
                            global_batch, shuffle=True, seed=rs.epoch,
                            **skip_kw, **kw),
                        window),
                    resume_skip)
            for batch in host_iter:
                rng = self.ctx.next_rng_key()
                xs, y, *rest = batch
                mask = rest[0] if rest else None
                if single:
                    # device-shard the batch over the context mesh exactly
                    # like train()'s infeed: the jit then compiles the same
                    # SPMD partitioning, which bitwise parity depends on
                    ctx_mesh = self.ctx.mesh
                    xs_d, y_d = _shard(ctx_mesh, xs), _shard(ctx_mesh, y)
                    mask_d = (None if mask is None
                              else shard_batch(ctx_mesh, mask))
                    with tracer.span("train.dispatch", kind="dist_step"):
                        new_params, opt_shard, new_mstate, loss = step_fn(
                            self.tstate.params, self.tstate.model_state,
                            opt_shard, xs_d, y_d, mask_d, rng)
                    loss_val = float(loss)
                else:
                    if mask is None:
                        rows = np.shape(
                            jax.tree_util.tree_leaves(y)[0])[0]
                        mask = np.ones((rows,), np.float32)
                    gsum, greg, ls, cnt, new_mstate = step_fn(
                        self.tstate.params, self.tstate.model_state,
                        xs, y, mask, rng)
                    t0 = time.perf_counter()
                    red = dist.allreduce_sum(
                        {"g": np.asarray(gsum), "ls": np.asarray(ls),
                         "c": np.asarray(cnt),
                         "flag": np.asarray(
                             1.0 if preempt_flag[0] else 0.0,
                             np.float32)})
                    dm["exchange_seconds"].observe(
                        time.perf_counter() - t0)
                    count_total = float(red["c"])
                    g = (red["g"] / max(count_total, 1.0)
                         + np.asarray(greg))
                    g_full = np.zeros((updater.padded_size,), np.float32)
                    g_full[: updater.flat_size] = g
                    loss_val = float(red["ls"]) / max(count_total, 1.0)
                    if float(red["flag"]) > 0:
                        preempt_flag[0] = True
                    with tracer.span("train.dispatch", kind="dist_step"):
                        new_slice, opt_shard = updater.step(
                            self.tstate.params, g_full, opt_shard,
                            mask_vec)
                    t0 = time.perf_counter()
                    parts = dist.exchange({"s": np.asarray(new_slice)})
                    dm["exchange_seconds"].observe(
                        time.perf_counter() - t0)
                    new_params = self.place_params(
                        updater.assemble([p["s"] for p in parts]))
                self.tstate = TrainState(new_params, new_mstate, (),
                                         self.tstate.step + 1)
                rs.iteration += 1
                rs.epoch_step += 1
                rs.loss = loss_val
                epoch_loss += loss_val
                epoch_batches += 1
                dm["steps"].inc()
                obs["steps"].inc()
                if self.train_summary is not None:
                    self.train_summary.add_scalar("Loss", loss_val,
                                                  rs.iteration)
                if preempt_flag[0] or (self._preemption is not None
                                       and self._preemption.requested):
                    _coordinated_preempt()
                if end_trigger(rs):
                    break
                if (checkpoint_trigger(rs)
                        and not isinstance(checkpoint_trigger, EveryEpoch)):
                    _save()
            rs.epoch += 1
            rs.epoch_step = 0
            rs.epoch_finished = True
            logger.info("Epoch %d done in %.2fs — mean loss %.5f (host %d "
                        "of %d)", rs.epoch, time.time() - epoch_start,
                        epoch_loss / max(epoch_batches, 1), dist.host_id,
                        dist.num_hosts)
            if checkpoint_trigger(rs):
                _save()
            if preempt_flag[0] or (self._preemption is not None
                                   and self._preemption.requested):
                _coordinated_preempt()
        if save_error[0] is not None:
            err, save_error[0] = save_error[0], None
            raise err
        return self

    def train_pipelined(self, train_set, criterion: Callable, stage_plan,
                        num_microbatches: int = 1, schedule: str = "1f1b",
                        end_trigger: Optional[Trigger] = None,
                        checkpoint_trigger: Optional[Trigger] = None,
                        batch_size: int = 32,
                        auto_resume: bool = False) -> "Estimator":
        """Pipeline-parallel training: ``stage_plan`` (a
        :class:`~analytics_zoo_tpu.pipeline.plan.StagePlan`) partitions
        the layer stack into K stages, each compiled as its own program,
        and a microbatch schedule (``"1f1b"`` or ``"gpipe"``) streams
        ``num_microbatches`` slices of every global batch through them
        (docs/pipeline-parallel.md). Checkpoints are stage-owned
        two-phase sharded commits; ``auto_resume=True`` restores the
        newest committed one bitwise, including after a mid-schedule
        kill. Loss/gradient semantics match the fused step bitwise or
        within the documented ULP bound (see
        :mod:`analytics_zoo_tpu.pipeline.trainer`)."""
        from analytics_zoo_tpu.pipeline import trainer as pipeline_trainer

        return pipeline_trainer.train_pipelined(
            self, train_set, criterion, stage_plan,
            num_microbatches=num_microbatches, schedule=schedule,
            end_trigger=end_trigger, checkpoint_trigger=checkpoint_trigger,
            batch_size=batch_size, auto_resume=auto_resume)

    def _checkpoint_manager(self):
        """The lazily-created async checkpoint manager for the configured
        ``set_checkpoint`` directory."""
        if self._ckpt_manager is None:
            from analytics_zoo_tpu.ft.manager import CheckpointManager

            self._ckpt_manager = CheckpointManager(
                self._checkpoint_path,
                keep_last=self._ckpt_keep_last,
                keep_every=self._ckpt_keep_every,
                asynchronous=self._ckpt_async,
                overwrite=self._checkpoint_overwrite)
        return self._ckpt_manager

    def _maybe_checkpoint(self):
        if self._checkpoint_path is None:
            return None
        with get_tracer().span("train.checkpoint",
                               iteration=self.run_state.iteration):
            return self._write_checkpoint()

    def _write_checkpoint(self):
        state = self.tstate
        if self.ctx.process_count > 1:
            # ZeRO-1 moments are sharded over the (cross-process) data axis,
            # so rank 0 can't fetch them alone — allgather non-addressable
            # leaves on EVERY rank (it's a collective), then rank 0 writes.
            from jax.experimental import multihost_utils

            state = jax.tree_util.tree_map(
                lambda a: (multihost_utils.process_allgather(a, tiled=True)
                           if isinstance(a, jax.Array)
                           and not a.is_fully_addressable else a),
                state)
            if self.ctx.process_index != 0:
                return None  # rank 0 owns the checkpoint dir
        # snapshot on THIS thread (the only work that needs the live state);
        # serialization + atomic commit + retention run on the writer thread
        seed, counter = self.ctx.rng_state()
        metadata = {"epoch": self.run_state.epoch,
                    "iteration": self.run_state.iteration,
                    "epoch_step": self.run_state.epoch_step,
                    "gradient_accumulation": self.gradient_accumulation,
                    "rng_seed": seed,
                    "rng_counter": counter}
        ds = self._active_train_set
        if ds is not None and hasattr(ds, "state_dict"):
            # the resumable stream position, under the ESTIMATOR's counters:
            # the live iterator may sit a few prefetched batches ahead of
            # the optimizer step this checkpoint captures, and rs.epoch /
            # rs.epoch_step are exactly what resume will replay with
            metadata["pipeline"] = ds.state_dict(
                epoch_seed=self.run_state.epoch,
                position=self.run_state.epoch_step)
        return self._checkpoint_manager().save(
            self.run_state.iteration, state, metadata=metadata)

    def _drain_checkpoints(self, raising: bool = True):
        """Wait for pending async checkpoint writes; surface writer errors
        (``raising=False`` logs instead — the exception-unwind path must
        not mask the original error)."""
        if self._ckpt_manager is None:
            return
        try:
            self._ckpt_manager.wait()
        except Exception:
            if raising:
                raise
            logger.exception("async checkpoint write failed during unwind")

    def _check_preemption(self, watchdog=None):
        """Act on a flagged SIGTERM/SIGINT: checkpoint synchronously (if
        configured), wait for durability, raise PreemptedError. Called at
        step/epoch boundaries — never from the signal handler itself."""
        h = self._preemption
        if h is None or not h.requested:
            return
        from analytics_zoo_tpu.ft.preemption import PreemptedError

        if watchdog is not None:
            watchdog.pause()
        self._drain_checkpoints()
        if (self._ckpt_manager is not None
                and self._ckpt_manager.latest_step() == self.run_state.iteration):
            # the trigger just checkpointed this very iteration (epoch
            # boundary) — it is already durable, don't write it twice
            path = self._ckpt_manager.step_path(self.run_state.iteration)
        else:
            path = self._maybe_checkpoint()
            self._drain_checkpoints()
        logger.warning("preemption: checkpoint %s committed at iteration %d "
                       "— exiting train loop", path,
                       self.run_state.iteration)
        raise PreemptedError(
            f"training preempted at iteration {self.run_state.iteration}"
            + (f"; checkpoint committed at {path}" if path else
               " (no checkpoint directory configured — state NOT saved)"),
            checkpoint_path=path)

    # -- evaluation ------------------------------------------------------

    def evaluate(self, validation_set, validation_method: Sequence,
                 batch_size: int = 32) -> Dict[str, float]:
        """Run metrics over a dataset. Final partial batches are wrap-padded
        to keep shapes static; a mask excludes the padding from statistics
        (exactness the reference gets from dynamic minibatch sizes)."""
        self._ensure_state()
        batch_size = _round_batch(batch_size, self.ctx.mesh.shape[self.ctx.data_axis])
        metric_objs = [metrics_lib.get(m) for m in validation_method]
        gather = getattr(validation_set, "gather_from", None)
        window = self.ctx.local_batch_window(batch_size)
        if (gather is not None and window is not None
                and not getattr(validation_set, "shard_rows", False)):
            gather = None  # see train(): only row-sharded caches span hosts
        cache = validation_set.device_cache if gather is not None else None
        dt = getattr(validation_set, "device_transform", None)
        fused_eval = gather is not None
        eval_plan = None
        if fused_eval and getattr(validation_set, "shard_rows", False):
            dev_plan = getattr(validation_set, "device_eval_plan", None)
            if dev_plan is None:
                # duck-typed sharded device set without an in-graph plan:
                # keep the streaming gather path (host index uploads)
                fused_eval = False
            else:
                eval_plan = (lambda _p=dev_plan, _b=batch_size: _p(_b))
        if fused_eval:
            # HBM-cached set: the whole evaluation epoch is ONE dispatch —
            # in-graph dataset-order plan, metric partials accumulated in
            # the scan carry, one stats fetch (no per-batch index uploads)
            scan_token = self._cache_token(
                "eval_scan",
                tuple(_metric_fingerprint(m) for m in metric_objs),
                id(dt) if dt is not None else None,
                id(validation_set), validation_set.num_samples, batch_size)
            scan_fn = self._jit_cache_get(scan_token)
            if scan_fn is None:
                scan_fn = self._jit_cache_put(
                    scan_token, self._make_eval_scan(
                        metric_objs, validation_set.num_samples, batch_size,
                        dt, gather, eval_plan))
            stats = scan_fn(self.tstate, cache)
            return {m.name: m.finalize(np.asarray(s), float(c))
                    for m, (s, c) in zip(metric_objs, stats)}
        token = self._cache_token(
            "eval",
            tuple(_metric_fingerprint(m) for m in metric_objs),
            id(dt) if dt is not None else None,
            id(validation_set) if gather is not None else None)
        eval_fn = self._jit_cache_get(token)
        if eval_fn is None:
            eval_fn = self._jit_cache_put(
                token, self._make_eval_step(metric_objs, dt, gather))
        mesh = self.ctx.mesh
        totals = [None] * len(metric_objs)
        counts = [0.0] * len(metric_objs)

        def _transfer(item):
            if gather is not None:
                idx, mask = item
                return shard_batch(mesh, idx), shard_batch(mesh, mask)
            xs, y, mask = item
            return (_shard(mesh, xs), _shard(mesh, y), shard_batch(mesh, mask))

        host_iter = (getattr(validation_set, "gather_eval_index_batches",
                             validation_set.eval_index_batches)(batch_size)
                     if gather is not None else
                     _windowed_iter(
                         lambda **kw: validation_set.eval_batches(
                             batch_size, **kw), window))
        eval_depth = int(getattr(validation_set, "prefetch_depth", 0) or 2)
        for batch in _device_prefetch(host_iter, _transfer, depth=eval_depth):
            stats = eval_fn(self.tstate, batch, cache)
            for i, (s, c) in enumerate(stats):
                s = np.asarray(s)
                totals[i] = s if totals[i] is None else totals[i] + s
                counts[i] += float(c)
        return {
            m.name: m.finalize(totals[i] if totals[i] is not None else 0.0, counts[i])
            for i, m in enumerate(metric_objs)
        }

    # -- prediction ------------------------------------------------------

    def predict(self, data_set, batch_size: int = 32) -> np.ndarray:
        """Batched inference over a feature set -> host ndarray (wrap-padded
        tail trimmed).
        """
        self._ensure_state()
        batch_size = _round_batch(batch_size, self.ctx.mesh.shape[self.ctx.data_axis])
        model = self.model

        cast = self._cast_for_compute
        device_transform = getattr(data_set, "device_transform", None)
        gather = getattr(data_set, "gather_from", None)
        window = self.ctx.local_batch_window(batch_size)
        if gather is not None and getattr(data_set, "shard_rows", False):
            # a row-sharded cache gathers in SHARD order — predictions must
            # come back in dataset order, so stream from the host copy
            gather = None
        elif gather is not None and window is not None:
            gather = None  # see train(): HBM cache is single-host only
        cache = data_set.device_cache if gather is not None else None

        if gather is not None:
            # Whole prediction pass in ONE dispatch (the eval-scan pattern):
            # dataset-order plan in-graph, per-step outputs stacked on
            # device, one fetch, wrap-pad tail trimmed on host. The stacked
            # float32 outputs live in HBM next to the cache, so wide-output
            # models (segmentation maps...) fall back to per-batch
            # streaming past a byte budget (checked via eval_shape below —
            # no compile, no execution).
            n = data_set.num_samples
            scan_token = self._cache_token(
                "predict_scan",
                id(device_transform) if device_transform is not None else None,
                id(data_set), n, batch_size)
            pfn = self._jit_cache_get(scan_token)
            if pfn is None:
                data_axis = self.ctx.data_axis
                mesh_ = self.ctx.mesh

                @jax.jit
                def pfn(tstate, cache=None):
                    idxs, _ = _eval_index_plan(n, batch_size)
                    idxs = jax.lax.with_sharding_constraint(
                        idxs, NamedSharding(mesh_, P(None, data_axis)))

                    def step(_, idx):
                        xs, _y = gather(cache, idx)
                        if device_transform is not None:
                            xs = device_transform(xs)
                        pred, _s = model.apply(
                            cast(tstate.params), tstate.model_state, cast(xs),
                            training=False, rng=None)
                        return None, jax.tree_util.tree_map(
                            lambda p: p.astype(jnp.float32), pred)

                    _, preds = jax.lax.scan(step, None, idxs)
                    # (steps, B, ...) -> (steps*B, ...)
                    return jax.tree_util.tree_map(
                        lambda p: p.reshape((-1,) + p.shape[2:]), preds)
                out_shapes = jax.eval_shape(pfn, self.tstate, cache)
                out_bytes = sum(
                    int(np.prod(s.shape)) * s.dtype.itemsize
                    for s in jax.tree_util.tree_leaves(out_shapes))
                budget = int(os.environ.get(
                    "AZOO_PREDICT_SCAN_BYTES", str(1 << 30)))
                if out_bytes > budget:
                    logger.info(
                        "predict: fused output would hold %.1f GiB on "
                        "device (budget %.1f) — streaming per batch",
                        out_bytes / 2**30, budget / 2**30)
                    pfn = None
                else:
                    self._jit_cache_put(scan_token, pfn)
            if pfn is not None:
                pred = pfn(self.tstate, cache)
                if isinstance(pred, (list, tuple)):
                    return tuple(np.asarray(p)[:n] for p in pred)
                return np.asarray(pred)[:n]

        token = self._cache_token(
            "predict",
            id(device_transform) if device_transform is not None else None,
            id(data_set) if gather is not None else None)
        fwd = self._jit_cache_get(token)
        if fwd is None:
            @jax.jit
            def fwd(tstate, xs, cache=None):
                if gather is not None:
                    xs, _ = gather(cache, xs)  # xs is the index vector
                if device_transform is not None:
                    xs = device_transform(xs)
                pred, _ = model.apply(cast(tstate.params), tstate.model_state,
                                      cast(xs), training=False, rng=None)
                return jax.tree_util.tree_map(lambda p: p.astype(jnp.float32), pred)
            self._jit_cache_put(token, fwd)

        mesh = self.ctx.mesh
        outs: List[Any] = []
        multi = False

        def _transfer(item):
            if gather is not None:
                idx, mask = item
                return shard_batch(mesh, idx), mask
            xs, _, mask = item
            return _shard(mesh, xs), mask

        if gather is not None:
            host_iter = data_set.eval_index_batches(batch_size)
        elif window is None:
            host_iter = data_set.eval_batches(batch_size)
        else:
            # Multi-host: each process materializes only its rows of each
            # batch, but keeps the GLOBAL mask — predictions are allgathered
            # below so every host returns the full ordered output (the
            # reference's predict collects to the driver the same way).
            if hasattr(data_set, "eval_index_batches") and hasattr(data_set, "take"):
                def _local_iter():
                    for idx, mask in data_set.eval_index_batches(batch_size):
                        x, _ = data_set.take(idx[window[0]:window[1]])
                        yield x, None, mask
            else:
                # duck-typed datasets without index batching: materialize the
                # global batch, slice x to the local rows, keep the mask
                def _local_iter():
                    lo, hi = window
                    for x, _, mask in data_set.eval_batches(batch_size):
                        xl = jax.tree_util.tree_map(
                            lambda a: np.asarray(a)[lo:hi], x)
                        yield xl, None, mask
            host_iter = _local_iter()
        for dev_xs, mask in _device_prefetch(host_iter, _transfer, depth=2):
            pred = fwd(self.tstate, dev_xs, cache)
            if window is not None:
                from jax.experimental import multihost_utils
                pred = multihost_utils.process_allgather(pred, tiled=True)
            valid = np.asarray(mask).astype(bool)
            if isinstance(pred, (list, tuple)):
                multi = True
                outs.append([np.asarray(p)[valid] for p in pred])
            else:
                outs.append(np.asarray(pred)[valid])
        if multi:
            return tuple(np.concatenate([o[i] for o in outs], axis=0)
                         for i in range(len(outs[0])))
        return np.concatenate(outs, axis=0)
