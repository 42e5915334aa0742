"""train_pipelined — the MPMD microbatch pipeline training driver.

One training step runs the whole microbatch schedule (1F1B or naive
GPipe fill/drain, :mod:`~analytics_zoo_tpu.pipeline.schedule`) through
per-stage COMPILED programs:

- ``fwd_s``  — stage ``s < K-1`` forward over its layer segment;
- ``last``   — the last stage fused: forward + loss-SUM + backward in
  one program (the ``loss_sum_fn`` math of the distributed grad step,
  so masking/count semantics are identical);
- ``bwd_s``  — stage ``s < K-1`` backward, REMATERIALIZING the forward
  from the stashed stage input (``jax.vjp`` over the segment) — slots
  hold inputs, not full activation tapes;
- ``combine``— the optimizer update on the full tree:
  ``g = Σ_m grads / max(count, 1) + d(regularization)`` with the frozen
  update-mask zeroing before AND after ``tx.update``, exactly the
  distributed combine.

Activations ride the preallocated per-(stage, slot) pools of
:mod:`~analytics_zoo_tpu.pipeline.buffers`; pool sizes come from a
dry-run of the event order (:meth:`MicrobatchSchedule.measured_slots`),
so an over-budget schedule fails at setup, not mid-step.

Parity contract (pinned by tests/test_pipeline.py):

- GPipe and 1F1B produce BITWISE-identical losses/params: both fold
  per-microbatch gradient sums in fixed ascending-microbatch order
  through the same jitted tree-add, and the per-(stage, microbatch)
  programs are the same executables — only the event order differs.
- Pipelined vs unpipelined on the same global batch is bitwise or
  documented-ULP: splitting one gemm into M microbatch gemms + adds
  reassociates the reduction (the PR 13 sum-vs-mean precedent; the
  test states its ULP bound).
- Dropout parity holds at M=1 only: the per-layer rng fold uses
  ABSOLUTE layer indices (StageSegment.indices), so a stage-split
  forward draws the unsplit model's masks, but every microbatch shares
  the step rng — M>=2 draws the same mask per microbatch where the
  unpipelined batch draws once over the full batch.
- ``model_state`` (e.g. batch-norm moments): every microbatch forwards
  with the step-start state; the committed new state is the LAST
  microbatch's — exact for stateless models, a documented boundary
  otherwise (docs/pipeline-parallel.md).

Fault tolerance: checkpoints are stage-owned two-phase sharded commits
(stage k's thread commits shard k via
:func:`~analytics_zoo_tpu.ft.distributed.commit_sharded_checkpoint`
with ``shard_meta={"stage": k}``), and every schedule event is a
``pipeline_mid_schedule_kill`` chaos site — the kill matrix proves
kill → ``auto_resume`` is bitwise even mid-schedule, because a step
only publishes state at its end.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from analytics_zoo_tpu.pipeline.buffers import ActivationSlots
from analytics_zoo_tpu.pipeline.plan import StagePlan, StageSegment
from analytics_zoo_tpu.pipeline.schedule import MicrobatchSchedule

__all__ = ["train_pipelined"]

logger = logging.getLogger("analytics_zoo_tpu")

#: Wall-clock bound on one stage-sharded checkpoint gang commit. The
#: committers are threads in ONE process, so a peer can't die without us
#: — the timeout only turns a filesystem wedge into an error.
_COMMIT_TIMEOUT_S = 120.0


def _slice(tree, lo: int, hi: int):
    """Row-slice every leaf of a host batch element (lists/tuples for
    multi-input models slice leaf-wise)."""
    return jax.tree_util.tree_map(lambda a: np.asarray(a)[lo:hi], tree)


def _make_segment_apply(segment: StageSegment, cast: Callable,
                        cast_input: bool):
    """The stage-local mirror of ``Sequential.apply``: same per-layer
    call protocol, same ``fold_in(rng, i)`` with the ABSOLUTE layer
    index ``i`` — the stage split must not move any layer's dropout
    stream. ``cast_input`` applies the compute-dtype cast to the stage
    input (stage 0 / single stage only, matching ``cast(xs)`` in the
    unpipelined loss)."""
    layers = segment.layers
    indices = segment.indices

    def seg_apply(params_s, state_s, x, rng):
        if cast_input:
            x = cast(x)
        p_all = cast(params_s)
        new_state: Dict[str, Any] = {}
        for i, layer in zip(indices, layers):
            kwargs: Dict[str, Any] = {"training": True}
            if rng is not None:
                kwargs["rng"] = jax.random.fold_in(rng, i)
            p = p_all.get(layer.name, {})
            if layer.has_state:
                x, upd = layer.call(p, x, state=state_s.get(layer.name, {}),
                                    **kwargs)
                new_state[layer.name] = upd
            else:
                x = layer.call(p, x, **kwargs)
        return x, new_state

    return seg_apply


def _build_programs(est, criterion: Callable, stage_plan: StagePlan,
                    segments: List[StageSegment]):
    """Per-stage jitted programs + the combine/accumulate programs,
    cached on the Estimator's compiled-step cache (same discipline as
    the fused paths: repeated ``train_pipelined`` calls must not
    recompile)."""
    token = est._cache_token("pipeline_programs", stage_plan.fingerprint(),
                             id(criterion),
                             getattr(criterion, "__name__", ""))
    cached = est._jit_cache_get(token)
    if cached is not None:
        return cached

    from analytics_zoo_tpu.keras import objectives as objectives_lib

    model = est.model
    cast = est._cast_for_compute
    ps_criterion = objectives_lib.get_per_sample(criterion)
    update_mask = est._update_mask(est.tstate.params)
    tx = est._tx()
    k = stage_plan.num_stages

    fwd: List[Optional[Callable]] = [None] * k
    bwd: List[Optional[Callable]] = [None] * k
    for s in range(k - 1):
        seg_apply = _make_segment_apply(segments[s], cast,
                                        cast_input=(s == 0))

        def fwd_fn(params_s, state_s, x, rng, _apply=seg_apply):
            return _apply(params_s, state_s, x, rng)

        def bwd_fn(params_s, state_s, x, dy, rng, _apply=seg_apply):
            def f(p, xx):
                y, _ = _apply(p, state_s, xx, rng)
                return y

            _, vjp = jax.vjp(f, params_s, x)
            dp, dx = vjp(dy)
            return dx, dp

        fwd[s] = jax.jit(fwd_fn)
        bwd[s] = jax.jit(bwd_fn)

    last_apply = _make_segment_apply(segments[k - 1], cast,
                                     cast_input=(k == 1))

    def last_fn(params_s, state_s, x, y, mask, rng):
        # the distributed grad step's loss_sum_fn, over the last segment
        def f(p, xx):
            pred, new_state = last_apply(p, state_s, xx, rng)
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
                    loss_sum = raw * rows
                    count = rows
            return loss_sum, (new_state, count)

        grads_fn = jax.value_and_grad(f, argnums=(0, 1), has_aux=True)
        (ls, (new_state, cnt)), (dp, dx) = grads_fn(params_s, x)
        return dx, dp, ls, cnt, new_state

    def combine_fn(params, gsum, count, opt_state):
        greg = jax.grad(model.regularization)(params)
        g = jax.tree_util.tree_map(
            lambda a, b: a / jnp.maximum(count, 1.0) + b, gsum, greg)
        if update_mask is not None:
            g = jax.tree_util.tree_map(
                lambda gg, m: gg if m else jnp.zeros_like(gg),
                g, update_mask)
        updates, new_opt = tx.update(g, opt_state, params)
        if update_mask is not None:
            updates = jax.tree_util.tree_map(
                lambda u, m: u if m else jnp.zeros_like(u),
                updates, update_mask)
        new_params = optax.apply_updates(params, updates)
        return new_params, new_opt

    def acc_fn(a, b):
        return jax.tree_util.tree_map(jnp.add, a, b)

    programs = {"fwd": fwd, "bwd": bwd, "last": jax.jit(last_fn),
                "combine": jax.jit(combine_fn), "acc": jax.jit(acc_fn)}
    return est._jit_cache_put(token, programs)


def _run_schedule(programs, stage_params, stage_state, events, slots,
                  num_stages: int, num_microbatches: int, mb_rows: int,
                  xs, y, mask, rng):
    """Execute one step's schedule: every event goes through the chaos
    hook, activations ride slot leases, per-microbatch gradient pieces
    accumulate and fold in FIXED ascending-microbatch order (the
    GPipe-vs-1F1B bitwise invariant)."""
    from analytics_zoo_tpu.ft import chaos

    k, m_total = num_stages, num_microbatches
    leases: Dict[Tuple[int, int], Any] = {}
    cot: Dict[Tuple[int, int], Any] = {}
    gparts: List[Dict[str, Any]] = [dict() for _ in range(m_total)]
    ls_parts: List[Any] = [None] * m_total
    cnt_parts: List[Any] = [None] * m_total
    state_out: Dict[int, Any] = {}

    for kind, s, m in events:
        chaos.maybe_fail("pipeline_mid_schedule_kill")
        lo, hi = m * mb_rows, (m + 1) * mb_rows
        if kind == "F":
            if s == 0:
                leases[(0, m)] = slots.checkout(0, _slice(xs, lo, hi))
            x = leases[(s, m)].payload
            yv, new_ss = programs["fwd"][s](
                stage_params[s], stage_state[s], x, rng)
            state_out[s] = new_ss
            leases[(s + 1, m)] = slots.checkout(s + 1, yv)
        elif kind == "L":
            if k == 1:
                leases[(s, m)] = slots.checkout(s, _slice(xs, lo, hi))
            x = leases[(s, m)].payload
            dx, dp, ls, cnt, new_ss = programs["last"](
                stage_params[s], stage_state[s], x,
                _slice(y, lo, hi), mask[lo:hi], rng)
            state_out[s] = new_ss
            gparts[m].update(dp)
            ls_parts[m], cnt_parts[m] = ls, cnt
            if s > 0:
                cot[(s - 1, m)] = dx
            slots.release(leases.pop((s, m)))
        else:  # "B"
            x = leases[(s, m)].payload
            dy = cot.pop((s, m))
            dx, dp = programs["bwd"][s](
                stage_params[s], stage_state[s], x, dy, rng)
            gparts[m].update(dp)
            if s > 0:
                cot[(s - 1, m)] = dx
            slots.release(leases.pop((s, m)))

    slots.assert_drained()
    if cot:
        raise RuntimeError(
            f"cotangents never consumed after the schedule drained: "
            f"{sorted(cot)}")

    gsum = gparts[0]
    ls_tot, cnt_tot = ls_parts[0], cnt_parts[0]
    for m in range(1, m_total):
        gsum = programs["acc"](gsum, gparts[m])
        ls_tot, cnt_tot = programs["acc"]((ls_tot, cnt_tot),
                                          (ls_parts[m], cnt_parts[m]))
    new_mstate: Dict[str, Any] = {}
    for s in range(k):
        new_mstate.update(state_out.get(s, {}))
    return gsum, ls_tot, cnt_tot, new_mstate


# -- stage-sharded checkpoints --------------------------------------------


def _commit_stage_gang(path: str, shards: List[List[Tuple[str, Any]]], *,
                       expected_keys, metadata, commit_id: str,
                       overwrite: bool) -> None:
    """All K stage shards through the two-phase sharded commit protocol:
    stage k plays host k (``shard_meta={"stage": k}`` rides in its shard
    manifest), stages 1..K-1 commit on threads while stage 0 — the
    coordinator that validates and publishes — runs in the caller's
    thread, so its exceptions surface directly."""
    from analytics_zoo_tpu.ft import distributed as dist_lib

    k = len(shards)
    errors: List[Optional[BaseException]] = [None] * k

    def commit(stage: int) -> None:
        try:
            dist_lib.commit_sharded_checkpoint(
                path, shards[stage], host_id=stage, num_hosts=k,
                expected_keys=expected_keys if stage == 0 else None,
                metadata=metadata if stage == 0 else None,
                commit_id=commit_id, timeout_s=_COMMIT_TIMEOUT_S,
                overwrite=overwrite, shard_meta={"stage": stage})
        except BaseException as e:  # surfaced below, per stage
            errors[stage] = e

    threads = [threading.Thread(target=commit, args=(stage,), daemon=True,
                                name=f"pipeline-ckpt-stage{stage}")
               for stage in range(1, k)]
    for t in threads:
        t.start()
    commit(0)
    for t in threads:
        t.join(_COMMIT_TIMEOUT_S)
    for stage, err in enumerate(errors):
        if err is not None:
            raise err


def _write_pipelined_checkpoint(est, stage_plan: StagePlan,
                                layer_stages: Dict[str, int], opt_state,
                                sched: MicrobatchSchedule) -> str:
    from analytics_zoo_tpu.common.observability import get_tracer
    from analytics_zoo_tpu.engine import checkpoint as ckpt_lib
    from analytics_zoo_tpu.ft import atomic

    rs = est.run_state
    tree = {"params": est.tstate.params,
            "model_state": est.tstate.model_state,
            "opt_state": opt_state,
            "step": est.tstate.step}
    flat = ckpt_lib._flatten(jax.device_get(tree))
    shards = stage_plan.partition_flat(flat, layer_stages)
    expected = {key for key, _ in flat}
    seed, counter = est.ctx.rng_state()
    metadata = {"epoch": rs.epoch,
                "iteration": rs.iteration,
                "epoch_step": rs.epoch_step,
                "rng_seed": seed,
                "rng_counter": counter,
                "pipeline": {"num_stages": stage_plan.num_stages,
                             "schedule": sched.mode,
                             "num_microbatches": sched.num_microbatches,
                             "plan": stage_plan.fingerprint()}}
    path = os.path.join(est._checkpoint_path, f"ckpt_{rs.iteration}")
    with get_tracer().span("train.checkpoint", iteration=rs.iteration,
                           pipeline=True):
        _commit_stage_gang(path, shards, expected_keys=expected,
                           metadata=metadata,
                           commit_id=f"pipeline-{rs.iteration}",
                           overwrite=est._checkpoint_overwrite)
    steps = [s for s, _ in atomic.committed_checkpoints(
        est._checkpoint_path, "ckpt")]
    keep = est._dist_keep_steps(steps)
    if keep is not None:
        atomic.sweep_stale(est._checkpoint_path, keep_steps=keep)
    return path


def _resume_pipelined(est, opt_template):
    """Restore the newest committed stage-sharded checkpoint: rebuild
    params/model_state/opt_state/step BY KEY against the live template
    (stage-sharded manifests order leaves by owning stage, never
    positionally), with the corrupt → previous-checkpoint fallback of
    the other resume paths. Returns ``(opt_state_or_None, resumed)``."""
    from analytics_zoo_tpu.engine import checkpoint as ckpt_lib
    from analytics_zoo_tpu.engine.estimator import TrainState
    from analytics_zoo_tpu.ft import atomic
    from analytics_zoo_tpu.ft.atomic import (CheckpointCorruptError,
                                             CheckpointError)
    from analytics_zoo_tpu.parallel.sharding import replicated

    atomic.sweep_stale(est._checkpoint_path)
    candidates = atomic.committed_checkpoints(est._checkpoint_path, "ckpt")
    if not candidates:
        return None, False
    template = {"params": est.tstate.params,
                "model_state": est.tstate.model_state,
                "opt_state": opt_template,
                "step": est.tstate.step}
    tpl_keys = [key for key, _ in ckpt_lib._flatten(template)]
    tpl_leaves, treedef = jax.tree_util.tree_flatten(template)
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
                if tuple(arr.shape) != tuple(np.shape(like)):
                    raise ValueError(
                        f"Checkpoint {path!r}: leaf {key!r} has shape "
                        f"{tuple(arr.shape)}, target expects "
                        f"{tuple(np.shape(like))}")
                leaves.append(arr)
            restored = jax.tree_util.tree_unflatten(treedef, leaves)
            if (meta or {}).get("pipeline") is None:
                raise CheckpointCorruptError(
                    f"checkpoint {path!r} carries no 'pipeline' metadata "
                    "— not a pipelined checkpoint")
        except CheckpointCorruptError as e:
            logger.warning("checkpoint %s is corrupt (%s) — trying the "
                           "previous committed one", path, e)
            last_err = e
            continue
        rep = replicated(est.ctx.mesh)
        rest = jax.device_put(
            (restored["model_state"], restored["step"]), rep)
        est.tstate = TrainState(
            est.place_params(restored["params"]), rest[0], (), rest[1])
        opt_state = jax.device_put(restored["opt_state"], rep)
        meta = meta or {}
        est.run_state.epoch = int(meta.get("epoch", 0))
        est.run_state.iteration = int(meta.get("iteration", 0))
        est.run_state.epoch_step = int(meta.get("epoch_step", 0))
        if "rng_counter" in meta:
            seed = int(meta.get("rng_seed", est.ctx.rng_state()[0]))
            est.ctx.set_rng_state(seed, int(meta["rng_counter"]))
        logger.info(
            "pipeline resumed from %s (epoch %d, iteration %d, %d "
            "stage shard(s))", path, est.run_state.epoch,
            est.run_state.iteration,
            int(meta["pipeline"].get("num_stages", 0)))
        return opt_state, True
    raise CheckpointError(
        f"every checkpoint under {est._checkpoint_path!r} is corrupt"
    ) from last_err


# -- the driver -----------------------------------------------------------


def train_pipelined(est, train_set, criterion: Callable,
                    stage_plan: StagePlan, *,
                    num_microbatches: int = 1, schedule: str = "1f1b",
                    end_trigger=None, checkpoint_trigger=None,
                    batch_size: int = 32, auto_resume: bool = False):
    """Pipeline-parallel training over ``stage_plan``'s K stages.

    ``batch_size`` is the GLOBAL batch — rounded up to divide
    ``num_microbatches``, then split into M contiguous row slices that
    flow through the schedule. With ``K=1, M=1`` the step degenerates to
    one fused program and the trajectory is an unpipelined baseline.
    See the module docstring for the parity contract and
    docs/pipeline-parallel.md for the schedule/bubble math.
    """
    from analytics_zoo_tpu.engine.estimator import (EveryEpoch, MaxEpoch,
                                                    TrainState,
                                                    _round_batch,
                                                    _skip_steps)
    from analytics_zoo_tpu.common.observability import (get_tracer,
                                                        training_metrics)
    from analytics_zoo_tpu.ft import distributed as dist_lib
    from analytics_zoo_tpu.ft.preemption import PreemptedError

    if not isinstance(stage_plan, StagePlan):
        raise TypeError(
            f"stage_plan must be a StagePlan, got "
            f"{type(stage_plan).__name__}")
    if est.gradient_accumulation > 1:
        raise NotImplementedError(
            "train_pipelined does not support gradient_accumulation > 1 "
            "— the schedule already accumulates over its microbatches; "
            "raise num_microbatches instead")
    if est.zero1:
        raise NotImplementedError(
            "zero1 is not supported under train_pipelined (optimizer "
            "state is stage-partitioned at checkpoint time instead)")

    est._ensure_state()
    if est.tstate.opt_state != ():
        # the pipelined loop carries the live optimizer state itself
        # (stage-partitioned at checkpoint time) — same discipline as
        # train_distributed
        est.tstate = est.tstate._replace(opt_state=())

    segments = stage_plan.split(est.model)
    layer_stages = {layer.name: seg.stage
                    for seg in segments for layer in seg.layers}
    param_names = set(est.tstate.params)
    covered = {name for seg in segments for name in seg.names}
    orphaned = sorted(param_names - covered)
    if orphaned:
        raise ValueError(
            f"params exist for layer(s) {orphaned} that the StagePlan "
            "did not assign — stage split would silently drop their "
            "gradients")

    k = stage_plan.num_stages
    m_total = int(num_microbatches)
    if m_total < 1:
        raise ValueError(
            f"num_microbatches must be >= 1, got {num_microbatches}")
    sched = MicrobatchSchedule(k, m_total, mode=schedule)
    events = sched.events()
    pool_sizes = sched.measured_slots()
    global_batch = _round_batch(batch_size, m_total)
    mb_rows = global_batch // m_total

    programs = _build_programs(est, criterion, stage_plan, segments)
    opt_state = None
    resumed = False
    if (auto_resume and est._checkpoint_path is not None
            and est.run_state.iteration == 0):
        opt_state, resumed = _resume_pipelined(
            est, est._init_opt_state(est.tstate.params))
    if opt_state is None:
        opt_state = est._init_opt_state(est.tstate.params)

    rs = est.run_state
    end_trigger = end_trigger or MaxEpoch(rs.epoch + 1)
    checkpoint_trigger = checkpoint_trigger or EveryEpoch()
    obs = training_metrics()
    tracer = get_tracer()
    save_error: List[Optional[BaseException]] = [None]
    last_saved = [rs.iteration if resumed else -1]

    def _save(coordinated_exit: bool = False):
        if save_error[0] is not None:
            err, save_error[0] = save_error[0], None
            raise err
        if est._checkpoint_path is None:
            return None
        if last_saved[0] == rs.iteration:
            return os.path.join(est._checkpoint_path,
                                f"ckpt_{rs.iteration}")
        try:
            path = _write_pipelined_checkpoint(
                est, stage_plan, layer_stages, opt_state, sched)
        except (dist_lib.DistTimeoutError, dist_lib.DistCommitError) as e:
            if coordinated_exit:
                raise
            logger.error("pipelined checkpoint at iteration %d failed "
                         "(%s) — training continues; the error re-raises "
                         "at the next save attempt", rs.iteration, e)
            save_error[0] = e
            return None
        last_saved[0] = rs.iteration
        return path

    def _preempt_exit():
        path = _save(coordinated_exit=True)
        raise PreemptedError(
            f"training preempted at iteration {rs.iteration}"
            + (f"; checkpoint committed at {path}" if path else
               " (no checkpoint directory configured — state NOT saved)"),
            checkpoint_path=path)

    while not end_trigger(rs):
        rs.epoch_finished = False
        resume_skip = rs.epoch_step
        epoch_start = time.time()
        epoch_loss, epoch_batches = 0.0, 0
        if hasattr(train_set, "train_batches"):
            host_iter = _skip_steps(
                lambda **kw: train_set.train_batches(
                    global_batch, shuffle=True, seed=rs.epoch, **kw),
                resume_skip)
        else:
            host_iter = _skip_steps(
                lambda **kw: train_set.batches(
                    global_batch, shuffle=True, seed=rs.epoch, **kw),
                resume_skip)
        for batch in host_iter:
            rng = est.ctx.next_rng_key()
            xs, y, *rest = batch
            mask = rest[0] if rest else None
            if mask is None:
                rows = np.shape(jax.tree_util.tree_leaves(y)[0])[0]
                mask = np.ones((rows,), np.float32)
            mask = np.asarray(mask, np.float32)
            stage_params = [
                {name: est.tstate.params[name]
                 for name in seg.names if name in est.tstate.params}
                for seg in segments]
            stage_state = [
                {name: est.tstate.model_state.get(name, {})
                 for name in seg.names
                 if name in est.tstate.model_state}
                for seg in segments]
            slots = ActivationSlots(pool_sizes)
            with tracer.span("train.dispatch", kind="pipeline_step",
                             stages=k, microbatches=m_total):
                gsum, ls_tot, cnt_tot, new_mstate = _run_schedule(
                    programs, stage_params, stage_state, events, slots,
                    k, m_total, mb_rows, xs, y, mask, rng)
                new_params, opt_state = programs["combine"](
                    est.tstate.params, gsum, cnt_tot, opt_state)
            loss_val = float(ls_tot) / max(float(cnt_tot), 1.0)
            est.tstate = TrainState(new_params, new_mstate, (),
                                    est.tstate.step + 1)
            rs.iteration += 1
            rs.epoch_step += 1
            rs.loss = loss_val
            epoch_loss += loss_val
            epoch_batches += 1
            obs["steps"].inc()
            if est.train_summary is not None:
                est.train_summary.add_scalar("Loss", loss_val,
                                             rs.iteration)
            if est._preemption is not None and est._preemption.requested:
                _preempt_exit()
            if end_trigger(rs):
                break
            if (checkpoint_trigger(rs)
                    and not isinstance(checkpoint_trigger, EveryEpoch)):
                _save()
        rs.epoch += 1
        rs.epoch_step = 0
        rs.epoch_finished = True
        logger.info("Epoch %d done in %.2fs — mean loss %.5f (%d stages, "
                    "%d microbatches, %s)", rs.epoch,
                    time.time() - epoch_start,
                    epoch_loss / max(epoch_batches, 1), k, m_total,
                    sched.mode)
        if checkpoint_trigger(rs):
            _save()
        if est._preemption is not None and est._preemption.requested:
            _preempt_exit()
    if save_error[0] is not None:
        err, save_error[0] = save_error[0], None
        raise err
    return est
