"""Pipeline parallelism — GPipe-style stage execution over a mesh axis.

The reference has no PP (SURVEY.md §2.4: DP only); this module is part of
the TPU rebuild's beyond-parity distributed story. Design follows the
stacked-stage idiom of TPU pipelining (praxis/scaling-book): all stages
share one layer STRUCTURE, their parameters are stacked on a leading
``stages`` axis, and that axis is sharded over the mesh's ``pipe`` axis —
so the whole pipeline is ONE pytree, one `shard_map`, one XLA program.

Schedule: classic GPipe fill-and-drain. With S stages and M microbatches,
the loop runs T = M + S - 1 ticks; at tick t, stage s processes microbatch
``t - s`` (when in range), receiving activations from stage s-1 via
``lax.ppermute`` over ICI neighbor links. Gradients flow through the same
permutes (ppermute is differentiable), so a jitted train step backprops
the pipeline in reverse automatically — no hand-written 1F1B needed for
correctness (recompute/memory scheduling can layer on via
``jax.checkpoint`` around ``stage_fn``).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, PartitionSpec as P


def stack_stage_params(param_list):
    """Stack S per-stage pytrees (identical structure) into one pytree with
    a leading ``stages`` axis — the shardable pipeline parameter layout."""
    return jax.tree_util.tree_map(
        lambda *leaves: jnp.stack(leaves, axis=0), *param_list)


def _pipeline_local(stacked_params, micro_x, stage_fn: Callable,
                    axis_name: str, n_stages: int):
    """Per-device body (inside shard_map over ``pipe``).

    ``stacked_params`` leaves arrive with leading dim 1 (this device's stage
    slice); ``micro_x`` is the full (M, mb, ...) microbatch stack
    (replicated — only stage 0 reads it). Returns the (M, mb, ...) outputs
    of the LAST stage (psum-broadcast so the result is replicated)."""
    s_idx = lax.axis_index(axis_name)
    params = jax.tree_util.tree_map(lambda a: a[0], stacked_params)
    m = micro_x.shape[0]

    out_shape = jax.eval_shape(stage_fn, params, micro_x[0])
    if out_shape.shape != micro_x.shape[1:] or \
            out_shape.dtype != micro_x.dtype:
        raise ValueError(
            f"pipeline stages must preserve activation shape AND dtype "
            f"(the ring buffer is typed once); got {micro_x.shape[1:]}/"
            f"{micro_x.dtype} -> {out_shape.shape}/{out_shape.dtype}")

    def tick(t, carry):
        recv, outputs = carry
        # stage 0 injects microbatch t; later stages consume the ring buffer
        inject = lax.dynamic_index_in_dim(micro_x, jnp.clip(t, 0, m - 1),
                                          axis=0, keepdims=False)
        x_in = jnp.where(s_idx == 0, inject, recv)
        y = stage_fn(params, x_in)
        active = (t - s_idx >= 0) & (t - s_idx < m)
        y = jnp.where(active, y, jnp.zeros_like(y))
        # the last stage records its finished microbatch at index t - s
        mb_idx = jnp.clip(t - s_idx, 0, m - 1)
        write = (active & (s_idx == n_stages - 1)).astype(y.dtype)
        prev = lax.dynamic_index_in_dim(outputs, mb_idx, axis=0,
                                        keepdims=False)
        outputs = lax.dynamic_update_index_in_dim(
            outputs, write * y + (1 - write) * prev, mb_idx, axis=0)
        # hand activations to the next stage (no wraparound edge: GPipe)
        recv_next = lax.ppermute(
            y, axis_name, [(i, i + 1) for i in range(n_stages - 1)])
        return recv_next, outputs

    recv0 = jnp.zeros(micro_x.shape[1:], micro_x.dtype)
    out0 = jnp.zeros((m,) + tuple(out_shape.shape), out_shape.dtype)
    _, outputs = lax.fori_loop(0, m + n_stages - 1, tick, (recv0, out0))
    # only the last shard's buffer is populated; broadcast it to all so the
    # out_spec can be replicated
    return lax.psum(outputs, axis_name)


def pipeline_apply(stage_fn: Callable, stacked_params, x, mesh: Mesh,
                   n_microbatches: int, pipe_axis: str = "pipe",
                   data_axis=None):
    """Run ``x`` through S pipelined stages.

    ``stage_fn(params, x) -> y`` is one stage (shape-preserving);
    ``stacked_params``: pytree with leading stages axis == mesh[pipe_axis];
    ``x``: (batch, ...) with batch % n_microbatches == 0.
    Returns (batch, ...) outputs. Differentiable end to end.

    ``data_axis``: a second mesh axis to shard each microbatch's batch dim
    over — dp x pp composition (every pipe rank then processes only its
    data shard; parameter gradients sum over the data axis through the
    shard_map backward as usual). None replicates the batch over the
    non-pipe axes (pure-pp behavior).
    """
    S = mesh.shape[pipe_axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    if leaves and leaves[0].shape[0] != S:
        raise ValueError(
            f"stacked params lead dim {leaves[0].shape[0]} != mesh "
            f"'{pipe_axis}' size {S}")
    b = x.shape[0]
    if b % n_microbatches:
        raise ValueError(f"batch {b} % microbatches {n_microbatches} != 0")
    mb = b // n_microbatches
    if data_axis is not None and mb % mesh.shape[data_axis] != 0:
        raise ValueError(
            f"microbatch size {mb} must divide across mesh axis "
            f"'{data_axis}' ({mesh.shape[data_axis]})")
    micro_x = x.reshape((n_microbatches, mb) + x.shape[1:])

    params_spec = jax.tree_util.tree_map(
        lambda _: P(pipe_axis), stacked_params)
    x_spec = P(None, data_axis) if data_axis is not None else P()
    fn = shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn,
                          axis_name=pipe_axis, n_stages=S),
        mesh=mesh,
        in_specs=(params_spec, x_spec),
        out_specs=x_spec,
        check_vma=False)
    out = fn(stacked_params, micro_x)
    return out.reshape((b,) + tuple(out.shape[2:]))
