"""Mixture-of-Experts with expert parallelism over a mesh axis.

The reference has no EP (SURVEY.md §2.4); this completes the dp/tp/pp/sp/ep
sharding family for the multi-chip story. The algorithm is the standard
TPU dispatch/combine formulation (Mesh-TF / Switch-style):

- router: tokens -> softmax over E experts, top-1 assignment;
- capacity: each expert takes at most C = ceil(tokens/E * factor) tokens;
  overflow tokens are dropped (their combine weight is 0 — the residual
  connection around the MoE layer carries them through unchanged);
- dispatch:  ``einsum('te,td->ecd'-style)`` one-hot scatter into per-expert
  buffers, whose E axis shards over the mesh ``expert`` axis;
- experts: two-layer FFN applied per expert slice (a batched matmul on the
  MXU — each device computes only its local experts);
- combine: the transposed einsum, weighted by the router gate, with the
  cross-expert sum riding the sharded contraction (XLA inserts the
  reduce-scatter/all-gather).

Everything is dense fixed-shape einsums — no dynamic gather/sort — so one
jitted program covers any routing pattern.

Below that Switch layer sits the other family (``route_topk`` /
``held_experts_ffn``): sigmoid top-k routing that drops nothing, computed
over the experts one chip of an expert-parallel group holds.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def init_moe_params(rng, d_model: int, d_hidden: int, n_experts: int,
                    dtype=jnp.float32):
    """Router + stacked expert FFN weights (E leading axis = the EP shard
    axis)."""
    k1, k2, k3 = jax.random.split(rng, 3)
    scale_in = math.sqrt(2.0 / d_model)
    scale_out = math.sqrt(2.0 / d_hidden)
    return {
        "router": jax.random.normal(k1, (d_model, n_experts), dtype) * 0.02,
        "w_in": jax.random.normal(
            k2, (n_experts, d_model, d_hidden), dtype) * scale_in,
        "w_out": jax.random.normal(
            k3, (n_experts, d_hidden, d_model), dtype) * scale_out,
    }


def moe_pspecs(expert_axis: str = "expert"):
    """PartitionSpecs for init_moe_params output (router replicated,
    experts sharded on their leading axis)."""
    return {"router": P(), "w_in": P(expert_axis), "w_out": P(expert_axis)}


def moe_ffn(params, x, capacity_factor: float = 1.25,
            return_aux: bool = False):
    """Top-1 MoE FFN. ``x``: (tokens, d_model) -> (tokens, d_model).

    Pure function of sharded inputs — run it under jit with ``w_in/w_out``
    placed by :func:`moe_pspecs` and GSPMD partitions the expert matmuls
    and inserts the dispatch/combine collectives; no shard_map needed.
    Dropped (over-capacity) tokens produce zero output, so call sites
    should wrap the layer in a residual connection.
    """
    t, d = x.shape
    e = params["router"].shape[1]
    c = max(1, int(math.ceil(t / e * capacity_factor)))

    logits = x @ params["router"]                      # (T, E)
    gates = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    expert_idx = jnp.argmax(gates, axis=-1)            # (T,)
    gate = jnp.max(gates, axis=-1)                     # (T,)

    # position of each token within its expert's queue (0-based; the -1
    # must apply only at the assigned entry, so mask AFTER subtracting)
    onehot = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)   # (T, E)
    pos = (jnp.cumsum(onehot, axis=0) - 1.0) * onehot  # (T, E), 0 elsewhere
    pos_in_expert = jnp.sum(pos, axis=-1)              # (T,)
    keep = pos_in_expert < c
    gate = gate * keep

    # dispatch tensor (T, E, C): one-hot in both expert and slot
    slot = jax.nn.one_hot(
        jnp.clip(pos_in_expert, 0, c - 1).astype(jnp.int32), c,
        dtype=jnp.float32)                             # (T, C)
    dispatch = onehot[:, :, None] * slot[:, None, :] * keep[:, None, None]
    combine = dispatch * gate[:, None, None]           # (T, E, C)

    xe = jnp.einsum("tec,td->ecd", dispatch, x.astype(jnp.float32))
    h = jax.nn.relu(jnp.einsum("ecd,edh->ech", xe,
                               params["w_in"].astype(jnp.float32)))
    ye = jnp.einsum("ech,ehd->ecd", h,
                    params["w_out"].astype(jnp.float32))
    y = jnp.einsum("tec,ecd->td", combine, ye).astype(x.dtype)

    if return_aux:
        # Switch-style load-balancing auxiliary loss
        frac_tokens = jnp.mean(onehot, axis=0)
        frac_gates = jnp.mean(gates, axis=0)
        aux = e * jnp.sum(frac_tokens * frac_gates)
        return y, {"aux_loss": aux,
                   "dropped": jnp.sum(1.0 - keep) / t}
    return y


def place_moe_params(params, mesh: Mesh, expert_axis: str = "expert"):
    """Device-put the params with their EP shardings."""
    specs = moe_pspecs(expert_axis)
    return {k: jax.device_put(v, NamedSharding(mesh, specs[k]))
            for k, v in params.items()}


# ---------------------------------------------------------------------------
# Top-k routing that drops nothing, over the experts one chip holds
# ---------------------------------------------------------------------------
#
# The layer of today's sparse decoders: every token picks its k best of E
# experts, no capacity, no drop. A chip of an expert-parallel group holds
# ``count`` of the E experts, those numbered from ``offset``; it routes over
# all E and computes the part of the result its own experts give. Shapes are
# fixed whatever the routing: a chunk of c tokens makes c*k assignments,
# sorted by expert, and a grouped matrix product visits only the rows of the
# experts held (its grid is as long as the live tiles, so rows of absent
# experts are neither read nor computed, and come back zero).

# rows x contraction x columns of one grouped-product tile; the largest of
# the published sizes' divisors that the 16 MiB of scoped VMEM holds
_GMM_TILING = (512, 1024, 1024)
# tokens of one chunk through the experts held: a sorted chunk holds chunk*k
# rows whatever the routing, so the chunk bounds the layer's buffers
_CHUNK_TOKENS = 4096


def route_topk(x, router, select_bias, k: int, normalize: bool = True,
               scale: float = 1.0):
    """Sigmoid top-k routing in float32. ``x`` (T, d), ``router`` (d, E),
    ``select_bias`` (E,): added to the scores for the pick only, outside the
    gradient. Returns (picked (T, k) int32, weights (T, k) float32, counts
    (E,) float32: the tokens routed to each expert)."""
    scores = jax.nn.sigmoid(jnp.dot(
        x.astype(jnp.float32), router.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(select_bias), k)
    w = jnp.take_along_axis(scores, picked, axis=-1)
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    counts = jnp.sum(jax.nn.one_hot(picked, scores.shape[-1],
                                    dtype=jnp.float32), axis=(0, 1))
    return picked.astype(jnp.int32), w * scale, counts


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_sorted(x, order, inv, k: int):
    """x (c, d) -> (c*k, d): row p is the token of the p-th assignment in
    expert order. Both directions are gathers (a scatter-add of rows is the
    slow way on the chip): the transpose gathers the k sorted rows of each
    token back and sums them."""
    del inv
    return x[order // k]


def _rows_to_sorted_fwd(x, order, inv, k):
    return x[order // k], (inv, x.shape[0])


def _rows_to_sorted_bwd(k, res, g):
    inv, c = res
    back = g[inv].reshape(c, k, g.shape[-1])
    return (jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype),
            None, None)


_rows_to_sorted.defvjp(_rows_to_sorted_fwd, _rows_to_sorted_bwd)


@jax.custom_vjp
def _rows_from_sorted(y, order, inv):
    """y (c*k, d) in expert order -> assignment order (token-major)."""
    del order
    return y[inv]


_rows_from_sorted.defvjp(lambda y, order, inv: (y[inv], order),
                         lambda order, g: (g[order], None, None))


def _gmm(lhs, rhs, sizes, offset):
    """Rows of ``lhs`` times their expert's matrix: jax's bundled grouped
    matrix product (megablox), told which experts ``rhs`` holds."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from analytics_zoo_tpu.ops.flash_attention import _interpret

    m, kdim = lhs.shape
    n = rhs.shape[-1]
    tiling = (min(_GMM_TILING[0], m), min(_GMM_TILING[1], kdim),
              min(_GMM_TILING[2], n))
    return gmm(lhs, rhs, sizes, lhs.dtype, tiling,
               jnp.asarray(offset, jnp.int32), None, False, _interpret())


def _held_chunk(x, picked, weights, w_gate_up, w_down, n_experts: int,
                offset: int):
    """One chunk of tokens through the experts held: x (c, d), picked and
    weights (c, k). Returns (c, d) in x's dtype."""
    c, k = picked.shape
    flat = picked.reshape(-1)
    order = jnp.argsort(flat, stable=True).astype(jnp.int32)
    inv = jnp.zeros_like(order).at[order].set(
        jnp.arange(order.shape[0], dtype=jnp.int32))
    sizes = jnp.sum(jax.nn.one_hot(flat, n_experts, dtype=jnp.int32), axis=0)
    xs = _rows_to_sorted(x, order, inv, k)
    gate_up = _gmm(xs, w_gate_up, sizes, offset)
    gate, up = jnp.split(gate_up, 2, axis=-1)
    ys = _gmm(jax.nn.silu(gate) * up, w_down, sizes, offset)
    y = _rows_from_sorted(ys, order, inv).reshape(c, k, -1)
    return jnp.sum(y.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(x.dtype)


def held_experts_ffn(x, picked, weights, w_gate_up, w_down, n_experts: int,
                     offset: int = 0):
    """The held experts' part of a top-k SwiGLU expert layer, nothing
    dropped: ``sum over the picks of a token that fall on a held expert of
    weight * W_down_e(silu(W_gate_e x) * W_up_e x)``. ``x`` (T, d);
    ``picked`` / ``weights`` (T, k) from :func:`route_topk` (expert numbers
    over all ``n_experts``); ``w_gate_up`` (count, d, 2h) with gate and up
    side by side, ``w_down`` (count, h, d): the experts numbered ``offset``
    .. ``offset + count - 1``. Tokens go through in chunks of
    ``_CHUNK_TOKENS``, each rematerialised in the backward pass. On one chip
    no exchange is made: what the absent experts would add is left out."""
    t, d = x.shape
    chunk = min(_CHUNK_TOKENS, t)
    if t % chunk:
        raise ValueError(f"{t} tokens do not divide into chunks of {chunk}")
    body = jax.checkpoint(lambda xc, pc, wc, a, b: _held_chunk(
        xc, pc, wc, a, b, n_experts, offset))
    if t == chunk:
        return body(x, picked, weights, w_gate_up, w_down)
    n = t // chunk
    k = picked.shape[-1]

    def step(_, inp):
        xc, pc, wc = inp
        return None, body(xc, pc, wc, w_gate_up, w_down)

    _, y = jax.lax.scan(step, None, (x.reshape(n, chunk, d),
                                     picked.reshape(n, chunk, k),
                                     weights.reshape(n, chunk, k)))
    return y.reshape(t, d)
