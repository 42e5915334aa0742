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
``held_experts_ffn``): sigmoid or softmax top-k routing that drops nothing,
computed over the experts one chip of an expert-parallel group holds.
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
# the largest source of a row gather that the chip serves at its fast rate:
# 32 768 rows out of 64 MiB take 0.24 ms on a v5e, out of 128 MiB 1.16 ms,
# sorted or not (the compiler keeps the smaller one in the chip's near memory)
_GATHER_SOURCE_BYTES = 64 << 20
# tokens of one chunk through the experts held: a sorted chunk holds chunk*k
# rows whatever the routing, so the chunk bounds the layer's buffers
_CHUNK_TOKENS = 4096


def route_topk(x, router, select_bias, k: int, normalize: bool = True,
               scale: float = 1.0, eps: float = 1e-20,
               scores: str = "sigmoid"):
    """Top-k routing in float32 over all E experts. ``x`` (T, d), ``router``
    (d, E), the scores ``x router`` at ``Precision.HIGHEST``.

    ``scores="sigmoid"``: a score's sigmoid a weight; ``select_bias`` (E,) is
    added for the pick only, outside the gradient. ``"softmax"``: the softmax
    over all E is the weights, the k most probable are picked and
    ``select_bias`` is left out. ``normalize``: a token's k weights over
    their sum plus ``eps`` (the published model files differ in it: 1e-20,
    1e-6, none); then times ``scale``.

    Returns (picked (T, k) int32, weights (T, k) float32, counts (E,)
    float32: the tokens routed to each expert) and, for softmax scores, the
    mean probability of each expert over the tokens (E,), which a balance
    term of the loss takes with the counts."""
    logits = jnp.dot(x.astype(jnp.float32), router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    if scores == "softmax":
        probs = jax.nn.softmax(logits, axis=-1)
        w, picked = jax.lax.top_k(probs, k)
    elif scores == "sigmoid":
        probs = jax.nn.sigmoid(logits)
        _, picked = jax.lax.top_k(probs + jax.lax.stop_gradient(select_bias),
                                  k)
        w = jnp.take_along_axis(probs, picked, axis=-1)
    else:
        raise ValueError(f"unknown router scores {scores!r}; known: "
                         f"['sigmoid', 'softmax']")
    if normalize:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + eps)
    counts = jnp.sum(jax.nn.one_hot(picked, probs.shape[-1],
                                    dtype=jnp.float32), axis=(0, 1))
    routed = (picked.astype(jnp.int32), w * scale, counts)
    return routed + (jnp.mean(probs, axis=0),) if scores == "softmax" else routed


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


def _tiling(m: int, kdim: int, n: int):
    """``_GMM_TILING``, no tile larger than the product it cuts."""
    return (min(_GMM_TILING[0], m), min(_GMM_TILING[1], kdim),
            min(_GMM_TILING[2], n))


def _gmm(lhs, rhs, sizes, offset):
    """Rows of ``lhs`` times their expert's matrix: jax's bundled grouped
    matrix product (megablox), told which experts ``rhs`` holds."""
    from jax.experimental.pallas.ops.tpu.megablox import gmm

    from analytics_zoo_tpu.ops.flash_attention import _interpret

    return gmm(lhs, rhs, sizes, lhs.dtype, _tiling(*lhs.shape, rhs.shape[-1]),
               jnp.asarray(offset, jnp.int32), None, False, _interpret())


def _held_chunk(x, picked, weights, w_gate_up, w_down, n_experts: int,
                offset: int, activation: str = "swiglu"):
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
    ys = _gmm(ACTIVATIONS[activation](gate_up), w_down, sizes, offset)
    y = _rows_from_sorted(ys, order, inv).reshape(c, k, -1)
    return jnp.sum(y.astype(jnp.float32) * weights[..., None],
                   axis=1).astype(x.dtype)


def _held_chunks(x, picked, weights, w_gate_up, w_down, n_experts: int,
                 offset: int, activation: str = "swiglu"):
    """All the tokens through :func:`_held_chunk`, ``_CHUNK_TOKENS`` at a
    time, each chunk rematerialised in the backward pass: buffers of
    chunk * k rows whatever the routing."""
    t, d = x.shape
    chunk = min(_CHUNK_TOKENS, t)
    if t % chunk:
        raise ValueError(f"{t} tokens do not divide into chunks of {chunk}")
    body = jax.checkpoint(lambda xc, pc, wc, a, b: _held_chunk(
        xc, pc, wc, a, b, n_experts, offset, activation))
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


# The held rows only. Of a call's t*k assignments the experts held get their
# share, count / n_experts of them from a uniform router; a buffer of twice
# that share holds them in expert order, and everything between the router and
# the sum back into tokens works on that buffer: one pass a call, no chunks,
# nothing rematerialised but an elementwise product. Rows past the held total
# belong to nobody. The grouped product never writes them (given the held
# groups' sizes alone it fills nothing in), so they are whatever the memory
# held, and nothing reads them: the way back is a gather by each held pick's
# own row.


def _held_capacity(t: int, k: int, count: int, n_experts: int) -> int:
    """Rows of the compacted buffer: twice the held experts' share of the
    t*k assignments, rounded up to the grouped product's row tile."""
    tile = _GMM_TILING[0]
    share = -(-2 * t * k * count // n_experts)
    return -(-share // tile) * tile


def _megablox():
    """The module of jax's grouped products themselves (the package's own
    ``gmm`` is their differentiable wrapper, which the chunks use)."""
    import importlib

    return importlib.import_module(
        "jax.experimental.pallas.ops.tpu.megablox.gmm")


def _held_gmm(lhs, rhs, sizes, transpose_rhs: bool = False):
    """Rows of ``lhs`` (r, .) times their expert's matrix (transposed on
    request), ``rhs`` and ``sizes`` over the held experts alone: the rows past
    ``sum(sizes)`` are not written."""
    from analytics_zoo_tpu.ops.flash_attention import _interpret

    m, kdim = lhs.shape
    n = rhs.shape[1 if transpose_rhs else 2]
    return _megablox().gmm(lhs, rhs, sizes, lhs.dtype, _tiling(m, kdim, n),
                           None, None, transpose_rhs, _interpret())


def _held_tgmm(lhs, rhs, sizes):
    """(count, columns of lhs, columns of rhs): an expert's sum over its rows
    of lhs_row^T rhs_row; rows past ``sum(sizes)`` are masked out."""
    from analytics_zoo_tpu.ops.flash_attention import _interpret

    m, kdim = lhs.shape
    return _megablox().tgmm(lhs.swapaxes(0, 1), rhs, sizes, lhs.dtype,
                            _tiling(m, kdim, rhs.shape[1]), None,
                            sizes.shape[0], None, _interpret())


def _swiglu(gate_up):
    """(r, 2h), gate and up side by side -> silu(gate) * up (r, h)."""
    gate, up = jnp.split(gate_up, 2, axis=-1)
    return jax.nn.silu(gate) * up


def _relu2(up):
    """(r, h) -> relu(up)^2 (r, h): the squared-ReLU expert, no gate."""
    return jnp.square(jax.nn.relu(up))


# an expert's activation by name, over what its first product gives: gate |
# up side by side (2h wide) for "swiglu", up alone (h wide) for "relu2"
ACTIVATIONS = {"swiglu": _swiglu, "relu2": _relu2}


def _sum_of_held(rows, slot, held, weights=None):
    """(t, d): a token's float32 sum, over those of its picks that are
    ``held`` (t, k), of ``rows[slot]``, each times its weight where given.
    All t*k rows are gathered (a pick that is not held reads row 0 and counts
    as nothing), a block of columns at a time, the block small enough for
    the fast gather."""
    r, d = rows.shape
    blocks = -(-r * d * rows.dtype.itemsize // _GATHER_SOURCE_BYTES)
    while d % blocks:
        blocks += 1
    out = []
    for cols in jnp.split(rows, blocks, axis=1):
        mine = jnp.where(held[..., None], cols[slot], 0).astype(jnp.float32)
        if weights is not None:
            mine = mine * weights[..., None]
        out.append(jnp.sum(mine, axis=1))
    return jnp.concatenate(out, axis=1).astype(rows.dtype)


def _held_compact(x, weights, w_gate_up, w_down, key, sizes, rows: int,
                  activation: str = "swiglu"):
    """Every token through the experts held in one pass over ``rows`` rows.
    ``key`` (t*k,): an assignment's expert among the ``count`` held, or
    ``count``; ``sizes`` (count,): the held experts' rows, ``rows`` or fewer
    in all. Returns y (t, d) and what the backward pass keeps."""
    k = weights.shape[-1]
    # held assignments first, by expert, then by token: the source of each row
    source = jnp.argsort(key, stable=True)[:rows].astype(jnp.int32)
    token = source // k
    # a held pick's row; the rows past the held total send theirs to picks
    # that are not held, where nobody looks
    slot = jnp.zeros_like(key).at[source].set(
        jnp.arange(rows, dtype=jnp.int32), unique_indices=True)
    held = (key < sizes.shape[0]).reshape(-1, k)
    slot = jnp.where(held, slot.reshape(-1, k), 0)
    xs = x[token]
    gate_up = _held_gmm(xs, w_gate_up, sizes)
    ys = _held_gmm(ACTIVATIONS[activation](gate_up), w_down, sizes)
    y = _sum_of_held(ys, slot, held, weights)
    return y, (token, source, slot, xs, gate_up, ys)


def _held_compact_bwd(kept, weights, w_gate_up, w_down, key, sizes, g,
                      activation: str = "swiglu"):
    """The gradients of :func:`_held_compact` to x, weights and both expert
    tensors, from ``g`` (t, d): gathers of r rows and the grouped products'
    own transposes; the sum of a token's rows is float32 as forward."""
    token, source, slot, xs, gate_up, ys = kept
    held = (key < sizes.shape[0]).reshape(slot.shape)
    g_rows = g[token].astype(jnp.float32)
    d_ys = (g_rows * weights.reshape(-1)[source][:, None]).astype(ys.dtype)
    d_row = jnp.sum(ys.astype(jnp.float32) * g_rows, axis=-1)
    # (a scatter of r numbers to their picks, not a gather of t*k; what the
    # rows past the held total send lands on picks that are not held)
    d_weights = jnp.zeros(key.shape, jnp.float32).at[source].set(
        d_row, unique_indices=True).reshape(slot.shape)
    d_weights = jnp.where(held, d_weights, 0).astype(weights.dtype)
    hidden, act_vjp = jax.vjp(ACTIVATIONS[activation], gate_up)
    d_down = _held_tgmm(hidden, d_ys, sizes)
    (d_gate_up,) = act_vjp(_held_gmm(d_ys, w_down, sizes, True))
    d_gate_up_w = _held_tgmm(xs, d_gate_up, sizes)
    d_xs = _held_gmm(d_gate_up, w_gate_up, sizes, True)
    return _sum_of_held(d_xs, slot, held), d_weights, d_gate_up_w, d_down


@functools.partial(jax.custom_vjp, nondiff_argnums=(8, 9, 10))
def _held_ffn(x, picked, weights, w_gate_up, w_down, key, sizes, compact,
              n_experts: int, offset: int, activation: str):
    """One pass over the compacted rows where ``compact`` says they fit, the
    chunks of all t*k rows where not; the backward pass takes the same
    side."""
    return _held_ffn_fwd(x, picked, weights, w_gate_up, w_down, key, sizes,
                         compact, n_experts, offset, activation)[0]


def _held_ffn_fwd(x, picked, weights, w_gate_up, w_down, key, sizes, compact,
                  n_experts, offset, activation):
    rows = _held_capacity(*picked.shape, sizes.shape[0], n_experts)

    def one_pass():
        return _held_compact(x, weights, w_gate_up, w_down, key, sizes, rows,
                             activation)

    def chunks():
        y = _held_chunks(x, picked, weights, w_gate_up, w_down, n_experts,
                         offset, activation)
        kept = jax.eval_shape(one_pass)[1]
        return y, jax.tree_util.tree_map(
            lambda s: jnp.zeros(s.shape, s.dtype), kept)

    y, kept = jax.lax.cond(compact, one_pass, chunks)
    return y, (kept, x, picked, weights, w_gate_up, w_down, key, sizes,
               compact)


def _held_ffn_bwd(n_experts, offset, activation, res, g):
    kept, x, picked, weights, w_gate_up, w_down, key, sizes, compact = res

    def chunks():
        _, vjp = jax.vjp(
            lambda x_, w_, a, b: _held_chunks(x_, picked, w_, a, b, n_experts,
                                              offset, activation),
            x, weights, w_gate_up, w_down)
        return vjp(g)

    d_x, d_weights, d_gate_up, d_down = jax.lax.cond(
        compact,
        lambda: _held_compact_bwd(kept, weights, w_gate_up, w_down, key,
                                  sizes, g, activation),
        chunks)
    return d_x, None, d_weights, d_gate_up, d_down, None, None, None


_held_ffn.defvjp(_held_ffn_fwd, _held_ffn_bwd)


def held_experts_ffn(x, picked, weights, w_gate_up, w_down, n_experts: int,
                     offset: int = 0, activation: str = "swiglu",
                     compact: bool = True):
    """The held experts' part of a top-k expert layer, nothing dropped:
    ``sum over the picks of a token that fall on a held expert of weight *
    W_down_e act(W_in_e x)``. ``x`` (T, d); ``picked`` / ``weights`` (T, k)
    from :func:`route_topk` (expert numbers over all ``n_experts``);
    ``w_gate_up`` the experts' first kernel, ``w_down`` (count, h, d): the
    experts numbered ``offset`` .. ``offset + count - 1``. ``activation``
    (``ACTIVATIONS``): ``"swiglu"``, ``silu(W_gate x) * W_up x`` from a
    ``(count, d, 2h)`` kernel with gate and up side by side, or ``"relu2"``,
    ``relu(W_up x)^2`` from an up kernel ``(count, d, h)`` alone; one path
    for both, the activation its data. On one chip no exchange is made: what
    the absent experts would add is left out.

    Returns ``(y, compact)``. The held assignments go through in one pass
    over a buffer of :func:`_held_capacity` rows, twice the held experts'
    share; ``compact`` (a bool scalar on the device) says that they fitted. A
    call whose routing sends more than that to the experts held takes all
    T*k assignments through in chunks of ``_CHUNK_TOKENS`` tokens instead,
    each rematerialised in the backward pass; so does every call, with no
    choice compiled in, where the experts held are half of all or more and
    the buffer would hold every assignment anyway, or where ``compact`` is
    False: a layer whose held load crosses the buffer's edge back and forth
    pays the difference between the two paths at each crossing, and the
    chunks' cost follows the load with no edge."""
    t, k = picked.shape
    count = w_gate_up.shape[0]
    rows = _held_capacity(t, k, count, n_experts)
    if not compact or rows >= t * k:
        return (_held_chunks(x, picked, weights, w_gate_up, w_down, n_experts,
                             offset, activation), jnp.zeros((), jnp.bool_))
    local = picked.reshape(-1) - offset
    key = jnp.where((local >= 0) & (local < count), local, count)
    sizes = jnp.sum(jax.nn.one_hot(key, count, dtype=jnp.int32), axis=0)
    compact = jnp.sum(sizes) <= rows
    return _held_ffn(x, picked, weights, w_gate_up, w_down, key, sizes,
                     compact, n_experts, offset, activation), compact
