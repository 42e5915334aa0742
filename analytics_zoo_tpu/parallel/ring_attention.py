"""Sequence/context parallelism: ring attention + Ulysses all-to-all.

The reference has NO long-context story (SURVEY.md §5: sequence length is a
static hyperparameter, no ring/blockwise attention) — this module is where
the TPU rebuild goes beyond parity, making long-context first-class:

- :func:`ring_attention` — K/V shards rotate around the ``seq`` mesh axis via
  ``lax.ppermute`` (ICI neighbor links) while each device holds its Q shard,
  accumulating online-softmax partials: memory O(S/n), comm overlapped with
  compute by XLA. The blockwise formulation follows the public ring-attention
  recipe (blockwise accumulation of (acc, max, denom)).
- :func:`ulysses_attention` — all-to-all reshards sequence↔heads so each
  device computes full-sequence attention for a head subset; cheaper at
  moderate S when heads % n == 0.

Both are written against ``shard_map`` with a named axis, so they compose
with dp/tp axes of the same mesh; wrappers accept global arrays and handle
the shard_map plumbing.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax, shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def _ring_attention_local(q, k, v, axis_name: str, causal: bool, scale: float,
                          km=None):
    """Per-shard body (inside shard_map). q/k/v: (B, H, S_local, D).
    ``km``: optional (B, S_local) key-validity shard (1 = attend) that
    rotates around the ring with its K/V shard — the padding-mask form of
    long-context attention."""
    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[2]
    q32 = q.astype(jnp.float32) * scale

    q_pos = my_idx * s_local + jnp.arange(s_local)  # global query positions

    def accumulate(i, acc, m_prev, l_prev, k_cur, v_cur, km_cur=None):
        """Online-softmax update against the K/V shard currently held."""
        # the shard we currently hold originated at (my_idx - i) mod n
        src = jax.lax.rem(my_idx - i + n, n)
        s = jnp.einsum("bhqd,bhkd->bhqk", q32, k_cur.astype(jnp.float32))
        valid = None
        if causal:
            k_pos = src * s_local + jnp.arange(s_local)
            valid = (q_pos[:, None] >= k_pos[None, :])[None, None]
        if km_cur is not None:
            kv = (km_cur > 0)[:, None, None, :]  # (B,1,1,S_local)
            valid = kv if valid is None else jnp.logical_and(valid, kv)
        if valid is not None:
            s = jnp.where(valid, s, _NEG_INF)
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # guard fully-masked rows (exp(-inf - -inf))
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        p = jnp.exp(s - m_safe)
        if valid is not None:
            p = jnp.where(valid, p, 0.0)
        alpha = jnp.exp(jnp.where(m_prev <= _NEG_INF, _NEG_INF, m_prev) - m_safe)
        alpha = jnp.where(m_prev <= _NEG_INF, 0.0, alpha)
        l_new = alpha * l_prev + jnp.sum(p, axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum(
            "bhqk,bhkd->bhqd", p, v_cur.astype(jnp.float32))
        return acc, m_new, l_new

    perm = None  # bound below once n is known statically

    def step(i, carry):
        acc, m_prev, l_prev, k_cur, v_cur = carry
        acc, m_new, l_new = accumulate(i, acc, m_prev, l_prev, k_cur, v_cur)
        # rotate K/V to the next neighbor over ICI
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        return acc, m_new, l_new, k_nxt, v_nxt

    def step_masked(i, carry):
        acc, m_prev, l_prev, k_cur, v_cur, km_cur = carry
        acc, m_new, l_new = accumulate(i, acc, m_prev, l_prev, k_cur, v_cur,
                                       km_cur)
        k_nxt = lax.ppermute(k_cur, axis_name, perm)
        v_nxt = lax.ppermute(v_cur, axis_name, perm)
        km_nxt = lax.ppermute(km_cur, axis_name, perm)
        return acc, m_new, l_new, k_nxt, v_nxt, km_nxt

    b, h, _, d = q.shape
    dv = v.shape[-1]
    n_static = lax.psum(1, axis_name)
    # mark the zero-init accumulators as device-varying over the seq axis,
    # matching the varying type the loop body produces.
    acc0, m0, l0 = (
        lax.pcast(t, axis_name, to="varying") for t in (
            jnp.zeros((b, h, s_local, dv), jnp.float32),
            jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32),
            jnp.zeros((b, h, s_local, 1), jnp.float32)))
    perm = [(j, (j + 1) % n_static) for j in range(n_static)]
    # n-1 rotating steps, then the last shard is consumed WITHOUT the final
    # ppermute pair (its result would be discarded — wasted ICI traffic).
    if km is None:
        acc, m, l, k_last, v_last = lax.fori_loop(
            0, n - 1, step, (acc0, m0, l0, k, v))
        acc, m, l = accumulate(n - 1, acc, m, l, k_last, v_last)
    else:
        acc, m, l, k_last, v_last, km_last = lax.fori_loop(
            0, n - 1, step_masked, (acc0, m0, l0, k, v, km))
        acc, m, l = accumulate(n - 1, acc, m, l, k_last, v_last, km_last)
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _ring_flash_local(q, k, v, axis_name: str, causal: bool, scale: float):
    """Ring attention with the Pallas flash kernel as the per-shard block
    engine: each rotation computes flash(q_shard, kv_shard) -> (out, lse)
    partials — O(S_local) memory on BOTH block dims instead of the
    O(S_local²) logits of the einsum body — merged by online logsumexp.

    The diagonal (i=0, src == my_idx) is the only causally-masked block and
    is static, so the kernel's static ``causal`` flag suffices; later
    rotations are all-or-nothing per device and are gated by sending the
    fully-masked shards' lse to -inf before the merge. Gradients flow
    through both partials (the kernel's lse output is differentiable)."""
    from analytics_zoo_tpu.ops.flash_attention import flash_attention_with_lse

    n = lax.psum(1, axis_name)
    my_idx = lax.axis_index(axis_name)
    s_local = q.shape[2]

    def merge(acc, m_prev, l_prev, o_i, lse_i):
        lse_i = lse_i[..., None]                       # (B,H,S,1)
        m_new = jnp.maximum(m_prev, lse_i)
        m_safe = jnp.where(m_new <= _NEG_INF, 0.0, m_new)
        alpha = jnp.where(m_prev <= _NEG_INF, 0.0, jnp.exp(m_prev - m_safe))
        beta = jnp.where(lse_i <= _NEG_INF, 0.0, jnp.exp(lse_i - m_safe))
        acc = acc * alpha + o_i.astype(jnp.float32) * beta
        l_new = l_prev * alpha + beta
        return acc, m_new, l_new

    b, h, _, dv = *q.shape[:3], v.shape[-1]
    # plain zeros (no pvary): this body runs under check_vma=False, where
    # varying-axis annotations are unused and warn
    acc0 = jnp.zeros((b, h, s_local, dv), jnp.float32)
    m0 = jnp.full((b, h, s_local, 1), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, s_local, 1), jnp.float32)

    # i = 0: the diagonal block (statically causal when requested)
    o0, lse0 = flash_attention_with_lse(q, k, v, causal=causal, scale=scale)
    acc, m, l = merge(acc0, m0, l0, o0, lse0)

    def step(i, carry):
        acc, m_prev, l_prev, k_cur, v_cur = carry
        perm = [(j, (j + 1) % n) for j in range(n)]
        k_cur = lax.ppermute(k_cur, axis_name, perm)
        v_cur = lax.ppermute(v_cur, axis_name, perm)
        o_i, lse_i = flash_attention_with_lse(q, k_cur, v_cur, causal=False,
                                              scale=scale)
        if causal:
            # after i rotations we hold the shard from (my_idx - i) mod n;
            # under causal masking only strictly-earlier shards contribute
            src = jax.lax.rem(my_idx - i + n, n)
            lse_i = jnp.where(src < my_idx, lse_i, _NEG_INF)
        acc, m_new, l_new = merge(acc, m_prev, l_prev, o_i, lse_i)
        return acc, m_new, l_new, k_cur, v_cur

    acc, m, l, _, _ = lax.fori_loop(1, n, step, (acc, m, l, k, v))
    out = acc / jnp.maximum(l, 1e-30)
    return out.astype(q.dtype)


def _flash_ring_shapes_ok(q, k, v, mesh, seq_axis) -> bool:
    n = mesh.shape[seq_axis]
    s_local = q.shape[2] // n
    # gate on the tiles the per-shard kernel would ACTUALLY resolve
    # (seq-aware default / AZOO_FLASH_BLOCK_Q/K pins, read per call) —
    # a pinned 512 tile must decline shards only divisible by 128
    from analytics_zoo_tpu.ops.flash_attention import _resolve_blocks

    bq, bk = _resolve_blocks(None, None, s_local, s_local)
    return (q.shape[2] % n == 0 and s_local % bq == 0
            and s_local % bk == 0 and q.shape[-1] <= 256
            and v.shape[-1] <= 256)


def _flash_ring_supported(q, k, v, mesh, seq_axis) -> bool:
    """Auto-select gate: shapes must tile the kernel AND the backend must be
    a real TPU — off-TPU the kernel would run in interpret mode (orders of
    magnitude slower than the einsum body). Tests force use_flash=True."""
    return (jax.default_backend() == "tpu"
            and _flash_ring_shapes_ok(q, k, v, mesh, seq_axis))


def ring_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                   causal: bool = False, scale: Optional[float] = None,
                   use_flash: Optional[bool] = None, key_mask=None):
    """Global entry: q/k/v (B, H, S, D) sharded (or shardable) on S over
    ``seq_axis``. Returns attention output with the same layout.

    ``use_flash=None`` auto-selects the Pallas per-shard block engine when
    the shard shapes tile the kernel (S/n multiple of 128, head_dim ≤ 256);
    the einsum body remains for odd shapes. ``key_mask``: optional (B, S)
    key-validity mask (1 = attend) — padded long sequences; its shards
    rotate with their K/V shards (einsum body; flash is bypassed)."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    if use_flash is None:
        use_flash = (key_mask is None
                     and _flash_ring_supported(q, k, v, mesh, seq_axis))
    if use_flash and key_mask is not None:
        raise NotImplementedError(
            "ring_attention: the flash block engine has no key_mask path — "
            "leave use_flash unset to use the einsum body")
    spec = P(None, None, seq_axis, None)
    # pallas_call's out avals carry no varying-mesh-axes annotation, so the
    # vma checker can't see through the flash body — disable it there
    kw = {"check_vma": False} if use_flash else {}
    if key_mask is not None:
        def masked_body(q_, k_, v_, m_):
            return _ring_attention_local(q_, k_, v_, axis_name=seq_axis,
                                         causal=causal, scale=scale, km=m_)

        fn = shard_map(
            masked_body, mesh=mesh,
            in_specs=(spec, spec, spec, P(None, seq_axis)),
            out_specs=spec, **kw)
        return fn(q, k, v, key_mask)
    body = _ring_flash_local if use_flash else _ring_attention_local
    fn = shard_map(
        functools.partial(body, axis_name=seq_axis,
                          causal=causal, scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **kw)
    return fn(q, k, v)


def _ulysses_local(q, k, v, axis_name: str, causal: bool, scale: float,
                   km=None):
    """Inside shard_map: (B, H, S_local, D) -> all-to-all to (B, H_local, S, D),
    full-sequence attention on the head subset, all-to-all back. The inner
    attention goes through the standard dispatcher — XLA's fused path at
    product shapes, the Pallas flash kernel once the full-sequence logits
    tensor crosses the memory threshold (the long-context case Ulysses
    exists for)."""
    from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention

    n = lax.psum(1, axis_name)

    # (B, H, S/n, D) -> (B, H/n, S, D): scatter heads, gather sequence
    def a2a_fwd(t):
        return lax.all_to_all(t, axis_name, split_axis=1, concat_axis=2,
                              tiled=True)

    def a2a_bwd(t):
        return lax.all_to_all(t, axis_name, split_axis=2, concat_axis=1,
                              tiled=True)

    qh, kh, vh = a2a_fwd(q), a2a_fwd(k), a2a_fwd(v)
    bias = None
    if km is not None:
        # the key mask is per-sequence-position: gather the shards into the
        # full (B, S) mask each head-subset needs
        km_full = lax.all_gather(km, axis_name, axis=1, tiled=True)
        bias = ((1.0 - (km_full > 0).astype(jnp.float32))
                * _NEG_INF)[:, None, None, :].astype(qh.dtype)
    out = scaled_dot_product_attention(qh, kh, vh, bias=bias, causal=causal,
                                       scale=scale)
    return a2a_bwd(out)


def ulysses_attention(q, k, v, mesh: Mesh, seq_axis: str = "seq",
                      causal: bool = False, scale: Optional[float] = None,
                      key_mask=None):
    """All-to-all sequence parallelism (DeepSpeed-Ulysses style). Requires
    n_heads % mesh[seq_axis] == 0. ``key_mask``: optional (B, S)
    key-validity mask (1 = attend) for padded long sequences."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    n = mesh.shape[seq_axis]
    if q.shape[1] % n != 0:
        raise ValueError(f"n_heads ({q.shape[1]}) must divide by "
                         f"mesh axis '{seq_axis}' size ({n})")
    spec = P(None, None, seq_axis, None)
    kw = {"check_vma": False}   # flash may engage inside on TPU
    if key_mask is not None:
        def masked_body(q_, k_, v_, m_):
            return _ulysses_local(q_, k_, v_, axis_name=seq_axis,
                                  causal=causal, scale=scale, km=m_)

        fn = shard_map(masked_body, mesh=mesh,
                       in_specs=(spec, spec, spec, P(None, seq_axis)),
                       out_specs=spec, **kw)
        return fn(q, k, v, key_mask)
    fn = shard_map(
        functools.partial(_ulysses_local, axis_name=seq_axis, causal=causal,
                          scale=scale),
        mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec, **kw)
    return fn(q, k, v)
