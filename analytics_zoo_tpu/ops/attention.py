"""Attention op: single entry point the layer library calls.

Dispatches between the Pallas flash-attention kernel (ops/flash_attention.py)
and a fused-by-XLA jnp path. Both take (B, N, S, D) q, (B, N_kv, S, D) k and
(B, N_kv, S, Dv) v (N_kv = N, or fewer key-value heads that divide N:
grouped-query attention; Dv = D, or the values' own width, which is then the
output's: latent attention), an additive bias/mask, a causal flag and, with it, a sliding
``window`` (query i sees key j iff 0 <= i - j < window; the kernels skip
the blocks wholly outside it). The default routes by the size of the S^2
logits tensor (see _flash_bytes_threshold): XLA at product shapes, the
O(S)-memory Pallas kernel where the logits tensor would dominate HBM. A
kernel that fails to compile or run raises — only a shape outside the
kernel's envelope (``NotImplementedError``) takes the XLA path, with a
warning.
"""

from __future__ import annotations

from typing import Optional

import os

import jax
import jax.numpy as jnp


import logging

logger = logging.getLogger("analytics_zoo_tpu")
_warned_fallback = False

_DEFAULT_FLASH_BYTES_THRESHOLD = 256 << 20
# Shapes OUTSIDE the regime the 256 MiB crossover applies to (bf16, seq
# axes divisible by the 512x512 default tiles) keep a 1 GiB
# memory-pressure bound: there flash is the OOM-enabler, not a speedup.
_CONSERVATIVE_FLASH_BYTES_THRESHOLD = 1 << 30


def _flash_bytes_threshold() -> int:
    """Total bytes of the logits tensor (batch*heads*s_q*s_k*itemsize) above
    which the dispatcher prefers the O(S)-memory Pallas kernel over XLA's
    materialized-logits path. 256 MiB ~= seq 2048 at 8 heads batch 4 (bf16).
    ``_auto_use_flash`` applies it to bf16 inputs whose sequence axes take
    the 512x512 default tiles; other dtypes/tilings keep the conservative
    1 GiB bound (flash past 1 GiB is about not materializing S^2, not
    speed). Where the speed crossover sits on the current machine is not
    measured (S4 in ROADMAP.md). The estimate counts the logits tensor
    only — the XLA path's f32 softmax copy roughly triples the true bf16
    peak — so treat the threshold as "bytes the caller will spend on S^2
    tensors", not an exact OOM bound. Re-read at every dispatch (malformed
    values fall back to the default), but under ``jax.jit`` the decision
    is baked in at TRACE time: changing the env var after a shape has
    compiled does not re-route already-cached executables."""
    try:
        return int(os.environ.get("AZOO_FLASH_BYTES_THRESHOLD",
                                  _DEFAULT_FLASH_BYTES_THRESHOLD))
    except ValueError:
        return _DEFAULT_FLASH_BYTES_THRESHOLD


def _auto_use_flash(q, k) -> bool:
    """The dispatcher's default routing decision (no explicit
    ``use_flash``). An operator-pinned AZOO_FLASH_BYTES_THRESHOLD applies
    verbatim to every shape (whoever tunes it knows their workload); the
    built-in default applies the 256 MiB crossover only to bf16 inputs
    whose sequence axes take the 512x512 default tiles, and the
    conservative 1 GiB memory-pressure bound everywhere else."""
    if jax.devices()[0].platform != "tpu":
        return False
    logits_bytes = (jnp.dtype(q.dtype).itemsize
                    * q.shape[0] * q.shape[1] * q.shape[2] * k.shape[2])
    threshold = _flash_bytes_threshold()
    if "AZOO_FLASH_BYTES_THRESHOLD" not in os.environ:
        # The regime check asks what tiles this shape would ACTUALLY get
        # (per-call env pins included): an AZOO_FLASH_BLOCK_Q/K pin to 128
        # puts even 512-divisible shapes on the 128-tile kernels, so the
        # 256 MiB crossover must not apply there.
        from analytics_zoo_tpu.ops.flash_attention import _resolve_blocks

        big_tiles = (q.dtype == jnp.bfloat16
                     and _resolve_blocks(None, None, q.shape[2],
                                         k.shape[2]) == (512, 512))
        if not big_tiles:
            threshold = _CONSERVATIVE_FLASH_BYTES_THRESHOLD
    return logits_bytes >= threshold


def check_window_and_heads(q, k, causal: bool, window) -> None:
    """What both paths ask of a ``window`` and of grouped heads."""
    if window is not None and (not causal or int(window) < 1):
        raise ValueError("a window is the newest `window` >= 1 keys of a "
                         "causal mask: pass causal=True")
    if q.shape[1] % k.shape[1]:
        raise ValueError(f"{q.shape[1]} query heads do not share "
                         f"{k.shape[1]} key-value heads evenly")


def _reference_attention(q, k, v, bias: Optional[jax.Array], causal: bool,
                         scale: float, dropout_rate: float = 0.0,
                         dropout_rng: Optional[jax.Array] = None,
                         window: Optional[int] = None) -> jax.Array:
    group = q.shape[1] // k.shape[1]
    if group > 1:      # grouped key-value heads: head h reads h // group
        k = jnp.repeat(k, group, axis=1)
        v = jnp.repeat(v, group, axis=1)
    logits = jnp.einsum("bnqd,bnkd->bnqk", q, k) * scale
    if bias is not None:
        logits = logits + bias
    if causal:
        s_q, s_k = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((s_q, s_k), jnp.bool_), k=s_k - s_q)
        if window is not None:   # the `window` newest causal keys only
            mask = mask & ~jnp.tril(jnp.ones((s_q, s_k), jnp.bool_),
                                    k=s_k - s_q - int(window))
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    # softmax in f32 for bf16 streams
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1).astype(q.dtype)
    if dropout_rate > 0.0 and dropout_rng is not None:
        keep = 1.0 - dropout_rate
        probs = jnp.where(jax.random.bernoulli(dropout_rng, keep, probs.shape),
                          probs / keep, 0.0)
    return jnp.einsum("bnqk,bnkd->bnqd", probs, v)


def scaled_dot_product_attention(q, k, v, bias: Optional[jax.Array] = None,
                                 causal: bool = False,
                                 scale: Optional[float] = None,
                                 dropout_rate: float = 0.0,
                                 dropout_rng: Optional[jax.Array] = None,
                                 use_flash: Optional[bool] = None,
                                 window: Optional[int] = None) -> jax.Array:
    """q: (batch, heads, seq, head_dim); k the same, or with fewer
    key-value heads that divide the query heads (grouped-query attention:
    query head h reads key-value head h // (heads / key-value heads)); v as
    k but for its last dim, the values' own width: the output is (batch,
    heads, seq, v's width). bias: additive, broadcastable to (batch, heads, q_len, k_len) — use
    large negatives for padding masks. ``window`` (needs ``causal``): query
    i sees key j iff 0 <= i - j < window — the sliding-window mask; on the
    kernel path blocks wholly outside the window are skipped, not masked.
    ``dropout_rate`` is attention-probability dropout (reference semantics);
    it forces the XLA path (the flash kernel has no prob-dropout)."""
    global _warned_fallback
    if scale is None:
        scale = q.shape[-1] ** -0.5
    check_window_and_heads(q, k, causal, window)
    explicit = use_flash is True
    if use_flash is None:
        # At product shapes (BERT seq 128/512) the logits tensor is small
        # and XLA's fused attention serves; past a few thousand tokens the
        # XLA path's materialized O(S^2) logits dominate HBM or OOM
        # outright, and _auto_use_flash routes to the Pallas kernel. The
        # kernel also remains the per-shard engine of ring attention, and
        # is available via use_flash=True.
        use_flash = _auto_use_flash(q, k)
    if use_flash and not (dropout_rate > 0.0 and dropout_rng is not None):
        try:
            from analytics_zoo_tpu.ops.flash_attention import flash_attention

            return flash_attention(q, k, v, bias=bias, causal=causal,
                                   scale=scale, window=window)
        except NotImplementedError as e:
            # Shape/bias outside kernel support. Warn when the caller
            # explicitly demanded the kernel — and also when the dispatcher
            # auto-selected it past the memory threshold: in that regime the
            # XLA fallback materializes the very S^2 tensors the threshold
            # exists to avoid, so a silent fallback would turn a shape-tiling
            # nit (seq % 128) into an undiagnosed OOM/HBM-thrash.
            if not _warned_fallback:
                _warned_fallback = True
                logger.warning(
                    "flash attention %s but unsupported (%s); falling back to "
                    "the XLA path, which will materialize the O(S^2) logits "
                    "this shape was routed to the kernel to avoid",
                    "requested" if explicit else "auto-selected", e)
    return _reference_attention(q, k, v, bias, causal, scale,
                                dropout_rate, dropout_rng, window)
