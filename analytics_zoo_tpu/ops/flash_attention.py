"""Flash attention (Pallas, TPU): tiled online-softmax attention, fwd + bwd.

The hot op of TransformerLayer/BERT (ref TransformerLayer.scala:50,
BERT.scala:60). The forward kernel streams K/V blocks through VMEM against a
resident Q block, maintaining running max/denominator — O(S) memory instead
of the O(S²) logits tensor (HBM-bandwidth-bound otherwise). The backward is
the standard tiled dq / dk-dv split (two kernels, each re-computing the
probability tile from the saved per-row logsumexp), so *training* gets the
memory and bandwidth win too — no O(S²) recompute fallback.

Additive bias is supported for the padding-mask layout (query dim == 1,
broadcastable to ``(batch, heads, 1, s_k)``) — exactly what BERT's attention
mask is — so masked BERT training stays on the fast path. d(bias) is
accumulated as a per-key row sum inside the dk/dv kernel (cheap: O(S) extra
output) and reduced back onto the bias's broadcast shape. Full-rank bias
(q dim > 1, e.g. relative-position matrices) falls back to the XLA path via
the dispatcher in ops.attention.

On non-TPU backends the kernels run in Pallas interpret mode so the CPU test
mesh exercises the real kernel code, not a shadow implementation.
"""

from __future__ import annotations

import functools
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Tunable without edits (on-chip sweeps): 128x128 tiles the MXU exactly;
# larger Q blocks amortize the per-block softmax bookkeeping.
def _check_block(name: str, raw) -> int:
    """ONE validator for every block-size source (env var, per-call arg):
    an integer, positive, multiple of 128 — non-conforming blocks fail
    deep inside the Mosaic lowering with obscure errors otherwise."""
    try:
        val = int(raw)
        if val != float(raw):  # reject silently-truncating floats
            raise ValueError
    except (TypeError, ValueError):
        raise ValueError(
            f"{name}={raw!r} is not an integer; expected a positive "
            f"multiple of 128 (the MXU tile width)") from None
    if val <= 0 or val % 128:
        raise ValueError(
            f"{name}={val} must be a positive multiple of 128 (the MXU "
            f"tile width)")
    return val


def _block_env(var: str, default: int) -> int:
    return _check_block(var, os.environ.get(var, str(default)))


# The conservative MXU-tile floor the seq-aware default falls back to on
# axes that don't divide by 512. (Not an env snapshot: AZOO_FLASH_BLOCK_Q/K
# are read inside _resolve_blocks on every call, so setting or unsetting
# them after import takes effect. Under jax.jit the block choice is still
# baked in at TRACE time, like every other env knob here.)
BLOCK_Q = 128
BLOCK_K = 128


def _resolve_blocks(block_q, block_k, s_q: int, s_k: int):
    """Per-call block sizes (autotune/sweep path), then explicit env pins
    (``AZOO_FLASH_BLOCK_Q/K``, read per call), then a seq-aware default —
    same validator, same clear error.

    The default tiles 512x512 whenever the sequence axes divide by 512:
    larger tiles amortize the per-block softmax bookkeeping and the grid
    step overhead over 16x the MXU work of a 128x128 tile. Whether that
    is the fastest tiling on the current machine is not measured (S4 in
    ROADMAP.md owns the sweep). Axes that don't divide by 512 keep the
    128 MXU floor.
    """
    env_q = os.environ.get("AZOO_FLASH_BLOCK_Q")
    env_k = os.environ.get("AZOO_FLASH_BLOCK_K")
    if block_q is not None:
        bq = _check_block("block_q", block_q)
    elif env_q is not None:
        bq = _check_block("AZOO_FLASH_BLOCK_Q", env_q)
    else:
        bq = 512 if s_q % 512 == 0 else BLOCK_Q
    if block_k is not None:
        bk = _check_block("block_k", block_k)
    elif env_k is not None:
        bk = _check_block("AZOO_FLASH_BLOCK_K", env_k)
    else:
        bk = 512 if s_k % 512 == 0 else BLOCK_K
    return bq, bk
_NEG_INF = -1e30


def _compute_dtype(ref) -> jnp.dtype:
    """MXU strategy: matmul operands stay in the INPUT dtype (bf16 inputs →
    bf16 MXU passes at full throughput, like XLA's own attention), with f32
    accumulation via preferred_element_type; softmax/statistics stay f32.
    f32 inputs keep exact f32 matmuls (the golden tests' path)."""
    return jnp.bfloat16 if ref.dtype == jnp.bfloat16 else jnp.float32


def _precision(cdt):
    """Mosaic's default contract precision rounds f32 operands to bf16 (on
    a v5e the f32 kernel then misses the f32 reference by 3.5e-3 at seq
    384); HIGHEST is its ``contract_precision<fp32>``, which is what "exact
    f32 matmuls" means on the chip. bf16 operands need no pin."""
    return jax.lax.Precision.HIGHEST if cdt == jnp.float32 else None


def _mm(a, b, cdt):  # a(m,k) @ b(k,n), f32 accumulate
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt),
                               (((1,), (0,)), ((), ())),
                               precision=_precision(cdt),
                               preferred_element_type=jnp.float32)


def _mm_nt(a, b, cdt):  # a(m,k) @ b(n,k)^T
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt),
                               (((1,), (1,)), ((), ())),
                               precision=_precision(cdt),
                               preferred_element_type=jnp.float32)


def _mm_tn(a, b, cdt):  # a(k,m)^T @ b(k,n)
    return jax.lax.dot_general(a.astype(cdt), b.astype(cdt),
                               (((0,), (0,)), ((), ())),
                               precision=_precision(cdt),
                               preferred_element_type=jnp.float32)


def _interpret() -> bool:
    """Pallas interpret mode off TPU (the CPU test mesh); compiled Mosaic
    on TPU. Asks the backend at call time and lets it raise: a backend
    that cannot come up is an error, never a reason to interpret."""
    return jax.default_backend() != "tpu"


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _maybe_bias(kernel, has_bias: bool, n_in: int):
    """Adapt a kernel written with a ``bias_ref`` slot to pallas' positional
    calling convention when no bias operand is passed. ``n_in`` counts the
    input refs *before* the bias slot."""
    if has_bias:
        return kernel

    def adapted(*refs):
        return kernel(*refs[:n_in], None, *refs[n_in:])

    return adapted


def _masked(s, qi, ki, block_q: int, block_k: int, causal_offset: int,
            window):
    """Scores of block (qi, ki) with the keys a query may not see at
    _NEG_INF: bottom-right aligned causal (matches the XLA reference's
    tril(k=s_k-s_q): query i attends keys <= i + (s_k - s_q)) and, with a
    ``window``, only the ``window`` newest of those."""
    q_pos = qi * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0) + causal_offset
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    seen = q_pos >= k_pos
    if window is not None:
        seen = jnp.logical_and(seen, q_pos - k_pos < window)
    return jnp.where(seen, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, o_ref, lse_ref,
                acc_ref, m_ref, l_ref, *, scale: float, causal: bool,
                blocks_k: int, block_q: int, block_k: int,
                causal_offset: int, has_bias: bool, window, steps_k: int):
    qi = pl.program_id(1)
    t = pl.program_id(2)
    # with a window the innermost axis walks only the K blocks a q block's
    # window can touch, from the first of them
    ki = t + _k_base(qi, block_q, block_k, causal_offset, window)
    cdt = _compute_dtype(q_ref)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def compute():
        q = q_ref[0]  # (block_q, d) input dtype — scale applied to s, not q
        k = k_ref[0]  # (block_k, d)
        v = v_ref[0]  # (block_k, dv)
        s = _mm_nt(q, k, cdt) * scale  # (block_q, block_k) f32
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _masked(s, qi, ki, block_q, block_k, causal_offset, window)
        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _mm(p, v, cdt)
        m_ref[...] = m_new

    if causal:
        # fully-masked K blocks (above the diagonal, or wholly older than
        # the window) contribute nothing: skip their compute, keep the
        # running statistics. A row with no key in a live block adds
        # exp(0) terms at the _NEG_INF maximum; the first real key's
        # alpha = exp(_NEG_INF - m) = 0 wipes them.
        pl.when(_causal_block_live(qi, ki, block_q, block_k, causal_offset,
                                   window, blocks_k))(compute)
    else:
        compute()

    @pl.when(t == steps_k - 1)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


def _pcall(kernel, interpret: bool, name: str, **kw):
    """Shared pallas_call plumbing for all three kernels: interpret flag
    plus the (TPU-only) grid dimension semantics — two parallel outer axes,
    sequential innermost axis carrying the accumulator scratch. ``name`` is
    the kernel's stable name in a device trace (``zoo_flash_fwd`` /
    ``zoo_flash_dq`` / ``zoo_flash_dkv``): the benchmark's readers find the
    kernels by it."""
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"))
    return pl.pallas_call(kernel, interpret=interpret, name=name, **kw)


def _k_base(qi, block_q: int, block_k: int, causal_offset: int, window):
    """The first K block a q block's window can touch: where the innermost
    axis starts when a window bounds it (0 without one)."""
    if window is None:
        return 0
    return jnp.maximum(qi * block_q + causal_offset - (window - 1), 0) // block_k


def _q_base(ki, block_q: int, block_k: int, causal_offset: int, window):
    """The first q block that can see K block ``ki`` (0 without a window:
    the axis is walked whole)."""
    if window is None:
        return 0
    return jnp.maximum(ki * block_k - causal_offset, 0) // block_q


def _inner_steps(blocks: int, block_outer: int, block_inner: int, window):
    """Steps of the innermost axis: all ``blocks`` without a window; with
    one, the most inner blocks that ``block_outer + window - 1`` positions
    can touch."""
    if window is None:
        return blocks
    return min(blocks, (block_outer + window - 2) // block_inner + 2)


def _stream_clamps(causal: bool, block_q: int, block_k: int,
                   causal_offset: int, blocks_q: int, blocks_k: int,
                   window=None):
    """Index-map clamps that stop the pipeline DMA-ing dead causal blocks.

    ``pl.when`` only skips the *compute* of a fully-masked block — the
    BlockSpec index maps advance regardless, so without clamping every
    dead block still crosses HBM→VMEM (~2x the minimal K/V traffic for
    causal). Clamping the streamed index to the live range makes every
    dead step revisit an already-fetched block, which the pallas pipeline
    elides. Returns (k_stream_idx, q_stream_idx): the K-block index for a
    given (q-row j, step t) and the q-block index for a given
    (k-block j, step t). With a ``window`` step t counts from the first
    block the window can touch (``_k_base`` / ``_q_base``)."""
    if not causal:
        return (lambda j, t: t), (lambda j, t: t)

    def k_stream(j, t):
        # last live K block for q row j: max q_pos = (j+1)*bq - 1 + off
        last = ((j + 1) * block_q - 1 + causal_offset) // block_k
        first = _k_base(j, block_q, block_k, causal_offset, window)
        return jnp.clip(first + t, first, jnp.clip(last, 0, blocks_k - 1))

    def q_stream(j, t):
        # first live q block for K block j: q_pos >= j*bk - off
        first = (j * block_k - causal_offset) // block_q
        first = jnp.clip(first, 0, blocks_q - 1)
        if window is None:
            return jnp.maximum(t, first)
        # last: q_pos - k_pos < window for the block's newest key
        last = ((j + 1) * block_k - 1 + window - 1 - causal_offset) // block_q
        return jnp.clip(first + t, first, jnp.clip(last, 0, blocks_q - 1))

    return k_stream, q_stream


def _flash_forward(q, k, v, bias_flat, scale: float, causal: bool,
                   block_q: int, block_k: int, window=None):
    """q/k/v flattened to (bn, s, d); bias_flat (bn, 1, s_k) or None. With
    grouped key-value heads k/v are (bn // group, s, d): q row i reads
    key-value row i // group (rows are batch-major, so the heads of one
    group are neighbours).
    Returns (out, lse) with lse (bn, 1, s_q) f32. The aux arrays ride as
    rank-3 so TPU block shapes are (1, 1, s) — the mosaic lowering requires
    the trailing two block dims to be (8k, 128k) or full. Grid layout and
    the long-sequence rationale: see the backward-section comment below."""
    bn, s_q, d = q.shape
    s_k = k.shape[1]
    dv = v.shape[-1]
    blocks_k = s_k // block_k
    group = bn // k.shape[0]
    interpret = _interpret()
    has_bias = bias_flat is not None
    ks, _ = _stream_clamps(causal, block_q, block_k, s_k - s_q,
                           s_q // block_q, blocks_k, window)
    steps_k = _inner_steps(blocks_k, block_q, block_k, window)

    kernel = _maybe_bias(functools.partial(
        _fwd_kernel, scale=scale, causal=causal, blocks_k=blocks_k,
        block_q=block_q, block_k=block_k, causal_offset=s_k - s_q,
        has_bias=has_bias, window=window, steps_k=steps_k), has_bias, n_in=3)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda i, j, t: (i // group, ks(j, t), 0)),
        pl.BlockSpec((1, block_k, dv),
                     lambda i, j, t: (i // group, ks(j, t), 0)),
    ]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda i, j, t: (i, 0, ks(j, t))))
        operands.append(bias_flat)

    out, lse = _pcall(
        kernel, interpret, "zoo_flash_fwd",
        grid=(bn, s_q // block_q, steps_k),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, 1, block_q), lambda i, j, t: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn, s_q, dv), q.dtype),
            jax.ShapeDtypeStruct((bn, 1, s_q), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, dv), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
    )(*operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward: dq kernel (3-D grid over (bn, q-block, k-block)), dk/dv/dbias
# kernel (3-D grid over (bn, k-block, q-block)). Both re-materialize the
# probability tile from the saved logsumexp, accumulating in an f32 VMEM
# scratch across the sequential innermost grid axis and flushing on its
# last step. A whole-row design (K/V as full (1, s, d) blocks with an
# in-kernel fori over pl.ds slices) unrolls into a Mosaic module whose
# size grows with the sequence; this blocked-grid form, the same shape
# jax's bundled kernel uses, keeps the module size independent of the
# sequence length and lets the pallas pipeline stream K/V blocks instead
# of holding whole rows in VMEM.
# ---------------------------------------------------------------------------


def _causal_block_live(qi, ki, block_q: int, block_k: int,
                       causal_offset: int, window=None, blocks=None,
                       blocks_q=None):
    """True iff any (q, k) pair in block (qi, ki) satisfies
    q_pos >= k_pos: max q_pos = (qi+1)*block_q - 1 + causal_offset,
    min k_pos = ki*block_k; with a ``window`` also q_pos - k_pos < window
    for some pair: min q_pos - max k_pos < window. ``blocks`` /
    ``blocks_q``: the number of K / q blocks, for a window's shifted axis,
    whose last steps can run past the end."""
    live = (qi + 1) * block_q - 1 + causal_offset >= ki * block_k
    if window is not None:
        live = jnp.logical_and(
            live, qi * block_q + causal_offset
            - ((ki + 1) * block_k - 1) < window)
        if blocks is not None:
            live = jnp.logical_and(live, ki < blocks)
        if blocks_q is not None:
            live = jnp.logical_and(live, qi < blocks_q)
    return live


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
               dq_ref, acc_ref, *, scale: float, causal: bool, blocks_k: int,
               block_q: int, block_k: int, causal_offset: int,
               has_bias: bool, window, steps_k: int):
    qi = pl.program_id(1)
    t = pl.program_id(2)
    ki = t + _k_base(qi, block_q, block_k, causal_offset, window)
    cdt = _compute_dtype(q_ref)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def compute():
        q = q_ref[0]                                  # (bq, d) input dtype
        do = do_ref[0]                                # (bq, dv)
        lse = lse_ref[0, 0][:, None]                  # (bq, 1)
        delta = delta_ref[0, 0][:, None]              # (bq, 1)
        k = k_ref[0]                                  # (bk, d)
        v = v_ref[0]                                  # (bk, dv)
        s = _mm_nt(q, k, cdt) * scale
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _masked(s, qi, ki, block_q, block_k, causal_offset, window)
        p = jnp.exp(s - lse)                          # (bq, bk) f32
        dp = _mm_nt(do, v, cdt)                       # (bq, bk)
        ds = p * (dp - delta)
        acc_ref[...] += _mm(ds, k, cdt)

    if causal:
        # fully-masked blocks above the diagonal: skip the compute (their
        # contribution is exactly zero); the scratch keeps accumulating
        pl.when(_causal_block_live(qi, ki, block_q, block_k, causal_offset,
                                   window, blocks_k))(compute)
    else:
        compute()

    @pl.when(t == steps_k - 1)
    def _flush():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, bias_ref,
                dk_ref, dv_ref, db_ref, dk_acc, dv_acc, db_acc, *,
                scale: float, causal: bool, blocks_q: int, block_q: int,
                block_k: int, causal_offset: int, has_bias: bool,
                window, steps_q: int, group: int):
    ki = pl.program_id(1)
    t = pl.program_id(2)
    # the innermost axis walks the q blocks of each of the group's query
    # heads in turn: one K/V block gathers dk/dv from all of them
    qi = t % steps_q + _q_base(ki, block_q, block_k, causal_offset, window)
    cdt = _compute_dtype(q_ref)

    @pl.when(t == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)
        if has_bias:
            db_acc[...] = jnp.zeros_like(db_acc)

    def compute():
        k = k_ref[0]                                  # (bk, d) input dtype
        v = v_ref[0]                                  # (bk, dv)
        q = q_ref[0]                                  # (bq, d)
        do = do_ref[0]                                # (bq, dv)
        lse = lse_ref[0, 0][:, None]                  # (bq, 1)
        delta = delta_ref[0, 0][:, None]              # (bq, 1)
        s = _mm_nt(q, k, cdt) * scale                 # (bq, bk) f32
        if has_bias:
            s = s + bias_ref[0, 0].astype(jnp.float32)[None, :]
        if causal:
            s = _masked(s, qi, ki, block_q, block_k, causal_offset, window)
        p = jnp.exp(s - lse)                          # (bq, bk) f32
        dv_acc[...] += _mm_tn(p, do, cdt)
        dp = _mm_nt(do, v, cdt)                       # (bq, bk)
        ds = p * (dp - delta)
        dk_acc[...] += _mm_tn(ds, q, cdt)             # scale applied at flush
        if has_bias:
            db_acc[...] += jnp.sum(ds, axis=0)[None, :]

    if causal:
        # q blocks entirely above the diagonal contribute exactly zero to
        # this k block — skip their compute, keep the accumulators
        pl.when(_causal_block_live(qi, ki, block_q, block_k, causal_offset,
                                   window, blocks_q=blocks_q))(compute)
    else:
        compute()

    @pl.when(t == group * steps_q - 1)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        db_ref[0, 0] = db_acc[0] if has_bias else jnp.zeros(
            (block_k,), jnp.float32)


def _flash_backward(q, k, v, bias_flat, out, lse, g, scale: float,
                    causal: bool, block_q: int, block_k: int, g_lse=None,
                    window=None):
    bn, s_q, d = q.shape
    s_k = k.shape[1]
    dv_dim = v.shape[-1]
    group = bn // k.shape[0]
    has_bias = bias_flat is not None
    interpret = _interpret()
    blocks_q = s_q // block_q
    blocks_k = s_k // block_k
    steps_k = _inner_steps(blocks_k, block_q, block_k, window)
    steps_q = _inner_steps(blocks_q, block_k, block_q, window)

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bn, 1, s_q)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    ks, qs = _stream_clamps(causal, block_q, block_k, s_k - s_q,
                            blocks_q, blocks_k, window)

    # dq: grid (bn, q-block, k-block) — q/do/lse/delta resident across the
    # sequential k axis, K/V streamed block-by-block by the pipeline
    dq_specs = [
        pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
        pl.BlockSpec((1, block_k, d),
                     lambda i, j, t: (i // group, ks(j, t), 0)),
        pl.BlockSpec((1, block_k, dv_dim),
                     lambda i, j, t: (i // group, ks(j, t), 0)),
        pl.BlockSpec((1, block_q, dv_dim), lambda i, j, t: (i, j, 0)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, t: (i, 0, j)),
        pl.BlockSpec((1, 1, block_q), lambda i, j, t: (i, 0, j)),
    ]
    dq_ops = [q, k, v, g, lse, delta]
    if has_bias:
        dq_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda i, j, t: (i, 0, ks(j, t))))
        dq_ops.append(bias_flat)
    dq = _pcall(
        _maybe_bias(functools.partial(
            _dq_kernel, scale=scale, causal=causal, blocks_k=blocks_k,
            block_q=block_q, block_k=block_k, causal_offset=s_k - s_q,
            has_bias=has_bias, window=window, steps_k=steps_k),
            has_bias, n_in=6),
        interpret, "zoo_flash_dq",
        grid=(bn, blocks_q, steps_k),
        in_specs=dq_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda i, j, t: (i, j, 0)),
        out_shape=jax.ShapeDtypeStruct((bn, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
    )(*dq_ops)

    # dk/dv/dbias: grid (key-value rows, k-block, group x q-block) — K/V
    # resident across the sequential axis, Q/dO/lse/delta of each of the
    # group's query heads streamed block-by-block
    def qrow(i, t):
        return i * group + t // steps_q

    def qblk(j, t):
        return qs(j, t % steps_q)

    dkv_specs = [
        pl.BlockSpec((1, block_q, d),
                     lambda i, j, t: (qrow(i, t), qblk(j, t), 0)),
        pl.BlockSpec((1, block_k, d), lambda i, j, t: (i, j, 0)),
        pl.BlockSpec((1, block_k, dv_dim), lambda i, j, t: (i, j, 0)),
        pl.BlockSpec((1, block_q, dv_dim),
                     lambda i, j, t: (qrow(i, t), qblk(j, t), 0)),
        pl.BlockSpec((1, 1, block_q),
                     lambda i, j, t: (qrow(i, t), 0, qblk(j, t))),
        pl.BlockSpec((1, 1, block_q),
                     lambda i, j, t: (qrow(i, t), 0, qblk(j, t))),
    ]
    dkv_ops = [q, k, v, g, lse, delta]
    if has_bias:
        dkv_specs.append(
            pl.BlockSpec((1, 1, block_k), lambda i, j, t: (i, 0, j)))
        dkv_ops.append(bias_flat)
    dk, dv, dbias = _pcall(
        _maybe_bias(functools.partial(
            _dkv_kernel, scale=scale, causal=causal, blocks_q=blocks_q,
            block_q=block_q, block_k=block_k, causal_offset=s_k - s_q,
            has_bias=has_bias, window=window, steps_q=steps_q, group=group),
            has_bias, n_in=6),
        interpret, "zoo_flash_dkv",
        grid=(bn // group, blocks_k, group * steps_q),
        in_specs=dkv_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, block_k, dv_dim), lambda i, j, t: (i, j, 0)),
            pl.BlockSpec((1, 1, block_k), lambda i, j, t: (i, 0, j)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bn // group, s_k, d), k.dtype),
            jax.ShapeDtypeStruct((bn // group, s_k, dv_dim), v.dtype),
            jax.ShapeDtypeStruct((bn // group, 1, s_k), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, dv_dim), jnp.float32),
            pltpu.VMEM((1, block_k), jnp.float32),
        ],
    )(*dkv_ops)
    return dq, dk, dv, (dbias if has_bias else None)


# ---------------------------------------------------------------------------
# custom_vjp wiring over the flattened (bn, s, d) layout
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8))
def _flash(q, k, v, bias_flat, scale: float, causal: bool,
           block_q: int, block_k: int, window=None):
    """Returns (out, lse) with lse (bn, 1, s_q) f32. The lse output is
    differentiable too: d(lse_i)/d(s_ij) = p_ij, which folds into the
    backward kernels as an extra ``+ g_lse`` inside the delta term — this is
    what lets ring attention merge per-shard flash partials and still get
    exact gradients through the merge."""
    return _flash_forward(q, k, v, bias_flat, scale, causal, block_q, block_k,
                          window)


# The two residuals only the forward kernel can make. A checkpoint whose
# policy is ``save_only_these_names(*FLASH_RESIDUALS)`` keeps them and so
# drops the kernel from its recomputation (DecoderBlock); under a bare
# ``jax.checkpoint``, or none, a name is the identity.
FLASH_RESIDUALS = ("zoo_flash_out", "zoo_flash_lse")


def _flash_fwd_rule(q, k, v, bias_flat, scale, causal, block_q, block_k,
                    window=None):
    out, lse = _flash_forward(q, k, v, bias_flat, scale, causal,
                              block_q, block_k, window)
    out = checkpoint_name(out, FLASH_RESIDUALS[0])
    lse = checkpoint_name(lse, FLASH_RESIDUALS[1])
    return (out, lse), (q, k, v, bias_flat, out, lse)


def _flash_bwd_rule(scale, causal, block_q, block_k, window, res, cts):
    q, k, v, bias_flat, out, lse = res
    g, g_lse = cts
    # ds = p*(dp - delta) + g_lse*p  ==  p*(dp - (delta - g_lse))
    dq, dk, dv, dbias = _flash_backward(
        q, k, v, bias_flat, out, lse, g, scale, causal, block_q, block_k,
        g_lse=g_lse, window=window)
    if dbias is not None:
        # cotangent aval must match the primal's (dbias accumulates in f32)
        dbias = dbias.astype(bias_flat.dtype)
    return dq, dk, dv, dbias


_flash.defvjp(_flash_fwd_rule, _flash_bwd_rule)


def _validate(q, k, scale, block_q: int, block_k: int, causal=True,
              window=None, bias=None):
    """Shared support-envelope check for both public entry points; returns
    the resolved scale."""
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s_q, s_k = q.shape[2], k.shape[2]
    from analytics_zoo_tpu.ops.attention import check_window_and_heads

    check_window_and_heads(q, k, causal, window)
    if q.shape[1] != k.shape[1] and bias is not None:
        raise NotImplementedError("bias with grouped key-value heads")
    if s_q % block_q or s_k % block_k:
        raise NotImplementedError(f"seq lens must tile ({block_q},{block_k})")
    if q.shape[-1] > 256:
        raise NotImplementedError("head_dim > 256")
    return scale


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """Pallas path. q: (batch, heads, seq, head_dim); k/v the same, or with
    fewer (key-value) heads that divide the query heads: query head h reads
    key-value head h // (heads / key-value heads). bias additive,
    broadcastable to (batch, heads, 1, s_k) (padding-mask layout).
    ``window`` (with ``causal``): a query sees only the ``window`` newest of
    its causal keys, itself included; blocks wholly outside are neither
    computed nor fetched, and the kernels' innermost axis walks only the
    blocks a window can touch. Raises NotImplementedError for unsupported
    shapes/bias so the dispatcher in ops.attention falls back to the XLA
    reference implementation. ``block_q``/``block_k`` override the
    seq-aware default tile sizes per call."""
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       q.shape[2], k.shape[2])
    scale = _validate(q, k, scale, block_q, block_k, causal, window, bias)
    window = None if window is None else int(window)
    b, n, s_q, d = q.shape
    s_k, n_kv = k.shape[2], k.shape[1]

    bias_flat = None
    if bias is not None:
        if bias.ndim != 4:
            raise NotImplementedError("bias must be rank-4")
        if bias.shape[2] != 1:
            # full-rank (per-query) bias: dbias would be O(S²); XLA path
            raise NotImplementedError("bias with query dim > 1")
        if bias.shape[3] not in (1, s_k):
            raise NotImplementedError("bias key dim mismatch")
        bias_flat = jnp.broadcast_to(
            bias[:, :, 0, :], (b, n, s_k)).reshape(b * n, 1, s_k)

    bn = b * n
    out, _ = _flash(q.reshape(bn, s_q, d), k.reshape(b * n_kv, s_k, d),
                    v.reshape(b * n_kv, s_k, v.shape[-1]), bias_flat, scale,
                    causal, block_q, block_k, window)
    return out.reshape(b, n, s_q, v.shape[-1])


def flash_attention_with_lse(q, k, v, causal: bool = False,
                             scale: Optional[float] = None,
                             block_q: Optional[int] = None,
                             block_k: Optional[int] = None):
    """Like :func:`flash_attention` but also returns the per-row logsumexp
    (b, n, s_q) f32 — the mergeable partial for ring attention. Both outputs
    are differentiable (the lse cotangent folds into the backward kernels'
    delta term)."""
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       q.shape[2], k.shape[2])
    scale = _validate(q, k, scale, block_q, block_k)
    b, n, s_q, d = q.shape
    s_k = k.shape[2]
    bn = b * n
    out, lse = _flash(q.reshape(bn, s_q, d), k.reshape(bn, s_k, d),
                      v.reshape(bn, s_k, v.shape[-1]), None, scale, causal,
                      block_q, block_k)
    return (out.reshape(b, n, s_q, v.shape[-1]),
            lse.reshape(b, n, s_q))
