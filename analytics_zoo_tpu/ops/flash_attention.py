"""Flash attention (Pallas, TPU): tiled online-softmax attention, fwd + bwd.

The hot op of TransformerLayer/BERT (ref TransformerLayer.scala:50,
BERT.scala:60). The forward kernel streams K/V blocks through VMEM against a
resident Q block, maintaining running max/denominator — O(S) memory instead
of the O(S²) logits tensor (HBM-bandwidth-bound otherwise). The backward is
one kernel that re-computes each live block's probability tile from the
saved per-row logsumexp once and takes dq, dk and dv from it: a K/V block
stays in VMEM and its live query blocks stream past, dk/dv gather in block
scratch and dq in a slab of the query head's whole row. A row whose slab
would not fit the VMEM budget (``_ONE_KERNEL_VMEM``) runs the standard tiled
dq / dk-dv split instead (two kernels, each re-computing the tile). Either
way *training* gets the memory and bandwidth win too — no O(S²) recompute
fallback.

Which (query block, key block) pairs a kernel visits is a block schedule,
computed from the shapes at trace time (``_schedule``): a block no query of
which sees any key (above the causal diagonal, older than a window) is no
grid step. Not causal, the schedule is the whole rectangle.

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
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
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
    is the fastest tiling on the current machine is not measured (D9 in
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
# The block schedule: which (query block, key block) pairs a kernel visits and
# in what order. Known at trace time from the shapes alone; the one place that
# knows which blocks are live.
# ---------------------------------------------------------------------------

# A step's word in the schedule's ``flags`` table: the first step of its
# resident block, the last, and whether the step has a block to compute.
_FIRST, _LAST, _LIVE = 1, 2, 4

# The most live blocks a schedule may hold: its three int32 tables are
# prefetched into SMEM, 1 MiB on a v5e, and this leaves a quarter of it.
_MAX_STEPS = 1 << 16

# The most VMEM the one-kernel backward may give a query head's whole dq: its
# f32 slab and the two buffers of its output block, lane-padded
# (``_dq_slab_bytes``). 32 of a v5e's 128 MiB stay for the block-sized
# operands and the score tile's temporaries. A longer row (over 98 304 bf16
# queries at widths to 128, 49 152 at 192: ring attention's longest shards)
# runs the two kernels, dq's and dk/dv's, each of which needs only blocks.
_ONE_KERNEL_VMEM = 96 << 20

# What the one-kernel backward asks of VMEM beside the slab: Mosaic's default
# scoped limit on a v5e, which the two kernels' blocks compile within.
_BLOCK_VMEM = 16 << 20


def _dq_slab_bytes(s_q: int, d: int, dtype) -> int:
    """VMEM for a row's dq in the one-kernel backward: an f32 ``(s_q, d)``
    slab and two ``dtype`` buffers of the output, ``d`` padded to lanes."""
    lanes = -(-d // 128) * 128
    return s_q * lanes * (4 + 2 * jnp.dtype(dtype).itemsize)


def _live_blocks(causal: bool, window, s_q: int, s_k: int, block_q: int,
                 block_k: int) -> np.ndarray:
    """Which blocks hold a (query, key) pair that is seen,
    ``(s_q // block_q, s_k // block_k)``. Query i sits at position
    ``i + s_k - s_q`` (bottom-right aligned, as the XLA reference's
    ``tril(k=s_k - s_q)``) and sees the keys at or before it, with a
    ``window`` only the ``window`` newest of them. Over a block
    ``q_pos - k_pos`` takes every value between its ends, and a pair is seen
    where that lies in ``[0, window)``. Not causal: every block."""
    blocks_q, blocks_k = s_q // block_q, s_k // block_k
    if not causal:
        return np.ones((blocks_q, blocks_k), bool)
    q_lo = np.arange(blocks_q)[:, None] * block_q + (s_k - s_q)
    k_lo = np.arange(blocks_k)[None, :] * block_k
    least = q_lo - (k_lo + block_k - 1)        # q_pos - k_pos at its ends
    most = q_lo + block_q - 1 - k_lo
    return (most >= 0) & (least < (np.inf if window is None else window))


class _Schedule(NamedTuple):
    """A kernel's walk over its blocks as two grid axes. Where a block is
    dead the live ones lie end to end on the inner axis, (1, all the steps),
    and three tables name them, one entry a step: the index of the block
    that stays in VMEM (``resident``), of the one streamed against it
    (``streamed``, ascending within a resident block, so sums keep their
    order) and the step's ``flags``. Where every block is live (not causal:
    the whole rectangle) the walk is ``direct``: the axes are (``blocks`` =
    the resident blocks, ``steps`` = the streamed ones), the grid's own
    indices are the blocks' and no table is read, so a resident operand
    stands still along the inner axis as far as the compiler can see.
    Forward and dq take a step once; dk/dv once for each of the ``heads``
    query heads that share the key block, heads innermost, so its inner axis
    is ``heads`` times as long and the tables are not."""
    resident: np.ndarray
    streamed: np.ndarray
    flags: np.ndarray
    blocks: int
    steps: int
    heads: int
    direct: bool

    @property
    def tables(self):
        return self.resident, self.streamed, self.flags

    def step(self, t):
        """The step, of one head's, that inner index ``t`` is."""
        return t if self.heads == 1 else t // self.heads

    def head(self, t):
        return 0 if self.heads == 1 else t % self.heads

    def resident_block(self, j, t, resident_ref):
        return j if self.direct else resident_ref[self.step(t)]

    def streamed_block(self, t, streamed_ref):
        return self.step(t) if self.direct else streamed_ref[self.step(t)]

    def this_step(self, resident_ref, streamed_ref, flags_ref):
        """(resident block, streamed block, first, last, live) of the grid
        step at hand: ``first`` / ``last`` bracket a resident block's steps,
        every head of a group included."""
        j, t = pl.program_id(1), pl.program_id(2)
        if self.direct:
            first, last, live = t == 0, t == self.steps * self.heads - 1, True
        else:
            flags = flags_ref[self.step(t)]
            first, last = flags & _FIRST != 0, flags & _LAST != 0
            live = flags & _LIVE != 0
            if self.heads > 1:
                first = jnp.logical_and(first, self.head(t) == 0)
                last = jnp.logical_and(last, self.head(t) == self.heads - 1)
        return (self.resident_block(j, t, resident_ref),
                self.streamed_block(t, streamed_ref), first, last, live)

    def row_edges(self):
        """(first, last): whether the grid step at hand is its row's first
        or last, the whole walk of a row between them."""
        j, t = pl.program_id(1), pl.program_id(2)
        return (jnp.logical_and(j == 0, t == 0),
                jnp.logical_and(j == self.blocks - 1,
                                t == self.steps * self.heads - 1))

    def when_live(self, live, compute) -> None:
        """The step's block work: on a live step only, and with no branch
        where every step is one."""
        if self.direct or (self.flags & _LIVE).all():
            compute()
        else:
            pl.when(live)(compute)


@functools.lru_cache(maxsize=256)
def _schedule(causal: bool, window, s_q: int, s_k: int, block_q: int,
              block_k: int, resident: str = "q", heads: int = 1) -> _Schedule:
    """The live blocks in the order a kernel walks them. ``resident="q"``
    (forward, dq): a query block against its live key blocks.
    ``resident="k"`` (dk/dv): a key block against its live query blocks. A
    resident block with no live partner (queries before the first key, keys
    older than every window) gets one step that is not ``_LIVE``, so that
    its output is still initialised and written."""
    live = _live_blocks(causal, window, s_q, s_k, block_q, block_k)
    if resident == "k":
        live = live.T
    if live.all():      # direct: the tables are one word each, never read
        return _Schedule(*[np.zeros(1, np.int32)] * 3, *live.shape, heads,
                         True)
    res, strm, flags = [], [], []
    for r, row in enumerate(live):
        partners = np.flatnonzero(row)
        word = np.full(max(partners.size, 1), _LIVE if partners.size else 0)
        word[0] |= _FIRST
        word[-1] |= _LAST
        res.append(np.full(word.size, r))
        strm.append(partners if partners.size else np.zeros(1))
        flags.append(word)
    res, strm, flags = (np.concatenate(t).astype(np.int32)
                        for t in (res, strm, flags))
    return _Schedule(res, strm, flags, 1, flags.size, heads, False)


def _index_maps(sched: _Schedule, resident: str, group: int,
                rows: str = "q"):
    """The index maps of a kernel's operands over its grid (row, j, t) and
    the schedule's tables: a q block ``(1, block_q, d)``, a per-query vector
    ``(1, 1, block_q)``, a K/V block and a per-key vector. ``resident``:
    which block stays in VMEM across the inner axis. ``rows="q"``: a row is
    a query head, and reads key-value row ``row // group``. ``rows="kv"``
    (the two-kernel dk/dv): a row is a key-value head, and the step names
    the query head of its group."""

    def resident_blk(j, t, tables):
        return sched.resident_block(j, t, tables[0])

    def streamed_blk(j, t, tables):
        return sched.streamed_block(t, tables[1])

    if resident == "q":
        q_of, k_of = resident_blk, streamed_blk
    else:
        q_of, k_of = streamed_blk, resident_blk

    def q_row(i, t):
        return i if rows == "q" else i * group + sched.head(t)

    def kv_row(i):
        return i // group if rows == "q" else i

    def q_blk(i, j, t, *tables):
        return q_row(i, t), q_of(j, t, tables), 0

    def q_vec(i, j, t, *tables):
        return q_row(i, t), 0, q_of(j, t, tables)

    def kv_blk(i, j, t, *tables):
        return kv_row(i), k_of(j, t, tables), 0

    def k_vec(i, j, t, *tables):
        return kv_row(i), 0, k_of(j, t, tables)

    return q_blk, q_vec, kv_blk, k_vec


def _maybe_bias(kernel, has_bias: bool, n_in: int):
    """Adapt a kernel written with a ``bias_ref`` slot to pallas' positional
    calling convention when no bias operand is passed. ``n_in`` counts the
    refs *before* the bias slot (the schedule's three tables included)."""
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


def _pcall(kernel, name: str, sched: _Schedule, rows: int, in_specs,
           out_specs, out_shape, scratch_shapes, operands,
           row_vmem: Optional[int] = None):
    """Shared pallas_call plumbing for all the kernels: a grid of ``rows``
    by the schedule's two axes (the innermost sequential, carrying the
    accumulator scratch), the schedule's tables prefetched into SMEM for the
    index maps and the kernel's ``pl.when``s, the interpret flag. ``name``
    is the kernel's stable name in a device trace (``zoo_flash_fwd`` /
    ``zoo_flash_dq`` / ``zoo_flash_dkv``): the benchmark's readers find the
    kernels by it. ``row_vmem``: the bytes of a scratch that a whole row
    carries (the one-kernel backward's dq), so that both inner axes are
    sequential and VMEM holds it beside the blocks."""
    interpret = _interpret()
    kw = {}
    if not interpret:
        kw["compiler_params"] = pltpu.CompilerParams(
            dimension_semantics=(
                "parallel", "parallel" if row_vmem is None else "arbitrary",
                "arbitrary"),
            vmem_limit_bytes=(None if row_vmem is None
                              else row_vmem + _BLOCK_VMEM))
    return pl.pallas_call(
        kernel, interpret=interpret, name=name, out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(rows, sched.blocks, sched.steps * sched.heads),
            in_specs=in_specs, out_specs=out_specs,
            scratch_shapes=scratch_shapes),
        **kw)(*sched.tables, *operands)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(resident_ref, streamed_ref, flags_ref, q_ref, k_ref, v_ref,
                bias_ref, o_ref, lse_ref, acc_ref, m_ref, l_ref, *,
                scale: float, causal: bool, sched: _Schedule,
                block_q: int, block_k: int, causal_offset: int,
                has_bias: bool, window):
    qi, ki, first, last, live = sched.this_step(resident_ref, streamed_ref,
                                               flags_ref)
    cdt = _compute_dtype(q_ref)

    @pl.when(first)
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
            # A row with no key in a live block adds exp(0) terms at the
            # _NEG_INF maximum; the first real key's
            # alpha = exp(_NEG_INF - m) = 0 wipes them.
            s = _masked(s, qi, ki, block_q, block_k, causal_offset, window)
        m_prev = m_ref[...]
        m_cur = jnp.max(s, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + _mm(p, v, cdt)
        m_ref[...] = m_new

    sched.when_live(live, compute)

    @pl.when(last)
    def _flush():
        l = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0, 0] = (m_ref[...] + jnp.log(l))[:, 0]


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
    group = bn // k.shape[0]
    has_bias = bias_flat is not None
    sched = _schedule(causal, window, s_q, s_k, block_q, block_k)

    kernel = _maybe_bias(functools.partial(
        _fwd_kernel, scale=scale, causal=causal, sched=sched,
        block_q=block_q, block_k=block_k, causal_offset=s_k - s_q,
        has_bias=has_bias, window=window), has_bias, n_in=6)

    q_blk, q_vec, kv_blk, k_vec = _index_maps(sched, "q", group)
    in_specs = [
        pl.BlockSpec((1, block_q, d), q_blk),
        pl.BlockSpec((1, block_k, d), kv_blk),
        pl.BlockSpec((1, block_k, dv), kv_blk),
    ]
    operands = [q, k, v]
    if has_bias:
        in_specs.append(pl.BlockSpec((1, 1, block_k), k_vec))
        operands.append(bias_flat)

    out, lse = _pcall(
        kernel, "zoo_flash_fwd", sched, bn, in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, dv), q_blk),
            pl.BlockSpec((1, 1, block_q), q_vec),
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
        operands=operands)
    return out, lse


# ---------------------------------------------------------------------------
# Backward. One kernel where a row's dq fits VMEM (``_ONE_KERNEL_VMEM``): a row
# is a query head, a K/V block stays resident while its live q blocks stream
# past (the schedule's ``resident="k"`` walk), and each live block's scores,
# probabilities and dS are made once for all three products; dk/dv gather in
# block scratch, dq in an f32 slab of the row's whole ``(s_q, d)``, zeroed at
# the row's first step and written once at its last. Key blocks ascend, so
# dq sums in the dq kernel's order. With grouped key-value heads each query
# head's dk/dv leave the kernel in f32 and XLA sums the group.
# Otherwise two kernels: dq (a q block resident, its live K/V blocks
# streamed) and dk/dv/dbias (a K/V block resident, its live q blocks
# streamed, each once for every query head of the group). Each grid is
# (rows, the schedule's two axes): the live blocks only. All re-materialize
# the probability tile from the saved logsumexp, accumulating in f32 VMEM
# scratch from a resident block's first step and flushing on its last. A
# whole-row design (K/V as full (1, s, d) blocks with an in-kernel fori over
# pl.ds slices) unrolls into a Mosaic module whose size grows with the
# sequence; this blocked-grid form keeps the module size independent of the
# sequence length (the schedule's tables grow with the live blocks of one
# head, 2 080 int32 words three times at 32 768 keys; the dq slab is zeroed
# and written in a loop of blocks) and lets the pallas pipeline stream the
# blocks instead of holding whole rows in VMEM.
# ---------------------------------------------------------------------------


def _dq_kernel(resident_ref, streamed_ref, flags_ref, q_ref, k_ref, v_ref,
               do_ref, lse_ref, delta_ref, bias_ref, dq_ref, acc_ref, *,
               scale: float, causal: bool, sched: _Schedule, block_q: int,
               block_k: int, causal_offset: int, has_bias: bool, window):
    qi, ki, first, last, live = sched.this_step(resident_ref, streamed_ref,
                                               flags_ref)
    cdt = _compute_dtype(q_ref)

    @pl.when(first)
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

    sched.when_live(live, compute)

    @pl.when(last)
    def _flush():
        dq_ref[0] = (acc_ref[...] * scale).astype(dq_ref.dtype)


def _each_block(n: int, size: int, body) -> None:
    """``body(rows)`` for the ``n`` row slices of ``size`` of a slab, as a
    loop the Mosaic module holds once (a whole-slab operation unrolls)."""
    def step(b, carry):
        body(pl.ds(pl.multiple_of(b * size, size), size))
        return carry

    jax.lax.fori_loop(0, n, step, 0)


def _dkv_kernel(resident_ref, streamed_ref, flags_ref, q_ref, k_ref, v_ref,
                do_ref, lse_ref, delta_ref, bias_ref, dk_ref, dv_ref, db_ref,
                *refs, scale: float, causal: bool, sched: _Schedule,
                block_q: int, block_k: int, causal_offset: int,
                has_bias: bool, window, with_dq: bool):
    # the steps of a K/V block walk its live q blocks (in the two-kernel form
    # each for every query head of the group in turn): one K/V block gathers
    # dk/dv from all of them. ``with_dq``: the one-kernel backward, whose
    # refs go on with dq's output and its slab
    if with_dq:
        dq_ref, dk_acc, dv_acc, db_acc, dq_acc = refs
    else:
        dk_acc, dv_acc, db_acc = refs
    ki, qi, first, last, live = sched.this_step(resident_ref, streamed_ref,
                                               flags_ref)
    cdt = _compute_dtype(q_ref)

    if with_dq:
        row_first, row_last = sched.row_edges()
        n_q = dq_acc.shape[0] // block_q

        @pl.when(row_first)
        def _zero_dq():
            def zero(rows):
                dq_acc[rows, :] = jnp.zeros((block_q, dq_acc.shape[1]),
                                            jnp.float32)
            _each_block(n_q, block_q, zero)

    @pl.when(first)
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
        if with_dq:
            rows = pl.ds(pl.multiple_of(qi * block_q, block_q), block_q)
            dq_acc[rows, :] += _mm(ds, k, cdt)

    sched.when_live(live, compute)

    @pl.when(last)
    def _flush():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)
        db_ref[0, 0] = db_acc[0] if has_bias else jnp.zeros(
            (block_k,), jnp.float32)

    if with_dq:
        @pl.when(row_last)
        def _flush_dq():
            def flush(rows):
                dq_ref[0, rows, :] = (dq_acc[rows, :] * scale).astype(
                    dq_ref.dtype)
            _each_block(n_q, block_q, flush)


def _flash_backward(q, k, v, bias_flat, out, lse, g, scale: float,
                    causal: bool, block_q: int, block_k: int, g_lse=None,
                    window=None):
    from analytics_zoo_tpu.common.observability import flash_backward_built

    bn, s_q, d = q.shape
    s_k = k.shape[1]
    dv_dim = v.shape[-1]
    group = bn // k.shape[0]
    has_bias = bias_flat is not None
    shapes = (causal, window, s_q, s_k, block_q, block_k)
    static = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
                  causal_offset=s_k - s_q, has_bias=has_bias, window=window)
    slab = _dq_slab_bytes(s_q, d, q.dtype)
    one = slab <= _ONE_KERNEL_VMEM
    flash_backward_built().labels(kernels="one" if one else "two").inc()

    delta = jnp.sum(g.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)[:, None, :]  # (bn, 1, s_q)
    if g_lse is not None:
        delta = delta - g_lse.astype(jnp.float32)

    def inputs(q_blk, q_vec, kv_blk, k_vec):
        """q, k, v, dO, lse, delta (and the bias) as the kernels read them."""
        specs = [
            pl.BlockSpec((1, block_q, d), q_blk),
            pl.BlockSpec((1, block_k, d), kv_blk),
            pl.BlockSpec((1, block_k, dv_dim), kv_blk),
            pl.BlockSpec((1, block_q, dv_dim), q_blk),
            pl.BlockSpec((1, 1, block_q), q_vec),
            pl.BlockSpec((1, 1, block_q), q_vec),
        ]
        ops = [q, k, v, g, lse, delta]
        if has_bias:
            specs.append(pl.BlockSpec((1, 1, block_k), k_vec))
            ops.append(bias_flat)
        return specs, ops

    def dkv_outputs(kv_blk, k_vec, rows, dtype_k, dtype_v):
        return ([pl.BlockSpec((1, block_k, d), kv_blk),
                 pl.BlockSpec((1, block_k, dv_dim), kv_blk),
                 pl.BlockSpec((1, 1, block_k), k_vec)],
                [jax.ShapeDtypeStruct((rows, s_k, d), dtype_k),
                 jax.ShapeDtypeStruct((rows, s_k, dv_dim), dtype_v),
                 jax.ShapeDtypeStruct((rows, 1, s_k), jnp.float32)])

    dkv_scratch = [pltpu.VMEM((block_k, d), jnp.float32),
                   pltpu.VMEM((block_k, dv_dim), jnp.float32),
                   pltpu.VMEM((1, block_k), jnp.float32)]

    if one:
        # a row is a query head: its K/V blocks resident in turn, its live q
        # blocks streamed; a query head's dk/dv (f32 where a group sums them)
        # and its whole dq
        sched = _schedule(*shapes, resident="k")
        specs, ops = inputs(*_index_maps(sched, "k", group))
        _, _, out_blk, out_vec = _index_maps(sched, "k", 1)
        out_specs, out_shape = dkv_outputs(
            out_blk, out_vec, bn,
            *((jnp.float32,) * 2 if group > 1 else (k.dtype, v.dtype)))
        dk, dv, dbias, dq = _pcall(
            _maybe_bias(functools.partial(
                _dkv_kernel, sched=sched, with_dq=True, **static),
                has_bias, n_in=9),
            "zoo_flash_dkv", sched, bn, specs,
            out_specs=out_specs + [
                pl.BlockSpec((1, s_q, d), lambda i, j, t, *tables: (i, 0, 0))],
            out_shape=out_shape + [
                jax.ShapeDtypeStruct((bn, s_q, d), q.dtype)],
            scratch_shapes=dkv_scratch + [pltpu.VMEM((s_q, d), jnp.float32)],
            operands=ops, row_vmem=slab)
        if group > 1:
            def summed(x, like):  # a group's query heads, neighbouring rows
                return x.reshape(bn // group, group, s_k, -1).sum(1).astype(
                    like.dtype)
            dk, dv = summed(dk, k), summed(dv, v)
        return dq, dk, dv, (dbias if has_bias else None)

    # dq: q/do/lse/delta resident across a q block's steps, its live K/V
    # blocks streamed one a step by the pipeline
    sched_q = _schedule(*shapes)
    q_blk, _, _, _ = maps = _index_maps(sched_q, "q", group)
    specs, ops = inputs(*maps)
    dq = _pcall(
        _maybe_bias(functools.partial(
            _dq_kernel, sched=sched_q, **static), has_bias, n_in=9),
        "zoo_flash_dq", sched_q, bn, specs,
        out_specs=pl.BlockSpec((1, block_q, d), q_blk),
        out_shape=jax.ShapeDtypeStruct((bn, s_q, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        operands=ops)

    # dk/dv/dbias: a row is a key-value head; K/V resident across a K block's
    # steps, Q/dO/lse/delta of each of the group's query heads streamed
    sched_k = _schedule(*shapes, resident="k", heads=group)
    _, _, kv_blk, k_vec = maps = _index_maps(sched_k, "k", group, rows="kv")
    specs, ops = inputs(*maps)
    out_specs, out_shape = dkv_outputs(kv_blk, k_vec, bn // group, k.dtype,
                                       v.dtype)
    dk, dv, dbias = _pcall(
        _maybe_bias(functools.partial(
            _dkv_kernel, sched=sched_k, with_dq=False, **static),
            has_bias, n_in=9),
        "zoo_flash_dkv", sched_k, bn // group, specs,
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=dkv_scratch, operands=ops)
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


def _validate(q, k, v, scale, block_q: int, block_k: int, causal=True,
              window=None, bias=None):
    """Shared support-envelope check for both public entry points; returns
    the resolved scale."""
    if q.shape[-1] != k.shape[-1] or k.shape[:-1] != v.shape[:-1]:
        raise ValueError(
            f"q {q.shape} and k {k.shape} contract over one width, and k and "
            f"v {v.shape} differ in their last dim at most")
    if scale is None:
        scale = q.shape[-1] ** -0.5
    s_q, s_k = q.shape[2], k.shape[2]
    from analytics_zoo_tpu.ops.attention import check_window_and_heads

    check_window_and_heads(q, k, causal, window)
    if q.shape[1] != k.shape[1] and bias is not None:
        raise NotImplementedError("bias with grouped key-value heads")
    if s_q % block_q or s_k % block_k:
        raise NotImplementedError(f"seq lens must tile ({block_q},{block_k})")
    if max(q.shape[-1], v.shape[-1]) > 256:
        raise NotImplementedError(
            f"head widths {q.shape[-1]} (q, k) and {v.shape[-1]} (v): > 256")
    live = _live_blocks(causal, window, s_q, s_k, block_q, block_k)
    steps = int(live.sum())
    if steps > _MAX_STEPS and not live.all():
        raise NotImplementedError(
            f"{steps} live ({block_q},{block_k}) blocks: the schedule's "
            f"tables would not fit SMEM (at most {_MAX_STEPS}); larger "
            f"blocks would")
    return scale


def flash_attention(q, k, v, bias: Optional[jax.Array] = None,
                    causal: bool = False, scale: Optional[float] = None,
                    block_q: Optional[int] = None,
                    block_k: Optional[int] = None,
                    window: Optional[int] = None):
    """Pallas path. q: (batch, heads, seq, head_dim); k the same, or with
    fewer (key-value) heads that divide the query heads: query head h reads
    key-value head h // (heads / key-value heads); v as k but for its last
    dim, the values' own width, which is the output's (latent attention: q
    and k 192 wide, v and the output 128; dq and dk come back as wide as q,
    dv as wide as v; neither width over 256). bias additive,
    broadcastable to (batch, heads, 1, s_k) (padding-mask layout).
    ``window`` (with ``causal``): a query sees only the ``window`` newest of
    its causal keys, itself included. The kernels' grids hold the live
    blocks only (``_schedule``): a block wholly above the diagonal or
    outside the window is no grid step. Raises NotImplementedError for
    unsupported shapes/bias so the dispatcher in ops.attention falls back
    to the XLA reference implementation. ``block_q``/``block_k`` override
    the seq-aware default tile sizes per call."""
    block_q, block_k = _resolve_blocks(block_q, block_k,
                                       q.shape[2], k.shape[2])
    scale = _validate(q, k, v, scale, block_q, block_k, causal, window, bias)
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
    scale = _validate(q, k, v, scale, block_q, block_k, causal)
    b, n, s_q, d = q.shape
    s_k = k.shape[2]
    bn = b * n
    out, lse = _flash(q.reshape(bn, s_q, d), k.reshape(bn, s_k, d),
                      v.reshape(bn, s_k, v.shape[-1]), None, scale, causal,
                      block_q, block_k)
    return (out.reshape(b, n, s_q, v.shape[-1]),
            lse.reshape(b, n, s_q))
