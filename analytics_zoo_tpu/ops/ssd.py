"""The state-space scan of a Mamba-2 layer in its chunked form (SSD: Dao & Gu
2024, "Transformers are SSMs", section 6).

A head j of width P keeps a state ``S_j`` of P x N; group g of the B and C
projections (N wide) serves the heads ``g * H / G .. (g + 1) * H / G - 1``:

    a_t = exp(dt_t * A_j)                       (A_j < 0, dt_t > 0)
    S_t = a_t S_{t-1} + dt_t x_t B_t^T          (S_0 = 0)
    y_t = S_t C_t + D_j x_t

The row is cut into chunks of ``chunk`` tokens, and the recurrence is taken
in four steps: (1) within a chunk, ``(L o C B^T)(dt x)`` with ``L`` the
chunk's cumulative decays, a causal product like attention's; (2) each
chunk's end state from its own tokens; (3) the states carried across chunks,
one step a chunk; (4) the carried state read out by C and decayed into the
chunk. Decays, ``dt`` and the states are float32 whatever the inputs' type,
and so is the arithmetic; the result is in x's type.

On the chip, where a lane tile holds a whole number of heads (P divides
128), a group's heads fill whole lane tiles (r P a multiple of 128 for r =
H / G), N is a multiple of 128 and the chunk of 8, the scan is a pair of
Pallas kernels with their own VJP, ``zoo_ssd_fwd`` and ``zoo_ssd_bwd``;
anywhere else it is the four steps in ``jax.numpy`` as XLA differentiates
them. ``zoo_ssd_built_total{path}`` counts which.

The kernels' grid is (batch row, group, chunk), the chunk axis sequential:
a group's r states stay in VMEM across the chunks as one float32 ``(N, r
P)`` slab, so steps (2)-(4) are one update a chunk and no state array is
written but the forward's residual. A chunk reads x ``(chunk, r P)``, B and
C ``(chunk, N)`` in the layouts the mixer already has, and dt as rows
``(r, chunk)``; it writes y once. Within it the cumulative log decays are
float32 rows (a product with a triangle of ones at float32 precision),
spread to the heads' lanes by a product with a one-hot matrix; C B^T is made
once for the group, and each head's masked decay tile ``exp(cum_l - cum_s)``
(float32, 0 above the diagonal) scales it in VMEM before the product with
``dt x``. A head of 64 multiplies its tile's 128 lanes and keeps its own
half: the MXU pass is the same.

The backward is one reverse pass over the chunks that carries the state's
gradient for a group in VMEM, reads the forward's entering states (float32
``(batch, chunks, N, H P)``, the one residual besides the inputs), and
recomputes each chunk's decays and C B^T. It writes dx, dB, dC and d(dt)
once; dA and dD leave as float32 partials a (row, group), which XLA sums.
A per-head sum becomes a row of ``(r, chunk)`` by a product with the one-hot
matrix, and the decays' gradient is taken back through the cumulative sum
by a product with the other triangle, both at float32 precision.

Precision: every product runs as XLA runs the ``jax.numpy`` form on the
chip at its default precision, its operands rounded once to bfloat16 with a
float32 sum, where x is bfloat16; at float32 precision where x is float32.
Decays, dt, the cumulative sums, the states and every accumulation are
float32, and y is rounded once to x's type.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.flash_attention import (_compute_dtype,
                                                   _interpret, _mm, _mm_nt,
                                                   _mm_tn)
from analytics_zoo_tpu.ops.qk_rotary import _params

LANES = 128
# The most bytes of a float32 (chunk, r P) or (chunk, chunk) tile the kernels
# take: a dozen such temporaries of a chunk sit in VMEM together. The
# Nemotron-H cell's tile, 128 x 512, is a quarter of it.
_TILE_BYTES = 1 << 20
F32 = jnp.float32


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def _causal_decays(cum):
    """cum (..., L): inclusive cumulative log decays of a chunk -> (..., L, L)
    with ``exp(cum[l] - cum[s])`` where s <= l and 0 above the diagonal (the
    exponent is set to -inf there first: its gradient is then 0, not inf)."""
    n = cum.shape[-1]
    seg = cum[..., :, None] - cum[..., None, :]
    below = jnp.tril(jnp.ones((n, n), jnp.bool_))
    return jnp.exp(jnp.where(below, seg, -jnp.inf))


def _scan_xla(x, dt, a, b, c, d, chunk: int):
    """The four steps in ``jax.numpy`` (see the module docstring)."""
    bsz, s, h, p = x.shape
    g, n = b.shape[-2:]
    z, r, f32 = s // chunk, h // g, F32
    # a token's log decay and its input dt x; heads as (group, head of group)
    la = (dt.astype(f32) * a.astype(f32)).reshape(bsz, z, chunk, g, r)
    cum = jnp.cumsum(jnp.moveaxis(la, 2, -1), axis=-1)          # (b,z,g,r,l)
    xs = (x.astype(f32) * dt.astype(f32)[..., None]).reshape(
        bsz, z, chunk, g, r, p)
    bb = b.astype(f32).reshape(bsz, z, chunk, g, n)
    cc = c.astype(f32).reshape(bsz, z, chunk, g, n)
    # (1) within a chunk
    scores = jnp.einsum("bzlgn,bzsgn->bzgls", cc, bb)
    mixed = _causal_decays(cum) * scores[:, :, :, None]       # (b,z,g,r,l,s)
    y = jnp.einsum("bzgrls,bzsgrp->bzlgrp", mixed, xs)
    # (2) a chunk's end state from its own tokens
    to_end = jnp.moveaxis(jnp.exp(cum[..., -1:] - cum), -1, 2)   # (b,z,l,g,r)
    ends = jnp.einsum("bzlgn,bzlgrp->bzgrpn", bb, xs * to_end[..., None])
    # (3) the state entering each chunk, carried one chunk at a time
    through = jnp.exp(cum[..., -1])                              # (b,z,g,r)

    def carry(state, inp):
        decay, end = inp
        return decay[..., None, None] * state + end, state

    _, entering = jax.lax.scan(
        carry, jnp.zeros((bsz, g, r, p, n), f32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(ends, 1, 0)))
    entering = jnp.moveaxis(entering, 0, 1)                      # (b,z,g,r,p,n)
    # (4) the entering state read out by C, decayed into the chunk
    y = y + jnp.einsum("bzlgn,bzgrpn->bzlgrp", cc, entering) * jnp.moveaxis(
        jnp.exp(cum), -1, 2)[..., None]
    y = y.reshape(bsz, s, h, p) + d.astype(f32)[:, None] * x.astype(f32)
    return y.astype(x.dtype)


# ---------------------------------------------------------------------------
# The kernels
# ---------------------------------------------------------------------------


def _iota(shape, dim):
    return jax.lax.broadcasted_iota(jnp.int32, shape, dim)


def _ones_where(cond):
    return jnp.where(cond, 1.0, 0.0).astype(F32)


def _parts(v, axis: int):
    """float32 v as three bfloat16 parts side by side along ``axis``, whose
    sum is v to the bit: a product of them with a matrix of ones and noughts
    (three copies stacked the same way) is v's own product at float32 in one
    bfloat16 pass of the MXU."""
    hi = v.astype(jnp.bfloat16).astype(F32)
    mid = (v - hi).astype(jnp.bfloat16).astype(F32)
    lo = v - hi - mid
    return jnp.concatenate([hi, mid, lo], axis=axis).astype(jnp.bfloat16)


def _thrice(m, axis: int):
    return jnp.concatenate([m] * 3, axis=axis).astype(jnp.bfloat16)


def _exact_mm(v, m):
    """v (M, K) @ m (K, N), m of ones and noughts, at float32."""
    return _mm(_parts(v, 1), _thrice(m, 0), jnp.bfloat16)


def _exact_mm_nt(m, v):
    """m (M, K) @ v (N, K)^T, m of ones and noughts, at float32."""
    return _mm_nt(_thrice(m, 1), _parts(v, 1), jnp.bfloat16)


class _Chunk:
    """What both kernels compute from a chunk's inputs: the cumulative log
    decays as rows ``(r, L)`` and spread to the heads' lanes ``(L, r P)``,
    dt spread the same way, the decays into the chunk (``into``), to its end
    (``to_end``) and across it (``through``, a row), x and dt x in
    float32."""

    def __init__(self, dt_ref, a_ref, x_ref, head_dim: int):
        dt = dt_ref[0, 0, 0]                                   # (r, L)
        r, length = dt.shape
        width = r * head_dim
        self.head_dim, self.length = head_dim, length
        # spread[j, k]: lane k belongs to head j
        self.spread = _ones_where(
            _iota((r, width), 1) // head_dim == _iota((r, width), 0))
        self.dt_rows = dt
        self.cum_rows = _exact_mm(dt * a_ref[0], _ones_where(
            _iota((length, length), 0) <= _iota((length, length), 1)))
        # cum and dt to the heads' lanes at once: their parts stacked on the
        # contraction, a block of the spread for each
        both = _ones_where(
            (_iota((6 * r, 2 * width), 1) // head_dim)
            == (_iota((6 * r, 2 * width), 0) // (3 * r)) * r
            + _iota((6 * r, 2 * width), 0) % r)
        spread = _mm_tn(
            jnp.concatenate([_parts(self.cum_rows, 0), _parts(dt, 0)],
                            axis=0), both.astype(jnp.bfloat16), jnp.bfloat16)
        self.cum, self.dt = spread[:, :width], spread[:, width:]
        last = self.cum[length - 1:length, :]
        self.into = jnp.exp(self.cum)
        self.to_end = jnp.exp(last - self.cum)
        self.through = jnp.exp(last)                           # (1, r P)
        self.x = x_ref[0].astype(F32)
        self.xd = self.x * self.dt
        self.causal = (_iota((length, length), 0)
                       >= _iota((length, length), 1))

    def decays(self, j: int, tile):
        """Head j's ``(L, L)`` decay tile, from its lane tile of ``cum``."""
        lane = j % (LANES // self.head_dim) * self.head_dim
        col = jnp.sum(jnp.where(_iota(tile.shape, 1) == lane, tile, 0.0),
                      axis=1, keepdims=True)
        seg = col - self.cum_rows[j:j + 1, :]
        return jnp.exp(jnp.where(self.causal, seg, -jnp.inf))

    def heads(self):
        """(lane tile, head j, lanes of j within the tile) for each head."""
        per = LANES // self.head_dim
        lane = _iota((self.length, LANES), 1) // self.head_dim
        for k in range(self.x.shape[1] // LANES):
            for h in range(per):
                yield k, k * per + h, lane == h

    def rows(self, v):
        """``(L, r P)`` -> ``(r, L)``: each head's sum over its lanes."""
        return _exact_mm_nt(self.spread, v)


def _tile(v, k):
    return v[:, k * LANES:(k + 1) * LANES]


def _tiles(v):
    return [_tile(v, k) for k in range(v.shape[1] // LANES)]


def _fwd_kernel(dt_ref, a_ref, d_ref, x_ref, b_ref, c_ref, y_ref, *rest,
                head_dim: int, keep: bool):
    if keep:
        entering_ref, state_ref = rest
    else:
        (state_ref,) = rest

    @pl.when(pl.program_id(2) == 0)
    def _init():
        state_ref[...] = jnp.zeros_like(state_ref)

    cdt = _compute_dtype(x_ref)
    ch = _Chunk(dt_ref, a_ref, x_ref, head_dim)
    bm, cm = b_ref[0], c_ref[0]
    cb = _mm_nt(cm, bm, cdt)                                   # (L, L)
    state = state_ref[...]                                     # (N, r P)
    if keep:
        entering_ref[0, 0] = state
    y = _tiles(ch.into * _mm(cm, state, cdt) + d_ref[0] * ch.x)
    for k, j, mine in ch.heads():
        part = _mm(ch.decays(j, _tile(ch.cum, k)) * cb, _tile(ch.xd, k), cdt)
        y[k] = y[k] + jnp.where(mine, part, 0.0)
    for k, t in enumerate(y):
        y_ref[0, :, k * LANES:(k + 1) * LANES] = t.astype(y_ref.dtype)
    state_ref[...] = ch.through * state + _mm_tn(bm, ch.xd * ch.to_end, cdt)


def _bwd_kernel(dt_ref, a_ref, d_ref, x_ref, b_ref, c_ref, entering_ref,
                dy_ref, dx_ref, db_ref, dc_ref, ddt_ref, da_ref, dd_ref,
                dstate_ref, *, head_dim: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    cdt = _compute_dtype(x_ref)
    ch = _Chunk(dt_ref, a_ref, x_ref, head_dim)
    length = ch.length
    r = ch.cum_rows.shape[0]
    bm, cm = b_ref[0], c_ref[0]
    cb = _mm_nt(cm, bm, cdt)
    state = entering_ref[0, 0]                 # the state entering the chunk
    dnext = dstate_ref[...]                # the gradient of the one leaving
    dy = dy_ref[0].astype(F32)
    dy_into = dy * ch.into
    read = _mm(cm, state, cdt)                 # y's carried part, before into
    from_end = _mm(bm, dnext, cdt)             # (L, r P)
    xd_end = ch.xd * ch.to_end
    dc = _mm_nt(dy_into, state, cdt)
    db = _mm_nt(xd_end, dnext, cdt)
    dxd = _tiles(ch.to_end * from_end)
    dcb = jnp.zeros((length, length), F32)
    # each head's sums of g = dm o m (its decay tile's gradient times the
    # tile): over s as a column of ``across``, over l as a row of ``below``
    across = jnp.zeros((length, LANES), F32)
    below = jnp.zeros((r, length), F32)
    head_row, head_lane = _iota((r, length), 0), _iota((length, LANES), 1)
    for k, j, mine in ch.heads():
        dec = ch.decays(j, _tile(ch.cum, k))
        m = dec * cb
        dy_t, xd_t = _tile(dy, k), _tile(ch.xd, k)
        dxd[k] = dxd[k] + jnp.where(mine, _mm_tn(m, dy_t, cdt), 0.0)
        dm = _mm_nt(jnp.where(mine, dy_t, 0.0), xd_t, cdt)
        dcb = dcb + dm * dec
        g = dm * m
        across = jnp.where(head_lane == j, jnp.sum(g, axis=1, keepdims=True),
                           across)
        below = jnp.where(head_row == j, jnp.sum(g, axis=0, keepdims=True),
                          below)
    dc = dc + _mm(dcb, bm, cdt)
    db = db + _mm_tn(dcb, cm, cdt)
    dxd = jnp.concatenate(dxd, axis=1)
    dx_ref[0] = (dxd * ch.dt + d_ref[0] * dy).astype(dx_ref.dtype)
    # the gradient of cum: through the decays into the chunk, within it
    # (cum[l] - cum[s]), to its end and across it
    eye = _ones_where(_iota((r, LANES), 0) == _iota((r, LANES), 1))
    dcum = (ch.rows(dy_into * read - xd_end * from_end)
            + _exact_mm_nt(eye, across) - below)
    last = jnp.sum(ch.spread * (
        jnp.sum(xd_end * from_end, axis=0, keepdims=True)
        + jnp.sum(dnext * state, axis=0, keepdims=True) * ch.through),
        axis=1, keepdims=True)                                  # (r, 1)
    dcum = dcum + jnp.where(_iota((r, length), 1) == length - 1, last, 0.0)
    # back through the cumulative sum: dla[t] = sum over l >= t of dcum[l]
    dla = _exact_mm(dcum, _ones_where(_iota((length, length), 0)
                                      >= _iota((length, length), 1)))
    ddt_ref[0, 0, 0] = ch.rows(dxd * ch.x) + dla * a_ref[0]
    da_ref[0, 0] += dla * ch.dt_rows
    dd_ref[0, 0] += (dy * ch.x).reshape(-1, 8, dy.shape[1]).sum(axis=0)
    dc_ref[0] = dc.astype(dc_ref.dtype)
    db_ref[0] = db.astype(db_ref.dtype)
    dstate_ref[...] = ch.through * dnext + _mm_tn(cm, dy_into, cdt)


def _specs(x, b, chunk: int, reverse: bool):
    """The grid (batch row, group, chunk) and the block specs of dt's rows,
    A's and D's rows, x (and y, dx, dy), B and C, the states, and the
    partial sums, for x ``(b, s, H, P)`` and B ``(b, s, G, N)``; ``reverse``
    walks the chunks from the last."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r, z = h // g, s // chunk

    def at(c):
        return z - 1 - c if reverse else c

    return (bsz, g, z), {
        "dt": pl.BlockSpec((1, 1, 1, r, chunk),
                           lambda i, j, c: (i, j, at(c), 0, 0)),
        "a": pl.BlockSpec((1, r, chunk), lambda i, j, c: (j, 0, 0)),
        "d": pl.BlockSpec((1, 1, r * p), lambda i, j, c: (j, 0, 0)),
        "x": pl.BlockSpec((1, chunk, r * p), lambda i, j, c: (i, at(c), j)),
        "bc": pl.BlockSpec((1, chunk, n), lambda i, j, c: (i, at(c), j)),
        "state": pl.BlockSpec((1, 1, n, r * p),
                              lambda i, j, c: (i, at(c), 0, j)),
        "part": pl.BlockSpec((1, 1, r, chunk), lambda i, j, c: (i, j, 0, 0)),
        "d_part": pl.BlockSpec((1, 1, 8, r * p), lambda i, j, c: (i, j, 0, 0)),
    }


def _operands(dt, a, d, groups: int, head_dim: int, chunk: int):
    """dt ``(b, s, H)`` as rows ``(b, G, chunks, r, chunk)``; A as rows
    ``(G, r, chunk)``; D over each head's lanes ``(G, 1, r P)``."""
    bsz, s, h = dt.shape
    r = h // groups
    dt_rows = dt.astype(F32).reshape(bsz, s // chunk, chunk, groups,
                                     r).transpose(0, 3, 1, 4, 2)
    a_rows = jnp.broadcast_to(a.astype(F32).reshape(groups, r, 1),
                              (groups, r, chunk))
    d_row = jnp.repeat(d.astype(F32), head_dim).reshape(groups, 1, -1)
    return dt_rows, a_rows, d_row


def _forward(x, dt, a, b, c, d, chunk: int, keep: bool):
    """y ``(b, s, H P)`` and, where ``keep``, the states entering each chunk
    ``(b, chunks, N, H P)`` float32. x ``(b, s, H, P)``; b, c ``(b, s, G,
    N)``."""
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    x2 = x.reshape(bsz, s, h * p)
    b2, c2 = b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n)
    ops = _operands(dt, a, d, g, p, chunk)
    grid, spec = _specs(x, b, chunk, reverse=False)
    out_specs = [spec["x"]]
    out_shape = [jax.ShapeDtypeStruct(x2.shape, x.dtype)]
    if keep:
        out_specs.append(spec["state"])
        out_shape.append(jax.ShapeDtypeStruct((bsz, grid[2], n, h * p), F32))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, head_dim=p, keep=keep),
        grid=grid,
        in_specs=[spec["dt"], spec["a"], spec["d"], spec["x"], spec["bc"],
                  spec["bc"]],
        out_specs=out_specs, out_shape=out_shape,
        scratch_shapes=[pltpu.VMEM((n, h // g * p), F32)],
        interpret=_interpret(), name="zoo_ssd_fwd",
        **_params(("parallel", "parallel", "arbitrary")),
    )(*ops, x2, b2, c2)
    y = out[0].reshape(x.shape)
    return (y, out[1]) if keep else (y, None)


def _backward(x, dt, a, b, c, d, entering, dy, chunk: int):
    bsz, s, h, p = x.shape
    g, n = b.shape[2:]
    r = h // g
    x2 = x.reshape(bsz, s, h * p)
    b2, c2 = b.reshape(bsz, s, g * n), c.reshape(bsz, s, g * n)
    ops = _operands(dt, a, d, g, p, chunk)
    grid, spec = _specs(x, b, chunk, reverse=True)
    part = jax.ShapeDtypeStruct((bsz, g, r, chunk), F32)
    d_part = jax.ShapeDtypeStruct((bsz, g, 8, r * p), F32)
    dx, db, dc, ddt, da, dd = pl.pallas_call(
        functools.partial(_bwd_kernel, head_dim=p),
        grid=grid,
        in_specs=[spec["dt"], spec["a"], spec["d"], spec["x"], spec["bc"],
                  spec["bc"], spec["state"], spec["x"]],
        out_specs=[spec["x"], spec["bc"], spec["bc"], spec["dt"],
                   spec["part"], spec["d_part"]],
        out_shape=[jax.ShapeDtypeStruct(x2.shape, x.dtype),
                   jax.ShapeDtypeStruct(b2.shape, b.dtype),
                   jax.ShapeDtypeStruct(c2.shape, c.dtype),
                   jax.ShapeDtypeStruct(ops[0].shape, F32), part, d_part],
        scratch_shapes=[pltpu.VMEM((n, r * p), F32)],
        interpret=_interpret(), name="zoo_ssd_bwd",
        **_params(("parallel", "parallel", "arbitrary")),
    )(*ops, x2, b2, c2, entering, dy.reshape(x2.shape))
    ddt = ddt.transpose(0, 2, 4, 1, 3).reshape(dt.shape)
    return (dx.reshape(x.shape), ddt.astype(dt.dtype),
            da.sum(axis=(0, 3)).reshape(h).astype(a.dtype),
            db.reshape(b.shape), dc.reshape(c.shape),
            dd.sum(axis=(0, 2)).reshape(h, p).sum(axis=1).astype(d.dtype))


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _fused(x, dt, a, b, c, d, chunk):
    return _forward(x, dt, a, b, c, d, chunk, keep=False)[0]


def _fused_fwd(x, dt, a, b, c, d, chunk):
    y, entering = _forward(x, dt, a, b, c, d, chunk, keep=True)
    return y, (x, dt, a, b, c, d, entering)


def _fused_bwd(chunk, res, dy):
    return _backward(*res, dy, chunk)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _refusal(x, b, chunk: int) -> Optional[str]:
    """Why the kernels do not take x ``(b, s, H, P)`` and B ``(b, s, G,
    N)`` in chunks of ``chunk``, or None."""
    _, _, h, p = x.shape
    g, n = b.shape[2:]
    width = h // g * p
    if LANES % p:
        return f"head width {p}: a {LANES}-lane tile holds no whole heads"
    if width % LANES:
        return f"a group's {h // g} heads of {p} fill no whole lane tile"
    if h // g > LANES:
        return f"{h // g} heads a group: the backward holds at most {LANES}"
    if n % LANES:
        return f"state width {n}: not a multiple of {LANES}"
    if chunk % 8:
        return f"a chunk of {chunk}: not a multiple of 8"
    if max(width, chunk) * chunk * 4 > _TILE_BYTES:
        return (f"a chunk's float32 tile of {chunk} x {max(width, chunk)} "
                f"passes {_TILE_BYTES} bytes")
    return None


def _validate(x, b, chunk: int) -> None:
    s, h = x.shape[1:3]
    if s % chunk:
        raise ValueError(f"a row of {s} tokens is not a whole number of "
                         f"chunks of {chunk}")
    if h % b.shape[2]:
        raise ValueError(f"{h} heads do not share {b.shape[2]} groups evenly")


def chunked_scan_kernel(x, dt, a, b, c, d, chunk: int = 128):
    """The Pallas path of :func:`chunked_scan`, in interpret mode off the
    chip. Raises NotImplementedError for shapes the kernels do not tile
    (``_refusal``)."""
    _validate(x, b, chunk)
    why = _refusal(x, b, chunk)
    if why:
        raise NotImplementedError(why)
    return _fused(x, dt, a, b, c, d, chunk)


def chunked_scan(x, dt, a, b, c, d, chunk: int = 128):
    """The scan above over whole rows. ``x`` (batch, seq, H, P); ``dt``
    (batch, seq, H), the step size after its softplus; ``a`` (H,), negative;
    ``b``, ``c`` (batch, seq, G, N); ``d`` (H,), the skip. Returns y (batch,
    seq, H, P) in x's dtype. ``seq`` must be a whole number of chunks: a row
    is refused, not padded. On the chip, where the shapes tile, the kernels
    (:func:`chunked_scan_kernel`); elsewhere the four steps in ``jax.numpy``.
    Each call counts one build in ``zoo_ssd_built_total`` under the path it
    took."""
    from analytics_zoo_tpu.common.observability import ssd_built

    _validate(x, b, chunk)
    kernel = _on_chip() and _refusal(x, b, chunk) is None
    ssd_built().labels(path="kernel" if kernel else "xla").inc()
    if kernel:
        return _fused(x, dt, a, b, c, d, chunk)
    return _scan_xla(x, dt, a, b, c, d, chunk)
