"""The per-head RMS norm and rotary embedding of queries or keys as one op.

``norm_rotary(x, gain, eps, theta)`` is ``rotary_embedding(rms_norm(x, gain,
eps), theta)`` over a head's width (``keras/layers/decoder.py``; ``theta`` a
base or an ``ops.rotary.Rotary`` spec, YaRN's scaled frequencies among them),
taken from the input projection's layout ``(batch, seq, heads, head_dim)``
to the attention kernels' ``(batch, heads, seq, head_dim)``. On the chip, at
head widths 64 and 128 and a sequence that the token tile divides, it is a
pair of Pallas kernels with their own VJP, ``zoo_qk_rotary_fwd`` and
``zoo_qk_rotary_bwd``: each reads and writes every number once, in x's
dtype, with float32 inside. Anywhere else it is the two functions as XLA
differentiates them; ``zoo_qk_rotary_built_total{path}`` counts which, with
``_scaled`` after the path where the spec scales its frequencies.

The numerics are the two functions': norm statistics and the rotation in
float32 over the same angle bits (``ops.rotary.angles``, as
``rotary_embedding`` computes them), the result rounded once to x's dtype.
The angles' cos and signed sin, times the spec's magnitude, are a table made
once a call outside the kernels and read a token tile at a time, resident
while the heads of that tile go past: a scaled spec is other data in the
same kernels.

A kernel row is one 128-lane tile of the projection's output: one head at
width 128, two neighbouring heads at width 64, so no row is half empty.
The rotation's half swap is a lane roll (two rolls and a lane select when
two heads share the row), a head's sum of squares a lane reduction (two
masked ones). The backward recomputes ``1 / rms`` from x, turns dy back by
the negated angle, writes dx in x's layout and leaves the gain's gradient
as float32 partials a token tile, which XLA sums.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from analytics_zoo_tpu.ops.flash_attention import _interpret
from analytics_zoo_tpu.ops.rotary import Rotary, angles, as_rotary

LANES = 128
# The token tile: the kernels take a sequence that it divides.
TOKENS = 512
# The most bytes of x one grid step reads: its lane tiles are the heads of
# one token tile, up to this many.
_STEP_BYTES = 1 << 20
WIDTHS = (64, 128)


def _on_chip() -> bool:
    return jax.default_backend() == "tpu"


def _tables(s: int, head_dim: int, spec: Optional[Rotary]):
    """(cos, signed sin), each ``(s, LANES)`` float32: a lane's angle is its
    head's pair's, as ``rotary_embedding`` makes it (``ops.rotary.angles``,
    times the spec's magnitude); the sign is the rotate-half's, minus on a
    head's first half. None without rotary."""
    if spec is None:
        return None
    cos, sin = angles(spec, s, head_dim)
    reps = (1, LANES // head_dim)
    return (jnp.tile(jnp.concatenate([cos, cos], axis=-1), reps),
            jnp.tile(jnp.concatenate([-sin, sin], axis=-1), reps))


class _Row:
    """What a kernel does to one 128-lane tile at a given head width: a
    head's sum over its lanes, and the rotate-half's swap."""

    def __init__(self, head_dim: int, shape):
        self.head_dim = head_dim
        if head_dim < LANES:
            lane = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
            self.first_head = lane < head_dim
            self.first_half = lane % head_dim < head_dim // 2

    def head_sum(self, v):
        """Each lane's head's sum of ``v`` over its lanes, broadcast back."""
        if self.head_dim == LANES:
            return jnp.sum(v, axis=-1, keepdims=True)
        lo = jnp.sum(jnp.where(self.first_head, v, 0.0), axis=-1,
                     keepdims=True)
        hi = jnp.sum(jnp.where(self.first_head, 0.0, v), axis=-1,
                     keepdims=True)
        return jnp.where(self.first_head, lo, hi)

    def swap(self, v):
        """A head's two halves swapped (the rotate-half without its sign)."""
        half = self.head_dim // 2
        if self.head_dim == LANES:
            return pltpu.roll(v, half, 1)
        return jnp.where(self.first_half, pltpu.roll(v, LANES - half, 1),
                         pltpu.roll(v, half, 1))


def _fwd_kernel(x_ref, g_ref, *refs, eps: float, head_dim: int, tiles: int,
                rotary: bool):
    if rotary:
        cos_ref, sin_ref, y_ref = refs
    else:
        (y_ref,) = refs
    per = LANES // head_dim
    g = g_ref[...]
    row = _Row(head_dim, (x_ref.shape[1], LANES))
    for j in range(tiles):
        x = x_ref[0, :, j * LANES:(j + 1) * LANES].astype(jnp.float32)
        r = jax.lax.rsqrt(row.head_sum(x * x) * (1.0 / head_dim) + eps)
        n = x * r * g
        if rotary:
            n = n * cos_ref[...] + row.swap(n) * sin_ref[...]
        for h in range(per):
            part = n if h == 0 else pltpu.roll(n, LANES - h * head_dim, 1)
            y_ref[0, j * per + h] = part[:, :head_dim].astype(y_ref.dtype)


def _bwd_kernel(x_ref, g_ref, dy_ref, *refs, eps: float, head_dim: int,
                tiles: int, rotary: bool):
    if rotary:
        cos_ref, sin_ref, dx_ref, dg_ref = refs
    else:
        dx_ref, dg_ref = refs
    per = LANES // head_dim

    @pl.when(pl.program_id(2) == 0)
    def _init():
        dg_ref[...] = jnp.zeros_like(dg_ref)

    g = g_ref[...]
    row = _Row(head_dim, (x_ref.shape[1], LANES))
    dg = jnp.zeros(dg_ref.shape[1:], jnp.float32)
    for j in range(tiles):
        x = x_ref[0, :, j * LANES:(j + 1) * LANES].astype(jnp.float32)
        heads = [dy_ref[0, j * per + h].astype(jnp.float32)
                 for h in range(per)]
        dy = heads[0] if per == 1 else jnp.concatenate(heads, axis=-1)
        r = jax.lax.rsqrt(row.head_sum(x * x) * (1.0 / head_dim) + eps)
        xh = x * r
        # the rotation's transpose is the rotation by the negated angle
        dn = (dy * cos_ref[...] - row.swap(dy) * sin_ref[...]) if rotary \
            else dy
        part = dn * xh
        dg = dg + part.reshape(-1, 8, LANES).sum(axis=0)
        u = dn * g
        dx = r * (u - xh * (row.head_sum(u * xh) * (1.0 / head_dim)))
        dx_ref[0, :, j * LANES:(j + 1) * LANES] = dx.astype(dx_ref.dtype)
    dg_ref[0] += dg


def _plan(x):
    """(lane tiles a grid step, steps along the heads) for x ``(b, s, heads
    * head_dim)``."""
    lanes = x.shape[2] // LANES
    most = max(1, _STEP_BYTES // (TOKENS * LANES * x.dtype.itemsize))
    tiles = max(t for t in range(1, min(lanes, most) + 1) if lanes % t == 0)
    return tiles, lanes // tiles


def _specs(x, head_dim: int, rotary: bool):
    """The grid (batch, token tiles, head steps) and the block specs of x
    ``(b, s, heads * head_dim)``, of the heads' layout ``(b, heads, s,
    head_dim)``, of the gain's row and the two tables."""
    b, s, _ = x.shape
    ts, (tiles, steps) = TOKENS, _plan(x)
    per_step = tiles * LANES // head_dim
    x_spec = pl.BlockSpec((1, ts, tiles * LANES), lambda i, t, h: (i, t, h))
    heads_spec = pl.BlockSpec((1, per_step, ts, head_dim),
                              lambda i, t, h: (i, h, t, 0))
    row_spec = pl.BlockSpec((1, LANES), lambda i, t, h: (0, 0))
    table_specs = ([pl.BlockSpec((ts, LANES), lambda i, t, h: (t, 0))] * 2
                   if rotary else [])
    return (b, s // ts, steps), tiles, x_spec, heads_spec, row_spec, \
        table_specs


def _params(semantics):
    if _interpret():
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=semantics)}


def _gain_row(gain, head_dim: int):
    return jnp.tile(gain.astype(jnp.float32), LANES // head_dim)[None]


def _forward(x, gain, eps, head_dim, spec):
    b, s, width = x.shape
    rotary = spec is not None
    grid, tiles, x_spec, heads_spec, row_spec, table_specs = _specs(
        x, head_dim, rotary)
    tables = _tables(s, head_dim, spec) or ()
    return pl.pallas_call(
        functools.partial(_fwd_kernel, eps=eps, head_dim=head_dim,
                          tiles=tiles, rotary=rotary),
        grid=grid, in_specs=[x_spec, row_spec] + table_specs,
        out_specs=heads_spec,
        out_shape=jax.ShapeDtypeStruct(
            (b, width // head_dim, s, head_dim), x.dtype),
        interpret=_interpret(), name="zoo_qk_rotary_fwd",
        **_params(("parallel", "parallel", "parallel")),
    )(x, _gain_row(gain, head_dim), *tables)


def _backward(x, gain, dy, eps, head_dim, spec):
    b, s, width = x.shape
    rotary = spec is not None
    grid, tiles, x_spec, heads_spec, row_spec, table_specs = _specs(
        x, head_dim, rotary)
    n_tok = grid[1]
    tables = _tables(s, head_dim, spec) or ()
    dx, dg = pl.pallas_call(
        functools.partial(_bwd_kernel, eps=eps, head_dim=head_dim,
                          tiles=tiles, rotary=rotary),
        grid=grid, in_specs=[x_spec, row_spec, heads_spec] + table_specs,
        out_specs=[x_spec, pl.BlockSpec(
            (1, 8, LANES), lambda i, t, h: (i * n_tok + t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                   jax.ShapeDtypeStruct((b * n_tok, 8, LANES), jnp.float32)],
        interpret=_interpret(), name="zoo_qk_rotary_bwd",
        **_params(("parallel", "parallel", "arbitrary")),
    )(x, _gain_row(gain, head_dim), dy, *tables)
    dgain = dg.sum(axis=(0, 1)).reshape(-1, head_dim).sum(axis=0)
    return dx, dgain.astype(gain.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _fused(x, gain, eps, head_dim, spec):
    return _forward(x, gain, eps, head_dim, spec)


def _fused_fwd(x, gain, eps, head_dim, spec):
    return _forward(x, gain, eps, head_dim, spec), (x, gain)


def _fused_bwd(eps, head_dim, spec, res, dy):
    x, gain = res
    return _backward(x, gain, dy, eps, head_dim, spec)


_fused.defvjp(_fused_fwd, _fused_bwd)


def _refusal(x) -> Optional[str]:
    """Why the kernels do not take x ``(b, s, heads, head_dim)``, or None."""
    _, s, n, d = x.shape
    if d not in WIDTHS:
        return f"head width {d}: the kernels take {WIDTHS}"
    if n * d % LANES:
        return f"{n} heads of {d} fill no whole {LANES}-lane tile"
    if s % TOKENS:
        return f"{s} tokens: not a multiple of {TOKENS}"
    return None


def norm_rotary_kernel(x, gain, eps: float, theta=None):
    """The Pallas path of :func:`norm_rotary`, in interpret mode off the
    chip. Raises NotImplementedError for a head width other than 64 or 128,
    an odd number of heads at 64 and a sequence the token tile
    (``TOKENS``) does not divide."""
    why = _refusal(x)
    if why:
        raise NotImplementedError(why)
    b, s, n, d = x.shape
    return _fused(x.reshape(b, s, n * d), gain, float(eps), d,
                  as_rotary(theta))


def _composed(x, gain, eps, theta):
    from analytics_zoo_tpu.keras.layers.decoder import (rms_norm,
                                                         rotary_embedding)

    y = rms_norm(x.transpose(0, 2, 1, 3), gain, eps)
    return y if theta is None else rotary_embedding(y, theta)


def norm_rotary(x, gain, eps: float, theta=None):
    """``rotary_embedding(rms_norm(h, gain, eps), theta)`` of the heads
    ``h = x.transpose(0, 2, 1, 3)``: x ``(batch, seq, heads, head_dim)`` as
    the input projection gives it, the result ``(batch, heads, seq,
    head_dim)`` as the attention kernels take it; no rotary where ``theta``
    is None, else a base or an ``ops.rotary.Rotary`` spec. On the chip at
    head widths 64 (an even number of heads) and 128 over a sequence that
    ``TOKENS`` divides, the fused kernels (:func:`norm_rotary_kernel`);
    elsewhere the two functions as they are. Each call counts one build in
    ``zoo_qk_rotary_built_total`` under the path it took, ``kernel`` or
    ``xla``, with ``_scaled`` after it where the spec's frequencies are
    scaled (YaRN)."""
    from analytics_zoo_tpu.common.observability import qk_rotary_built

    spec = as_rotary(theta)
    kernel = _on_chip() and _refusal(x) is None
    path = ("kernel" if kernel else "xla") + (
        "_scaled" if spec is not None and spec.scaled else "")
    qk_rotary_built().labels(path=path).inc()
    if kernel:
        return norm_rotary_kernel(x, gain, eps, theta)
    return _composed(x, gain, eps, theta)
