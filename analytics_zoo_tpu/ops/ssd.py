"""The state-space scan of a Mamba-2 layer in its chunked form (SSD: Dao & Gu
2024, "Transformers are SSMs", section 6), in ``jax.numpy`` under XLA, its
gradient by differentiation.

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
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _causal_decays(cum):
    """cum (..., L): inclusive cumulative log decays of a chunk -> (..., L, L)
    with ``exp(cum[l] - cum[s])`` where s <= l and 0 above the diagonal (the
    exponent is set to -inf there first: its gradient is then 0, not inf)."""
    n = cum.shape[-1]
    seg = cum[..., :, None] - cum[..., None, :]
    below = jnp.tril(jnp.ones((n, n), jnp.bool_))
    return jnp.exp(jnp.where(below, seg, -jnp.inf))


def chunked_scan(x, dt, a, b, c, d, chunk: int = 128):
    """The scan above over whole rows. ``x`` (batch, seq, H, P); ``dt``
    (batch, seq, H), the step size after its softplus; ``a`` (H,), negative;
    ``b``, ``c`` (batch, seq, G, N); ``d`` (H,), the skip. Returns y (batch,
    seq, H, P) in x's dtype. ``seq`` must be a whole number of chunks: a row
    is refused, not padded."""
    bsz, s, h, p = x.shape
    g, n = b.shape[-2:]
    if s % chunk:
        raise ValueError(f"a row of {s} tokens is not a whole number of "
                         f"chunks of {chunk}")
    if h % g:
        raise ValueError(f"{h} heads do not share {g} groups evenly")
    z, r, f32 = s // chunk, h // g, jnp.float32
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
