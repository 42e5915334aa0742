"""The optimizers the configurations state, the lower precision of the
control, and the follower that takes a plain reference through its first
training steps. `jax.numpy` only; imports nothing of the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

tree_map = jax.tree_util.tree_map

# the nearest precision below the one a configuration states: the type of
# the forward pass's operands and the type of the backward pass's gradients
# (fp8 training keeps e4m3 forward and e5m2 backward: Micikevicius et al.
# 2022, "FP8 formats for deep learning")
BELOW = {"float32": (jnp.bfloat16, jnp.bfloat16),
         "bfloat16": (jnp.float8_e4m3fn, jnp.float8_e5m2),
         "float16": (jnp.float8_e4m3fn, jnp.float8_e5m2)}


def lower_precision(stated: str):
    """`cast` for the control: the whole step in the precision below `stated`.
    Forward, a contraction's operand is rounded to that type (values clipped
    to its range). Backward, the cotangent that comes back to the operand is
    rounded to it too, scaled by its largest entry first as a step that keeps
    its gradients in that type has to (unscaled they would all flush to
    nought)."""
    forward, gradient = BELOW[stated]

    def rounded(t, dtype):
        top = float(jnp.finfo(dtype).max)
        return jnp.clip(t, -top, top).astype(dtype).astype(t.dtype)

    @jax.custom_vjp
    def cast(t):
        return rounded(t, forward)

    def backward(_, g):
        scale = jnp.maximum(jnp.max(jnp.abs(g)), jnp.finfo(g.dtype).tiny) / (
            float(jnp.finfo(gradient).max))
        return (rounded(g / scale, gradient) * scale,)

    cast.defvjp(lambda t: (rounded(t, forward), None), backward)
    return cast


class Adam:
    """Kingma & Ba 2014 with bias correction; eps outside the root."""

    def __init__(self, lr, b1=0.9, b2=0.999, eps=1e-8):
        self.lr, self.b1, self.b2, self.eps = lr, b1, b2, eps

    def init(self, w):
        return {"m": tree_map(jnp.zeros_like, w),
                "v": tree_map(jnp.zeros_like, w),
                "t": jnp.zeros((), jnp.float32)}

    def step(self, w, g, s):
        t = s["t"] + 1
        m = tree_map(lambda m_, g_: self.b1 * m_ + (1 - self.b1) * g_,
                     s["m"], g)
        v = tree_map(lambda v_, g_: self.b2 * v_ + (1 - self.b2) * g_ * g_,
                     s["v"], g)
        c1, c2 = 1 - self.b1 ** t, 1 - self.b2 ** t
        w = tree_map(lambda w_, m_, v_: w_ - self.lr * (m_ / c1) / (
            jnp.sqrt(v_ / c2) + self.eps), w, m, v)
        return w, {"m": m, "v": v, "t": t}


class Momentum:
    """SGD with heavy-ball momentum: trace = g + mu * trace."""

    def __init__(self, lr, momentum=0.9):
        self.lr, self.mu = lr, momentum

    def init(self, w):
        return {"trace": tree_map(jnp.zeros_like, w)}

    def step(self, w, g, s):
        trace = tree_map(lambda t_, g_: g_ + self.mu * t_, s["trace"], g)
        return tree_map(lambda w_, t_: w_ - self.lr * t_, w, trace), {
            "trace": trace}



def follow(row_losses, w, batches, opt, cfg, cast=lambda t: t, row_block=None):
    """Take `w` through `batches` (a list of (x, y)), one optimizer step a
    batch, the loss being the mean of `row_losses` over the batch. Rows go
    through in blocks of `row_block` (None: the whole batch at once, which a
    model with batch statistics needs). Returns the loss of each step, the
    first step's gradient and the weights after the last."""

    def loss_sum(w_, x, y):
        return jnp.sum(row_losses(w_, x, y, cfg, cast))

    grad_fn = jax.jit(jax.value_and_grad(loss_sum))
    add = jax.jit(lambda a, b: tree_map(jnp.add, a, b))
    step = jax.jit(lambda w_, g, s, n: opt.step(
        w_, tree_map(lambda t: t / n, g), s))
    state, losses, first = opt.init(w), [], None
    with jax.default_matmul_precision("highest"):
        for x, y in batches:
            n = len(y)
            blk = row_block or n
            total, grad = 0.0, None
            for lo in range(0, n, blk):
                xs = tree_map(lambda a: a[lo:lo + blk], x)
                part, g = grad_fn(w, xs, y[lo:lo + blk])
                total += float(part)
                grad = g if grad is None else add(grad, g)
            losses.append(total / n)
            if first is None:
                first = tree_map(lambda t: t / n, grad)
            w, state = step(w, grad, state, float(n))
    return losses, first, w
