"""Plain ResNet-50 as He et al. 2015 publish it (a stage's stride of 2 sits in
its first 1x1), float32, `jax.numpy` / `jax.lax` only. Imports nothing of the
program.

NHWC. Every convolution is followed by batch normalisation over (N, H, W)
with the batch's own biased variance (training mode; eps as the configuration
states), no convolution bias. 7x7/2 stem, 3x3/2 max pool, stages of
3, 4, 6, 3 bottlenecks with widths 64..512 (x4 out), global average pool, a
dense head, softmax. "Same" padding is TensorFlow's (more on the high side).
Pixels arrive as uint8 and are scaled to [-1, 1] first, as the traffic states.
Initialisation: He normal (fan-in) kernels, BN gain 1 and shift 0, head
normal(0, 0.01) with zero bias. The moving statistics are not followed: they
do not enter a training step.

Each bottleneck is rematerialised in the backward pass so that a batch of 256
fits one chip in float32; that changes no number.

`cast` is applied to both operands of every contraction and to every stored
activation (see bert.py); batch statistics stay in float32.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LOSS_EPS = 1e-7


def _stages(cfg):
    return list(zip(cfg["stage_widths"], cfg["stage_blocks"]))


def init_weights(cfg: dict, key) -> dict:
    n_conv = 1 + sum(3 * n + 1 for _, n in _stages(cfg))
    keys = iter(jax.random.split(key, n_conv + 1))

    def conv_bn(kh, kw, cin, cout):
        std = math.sqrt(2.0 / (kh * kw * cin))
        return {"k": std * jax.random.normal(next(keys), (kh, kw, cin, cout),
                                             jnp.float32),
                "g": jnp.ones((cout,), jnp.float32),
                "b": jnp.zeros((cout,), jnp.float32)}

    w = {"stem": conv_bn(7, 7, cfg["num_channels"], cfg["stem_width"]),
         "stages": []}
    cin = cfg["stem_width"]
    for width, n in _stages(cfg):
        blocks = []
        for i in range(n):
            blk = {"a": conv_bn(1, 1, cin, width),
                   "b": conv_bn(3, 3, width, width),
                   "c": conv_bn(1, 1, width, 4 * width)}
            if i == 0:
                blk["proj"] = conv_bn(1, 1, cin, 4 * width)
            blocks.append(blk)
            cin = 4 * width
        w["stages"].append(blocks)
    w["fc"] = {"w": 0.01 * jax.random.normal(
        next(keys), (cin, cfg["num_labels"]), jnp.float32),
        "b": jnp.zeros((cfg["num_labels"],), jnp.float32)}
    return w


def _conv_bn(x, p, stride, eps, cast, relu=True):
    y = jax.lax.conv_general_dilated(
        cast(x), cast(p["k"]), (stride, stride), "SAME",
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    mean = jnp.mean(y, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(y - mean), axis=(0, 1, 2))
    y = cast((cast(y) - mean) / jnp.sqrt(var + eps) * p["g"] + p["b"])
    return jax.nn.relu(y) if relu else y


def _bottleneck(x, p, stride, eps, cast):
    short = x
    if "proj" in p:
        short = _conv_bn(x, p["proj"], stride, eps, cast, relu=False)
    y = _conv_bn(x, p["a"], stride, eps, cast)
    y = _conv_bn(y, p["b"], 1, eps, cast)
    y = _conv_bn(y, p["c"], 1, eps, cast, relu=False)
    return cast(jax.nn.relu(y + short))


def logits(w: dict, x, cfg: dict, cast=lambda t: t):
    """`x`: uint8 pixels (B, H, W, C). Returns (B, labels)."""
    eps = cfg["batch_norm_eps"]
    h = (x.astype(jnp.float32) - 127.5) / 127.5
    h = _conv_bn(h, w["stem"], 2, eps, cast)
    h = jax.lax.reduce_window(h, -jnp.inf, jax.lax.max, (1, 3, 3, 1),
                              (1, 2, 2, 1), "SAME")
    for si, blocks in enumerate(w["stages"]):
        for bi, p in enumerate(blocks):
            stride = 2 if (si > 0 and bi == 0) else 1
            h = jax.checkpoint(
                lambda h_, p_, s=stride: _bottleneck(h_, p_, s, eps, cast))(h, p)
    pooled = cast(jnp.mean(h, axis=(1, 2)))
    return jnp.matmul(cast(pooled), cast(w["fc"]["w"])) + w["fc"]["b"]


def probabilities(w, x, cfg, cast=lambda t: t):
    return jax.nn.softmax(logits(w, x, cfg, cast), axis=-1)


def row_losses(w, x, y, cfg, cast=lambda t: t):
    p = jnp.clip(probabilities(w, x, cfg, cast), LOSS_EPS, 1.0)
    return -jnp.log(jnp.take_along_axis(p, y[:, None].astype(jnp.int32),
                                        axis=-1)[:, 0])
