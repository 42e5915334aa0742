"""Independent ResNet-50 ceiling cross-check (VERDICT r3 #7): train one
synthetic ResNet-50 step built on flax.linen — a second, independently
written implementation path (linen modules, linen BatchNorm, its own
autodiff structure) — on the same chip with the same batch/dtype as
the benchmark's resnet50 configuration. If both land at the same imgs/sec, the
"memory-wall roofline" argument becomes "parity with an independent
implementation of the same model".

    python scripts/flax_resnet_crosscheck.py [--batch 256]

Prints one JSON line. No outer timeout (docs/performance.md protocol).
"""

from __future__ import annotations

import argparse
import functools
import json
import time
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=256)
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--warmup", type=int, default=5)
    args = ap.parse_args()

    import flax.linen as nn
    import optax

    class Bottleneck(nn.Module):
        filters: int
        strides: int = 1
        project: bool = False

        @nn.compact
        def __call__(self, x, train: bool):
            conv = functools.partial(nn.Conv, use_bias=False,
                                     dtype=jnp.bfloat16)
            bn = functools.partial(nn.BatchNorm, use_running_average=not train,
                                   momentum=0.9, dtype=jnp.bfloat16)
            residual = x
            y = conv(self.filters, (1, 1))(x)
            y = nn.relu(bn()(y))
            y = conv(self.filters, (3, 3), strides=(self.strides,) * 2)(y)
            y = nn.relu(bn()(y))
            y = conv(4 * self.filters, (1, 1))(y)
            y = bn(scale_init=nn.initializers.zeros)(y)
            if self.project:
                residual = conv(4 * self.filters, (1, 1),
                                strides=(self.strides,) * 2)(residual)
                residual = bn()(residual)
            return nn.relu(y + residual)

    class ResNet50(nn.Module):
        stage_sizes: Sequence[int] = (3, 4, 6, 3)
        num_classes: int = 1000

        @nn.compact
        def __call__(self, x, train: bool = True):
            x = nn.Conv(64, (7, 7), strides=(2, 2), use_bias=False,
                        dtype=jnp.bfloat16)(x)
            x = nn.relu(nn.BatchNorm(use_running_average=not train,
                                     momentum=0.9, dtype=jnp.bfloat16)(x))
            x = nn.max_pool(x, (3, 3), strides=(2, 2), padding="SAME")
            for i, n_blocks in enumerate(self.stage_sizes):
                filters = 64 * 2 ** i
                for j in range(n_blocks):
                    strides = 2 if i > 0 and j == 0 else 1
                    x = Bottleneck(filters, strides,
                                   project=(j == 0))(x, train)
            x = jnp.mean(x, axis=(1, 2))
            return nn.Dense(self.num_classes, dtype=jnp.float32)(x)

    model = ResNet50()
    rng = jax.random.PRNGKey(0)
    x0 = jnp.zeros((args.batch, 224, 224, 3), jnp.float32)
    variables = model.init(rng, x0, train=True)
    params, batch_stats = variables["params"], variables["batch_stats"]
    tx = optax.sgd(0.1, momentum=0.9)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, batch_stats, opt_state, x, y):
        def loss_fn(p):
            logits, mut = model.apply(
                {"params": p, "batch_stats": batch_stats}, x, train=True,
                mutable=["batch_stats"])
            loss = optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()
            return loss, mut["batch_stats"]

        (loss, new_bs), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_bs, new_opt, loss

    rng_np = np.random.default_rng(0)
    x = jnp.asarray(rng_np.normal(size=(args.batch, 224, 224, 3)),
                    jnp.float32)
    y = jnp.asarray(rng_np.integers(0, 1000, args.batch), jnp.int32)

    for _ in range(args.warmup):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, x, y)
    jax.block_until_ready(params)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        params, batch_stats, opt_state, loss = train_step(
            params, batch_stats, opt_state, x, y)
    jax.block_until_ready(params)
    dt = time.perf_counter() - t0

    imgs = args.batch * args.steps / dt
    print(json.dumps({
        "metric": "flax_linen_resnet50_train_imgs_per_sec_per_chip",
        "value": round(imgs / jax.device_count(), 1),
        "batch": args.batch,
        "platform": jax.devices()[0].platform,
        "device": jax.devices()[0].device_kind,
        "loss": round(float(loss), 3),
    }), flush=True)


if __name__ == "__main__":
    main()
