"""Time Pallas flash attention against the XLA reference attention path.

The VERDICT-r2 evidence harness: fwd and fwd+bwd wall-clock for both
implementations of ``ops.scaled_dot_product_attention`` across sequence
lengths, on whatever backend is live (designed for the real chip; runs on
CPU interpret mode too, just slowly). Prints one JSON line per config.

Usage:  python examples/perf/attention_bench.py [--seqs 128,512,1024,2048]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

import jax
import jax.numpy as jnp
import numpy as np

from analytics_zoo_tpu.ops.attention import _reference_attention  # noqa: E402
from analytics_zoo_tpu.ops.flash_attention import flash_attention  # noqa: E402


def _time_fn(fn, *args, steps: int = 20, warmup: int = 3) -> float:
    out = None
    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps


def bench_config(batch: int, heads: int, seq: int, head_dim: int,
                 causal: bool, steps: int) -> dict:
    rng = np.random.default_rng(0)
    shape = (batch, heads, seq, head_dim)
    q = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    k = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)
    v = jnp.asarray(rng.normal(size=shape), jnp.bfloat16)

    rec = {"batch": batch, "heads": heads, "seq": seq, "head_dim": head_dim,
           "causal": causal}

    # attention FLOPs: 2*S^2*D (QK^T) + 2*S^2*D (PV), x0.5 if causal
    flops_fwd = 4.0 * batch * heads * seq * seq * head_dim
    if causal:
        flops_fwd *= 0.5

    # Call the two implementations DIRECTLY (not through the dispatcher):
    # the dispatcher silently falls back to XLA for shapes the kernel
    # rejects, which would record XLA timings under the "flash" label.
    impls = {
        "flash": lambda q, k, v: flash_attention(q, k, v, causal=causal),
        "xla": lambda q, k, v: _reference_attention(
            q, k, v, None, causal, head_dim ** -0.5),
    }
    for name, impl in impls.items():
        fwd = jax.jit(impl)

        def loss(q, k, v, f=impl):
            return jnp.sum(f(q, k, v).astype(jnp.float32))

        grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
        try:
            t_fwd = _time_fn(fwd, q, k, v, steps=steps)
            t_bwd = _time_fn(grad, q, k, v, steps=steps)
        except Exception as e:  # noqa: BLE001 — record, keep the other path
            rec[name] = {"error": str(e)[:200]}
            continue
        rec[name] = {
            "fwd_ms": round(t_fwd * 1e3, 3),
            "fwd_bwd_ms": round(t_bwd * 1e3, 3),
            "fwd_tflops": round(flops_fwd / t_fwd / 1e12, 2),
        }
    if "fwd_ms" in rec.get("flash", {}) and "fwd_ms" in rec.get("xla", {}):
        rec["flash_speedup_fwd"] = round(
            rec["xla"]["fwd_ms"] / rec["flash"]["fwd_ms"], 2)
        rec["flash_speedup_fwd_bwd"] = round(
            rec["xla"]["fwd_bwd_ms"] / rec["flash"]["fwd_bwd_ms"], 2)
    return rec


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--seqs", default="128,512,1024,2048")
    p.add_argument("--batch", type=int, default=8)
    p.add_argument("--heads", type=int, default=12)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--steps", type=int, default=20)
    p.add_argument("--causal", action="store_true")
    args = p.parse_args()

    print(f"backend: {jax.devices()[0].device_kind}", flush=True)
    for seq in (int(s) for s in args.seqs.split(",")):
        # keep the O(S^2) XLA logits tensor within memory at long seq
        batch = max(1, args.batch * 1024 // max(seq, 1024))
        rec = bench_config(batch, args.heads, seq, args.head_dim,
                           args.causal, args.steps)
        print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
