"""On-chip flash-attention timing sweep: Pallas fwd/bwd vs XLA reference
at seq 1024/2048/4096 (+causal), optional block-size sweep, and one
end-to-end long-sequence (8k) attention-layer train step — the
measurement set behind docs/performance.md's dispatcher table
(VERDICT r3 #6). Run directly on the TPU interpreter:

    python scripts/flash_bench.py [--blocks] [--seqs 1024,2048,4096]

Prints one JSON line per measurement. No outer timeout — see the
measuring protocol in docs/performance.md.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), ".."))


def _time_fn(fn, *args, steps=20, warmup=5):
    import jax

    for _ in range(warmup):
        out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(steps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / steps * 1e3  # ms


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seqs", default="1024,2048,4096")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--heads", type=int, default=8)
    ap.add_argument("--dim", type=int, default=64)
    ap.add_argument("--dtype", default="bfloat16")
    ap.add_argument("--blocks", action="store_true",
                    help="sweep block_q/block_k tile sizes in-process "
                         "(per-call static args) and report the best "
                         "combination per shape")
    ap.add_argument("--e2e-8k", action="store_true",
                    help="end-to-end 8k-seq attention train step, "
                         "flash vs XLA")
    ap.add_argument("--e2e-seq", type=int, default=8192,
                    help="sequence length for the --e2e-8k step (e.g. "
                         "32768 demonstrates the O(S)-memory regime where "
                         "the XLA path's logits tensor cannot fit at all)")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from analytics_zoo_tpu.common.runtime import device_info
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from analytics_zoo_tpu.ops.flash_attention import (_resolve_blocks,
                                                        flash_attention)

    dt = jnp.dtype(args.dtype)
    print(json.dumps({"device": device_info()}), flush=True)

    # per-call block sizes (flash_attention(block_q=, block_k=)) make the
    # sweep a single process: each (bq, bk) is a distinct static jit key
    block_grid = [(None, None)]
    if args.blocks:
        block_grid = [(bq, bk)
                      for bq in (128, 256, 512, 1024)
                      for bk in (128, 256, 512, 1024)]

    # --seqs "" skips the sweep entirely (e2e-only runs)
    for s in (int(v) for v in args.seqs.split(",") if v.strip()):
        for causal in (False, True):
            key = jax.random.PRNGKey(s)
            kq, kk, kv, kg = jax.random.split(key, 4)
            shape = (args.batch, args.heads, s, args.dim)
            q = jax.random.normal(kq, shape, dt)
            k = jax.random.normal(kk, shape, dt)
            v = jax.random.normal(kv, shape, dt)
            g = jax.random.normal(kg, shape, dt)
            scale = args.dim ** -0.5

            def make_bwd(f):
                def loss(q_, k_, v_):
                    return jnp.vdot(f(q_, k_, v_).astype(jnp.float32),
                                    g.astype(jnp.float32))
                return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))

            xl_f = jax.jit(lambda q_, k_, v_: _reference_attention(
                q_, k_, v_, None, causal, scale))
            xla_rec = {}
            try:
                xla_rec["xla_fwd_ms"] = round(_time_fn(xl_f, q, k, v), 2)
                xla_rec["xla_bwd_ms"] = round(
                    _time_fn(make_bwd(xl_f), q, k, v), 2)
            except Exception as e:  # noqa: BLE001
                xla_rec["xla_error"] = str(e)[:200]  # OOM at long seq = the point

            best = None
            emitted = 0
            for bq, bk in block_grid:
                if bq is not None and (s % bq or s % bk):
                    continue
                emitted += 1
                fl_f = jax.jit(lambda q_, k_, v_, bq=bq, bk=bk:
                               flash_attention(q_, k_, v_, causal=causal,
                                               scale=scale, block_q=bq,
                                               block_k=bk))
                rec = {"seq": s, "causal": causal, "dtype": args.dtype,
                       "batch": args.batch, "heads": args.heads,
                       "dim": args.dim,
                       # report the tiles the call actually resolves (the
                       # no-arg row rides the seq-aware default)
                       **dict(zip(("block_q", "block_k"),
                                  _resolve_blocks(bq, bk, s, s))),
                       **xla_rec}
                try:
                    rec["flash_fwd_ms"] = round(_time_fn(fl_f, q, k, v), 2)
                    rec["flash_bwd_ms"] = round(
                        _time_fn(make_bwd(fl_f), q, k, v), 2)
                    tot = rec["flash_fwd_ms"] + rec["flash_bwd_ms"]
                    if best is None or tot < best[0]:
                        best = (tot, rec)
                except Exception as e:  # noqa: BLE001
                    rec["flash_error"] = str(e)[:200]
                print(json.dumps(rec), flush=True)
            if emitted == 0:
                # every block combo skipped (seq not tileable): still emit
                # the XLA row so the shape doesn't silently vanish
                print(json.dumps({
                    "seq": s, "causal": causal, **xla_rec,
                    "flash_error": f"seq {s} not divisible by any swept "
                                   f"block size"}), flush=True)
            if args.blocks and best is not None:
                print(json.dumps({"best_for": [s, causal], **best[1]}),
                      flush=True)

    if args.e2e_8k:
        # one training step of a single attention layer at seq 8192 (or
        # --e2e-seq) — the >1 GiB-logits regime where the Pallas path
        # must win; at 32k+ the XLA path's logits don't fit at all and
        # the recorded XLA row is the expected RESOURCE_EXHAUSTED
        import optax

        s = args.e2e_seq
        b, h, d = 1, 8, 64
        key = jax.random.PRNGKey(0)
        x = jax.random.normal(key, (b, s, h * d), dt)
        w = {"qkv": jax.random.normal(key, (h * d, 3 * h * d), dt) * 0.02,
             "o": jax.random.normal(key, (h * d, h * d), dt) * 0.02}

        def step(params, use_flash):
            def loss(p):
                qkv = (x @ p["qkv"]).reshape(b, s, 3, h, d)
                q, k_, v_ = (qkv[:, :, i].transpose(0, 2, 1, 3)
                             for i in range(3))
                if use_flash:
                    o = flash_attention(q, k_, v_, causal=True,
                                        scale=d ** -0.5)
                else:
                    o = _reference_attention(q, k_, v_, None, True,
                                             d ** -0.5)
                o = o.transpose(0, 2, 1, 3).reshape(b, s, h * d)
                return jnp.mean(jnp.square((o @ p["o"]).astype(jnp.float32)))
            return jax.grad(loss)(params)

        for use_flash in (True, False):
            rec = {"e2e": f"attn{s // 1024}k_train_step", "flash": use_flash}
            try:
                f = jax.jit(lambda p: step(p, use_flash))
                rec["step_ms"] = round(_time_fn(f, w, steps=10, warmup=3), 2)
            except Exception as e:  # noqa: BLE001
                rec["error"] = str(e)[:200]
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
