"""Sequence-serving benchmark: continuous batching vs the naive convoy.

The generation record behind BENCH_SEQ.json (and the CI smoke gate in
tier1.yml). Four claims, measured on one Zipfian mixed-length workload:

1. **Parity.** Tokens from the continuous batcher are bitwise equal to
   the single-request sequential reference (``Seq2seqNet.infer``), for
   every checked request — interleaved admission/eviction changes
   nothing. The convoy baseline is held to the same check, so the
   throughput comparison below is between two *correct* schedulers.
2. **Zero serve-time compiles.** After ``warmup()`` pre-builds the
   (batch x length) prefill grid, the admission scatters and the decode
   step, the whole benchmark run observes zero XLA backend compiles
   (``zoo_compile_total``).
3. **Goodput.** Tokens/sec of iteration-level continuous batching vs a
   naive fixed-batch convoy that pads each batch to its longest member
   and steps until the *slowest* member finishes. Both run the exact
   same AOT executables (same ``compile_program`` tags on the same
   model -> LRU hits); only the schedule differs, so the ratio isolates
   scheduling. Under Zipfian output budgets the convoy burns most of
   its slot-steps on finished rows; the acceptance bar is >= 2x.
4. **Warm restart + int8 hygiene.** A fresh process (fresh
   ``InferenceModel``) against the populated AOT cache dir compiles
   zero and still decodes bitwise-correct tokens — proof it loaded the
   *f32* entries, not the int8 variants, whose keys are salted disjoint
   (``--smoke`` skips these phases; scripts/aot_inspect.py --list shows
   the same split offline).

Usage::

    python scripts/seq_serving_bench.py            # full run -> BENCH_SEQ.json
    python scripts/seq_serving_bench.py --smoke    # CI gate: parity + 0 compiles

``--smoke`` prints a JSON verdict and exits non-zero on any gate
failure; it never writes BENCH_SEQ.json.
"""

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                ".."))

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402

# Two model sizes: the smoke gate only checks parity and compile
# counts, so it uses a tiny net; the full goodput record needs the
# decode step's device time to dominate per-iteration host overhead
# (sub-ms steps measure the python loop, not the scheduler).
SMOKE_SIZE = {"vocab": 32, "embed": 16, "hidden": (32,)}
FULL_SIZE = {"vocab": 64, "embed": 64, "hidden": (1024,)}


def _compile_counter():
    from analytics_zoo_tpu.common.observability import (
        get_registry,
        install_compile_listener,
    )

    install_compile_listener()
    return get_registry().counter(
        "zoo_compile_total",
        "XLA backend compilations observed process-wide "
        "(jax.monitoring).").labels()


def build_seq_model(size, quantize=False, cache_dir=None):
    """An LSTM seq2seq behind an InferenceModel. Layer names inside
    Seq2seqNet are fixed (src_embed/enc_0/dec_0/...), so the params
    pytree — and therefore every AOT cache key — is identical across
    fresh builds: what makes the warm-restart phase honest."""
    import analytics_zoo_tpu as zoo
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.models.seq2seq import Seq2seqNet

    zoo.init_nncontext()
    net = Seq2seqNet(size["vocab"], size["embed"], size["hidden"],
                     cell_type="lstm", name="seqbench")
    model = InferenceModel()
    model.do_load_keras(net)
    if quantize:
        model.do_quantize()
    if cache_dir:
        model.set_aot_cache(cache_dir)
    return net, model


def _latency_ms(lat):
    lat = np.asarray(sorted(lat))
    return {
        "p50": round(float(np.percentile(lat, 50)), 2),
        "p95": round(float(np.percentile(lat, 95)), 2),
        "p99": round(float(np.percentile(lat, 99)), 2),
        "mean": round(float(lat.mean()), 2),
    }


def _zipf_probs(pool, s):
    w = np.array([1.0 / (k ** s) for k in range(1, pool + 1)])
    return w / w.sum()


def make_workload(n, cfg, vocab, zipf_s=1.05, seed=0):
    """``n`` requests of (prompt, max_new_tokens): prompt lengths AND
    output budgets both Zipf-skewed over their full range — mostly
    short, a heavy tail of long. The mixed-length regime where a convoy
    scheduler is worst and length-bucketed admission matters most."""
    rng = np.random.default_rng(seed)
    lens = rng.choice(np.arange(1, cfg.max_prompt_len + 1), size=n,
                      p=_zipf_probs(cfg.max_prompt_len, zipf_s))
    budgets = rng.choice(np.arange(1, cfg.max_new_tokens + 1), size=n,
                         p=_zipf_probs(cfg.max_new_tokens, zipf_s))
    return [(rng.integers(2, vocab, size=int(l)).astype(np.int32), int(b))
            for l, b in zip(lens, budgets)]


def references(net, model, workload, limit=None):
    """Single-request sequential generates via the one-program scan
    reference (``infer``) — the parity oracle. Eagerly compiles one scan
    per distinct (prompt_len, budget), so call this *before* taking the
    serve-time compile snapshot."""
    out = []
    for prompt, mnt in (workload if limit is None else workload[:limit]):
        toks = net.infer(model.params, prompt[None, :],
                         start_token=1, max_seq_len=mnt)
        out.append(np.asarray(toks)[0].astype(np.int32))
    return out


def _bitwise(results, refs):
    return all(np.array_equal(np.asarray(r, np.int32), ref)
               for r, ref in zip(results, refs))


def run_continuous(model, cfg, workload, compiles, name="seq-bench",
                   prime=0):
    """Drive the ContinuousBatcher open-loop (all requests submitted at
    t0) and measure wall, tokens/sec and per-request completion
    latency. ``prime`` extra throwaway requests warm dispatch first."""
    from analytics_zoo_tpu.serving.sequence import ContinuousBatcher

    b = ContinuousBatcher(model, cfg, name=name)
    b.warmup()
    if prime:
        futs = [b.submit(p, max_new_tokens=m, eos=None)
                for p, m in workload[:prime]]
        for f in futs:
            f.result(timeout=300)
    c0 = compiles.value
    done_at = [None] * len(workload)
    t0 = time.perf_counter()
    futs = []
    for i, (prompt, mnt) in enumerate(workload):
        f = b.submit(prompt, max_new_tokens=mnt, eos=None)
        f.add_done_callback(
            lambda _f, i=i: done_at.__setitem__(i, time.perf_counter()))
        futs.append(f)
    results = [np.asarray(f.result(timeout=600)) for f in futs]
    wall = time.perf_counter() - t0
    b.stop(drain=False)
    tokens = int(sum(len(r) for r in results))
    record = {
        "wall_s": round(wall, 3),
        "tokens": tokens,
        "tokens_per_sec": round(tokens / wall, 1),
        "latency_ms": _latency_ms([(d - t0) * 1e3 for d in done_at]),
        "serve_compiles": int(compiles.value - c0),
    }
    return record, results


def run_convoy(net, model, cfg, workload, compiles):
    """Naive fixed-batch generate: take requests ``slots`` at a time,
    pad the whole batch to its longest member's length bucket, and step
    until the slowest member exhausts its budget — no admissions until
    the batch drains. Runs the *same* compiled programs as the
    continuous batcher (identical ``compile_program`` tags on the same
    model), so the goodput gap is pure scheduling."""
    import jax
    import jax.numpy as jnp

    S = cfg.slots
    step_fn, params, mstate = model.compile_program(
        "seq_step",
        lambda p, s, carries, t: net.seq_step(p, carries, t),
        (net.seq_init_carries(S), jnp.zeros((S,), jnp.int32)), warm=True)

    def prefill(bb, lb):
        return model.compile_program(
            f"seq_prefill_{bb}x{lb}",
            lambda p, s, src, m: net.seq_prefill(p, src, m),
            (jnp.zeros((bb, lb), jnp.int32),
             jnp.zeros((bb, lb), jnp.float32)), warm=True)

    def admit(bb):
        def inner(p, s, slot_carries, new_carries, i):
            return jax.tree_util.tree_map(
                lambda sc, c: sc.at[i].set(c.astype(sc.dtype), mode="drop"),
                slot_carries, new_carries)

        return model.compile_program(
            f"seq_admit_{bb}", inner,
            (net.seq_init_carries(S), net.seq_init_carries(bb),
             jnp.zeros((bb,), jnp.int32)), warm=True)

    def bucket(n, ladder):
        for x in ladder:
            if n <= x:
                return x
        return ladder[-1]

    c0 = compiles.value
    lat = []
    results = []
    t0 = time.perf_counter()
    for g0 in range(0, len(workload), S):
        group = workload[g0:g0 + S]
        carries = net.seq_init_carries(S)
        tokens = np.zeros((S,), np.int32)
        # the convoy's defining move: one pad target for the whole batch
        lb = bucket(max(p.shape[0] for p, _ in group), cfg.length_ladder())
        for j0 in range(0, len(group), cfg.max_prefill_batch):
            chunk = group[j0:j0 + cfg.max_prefill_batch]
            bb = bucket(len(chunk), cfg.batch_ladder())
            prefill_fn, _, _ = prefill(bb, lb)
            admit_fn, _, _ = admit(bb)
            src = np.zeros((bb, lb), np.int32)
            mask = np.zeros((bb, lb), np.float32)
            idx = np.full((bb,), S, np.int32)  # S == scatter drop index
            for i, (prompt, _mnt) in enumerate(chunk):
                n = prompt.shape[0]
                src[i, :n] = prompt
                mask[i, :n] = 1.0
                idx[i] = j0 + i
            new_c = prefill_fn(params, mstate, src, mask)
            carries = admit_fn(params, mstate, carries, new_c, idx)
        tokens[:len(group)] = cfg.start_token
        outs = [[] for _ in group]
        for _ in range(max(m for _, m in group)):
            carries, nxt = step_fn(params, mstate, carries, tokens)
            nxt = np.asarray(nxt)
            for i, (_p, mnt) in enumerate(group):
                if len(outs[i]) < mnt:
                    outs[i].append(int(nxt[i]))
                tokens[i] = nxt[i]  # finished rows keep stepping: convoy
        t_batch = time.perf_counter()
        for o in outs:
            results.append(np.asarray(o, np.int32))
            lat.append((t_batch - t0) * 1e3)  # open loop: all arrive at t0
    wall = time.perf_counter() - t0
    tokens_n = int(sum(len(r) for r in results))
    record = {
        "wall_s": round(wall, 3),
        "tokens": tokens_n,
        "tokens_per_sec": round(tokens_n / wall, 1),
        "latency_ms": _latency_ms(lat),
        "serve_compiles": int(compiles.value - c0),
    }
    return record, results


def run_restart(cfg, cache_dir, compiles, check, size):
    """A fresh ``InferenceModel`` (a restarted process's state) against
    the already-populated AOT cache dir: warmup must deserialize every
    program (zero backend compiles), and one real generate must still
    match the f32 reference bitwise — proof the int8 entries sitting in
    the same directory were never cross-hit."""
    from analytics_zoo_tpu.common.observability import aot_cache_counters
    from analytics_zoo_tpu.serving.sequence import ContinuousBatcher

    events = aot_cache_counters()
    net, model = build_seq_model(size, cache_dir=cache_dir)
    # the parity oracle compiles its own eager scan — run it before the
    # snapshot so the compile count covers only the serving path
    want = references(net, model, [check])[0]
    b = ContinuousBatcher(model, cfg, name="seq-restart")
    c0 = compiles.value
    ev0 = {k: c.value for k, c in events.items()}
    t0 = time.perf_counter()
    b.warmup()
    prompt, mnt = check
    got = np.asarray(b.submit(prompt, max_new_tokens=mnt,
                              eos=None).result(timeout=300))
    elapsed = time.perf_counter() - t0
    b.stop(drain=False)
    return {
        "warmup_to_first_generate_s": round(elapsed, 3),
        "compiles": int(compiles.value - c0),
        "aot_cache_events": {k: int(c.value - ev0[k])
                             for k, c in events.items()},
        "generate_bitwise_vs_f32_reference": bool(
            np.array_equal(got.astype(np.int32), want)),
    }


def scan_cache(cache_dir):
    """Variant census of the shared cache dir: every key is tagged f32
    or int8 in its sidecar, and the two key sets must be disjoint."""
    from analytics_zoo_tpu.inference.aot_cache import AotExecutableCache

    by_variant = {}
    for e in AotExecutableCache(cache_dir).entries():
        variant = (e["meta"] or {}).get("variant", "-")
        by_variant.setdefault(variant, set()).add(e["key"])
    f32 = by_variant.get("f32", set())
    int8 = by_variant.get("int8", set())
    return {
        "entries": {k: len(v) for k, v in sorted(by_variant.items())},
        "f32_int8_key_overlap": len(f32 & int8),
        "disjoint": not (f32 & int8),
    }


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="fast CI gate: bitwise parity + zero "
                        "post-warmup compiles; no BENCH_SEQ.json")
    parser.add_argument("--requests", type=int, default=None)
    parser.add_argument("--slots", type=int, default=16)
    parser.add_argument("--max-prompt-len", type=int, default=8)
    parser.add_argument("--max-new-tokens", type=int, default=96)
    parser.add_argument("--zipf-s", type=float, default=1.3)
    parser.add_argument("--repeats", type=int, default=3,
                        help="timed passes per scheduler; the workload "
                        "is deterministic so spread is host noise "
                        "(strictly subtractive) and the best pass is "
                        "the capability estimate")
    parser.add_argument("--parity-checks", type=int, default=16,
                        help="how many requests to verify bitwise in "
                        "the full run (smoke verifies all)")
    parser.add_argument("--cache-dir", default=None)
    parser.add_argument("--out", default=None)
    args = parser.parse_args(argv)

    from analytics_zoo_tpu.serving.sequence import SequenceConfig

    if args.smoke:
        cfg = SequenceConfig(max_prompt_len=8, max_prefill_batch=2,
                             slots=4, max_new_tokens=6, start_token=1)
        n = args.requests or 16
    else:
        cfg = SequenceConfig(max_prompt_len=args.max_prompt_len,
                             max_prefill_batch=8, slots=args.slots,
                             max_new_tokens=args.max_new_tokens,
                             start_token=1, max_queue_size=4096)
        n = args.requests or 224
    size = SMOKE_SIZE if args.smoke else FULL_SIZE
    compiles = _compile_counter()
    workload = make_workload(n, cfg, size["vocab"], zipf_s=args.zipf_s)
    cache_dir = args.cache_dir or tempfile.mkdtemp(prefix="azoo-seq-bench-")

    net, model = build_seq_model(size, cache_dir=None if args.smoke
                                 else cache_dir)
    checks = n if args.smoke else min(args.parity_checks, n)
    refs = references(net, model, workload, limit=checks)

    if args.smoke:
        cont, results = run_continuous(model, cfg, workload, compiles)
        parity = _bitwise(results[:checks], refs)
        verdict = {
            "metric": "sequence_serving_smoke",
            "requests": n,
            "parity_bitwise": parity,
            "serve_compiles": cont["serve_compiles"],
            "tokens_per_sec": cont["tokens_per_sec"],
            "ok": parity and cont["serve_compiles"] == 0,
        }
        print(json.dumps(verdict))
        return 0 if verdict["ok"] else 1

    # full record ---------------------------------------------------------
    def best_of(runs):
        rec, results = max(runs, key=lambda t: t[0]["tokens_per_sec"])
        rec["repeats_tokens_per_sec"] = sorted(
            r["tokens_per_sec"] for r, _ in runs)
        rec["serve_compiles"] = sum(r["serve_compiles"] for r, _ in runs)
        return rec, results

    repeats = max(1, args.repeats)
    cont, cont_results = best_of([
        run_continuous(model, cfg, workload, compiles,
                       prime=2 * cfg.slots if i == 0 else 0)
        for i in range(repeats)])
    convoy, convoy_results = best_of([
        run_convoy(net, model, cfg, workload, compiles)
        for _ in range(repeats)])
    parity = (_bitwise(cont_results[:checks], refs)
              and _bitwise(convoy_results[:checks], refs))

    net_q, model_q = build_seq_model(size, quantize=True,
                                     cache_dir=cache_dir)
    int8, _ = best_of([
        run_continuous(model_q, cfg, workload, compiles, name="seq-int8",
                       prime=2 * cfg.slots if i == 0 else 0)
        for i in range(repeats)])

    restart = run_restart(cfg, cache_dir, compiles, workload[0], size)
    cache = scan_cache(cache_dir)

    record = {
        "metric": "sequence_serving",
        "requests": n,
        "zipf_s": args.zipf_s,
        "config": {"slots": cfg.slots,
                   "max_prompt_len": cfg.max_prompt_len,
                   "max_new_tokens": cfg.max_new_tokens,
                   "prompt_buckets": list(cfg.length_ladder()),
                   "prefill_batch_buckets": list(cfg.batch_ladder())},
        "workload": {
            "prompt_len_mean": round(float(np.mean(
                [p.shape[0] for p, _ in workload])), 2),
            "new_tokens_mean": round(float(np.mean(
                [m for _, m in workload])), 2),
        },
        "parity": {"checked": checks, "bitwise": parity},
        "continuous": cont,
        "convoy": convoy,
        "goodput_ratio": round(cont["tokens_per_sec"]
                               / convoy["tokens_per_sec"], 3),
        "goodput_gate_2x": cont["tokens_per_sec"]
        >= 2.0 * convoy["tokens_per_sec"],
        "p99_ratio": round(cont["latency_ms"]["p99"]
                           / convoy["latency_ms"]["p99"], 3),
        "int8": {
            "tokens_per_sec": int8["tokens_per_sec"],
            "serve_compiles": int8["serve_compiles"],
            "vs_f32": round(int8["tokens_per_sec"]
                            / cont["tokens_per_sec"], 3),
        },
        "restart": restart,
        "warm_restart_zero_compiles": restart["compiles"] == 0,
        "aot_cache": cache,
        "aot_cache_dir": cache_dir,
        "zero_serve_compiles": (cont["serve_compiles"] == 0
                                and convoy["serve_compiles"] == 0
                                and int8["serve_compiles"] == 0),
        "device": device_info(),
    }
    out_path = args.out or os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "BENCH_SEQ.json")
    print(json.dumps(record))
    with open(out_path, "w") as f:
        json.dump(record, f, indent=2)
        f.write("\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
