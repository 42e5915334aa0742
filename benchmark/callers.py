"""The `callers` driver: a closed loop of waiting callers over
`ServingEngine.predict_async`.

Each caller has one request outstanding: when its answer is in hand it sends
its next at once. One thread submits; the engine's completion callback stamps
the answer's arrival and hands the caller back to that thread, so no pool of
OS threads fights for the interpreter lock. How long a caller that had its
answer waited for this thread is reported as `late_ms`.

After the window, with the engine shut down and the program's state freed, the
plain reference answers a sample of the finished requests, drawn from the seed
with the largest request in it.
"""

from __future__ import annotations

import gc
import queue
import statistics
import time

import numpy as np

from benchmark import cells, data, flops, harness, models

WAIT_FOR_ANSWERS_S = 60.0


def sample_finished(done: list, n: int, rng) -> list:
    """`n` of the finished requests, the one with most rows among them."""
    if not done:
        return []
    biggest = max(range(len(done)), key=lambda i: done[i]["rows"])
    pick = set(rng.choice(len(done), size=min(n, len(done)),
                          replace=False).tolist()) | {biggest}
    return [done[i] for i in sorted(pick)]


def model_seed(seed: int, model: int) -> int:
    """Each served model is a classifier of its own, with its own weights."""
    return seed + 1_000_003 * model


def reference_answers(cfg, seed, pool, sample, row_block, cast=lambda t: t):
    """The plain reference's probabilities for the rows of `sample` (in its
    order), each request answered with the weights of the model it went to,
    in blocks of `row_block` rows."""
    import jax
    import jax.numpy as jnp

    ref = models.reference(cfg)
    # the weights go in as an argument: as constants they would be compiled in
    fwd = jax.jit(lambda w_, x: ref.probabilities(w_, x, cfg, cast))
    answers = [None] * len(sample)
    for model in sorted({r["model"] for r in sample}):
        mine = [i for i, r in enumerate(sample) if r["model"] == model]
        w = models.reference_weights(cfg, model_seed(seed, model))
        idx = np.concatenate([np.arange(sample[i]["lo"],
                                        sample[i]["lo"] + sample[i]["rows"])
                              for i in mine])
        n = len(idx)
        idx = np.concatenate([idx, np.zeros(-n % row_block, idx.dtype)])
        want = []
        with jax.default_matmul_precision("highest"):
            for lo in range(0, len(idx), row_block):
                rows = data.take(pool, idx[lo:lo + row_block])
                want.append(np.asarray(fwd(w, [jnp.asarray(a) for a in rows])))
        want, at = np.concatenate(want)[:n], 0
        for i in mine:
            answers[i] = want[at:at + sample[i]["rows"]]
            at += sample[i]["rows"]
        del w
    return np.concatenate(answers)


def prob_gap(served, want) -> float:
    """Widest |served probability - reference's| over the sampled answers."""
    if served.shape != want.shape or not np.all(np.isfinite(served)):
        return float("inf")
    return float(np.abs(served.astype(np.float64) - want).max())


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    import jax
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.serving import ServingEngine
    from analytics_zoo_tpu.serving.batcher import BatcherConfig

    import analytics_zoo_tpu as zoo

    cfg, traffic = cell["config"], cell["traffic"]
    dev = harness.device(cell["chips"], any_platform)
    zoo.init_nncontext()
    rng = np.random.default_rng(seed)
    pool, _ = data.rows(cfg, traffic["pool_rows"], rng)
    deck = data.request_deck(traffic, rng)
    starts = rng.integers(0, traffic["pool_rows"] - max(traffic["rows"]) + 1,
                          len(deck))
    n_models = traffic["models"]
    engine = ServingEngine()
    for m in range(n_models):
        prog = models.Program(cfg, model_seed(seed, m), train=False)
        engine.register(f"model{m}", InferenceModel().do_load_keras(prog.model),
                        example_input=[a[:1] for a in pool],
                        config=BatcherConfig(**traffic["batcher"]))
    registry = engine.metrics.registry

    spans = harness.Spans()
    freed = queue.SimpleQueue()      # callers whose answer has arrived
    sent, done = [], []

    def submit(caller: int, ready_at: float) -> None:
        i = len(sent)
        rows, lo = deck[i % len(deck)], int(starts[i % len(deck)])
        req = {"rows": rows, "lo": lo, "caller": caller,
               "model": caller % n_models}    # a caller stays with its model
        sent.append(req)
        with spans("bench.submit"):
            x = [a[lo:lo + rows] for a in pool]
            req["t_submit"] = time.perf_counter()
            req["late"] = req["t_submit"] - ready_at
            try:
                fut = engine.predict_async(f"model{req['model']}", x)
            except Exception as e:  # noqa: BLE001 — a refusal is a failure
                req.update(t_done=time.perf_counter(), error=repr(e))
                freed.put(req)
                return

        def on_answer(f, req=req):
            req["t_done"] = time.perf_counter()
            err = f.exception()
            if err is None:
                req["answer"] = np.asarray(f.result(), np.float32)
            else:
                req["error"] = repr(err)
            freed.put(req)

        fut.add_done_callback(on_answer)

    def loop(duration: float) -> tuple:
        """Keep every caller's one request outstanding for `duration`
        seconds, then wait for the answers still due. Returns (t0, t1)."""
        t0 = time.perf_counter()
        for c in range(traffic["callers"]):
            submit(c, time.perf_counter())
        outstanding = traffic["callers"]
        while outstanding:
            open_ = time.perf_counter() - t0 < duration
            with spans("bench.wait_answer"):
                try:
                    req = freed.get(timeout=WAIT_FOR_ANSWERS_S)
                except queue.Empty:
                    break                      # never came: counted below
            if "error" not in req:
                done.append(req)
            if open_:
                submit(req["caller"], req["t_done"])
            else:
                outstanding -= 1
        return t0, t0 + duration

    loop(traffic["warm_seconds"])                # every bucket, warm
    sent.clear(), done.clear()
    ctx = {"counters": {"setup_end": harness.counters(registry)}}
    if traced:
        seconds = min(seconds, traffic["trace_seconds"])
    rec = {}
    with harness.window(traced, cell["name"], rec, traffic["module_pattern"],
                        spans,
                        warm=lambda: (loop(0.3), sent.clear(), done.clear())):
        ctx["counters"]["window_start"] = harness.counters(registry)
        setup_s = time.perf_counter() - t_start
        t0, t1 = loop(seconds)
        ctx["counters"]["window_end"] = harness.counters(registry)
    in_window = [r for r in done if r["t_done"] <= t1]
    rows_answered = sum(r["rows"] for r in in_window)
    # a request that failed, was refused or never came is worse than any
    worst = max([seconds] + [r["t_done"] - r["t_submit"] for r in done])
    latency_ms = ([(r["t_done"] - r["t_submit"]) * 1e3 for r in done]
                  + [worst * 1e3] * (len(sent) - len(done)))
    ctx["series"] = {"latency_ms": latency_ms,
                     "late_ms": [r["late"] * 1e3 for r in sent]}
    useful_rows = ctx["counters"]["window_end"].get(
        "zoo_serving_rows_total", 0) - ctx["counters"]["window_start"].get(
        "zoo_serving_rows_total", 0)
    ctx.update(trace=rec["trace"], memory=harness.memory_peak(),
               peaks=flops.PEAKS.get(dev["kind"]),
               window_flops=cells.load(cfg["flops"])(
                   cfg, useful_rows, cfg["seq_len"]))
    harness.log(f"{len(sent)} requests, {len(done)} answered, "
                f"{rows_answered} rows in the window of {seconds:g} s; set-up "
                f"{setup_s:.1f} s; peak {ctx['memory']['memory_peak_bytes'] / 1e9:.2f} GB")

    engine.shutdown()
    del engine, prog
    gc.collect()
    t_ref = time.perf_counter()
    sample = sample_finished(done, traffic["check_requests"], rng)
    numbers = {"prob_gap": float("inf")}
    if sample:
        numbers["prob_gap"] = prob_gap(
            np.concatenate([r["answer"] for r in sample]),
            reference_answers(cfg, seed, pool, sample,
                              traffic["reference_row_block"]))
    harness.log(f"reference answered {sum(r['rows'] for r in sample)} rows of "
                f"{len(sample)} requests in {time.perf_counter() - t_ref:.1f} s")
    p95 = statistics.quantiles(latency_ms, n=100, method="inclusive")[94]
    return {"device": dev, "ctx": ctx, "attempted": len(sent),
            "failed": len(sent) - len(done), "numbers": numbers,
            "sample": sample, "pool": pool,
            "end_to_end": {"serve_rows_per_s": rows_answered / seconds,
                           "serve_p95_ms": p95, "setup_s": setup_s}}
