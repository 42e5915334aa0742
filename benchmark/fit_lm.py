"""The `fit` driver for a causal language model of some 0.7 B parameters:
whole `Estimator.train` calls over rows of tokens in host DRAM, as `fit.py`
drives the smaller models, with what that size and that model force:

- the first gradient is read from Adam's first moment (over 1 - b1), and every
  tree that is kept (the start, the first gradient, the end) is brought to
  the host and into the reference's layout there: one more weight-sized
  buffer on the device is 2.8 GB;
- the reference's follower donates its weights, moments and gradient sum,
  keeps the start and the first gradient on the host, and carries the
  selection bias, which a step updates outside the gradient;
- the traced run keeps the device planes for a reduction by kernel and scope
  (`trace_lm.py`) and hands the compiled step's text over for the scopes;
- a traffic file may state `row_sets`: the seed then gives so many sets of
  one call's rows, and the call of the estimator's epoch e is fed set e mod
  `row_sets`, so that no row is fed twice in a run (what replayed rows and
  what fresh ones do to the expert load, call by call: PERF.md, PR 32).
"""

from __future__ import annotations

import gc
import json
import time

import numpy as np

from benchmark import (cells, check, data, fit, flops, harness, models,
                       models_lm, readers_lm, trace_lm)


def counters() -> dict:
    """`harness.counters()`, and the one labelled child a reader needs: a
    family's children are summed there."""
    from analytics_zoo_tpu.common.observability import get_registry

    out = harness.counters()
    fam = get_registry()._families.get("zoo_moe_assignments_total")
    if fam is not None:
        out["zoo_moe_assignments_total_held"] = float(
            fam.labels(held="true").value)
    return out


def call_series(marks: list) -> dict:
    """What each call of the window took and what its router did, from the
    clock and the counters read after every call (`marks`: the window's start,
    then one a call): seconds, the held share of its assignments in %, and how
    many of its expert-layer calls overflowed the compacted buffer."""
    out = {"call_s": [], "call_held_share": [], "call_overflows": []}
    for (t0, c0), (t1, c1) in zip(marks, marks[1:]):
        def d(name):
            return c1.get(name, 0.0) - c0.get(name, 0.0)

        out["call_s"].append(t1 - t0)
        total = d("zoo_moe_assignments_total")
        out["call_held_share"].append(
            d("zoo_moe_assignments_total_held") / total * 100.0 if total
            else None)
        out["call_overflows"].append(
            d("zoo_moe_calls_total") - d("zoo_moe_calls_compact_total"))
    return out


def first_gradient(opt_state, b1: float):
    """The first gradient as the optimizer got it, from its state after one
    step: Adam's first moment is (1 - b1) times that gradient. On the host."""
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "mu")):
        if hasattr(node, "mu"):
            return jax.tree_util.tree_map(
                lambda m: np.asarray(m) / np.float32(1.0 - b1),
                jax.device_get(node.mu))
    raise ValueError("no first moment in the optimizer's state")


def row_sets(cfg: dict, traffic: dict, seed: int) -> tuple:
    """All the rows of a run, (inputs, labels), one draw from the seed with a
    leading axis of sets: `row_sets` of them (one where the traffic file
    states none), each the rows of one call."""
    sets = traffic.get("row_sets", 1)
    n = traffic["batch"] * traffic["steps_per_call"]
    x, y = data.rows(cfg, sets * n, np.random.default_rng(seed))
    return (x.reshape(sets, n, *x.shape[1:]), y.reshape(sets, n, *y.shape[1:]))


class Feed:
    """Each set of rows behind a cached feature set of its own, all built at
    set-up; `of(epoch)` is the one a call of that epoch trains on."""

    def __init__(self, traffic: dict, xs, ys):
        make = cells.load(traffic["feature_set"])
        self.sets = [make(traffic, x, y) for x, y in zip(xs, ys)]

    def of(self, epoch: int):
        return self.sets[epoch % len(self.sets)]

    def close(self) -> None:
        for fs in self.sets:
            if hasattr(fs, "close"):
                fs.close()


def first_steps(prog, feed: Feed, traffic: dict, tape, b1: float) -> dict:
    """Drive the estimator through its first steps with the window's own
    call: stop after step 1 and after step `check_steps`, and keep what the
    state showed there, on the host in the reference's layout."""
    import jax
    from analytics_zoo_tpu.engine.triggers import MaxIteration

    est, batch, steps = prog.est, traffic["batch"], traffic["check_steps"]

    def kept(tree):
        return prog.to_reference_layout(jax.device_get(tree), np)

    def train_to(step):
        est.train(feed.of(est.run_state.epoch), prog.criterion,
                  end_trigger=MaxIteration(step), batch_size=batch)

    est._ensure_state()
    start = kept(est.tstate.params)
    train_to(1)
    first = prog.to_reference_layout(
        first_gradient(est.tstate.opt_state, b1), np)
    train_to(steps)
    end = kept(est.tstate.params)
    change = jax.tree_util.tree_map(np.subtract, end, start)
    return {"losses": [tape.losses[k + 1] for k in range(steps)],
            "first": first, "change": change, "start": start}


def follow(ref, cfg, w, batches, opt, cast=lambda t: t, row_block=1) -> dict:
    """Take the reference from the weights `w` (on the host) through
    `batches`, one optimizer step a batch on the mean of the rows' losses, the
    selection bias updated after each step from the step's counts. Rows go
    through `row_block` at a time; the gradient sum, the weights and the
    moments are donated from call to call, and the moments are on the device
    only for the update. Returns the losses, the first
    gradient and the change of the weights, both on the host."""
    import jax
    import jax.numpy as jnp

    tmap = jax.tree_util.tree_map

    def add_grad(w_, acc, bias, x, y):
        def loss_sum(w__):
            rows, counts = ref.losses_and_counts(w__, x, y, cfg, cast, bias)
            return jnp.sum(rows), counts

        (total, counts), g = jax.value_and_grad(loss_sum, has_aux=True)(w_)
        return total, counts, tmap(jnp.add, acc, g)

    add_grad = jax.jit(add_grad, donate_argnums=(1,))
    mean = jax.jit(lambda g, n: tmap(lambda t: t / n, g), donate_argnums=(0,))
    step = jax.jit(opt.step, donate_argnums=(0, 2))
    new_bias = jax.jit(lambda b, c: ref.update_bias(b, c, cfg))
    start = w
    w = tmap(jnp.array, start)       # a copy: on the CPU asarray would alias
    bias, moments = ref.init_bias(cfg), None
    losses, first = [], None
    with jax.default_matmul_precision("highest"):
        for at, (x, y) in enumerate(batches):
            n = len(y)
            acc, total, counts = tmap(jnp.zeros_like, w), 0.0, 0.0
            for lo in range(0, n, row_block):
                part, c, acc = add_grad(w, acc, bias, x[lo:lo + row_block],
                                        y[lo:lo + row_block])
                total, counts = total + float(part), counts + c
            losses.append(total / n)
            grad = mean(acc, float(n))
            if first is None:
                first = jax.device_get(grad)
            # the moments wait on the host while a gradient is computed: with
            # them, the weights and the gradient sum on the device (11.3 GB)
            # the allocator finds no room for a row's 3.4 GB of scratch
            state = opt.init(w) if moments is None else tmap(jnp.array, moments)
            moments = None       # 5.6 GB: not on the host twice at the fetch
            w, state = step(w, grad, state)
            moments = jax.device_get(state) if at + 1 < len(batches) else None
            del state, grad
            bias = new_bias(bias, counts)
    end = jax.device_get(w)
    return {"losses": losses, "first": first,
            "change": tmap(np.subtract, end, start),
            "bias": np.asarray(bias)}


def fit_numbers(got: dict, want: dict) -> dict:
    """`check.fit_numbers` for trees of 2.8 GB: the same numbers from the same
    tables, each table filled a leaf at a time. `check.leaf_table` copies both
    of its trees whole to float64, 11.3 GB beside the 14 GB that the start and
    the four kept trees take, and a one-chip machine gives a run 40 GiB."""
    import statistics

    import jax

    def table(name):
        rows = [check.leaf_table(a, b) for a, b in zip(
            jax.tree_util.tree_leaves(got[name]),
            jax.tree_util.tree_leaves(want[name]))]
        return {k: [r[k][0] for r in rows] for k in ("got", "want", "diff")}

    out = check.fit_numbers({"losses": got["losses"]},
                            {"losses": want["losses"]})
    first = table("first")
    med = statistics.median(first["want"])
    moved = [w >= check.NOUGHT * med for w in first["want"]]
    for name, rows, keep in (("grad", first, [True] * len(moved)),
                             ("change", table("change"), moved)):
        for key, value in check.norm_gaps(rows, keep).items():
            out[f"{name}_{key}"] = value
    out["grad_diff_best_leaf"] = check.diff_best_leaf(first)
    return out


def host_peak_gb() -> float:
    """The largest resident set this process has had. Some 8.5 GB of it are
    the runtime's mappings of the chip; a one-chip machine ends a run that
    holds 40 GiB beside them."""
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024 / 1e9


def lower_precision(stated: str):
    """`optim.lower_precision` for the control, with a cotangent that is all
    nought left nought: that cast scales a cotangent by its largest entry, and
    where flushing makes the scale 0 an all-nought one comes back 0/0. An
    expert no token of a row picked has such cotangents."""
    import jax
    import jax.numpy as jnp
    from benchmark.reference import optim

    cast = optim.lower_precision(stated)

    @jax.custom_vjp
    def safe(t):
        return cast(t)

    def forward(t):
        return jax.vjp(cast, t)

    def backward(pull, g):
        return (jnp.where(jnp.any(g != 0), pull(g)[0], jnp.zeros_like(g)),)

    safe.defvjp(forward, backward)
    return safe


def reference_steps(cfg, traffic, xs, ys, took, start, cast=lambda t: t,
                    batch_rows=None) -> dict:
    """The plain reference through the same steps from the same start, on
    the rows the program was fed: `took` = (epoch, batch) of each step, the
    epoch's set (`Feed.of`) in the epoch's order (`batch_rows`: only so many
    rows of each batch, a planted fault)."""
    import jax.numpy as jnp

    ref, batch = models.reference(cfg), traffic["batch"]
    order = cells.load(traffic["epoch_order"])
    batches = []
    for epoch, k in took:
        x, y = xs[epoch % len(xs)], ys[epoch % len(ys)]
        idx = order(epoch, len(y))[k * batch:(k + 1) * batch][:batch_rows]
        batches.append((jnp.asarray(x[idx]), jnp.asarray(y[idx])))
    spec = cfg["optimizer"]
    opt = cells.load(spec["reference"])(**spec["args"])
    return follow(ref, cfg, start, batches, opt, cast,
                  traffic["reference_row_block"])


def step_text(est, traffic: dict, seq: int) -> str:
    """The compiled train step's HLO text, for the scopes of its
    instructions: the estimator's cached step lowered once more (a cache
    hit). A traced run whose step cannot be read so fails here."""
    import jax
    import jax.numpy as jnp

    fn = next(f for t, f in est._jit_cache.items() if t[0] == "train")
    ids = jax.ShapeDtypeStruct((traffic["batch"], seq), jnp.int32)
    mask = jax.ShapeDtypeStruct((traffic["batch"],), jnp.float32)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    return fn.lower(est.tstate, (ids, ids, mask), key, None).compile().as_text()


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    import jax
    from analytics_zoo_tpu.engine.triggers import MaxEpoch

    import analytics_zoo_tpu as zoo

    cfg, traffic = cell["config"], cell["traffic"]
    dev = harness.device(cell["chips"], any_platform)
    zoo.init_nncontext()
    xs, ys = row_sets(cfg, traffic, seed)
    # (the program first: where it lacks the model, as a parent commit does,
    # the run ends here, before the feed's threads exist)
    prog = models_lm.Program(cfg, seed)
    feed = Feed(traffic, xs, ys)
    est, tape = prog.est, fit.LossTape()
    est.train_summary = tape
    b1 = cfg["optimizer"]["args"].get("beta_1", 0.9)
    seen = first_steps(prog, feed, traffic, tape, b1)
    start = seen.pop("start")
    took = fit.steps_taken(traffic)

    spans = harness.Spans()

    def call():
        with spans("bench.train_call"):
            est.train(feed.of(est.run_state.epoch), prog.criterion,
                      batch_size=traffic["batch"],
                      end_trigger=MaxEpoch(est.run_state.epoch + 1))
            jax.block_until_ready(est.tstate)

    call()                                     # the window's call, warm
    est.train_summary = None
    ctx = {"counters": {"setup_end": counters()}, "series": {}}
    if traced:
        seconds = min(seconds, traffic["trace_seconds"])
    rec, kept, calls = {}, {}, 0
    with trace_lm.recording(kept), harness.window(
            traced, cell["name"], rec, traffic["module_pattern"], spans,
            warm=call):
        ctx["counters"]["window_start"] = counters()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        marks = [(t0, ctx["counters"]["window_start"])]
        while marks[-1][0] - t0 < seconds:
            call()
            calls += 1
            marks.append((time.perf_counter(), counters()))
        elapsed, ctx["counters"]["window_end"] = marks[-1][0] - t0, marks[-1][1]
    ctx["series"] = call_series(marks)
    harness.log("calls " + json.dumps(
        {k: [v if v is None else round(v, 3) for v in vs]
         for k, vs in ctx["series"].items()}))
    steps = calls * traffic["steps_per_call"]
    items = steps * traffic["batch"] * traffic.get("items_per_row", 1)
    seq = cfg["seq_len"]
    ctx.update(trace=rec["trace"], memory=harness.memory_peak(),
               peaks=flops.PEAKS.get(dev["kind"]),
               lm={"cfg": cfg, "rows": traffic["batch"], "seq": seq},
               window_flops=3.0 * steps * cells.load(cfg["flops"])(
                   cfg, traffic["batch"], seq, readers_lm.held_per_step(ctx)))
    if kept.get("devices"):
        ctx["kernels"] = trace_lm.reduce(
            kept["devices"], traffic["module_pattern"],
            trace_lm.scope_map(step_text(est, traffic, seq)))
        harness.log(f"kernels {ctx['kernels']}")
    harness.log(f"{calls} calls, {steps} steps in {elapsed:.3f} s; set-up "
                f"{setup_s:.1f} s; peak {ctx['memory']['memory_peak_bytes'] / 1e9:.2f} GB"
                f" on the chip, resident set {host_peak_gb():.1f} GB")

    # free the program's state, then let the reference follow
    feed.close()
    del feed, prog, est, call, kept
    # jax's cache of jitted functions holds the step, the step's closure the
    # estimator, and the estimator its 8.5 GB of state (in a cycle with the
    # model): drop the cache, then collect
    jax.clear_caches()
    gc.collect()
    still = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use", 0)
    harness.log(f"program freed: {still / 1e9:.2f} GB still in use")
    t_ref = time.perf_counter()
    want = reference_steps(cfg, traffic, xs, ys, took, start)
    numbers = fit_numbers(seen, want)
    harness.log(f"reference followed {len(took)} steps and was compared in "
                f"{time.perf_counter() - t_ref:.1f} s; peak resident set "
                f"{host_peak_gb():.1f} GB")
    return {"device": dev, "ctx": ctx, "attempted": steps, "failed": 0,
            "numbers": numbers,
            "seen": seen, "want": want, "start": start,
            "end_to_end": {
                # the rate under the name the traffic file gives it: a later cell
                # whose runs agree more closely can bring a rate, and a bound,
                # of its own
                traffic["rate_metric"]: items / elapsed / cell["chips"],
                "setup_s": setup_s}}
