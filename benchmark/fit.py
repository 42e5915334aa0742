"""The `fit` driver: whole `Estimator.train` calls over a cached feature set.

Set-up builds one estimator with seeded weights, drives it through its first
steps by the window's own call and feed (which also compiles and warms every
shape), keeps what those steps showed, and hands the same estimator to the
window. After the window, with the program's state freed, the plain reference
follows the same first steps from the same seed.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchmark import cells, check, data, flops, harness, models
from benchmark.reference import optim


class LossTape:
    """Stands where the program's TensorBoard writer would: keeps each
    step's loss as `Estimator.train` drains it."""

    def __init__(self):
        self.losses = {}

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.losses[int(step)] = float(value)


# A traffic file names its cached set under `feature_set` and the row order
# of that set's epochs under `epoch_order`, each as `module:function`.

def hbm_set(traffic: dict, x, y):
    """The whole set in HBM; the step gathers its rows on the device."""
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet

    return ArrayFeatureSet(x, y).cache_device()


def hostfed_set(traffic: dict, x, y):
    """The set in host DRAM behind the native prefetcher."""
    import jax.numpy as jnp
    from analytics_zoo_tpu.data.pmem import cached_feature_set

    fs = cached_feature_set(x, y, memory_type="DRAM")
    if traffic.get("uint8_pixels"):
        # pixels cross the host link as bytes; the step scales them
        fs.device_transform = lambda v: (v.astype(jnp.float32) - 127.5) / 127.5
    return fs


def device_order(epoch: int, n: int) -> np.ndarray:
    """The device cache permutes in the step from `PRNGKey(epoch)`."""
    import jax

    return np.asarray(jax.random.permutation(jax.random.PRNGKey(epoch), n))


def numpy_order(epoch: int, n: int) -> np.ndarray:
    """The host caches shuffle with numpy's generator seeded by the epoch."""
    order = np.arange(n, dtype=np.uint64)
    np.random.default_rng(epoch).shuffle(order)
    return order.astype(np.int64)


def steps_taken(traffic: dict) -> list:
    """The (epoch, batch) that each of the first `check_steps` steps takes. A
    fused path runs them as the first steps of epoch 0. A per-step path is
    stopped by `MaxIteration`, which closes the epoch: step 1 is batch 0 of
    epoch 0, the others are the first batches of epoch 1."""
    steps = traffic["check_steps"]
    if traffic["fused"]:
        return [(0, k) for k in range(steps)]
    return [(0, 0)] + [(1, k) for k in range(steps - 1)]


def first_gradient(opt_state):
    """The first gradient as the optimizer got it, from its state after one
    step: momentum's trace is that gradient."""
    import jax

    for node in jax.tree_util.tree_leaves(
            opt_state, is_leaf=lambda n: hasattr(n, "trace")):
        if hasattr(node, "trace"):
            return node.trace
    raise ValueError("no momentum trace in the optimizer's state: reading "
                     "the first gradient of another optimizer is not written")


def first_steps(prog, fs, traffic: dict, tape: LossTape) -> dict:
    """Drive the estimator through its first steps with the window's own
    call. A per-step path stops after step 1 and after step `check_steps` and
    shows its state there; a fused path runs its first whole call and shows
    the losses. Returns what was seen."""
    import jax
    from analytics_zoo_tpu.engine.triggers import MaxEpoch, MaxIteration

    est, batch = prog.est, traffic["batch"]
    steps = traffic["check_steps"]
    if traffic["fused"]:                         # one dispatch an epoch
        est.train(fs, prog.criterion, end_trigger=MaxEpoch(1), batch_size=batch)
        jax.block_until_ready(est.tstate)
        return {"losses": [tape.losses[k + 1] for k in range(steps)]}

    def kept(tree):      # on the host, in the reference's layout
        return jax.device_get(prog.to_reference_layout(tree))

    start = jax.tree_util.tree_map(lambda a: a.copy(), est.tstate.params)
    est.train(fs, prog.criterion, end_trigger=MaxIteration(1), batch_size=batch)
    first = kept(first_gradient(est.tstate.opt_state))
    est.train(fs, prog.criterion, end_trigger=MaxIteration(steps),
              batch_size=batch)
    change = kept(jax.tree_util.tree_map(lambda a, b: a - b,
                                         est.tstate.params, start))
    del start
    return {"losses": [tape.losses[k + 1] for k in range(steps)],
            "first": first, "change": change}


def reference_steps(cfg, traffic, seed, x, y, took, cast=lambda t: t,
                    opt=None, batch_rows=None) -> dict:
    """The plain reference through the same steps from the same seed."""
    import jax
    import jax.numpy as jnp

    ref, batch, n = models.reference(cfg), traffic["batch"], len(y)
    w0 = models.reference_weights(cfg, seed)
    batches = []
    order = cells.load(traffic["epoch_order"])
    for epoch, k in took:
        idx = order(epoch, n)[k * batch:(k + 1) * batch][:batch_rows]
        batches.append((jax.tree_util.tree_map(jnp.asarray, data.take(x, idx)),
                        jnp.asarray(y[idx])))
    spec = cfg["optimizer"]
    opt = opt or cells.load(spec["reference"])(**spec["args"])
    losses, first, w = optim.follow(ref.row_losses, w0, batches, opt, cfg,
                                    cast, traffic["reference_row_block"])
    change = jax.tree_util.tree_map(lambda a, b: a - b, w, w0)
    return {"losses": losses, "first": jax.device_get(first),
            "change": jax.device_get(change)}


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    import jax
    from analytics_zoo_tpu.engine.triggers import MaxEpoch

    import analytics_zoo_tpu as zoo

    cfg, traffic = cell["config"], cell["traffic"]
    dev = harness.device(cell["chips"], any_platform)
    zoo.init_nncontext()
    rng = np.random.default_rng(seed)
    x, y = data.rows(cfg, traffic["batch"] * traffic["steps_per_call"], rng)
    fs = cells.load(traffic["feature_set"])(traffic, x, y)
    prog = models.Program(cfg, seed)
    est, tape = prog.est, LossTape()
    est.train_summary = tape
    seen = first_steps(prog, fs, traffic, tape)
    took = steps_taken(traffic)

    spans = harness.Spans()

    def call():
        with spans("bench.train_call"):
            est.train(fs, prog.criterion, batch_size=traffic["batch"],
                      end_trigger=MaxEpoch(est.run_state.epoch + 1))
            jax.block_until_ready(est.tstate)

    call()                                     # the window's call, warm
    est.train_summary = None
    ctx = {"counters": {"setup_end": harness.counters()}, "series": {}}
    if traced:
        seconds = min(seconds, traffic["trace_seconds"])
    rec, calls = {}, 0
    with harness.window(traced, cell["name"], rec, traffic["module_pattern"],
                        spans, warm=call):
        ctx["counters"]["window_start"] = harness.counters()
        t0 = time.perf_counter()
        setup_s = t0 - t_start
        while time.perf_counter() - t0 < seconds:
            call()
            calls += 1
        elapsed = time.perf_counter() - t0
        ctx["counters"]["window_end"] = harness.counters()
    steps = calls * traffic["steps_per_call"]
    items = steps * traffic["batch"] * traffic.get("items_per_row", 1)
    ctx.update(trace=rec["trace"], memory=harness.memory_peak(),
               peaks=flops.PEAKS.get(dev["kind"]),
               window_flops=3.0 * steps * cells.load(cfg["flops"])(
                   cfg, traffic["batch"], cfg.get("seq_len", 0)))
    harness.log(f"{calls} calls, {steps} steps in {elapsed:.3f} s; set-up "
                f"{setup_s:.1f} s; peak {ctx['memory']['memory_peak_bytes'] / 1e9:.2f} GB")

    # free the program's state, then let the reference follow
    if hasattr(fs, "close"):
        fs.close()
    del fs, prog, est, call
    gc.collect()
    t_ref = time.perf_counter()
    want = reference_steps(cfg, traffic, seed, x, y, took)
    harness.log(f"reference followed {len(took)} steps in "
                f"{time.perf_counter() - t_ref:.1f} s")
    return {"device": dev, "ctx": ctx, "attempted": steps, "failed": 0,
            "numbers": check.fit_numbers(seen, want),
            "seen": seen, "want": want,
            "end_to_end": {
                "train_items_per_s_per_chip": items / elapsed / cell["chips"],
                "setup_s": setup_s}}
