"""Pipeline-parallel bench → BENCH_PIPE.json.

Four experiments over the MPMD stage axis (docs/pipeline-parallel.md),
each an acceptance gate the CI step asserts on:

1. **Training parity matrix**: the same model and global batch trained
   through :meth:`~analytics_zoo_tpu.engine.estimator.Estimator
   .train_pipelined` for every (K stages, M microbatches, schedule)
   cell against the unpipelined K=1 M=1 run. Stage splitting alone
   (M=1) must be **bitwise**; M≥2 re-associates the per-microbatch
   gradient sums and must stay within the documented ULP bound; GPipe
   and 1F1B run the identical per-stage programs in a different order
   over the same fixed fold, so they must be bitwise **each other**.

2. **Stage-split serving**: a StagePlan-attached
   :class:`~analytics_zoo_tpu.inference.inference_model.InferenceModel`
   warmed over a bucket ladder must predict bitwise-identical to the
   unsplit model per bucket, take **zero** executable-cache misses
   after warmup, and populate the AOT cache with one *distinct* entry
   per (bucket, stage) cell — the stage salt in
   :meth:`~analytics_zoo_tpu.inference.aot_cache.AotExecutableCache
   .key_for` is what keeps equal-shaped stages from cross-hitting.

3. **Kill → resume**: a pipelined run (tests/_pipeline_worker.py)
   hard-killed at the ``pipeline_mid_schedule_kill`` chaos site between
   two microbatch schedule events, mid-schedule after its first
   checkpoint committed; the restarted run must finish with final
   params bitwise-identical to an uninterrupted reference run's.

4. **Bubble fractions**: the analytic cost model
   (:func:`~analytics_zoo_tpu.pipeline.schedule.bubble_fraction`) must
   put 1F1B strictly below naive fill/drain GPipe at every K≥2 cell
   with ≥4 microbatches under the equal activation-slot budget
   (min(K, M) slots per stage) both schedules run with.

CPU-only by construction: the kill → resume worker is a forced-host-device
CPU process, so the script pins ``JAX_PLATFORMS=cpu`` itself; it checks
parity and counts, never device time.

::

    python scripts/pipeline_bench.py --smoke
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile

# CPU-only by construction (see the module docstring): pinned before jax
# is imported, and inherited by every worker this script spawns.
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402

#: M≥2 folds the per-microbatch gradient sums in a different
#: association than the single fused step; measured divergence on the
#: parity model is ≤14 ULP (docs/pipeline-parallel.md "Parity") — 64
#: leaves headroom without ever hiding a real defect.
ULP_BOUND = 64


# ---------------------------------------------------------------------------
# 1: training parity matrix
# ---------------------------------------------------------------------------


def _make_estimator():
    import optax

    from analytics_zoo_tpu.common.nncontext import get_nncontext
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    get_nncontext().set_rng_state(123, 0)
    model = Sequential([
        Dense(8, activation="relu", input_shape=(4,), name="d1"),
        Dense(8, activation="relu", name="d2"),
        Dense(2, name="d3"),
    ])
    return Estimator(model, optax.adam(1e-2))


class _ArrayDS:
    """Deterministic in-memory dataset with the batches() protocol."""

    def __init__(self, n: int = 64):
        import numpy as np

        r = np.random.RandomState(0)
        self.x = r.randn(n, 4).astype(np.float32)
        self.y = r.randn(n, 2).astype(np.float32)

    def batches(self, batch_size, shuffle=True, seed=0, start_step=0):
        import numpy as np

        idx = (np.random.RandomState(seed).permutation(len(self.x))
               if shuffle else np.arange(len(self.x)))
        for i in range(start_step, len(self.x) // batch_size):
            sl = idx[i * batch_size:(i + 1) * batch_size]
            yield self.x[sl], self.y[sl]


def _train_cell(num_stages: int, num_microbatches: int, mode: str):
    """(final loss, flat param vector) for one pipelined run."""
    import jax
    import numpy as np

    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.pipeline import StagePlan

    def mse(y, pred):
        import jax.numpy as jnp

        return jnp.mean((y - pred) ** 2)

    rules = {1: ((r".", 0),),
             2: ((r"^d1$", 0), (r".", 1)),
             3: ((r"^d1$", 0), (r"^d2$", 1), (r".", 2))}[num_stages]
    est = _make_estimator()
    est.train_pipelined(_ArrayDS(), mse, StagePlan(num_stages, rules=rules),
                        num_microbatches=num_microbatches, schedule=mode,
                        end_trigger=MaxIteration(4), batch_size=16)
    flat = jax.tree_util.tree_leaves(jax.device_get(est.tstate.params))
    return (est.run_state.loss,
            np.concatenate([np.asarray(a).ravel() for a in flat]))


def _max_ulp(a, b) -> int:
    import numpy as np

    if np.array_equal(a, b):
        return 0
    ia = a.view(np.int32).astype(np.int64)
    ib = b.view(np.int32).astype(np.int64)
    return int(np.max(np.abs(ia - ib)))


def bench_train_parity():
    import numpy as np

    base_loss, base = _train_cell(1, 1, "1f1b")
    cells = []
    by_cell = {}
    for num_stages, num_microbatches, mode in [
            (2, 1, "1f1b"), (3, 1, "1f1b"),
            (2, 2, "1f1b"), (2, 2, "gpipe"),
            (3, 4, "1f1b"), (3, 4, "gpipe")]:
        loss, params = _train_cell(num_stages, num_microbatches, mode)
        ulp = _max_ulp(base, params)
        cell = {"stages": num_stages, "microbatches": num_microbatches,
                "schedule": mode, "loss": loss,
                "bitwise_vs_unpipelined": bool(np.array_equal(base, params)),
                "max_ulp_vs_unpipelined": ulp}
        cells.append(cell)
        by_cell[(num_stages, num_microbatches, mode)] = params
        print(f"[train] K={num_stages} M={num_microbatches} {mode}: "
              f"bitwise={cell['bitwise_vs_unpipelined']} max_ulp={ulp}")
        if num_microbatches == 1:
            assert cell["bitwise_vs_unpipelined"], cell
        assert ulp <= ULP_BOUND, cell
    schedules_bitwise = all(
        np.array_equal(by_cell[(k, m, "1f1b")], by_cell[(k, m, "gpipe")])
        for k, m in [(2, 2), (3, 4)])
    assert schedules_bitwise
    return {
        "base_loss": base_loss,
        "cells": cells,
        "bitwise_at_m1": True,
        "ulp_bound": ULP_BOUND,
        "max_ulp": max(c["max_ulp_vs_unpipelined"] for c in cells),
        "gpipe_bitwise_vs_1f1b": schedules_bitwise,
        # the headline acceptance bit: every M=1 cell bitwise, every
        # M≥2 cell inside the documented bound, schedules bitwise
        "parity_ok": True,
    }


# ---------------------------------------------------------------------------
# 2: stage-split serving
# ---------------------------------------------------------------------------


def bench_serving(workdir: str):
    import numpy as np

    from analytics_zoo_tpu.inference.aot_cache import AotExecutableCache
    from analytics_zoo_tpu.inference.inference_model import InferenceModel
    from analytics_zoo_tpu.pipeline import StagePlan

    buckets = (4, 16)
    num_stages = 2
    est = _make_estimator()
    net = est.model
    rng = np.random.default_rng(3)

    ref = InferenceModel().do_load_keras(net)
    cache_dir = os.path.join(workdir, "aot")
    staged = InferenceModel(aot_cache_dir=cache_dir).do_load_keras(net)
    staged.set_stage_plan(
        StagePlan(num_stages, rules=((r"^d1$", 0), (r".", 1))))
    for b in buckets:
        staged.do_optimize(np.zeros((b, 4), np.float32))
    stats0 = dict(staged.cache_stats)

    per_bucket = []
    for b in buckets:
        x = rng.normal(size=(b, 4)).astype(np.float32)
        bitwise = bool(np.array_equal(np.asarray(ref.do_predict(x)),
                                      np.asarray(staged.do_predict(x))))
        per_bucket.append({"bucket": b, "bitwise": bitwise})
        assert bitwise, per_bucket[-1]
    post_warm_misses = staged.cache_stats["misses"] - stats0["misses"]
    assert post_warm_misses == 0, staged.cache_stats

    entries = AotExecutableCache(cache_dir).entries()
    keys = {e["key"] for e in entries}
    stage_cells = sorted(
        ((e["meta"] or {}).get("args"), (e["meta"] or {}).get("stage"))
        for e in entries)
    # one distinct key per (bucket, stage) — equal-shaped stages must
    # not collapse onto one entry (that would be a cross-hit)
    no_cross_hits = len(keys) == len(buckets) * num_stages
    assert no_cross_hits, stage_cells
    print(f"[serving] buckets={buckets} stages={num_stages}: bitwise per "
          f"bucket, {post_warm_misses} post-warmup misses, "
          f"{len(keys)} distinct AOT entries")
    return {
        "buckets": list(buckets),
        "stages": num_stages,
        "per_bucket": per_bucket,
        "parity_bitwise": all(c["bitwise"] for c in per_bucket),
        "post_warmup_misses": int(post_warm_misses),
        "aot_entries": len(entries),
        "aot_distinct_keys": len(keys),
        "no_aot_cross_hits": no_cross_hits,
        "cache_stats": dict(staged.cache_stats),
    }


# ---------------------------------------------------------------------------
# 3: kill → resume through the pipeline chaos site
# ---------------------------------------------------------------------------

#: default worker config (K=2, M=2, 2 epochs × 2 steps of 6 schedule
#: events each) fires the chaos site 24 times; skipping 14 lands the
#: kill mid-schedule in step 3, after the iteration-2 checkpoint
#: committed — resume has real work left to redo.
_KILL_SKIP = 14


def _run_worker(ckpt_dir: str, out_path: str, chaos: bool):
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    for k in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP"):
        env.pop(k, None)
    if chaos:
        env["AZOO_FT_CHAOS"] = "pipeline_mid_schedule_kill"
        env["AZOO_FT_CHAOS_SKIP"] = str(_KILL_SKIP)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_pipeline_worker.py"),
         ckpt_dir, out_path],
        env=env, capture_output=True, text=True, timeout=300)
    doc = None
    if os.path.isfile(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    return proc.returncode, doc, proc.stderr[-2000:]


def bench_kill_resume(workdir: str):
    from analytics_zoo_tpu.ft import atomic, chaos as chaos_mod

    ref_rc, ref_doc, err = _run_worker(
        os.path.join(workdir, "ck_ref"),
        os.path.join(workdir, "ref.json"), chaos=False)
    assert ref_rc == 0 and ref_doc is not None, (ref_rc, err)

    kill_ck = os.path.join(workdir, "ck_kill")
    kill_rc, _doc, err = _run_worker(
        kill_ck, os.path.join(workdir, "kill.json"), chaos=True)
    assert kill_rc == chaos_mod.EXIT_CODE, (kill_rc, err)
    committed = [s for s, _ in atomic.committed_checkpoints(kill_ck)]
    for _s, path in atomic.committed_checkpoints(kill_ck):
        atomic.verify_checksums(path)

    res_rc, res_doc, err = _run_worker(
        kill_ck, os.path.join(workdir, "resume.json"), chaos=False)
    assert res_rc == 0 and res_doc is not None, (res_rc, err)
    bitwise = res_doc["params"] == ref_doc["params"]
    assert bitwise
    print(f"[kill_resume] victim rc={kill_rc}, committed after kill: "
          f"{committed}, resumed bitwise: {bitwise}")
    return {
        "chaos_point": "pipeline_mid_schedule_kill",
        "chaos_skip": _KILL_SKIP,
        "victim_rc": kill_rc,
        "committed_steps_after_kill": committed,
        "resume_iteration": res_doc["iteration"],
        "bitwise_identical_to_reference": bitwise,
    }


# ---------------------------------------------------------------------------
# 4: analytic bubble fractions
# ---------------------------------------------------------------------------


def bench_bubble():
    from analytics_zoo_tpu.pipeline import bubble_fraction

    cells = []
    for num_stages in (2, 3, 4):
        for num_microbatches in (4, 8):
            b1 = bubble_fraction(num_stages, num_microbatches, "1f1b")
            bg = bubble_fraction(num_stages, num_microbatches, "gpipe")
            cells.append({"stages": num_stages,
                          "microbatches": num_microbatches,
                          "bubble_1f1b": round(b1, 4),
                          "bubble_gpipe": round(bg, 4),
                          "strictly_better": b1 < bg})
            print(f"[bubble] K={num_stages} M={num_microbatches}: "
                  f"1f1b={b1:.4f} gpipe={bg:.4f}")
            assert b1 < bg, cells[-1]
    return {"cells": cells,
            "one_f_one_b_strictly_below_gpipe": True,
            "slot_budget": "min(K, M) per stage (equal for both modes)"}


# ---------------------------------------------------------------------------


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    parser.add_argument("--smoke", action="store_true",
                        help="CI-sized run (the full matrix is already "
                        "CPU-minutes small; --smoke is the gate's spelling)")
    parser.add_argument("--out", default=os.path.join(REPO,
                                                      "BENCH_PIPE.json"))
    args = parser.parse_args(argv)

    report = {"bench": "pipeline", "mode": "smoke" if args.smoke else "full",
              "device": device_info()}
    with tempfile.TemporaryDirectory(prefix="pipe_bench_") as workdir:
        report["train_parity"] = bench_train_parity()
        report["serving"] = bench_serving(workdir)
        report["kill_resume"] = bench_kill_resume(workdir)
        report["bubble"] = bench_bubble()

    # the four acceptance gates, spelled out for the CI assert
    report["gates"] = {
        "train_parity_ok": report["train_parity"]["parity_ok"],
        "serving_bitwise_zero_recompiles":
            report["serving"]["parity_bitwise"]
            and report["serving"]["post_warmup_misses"] == 0
            and report["serving"]["no_aot_cross_hits"],
        "kill_resume_bitwise":
            report["kill_resume"]["bitwise_identical_to_reference"],
        "bubble_1f1b_below_gpipe":
            report["bubble"]["one_f_one_b_strictly_below_gpipe"],
    }
    assert all(report["gates"].values()), report["gates"]
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
