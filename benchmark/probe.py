#!/usr/bin/env python3
"""Readings for the limits, on the chip, many seeds in one process:

    python3 benchmark/probe.py --workload <cell> --seeds 12 --faults 3 [--first-seed N] [--memory]

For each seed: the numbers the sound program gives against the reference (the
lower reading). For the first `--faults` seeds also the control (the reference
in the next lower precision, put in the program's place) and each fault the
cell can have, planted in the reference put in the program's place. One JSON
line a reading, on standard output and in chiprun_out/. `--memory` also
prints `memory_analysis()` of the first per-step train program the estimator
compiles, to hold beside the run's `memory_stats()`. Not part of a benchmark
run; the limits in benchmark/limits/ are set from what it prints.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def spy_on_memory():
    """Print `memory_analysis()` of the first per-step train program."""
    from analytics_zoo_tpu.engine.estimator import Estimator

    make, seen = Estimator._make_train_step, []

    def spied(self, *a, **k):
        fn = make(self, *a, **k)

        def call(*args):
            if not seen:
                seen.append(fn.lower(*args).compile().memory_analysis())
                print(f"probe: memory_analysis {seen[0]}", file=sys.stderr,
                      flush=True)
            return fn(*args)

        return call

    Estimator._make_train_step = spied


def leaves_of(got, want):
    """Each leaf's difference and gap of norms over the reference's norm."""
    from benchmark import check

    out = {}
    for key in ("first", "change"):
        t = check.leaf_table(got[key], want[key])
        out[key] = {"want": t["want"],
                    "diff": [d / w for d, w in zip(t["diff"], t["want"])],
                    "gap": [abs(g - w) / w
                            for g, w in zip(t["got"], t["want"])]}
    return out


KINDS = ("control_lower_precision", "fault_state_unchanged",
         "fault_half_batch", "fault_answers_swapped")


def fit_readings(cell, seed, kinds, seconds, program=True):
    import jax
    import numpy as np

    from benchmark import check, data, fit
    from benchmark.reference import optim

    cfg, traffic = cell["config"], cell["traffic"]
    fused = traffic["fused"]
    took = fit.steps_taken(traffic)
    x, y = data.rows(cfg, traffic["batch"] * traffic["steps_per_call"],
                     np.random.default_rng(seed))
    if program:
        run = fit.run(cell, seed, seconds, False, time.perf_counter())
        yield "program", dict(run["numbers"], **(
            {} if fused else {"leaves": leaves_of(run["seen"], run["want"])}))
        want = run["want"]
    elif kinds:
        want = fit.reference_steps(cfg, traffic, seed, x, y, took)

    def reading(**kw):
        got = fit.reference_steps(cfg, traffic, seed, x, y, took, **kw)
        if fused:        # a fused path shows its losses only
            return check.fit_numbers({"losses": got["losses"]}, want)
        return dict(check.fit_numbers(got, want), leaves=leaves_of(got, want))

    if "control_lower_precision" in kinds:
        yield "control_lower_precision", reading(
            cast=optim.lower_precision(cfg["compute_dtype"]))

    class Unchanged:
        """A step that returns its state unchanged."""
        def init(self, w):
            return {}

        def step(self, w, g, s):
            return w, s

    if "fault_state_unchanged" in kinds:
        yield "fault_state_unchanged", reading(opt=Unchanged())
    if "fault_half_batch" in kinds:
        yield "fault_half_batch", reading(batch_rows=traffic["batch"] // 2)
    jax.clear_caches()


def callers_readings(cell, seed, kinds, seconds, program=True):
    import numpy as np

    from benchmark import callers
    from benchmark.reference import optim

    cfg, traffic = cell["config"], cell["traffic"]
    run = callers.run(cell, seed, seconds, False, time.perf_counter())
    yield "program", dict(run["numbers"], rows_per_s=run["end_to_end"][
        "serve_rows_per_s"], sampled_rows=sum(r["rows"] for r in run["sample"]))
    if not kinds:
        return
    sample, pool = run["sample"], run["pool"]
    want = callers.reference_answers(cfg, seed, pool, sample,
                                     traffic["reference_row_block"])
    low = callers.reference_answers(
        cfg, seed, pool, sample, traffic["reference_row_block"],
        optim.lower_precision(cfg["compute_dtype"]))
    yield "control_lower_precision", {"prob_gap": callers.prob_gap(low, want)}
    served = np.concatenate([r["answer"] for r in sample])
    # scatter fault: two callers' answers swapped
    swapped = served.copy()
    swapped[[0, -1]] = swapped[[-1, 0]]
    yield "fault_answers_swapped", {"prob_gap": callers.prob_gap(swapped, want)}


def main():
    from benchmark import cells

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--faults", type=int, default=3,
                    help="the first so many seeds also read --kinds")
    ap.add_argument("--kinds", default=",".join(KINDS),
                    help="which of the control and the faults to read")
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=3.0,
                    help="the window of each run of the program")
    ap.add_argument("--memory", action="store_true")
    ap.add_argument("--no-program", action="store_true",
                    help="fit cells: the control and the faults only, which "
                         "need the reference alone")
    args = ap.parse_args()
    cell = cells.resolve(args.workload)
    readings = {"benchmark.fit:run": fit_readings,
                "benchmark.callers:run": callers_readings}[
        cell["traffic"]["driver"]]
    if args.memory:
        spy_on_memory()
    os.makedirs(os.path.join(cells.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(cells.ROOT, "chiprun_out",
                        f"probe_{args.workload}.jsonl")
    with open(path, "a") as out:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            kinds = args.kinds.split(",") if i < args.faults else []
            for kind, numbers in readings(cell, seed, kinds, args.seconds,
                                          not args.no_program):
                leaves = numbers.pop("leaves", None)     # to the file only
                line = {"cell": args.workload, "seed": seed, "kind": kind,
                        "numbers": numbers}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(dict(line, leaves=leaves)) + "\n")
                out.flush()
            print(f"probe: seed {seed} took {time.perf_counter() - t0:.1f} s",
                  file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
