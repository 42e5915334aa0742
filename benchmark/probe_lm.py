#!/usr/bin/env python3
"""Readings for the limits of a language-model cell, on the chip, several
seeds in one process (`probe.py` knows the drivers of `fit.py` and
`callers.py` only):

    python3 benchmark/probe_lm.py --workload <cell> --seeds 6 --faults 3 [--first-seed N] [--no-half-batch]

For each seed: the numbers the sound program gives against the reference. For
the first `--faults` seeds also the control (the reference with its products
in the next lower precision, put in the program's place), a state left
unchanged, one leaf left unmoved and half a batch. Every reading goes through
`check.verdict` with the cell's limits file, as a run's does: `correct` and
the limits it passed (`over`) are in its line, and the exit code is 1 if a
sound reading is not correct or a control or a fault is. One JSON line a
reading, on standard output and in chiprun_out/. A seed with its faults holds a dozen trees of 2.8 GB on the
host at its fullest: on a 40 GiB machine give such seeds a process each. Not
part of a benchmark run; the limits in benchmark/limits/ are set from what it
prints.
"""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def readings(cell, seed, faults: bool, seconds: float, half: bool = True):
    import jax
    import numpy as np

    from benchmark import fit, fit_lm

    cfg, traffic = cell["config"], cell["traffic"]
    run = fit_lm.run(cell, seed, seconds, False, time.perf_counter())
    yield "program", dict(
        run["numbers"], losses=run["seen"]["losses"],
        reference_losses=run["want"]["losses"],
        items_per_s=run["end_to_end"][traffic["rate_metric"]],
        memory_peak_bytes=run["ctx"]["memory"]["memory_peak_bytes"])
    if not faults:
        return
    # each kept tree is 2.8 GB on the host: let go of whatever is done with
    want, start, took = run["want"], run["start"], fit.steps_taken(traffic)
    # one tensor the program never moved (the first expert layer's w_down,
    # 134 MB of 2.8 GB): nought for the reading, then put back
    seen = run["seen"]
    leaf = next(p for p in seen["change"]["layers"] if "experts" in p)[
        "experts"]["w_down"]
    moved = leaf.copy()
    leaf[...] = 0
    yield "fault_one_leaf_unmoved", fit_lm.fit_numbers(seen, want)
    leaf[...] = moved
    del run, seen, leaf, moved
    gc.collect()
    xs, ys = fit_lm.row_sets(cfg, traffic, seed)
    low = fit_lm.reference_steps(cfg, traffic, xs, ys, took, start,
                                 fit_lm.lower_precision(cfg["compute_dtype"]))
    yield "control_lower_precision", fit_lm.fit_numbers(low, want)
    del low
    gc.collect()
    still = dict(want, change=jax.tree_util.tree_map(np.zeros_like,
                                                     want["change"]))
    yield "fault_state_unchanged", fit_lm.fit_numbers(still, want)
    del still
    if not half:
        return
    half = fit_lm.reference_steps(cfg, traffic, xs, ys, took, start,
                                  batch_rows=traffic["batch"] // 2)
    yield "fault_half_batch", fit_lm.fit_numbers(half, want)


def main():
    from benchmark import cells, check

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=6)
    ap.add_argument("--faults", type=int, default=3)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--no-half-batch", action="store_true",
                    help="leave the half-batch fault out (it costs a "
                         "reference run)")
    args = ap.parse_args()
    cell = cells.resolve(args.workload)
    os.makedirs(os.path.join(cells.ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(cells.ROOT, "chiprun_out",
                        f"probe_{args.workload}.jsonl")
    wrong = 0
    with open(path, "a") as out:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            t0 = time.perf_counter()
            for kind, numbers in readings(cell, seed, i < args.faults,
                                          args.seconds,
                                          not args.no_half_batch):
                ok, compared = check.verdict(numbers, cell["limits"])
                wrong += ok != (kind == "program")
                line = {"cell": args.workload, "seed": seed, "kind": kind,
                        "correct": ok, "over": sorted(
                            k for k, (v, lim) in compared.items()
                            if v is None or not v <= lim),
                        "numbers": numbers}
                print(json.dumps(line), flush=True)
                out.write(json.dumps(line) + "\n")
                out.flush()
            print(f"probe: seed {seed} took {time.perf_counter() - t0:.1f} s",
                  file=sys.stderr, flush=True)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
