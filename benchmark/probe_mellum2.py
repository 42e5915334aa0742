#!/usr/bin/env python3
"""Readings for the limits of a `mellum` cell that a run does not take, on
the chip:

    python3 benchmark/probe_mellum2.py --workload <cell> --seeds 1 [--first-seed N]

For each seed, the plain reference alone from the seed's weights over the
seed's rows: the sound one (what a run compares the program with), the
control (its products in the next lower precision) and two faults of what
this family adds, each the same weights under another function: the full
layers' rotary left plain (`fault_no_yarn`: their section's `rope_type`
`default`), and the balance term left out (`fault_no_balance`:
`router_aux_loss_coef` 0). Control and faults go through `check.verdict`
against the sound reference under the cell's limits file, with
`fit_mellum2.fit_numbers`; the sound program's own readings are in every
run's result line (`compared`). One JSON line a reading on standard output;
exit code 1 if the control reads `correct`. Not part of a benchmark run; the
limits in benchmark/limits/ are set from what it prints."""

import argparse
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def faults(cfg: dict) -> dict:
    rope = dict(cfg["rope_parameters"])
    rope["full_attention"] = {"rope_type": "default",
                              "rope_theta": rope["full_attention"]["rope_theta"]}
    return {"fault_no_yarn": dict(cfg, rope_parameters=rope),
            "fault_no_balance": dict(cfg, router_aux_loss_coef=0.0)}


def readings(cell, seed):
    import jax

    from benchmark import fit, fit_lm, fit_mellum2, models

    cfg, traffic = cell["config"], cell["traffic"]
    xs, ys = fit_lm.row_sets(cfg, traffic, seed)
    took = fit.steps_taken(traffic)
    start = jax.device_get(models.reference_weights(cfg, seed))
    want = fit_lm.reference_steps(cfg, traffic, xs, ys, took, start)
    yield "reference", {"losses": want["losses"]}
    low = fit_lm.reference_steps(cfg, traffic, xs, ys, took, start,
                                 fit_lm.lower_precision(cfg["compute_dtype"]))
    yield "control_lower_precision", fit_mellum2.fit_numbers(low, want, cfg)
    del low
    gc.collect()
    for kind, off_cfg in faults(cfg).items():
        off = fit_lm.reference_steps(off_cfg, traffic, xs, ys, took, start)
        yield kind, fit_mellum2.fit_numbers(off, want, cfg)
        del off
        gc.collect()


def main():
    from benchmark import cells, check, harness

    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=1)
    ap.add_argument("--first-seed", type=int, default=3_000_000_001)
    args = ap.parse_args()
    cell = cells.resolve(args.workload)
    harness.device(cell["chips"])
    wrong = 0
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        for kind, numbers in readings(cell, seed):
            line = {"cell": args.workload, "seed": seed, "kind": kind,
                    "numbers": numbers}
            if kind != "reference":
                ok, compared = check.verdict(numbers, cell["limits"])
                wrong += ok and kind.startswith("control")
                line.update(correct=ok, over=sorted(
                    k for k, (v, lim) in compared.items()
                    if v is None or not v <= lim))
            print(json.dumps(line), flush=True)
        print(f"probe: seed {seed} took {time.perf_counter() - t0:.1f} s",
              file=sys.stderr, flush=True)
    sys.exit(1 if wrong else 0)


if __name__ == "__main__":
    main()
