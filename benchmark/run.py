#!/usr/bin/env python3
"""The benchmark's one entry:

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of BENCHMARK.json on the chips it asks for and prints, as the
last line of standard output, one JSON object (see benchmark/README.md).
Exits non-zero and prints no result when JAX finds no TPU.
"""

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    from benchmark import cells, harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    try:
        run = cells.load(cell["traffic"]["driver"])(
            cell, args.seed, args.seconds, bool(args.trace), T_START)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 1
    harness.emit(harness.result_line(cell, run["device"], run,
                                     bool(args.trace)))
    return 0


if __name__ == "__main__":
    sys.exit(main())
