#!/usr/bin/env python3
"""A cell's run with the program's own tracer on or off, for the builder:

    python3 benchmark/span_gaps.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1> --tracer <0|1>

`benchmark/run.py` never loads this file and no number of the benchmark comes
from it. With `--trace 1` the spans that `Estimator.train` retires
(`train.call`, `train.epoch`, `train.fill`, `train.infeed_wait`,
`train.dispatch`, `train.drain`, ...) are handed to `trace.reduce` beside the
benchmark's own, through the tracer's wall-clock export, so the window's idle
gaps are listed by the program's spans. With `--trace 0` the run is the cell's
end-to-end run, whose items/s with `--tracer 1` against `--tracer 0` is what
tracing costs. Prints one JSON line: the result line of `run.py` plus
`program_spans` (count and seconds by name inside the window's calls) and
`spans_per_s`. `--export <path>` also writes the tracer's ring as Chrome
trace-event JSON (Perfetto), every span of every call.
"""

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import os            # noqa: E402
import sys           # noqa: E402
from collections import defaultdict  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# the infeed thread's spans run beside the loop's: they say what that thread
# did, not what the loop was doing while the device stood idle
LOOP_SPANS = "train."


class _WithProgramSpans(list):
    """The benchmark's spans, and after them the loop's own from the tracer.
    An epoch's first wait lies inside its `train.fill`: it is left out, so
    that idle time there is listed under the fill and `train.infeed_wait`
    keeps the waits of the steps after it."""

    def __iter__(self):
        from analytics_zoo_tpu.common.observability import get_tracer

        yield from list.__iter__(self)
        loop = [s for s in get_tracer().wall_spans_ns()
                if s[0].startswith(LOOP_SPANS)]
        fills = [(s, e) for n, s, e in loop if n == "train.fill"]
        for name, start, end in loop:
            if name != "train.infeed_wait" or not any(
                    s <= start and end <= e for s, e in fills):
                yield name, start, end


def main(argv=None, root=None, any_platform=False) -> int:
    """`root` and `any_platform` are for the tests, which drive a tiny cell
    of a temporary benchmark on the CPU."""
    from benchmark import cells, harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--tracer", type=int, choices=(0, 1), default=1)
    ap.add_argument("--export", default=None)
    args = ap.parse_args(argv)

    from analytics_zoo_tpu.common.observability import get_tracer

    made, plain = [], harness.Spans

    class ProgramSpans(plain):
        """`harness.Spans` whose `events` adds the tracer's."""

        def __init__(self):
            self.events = _WithProgramSpans()
            made.append(self)

    harness.Spans = ProgramSpans          # the driver asks the module for it
    tracer = get_tracer()
    if args.tracer:
        tracer.enable()
    cell = cells.resolve(args.workload, root or cells.ROOT)
    try:
        run = cells.load(cell["traffic"]["driver"])(
            cell, args.seed, args.seconds, bool(args.trace), T_START,
            any_platform=any_platform)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 1
    finally:
        tracer.disable()
        harness.Spans = plain
    line = harness.result_line(cell, run["device"], run, bool(args.trace))
    if args.export:
        tracer.export_chrome_trace(args.export)

    # the window's calls are the last the benchmark wrapped
    per_call = cell["traffic"].get("steps_per_call", 1)
    calls = [e for e in list.__iter__(made[0].events)
             if e[0] == "bench.train_call"][-(run["attempted"] // per_call):]
    lo, hi = calls[0][1], calls[-1][2]
    by_name, inside = defaultdict(lambda: [0, 0.0]), 0
    for name, start, end in tracer.wall_spans_ns():
        if lo <= start and end <= hi:
            inside += 1
            by_name[name][0] += 1
            by_name[name][1] += (end - start) / 1e9
    line["tracer"] = bool(args.tracer)
    line["calls_s"] = (hi - lo) / 1e9
    line["spans_per_s"] = inside / ((hi - lo) / 1e9)
    line["program_spans"] = {k: {"count": c, "seconds": s}
                             for k, (c, s) in sorted(by_name.items())}
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
