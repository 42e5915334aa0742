"""From a `jax.profiler` trace (`*.xplane.pb`) to busy time, idle gaps, time
per compiled module and the heaviest operations.

Read with `jax.profiler.ProfileData`, nothing else. A device plane is named
`/device:TPU:<n>`; its line `XLA Ops` holds one event per executed operation
(exclusive device time) and `XLA Modules` one per executed program. Starts are
nanoseconds from the session's start. The host's side comes from the
benchmark's own spans (`harness.Spans`), brought onto the trace's clock by
`clock_offset`: the profiler's host tracer stays off (PERF.md, section 6).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = "/device:TPU:"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def newest_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not found:
        raise FileNotFoundError(f"no *.xplane.pb under {log_dir}")
    return found[-1]


def load(path: str) -> dict:
    """`{plane: {"ops": [(name, start, end)], "modules": [...]}}` of the
    device planes, times in ns."""
    from jax.profiler import ProfileData

    out = {}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PLANE):
            dev = {"ops": [], "modules": []}
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(line.name)
                if key:
                    dev[key] = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                                for e in line.events]
            out[plane.name] = dev
    return out


def busy_union(events) -> list:
    """Merged, sorted `[start, end]` intervals covered by `events`."""
    merged = []
    for _, start, end in sorted(events, key=lambda e: e[1]):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    return merged


def self_times(events) -> list:
    """`(name, seconds)` of each event's exclusive time: its duration less
    that of the events nested in it (a `while` holds its body's operations on
    the same line)."""
    out, stack = [], []      # stack of [name, end, self_ns]
    for name, start, end in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and stack[-1][1] <= start:
            done = stack.pop()
            out.append((done[0], done[2] / 1e9))
        if stack:
            stack[-1][2] -= min(end, stack[-1][1]) - start
        stack.append([name, end, end - start])
    out += [(n, t / 1e9) for n, _, t in stack]
    return out


def gaps(merged, lo: int, hi: int) -> list:
    """`(start, end)` of every stretch of `[lo, hi]` that `merged` leaves
    uncovered."""
    out, at = [], lo
    for start, end in merged:
        if end <= lo:
            continue
        if start >= hi:
            break
        if start > at:
            out.append((at, start))
        at = max(at, end)
    if at < hi:
        out.append((at, hi))
    return out


CLOCK_MARK = "bench_clock_mark"


def clock_offset(devices: dict, stamps) -> int:
    """Nanoseconds to add to a host `time.time_ns()` stamp to get the trace's
    time. The trace counts from its session's start and the host tracer is
    off, so the clocks are tied by marker programs: `stamps[i]` is the host
    time at which the i-th program named `CLOCK_MARK` was dispatched, and a
    program cannot start before its dispatch, so the least (device start -
    stamp) is the offset plus the quickest dispatch (some 0.1 ms)."""
    starts = sorted(s for dev in list(devices.values())[:1]
                    for n, s, _ in dev["modules"] if CLOCK_MARK in n)
    if not starts or len(starts) != len(stamps):
        raise ValueError(f"{len(starts)} clock marks in the trace for "
                         f"{len(stamps)} dispatched")
    return int(min(d - t for d, t in zip(starts, stamps)))


def _window(host, span: str):
    """The traced window: the host span named `span` (the benchmark wraps its
    whole measured window in one)."""
    marks = [(s, e) for n, s, e in host if n == span]
    return min(s for s, _ in marks), max(e for _, e in marks)


def _span_at(host, t: int) -> str:
    """The innermost `bench.*` span (not the window's own) that covers `t`."""
    best = None
    for name, start, end in host:
        if start <= t < end and name != "bench.window":
            if best is None or end - start < best[1]:
                best = (name, end - start)
    return best[0] if best else "_no_bench_span_"


def _short(name: str) -> str:
    return re.sub(r"[^A-Za-z0-9_.:-]+", "_", name)[:64]


def reduce(devices: dict, host: list, module_pattern: str,
           min_gap_ns: int = 20_000) -> dict:
    """Everything the per-layer readers take from a trace: `devices` as
    `load` gives them, `host` = the benchmark's spans on the trace's clock.

    busy_s / window_s: union of `XLA Ops` clipped to the window, averaged
    over the device planes. module_s / module_calls: device time and number of
    `XLA Modules` events whose name matches `module_pattern`. device_ops: the
    ten operations with most exclusive time (digits in a name's numeric suffix
    kept: two fusions are two operations). idle_gaps: idle time by the host
    span its middle falls in; gaps under `min_gap_ns` pooled as
    `_shorter_gaps_`."""
    lo, hi = _window(host, "bench.window")
    pattern = re.compile(module_pattern)
    busy, module_s, module_calls, all_modules = [], 0.0, 0, 0
    op_time, gap_time = defaultdict(float), defaultdict(float)
    mod_time = defaultdict(float)
    for dev in devices.values():
        ops = [(n, max(s, lo), min(e, hi)) for n, s, e in dev["ops"]
               if e > lo and s < hi]
        merged = busy_union(ops)
        busy.append(sum(e - s for s, e in merged))
        for name, seconds in self_times(ops):
            op_time[_short(name)] += seconds
        for s, e in gaps(merged, lo, hi):
            key = (_span_at(host, (s + e) // 2)
                   if e - s >= min_gap_ns else "_shorter_gaps_")
            gap_time[key] += (e - s) / 1e9
        for name, s, e in dev["modules"]:
            if e > lo and s < hi:
                all_modules += 1
                mod_time[_short(name)] += (min(e, hi) - max(s, lo)) / 1e9
                if pattern.search(name):
                    module_s += (min(e, hi) - max(s, lo)) / 1e9
                    module_calls += 1
    n_dev = max(len(devices), 1)
    top = lambda d: sorted(([k, v / n_dev] for k, v in d.items()),  # noqa: E731
                           key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / 1e9 / n_dev, "window_s": (hi - lo) / 1e9,
            "module_s": module_s / n_dev, "module_calls": module_calls / n_dev,
            "modules_all": all_modules / n_dev, "modules": top(mod_time),
            "device_ops": top(op_time), "idle_gaps": top(gap_time)}
