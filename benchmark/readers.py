"""Readers of the per-layer metrics. `benchmark/metrics/<name>.json` names one
as `module:function` with its arguments; a later PR adds a reader by adding a
module. A reader takes the run's record `ctx` and returns a number, or None
when it finds nothing to read (the metric is then left out of the line).

`ctx`: `counters` = {"setup_end", "window_start", "window_end"} snapshots of
the program's registries (summaries as `<name>_sum` / `<name>_count`);
`trace` = `trace.reduce(...)` of the traced window or None; `series` = lists
the load generator kept; `window_flops` = model FLOPs of the window's useful
work; `memory` = `harness.memory_peak()`; `peaks`.
"""

from __future__ import annotations

import statistics

from benchmark import cells


def call(spec: dict, ctx: dict):
    value = cells.load(spec["reader"])(ctx, **spec.get("args", {}))
    return None if value is None else float(value)


def _delta(ctx, name):
    c = ctx["counters"]
    if name not in c["window_end"]:
        return None
    return c["window_end"][name] - c["window_start"].get(name, 0.0)


def _sum_deltas(ctx, names):
    parts = [_delta(ctx, n) for n in names]
    return None if any(p is None for p in parts) else sum(parts)


def counter_at(ctx, name, at="setup_end", scale=1.0):
    value = ctx["counters"][at].get(name)
    return None if value is None else value * scale


def counter_delta(ctx, name, scale=1.0):
    d = _delta(ctx, name)
    return None if d is None else d * scale


def counter_ratio(ctx, num, den, scale=1.0):
    """Sum of the deltas of `num` over the sum of the deltas of `den`."""
    n, d = _sum_deltas(ctx, num), _sum_deltas(ctx, den)
    return None if n is None or not d else n / d * scale


def modules_per(ctx, counter):
    """Executed programs of any name per unit of `counter`'s delta."""
    d = _delta(ctx, counter)
    if ctx["trace"] is None or not d:
        return None
    return ctx["trace"]["modules_all"] / d


def module_ms_per(ctx, counter=None):
    """Device milliseconds of the cell's modules per unit of `counter`'s
    delta, or per executed module when no counter is named."""
    t = ctx["trace"]
    if t is None or not t["module_calls"]:
        return None
    per = _delta(ctx, counter) if counter else t["module_calls"]
    return None if not per else t["module_s"] / per * 1e3


def mfu(ctx):
    """Model FLOPs of the window's useful work over the device time of the
    modules that did it, as a share of the chip's bf16 peak."""
    t = ctx["trace"]
    if t is None or not t["module_s"] or not ctx.get("window_flops"):
        return None
    return (ctx["window_flops"] / t["module_s"]
            / ctx["peaks"]["bf16_flops"] * 100.0)


def idle_share(ctx):
    t = ctx["trace"]
    if t is None or not t["window_s"] or not t["busy_s"]:
        return None
    return (1.0 - t["busy_s"] / t["window_s"]) * 100.0


def hbm_peak_frac(ctx):
    if not ctx["memory"]["memory_peak_bytes"]:
        return None
    return (ctx["memory"]["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
            * 100.0)


def percentile(ctx, series, q):
    """The q-th percentile of a series the load generator kept (`q` in 1..99,
    by `statistics.quantiles` over 100 cuts)."""
    values = ctx["series"].get(series)
    if not values or len(values) < 2:
        return None
    return statistics.quantiles(values, n=100, method="inclusive")[q - 1]
