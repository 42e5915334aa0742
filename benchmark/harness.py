"""What both drivers share: the look for the chip, the program's counters,
the device's memory peak, the traced window and the result line."""

from __future__ import annotations

import contextlib
import json
import os
import shutil
import sys
import time

from benchmark import cells, check, flops, readers, trace as trace_lib

OUT_DIR = os.path.join(cells.ROOT, ".bench_out")     # git-ignored, in the checkout


def log(msg: str) -> None:
    print(f"bench: {msg}", file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX found no TPU, or fewer chips than the cell asks for."""


def device(chips: int, any_platform: bool = False) -> dict:
    """The device as JAX reports it. Raises `NoChip` unless it is a TPU with
    at least `chips` chips and a row in the peaks table (`any_platform` is for
    the tests, which drive the rest of a run on the CPU)."""
    import logging

    import jax

    # the program logs a line an epoch; keep the run's last lines for the check
    logging.getLogger("analytics_zoo_tpu").setLevel(logging.WARNING)
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    if any_platform:
        return info
    if info["platform"] != "tpu" or info["count"] < chips:
        raise NoChip(f"the cell asks for {chips} TPU chip(s); JAX found "
                     f"{info['count']} x {info['kind']} ({info['platform']})")
    flops.peaks(info["kind"])           # KeyError for a chip without peaks
    return info


def memory_peak() -> dict:
    """HBM taken on the fullest chip, as `memory_stats()` has it:
    `peak_bytes_in_use`, the allocator's peak of live buffers (arguments,
    results, weights, state); `peak_bytes_reserved`, the peak of the region it
    sets aside for the programs' own scratch (a step's activations and other
    temporaries live there, outside `bytes_in_use`); and `memory_peak_bytes`,
    their sum. The two regions are disjoint, but their peaks need not fall
    together: the sum is the most the chip can have held, and no less than the
    larger of the two. All 0 where the backend keeps no such count, as the
    CPU's does not."""
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()]
    log(f"memory_stats {stats[0]}")
    fields = ("peak_bytes_in_use", "peak_bytes_reserved")
    fullest = max(stats, key=lambda s: sum(int(s.get(f, 0)) for f in fields))
    out = {f: int(fullest.get(f, 0)) for f in fields}
    out["memory_peak_bytes"] = sum(out.values())
    return out


def counters(*registries) -> dict:
    """The program's counters, flat: `{family: value}` summed over a family's
    label children; a summary gives `<family>_sum` and `<family>_count`."""
    from analytics_zoo_tpu.common.observability import get_registry

    out = {}
    for reg in (get_registry(),) + registries:
        for name, fam in list(reg._families.items()):
            for child in list(fam._children.values()):
                if fam.kind == "summary":
                    out[name + "_sum"] = out.get(name + "_sum", 0.0) + child.sum
                    out[name + "_count"] = (out.get(name + "_count", 0.0)
                                            + child.count)
                else:
                    out[name] = out.get(name, 0.0) + float(child.value)
    return out


class Spans:
    """The benchmark's own host spans, around its calls into the program:
    `with spans("bench.submit"): ...`. Kept in memory as (name, start, end) in
    nanoseconds of `time.time_ns()`; `window` brings them onto the trace's
    clock. (Not `jax.profiler.TraceAnnotation`: the profiler's host tracer,
    which would record those, has to stay off; see `window`.)"""

    def __init__(self):
        self.events = []

    @contextlib.contextmanager
    def __call__(self, name: str):
        start = time.time_ns()
        try:
            yield
        finally:
            self.events.append((name, start, time.time_ns()))


@contextlib.contextmanager
def window(traced: bool, cell_name: str, result: dict, module_pattern: str,
           spans: Spans, warm=None):
    """The measured window. Traced, it is recorded by `jax.profiler` under
    `.bench_out/` and reduced into `result["trace"]`; the trace is deleted
    once read. The profiler's first dispatch is slow, so `warm()` runs under
    the profiler before the window opens. The profiler's host tracer stays
    off: on the host-fed path it wrote 1 GB in 17 s, slowed the steps five
    times and took 80 s to stop (my chip run, PR 25)."""
    import jax

    if not traced:
        result["trace"] = None
        yield
        return
    import jax.numpy as jnp

    def bench_clock_mark(a):
        return a + 1

    mark = jax.jit(bench_clock_mark)
    mark(jnp.zeros((8, 128), jnp.float32)).block_until_ready()
    log_dir = os.path.join(OUT_DIR, "trace", cell_name)
    shutil.rmtree(log_dir, ignore_errors=True)
    os.makedirs(log_dir, exist_ok=True)
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 0
    options.enable_hlo_proto = False
    jax.profiler.start_trace(log_dir, profiler_options=options)
    try:
        if warm is not None:
            warm()
        stamps = []
        for _ in range(5):            # tie the host's clock to the trace's
            stamps.append(time.time_ns())
            mark(jnp.zeros((8, 128), jnp.float32)).block_until_ready()
        with spans("bench.window"):
            yield
    finally:
        jax.profiler.stop_trace()
    t0 = time.perf_counter()
    devices = trace_lib.load(trace_lib.newest_xplane(log_dir))
    off = trace_lib.clock_offset(devices, stamps) if devices else 0
    host = [(n, s + off, e + off) for n, s, e in spans.events]
    result["trace"] = trace_lib.reduce(devices, host, module_pattern)
    shutil.rmtree(log_dir, ignore_errors=True)
    log(f"trace read in {time.perf_counter() - t0:.1f} s: busy "
        f"{result['trace']['busy_s']:.3f} of {result['trace']['window_s']:.3f} s; "
        f"modules {result['trace']['modules'][:4]}")


def result_line(cell: dict, dev: dict, run: dict, traced: bool) -> dict:
    """The run's last line. `run`: end_to_end values by name, `ctx` for the
    readers, `attempted`, `failed`, `numbers` (what was compared)."""
    correct, compared = check.verdict(run["numbers"], cell["limits"])
    metrics = {}
    if traced:
        for spec in cell["per_layer"]:
            value = readers.call(spec, run["ctx"])
            if value is not None:
                metrics[spec["name"]] = {"value": value, "unit": spec["unit"]}
    else:
        for spec in cell["end_to_end"]:
            metrics[spec["name"]] = {"value": float(run["end_to_end"][spec["name"]]),
                                     "unit": spec["unit"]}
    dev = dict(dev, **run["ctx"]["memory"])
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics, "device": dev}
    t = run["ctx"]["trace"]
    if traced and t is not None:
        dev.update(busy_s=t["busy_s"], window_s=t["window_s"])
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["compared"] = compared
    return line


def emit(line: dict) -> None:
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
