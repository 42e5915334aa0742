#!/usr/bin/env python3
"""Where a decoder cell's step goes, by every named scope of the step, for
the builder:

    python3 benchmark/scope_shares.py --workload <cell> --seed <n> [--seconds <s>]

`benchmark/run.py` never loads this file and no number of the benchmark comes
from it. The cell's own driver runs traced, with the scopes that no accepted
metric reads yet lent to `trace_lm.SCOPES` beside the accepted ones (through
`fit_lfm2.scopes_beside`, as the drivers of the two later cells lend theirs)
for the length of the run. `trace_lm.scope_map` and `trace_lm.reduce` are
wrapped for that run too: the first keeps which instructions a
rematerialised pass made (`rematted_computation` in their `op_name`) and the
hash of the compiled step's text with its source information stripped, which
is the same for two trees that differ in scopes alone; the second lays the
session's operations out by scope, the rematerialised part apart, beside what
it hands back to the driver.

Prints one JSON line: the result line of `run.py` and the run's `end_to_end`
values, then `scopes`, for each of the seventeen scopes its ms a step, share
of the step's module time (%) and the ms of it that a rematerialised pass ran;
`unscoped`, the same for the operations under none; `unscoped_top`, the ten
largest of those by exclusive time; `step_ms`, `remat_ms` and
`program_sha256`. Where the trace holds no device plane (the CPU) every one
of those numbers is null.
"""

import time

T_START = time.perf_counter()

import argparse      # noqa: E402
import contextlib    # noqa: E402
import hashlib       # noqa: E402
import os            # noqa: E402
import re            # noqa: E402
import sys           # noqa: E402
from collections import defaultdict  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from benchmark import fit_lfm2, trace as trace_lib, trace_lm  # noqa: E402

# the nine that accepted metrics read, then the eight beside them (PR 39)
SCOPES = ("attn.window", "attn.full", "attn.latent", "conv.short",
          "moe.route", "moe.experts", "moe.shared", "lm.loss", "optimizer",
          "attn.proj_in", "attn.qk_rotary", "attn.proj_out", "block.norm",
          "block.cast", "mlp.dense", "lm.embed", "lm.head")
REMAT = "rematted_computation"
TOP = 10
_DEBUG_TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")
_METADATA = re.compile(r",?\s*(?<!\w)metadata=\{[^}]*\}")
_NAME = re.compile(r"%[\w.\-]+")
_KERNEL_BODY = re.compile(r'"body":"([A-Za-z0-9+/=]+)"')


def kernel_body(serialized: str) -> str:
    """A Mosaic kernel's module (base64 bytecode, the `body` of a
    `tpu_custom_call`'s configuration) as text without its locations: they
    hold the Python frames of every call down to the kernel, so a line moved
    in a caller changes the bytes and not the kernel."""
    import base64

    from jax._src.interpreters import mlir
    from jaxlib.mlir import ir

    ctx = mlir.make_ir_context()
    ctx.allow_unregistered_dialects = True
    with ctx:
        module = ir.Module.parse(base64.b64decode(serialized))
        return module.operation.get_asm(enable_debug_info=False)


def program_text(hlo_text: str) -> str:
    """A compiled module's text less what names its source: each
    instruction's `metadata={...}`, the tables of files, functions and stack
    frames that it points into (each a header line, then rows up to a blank
    line), the locations inside each Pallas kernel's module (a hash of
    `kernel_body` stands for the module), and the names of instructions and
    computations, which are numbered in order of first appearance. (XLA
    numbers a name by how many of its kind it has made: a scope that lowers
    a called function once more moves the numbers of a program it then
    inlines to the same.)"""
    out, skipping, names = [], False, {}

    def number(m):
        return names.setdefault(m.group(0), f"%{len(names)}")

    def body(m):
        return '"body":"%s"' % hashlib.sha256(
            kernel_body(m.group(1)).encode()).hexdigest()

    for line in hlo_text.splitlines():
        if line in _DEBUG_TABLES:
            skipping = True
        if skipping:
            skipping = bool(line)
            continue
        line = _KERNEL_BODY.sub(body, _METADATA.sub("", line))
        out.append(_NAME.sub(number, line))
    return "\n".join(out)


def breakdown(devices: dict, module_pattern: str, scopes: dict,
              remat: set) -> dict:
    """As `trace_lm.reduce` sums by scope, over the operations inside the
    modules that match: seconds by scope (None: under none), the part of each
    that rematerialised instructions (`remat`) ran, and seconds by operation
    of those under none; averaged over the device planes."""
    pattern = re.compile(module_pattern)
    by, again, loose = defaultdict(float), defaultdict(float), defaultdict(float)
    module_s, module_calls = 0.0, 0
    for dev in devices.values():
        spans = [(s, e) for n, s, e in dev["modules"] if pattern.search(n)]
        module_s += sum(e - s for s, e in spans) / 1e9
        module_calls += len(spans)
        inside = [ev for ev in dev["ops"]
                  if any(s <= ev[1] < e for s, e in spans)]
        for name, seconds in trace_lib.self_times(inside):
            instr = trace_lm.instruction_name(name)
            scope = scopes.get(instr)
            by[scope] += seconds
            if instr in remat:
                again[scope] += seconds
            if scope is None:
                loose[name] += seconds
    n = max(len(devices), 1)
    return {"module_s": module_s / n, "module_calls": module_calls / n,
            "scope_s": {k: v / n for k, v in by.items()},
            "remat_s": {k: v / n for k, v in again.items()},
            "unscoped_s": {k: v / n for k, v in loose.items()}}


def shares(seen: dict) -> dict:
    """The line's own keys from what `reading` kept: ms a step and share of
    the step by scope, null throughout where no step was traced."""
    b = seen.get("by")
    calls = b["module_calls"] if b else 0

    def entry(scope):
        if not calls:
            return {"ms": None, "share": None, "remat_ms": None}
        s = b["scope_s"].get(scope, 0.0)
        return {"ms": s / calls * 1e3,
                "share": s / b["module_s"] * 100.0 if b["module_s"] else None,
                "remat_ms": b["remat_s"].get(scope, 0.0) / calls * 1e3}

    top = (sorted(b["unscoped_s"].items(), key=lambda kv: -kv[1])[:TOP]
           if calls else [])
    return {"scopes": {scope: entry(scope) for scope in SCOPES},
            "unscoped": entry(None),
            "unscoped_top": [
                {"op": name[:160],
                 "op_name": seen["op_names"].get(
                     trace_lm.instruction_name(name), "")[:200],
                 "ms": s / calls * 1e3} for name, s in top],
            "step_ms": b["module_s"] / calls * 1e3 if calls else None,
            "remat_ms": (sum(b["remat_s"].values()) / calls * 1e3 if calls
                         else None),
            "program_sha256": seen.get("program_sha256")}


@contextlib.contextmanager
def reading(seen: dict):
    """For the length of a run: every scope of `SCOPES` lent to
    `trace_lm.SCOPES`, and `trace_lm.scope_map` and `trace_lm.reduce`
    wrapped so that what they saw is kept in `seen`."""
    scope_map, reduce = trace_lm.scope_map, trace_lm.reduce

    def mapping(hlo_text):
        names = {}
        for line in hlo_text.splitlines():
            m = trace_lm._INSTR.match(line)
            if m:
                names[m.group(1)] = m.group(2)
        seen["op_names"] = names
        seen["remat"] = {i for i, op in names.items() if REMAT in op}
        seen["program_sha256"] = hashlib.sha256(
            program_text(hlo_text).encode()).hexdigest()
        return scope_map(hlo_text)

    def reducing(devices, module_pattern, scopes):
        seen["by"] = breakdown(devices, module_pattern, scopes,
                               seen.get("remat", set()))
        return reduce(devices, module_pattern, scopes)

    lent = tuple(s for s in SCOPES if s not in trace_lm.SCOPES)
    trace_lm.scope_map, trace_lm.reduce = mapping, reducing
    try:
        with fit_lfm2.scopes_beside(lent):
            yield
    finally:
        trace_lm.scope_map, trace_lm.reduce = scope_map, reduce


def main(argv=None, root=None, any_platform=False) -> int:
    """`root` and `any_platform` are for the tests, which drive a tiny cell
    of a temporary benchmark on the CPU."""
    from benchmark import cells, harness

    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)

    cell = cells.resolve(args.workload, root or cells.ROOT)
    seen = {}
    try:
        with reading(seen):
            run = cells.load(cell["traffic"]["driver"])(
                cell, args.seed, args.seconds, True, T_START,
                any_platform=any_platform)
    except harness.NoChip as e:
        print(f"bench: {e}; no result", file=sys.stderr)
        return 1
    line = harness.result_line(cell, run["device"], run, True)
    line["end_to_end"] = run["end_to_end"]
    line.update(shares(seen))
    harness.emit(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
