"""A reduction of its own beside `trace.reduce`, which keeps the ten largest
operations only: device time by kernel and by the program's named scope, for
the readers of `readers_lm.py`. From the same xplane file, through
`trace.load` and `trace.self_times`.

An `XLA Ops` event is named by its HLO instruction (`%gmm.2 = bf16[...]
custom-call(...)`): a Pallas kernel's instruction carries the kernel's name
(`zoo_flash_fwd`, `gmm`, `tgmm`), so kernels are found by name. The trace
carries no scope for an operation; the compiled step's HLO text does
(`metadata={op_name=".../optimizer/..."}`), so the driver hands that text
over, and every instruction is put under the first of the program's scopes
its `op_name` names. Everything is summed over the whole profiler session
(the warm call too) and given per executed module, so no window is needed:
shares and rooflines are ratios of sums over the same steps."""

from __future__ import annotations

import contextlib
import re
from collections import defaultdict

from benchmark import trace as trace_lib

KERNELS = {"flash_fwd": r"zoo_flash_fwd", "flash_dq": r"zoo_flash_dq",
           "flash_dkv": r"zoo_flash_dkv", "gmm": r"gmm",
           "tgmm": r"tgmm"}
SCOPES = ("attn.window", "attn.full", "moe.route", "moe.experts",
          "moe.shared", "lm.loss", "optimizer")
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*?op_name=\"([^\"]*)\"")


def instruction_name(event_name: str) -> str:
    """`%gmm.2 = bf16[...] custom-call(...)` -> `gmm.2`."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def scope_map(hlo_text: str) -> dict:
    """{instruction: scope} from a compiled module's text."""
    out = {}
    for line in hlo_text.splitlines():
        m = _INSTR.match(line)
        if m:
            for scope in SCOPES:
                if scope in m.group(2):
                    out[m.group(1)] = scope
                    break
    return out


def reduce(devices: dict, module_pattern: str, scopes: dict) -> dict:
    """`devices` as `trace.load` gives them. Returns seconds of the whole
    session, averaged over the device planes: `module_s`, `module_calls` of
    the modules that match; `kernel_s` {kernel: seconds} and `kernel_calls`;
    `scope_s` {scope: seconds} by `scopes` (from `scope_map`)."""
    pattern = re.compile(module_pattern)
    kernels = {k: re.compile(v) for k, v in KERNELS.items()}
    kernel_s, kernel_calls = defaultdict(float), defaultdict(int)
    scope_s = defaultdict(float)
    module_s, module_calls = 0.0, 0
    for dev in devices.values():
        spans = [(s, e) for n, s, e in dev["modules"] if pattern.search(n)]
        module_s += sum(e - s for s, e in spans) / 1e9
        module_calls += len(spans)
        inside = [ev for ev in dev["ops"]
                  if any(s <= ev[1] < e for s, e in spans)] if spans else []
        for name, seconds in trace_lib.self_times(inside):
            instr = instruction_name(name)
            for kernel, rx in kernels.items():
                if rx.match(instr):
                    kernel_s[kernel] += seconds
                    kernel_calls[kernel] += 1
                    break
            if instr in scopes:
                scope_s[scopes[instr]] += seconds
    n = max(len(devices), 1)
    return {"module_s": module_s / n, "module_calls": module_calls / n,
            "kernel_s": {k: v / n for k, v in kernel_s.items()},
            "kernel_calls": {k: v / n for k, v in kernel_calls.items()},
            "scope_s": {k: v / n for k, v in scope_s.items()}}


@contextlib.contextmanager
def recording(kept: dict):
    """While this is open, what `trace.load` returns is also kept under
    `kept["devices"]`: `harness.window` reads the trace and deletes it before
    it hands control back, and returns `trace.reduce` alone."""
    load = trace_lib.load

    def keeping(path):
        kept["devices"] = load(path)
        return kept["devices"]

    trace_lib.load = keeping
    try:
        yield
    finally:
        trace_lib.load = load
