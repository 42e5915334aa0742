"""Readers of the per-layer metrics a `mellum` cell adds, beside
`readers_lm.py`: each takes the run's record `ctx` and returns a number, or
None where it finds nothing to read (a program without the counter, as a
parent commit is; a run on the CPU, which has no kernels)."""

from __future__ import annotations

from benchmark import flops_mellum2, readers, readers_lm


def window_kernel_roofline(ctx):
    """The flash kernels of the window layers: the least time the chip could
    take for the in-window (query, key) pairs the program's counter saw a
    step, forward plus twice that for the backward pass, their operations
    over the bf16 peak or their bytes over the bandwidth, over the device
    time of the flash kernels that the scope map puts under `attn.window`
    (`window_kernel_s`, which `fit_mellum2.py` adds to the trace's
    reduction)."""
    lm, k = ctx.get("lm"), ctx.get("kernels")
    pairs = readers._delta(ctx, "zoo_lm_window_pairs_total")
    steps = readers._delta(ctx, "zoo_train_steps_total")
    if (not lm or not k or not k.get("window_kernel_s")
            or not k["module_calls"] or not pairs or not steps):
        return None
    cfg = lm["cfg"]
    return readers_lm._roofline(
        ctx, k["window_kernel_s"] / k["module_calls"],
        3.0 * flops_mellum2.pair_flops(cfg) * pairs / steps,
        flops_mellum2.window_kernel_bytes(cfg, lm["rows"], lm["seq"]))
