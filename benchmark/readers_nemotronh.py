"""Readers of the per-layer metrics a `nemotron_h` cell adds, beside
`readers_lm.py`: each takes the run's record `ctx` and returns a number, or
None where it finds nothing to read (a program without the scope or the
counter, as a parent commit is; a run on the CPU)."""

from __future__ import annotations

from benchmark import flops_nemotronh, readers, readers_lm


def scan_roofline(ctx):
    """The chunked scans of a step: the least time the chip could take for
    the (token, Mamba layer) pairs the program's counter saw, forward plus
    twice that for the backward pass, its operations over the bf16 peak or
    its bytes over the bandwidth, over the device time under the scope
    `ssm.scan` (the forward run again for rematerialisation is time, not
    work: with the whole half recomputed this cannot pass 75 %)."""
    lm = ctx.get("lm")
    pairs = readers._delta(ctx, "zoo_lm_ssm_token_layers_total")
    steps = readers._delta(ctx, "zoo_train_steps_total")
    seconds = readers_lm._per_step(ctx, "scope_s", ("ssm.scan",))
    if not lm or not pairs or not steps or not seconds:
        return None
    cfg = lm["cfg"]
    return readers_lm._roofline(
        ctx, seconds,
        3.0 * flops_nemotronh.scan_forward_flops(cfg, pairs / steps),
        flops_nemotronh.scan_bytes(cfg, pairs / steps))
