"""Readers of the per-layer metrics a `deepseek_v3` cell adds, beside
`readers_lm.py`: each takes the run's record `ctx` and returns a number, or
None where it finds nothing to read (a program without the scope or the
counter, as a parent commit is; a run on the CPU)."""

from __future__ import annotations

from benchmark import flops_kanana2, readers, readers_lm


def latent_roofline(ctx):
    """The latent projections of a step: the least time the chip could take
    for the (token, latent layer) pairs the program's counter saw, forward
    plus twice that for the backward pass, over the device time under the
    scope `attn.latent` (a forward run again for rematerialisation is time,
    not work: with the half's projections recomputed this cannot pass 75 %)."""
    lm = ctx.get("lm")
    pairs = readers._delta(ctx, "zoo_lm_latent_token_layers_total")
    steps = readers._delta(ctx, "zoo_train_steps_total")
    seconds = readers_lm._per_step(ctx, "scope_s", ("attn.latent",))
    if not lm or not pairs or not steps or not seconds:
        return None
    cfg = lm["cfg"]
    return readers_lm._roofline(
        ctx, seconds,
        3.0 * flops_kanana2.latent_forward_flops(cfg, pairs / steps),
        flops_kanana2.latent_bytes(cfg, pairs / steps,
                                   cfg["num_hidden_layers"]))


def attn_kernel_roofline(ctx):
    """`readers_lm.attn_kernel_roofline` with the work of kernels whose
    queries and keys are wider than their values."""
    lm = ctx.get("lm")
    seconds = readers_lm._per_step(
        ctx, "kernel_s", ("flash_fwd", "flash_dq", "flash_dkv"))
    if not lm or not seconds:
        return None
    args = (lm["cfg"], lm["rows"], lm["seq"])
    return readers_lm._roofline(
        ctx, seconds,
        3.0 * flops_kanana2.attention_kernel_forward_flops(*args),
        flops_kanana2.attention_kernel_bytes(*args))
