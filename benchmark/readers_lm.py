"""Readers of the language-model cell's per-layer metrics. As in
`readers.py`, each takes the run's record `ctx` and returns a number, or None
where it finds nothing to read (a parent that lacks the counters or the
kernels, a run on the CPU): the metric is then left out of the line.

Beside what `readers.py` lists, `ctx` may hold `kernels` (`trace_lm.reduce`
of the traced session) and `lm` = {"cfg", "rows", "seq"}."""

from __future__ import annotations

from benchmark import flops_lm, readers


def _per_step(ctx, kind: str, names):
    k = ctx.get("kernels")
    if not k or not k["module_calls"]:
        return None
    found = [k[kind][n] for n in names if n in k[kind]]
    return sum(found) / k["module_calls"] if found else None


def _roofline(ctx, seconds, flops, nbytes):
    """The least time the chip could take (the larger of operations over the
    peak and bytes over the bandwidth) over the time taken, in %."""
    if not seconds or ctx.get("peaks") is None:
        return None
    least = max(flops / ctx["peaks"]["bf16_flops"],
                nbytes / ctx["peaks"]["hbm_bytes_per_s"])
    return least / seconds * 100.0


def attn_kernel_roofline(ctx):
    """The flash forward, dq and dkv kernels of a step: forward plus twice
    that for the backward pass, over all their device time (a forward run
    again for rematerialisation is time, not work)."""
    lm = ctx.get("lm")
    seconds = _per_step(ctx, "kernel_s", ("flash_fwd", "flash_dq", "flash_dkv"))
    if not lm or not seconds:
        return None
    args = (lm["cfg"], lm["rows"], lm["seq"])
    return _roofline(ctx, seconds,
                     3.0 * flops_lm.attention_kernel_forward_flops(*args),
                     flops_lm.attention_kernel_bytes(*args))


def held_per_step(ctx):
    """(token, expert) pairs that fell on a held expert, a step of the
    window, over all layers: what the counters saw; None where they saw
    none."""
    held = readers._delta(ctx, "zoo_moe_assignments_total_held")
    steps = readers._delta(ctx, "zoo_train_steps_total")
    return held / steps if held and steps else None


def moe_experts_roofline(ctx):
    """The grouped products over the experts held (`gmm`, `tgmm`): the
    assignments the counters saw a step, forward and backward."""
    lm, held = ctx.get("lm"), held_per_step(ctx)
    seconds = _per_step(ctx, "kernel_s", ("gmm", "tgmm"))
    if not lm or not held or not seconds:
        return None
    cfg = lm["cfg"]
    layers = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    return _roofline(ctx, seconds,
                     3.0 * flops_lm.expert_forward_flops(cfg, held),
                     flops_lm.expert_kernel_bytes(cfg, held, layers))


def device_share(ctx, scopes=()):
    """Device time of the operations under the named scopes over the step's
    module time, in %. The scopes come from the compiled step's text; a trace
    reduced without it has none, and the metric is left out."""
    k = ctx.get("kernels")
    part = _per_step(ctx, "scope_s", scopes)
    if part is None or not k["module_s"]:
        return None
    return part * k["module_calls"] / k["module_s"] * 100.0


def load_max_over_mean(ctx):
    """Most over mean tokens of a held expert, over the window's steps."""
    return readers.counter_ratio(ctx, ["zoo_moe_expert_tokens_max_sum"],
                                 ["zoo_moe_expert_tokens_mean_sum"])


def held_share(ctx):
    """Assignments that fell on an expert held here, of all, in %."""
    held = readers._delta(ctx, "zoo_moe_assignments_total_held")
    total = readers._delta(ctx, "zoo_moe_assignments_total")
    return held / total * 100.0 if held is not None and total else None
