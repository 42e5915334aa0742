"""`fit_lm.run` for a `mellum` model: the same driver, with the scope
`moe.balance` beside the seven that `trace_lm.SCOPES` names (lent as
`fit_lfm2.py` lends its own), with the key name the accepted readers read
(`num_dense_layers`, `flops_lm._layers`) derived from this family's
`mlp_layer_types`, and with the trace's reduction (`trace_lm.reduce`) giving
one number more, `window_kernel_s`: the device time of the flash kernels that
the scope map puts under `attn.window`, which `train_window_kernel_roofline`
reads. All three for the length of this run only: scopes, kernels and layer
kinds are not yet data of a configuration (PERF.md, Open questions).

It compares two numbers more than `fit_lm.fit_numbers` gives, each the worst
first-gradient gap over a few leaves, as `fit_kanana2.py`'s rotary one:
`grad_diff_router_leaf` over the routers' kernels, which only the softmax,
the division of the top k by their sum and the balance term reach, and
`grad_diff_yarn_leaf` over the full layers' q / k norm gains, through which
YaRN's table reaches the gradient.

The reference's balance term is over the rows of one call, so the traffic's
`reference_row_block` is its `batch` (a step's rows in one call)."""

from __future__ import annotations

import contextlib
from collections import defaultdict

from benchmark import check, fit_lfm2, fit_lm, flops_mellum2, trace_lm
from benchmark import trace as trace_lib

SCOPES = ("moe.balance",)
FLASH = ("flash_fwd", "flash_dq", "flash_dkv")


def _leaf_gap(got: dict, want: dict, pick) -> float:
    """The norm of the difference between the two sides' first gradient over
    the reference's norm, the worst of the leaves `pick(layer kind, layer)`
    names."""
    def leaves(side):
        return [pick(i, layer) for i, layer in
                enumerate(side["first"]["layers"]) if pick(i, layer)]

    table = check.leaf_table(leaves(got), leaves(want))
    return max(d / w for d, w in zip(table["diff"], table["want"]))


def router_leaf_gap(got: dict, want: dict) -> float:
    """Over the expert layers' router kernels."""
    return _leaf_gap(got, want, lambda i, layer: (
        [layer["router"]] if "router" in layer else []))


def yarn_leaf_gap(got: dict, want: dict, cfg: dict) -> float:
    """Over the `full_attention` layers' q and k norm gains."""
    full = [k == "full_attention" for k in cfg["layer_types"]]
    return _leaf_gap(got, want, lambda i, layer: (
        [layer["q_norm"], layer["k_norm"]] if full[i] else []))


def fit_numbers(got: dict, want: dict, cfg: dict) -> dict:
    """`fit_lm.fit_numbers` and the two leaves' gaps."""
    return dict(fit_lm.fit_numbers(got, want),
                grad_diff_router_leaf=router_leaf_gap(got, want),
                grad_diff_yarn_leaf=yarn_leaf_gap(got, want, cfg))


def with_accepted_names(cfg: dict) -> dict:
    """The configuration with `num_dense_layers` beside its own
    `mlp_layer_types`; the program and the reference read the published
    names only."""
    return dict(cfg, num_dense_layers=flops_mellum2._dense_layers(cfg))


def window_kernel_seconds(devices: dict, module_pattern: str,
                          scopes: dict) -> float:
    """Seconds of the flash kernels' operations that `scopes` (from
    `trace_lm.scope_map`) puts under `attn.window`, inside the modules that
    match, over the whole session, averaged over the device planes."""
    import re

    pattern = re.compile(module_pattern)
    flash = [re.compile(trace_lm.KERNELS[k]) for k in FLASH]
    total = defaultdict(float)
    for plane, dev in devices.items():
        spans = [(s, e) for n, s, e in dev["modules"] if pattern.search(n)]
        inside = [ev for ev in dev["ops"]
                  if any(s <= ev[1] < e for s, e in spans)]
        for name, seconds in trace_lib.self_times(inside):
            instr = trace_lm.instruction_name(name)
            if (scopes.get(instr) == "attn.window"
                    and any(rx.match(instr) for rx in flash)):
                total[plane] += seconds
    return sum(total.values()) / max(len(devices), 1)


@contextlib.contextmanager
def window_kernels_beside():
    """`trace_lm.reduce` with `window_kernel_s` in what it returns, for the
    length of a run."""
    reduce = trace_lm.reduce

    def both(devices, module_pattern, scopes):
        out = reduce(devices, module_pattern, scopes)
        out["window_kernel_s"] = window_kernel_seconds(devices, module_pattern,
                                                       scopes)
        return out

    trace_lm.reduce = both
    try:
        yield
    finally:
        trace_lm.reduce = reduce


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    traffic = cell["traffic"]
    if traffic["reference_row_block"] != traffic["batch"]:
        raise ValueError("the reference's balance term is over one call's "
                         "rows: reference_row_block must be the batch")
    cell = dict(cell, config=with_accepted_names(cell["config"]))
    with fit_lfm2.scopes_beside(SCOPES), window_kernels_beside():
        run = fit_lm.run(cell, seed, seconds, traced, t_start, any_platform)
    run["numbers"].update(
        grad_diff_router_leaf=router_leaf_gap(run["seen"], run["want"]),
        grad_diff_yarn_leaf=yarn_leaf_gap(run["seen"], run["want"],
                                          cell["config"]))
    return run
