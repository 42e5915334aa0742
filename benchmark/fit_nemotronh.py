"""`fit_lm.run` for a `nemotron_h` model: the same driver, with the Mamba
mixer's three scopes beside the seven that `trace_lm.SCOPES` names (lent as
`fit_lfm2.py` lends its own), and with `afmoe`'s key names (`layer_types`,
`num_dense_layers`, `num_experts`) derived from this family's published ones,
for the length of this run only (as `fit_kanana2.py` does: PERF.md, Open
questions).

It compares one number more than `fit_lm.fit_numbers` gives,
`grad_diff_ssm_leaf`, built as `fit_kanana2.py`'s rotary one: the worst
first-gradient gap over the Mamba leaves that only the scan and its
convolution reach (`A_log`, `dt_bias`, `D`, the convolution's taps), where a
fault of the mixer shows wholly while the bulk norms move by rounding."""

from __future__ import annotations

from benchmark import check, fit_lfm2, fit_lm

SCOPES = ("ssm.proj_in", "ssm.scan", "ssm.proj_out")
SSM_LEAVES = ("A_log", "dt_bias", "D", "conv_w")
KINDS = {"M": "mamba", "*": "full_attention", "E": "experts"}


def ssm_leaf_gap(got: dict, want: dict) -> float:
    """The norm of the difference between the two sides' first gradient over
    the reference's norm, on the Mamba layers' scan leaves, the worst of
    them."""
    def leaves(side):
        return [[layer[name] for name in SSM_LEAVES]
                for layer in side["first"]["layers"] if "A_log" in layer]

    table = check.leaf_table(leaves(got), leaves(want))
    return max(d / w for d, w in zip(table["diff"], table["want"]))


def fit_numbers(got: dict, want: dict) -> dict:
    """`fit_lm.fit_numbers` and the scan leaves' gap."""
    return dict(fit_lm.fit_numbers(got, want),
                grad_diff_ssm_leaf=ssm_leaf_gap(got, want))


def with_accepted_names(cfg: dict) -> dict:
    """The configuration with `layer_types`, `num_dense_layers` and
    `num_experts` beside its own `hybrid_override_pattern` and
    `n_routed_experts`; the program and the reference read the published
    names only."""
    return dict(cfg,
                layer_types=[KINDS[c] for c in cfg["hybrid_override_pattern"]],
                num_dense_layers=0, num_experts=cfg["n_routed_experts"])


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    cell = dict(cell, config=with_accepted_names(cell["config"]))
    with fit_lfm2.scopes_beside(SCOPES):
        run = fit_lm.run(cell, seed, seconds, traced, t_start, any_platform)
    run["numbers"]["grad_diff_ssm_leaf"] = ssm_leaf_gap(run["seen"],
                                                        run["want"])
    return run
