"""`fit_lm.run` for a `deepseek_v3` model: the same driver, with the mixer's
scope `attn.latent` beside the seven that `trace_lm.SCOPES` names (lent as
`fit_lfm2.py` lends its own), and with the three keys the accepted expert
roofline reads under `afmoe`'s names (`readers_lm.moe_experts_roofline`,
`flops_lm.expert_kernel_bytes`) derived from this family's published ones, for
the length of this run only: scopes, kernels and layer kinds are not yet data
of a configuration (PERF.md, Open questions).

It compares one number more than `fit_lm.fit_numbers` gives,
`grad_diff_rotary_leaf`. The five accepted numbers are bulk norms and the
best-agreeing leaf: a fault of the latent mechanism (the rotary pairs taken as
halves, the rotary key not shared) changes the gradient of the two rotary
projections wholly and every norm, and every other leaf, by less than bfloat16
does, so a run with such a fault reads as a sound one under all five."""

from __future__ import annotations

from benchmark import check, fit_lfm2, fit_lm

SCOPES = ("attn.latent",)
ROTARY_LEAVES = ("wq_rope", "w_kr")


def rotary_leaf_gap(got: dict, want: dict) -> float:
    """The norm of the difference between the two sides' first gradient over
    the reference's norm, on the layers' rotary leaves (the queries' rotary
    projection and the one shared rotary key's), the worst of them."""
    def leaves(side):
        return [[layer[name] for name in ROTARY_LEAVES]
                for layer in side["first"]["layers"]]

    table = check.leaf_table(leaves(got), leaves(want))
    return max(d / w for d, w in zip(table["diff"], table["want"]))


def fit_numbers(got: dict, want: dict) -> dict:
    """`fit_lm.fit_numbers` and the rotary leaves' gap."""
    return dict(fit_lm.fit_numbers(got, want),
                grad_diff_rotary_leaf=rotary_leaf_gap(got, want))


def with_accepted_names(cfg: dict) -> dict:
    """The configuration with `layer_types`, `num_dense_layers` and
    `num_experts` beside its own `num_hidden_layers`,
    `first_k_dense_replace` and `n_routed_experts`; the program and the
    reference read the published names only."""
    return dict(cfg,
                layer_types=["latent_attention"] * cfg["num_hidden_layers"],
                num_dense_layers=cfg["first_k_dense_replace"],
                num_experts=cfg["n_routed_experts"])


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    cell = dict(cell, config=with_accepted_names(cell["config"]))
    with fit_lfm2.scopes_beside(SCOPES):
        run = fit_lm.run(cell, seed, seconds, traced, t_start, any_platform)
    run["numbers"]["grad_diff_rotary_leaf"] = rotary_leaf_gap(run["seen"],
                                                              run["want"])
    return run
