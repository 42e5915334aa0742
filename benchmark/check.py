"""The comparison that decides `correct`: numbers of the timed path against
the plain reference, each with a limit of its own (benchmark/limits/).

Every number is a gap, 0 when program and reference agree:
- `loss_gap_<k>`: |program's loss - reference's| / |reference's|, step k;
- `grad_norm_gap`: the worst leaf's gap between the norm of the first
  gradient as the optimizer got it and the reference's, against the
  reference's norm of that leaf or of the median leaf, whichever is larger;
- `change_norm_gap`: the same of the parameters' change after the followed
  steps, over the leaves whose reference gradient is not nought to rounding
  (at least a thousandth of the median leaf's); each also by its median
  leaf (`..._median_leaf`), which is steady from seed to seed;
- `grad_diff_best_leaf`: the norm of the difference between the two sides'
  first gradient over the reference's norm, on the leaf where they agree
  best. A norm is a bulk number that rounding hardly moves; the difference
  is where the next lower precision shows;
- `prob_gap`: the widest |served probability - reference's| over the
  sampled answers.
"""

from __future__ import annotations

import math
import statistics
import sys

import numpy as np

NOUGHT = 1e-3     # of the median leaf's gradient norm


def _leaves(tree) -> list:
    import jax

    return [np.asarray(leaf, np.float64).ravel()
            for leaf in jax.tree_util.tree_leaves(tree)]


def leaf_table(got, want) -> dict:
    """A leaf a row: the norm of `got`, of `want` and of their difference."""
    g, w = _leaves(got), _leaves(want)
    return {"got": [float(np.linalg.norm(a)) for a in g],
            "want": [float(np.linalg.norm(b)) for b in w],
            "diff": [float(np.linalg.norm(a - b)) for a, b in zip(g, w)]}


def norm_gaps(table: dict, keep: list) -> dict:
    """Of the leaves kept: the worst and the median leaf's gap of norms, each
    against the reference's norm of that leaf or of the median leaf, whichever
    is larger."""
    med = statistics.median(table["want"])
    gaps = [abs(g - w) / max(w, med)
            for g, w, k in zip(table["got"], table["want"], keep) if k]
    return {"norm_gap": max(gaps),
            "norm_gap_median_leaf": statistics.median(gaps)}


def diff_best_leaf(table: dict) -> float:
    """The difference's norm over the reference's, on the leaf where the two
    sides agree best (a leaf whose reference is all nought is left out)."""
    return min(d / w for d, w in zip(table["diff"], table["want"]) if w > 0)


def fit_numbers(got: dict, want: dict) -> dict:
    """`got` / `want`: {"losses": [...], and where the path shows its state
    between steps "first": the first gradient, "change": the parameters'
    change after the followed steps, both as trees of one layout}."""
    out = {f"loss_gap_{k + 1}": abs(a - b) / abs(b)
           for k, (a, b) in enumerate(zip(got["losses"], want["losses"]))}
    if "first" in got:
        first = leaf_table(got["first"], want["first"])
        med = statistics.median(first["want"])
        moved = [w >= NOUGHT * med for w in first["want"]]
        for name, table, keep in (
                ("grad", first, [True] * len(moved)),
                ("change", leaf_table(got["change"], want["change"]), moved)):
            for key, value in norm_gaps(table, keep).items():
                out[f"{name}_{key}"] = value
        out["grad_diff_best_leaf"] = diff_best_leaf(first)
    return out


def verdict(numbers: dict, limits: dict) -> tuple:
    """(`correct`, {name: [number, limit]}); every limit holds its number, and
    a number that is missing or not finite fails. Printed on standard error,
    each number beside its limit, as the run's last lines."""
    compared, ok = {}, bool(limits)
    for name, limit in limits.items():
        value = numbers.get(name)
        value = None if value is None else float(value)
        compared[name] = [value, limit]
        if value is None or not (math.isfinite(value) and value <= limit):
            ok = False
        shown = "missing" if value is None else f"{value:.6g}"
        print(f"compared {name} {shown} limit {limit:g}", file=sys.stderr)
    print(f"correct {ok}", file=sys.stderr, flush=True)
    return ok, compared
