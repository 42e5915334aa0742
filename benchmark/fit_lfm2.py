"""`fit_lm.run` for a model with gated short convolutions: the same driver,
with the mixer's scope `conv.short` beside the seven that `trace_lm.SCOPES`
names, for the length of this run only (a configuration's scopes are not yet
data of the configuration: PERF.md, Open questions)."""

from __future__ import annotations

import contextlib

from benchmark import fit_lm, trace_lm

SCOPES = ("conv.short",)


@contextlib.contextmanager
def scopes_beside(more: tuple):
    kept = trace_lm.SCOPES
    trace_lm.SCOPES = kept + more
    try:
        yield
    finally:
        trace_lm.SCOPES = kept


def run(cell: dict, seed: int, seconds: float, traced: bool, t_start: float,
        any_platform: bool = False) -> dict:
    with scopes_beside(SCOPES):
        return fit_lm.run(cell, seed, seconds, traced, t_start, any_platform)
