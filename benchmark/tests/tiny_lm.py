"""A tiny language-model cell for the CPU tests, ADDED beside the copied
benchmark like `tiny.py`'s: Trinity's block at toy widths, 4 of 8 experts
held from number 2, a window of 8 in 32 tokens."""

import itertools
import json
import os

import numpy as np

from benchmark.tests import tiny

CELL = "trinity-tiny.tiny-seq"
TINY_TRINITY = {
    "program": "benchmark.models_lm:trinity",
    "reference": "benchmark.reference.trinity",
    "rows": "benchmark.data_lm:next_token_rows",
    "flops": "benchmark.flops_lm:trinity_forward_flops",
    "source": "tests", "model_type": "afmoe", "hidden_size": 64,
    "head_dim": 16, "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "sliding_window": 8,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mup_enabled": True,
    "route_norm": True, "route_scale": 2.826, "score_func": "sigmoid",
    "load_balance_coeff": 0.001, "vocab_size": 96,
    "assumed": {"seq_len": 32, "compute_dtype": "bfloat16",
                "initializer_range": 0.02,
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TRAFFIC = {"driver": "benchmark.fit_lm:run",
           "feature_set": "benchmark.fit:hostfed_set",
           "epoch_order": "benchmark.fit:numpy_order", "fused": False,
           "batch": 2, "steps_per_call": 4, "items_per_row": 32,
           "check_steps": 3, "reference_row_block": 1, "trace_seconds": 1,
           "module_pattern": "^jit_train_", "row_sets": 4,
           "rate_metric": "train_tokens_per_s_per_chip"}
# the numbers the real cell's limits name, at toy readings on the CPU (bf16
# against float32 at widths of 64; sound largest / control smallest over four
# seeds): grad_diff_best_leaf 0.049 / 0.24, grad_norm_gap 0.017 / 0.052,
# change_norm_gap 0.015 (a leaf left unmoved reads 1); half a batch reads 0.34
# by grad_norm_gap_median_leaf. No loss: on rows a step has not seen before,
# neither the control nor half a batch moves it past what sound runs read
# (PERF.md, PR 32)
LIMITS = {"grad_norm_gap": 0.04,
          "grad_norm_gap_median_leaf": 6e-3, "change_norm_gap": 0.05,
          "change_norm_gap_median_leaf": 7.5e-3, "grad_diff_best_leaf": 0.12}


class Recorded:
    """A feature set that keeps a copy of every batch the program draws from
    it, as (the set's number, the epoch's seed, inputs, labels) in `fed`."""

    def __init__(self, inner, number: int, fed: list):
        self._inner, self._number, self._fed = inner, number, fed

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def train_batches(self, batch_size, shuffle=True, seed=0, borrowed=False):
        for x, y, mask in self._inner.train_batches(batch_size, shuffle, seed,
                                                    borrowed=borrowed):
            self._fed.append((self._number, seed, np.array(x), np.array(y)))
            yield x, y, mask


def recording(feature_set, fed: list):
    """`feature_set` (a traffic file's) with every set it makes `Recorded`,
    numbered in the order they are made."""
    numbers = itertools.count()
    return lambda traffic, x, y: Recorded(feature_set(traffic, x, y),
                                          next(numbers), fed)


def add_cell(root: str) -> str:
    """The tiny cell into a root that `tiny.make_root` made."""
    here = os.path.join(root, "benchmark")
    tiny._write(os.path.join(here, "configs", "trinity-tiny.json"), TINY_TRINITY)
    tiny._write(os.path.join(here, "traffic", "tiny-seq.json"), TRAFFIC)
    tiny._write(os.path.join(here, "limits", CELL + ".json"), LIMITS)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "trinity-tiny", "source": "tests",
                             "file": "benchmark/configs/trinity-tiny.json",
                             "reduced": [], "why": "a toy size for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "trinity-tiny",
                               "traffic": "tiny-seq", "chips": 1,
                               "why": "a toy cell for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "trinity-mini.fit-seq8k" in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return CELL
