"""A tiny `lfm2_moe` cell for the CPU tests, ADDED beside the copied benchmark
like `tiny_lm.py`'s: two short convolutions round one attention layer at toy
widths, 4 of 8 experts held from number 2, no shared expert, a tied head,
batch 1 as the real cell has it."""

import json
import os

from benchmark.tests import tiny

CELL = "lfm2-tiny.tiny-seq1"
REAL = "lfm2-24b-a2b.fit-seq32k"
TINY_LFM2 = {
    "program": "benchmark.models_lfm2:lfm2",
    "reference": "benchmark.reference.lfm2",
    "rows": "benchmark.data_lm:next_token_rows",
    "flops": "benchmark.flops_lfm2:lfm2_forward_flops",
    "source": "tests", "model_type": "lfm2_moe", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 96,
    "assumed": {"seq_len": 32, "tie_embeddings": True,
                "compute_dtype": "bfloat16", "initializer_range": 0.02,
                "bias_rate": 0.001,
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TRAFFIC = {"driver": "benchmark.fit_lfm2:run",
           "feature_set": "benchmark.fit:hostfed_set",
           "epoch_order": "benchmark.fit:numpy_order", "fused": False,
           "batch": 1, "steps_per_call": 4, "items_per_row": 32,
           "check_steps": 3, "reference_row_block": 1, "trace_seconds": 1,
           "module_pattern": "^jit_train_", "row_sets": 4,
           "rate_metric": "train_tokens_per_s_per_chip"}


def add_cell(root: str, limits: dict) -> str:
    """The tiny cell, under `limits`, into a root that `tiny.make_root`
    made."""
    here = os.path.join(root, "benchmark")
    tiny._write(os.path.join(here, "configs", "lfm2-tiny.json"), TINY_LFM2)
    tiny._write(os.path.join(here, "traffic", "tiny-seq1.json"), TRAFFIC)
    tiny._write(os.path.join(here, "limits", CELL + ".json"), limits)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "lfm2-tiny", "source": "tests",
                             "file": "benchmark/configs/lfm2-tiny.json",
                             "reduced": [], "why": "a toy size for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "lfm2-tiny",
                               "traffic": "tiny-seq1", "chips": 1,
                               "why": "a toy cell for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return CELL
