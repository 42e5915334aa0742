"""A tiny `mellum` cell for the CPU tests, ADDED beside the copied benchmark
like the other tiny cells: one period of four layers at toy widths (three
windows of 8 and a full layer with YaRN over 32 tokens, 4 query heads of 16
over 2 key-value heads), softmax top-2 over 4 of 8 SwiGLU experts held from
number 2 with a balance term, a head of its own, batch 1 as the real cell has
it."""

import json
import os

from benchmark.tests import tiny

CELL = "mellum2-tiny.tiny-seq32-yarn"
REAL = "mellum2-12b-a2.5b.fit-seq32k-yarn"
ROPE = {"full_attention": {
            "rope_type": "yarn", "rope_theta": 10000, "factor": 16,
            "original_max_position_embeddings": 2048, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
TINY_MELLUM2 = {
    "program": "benchmark.models_mellum2:mellum2",
    "reference": "benchmark.reference.mellum2",
    "rows": "benchmark.data_lm:next_token_rows",
    "flops": "benchmark.flops_mellum2:mellum2_forward_flops",
    "source": "tests", "model_type": "mellum", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "intermediate_size": 48, "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "num_hidden_layers": 4,
    "num_experts": 4, "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "sliding_window": 8,
    "rope_parameters": ROPE, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "attention_bias": False, "use_sliding_window": True,
    "max_window_layers": 0, "tie_word_embeddings": False, "vocab_size": 96,
    "assumed": {"seq_len": 32, "compute_dtype": "bfloat16",
                "initializer_range": 0.02, "router_aux_loss_coef": 0.01,
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TRAFFIC = {"driver": "benchmark.fit_mellum2:run",
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
    tiny._write(os.path.join(here, "configs", "mellum2-tiny.json"),
                TINY_MELLUM2)
    tiny._write(os.path.join(here, "traffic", "tiny-seq32-yarn.json"), TRAFFIC)
    tiny._write(os.path.join(here, "limits", CELL + ".json"), limits)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "mellum2-tiny", "source": "tests",
                             "file": "benchmark/configs/mellum2-tiny.json",
                             "reduced": [], "why": "a toy size for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "mellum2-tiny",
                               "traffic": "tiny-seq32-yarn", "chips": 1,
                               "why": "a toy cell for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return CELL
