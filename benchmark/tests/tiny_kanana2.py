"""A tiny `deepseek_v3` cell for the CPU tests, ADDED beside the copied
benchmark like `tiny_lfm2.py`'s: three latent-attention layers at toy widths
(queries and keys 24 wide, values 16, a latent of 32), one leading dense
layer, 4 of 8 experts held from number 2 with two shared experts, a head of
its own, batch 1 as the real cell has it."""

import json
import os

from benchmark.tests import tiny

CELL = "kanana2-tiny.tiny-seq1k"
REAL = "kanana-2-30b-a3b.fit-seq16k"
TINY_KANANA2 = {
    "program": "benchmark.models_kanana2:kanana2",
    "reference": "benchmark.reference.kanana2",
    "rows": "benchmark.data_lm:next_token_rows",
    "flops": "benchmark.flops_kanana2:kanana2_forward_flops",
    "source": "tests", "model_type": "deepseek_v3", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "qk_head_dim": 24,
    "v_head_dim": 16, "kv_lora_rank": 32, "q_lora_rank": None,
    "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "n_routed_experts": 4, "router_num_experts": 8, "experts_held_offset": 2,
    "n_shared_experts": 2, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "rope_theta": 1000000, "rope_interleave": True, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "vocab_size": 96,
    "assumed": {"seq_len": 32, "compute_dtype": "bfloat16",
                "initializer_range": 0.02, "bias_rate": 0.001,
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TRAFFIC = {"driver": "benchmark.fit_kanana2:run",
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
    tiny._write(os.path.join(here, "configs", "kanana2-tiny.json"),
                TINY_KANANA2)
    tiny._write(os.path.join(here, "traffic", "tiny-seq1k.json"), TRAFFIC)
    tiny._write(os.path.join(here, "limits", CELL + ".json"), limits)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "kanana2-tiny", "source": "tests",
                             "file": "benchmark/configs/kanana2-tiny.json",
                             "reduced": [], "why": "a toy size for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "kanana2-tiny",
                               "traffic": "tiny-seq1k", "chips": 1,
                               "why": "a toy cell for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return CELL
