"""A tiny `nemotron_h` cell for the CPU tests, ADDED beside the copied
benchmark like the other tiny cells: five one-part layers at toy widths
(`MEM*E`: two Mamba-2 mixers of 8 heads of 8 over 2 groups of 16, scanned in
chunks of 8; one attention layer; two expert layers of 4 of 8 squared-ReLU
experts held from number 2 beside a shared one), a head of its own, batch 2 as
the real cell has it."""

import json
import os

from benchmark.tests import tiny

CELL = "nemotronh-tiny.tiny-seq32-ssm"
REAL = "nemotron-twotower-30b-a3b.fit-seq8k-ssm"
TINY_NEMOTRONH = {
    "program": "benchmark.models_nemotronh:nemotronh",
    "reference": "benchmark.reference.nemotronh",
    "rows": "benchmark.data_lm:next_token_rows",
    "flops": "benchmark.flops_nemotronh:nemotronh_forward_flops",
    "source": "tests", "model_type": "nemotron_h", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "mamba_num_heads": 8, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "time_step_limit": [0, None],
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "rescale_prenorm_residual": True, "layer_norm_epsilon": 1e-5,
    "tie_word_embeddings": False, "vocab_size": 96,
    "assumed": {"seq_len": 32, "compute_dtype": "bfloat16",
                "initializer_range": 0.02, "bias_rate": 0.001,
                "rescale_prenorm_residual_layers": 52,
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TRAFFIC = {"driver": "benchmark.fit_nemotronh:run",
           "feature_set": "benchmark.fit:hostfed_set",
           "epoch_order": "benchmark.fit:numpy_order", "fused": False,
           "batch": 2, "steps_per_call": 4, "items_per_row": 32,
           "check_steps": 3, "reference_row_block": 1, "trace_seconds": 1,
           "module_pattern": "^jit_train_", "row_sets": 4,
           "rate_metric": "train_tokens_per_s_per_chip"}


def add_cell(root: str, limits: dict) -> str:
    """The tiny cell, under `limits`, into a root that `tiny.make_root`
    made."""
    here = os.path.join(root, "benchmark")
    tiny._write(os.path.join(here, "configs", "nemotronh-tiny.json"),
                TINY_NEMOTRONH)
    tiny._write(os.path.join(here, "traffic", "tiny-seq32-ssm.json"), TRAFFIC)
    tiny._write(os.path.join(here, "limits", CELL + ".json"), limits)
    path = os.path.join(root, "BENCHMARK.json")
    with open(path) as f:
        bench = json.load(f)
    bench["configs"].append({"name": "nemotronh-tiny", "source": "tests",
                             "file": "benchmark/configs/nemotronh-tiny.json",
                             "reduced": [], "why": "a toy size for the CPU tests"})
    bench["workloads"].append({"name": CELL, "config": "nemotronh-tiny",
                               "traffic": "tiny-seq32-ssm", "chips": 1,
                               "why": "a toy cell for the CPU tests"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if REAL in m.get("workloads", []):
            m["workloads"].append(CELL)
    with open(path, "w") as f:
        json.dump(bench, f)
    return CELL
