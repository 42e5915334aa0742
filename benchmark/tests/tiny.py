"""A benchmark of tiny cells in a temporary directory, for the tests: the
real BENCHMARK.json and data files copied, and tiny configurations, traffic
mixes, limits and cells ADDED beside them: no copied file is edited. The
serving metrics, which no landed cell reports, come from held_out.json."""

import json
import os
import shutil

from benchmark import cells

TINY_BERT = {
    "program": "benchmark.models:bert", "reference": "benchmark.reference.bert",
    "rows": "benchmark.data:token_rows",
    "flops": "benchmark.flops:bert_forward_flops",
    "source": "tests", "hidden_size": 64,
    "intermediate_size": 128, "num_attention_heads": 4,
    "num_hidden_layers": 2, "max_position_embeddings": 32,
    "type_vocab_size": 2, "vocab_size": 1000, "initializer_range": 0.02,
    "attention_probs_dropout_prob": 0.0, "hidden_dropout_prob": 0.0,
    "assumed": {"num_labels": 2, "seq_len": 16, "compute_dtype": "bfloat16",
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:Adam",
                    "reference": "benchmark.reference.optim:Adam",
                    "args": {"lr": 0.001}}}}
TINY_RESNET = {
    "program": "benchmark.models:resnet50",
    "reference": "benchmark.reference.resnet50",
    "rows": "benchmark.data:image_rows",
    "flops": "benchmark.flops:resnet50_forward_flops",
    "source": "tests", "image_size": 32,
    "num_channels": 3, "stem_width": 64, "stage_widths": [64, 128, 256, 512],
    "stage_blocks": [3, 4, 6, 3], "num_labels": 10,
    "assumed": {"batch_norm_eps": 0.001, "compute_dtype": "bfloat16",
                "optimizer": {
                    "program": "analytics_zoo_tpu.keras.optimizers:SGD",
                    "reference": "benchmark.reference.optim:Momentum",
                    "args": {"lr": 0.01, "momentum": 0.9}}}}
TRAFFIC = {
    "tiny-hbm": {"driver": "benchmark.fit:run",
                 "feature_set": "benchmark.fit:hbm_set",
                 "epoch_order": "benchmark.fit:device_order", "fused": True,
                 "batch": 8,
                 "steps_per_call": 4, "items_per_row": 16, "check_steps": 3,
                 "reference_row_block": 4, "trace_seconds": 1,
                 "module_pattern": "^jit_train_"},
    "tiny-hostfed": {"driver": "benchmark.fit:run",
                     "feature_set": "benchmark.fit:hostfed_set",
                     "epoch_order": "benchmark.fit:numpy_order",
                     "fused": False, "uint8_pixels": True, "batch": 8, "steps_per_call": 4,
                     "items_per_row": 1, "check_steps": 3,
                     "reference_row_block": None, "trace_seconds": 1,
                     "module_pattern": "^jit_train_"},
    "tiny-callers": {"driver": "benchmark.callers:run", "callers": 8,
                     "rows": [1, 2, 4, 8], "weights": [8, 4, 2, 1],
                     "deck_repeats": 4, "models": 2, "pool_rows": 64, "check_requests": 16,
                     "reference_row_block": 16, "batcher": {},
                     "warm_seconds": 0.3, "trace_seconds": 1,
                     "module_pattern": "^jit_forward"},
}
# set from tiny CPU readings (sound runs read under a fifth of these; each
# planted fault reads five times over one of them): they belong to these toy
# sizes, not to the cells of the benchmark
LIMITS = {
    "bert-tiny.tiny-hbm": {"loss_gap_1": 2e-3, "loss_gap_2": 2e-3,
                           "loss_gap_3": 2e-3},
    "resnet-tiny.tiny-hostfed": {"loss_gap_1": 0.25, "loss_gap_2": 0.25,
                                 "loss_gap_3": 0.25, "grad_norm_gap": 0.7,
                                 "change_norm_gap": 0.7},
    "bert-tiny.tiny-callers": {"prob_gap": 2e-3},
}


def _write(path, obj):
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "x") as f:         # "x": never over an existing file
        json.dump(obj, f)


def make_root(tmp) -> str:
    """`tmp`/BENCHMARK.json and `tmp`/benchmark/ with the tiny cells added."""
    root = str(tmp)
    shutil.copytree(os.path.join(cells.HERE), os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = cells.manifest()
    here = os.path.join(root, "benchmark")
    held = cells.held_out()
    # the serving metrics for the toy serve cell; any other held-out entry
    # as it is
    served = set()
    for key in ("end_to_end", "per_layer"):
        for m in held[key]:
            if "bert-base.serve-callers" in m["workloads"]:
                served.add(m["name"])
                m = dict(m, workloads=[])
            bench[key].append(m)
    _write(os.path.join(here, "configs", "bert-tiny.json"), TINY_BERT)
    _write(os.path.join(here, "configs", "resnet-tiny.json"), TINY_RESNET)
    for name, t in TRAFFIC.items():
        _write(os.path.join(here, "traffic", name + ".json"), t)
    for name, lim in LIMITS.items():
        _write(os.path.join(here, "limits", name + ".json"), lim)
    for cfg in ("bert-tiny", "resnet-tiny"):
        bench["configs"].append({
            "name": cfg, "source": "tests",
            "file": f"benchmark/configs/{cfg}.json", "reduced": [],
            "why": "a toy size for the CPU tests"})
    fit, serve = [], []
    for cell in LIMITS:
        cfg, traffic = cell.split(".")
        bench["workloads"].append({"name": cell, "config": cfg,
                                   "traffic": traffic, "chips": 1,
                                   "why": "a toy cell for the CPU tests"})
        (serve if "callers" in cell else fit).append(cell)
    # the toy fit cells report what the image cell does (the decoder cell's
    # rate and its metrics are `tiny_lm`'s to join)
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in served:
            m["workloads"] += serve
        elif "resnet50.fit-hostfed" in m.get("workloads", []):
            m["workloads"] += fit
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return root
