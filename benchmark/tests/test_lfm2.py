"""What PR 35 added to the benchmark, held to the contract and run at tiny sizes
on the CPU (no number here is a device metric): the `lfm2-24b-a2b`
configuration and its cell, the `Family`, the FLOP and byte functions against
hand counts, the driver that lends `trace_lm` one more scope, the readers, and
`probe_lm.readings` failing the control and each fault under a toy limits
file."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import (cells, check, fit_lfm2, flops_lfm2, flops_lm, harness,
                       probe_lm, readers, readers_lfm2, trace_lm)
from benchmark.tests import tiny, tiny_lfm2
from benchmark.tests.contract import WIDTH

BENCH = cells.manifest()
CELL, CONFIG = "lfm2-24b-a2b.fit-seq32k", "lfm2-24b-a2b"
RATE = "train_tokens_per_s_per_chip"
SEED = 2 ** 31 + 29
# the catalog's row for the model (`model-configs` guide, architectures.jsonl):
# every number of its `config`, under the same key
PUBLISHED = {
    "conv_L_cache": 3, "conv_bias": False, "hidden_size": 2048,
    "intermediate_size": 11776, "max_position_embeddings": 128000,
    "model_type": "lfm2_moe", "moe_intermediate_size": 1536,
    "norm_eps": 1e-05, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_dense_layers": 2, "num_experts": 64, "num_experts_per_tok": 4,
    "num_hidden_layers": 40, "num_key_value_heads": 8,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 1, "use_expert_bias": True, "vocab_size": 65536,
    "layer_types": ["conv", "conv"] + ["full_attention", "conv", "conv",
                                       "conv"] * 9 + ["full_attention", "conv"]}
# the toy cell's limits, from toy readings on the CPU (bf16 against float32
# at widths of 64; sound largest / control smallest over four seeds):
# grad_norm_gap 0.0045 / 0.056, its median leaf 3.3e-4 / 6.1e-3,
# change_norm_gap 5.9e-3 / 0.71 (a leaf left unmoved reads 1), its median leaf
# 5.9e-4 / 2.1e-3 (a state unchanged reads 1), grad_diff_best_leaf 6.9e-3 /
# 0.10
LIMITS = {"grad_norm_gap": 0.02, "grad_norm_gap_median_leaf": 1.5e-3,
          "change_norm_gap": 0.05, "change_norm_gap_median_leaf": 1.1e-3,
          "grad_diff_best_leaf": 0.03}


def _entry():
    return next(c for c in BENCH["configs"] if c["name"] == CONFIG)


def _file():
    with open(os.path.join(cells.ROOT, _entry()["file"])) as f:
        return json.load(f)


# -- the entries, held to the contract ----------------------------------------

def test_every_published_key_is_there_at_its_value_or_listed_as_reduced():
    entry, cfg = _entry(), _file()
    assert len(PUBLISHED["layer_types"]) == 40
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/LiquidAI/LFM2-24B-A2B/blob/main/config.json")
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            # cut, with the published value beside it
            assert cfg[key] != value, key
            said = len(value) if isinstance(value, list) else value
            assert f"{said} published" in cfg["reduced"][key].replace(
                "the published 40", "40 published"), key
        else:
            assert cfg[key] == value, key


@pytest.mark.parametrize("key", _entry()["reduced"])
def test_no_cut_names_a_width(key):
    assert not WIDTH.search(key), key


def test_the_cut_is_one_dense_layer_and_one_whole_period():
    cfg = _file()
    assert cfg["layer_types"] == PUBLISHED["layer_types"][1:6] == [
        "conv", "full_attention", "conv", "conv", "conv"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_dense_layers"] == 1
    assert (cfg["num_experts"], cfg["router_num_experts"],
            cfg["experts_held_offset"]) == (8, 64, 0)
    assert cfg["vocab_size"] * 8 == 65536
    assert cfg["assumed"]["seq_len"] == 32768
    assert cfg["assumed"]["tie_embeddings"] is True
    for part in ("deployment", "assumed"):
        assert cfg[part]


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_moved():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "resnet50.fit-hostfed", "trinity-mini.fit-seq8k", CELL]
    assert [c["name"] for c in BENCH["configs"]][-1] == CONFIG
    cell = BENCH["workloads"][-1]
    assert cell == dict(cell, config=CONFIG, traffic="fit-seq32k", chips=1)
    assert len(cell["why"]) <= 200 and len(_entry()["why"]) <= 200
    # three metrics of its own, the tail of `per_layer`; every other it
    # reports is the other decoder cell's, with its name appended
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == [
        m["name"] for m in own] == [
            "train_conv_device_share", "train_conv_roofline",
            "train_attn_kernel_roofline.lfm2"]
    assert all(m["moves"] == RATE and m["unit"] == "%" for m in own)
    shared = [m for m in BENCH["per_layer"]
              if CELL in m.get("workloads", []) and m not in own]
    assert all(m["workloads"] == ["trinity-mini.fit-seq8k", CELL]
               for m in shared)
    assert len(shared) == 18
    # the accepted attention roofline counts Trinity's layer kinds: not ours
    other = next(m for m in BENCH["per_layer"]
                 if m["name"] == "train_attn_kernel_roofline")
    assert other["workloads"] == ["trinity-mini.fit-seq8k"]
    resolved = cells.resolve(CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {RATE, "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == (
        {"compile_s"} | {m["name"] for m in own + shared})


def test_the_traffic_is_one_row_of_32768_tokens_four_steps_a_call():
    traffic = cells.resolve(CELL)["traffic"]
    other = cells.resolve("trinity-mini.fit-seq8k")["traffic"]
    assert (traffic["batch"], traffic["items_per_row"],
            traffic["steps_per_call"], traffic["row_sets"]) == (1, 32768, 4, 4)
    assert traffic["driver"] == "benchmark.fit_lfm2:run"
    assert traffic["rate_metric"] == RATE and traffic["fused"] is False
    same = ("feature_set", "epoch_order", "check_steps", "reference_row_block",
            "trace_seconds", "module_pattern")
    assert {k: traffic[k] for k in same} == {k: other[k] for k in same}


def test_the_limits_name_the_numbers_the_other_decoder_cell_compares():
    mine = cells.resolve(CELL)["limits"]
    assert set(mine) == set(LIMITS) == set(
        cells.resolve("trinity-mini.fit-seq8k")["limits"])
    assert all(0 < v < 1 for v in mine.values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(cells.HERE, "reference", "lfm2.py")) as f:
        text = f.read()
    assert "analytics_zoo_tpu" not in text.split('"""', 2)[2]
    assert "from benchmark" not in text and "import benchmark" not in text


# -- operations and bytes against hand counts -----------------------------------

def _cfg():
    return cells.resolve(CELL)["config"]


def test_the_forward_pass_is_506_5_mflop_a_token():
    cfg, seq = _cfg(), 32768
    d = 2048
    conv = 8 * d * d + 2 * d * 4                 # W_in, W_out, 3 taps, 2 gates
    dense = conv + 3 * 2 * d * 11776
    held = 4 * 8 / 64 * 3 * 2 * d * 1536         # half an assignment a token
    conv_moe = conv + 2 * d * 64 + held
    attn_moe = (2 * d * (32 + 8 + 8) * 64 + 2 * 32 * 64 * d
                + 2 * d * 64 + held)
    scores = 4 * 64 * 32 * (seq + 1) / 2         # causal keys, 32 heads x 64
    head = 2 * d * 8192
    want = dense + 3 * conv_moe + attn_moe + scores + head
    assert [round(x / 1e6, 1) for x in (dense, 3 * conv_moe, attn_moe, scores,
                                        head)] == [178.3, 129.8, 30.7, 134.2,
                                                   33.6]
    got = flops_lfm2.lfm2_forward_flops(cfg, 1, seq)
    assert got == pytest.approx(seq * want, rel=1e-12)
    assert round(got / seq / 1e6, 1) == 506.5
    assert round(3 * got / 1e12, 1) == 49.8      # a step, backward included
    # the held experts by what the counters saw, not by the uniform share
    more = flops_lfm2.lfm2_forward_flops(cfg, 1, seq, 4 * seq * 0.5 + 1000)
    assert more - got == pytest.approx(
        flops_lm.expert_forward_flops(cfg, 1000))


def test_the_attention_kernels_work_is_the_full_layers_alone():
    cfg, seq = _cfg(), 32768
    one_layer = 4 * 64 * 32 * seq * (seq + 1) / 2
    assert flops_lfm2.attention_kernel_forward_flops(cfg, 1, seq) == one_layer
    # the accepted function would count all five layers as full attention
    assert flops_lm.attention_kernel_forward_flops(
        dict(cfg, sliding_window=None, head_dim=64), 1, seq) == 5 * one_layer
    q, kv = seq * 32 * 64 * 2, seq * 8 * 64 * 2
    assert flops_lfm2.attention_kernel_bytes(cfg, 1, seq) == 6 * q + 6 * kv
    assert flops_lfm2.head_dim(cfg) == 64


def test_the_convolutions_operations_and_bytes():
    cfg, d = _cfg(), 2048
    pairs = 4 * 32768.0                          # four conv layers a step
    assert flops_lfm2.conv_forward_flops(cfg, pairs) == pairs * (
        8 * d * d + 2 * d * 4)
    weights = 4 * (4 * d * d + 3 * d) * 2
    assert flops_lfm2.conv_bytes(cfg, pairs, 4) == (
        pairs * 5 * d * 2 + 3 * weights)
    # compute-bound on a v5e: 13.2 TFLOP against 3.1 GB a step
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert (3 * flops_lfm2.conv_forward_flops(cfg, pairs) / peaks["bf16_flops"]
            > 10 * flops_lfm2.conv_bytes(cfg, pairs, 4)
            / peaks["hbm_bytes_per_s"])


# -- the readers --------------------------------------------------------------

def _ctx(scope_s, kernel_s, pairs=4 * 32768.0, steps=2.0):
    return {"counters": {
        "setup_end": {}, "window_start": {},
        "window_end": {"zoo_lm_conv_token_layers_total": pairs * steps,
                       "zoo_train_steps_total": steps}},
        "kernels": {"module_s": 2.0, "module_calls": 2.0, "scope_s": scope_s,
                    "kernel_s": kernel_s, "kernel_calls": {}},
        "lm": {"cfg": _cfg(), "rows": 1, "seq": 32768},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0}}


def test_the_conv_roofline_is_least_time_over_the_scopes_time():
    ctx = _ctx({"conv.short": 0.4}, {})
    least = 3 * flops_lfm2.conv_forward_flops(_cfg(), 4 * 32768.0) / 197e12
    assert readers_lfm2.conv_roofline(ctx) == pytest.approx(
        least / 0.2 * 100.0)
    assert 30 < readers_lfm2.conv_roofline(ctx) < 40
    spec = {"reader": "benchmark.readers_lm:device_share",
            "args": {"scopes": ["conv.short"]}}
    assert readers.call(spec, ctx) == pytest.approx(20.0)
    # a program with no such scope or counter (a parent commit): nothing
    assert readers_lfm2.conv_roofline(_ctx({}, {})) is None
    assert readers_lfm2.conv_roofline(_ctx({"conv.short": 0.4}, {}, 0.0)) is None
    assert readers.call(spec, _ctx({}, {})) is None


def test_the_attention_roofline_is_the_full_layers_work_over_the_kernels_time():
    ctx = _ctx({}, {"flash_fwd": 0.1, "flash_dq": 0.1, "flash_dkv": 0.2})
    least = 3 * flops_lfm2.attention_kernel_forward_flops(
        _cfg(), 1, 32768) / 197e12
    assert readers_lfm2.attn_kernel_roofline(ctx) == pytest.approx(
        least / 0.2 * 100.0)
    assert readers_lfm2.attn_kernel_roofline(_ctx({}, {})) is None


def test_the_new_metrics_files_name_their_readers():
    for name in ("train_conv_device_share", "train_conv_roofline",
                 "train_attn_kernel_roofline.lfm2"):
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert callable(cells.load(spec["reader"]))


# -- the driver, at toy sizes -----------------------------------------------------

def test_the_scope_is_lent_for_the_length_of_a_run_only():
    before = trace_lm.SCOPES
    assert "conv.short" not in before and len(before) == 7
    with fit_lfm2.scopes_beside(fit_lfm2.SCOPES):
        assert trace_lm.SCOPES == before + ("conv.short",)
        text = ('  %fusion.1 = f32[2] fusion(), metadata={op_name="jit(train_'
                'step)/transpose(jvp(conv.short))/mul"}\n'
                '  %fusion.2 = f32[2] fusion(), metadata={op_name="jit(train_'
                'step)/attn.full/dot"}')
        assert trace_lm.scope_map(text) == {"fusion.1": "conv.short",
                                            "fusion.2": "attn.full"}
    assert trace_lm.SCOPES is before
    with pytest.raises(RuntimeError):
        with fit_lfm2.scopes_beside(fit_lfm2.SCOPES):
            raise RuntimeError("a run that fails")
    assert trace_lm.SCOPES is before
    assert trace_lm.scope_map(text) == {"fusion.2": "attn.full"}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_lfm2"))
    tiny_lfm2.add_cell(made, LIMITS)
    return made


@pytest.fixture(scope="module")
def sound(root):
    cell = cells.resolve(tiny_lfm2.CELL, root)
    scopes = []
    step_text = trace_lm.scope_map

    def seen(text):
        scopes.append(trace_lm.SCOPES)
        return step_text(text)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace_lm, "scope_map", seen)
        run = cells.load(cell["traffic"]["driver"])(
            cell, SEED, 0.5, True, time.perf_counter(), any_platform=True)
    return cell, run, scopes


def test_a_sound_run_is_correct_and_counts_the_mixers_work(sound):
    cell, run, _ = sound
    line = harness.result_line(cell, run["device"], run, False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {RATE, "setup_s"}
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    json.dumps(line)
    # tokens x conv layers of the window's steps, from what the steps returned
    pairs = readers._delta(run["ctx"], "zoo_lm_conv_token_layers_total")
    assert pairs == line["attempted"] * 32 * 2
    assert run["ctx"]["window_flops"] > 0
    assert trace_lm.SCOPES == ("attn.window", "attn.full", "moe.route",
                               "moe.experts", "moe.shared", "lm.loss",
                               "optimizer")


def test_the_traced_line_leaves_out_what_the_cpu_cannot_read(sound):
    """On the CPU there is no device plane: the trace's metrics are left out
    and none raises; the counters' are there."""
    cell, run, _ = sound
    line = harness.result_line(cell, run["device"], run, True)
    assert {"compile_s", "train_moe_held_share", "train_moe_load_max_over_mean",
            "train_moe_compact_share"} <= set(line["metrics"])
    assert not {"train_conv_roofline", "train_conv_device_share",
                "train_attn_kernel_roofline.lfm2"} & set(line["metrics"])


def test_the_tree_the_harness_compares_has_the_tied_leaf_once(sound):
    _, run, _ = sound
    for tree in (run["seen"]["first"], run["seen"]["change"], run["start"],
                 run["want"]["first"], run["want"]["change"]):
        assert "head" not in tree and tree["embed"].shape == (96, 64)
        assert [sorted(p) for p in tree["layers"]][0] == [
            "conv", "ffn_norm", "mlp", "operator_norm"]
    assert (jax.tree_util.tree_structure(run["seen"]["first"])
            == jax.tree_util.tree_structure(run["want"]["first"]))
    moved = [float(np.abs(a).max())
             for a in jax.tree_util.tree_leaves(run["seen"]["change"])]
    assert min(moved) > 0


@pytest.fixture(scope="module")
def probed(root):
    """{kind: numbers} as `probe_lm.py` reads them on the chip: the sound
    program, the control and the planted faults (batch 1: no half batch)."""
    cell = cells.resolve(tiny_lfm2.CELL, root)
    device = harness.device
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "device",
                      lambda chips, _=False: device(chips, True))
        return cell, dict(probe_lm.readings(cell, SEED, True, 0.3, half=False))


@pytest.mark.parametrize("kind,caught_by", [
    ("control_lower_precision", "grad_diff_best_leaf"),
    ("fault_state_unchanged", "change_norm_gap_median_leaf"),
    ("fault_one_leaf_unmoved", "change_norm_gap")])
def test_the_control_and_planted_faults_are_not_correct(probed, kind,
                                                        caught_by):
    """Through `check.verdict` with the cell's limits file, as a run is."""
    cell, numbers = probed
    assert set(numbers) == {"program", "control_lower_precision",
                            "fault_state_unchanged", "fault_one_leaf_unmoved"}
    assert check.verdict(numbers["program"], cell["limits"])[0] is True
    ok, compared = check.verdict(numbers[kind], cell["limits"])
    assert ok is False
    value, limit = compared[caught_by]
    assert value > limit, (kind, compared)
