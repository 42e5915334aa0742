"""What the `nemotron_h` cell adds to the benchmark, held to the contract and
run at tiny sizes on the CPU (no number here is a device metric): the
`nemotron-twotower-30b-a3b` configuration and its cell, the `Family`, the FLOP
and byte functions against hand counts, the driver that lends `trace_lm` the
Mamba mixer's three scopes and the accepted readers three key names, the
readers, and the control and the planted fault (`probe_lm.readings`,
`probe_nemotronh.readings`) failing under a toy limits file."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import (cells, check, fit_lm, fit_nemotronh, flops_lm,
                       flops_nemotronh, harness, probe_lm, probe_nemotronh,
                       readers, readers_nemotronh, trace_lm)
from benchmark.tests import tiny, tiny_nemotronh
from benchmark.tests.contract import WIDTH

BENCH = cells.manifest()
CELL, CONFIG = ("nemotron-twotower-30b-a3b.fit-seq8k-ssm",
                "nemotron-twotower-30b-a3b")
OTHERS = ["trinity-mini.fit-seq8k", "lfm2-24b-a2b.fit-seq32k",
          "kanana-2-30b-a3b.fit-seq16k"]
RATE = "train_tokens_per_s_per_chip"
SEED = 2 ** 31 + 29
# the catalog's row for the model (`model-configs` guide, architectures.jsonl):
# every key of its `config`
PUBLISHED = {
    "attention_bias": False, "chunk_size": 128, "conv_kernel": 4, "expand": 2,
    "head_dim": 128, "hidden_size": 2688,
    "hybrid_override_pattern":
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME",
    "intermediate_size": 1856, "layer_norm_epsilon": 1e-05,
    "mamba_head_dim": 64, "mamba_hidden_act": "silu", "mamba_num_heads": 64,
    "mamba_proj_bias": False, "max_position_embeddings": 262144,
    "mlp_bias": False, "mlp_hidden_act": "relu2", "model_type": "nemotron_h",
    "moe_intermediate_size": 1856, "moe_shared_expert_intermediate_size": 3712,
    "n_group": 1, "n_groups": 8, "n_routed_experts": 128,
    "n_shared_experts": 1, "norm_eps": 1e-05, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts_per_tok": 6,
    "num_hidden_layers": 52, "num_key_value_heads": 2, "num_logits_to_keep": 1,
    "partial_rotary_factor": 1, "rescale_prenorm_residual": True,
    "residual_in_fp32": False, "rope_theta": 10000,
    "routed_scaling_factor": 2.5, "sliding_window": None,
    "ssm_state_size": 128, "tie_word_embeddings": False,
    "time_step_floor": 0.0001, "time_step_limit": [0, None],
    "time_step_max": 0.1, "time_step_min": 0.001, "topk_group": 1,
    "use_bias": False, "use_conv_bias": True, "use_mamba_kernels": True,
    "vocab_size": 131072}
# the toy cell's limits, from toy readings on the CPU at SEED's neighbour
# 2**31 + 29 (bf16 against float32 at widths of 64; sound / control / the
# gate after the norm): grad_norm_gap 0.0023 / 0.142 / 0.89, its median leaf
# 2.9e-4 / 0.023 / 0.41, change_norm_gap 0.0048 / 0.020 / 0.094 (a leaf left
# unmoved reads 1), its median leaf 7.2e-4 / 5.1e-3 / 0.013 (a state
# unchanged reads 1), grad_diff_best_leaf 0.0055 / 0.148 / 0.84,
# grad_diff_ssm_leaf 0.015 / 0.44 / 1.00
LIMITS = {"grad_norm_gap": 0.03, "grad_norm_gap_median_leaf": 3e-3,
          "change_norm_gap": 0.012, "change_norm_gap_median_leaf": 2.5e-3,
          "grad_diff_best_leaf": 0.04, "grad_diff_ssm_leaf": 0.12}


def _entry():
    return next(c for c in BENCH["configs"] if c["name"] == CONFIG)


def _file():
    with open(os.path.join(cells.ROOT, _entry()["file"])) as f:
        return json.load(f)


# -- the entries, held to the contract ----------------------------------------

def test_every_published_key_is_there_at_its_value_or_listed_as_reduced():
    entry, cfg = _entry(), _file()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/nvidia/Nemotron-Labs-TwoTower-30B-A3B-Base-"
        "BF16/blob/main/config.json")
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value, key
            assert str(value) in cfg["reduced"][key], key
        else:
            assert key in cfg and cfg[key] == value, key
    assert not {"layer_types", "num_dense_layers", "num_experts"} & set(cfg)


@pytest.mark.parametrize("key", _entry()["reduced"])
def test_no_cut_names_a_width(key):
    assert not WIDTH.search(key), key


def test_the_cut_is_the_first_nine_layers_in_the_published_ratio():
    cfg = _file()
    pattern = cfg["hybrid_override_pattern"]
    assert PUBLISHED["hybrid_override_pattern"].startswith(pattern)
    assert pattern == "MEMEM*EME" and cfg["num_hidden_layers"] == 9
    whole = PUBLISHED["hybrid_override_pattern"]
    assert [whole.count(c) for c in "ME*"] == [23, 23, 6]
    assert [pattern.count(c) for c in "ME*"] == [4, 4, 1]
    assert (cfg["n_routed_experts"], cfg["router_num_experts"],
            cfg["experts_held_offset"]) == (8, 128, 0)
    assert cfg["vocab_size"] * 8 == 131072
    assert cfg["assumed"]["seq_len"] == 8192
    assert cfg["assumed"]["rescale_prenorm_residual_layers"] == 52
    for said in ("sixteen chips", "8 of the 128", "split over 8 chips",
                 "layers 0-8"):
        assert said in cfg["deployment"], said
    for said in ("denoiser", "not built"):
        assert said in cfg["assumed"]["objective"], said
    assert "rope_theta" in cfg["assumed"]["unused"]
    assert cfg["assumed"]["optimizer"]["args"] == {"lr": 0.0001}


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_moved():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "resnet50.fit-hostfed"] + OTHERS + [CELL]
    assert [c["name"] for c in BENCH["configs"]][-1] == CONFIG
    cell = BENCH["workloads"][-1]
    assert cell == dict(cell, config=CONFIG, traffic="fit-seq8k-ssm", chips=1)
    assert len(cell["why"]) <= 200 and len(_entry()["why"]) <= 200
    for said in ("45 %", "768 tokens", "1/16", "attention all 16384"):
        assert said in cell["why"], said
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in BENCH["per_layer"]][-2:] == [
        m["name"] for m in own] == [
            "train_ssm_device_share", "train_ssm_scan_roofline"]
    assert all(m["moves"] == RATE and m["unit"] == "%" and
               m["source"] == "device_trace" for m in own)
    assert [(m["layer"], m["better"]) for m in own] == [
        ("train step", "lower"), ("kernels", "higher")]
    shared = [m for m in BENCH["per_layer"]
              if CELL in m.get("workloads", []) and m not in own]
    assert all(m["workloads"] == OTHERS + [CELL] for m in shared)
    assert len(shared) == 17
    for name in ("train_moe_experts_roofline", "train_attn_kernel_roofline",
                 "train_attn_kernel_roofline.lfm2",
                 "train_attn_kernel_roofline.kanana2"):
        other = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in other["workloads"]
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == RATE)
    assert rate["workloads"] == OTHERS + [CELL] and rate["bound"] == 0.04
    resolved = cells.resolve(CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {RATE, "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == (
        {"compile_s"} | {m["name"] for m in own + shared})


def test_the_manifest_is_as_the_parent_had_it_but_for_what_is_appended():
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names)) == 39
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "layer",
                          "moves", "workloads"}
        assert CELL not in m.get("workloads", [])[:-1]
    assert BENCH["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0


def test_the_traffic_is_fit_seq8ks_feed_through_the_ssm_driver():
    traffic = cells.resolve(CELL)["traffic"]
    other = cells.resolve("trinity-mini.fit-seq8k")["traffic"]
    assert traffic["driver"] == "benchmark.fit_nemotronh:run"
    assert {k: v for k, v in traffic.items() if k not in ("driver", "why")} == {
        k: v for k, v in other.items() if k not in ("driver", "why")}
    assert (traffic["batch"], traffic["items_per_row"],
            traffic["steps_per_call"], traffic["row_sets"],
            traffic["check_steps"], traffic["reference_row_block"]) == (
                2, 8192, 4, 4, 3, 1)


def test_the_limits_name_the_other_decoder_cells_numbers_and_one_more():
    mine = cells.resolve(CELL)["limits"]
    assert set(mine) == set(LIMITS) == set(
        cells.resolve("trinity-mini.fit-seq8k")["limits"]) | {
            "grad_diff_ssm_leaf"}
    assert all(0 < v < 1 for v in mine.values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(cells.HERE, "reference", "nemotronh.py")) as f:
        text = f.read()
    assert "analytics_zoo_tpu" not in text.split('"""', 2)[2]
    assert "pallas" not in text and "flash" not in text and "ssd" not in text
    imports = [line for line in text.splitlines()
               if line.startswith(("import ", "from "))]
    assert [line for line in imports if "benchmark" in line] == [
        "from benchmark.reference.kanana2 import _blocks, _rms, route, "
        "update_bias",
        "from benchmark.reference.trinity import attention"]


# -- operations and bytes against hand counts -----------------------------------

def _cfg():
    return cells.resolve(CELL)["config"]


def test_the_forward_pass_is_717_6_mflop_a_token():
    """The hand counts by layer kind, over 8192 causal keys (added from
    parts rounded first they read "about 718")."""
    cfg, seq, d = _cfg(), 8192, 2688
    mamba = 2 * d * (4096 + 6144 + 64) + 2 * 4096 * d
    scan = 2 * 128 * (8 * 128 + 64 * 64) + 2 * (2 + 1 / 128) * 64 * 64 * 128
    attention = (2 * d * (4096 + 512) + 2 * 4096 * d
                 + 2 * 2 * 128 * 32 * (seq + 1) / 2)
    experts = 2 * d * 128 + 2 * 2 * d * 3712 + 6 * 8 / 128 * 2 * 2 * d * 1856
    head = 2 * d * 16384
    assert [round(x / 1e6, 1) for x in (mamba, scan, attention, experts,
                                        head)] == [77.4, 3.4, 113.9, 48.1, 88.1]
    total = 4 * (mamba + scan) + attention + 4 * experts + head
    assert round(total / 1e6, 1) == 717.6
    # the Mamba layers' share of the work, and the attention layer's
    assert round(4 * (mamba + scan) / total * 100) == 45
    assert round(attention / total * 100) == 16
    got = flops_nemotronh.nemotronh_forward_flops(cfg, 2, seq)
    assert got == pytest.approx(2 * seq * total, rel=1e-12)
    assert round(3 * got / 1e12, 1) == 35.3
    # the held experts by what the counters saw, not by the uniform share
    more = flops_nemotronh.nemotronh_forward_flops(
        cfg, 2, seq, 2 * seq * 4 * 0.375 + 1000)
    assert more - got == pytest.approx(
        flops_nemotronh.held_expert_flops(cfg, 1000))
    assert flops_nemotronh.held_expert_flops(cfg, 1000) == pytest.approx(
        flops_lm.expert_forward_flops(dict(cfg, hidden_size=d), 1000) * 2 / 3)


def test_the_scans_four_parts_and_its_bytes():
    cfg = _cfg()
    parts = flops_nemotronh.scan_parts(cfg)
    assert parts == {"within": 2 * 128 * (8 * 128 + 64 * 64),
                     "states": 2 * 64 * 64 * 128,
                     "across": 2 * 64 * 64 * 128 / 128,
                     "out": 2 * 64 * 64 * 128}
    assert sum(parts.values()) == 3_416_064
    pairs = 4 * 16384.0
    assert flops_nemotronh.scan_forward_flops(cfg, pairs) == pairs * 3_416_064
    # x, B, C in and y out in bf16, dt in float32; three passes
    assert flops_nemotronh.scan_bytes(cfg, pairs) == 3 * pairs * (
        (4096 + 2048 + 4096) * 2 + 64 * 4)
    # memory-bound on a v5e by the count: 5.0 ms a step at 819 GB/s
    work = 3 * flops_nemotronh.scan_forward_flops(cfg, pairs) / 197e12
    least = flops_nemotronh.scan_bytes(cfg, pairs) / 819e9
    assert least > work and round(least * 1e3, 1) == 5.0


# -- the readers --------------------------------------------------------------

def _ctx(scope_s, pairs=4 * 16384.0, steps=2.0):
    return {"counters": {
        "setup_end": {}, "window_start": {},
        "window_end": {"zoo_lm_ssm_token_layers_total": pairs * steps,
                       "zoo_train_steps_total": steps}},
        "kernels": {"module_s": 2.0, "module_calls": 2.0, "scope_s": scope_s,
                    "kernel_s": {}, "kernel_calls": {}},
        "lm": {"cfg": _cfg(), "rows": 2, "seq": 8192},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0}}


def test_the_scan_roofline_is_least_time_over_the_scopes_time():
    ctx = _ctx({"ssm.scan": 0.04, "ssm.proj_in": 0.1, "ssm.proj_out": 0.06})
    least = flops_nemotronh.scan_bytes(_cfg(), 4 * 16384.0) / 819e9
    assert readers_nemotronh.scan_roofline(ctx) == pytest.approx(
        least / 0.02 * 100.0)
    assert 20 < readers_nemotronh.scan_roofline(ctx) < 30
    with open(os.path.join(cells.HERE, "metrics",
                           "train_ssm_device_share.json")) as f:
        spec = json.load(f)
    assert readers.call(spec, ctx) == pytest.approx(10.0)
    # a program with no such scope or counter (a parent commit): nothing
    assert readers_nemotronh.scan_roofline(_ctx({})) is None
    assert readers_nemotronh.scan_roofline(_ctx({"ssm.scan": 0.04}, 0.0)) is None
    assert readers_nemotronh.scan_roofline(
        dict(_ctx({}), kernels=None)) is None                    # the CPU's
    assert readers.call(spec, _ctx({})) is None


def test_the_new_metrics_files_name_their_readers():
    for name, reader in (
            ("train_ssm_device_share", "benchmark.readers_lm:device_share"),
            ("train_ssm_scan_roofline",
             "benchmark.readers_nemotronh:scan_roofline")):
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert spec["reader"] == reader and callable(cells.load(reader))


def test_the_lent_names_are_derived_from_the_pattern():
    cfg = _cfg()
    lent = fit_nemotronh.with_accepted_names(cfg)
    assert lent["layer_types"] == [
        "mamba", "experts", "mamba", "experts", "mamba", "full_attention",
        "experts", "mamba", "experts"]
    assert (lent["num_dense_layers"], lent["num_experts"]) == (0, 8)
    assert {k: v for k, v in lent.items() if k in cfg} == cfg


# -- the driver, at toy sizes -----------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_nemotronh"))
    tiny_nemotronh.add_cell(made, LIMITS)
    return made


@pytest.fixture(scope="module")
def sound(root):
    cell = cells.resolve(tiny_nemotronh.CELL, root)
    scopes = []
    first_steps = fit_lm.first_steps

    def seen(*args):
        scopes.append(trace_lm.SCOPES)       # what the run's scopes are
        return first_steps(*args)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fit_lm, "first_steps", seen)
        run = cells.load(cell["traffic"]["driver"])(
            cell, SEED, 0.5, True, time.perf_counter(), any_platform=True)
    return cell, run, scopes


def test_the_scopes_are_lent_for_the_length_of_a_run_only(sound):
    _, _, scopes = sound
    assert scopes and all(s[-3:] == fit_nemotronh.SCOPES for s in scopes)
    assert trace_lm.SCOPES == ("attn.window", "attn.full", "moe.route",
                               "moe.experts", "moe.shared", "lm.loss",
                               "optimizer")


def test_a_sound_run_is_correct_and_counts_the_mixers_work(sound):
    cell, run, _ = sound
    line = harness.result_line(cell, run["device"], run, False)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == set(LIMITS)
    assert 0 < line["compared"]["grad_diff_ssm_leaf"][0] < 0.05
    assert set(line["metrics"]) == {RATE, "setup_s"}
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    json.dumps(line)
    # tokens x Mamba layers of the window's steps, from what the steps returned
    pairs = readers._delta(run["ctx"], "zoo_lm_ssm_token_layers_total")
    assert pairs == line["attempted"] * 2 * 32 * 2
    assert run["ctx"]["window_flops"] > 0
    assert run["ctx"]["lm"]["cfg"]["num_experts"] == 4
    assert "num_experts" not in cell["config"]


def test_the_traced_line_leaves_out_what_the_cpu_cannot_read(sound):
    cell, run, _ = sound
    line = harness.result_line(cell, run["device"], run, True)
    assert {"compile_s", "train_moe_held_share", "train_moe_load_max_over_mean",
            "train_moe_compact_share"} <= set(line["metrics"])
    assert not {"train_ssm_device_share", "train_ssm_scan_roofline",
                "train_moe_experts_roofline"} & set(line["metrics"])


def test_the_tree_the_harness_compares_keeps_every_projection_apart(sound):
    _, run, _ = sound
    for tree in (run["seen"]["first"], run["seen"]["change"], run["start"],
                 run["want"]["first"], run["want"]["change"]):
        assert tree["head"].shape == (64, 96) and tree["embed"].shape == (96, 64)
        assert sorted(tree["layers"][0]) == [
            "A_log", "D", "conv_b", "conv_w", "dt_bias", "gate_norm", "norm",
            "w_b", "w_c", "w_dt", "w_out", "w_x", "w_z"]
        assert sorted(tree["layers"][3]) == ["norm", "wk", "wo", "wq", "wv"]
        assert tree["layers"][1]["experts"]["w_up"].shape == (4, 64, 32)
        assert tree["layers"][1]["shared"]["w_down"].shape == (48, 64)
    assert (jax.tree_util.tree_structure(run["seen"]["first"])
            == jax.tree_util.tree_structure(run["want"]["first"]))
    moved = [float(np.abs(a).max())
             for a in jax.tree_util.tree_leaves(run["seen"]["change"])]
    assert min(moved) > 0


@pytest.fixture(scope="module")
def probed(root):
    """{kind: numbers} as `probe_lm.py` and `probe_nemotronh.py` read them on
    the chip: the sound program, the control and the planted faults."""
    cell = cells.resolve(tiny_nemotronh.CELL, root)
    device = harness.device
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "device",
                      lambda chips, _=False: device(chips, True))
        numbers = dict(probe_lm.readings(cell, SEED, True, 0.3, half=False))
        numbers.update({"reference_alone/" + k: v
                        for k, v in probe_nemotronh.readings(cell, SEED)})
    return cell, numbers


def _five(cell):
    return {k: v for k, v in cell["limits"].items()
            if k != "grad_diff_ssm_leaf"}


@pytest.mark.parametrize("kind,caught_by", [
    ("control_lower_precision", "grad_diff_best_leaf"),
    ("fault_state_unchanged", "change_norm_gap_median_leaf"),
    ("fault_one_leaf_unmoved", "change_norm_gap"),
    ("reference_alone/control_lower_precision", "grad_diff_ssm_leaf"),
    ("reference_alone/fault_gate_after_norm", "grad_diff_ssm_leaf")])
def test_the_control_and_planted_faults_are_not_correct(probed, kind,
                                                        caught_by):
    cell, numbers = probed
    assert set(numbers) == {
        "program", "control_lower_precision", "fault_state_unchanged",
        "fault_one_leaf_unmoved", "reference_alone/reference",
        "reference_alone/control_lower_precision",
        "reference_alone/fault_gate_after_norm"}
    limits = cell["limits"] if "/" in kind else _five(cell)
    assert check.verdict(numbers["program"], _five(cell))[0] is True
    ok, compared = check.verdict(numbers[kind], limits)
    assert ok is False
    value, limit = compared[caught_by]
    assert value > limit, (kind, compared)


def test_the_ssm_leaf_gap_is_the_worst_scan_leaf():
    def side(a_log):
        layers = [{"A_log": np.ones(4) * a_log, "dt_bias": np.ones(4),
                   "D": np.ones(4), "conv_w": np.ones((6, 4))},
                  {"norm": np.ones(4)}]
        return {"losses": [1.0], "first": {"layers": layers},
                "change": {"layers": layers}}

    got = fit_nemotronh.fit_numbers(side(1.5), side(1.0))
    assert got["grad_diff_ssm_leaf"] == pytest.approx(0.5)


def test_the_reference_alone_is_the_reference_a_run_compares_with(probed,
                                                                  sound):
    _, numbers = probed
    _, run, _ = sound
    assert numbers["reference_alone/reference"]["losses"] == pytest.approx(
        run["want"]["losses"], rel=1e-6)
