"""What the `mellum` cell adds to the benchmark, held to the contract and run
at tiny sizes on the CPU (no number here is a device metric): the
`mellum2-12b-a2.5b` configuration and its cell, the FLOP and byte functions
against hand counts, the driver that lends `trace_lm` the scope
`moe.balance`, the accepted readers one key name and the trace's reduction
the window kernels' seconds, the new reader, and the control and the planted
faults (`probe_mellum2.readings`) failing under a toy limits file."""

import json
import os
import time

import pytest

from benchmark import (cells, check, fit_lm, fit_mellum2, flops_lm,
                       flops_mellum2, harness, probe_mellum2, readers,
                       readers_mellum2, trace_lm)
from benchmark.tests import tiny, tiny_mellum2
from benchmark.tests.contract import WIDTH

BENCH = cells.manifest()
CELL, CONFIG = "mellum2-12b-a2.5b.fit-seq32k-yarn", "mellum2-12b-a2.5b"
OTHERS = ["trinity-mini.fit-seq8k", "lfm2-24b-a2b.fit-seq32k",
          "kanana-2-30b-a3b.fit-seq16k",
          "nemotron-twotower-30b-a3b.fit-seq8k-ssm"]
RATE = "train_tokens_per_s_per_chip"
SEED = 2 ** 31 + 29
SLIDING, FULL = "sliding_attention", "full_attention"
# the catalog's row for the model (`model-configs` guide, architectures.jsonl):
# every key of its `config`
PUBLISHED = {
    "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
    "hidden_size": 2304, "intermediate_size": 7168,
    "layer_types": [SLIDING, SLIDING, SLIDING, FULL] * 7,
    "mlp_layer_types": ["sparse"] * 28, "max_position_embeddings": 131072,
    "max_window_layers": 0, "model_type": "mellum",
    "moe_intermediate_size": 896, "norm_topk_prob": True,
    "num_attention_heads": 32, "num_experts": 64, "num_experts_per_tok": 8,
    "num_hidden_layers": 28, "num_key_value_heads": 4, "rms_norm_eps": 1e-06,
    "rope_parameters": {
        FULL: {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
               "original_max_position_embeddings": 8192, "beta_fast": 32,
               "beta_slow": 1, "attention_factor": 1.2772588722239782},
        SLIDING: {"rope_type": "default", "rope_theta": 500000}},
    "sliding_window": 1024, "tie_word_embeddings": False,
    "vocab_size": 98304, "use_sliding_window": True}
# the toy cell's limits, from toy readings on the CPU at SEED (bf16 against
# float32 at widths of 64, a balance coefficient of 0.01; sound / control /
# full layers' rotary left plain / balance term left out): grad_norm_gap
# 0.0024 / 0.094 / 0.42 / 0.028, its median leaf 5.4e-4 / 9.3e-3 / 0.027 /
# 6e-5, change_norm_gap 0.0089 / 0.019 / 0.014 / 0.028, its median leaf
# 1.0e-3 / 3.9e-3 / 2.9e-3 / 4.5e-4, grad_diff_best_leaf 0.0081 / 0.127 /
# 0.075 / 0, grad_diff_router_leaf 0.071 / 0.72 / 0.25 / 0.84,
# grad_diff_yarn_leaf 0.023 / 0.32 / 0.42 / 0.001
LIMITS = {"grad_norm_gap": 0.03, "grad_norm_gap_median_leaf": 3e-3,
          "change_norm_gap": 0.014, "change_norm_gap_median_leaf": 2.5e-3,
          "grad_diff_best_leaf": 0.04, "grad_diff_router_leaf": 0.2,
          "grad_diff_yarn_leaf": 0.12}


def _entry():
    return next(c for c in BENCH["configs"] if c["name"] == CONFIG)


def _file():
    with open(os.path.join(cells.ROOT, _entry()["file"])) as f:
        return json.load(f)


# -- the entries, held to the contract ----------------------------------------

def test_every_published_key_is_there_at_its_value_or_listed_as_reduced():
    entry, cfg = _entry(), _file()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/JetBrains/Mellum2-12B-A2.5B-Instruct/blob/main/"
        "config.json")
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types", "num_experts",
        "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            assert cfg[key] != value, key
        else:
            assert key in cfg and cfg[key] == value, key
    for key in ("num_hidden_layers", "num_experts", "vocab_size"):
        assert str(PUBLISHED[key]) in cfg["reduced"][key], key


@pytest.mark.parametrize("key", _entry()["reduced"])
def test_no_cut_names_a_width(key):
    assert not WIDTH.search(key), key


def test_the_cut_is_one_whole_period_of_the_first_four_layers():
    cfg = _file()
    assert cfg["layer_types"] == PUBLISHED["layer_types"][:4] == [
        SLIDING, SLIDING, SLIDING, FULL]
    assert cfg["mlp_layer_types"] == ["sparse"] * 4
    assert cfg["num_hidden_layers"] == 4
    assert (cfg["num_experts"], cfg["router_num_experts"],
            cfg["experts_held_offset"]) == (16, 64, 0)
    assert cfg["vocab_size"] * 8 == 98304
    assumed = cfg["assumed"]
    assert assumed["seq_len"] == 32768 and assumed["compute_dtype"] == "bfloat16"
    assert assumed["router_aux_loss_coef"] == 0.001
    assert assumed["optimizer"]["args"] == {"lr": 0.0001}
    for key in ("qk_norm", "multi_token_head", "balance_rule", "packing"):
        assert key in assumed, key
    for said in ("two expert-parallel groups of four", "16 of the 64",
                 "split over all eight chips", "layers 0-3"):
        assert said in cfg["deployment"], said


def test_the_cell_and_its_metric_are_appended_and_nothing_else_moved():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "resnet50.fit-hostfed"] + OTHERS + [CELL]
    assert [c["name"] for c in BENCH["configs"]][-1] == CONFIG
    cell = BENCH["workloads"][-1]
    assert cell == dict(cell, config=CONFIG, traffic="fit-seq32k-yarn",
                        chips=1)
    assert len(cell["why"]) <= 200 and len(_entry()["why"]) <= 200
    for said in ("32768", "1024", "8192", "4096 tokens", "1/4",
                 "attention all 32768"):
        assert said in cell["why"], said
    own = BENCH["per_layer"][-1]
    assert own == {"name": "train_window_kernel_roofline", "unit": "%",
                   "better": "higher", "source": "device_trace",
                   "layer": "kernels", "moves": RATE, "workloads": [CELL]}
    shared = [m for m in BENCH["per_layer"][:-1]
              if CELL in m.get("workloads", [])]
    assert len(shared) == 19
    assert all(m["workloads"][-1] == CELL for m in shared)
    assert {m["name"] for m in shared} >= {
        "train_moe_experts_roofline", "train_attn_kernel_roofline",
        "train_attn_device_share", "train_moe_held_share"}
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == RATE)
    assert rate["workloads"] == OTHERS + [CELL] and rate["bound"] == 0.04
    resolved = cells.resolve(CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {RATE, "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == (
        {"compile_s"} | {m["name"] for m in shared + [own]})
    with open(os.path.join(cells.HERE, "metrics",
                           "train_window_kernel_roofline.json")) as f:
        assert json.load(f)["reader"] == (
            "benchmark.readers_mellum2:window_kernel_roofline")


def test_the_traffic_is_fit_seq32ks_feed_through_the_mellum_driver():
    traffic = cells.resolve(CELL)["traffic"]
    other = cells.resolve("lfm2-24b-a2b.fit-seq32k")["traffic"]
    assert traffic["driver"] == "benchmark.fit_mellum2:run"
    assert {k: v for k, v in traffic.items() if k not in ("driver", "why")} == {
        k: v for k, v in other.items() if k not in ("driver", "why")}
    assert traffic["reference_row_block"] == traffic["batch"] == 1


def test_the_limits_name_the_other_decoder_cells_numbers_and_two_more():
    mine = cells.resolve(CELL)["limits"]
    assert set(mine) == set(LIMITS) == set(
        cells.resolve("trinity-mini.fit-seq8k")["limits"]) | {
            "grad_diff_router_leaf", "grad_diff_yarn_leaf"}
    assert all(0 < v < 1 for v in mine.values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(cells.HERE, "reference", "mellum2.py")) as f:
        text = f.read()
    assert "analytics_zoo_tpu" not in text.split('"""', 2)[2]
    assert "pallas" not in text and "flash" not in text
    imports = [line for line in text.splitlines()
               if line.startswith(("import ", "from "))]
    assert [line for line in imports if "benchmark" in line] == [
        "from benchmark.reference.kanana2 import _blocks, _rms",
        "from benchmark.reference.lfm2 import attention as full_attention"]


# -- operations and bytes against hand counts -----------------------------------

def _cfg():
    return cells.resolve(CELL)["config"]


def test_the_forward_pass_is_644_75_mflop_a_token():
    """The hand count by part: projections, routers, held experts at the
    uniform share, the head, the full layer's and the three windows'
    cores."""
    cfg, seq, d = _cfg(), 32768, 2304
    projections = 4 * 2 * (d * 5120 + 4096 * d)
    routers = 4 * 2 * d * 64
    experts = 4 * 8 * 16 / 64 * 2 * 3 * d * 896
    head = 2 * d * 12288
    full = 4 * 32 * 128 * (seq + 1) / 2
    window = 3 * 4 * 32 * 128 * (1024 * 1025 / 2 + (seq - 1024) * 1024) / seq
    assert [round(x / 1e6, 2) for x in (projections, routers, experts, head,
                                        full, window)] == [
        169.87, 1.18, 99.09, 56.62, 268.44, 49.55]
    total = projections + routers + experts + head + full + window
    assert round(total / 1e6, 2) == 644.75
    assert round((full + window) / total * 100) == 49
    got = flops_mellum2.mellum2_forward_flops(cfg, 1, seq)
    assert got == pytest.approx(seq * total, rel=1e-12)
    assert round(3 * got / 1e12, 1) == 63.4
    # the held experts by what the counters saw, not by the uniform share
    more = flops_mellum2.mellum2_forward_flops(
        cfg, 1, seq, flops_mellum2.held_assignments(cfg, 1, seq) + 1000)
    assert more - got == pytest.approx(flops_lm.expert_forward_flops(cfg,
                                                                     1000))


def test_the_window_pairs_and_bytes():
    cfg = _cfg()
    pairs = flops_mellum2.window_pairs(cfg, 1, 32768)
    assert pairs == 3 * (1024 * 1025 / 2 + (32768 - 1024) * 1024)
    assert flops_mellum2.pair_flops(cfg) == 4 * 128 * 32
    # q, k, v in and o out forward; q, k, v, o, do in and dq, dk, dv out
    q, kv = 32768 * 32 * 128 * 2, 32768 * 4 * 128 * 2
    assert flops_mellum2.window_kernel_bytes(cfg, 1, 32768) == 3 * (
        6 * q + 6 * kv)
    # compute-bound on a v5e by the count
    assert (3 * flops_mellum2.pair_flops(cfg) * pairs / 197e12
            > flops_mellum2.window_kernel_bytes(cfg, 1, 32768) / 819e9)
    # the accepted attention roofline's count reads this file as it is,
    # with the one key the driver lends
    lent = fit_mellum2.with_accepted_names(cfg)
    assert lent["num_dense_layers"] == 0
    assert {k: v for k, v in lent.items() if k in cfg} == cfg
    assert flops_lm.attention_pairs(lent, 1, 32768) == (
        flops_mellum2.attention_pairs(cfg, 1, 32768))


# -- the reader and the window kernels' seconds --------------------------------

def _ctx(window_s, pairs=None, steps=2.0):
    cfg = _cfg()
    pairs = flops_mellum2.window_pairs(cfg, 1, 32768) if pairs is None else pairs
    return {"counters": {
        "setup_end": {}, "window_start": {},
        "window_end": {"zoo_lm_window_pairs_total": pairs * steps,
                       "zoo_train_steps_total": steps}},
        "kernels": {"module_s": 2.0, "module_calls": 2.0, "scope_s": {},
                    "kernel_s": {}, "kernel_calls": {},
                    "window_kernel_s": window_s},
        "lm": {"cfg": cfg, "rows": 1, "seq": 32768},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0}}


def test_the_window_roofline_is_least_time_over_the_kernels_time():
    cfg = _cfg()
    least = (3 * flops_mellum2.pair_flops(cfg)
             * flops_mellum2.window_pairs(cfg, 1, 32768) / 197e12)
    got = readers_mellum2.window_kernel_roofline(_ctx(0.2))
    assert got == pytest.approx(least / 0.1 * 100.0)
    assert 0 < got < 100
    # a program without the counter (a parent commit), a trace without the
    # window kernels, or no trace at all (the CPU's): nothing
    assert readers_mellum2.window_kernel_roofline(_ctx(0.2, pairs=0.0)) is None
    assert readers_mellum2.window_kernel_roofline(_ctx(0.0)) is None
    assert readers_mellum2.window_kernel_roofline(
        dict(_ctx(0.2), kernels=None)) is None


def test_the_window_kernels_seconds_are_the_flash_kernels_under_the_scope():
    ops = [("%zoo_flash_fwd.1 = bf16[] custom-call()", 100, 300),
           ("%zoo_flash_dkv.2 = bf16[] custom-call()", 300, 700),
           ("%zoo_flash_fwd.3 = bf16[] custom-call()", 700, 800),
           ("%fusion.4 = bf16[] fusion()", 800, 900),
           ("%zoo_flash_fwd.5 = bf16[] custom-call()", 2000, 2100)]
    devices = {"/device:TPU:0": {"ops": ops, "modules": [
        ("jit_train_step", 0, 1000), ("jit_other", 1500, 2500)]}}
    scopes = {"zoo_flash_fwd.1": "attn.window", "zoo_flash_dkv.2": "attn.window",
              "zoo_flash_fwd.3": "attn.full", "fusion.4": "attn.window",
              "zoo_flash_fwd.5": "attn.window"}
    assert fit_mellum2.window_kernel_seconds(
        devices, "^jit_train_", scopes) == pytest.approx(600e-9)
    with fit_mellum2.window_kernels_beside():
        out = trace_lm.reduce(devices, "^jit_train_", scopes)
    assert out["window_kernel_s"] == pytest.approx(600e-9)
    assert "window_kernel_s" not in trace_lm.reduce(devices, "^jit_train_",
                                                    scopes)


def test_the_leaf_gaps_are_the_worst_router_and_full_layer_gain():
    import numpy as np

    def side(router, gain):
        layers = [{"router": np.ones(4) * router, "q_norm": np.ones(2),
                   "k_norm": np.ones(2)},
                  {"router": np.ones(4), "q_norm": np.ones(2) * gain,
                   "k_norm": np.ones(2)}]
        return {"losses": [1.0], "first": {"layers": layers},
                "change": {"layers": layers}}

    cfg = {"layer_types": [SLIDING, FULL]}
    got = fit_mellum2.fit_numbers(side(1.5, 1.25), side(1.0, 1.0), cfg)
    assert got["grad_diff_router_leaf"] == pytest.approx(0.5)
    assert got["grad_diff_yarn_leaf"] == pytest.approx(0.25)


# -- the driver, at toy sizes -----------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_mellum2"))
    tiny_mellum2.add_cell(made, LIMITS)
    return made


@pytest.fixture(scope="module")
def sound(root):
    cell = cells.resolve(tiny_mellum2.CELL, root)
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


def test_a_sound_run_is_correct_and_counts_the_window_pairs(sound):
    cell, run, scopes = sound
    assert scopes and all(s[-1:] == fit_mellum2.SCOPES for s in scopes)
    assert "moe.balance" not in trace_lm.SCOPES
    line = harness.result_line(cell, run["device"], run, False)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == set(LIMITS)
    assert set(line["metrics"]) == {RATE, "setup_s"}
    json.dumps(line)
    pairs = readers._delta(run["ctx"], "zoo_lm_window_pairs_total")
    assert pairs == line["attempted"] * 3 * (8 * 9 / 2 + 24 * 8)
    assert readers._delta(run["ctx"], "zoo_moe_balance_loss_count") == (
        line["attempted"])
    assert run["ctx"]["lm"]["cfg"]["num_dense_layers"] == 0
    assert "num_dense_layers" not in cell["config"]
    traced = harness.result_line(cell, run["device"], run, True)
    assert {"compile_s", "train_moe_held_share"} <= set(traced["metrics"])
    assert "train_window_kernel_roofline" not in traced["metrics"]


def test_a_row_block_other_than_the_batch_is_refused(root):
    cell = cells.resolve(tiny_mellum2.CELL, root)
    cell = dict(cell, traffic=dict(cell["traffic"], reference_row_block=2))
    with pytest.raises(ValueError, match="reference_row_block"):
        fit_mellum2.run(cell, SEED, 0.1, False, time.perf_counter(), True)


@pytest.fixture(scope="module")
def probed(root):
    cell = cells.resolve(tiny_mellum2.CELL, root)
    return cell, dict(probe_mellum2.readings(cell, SEED))


@pytest.mark.parametrize("kind,caught_by", [
    ("control_lower_precision", "grad_diff_best_leaf"),
    ("control_lower_precision", "grad_diff_router_leaf"),
    ("fault_no_yarn", "grad_diff_yarn_leaf"),
    ("fault_no_balance", "grad_diff_router_leaf")])
def test_the_control_and_planted_faults_are_not_correct(probed, sound, kind,
                                                        caught_by):
    cell, numbers = probed
    assert set(numbers) == {"reference", "control_lower_precision",
                            "fault_no_yarn", "fault_no_balance"}
    ok, compared = check.verdict(numbers[kind], cell["limits"])
    assert ok is False
    value, limit = compared[caught_by]
    assert value > limit, (kind, compared)
    # the probe's reference is the reference a run compares with
    assert numbers["reference"]["losses"] == pytest.approx(
        sound[1]["want"]["losses"], rel=1e-6)
