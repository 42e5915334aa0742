"""What PR 37 added to the benchmark, held to the contract and run at tiny sizes
on the CPU (no number here is a device metric): the `kanana-2-30b-a3b`
configuration and its cell, the `Family`, the FLOP and byte functions against
hand counts, the driver that lends `trace_lm` one more scope and the accepted
readers three key names, the readers, and the control and the planted faults
(`probe_lm.readings`, `probe_kanana2.readings`) failing under a toy limits
file."""

import json
import os
import time

import jax
import numpy as np
import pytest

from benchmark import (cells, check, fit_kanana2, flops_kanana2, flops_lm,
                       harness, probe_kanana2, probe_lm, readers,
                       readers_kanana2, readers_lm, trace_lm)
from benchmark.tests import tiny, tiny_kanana2
from benchmark.tests.contract import WIDTH

BENCH = cells.manifest()
CELL, CONFIG = "kanana-2-30b-a3b.fit-seq16k", "kanana-2-30b-a3b"
OTHERS = ["trinity-mini.fit-seq8k", "lfm2-24b-a2b.fit-seq32k"]
RATE = "train_tokens_per_s_per_chip"
SEED = 2 ** 31 + 29
# the catalog's row for the model (`model-configs` guide, architectures.jsonl):
# every key of its `config`
PUBLISHED = {
    "attention_bias": False, "first_k_dense_replace": 1, "head_dim": 64,
    "hidden_act": "silu", "hidden_size": 2048, "intermediate_size": 6144,
    "kv_lora_rank": 512, "max_position_embeddings": 32768,
    "model_type": "deepseek_v3", "moe_intermediate_size": 768,
    "moe_layer_freq": 1, "n_group": 1, "n_routed_experts": 128,
    "n_shared_experts": 2, "norm_topk_prob": True, "num_attention_heads": 32,
    "num_experts_per_tok": 6, "num_hidden_layers": 48,
    "num_key_value_heads": 32, "q_lora_rank": None, "qk_head_dim": 192,
    "qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
    "rope_interleave": True, "rope_scaling": None, "rope_theta": 1000000,
    "routed_scaling_factor": 2.448, "scoring_func": "sigmoid",
    "tie_word_embeddings": False, "topk_group": 1, "topk_method": "noaux_tc",
    "v_head_dim": 128, "vocab_size": 128256}
# the toy cell's limits, from toy readings on the CPU (bf16 against float32
# at widths of 64; sound largest over four seeds / control smallest over two):
# grad_norm_gap 0.038 / 0.075, its median leaf 4.3e-4 / 5.4e-3,
# change_norm_gap 0.012 / 0.014 (a leaf left unmoved reads 1), its median leaf
# 6.8e-4 / 2.7e-3 (a state unchanged reads 1), grad_diff_best_leaf 0.010 /
# 0.119, grad_diff_rotary_leaf 0.029 / 0.23 over three seeds. The rotary
# embedding over halves reads as a sound program under the first five (0.013,
# 5.9e-4, 0.025, 7.9e-4, 5.8e-3 at most) and 0.77-1.02 under the sixth
LIMITS = {"grad_norm_gap": 0.06, "grad_norm_gap_median_leaf": 2e-3,
          "change_norm_gap": 0.05, "change_norm_gap_median_leaf": 1.5e-3,
          "grad_diff_best_leaf": 0.03, "grad_diff_rotary_leaf": 0.08}


def _entry():
    return next(c for c in BENCH["configs"] if c["name"] == CONFIG)


def _file():
    with open(os.path.join(cells.ROOT, _entry()["file"])) as f:
        return json.load(f)


# -- the entries, held to the contract ----------------------------------------

def test_every_published_key_is_there_at_its_value_or_listed_as_reduced():
    entry, cfg = _entry(), _file()
    assert entry["source"] == cfg["source"] == (
        "https://huggingface.co/kakaocorp/kanana-2-30b-a3b-instruct-2601/"
        "blob/main/config.json")
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "n_routed_experts", "vocab_size"}
    for key, value in PUBLISHED.items():
        if key in entry["reduced"]:
            # cut, with the published value beside it
            assert cfg[key] != value, key
            assert f"{value} published" in cfg["reduced"][key], key
        else:
            assert key in cfg and cfg[key] == value, key
    # the published names only: none of another family's for the same thing
    assert not {"layer_types", "num_dense_layers", "num_experts"} & set(cfg)


@pytest.mark.parametrize("key", _entry()["reduced"])
def test_no_cut_names_a_width(key):
    assert not WIDTH.search(key), key


def test_the_cut_is_the_dense_layer_and_five_expert_layers_of_a_stage():
    cfg = _file()
    assert (cfg["num_hidden_layers"], cfg["first_k_dense_replace"]) == (6, 1)
    assert 48 % cfg["num_hidden_layers"] == 0          # 8 stages of 6
    assert cfg["num_hidden_layers"] - cfg["first_k_dense_replace"] >= 4
    assert (cfg["n_routed_experts"], cfg["router_num_experts"],
            cfg["experts_held_offset"]) == (16, 128, 0)
    assert cfg["n_routed_experts"] >= 8 and cfg["vocab_size"] * 8 == 128256
    assert cfg["assumed"]["seq_len"] == 16384
    for said in ("16 of the 128", "1/8 of the vocabulary", "shared experts",
                 "eight times"):
        assert said in cfg["deployment"], said
    assert cfg["assumed"]["optimizer"]["args"] == {"lr": 0.0001}


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_moved():
    assert [w["name"] for w in BENCH["workloads"]] == [
        "resnet50.fit-hostfed"] + OTHERS + [CELL]
    assert [c["name"] for c in BENCH["configs"]][-1] == CONFIG
    cell = BENCH["workloads"][-1]
    assert cell == dict(cell, config=CONFIG, traffic="fit-seq16k", chips=1)
    assert len(cell["why"]) <= 200 and len(_entry()["why"]) <= 200
    # three metrics of its own, the tail of `per_layer`; every other it
    # reports is the other decoder cells', with its name appended
    own = [m for m in BENCH["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in BENCH["per_layer"]][-3:] == [
        m["name"] for m in own] == [
            "train_attn_kernel_roofline.kanana2", "train_latent_device_share",
            "train_latent_roofline"]
    assert all(m["moves"] == RATE and m["unit"] == "%" for m in own)
    assert [m["layer"] for m in own] == ["kernels", "train step", "train step"]
    shared = [m for m in BENCH["per_layer"]
              if CELL in m.get("workloads", []) and m not in own]
    assert all(m["workloads"] == OTHERS + [CELL] for m in shared)
    assert len(shared) == 18
    # the accepted attention rooflines count other layer kinds and one width
    for name in ("train_attn_kernel_roofline",
                 "train_attn_kernel_roofline.lfm2", "train_conv_roofline",
                 "train_conv_device_share"):
        other = next(m for m in BENCH["per_layer"] if m["name"] == name)
        assert CELL not in other["workloads"]
    rate = next(m for m in BENCH["end_to_end"] if m["name"] == RATE)
    assert rate["workloads"] == OTHERS + [CELL] and rate["bound"] == 0.04
    resolved = cells.resolve(CELL)
    assert {m["name"] for m in resolved["end_to_end"]} == {RATE, "setup_s"}
    assert {m["name"] for m in resolved["per_layer"]} == (
        {"compile_s"} | {m["name"] for m in own + shared})


def test_the_manifest_is_as_the_parent_had_it_but_for_what_is_appended():
    """Entry by entry against the lists with this PR's entries taken off:
    nothing in the middle, nothing edited but the appended cell names."""
    names = [m["name"] for m in BENCH["per_layer"]]
    assert len(names) == len(set(names)) == 37
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "layer",
                          "moves", "workloads"}
        lists = m.get("workloads", [])
        assert CELL not in lists[:-1]
    assert BENCH["run_seconds"] == 20
    assert sum(w["chips"] == 4 for w in BENCH["workloads"]) == 0


def test_the_traffic_is_one_row_of_16384_tokens_four_steps_a_call():
    traffic = cells.resolve(CELL)["traffic"]
    other = cells.resolve("lfm2-24b-a2b.fit-seq32k")["traffic"]
    assert (traffic["batch"], traffic["items_per_row"],
            traffic["steps_per_call"], traffic["row_sets"]) == (1, 16384, 4, 4)
    assert traffic["driver"] == "benchmark.fit_kanana2:run"
    assert traffic["rate_metric"] == RATE and traffic["fused"] is False
    same = ("feature_set", "epoch_order", "check_steps", "reference_row_block",
            "trace_seconds", "module_pattern", "batch", "steps_per_call")
    assert {k: traffic[k] for k in same} == {k: other[k] for k in same}


def test_the_limits_name_the_other_decoder_cells_numbers_and_one_more():
    mine = cells.resolve(CELL)["limits"]
    assert set(mine) == set(LIMITS) == set(
        cells.resolve("trinity-mini.fit-seq8k")["limits"]) | {
            "grad_diff_rotary_leaf"}
    assert all(0 < v < 1 for v in mine.values())


def test_the_reference_imports_nothing_of_the_program():
    with open(os.path.join(cells.HERE, "reference", "kanana2.py")) as f:
        text = f.read()
    assert "analytics_zoo_tpu" not in text.split('"""', 2)[2]
    assert "from benchmark" not in text and "import benchmark" not in text
    assert "pallas" not in text and "flash" not in text


# -- operations and bytes against hand counts -----------------------------------

def _cfg():
    return cells.resolve(CELL)["config"]


def test_the_forward_pass_is_589_7_mflop_a_token_outside_the_kernels():
    """(ISSUE 37's 316.2 and 589.8 add parts that were rounded first.)"""
    cfg, seq = _cfg(), 16384
    d = 2048
    latent = 2 * (d * 32 * 192 + d * (512 + 64) + 512 * 32 * 256)
    projections = 6 * (latent + 2 * 32 * 128 * d)
    dense = 3 * 2 * d * 6144
    held = 6 * 16 / 128 * 3 * 2 * d * 768          # 0.75 assignments a token
    expert_layers = 5 * (2 * d * 128 + 3 * 2 * d * 2 * 768 + held)
    head = 2 * d * 16032
    kernels = 6 * (2 * 192 + 2 * 128) * 32 * (seq + 1) / 2   # causal keys
    assert [round(x / 1e6, 1) for x in (projections, dense, expert_layers,
                                        head)] == [316.1, 75.5, 132.4, 65.7]
    assert round(3 * latent / 1e6, 1) == 107.7
    outside = projections + dense + expert_layers + head
    assert round(outside / 1e6, 1) == 589.7
    got = flops_kanana2.kanana2_forward_flops(cfg, 1, seq)
    assert got == pytest.approx(seq * (outside + kernels), rel=1e-12)
    assert round(seq * kernels / 1e12, 2) == 16.49
    assert round(got / 1e12, 1) == 26.2 and round(3 * got / 1e12, 1) == 78.5
    # the held experts by what the counters saw, not by the uniform share
    more = flops_kanana2.kanana2_forward_flops(cfg, 1, seq,
                                               5 * seq * 0.75 + 1000)
    assert more - got == pytest.approx(
        flops_lm.expert_forward_flops(cfg, 1000))


def test_the_attention_kernels_work_counts_both_widths():
    cfg, seq = _cfg(), 16384
    one_layer = (2 * 192 + 2 * 128) * 32 * seq * (seq + 1) / 2
    assert round(one_layer / 1e12, 3) == 2.749
    assert flops_kanana2.attention_kernel_forward_flops(
        cfg, 1, seq) == 6 * one_layer
    assert flops_kanana2.attention_kernel_forward_flops(
        cfg, 2, seq) == 12 * one_layer
    qk, vo = seq * 32 * 192 * 2, seq * 32 * 128 * 2
    assert flops_kanana2.attention_kernel_bytes(cfg, 1, seq) == 6 * (
        6 * qk + 6 * vo)
    assert flops_kanana2.qk_dim(cfg) == 192
    # compute-bound on a v5e: 49.5 TFLOP against 7.7 GB a step
    assert (3 * 6 * one_layer / 197e12
            > 10 * flops_kanana2.attention_kernel_bytes(cfg, 1, seq) / 819e9)


def test_the_latent_projections_operations_and_bytes():
    cfg, d = _cfg(), 2048
    pairs = 6 * 16384.0                           # six layers a step
    weights = d * 6144 + d * 576 + 512 * 8192
    assert flops_kanana2.latent_forward_flops(cfg, pairs) == pairs * 2 * weights
    assert flops_kanana2.latent_bytes(cfg, pairs, 6) == (
        pairs * (3 * d + 2 * 32 * (192 + 192 + 128)) * 2
        + 3 * 6 * weights * 2)
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    assert (3 * flops_kanana2.latent_forward_flops(cfg, pairs)
            / peaks["bf16_flops"]
            > 4 * flops_kanana2.latent_bytes(cfg, pairs, 6)
            / peaks["hbm_bytes_per_s"])


# -- the readers --------------------------------------------------------------

def _ctx(scope_s, kernel_s, pairs=6 * 16384.0, steps=2.0):
    return {"counters": {
        "setup_end": {}, "window_start": {},
        "window_end": {"zoo_lm_latent_token_layers_total": pairs * steps,
                       "zoo_train_steps_total": steps}},
        "kernels": {"module_s": 2.0, "module_calls": 2.0, "scope_s": scope_s,
                    "kernel_s": kernel_s, "kernel_calls": {}},
        "lm": {"cfg": _cfg(), "rows": 1, "seq": 16384},
        "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
        "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0}}


def test_the_latent_roofline_is_least_time_over_the_scopes_time():
    ctx = _ctx({"attn.latent": 0.2}, {})
    least = 3 * flops_kanana2.latent_forward_flops(_cfg(), 6 * 16384.0) / 197e12
    assert readers_kanana2.latent_roofline(ctx) == pytest.approx(
        least / 0.1 * 100.0)
    assert 50 < readers_kanana2.latent_roofline(ctx) < 60
    spec = {"reader": "benchmark.readers_lm:device_share",
            "args": {"scopes": ["attn.latent"]}}
    assert readers.call(spec, ctx) == pytest.approx(10.0)
    # a program with no such scope or counter (a parent commit): nothing
    assert readers_kanana2.latent_roofline(_ctx({}, {})) is None
    assert readers_kanana2.latent_roofline(
        _ctx({"attn.latent": 0.2}, {}, 0.0)) is None
    assert readers.call(spec, _ctx({}, {})) is None


def test_the_attention_roofline_is_both_widths_work_over_the_kernels_time():
    ctx = _ctx({}, {"flash_fwd": 0.4, "flash_dq": 0.4, "flash_dkv": 0.6})
    least = 3 * flops_kanana2.attention_kernel_forward_flops(
        _cfg(), 1, 16384) / 197e12
    assert readers_kanana2.attn_kernel_roofline(ctx) == pytest.approx(
        least / 0.7 * 100.0)
    assert 30 < readers_kanana2.attn_kernel_roofline(ctx) < 40
    assert readers_kanana2.attn_kernel_roofline(_ctx({}, {})) is None
    for reader in (readers_kanana2.attn_kernel_roofline,
                   readers_kanana2.latent_roofline):
        assert reader(dict(_ctx({}, {}), kernels=None)) is None  # the CPU's


def test_the_new_metrics_files_name_their_readers():
    for name in ("train_attn_kernel_roofline.kanana2",
                 "train_latent_device_share", "train_latent_roofline"):
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            spec = json.load(f)
        assert callable(cells.load(spec["reader"]))


def test_the_accepted_expert_roofline_reads_the_lent_names():
    """`readers_lm.moe_experts_roofline` reads `layer_types`,
    `num_dense_layers` and `num_experts`; the driver derives them from the
    published keys and the file itself does not hold them."""
    cfg = _cfg()
    lent = fit_kanana2.with_accepted_names(cfg)
    assert lent["layer_types"] == ["latent_attention"] * 6
    assert (lent["num_dense_layers"], lent["num_experts"]) == (1, 16)
    assert {k: v for k, v in lent.items() if k in cfg} == cfg
    ctx = _ctx({}, {"gmm": 0.04, "tgmm": 0.02})
    ctx["counters"]["window_end"]["zoo_moe_assignments_total_held"] = (
        2.0 * 5 * 16384 * 0.75)
    with pytest.raises(KeyError):
        readers_lm.moe_experts_roofline(ctx)
    ctx["lm"]["cfg"] = lent
    held = 5 * 16384 * 0.75
    least = max(3 * flops_lm.expert_forward_flops(lent, held) / 197e12,
                flops_lm.expert_kernel_bytes(lent, held, 5) / 819e9)
    assert readers_lm.moe_experts_roofline(ctx) == pytest.approx(
        least / 0.03 * 100.0)


# -- the driver, at toy sizes -----------------------------------------------------

@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_kanana2"))
    tiny_kanana2.add_cell(made, LIMITS)
    return made


@pytest.fixture(scope="module")
def sound(root):
    cell = cells.resolve(tiny_kanana2.CELL, root)
    scopes, cfgs = [], []
    step_text = trace_lm.scope_map

    def seen(text):
        scopes.append(trace_lm.SCOPES)
        return step_text(text)

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(trace_lm, "scope_map", seen)
        run = cells.load(cell["traffic"]["driver"])(
            cell, SEED, 0.5, True, time.perf_counter(), any_platform=True)
    return cell, run, scopes


def test_the_scope_is_lent_for_the_length_of_a_run_only(sound):
    before = trace_lm.SCOPES
    assert "attn.latent" not in before and len(before) == 7
    from benchmark import fit_lfm2

    with fit_lfm2.scopes_beside(fit_kanana2.SCOPES):
        assert trace_lm.SCOPES == before + ("attn.latent",)
        text = ('  %fusion.1 = f32[2] fusion(), metadata={op_name="jit(train_'
                'step)/transpose(jvp(attn.latent))/dot_general"}\n'
                '  %fusion.2 = f32[2] fusion(), metadata={op_name="jit(train_'
                'step)/attn.full/pallas_call"}')
        assert trace_lm.scope_map(text) == {"fusion.1": "attn.latent",
                                            "fusion.2": "attn.full"}
    assert trace_lm.SCOPES is before
    assert trace_lm.scope_map(text) == {"fusion.2": "attn.full"}


def test_a_sound_run_is_correct_and_counts_the_mixers_work(sound):
    cell, run, _ = sound
    line = harness.result_line(cell, run["device"], run, False)
    assert line["correct"] is True, line["compared"]
    assert set(line["compared"]) == set(LIMITS)
    assert 0 < line["compared"]["grad_diff_rotary_leaf"][0] < 0.04
    assert set(line["metrics"]) == {RATE, "setup_s"}
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    json.dumps(line)
    # tokens x latent layers of the window's steps, from what the steps returned
    pairs = readers._delta(run["ctx"], "zoo_lm_latent_token_layers_total")
    assert pairs == line["attempted"] * 32 * 3
    assert run["ctx"]["window_flops"] > 0
    # the readers got the configuration with the lent names; the cell's own
    # was left as the file has it
    assert run["ctx"]["lm"]["cfg"]["num_experts"] == 4
    assert "num_experts" not in cell["config"]
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
    assert not {"train_latent_roofline", "train_latent_device_share",
                "train_attn_kernel_roofline.kanana2",
                "train_moe_experts_roofline"} & set(line["metrics"])


def test_the_tree_the_harness_compares_keeps_every_projection_apart(sound):
    _, run, _ = sound
    for tree in (run["seen"]["first"], run["seen"]["change"], run["start"],
                 run["want"]["first"], run["want"]["change"]):
        assert tree["head"].shape == (64, 96) and tree["embed"].shape == (96, 64)
        assert sorted(tree["layers"][0]) == [
            "attn_norm", "ffn_norm", "kv_norm", "mlp", "w_c", "w_kr",
            "wk_nope", "wo", "wq_nope", "wq_rope", "wv"]
        assert tree["layers"][1]["w_kr"].shape == (64, 8)        # one head
        assert tree["layers"][1]["shared"]["w_down"].shape == (64, 64)
        assert tree["layers"][1]["experts"]["w_down"].shape == (4, 32, 64)
    assert (jax.tree_util.tree_structure(run["seen"]["first"])
            == jax.tree_util.tree_structure(run["want"]["first"]))
    moved = [float(np.abs(a).max())
             for a in jax.tree_util.tree_leaves(run["seen"]["change"])]
    assert min(moved) > 0


@pytest.fixture(scope="module")
def probed(root):
    """{kind: numbers} as `probe_lm.py` and `probe_kanana2.py` read them on
    the chip: the sound program, the control and the planted faults (batch 1:
    no half batch)."""
    cell = cells.resolve(tiny_kanana2.CELL, root)
    device = harness.device
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "device",
                      lambda chips, _=False: device(chips, True))
        numbers = dict(probe_lm.readings(cell, SEED, True, 0.3, half=False))
        numbers.update({"reference_alone/" + k: v
                        for k, v in probe_kanana2.readings(cell, SEED)})
    return cell, numbers


@pytest.mark.parametrize("kind,caught_by", [
    ("control_lower_precision", "grad_diff_best_leaf"),
    ("fault_state_unchanged", "change_norm_gap_median_leaf"),
    ("fault_one_leaf_unmoved", "change_norm_gap"),
    ("reference_alone/control_lower_precision", "grad_diff_rotary_leaf"),
    ("reference_alone/fault_rotary_halves", "grad_diff_rotary_leaf")])
def test_the_control_and_planted_faults_are_not_correct(probed, kind,
                                                        caught_by):
    """Through `check.verdict` with the cell's limits file, as a run is;
    `probe_lm.py` drives `fit_lm.run` itself and so reads the five numbers
    that gives, `probe_kanana2.py` all six."""
    cell, numbers = probed
    assert set(numbers) == {
        "program", "control_lower_precision", "fault_state_unchanged",
        "fault_one_leaf_unmoved", "reference_alone/reference",
        "reference_alone/control_lower_precision",
        "reference_alone/fault_rotary_halves"}
    limits = cell["limits"] if "/" in kind else _five(cell)
    assert check.verdict(numbers["program"], _five(cell))[0] is True
    ok, compared = check.verdict(numbers[kind], limits)
    assert ok is False
    value, limit = compared[caught_by]
    assert value > limit, (kind, compared)


def _five(cell):
    return {k: v for k, v in cell["limits"].items()
            if k != "grad_diff_rotary_leaf"}


def test_only_the_rotary_leaves_see_the_rotary_pairs_taken_as_halves(probed):
    """Under the five numbers the other decoder cells compare the fault reads
    as a sound program; the gradient of the two rotary projections is another
    one altogether."""
    cell, numbers = probed
    fault = numbers["reference_alone/fault_rotary_halves"]
    assert check.verdict(fault, _five(cell))[0] is True
    assert fault["grad_diff_rotary_leaf"] > 0.5
    # a leaf each of the reference's layers has, the shared key's among them
    run_numbers = fit_kanana2.fit_numbers(
        {"losses": [1.0], "first": {"layers": [
            {"wq_rope": np.ones((4, 4)), "w_kr": np.ones((4, 2))}]},
         "change": {"layers": [{"wq_rope": np.ones((4, 4)),
                                "w_kr": np.ones((4, 2))}]}},
        {"losses": [1.0], "first": {"layers": [
            {"wq_rope": np.ones((4, 4)), "w_kr": 2 * np.ones((4, 2))}]},
         "change": {"layers": [{"wq_rope": np.ones((4, 4)),
                                "w_kr": np.ones((4, 2))}]}})
    assert run_numbers["grad_diff_rotary_leaf"] == pytest.approx(0.5)


def test_the_reference_alone_is_the_reference_a_run_compares_with(probed,
                                                                  sound):
    """`probe_kanana2` starts from the seed's weights and not from the
    program's state: the same numbers, so the same losses and control."""
    _, numbers = probed
    _, run, _ = sound
    assert numbers["reference_alone/reference"]["losses"] == pytest.approx(
        run["want"]["losses"], rel=1e-6)
    for key, value in numbers["control_lower_precision"].items():
        if key.endswith("gap") or key.endswith("leaf"):
            assert numbers["reference_alone/control_lower_precision"][
                key] == pytest.approx(value, rel=1e-3)
