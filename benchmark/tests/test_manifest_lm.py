"""The entries PR 29 added to BENCHMARK.json, held to the contract's letters:
`vocab_size` (a vocabulary's slice) and `num_hidden_layers` (a depth) are cuts
the contract allows, a width is not (`contract.WIDTH`, which
`test_manifest.py` holds every configuration to). Since PR 32 the cell reports
its rate as `train_tokens_per_s_per_chip`, under a bound of its own, and what
it shared with the image cell under names of its own (`<name>.lm`)."""

import json
import os

import pytest

from benchmark import cells
from benchmark.tests.contract import WIDTH

BENCH = cells.manifest()
CELL, CONFIG = "trinity-mini.fit-seq8k", "trinity-mini"
RATE = "train_tokens_per_s_per_chip"


def _entry():
    return next(c for c in BENCH["configs"] if c["name"] == CONFIG)


def _file():
    with open(os.path.join(cells.ROOT, _entry()["file"])) as f:
        return json.load(f)


def test_the_configuration_is_used_lies_under_paths_and_lists_its_cuts():
    entry, cfg = _entry(), _file()
    assert CONFIG in {w["config"] for w in BENCH["workloads"]}
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    assert entry["source"] == cfg["source"]
    assert set(entry["reduced"]) == set(cfg["reduced"]) == {
        "num_hidden_layers", "num_dense_layers", "layer_types", "num_experts",
        "vocab_size"}
    assert len(entry["reduced"]) <= 16


@pytest.mark.parametrize("key", _entry()["reduced"])
def test_no_cut_names_a_width(key):
    assert not WIDTH.search(key), key


@pytest.mark.parametrize("key,value", [
    ("hidden_size", 2048), ("intermediate_size", 6144),
    ("moe_intermediate_size", 1024), ("head_dim", 128),
    ("num_attention_heads", 32), ("num_key_value_heads", 4),
    ("num_experts_per_tok", 8), ("num_shared_experts", 1),
    ("sliding_window", 2048), ("router_num_experts", 128)])
def test_widths_are_the_published_ones(key, value):
    assert _file()[key] == value


@pytest.mark.parametrize("key", [
    "hidden_size", "intermediate_size", "moe_intermediate_size", "head_dim",
    "kv_lora_rank", "q_proj_size", "ssm_state_size", "expansion_factor",
    "num_experts_per_tok"])
def test_the_rule_knows_a_width(key):
    assert WIDTH.search(key), key


def test_the_cut_is_one_dense_layer_and_one_whole_period():
    cfg = _file()
    assert cfg["layer_types"] == ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg["num_hidden_layers"] == len(cfg["layer_types"]) == 5
    assert cfg["num_dense_layers"] == 1
    assert cfg["num_experts"] * 8 == cfg["router_num_experts"]
    assert cfg["vocab_size"] * 8 == 200192
    for part in ("deployment", "assumed"):
        assert cfg[part]


def test_the_cell_and_its_metrics_are_appended_and_nothing_else_moved():
    assert [w["name"] for w in BENCH["workloads"]][:1] == ["resnet50.fit-hostfed"]
    cell = next(w for w in BENCH["workloads"] if w["name"] == CELL)
    assert cell == dict(cell, config=CONFIG, traffic="fit-seq8k", chips=1)
    assert len(cell["why"]) <= 200 and len(_entry()["why"]) <= 200
    # what the image cell reports, the same under the cell's own names, and
    # the cell's own metrics: the tail of `per_layer`, in the order they came
    image = {m["name"]: m for m in BENCH["per_layer"]
             if m.get("workloads") == ["resnet50.fit-hostfed"]}
    mine = {m["name"]: m for m in BENCH["per_layer"]
            if m.get("workloads") == [CELL]}
    assert not [m["name"] for m in BENCH["per_layer"]
                if len(m.get("workloads", [])) > 1]
    twins = [n for n in mine if n.endswith(".lm")]
    own = [n for n in mine if n not in twins]
    assert len(own) >= 7
    assert [m["name"] for m in BENCH["per_layer"]][-len(mine):] == twins + own
    assert twins == [n + ".lm" for n in image]
    for name, m in mine.items():
        assert m["moves"] == RATE
        if name in twins:      # the image cell's entry, and its reader's file
            theirs = image[name[:-len(".lm")]]
            assert dict(theirs, name=name, moves=RATE, workloads=[CELL]) == m
            specs = []
            for n in (name, theirs["name"]):
                with open(os.path.join(cells.HERE, "metrics", n + ".json")) as f:
                    specs.append(json.load(f))
            assert specs[0] == specs[1]
        else:
            assert m["unit"] == ("%" if "share" in name or "roofline" in name
                                 else "ratio")
    resolved = cells.resolve(CELL)
    rate = {m["name"]: m for m in resolved["end_to_end"]}
    assert set(rate) == {RATE, "setup_s"}
    assert rate[RATE]["workloads"] == [CELL]
    assert rate[RATE] == dict(rate[RATE], unit="tokens/s/chip",
                              better="higher", source="host_clock")
    assert resolved["traffic"]["rate_metric"] == RATE
    # the image cell's rate keeps its 1 % and is the image cell's alone
    items = next(m for m in BENCH["end_to_end"]
                 if m["name"] == "train_items_per_s_per_chip")
    assert items["bound"] == 0.01
    assert items["workloads"] == ["resnet50.fit-hostfed"]
    assert {m["name"] for m in resolved["per_layer"]} == {"compile_s"} | set(mine)
    assert "train_step_mfu.lm" in mine
