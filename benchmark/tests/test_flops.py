"""Each FLOP function against a count made by hand."""

import pytest

from benchmark import flops


def test_bert_base_forward_by_hand():
    cfg = {"hidden_size": 768, "intermediate_size": 3072,
           "num_hidden_layers": 12, "num_labels": 2}
    # per token and layer: q, k, v, out = 4 * 768^2 MACs; ffn = 2 * 768 * 3072
    # MACs; scores and context = 2 * 128 * 768 MACs at seq 128
    macs_token_layer = 4 * 768 ** 2 + 2 * 768 * 3072 + 2 * 128 * 768
    per_row = 128 * 12 * 2 * macs_token_layer + 2 * 768 * 768 + 2 * 768 * 2
    assert flops.bert_forward_flops(cfg, 1, 128) == pytest.approx(per_row)
    assert flops.bert_forward_flops(cfg, 64, 128) == pytest.approx(64 * per_row)
    # the known round number: ~22.3 GFLOP a sequence of 128
    assert per_row == pytest.approx(22.35e9, rel=0.01)


def test_resnet50_forward_by_hand():
    cfg = {"image_size": 224, "num_channels": 3, "stem_width": 64,
           "stage_widths": [64, 128, 256, 512], "stage_blocks": [3, 4, 6, 3],
           "num_labels": 1000}
    stem = 112 * 112 * 49 * 3 * 64
    # stage 2 at 56x56: first block (in 64) and two more (in 256)
    s2 = 56 * 56 * ((64 * 256 + 64 * 64 + 9 * 64 * 64 + 64 * 256)
                    + 2 * (256 * 64 + 9 * 64 * 64 + 64 * 256))
    got = flops.resnet50_forward_flops(cfg, 1)
    # the published 3.8e9 multiply-adds (He et al., table 1) within 2%
    assert got / 2 == pytest.approx(3.8e9, rel=0.02)
    one_stage = dict(cfg, stage_widths=[64], stage_blocks=[3])
    assert flops.resnet50_forward_flops(one_stage, 1) == pytest.approx(
        2 * (stem + s2 + 256 * 1000))


def test_unknown_chip_is_an_error():
    assert flops.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("cpu")
