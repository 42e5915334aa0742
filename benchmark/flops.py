"""Model FLOPs from shapes, and the published peaks. The yardstick's own
copy: nothing here is read from the program.

A multiply-add counts 2. Recomputation is never counted. Training counts
3x the forward pass (backward = 2x).
"""

from __future__ import annotations

# Published per-chip peaks, keyed by `device_kind` as JAX reports it.
# Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s bf16, 16 GB HBM2e
# at 819 GB/s). A device that is not here is an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]


def bert_forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """Forward pass of the classifier over `rows` sequences of `seq` tokens:
    per layer and token the q, k, v, output (4 h^2) and feed-forward (2 h m)
    products, QK^T and PV (2 * seq * h between them); the pooler and the head
    once a row. Embedding look-ups, softmax, LayerNorm and GELU are not
    matrix products and are left out."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    per_token = cfg["num_hidden_layers"] * (
        2.0 * (4 * h * h + 2 * h * m) + 2.0 * 2 * seq * h)
    per_row = 2.0 * h * h + 2.0 * h * cfg["num_labels"]
    return rows * (seq * per_token + per_row)


def _conv(h, w, k, cin, cout, stride):
    ho, wo = -(-h // stride), -(-w // stride)
    return 2.0 * ho * wo * k * k * cin * cout, ho, wo


def resnet50_forward_flops(cfg: dict, rows: int, seq: int = 0) -> float:
    """Forward pass over `rows` images: every convolution ("same" padding,
    so the output is ceil(size / stride); a stage's stride sits in its first 1x1,
    as published) and the dense head. Batch norm, ReLU
    and pooling are left out."""
    size = cfg["image_size"]
    total, h, w = _conv(size, size, 7, cfg["num_channels"], cfg["stem_width"], 2)
    h, w = -(-h // 2), -(-w // 2)          # 3x3/2 max pool
    cin = cfg["stem_width"]
    for si, (width, n) in enumerate(zip(cfg["stage_widths"],
                                        cfg["stage_blocks"])):
        for bi in range(n):
            stride = 2 if (si > 0 and bi == 0) else 1
            if bi == 0:
                total += _conv(h, w, 1, cin, 4 * width, stride)[0]
            f, h, w = _conv(h, w, 1, cin, width, stride)
            total += f + _conv(h, w, 3, width, width, 1)[0]
            total += _conv(h, w, 1, width, 4 * width, 1)[0]
            cin = 4 * width
    return rows * (total + 2.0 * cin * cfg["num_labels"])

