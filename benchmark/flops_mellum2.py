"""Operations and bytes of a `mellum` decoder from its shapes, beside
`flops_lm.py` (whose functions read `afmoe`'s key names): the whole forward
pass (the configuration's `flops`), and the flash kernels' work over the
`sliding_attention` layers alone for their roofline share. The mathematics
is counted, whatever implements it: keys inside the window, experts actually
visited, no recomputation. A multiply-add counts 2; training counts 3x the
forward pass."""

from __future__ import annotations

from benchmark import flops_lm


def _dense_layers(cfg: dict) -> int:
    """The leading `dense` entries of `mlp_layer_types`."""
    kinds = cfg["mlp_layer_types"]
    return next((i for i, k in enumerate(kinds) if k != "dense"), len(kinds))


def _windows(cfg: dict) -> int:
    return sum(k == "sliding_attention" for k in cfg["layer_types"])


def window_pairs(cfg: dict, rows: int, seq: int) -> float:
    """(query, key) pairs inside the windows of all `sliding_attention`
    layers: a row's queries i see min(i + 1, `sliding_window`) keys."""
    return rows * _windows(cfg) * flops_lm.seen_keys(seq, cfg["sliding_window"])


def attention_pairs(cfg: dict, rows: int, seq: int) -> float:
    """(query, key) pairs of all layers, windowed and full."""
    full = len(cfg["layer_types"]) - _windows(cfg)
    return (window_pairs(cfg, rows, seq)
            + rows * full * flops_lm.seen_keys(seq, None))


def pair_flops(cfg: dict) -> float:
    """QK^T and PV of one (query, key) pair over all query heads, forward:
    4 x head_dim a head."""
    return 4.0 * cfg["head_dim"] * cfg["num_attention_heads"]


def window_kernel_bytes(cfg: dict, rows: int, seq: int,
                        itemsize: int = 2) -> float:
    """Forward and backward of the `sliding_attention` layers: q, k, v read
    and o written; then q, k, v, o, do read and dq, dk, dv written."""
    hd = cfg["head_dim"]
    q = rows * seq * cfg["num_attention_heads"] * hd * itemsize
    kv = rows * seq * cfg["num_key_value_heads"] * hd * itemsize
    return _windows(cfg) * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def held_assignments(cfg: dict, rows: int, seq: int) -> float:
    """(token, expert) pairs a uniform router sends to the experts held, over
    all expert layers: `num_experts_per_tok` x `num_experts` /
    `router_num_experts` a token and layer."""
    n_moe = len(cfg["layer_types"]) - _dense_layers(cfg)
    return (float(rows * seq) * n_moe * cfg["num_experts_per_tok"]
            * cfg["num_experts"] / cfg["router_num_experts"])


def mellum2_forward_flops(cfg: dict, rows: int, seq: int,
                          held: float = None) -> float:
    """Forward pass over `rows` documents of `seq` tokens on this chip: the
    projections (q | k | v in, o out), the scores and values over the keys
    seen (windowed and full), the dense feed-forwards of the leading dense
    layers, the routers, the held experts over the `held` (token, expert)
    pairs that fell on them in all layers (where none are given,
    `held_assignments`) and the head over the slice. Norms, rotary
    embedding, softmax, the balance term and the embedding look-up are left
    out."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    layers, dense = len(cfg["layer_types"]), _dense_layers(cfg)
    tokens = float(rows * seq)
    per_token = (layers * (2.0 * d * (nq + 2 * nkv) * hd + 2.0 * nq * hd * d)
                 + dense * 3 * 2.0 * d * cfg["intermediate_size"]
                 + (layers - dense) * 2.0 * d * cfg["router_num_experts"])
    if held is None:
        held = held_assignments(cfg, rows, seq)
    return (tokens * per_token + pair_flops(cfg) * attention_pairs(cfg, rows, seq)
            + flops_lm.expert_forward_flops(cfg, held)
            + tokens * 2.0 * d * cfg["vocab_size"])
