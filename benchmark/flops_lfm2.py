"""Operations and bytes of an `lfm2_moe` decoder from its shapes, beside
`flops_lm.py` (whose `_layers` knows attention layers only): the whole forward
pass (the configuration's `flops`), the attention kernels' work over the
`full_attention` layers alone, and the gated short convolution's. The
mathematics is counted, whatever implements it: causal keys, experts actually
visited, no recomputation. A multiply-add counts 2; training counts 3x the
forward pass."""

from __future__ import annotations

from benchmark import flops_lm


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def _count(cfg: dict, kind: str) -> int:
    return sum(k == kind for k in cfg["layer_types"])


def attention_kernel_forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """QK^T and PV over the causal keys of the `full_attention` layers: 4 x
    head width a (query, key) pair and query head."""
    pairs = rows * _count(cfg, "full_attention") * flops_lm.seen_keys(seq, None)
    return 4.0 * head_dim(cfg) * cfg["num_attention_heads"] * pairs


def attention_kernel_bytes(cfg: dict, rows: int, seq: int,
                           itemsize: int = 2) -> float:
    """Forward and backward of the `full_attention` layers: q, k, v read and
    o written; then q, k, v, o, do read and dq, dk, dv written."""
    q = rows * seq * cfg["num_attention_heads"] * head_dim(cfg) * itemsize
    kv = rows * seq * cfg["num_key_value_heads"] * head_dim(cfg) * itemsize
    return _count(cfg, "full_attention") * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def conv_forward_flops(cfg: dict, token_layers: float) -> float:
    """The gated short convolution over `token_layers` (token, conv layer)
    pairs: the input projection d -> 3d and the output projection d -> d
    (8 d^2), the `conv_L_cache` taps and the two gating products a channel."""
    d = cfg["hidden_size"]
    return token_layers * (8.0 * d * d + 2.0 * d * (cfg["conv_L_cache"] + 1))


def conv_bytes(cfg: dict, token_layers: float, layers: int,
               itemsize: int = 2) -> float:
    """The least a training step moves for it: a token's input read and
    output written forward; input and output's cotangent read and the input's
    written backward; a layer's weights read twice and their gradient written
    once."""
    d = cfg["hidden_size"]
    weights = layers * (4 * d * d + d * cfg["conv_L_cache"]) * itemsize
    return token_layers * 5 * d * itemsize + 3.0 * weights


def lfm2_forward_flops(cfg: dict, rows: int, seq: int,
                       held_assignments: float = None) -> float:
    """Forward pass over `rows` documents of `seq` tokens on this chip: the
    mixers (short convolutions; attention's projections, and its scores and
    values over the causal keys), the dense feed-forward, the router, the held
    experts over the `held_assignments` (token, expert) pairs that fell on
    them in all layers (where none are given, the share of the picks a uniform
    router sends them: `num_experts_per_tok` x `num_experts` /
    `router_num_experts` a token) and the tied head over the slice. Norms,
    rotary embedding, softmax and the embedding look-up are left out."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tokens = float(rows * seq)
    n_moe = len(cfg["layer_types"]) - cfg["num_dense_layers"]
    per_token = (_count(cfg, "full_attention")
                 * (2.0 * d * (nq + 2 * nkv) * hd + 2.0 * nq * hd * d)
                 + cfg["num_dense_layers"] * 3 * 2.0 * d * cfg["intermediate_size"]
                 + n_moe * 2.0 * d * cfg["router_num_experts"])
    if held_assignments is None:
        held_assignments = (tokens * n_moe * cfg["num_experts_per_tok"]
                            * cfg["num_experts"] / cfg["router_num_experts"])
    return (tokens * per_token
            + conv_forward_flops(cfg, tokens * _count(cfg, "conv"))
            + attention_kernel_forward_flops(cfg, rows, seq)
            + flops_lm.expert_forward_flops(cfg, held_assignments)
            + tokens * 2.0 * d * cfg["vocab_size"])
