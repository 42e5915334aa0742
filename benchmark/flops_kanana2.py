"""Operations and bytes of a `deepseek_v3` decoder (latent attention with no
query compression) from its shapes, beside `flops_lm.py` (whose functions read
`afmoe`'s key names and one head width): the whole forward pass (the
configuration's `flops`), the attention kernels' work at a query-key width
beside a value width, and the latent projections'. The mathematics is
counted, whatever implements it: causal keys, experts actually visited, no
recomputation. A multiply-add counts 2; training counts 3x the forward pass."""

from __future__ import annotations

from benchmark import flops_lm


def qk_dim(cfg: dict) -> int:
    return cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]


def attention_kernel_forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """QK^T over the query-key width and PV over the value width, over the
    causal keys of every layer: 2 x (qk + v) a (query, key) pair and head."""
    pairs = rows * cfg["num_hidden_layers"] * flops_lm.seen_keys(seq, None)
    return (2.0 * (qk_dim(cfg) + cfg["v_head_dim"])
            * cfg["num_attention_heads"] * pairs)


def attention_kernel_bytes(cfg: dict, rows: int, seq: int,
                           itemsize: int = 2) -> float:
    """Forward and backward of all layers: q, k (query-key wide), v read and
    o (value wide) written; then q, k, v, o, do read and dq, dk, dv written."""
    tokens = rows * seq * cfg["num_attention_heads"] * itemsize
    qk, vo = tokens * qk_dim(cfg), tokens * cfg["v_head_dim"]
    return cfg["num_hidden_layers"] * ((2 * qk + 2 * vo) + (4 * qk + 4 * vo))


def _latent_weights(cfg: dict) -> int:
    """W_q, W_kva and W_kvb: what stands between a layer's norm and the
    kernel's operands."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rank = cfg["kv_lora_rank"]
    return (d * h * qk_dim(cfg) + d * (rank + cfg["qk_rope_head_dim"])
            + rank * h * (cfg["qk_nope_head_dim"] + cfg["v_head_dim"]))


def latent_forward_flops(cfg: dict, token_layers: float) -> float:
    """The three latent projections over `token_layers` (token, layer) pairs;
    the latent's norm, rotary and the joins are left out."""
    return token_layers * 2.0 * _latent_weights(cfg)


def latent_bytes(cfg: dict, token_layers: float, layers: int,
                 itemsize: int = 2) -> float:
    """The least a training step moves for them: a token's input read and its
    q, k, v written forward; the input and their cotangents read and the
    input's written backward; a layer's weights read twice and their gradient
    written once."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    qkv = h * (2 * qk_dim(cfg) + cfg["v_head_dim"])
    return (token_layers * (3 * d + 2 * qkv) * itemsize
            + 3.0 * layers * _latent_weights(cfg) * itemsize)


def kanana2_forward_flops(cfg: dict, rows: int, seq: int,
                          held_assignments: float = None) -> float:
    """Forward pass over `rows` documents of `seq` tokens on this chip: the
    latent projections and the output projection, the scores and values over
    the causal keys, the dense and shared feed-forwards, the router, the held
    experts over the `held_assignments` (token, expert) pairs that fell on
    them in all layers (where none are given, the share of the picks a uniform
    router sends them: `num_experts_per_tok` x `n_routed_experts` /
    `router_num_experts` a token), and the head over the slice. Norms, rotary
    embedding, softmax and the embedding look-up are left out."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    layers, dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    tokens = float(rows * seq)
    per_token = (layers * 2.0 * (_latent_weights(cfg)
                                 + h * cfg["v_head_dim"] * d)
                 + dense * 3 * 2.0 * d * cfg["intermediate_size"]
                 + (layers - dense) * (
                     2.0 * d * cfg["router_num_experts"]
                     + 3 * 2.0 * d * cfg["moe_intermediate_size"]
                     * cfg["n_shared_experts"]))
    if held_assignments is None:
        held_assignments = (tokens * (layers - dense)
                            * cfg["num_experts_per_tok"]
                            * cfg["n_routed_experts"]
                            / cfg["router_num_experts"])
    return (tokens * per_token
            + attention_kernel_forward_flops(cfg, rows, seq)
            + flops_lm.expert_forward_flops(cfg, held_assignments)
            + tokens * 2.0 * d * cfg["vocab_size"])
