"""Operations and bytes of a decoder-only language model from its shapes: the
whole forward pass (a configuration's `flops`), and the kernels' own work for
their roofline shares. The mathematics is counted, whatever implements it:
keys inside the window, experts actually visited, no recomputation. A
multiply-add counts 2; training counts 3x the forward pass."""

from __future__ import annotations


def seen_keys(seq: int, window) -> float:
    """Keys a row's queries see, summed over its `seq` queries: causal, and
    with a `window` at most the `window` newest."""
    if window is None or window >= seq:
        return seq * (seq + 1) / 2.0
    return window * (window + 1) / 2.0 + (seq - window) * float(window)


def _layers(cfg):
    for i, kind in enumerate(cfg["layer_types"]):
        yield (cfg["sliding_window"] if kind == "sliding_attention" else None,
               i < cfg["num_dense_layers"])


def attention_pairs(cfg: dict, rows: int, seq: int) -> float:
    """(query, key) pairs of all layers."""
    return rows * sum(seen_keys(seq, w) for w, _ in _layers(cfg))


def attention_kernel_forward_flops(cfg: dict, rows: int, seq: int) -> float:
    """QK^T and PV over the keys seen: 4 x head_dim a pair and query head."""
    return (4.0 * cfg["head_dim"] * cfg["num_attention_heads"]
            * attention_pairs(cfg, rows, seq))


def attention_kernel_bytes(cfg: dict, rows: int, seq: int,
                           itemsize: int = 2) -> float:
    """Forward and backward of all layers: q, k, v read and o written; then q,
    k, v, o, do read and dq, dk, dv written."""
    hd = cfg["head_dim"]
    q = rows * seq * cfg["num_attention_heads"] * hd * itemsize
    kv = rows * seq * cfg["num_key_value_heads"] * hd * itemsize
    return len(cfg["layer_types"]) * ((2 * q + 2 * kv) + (4 * q + 4 * kv))


def expert_forward_flops(cfg: dict, assignments: float) -> float:
    """`assignments` (token, expert) pairs through a SwiGLU expert."""
    return assignments * 3 * 2.0 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_kernel_bytes(cfg: dict, assignments: float, layers: int,
                        itemsize: int = 2) -> float:
    """Forward and backward of the grouped products over the experts held:
    their weights read twice and their gradient written once a layer; a row's
    input, gate and up, their product and the output, each read or written
    three times."""
    d, h = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = layers * cfg["num_experts"] * 3 * d * h * itemsize
    rows = assignments * (2 * d + 3 * h) * itemsize
    return 3.0 * (weights + rows)


def trinity_forward_flops(cfg: dict, rows: int, seq: int,
                          held_assignments: float = None) -> float:
    """Forward pass over `rows` documents of `seq` tokens on this chip: the
    projections, the scores and values over the keys seen, the dense and
    shared feed-forwards, the router, the held experts over the
    `held_assignments` (token, expert) pairs that fell on them in all layers
    (where none are given, the share of the picks a uniform router sends
    them: `num_experts_per_tok` x `num_experts` / `router_num_experts` a
    token), and the head over the slice. Norms, rotary embedding, softmax and
    the embedding look-up are left out."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    tokens = float(rows * seq)
    per_token = 0.0
    for _, dense in _layers(cfg):
        per_token += 2.0 * d * (2 * nq + 2 * nkv) * hd + 2.0 * nq * hd * d
        if dense:
            per_token += 3 * 2.0 * d * cfg["intermediate_size"]
        else:
            per_token += 2.0 * d * cfg["router_num_experts"]
            per_token += (3 * 2.0 * d * cfg["moe_intermediate_size"]
                          * cfg["num_shared_experts"])
    if held_assignments is None:
        n_moe = sum(not dense for _, dense in _layers(cfg))
        held_assignments = (tokens * n_moe * cfg["num_experts_per_tok"]
                            * cfg["num_experts"] / cfg["router_num_experts"])
    return (tokens * per_token + attention_kernel_forward_flops(cfg, rows, seq)
            + expert_forward_flops(cfg, held_assignments)
            + tokens * 2.0 * d * cfg["vocab_size"])
