"""Operations and bytes of a `nemotron_h` decoder from its shapes, beside
`flops_lm.py` (whose functions read `afmoe`'s key names and count SwiGLU
experts): the whole forward pass by layer kind (the configuration's
`flops`), and the chunked scan's four parts. The mathematics is counted,
whatever implements it: causal keys, experts actually visited, the scan's
products as the chunked form computes them (its within-chunk squares whole),
no recomputation. A multiply-add counts 2; training counts 3x the forward
pass."""

from __future__ import annotations

from benchmark import flops_lm


def _kinds(cfg: dict) -> dict:
    pattern = cfg["hybrid_override_pattern"]
    return {k: pattern.count(c) for k, c in
            (("mamba", "M"), ("attention", "*"), ("experts", "E"))}


def _ssm_widths(cfg: dict) -> tuple:
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h, p, cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]


def mamba_projection_flops(cfg: dict) -> float:
    """A token through one Mamba layer's two projections: W_in (d -> z, x, B,
    C, dt) and W_out (H P -> d)."""
    h, p, g, n, _ = _ssm_widths(cfg)
    inner = h * p
    return 2.0 * cfg["hidden_size"] * ((2 * inner + 2 * g * n + h) + inner)


def scan_parts(cfg: dict) -> dict:
    """A token's share of one Mamba layer's chunked scan, by part: within
    its chunk, C B^T over the chunk (a group's N a pair) and that square,
    decayed, times dt x (a head's P a pair), both over all L pairs of the
    chunk; its term of the chunk's end state (P x N a head); the recurrence
    over chunks (P x N a head and chunk, over the chunk's L tokens); the
    state read out by C (P x N a head)."""
    h, p, g, n, chunk = _ssm_widths(cfg)
    return {"within": 2.0 * chunk * (g * n + h * p),
            "states": 2.0 * h * p * n,
            "across": 2.0 * h * p * n / chunk,
            "out": 2.0 * h * p * n}


def scan_forward_flops(cfg: dict, token_layers: float) -> float:
    """The chunked scan over `token_layers` (token, Mamba layer) pairs."""
    return token_layers * sum(scan_parts(cfg).values())


def scan_bytes(cfg: dict, token_layers: float) -> float:
    """The least a training step moves for the scan: a pair's x, B and C in
    (bf16), dt in (float32) and y out (bf16), forward; three times that for
    the forward and backward passes."""
    h, p, g, n, _ = _ssm_widths(cfg)
    return 3.0 * token_layers * ((h * p + 2 * g * n + h * p) * 2 + h * 4)


def attention_forward_flops(cfg: dict, seq: int) -> float:
    """A token through one attention layer: q, k, v, o and the scores and
    values over its causal keys."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    proj = 2.0 * d * (nq + 2 * nkv) * hd + 2.0 * nq * hd * d
    return proj + 2.0 * 2 * hd * nq * flops_lm.seen_keys(seq, None) / seq


def expert_layer_flops(cfg: dict) -> float:
    """A token through one expert layer outside the held experts: the router
    and the shared squared-ReLU expert (two products)."""
    d = cfg["hidden_size"]
    return (2.0 * d * cfg["router_num_experts"]
            + 2 * 2.0 * d * cfg["moe_shared_expert_intermediate_size"])


def held_expert_flops(cfg: dict, held_assignments: float) -> float:
    """The squared-ReLU experts over (token, expert) pairs: up and down."""
    return (held_assignments * 2 * 2.0 * cfg["hidden_size"]
            * cfg["moe_intermediate_size"])


def nemotronh_forward_flops(cfg: dict, rows: int, seq: int,
                            held_assignments: float = None) -> float:
    """Forward pass over `rows` documents of `seq` tokens on this chip: the
    Mamba layers' projections and scans, the attention layers, the expert
    layers' router and shared expert, the held experts over the
    `held_assignments` (token, expert) pairs that fell on them in all layers
    (where none are given, the share of the picks a uniform router sends
    them: `num_experts_per_tok` x `n_routed_experts` / `router_num_experts` a
    token), and the head over the slice. Norms, the convolution, the gate and
    the embedding look-up are left out."""
    kinds, tokens = _kinds(cfg), float(rows * seq)
    if held_assignments is None:
        held_assignments = (tokens * kinds["experts"]
                            * cfg["num_experts_per_tok"]
                            * cfg["n_routed_experts"]
                            / cfg["router_num_experts"])
    per_token = (kinds["mamba"] * mamba_projection_flops(cfg)
                 + kinds["attention"] * attention_forward_flops(cfg, seq)
                 + kinds["experts"] * expert_layer_flops(cfg)
                 + 2.0 * cfg["hidden_size"] * cfg["vocab_size"])
    return (tokens * per_token
            + scan_forward_flops(cfg, tokens * kinds["mamba"])
            + held_expert_flops(cfg, held_assignments))
