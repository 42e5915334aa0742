"""Plain Nemotron-H (`model_type: nemotron_h`, nvidia), float32, `jax.numpy`
only: a causal decoder whose layers are each ONE part behind one pre-norm,
`h += f(rms(h))`, the part by the letter of `hybrid_override_pattern`: `M` a
Mamba-2 mixer, `*` grouped-query attention with no position, `E` a sigmoid
top-k expert layer of squared-ReLU experts beside a shared one; a final norm
and a head of its own.

Imports nothing of the program (the attention core is the Trinity
reference's; the router and the bias rule the Kanana-2 reference's, which
read the same keys). Weights are made here from a key, in this file's own
layout (every projection apart); the harness pours the same numbers into the
program.

From the published `config.json` and the family's model file (T tokens, d
hidden, H Mamba heads of P, G groups of N, K conv taps):

    M:  z = u W_z (H P),  xBC = [u W_x (H P) | u W_B (G N) | u W_C (G N)],  dl = u W_dt (H)
        xBC <- silu(causal depthwise conv over K tokens of xBC + b_conv)
        dt = softplus(dl + dt_bias);  a_t = exp(-dt_t exp(A_log))
        S_t = a_t S_{t-1} + dt_t x_t B_t^T  (P x N a head, head j reads group j // (H / G))
        y_t = S_t C_t + D x_t
        out = rms_G(y * silu(z); gain) W_out     (the norm over G groups of H P / G)
    *:  o_h = softmax(q_h k_g^T / sqrt(hd) + causal) v_g,  out = [o_1 .. o_nq] W_o
    E:  s = sigmoid(W_r m) over the router's experts, picks = top-k of s + b
        (b a buffer, no gradient), w = s[picks] / (sum of s[picks] + 1e-20) x scale
        out = W_down_sh relu(W_up_sh m)^2 + sum over picks e of w_e W_down_e relu(W_up_e m)^2

- `h = E[ids]` (no multiplier); RMS norms with a learned gain, eps
  `layer_norm_epsilon`; after the last layer `norm_f`, then the head
  (`tie_word_embeddings` false);
- no biases anywhere but the convolution's (`use_conv_bias`);
  `time_step_limit` [0, null]: dt is not clamped;
- the scan is the recurrence itself, a `lax.scan` over tokens (not the
  program's chunks), under `jax.checkpoint` over blocks of `SCAN_BLOCK`
  tokens, so that only the states at block edges are kept;
- the mean token cross-entropy of a row over the vocabulary held here.

The chip's share of a deployment: `n_routed_experts` routed experts are held
here, those numbered from `experts_held_offset`; the router keeps its
`router_num_experts` outputs and what the absent experts would add is left
out; the shared expert is whole. `vocab_size` is the slice of the vocabulary
held here.

Departures from the published description, each noted:
- the family's second tower (a denoiser for generation by diffusion over
  blocks) is not built: this is the stack `config.json` describes, trained by
  the next token's loss;
- the selection bias's rule is the training framework's: after a step
  `b += bias_rate * sign(mean(n) - n_e)`, then centred;
- initialisation: normal(0, `initializer_range`) matrices, the Mamba output
  projection over the root of `rescale_prenorm_residual_layers`, the
  convolution's taps and bias uniform in +-1 / sqrt(K) (a depthwise
  convolution's default), `A_log = log(1 .. H)`, `D = 1`, `dt_bias` the
  inverse softplus of dt log-uniform in [`time_step_min`, `time_step_max`]
  floored at `time_step_floor`, gains 1;
- the feed-forwards and the head with its loss are computed `TOKEN_BLOCK`
  tokens at a time and the held experts on every token weighted by the
  routing weight, as in the Kanana-2 reference;
- `ssm_gate_after_norm` (the harness's planted fault, never in a
  configuration): the gate applied after the grouped norm, not before it.

`cast` is applied to both operands of every contraction and to the stored
intermediates (the residual stream, the normed inputs, x, B and C into the
scan, the gated output), as a compute type is; dt, the decays, the states,
norm statistics, the router's scores and the loss stay float32. The caller
sets `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kanana2 import _blocks, _rms, route, update_bias
from benchmark.reference.trinity import attention

SCAN_BLOCK = 256
TOKEN_BLOCK = 4096
LETTERS = {"M": "mamba", "*": "attention", "E": "moe"}


def kinds(cfg: dict) -> list:
    """Each layer's one part, from the pattern's letters."""
    return [LETTERS[c] for c in cfg["hybrid_override_pattern"]]


def router_width(cfg: dict) -> int:
    return cfg.get("router_num_experts", cfg["n_routed_experts"])


def widths(cfg: dict) -> tuple:
    """(H, P, G, N, H P): the Mamba heads, their width, the groups of B and
    C, the state's width, the inner width."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    return h, p, cfg["n_groups"], cfg["ssm_state_size"], h * p


def init_dt_bias(cfg: dict, key, heads: int):
    """The inverse softplus of dt log-uniform in [time_step_min,
    time_step_max], floored at time_step_floor."""
    lo, hi = math.log(cfg["time_step_min"]), math.log(cfg["time_step_max"])
    dt = jnp.exp(jax.random.uniform(key, (heads,), jnp.float32) * (hi - lo) + lo)
    dt = jnp.maximum(dt, cfg["time_step_floor"])
    return dt + jnp.log(-jnp.expm1(-dt))


def init_weights(cfg: dict, key) -> dict:
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, p, g, n, inner = widths(cfg)
    k_conv = cfg["conv_kernel"]
    hd, nq = cfg["head_dim"], cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]
    std = cfg.get("initializer_range", 0.02)
    out_scale = (1.0 / math.sqrt(cfg["rescale_prenorm_residual_layers"])
                 if cfg["rescale_prenorm_residual"] else 1.0)
    keys = iter(jax.random.split(key, 4 + 16 * cfg["num_hidden_layers"]))

    def mat(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def uniform(bound, *shape):
        return jax.random.uniform(next(keys), shape, jnp.float32, -bound, bound)

    def ones(m):
        return jnp.ones((m,), jnp.float32)

    def relu2(width, *lead):
        return {"w_up": mat(*lead, d, width), "w_down": mat(*lead, width, d)}

    layers = []
    for kind in kinds(cfg):
        lp = {"norm": ones(d)}
        if kind == "mamba":
            conv = inner + 2 * g * n
            lp.update(w_z=mat(d, inner), w_x=mat(d, inner), w_b=mat(d, g * n),
                      w_c=mat(d, g * n), w_dt=mat(d, h),
                      conv_w=uniform(k_conv ** -0.5, conv, k_conv),
                      conv_b=uniform(k_conv ** -0.5, conv),
                      dt_bias=init_dt_bias(cfg, next(keys), h),
                      A_log=jnp.log(jnp.arange(1, h + 1, dtype=jnp.float32)),
                      D=ones(h), gate_norm=ones(inner),
                      w_out=mat(inner, d) * out_scale)
        elif kind == "attention":
            lp.update(wq=mat(d, nq * hd), wk=mat(d, nkv * hd),
                      wv=mat(d, nkv * hd), wo=mat(nq * hd, d))
        else:
            lp.update(router=mat(d, router_width(cfg)),
                      shared=relu2(cfg["moe_shared_expert_intermediate_size"]),
                      experts=relu2(cfg["moe_intermediate_size"],
                                    cfg["n_routed_experts"]))
        layers.append(lp)
    return {"embed": mat(v, d), "layers": layers, "final_norm": ones(d),
            "head": mat(d, v)}


def init_bias(cfg: dict):
    """The selection bias of every expert layer: (expert layers, router width)."""
    n = sum(kind == "moe" for kind in kinds(cfg))
    return jnp.zeros((n, router_width(cfg)), jnp.float32)


def ssm_scan(x, dt, a, b, c, d, block: int = SCAN_BLOCK):
    """The recurrence, one token at a time. x (B, T, H, P), dt (B, T, H), a
    (H,) negative, b and c (B, T, G, N), d (H,); all float32. Returns y (B, T,
    H, P). Blocks of `block` tokens (which divides T) each run under
    `jax.checkpoint`: only the states at the blocks' edges are kept."""
    bsz, t, h, p = x.shape
    g, n = b.shape[-2:]
    r, block = h // g, min(block, t)
    if t % block:
        raise ValueError(f"{t} tokens do not cut into blocks of {block}")

    def step(state, inp):
        x_t, dt_t, b_t, c_t = inp           # (B,G,R,P), (B,G,R), (B,G,N) x 2
        decay = jnp.exp(dt_t * a.reshape(g, r))
        state = (decay[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[:, :, None, None, :])
        return state, jnp.einsum("bgrpn,bgn->bgrp", state, c_t)

    def tokens(state, xs):
        return jax.lax.scan(step, state, xs)

    def blocks(a_):          # (B, T, ...) -> (T / block, block, B, ...)
        a_ = jnp.moveaxis(a_, 1, 0)
        return a_.reshape(t // block, block, *a_.shape[1:])

    seq = (blocks(x.reshape(bsz, t, g, r, p)), blocks(dt.reshape(bsz, t, g, r)),
           blocks(b), blocks(c))
    _, y = jax.lax.scan(jax.checkpoint(tokens),
                        jnp.zeros((bsz, g, r, p, n), jnp.float32), seq)
    y = jnp.moveaxis(y.reshape(t, bsz, g, r, p), 0, 1).reshape(bsz, t, h, p)
    return y + d[:, None] * x


def mamba(lp, u, cfg: dict, cast=lambda t: t):
    """The Mamba-2 mixer over the normed input u (B, T, d)."""
    bsz, t, _ = u.shape
    h, p, g, n, inner = widths(cfg)
    k_conv = cfg["conv_kernel"]

    def mm(a_, b_):
        return jnp.matmul(cast(a_), cast(b_))

    z = mm(u, lp["w_z"])
    xbc = jnp.concatenate([mm(u, lp["w_x"]), mm(u, lp["w_b"]),
                           mm(u, lp["w_c"])], axis=-1)
    padded = jnp.pad(xbc, ((0, 0), (k_conv - 1, 0), (0, 0)))
    conv = sum(lp["conv_w"][:, j] * padded[:, j:j + t]
               for j in range(k_conv)) + lp["conv_b"]
    x, b, c = jnp.split(cast(jax.nn.silu(conv)), [inner, inner + g * n], axis=-1)
    dt = jax.nn.softplus(mm(u, lp["w_dt"]) + lp["dt_bias"])
    y = ssm_scan(x.reshape(bsz, t, h, p), dt, -jnp.exp(lp["A_log"]),
                 b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n), lp["D"])
    y = y.reshape(bsz, t, inner)
    gate = jax.nn.silu(z)
    eps = cfg["layer_norm_epsilon"]
    gain = lp["gate_norm"].reshape(g, inner // g)
    if cfg.get("ssm_gate_after_norm"):
        y = _rms(y.reshape(bsz, t, g, -1), gain, eps).reshape(y.shape) * gate
    else:
        y = _rms((y * gate).reshape(bsz, t, g, -1), gain, eps).reshape(y.shape)
    return mm(cast(y), lp["w_out"])


def self_attention(lp, u, cfg: dict, cast=lambda t: t):
    """Causal grouped-query attention with no position over u (B, T, d)."""
    bsz, t, _ = u.shape
    hd, nq = cfg["head_dim"], cfg["num_attention_heads"]
    nkv = cfg["num_key_value_heads"]

    def mm(a_, b_):
        return jnp.matmul(cast(a_), cast(b_))

    def heads(x, m):
        return x.reshape(bsz, t, m, hd).transpose(0, 2, 1, 3)

    q, k = heads(mm(u, lp["wq"]), nq), heads(mm(u, lp["wk"]), nkv)
    v = heads(mm(u, lp["wv"]), nkv)
    o = attention(cast(q), cast(k), cast(v), None, cast)
    return mm(cast(o.transpose(0, 2, 1, 3).reshape(bsz, t, nq * hd)), lp["wo"])


def _relu2(lp, m, mm):
    return mm(jnp.square(jax.nn.relu(mm(m, lp["w_up"]))), lp["w_down"])


def expert_layer(lp, m, bias, cfg: dict, cast=lambda t: t):
    """m: (T, d). The shared expert plus the held experts' part; and the
    tokens routed to each of the router's experts."""
    def mm(a_, b_):
        return jnp.matmul(cast(a_), cast(b_))

    picked, w, counts = route(m, lp["router"], bias, cfg, cast)

    def add_expert(y, held):
        number, one = held
        w_e = jnp.sum(jnp.where(picked == number, w, 0.0), axis=-1)
        return y + w_e[:, None] * _relu2(one, m, mm), None

    numbers = (cfg.get("experts_held_offset", 0)
               + jnp.arange(cfg["n_routed_experts"]))
    shared = _blocks(lambda x: _relu2(lp["shared"], x, mm),
                     min(TOKEN_BLOCK, m.shape[0]), m)
    y, _ = jax.lax.scan(jax.checkpoint(add_expert), shared,
                        (numbers, lp["experts"]))
    return y, counts


def _layer(lp, h, bias, cfg, cast, kind):
    bsz, t, d = h.shape
    u = cast(_rms(h, lp["norm"], cfg["layer_norm_epsilon"]))
    counts = None
    if kind == "mamba":
        y = mamba(lp, u, cfg, cast)
    elif kind == "attention":
        y = self_attention(lp, u, cfg, cast)
    else:
        y, counts = expert_layer(lp, u.reshape(bsz * t, d), bias, cfg, cast)
        y = y.reshape(bsz, t, d)
    return cast(h + y), counts


def hidden(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    """ids (B, S) -> (the final normed hidden state (B, S, d), counts: the
    tokens routed to each expert, a row an expert layer)."""
    bias = init_bias(cfg) if bias is None else bias
    h = cast(w["embed"][ids])
    counts, at = [], 0
    for lp, kind in zip(w["layers"], kinds(cfg)):
        b_l = bias[at] if kind == "moe" else None
        h, n = jax.checkpoint(
            lambda p_, h_, b_, k=kind: _layer(p_, h_, b_, cfg, cast, k))(
                lp, h, b_l)
        if kind == "moe":
            counts.append(n)
            at += 1
    h = cast(_rms(h, w["final_norm"], cfg["layer_norm_epsilon"]))
    return h, (jnp.stack(counts) if counts else jnp.zeros((0, 1), jnp.float32))


def logits(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    h, _ = hidden(w, ids, cfg, cast, bias)
    return jnp.matmul(cast(h), cast(w["head"])).astype(jnp.float32)


def losses_and_counts(w, ids, labels, cfg, cast=lambda t: t, bias=None):
    """(the mean token cross-entropy of each row (B,), counts)."""
    h, counts = hidden(w, ids, cfg, cast, bias)
    b, s, d = h.shape
    head = cast(w["head"])

    def nll(hb, yb):
        z = jnp.matmul(hb, head).astype(jnp.float32)
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    rows = _blocks(nll, min(TOKEN_BLOCK, b * s), h.reshape(b * s, d),
                   labels.reshape(b * s).astype(jnp.int32))
    return jnp.mean(rows.reshape(b, s), axis=-1), counts


def row_losses(w, x, y, cfg, cast=lambda t: t, bias=None):
    """x: ids (B, S); y: the next token of each (B, S)."""
    return losses_and_counts(w, x, y, cfg, cast, bias)[0]
