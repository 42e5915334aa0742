"""Plain Mellum2 (`model_type: mellum`, JetBrains), float32, `jax.numpy` only:
a causal decoder of grouped-query attention layers, windowed or full by
`layer_types`, each kind with the rotary embedding of its own
`rope_parameters` section (YaRN on the full layers), and softmax top-k expert
layers with no shared expert, trained with a balance term in the loss.

Imports nothing of the program (the full layers' attention core is the LFM2
reference's, the norm and the token blocks the Kanana-2 reference's). Weights
are made here from a key, in this file's own layout (every projection apart);
the harness pours the same numbers into the program.

From the published `config.json` (T tokens, d hidden, hd `head_dim`):
- `h = E[ids]` (no multiplier); a layer: `h += Attn(RMS_in(h))`,
  `h += FF(RMS_post(h))`, RMS norms with a learned gain, eps `rms_norm_eps`;
  after the last layer one more, then an untied head;
- attention: q (`num_attention_heads` x hd), k, v (`num_key_value_heads` x
  hd) from the normed input, no bias, no gate; q and k RMS-normed over a
  head's width with a learned gain (Qwen3-MoE's layout, whose keys the file
  has: no key says otherwise), then rotated (halves), then
  `softmax(q k^T / sqrt(hd)) v`, causal, a key-value head shared by heads /
  key-value heads queries; `sliding_attention` layers see keys
  `i - sliding_window < j <= i`; `W_o o`;
- rotary by layer kind, `rope_parameters[kind]`: `default` turns pair i by
  `t r_i`, `r_i = 1 / theta^(2i / hd)`; `yarn` by `t inv_i`,

      low  = floor(hd ln(orig / (beta_fast 2 pi)) / (2 ln theta))
      high = ceil(hd ln(orig / (beta_slow 2 pi)) / (2 ln theta))
      e_i = 1 - clamp((i - low) / (high - low), 0, 1)
      inv_i = r_i / factor (1 - e_i) + r_i e_i

  (`orig` = `original_max_position_embeddings`), and cos and sin times
  `attention_factor`;
- FF of the leading `dense` entries of `mlp_layer_types`: `W_2 (silu(W_1 m) *
  W_3 m)`, width `intermediate_size`; of the `sparse` ones: `p = softmax(W_r
  m)` in float32 over the router's experts, the `num_experts_per_tok` most
  probable picked, `w = p[picks] / sum(p[picks])` (`norm_topk_prob`),
  `y = sum_e w_e SwiGLU_e(m)`, each expert `moe_intermediate_size` wide; no
  shared expert, no selection bias;
- the loss: the mean token cross-entropy over the vocabulary held here plus
  `router_aux_loss_coef` x E x sum_e f_e P_e, with f_e the tokens that picked
  expert e over the tokens (summing to k) and P_e the mean of p_e over the
  tokens, both pooled over the expert layers, E the router's width: what
  `load_balancing_loss_func` of the transformers library computes from a
  step's router logits.

The chip's share of a deployment: `num_experts` routed experts are held here,
those numbered from `experts_held_offset`; the router keeps its
`router_num_experts` outputs (so f and P cover all of them) and what the
absent experts would add is left out. `vocab_size` is the slice of the
vocabulary held here.

Departures, each noted:
- `losses_and_counts` gives each row its cross-entropy plus the balance term
  of the rows it was given, the term entering as `t - stop_gradient(t)`:
  nought in value, so a row's loss reads as the program's reported loss (the
  criterion's) does, while its gradient is the whole objective's. The term is
  over the rows of one call, so a step's rows go through in one call
  (`reference_row_block` equal to the batch); `objective` gives the value
  itself;
- attention is computed a block of queries at a time (full layers: the LFM2
  reference's segments; window layers: `WINDOW_BLOCK` queries against the
  `sliding_window` keys before the block and the block's own), each block
  under `jax.checkpoint`, and every layer too;
- the dense feed-forward and the head with its loss `TOKEN_BLOCK` tokens at a
  time; the held experts on every token weighted by the routing weight
  (nought where the token did not pick the expert), one after another in a
  `lax.scan` under `jax.checkpoint`;
- no multi-token head: `config.json` has no key for one;
- initialisation is normal(0, `initializer_range`), gains 1.

`cast` is applied to both operands of every contraction and to the stored
intermediates (the residual stream, the normed inputs, probabilities), as a
compute type is: the identity here, a rounding to a lower precision in the
control. Norm and softmax statistics, the router's probabilities, the
balance term and the loss stay in float32. The caller sets
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark.reference.kanana2 import _blocks, _rms
from benchmark.reference.lfm2 import attention as full_attention

TOKEN_BLOCK = 4096
WINDOW_BLOCK = 512


def kinds(cfg: dict) -> list:
    """(attention kind, feed-forward kind) of each layer."""
    return list(zip(cfg["layer_types"], cfg["mlp_layer_types"]))


def init_weights(cfg: dict, key) -> dict:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    std = cfg.get("initializer_range", 0.02)
    keys = iter(jax.random.split(key, 4 + 16 * len(cfg["layer_types"])))

    def mat(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def swiglu(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    layers = []
    for _, ff in kinds(cfg):
        p = {"in_norm": ones(d), "pre_mlp_norm": ones(d),
             "wq": mat(d, nq * hd), "wk": mat(d, nkv * hd),
             "wv": mat(d, nkv * hd), "wo": mat(nq * hd, d),
             "q_norm": ones(hd), "k_norm": ones(hd)}
        if ff == "dense":
            p["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            p["router"] = mat(d, cfg["router_num_experts"])
            p["experts"] = swiglu(cfg["moe_intermediate_size"],
                                  cfg["num_experts"])
        layers.append(p)
    return {"embed": mat(cfg["vocab_size"], d), "layers": layers,
            "final_norm": ones(d), "head": mat(d, cfg["vocab_size"])}


def init_bias(cfg: dict):
    """No selection bias: a zero row an expert layer, never moved."""
    n = sum(ff == "sparse" for _, ff in kinds(cfg))
    return jnp.zeros((n, cfg["router_num_experts"]), jnp.float32)


def update_bias(bias, counts, cfg: dict):
    return bias


def yarn_inverse_frequencies(section: dict, head_dim: int):
    """(inv (head_dim / 2,), magnitude) of a `yarn` section, from the
    formula in the module docstring."""
    theta, factor = section["rope_theta"], section["factor"]
    orig = section["original_max_position_embeddings"]

    def dim(turns):
        return head_dim * math.log(orig / (turns * 2 * math.pi)) / (
            2 * math.log(theta))

    low = max(math.floor(dim(section.get("beta_fast", 32))), 0)
    high = min(math.ceil(dim(section.get("beta_slow", 1))), head_dim - 1)
    i = jnp.arange(head_dim // 2, dtype=jnp.float32)
    r = 1.0 / theta ** (2.0 * i / head_dim)
    e = 1.0 - jnp.clip((i - low) / (high - low), 0.0, 1.0)
    magnitude = section.get("attention_factor",
                            0.1 * math.log(factor) + 1.0)
    return r / factor * (1.0 - e) + r * e, magnitude


def _rope(x, section: dict):
    """x: (b, heads, s, hd); position t of a row is t."""
    s, hd = x.shape[-2], x.shape[-1]
    if section.get("rope_type", "default") == "yarn":
        inv, magnitude = yarn_inverse_frequencies(section, hd)
    else:
        inv = 1.0 / section["rope_theta"] ** (
            2.0 * jnp.arange(hd // 2, dtype=jnp.float32) / hd)
        magnitude = 1.0
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    turned = x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)
    return turned * magnitude


def window_attention(q, k, v, window: int, cast=lambda t: t,
                     block=WINDOW_BLOCK):
    """q: (b, nq, s, hd); k, v: (b, nkv, s, hd). Query i sees keys
    i - window < j <= i: a block of queries against the `window` keys before
    it and its own, the keys padded with `window` noughts in front (masked)."""
    b, nq, s, hd = q.shape
    nkv = k.shape[1]
    block = min(block, s)
    if s % block:
        raise ValueError(f"{s} queries do not cut into blocks of {block}")
    n = s // block
    q = q.reshape(b, nkv, nq // nkv, n, block, hd)
    kp = jnp.pad(cast(k), ((0, 0), (0, 0), (window, 0), (0, 0)))
    vp = jnp.pad(cast(v), ((0, 0), (0, 0), (window, 0), (0, 0)))

    def one(at_and_q):
        at, qb = at_and_q           # the block's first query
        kb = jax.lax.dynamic_slice_in_dim(kp, at, window + block, axis=2)
        vb = jax.lax.dynamic_slice_in_dim(vp, at, window + block, axis=2)
        scores = jnp.einsum("bngqd,bnkd->bngqk", cast(qb), kb) / math.sqrt(hd)
        i = at + jnp.arange(block)[:, None]
        j = at - window + jnp.arange(window + block)[None, :]
        seen = (j <= i) & (i - j < window) & (j >= 0)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bngqk,bnkd->bngqd", cast(probs), vb)

    got = jax.lax.map(jax.checkpoint(one),
                      (block * jnp.arange(n), jnp.moveaxis(q, 3, 0)))
    return jnp.moveaxis(got, 0, 3).reshape(b, nq, s, hd)


def _swiglu(p, m, mm):
    return mm(jax.nn.silu(mm(m, p["w_gate"])) * mm(m, p["w_up"]), p["w_down"])


def route(m, router, cfg: dict, cast=lambda t: t):
    """(picked (T, k), weights (T, k), counts (E,), the mean probability of
    each expert (E,))."""
    probs = jax.nn.softmax(
        jnp.matmul(cast(m), cast(router)).astype(jnp.float32), axis=-1)
    w, picked = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    counts = jnp.sum(jax.nn.one_hot(picked, probs.shape[-1], dtype=jnp.float32),
                     axis=(0, 1))
    return picked, w, counts, jnp.mean(probs, axis=0)


def expert_layer(p, m, cfg: dict, cast=lambda t: t):
    """m: (T, d). The held experts' part (there is no other); the tokens
    routed to each of the router's experts and their mean probabilities."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b))

    picked, w, counts, mean_p = route(m, p["router"], cfg, cast)

    def add_expert(y, held):
        number, one = held
        w_e = jnp.sum(jnp.where(picked == number, w, 0.0), axis=-1)
        return y + w_e[:, None] * _swiglu(one, m, mm), None

    numbers = cfg.get("experts_held_offset", 0) + jnp.arange(cfg["num_experts"])
    y, _ = jax.lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(m),
                        (numbers, p["experts"]))
    return y, counts, mean_p


def _layer(p, h, cfg, cast, attn_kind, ff_kind):
    b, s, d = h.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    section = cfg["rope_parameters"][attn_kind]
    a = cast(_rms(h, p["in_norm"], eps))
    q = _rope(_rms(heads(mm(a, p["wq"]), nq), p["q_norm"], eps), section)
    k = _rope(_rms(heads(mm(a, p["wk"]), nkv), p["k_norm"], eps), section)
    v = heads(mm(a, p["wv"]), nkv)
    if attn_kind == "sliding_attention":
        o = window_attention(cast(q), cast(k), cast(v), cfg["sliding_window"],
                             cast)
    else:
        o = full_attention(cast(q), cast(k), cast(v), cast)
    h = cast(h + mm(cast(o.transpose(0, 2, 1, 3).reshape(b, s, nq * hd)),
                    p["wo"]))
    m = cast(_rms(h, p["pre_mlp_norm"], eps)).reshape(b * s, d)
    if ff_kind == "dense":
        y = _blocks(lambda t: _swiglu(p["mlp"], t, mm),
                    min(TOKEN_BLOCK, b * s), m)
        stats = None
    else:
        y, counts, mean_p = expert_layer(p, m, cfg, cast)
        stats = (counts, mean_p)
    return cast(h + y.reshape(b, s, d)), stats


def hidden(w: dict, ids, cfg: dict, cast=lambda t: t):
    """ids (B, S) -> (the final normed hidden state (B, S, d), counts: the
    tokens routed to each expert, a row an expert layer, and the mean
    probabilities in the same layout)."""
    h = cast(w["embed"][ids])
    counts, probs = [], []
    for p, (attn_kind, ff_kind) in zip(w["layers"], kinds(cfg)):
        h, stats = jax.checkpoint(
            lambda p_, h_, a=attn_kind, f=ff_kind: _layer(
                p_, h_, cfg, cast, a, f))(p, h)
        if stats is not None:
            counts.append(stats[0])
            probs.append(stats[1])
    h = cast(_rms(h, w["final_norm"], cfg["rms_norm_eps"]))
    return h, jnp.stack(counts), jnp.stack(probs)


def balance(counts, probs, tokens: int, cfg: dict):
    """`router_aux_loss_coef` x E x sum_e f_e P_e, pooled over the layers:
    `counts` and `probs` a row an expert layer, `tokens` the tokens a layer
    routed."""
    f = jnp.sum(counts, axis=0) / (counts.shape[0] * tokens)
    big_p = jnp.mean(probs, axis=0)
    return cfg["router_aux_loss_coef"] * probs.shape[-1] * jnp.sum(f * big_p)


def logits(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    h, _, _ = hidden(w, ids, cfg, cast)
    return jnp.matmul(cast(h), cast(w["head"])).astype(jnp.float32)


def _row_nll(w, h, labels, cast):
    b, s, d = h.shape
    head = cast(w["head"])

    def nll(hb, yb):
        z = jnp.matmul(hb, head).astype(jnp.float32)
        picked = jnp.take_along_axis(z, yb[:, None], axis=-1)[:, 0]
        return jax.nn.logsumexp(z, axis=-1) - picked

    rows = _blocks(nll, min(TOKEN_BLOCK, b * s), h.reshape(b * s, d),
                   labels.reshape(b * s).astype(jnp.int32))
    return jnp.mean(rows.reshape(b, s), axis=-1)


def objective(w, ids, labels, cfg, cast=lambda t: t):
    """The training loss of these rows as one step: the mean token
    cross-entropy plus the balance term."""
    h, counts, probs = hidden(w, ids, cfg, cast)
    return (jnp.mean(_row_nll(w, h, labels, cast))
            + balance(counts, probs, ids.size, cfg))


def losses_and_counts(w, ids, labels, cfg, cast=lambda t: t, bias=None):
    """(each row's mean token cross-entropy in value, with the gradient of
    the objective over the rows given (module docstring) (B,), counts)."""
    h, counts, probs = hidden(w, ids, cfg, cast)
    term = balance(counts, probs, ids.size, cfg)
    rows = _row_nll(w, h, labels, cast)
    return rows + (term - jax.lax.stop_gradient(term)), counts


def row_losses(w, x, y, cfg, cast=lambda t: t, bias=None):
    """x: ids (B, S); y: the next token of each (B, S)."""
    return losses_and_counts(w, x, y, cfg, cast, bias)[0]
