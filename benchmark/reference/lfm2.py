"""Plain LFM2-MoE (`model_type: lfm2_moe`, LiquidAI), float32, `jax.numpy` only:
a causal decoder whose layers mix tokens by a gated short convolution or by
grouped-query attention with rotary positions, under two pre-norms a layer,
with a sigmoid top-k expert layer that has no shared expert, and a head tied to
the embedding.

Imports nothing of the program. Weights are made here from a key, in this
file's own layout; the harness pours the same numbers into the program.

From the published `config.json` and the family's model file (T tokens a row,
d hidden):
- `h = E[ids]` (no multiplier);
- a layer: `h += Mixer(RMS_op(h))`, `h += FF(RMS_ffn(h))`; RMS norms with a
  learned gain, eps `norm_eps`; after the last layer one more RMS norm, then
  the head, `E^T` (`tie_embeddings`);
- `conv` mixer (gated short convolution): `[B | C | X] = W_in u` (d -> 3d, no
  bias), `z = B * X`, `c[t] = sum_{j=0..L-1} w[:, j] * z[t - (L-1) + j]` a
  channel, causal, `z` = 0 before the row's first token, `L = conv_L_cache`,
  `conv_bias` false, no activation; `y = W_out (C * c)` (d -> d);
- `full_attention` mixer: q (heads x hd), k, v (key-value heads x hd) from the
  normed input, hd = d / heads, no bias; q and k RMS-normed over a head's
  width (learned gain), then rotary positions, halves rotated, theta
  `rope_parameters.rope_theta`; causal `softmax(q k^T / sqrt(hd)) v`, a
  key-value head shared by heads / key-value heads queries; `W_o o`. No gate,
  no window;
- FF of the first `num_dense_layers` layers: `W_2 (silu(W_1 m) * W_3 m)`,
  width `intermediate_size`. Of the others: `s = sigmoid(W_r m)` in float32
  over `router_num_experts`; the `num_experts_per_tok` largest of `s + b` are
  picked (`b`, the expert bias, a buffer outside the gradient:
  `use_expert_bias`); `w = s[picked]`, `w / (sum(w) + 1e-6)`
  (`norm_topk_prob`), times `routed_scaling_factor`;
  `y = sum_e w_e SwiGLU_e(m)`, each expert `moe_intermediate_size` wide. No
  shared expert;
- the mean token cross-entropy of a row over the vocabulary held here.

The chip's share of a deployment: `num_experts` routed experts are held here,
those numbered from `experts_held_offset`; the router keeps its
`router_num_experts` outputs and what the absent experts would add is left
out (a token none of whose picks is held gets nought from the layer).
`vocab_size` is the slice of the vocabulary held here.

Departures, each noted:
- the expert bias's rule is the training framework's, not the model file's:
  after a step `b += bias_rate * sign(mean(n) - n_e)` and `b` is then centred
  (its mean taken off), `n_e` the step's tokens routed to expert e;
- attention is computed `QUERY_BLOCK` queries at a time, a `lax.map` over the
  blocks of one segment of `SEGMENT` queries against the keys up to that
  segment's end (masked), the block under `jax.checkpoint`: the same
  mathematics, and the scores of a block of 32 heads on 32 768 keys are 1 GB
  and not the row's 137 GB;
- the dense feed-forward and the head with its loss are computed
  `TOKEN_BLOCK` tokens at a time, each block under `jax.checkpoint`: a token's
  feed-forward and loss see no other token. The convolution runs over the
  whole row (a block would need its neighbour's last two tokens);
- the held experts are computed on every token and weighted by the routing
  weight (0 where the token did not pick the expert): no dispatch to test.
  They are added one after another in a `lax.scan` whose body is under
  `jax.checkpoint`;
- initialisation is normal(0, `initializer_range`), gains 1, the taps too.

`cast` is applied to both operands of every contraction (the convolution's
taps among them) and to the stored intermediates (the residual stream, the
normed inputs, `z`, `C * c`, probabilities), as a compute type is: the
identity here, a rounding to a lower precision in the control. Norm and
softmax statistics, the router's scores and the loss stay in float32, as the
program's bfloat16 policy keeps them. The caller sets
`jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
SEGMENT = 8192
TOKEN_BLOCK = 4096
ROUTE_EPS = 1e-6


def kinds(cfg: dict) -> list:
    """(mixer kind, feed-forward kind) of each layer."""
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(cfg["layer_types"])]


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def init_weights(cfg: dict, key) -> dict:
    d, hd = cfg["hidden_size"], head_dim(cfg)
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
    for mixer, ff in kinds(cfg):
        p = {"operator_norm": ones(d), "ffn_norm": ones(d)}
        if mixer == "conv":
            p["conv"] = {"w_b": mat(d, d), "w_c": mat(d, d), "w_x": mat(d, d),
                         "taps": mat(d, cfg["conv_L_cache"]),
                         "w_out": mat(d, d)}
        else:
            p.update(wq=mat(d, nq * hd), wk=mat(d, nkv * hd),
                     wv=mat(d, nkv * hd), wo=mat(nq * hd, d),
                     q_norm=ones(hd), k_norm=ones(hd))
        if ff == "dense":
            p["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            p["router"] = mat(d, cfg["router_num_experts"])
            p["experts"] = swiglu(cfg["moe_intermediate_size"],
                                  cfg["num_experts"])
        layers.append(p)
    w = {"embed": mat(cfg["vocab_size"], d), "layers": layers,
         "final_norm": ones(d)}
    if not cfg.get("tie_embeddings", True):
        w["head"] = mat(d, cfg["vocab_size"])
    return w


def init_bias(cfg: dict):
    """The expert bias of every expert layer: (expert layers, router width)."""
    n = sum(ff == "moe" for _, ff in kinds(cfg))
    return jnp.zeros((n, cfg["router_num_experts"]), jnp.float32)


def update_bias(bias, counts, cfg: dict):
    """After a step: towards the experts that got fewer tokens than the mean,
    then centred. `counts`: the step's tokens routed to each expert, a row a
    layer."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    bias = bias + cfg["bias_rate"] * jnp.sign(mean - counts)
    return bias - jnp.mean(bias, axis=-1, keepdims=True)


def _rms(x, gain, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * gain


def _rope(x, theta):
    """x: (b, heads, s, head_dim); position t of a row is t."""
    s, hd = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    ang = jnp.concatenate([ang, ang], axis=-1)
    x1, x2 = x[..., : hd // 2], x[..., hd // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], axis=-1) * jnp.sin(ang)


def _blocks(fn, block: int, *arrays):
    """`fn(*arrays)` over the arrays' leading axis cut into blocks of `block`
    (which divides it), one block after another, each under
    `jax.checkpoint`; the results joined along that axis."""
    n = arrays[0].shape[0] // block
    if n <= 1:
        return jax.checkpoint(fn)(*arrays)
    cut = tuple(a.reshape(n, block, *a.shape[1:]) for a in arrays)
    out = jax.lax.map(jax.checkpoint(lambda args: fn(*args)), cut)
    return out.reshape(n * block, *out.shape[2:])


def attention(q, k, v, cast=lambda t: t, block=QUERY_BLOCK, segment=SEGMENT):
    """q: (b, nq, s, hd); k, v: (b, nkv, s, hd). Causal, every key seen."""
    b, nq, s, hd = q.shape
    nkv = k.shape[1]
    q = q.reshape(b, nkv, nq // nkv, s, hd)
    segment = min(segment, s)
    block = min(block, segment)
    if s % segment or segment % block:
        raise ValueError(f"{s} queries do not cut into segments of {segment} "
                         f"and blocks of {block}")
    out = []
    for q0 in range(0, s, segment):
        q1 = q0 + segment
        kb, vb = cast(k[:, :, :q1]), cast(v[:, :, :q1])
        j = jnp.arange(q1)[None, :]

        def one(at_and_q):
            at, qb = at_and_q
            scores = jnp.einsum("bngqd,bnkd->bngqk", cast(qb), kb) / math.sqrt(hd)
            i = at + jnp.arange(qb.shape[3])[:, None]
            probs = jax.nn.softmax(jnp.where(j <= i, scores, -1e30), axis=-1)
            return jnp.einsum("bngqk,bnkd->bngqd", cast(probs), vb)

        n = segment // block
        seg = q[:, :, :, q0:q1].reshape(b, nkv, nq // nkv, n, block, hd)
        starts = q0 + block * jnp.arange(n)
        got = jax.lax.map(jax.checkpoint(one),
                          (starts, jnp.moveaxis(seg, 3, 0)))
        out.append(jnp.moveaxis(got, 0, 3).reshape(b, nkv, nq // nkv,
                                                   segment, hd))
    return jnp.concatenate(out, axis=3).reshape(b, nq, s, hd)


def short_conv(p, u, cast=lambda t: t):
    """The gated short convolution over u (b, s, d)."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b))

    s, taps = u.shape[1], p["taps"]
    z = cast(mm(u, p["w_b"]) * mm(u, p["w_x"]))
    width = taps.shape[1]
    z = jnp.pad(z, ((0, 0), (width - 1, 0), (0, 0)))
    c = sum(cast(taps[:, j]) * z[:, j:j + s] for j in range(width))
    return mm(cast(mm(u, p["w_c"]) * c), p["w_out"])


def _swiglu(p, m, mm):
    return mm(jax.nn.silu(mm(m, p["w_gate"])) * mm(m, p["w_up"]), p["w_down"])


def route(m, router, bias, cfg: dict, cast=lambda t: t):
    """(picked (T, k) expert numbers, their weights (T, k), counts (E,))."""
    scores = jax.nn.sigmoid(jnp.matmul(cast(m), cast(router)).astype(jnp.float32))
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picked, axis=-1)
    if cfg["norm_topk_prob"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + ROUTE_EPS)
    w = w * cfg["routed_scaling_factor"]
    counts = jnp.sum(jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32),
                     axis=(0, 1))
    return picked, w, counts


def expert_layer(p, m, bias, cfg: dict, cast=lambda t: t):
    """m: (T, d). The held experts' part (there is no other); and the tokens
    routed to each of the router's experts."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b))

    picked, w, counts = route(m, p["router"], bias, cfg, cast)

    def add_expert(y, held):
        number, one = held
        w_e = jnp.sum(jnp.where(picked == number, w, 0.0), axis=-1)
        return y + w_e[:, None] * _swiglu(one, m, mm), None

    numbers = cfg.get("experts_held_offset", 0) + jnp.arange(cfg["num_experts"])
    y, _ = jax.lax.scan(jax.checkpoint(add_expert), jnp.zeros_like(m),
                        (numbers, p["experts"]))
    return y, counts


def _layer(p, h, bias, cfg, cast, mixer, ff_kind):
    b, s, d = h.shape
    hd, eps = head_dim(cfg), cfg["norm_eps"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    a = cast(_rms(h, p["operator_norm"], eps))
    if mixer == "conv":
        y = short_conv(p["conv"], a, cast)
    else:
        theta = cfg["rope_parameters"]["rope_theta"]
        q = _rope(_rms(heads(mm(a, p["wq"]), nq), p["q_norm"], eps), theta)
        k = _rope(_rms(heads(mm(a, p["wk"]), nkv), p["k_norm"], eps), theta)
        v = heads(mm(a, p["wv"]), nkv)
        o = attention(cast(q), cast(k), cast(v), cast)
        y = mm(cast(o.transpose(0, 2, 1, 3).reshape(b, s, nq * hd)), p["wo"])
    h = cast(h + y)
    m = cast(_rms(h, p["ffn_norm"], eps))
    if ff_kind == "dense":
        flat = m.reshape(b * s, d)
        y = _blocks(lambda t: _swiglu(p["mlp"], t, mm),
                    min(TOKEN_BLOCK, b * s), flat)
        y, counts = y.reshape(b, s, d), None
    else:
        y, counts = expert_layer(p, m.reshape(b * s, d), bias, cfg, cast)
        y = y.reshape(b, s, d)
    return cast(h + y), counts


def hidden(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    """ids (B, S) -> (the final normed hidden state (B, S, d), counts: the
    tokens routed to each expert, a row an expert layer)."""
    bias = init_bias(cfg) if bias is None else bias
    h = cast(w["embed"][ids])
    counts, at = [], 0
    for p, (mixer, ff_kind) in zip(w["layers"], kinds(cfg)):
        b_l = bias[at] if ff_kind == "moe" else None
        h, n = jax.checkpoint(
            lambda p_, h_, b_, a=mixer, f=ff_kind: _layer(
                p_, h_, b_, cfg, cast, a, f))(p, h, b_l)
        if ff_kind == "moe":
            counts.append(n)
            at += 1
    h = cast(_rms(h, w["final_norm"], cfg["norm_eps"]))
    return h, (jnp.stack(counts) if counts else jnp.zeros((0, 1), jnp.float32))


def _head(w: dict):
    return w["head"] if "head" in w else w["embed"].T


def logits(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    h, _ = hidden(w, ids, cfg, cast, bias)
    return jnp.matmul(cast(h), cast(_head(w))).astype(jnp.float32)


def losses_and_counts(w, ids, labels, cfg, cast=lambda t: t, bias=None):
    """(the mean token cross-entropy of each row (B,), counts)."""
    h, counts = hidden(w, ids, cfg, cast, bias)
    b, s, d = h.shape
    head = cast(_head(w))

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
