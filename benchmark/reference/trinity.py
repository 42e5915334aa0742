"""Plain Trinity-Mini (`model_type: afmoe`, arcee-ai), float32, `jax.numpy` only:
a causal decoder with window and full attention mixed, a gated grouped-query
attention and a sigmoid top-k expert layer with one shared expert.

Imports nothing of the program. Weights are made here from a key, in this
file's own layout; the harness pours the same numbers into the program.

From the published `config.json` and `modeling_afmoe.py` (T tokens, d hidden):
- `h = E[ids] * sqrt(d)` (`mup_enabled`);
- a layer: `h += RMS_post_attn(Attn(RMS_in(h)))`,
  `h += RMS_post_mlp(MLP(RMS_pre_mlp(h)))`, eps `rms_norm_eps`, learned gains;
- attention: q (heads x head_dim), k, v (key-value heads x head_dim) and a
  gate g (heads x head_dim) from the normed input; q and k RMS-normed over a
  head's width (learned gain); rotary embedding (theta `rope_theta`, halves
  rotated) on q and k of `sliding_attention` layers only, `full_attention`
  layers carry no position; softmax(q k^T / sqrt(head_dim)) v, causal, a key-value
  head shared by heads / key-value heads queries, and on sliding layers key j
  seen from query i iff 0 <= i - j < `sliding_window`; `W_o (o * sigmoid(g))`;
- the first `num_dense_layers` layers: `W_down(silu(W_gate m) * W_up m)`;
- the others: `s = sigmoid(W_r m)` over `router_num_experts`, the
  `num_experts_per_tok` largest of `s + b` picked (`b` the selection bias,
  outside the gradient), `w = s[picked]`, `w / (sum(w) + 1e-20) * route_scale`
  (`route_norm`), `y = SwiGLU_shared(m) + sum_e w_e SwiGLU_e(m)`; one group, so
  no group limit;
- final RMS norm, an untied head, the mean token cross-entropy of a row.

The chip's share of a deployment: `num_experts` routed experts are held here,
those numbered from `experts_held_offset`; the router keeps its
`router_num_experts` outputs and what the absent experts would add is left out.
`vocab_size` is the slice of the vocabulary held here.

Departures, each noted:
- the selection bias's rule is the training framework's, not the model file's:
  after a step `b += load_balance_coeff * sign(mean(n) - n_e)` and `b` is then
  centred (its mean taken off), `n_e` the step's tokens routed to expert e;
- attention is computed a block of queries at a time against the keys that
  block can see, each block and each layer under `jax.checkpoint`: the same
  mathematics, and 8192 tokens with their gradients fit one chip;
- the held experts are computed on every token and weighted by the routing
  weight (0 where the token did not pick the expert): no dispatch to test.
  They are added one after another in a `lax.scan` whose body is under
  `jax.checkpoint`: the compiler sees one expert, not `num_experts` copies
  (half the compile time at 16), and keeps one expert's intermediates;
- initialisation is normal(0, `initializer_range`), gains 1.

`cast` is applied to both operands of every contraction and to the stored
intermediates (the residual stream, the normed inputs, probabilities, gated
outputs), as a compute type is: the identity here, a rounding to a lower
precision in the control. Norm and softmax statistics, the router's scores
and the loss stay in float32, as the program's bfloat16 policy keeps them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 1024


def kinds(cfg: dict) -> list:
    """(attention kind, feed-forward kind) of each layer."""
    return [(kind, "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, kind in enumerate(cfg["layer_types"])]


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
        p = {"in_norm": ones(d), "post_attn_norm": ones(d),
             "pre_mlp_norm": ones(d), "post_mlp_norm": ones(d),
             "wq": mat(d, nq * hd), "wk": mat(d, nkv * hd),
             "wv": mat(d, nkv * hd), "wg": mat(d, nq * hd),
             "wo": mat(nq * hd, d), "q_norm": ones(hd), "k_norm": ones(hd)}
        if ff == "dense":
            p["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            width = cfg["moe_intermediate_size"]
            p["router"] = mat(d, cfg["router_num_experts"])
            p["shared"] = swiglu(width * cfg["num_shared_experts"])
            p["experts"] = swiglu(width, cfg["num_experts"])
        layers.append(p)
    return {"embed": mat(cfg["vocab_size"], d), "layers": layers,
            "final_norm": ones(d), "head": mat(d, cfg["vocab_size"])}


def init_bias(cfg: dict):
    """The selection bias of every expert layer: (expert layers, router width)."""
    n = sum(ff == "moe" for _, ff in kinds(cfg))
    return jnp.zeros((n, cfg["router_num_experts"]), jnp.float32)


def update_bias(bias, counts, cfg: dict):
    """After a step: towards the experts that got fewer tokens than the mean,
    then centred. `counts`: the step's tokens routed to each expert, a row a
    layer."""
    mean = jnp.mean(counts, axis=-1, keepdims=True)
    bias = bias + cfg["load_balance_coeff"] * jnp.sign(mean - counts)
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


def attention(q, k, v, window, cast=lambda t: t, block=QUERY_BLOCK):
    """q: (b, nq, s, hd); k, v: (b, nkv, s, hd). Causal; `window` None or the
    number of keys a query sees, itself included."""
    b, nq, s, hd = q.shape
    nkv = k.shape[1]
    q = q.reshape(b, nkv, nq // nkv, s, hd)
    block = min(block, s)

    @jax.checkpoint
    def one(qb, kb, vb, q0, k0):
        scores = jnp.einsum("bngqd,bnkd->bngqk", cast(qb), cast(kb)) / math.sqrt(hd)
        i = q0 + jnp.arange(qb.shape[3])[:, None]
        j = k0 + jnp.arange(kb.shape[2])[None, :]
        seen = j <= i
        if window is not None:
            seen = seen & (i - j < window)
        probs = jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1)
        return jnp.einsum("bngqk,bnkd->bngqd", cast(probs), cast(vb))

    out = []
    for q0 in range(0, s, block):
        q1 = min(q0 + block, s)
        k0 = 0 if window is None else max(0, q0 - window + 1)
        out.append(one(q[:, :, :, q0:q1], k[:, :, k0:q1], v[:, :, k0:q1], q0, k0))
    return jnp.concatenate(out, axis=3).reshape(b, nq, s, hd)


def _swiglu(p, m, mm):
    return mm(jax.nn.silu(mm(m, p["w_gate"])) * mm(m, p["w_up"]), p["w_down"])


def route(m, router, bias, cfg: dict, cast=lambda t: t):
    """(picked (T, k) expert numbers, their weights (T, k), counts (E,))."""
    scores = jax.nn.sigmoid(jnp.matmul(cast(m), cast(router)).astype(jnp.float32))
    _, picked = jax.lax.top_k(scores + jax.lax.stop_gradient(bias),
                              cfg["num_experts_per_tok"])
    w = jnp.take_along_axis(scores, picked, axis=-1)
    if cfg["route_norm"]:
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    w = w * cfg["route_scale"]
    counts = jnp.sum(jax.nn.one_hot(picked, scores.shape[-1], dtype=jnp.float32),
                     axis=(0, 1))
    return picked, w, counts


def expert_layer(p, m, bias, cfg: dict, cast=lambda t: t):
    """m: (T, d). The shared expert plus the held experts' part; and the
    tokens routed to each of the router's experts."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b))

    picked, w, counts = route(m, p["router"], bias, cfg, cast)

    def add_expert(y, held):
        number, one = held
        w_e = jnp.sum(jnp.where(picked == number, w, 0.0), axis=-1)
        return y + w_e[:, None] * _swiglu(one, m, mm), None

    numbers = cfg.get("experts_held_offset", 0) + jnp.arange(cfg["num_experts"])
    y, _ = jax.lax.scan(jax.checkpoint(add_expert),
                        _swiglu(p["shared"], m, mm), (numbers, p["experts"]))
    return y, counts


def _layer(p, h, bias, cfg, cast, attn_kind, ff_kind):
    b, s, d = h.shape
    hd, eps = cfg["head_dim"], cfg["rms_norm_eps"]
    nq, nkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    def heads(t, n):
        return t.reshape(b, s, n, hd).transpose(0, 2, 1, 3)

    a = cast(_rms(h, p["in_norm"], eps))
    q = _rms(heads(mm(a, p["wq"]), nq), p["q_norm"], eps)
    k = _rms(heads(mm(a, p["wk"]), nkv), p["k_norm"], eps)
    v = heads(mm(a, p["wv"]), nkv)
    window = None
    if attn_kind == "sliding_attention":
        q, k = _rope(q, cfg["rope_theta"]), _rope(k, cfg["rope_theta"])
        window = cfg["sliding_window"]
    o = attention(cast(q), cast(k), cast(v), window, cast)
    o = o.transpose(0, 2, 1, 3).reshape(b, s, nq * hd)
    o = cast(o * jax.nn.sigmoid(mm(a, p["wg"])))
    h = cast(h + _rms(mm(o, p["wo"]), p["post_attn_norm"], eps))
    m = cast(_rms(h, p["pre_mlp_norm"], eps))
    if ff_kind == "dense":
        y, counts = _swiglu(p["mlp"], m, mm), None
    else:
        y, counts = expert_layer(p, m.reshape(b * s, d), bias, cfg, cast)
        y = y.reshape(b, s, d)
    return cast(h + _rms(y, p["post_mlp_norm"], eps)), counts


def hidden(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    """ids (B, S) -> (the final normed hidden state (B, S, d), counts: the
    tokens routed to each expert, a row an expert layer)."""
    bias = init_bias(cfg) if bias is None else bias
    h = cast(w["embed"][ids] * math.sqrt(cfg["hidden_size"]))
    counts, at = [], 0
    for p, (attn_kind, ff_kind) in zip(w["layers"], kinds(cfg)):
        b_l = bias[at] if ff_kind == "moe" else None
        h, n = jax.checkpoint(
            lambda p_, h_, b_, a=attn_kind, f=ff_kind: _layer(
                p_, h_, b_, cfg, cast, a, f))(p, h, b_l)
        if ff_kind == "moe":
            counts.append(n)
            at += 1
    h = cast(_rms(h, w["final_norm"], cfg["rms_norm_eps"]))
    return h, (jnp.stack(counts) if counts else jnp.zeros((0, 1), jnp.float32))


def logits(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    h, _ = hidden(w, ids, cfg, cast, bias)
    return jnp.matmul(cast(h), cast(w["head"])).astype(jnp.float32)


def losses_and_counts(w, ids, labels, cfg, cast=lambda t: t, bias=None):
    """(the mean token cross-entropy of each row (B,), counts)."""
    h, counts = hidden(w, ids, cfg, cast, bias)
    z = jnp.matmul(cast(h), cast(w["head"])).astype(jnp.float32)
    picked = jnp.take_along_axis(z, labels[..., None].astype(jnp.int32),
                                 axis=-1)[..., 0]
    nll = jax.nn.logsumexp(z, axis=-1) - picked
    return jnp.mean(nll, axis=-1), counts


def row_losses(w, x, y, cfg, cast=lambda t: t, bias=None):
    """x: ids (B, S); y: the next token of each (B, S)."""
    return losses_and_counts(w, x, y, cfg, cast, bias)[0]
