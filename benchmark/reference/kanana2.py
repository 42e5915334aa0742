"""Plain Kanana-2 (`model_type: deepseek_v3` with `q_lora_rank` null, kakaocorp),
float32, `jax.numpy` only: a causal decoder whose every layer mixes tokens by
multi-head latent attention (keys and values of all heads from one low-rank
latent a token, one rotary key shared by all heads, queries and keys wider than
values), under two pre-norms a layer, with one leading dense feed-forward and
then sigmoid top-k expert layers with shared experts, and a head of its own.

Imports nothing of the program. Weights are made here from a key, in this
file's own layout (every projection apart); the harness pours the same numbers
into the program.

From the published `config.json` and the family's model file (T tokens a row,
d hidden, H heads, x a layer's input):

    u  = rms(x; g_in)
    q  = u W_q                      (d -> H x (nope + rope))   q = [q_nope | q_rope] a head
    a  = u W_kva                    (d -> rank + rope)         a = [c | k_rope]: k_rope is ONE head, shared by all H
    kv = rms(c; g_kv) W_kvb         (rank -> H x (nope + v))   kv = [k_nope | v] a head
    q_rope, k_rope <- rotary(., theta) over interleaved pairs (x[2i], x[2i+1])
    q_h = [q_nope_h | q_rope_h],  k_h = [k_nope_h | k_rope]    (nope + rope wide)
    o_h = softmax(q_h k_h^T / sqrt(nope + rope) + causal) v_h  (v wide)
    x  += [o_1 .. o_H] W_o          (H x v -> d)
    m  = rms(x; g_mlp)
    x  += SwiGLU_shared(m; width n_shared x moe width)
          + scale * sum over the k picks e of  w_e SwiGLU_e(m; moe width),
       s = sigmoid(W_r m) over the router's experts, picks = top-k of s + b (b
       a buffer, no gradient), w = s[picks] / (sum of s[picks] + 1e-20)

- `h = E[ids]` (no multiplier); RMS norms with a learned gain, eps
  `rms_norm_eps`; after the last layer one more RMS norm, then the head (its
  own: `tie_word_embeddings` false);
- here `W_q` is kept as `wq_nope` and `wq_rope`, `W_kva` as `w_c` and `w_kr`,
  `W_kvb` as `wk_nope` and `wv`, a head's columns side by side in each;
- the first `first_k_dense_replace` layers have a dense SwiGLU of
  `intermediate_size` in place of the expert layer;
- `scoring_func` sigmoid, `topk_method` noaux_tc with `n_group` =
  `topk_group` = 1 (no group limit), `norm_topk_prob`,
  `routed_scaling_factor`; `rope_interleave` false turns the rotary pairs
  into halves `(x[i], x[i + rope / 2])` (the harness's planted fault);
- the mean token cross-entropy of a row over the vocabulary held here.

The chip's share of a deployment: `n_routed_experts` routed experts are held
here, those numbered from `experts_held_offset`; the router keeps its
`router_num_experts` outputs and what the absent experts would add is left
out; the shared experts are whole. `vocab_size` is the slice of the vocabulary
held here.

Departures, each noted:
- the selection bias's rule is the training framework's, not the model file's:
  after a step `b += bias_rate * sign(mean(n) - n_e)` and `b` is then centred
  (its mean taken off), `n_e` the step's tokens routed to expert e;
- attention is computed `QUERY_BLOCK` queries at a time, a `lax.map` over the
  blocks of one segment of `SEGMENT` queries against the keys up to that
  segment's end (masked), the block under `jax.checkpoint`: the same
  mathematics, and the scores of a block of 32 heads on 16 384 keys are 0.5 GB
  and not the row's 34 GB;
- the dense feed-forward, the shared experts and the head with its loss are
  computed `TOKEN_BLOCK` tokens at a time, each block under `jax.checkpoint`:
  a token's feed-forward and loss see no other token;
- the held experts are computed on every token and weighted by the routing
  weight (0 where the token did not pick the expert): no dispatch to test.
  They are added one after another in a `lax.scan` whose body is under
  `jax.checkpoint`;
- initialisation is normal(0, `initializer_range`), gains 1.

`cast` is applied to both operands of every contraction and to the stored
intermediates (the residual stream, the normed inputs, the latent, the rotated
queries and keys, probabilities), as a compute type is: the identity here, a
rounding to a lower precision in the control. Norm and softmax statistics, the
router's scores and the loss stay in float32, as the program's bfloat16 policy
keeps them. The caller sets `jax.default_matmul_precision("highest")`.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 256
SEGMENT = 8192
TOKEN_BLOCK = 4096
ROUTE_EPS = 1e-20


def kinds(cfg: dict) -> list:
    """The feed-forward kind of each layer (the mixer is the same in all)."""
    return ["dense" if i < cfg["first_k_dense_replace"] else "moe"
            for i in range(cfg["num_hidden_layers"])]


def router_width(cfg: dict) -> int:
    return cfg.get("router_num_experts", cfg["n_routed_experts"])


def init_weights(cfg: dict, key) -> dict:
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    v, rank = cfg["v_head_dim"], cfg["kv_lora_rank"]
    std = cfg.get("initializer_range", 0.02)
    keys = iter(jax.random.split(key, 4 + 20 * cfg["num_hidden_layers"]))

    def mat(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ones(n):
        return jnp.ones((n,), jnp.float32)

    def swiglu(width, *lead):
        return {"w_gate": mat(*lead, d, width), "w_up": mat(*lead, d, width),
                "w_down": mat(*lead, width, d)}

    layers = []
    for ff in kinds(cfg):
        p = {"attn_norm": ones(d), "ffn_norm": ones(d),
             "wq_nope": mat(d, h * nope), "wq_rope": mat(d, h * rope),
             "w_c": mat(d, rank), "w_kr": mat(d, rope), "kv_norm": ones(rank),
             "wk_nope": mat(rank, h * nope), "wv": mat(rank, h * v),
             "wo": mat(h * v, d)}
        if ff == "dense":
            p["mlp"] = swiglu(cfg["intermediate_size"])
        else:
            width = cfg["moe_intermediate_size"]
            p["router"] = mat(d, router_width(cfg))
            p["shared"] = swiglu(width * cfg["n_shared_experts"])
            p["experts"] = swiglu(width, cfg["n_routed_experts"])
        layers.append(p)
    return {"embed": mat(cfg["vocab_size"], d), "layers": layers,
            "final_norm": ones(d), "head": mat(d, cfg["vocab_size"])}


def init_bias(cfg: dict):
    """The selection bias of every expert layer: (expert layers, router width)."""
    n = sum(ff == "moe" for ff in kinds(cfg))
    return jnp.zeros((n, router_width(cfg)), jnp.float32)


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


def rotary(x, theta, interleave: bool = True):
    """x: (b, heads, s, width); position t of a row is t. Frequency i turns
    the pair (x[2i], x[2i+1]) by t / theta^(2i / width); not `interleave`,
    the pair (x[i], x[i + width / 2])."""
    s, width = x.shape[-2], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, width, 2, dtype=jnp.float32) / width))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    if interleave:
        pairs = x.reshape(*x.shape[:-1], width // 2, 2)
        x1, x2 = pairs[..., 0], pairs[..., 1]
        return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                         axis=-1).reshape(x.shape)
    x1, x2 = x[..., : width // 2], x[..., width // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)


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
    """q, k: (b, H, s, nope + rope); v: (b, H, s, v). Causal, every key seen;
    the scores are scaled by the root of q's width."""
    b, h, s, width = q.shape
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
            scores = jnp.einsum("bhqd,bhkd->bhqk", cast(qb), kb) / math.sqrt(width)
            i = at + jnp.arange(qb.shape[2])[:, None]
            probs = jax.nn.softmax(jnp.where(j <= i, scores, -1e30), axis=-1)
            return jnp.einsum("bhqk,bhkd->bhqd", cast(probs), vb)

        n = segment // block
        seg = q[:, :, q0:q1].reshape(b, h, n, block, width)
        starts = q0 + block * jnp.arange(n)
        got = jax.lax.map(jax.checkpoint(one), (starts, jnp.moveaxis(seg, 2, 0)))
        out.append(jnp.moveaxis(got, 0, 2).reshape(b, h, segment, v.shape[-1]))
    return jnp.concatenate(out, axis=2)


def latent_attention(p, u, cfg: dict, cast=lambda t: t):
    """The mixer over the normed input u (b, s, d), before W_o: (b, s, H x v)."""
    b, s, _ = u.shape
    h, theta = cfg["num_attention_heads"], cfg["rope_theta"]
    interleave = cfg.get("rope_interleave", True)

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    def heads(t):
        return t.reshape(b, s, h, -1).transpose(0, 2, 1, 3)

    q_nope = heads(mm(u, p["wq_nope"]))
    q_rope = rotary(heads(mm(u, p["wq_rope"])), theta, interleave)
    c = cast(_rms(mm(u, p["w_c"]), p["kv_norm"], cfg["rms_norm_eps"]))
    k_rope = rotary(mm(u, p["w_kr"])[:, None], theta, interleave)   # one head
    k_nope, v = heads(mm(c, p["wk_nope"])), heads(mm(c, p["wv"]))
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope, (b, h, s, k_rope.shape[-1]))], axis=-1)
    o = attention(cast(q), cast(k), cast(v), cast)
    return o.transpose(0, 2, 1, 3).reshape(b, s, -1)


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
    """m: (T, d). The shared experts (one SwiGLU as wide as all of them) plus
    the held experts' part; and the tokens routed to each of the router's
    experts."""
    def mm(a, b):
        return jnp.matmul(cast(a), cast(b))

    picked, w, counts = route(m, p["router"], bias, cfg, cast)

    def add_expert(y, held):
        number, one = held
        w_e = jnp.sum(jnp.where(picked == number, w, 0.0), axis=-1)
        return y + w_e[:, None] * _swiglu(one, m, mm), None

    numbers = (cfg.get("experts_held_offset", 0)
               + jnp.arange(cfg["n_routed_experts"]))
    shared = _blocks(lambda t: _swiglu(p["shared"], t, mm),
                     min(TOKEN_BLOCK, m.shape[0]), m)
    y, _ = jax.lax.scan(jax.checkpoint(add_expert), shared,
                        (numbers, p["experts"]))
    return y, counts


def _layer(p, h, bias, cfg, cast, ff_kind):
    b, s, d = h.shape
    eps = cfg["rms_norm_eps"]

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    u = cast(_rms(h, p["attn_norm"], eps))
    h = cast(h + mm(cast(latent_attention(p, u, cfg, cast)), p["wo"]))
    m = cast(_rms(h, p["ffn_norm"], eps)).reshape(b * s, d)
    if ff_kind == "dense":
        y = _blocks(lambda t: _swiglu(p["mlp"], t, mm),
                    min(TOKEN_BLOCK, b * s), m)
        counts = None
    else:
        y, counts = expert_layer(p, m, bias, cfg, cast)
    return cast(h + y.reshape(b, s, d)), counts


def hidden(w: dict, ids, cfg: dict, cast=lambda t: t, bias=None):
    """ids (B, S) -> (the final normed hidden state (B, S, d), counts: the
    tokens routed to each expert, a row an expert layer)."""
    bias = init_bias(cfg) if bias is None else bias
    h = cast(w["embed"][ids])
    counts, at = [], 0
    for p, ff_kind in zip(w["layers"], kinds(cfg)):
        b_l = bias[at] if ff_kind == "moe" else None
        h, n = jax.checkpoint(
            lambda p_, h_, b_, f=ff_kind: _layer(p_, h_, b_, cfg, cast, f))(
                p, h, b_l)
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
