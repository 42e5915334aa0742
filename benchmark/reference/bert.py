"""Plain BERT classifier (Devlin et al. 2018), float32, `jax.numpy` only.

Imports nothing of the program. Weights are made here from a key, in this
file's own layout; the harness pours the same numbers into the program.

Follows the published model: word + position + token-type embeddings, LayerNorm
(eps 1e-12), post-LN blocks (self-attention, GELU feed-forward), tanh pooler
over the first token, a dense head. Departures, each noted:
- GELU is the tanh approximation, as in Google's released `modeling.py`.
- Padded keys get an additive -1e9 (the release uses -10000); with float32
  softmax both give them weight 0.
- Initialisation is normal(0, 0.02) without truncation; biases 0, LN gains 1.

`cast` is applied to both operands of every contraction and to every stored
intermediate (the residual stream, LayerNorm and GELU outputs, attention
probabilities), as a compute type is: the identity here, a rounding to a lower
precision in the control (`optim.lower_precision`). Statistics of LayerNorm and
softmax stay in float32, as the program's bf16 policy keeps them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

LN_EPS = 1e-12
LOSS_EPS = 1e-7     # the stated loss clips probabilities to [1e-7, 1]


def init_weights(cfg: dict, key) -> dict:
    """Weights of the whole classifier from one key. `cfg` holds the published
    names: vocab_size, hidden_size, num_hidden_layers, intermediate_size,
    max_position_embeddings, type_vocab_size, num_labels."""
    h, m = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg.get("initializer_range", 0.02)
    keys = iter(jax.random.split(key, 8 + 6 * cfg["num_hidden_layers"]))

    def mat(*shape):
        return std * jax.random.normal(next(keys), shape, jnp.float32)

    def ln():
        return {"g": jnp.ones((h,), jnp.float32),
                "b": jnp.zeros((h,), jnp.float32)}

    def zeros(n):
        return jnp.zeros((n,), jnp.float32)

    layers = []
    for _ in range(cfg["num_hidden_layers"]):
        layers.append({
            "wq": mat(h, h), "bq": zeros(h), "wk": mat(h, h), "bk": zeros(h),
            "wv": mat(h, h), "bv": zeros(h), "wo": mat(h, h), "bo": zeros(h),
            "ln1": ln(), "w1": mat(h, m), "b1": zeros(m),
            "w2": mat(m, h), "b2": zeros(h), "ln2": ln()})
    return {
        "word": mat(cfg["vocab_size"], h),
        "pos": mat(cfg["max_position_embeddings"], h),
        "type": mat(cfg["type_vocab_size"], h),
        "emb_ln": ln(), "layers": layers,
        "pool": {"w": mat(h, h), "b": zeros(h)},
        "cls": {"w": mat(h, cfg["num_labels"]),
                "b": zeros(cfg["num_labels"])}}


def _layer_norm(x, p):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + LN_EPS) * p["g"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def logits(w: dict, x, cfg: dict, cast=lambda t: t):
    """`x` = [ids, token types, mask], each (B, S). Returns (B, labels)."""
    ids, types, mask = x
    n_head = cfg["num_attention_heads"]
    b, s = ids.shape

    def mm(a, bmat):
        return jnp.matmul(cast(a), cast(bmat))

    e = w["word"][ids] + w["type"][types] + w["pos"][jnp.arange(s)][None]
    h = cast(_layer_norm(cast(e), w["emb_ln"]))
    bias = (1.0 - mask.astype(jnp.float32))[:, None, None, :] * -1e9
    d = h.shape[-1] // n_head

    def heads(t):
        return t.reshape(b, s, n_head, d).transpose(0, 2, 1, 3)

    for p in w["layers"]:
        q = heads(cast(mm(h, p["wq"]) + p["bq"]))
        k = heads(cast(mm(h, p["wk"]) + p["bk"]))
        v = heads(cast(mm(h, p["wv"]) + p["bv"]))
        scores = jnp.einsum("bnqd,bnkd->bnqk", q, k) / math.sqrt(d)
        probs = cast(jax.nn.softmax(cast(scores) + bias, axis=-1))
        ctx = cast(jnp.einsum("bnqk,bnkd->bnqd", probs, v))
        ctx = ctx.transpose(0, 2, 1, 3).reshape(b, s, n_head * d)
        a = cast(mm(ctx, p["wo"]) + p["bo"])
        h = cast(_layer_norm(cast(h + a), p["ln1"]))
        f = cast(_gelu(cast(mm(h, p["w1"]) + p["b1"])))
        f = cast(mm(f, p["w2"]) + p["b2"])
        h = cast(_layer_norm(cast(h + f), p["ln2"]))
    pooled = cast(jnp.tanh(mm(h[:, 0], w["pool"]["w"]) + w["pool"]["b"]))
    return mm(pooled, w["cls"]["w"]) + w["cls"]["b"]


def probabilities(w, x, cfg, cast=lambda t: t):
    """What the served classifier answers: softmax over the labels."""
    return jax.nn.softmax(logits(w, x, cfg, cast), axis=-1)


def row_losses(w, x, y, cfg, cast=lambda t: t):
    """Sparse categorical cross-entropy of each row, on clipped
    probabilities as the configuration states it."""
    p = jnp.clip(probabilities(w, x, cfg, cast), LOSS_EPS, 1.0)
    return -jnp.log(jnp.take_along_axis(p, y[:, None].astype(jnp.int32),
                                        axis=-1)[:, 0])
