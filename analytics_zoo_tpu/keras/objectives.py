"""Loss functions — parity with ref pipeline/api/keras/objectives (15 files).

Each reference objective is a Scala class wrapping a BigDL criterion; here
each is a pure function ``(y_true, y_pred) -> scalar`` (mean over batch),
differentiable by jax.grad. Keras-1 conventions preserved: class labels for
the sparse losses are 0-based ints (the reference handles BigDL's 1-based
labels internally, TFTrainingHelper.scala:222-247 — a JVM-ism that does not
survive the rebuild).
"""

from __future__ import annotations

from typing import Callable, Union

import jax
import jax.numpy as jnp

_EPS = 1e-7


def mean_squared_error(y_true, y_pred):
    """Ref MeanSquaredError — mean((y_pred - y_true)^2)."""
    return jnp.mean(jnp.square(y_pred - y_true))


def mean_absolute_error(y_true, y_pred):
    """Ref MeanAbsoluteError — mean|y_pred - y_true|."""
    return jnp.mean(jnp.abs(y_pred - y_true))


def mean_absolute_percentage_error(y_true, y_pred):
    """Ref MeanAbsolutePercentageError — 100 * mean|rel error|."""
    diff = jnp.abs((y_true - y_pred) / jnp.clip(jnp.abs(y_true), _EPS, None))
    return 100.0 * jnp.mean(diff)


def mean_squared_logarithmic_error(y_true, y_pred):
    """Ref MeanSquaredLogarithmicError — MSE in log1p space."""
    a = jnp.log(jnp.clip(y_pred, _EPS, None) + 1.0)
    b = jnp.log(jnp.clip(y_true, _EPS, None) + 1.0)
    return jnp.mean(jnp.square(a - b))


def binary_crossentropy(y_true, y_pred):
    """Ref BinaryCrossEntropy — probabilities in, clipped at 1e-7."""
    p = jnp.clip(y_pred, _EPS, 1.0 - _EPS)
    return -jnp.mean(y_true * jnp.log(p) + (1.0 - y_true) * jnp.log(1.0 - p))


def categorical_crossentropy(y_true, y_pred):
    """Ref CategoricalCrossEntropy — one-hot labels, probability
    inputs."""
    p = jnp.clip(y_pred, _EPS, 1.0)
    return -jnp.mean(jnp.sum(y_true * jnp.log(p), axis=-1))


def categorical_crossentropy_from_logits(y_true, y_pred):
    """One-hot labels over raw logits (log_softmax inside — the
    numerically-stable training form)."""
    logp = jax.nn.log_softmax(y_pred, axis=-1)
    return -jnp.mean(jnp.sum(y_true * logp, axis=-1))


def sparse_categorical_crossentropy(y_true, y_pred):
    """Ref SparseCategoricalCrossEntropy — int labels, probability inputs."""
    labels = y_true.astype(jnp.int32)
    if labels.ndim == y_pred.ndim:
        labels = jnp.squeeze(labels, axis=-1)
    p = jnp.clip(y_pred, _EPS, 1.0)
    ll = jnp.take_along_axis(jnp.log(p), labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


def sparse_categorical_crossentropy_from_logits(y_true, y_pred):
    """Int labels over raw logits (log_softmax inside — the
    numerically-stable training form; BERT/transformer default)."""
    labels = y_true.astype(jnp.int32)
    if labels.ndim == y_pred.ndim:
        labels = jnp.squeeze(labels, axis=-1)
    logp = jax.nn.log_softmax(y_pred, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -jnp.mean(ll)


# Token-level cross-entropy over [rows, tokens, vocabulary] logits: a language
# model's loss. At 16 384 tokens by 25 024 entries the float32 probabilities
# of a batch are 1.6 GB, and as much again for their gradient, so the loss
# goes through the tokens a block at a time, forward and backward: per block
# it upcasts the logits, takes the log-sum-exp and the label's logit, and
# keeps only the log-sum-exp (one float a token). The gradient, softmax less
# the label's one-hot, is rebuilt a block at a time in the logits' own type.
_TOKEN_BLOCK = 2048


def _token_blocks(logits, labels):
    n, v = logits.shape
    blk = _TOKEN_BLOCK if n % _TOKEN_BLOCK == 0 else n
    return logits.reshape(n // blk, blk, v), labels.reshape(n // blk, blk)


@jax.custom_vjp
def _token_nll(logits, labels):
    """logits (N, V) in any float type, labels (N,) -> float32 (N,)."""
    return _token_nll_fwd(logits, labels)[0]


def _token_nll_fwd(logits, labels):
    def block(args):
        z, lab = args
        z = z.astype(jnp.float32)
        lse = jax.nn.logsumexp(z, axis=-1)
        picked = jnp.take_along_axis(z, lab[:, None], axis=-1)[:, 0]
        return lse - picked, lse

    nll, lse = jax.lax.map(block, _token_blocks(logits, labels))
    return nll.reshape(-1), (logits, labels, lse.reshape(-1))


def _token_nll_bwd(res, g):
    logits, labels, lse = res

    def block(args):
        z, lab, lse_b, g_b = args
        p = jnp.exp(z.astype(jnp.float32) - lse_b[:, None])
        hot = jax.nn.one_hot(lab, z.shape[-1], dtype=jnp.float32)
        return ((p - hot) * g_b[:, None]).astype(z.dtype)

    zb, lb = _token_blocks(logits, labels)
    d = jax.lax.map(block, (zb, lb, lse.reshape(lb.shape),
                            g.astype(jnp.float32).reshape(lb.shape)))
    return d.reshape(logits.shape), None


_token_nll.defvjp(_token_nll_fwd, _token_nll_bwd)


def _token_rows(y_true, y_pred):
    """Cross-entropy of every token, (rows, tokens) float32."""
    labels = y_true.astype(jnp.int32)
    if labels.ndim == y_pred.ndim:
        labels = jnp.squeeze(labels, axis=-1)
    with jax.named_scope("lm.loss"):
        nll = _token_nll(y_pred.reshape(-1, y_pred.shape[-1]),
                         labels.reshape(-1))
    return nll.reshape(labels.shape)


def token_crossentropy_from_logits(y_true, y_pred):
    """Mean token cross-entropy of a causal language model: int labels
    (rows, tokens), the next token of each position, over raw logits (rows,
    tokens, vocabulary) in the model's compute type. Statistics in float32,
    a block of tokens at a time: the float32 probabilities of the whole
    batch never exist. ``takes_compute_dtype`` tells ``Estimator.train`` to
    hand the logits over as the model made them, not upcast."""
    return jnp.mean(_token_rows(y_true, y_pred))


def _ps_token_ce_logits(y_true, y_pred):
    return jnp.mean(_token_rows(y_true, y_pred), axis=-1)


token_crossentropy_from_logits.takes_compute_dtype = True


def hinge(y_true, y_pred):
    """Ref HingeCriterion — labels in {-1, +1}, mean margin loss."""
    return jnp.mean(jnp.maximum(1.0 - y_true * y_pred, 0.0))


def squared_hinge(y_true, y_pred):
    """Squared hinge over {-1, +1} labels."""
    return jnp.mean(jnp.square(jnp.maximum(1.0 - y_true * y_pred, 0.0)))


def rank_hinge(y_true, y_pred, margin: float = 1.0):
    """Ref RankHinge — pairwise ranking loss over (pos, neg) interleaved
    batches produced by ``Relations.generateRelationPairs``
    (feature/common/Relations.scala:92): even rows positive, odd negative.
    """
    pos = y_pred[0::2]
    neg = y_pred[1::2]
    return jnp.mean(jnp.maximum(0.0, margin + neg - pos))


def kullback_leibler_divergence(y_true, y_pred):
    """Ref KullbackLeiblerDivergence — KL(t || p) over distributions."""
    t = jnp.clip(y_true, _EPS, 1.0)
    p = jnp.clip(y_pred, _EPS, 1.0)
    return jnp.mean(jnp.sum(t * jnp.log(t / p), axis=-1))


def poisson(y_true, y_pred):
    """Ref PoissonCriterion — mean(pred - true*log(pred))."""
    return jnp.mean(y_pred - y_true * jnp.log(y_pred + _EPS))


def cosine_proximity(y_true, y_pred):
    """Ref CosineProximityCriterion — negative mean cosine
    similarity."""
    t = y_true / (jnp.linalg.norm(y_true, axis=-1, keepdims=True) + _EPS)
    p = y_pred / (jnp.linalg.norm(y_pred, axis=-1, keepdims=True) + _EPS)
    return -jnp.mean(jnp.sum(t * p, axis=-1))


# BigDL-criterion parity extras used by the model zoo / nnframes
def binary_crossentropy_from_logits(y_true, y_pred):
    """Sigmoid BCE over raw logits (stable log1p(exp) form; the
    nnframes/model-zoo training default)."""
    return jnp.mean(jnp.maximum(y_pred, 0) - y_pred * y_true
                    + jnp.log1p(jnp.exp(-jnp.abs(y_pred))))


_LOSSES = {
    "mse": mean_squared_error,
    "mean_squared_error": mean_squared_error,
    "mae": mean_absolute_error,
    "mean_absolute_error": mean_absolute_error,
    "mape": mean_absolute_percentage_error,
    "mean_absolute_percentage_error": mean_absolute_percentage_error,
    "msle": mean_squared_logarithmic_error,
    "mean_squared_logarithmic_error": mean_squared_logarithmic_error,
    "binary_crossentropy": binary_crossentropy,
    "binary_crossentropy_from_logits": binary_crossentropy_from_logits,
    "categorical_crossentropy": categorical_crossentropy,
    "categorical_crossentropy_from_logits": categorical_crossentropy_from_logits,
    "sparse_categorical_crossentropy": sparse_categorical_crossentropy,
    "sparse_categorical_crossentropy_from_logits": sparse_categorical_crossentropy_from_logits,
    "token_crossentropy_from_logits": token_crossentropy_from_logits,
    "hinge": hinge,
    "squared_hinge": squared_hinge,
    "rank_hinge": rank_hinge,
    "kld": kullback_leibler_divergence,
    "kullback_leibler_divergence": kullback_leibler_divergence,
    "poisson": poisson,
    "cosine_proximity": cosine_proximity,
}


def get(loss: Union[str, Callable]) -> Callable:
    """Resolve a keras-1 loss spec — a name from the 21-alias table or
    any callable ``(y_true, y_pred) -> scalar`` — to the function."""
    if callable(loss):
        return loss
    try:
        return _LOSSES[loss]
    except KeyError:
        raise ValueError(f"Unknown loss '{loss}'. Known: {sorted(_LOSSES)}")


# ---------------------------------------------------------------------------
# Per-sample forms (used by the Loss validation metric AND by the train step
# so wrap-padded tail batches can be exactly masked — duplicated samples must
# not get double gradient weight; see engine/estimator.py).
# ---------------------------------------------------------------------------


def _rowmean(v, y_pred):
    """Collapse everything but the batch dim to a per-sample mean."""
    return jnp.mean(v.reshape(v.shape[0], -1), axis=-1)


def _ps_mse(y_true, y_pred):
    return jnp.mean(jnp.square(y_pred - y_true).reshape(y_pred.shape[0], -1), axis=-1)


def _ps_mae(y_true, y_pred):
    return jnp.mean(jnp.abs(y_pred - y_true).reshape(y_pred.shape[0], -1), axis=-1)


def _ps_bce(y_true, y_pred):
    p = jnp.clip(y_pred, _EPS, 1.0 - _EPS)
    v = -(y_true * jnp.log(p) + (1.0 - y_true) * jnp.log(1.0 - p))
    return jnp.mean(v.reshape(y_pred.shape[0], -1), axis=-1)


def _ps_cce(y_true, y_pred):
    p = jnp.clip(y_pred, _EPS, 1.0)
    return -jnp.sum(y_true * jnp.log(p), axis=-1).reshape(y_pred.shape[0], -1).mean(axis=-1)


def _ps_cce_logits(y_true, y_pred):
    logp = jax.nn.log_softmax(y_pred, axis=-1)
    return -jnp.sum(y_true * logp, axis=-1).reshape(y_pred.shape[0], -1).mean(axis=-1)


def _ps_scce(y_true, y_pred):
    labels = y_true.astype(jnp.int32)
    if labels.ndim == y_pred.ndim:
        labels = jnp.squeeze(labels, axis=-1)
    p = jnp.clip(y_pred, _EPS, 1.0)
    ll = jnp.take_along_axis(jnp.log(p), labels[..., None], axis=-1)[..., 0]
    return -ll.reshape(y_pred.shape[0], -1).mean(axis=-1)


def _ps_scce_logits(y_true, y_pred):
    labels = y_true.astype(jnp.int32)
    if labels.ndim == y_pred.ndim:
        labels = jnp.squeeze(labels, axis=-1)
    logp = jax.nn.log_softmax(y_pred, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    return -ll.reshape(y_pred.shape[0], -1).mean(axis=-1)


def _ps_bce_logits(y_true, y_pred):
    v = (jnp.maximum(y_pred, 0) - y_pred * y_true
         + jnp.log1p(jnp.exp(-jnp.abs(y_pred))))
    return _rowmean(v, y_pred)


def _ps_mape(y_true, y_pred):
    diff = jnp.abs((y_true - y_pred) / jnp.clip(jnp.abs(y_true), _EPS, None))
    return 100.0 * _rowmean(diff, y_pred)


def _ps_msle(y_true, y_pred):
    a = jnp.log(jnp.clip(y_pred, _EPS, None) + 1.0)
    b = jnp.log(jnp.clip(y_true, _EPS, None) + 1.0)
    return _rowmean(jnp.square(a - b), y_pred)


def _ps_hinge(y_true, y_pred):
    return _rowmean(jnp.maximum(1.0 - y_true * y_pred, 0.0), y_pred)


def _ps_squared_hinge(y_true, y_pred):
    return _rowmean(jnp.square(jnp.maximum(1.0 - y_true * y_pred, 0.0)), y_pred)


def _ps_kld(y_true, y_pred):
    t = jnp.clip(y_true, _EPS, 1.0)
    p = jnp.clip(y_pred, _EPS, 1.0)
    return _rowmean(jnp.sum(t * jnp.log(t / p), axis=-1), y_pred)


def _ps_poisson(y_true, y_pred):
    return _rowmean(y_pred - y_true * jnp.log(y_pred + _EPS), y_pred)


def _ps_cosine(y_true, y_pred):
    t = y_true / (jnp.linalg.norm(y_true, axis=-1, keepdims=True) + _EPS)
    p = y_pred / (jnp.linalg.norm(y_pred, axis=-1, keepdims=True) + _EPS)
    return -_rowmean(jnp.sum(t * p, axis=-1), y_pred)


def _ps_rank_hinge(y_true, y_pred, margin: float = 1.0):
    """Per-PAIR hinge, written back to both interleaved slots (each weighted
    ½) so ``sum(ps * mask) / sum(mask)`` equals the mean over unmasked pairs
    — pair padding masks both members together (PairFeatureSet batching)."""
    pair = jnp.maximum(0.0, margin + y_pred[1::2] - y_pred[0::2])
    pair = pair.reshape(pair.shape[0], -1).mean(axis=-1)
    return jnp.repeat(pair, 2, axis=0)


_PER_SAMPLE = {
    mean_squared_error: _ps_mse,
    mean_absolute_error: _ps_mae,
    mean_absolute_percentage_error: _ps_mape,
    mean_squared_logarithmic_error: _ps_msle,
    binary_crossentropy: _ps_bce,
    categorical_crossentropy: _ps_cce,
    categorical_crossentropy_from_logits: _ps_cce_logits,
    sparse_categorical_crossentropy: _ps_scce,
    sparse_categorical_crossentropy_from_logits: _ps_scce_logits,
    binary_crossentropy_from_logits: _ps_bce_logits,
    token_crossentropy_from_logits: _ps_token_ce_logits,
    hinge: _ps_hinge,
    squared_hinge: _ps_squared_hinge,
    kullback_leibler_divergence: _ps_kld,
    poisson: _ps_poisson,
    cosine_proximity: _ps_cosine,
    rank_hinge: _ps_rank_hinge,
}


def get_per_sample(loss_fn: Callable):
    """Per-sample form of a loss, or None if only the scalar form exists."""
    return _PER_SAMPLE.get(loss_fn)


# Class-style aliases matching reference objective names
MeanSquaredError = mean_squared_error
MeanAbsoluteError = mean_absolute_error
SparseCategoricalCrossEntropy = sparse_categorical_crossentropy
CategoricalCrossEntropy = categorical_crossentropy
BinaryCrossEntropy = binary_crossentropy
RankHinge = rank_hinge
