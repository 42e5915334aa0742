"""Softmax routing with a balance term, YaRN rotary beside plain rotary, and
`CausalLM` built from a `mellum` configuration, against the plain float32
reference (benchmark/reference/mellum2.py), at tiny widths on the CPU with
seeded weights; and the shared code they pass through (the sigmoid router,
the plain rotary table) pinned to what it computed before.

Tolerances: both sides compute in float32 with `highest` matmul precision,
so they differ by the order of the sums alone: 2e-5 of the largest value on
a forward pass, 1e-4 on gradients (two passes), 2e-3 on the change after
three Adam steps (a step divides by the root of a squared gradient near
nought). The fused kernels in interpret mode keep `tests/test_qk_rotary.py`'s
float32 bounds (2e-6 of values, 2e-5 of gradients)."""

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import mellum2 as ref, optim

# the published `full_attention` section of `rope_parameters`
PUBLISHED_YARN = {"rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                  "original_max_position_embeddings": 8192, "beta_fast": 32,
                  "beta_slow": 1, "attention_factor": 1.2772588722239782}
# YaRN's ramp in the middle of a 16-wide head at a toy theta (2 .. 6)
ROPE = {"full_attention": dict(PUBLISHED_YARN, rope_theta=10000,
                               original_max_position_embeddings=2048),
        "sliding_attention": {"rope_type": "default", "rope_theta": 10000}}
CFG = {
    "model_type": "mellum", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 48,
    "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
    "mlp_layer_types": ["sparse"] * 4, "num_hidden_layers": 4,
    "num_experts": 4, "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "norm_topk_prob": True, "sliding_window": 8,
    "rope_parameters": ROPE, "rms_norm_eps": 1e-6, "hidden_act": "silu",
    "attention_bias": False, "use_sliding_window": True,
    "max_window_layers": 0, "tie_word_embeddings": False, "vocab_size": 96,
    "initializer_range": 0.05, "router_aux_loss_coef": 0.01, "seq_len": 32,
    "compute_dtype": None,
}


def _model(cfg=CFG, **kw):
    from benchmark import models_lm

    return models_lm._build(dict(cfg, **kw))


def _weights(model, cfg=CFG, seed=0):
    """(reference tree, program tree) of the same numbers, the gains off 1
    so that a norm left out or misplaced shows."""
    from benchmark import models_mellum2

    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, w)
    return w, models_mellum2._to_program(w, model)


def _rows(cfg=CFG, n=2, seed=3):
    t = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


# -- the rotary spec ------------------------------------------------------------

def test_yarn_at_the_published_numbers():
    """low 18 and high 35 at head width 128 and theta 5e5; the magnitude
    0.1 ln 16 + 1; the frequencies below low kept, from high on divided by
    16, between them blended; the same numbers as the reference's formula."""
    from analytics_zoo_tpu.ops.rotary import Rotary, Yarn, frequencies

    yarn = Yarn(16.0, 8192, 32.0, 1.0, 1.2772588722239782)
    assert yarn.ramp(5e5, 128) == (18.0, 35.0)
    assert Yarn(16.0, 8192).magnitude() == pytest.approx(
        0.1 * math.log(16) + 1) == pytest.approx(1.27726, abs=5e-6)
    inv, magnitude = frequencies(Rotary(5e5, yarn), 128)
    plain, one = frequencies(Rotary(5e5), 128)
    assert magnitude == pytest.approx(1.2772588722239782) and one == 1.0
    inv, plain = np.asarray(inv, np.float64), np.asarray(plain, np.float64)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert np.all(inv[19:35] < plain[19:35])
    assert np.all(inv[19:35] > plain[19:35] / 16)
    want, want_m = ref.yarn_inverse_frequencies(PUBLISHED_YARN, 128)
    np.testing.assert_allclose(inv, np.asarray(want), rtol=1e-6)
    assert want_m == magnitude


def test_the_plain_rotary_and_the_sigmoid_router_are_as_before():
    """The shared code, pinned to the expressions it had before the spec and
    the softmax branch: the same arrays, bit for bit."""
    from analytics_zoo_tpu.keras.layers.decoder import rotary_embedding
    from analytics_zoo_tpu.ops import qk_rotary
    from analytics_zoo_tpu.ops.rotary import Rotary
    from analytics_zoo_tpu.parallel.moe import route_topk

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 40, 16))
    inv = 1.0 / (1e4 ** (jnp.arange(0, 16, 2, dtype=jnp.float32) / 16))
    ang = jnp.arange(40, dtype=jnp.float32)[:, None] * inv[None]
    halves = jnp.concatenate([ang, ang], axis=-1)
    rotated = jnp.concatenate([-x[..., 8:], x[..., :8]], axis=-1)
    before = x * jnp.cos(halves) + rotated * jnp.sin(halves)
    for theta in (1e4, Rotary(1e4)):
        np.testing.assert_array_equal(np.asarray(rotary_embedding(x, theta)),
                                      np.asarray(before))
    pairs = jnp.repeat(ang, 2, axis=-1)
    flip = jnp.stack([-x.reshape(2, 3, 40, 8, 2)[..., 1],
                      x.reshape(2, 3, 40, 8, 2)[..., 0]], -1).reshape(x.shape)
    np.testing.assert_array_equal(
        np.asarray(rotary_embedding(x, 1e4, interleaved=True)),
        np.asarray(x * jnp.cos(pairs) + flip * jnp.sin(pairs)))
    cos, sin = qk_rotary._tables(40, 64, Rotary(1e4))
    inv = 1.0 / (1e4 ** (jnp.arange(0, 64, 2, dtype=jnp.float32) / 64))
    ang = jnp.arange(40, dtype=jnp.float32)[:, None] * inv[None]
    np.testing.assert_array_equal(np.asarray(cos), np.asarray(jnp.tile(
        jnp.concatenate([jnp.cos(ang)] * 2, axis=-1), (1, 2))))
    np.testing.assert_array_equal(np.asarray(sin), np.asarray(jnp.tile(
        jnp.concatenate([-jnp.sin(ang), jnp.sin(ang)], axis=-1), (1, 2))))

    t = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(2), (8, 6))
    bias = 0.3 * jax.random.normal(jax.random.PRNGKey(3), (6,))
    scores = jax.nn.sigmoid(jnp.dot(t, router,
                                    precision=jax.lax.Precision.HIGHEST))
    _, picked = jax.lax.top_k(scores + bias, 2)
    w = jnp.take_along_axis(scores, picked, axis=-1)
    w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20) * 2.5
    counts = jnp.sum(jax.nn.one_hot(picked, 6), axis=(0, 1))
    got = route_topk(t, router, bias, 2, True, 2.5)
    assert len(got) == 3
    for a, b in zip(got, (picked, w, counts)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("width", [64, 128])
def test_a_yarn_table_in_the_fused_kernels(width):
    """The same kernels with the scaled table as data, against the two
    functions with the same spec, forward and backward; a build is counted
    under its path with `_scaled`."""
    from analytics_zoo_tpu.common.observability import qk_rotary_built
    from analytics_zoo_tpu.keras.layers.decoder import rms_norm, rotary_embedding
    from analytics_zoo_tpu.ops import qk_rotary
    from analytics_zoo_tpu.ops.rotary import Rotary, Yarn

    spec = Rotary(1e4, Yarn(16.0, 64))
    kx, kg, kd = jax.random.split(jax.random.PRNGKey(width), 3)
    x = 2.0 * jax.random.normal(kx, (1, 512, 2, width))
    gain = 1.0 + 0.1 * jax.random.normal(kg, (width,))
    dy = jax.random.normal(kd, (1, 2, 512, width))

    def both(op):
        y, vjp = jax.vjp(lambda a, g: op(a, g, 1e-6, spec), x, gain)
        return (y,) + vjp(dy)

    def composed(a, g, eps, s):
        return rotary_embedding(rms_norm(a.transpose(0, 2, 1, 3), g, eps), s)

    got = jax.jit(lambda: both(qk_rotary.norm_rotary_kernel))()
    want = jax.jit(lambda: both(composed))()
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]),
                               rtol=2e-6,
                               atol=2e-6 * float(jnp.max(jnp.abs(want[0]))))
    for a, b in zip(got[1:], want[1:]):
        _close(a, b, 2e-5)
    fam = qk_rotary_built()
    before = fam.labels(path="xla_scaled").value
    qk_rotary.norm_rotary(x, gain, 1e-6, spec)
    assert fam.labels(path="xla_scaled").value == before + 1


# -- the softmax router and the balance term ---------------------------------------

def test_softmax_routing_picks_the_most_probable_and_ignores_the_bias():
    from analytics_zoo_tpu.parallel.moe import route_topk

    t = jax.random.normal(jax.random.PRNGKey(1), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(2), (8, 6))
    probs = np.asarray(jax.nn.softmax(jnp.dot(
        t, router, precision=jax.lax.Precision.HIGHEST), axis=-1), np.float64)
    for bias in (jnp.zeros(6), jnp.arange(6.0)):
        picked, w, counts, mean_p = route_topk(t, router, bias, 2, True, 1.0,
                                               0.0, scores="softmax")
        want = np.argsort(-probs, axis=-1)[:, :2]
        np.testing.assert_array_equal(np.asarray(picked), want)
        top = np.take_along_axis(probs, want, axis=-1)
        _close(w, top / top.sum(-1, keepdims=True), 1e-6)
        np.testing.assert_array_equal(
            np.asarray(counts), np.bincount(want.ravel(), minlength=6))
        _close(mean_p, probs.mean(0), 1e-6)
    with pytest.raises(ValueError, match="unknown router scores"):
        route_topk(t, router, bias, 2, scores="relu")


def test_the_held_rows_go_through_in_chunks_where_asked():
    """``compact=False``: 16 of 64 experts held, where a compacted pass
    exists (a buffer of 2048 of the 4096 assignments), the chunks alone are
    built (no `cond`), the layer says no compacted pass ran, and the output
    and all four gradients are the compacted pass's to float32 rounding."""
    from analytics_zoo_tpu.parallel import moe
    from conftest import inner_jaxprs

    t, d, h, n, k = 512, 32, 16, 64, 8
    keys = iter(jax.random.split(jax.random.PRNGKey(7), 8))
    x = jax.random.normal(next(keys), (t, d))
    router = jax.random.normal(next(keys), (d, n))
    w_gate_up = 0.2 * jax.random.normal(next(keys), (16, d, 2 * h))
    w_down = 0.2 * jax.random.normal(next(keys), (16, h, d))
    g = jax.random.normal(next(keys), (t, d))
    picked, weights = moe.route_topk(x, router, jnp.zeros(n), k,
                                     scores="softmax")[:2]
    assert moe._held_capacity(t, k, 16, n) == 2048 < t * k

    def run(compact):
        def loss(x_, w_, a, b):
            y, fitted = moe.held_experts_ffn(x_, picked, w_, a, b, n, 0,
                                             compact=compact)
            return jnp.sum(y * g), (y, fitted)

        with jax.default_matmul_precision("highest"):
            (_, (y, fitted)), grads = jax.value_and_grad(
                loss, (0, 1, 2, 3), has_aux=True)(x, weights, w_gate_up,
                                                  w_down)
        return y, bool(fitted), grads

    def conds(jaxpr):
        return any(e.primitive.name == "cond" or any(map(conds, inner_jaxprs(e)))
                   for e in jaxpr.eqns)

    jaxpr = jax.make_jaxpr(lambda *a: moe.held_experts_ffn(
        *a, n, 0, compact=False))(x, picked, weights, w_gate_up, w_down)
    assert not conds(jaxpr.jaxpr)
    want_y, fitted, want = run(True)
    y, chunked, grads = run(False)
    assert fitted and not chunked
    _close(y, want_y, 2e-5)
    for a, b in zip(grads, want):
        _close(a, b, 1e-4)


def test_the_balance_term_by_hand_on_three_tokens():
    """Three tokens, four experts, two picks, two layers: f = the tokens that
    picked an expert over the tokens, P = its mean probability, each pooled
    over the layers; the term is coef x E x sum f P."""
    from analytics_zoo_tpu.keras.layers.moe import SparseMoE

    probs = np.array([[[0.4, 0.3, 0.2, 0.1],      # picks 0, 1
                       [0.1, 0.2, 0.3, 0.4],      # 3, 2
                       [0.5, 0.1, 0.3, 0.1]],     # 0, 2
                      [[0.3, 0.2, 0.4, 0.1],      # 2, 0
                       [0.7, 0.05, 0.15, 0.1],    # 0, 2
                       [0.1, 0.6, 0.2, 0.1]]])    # 1, 2
    f = np.mean([[2, 1, 2, 1], [2, 1, 3, 0]], axis=0) / 3.0
    p = probs.mean(axis=(0, 1))
    want = 0.01 * 4 * np.sum(f * p)

    model = _model(num_experts=4, router_num_experts=4, experts_held_offset=0,
                   layer_types=["full_attention"] * 2,
                   mlp_layer_types=["sparse"] * 2, num_hidden_layers=2)
    state = {}
    for block, layer in zip(model.blocks, probs):
        # three tokens of width 3, token t the t-th unit vector: its scores
        # are row t of the router, the log of its probabilities
        moe = SparseMoE(4, 2, 2, None, 0, True, 1.0, 0.0, 0.0,
                        scores="softmax", balance_coef=0.01)
        moe.ensure_built((None, 3, 3))
        params = dict(moe.init_params(jax.random.PRNGKey(0)),
                      router=jnp.asarray(np.log(layer), jnp.float32))
        _, state[block.name] = moe.call(params, jnp.eye(3)[None],
                                        training=True)
        assert float(jnp.sum(state[block.name]["balance_f"])) == pytest.approx(2)
        _close(state[block.name]["balance_p"], layer.mean(0), 1e-6)
    np.testing.assert_allclose(np.mean(
        [np.asarray(state[b.name]["balance_f"]) for b in model.blocks], 0), f,
        rtol=1e-6)
    assert float(model.auxiliary_loss(state)) == pytest.approx(want, rel=1e-5)
    counts = jnp.asarray([[2, 1, 2, 1], [2, 1, 3, 0]], jnp.float32)
    assert float(ref.balance(counts, jnp.asarray(probs.mean(axis=1)), 3,
                             CFG)) == pytest.approx(want, rel=1e-6)
    # a family with no balance term adds a constant nought
    assert _model(router_aux_loss_coef=0.0).auxiliary_loss({}) == 0.0


def test_four_shares_add_up_to_the_uncut_layer():
    """64 experts over four shares of 16 (0-15 .. 48-63) at toy widths: the
    parts add up to the reference's whole layer, and every share reads the
    same balance statistics (the router is whole on every chip)."""
    from analytics_zoo_tpu.keras.layers.moe import SparseMoE

    cfg = dict(CFG, hidden_size=32, moe_intermediate_size=16, num_experts=64,
               router_num_experts=64, experts_held_offset=0,
               num_experts_per_tok=8)
    whole = SparseMoE(64, 16, 8, (0, 64), 0, True, 1.0, 0.0, 0.0,
                      scores="softmax", balance_coef=0.001)
    whole.ensure_built((None, 64, 32))
    wp = whole.init_params(jax.random.PRNGKey(5))
    x = jax.random.normal(jax.random.PRNGKey(6), (1, 64, 32))
    total, stats = 0.0, []
    with jax.default_matmul_precision("highest"):
        for at in range(0, 64, 16):
            share = SparseMoE(64, 16, 8, (at, 16), 0, True, 1.0, 0.0, 0.0,
                              scores="softmax", balance_coef=0.001)
            share.ensure_built((None, 64, 32))
            p = dict(wp, experts_w_gate_up=wp["experts_w_gate_up"][at:at + 16],
                     experts_w_down=wp["experts_w_down"][at:at + 16])
            y, st = share.call(p, x, training=True)
            total = total + y
            stats.append(st)
        gate, up = jnp.split(wp["experts_w_gate_up"], 2, axis=-1)
        want, counts, mean_p = ref.expert_layer(
            {"router": wp["router"], "experts": {
                "w_gate": gate, "w_up": up,
                "w_down": wp["experts_w_down"]}}, x[0], cfg)
    _close(total[0], want, 1e-4)
    for st in stats:
        _close(st["balance_f"], counts / 64, 1e-7)
        _close(st["balance_p"], mean_p, 1e-6)


# -- the whole model against the reference -------------------------------------------

@pytest.fixture(scope="module")
def tiny():
    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    return model, w, params, state


def test_logits_the_loss_with_its_balance_term_and_the_gradient(tiny):
    """Logits, the criterion plus the balance term, and every leaf of its
    gradient; the full layer's YaRN reaches the logits (the reference's with
    plain rotary there are others)."""
    from analytics_zoo_tpu.keras import objectives
    from benchmark import models_mellum2

    model, w, params, state = tiny
    x, y = _rows()

    def loss(p):
        z, st = model.apply(p, state, x, training=True)
        term = model.auxiliary_loss(st)
        return objectives.token_crossentropy_from_logits(y, z) + term, (z, term)

    plain = dict(CFG, rope_parameters=dict(
        ROPE, full_attention=ROPE["sliding_attention"]))
    with jax.default_matmul_precision("highest"):
        (got, (z, term)), grads = jax.jit(jax.value_and_grad(
            loss, has_aux=True))(params)
        want_z, other_z = jax.jit(lambda w_: (ref.logits(w_, x, CFG),
                                              ref.logits(w_, x, plain)))(w)
        want, ref_grads = jax.jit(jax.value_and_grad(
            lambda w_: ref.objective(w_, x, y, CFG)))(w)
    _close(z, want_z, 2e-5)
    assert float(jnp.max(jnp.abs(other_z - want_z))) > 1e-3
    _close(got, want, 2e-5)
    assert float(term) > 1e-3                # the term is in the loss
    got_grads = models_mellum2._from_program(grads, model)
    assert (jax.tree_util.tree_structure(got_grads)
            == jax.tree_util.tree_structure(ref_grads))
    for path, a in jax.tree_util.tree_leaves_with_path(got_grads):
        b = dict(jax.tree_util.tree_leaves_with_path(ref_grads))[path]
        assert float(jnp.max(jnp.abs(b))) > 0, path
        _close(a, b, 1e-4)


def test_three_estimator_steps_with_adam_and_the_balance_term():
    """`Estimator.train`, one dispatch a step, against the reference's three
    steps: the reported loss is the criterion's, the first gradient and the
    change are the whole objective's; the window pairs and the balance term
    reach the counters at the drain."""
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.common.observability import get_registry
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import fit_lm, models_mellum2

    def read(name, field="value"):
        fam = get_registry()._families.get(name)
        return sum(getattr(c, field) for c in fam._children.values()) if fam \
            else 0.0

    nncontext.init_nncontext(mesh_shape=(1, 8))   # no data axis to round to
    model = _model()
    w, params = _weights(model)
    w = jax.device_get(w)        # the step donates what set_weights placed
    est = model._get_estimator()
    model.set_weights(params)
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="token_crossentropy_from_logits")
    x, y = _rows(n=1)
    losses = []
    before = (read("zoo_lm_window_pairs_total"),
              read("zoo_moe_balance_loss", "count"))

    class Tape:
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                losses.append(value)

    est.train_summary = Tape()
    with jax.default_matmul_precision("highest"):
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(1))
        first = models_mellum2._from_program(
            fit_lm.first_gradient(est.tstate.opt_state, 0.9), model, np)
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(3))
        batches = [(jnp.asarray(x), jnp.asarray(y))] * 3
        want = fit_lm.follow(ref, CFG, w, batches, optim.Adam(lr=1e-3),
                             row_block=1)
    # the reference's row losses are the cross-entropy in value
    _close(losses, want["losses"], 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(first),
                    jax.tree_util.tree_leaves(want["first"])):
        _close(a, b, 1e-4)
    end = models_mellum2._from_program(jax.device_get(est.tstate.params),
                                       model, np)
    change = jax.tree_util.tree_map(np.subtract, end, w)
    for a, b in zip(jax.tree_util.tree_leaves(change),
                    jax.tree_util.tree_leaves(want["change"])):
        _close(a, b, 2e-3)
    # three steps of one row: 3 windows of 8 over 32 tokens a step
    pairs = 3 * (8 * 9 / 2 + 24 * 8)
    assert read("zoo_lm_window_pairs_total") - before[0] == 3 * pairs
    assert read("zoo_moe_balance_loss", "count") - before[1] == 3
    assert all(np.abs(np.asarray(est.tstate.model_state[b.name]["select_bias"]))
               .max() == 0 for b in model.blocks)


# -- the model's layers by configuration ---------------------------------------------

def test_a_mellum_file_builds_its_layers_and_rotary_by_kind():
    from analytics_zoo_tpu.ops.rotary import Rotary, Yarn

    model = _model()
    kinds = [(b.attn.window, b.attn.rope_theta) for b in model.blocks]
    plain = Rotary(10000.0)
    yarn = Rotary(10000.0, Yarn(16.0, 2048, 32.0, 1.0, 1.2772588722239782))
    assert kinds == [(8, plain)] * 3 + [(None, yarn)]
    assert all(b.norms == "pre" and not b.attn.gated and b.attn.qk_norm
               for b in model.blocks)
    moe = model.blocks[0].mlp
    assert (moe.scores, moe.balance_coef, moe.bias_rate, moe.route_eps,
            moe.route_scale, moe.n_shared, moe.top_k, moe.experts_held,
            moe.n_experts) == ("softmax", 0.01, 0.0, 0.0, 1.0, 0, 2, (2, 4), 8)
    assert model.windows == [8, 8, 8] and model.head is not None
    no_coef = {k: v for k, v in CFG.items() if k != "router_aux_loss_coef"}
    assert _model(no_coef).balance_coef == 0.001
    # a leading dense layer from `mlp_layer_types`
    dense = _model(mlp_layer_types=["dense"] + ["sparse"] * 3)
    assert type(dense.blocks[0].mlp).__name__ == "SwiGLU"
    assert dense.blocks[0].mlp.width == 48


@pytest.mark.parametrize("key,value", [
    ("use_sliding_window", False), ("max_window_layers", 28),
    ("attention_bias", True), ("hidden_act", "gelu")])
def test_what_is_not_built_is_refused_by_key(key, value):
    with pytest.raises(NotImplementedError, match=f"mellum with {key}="):
        _model(**{key: value})


@pytest.mark.parametrize("change,match", [
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"mlp_layer_types": ["sparse", "shared", "sparse", "sparse"]},
     "mlp_layer_types"),
    ({"rope_parameters": dict(ROPE, full_attention=dict(
        PUBLISHED_YARN, rope_type="linear"))}, "'linear'"),
    ({"rope_parameters": dict(ROPE, full_attention=dict(
        PUBLISHED_YARN, mscale=0.707))}, "mscale"),
])
def test_a_layout_or_rotary_that_is_not_built_is_refused(change, match):
    with pytest.raises(NotImplementedError, match=match):
        _model(**change)


def test_from_config_names_the_families_it_knows():
    with pytest.raises(ValueError, match="known.*mellum"):
        _model(model_type="qwen3_moe")


# -- the benchmark's configuration through the program's own count ----------------------

def test_the_benchmarks_configuration_builds_with_538_5_m_parameters():
    """Shapes only (`eval_shape`): nothing of that size is made here."""
    from analytics_zoo_tpu.models.causal_lm import CausalLM

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "mellum2-12b-a2.5b.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(cfg["assumed"])
    model = CausalLM.from_config(cfg, seq_len=cfg["seq_len"],
                                 dtype=cfg["compute_dtype"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    # attention 2304 x (4096 + 512 + 512) in, 4096 x 2304 out; the q / k
    # gains and two block norms; the router 2304 x 64; 16 experts of 3 x
    # 2304 x 896
    layer = 11_796_480 + 9_437_184 + 4_864 + 147_456 + 99_090_432
    assert [count(params[b.name]) for b in model.blocks] == [layer] * 4
    assert count(params[model.embed.name]) == count(
        params[model.head.name]) == 28_311_552
    assert sum(count(v) for v in params.values()) == 538_531_072
    assert model.windows == [1024] * 3 and model.experts_held == (0, 16)
    assert model.blocks[3].attn.rope_theta.yarn.ramp(5e5, 128) == (18.0, 35.0)
    assert model.balance_coef == 0.001
    assert [b.mlp.compact for b in model.blocks] == [False] * 4
