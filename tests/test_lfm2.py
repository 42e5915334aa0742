"""The gated short convolution, the two-norm block, the tied head and `CausalLM`
built from an `lfm2_moe` configuration, against the plain float32 reference
(benchmark/reference/lfm2.py), at tiny widths on the CPU with seeded weights.

Tolerances: both sides compute in float32 with `highest` matmul precision, so
they differ by the order of the sums alone: 2e-5 of the largest value on a
forward pass, 1e-4 on gradients (two passes), 2e-3 on the change after three
Adam steps (a step divides by the root of a squared gradient near nought).
bfloat16 where float32 is stated misses the first by three orders
(`test_bfloat16_where_float32_is_stated_fails`)."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import lfm2 as ref, optim

CFG = {
    "model_type": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "layer_types": ["conv", "full_attention", "conv"],
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "conv_L_cache": 3, "conv_bias": False,
    "norm_eps": 1e-5, "norm_topk_prob": True, "routed_scaling_factor": 1,
    "use_expert_bias": True,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "vocab_size": 96, "initializer_range": 0.05, "seq_len": 32,
    "compute_dtype": None, "bias_rate": 0.001, "tie_embeddings": True,
}


def _model(cfg=CFG, **kw):
    from benchmark import models_lm

    return models_lm._build(dict(cfg, **kw))


def _weights(model, cfg=CFG, seed=0):
    """(reference tree, program tree) of the same numbers."""
    from benchmark import models_lfm2

    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    # gains off 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, w)
    return w, models_lfm2._to_program(w, model)


def _rows(cfg=CFG, n=2, seed=3):
    t = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


# -- the whole model ------------------------------------------------------

def test_logits_and_row_losses_of_the_whole_model():
    from analytics_zoo_tpu.keras import objectives

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()
    # (each side under one `jit`: op by op the two take six times as long)
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply(p, state, x, training=False)[0])(
            params)
        _close(got, jax.jit(lambda w_: ref.logits(w_, x, CFG))(w), 2e-5)
        rows = objectives.get_per_sample(
            objectives.token_crossentropy_from_logits)(y, got)
        _close(rows, jax.jit(lambda w_: ref.row_losses(w_, x, y, CFG))(w), 2e-5)


def test_bfloat16_where_float32_is_stated_fails():
    model = _model(compute_dtype="bfloat16")
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, _ = _rows()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply(p, state, x, training=False)[0])(
            params)
        want = np.asarray(jax.jit(lambda w_: ref.logits(w_, x, CFG))(w))
    miss = np.max(np.abs(np.asarray(got, np.float32) - want))
    assert miss > 100 * 2e-5 * np.max(np.abs(want))


def test_gradients_of_one_step_leaf_by_leaf_the_tied_leaf_too():
    from analytics_zoo_tpu.keras import objectives
    from benchmark import models_lfm2

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()

    def loss(p):
        z, _ = model.apply(p, state, x, training=True)
        return objectives.token_crossentropy_from_logits(y, z)

    with jax.default_matmul_precision("highest"):
        got = models_lfm2._from_program(jax.jit(jax.grad(loss))(params), model)
        want = jax.jit(jax.grad(
            lambda w_: jnp.mean(ref.row_losses(w_, x, y, CFG))))(w)
    assert "head" not in got and got["embed"].shape == (96, 64)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(b))) > 0
        _close(a, b, 1e-4)


def test_the_tied_leaf_gets_the_sum_of_both_uses():
    """Against the same model untied, whose embedding and head get a gradient
    each: tied, the one leaf gets the embedding's plus the head's transposed."""
    from analytics_zoo_tpu.keras import objectives

    tied, untied = _model(), _model(tie_embeddings=False)
    _, params = _weights(tied)
    _, state = tied.init(jax.random.PRNGKey(0))
    _, state_u = untied.init(jax.random.PRNGKey(0))
    x, y = _rows()
    assert tied.head is None and untied.head is not None
    assert [l.name for l in tied.layers()][-1] == tied.final_norm.name
    # the same numbers, the head a copy of the embedding's transpose
    by_name = dict(zip([l.name for l in tied.layers()],
                       [l.name for l in untied.layers()]))
    params_u = {by_name[k]: v for k, v in params.items()}
    emb = params[tied.embed.name]["embeddings"]
    params_u[untied.head.name] = {"kernel": emb.T}

    def loss(model, st):
        def fn(p):
            z, _ = model.apply(p, st, x, training=True)
            return objectives.token_crossentropy_from_logits(y, z)
        return fn

    with jax.default_matmul_precision("highest"):
        g = jax.jit(jax.grad(loss(tied, state)))(params)
        g_u = jax.jit(jax.grad(loss(untied, state_u)))(params_u)
    both = (g_u[untied.embed.name]["embeddings"]
            + g_u[untied.head.name]["kernel"].T)
    _close(g[tied.embed.name]["embeddings"], both, 1e-5)
    n = sum(a.size for a in jax.tree_util.tree_leaves(params))
    n_u = sum(a.size for a in jax.tree_util.tree_leaves(params_u))
    assert n_u - n == 96 * 64


def test_three_estimator_steps_with_adam_and_the_bias_update():
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.common.observability import get_registry
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import fit_lm, models_lfm2

    def conv_pairs():
        fam = get_registry()._families.get("zoo_lm_conv_token_layers_total")
        return sum(c.value for c in fam._children.values()) if fam else 0.0

    nncontext.init_nncontext(mesh_shape=(1, 8))   # no data axis to round to
    model = _model()
    w, params = _weights(model)
    w = jax.device_get(w)        # the step donates what set_weights placed
    est = model._get_estimator()
    model.set_weights(params)
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="token_crossentropy_from_logits")
    x, y = _rows(n=1)            # batch 1, as the benchmark's cell
    losses, before = [], conv_pairs()

    class Tape:
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                losses.append(value)

    est.train_summary = Tape()
    with jax.default_matmul_precision("highest"):
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(3))
        batches = [(jnp.asarray(x), jnp.asarray(y))] * 3
        want = fit_lm.follow(ref, CFG, w, batches,
                             optim.Adam(lr=1e-3), row_block=1)
    _close(losses, want["losses"], 1e-5)
    end = models_lfm2._from_program(jax.device_get(est.tstate.params), model, np)
    change = jax.tree_util.tree_map(np.subtract, end, w)
    for a, b in zip(jax.tree_util.tree_leaves(change),
                    jax.tree_util.tree_leaves(want["change"])):
        _close(a, b, 2e-3)
    # the bias moved, outside the gradient, as the reference's did
    bias = np.stack([np.asarray(est.tstate.model_state[b.name]["select_bias"])
                     for b in model.blocks if b.has_state])
    assert np.abs(bias).max() > 0
    np.testing.assert_allclose(bias, want["bias"], atol=1e-7)
    # tokens x conv layers of the three steps reached the counter at the drain
    assert conv_pairs() - before == 3 * 32 * 2


# -- the gated short convolution alone ---------------------------------------

def _conv(kernel=3, seq=12, d=16, seed=0):
    from analytics_zoo_tpu.keras.layers import GatedShortConv

    layer = GatedShortConv(kernel)
    layer.ensure_built((None, seq, d))
    p = layer.init_params(jax.random.PRNGKey(seed))
    p = jax.tree_util.tree_map(lambda a: 10.0 * a, p)    # off the noise floor
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, d))
    return layer, p, x


@pytest.mark.parametrize("kernel", [1, 2, 3, 4])
def test_the_convolution_against_a_written_out_loop(kernel):
    layer, p, x = _conv(kernel)
    assert {k: v.shape for k, v in p.items()} == {
        "w_in": (16, 48), "taps": (16, kernel), "w_out": (16, 16)}
    with jax.default_matmul_precision("highest"):
        got = np.asarray(layer.call(p, x))
    w_in, taps, w_out = (np.asarray(p[k], np.float64)
                         for k in ("w_in", "taps", "w_out"))
    xs = np.asarray(x, np.float64)
    want = np.zeros_like(xs)
    for b in range(xs.shape[0]):
        bcx = xs[b] @ w_in
        B, C, X = bcx[:, :16], bcx[:, 16:32], bcx[:, 32:]
        z = B * X
        for t in range(xs.shape[1]):
            c = np.zeros(16)
            for j in range(kernel):
                at = t - (kernel - 1) + j
                if at >= 0:                      # nought before the row's start
                    c += taps[:, j] * z[at]
            want[b, t] = (C[t] * c) @ w_out
    _close(got, want, 1e-5)


@pytest.mark.parametrize("at", [0, 5, 11])
def test_no_output_before_t_depends_on_an_input_after_t(at):
    """And the `kernel - 1` = 2 places after a changed token do, no further."""
    layer, p, x = _conv()
    moved = x.at[:, at].add(1.0)
    with jax.default_matmul_precision("highest"):
        diff = np.abs(np.asarray(layer.call(p, moved) - layer.call(p, x)))
    reach = diff.max(axis=(0, 2)) > 0
    assert not reach[:at].any() and not reach[at + 3:].any()
    assert reach[at:at + 3].all()


def test_the_first_two_positions_see_nought_before_the_row():
    """Position 0 reads its own token alone, position 1 two: what stands
    before the row's start counts as nought, whatever the batch holds there."""
    layer, p, x = _conv()
    with jax.default_matmul_precision("highest"):
        whole = layer.call(p, x)
        one = layer.call(p, x[:, :1])
        two = layer.call(p, x[:, :2])
        rolled = layer.call(p, jnp.roll(x, 1, axis=0))   # other rows about
    _close(whole[:, :1], one, 1e-6)
    _close(whole[:, :2], two, 1e-6)
    _close(jnp.roll(rolled, -1, axis=0), whole, 1e-6)
    # position 0 is its own token through the newest tap alone
    bcx = x[:, 0] @ p["w_in"]
    b, c, xx = jnp.split(bcx, 3, axis=-1)
    _close(whole[:, 0], (c * p["taps"][:, -1] * b * xx) @ p["w_out"], 1e-5)


def test_the_mixer_runs_under_its_scope():
    layer, p, x = _conv()
    text = jax.jit(lambda p_, x_: layer.call(p_, x_)).lower(p, x).as_text(
        debug_info=True)
    assert "conv.short" in text


# -- the block ------------------------------------------------------------

def _block(mixer, norms, remat=True, dtype=None):
    from analytics_zoo_tpu.keras.layers import DecoderBlock, SwiGLU

    block = DecoderBlock(mixer, SwiGLU(32), remat=remat, norms=norms,
                         dtype=dtype)
    block.ensure_built((None, 16, 16))
    return block, block.init_params(jax.random.PRNGKey(0))


def test_the_two_norm_layouts_and_the_mixers_place_in_the_parameters():
    from analytics_zoo_tpu.keras.layers import (GatedShortConv,
                                                GroupedQueryAttention)

    _, p = _block(GatedShortConv(3), "pre")
    assert set(p) == {"conv", "mlp", "in_norm", "pre_mlp_norm"}
    _, p = _block(GroupedQueryAttention(2, 1, 8, gated=False), "sandwich")
    assert set(p) == {"attn", "mlp", "in_norm", "post_attn_norm",
                      "pre_mlp_norm", "post_mlp_norm"}
    assert p["attn"]["w_in"].shape == (16, (2 + 1 + 1) * 8)   # no gate
    with pytest.raises(ValueError, match="norm layout"):
        _block(GatedShortConv(3), "post")


def test_a_pre_normed_block_is_its_equations():
    from analytics_zoo_tpu.keras.layers import GatedShortConv
    from analytics_zoo_tpu.keras.layers.decoder import rms_norm

    block, p = _block(GatedShortConv(3), "pre", remat=False)
    p = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(jax.random.PRNGKey(a.size),
                                              a.shape), p)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    with jax.default_matmul_precision("highest"):
        got = block.call(p, x)
        h = x + block.mixer.call(p["conv"],
                                 rms_norm(x, p["in_norm"]["gain"], 1e-5))
        want = h + block.mlp.call(p["mlp"],
                                  rms_norm(h, p["pre_mlp_norm"]["gain"], 1e-5))
    _close(got, want, 1e-6)


@pytest.mark.parametrize("norms", ["pre", "sandwich"])
def test_a_conv_half_keeps_nothing_and_has_the_same_gradients(norms):
    """Rematerialised, the convolution's half is recomputed whole (a bare
    checkpoint: no name to keep) and gives the gradients it gives without."""
    from analytics_zoo_tpu.keras.layers import GatedShortConv

    assert GatedShortConv(3).kept_residuals() == ()
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 16))
    g = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 16))
    grads = []
    for remat in (True, False):
        block, p = _block(GatedShortConv(3), norms, remat=remat)
        grad = jax.grad(lambda p_, x_: jnp.sum(g * block.call(p_, x_)), (0, 1))
        grads.append(jax.tree_util.tree_leaves(grad(p, x)))
        text = str(jax.make_jaxpr(grad)(p, x))
        assert ("checkpoint" in text or "remat" in text) == remat
        assert "save_only_these_names" not in text
    for a, b in zip(*grads):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_an_attention_half_keeps_the_flash_residuals():
    from analytics_zoo_tpu.keras.layers import GroupedQueryAttention
    from analytics_zoo_tpu.ops.flash_attention import FLASH_RESIDUALS

    assert GroupedQueryAttention(2, 1, 8).kept_residuals() == FLASH_RESIDUALS


# -- the model's layers by configuration ---------------------------------------

def test_rotary_on_full_layers_for_lfm2_moe_and_none_for_afmoe():
    from analytics_zoo_tpu.models.causal_lm import CausalLM

    model = _model()
    assert [type(b.mixer).__name__ for b in model.blocks] == [
        "GatedShortConv", "GroupedQueryAttention", "GatedShortConv"]
    assert [b.attn is None for b in model.blocks] == [True, False, True]
    attn = model.blocks[1].attn
    assert (attn.rope_theta, attn.window, attn.gated, attn.qk_norm,
            attn.head_dim) == (1000000, None, False, True, 16)
    assert all(b.norms == "pre" for b in model.blocks)
    assert model.blocks[0].mixer.kernel == 3 and model.embed_scale == 1.0
    moe = model.blocks[1].mlp
    assert (moe.n_shared, moe.route_eps, moe.top_k, moe.experts_held,
            moe.n_experts) == (0, 1e-6, 2, (2, 4), 8)
    afmoe = CausalLM.from_config({
        "model_type": "afmoe", "hidden_size": 64, "head_dim": 16,
        "num_attention_heads": 4, "num_key_value_heads": 2,
        "intermediate_size": 128, "moe_intermediate_size": 32,
        "layer_types": ["sliding_attention", "full_attention"],
        "num_dense_layers": 1, "num_experts": 4, "num_experts_per_tok": 2,
        "num_shared_experts": 1, "sliding_window": 8, "rope_theta": 10000,
        "rms_norm_eps": 1e-5, "route_norm": True, "route_scale": 2.0,
        "load_balance_coeff": 0.001, "vocab_size": 96}, seq_len=32)
    assert [(b.attn.window, b.attn.rope_theta) for b in afmoe.blocks] == [
        (8, 10000), (None, None)]
    assert all(b.norms == "sandwich" and b.attn.gated for b in afmoe.blocks)
    assert afmoe.head is not None and afmoe.blocks[1].mlp.route_eps == 1e-20


def test_a_full_layer_with_rotary_positions_tells_distances_apart():
    """One head reads two tokens next to each other, and the same two with
    three nought tokens between (nought keys and values: they take weight and
    give nothing, so the output keeps its direction unless the scores moved).
    Without positions the direction is the same; with rotary positions on a
    full layer the distance tells."""
    from analytics_zoo_tpu.keras.layers import GroupedQueryAttention

    x = jax.random.normal(jax.random.PRNGKey(1), (1, 2, 64))
    apart = jnp.concatenate([x[:, :1], jnp.zeros((1, 3, 64)), x[:, 1:]], axis=1)

    def cosine(rope):
        layer = GroupedQueryAttention(1, 1, 16, rope_theta=rope, gated=False)
        layer.ensure_built((None, 5, 64))
        p = layer.init_params(jax.random.PRNGKey(0))
        a = np.asarray(layer.call(p, x)[0, 1], np.float64)
        b = np.asarray(layer.call(p, apart)[0, 4], np.float64)
        return float(np.sum(a * b) / np.linalg.norm(a) / np.linalg.norm(b))

    assert cosine(None) > 1 - 1e-6
    assert cosine(1e6) < 1 - 1e-3


@pytest.mark.parametrize("bad,match", [
    ({"model_type": "mamba2"}, "known.*afmoe.*lfm2_moe"),
    ({"layer_types": ["conv", "linear_attention", "conv"]},
     "layer 1: unknown kind 'linear_attention'.*conv"),
])
def test_from_config_names_what_it_knows(bad, match):
    with pytest.raises(ValueError, match=match):
        _model(**bad)


# -- the router and the expert layer with no shared expert --------------------

@pytest.mark.parametrize("eps", [1e-20, 1e-6, 0.5])
def test_the_normalisers_epsilon_is_an_argument(eps):
    from analytics_zoo_tpu.parallel.moe import route_topk

    x = jax.random.normal(jax.random.PRNGKey(0), (16, 8))
    router = jax.random.normal(jax.random.PRNGKey(1), (8, 6))
    bias = jnp.zeros((6,))
    with jax.default_matmul_precision("highest"):
        picked, w, _ = route_topk(x, router, bias, 2, True, 1.0, eps)
        raw = route_topk(x, router, bias, 2, False)[1]
    _close(w, raw / (jnp.sum(raw, axis=-1, keepdims=True) + eps), 1e-6)
    np.testing.assert_array_equal(
        np.asarray(picked), np.asarray(route_topk(x, router, bias, 2)[0]))


def test_a_token_none_of_whose_picks_is_held_gets_nought(monkeypatch):
    """No shared expert: the layer's output for such a token is exactly 0, on
    the compacted path too (2 of 16 held, a buffer of twice their share)."""
    from analytics_zoo_tpu.keras.layers import SparseMoE

    layer = SparseMoE(16, 32, top_k=2, experts_held=(6, 2), n_shared=0,
                      route_eps=1e-6)
    layer.ensure_built((None, 64))
    p = layer.init_params(jax.random.PRNGKey(0))
    p = dict(p, router=8.0 * p["router"])     # scores apart, none saturated
    assert set(p) == {"router", "experts_w_gate_up", "experts_w_down"}
    x = jax.random.normal(jax.random.PRNGKey(1), (1024, 64))
    with jax.default_matmul_precision("highest"):
        y, st = layer.call(p, x, training=True)
        s = jax.nn.sigmoid(x @ p["router"])
    assert float(st["compact"]) == 1.0
    picked = np.argsort(-np.asarray(s), axis=-1)[:, :2]
    held = ((picked >= 6) & (picked < 8)).any(axis=-1)
    assert 0 < held.sum() < 1024
    got = np.abs(np.asarray(y)).max(axis=-1)
    assert (got[~held] == 0).all() and (got[held] > 0).all()
    # a token with one held pick: that expert's output times its weight
    t = int(np.flatnonzero(held & ~((picked >= 6) & (picked < 8)).all(-1))[0])
    e = int(picked[t][(picked[t] >= 6) & (picked[t] < 8)][0])
    w = float(s[t, e] / (s[t, picked[t]].sum() + 1e-6))
    with jax.default_matmul_precision("highest"):
        gate, up = jnp.split(x[t] @ p["experts_w_gate_up"][e - 6], 2)
        want = w * ((jax.nn.silu(gate) * up) @ p["experts_w_down"][e - 6])
    _close(y[t], want, 1e-5)


# -- the flash kernels at head width 64 ----------------------------------------

@pytest.mark.parametrize("heads,blocks", [((4, 1), (128, 128)),
                                          ((8, 2), (256, 128))])
def test_the_flash_kernels_at_head_width_64_with_grouped_heads(heads, blocks):
    """Forward, dq and dkv kernels in interpret mode against the XLA path:
    half a lane wide, four query heads a key-value head."""
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    nq, nkv = heads
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, nq, 256, 64))
    k = jax.random.normal(ks[1], (1, nkv, 256, 64))
    v = jax.random.normal(ks[2], (1, nkv, 256, 64))
    g = jax.random.normal(ks[3], (1, nq, 256, 64))

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(q, k, v, causal=True,
                                           block_q=blocks[0], block_k=blocks[1]))

    def xla(q, k, v):
        return jnp.sum(g * _reference_attention(q, k, v, None, True, 64 ** -0.5))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(xla, (0, 1, 2))(q, k, v)
    _close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):
        _close(a, b, 2e-5)


def test_the_dispatcher_sends_a_32k_row_of_64_wide_heads_to_the_kernels(
        monkeypatch):
    """By the size of the logits, not by `use_flash=True`: 32 heads x 32 768^2
    in bfloat16 are 69 GB."""
    from analytics_zoo_tpu.ops import attention

    class Tpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Tpu()])
    monkeypatch.delenv("AZOO_FLASH_BYTES_THRESHOLD", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_Q", raising=False)
    monkeypatch.delenv("AZOO_FLASH_BLOCK_K", raising=False)
    q = jax.ShapeDtypeStruct((1, 32, 32768, 64), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((1, 8, 32768, 64), jnp.bfloat16)
    assert attention._auto_use_flash(q, k)
    short = jax.ShapeDtypeStruct((1, 32, 512, 64), jnp.bfloat16)
    assert not attention._auto_use_flash(short, short)


# -- the benchmark's configuration through the program's own count ---------------

def test_the_benchmarks_configuration_builds_with_469_3_m_parameters():
    """Shapes only (`eval_shape`): nothing of that size is made here."""
    import json

    from analytics_zoo_tpu.models.causal_lm import CausalLM

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "lfm2-24b-a2b.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(cfg["assumed"])
    model = CausalLM.from_config(cfg, seq_len=cfg["seq_len"],
                                 dtype=cfg["compute_dtype"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    by_layer = {k: sum(int(np.prod(a.shape))
                       for a in jax.tree_util.tree_leaves(v))
                for k, v in params.items()}
    blocks = [by_layer[b.name] for b in model.blocks]
    # the dense layer (conv mixer 4 d^2 + 3 d of taps, SwiGLU 3 x 2048 x
    # 11 776, two gains), the attention expert layer (q, k, v, o 10.49 M, two
    # head gains, router 0.13 M, 8 experts x 9.44 M, two gains), three conv
    # expert layers, the tied embedding's slice, the last norm
    assert blocks == [89_139_200, 86_118_528, 92_416_000, 92_416_000,
                      92_416_000]
    assert by_layer[model.embed.name] == 8192 * 2048
    assert sum(by_layer.values()) == 469_284_992
    assert model.conv_layers == 4 and model.experts_held == (0, 8)
