"""The chunked state-space scan (`ops/ssd.py`), the Mamba-2 mixer, blocks of
one part, squared-ReLU experts, and `CausalLM` built from a `nemotron_h`
configuration, against the plain float32 reference
(benchmark/reference/nemotronh.py, whose scan is the recurrence a token at a
time), at tiny widths on the CPU with seeded weights.

Tolerances: both sides compute in float32 with `highest` matmul precision, so
they differ by the order of the sums alone: 2e-5 of the largest value on a
forward pass (the chunked scan sums a chunk's terms as a product and the
chunks' states as a recurrence, the reference one token after another: 1e-5
of its largest output), 1e-4 on gradients (two passes), 2e-3 on the change
after three Adam steps (a step divides by the root of a squared gradient
near nought)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import nemotronh as ref, optim

CFG = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "mamba_num_heads": 8,
    "mamba_head_dim": 8, "n_groups": 2, "ssm_state_size": 16,
    "conv_kernel": 4, "chunk_size": 8, "expand": 2,
    "hybrid_override_pattern": "MEM*E", "num_hidden_layers": 5,
    "intermediate_size": 32, "moe_intermediate_size": 32,
    "moe_shared_expert_intermediate_size": 48, "n_routed_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2, "n_shared_experts": 1,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "mamba_proj_bias": False, "mlp_bias": False, "attention_bias": False,
    "use_bias": False, "use_conv_bias": True, "time_step_limit": [0, None],
    "time_step_min": 0.001, "time_step_max": 0.1, "time_step_floor": 1e-4,
    "rescale_prenorm_residual": True, "rescale_prenorm_residual_layers": 52,
    "residual_in_fp32": False, "layer_norm_epsilon": 1e-5, "norm_eps": 1e-5,
    "rope_theta": 10000, "partial_rotary_factor": 1, "sliding_window": None,
    "tie_word_embeddings": False, "vocab_size": 96, "initializer_range": 0.05,
    "seq_len": 32, "compute_dtype": None, "bias_rate": 0.001,
}


def _model(cfg=CFG, **kw):
    from benchmark import models_lm

    return models_lm._build(dict(cfg, **kw))


def _weights(model, cfg=CFG, seed=0):
    """(reference tree, program tree) of the same numbers."""
    from benchmark import models_nemotronh

    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    # vectors off their initial values, so that a norm, a skip or a bias
    # left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, w)
    return w, models_nemotronh._to_program(w, model)


def _rows(cfg=CFG, n=2, seed=3):
    t = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


# -- the chunked scan against the recurrence ---------------------------------

def _scan_inputs(t=32, seed=0, bsz=2, h=4, p=8, g=2, n=16):
    k = jax.random.split(jax.random.PRNGKey(seed), 6)
    return (jax.random.normal(k[0], (bsz, t, h, p)),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            jax.random.normal(k[3], (bsz, t, g, n)),
            jax.random.normal(k[4], (bsz, t, g, n)),
            jax.random.normal(k[5], (h,)))


@pytest.mark.parametrize("chunk", [1, 4, 8, 16, 32])
def test_the_chunked_scan_is_the_recurrence_forward_and_backward(chunk):
    from analytics_zoo_tpu.ops.ssd import chunked_scan

    args = _scan_inputs()
    g = jax.random.normal(jax.random.PRNGKey(7), args[0].shape)
    with jax.default_matmul_precision("highest"):
        want = ref.ssm_scan(*args, block=8)
        _close(chunked_scan(*args, chunk), want, 2e-5)
        got = jax.grad(lambda *a: jnp.sum(g * chunked_scan(*a, chunk)),
                       argnums=tuple(range(6)))(*args)
        grads = jax.grad(lambda *a: jnp.sum(g * ref.ssm_scan(*a, block=8)),
                         argnums=tuple(range(6)))(*args)
    for name, a, b in zip("x dt a b c d".split(), got, grads):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        _close(a, b, 1e-4)


def test_the_scan_carries_state_across_chunks_and_decays_it():
    """A row whose later chunks have no input reads out what the first chunk
    left, decayed token by token; with no decay the state is kept whole."""
    from analytics_zoo_tpu.ops.ssd import chunked_scan

    x, dt, a, b, c, d = _scan_inputs(t=24)
    x = x.at[:, 8:].set(0.0)
    with jax.default_matmul_precision("highest"):
        y = chunked_scan(x, dt, a, b, c, d, 8)
        kept = chunked_scan(x, dt, jnp.zeros_like(a), b, c, d, 8)
    assert float(jnp.max(jnp.abs(y[:, 16:]))) > 0
    _close(y, ref.ssm_scan(x, dt, a, b, c, d, block=8), 2e-5)
    assert float(jnp.max(jnp.abs(kept[:, 16:]))) > float(
        jnp.max(jnp.abs(y[:, 16:])))


def test_a_row_that_is_not_whole_chunks_is_refused():
    from analytics_zoo_tpu.ops.ssd import chunked_scan

    with pytest.raises(ValueError, match="not a whole number of chunks"):
        chunked_scan(*_scan_inputs(t=20), 8)


def test_bfloat16_inputs_keep_the_scan_in_float32():
    from analytics_zoo_tpu.ops.ssd import chunked_scan

    x, dt, a, b, c, d = _scan_inputs()
    low = [t.astype(jnp.bfloat16) for t in (x, b, c)]
    y = chunked_scan(low[0], dt, a, low[1], low[2], d, 8)
    assert y.dtype == jnp.bfloat16
    with jax.default_matmul_precision("highest"):
        want = ref.ssm_scan(*(t.astype(jnp.float32) for t in low[:1]), dt, a,
                            low[1].astype(jnp.float32),
                            low[2].astype(jnp.float32), d)
    # one rounding of the output to bfloat16
    _close(y, want, 1e-2)


# -- the Mamba-2 mixer ---------------------------------------------------------

def _mixer(seq=32, seed=0):
    from analytics_zoo_tpu.keras.layers import Mamba2Mixer

    layer = Mamba2Mixer(8, 8, 2, 16, conv_kernel=4, chunk=8,
                        out_scale=52 ** -0.5)
    layer.ensure_built((None, seq, 64))
    p = layer.init_params(jax.random.PRNGKey(seed))
    p["w_in"] = 4.0 * p["w_in"] / 0.02 * 0.05       # inputs apart from 0
    p["norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (64,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, 64))
    return layer, p, x


def _reference_of(p):
    from benchmark import models_nemotronh

    cut = [64, 128, 128 + 32, 128 + 64]
    w = dict(zip(models_nemotronh.MAMBA_IN, jnp.split(p["w_in"], cut, axis=1)))
    w.update({theirs: p[mine] for mine, theirs in models_nemotronh.MAMBA})
    return w


def test_the_mixers_weights_are_the_published_ones():
    layer, p, _ = _mixer()
    assert {k: v.shape for k, v in p.items()} == {
        "w_in": (64, 64 + (64 + 2 * 2 * 16) + 8), "conv_taps": (128, 4),
        "conv_bias": (128,), "dt_bias": (8,), "a_log": (8,), "d_skip": (8,),
        "norm": (64,), "w_out": (64, 64)}
    assert layer.block_key == "mamba" and layer.kept_residuals() == ()
    assert layer.float32_params == ("a_log", "dt_bias", "d_skip")


def test_the_family_initial_values():
    layer, _, _ = _mixer()
    p = layer.init_params(jax.random.PRNGKey(3))
    np.testing.assert_allclose(np.asarray(p["a_log"]), np.log(np.arange(1, 9)),
                               rtol=1e-6)
    np.testing.assert_array_equal(np.asarray(p["d_skip"]), np.ones(8))
    dt = np.asarray(jax.nn.softplus(p["dt_bias"]))
    assert np.all(dt >= 1e-3 * (1 - 1e-5)) and np.all(dt <= 0.1 * (1 + 1e-5))
    assert len(set(np.round(dt, 6))) == 8
    np.testing.assert_allclose(np.std(np.asarray(p["w_out"])),
                               0.02 / np.sqrt(52), rtol=0.2)
    assert np.max(np.abs(np.asarray(p["conv_taps"]))) <= 0.5
    # the reference starts from the same laws
    w = ref.init_weights(CFG, jax.random.PRNGKey(3))["layers"][0]
    np.testing.assert_array_equal(np.asarray(w["A_log"]),
                                  np.log(np.arange(1, 9, dtype=np.float32)))
    np.testing.assert_array_equal(np.asarray(w["D"]), np.ones(8))
    dt = np.asarray(jax.nn.softplus(w["dt_bias"]))
    assert np.all(dt >= 1e-3 * (1 - 1e-5)) and np.all(dt <= 0.1 * (1 + 1e-5))
    # the floor holds where the range reaches under it
    floored = ref.init_dt_bias(dict(CFG, time_step_min=1e-6, time_step_max=2e-6,
                                    time_step_floor=1e-4),
                               jax.random.PRNGKey(0), 8)
    np.testing.assert_allclose(np.asarray(jax.nn.softplus(floored)),
                               np.full(8, 1e-4), rtol=1e-4)


def test_the_mixer_against_the_reference_forward_and_gradients():
    layer, p, x = _mixer()
    g = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def mine(p_, x_):
        return jnp.sum(g * layer.call(p_, x_))

    def theirs(w_, x_):
        return jnp.sum(g * ref.mamba(w_, x_, CFG))

    w = _reference_of(p)
    with jax.default_matmul_precision("highest"):
        _close(layer.call(p, x), ref.mamba(w, x, CFG), 2e-5)
        got_p, got_x = jax.grad(mine, (0, 1))(p, x)
        want_w, want_x = jax.grad(theirs, (0, 1))(w, x)
        # the gate after the norm, the probe's planted fault, is another
        # function of the same weights
        off = ref.mamba(w, x, dict(CFG, ssm_gate_after_norm=True))
    assert np.abs(np.asarray(off - ref.mamba(w, x, CFG))).max() > 1e-2
    _close(got_x, want_x, 1e-4)
    got_w = _reference_of(got_p)
    for name in want_w:
        assert float(jnp.max(jnp.abs(want_w[name]))) > 0, name
        _close(got_w[name], want_w[name], 1e-4)


def test_the_mixer_runs_under_three_scopes_of_its_own():
    layer, p, x = _mixer()
    hlo = jax.jit(layer.call).lower(p, x).as_text("hlo", debug_info=True)
    for scope in ("ssm.proj_in", "ssm.scan", "ssm.proj_out"):
        assert scope in hlo, scope


# -- squared-ReLU experts and one chip's share --------------------------------

def _experts(n_experts=32, count=2, width=16, shared=24, d=32, seed=0):
    from analytics_zoo_tpu.keras.layers import SparseMoE

    k = iter(jax.random.split(jax.random.PRNGKey(seed), 8))

    def mat(*shape):
        return 0.2 * jax.random.normal(next(k), shape)

    whole = {"router": mat(d, n_experts), "shared_w_up": mat(d, shared),
             "shared_w_down": mat(shared, d),
             "experts_w_up": mat(n_experts, d, width),
             "experts_w_down": mat(n_experts, width, d)}
    layers = []
    for at in range(0, n_experts, count):
        layer = SparseMoE(n_experts, width, 2, (at, count), 1, True, 2.5,
                          activation="relu2", shared_width=shared)
        layer.ensure_built((None, 8, d))
        layers.append(layer)
    x = jax.random.normal(next(k), (2, 64, d))
    return whole, layers, x


def test_sixteen_shares_add_up_to_the_uncut_layer_with_the_shared_expert_once():
    whole, layers, x = _experts()
    cfg = dict(CFG, n_routed_experts=32, router_num_experts=32,
               experts_held_offset=0, num_experts_per_tok=2)
    assert len(layers) == 16
    total = 0.0
    with jax.default_matmul_precision("highest"):
        for layer in layers:
            at, count = layer.experts_held
            p = dict(whole, experts_w_up=whole["experts_w_up"][at:at + count],
                     experts_w_down=whole["experts_w_down"][at:at + count])
            assert {k: v.shape for k, v in layer.init_params(
                jax.random.PRNGKey(0)).items()} == {
                    k: v.shape for k, v in p.items()}
            y, _ = layer.call(p, x, training=True)
            total = total + y
        shared = ref._relu2({"w_up": whole["shared_w_up"],
                             "w_down": whole["shared_w_down"]},
                            x.reshape(-1, 32), jnp.matmul)
        want, _ = ref.expert_layer(
            {"router": whole["router"],
             "shared": {"w_up": whole["shared_w_up"],
                        "w_down": whole["shared_w_down"]},
             "experts": {"w_up": whole["experts_w_up"],
                         "w_down": whole["experts_w_down"]}},
            x.reshape(-1, 32), jnp.zeros((32,)), cfg)
    _close(total.reshape(-1, 32) - 15 * shared, want, 1e-4)


def test_squared_relu_experts_compacted_and_in_chunks_agree_with_gradients():
    """The compacted pass and the chunks over all assignments are one path
    with the activation as its data: both give the reference's held part
    and its gradients."""
    from analytics_zoo_tpu.parallel import moe

    whole, _, _ = _experts()
    # 512 tokens: the compacted buffer (a row tile) is then under all picks
    flat = jax.random.normal(jax.random.PRNGKey(6), (512, 32))
    picked, weights, _ = moe.route_topk(flat, whole["router"],
                                        jnp.zeros((32,)), 2)
    up, down = whole["experts_w_up"][2:4], whole["experts_w_down"][2:4]

    def want_fn(f, w_, u, dn):
        y = 0.0
        for e in range(2):
            w_e = jnp.sum(jnp.where(picked == 2 + e, w_, 0.0), axis=-1)
            y = y + w_e[:, None] * (jnp.square(jax.nn.relu(f @ u[e])) @ dn[e])
        return y

    g = jax.random.normal(jax.random.PRNGKey(4), flat.shape)
    with jax.default_matmul_precision("highest"):
        want = want_fn(flat, weights, up, down)
        want_g = jax.grad(lambda *a: jnp.sum(g * want_fn(*a)), (0, 1, 2, 3))(
            flat, weights, up, down)
        y, compact = moe.held_experts_ffn(flat, picked, weights, up, down, 32,
                                          2, "relu2")
        got_g = jax.grad(lambda *a: jnp.sum(g * moe.held_experts_ffn(
            a[0], picked, a[1], a[2], a[3], 32, 2, "relu2")[0]),
            (0, 1, 2, 3))(flat, weights, up, down)
        chunks = moe._held_chunks(flat, picked, weights, up, down, 32, 2,
                                  "relu2")
    assert bool(compact)
    _close(y, want, 1e-5)
    _close(chunks, want, 1e-5)
    for a, b in zip(got_g, want_g):
        _close(a, b, 1e-4)


def test_an_unknown_expert_activation_is_refused():
    from analytics_zoo_tpu.keras.layers import SparseMoE

    with pytest.raises(ValueError, match="unknown expert activation"):
        SparseMoE(8, 16, 2, activation="gelu")


# -- the whole model ------------------------------------------------------

def test_logits_and_row_losses_of_the_whole_model():
    from analytics_zoo_tpu.keras import objectives

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()
    with jax.default_matmul_precision("highest"):
        got = jax.jit(lambda p: model.apply(p, state, x, training=False)[0])(
            params)
        _close(got, jax.jit(lambda w_: ref.logits(w_, x, CFG))(w), 2e-5)
        rows = objectives.get_per_sample(
            objectives.token_crossentropy_from_logits)(y, got)
        _close(rows, jax.jit(lambda w_: ref.row_losses(w_, x, y, CFG))(w), 2e-5)
        off = jax.jit(lambda w_: ref.logits(
            w_, x, dict(CFG, ssm_gate_after_norm=True)))(w)
    assert np.abs(np.asarray(off) - np.asarray(got)).max() > 1e-3


def test_gradients_of_one_step_leaf_by_leaf():
    from analytics_zoo_tpu.keras import objectives
    from benchmark import models_nemotronh

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()

    def loss(p):
        z, _ = model.apply(p, state, x, training=True)
        return objectives.token_crossentropy_from_logits(y, z)

    with jax.default_matmul_precision("highest"):
        got = models_nemotronh._from_program(jax.jit(jax.grad(loss))(params),
                                             model)
        want = jax.jit(jax.grad(
            lambda w_: jnp.mean(ref.row_losses(w_, x, y, CFG))))(w)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for path, a in jax.tree_util.tree_leaves_with_path(got):
        b = dict(jax.tree_util.tree_leaves_with_path(want))[path]
        assert float(jnp.max(jnp.abs(b))) > 0, path
        _close(a, b, 1e-4)


def test_the_weight_maps_are_each_others_inverse():
    from benchmark import models_nemotronh

    model = _model()
    w, params = _weights(model)
    back = models_nemotronh._from_program(params, model)
    assert (jax.tree_util.tree_structure(w)
            == jax.tree_util.tree_structure(back))
    for a, b in zip(jax.tree_util.tree_leaves(w),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    mamba = params[model.blocks[0].name]["mamba"]
    # [z | x | B | C | dt], as the published in_proj lays them out
    np.testing.assert_array_equal(np.asarray(mamba["w_in"][:, 128:160]),
                                  np.asarray(w["layers"][0]["w_b"]))


def test_three_estimator_steps_with_adam_and_the_bias_update():
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.common.observability import get_registry
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import fit_lm, models_nemotronh

    def ssm_pairs():
        fam = get_registry()._families.get("zoo_lm_ssm_token_layers_total")
        return sum(c.value for c in fam._children.values()) if fam else 0.0

    nncontext.init_nncontext(mesh_shape=(1, 8))   # no data axis to round to
    model = _model()
    w, params = _weights(model)
    w = jax.device_get(w)        # the step donates what set_weights placed
    est = model._get_estimator()
    model.set_weights(params)
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="token_crossentropy_from_logits")
    x, y = _rows(n=1)
    losses, before = [], ssm_pairs()

    class Tape:
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                losses.append(value)

    est.train_summary = Tape()
    with jax.default_matmul_precision("highest"):
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(1))
        first = models_nemotronh._from_program(
            fit_lm.first_gradient(est.tstate.opt_state, 0.9), model, np)
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(3))
        batches = [(jnp.asarray(x), jnp.asarray(y))] * 3
        want = fit_lm.follow(ref, CFG, w, batches,
                             optim.Adam(lr=1e-3), row_block=1)
    _close(losses, want["losses"], 1e-5)
    for a, b in zip(jax.tree_util.tree_leaves(first),
                    jax.tree_util.tree_leaves(want["first"])):
        _close(a, b, 1e-4)
    end = models_nemotronh._from_program(jax.device_get(est.tstate.params),
                                         model, np)
    change = jax.tree_util.tree_map(np.subtract, end, w)
    for a, b in zip(jax.tree_util.tree_leaves(change),
                    jax.tree_util.tree_leaves(want["change"])):
        _close(a, b, 2e-3)
    bias = np.stack([np.asarray(est.tstate.model_state[b.name]["select_bias"])
                     for b in model.blocks if b.has_state])
    assert np.abs(bias).max() > 0
    np.testing.assert_allclose(bias, want["bias"], atol=1e-7)
    # tokens x Mamba layers of the three steps reached the counter
    assert ssm_pairs() - before == 3 * 32 * 2
    assert (model.ssm_layers, model.latent_layers, model.conv_layers) == (
        2, 0, 0)


# -- the model's layers by configuration ---------------------------------------

def test_a_nemotron_h_file_builds_one_part_layers_by_its_pattern():
    model = _model()
    assert [(type(b.mixer).__name__, type(b.mlp).__name__)
            for b in model.blocks] == [
        ("Mamba2Mixer", "NoneType"), ("NoneType", "SparseMoE"),
        ("Mamba2Mixer", "NoneType"), ("GroupedQueryAttention", "NoneType"),
        ("NoneType", "SparseMoE")]
    assert [sorted(b._norms) for b in model.blocks] == [
        ["in_norm"], ["pre_mlp_norm"], ["in_norm"], ["in_norm"],
        ["pre_mlp_norm"]]
    attn = model.blocks[3].attn
    assert (attn.n_head, attn.n_kv_head, attn.head_dim, attn.window,
            attn.rope_theta, attn.qk_norm, attn.gated) == (
                4, 2, 16, None, None, False, False)
    mamba = model.blocks[0].mixer
    assert (mamba.n_heads, mamba.head_dim, mamba.n_groups, mamba.state_dim,
            mamba.conv_kernel, mamba.chunk, mamba.time_step, mamba.epsilon) == (
                8, 8, 2, 16, 4, 8, (0.001, 0.1, 1e-4), 1e-5)
    assert mamba.out_scale == pytest.approx(52 ** -0.5)
    moe = model.blocks[1].mlp
    assert (moe.activation, moe.n_shared, moe.shared_width, moe.width,
            moe.route_eps, moe.top_k, moe.experts_held, moe.n_experts,
            moe.route_scale, moe.route_norm, moe.bias_rate) == (
                "relu2", 1, 48, 32, 1e-20, 2, (2, 4), 8, 2.5, True, 0.001)
    assert model.embed_scale == 1.0 and model.head is not None
    # one part a block: the parameters hold that part and its norm alone
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    assert sorted(params[model.blocks[1].name]) == ["mlp", "pre_mlp_norm"]
    assert sorted(params[model.blocks[1].name]["mlp"]) == [
        "experts_w_down", "experts_w_up", "router", "shared_w_down",
        "shared_w_up"]


@pytest.mark.parametrize("key,value", [
    ("mamba_proj_bias", True), ("mlp_bias", True), ("attention_bias", True),
    ("use_bias", True), ("use_conv_bias", False), ("residual_in_fp32", True),
    ("n_group", 8), ("topk_group", 4), ("mlp_hidden_act", "silu"),
    ("mamba_hidden_act", "gelu"), ("sliding_window", 4096)])
def test_what_is_not_built_is_refused_by_key(key, value):
    with pytest.raises(NotImplementedError, match=f"nemotron_h with {key}="):
        _model(**{key: value})


@pytest.mark.parametrize("limit", [[0.0, 0.1], [0.001, None]])
def test_a_clamped_time_step_is_refused(limit):
    with pytest.raises(NotImplementedError, match="time_step_limit"):
        _model(time_step_limit=limit)


def test_a_dense_layer_or_a_pattern_of_another_depth_is_refused():
    with pytest.raises(NotImplementedError, match=r"\['-'\]"):
        _model(hybrid_override_pattern="M-M*E")
    with pytest.raises(ValueError, match="a pattern of 4 layers"):
        _model(hybrid_override_pattern="MEM*")


def test_from_config_names_the_families_it_knows():
    with pytest.raises(ValueError, match="known.*deepseek_v3.*nemotron_h"):
        _model(model_type="mamba2")


# -- the benchmark's configuration through the program's own count ---------------

def test_the_benchmarks_configuration_builds_with_667_0_m_parameters():
    """Shapes only (`eval_shape`): nothing of that size is made here."""
    from analytics_zoo_tpu.models.causal_lm import CausalLM

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "nemotron-twotower-30b-a3b.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(cfg["assumed"])
    model = CausalLM.from_config(cfg, seq_len=cfg["seq_len"],
                                 dtype=cfg["compute_dtype"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    by_layer = [count(params[b.name]) for b in model.blocks]
    # M: in_proj 2688 x 10304, conv 6144 x 4 + 6144, out_proj 4096 x 2688,
    # A_log, D, dt_bias 64 each, the gated norm 4096, the block norm 2688;
    # E: router 2688 x 128, shared 2 x 2688 x 3712, 8 experts 2 x 2688 x
    # 1856, the norm; *: q 2688 x 4096, k, v 2688 x 256, o 4096 x 2688, norm
    m, e, a = 38_744_896, 100_125_312, 23_399_040
    assert by_layer == [m, e, m, e, m, a, e, m, e]
    assert count(params[model.embed.name]) == count(
        params[model.head.name]) == 44_040_192
    assert sum(count(v) for v in params.values()) == 666_962_944
    assert model.ssm_layers == 4 and model.experts_held == (0, 8)
    mlp = params[model.blocks[1].name]["mlp"]
    assert mlp["experts_w_up"].shape == (8, 2688, 1856)
    assert mlp["shared_w_up"].shape == (2688, 3712)
    assert params[model.blocks[0].name]["mamba"]["w_in"].shape == (2688, 10304)
