"""Latent attention, the interleaved rotary embedding, the flash kernels at a
query-key width beside a value width, and `CausalLM` built from a `deepseek_v3`
configuration, against the plain float32 reference
(benchmark/reference/kanana2.py), at tiny widths on the CPU with seeded
weights.

Tolerances: both sides compute in float32 with `highest` matmul precision, so
they differ by the order of the sums alone: 2e-5 of the largest value on a
forward pass, 1e-4 on gradients (two passes), 2e-3 on the change after three
Adam steps (a step divides by the root of a squared gradient near nought).
The kernels in bfloat16 are held to the float32 reference on the same rounded
inputs at 2e-2 (their products round to bfloat16 once more)."""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import kanana2 as ref, optim

CFG = {
    "model_type": "deepseek_v3", "hidden_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 4, "qk_nope_head_dim": 16, "qk_rope_head_dim": 8,
    "qk_head_dim": 24, "v_head_dim": 16, "kv_lora_rank": 32,
    "q_lora_rank": None, "intermediate_size": 128, "moe_intermediate_size": 32,
    "num_hidden_layers": 3, "first_k_dense_replace": 1, "n_routed_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2, "n_shared_experts": 2,
    "num_experts_per_tok": 2, "n_group": 1, "topk_group": 1,
    "topk_method": "noaux_tc", "scoring_func": "sigmoid",
    "norm_topk_prob": True, "routed_scaling_factor": 2.448,
    "rope_theta": 1000000, "rope_interleave": True, "rope_scaling": None,
    "rms_norm_eps": 1e-6, "tie_word_embeddings": False, "vocab_size": 96,
    "initializer_range": 0.05, "seq_len": 32, "compute_dtype": None,
    "bias_rate": 0.001,
}


def _model(cfg=CFG, **kw):
    from benchmark import models_lm

    return models_lm._build(dict(cfg, **kw))


def _weights(model, cfg=CFG, seed=0):
    """(reference tree, program tree) of the same numbers."""
    from benchmark import models_kanana2

    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    # gains off 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, w)
    return w, models_kanana2._to_program(w, model)


def _rows(cfg=CFG, n=2, seed=3):
    t = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


# -- the rotary embedding over neighbouring pairs ------------------------------

def test_interleaved_rotary_is_a_complex_rotation_of_neighbouring_pairs():
    from analytics_zoo_tpu.keras.layers import rotary_embedding

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 12, 8))
    got = np.asarray(rotary_embedding(x, 1e6, interleaved=True), np.float64)
    xs = np.asarray(x, np.float64)
    z = xs[..., 0::2] + 1j * xs[..., 1::2]           # (x[2i], x[2i+1])
    t = np.arange(12)[:, None]
    turned = z * np.exp(1j * t / 1e6 ** (np.arange(0, 8, 2) / 8))
    want = np.stack([turned.real, turned.imag], axis=-1).reshape(xs.shape)
    _close(got, want, 1e-6)
    # the half-rotated form pairs (x[i], x[i + 4]): another function of x
    halves = np.asarray(rotary_embedding(x, 1e6), np.float64)
    assert np.abs(halves - want).max() > 0.1
    # ... and the same one on de-interleaved entries (the published model
    # file's own way to the interleaved form)
    order = np.r_[0:8:2, 1:8:2]
    _close(np.asarray(rotary_embedding(x[..., order], 1e6)), want[..., order],
           1e-6)
    # positions: entry t of a row is turned by `positions[t]`
    at = np.asarray([5, 0, 7])
    turned = z[:, :, :3] * np.exp(
        1j * at[:, None] / 1e6 ** (np.arange(0, 8, 2) / 8))
    _close(rotary_embedding(x[:, :, :3], 1e6, positions=jnp.asarray(at),
                            interleaved=True),
           np.stack([turned.real, turned.imag], axis=-1).reshape(2, 3, 3, 8),
           1e-6)


# -- latent attention alone --------------------------------------------------

def _latent(seq=16, seed=0, **kw):
    from analytics_zoo_tpu.keras.layers import LatentAttention

    layer = LatentAttention(4, 16, 8, 16, 32, rope_theta=1e6, epsilon=1e-6,
                            **kw)
    layer.ensure_built((None, seq, 64))
    p = layer.init_params(jax.random.PRNGKey(seed))
    p = jax.tree_util.tree_map(lambda a: 4.0 * a, p)     # scores apart
    p["kv_norm"] = 1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(9), (32,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 1), (2, seq, 64))
    return layer, p, x


def _reference_of(layer, p):
    """The layer's weights in the reference's layout, and its configuration."""
    from benchmark import models_kanana2

    wq_nope, wq_rope = models_kanana2._from_heads(p["w_q"], 4, 16, jnp)
    wk_nope, wv = models_kanana2._from_heads(p["w_kv_b"], 4, 16, jnp)
    w_c, w_kr = jnp.split(p["w_kv_a"], [32], axis=1)
    return {"wq_nope": wq_nope, "wq_rope": wq_rope, "w_c": w_c, "w_kr": w_kr,
            "kv_norm": p["kv_norm"], "wk_nope": wk_nope, "wv": wv,
            "wo": p["w_out"]}


def test_the_layers_weights_are_the_published_ones():
    layer, p, _ = _latent()
    assert {k: v.shape for k, v in p.items()} == {
        "w_q": (64, 4 * 24), "w_kv_a": (64, 32 + 8), "kv_norm": (32,),
        "w_kv_b": (32, 4 * 32), "w_out": (4 * 16, 64)}
    assert layer.block_key == "attn" and layer.qk_dim == 24


def test_latent_attention_against_the_reference_forward_and_gradients():
    layer, p, x = _latent()
    cfg = CFG
    g = jax.random.normal(jax.random.PRNGKey(5), x.shape)

    def mine(p_, x_):
        return jnp.sum(g * layer.call(p_, x_))

    def theirs(w_, x_):
        return jnp.sum(g * (ref.latent_attention(w_, x_, cfg) @ w_["wo"]))

    w = _reference_of(layer, p)
    with jax.default_matmul_precision("highest"):
        _close(layer.call(p, x), ref.latent_attention(w, x, cfg) @ w["wo"],
               2e-5)
        got_p, got_x = jax.grad(mine, (0, 1))(p, x)
        want_w, want_x = jax.grad(theirs, (0, 1))(w, x)
        # the reference with the pairs taken as halves, the benchmark's
        # planted fault, is another function of the same weights
        off = ref.latent_attention(w, x, dict(cfg, rope_interleave=False))
    assert np.abs(np.asarray(off - ref.latent_attention(w, x, cfg))).max() > 1e-2
    _close(got_x, want_x, 1e-4)
    got_w = _reference_of(layer, got_p)
    for name in want_w:
        assert float(jnp.max(jnp.abs(want_w[name]))) > 0, name
        _close(got_w[name], want_w[name], 1e-4)


def test_the_rotary_key_is_one_head_and_its_gradient_the_sum_over_heads():
    """Against the kernel's operands written out with the rotary key given
    to each head apart (the same numbers four times): the four heads'
    gradients, which differ, add up to what the layer's one key gets."""
    from analytics_zoo_tpu.keras.layers.decoder import (rms_norm,
                                                        rotary_embedding)
    from analytics_zoo_tpu.ops.attention import _reference_attention

    layer, p, x = _latent()
    g = jax.random.normal(jax.random.PRNGKey(5), (2, 4, 16, 16))

    def core(k_rope_heads, p_):
        """The kernel's operands written out, the rotary key a head apart:
        k_rope_heads (b, 4, s, 8)."""
        b, s, _ = x.shape
        q = (x @ p_["w_q"]).reshape(b, s, 4, 24).transpose(0, 2, 1, 3)
        c = rms_norm((x @ p_["w_kv_a"])[..., :32], p_["kv_norm"], 1e-6)
        kv = (c @ p_["w_kv_b"]).reshape(b, s, 4, 32).transpose(0, 2, 1, 3)
        q = jnp.concatenate([q[..., :16], rotary_embedding(
            q[..., 16:], 1e6, interleaved=True)], axis=-1)
        k = jnp.concatenate([kv[..., :16], rotary_embedding(
            k_rope_heads, 1e6, interleaved=True)], axis=-1)
        return jnp.sum(g * _reference_attention(q, k, kv[..., 16:], None, True,
                                                24 ** -0.5))

    k_rope = (x @ p["w_kv_a"])[..., 32:]                     # (b, s, 8)
    apart = jnp.broadcast_to(k_rope[:, None], (2, 4, 16, 8))
    with jax.default_matmul_precision("highest"):
        by_head = jax.grad(core)(apart, p)                   # (b, 4, s, 8)
        # the layer's own gradient to W_kva's rotary columns (W_out the
        # identity, so that its output is the kernel's)
        got = jax.grad(lambda p_: jnp.sum(
            layer.call(dict(p_, w_out=jnp.eye(64)), x)
            * g.transpose(0, 2, 1, 3).reshape(2, 16, 64)))(p)["w_kv_a"][:, 32:]
    assert np.abs(np.asarray(by_head[:, 0] - by_head[:, 1])).max() > 1e-3
    _close(got, jnp.einsum("bsd,bhsr->dr", x, by_head), 1e-4)


def test_the_projections_and_the_kernel_run_under_scopes_of_their_own():
    """`attn.latent` is not wrapped round `attn.full`: a trace's reduction
    gives an operation to the first scope its name holds."""
    import re

    layer, p, x = _latent()
    text = jax.jit(lambda p_, x_: layer.call(p_, x_)).lower(p, x).as_text(
        debug_info=True)
    names = set(re.findall(r'"(jit\([^"]*)"', text))
    latent = {n for n in names if "attn.latent" in n}
    full = {n for n in names if "attn.full" in n}
    out = {n for n in names if "attn.proj_out" in n}
    assert latent and full and out
    assert not latent & full and not latent & out and not full & out
    assert any("dot_general" in n for n in latent)
    # the heads merged and the output projection under a scope of their own
    assert any("dot_general" in n for n in out)
    assert not any("dot_general" in n for n in names - latent - full - out)


def test_a_latent_half_keeps_the_flash_residuals_and_is_the_blocks_attn():
    from analytics_zoo_tpu.keras.layers import (DecoderBlock, LatentAttention,
                                                SwiGLU)
    from analytics_zoo_tpu.ops.flash_attention import FLASH_RESIDUALS

    mixer = LatentAttention(4, 16, 8, 16, 32)
    assert mixer.kept_residuals() == FLASH_RESIDUALS
    block = DecoderBlock(mixer, SwiGLU(32), norms="pre", dtype=None)
    block.ensure_built((None, 16, 64))
    assert block.attn is mixer and block.mixer is mixer
    assert set(block.init_params(jax.random.PRNGKey(0))) == {
        "attn", "mlp", "in_norm", "pre_mlp_norm"}
    with pytest.raises(ValueError, match="rotary pairs over 7"):
        LatentAttention(4, 16, 7, 16, 32)


def test_a_latent_block_runs_the_flash_forward_once(monkeypatch):
    """On the kernel path (interpret mode; the dispatcher's choice patched)
    a rematerialised block's gradient holds one forward kernel and one
    backward (``zoo_flash_dkv``, dq beside dk / dv), with operands 24 and 16
    wide."""
    from analytics_zoo_tpu.keras.layers import (DecoderBlock, LatentAttention,
                                                SwiGLU)
    from analytics_zoo_tpu.ops import attention
    from conftest import inner_jaxprs

    monkeypatch.setattr(attention, "_auto_use_flash", lambda q, k: True)
    block = DecoderBlock(LatentAttention(2, 16, 8, 16, 32), SwiGLU(32),
                         norms="pre", dtype=None)
    block.ensure_built((None, 128, 64))
    p = block.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 64))
    jaxpr = jax.make_jaxpr(jax.grad(lambda p_: jnp.sum(block.call(p_, x))))(p)

    def calls(jp):
        for eqn in jp.eqns:
            if eqn.primitive.name == "pallas_call":
                yield (eqn.params["name"] if "name" in eqn.params
                       else eqn.params["name_and_src_info"].name,
                       [v.aval.shape[-1] for v in eqn.invars[3:6]])
            for inner in inner_jaxprs(eqn):
                yield from calls(inner)

    found = list(calls(jaxpr.jaxpr))
    assert sorted(n for n, _ in found) == ["zoo_flash_dkv", "zoo_flash_fwd"]
    assert all(widths == [24, 24, 16] for _, widths in found)


# -- the flash kernels at a query-key width beside a value width -----------------

@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("heads", [(4, 4), (4, 2)])
@pytest.mark.parametrize("s_q,s_k", [(256, 256), (128, 256)])
def test_the_flash_kernels_at_192_beside_128(s_q, s_k, heads, dtype):
    """Forward, dq and dkv in interpret mode against the XLA path in
    float32: out and dv 128 wide, dq and dk 192."""
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    nq, nkv = heads
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, nq, s_q, 192)).astype(dtype)
    k = jax.random.normal(ks[1], (1, nkv, s_k, 192)).astype(dtype)
    v = jax.random.normal(ks[2], (1, nkv, s_k, 128)).astype(dtype)
    g = jax.random.normal(ks[3], (1, nq, s_q, 128))

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(q, k, v, causal=True,
                                           scale=192 ** -0.5, block_q=128,
                                           block_k=128))

    def xla(q, k, v):
        return jnp.sum(g * _reference_attention(q, k, v, None, True,
                                                192 ** -0.5))

    with jax.default_matmul_precision("highest"):
        out = flash_attention(q, k, v, causal=True, scale=192 ** -0.5,
                              block_q=128, block_k=128)
        got = jax.grad(kernel, (0, 1, 2))(q, k, v)
        f32 = [t.astype(jnp.float32) for t in (q, k, v)]
        want_out = _reference_attention(*f32, None, True, 192 ** -0.5)
        want = jax.grad(xla, (0, 1, 2))(*f32)
    assert out.shape == (1, nq, s_q, 128) and out.dtype == q.dtype
    assert [t.shape[-1] for t in got] == [192, 192, 128]
    assert [t.dtype for t in got] == [q.dtype] * 3
    tol = 2e-5 if dtype == "float32" else 2e-2
    _close(out, want_out, tol)
    for a, b in zip(got, want):
        _close(a, b, tol)


def test_validate_checks_both_widths():
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    S = jax.ShapeDtypeStruct
    q, k = S((1, 2, 128, 192), jnp.float32), S((1, 2, 128, 192), jnp.float32)

    def run(q, k, v):
        return jax.eval_shape(lambda *a: flash_attention(*a, causal=True),
                              q, k, v)

    assert run(q, k, S((1, 2, 128, 128), jnp.float32)).shape == (1, 2, 128, 128)
    with pytest.raises(ValueError, match="contract over one width"):
        run(q, S((1, 2, 128, 128), jnp.float32), S((1, 2, 128, 128),
                                                    jnp.float32))
    with pytest.raises(ValueError, match="last dim at most"):
        run(q, k, S((1, 2, 256, 128), jnp.float32))
    # a value width the kernels do not take falls back like a key width
    with pytest.raises(NotImplementedError, match="192 .q, k. and 320 .v."):
        run(q, k, S((1, 2, 128, 320), jnp.float32))
    with pytest.raises(NotImplementedError, match="320 .q, k."):
        run(S((1, 2, 128, 320), jnp.float32), S((1, 2, 128, 320), jnp.float32),
            S((1, 2, 128, 128), jnp.float32))


def test_the_dispatcher_sends_a_16k_row_of_192_wide_heads_to_the_kernels(
        monkeypatch):
    """By the size of the logits: 32 heads x 16 384^2 in bfloat16 are 17 GB."""
    from analytics_zoo_tpu.ops import attention

    class Tpu:
        platform = "tpu"

    monkeypatch.setattr(jax, "devices", lambda *a: [Tpu()])
    for var in ("AZOO_FLASH_BYTES_THRESHOLD", "AZOO_FLASH_BLOCK_Q",
                "AZOO_FLASH_BLOCK_K"):
        monkeypatch.delenv(var, raising=False)
    q = jax.ShapeDtypeStruct((1, 32, 16384, 192), jnp.bfloat16)
    assert attention._auto_use_flash(q, q)


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
        # the planted fault of the benchmark's probe is another model
        off = jax.jit(lambda w_: ref.logits(
            w_, x, dict(CFG, rope_interleave=False)))(w)
    assert np.abs(np.asarray(off) - np.asarray(got)).max() > 1e-3


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


def test_gradients_of_one_step_leaf_by_leaf():
    from analytics_zoo_tpu.keras import objectives
    from benchmark import models_kanana2

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()

    def loss(p):
        z, _ = model.apply(p, state, x, training=True)
        return objectives.token_crossentropy_from_logits(y, z)

    with jax.default_matmul_precision("highest"):
        got = models_kanana2._from_program(jax.jit(jax.grad(loss))(params),
                                           model)
        want = jax.jit(jax.grad(
            lambda w_: jnp.mean(ref.row_losses(w_, x, y, CFG))))(w)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        assert float(jnp.max(jnp.abs(b))) > 0
        _close(a, b, 1e-4)


def test_the_weight_maps_are_each_others_inverse():
    from benchmark import models_kanana2

    model = _model()
    w, params = _weights(model)
    back = models_kanana2._from_program(params, model)
    for a, b in zip(jax.tree_util.tree_leaves(w),
                    jax.tree_util.tree_leaves(back)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    attn = params[model.blocks[0].name]["attn"]
    # a head's columns side by side, as the published model keeps them
    np.testing.assert_array_equal(
        np.asarray(attn["w_q"][:, 24:40]),
        np.asarray(w["layers"][0]["wq_nope"][:, 16:32]))
    np.testing.assert_array_equal(
        np.asarray(attn["w_kv_b"][:, 48:64]),
        np.asarray(w["layers"][0]["wv"][:, 16:32]))


def test_three_estimator_steps_with_adam_and_the_bias_update():
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.common.observability import get_registry
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import fit_lm, models_kanana2

    def latent_pairs():
        fam = get_registry()._families.get("zoo_lm_latent_token_layers_total")
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
    losses, before = [], latent_pairs()

    class Tape:
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                losses.append(value)

    est.train_summary = Tape()
    with jax.default_matmul_precision("highest"):
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(1))
        first = models_kanana2._from_program(
            fit_lm.first_gradient(est.tstate.opt_state, 0.9), model, np)
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=1,
                  end_trigger=MaxIteration(3))
        batches = [(jnp.asarray(x), jnp.asarray(y))] * 3
        want = fit_lm.follow(ref, CFG, w, batches,
                             optim.Adam(lr=1e-3), row_block=1)
    _close(losses, want["losses"], 1e-5)
    # the gradient the optimizer got at step 1, and the change after step 3
    for a, b in zip(jax.tree_util.tree_leaves(first),
                    jax.tree_util.tree_leaves(want["first"])):
        _close(a, b, 1e-4)
    end = models_kanana2._from_program(jax.device_get(est.tstate.params),
                                       model, np)
    change = jax.tree_util.tree_map(np.subtract, end, w)
    for a, b in zip(jax.tree_util.tree_leaves(change),
                    jax.tree_util.tree_leaves(want["change"])):
        _close(a, b, 2e-3)
    # the bias moved, outside the gradient, as the reference's did
    bias = np.stack([np.asarray(est.tstate.model_state[b.name]["select_bias"])
                     for b in model.blocks if b.has_state])
    assert np.abs(bias).max() > 0
    np.testing.assert_allclose(bias, want["bias"], atol=1e-7)
    # tokens x latent layers of the three steps reached the counter at the
    # drain; the convolutions' counter saw none
    assert latent_pairs() - before == 3 * 32 * 3
    assert model.latent_layers == 3 and model.conv_layers == 0


# -- the model's layers by configuration ---------------------------------------

def test_a_deepseek_v3_file_builds_latent_layers_under_its_own_key_names():
    model = _model()
    assert [type(b.mixer).__name__ for b in model.blocks] == [
        "LatentAttention"] * 3
    assert [type(b.mlp).__name__ for b in model.blocks] == [
        "SwiGLU", "SparseMoE", "SparseMoE"]
    attn = model.blocks[1].attn
    assert (attn.n_head, attn.qk_nope_dim, attn.qk_rope_dim, attn.v_dim,
            attn.kv_rank, attn.rope_theta, attn.epsilon) == (
                4, 16, 8, 16, 32, 1000000, 1e-6)
    assert all(b.norms == "pre" for b in model.blocks)
    assert model.embed_scale == 1.0 and model.head is not None
    moe = model.blocks[1].mlp
    assert (moe.n_shared, moe.route_eps, moe.top_k, moe.experts_held,
            moe.n_experts, moe.route_scale, moe.route_norm, moe.bias_rate,
            moe.width) == (2, 1e-20, 2, (2, 4), 8, 2.448, True, 0.001, 32)
    assert model.blocks[0].mlp.width == 128
    # with no router width stated every expert is held
    whole = {k: v for k, v in CFG.items()
             if k not in ("router_num_experts", "experts_held_offset")}
    assert _model(whole).blocks[1].mlp.experts_held == (0, 4)


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("n_group", 8), ("topk_group", 4),
    ("rope_scaling", {"type": "yarn", "factor": 40}),
    ("rope_interleave", False), ("scoring_func", "softmax")])
def test_what_is_not_built_is_refused_by_key(key, value):
    with pytest.raises(NotImplementedError, match=f"deepseek_v3 with {key}="):
        _model(**{key: value})


def test_from_config_names_the_families_it_knows():
    with pytest.raises(ValueError, match="known.*afmoe.*deepseek_v3.*lfm2_moe"):
        _model(model_type="mamba2")


# -- the benchmark's configuration through the program's own count ---------------

def test_the_benchmarks_configuration_builds_with_687_5_m_parameters():
    """Shapes only (`eval_shape`): nothing of that size is made here."""
    from analytics_zoo_tpu.models.causal_lm import CausalLM

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "kanana-2-30b-a3b.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg.update(cfg["assumed"])
    model = CausalLM.from_config(cfg, seq_len=cfg["seq_len"],
                                 dtype=cfg["compute_dtype"])
    params, _ = jax.eval_shape(model.init, jax.random.PRNGKey(0))

    def count(tree):
        return sum(int(np.prod(a.shape))
                   for a in jax.tree_util.tree_leaves(tree))

    by_layer = {k: count(v) for k, v in params.items()}
    # attention: w_q 2048 x 6144, w_kv_a 2048 x 576, the latent's gain 512,
    # w_kv_b 512 x 8192, w_out 4096 x 2048
    assert [count(params[b.name]["attn"]) for b in model.blocks] == [
        26_345_984] * 6
    # the dense layer (attention, SwiGLU 3 x 2048 x 6144, two gains); an
    # expert layer (attention, router 262 144, shared 9 437 184, 16 experts
    # x 4 718 592, two gains); embedding + head + the last norm
    assert [by_layer[b.name] for b in model.blocks] == [
        64_098_816] + [111_546_880] * 5
    assert (by_layer[model.embed.name] + by_layer[model.head.name]
            + by_layer[model.final_norm.name]) == 65_669_120
    assert sum(by_layer.values()) == 687_502_336
    assert model.latent_layers == 6 and model.experts_held == (0, 16)
    mlp = params[model.blocks[1].name]["mlp"]
    assert mlp["router"].shape == (2048, 128)
    assert mlp["shared_w_gate_up"].shape == (2048, 2 * 2 * 768)
    assert mlp["experts_w_gate_up"].shape == (16, 2048, 2 * 768)
