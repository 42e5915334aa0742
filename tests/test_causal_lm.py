"""The decoder block library, the sigmoid top-k expert layer and `CausalLM`
against the plain float32 reference (benchmark/reference/trinity.py), at tiny
widths on the CPU with seeded weights."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import optim, trinity as ref
from conftest import inner_jaxprs

CFG = {
    "hidden_size": 64, "head_dim": 16, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 128,
    "moe_intermediate_size": 32,
    "layer_types": ["sliding_attention", "sliding_attention", "full_attention"],
    "num_hidden_layers": 3, "num_dense_layers": 1, "num_experts": 4,
    "router_num_experts": 8, "experts_held_offset": 2,
    "num_experts_per_tok": 2, "num_shared_experts": 1, "sliding_window": 8,
    "rope_theta": 10000, "rms_norm_eps": 1e-5, "mup_enabled": True,
    "route_norm": True, "route_scale": 2.826, "load_balance_coeff": 0.001,
    "vocab_size": 96, "initializer_range": 0.05, "seq_len": 32,
    "compute_dtype": None,
}


def _model(cfg=CFG, **kw):
    from benchmark import models_lm

    return models_lm._build(dict(cfg, **kw))


def _weights(model, cfg=CFG, seed=0):
    """(reference tree, program tree) of the same numbers."""
    from benchmark import models_lm

    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))
    # gains off 1, so that a norm left out or misplaced shows
    keys = iter(jax.random.split(jax.random.PRNGKey(seed + 1), 64))
    w = jax.tree_util.tree_map(
        lambda a: a + 0.1 * jax.random.normal(next(keys), a.shape)
        if a.ndim == 1 else a, w)
    return w, models_lm._to_program(w, model)


def _rows(cfg=CFG, n=2, seed=3):
    t = np.random.default_rng(seed).integers(
        0, cfg["vocab_size"], (n, cfg["seq_len"] + 1)).astype(np.int32)
    return t[:, :-1], t[:, 1:]


def _counter(text, name):
    return sum(float(line.split()[-1]) for line in text.splitlines()
               if line.startswith(name + " ") or line.startswith(name + "{"))


def _close(a, b, tol):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    assert np.max(np.abs(a - b)) <= tol * max(1.0, np.max(np.abs(b))), (
        np.max(np.abs(a - b)), np.max(np.abs(b)))


def test_logits_and_loss_of_the_whole_model():
    from analytics_zoo_tpu.keras import objectives

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()
    with jax.default_matmul_precision("highest"):
        got, _ = model.apply(params, state, x, training=False)
        want = ref.logits(w, x, CFG)
        _close(got, want, 2e-5)
        loss = objectives.get("token_crossentropy_from_logits")(y, got)
        rows = objectives.get_per_sample(
            objectives.token_crossentropy_from_logits)(y, got)
        want_rows = ref.row_losses(w, x, y, CFG)
    _close(rows, want_rows, 2e-5)
    _close(loss, jnp.mean(want_rows), 2e-5)


def test_gradients_of_one_step():
    from analytics_zoo_tpu.keras import objectives
    from benchmark import models_lm

    model = _model()
    w, params = _weights(model)
    _, state = model.init(jax.random.PRNGKey(0))
    x, y = _rows()

    def loss(p):
        z, _ = model.apply(p, state, x, training=True)
        return objectives.token_crossentropy_from_logits(y, z)

    with jax.default_matmul_precision("highest"):
        got = models_lm._from_program(jax.grad(loss)(params), model)
        want = jax.grad(lambda w_: jnp.mean(ref.row_losses(w_, x, y, CFG)))(w)
    for a, b in zip(jax.tree_util.tree_leaves(got),
                    jax.tree_util.tree_leaves(want)):
        _close(a, b, 1e-4)


def test_three_estimator_steps_with_adam_and_the_bias_update():
    from analytics_zoo_tpu.common import nncontext
    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.triggers import MaxIteration
    from analytics_zoo_tpu.keras.optimizers import Adam
    from benchmark import fit_lm, models_lm

    nncontext.init_nncontext(mesh_shape=(1, 8))   # no data axis to round to
    model = _model()
    w, params = _weights(model)
    w = jax.device_get(w)        # the step donates what set_weights placed
    est = model._get_estimator()
    model.set_weights(params)
    model.compile(optimizer=Adam(lr=1e-3),
                  loss="token_crossentropy_from_logits")
    x, y = _rows(n=2)
    losses = []

    class Tape:
        def add_scalar(self, tag, value, step):
            if tag == "Loss":
                losses.append(value)

    est.train_summary = Tape()
    with jax.default_matmul_precision("highest"):
        est.train(ArrayFeatureSet(x, y), model.criterion, batch_size=2,
                  end_trigger=MaxIteration(3))
        batches = [(jnp.asarray(x), jnp.asarray(y))] * 3
        want = fit_lm.follow(ref, CFG, w, batches,
                             optim.Adam(lr=1e-3), row_block=1)
    _close(losses, want["losses"], 1e-5)
    end = models_lm._from_program(jax.device_get(est.tstate.params), model, np)
    change = jax.tree_util.tree_map(np.subtract, end, w)
    for a, b in zip(jax.tree_util.tree_leaves(change),
                    jax.tree_util.tree_leaves(want["change"])):
        _close(a, b, 2e-3)
    # the bias moved, outside the gradient, as the reference's did
    bias = np.stack([np.asarray(est.tstate.model_state[b.name]["select_bias"])
                     for b in model.blocks if b.has_state])
    assert np.abs(bias).max() > 0
    np.testing.assert_allclose(bias, want["bias"], atol=1e-7)
    # and the step's statistics reached the counters at the drain
    from analytics_zoo_tpu.common.observability import get_registry

    text = get_registry().render()
    assert "zoo_train_tokens_total" in text
    assert 'zoo_moe_assignments_total{held="true"}' in text
    # two expert layers a step, three steps; half the experts held, so no
    # compacted pass exists to take
    assert _counter(text, "zoo_moe_calls_total") >= 6
    assert "zoo_moe_calls_compact_total" in text


@pytest.mark.parametrize("window", [None, 8, 13, 200])
@pytest.mark.parametrize("heads", [(4, 2), (4, 1), (2, 2)])
def test_window_and_grouped_heads_on_the_xla_path(window, heads):
    from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention

    nq, nkv = heads
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    q = jax.random.normal(ks[0], (2, nq, 32, 16))
    k = jax.random.normal(ks[1], (2, nkv, 32, 16))
    v = jax.random.normal(ks[2], (2, nkv, 32, 16))
    with jax.default_matmul_precision("highest"):
        got = scaled_dot_product_attention(q, k, v, causal=True, window=window,
                                           use_flash=False)
        want = ref.attention(q, k, v, window, block=8)
    _close(got, want, 1e-5)


@pytest.mark.parametrize("window", [None, 200, 130, 1])
@pytest.mark.parametrize("heads,blocks", [((4, 2), (128, 128)),
                                          ((4, 1), (128, 256)),
                                          ((2, 2), (256, 128))])
def test_window_and_grouped_heads_in_the_flash_kernels(window, heads, blocks):
    """Forward, dq and dkv kernels in interpret mode against the XLA path; a
    window that is no multiple of a block, one of a single key, none."""
    from analytics_zoo_tpu.ops.attention import _reference_attention
    from analytics_zoo_tpu.ops.flash_attention import flash_attention

    nq, nkv = heads
    ks = jax.random.split(jax.random.PRNGKey(2), 4)
    q = jax.random.normal(ks[0], (1, nq, 512, 32))
    k = jax.random.normal(ks[1], (1, nkv, 512, 32))
    v = jax.random.normal(ks[2], (1, nkv, 512, 32))
    g = jax.random.normal(ks[3], (1, nq, 512, 32))

    def kernel(q, k, v):
        return jnp.sum(g * flash_attention(q, k, v, causal=True, window=window,
                                           block_q=blocks[0], block_k=blocks[1]))

    def xla(q, k, v):
        return jnp.sum(g * _reference_attention(q, k, v, None, True,
                                                32 ** -0.5, window=window))

    with jax.default_matmul_precision("highest"):
        got = jax.value_and_grad(kernel, (0, 1, 2))(q, k, v)
        want = jax.value_and_grad(xla, (0, 1, 2))(q, k, v)
    _close(got[0], want[0], 1e-5)
    for a, b in zip(got[1], want[1]):
        _close(a, b, 2e-5)


def _kernel_calls(jaxpr, counts=None):
    """Every `pallas_call` of a jaxpr by its kernel's name, nested ones too."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            name = eqn.params["name"]
            counts[name] = counts.get(name, 0) + 1
        for inner in inner_jaxprs(eqn):
            _kernel_calls(inner, counts)
    return counts


# What the attention half's checkpoint keeps: the kernel's two named
# residuals (the block as it is), nothing (a bare `jax.checkpoint`, the block
# before PR 34), or there is no checkpoint.
_BLOCKS = {"kept": 1, "bare": 2, "none": 1}


def _block_grads(kind, window, monkeypatch, count=False):
    """Gradients of one `DecoderBlock` on the flash path (interpret mode) to
    its input and every parameter; or its kernels' calls by name."""
    from analytics_zoo_tpu.keras.layers import (DecoderBlock,
                                                GroupedQueryAttention, SwiGLU)
    from analytics_zoo_tpu.ops import attention

    monkeypatch.setattr(attention, "_auto_use_flash", lambda q, k: True)
    if kind == "bare":
        monkeypatch.setattr(jax.checkpoint_policies, "save_only_these_names",
                            lambda *names: None)
    block = DecoderBlock(
        GroupedQueryAttention(4, 2, 16, window=window,
                              rope_theta=None if window is None else 1e4),
        SwiGLU(128), remat=kind != "none")
    block.ensure_built((None, 256, 64))
    p = block.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 256, 64))
    g = jax.random.normal(jax.random.PRNGKey(2), (2, 256, 64))

    def loss(p, x):
        return jnp.sum(g * block.call(p, x).astype(jnp.float32))

    grad = jax.grad(loss, (0, 1))
    if count:
        return _kernel_calls(jax.make_jaxpr(grad)(p, x).jaxpr)
    # operation by operation: what one whole XLA:CPU program rounds where
    # depends on how it fused bfloat16 operations, not on what was computed
    return jax.tree_util.tree_leaves(grad(p, x))


@pytest.mark.parametrize("kind,forwards", list(_BLOCKS.items()))
def test_a_block_runs_the_flash_forward_once(kind, forwards, monkeypatch):
    """The checkpoint of the attention half keeps the kernel's output and
    log-sum-exp, so the backward pass holds no second forward kernel; a bare
    checkpoint holds two."""
    assert _block_grads(kind, 128, monkeypatch, count=True) == {
        "zoo_flash_fwd": forwards, "zoo_flash_dkv": 1}


@pytest.mark.parametrize("window", [128, None])
@pytest.mark.parametrize("kind", ["bare", "none"])
def test_a_block_that_keeps_the_residuals_has_the_same_gradients(
        kind, window, monkeypatch):
    """Bit for bit: the backward kernels read the first pass's output and
    log-sum-exp, which are the bits the second pass produced."""
    got = _block_grads("kept", window, monkeypatch)
    want = _block_grads(kind, window, monkeypatch)
    assert len(got) == len(want) == 11
    for a, b in zip(got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_a_window_needs_a_causal_mask():
    from analytics_zoo_tpu.ops.attention import scaled_dot_product_attention

    q = jnp.zeros((1, 2, 8, 4))
    with pytest.raises(ValueError, match="causal"):
        scaled_dot_product_attention(q, q, q, window=4)
    with pytest.raises(ValueError, match="evenly"):
        scaled_dot_product_attention(jnp.zeros((1, 3, 8, 4)), q, q, causal=True)


def test_full_layers_carry_no_position_and_sliding_layers_do():
    """Tokens moved together with their causal order kept change a sliding
    layer's output (rotary positions) and not a full layer's: here, the same
    row shifted right behind one more leading token, read at the shifted
    places, against the row as it was; the window is wider than the row so
    that only the positions differ."""
    from analytics_zoo_tpu.keras.layers import GroupedQueryAttention

    def run(rope):
        layer = GroupedQueryAttention(4, 2, 16, window=None, rope_theta=rope)
        layer.ensure_built((None, 12, 64))
        p = layer.init_params(jax.random.PRNGKey(0))
        x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64))
        first = layer.call(p, x)[:, 0]
        # row 0 attends to itself alone wherever it stands: put the same
        # token at place 0 and, alone in its window, at place 5
        alone = GroupedQueryAttention(4, 2, 16, window=1, rope_theta=rope)
        alone.ensure_built((None, 12, 64))
        moved = jnp.roll(x, 5, axis=1)
        return first, alone.call(p, moved)[:, 5], layer, p, x

    first, moved, *_ = run(None)
    _close(first, moved, 1e-5)            # no position anywhere
    # with rotary positions a lone token still reads the same (the rotation
    # of q and k cancels in q.k, and v is not rotated) ...
    first, moved, layer, p, x = run(10000.0)
    _close(first, moved, 1e-5)
    # ... but two tokens at another distance do not
    near = layer.call(p, x[:, :2])[:, 1]
    far = layer.call(p, jnp.concatenate(
        [x[:, :1], jnp.zeros((1, 3, 64)), x[:, 1:2]], axis=1))
    assert np.max(np.abs(np.asarray(near) - np.asarray(far[:, 4]))) > 1e-3

    # the model tells the two kinds apart by `layer_types`
    model = _model()
    kinds = [(b.attn.window, b.attn.rope_theta) for b in model.blocks]
    assert kinds == [(8, 10000), (8, 10000), (None, None)]


@pytest.fixture
def chunks_of_64(monkeypatch):
    """128 tokens go through the experts held in two chunks."""
    from analytics_zoo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_CHUNK_TOKENS", 64)


def test_a_router_forced_onto_one_held_expert_drops_nothing(chunks_of_64):
    from analytics_zoo_tpu.keras.layers import SparseMoE

    layer = SparseMoE(8, 32, top_k=2, experts_held=(2, 4), n_shared=0,
                      route_norm=False)
    layer.ensure_built((None, 64))
    p = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (128, 64))
    # a bias that sends every token to held expert 3 and absent expert 7
    state = {"select_bias": jnp.zeros((8,)).at[3].set(10.0).at[7].set(9.0),
             "expert_tokens": jnp.zeros((8,))}
    with jax.default_matmul_precision("highest"):
        y, new = layer.call(p, x, state=state, training=True)
        s = jax.nn.sigmoid(x @ p["router"])[:, 3]
        gate, up = jnp.split(x @ p["experts_w_gate_up"][1], 2, axis=-1)
        want = s[:, None] * ((jax.nn.silu(gate) * up) @ p["experts_w_down"][1])
    np.testing.assert_array_equal(
        np.asarray(new["expert_tokens"]), [0, 0, 0, 128, 0, 0, 0, 128])
    _close(y, want, 1e-5)                 # all 128 tokens, none dropped
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 0


def _share_of_sixteen(seed=0, tokens=1024):
    """Two of sixteen experts held, top-2, 1024 tokens: 2048 assignments, a
    compacted buffer of 512 rows (twice the 256 a uniform router sends)."""
    from analytics_zoo_tpu.keras.layers import SparseMoE

    cfg = dict(CFG, num_experts=2, router_num_experts=16,
               experts_held_offset=6)
    w = ref.init_weights(cfg, jax.random.PRNGKey(seed))["layers"][1]
    layer = SparseMoE(16, 32, top_k=2, experts_held=(6, 2), n_shared=0,
                      route_scale=2.826)
    layer.ensure_built((None, 64))
    p = {"router": 4.0 * w["router"],       # scores spread enough to pick by
         "experts_w_gate_up": jnp.concatenate(
             [w["experts"]["w_gate"], w["experts"]["w_up"]], axis=-1),
         "experts_w_down": w["experts"]["w_down"]}
    m = jax.random.normal(jax.random.PRNGKey(seed + 1), (tokens, 64))
    g = jax.random.normal(jax.random.PRNGKey(seed + 2), (tokens, 64))
    return cfg, w, layer, p, m, g


def _value_and_grads(layer, p, m, g, bias):
    """y, the step's state, and the gradients of sum(y * g) to x, the router
    and both expert tensors."""
    state = {"select_bias": bias, "expert_tokens": jnp.zeros_like(bias)}

    def loss(p_, m_):
        y, st = layer.call(p_, m_, state=state, training=True)
        return jnp.sum(y * g), (y, st)

    (_, (y, st)), grads = jax.value_and_grad(loss, (0, 1), has_aux=True)(p, m)
    return y, st, (grads[1], grads[0]["router"],
                   grads[0]["experts_w_gate_up"], grads[0]["experts_w_down"])


def _chunks_only(monkeypatch):
    """The parent's expert layer: every call through the chunks."""
    from analytics_zoo_tpu.parallel import moe

    monkeypatch.setattr(moe, "_held_capacity", lambda t, k, *_: t * k)


def _reference_value_and_grads(cfg, w, p, m, g, bias):
    """The same from benchmark/reference/trinity.py's ``expert_layer`` less
    its shared expert, in the program's layout."""
    def loss(w_, m_):
        y, _ = ref.expert_layer(w_, m_, bias, cfg)
        return jnp.sum((y - ref._swiglu(w_["shared"], m_, jnp.matmul)) * g), y

    w = dict(w, router=p["router"])
    (_, y), (dw, dm) = jax.value_and_grad(loss, (0, 1), has_aux=True)(w, m)
    y = y - ref._swiglu(w["shared"], m, jnp.matmul)
    return y, (dm, dw["router"], jnp.concatenate(
        [dw["experts"]["w_gate"], dw["experts"]["w_up"]], axis=-1),
        dw["experts"]["w_down"])


NAMES = ("x", "router", "experts_w_gate_up", "experts_w_down")


@pytest.mark.parametrize("against", ["chunks", "reference"])
def test_the_compacted_pass_is_the_expert_layer(against, chunks_of_64,
                                                monkeypatch):
    """(a) random routing, 2 of 16 held: the one pass over 512 rows gives the
    chunks' output and gradients, and the reference's."""
    cfg, w, layer, p, m, g = _share_of_sixteen()
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (16,))
    with jax.default_matmul_precision("highest"):
        y, st, grads = _value_and_grads(layer, p, m, g, bias)
        assert float(st["compact"]) == 1.0
        held = float(jnp.sum(st["expert_tokens"][6:8]))
        assert 128 < held <= 512            # a real share, inside the buffer
        if against == "chunks":
            _chunks_only(monkeypatch)
            want_y, st2, want = _value_and_grads(layer, p, m, g, bias)
            assert float(st2["compact"]) == 0.0
        else:
            want_y, want = _reference_value_and_grads(cfg, w, p, m, g, bias)
    _close(y, want_y, 1e-5)
    for name, a, b in zip(NAMES, grads, want):
        assert float(jnp.max(jnp.abs(b))) > 0, name
        _close(a, b, 1e-4)


def test_more_held_rows_than_the_buffer_go_through_the_chunks(chunks_of_64,
                                                              monkeypatch):
    """(b) a bias that sends both picks of every token to the two held
    experts: 2048 held rows for a buffer of 512, so the chunks run; every
    token comes back, and the step's statistic says the pass was not taken."""
    cfg, w, layer, p, m, g = _share_of_sixteen()
    bias = jnp.zeros((16,)).at[6].set(10.0).at[7].set(9.0)
    with jax.default_matmul_precision("highest"):
        y, st, grads = _value_and_grads(layer, p, m, g, bias)
        want_y, want = _reference_value_and_grads(cfg, w, p, m, g, bias)
        # and it is the parent's computation, to the last bit
        _chunks_only(monkeypatch)
        same_y, _, same = _value_and_grads(layer, p, m, g, bias)
    assert float(st["compact"]) == 0.0
    np.testing.assert_array_equal(
        np.asarray(st["expert_tokens"]), [0] * 6 + [1024, 1024] + [0] * 8)
    assert float(jnp.min(jnp.max(jnp.abs(y), axis=-1))) > 0   # none dropped
    _close(y, want_y, 1e-5)
    for a, b, c in zip(grads, want, same):
        _close(a, b, 1e-4)
        np.testing.assert_array_equal(np.asarray(a), np.asarray(c))
    np.testing.assert_array_equal(np.asarray(y), np.asarray(same_y))


def test_rows_past_the_held_total_are_never_read(chunks_of_64, monkeypatch):
    """(c) on the chip the grouped product leaves the rows between the held
    total and the buffer's end as the memory was; the interpreter leaves
    zeros. Poisoned with NaN, forward and backward: the same output and
    gradients, all finite."""
    from analytics_zoo_tpu.parallel import moe

    cfg, w, layer, p, m, g = _share_of_sixteen(seed=3)
    bias = jnp.zeros((16,))
    with jax.default_matmul_precision("highest"):
        want_y, _, want = _value_and_grads(layer, p, m, g, bias)
        real, poisoned = moe._held_gmm, []

        def gmm(lhs, rhs, sizes, transpose_rhs=False):
            out = real(lhs, rhs, sizes, transpose_rhs)
            live = jnp.arange(out.shape[0])[:, None] < jnp.sum(sizes)
            poisoned.append(out.shape)
            return jnp.where(live, out, jnp.nan)

        monkeypatch.setattr(moe, "_held_gmm", gmm)
        y, st, grads = _value_and_grads(layer, p, m, g, bias)
    assert float(st["compact"]) == 1.0
    # two forward, two backward (and the forward's shapes traced once more)
    assert len(poisoned) >= 4 and {s[0] for s in poisoned} == {512}
    for a, b in zip((y, *grads), (want_y, *want)):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def _has_cond(jaxpr) -> bool:
    """A `cond` among the layer's own operations (a kernel's body aside)."""
    return any(e.primitive.name == "cond"
               or any(map(_has_cond, inner_jaxprs(e))) for e in jaxpr.eqns)


@pytest.mark.parametrize("held,n_experts,k,tokens,compacted", [
    ((2, 4), 8, 2, 128, False),        # half of the experts: the cases above
    ((0, 8), 8, 2, 1024, False),       # all of them
    ((1, 2), 4, 2, 4096, False),       # the serialization sweep's share
    ((6, 2), 16, 2, 1024, True),       # an eighth
    ((0, 16), 128, 8, 16384, True),    # the benchmark's cell
])
def test_a_compacted_pass_exists_only_where_it_is_smaller(held, n_experts, k,
                                                          tokens, compacted):
    """(d) the buffer is twice the held share of the assignments, a multiple
    of the row tile; where that is all of them no second path is built."""
    from analytics_zoo_tpu.parallel import moe

    rows = moe._held_capacity(tokens, k, held[1], n_experts)
    assert rows % 512 == 0 and rows >= 2 * tokens * k * held[1] / n_experts
    assert rows - 512 < 2 * tokens * k * held[1] / n_experts
    assert (rows < tokens * k) == compacted
    S = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(lambda *a: moe.held_experts_ffn(
        *a, n_experts, held[0]))(
            S((tokens, 8), jnp.float32), S((tokens, k), jnp.int32),
            S((tokens, k), jnp.float32), S((held[1], 8, 16), jnp.float32),
            S((held[1], 8, 8), jnp.float32))
    assert _has_cond(jaxpr.jaxpr) == compacted
    if compacted and tokens == 16384:
        assert rows == 32768


# the three families' expert layers: the reference, its configuration at the
# toy widths, the key that counts the experts held, the layer's own
# arguments, and whether shared experts stand beside the routed ones
_LFM2_MOE = dict(CFG, norm_topk_prob=True, routed_scaling_factor=1)
_DEEPSEEK_V3 = dict(_LFM2_MOE, routed_scaling_factor=2.448, n_shared_experts=2,
                    num_hidden_layers=3, first_k_dense_replace=1,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    kv_lora_rank=32)
_EXPERT_LAYERS = {
    "afmoe": ("benchmark.reference.trinity", CFG, "num_experts",
              dict(route_scale=2.826), True),
    "lfm2_moe": ("benchmark.reference.lfm2", _LFM2_MOE, "num_experts",
                 dict(route_eps=1e-6), False),
    "deepseek_v3": ("benchmark.reference.kanana2", _DEEPSEEK_V3,
                    "n_routed_experts", dict(route_scale=2.448), True),
}


@pytest.mark.parametrize("family", sorted(_EXPERT_LAYERS))
def test_the_eight_shares_add_up_to_the_uncut_expert_layer(family):
    """The routed parts of all shares, plus the shared experts (where the
    family has them; two of them are one SwiGLU twice as wide) counted once,
    equal the uncut reference's expert layer."""
    import importlib

    from analytics_zoo_tpu.keras.layers import SparseMoE

    module, cfg, held_key, layer_args, has_shared = _EXPERT_LAYERS[family]
    ref_ = importlib.import_module(module)
    whole = dict(cfg, **{held_key: 8}, experts_held_offset=0)
    w = ref_.init_weights(whole, jax.random.PRNGKey(5))["layers"][1]
    m = jax.random.normal(jax.random.PRNGKey(6), (64, 64))
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(7), (8,))

    def shared():      # what every chip computes alike
        return ref_._swiglu(w["shared"], m, jnp.matmul) if has_shared else 0.0

    with jax.default_matmul_precision("highest"):
        want, counts = ref_.expert_layer(w, m, bias, whole)
        total = 0.0
        for offset in range(0, 8, 2):            # four shares of two
            share = SparseMoE(8, 32, top_k=2, experts_held=(offset, 2),
                              n_shared=0, **layer_args)
            share.ensure_built((None, 64))
            held = {k: v[offset:offset + 2] for k, v in w["experts"].items()}
            p = {"router": w["router"],
                 "experts_w_gate_up": jnp.concatenate(
                     [held["w_gate"], held["w_up"]], axis=-1),
                 "experts_w_down": held["w_down"]}
            y, st = share.call(p, m, state={
                "select_bias": bias, "expert_tokens": jnp.zeros((8,))},
                training=True)
            np.testing.assert_array_equal(np.asarray(st["expert_tokens"]),
                                          np.asarray(counts))
            # the reference, given the same share, computes the same part
            part, _ = ref_.expert_layer(
                dict(w, experts=held), m, bias,
                dict(whole, **{held_key: 2}, experts_held_offset=offset))
            _close(y, part - shared(), 1e-5)
            total = total + y
        total = total + shared()
    _close(total, want, 1e-5)


def test_the_sliced_vocabulary_loss_is_the_reference_over_the_slice():
    whole = dict(CFG, vocab_size=192)
    w = ref.init_weights(whole, jax.random.PRNGKey(8))
    x, y = _rows()                                # ids under 96: the slice
    cut = dict(w, embed=w["embed"][:96], head=w["head"][:, :96])
    with jax.default_matmul_precision("highest"):
        z = ref.logits(w, x, whole)[..., :96]      # the slice's logits
        picked = jnp.take_along_axis(z, y[..., None], axis=-1)[..., 0]
        want = jnp.mean(jax.nn.logsumexp(z, axis=-1) - picked, axis=-1)
        got = ref.row_losses(cut, x, y, CFG)
        model = _model()
        from benchmark import models_lm

        _, state = model.init(jax.random.PRNGKey(0))
        logits, _ = model.apply(models_lm._to_program(cut, model), state, x)
    _close(got, want, 1e-5)
    assert logits.shape == (2, 32, 96)


def test_token_crossentropy_never_upcasts_the_whole_batch():
    """bfloat16 logits in, float32 loss out, a gradient in bfloat16; and the
    estimator hands such a loss the logits as the model made them."""
    from analytics_zoo_tpu.keras import objectives

    fn = objectives.token_crossentropy_from_logits
    assert fn.takes_compute_dtype
    z = jax.random.normal(jax.random.PRNGKey(0), (2, 4096, 32), jnp.bfloat16)
    y = jax.random.randint(jax.random.PRNGKey(1), (2, 4096), 0, 32)
    loss, grad = jax.value_and_grad(lambda t: fn(y, t))(z)
    assert loss.dtype == jnp.float32 and grad.dtype == jnp.bfloat16
    want = jax.value_and_grad(
        lambda t: objectives.sparse_categorical_crossentropy_from_logits(
            y, t.astype(jnp.float32)))(z)
    _close(loss, want[0], 1e-5)
    _close(grad.astype(jnp.float32), want[1], 1e-2)
