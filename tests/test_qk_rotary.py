"""The fused per-head norm and rotary of queries and keys
(``ops/qk_rotary.py``) against the two functions it fuses,
``rotary_embedding(rms_norm(x, gain, eps), theta)``.

The Pallas kernels run in interpret mode on the CPU mesh (the module picks
interpret off the chip, as the flash kernels' does); ``_on_chip`` is patched
where a test needs the dispatcher to take the kernel path here."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common.observability import qk_rotary_built
from analytics_zoo_tpu.keras.layers.decoder import (GroupedQueryAttention,
                                                     rms_norm,
                                                     rotary_embedding)
from analytics_zoo_tpu.ops import qk_rotary

EPS = 1e-6
THETA = 10000.0
# Values: within a last bit of bf16. The fused op rounds once where the two
# functions round the norm too, and a rotated entry sums two of those: off
# by up to a bit of the largest entry where the sum cancels (``atol``, times
# the largest), a bit of its own elsewhere (``rtol``). Gradients: the largest
# difference over the largest entry, two bf16 ulps in bf16, float32
# rounding in float32.
VALUE_TOL = {jnp.bfloat16: 2.0 ** -7, jnp.float32: 2e-6}
GRAD_TOL = {jnp.bfloat16: 8e-3, jnp.float32: 2e-5}


def _inputs(shape, dtype, seed=0):
    b, s, n, d = shape
    kx, kg, kd = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = (2.0 * jax.random.normal(kx, shape)).astype(dtype)
    gain = (1.0 + 0.1 * jax.random.normal(kg, (d,))).astype(dtype)
    dy = jax.random.normal(kd, (b, n, s, d)).astype(dtype)
    return x, gain, dy


def _composed(x, gain, eps, theta):
    y = rms_norm(x.transpose(0, 2, 1, 3), gain, eps)
    return y if theta is None else rotary_embedding(y, theta)


def _value_and_vjp(op, x, gain, dy, theta):
    y, vjp = jax.vjp(lambda x, g: op(x, g, EPS, theta), x, gain)
    return (y,) + vjp(dy)


def _built():
    fam = qk_rotary_built()
    return {p: fam.labels(path=p).value for p in ("kernel", "xla")}


def _rel(a, b):
    a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("heads", [4, 8])
@pytest.mark.parametrize("theta", [None, THETA], ids=["norm", "rotary"])
@pytest.mark.parametrize("width", [64, 128])
def test_the_kernels_against_the_two_functions(width, theta, heads, dtype):
    x, gain, dy = _inputs((2, 512, heads, width), dtype)
    y, dx, dgain = jax.jit(
        lambda x, g, dy: _value_and_vjp(qk_rotary.norm_rotary_kernel, x, g,
                                        dy, theta))(x, gain, dy)
    ref_y, ref_dx, ref_dgain = jax.jit(
        lambda x, g, dy: _value_and_vjp(_composed, x, g, dy, theta))(
            x, gain, dy)
    assert y.shape == (2, heads, 512, width) and y.dtype == dtype
    assert dx.shape == x.shape and dx.dtype == dtype
    assert dgain.shape == gain.shape and dgain.dtype == dtype
    ref_y = np.asarray(ref_y, np.float32)
    np.testing.assert_allclose(
        np.asarray(y, np.float32), ref_y, rtol=VALUE_TOL[dtype],
        atol=VALUE_TOL[dtype] * np.max(np.abs(ref_y)))
    assert _rel(dx, ref_dx) < GRAD_TOL[dtype]
    assert _rel(dgain, ref_dgain) < GRAD_TOL[dtype]


@pytest.mark.parametrize("shape", [(1, 384, 4, 128), (1, 512, 4, 96),
                                   (2, 512, 2, 32), (1, 512, 3, 64)],
                         ids=["tokens-off-the-tile", "width-96", "width-32",
                              "odd-heads-at-64"])
def test_what_the_kernels_do_not_take_runs_on_xla(shape, monkeypatch):
    monkeypatch.setattr(qk_rotary, "_on_chip", lambda: True)
    x, gain, dy = _inputs(shape, jnp.float32)
    with pytest.raises(NotImplementedError):
        qk_rotary.norm_rotary_kernel(x, gain, EPS, THETA)
    before = _built()
    got = _value_and_vjp(qk_rotary.norm_rotary, x, gain, dy, THETA)
    assert _built() == {"kernel": before["kernel"], "xla": before["xla"] + 1}
    for a, b in zip(got, _value_and_vjp(_composed, x, gain, dy, THETA)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("on_chip,shape,path", [
    (True, (1, 512, 4, 128), "kernel"),
    (True, (1, 512, 4, 64), "kernel"),
    (True, (1, 256, 4, 128), "xla"),
    (False, (1, 512, 4, 128), "xla"),
])
def test_each_build_is_counted_once_under_its_path(on_chip, shape, path,
                                                   monkeypatch):
    monkeypatch.setattr(qk_rotary, "_on_chip", lambda: on_chip)
    before = _built()
    x, gain, _ = _inputs(shape, jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda x: qk_rotary.norm_rotary(
        x, gain, EPS, THETA).astype(jnp.float32).sum()), x)
    assert _built() == {p: before[p] + (p == path) for p in before}


# Trinity's attention (128 wide, gated, a window) and LFM2's (64 wide, no
# gate, full), at the tests' widths and one token tile
LAYERS = {
    "trinity": dict(n_head=4, n_kv_head=2, head_dim=128, window=128,
                    rope_theta=THETA, gated=True),
    "lfm2": dict(n_head=4, n_kv_head=2, head_dim=64, window=None,
                 rope_theta=1e6, gated=False),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("layer", sorted(LAYERS))
def test_the_attention_layer_with_the_kernels_against_the_two_functions(
        layer, dtype, monkeypatch):
    attn = GroupedQueryAttention(**LAYERS[layer], epsilon=1e-5)
    attn.ensure_built((2, 512, 64))
    params = jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                    attn.init_params(jax.random.PRNGKey(0)))
    params["q_norm"] = params["q_norm"] * 1.25
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 512, 64)).astype(dtype)

    def run(on_chip):
        monkeypatch.setattr(qk_rotary, "_on_chip", lambda: on_chip)

        def loss(p, x):
            y = attn.call(p, x)
            return jnp.sum(y.astype(jnp.float32) ** 2), y
        return jax.jit(jax.value_and_grad(loss, (0, 1), has_aux=True))(
            params, x)

    (_, y), grads = run(True)
    (_, ref_y), ref_grads = run(False)
    np.testing.assert_allclose(np.asarray(y, np.float32),
                               np.asarray(ref_y, np.float32),
                               rtol=GRAD_TOL[dtype], atol=GRAD_TOL[dtype])
    flat, ref_flat = (jax.tree_util.tree_leaves_with_path(g)
                      for g in (grads, ref_grads))
    for (path, g), (_, ref) in zip(flat, ref_flat):
        assert _rel(g, ref) < 2 * GRAD_TOL[dtype], jax.tree_util.keystr(path)
