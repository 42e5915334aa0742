"""The chunked scan's Pallas kernels (``ops/ssd.py``: ``zoo_ssd_fwd`` /
``zoo_ssd_bwd``) against the ``jax.numpy`` form they replace on the chip and
against the recurrence a token at a time (benchmark/reference/nemotronh.py),
at the Nemotron-H cell's widths (heads of 64, B and C 128 wide, 8 heads a
group, chunks of 128) over a few chunks.

The kernels run in interpret mode on the CPU (the module picks interpret off
the chip, as the flash kernels' does); ``_on_chip`` is patched where a test
needs the dispatcher to take the kernel path here. Tolerances: in float32
both forms sum the same terms in another order, 2e-5 of the largest value on
y, 1e-4 on gradients (as ``tests/test_nemotronh.py`` holds the ``jax.numpy``
form to the recurrence); with bfloat16 inputs the kernels round each
product's operands to bfloat16 as XLA's default precision does on the chip,
so they stay within 1.5e-2 of the float32 recurrence (four bfloat16 ulps).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from analytics_zoo_tpu.common.observability import ssd_built
from analytics_zoo_tpu.ops import ssd
from benchmark.reference import nemotronh as ref

NAMES = "x dt a b c d".split()


def _inputs(bsz, t, h, g, p=64, n=128, seed=0, dtype=jnp.float32):
    k = jax.random.split(jax.random.PRNGKey(seed), 7)
    return (jax.random.normal(k[0], (bsz, t, h, p)).astype(dtype),
            jax.nn.softplus(jax.random.normal(k[1], (bsz, t, h)) - 1.0),
            -jnp.exp(jax.random.normal(k[2], (h,))),
            (0.2 * jax.random.normal(k[3], (bsz, t, g, n))).astype(dtype),
            (0.2 * jax.random.normal(k[4], (bsz, t, g, n))).astype(dtype),
            jax.random.normal(k[5], (h,))), \
        jax.random.normal(k[6], (bsz, t, h, p)).astype(dtype)


def _shapes(bsz, t, h, p, g, n, dtype=jnp.float32, sharding=None):
    """The six inputs' shapes, for what reads no numbers."""
    def one(dims, dt=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dt, sharding=sharding)

    return (one((bsz, t, h, p), dtype), one((bsz, t, h)), one((h,)),
            one((bsz, t, g, n), dtype), one((bsz, t, g, n), dtype),
            one((h,)))


def _value_and_grads(scan, args, dy):
    """y and the vjp of ``dy``, in one jitted program (op by op the
    ``jax.numpy`` form would compile each operation on its own)."""
    def run(args, dy):
        y, vjp = jax.vjp(scan, *args)
        return (y,) + vjp(dy)

    return jax.jit(run)(args, dy)


def _rel(a, b):
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return np.max(np.abs(a - b)) / np.max(np.abs(b))


def _built():
    fam = ssd_built()
    return {p: fam.labels(path=p).value for p in ("kernel", "xla")}


def test_the_kernels_are_the_numpy_form_and_the_recurrence():
    args, dy = _inputs(2, 256, 16, 2)
    with jax.default_matmul_precision("highest"):
        got = _value_and_grads(
            lambda *a: ssd.chunked_scan_kernel(*a, 128), args, dy)
        xla = _value_and_grads(lambda *a: ssd._scan_xla(*a, 128), args, dy)
        rec = _value_and_grads(lambda *a: ref.ssm_scan(*a, block=128), args,
                               dy)
    for name, a, b, c in zip(["y"] + NAMES, got, xla, rec):
        tol = 2e-5 if name == "y" else 1e-4
        assert float(jnp.max(jnp.abs(c))) > 0, name
        assert _rel(a, b) <= tol, (name, _rel(a, b))
        assert _rel(a, c) <= tol, (name, _rel(a, c))


def test_bfloat16_inputs_keep_the_kernels_in_float32():
    args, dy = _inputs(1, 256, 8, 1, seed=1, dtype=jnp.bfloat16)
    got = _value_and_grads(lambda *a: ssd.chunked_scan_kernel(*a, 128), args,
                           dy)
    wide = [t.astype(jnp.float32) for t in args]
    with jax.default_matmul_precision("highest"):
        rec = _value_and_grads(lambda *a: ref.ssm_scan(*a, block=128), wide,
                               dy.astype(jnp.float32))
    assert got[0].dtype == jnp.bfloat16
    assert [t.dtype for t in got[1:]] == [t.dtype for t in args]
    for name, a, c in zip(["y"] + NAMES, got, rec):
        assert _rel(a, c) <= 1.5e-2, (name, _rel(a, c))


@pytest.mark.parametrize("shape,chunk,why", [
    ((1, 256, 4, 48), 128, "head width 48"),
    ((1, 256, 2, 32), 128, "fill no whole lane tile"),
    ((1, 240, 8, 64), 12, "not a multiple of 8"),
    ((1, 256, 256, 1), 8, "256 heads a group"),
    ((1, 2048, 64, 64), 1024, "passes"),
])
def test_shapes_the_kernels_do_not_tile_are_refused(shape, chunk, why):
    bsz, t, h, p = shape
    with pytest.raises(NotImplementedError, match=why):
        ssd.chunked_scan_kernel(*_shapes(bsz, t, h, p, 1, 128), chunk)


def test_a_state_width_the_kernels_do_not_tile_is_refused():
    with pytest.raises(NotImplementedError, match="state width 64"):
        ssd.chunked_scan_kernel(*_shapes(1, 256, 8, 64, 1, 64), 128)


def test_the_dispatcher_counts_each_path(monkeypatch):
    monkeypatch.setattr(ssd, "_on_chip", lambda: True)
    tiled, _ = _inputs(1, 128, 2, 1)
    untiled, _ = _inputs(1, 32, 4, 2, p=8, n=16)
    before = _built()
    y = jax.jit(lambda *a: (ssd.chunked_scan(*a, 64),
                            ssd.chunked_scan_kernel(*a, 64)))(*tiled)
    np.testing.assert_array_equal(*y)
    y = jax.jit(lambda *a: (ssd.chunked_scan(*a, 8),
                            ssd._scan_xla(*a, 8)))(*untiled)
    np.testing.assert_array_equal(*y)
    monkeypatch.setattr(ssd, "_on_chip", lambda: False)
    jax.eval_shape(lambda *a: ssd.chunked_scan(*a, 64), *tiled)
    after = _built()
    assert after["kernel"] - before["kernel"] == 1
    assert after["xla"] - before["xla"] == 2


def test_the_mixer_with_the_kernels_is_the_mixer_without(monkeypatch):
    from analytics_zoo_tpu.keras.layers import Mamba2Mixer

    layer = Mamba2Mixer(4, 64, 2, 128, chunk=64)
    layer.ensure_built((None, 128, 32))
    p = layer.init_params(jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, 32))
    g = jax.random.normal(jax.random.PRNGKey(2), (1, 128, 32))

    def run():
        with jax.default_matmul_precision("highest"):
            return _value_and_grads(layer.call, (p, x), g)

    want = run()
    monkeypatch.setattr(ssd, "_on_chip", lambda: True)
    before = _built()["kernel"]
    got = run()
    assert _built()["kernel"] - before == 1
    assert _rel(got[0], want[0]) <= 2e-5
    for a, b in zip(jax.tree_util.tree_leaves(got[1:]),
                    jax.tree_util.tree_leaves(want[1:])):
        assert _rel(a, b) <= 1e-4


# -- the kernels as the chip's compiler builds them ---------------------------

@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means no description
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def test_the_cells_scan_compiles_for_the_chip(one_chip, monkeypatch):
    """Forward and backward at the Nemotron-H cell's size (2 rows of 8192
    tokens, 64 heads of 64, 8 groups of 128, chunks of 128, bfloat16),
    compiled by Mosaic for a described v5e: a block or a layout the chip
    refuses fails here."""
    from analytics_zoo_tpu.ops import qk_rotary

    monkeypatch.setattr(ssd, "_interpret", lambda: False)
    monkeypatch.setattr(qk_rotary, "_interpret", lambda: False)
    b, s, h, p, g, n = 2, 8192, 64, 64, 8, 128

    args = _shapes(b, s, h, p, g, n, jnp.bfloat16, one_chip)

    def step(*a):
        y, vjp = jax.vjp(ssd.chunked_scan_kernel, *a)
        return y, vjp(y)

    text = jax.jit(step).lower(*args).compile().as_text()
    assert text.count("tpu_custom_call") == 2
