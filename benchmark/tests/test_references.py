"""The two plain references held to the program at a tiny size on the CPU,
both in float32 with `default_matmul_precision("highest")`."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import cells, check, data, models
from benchmark.reference import optim
from benchmark.tests import tiny


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _program_f32(cfg, seed):
    prog = models.Program(cfg, seed)
    prog.model.compute_dtype = None          # the program's own float32 path
    return prog


def test_bert_forward_and_gradient(root):
    cfg = cells.resolve("bert-tiny.tiny-hbm", root)["config"]
    prog, w = _program_f32(cfg, 3), models.reference_weights(cfg, 3)
    x, y = data.rows(cfg, 8, np.random.default_rng(3))
    xs, ys = [jnp.asarray(a) for a in x], jnp.asarray(y)

    def program_loss(params):
        pred, _ = prog.model.apply(params, {}, xs, training=True)
        return prog.criterion(ys, pred)

    with jax.default_matmul_precision("highest"):
        got, _ = prog.model.apply(prog.est.tstate.params, {}, xs)
        want = prog.ref.probabilities(w, xs, cfg)
        g_prog = prog.to_reference_layout(
            jax.grad(program_loss)(prog.est.tstate.params))
        g_ref = jax.grad(lambda w_: jnp.mean(
            prog.ref.row_losses(w_, xs, ys, cfg)))(w)
    assert float(jnp.abs(got - want).max()) < 1e-5
    assert float(jnp.std(want[:, 0])) > 1e-4, "rows must differ"
    for a, b in zip(jax.tree_util.tree_leaves(g_prog),
                    jax.tree_util.tree_leaves(g_ref)):
        assert float(jnp.abs(a - b).max()) <= 1e-4 * (
            1e-3 + float(jnp.abs(b).max()))


def test_resnet50_train_mode_forward(root):
    cfg = cells.resolve("resnet-tiny.tiny-hostfed", root)["config"]
    prog, w = _program_f32(cfg, 4), models.reference_weights(cfg, 4)
    x, _ = data.rows(cfg, 8, np.random.default_rng(4))
    scaled = (jnp.asarray(x).astype(jnp.float32) - 127.5) / 127.5
    with jax.default_matmul_precision("highest"):
        got, _ = prog.model.apply(prog.est.tstate.params,
                                  prog.est.tstate.model_state, scaled,
                                  training=True)
        want = prog.ref.probabilities(w, jnp.asarray(x), cfg)
    # 50 layers of batch statistics over 8 rows amplify float32 rounding
    assert float(jnp.abs(got - want).max()) < 1e-3
    assert float(jnp.std(want[:, 0])) > 1e-3, "rows must differ"


def test_weights_come_from_the_seed_alone(root):
    cfg = cells.resolve("bert-tiny.tiny-hbm", root)["config"]
    a, b = models.reference_weights(cfg, 2 ** 31 + 5), models.reference_weights(
        cfg, 2 ** 31 + 5)
    c = models.reference_weights(cfg, 5)
    assert all(bool(jnp.array_equal(u, v)) for u, v in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)))
    assert not bool(jnp.array_equal(a["word"], c["word"]))
    prog = models.Program(cfg, 2 ** 31 + 5)
    back = prog.to_reference_layout(prog.est.tstate.params)
    assert all(bool(jnp.array_equal(u, v)) for u, v in zip(
        jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(back)))


@pytest.mark.parametrize("name,ref,make", [
    ("adam", optim.Adam(1e-2), lambda: __import__("optax").adam(1e-2)),
    ("momentum", optim.Momentum(0.1, 0.9),
     lambda: __import__("optax").sgd(0.1, momentum=0.9))])
def test_reference_optimizers_match_the_stated_ones(name, ref, make):
    import optax

    rng = np.random.default_rng(0)
    w = {"a": jnp.arange(4.0), "b": jnp.ones((2, 2))}

    def row_losses(w_, x, y, cfg, cast):
        return (x @ w_["b"]).sum(-1) * y + w_["a"][:2].sum() * x[:, 0]

    batches = [(jnp.asarray(rng.random((6, 2)), jnp.float32),
                jnp.asarray(rng.random(6), jnp.float32)) for _ in range(3)]
    losses, first, after = optim.follow(row_losses, w, batches, ref, {},
                                        row_block=2)
    tx, p = make(), w
    state = tx.init(p)
    for x, y in batches:
        g = jax.grad(lambda p_: jnp.mean(row_losses(p_, x, y, {}, None)))(p)
        upd, state = tx.update(g, state, p)
        p = optax.apply_updates(p, upd)
    for u, v in zip(jax.tree_util.tree_leaves(p),
                    jax.tree_util.tree_leaves(after)):
        assert float(jnp.abs(u - v).max()) < 1e-6
    assert len(losses) == 3 and first["a"].shape == (4,)


def test_norm_gap_by_the_worst_leaf_against_the_median():
    def numbers(got, keep=(True, True, True)):
        want = [np.array([1.0]), np.array([2.0]), np.array([1e-9])]
        table = check.leaf_table([np.array([g]) for g in got], want)
        return check.norm_gaps(table, list(keep))

    assert numbers([1.1, 2.0, 1e-9])["norm_gap"] == pytest.approx(0.1)
    # a leaf that is all but zero is measured against the median leaf
    assert numbers([1.0, 2.0, 0.5])["norm_gap"] == pytest.approx(0.5)
    assert numbers([1.0, 2.0, 0.5], (True, True, False))["norm_gap"] == 0.0
    # the difference's norm sees a turn that the norm does not
    turned = check.leaf_table([np.array([0.0, 1.0])], [np.array([1.0, 0.0])])
    assert check.norm_gaps(turned, [True])["norm_gap"] == 0.0
    assert check.diff_best_leaf(turned) == pytest.approx(2 ** 0.5)


def test_a_limit_whose_number_is_missing_fails():
    assert check.verdict({"loss_gap_1": 0.0}, {"loss_gap_1": 1e-3})[0] is True
    ok, compared = check.verdict({"loss_gap_1": 0.0},
                                 {"loss_gap_1": 1e-3, "grad_norm_gap": 0.1})
    assert ok is False and compared["grad_norm_gap"] == [None, 0.1]
    assert check.verdict({"loss_gap_1": float("nan")},
                         {"loss_gap_1": 1e-3})[0] is False
    assert check.verdict({"loss_gap_1": 0.0}, {})[0] is False
