"""A run end to end at a tiny size on the CPU: no chip, no result; with the
look for a chip skipped, a sound run is correct and each planted fault, and the
control, is not."""

import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from benchmark import callers, cells, check, fit, harness
from benchmark.reference import optim
from benchmark.tests import tiny

SEED = 2 ** 31 + 11


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


def _drive(root, cell_name, traced=False, seconds=0.6):
    cell = cells.resolve(cell_name, root)
    run = cells.load(cell["traffic"]["driver"])(
        cell, SEED, seconds, traced, time.perf_counter(), any_platform=True)
    return cell, run, harness.result_line(cell, run["device"], run, traced)


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(cells.HERE, "run.py"), "--workload",
         "resnet50.fit-hostfed", "--seed", "1", "--seconds", "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=300, cwd=cells.ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


@pytest.mark.parametrize("cell_name", list(tiny.LIMITS))
def test_sound_run_is_correct(root, cell_name):
    cell, run, line = _drive(root, cell_name)
    assert line["correct"] is True, line["compared"]
    assert list(line)[-1] == "compared" and line["compared"]
    assert set(line["metrics"]) == {m["name"] for m in cell["end_to_end"]}
    assert line["failed"] == 0 and line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"      # never a device metric
    json.dumps(line)


def test_traced_run_reports_per_layer_metrics_without_device_numbers(root):
    cell, run, line = _drive(root, "bert-tiny.tiny-callers", traced=True)
    assert "serve_rows_per_flush" in line["metrics"]
    assert "serve_latency_p50_ms" in line["metrics"]
    # no TPU plane in a CPU trace: every device-trace metric is left out
    assert not {"serve_forward_mfu", "serve_device_idle_share",
                "serve_forward_device_ms"} & set(line["metrics"])


def test_fused_step_that_keeps_its_state_is_not_correct(root, monkeypatch):
    from analytics_zoo_tpu.engine import estimator

    monkeypatch.setattr(estimator.optax, "apply_updates", lambda p, u: p)
    _, _, line = _drive(root, "bert-tiny.tiny-hbm")
    assert line["correct"] is False
    assert line["compared"]["loss_gap_1"][0] < line["compared"]["loss_gap_1"][1]


def test_fused_half_batch_is_not_correct(root, monkeypatch):
    from analytics_zoo_tpu.engine import estimator

    plan = estimator._epoch_index_plan

    def half(perm_key, n, batch):
        idxs, masks = plan(perm_key, n, batch)
        return idxs, masks.at[:, batch // 2:].set(0.0)

    monkeypatch.setattr(estimator, "_epoch_index_plan", half)
    _, _, line = _drive(root, "bert-tiny.tiny-hbm")
    assert line["correct"] is False


def test_hostfed_step_that_keeps_its_state_is_not_correct(root, monkeypatch):
    from analytics_zoo_tpu.engine import estimator

    monkeypatch.setattr(estimator.optax, "apply_updates", lambda p, u: p)
    _, _, line = _drive(root, "resnet-tiny.tiny-hostfed")
    assert line["correct"] is False
    assert line["compared"]["change_norm_gap"][0] == pytest.approx(1.0)


def test_hostfed_half_batch_is_not_correct(root, monkeypatch):
    from analytics_zoo_tpu.data import pmem

    batches = pmem.NativeCachedFeatureSet.train_batches

    def half(self, batch_size, shuffle=True, seed=0):
        for x, y, mask in batches(self, batch_size, shuffle, seed):
            mask = mask.copy()
            mask[batch_size // 2:] = 0.0
            yield x, y, mask

    monkeypatch.setattr(pmem.NativeCachedFeatureSet, "train_batches", half)
    _, _, line = _drive(root, "resnet-tiny.tiny-hostfed")
    assert line["correct"] is False


def test_altered_answer_is_not_correct(root, monkeypatch):
    from analytics_zoo_tpu.inference import inference_model

    fetch = inference_model.InferenceModel.do_fetch

    def altered(self, handle):
        out = np.array(fetch(self, handle))
        out[0, 0] += 0.25           # one answer, where it is produced
        return out

    monkeypatch.setattr(inference_model.InferenceModel, "do_fetch", altered)
    _, _, line = _drive(root, "bert-tiny.tiny-callers")
    assert line["correct"] is False


def test_control_in_lower_precision_reads_apart(root):
    """The reference put in the program's place, one precision down, reads
    over three times what the sound program reads on one number at least."""
    cell, run, _ = _drive(root, "bert-tiny.tiny-hbm")
    cfg, traffic = cell["config"], cell["traffic"]
    from benchmark import data

    x, y = data.rows(cfg, traffic["batch"] * traffic["steps_per_call"],
                     np.random.default_rng(SEED))
    took = fit.steps_taken(traffic)
    low = fit.reference_steps(cfg, traffic, SEED, x, y, took,
                              cast=optim.lower_precision(cfg["compute_dtype"]))
    control = check.fit_numbers({"losses": low["losses"]}, run["want"])
    assert any(control[k] > 3 * run["numbers"][k] for k in control)

    cell, run, _ = _drive(root, "bert-tiny.tiny-callers")
    cfg, traffic = cell["config"], cell["traffic"]
    args = (cfg, SEED, run["pool"], run["sample"],
            traffic["reference_row_block"])
    gap = callers.prob_gap(
        callers.reference_answers(*args, optim.lower_precision("bfloat16")),
        callers.reference_answers(*args))
    assert gap > 3 * run["numbers"]["prob_gap"]


def test_resnet_control_reads_apart():
    """The ResNet-50 reference in fp8 (e4m3 forward, e5m2 backward) against
    the same reference with its operands rounded to bfloat16, which stands
    here for the stated precision (the CPU's program is not the chip's): the
    first gradient's difference on its best leaf and the median leaf's gap of
    norms both read over three times as much. Published widths and depth, 16
    images of 160 x 160; at the cell's own size the chip reads 7 and 6 times
    (PERF.md)."""
    import jax
    import jax.numpy as jnp

    from benchmark import data
    from benchmark.reference import resnet50 as ref

    cfg = dict(cells.resolve("resnet50.fit-hostfed")["config"], image_size=160)
    x, y = data.rows(cfg, 16, np.random.default_rng(3))
    x, y = jnp.asarray(x), jnp.asarray(y)
    w = jax.jit(lambda k: ref.init_weights(cfg, k))(jax.random.PRNGKey(3))

    def bfloat16(t):
        q = jax.lax.reduce_precision(t, 8, 7)
        return t + jax.lax.stop_gradient(q - t)

    def first_gradient(cast):
        with jax.default_matmul_precision("highest"):
            return jax.jit(jax.grad(lambda w_: jnp.mean(
                ref.row_losses(w_, x, y, cfg, cast))))(w)

    want = first_gradient(lambda t: t)
    stated = check.leaf_table(first_gradient(bfloat16), want)
    control = check.leaf_table(
        first_gradient(optim.lower_precision(cfg["compute_dtype"])), want)
    every = [True] * len(stated["want"])
    assert check.diff_best_leaf(control) > 3 * check.diff_best_leaf(stated)
    assert (check.norm_gaps(control, every)["norm_gap_median_leaf"]
            > 3 * check.norm_gaps(stated, every)["norm_gap_median_leaf"])
