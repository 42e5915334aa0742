"""The language-model cell's driver, follower, readers and FLOP functions at
tiny sizes on the CPU: no number here is a device metric."""

import json
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import (cells, check, fit, fit_lm, flops_lm, harness, probe_lm,
                       readers, readers_lm, trace_lm)
from benchmark.reference import optim, trinity as ref
from benchmark.tests import tiny, tiny_lm

SEED = 2 ** 31 + 29


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    made = tiny.make_root(tmp_path_factory.mktemp("bench_lm"))
    tiny_lm.add_cell(made)
    return made


@pytest.fixture(scope="module")
def fed():
    """Every batch the sound run's program drew (`tiny_lm.Recorded`)."""
    return []


@pytest.fixture(scope="module")
def sound(root, fed):
    cell = cells.resolve(tiny_lm.CELL, root)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fit, "hostfed_set",
                      tiny_lm.recording(fit.hostfed_set, fed))
        run = cells.load(cell["traffic"]["driver"])(
            cell, SEED, 0.5, True, time.perf_counter(), any_platform=True)
    return cell, run


def test_sound_run_is_correct_and_reports_its_metrics(sound):
    cell, run = sound
    line = harness.result_line(cell, run["device"], run, False)
    assert line["correct"] is True, line["compared"]
    assert set(line["metrics"]) == {"train_tokens_per_s_per_chip", "setup_s"}
    assert line["failed"] == 0 and line["attempted"] % 4 == 0
    json.dumps(line)


def _calls(fed):
    """The batches fed, a call a list: a call is one epoch's seed."""
    calls = []
    for number, epoch, x, y in fed:
        if not calls or calls[-1][0][1] != epoch:
            calls.append([])
        calls[-1].append((number, epoch, x, y))
    return calls


def test_each_call_is_fed_the_next_set_of_rows_and_wraps(sound, fed):
    cell, run = sound
    traffic = cell["traffic"]
    assert traffic["row_sets"] == 4
    xs, ys = fit_lm.row_sets(cell["config"], traffic, SEED)
    assert xs.shape == (4, 8, 32) and ys.shape == (4, 8, 32)
    assert len({row.tobytes() for row in xs.reshape(32, 32)}) == 32
    calls = _calls(fed)
    # the two calls of the first steps, the warm call, the traced run's warm
    # call, then the window's
    assert len(calls) == 4 + run["attempted"] // 4 >= 6
    for at, call in enumerate(calls):
        assert {(number, epoch) for number, epoch, _, _ in call} == {
            (at % 4, at)}
    # a whole call is its set's 8 rows, each once, in the epoch's order
    for at in (2, 3, 4, 5):
        x = np.concatenate([x for _, _, x, _ in calls[at]])
        y = np.concatenate([y for _, _, _, y in calls[at]])
        order = fit.numpy_order(at, 8)
        np.testing.assert_array_equal(x, xs[at % 4][order])
        np.testing.assert_array_equal(y, ys[at % 4][order])
    np.testing.assert_array_equal(
        np.sort(np.concatenate([x for _, _, x, _ in calls[4]]), axis=0),
        np.sort(xs[0], axis=0))                   # call 4: the first set again


def test_the_reference_follows_the_rows_the_program_was_fed(sound, fed):
    cell, run = sound
    cfg, traffic = cell["config"], cell["traffic"]
    took = fit.steps_taken(traffic)
    assert took == [(0, 0), (1, 0), (1, 1)]
    calls = _calls(fed)
    # step 1 ends the first call after one batch; steps 2 and 3 the second
    program = [calls[0][0], calls[1][0], calls[1][1]]
    assert [(n, e) for n, e, _, _ in program] == [(0, 0), (1, 1), (1, 1)]
    seen = []
    follow = fit_lm.follow

    def spy(ref, cfg_, w, batches, *rest):
        seen.extend(batches)
        return follow(ref, cfg_, w, batches, *rest)

    xs, ys = fit_lm.row_sets(cfg, traffic, SEED)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(fit_lm, "follow", spy)
        want = fit_lm.reference_steps(cfg, traffic, xs, ys, took, run["start"])
    for (_, _, x, y), (rx, ry) in zip(program, seen):
        np.testing.assert_array_equal(x, np.asarray(rx))
        np.testing.assert_array_equal(y, np.asarray(ry))
    assert want["losses"] == run["want"]["losses"]
    for got, ref_loss in zip(run["seen"]["losses"], want["losses"]):
        assert abs(got - ref_loss) / ref_loss < 5e-3


def test_the_same_seed_gives_the_same_rows_and_no_row_sets_is_one_set(sound):
    cell, _ = sound
    cfg, traffic = cell["config"], cell["traffic"]
    xs, ys = fit_lm.row_sets(cfg, traffic, SEED)
    again = fit_lm.row_sets(cfg, traffic, SEED)
    np.testing.assert_array_equal(xs, again[0])
    np.testing.assert_array_equal(ys, again[1])
    assert not np.array_equal(xs, fit_lm.row_sets(cfg, traffic, SEED + 1)[0])
    # a traffic file that states no `row_sets`: the 8 rows it gave before
    # (`data.rows` from the seed's generator), and every epoch is fed them
    plain = {k: v for k, v in traffic.items() if k != "row_sets"}
    x, y = cells.load(cfg["rows"])(cfg, 8, np.random.default_rng(SEED))
    one = fit_lm.row_sets(cfg, plain, SEED)
    assert one[0].shape == (1, 8, 32)
    np.testing.assert_array_equal(one[0][0], x)
    np.testing.assert_array_equal(one[1][0], y)
    feed = fit_lm.Feed(dict(plain, feature_set="benchmark.fit:hbm_set"), *one)
    assert len(feed.sets) == 1 and feed.of(0) is feed.of(7)
    real = cells.resolve("trinity-mini.fit-seq8k")["traffic"]
    assert real["row_sets"] == 4
    assert "row_sets" not in cells.resolve("resnet50.fit-hostfed")["traffic"]


def test_the_call_series_is_the_clock_and_the_counters_deltas(sound):
    _, run = sound
    series = run["ctx"]["series"]
    calls = run["attempted"] // 4
    assert {len(v) for v in series.values()} == {calls}
    assert all(s > 0 for s in series["call_s"])
    # 4 of 8 experts held: about half, whatever the call
    assert all(20.0 < h < 80.0 for h in series["call_held_share"])
    c = run["ctx"]["counters"]
    moe = (c["window_end"]["zoo_moe_calls_total"]
           - c["window_start"]["zoo_moe_calls_total"])
    compact = (c["window_end"]["zoo_moe_calls_compact_total"]
               - c["window_start"]["zoo_moe_calls_compact_total"])
    assert sum(series["call_overflows"]) == moe - compact
    marks = [(0.0, {"zoo_moe_calls_total": 0.0}),
             (2.0, {"zoo_moe_calls_total": 8.0,
                    "zoo_moe_calls_compact_total": 7.0,
                    "zoo_moe_assignments_total": 100.0,
                    "zoo_moe_assignments_total_held": 12.5})]
    assert fit_lm.call_series(marks) == {
        "call_s": [2.0], "call_held_share": [12.5], "call_overflows": [1.0]}


def test_numbers_a_leaf_at_a_time_are_the_whole_trees_numbers(sound):
    _, run = sound
    whole = check.fit_numbers(run["seen"], run["want"])
    assert run["numbers"] == whole and len(whole) == 8
    assert fit_lm.host_peak_gb() > 0.1


def test_traced_line_reads_the_counters_and_no_device_number(sound):
    cell, run = sound
    line = harness.result_line(cell, run["device"], run, True)
    got = line["metrics"]
    # 4 of 8 experts held, 2 picks a token: a uniform router sends half
    assert 20.0 < got["train_moe_held_share"]["value"] < 80.0
    assert got["train_moe_load_max_over_mean"]["value"] >= 1.0
    assert got["compiles_in_window.train.lm"]["value"] == 0
    # no TPU plane in a CPU trace: every device-trace metric is left out
    assert not {n for n in got if "roofline" in n or "device_share" in n
                or "mfu" in n}
    c = run["ctx"]["counters"]
    tokens = (c["window_end"]["zoo_train_tokens_total"]
              - c["window_start"]["zoo_train_tokens_total"])
    assert tokens == run["attempted"] * 2 * 32


@pytest.fixture(scope="module")
def probed(root):
    """{kind: numbers} as `probe_lm.py` reads them on the chip: the sound
    program, the control and the planted faults."""
    cell = cells.resolve(tiny_lm.CELL, root)
    device = harness.device
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(harness, "device", lambda chips, _=False: device(chips, True))
        return cell, dict(probe_lm.readings(cell, SEED, True, 0.3))


@pytest.mark.parametrize("kind,caught_by", [
    ("control_lower_precision", "grad_diff_best_leaf"),
    # (half a batch's gradient has about root two times the norm; what it does
    # to the loss depends on the rows)
    ("fault_half_batch", "grad_norm_gap_median_leaf"),
    ("fault_state_unchanged", "change_norm_gap_median_leaf"),
    ("fault_one_leaf_unmoved", "change_norm_gap")])
def test_the_control_and_planted_faults_are_not_correct(probed, kind,
                                                        caught_by):
    """Through `check.verdict` with the cell's limits file, as a run is."""
    cell, numbers = probed
    assert check.verdict(numbers["program"], cell["limits"])[0] is True
    ok, compared = check.verdict(numbers[kind], cell["limits"])
    assert ok is False
    value, limit = compared[caught_by]
    assert value > limit, (kind, compared)


def test_only_the_worst_leaf_sees_one_leaf_left_unmoved(probed):
    cell, numbers = probed
    _, compared = check.verdict(numbers["fault_one_leaf_unmoved"],
                                cell["limits"])
    over = {k for k, (value, limit) in compared.items() if value > limit}
    assert over == {"change_norm_gap"}


def test_limits_of_the_cell_and_of_the_toy_name_the_same_numbers(sound):
    cell, _ = sound
    real = cells.resolve("trinity-mini.fit-seq8k")["limits"]
    assert set(real) == set(cell["limits"]) == {
        "grad_norm_gap", "grad_norm_gap_median_leaf",
        "change_norm_gap", "change_norm_gap_median_leaf",
        "grad_diff_best_leaf"}
    assert all(0 < v < 1 for v in real.values())


def test_follower_carries_the_bias_and_keeps_its_trees_on_the_host(root):
    cfg = cells.resolve(tiny_lm.CELL, root)["config"]
    w = jax.device_get(ref.init_weights(cfg, jax.random.PRNGKey(0)))
    x, y = cells.load(cfg["rows"])(cfg, 2, np.random.default_rng(1))
    out = fit_lm.follow(ref, cfg, w, [(jnp.asarray(x), jnp.asarray(y))] * 2,
                        optim.Adam(lr=1e-3), row_block=1)
    assert len(out["losses"]) == 2 and out["losses"][1] < out["losses"][0]
    assert out["bias"].shape == (2, 8) and np.abs(out["bias"]).max() > 0
    np.testing.assert_allclose(out["bias"].sum(axis=-1), 0, atol=1e-6)
    for leaf in jax.tree_util.tree_leaves((out["first"], out["change"])):
        assert isinstance(leaf, np.ndarray)
    # rows one at a time or both at once: the same mean gradient
    both = fit_lm.follow(ref, cfg, w, [(jnp.asarray(x), jnp.asarray(y))],
                         optim.Adam(lr=1e-3), row_block=2)
    for a, b in zip(jax.tree_util.tree_leaves(out["first"]),
                    jax.tree_util.tree_leaves(both["first"])):
        np.testing.assert_allclose(a, b, atol=1e-6)


def test_first_gradient_is_adams_first_moment_over_one_less_b1():
    import optax

    g = {"a": jnp.asarray([1.0, -2.0, 4.0])}
    tx = optax.adam(1e-3, b1=0.8)
    _, state = tx.update(g, tx.init(g), g)
    np.testing.assert_allclose(fit_lm.first_gradient(state, 0.8)["a"],
                               [1.0, -2.0, 4.0], rtol=1e-6)
    with pytest.raises(ValueError):
        fit_lm.first_gradient(optax.sgd(0.1).init(g), 0.9)


def test_flops_count_the_mathematics():
    assert flops_lm.seen_keys(4, None) == 10          # 1 + 2 + 3 + 4
    assert flops_lm.seen_keys(4, 2) == 7              # 1 + 2 + 2 + 2
    assert flops_lm.seen_keys(4, 9) == 10
    cfg = cells.resolve("trinity-mini.fit-seq8k")["config"]
    per_token = flops_lm.trinity_forward_flops(cfg, 2, 8192) / (2 * 8192)
    # ISSUE 29's reckoning: 0.27 + 0.20 + 0.13 + 0.05 + 0.10 GFLOP a token
    assert 0.70e9 < per_token < 0.78e9
    pairs = flops_lm.attention_pairs(cfg, 1, 8192)
    assert pairs == 4 * flops_lm.seen_keys(8192, 2048) + 8192 * 8193 / 2
    assert flops_lm.attention_kernel_forward_flops(cfg, 1, 8192) == (
        4 * 128 * 32 * pairs)
    one = flops_lm.expert_forward_flops(cfg, 1)
    assert one == 3 * 2 * 2048 * 1024
    # the experts actually visited: a uniform router's 4 layers x 16384 x 8 / 8
    # unless the counters say otherwise
    uniform = flops_lm.trinity_forward_flops(cfg, 2, 8192)
    assert flops_lm.trinity_forward_flops(cfg, 2, 8192, 65536.0) == uniform
    assert flops_lm.trinity_forward_flops(cfg, 2, 8192, 65537.0) == uniform + one
    assert flops_lm.expert_kernel_bytes(cfg, 0, 4) == 3 * 4 * 16 * 3 * 2048 * 1024 * 2


HLO = '''
ENTRY %main {
  %gmm.2 = bf16[8,8]{1,0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(moe.experts)/jit(gmm)/pallas_call" stack_frame_id=5}
  %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fc, metadata={op_name="jit(train_step)/optimizer/mul"}
  ROOT %zoo_flash_fwd.1 = bf16[8]{0} custom-call(%q), metadata={op_name="jit(train_step)/jvp(attn.window)/zoo_flash_fwd"}
  %fusion.9 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fd
}
'''


def test_kernel_and_scope_reduction_of_a_trace():
    scopes = trace_lm.scope_map(HLO)
    assert scopes == {"gmm.2": "moe.experts", "fusion.7": "optimizer",
                      "zoo_flash_fwd.1": "attn.window"}
    ms = 1_000_000
    dev = {"modules": [("jit_train_step(1)", 0, 10 * ms),
                       ("jit_train_step(1)", 20 * ms, 30 * ms),
                       ("jit_other(2)", 40 * ms, 41 * ms)],
           "ops": [("%zoo_flash_fwd.1 = bf16[8]{0} custom-call(...)", 0, 2 * ms),
                   ("%gmm.2 = bf16[8,8] custom-call(...)", 2 * ms, 3 * ms),
                   ("%tgmm.1 = bf16[8,8] custom-call(...)", 3 * ms, 5 * ms),
                   ("%fusion.7 = f32[8] fusion(...)", 5 * ms, 9 * ms),
                   ("%zoo_flash_dkv.3 = bf16[8] custom-call(...)", 21 * ms, 24 * ms),
                   ("%gmm.2 = bf16[8,8] custom-call(...)", 40 * ms, 41 * ms)]}
    k = trace_lm.reduce({"/device:TPU:0": dev}, "^jit_train_", scopes)
    assert k["module_calls"] == 2 and abs(k["module_s"] - 0.020) < 1e-9
    assert abs(k["kernel_s"]["flash_fwd"] - 0.002) < 1e-9
    assert abs(k["kernel_s"]["gmm"] - 0.001) < 1e-9     # not the other module's
    assert abs(k["kernel_s"]["tgmm"] - 0.002) < 1e-9
    assert abs(k["scope_s"]["optimizer"] - 0.004) < 1e-9
    ctx = {"kernels": k, "peaks": {"bf16_flops": 100e12, "hbm_bytes_per_s": 1e12},
           "counters": {"window_start": {}, "window_end": {}}}
    share = readers_lm.device_share(ctx, scopes=["attn.window", "attn.full"])
    assert abs(share - 10.0) < 1e-6          # the forward kernel's 2 of 20 ms
    # a compiled text that names no scope: no share, whatever the kernels
    bare = dict(ctx, kernels=trace_lm.reduce({"/device:TPU:0": dev},
                                             "^jit_train_", {}))
    assert bare["kernels"]["kernel_s"] == k["kernel_s"]
    assert readers_lm.device_share(bare, scopes=["attn.window"]) is None
    assert readers_lm.device_share(bare, scopes=["optimizer"]) is None


def test_rooflines_from_kernel_time_and_counters():
    cfg = cells.resolve("trinity-mini.fit-seq8k")["config"]
    flops = 3 * flops_lm.attention_kernel_forward_flops(cfg, 2, 8192)
    seconds = 2 * flops / 197e12                    # half the peak
    ctx = {"lm": {"cfg": cfg, "rows": 2, "seq": 8192},
           "peaks": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9},
           "kernels": {"module_calls": 4, "module_s": 2.0,
                       "kernel_s": {"flash_fwd": 2 * seconds,
                                    "flash_dq": seconds, "flash_dkv": seconds,
                                    "gmm": 0.04, "tgmm": 0.04},
                       "scope_s": {}},
           "counters": {"window_start": {"zoo_train_steps_total": 0,
                                         "zoo_moe_assignments_total_held": 0,
                                         "zoo_moe_assignments_total": 0},
                        "window_end": {"zoo_train_steps_total": 8,
                                       "zoo_moe_assignments_total_held": 8 * 65536,
                                       "zoo_moe_assignments_total": 8 * 524288}}}
    assert abs(readers_lm.attn_kernel_roofline(ctx) - 50.0) < 1e-6
    assert abs(readers_lm.held_share(ctx) - 12.5) < 1e-9
    want = max(3 * flops_lm.expert_forward_flops(cfg, 65536) / 197e12,
               flops_lm.expert_kernel_bytes(cfg, 65536, 4) / 819e9) / 0.02 * 100
    assert abs(readers_lm.moe_experts_roofline(ctx) - want) < 1e-6
    assert 0 < want < 100


@pytest.mark.parametrize("reader", [
    readers_lm.attn_kernel_roofline, readers_lm.moe_experts_roofline,
    readers_lm.device_share, readers_lm.load_max_over_mean,
    readers_lm.held_share])
def test_readers_find_nothing_on_a_program_without_the_counters(reader):
    """A parent's run, or another model's cell: no `kernels`, no `lm`, none
    of the counters. Nothing is raised and the metric is left out."""
    ctx = {"counters": {"setup_end": {}, "window_start": {}, "window_end": {}},
           "trace": None, "series": {}, "memory": {"memory_peak_bytes": 0},
           "peaks": None}
    assert reader(ctx) is None
    assert readers.call({"reader": f"benchmark.readers_lm:{reader.__name__}"},
                        ctx) is None
