"""Pipeline-parallel stage axis: StagePlan partitioning, the microbatch
schedules, activation-slot discipline, training parity vs the
unpipelined fused step, stage-split serving, stage-owned checkpoints
and the mid-schedule kill → resume drill (docs/pipeline-parallel.md)."""

import json
import os
import subprocess
import sys
import threading

import numpy as np
import optax
import pytest

from conftest import max_ulp
from analytics_zoo_tpu.mesh.config import MeshConfig, STAGE_AXIS
from analytics_zoo_tpu.mesh.plan import ShardingPlan
from analytics_zoo_tpu.pipeline import (
    ActivationSlots,
    MicrobatchSchedule,
    StageAssignmentError,
    StageLadderError,
    StagePlan,
    bubble_fraction,
)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class _Layer:
    def __init__(self, name):
        self.name = name


class _Stack:
    def __init__(self, *names):
        self._layers = [_Layer(n) for n in names]

    def layers(self):
        return list(self._layers)


# ---------------------------------------------------------------------------
# StagePlan assignment
# ---------------------------------------------------------------------------


def test_first_match_wins():
    plan = StagePlan(2, rules=((r"^enc", 0), (r"^enc_late", 1), (r".", 1)))
    # "enc_late" matches the FIRST rule (^enc) — order is the contract
    assert plan.stage_of("enc_late")[0] == 0
    assert plan.stage_of("dec")[0] == 1


def test_unmatched_layer_fails_loudly():
    plan = StagePlan(2, rules=((r"^enc", 0), (r"^dec", 1)))
    with pytest.raises(StageAssignmentError, match="'pool'"):
        plan.split(_Stack("enc_1", "pool", "dec_1"))


def test_non_monotonic_assignment_rejected():
    plan = StagePlan(2, rules=((r"^a", 1), (r".", 0)))
    with pytest.raises(StageAssignmentError, match="non-decreasing"):
        plan.assign(["a_1", "b_1"])


def test_empty_stage_rejected():
    plan = StagePlan(3, rules=((r"^a", 0), (r".", 2)))
    with pytest.raises(StageAssignmentError, match=r"stage\(s\) \[1\]"):
        plan.assign(["a_1", "b_1"])


def test_split_partitions_with_absolute_indices():
    plan = StagePlan(2, rules=((r"^a", 0), (r".", 1)))
    segs = plan.split(_Stack("a_1", "a_2", "b_1"))
    assert [s.names for s in segs] == [("a_1", "a_2"), ("b_1",)]
    assert [s.indices for s in segs] == [(0, 1), (2,)]


def test_rule_stage_out_of_range_and_bad_regex():
    with pytest.raises(ValueError, match="outside"):
        StagePlan(2, rules=((r".", 2),))
    with pytest.raises(ValueError, match="not a valid regex"):
        StagePlan(2, rules=((r"(", 0),))


def test_mesh_stage_axis_must_match_num_stages():
    mesh = MeshConfig.from_spec("data=1,stage=4")
    with pytest.raises(ValueError, match="stage=4"):
        StagePlan(2, rules=((r".", 0),), mesh=mesh)
    # matching length composes fine
    StagePlan(4, rules=((r".", 0),), mesh=mesh)


def test_fingerprint_stable_and_rule_ordered():
    a = StagePlan(2, rules=((r"^a", 0), (r".", 1)))
    b = StagePlan(2, rules=((r".", 1), (r"^a", 0)))
    assert a.fingerprint() != b.fingerprint()
    assert a.fingerprint() == \
        StagePlan(2, rules=((r"^a", 0), (r".", 1))).fingerprint()
    assert "stages=2" in a.fingerprint()


def test_owner_of_key_matches_layer_segment_only():
    plan = StagePlan(2, rules=((r"^d1$", 0), (r".", 1)))
    layer_stages = {"d1": 0, "d2": 1}
    # the layer-name PATH SEGMENT decides — "params"/"opt_state" prefixes
    # and non-layer keys must not be rule-matched
    assert plan.owner_of_key("params/d1/kernel", layer_stages) == 0
    assert plan.owner_of_key("opt_state/0/mu/d2/bias", layer_stages) == 1
    assert plan.owner_of_key("step", layer_stages) == 0  # coordinator


def test_partition_flat_covers_every_leaf():
    plan = StagePlan(2, rules=((r"^d1$", 0), (r".", 1)))
    layer_stages = {"d1": 0, "d2": 1}
    flat = [("params/d1/kernel", 1), ("params/d2/kernel", 2), ("step", 3)]
    shards = plan.partition_flat(flat, layer_stages)
    assert [k for k, _ in shards[0]] == ["params/d1/kernel", "step"]
    assert [k for k, _ in shards[1]] == ["params/d2/kernel"]


# ---------------------------------------------------------------------------
# mesh stage axis + ShardingPlan rejection (satellites)
# ---------------------------------------------------------------------------


def test_mesh_from_spec_renders_stage_axis():
    mesh = MeshConfig.from_spec("data=2,stage=4")
    assert mesh.axis_length(STAGE_AXIS) == 4
    assert "stage=4" in mesh.describe()
    assert "stage=4" in mesh.fingerprint()


def test_sharding_plan_rejects_stage_axis_rule():
    mesh = MeshConfig.from_spec("data=2,stage=2")
    with pytest.raises(ValueError, match=r"'kernel\$'.*'stage'"):
        ShardingPlan(mesh, rules=(("kernel$", ("stage",)),))


# ---------------------------------------------------------------------------
# microbatch schedules
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("mode", ["1f1b", "gpipe"])
@pytest.mark.parametrize("num_stages,num_microbatches",
                         [(1, 1), (1, 4), (2, 2), (3, 4), (4, 8)])
def test_events_cover_every_op_once(num_stages, num_microbatches, mode):
    sched = MicrobatchSchedule(num_stages, num_microbatches, mode)
    events = sched.events()
    # (2K-1)·M events: F and B per non-last stage per microbatch, one
    # fused loss+backward (L) per microbatch on the last stage
    assert len(events) == (2 * num_stages - 1) * num_microbatches
    assert len(set(events)) == len(events)
    for kind, last_stage in (("F", num_stages - 1), ("B", num_stages - 1)):
        assert {(s, m) for k, s, m in events if k == kind} == {
            (s, m) for s in range(num_stages - 1)
            for m in range(num_microbatches)}
    assert {(s, m) for k, s, m in events if k == "L"} == {
        (num_stages - 1, m) for m in range(num_microbatches)}


@pytest.mark.parametrize("mode", ["1f1b", "gpipe"])
@pytest.mark.parametrize("num_stages,num_microbatches",
                         [(1, 2), (2, 1), (2, 4), (3, 4), (4, 8)])
def test_measured_slots_respect_declared_budget(num_stages,
                                                num_microbatches, mode):
    sched = MicrobatchSchedule(num_stages, num_microbatches, mode)
    budget = sched.slot_budget()
    measured = sched.measured_slots()   # raises on any slot leak
    if mode == "gpipe":
        # chunked fill/drain peaks exactly at the declared pool
        assert measured == budget
    else:
        # 1F1B's steady state hands a microbatch from stage s to s+1:
        # at that instant both slots exist, costing at most one slot
        # over the analytic budget at stages ≥ 1, none at stage 0
        assert measured[0] == budget[0]
        for s in range(num_stages):
            assert 0 <= measured[s] - budget[s] <= (1 if s else 0)


def test_bubble_1f1b_strictly_below_gpipe_at_4_microbatches():
    for num_stages in (2, 3, 4):
        for num_microbatches in (4, 8):
            b1 = bubble_fraction(num_stages, num_microbatches, "1f1b")
            bg = bubble_fraction(num_stages, num_microbatches, "gpipe")
            assert b1 < bg, (num_stages, num_microbatches, b1, bg)
    # degenerate single-microbatch pipelines have nothing to overlap:
    # the schedules coincide
    assert bubble_fraction(3, 1, "1f1b") == bubble_fraction(3, 1, "gpipe")


def test_schedule_rejects_bad_mode_and_sizes():
    with pytest.raises(ValueError):
        MicrobatchSchedule(2, 2, "zigzag")
    with pytest.raises(ValueError):
        MicrobatchSchedule(0, 2, "1f1b")
    with pytest.raises(ValueError):
        MicrobatchSchedule(2, 0, "1f1b")


# ---------------------------------------------------------------------------
# activation-slot lease discipline
# ---------------------------------------------------------------------------


def test_slot_lease_checkout_release_cycle():
    slots = ActivationSlots({0: 2, 1: 1})
    a = slots.checkout(0, payload="x")
    b = slots.checkout(0, payload="y")
    assert slots.in_flight(0) == 2
    with pytest.raises(RuntimeError, match="exhausted"):
        slots.checkout(0, payload="z")
    slots.release(a)
    slots.release(b)
    with pytest.raises(RuntimeError, match="released twice"):
        slots.release(a)
    c = slots.checkout(1, payload="w")
    with pytest.raises(RuntimeError):
        slots.assert_drained()
    slots.release(c)
    slots.assert_drained()
    assert slots.peak(0) == 2


# ---------------------------------------------------------------------------
# training parity vs the unpipelined fused step
# ---------------------------------------------------------------------------


def _make_estimator():
    from analytics_zoo_tpu.common.nncontext import get_nncontext
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    get_nncontext().set_rng_state(123, 0)
    model = Sequential([
        Dense(8, activation="relu", input_shape=(4,), name="d1"),
        Dense(8, activation="relu", name="d2"),
        Dense(2, name="d3"),
    ])
    return Estimator(model, optax.adam(1e-2))


class _ArrayDS:
    def __init__(self, n=64):
        r = np.random.RandomState(0)
        self.x = r.randn(n, 4).astype(np.float32)
        self.y = r.randn(n, 2).astype(np.float32)

    def batches(self, batch_size, shuffle=True, seed=0, start_step=0):
        idx = (np.random.RandomState(seed).permutation(len(self.x))
               if shuffle else np.arange(len(self.x)))
        for i in range(start_step, len(self.x) // batch_size):
            sl = idx[i * batch_size:(i + 1) * batch_size]
            yield self.x[sl], self.y[sl]


def _mse(y, pred):
    import jax.numpy as jnp

    return jnp.mean((y - pred) ** 2)


_RULES = {1: ((r".", 0),),
          2: ((r"^d1$", 0), (r".", 1)),
          3: ((r"^d1$", 0), (r"^d2$", 1), (r".", 2))}


def _train_cell(num_stages, num_microbatches, mode, ckpt_dir=None,
                iterations=4):
    import jax

    from analytics_zoo_tpu.engine.triggers import (
        MaxIteration,
        SeveralIteration,
    )

    est = _make_estimator()
    if ckpt_dir:
        est.set_checkpoint(ckpt_dir, keep_last=3)
    est.train_pipelined(
        _ArrayDS(), _mse, StagePlan(num_stages, rules=_RULES[num_stages]),
        num_microbatches=num_microbatches, schedule=mode,
        end_trigger=MaxIteration(iterations),
        checkpoint_trigger=SeveralIteration(2) if ckpt_dir else None,
        batch_size=16)
    flat = jax.tree_util.tree_leaves(jax.device_get(est.tstate.params))
    return np.concatenate([np.asarray(a).ravel() for a in flat])


def test_stage_split_alone_is_bitwise():
    """K≥2 with M=1 runs the same math in the same order — the stage cut
    must not perturb a single bit of the trained params."""
    base = _train_cell(1, 1, "1f1b")
    np.testing.assert_array_equal(base, _train_cell(2, 1, "1f1b"))


def test_microbatching_is_ulp_bounded_and_schedules_bitwise():
    """M≥2 re-associates the per-microbatch gradient sums (documented
    ULP bound, measured ≤14 on this model); GPipe and 1F1B run identical
    programs over the identical fixed fold order, so they must match
    bitwise each other."""
    base = _train_cell(1, 1, "1f1b")
    p1 = _train_cell(2, 2, "1f1b")
    pg = _train_cell(2, 2, "gpipe")
    assert max_ulp(base, p1) <= 64
    np.testing.assert_array_equal(p1, pg)


@pytest.mark.slow
def test_parity_matrix_three_stages():
    base = _train_cell(1, 1, "1f1b")
    np.testing.assert_array_equal(base, _train_cell(3, 1, "1f1b"))
    p1 = _train_cell(3, 4, "1f1b")
    pg = _train_cell(3, 4, "gpipe")
    assert max_ulp(base, p1) <= 64
    np.testing.assert_array_equal(p1, pg)


def test_gradient_accumulation_composition_rejected():
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    model = Sequential([Dense(2, input_shape=(4,), name="d1")])
    est = Estimator(model, optax.adam(1e-2), gradient_accumulation=2)
    with pytest.raises(NotImplementedError, match="gradient_accumulation"):
        est.train_pipelined(_ArrayDS(), _mse, StagePlan(1, rules=_RULES[1]),
                            batch_size=16)


# ---------------------------------------------------------------------------
# stage-owned sharded checkpoints
# ---------------------------------------------------------------------------


@pytest.fixture
def inspect_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ckpt_inspect", os.path.join(REPO, "scripts", "ckpt_inspect.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_pipelined_checkpoint_commits_stage_shards(tmp_path, inspect_mod,
                                                   capsys):
    """A pipelined run commits two-phase sharded checkpoints whose shard
    manifest names the owning stage per host dir, and ckpt_inspect
    renders the stage column."""
    from analytics_zoo_tpu.ft import atomic

    ckpt = str(tmp_path / "ck")
    _train_cell(2, 2, "1f1b", ckpt_dir=ckpt)
    committed = atomic.committed_checkpoints(ckpt)
    assert committed, "no checkpoint committed"
    step, path = committed[-1]
    manifest = atomic.read_manifest(path)
    hosts = manifest["shards"]["hosts"]
    assert [h["stage"] for h in hosts] == [0, 1]
    assert manifest["metadata"]["pipeline"]["num_stages"] == 2
    atomic.verify_checksums(path)

    rows = inspect_mod.main([ckpt, "--verify"])
    out = capsys.readouterr().out
    assert rows[-1]["shard_problems"] == []
    assert {r["host"]: r["stage"] for r in rows[-1]["shard_rows"]} == \
        {0: 0, 1: 1}
    assert "stage" in out


# ---------------------------------------------------------------------------
# stage-split serving
# ---------------------------------------------------------------------------


def _load_inference(net, **kw):
    from analytics_zoo_tpu.inference.inference_model import InferenceModel

    return InferenceModel(**kw).do_load_keras(net)


@pytest.fixture
def serve_net():
    return _make_estimator().model


def test_staged_predict_bitwise_with_stage_salted_aot(serve_net, tmp_path,
                                                      rng):
    from analytics_zoo_tpu.inference.aot_cache import AotExecutableCache

    x16 = rng.normal(size=(16, 4)).astype(np.float32)
    x4 = rng.normal(size=(4, 4)).astype(np.float32)
    ref = _load_inference(serve_net)
    staged = _load_inference(serve_net, aot_cache_dir=str(tmp_path))
    staged.set_stage_plan(StagePlan(2, rules=_RULES[2]))
    for b in (4, 16):
        staged.do_optimize(np.zeros((b, 4), np.float32))
    misses0 = staged.cache_stats["misses"]
    for x in (x4, x16):
        np.testing.assert_array_equal(np.asarray(ref.do_predict(x)),
                                      np.asarray(staged.do_predict(x)))
    # warmup covered every (bucket, stage) cell: zero serve-time compiles
    assert staged.cache_stats["misses"] == misses0
    entries = AotExecutableCache(str(tmp_path)).entries()
    # one DISTINCT key per (bucket, stage) — no cross-hits
    assert len({e["key"] for e in entries}) == 4
    assert sorted((e["meta"] or {}).get("stage") for e in entries) == \
        ["0", "0", "1", "1"]


def test_set_stage_plan_rejected_leaves_model_untouched(serve_net, rng):
    x = rng.normal(size=(4, 4)).astype(np.float32)
    m = _load_inference(serve_net)
    ref = np.asarray(m.do_predict(x))
    gen = m._gen
    with pytest.raises(StageAssignmentError):
        m.set_stage_plan(StagePlan(2, rules=((r"^nomatch", 0),)))
    assert m.stage_plan is None
    assert m._gen == gen
    np.testing.assert_array_equal(ref, np.asarray(m.do_predict(x)))


def test_stage_and_sharding_plans_mutually_exclusive(serve_net):
    splan = StagePlan(2, rules=_RULES[2])
    shard = ShardingPlan(MeshConfig.from_spec("data=1"), rules=())
    m = _load_inference(serve_net)
    m.set_stage_plan(splan)
    with pytest.raises(NotImplementedError):
        m.set_sharding_plan(shard)
    m2 = _load_inference(serve_net)
    m2.set_sharding_plan(shard)
    with pytest.raises(NotImplementedError):
        m2.set_stage_plan(splan)


def test_validate_ladder_names_bucket_and_stage():
    plan = StagePlan(2, rules=_RULES[2],
                     mesh=MeshConfig.from_spec("data=4,stage=2"))
    with pytest.raises(StageLadderError, match="bucket 6.*stage 0"):
        plan.validate_ladder((4, 6))
    plan.validate_ladder((4, 8))


def test_engine_register_stage_plan_serves_and_reports(serve_net, rng):
    from analytics_zoo_tpu.serving.engine import BatcherConfig, ServingEngine

    x = rng.normal(size=(8, 4)).astype(np.float32)
    ref = np.asarray(_load_inference(serve_net).do_predict(x))
    eng = ServingEngine()
    try:
        model = _load_inference(serve_net)
        eng.register("pipe", model, example_input=x,
                     config=BatcherConfig(max_batch_size=8, buckets=(4, 8)),
                     stage_plan=StagePlan(2, rules=_RULES[2]))
        np.testing.assert_array_equal(ref, np.asarray(eng.predict("pipe", x)))
        entry = next(iter(eng._models["pipe"].values()))
        assert entry.info()["stages"]["num_stages"] == 2
    finally:
        eng.shutdown()


def test_engine_register_bad_ladder_leaves_model_untouched(serve_net, rng):
    """The PR-11 no-mutation pin, stage flavored: a ladder the StagePlan
    rejects must fail the register BEFORE the model is touched."""
    from analytics_zoo_tpu.serving.engine import BatcherConfig, ServingEngine

    x = rng.normal(size=(8, 4)).astype(np.float32)
    eng = ServingEngine()
    try:
        model = _load_inference(serve_net)
        ref = np.asarray(model.do_predict(x))
        gen = model._gen
        with pytest.raises(StageLadderError, match="bucket 6"):
            eng.register(
                "pipe", model, example_input=x,
                config=BatcherConfig(max_batch_size=8, buckets=(4, 6)),
                stage_plan=StagePlan(
                    2, rules=_RULES[2],
                    mesh=MeshConfig.from_spec("data=4,stage=2")))
        assert model.stage_plan is None
        assert model._gen == gen
        np.testing.assert_array_equal(ref, np.asarray(model.do_predict(x)))
        assert "pipe" not in eng._models
    finally:
        eng.shutdown()


def test_engine_register_duck_typed_model_rejects_stage_plan():
    from analytics_zoo_tpu.serving.engine import ServingEngine

    class Duck:
        def do_predict(self, x):
            return x

    eng = ServingEngine()
    try:
        with pytest.raises(TypeError, match="set_stage_plan"):
            eng.register("duck", Duck(),
                         example_input=np.zeros((2, 2), np.float32),
                         stage_plan=StagePlan(1, rules=_RULES[1]))
    finally:
        eng.shutdown()


# ---------------------------------------------------------------------------
# AOT stage salt
# ---------------------------------------------------------------------------


def test_aot_key_stage_salt_isolates_equal_hlo():
    from analytics_zoo_tpu.inference.aot_cache import AotExecutableCache

    class _Lowered:
        def as_text(self):
            return "HloModule same_for_both_stages"

    low = _Lowered()
    k0 = AotExecutableCache.key_for(low, "args", stage="0")
    k1 = AotExecutableCache.key_for(low, "args", stage="1")
    unstaged = AotExecutableCache.key_for(low, "args")
    assert len({k0, k1, unstaged}) == 3
    # default "" hashes to the pre-stage key: existing caches stay warm
    assert unstaged == AotExecutableCache.key_for(low, "args", stage="")


# ---------------------------------------------------------------------------
# chaos site + kill → resume canary
# ---------------------------------------------------------------------------


def test_pipeline_chaos_point_registered(monkeypatch):
    from analytics_zoo_tpu.ft import chaos

    assert "pipeline_mid_schedule_kill" in chaos.PIPELINE_POINTS
    monkeypatch.setenv("AZOO_FT_CHAOS", "pipeline_mid_schedule_kill")
    assert chaos.active_point() == "pipeline_mid_schedule_kill"
    monkeypatch.setenv("AZOO_FT_CHAOS", "no_such_pipeline_point")
    with pytest.raises(ValueError, match="no_such_pipeline_point"):
        chaos.active_point()


def _run_worker(ckpt_dir, out_path, extra_env):
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    for k in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP"):
        env.pop(k, None)
    env.update(extra_env)
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "tests", "_pipeline_worker.py"),
         str(ckpt_dir), str(out_path)],
        env=env, capture_output=True, text=True, timeout=240)
    doc = None
    if os.path.isfile(out_path):
        with open(out_path) as f:
            doc = json.load(f)
    return proc.returncode, doc, proc.stderr[-2000:]


def _kill_resume_drill(tmp_path, worker_env, skip=14):
    """ref run → chaos-armed kill (must die 43 mid-schedule) →
    disarmed resume (must finish bitwise the ref).

    ``skip`` positions the kill: the site fires (2K-1)·M times per
    step, and it must land mid-schedule in step 3 — after the
    iteration-2 checkpoint committed, with real work left to redo."""
    from analytics_zoo_tpu.ft import atomic, chaos

    rc, ref, err = _run_worker(tmp_path / "ck_ref", tmp_path / "ref.json",
                               worker_env)
    assert rc == 0 and ref is not None, (rc, err)

    kill_ck = tmp_path / "ck_kill"
    rc, _doc, err = _run_worker(kill_ck, tmp_path / "kill.json", {
        **worker_env,
        "AZOO_FT_CHAOS": "pipeline_mid_schedule_kill",
        "AZOO_FT_CHAOS_SKIP": str(skip)})
    assert rc == chaos.EXIT_CODE, (rc, err)
    committed = [s for s, _ in atomic.committed_checkpoints(str(kill_ck))]
    assert committed and committed[-1] < ref["iteration"]

    rc, res, err = _run_worker(kill_ck, tmp_path / "resume.json", worker_env)
    assert rc == 0 and res is not None, (rc, err)
    assert res["iteration"] == ref["iteration"]
    assert res["params"] == ref["params"], "resume diverged from reference"


def test_kill_mid_schedule_resumes_bitwise(tmp_path):
    _kill_resume_drill(tmp_path, {})


@pytest.mark.slow
@pytest.mark.parametrize("worker_env,skip", [
    # K=3 M=4 fires 20 events/step: 45 lands mid-step-3
    ({"PIPE_STAGES": "3", "PIPE_MICROBATCHES": "4"}, 45),
    ({"PIPE_SCHEDULE": "gpipe"}, 14),
], ids=["k3m4", "gpipe"])
def test_kill_matrix(tmp_path, worker_env, skip):
    _kill_resume_drill(tmp_path, worker_env, skip=skip)
