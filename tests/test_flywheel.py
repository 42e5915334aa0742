"""Online-learning flywheel (ISSUE 15): error-diffusion capture on the
serving hot path, atomic capture segments, replay into Pipeline,
warm-start incremental retrain with a checkpointed consumption
high-water mark, and canary-gated promotion with quarantine-on-rollback.
The subprocess mid-retrain-kill matrix (bitwise-identical resumed
candidate) lives at the bottom; one cell runs unmarked as the canary."""

import json
import os
import shutil
import subprocess
import sys
import threading
import time
import zlib
from concurrent.futures import Future

import numpy as np
import pytest

from analytics_zoo_tpu.batch import writers
from analytics_zoo_tpu.flywheel import (
    CaptureConfig,
    CaptureSource,
    CaptureTap,
    FlywheelController,
    FlywheelTrainer,
    RetrainConfig,
)
from analytics_zoo_tpu.flywheel.capture import (
    _Sampler,
    committed_segments,
    is_quarantined,
    quarantine_segment,
    segment_dirs,
)
from analytics_zoo_tpu.ft import atomic, chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_flywheel_worker.py")


class _Boom(Exception):
    """Stands in for os._exit in in-process chaos tests."""


@pytest.fixture
def chaos_raise(monkeypatch):
    def arm(point, skip=0):
        chaos.reset()
        monkeypatch.setenv("AZOO_FT_CHAOS", point)
        monkeypatch.setenv("AZOO_FT_CHAOS_SKIP", str(skip))
        monkeypatch.setattr(chaos, "fail",
                            lambda p: (_ for _ in ()).throw(_Boom(p)))
    yield arm
    chaos.reset()


@pytest.fixture(autouse=True)
def _disarm_serving_chaos():
    yield
    chaos.reset()


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


def _wait_until(cond, timeout=5.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if cond():
            return True
        time.sleep(interval)
    return cond()


def _offer_rows(tap, n, model="m", version="1", start=0, dim=3):
    """Drive the tap offline: pre-built futures, deterministic rows."""
    for i in range(start, start + n):
        fut = Future()
        x = (np.arange(dim, dtype=np.float32) + i)[None, :]
        tap.offer(model, version, x, fut, trace=f"t{i:04d}")
        fut.set_result(np.full((1, 2), float(i), np.float32))


# ---------------------------------------------------------------------------
# satellite 1: ShardWriter time-based roll
# ---------------------------------------------------------------------------


def test_shard_writer_time_roll_commits_partial_shard(tmp_path):
    clock = _FakeClock()
    committed = []
    w = writers.JsonlShardWriter(str(tmp_path), rows_per_shard=100,
                                 roll_interval_s=5.0, clock=clock,
                                 on_shard=committed.append)
    w.append(np.array([0.0, 1.0]))
    clock.advance(4.9)
    assert w.maybe_roll() is False  # quiet interval not yet reached
    clock.advance(0.2)
    assert w.maybe_roll() is True
    # the partial shard went through the full commit protocol
    assert len(committed) == 1 and committed[0]["rows"] == 2
    doc = writers.read_manifest(str(tmp_path))
    assert [s["rows"] for s in doc["shards"]] == [2]
    # appends reset the quiet timer; an empty buffer never rolls
    assert w.maybe_roll() is False
    w.append(np.array([2.0]))
    clock.advance(2.0)
    assert w.maybe_roll() is False
    clock.advance(3.5)
    assert w.maybe_roll() is True
    w.finalize()
    assert list(writers.iter_output_rows(str(tmp_path))) == [0.0, 1.0, 2.0]


def test_shard_writer_roll_validation_and_force(tmp_path):
    with pytest.raises(ValueError, match="roll_interval_s"):
        writers.JsonlShardWriter(str(tmp_path / "a"), roll_interval_s=0)
    w = writers.JsonlShardWriter(str(tmp_path / "b"), rows_per_shard=100)
    assert w.roll() is False  # nothing buffered
    w.append(np.array([1.0]))
    assert w.roll() is True   # explicit force needs no interval config
    assert w.maybe_roll() is False  # no roll_interval_s -> time roll off
    w.finalize()
    with pytest.raises(RuntimeError):
        w.roll()


# ---------------------------------------------------------------------------
# satellite 2: concurrent-reader hardening
# ---------------------------------------------------------------------------


def test_readers_on_live_capture_dir_see_only_committed_shards(tmp_path):
    """Regression: reading a directory while a writer commits shards must
    return only manifest-listed shards — no `.tmp` debris, no torn
    manifest reads — at every point in the interleaving."""
    d = str(tmp_path)
    stop = threading.Event()
    failures = []

    def write():
        w = writers.JsonlShardWriter(d, rows_per_shard=2)
        i = 0
        while not stop.is_set():
            w.append(np.array([float(i)]))
            i += 1
        w.finalize()

    def read():
        while not stop.is_set():
            try:
                doc = writers.read_manifest(d)
                if doc is None:
                    continue
                for rec in doc["shards"]:
                    if not os.path.isfile(os.path.join(d, rec["file"])):
                        failures.append(f"listed shard missing: {rec}")
                    if rec["file"].endswith(".tmp"):
                        failures.append(f"tmp debris listed: {rec}")
            except Exception as e:  # noqa: BLE001 — the regression itself
                failures.append(repr(e))

    writer = threading.Thread(target=write)
    readers = [threading.Thread(target=read) for _ in range(2)]
    writer.start()
    for r in readers:
        r.start()
    time.sleep(0.5)
    stop.set()
    writer.join(timeout=10)
    for r in readers:
        r.join(timeout=10)
    assert not failures, failures[:5]
    # after finalize the full output reads back contiguously
    rows = list(writers.iter_output_rows(d))
    assert rows == [float(i) for i in range(len(rows))] and rows


def test_iter_output_rows_raises_loud_on_truncated_shard(tmp_path):
    w = writers.JsonlShardWriter(str(tmp_path), rows_per_shard=2)
    w.append(np.array([0.0, 1.0, 2.0, 3.0]))
    w.finalize()
    shard = os.path.join(str(tmp_path), "shard_00000.jsonl")
    with open(shard) as f:
        first_line = f.readline()
    with open(shard, "w") as f:
        f.write(first_line)  # drop row 1: fewer rows than the manifest
    with pytest.raises(writers.ShardCorruptError):
        list(writers.iter_output_rows(str(tmp_path)))


def test_read_manifest_retries_through_transient_unreadability(tmp_path,
                                                               monkeypatch):
    w = writers.JsonlShardWriter(str(tmp_path), rows_per_shard=1)
    w.append(np.array([0.0]))
    w.finalize()
    real_open = open
    calls = [0]

    def flaky_open(path, *a, **kw):
        if str(path).endswith(writers.MANIFEST) and calls[0] == 0:
            calls[0] += 1
            raise OSError("transient EBUSY")
        return real_open(path, *a, **kw)

    monkeypatch.setattr("builtins.open", flaky_open)
    doc = writers.read_manifest(str(tmp_path))
    assert doc is not None and len(doc["shards"]) == 1


# ---------------------------------------------------------------------------
# sampler determinism (satellite 4)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("fraction,n", [(0.01, 1000), (0.1, 997),
                                        (0.333, 100), (1.0, 50)])
def test_error_diffusion_sampler_exact_count(fraction, n):
    s = _Sampler(fraction)
    fired = sum(s.fire() for _ in range(n))
    assert abs(fired - int(fraction * n)) <= 1, (fired, fraction, n)


def test_error_diffusion_sampler_concurrency_insensitive():
    s = _Sampler(0.07)
    per_thread = 500
    threads = 8
    counts = [0] * threads

    def hammer(slot):
        acc = 0
        for _ in range(per_thread):
            if s.fire():
                acc += 1
        counts[slot] = acc

    ts = [threading.Thread(target=hammer, args=(i,)) for i in range(threads)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = threads * per_thread
    assert abs(sum(counts) - int(0.07 * total)) <= 1, counts


def test_sampler_rejects_bad_fraction():
    for bad in (0.0, -0.1, 1.5):
        with pytest.raises(ValueError):
            _Sampler(bad)


@pytest.mark.parametrize("fraction,per_key", [(0.1, 1000), (0.25, 997),
                                              (1.0, 40)])
def test_keyed_sampler_exact_per_route_key(fraction, per_key):
    """Sticky-routing sampling bias fix: a sampler shared by sticky
    route keys holds the error-diffusion exactness PER KEY — every key
    contributes floor(f·N_k)±1 of its own N_k requests even when the
    streams interleave in the worst (round-robin) order."""
    s = _Sampler(fraction)
    keys = [f"tenant-{k}" for k in range(5)]
    counts = dict.fromkeys(keys, 0)
    for _ in range(per_key):
        for k in keys:
            counts[k] += s.fire(k)
    for k, n in counts.items():
        assert abs(n - int(fraction * per_key)) <= 1, counts
    # keyless traffic still rides the single global accumulator
    fired = sum(s.fire() for _ in range(per_key))
    assert abs(fired - int(fraction * per_key)) <= 1


def test_keyed_sampler_lru_bound_and_determinism():
    s = _Sampler(0.5)
    # a re-seen key restarts from its deterministic hash phase after
    # eviction — the fire pattern is a pure function of (key, N)
    pattern = [s.fire("k") for _ in range(8)]
    for i in range(_Sampler.MAX_KEYS + 64):  # churn k out of the LRU
        s.fire(f"churn-{i}")
    assert len(s._keyed) <= _Sampler.MAX_KEYS
    assert [s.fire("k") for _ in range(8)] == pattern


# ---------------------------------------------------------------------------
# capture tap
# ---------------------------------------------------------------------------


def test_capture_tap_writes_committed_replayable_segment(tmp_path):
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0,
                                   rows_per_shard=8, idle_poll_s=0.01),
                     clock=lambda: 1700000000.0)
    tap.enable("m")
    _offer_rows(tap, 20)
    assert tap.flush()
    segment = tap.rotate("m")
    tap.close()
    assert segment is not None and writers.job_complete(segment)
    assert committed_segments(str(tmp_path / "m")) == [segment]
    rows = list(writers.iter_output_rows(segment))
    assert len(rows) == 20
    # canonical capture record: inputs, dtypes, prediction, version,
    # trace, timestamp — everything replay/forensics needs
    r = rows[0]
    assert r["v"] == "1" and r["t"] == "t0000" and r["ts"] == 1700000000.0
    assert np.dtype(r["xd"][0]) == np.float32
    assert np.dtype(r["yd"][0]) == np.float32
    np.testing.assert_array_equal(np.asarray(r["x"][0], np.float32),
                                  [0.0, 1.0, 2.0])


def test_capture_tap_drops_failed_predictions_and_counts_them(tmp_path):
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0,
                                   idle_poll_s=0.01))
    tap.enable("m")
    before = tap.metrics["dropped"].labels(reason="predict_failed").value
    fut = Future()
    tap.offer("m", "1", [np.ones((1, 3), np.float32)], fut)
    fut.set_exception(RuntimeError("model exploded"))
    ok = Future()
    tap.offer("m", "1", [np.ones((1, 3), np.float32)], ok)
    ok.set_result(np.zeros((1, 2), np.float32))
    tap.flush()
    segment = tap.rotate("m")
    tap.close()
    assert len(list(writers.iter_output_rows(segment))) == 1
    assert tap.metrics["dropped"].labels(
        reason="predict_failed").value == before + 1


def test_capture_tap_disabled_model_not_sampled(tmp_path):
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0))
    tap.enable("m")
    tap.disable("m")
    fut = Future()
    assert tap.offer("m", "1", [np.ones((1, 3), np.float32)], fut) is False
    tap.close()
    assert segment_dirs(str(tmp_path / "m")) == []


def test_capture_tap_resumes_unfinalized_segment_after_restart(tmp_path):
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0,
                                   rows_per_shard=4, idle_poll_s=0.01))
    tap.enable("m")
    _offer_rows(tap, 6)
    tap.flush()
    tap.close(finalize=False)  # crash: segment left uncommitted
    assert committed_segments(str(tmp_path / "m")) == []
    tap2 = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0,
                                    rows_per_shard=4, idle_poll_s=0.01))
    tap2.enable("m")
    _offer_rows(tap2, 6, start=6)
    tap2.flush()
    segment = tap2.rotate("m")
    tap2.close()
    # same segment_00000 resumed, not a parallel second segment
    assert os.path.basename(segment) == "segment_00000"
    assert len(segment_dirs(str(tmp_path / "m"))) == 1
    rows = list(writers.iter_output_rows(segment))
    # the 4-row shard committed before the crash survives; the 2 buffered
    # rows died with the process (they were never durable)
    assert [r["t"] for r in rows] \
        == [f"t{i:04d}" for i in range(4)] + [f"t{i:04d}" for i in
                                              range(6, 12)]


def test_capture_torn_shard_then_writer_resume(tmp_path, chaos_raise):
    """The capture_writer_torn chaos point: a shard commit dies mid-write;
    the staging debris is invisible to readers and the resumed writer
    continues at the committed row offset."""
    from analytics_zoo_tpu.flywheel.capture import CaptureShardWriter

    d = str(tmp_path / "seg")
    chaos_raise("capture_writer_torn", skip=1)  # second shard commit dies
    w = CaptureShardWriter(d, rows_per_shard=2)
    w.append([{"i": 0}, {"i": 1}])  # shard 0 commits
    with pytest.raises(_Boom):
        w.append([{"i": 2}, {"i": 3}])  # shard 1 torn mid-write
    chaos.reset()
    doc = writers.read_manifest(d)
    assert [s["rows"] for s in doc["shards"]] == [2]  # torn shard unlisted
    w2 = CaptureShardWriter(d, rows_per_shard=2)  # restart sweeps .tmp
    assert not [f for f in os.listdir(d) if f.endswith(".tmp")]
    w2.append([{"i": 2}, {"i": 3}])
    w2.finalize()
    assert [r["i"] for r in writers.iter_output_rows(d)] == [0, 1, 2, 3]


# ---------------------------------------------------------------------------
# no-double-capture: the hook sits on the real-submit path only
# ---------------------------------------------------------------------------


def _engine_with_tap(tmp_path, **engine_kw):
    from analytics_zoo_tpu.serving import BatcherConfig, ServingEngine

    class Doubler:
        def do_predict(self, x):
            return np.asarray(x, np.float32) * 2.0

    engine = ServingEngine(**engine_kw)
    engine.register("m", Doubler(), np.ones((1, 3), np.float32),
                    config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0),
                    version="1")
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path / "cap"),
                                   fraction=1.0, idle_poll_s=0.01))
    tap.enable("m")
    engine.set_capture(tap)
    return engine, tap


@pytest.mark.parametrize("clients", [1, 8])
def test_capture_counts_each_request_once(tmp_path, clients):
    """One caller, then eight at once: with the tap on every predict is
    answered (no client error) and lands in the segment exactly once."""
    engine, tap = _engine_with_tap(tmp_path)
    per_client, errors = 10, []
    try:
        x = np.ones((1, 3), np.float32)
        sampled0 = tap.metrics["sampled"].value

        def client():
            for _ in range(per_client):
                try:
                    np.testing.assert_array_equal(
                        engine.predict("m", x), x * 2.0)
                except Exception as e:  # noqa: BLE001 — counted, must be 0
                    errors.append(e)

        threads = [threading.Thread(target=client) for _ in range(clients)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
        assert not errors, errors
        total = clients * per_client
        assert tap.metrics["sampled"].value - sampled0 == total
        tap.flush()
        segment = tap.rotate("m")
        rows = list(writers.iter_output_rows(segment))
        assert len(rows) == total
        assert len({r["t"] for r in rows}) == total
    finally:
        tap.close()
        engine.shutdown()


def test_cache_hits_never_reach_the_tap(tmp_path):
    from analytics_zoo_tpu.serving.result_cache import ResultCacheConfig

    engine, tap = _engine_with_tap(tmp_path,
                                   result_cache=ResultCacheConfig())
    try:
        x = np.ones((1, 3), np.float32)
        engine.predict("m", x)          # miss: submitted, sampled
        for _ in range(5):
            engine.predict("m", x)      # hits: never submitted
        tap.flush()
        segment = tap.rotate("m")
        rows = list(writers.iter_output_rows(segment))
        assert len(rows) == 1, [r["t"] for r in rows]
    finally:
        tap.close()
        engine.shutdown()


def test_shadow_mirrors_never_reach_the_tap(tmp_path):
    from analytics_zoo_tpu.serving import BatcherConfig

    class Tripler:
        def do_predict(self, x):
            return np.asarray(x, np.float32) * 3.0

    engine, tap = _engine_with_tap(tmp_path)
    try:
        engine.register("m", Tripler(), np.ones((1, 3), np.float32),
                        config=BatcherConfig(max_batch_size=8,
                                             max_wait_ms=1.0),
                        version="2", shadow=True, shadow_fraction=1.0)
        x = np.ones((1, 3), np.float32)
        for _ in range(8):
            np.testing.assert_array_equal(engine.predict("m", x), x * 2.0)
        # every request was mirrored to the shadow; the tap saw each
        # request exactly once, and only the serving version's output
        _wait_until(lambda: tap.metrics["sampled"].value >= 8)
        tap.flush()
        segment = tap.rotate("m")
        rows = list(writers.iter_output_rows(segment))
        assert len(rows) == 8
        assert {r["v"] for r in rows} == {"1"}
        for r in rows:
            np.testing.assert_array_equal(
                np.asarray(r["y"][0], np.float32), x[0] * 2.0)
    finally:
        tap.close()
        engine.shutdown()


# ---------------------------------------------------------------------------
# replay source
# ---------------------------------------------------------------------------


def _make_segments(tmp_path, counts=(10, 6)):
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path), fraction=1.0,
                                   rows_per_shard=4, idle_poll_s=0.01),
                     clock=lambda: 1700000000.0)
    tap.enable("m")
    segs, start = [], 0
    for n in counts:
        _offer_rows(tap, n, start=start)
        tap.flush()
        segs.append(tap.rotate("m"))
        start += n
    tap.close()
    return segs


def test_capture_source_replays_all_rows_with_dtypes(tmp_path):
    segs = _make_segments(tmp_path)
    src = CaptureSource(segs)
    assert len(src) == 16
    x, y = src.fetch(0)
    assert x.dtype == np.float32 and y.dtype == np.float32
    np.testing.assert_array_equal(x, [0.0, 1.0, 2.0])
    np.testing.assert_array_equal(y, np.zeros(2, np.float32))
    # stable ordering: segment order then row order
    xs = [float(src.fetch(i)[0][0]) for i in range(16)]
    assert xs == [float(i) for i in range(16)]


def test_capture_source_model_dir_discovers_committed_only(tmp_path):
    segs = _make_segments(tmp_path, counts=(4, 4, 4))
    quarantine_segment(segs[1], reason="test")
    src = CaptureSource(str(tmp_path / "m"))
    assert len(src) == 8  # quarantined middle segment excluded
    xs = sorted(float(src.fetch(i)[0][0]) for i in range(8))
    assert xs == [0.0, 1.0, 2.0, 3.0, 8.0, 9.0, 10.0, 11.0]


def test_capture_source_rejects_quarantined_and_uncommitted_explicit(
        tmp_path):
    segs = _make_segments(tmp_path, counts=(4,))
    quarantine_segment(segs[0], reason="test")
    with pytest.raises(ValueError, match="quarantined"):
        CaptureSource(segs)
    with pytest.raises(ValueError, match="no committed capture segments"):
        CaptureSource(str(tmp_path / "nope"))


def test_capture_source_corrupt_shard_is_loud(tmp_path):
    segs = _make_segments(tmp_path, counts=(8,))
    shard = os.path.join(segs[0], "shard_00001.jsonl")
    data = open(shard, "rb").read()
    with open(shard, "wb") as f:
        f.write(data[:-3] + b"!!!")
    src = CaptureSource(segs)
    src.fetch(0)  # first shard intact
    with pytest.raises(writers.ShardCorruptError):
        src.fetch(6)  # second shard fails its CRC at read time


def test_pipeline_from_capture_deterministic_batches(tmp_path):
    from analytics_zoo_tpu.data.pipeline import Pipeline

    segs = _make_segments(tmp_path)
    a = Pipeline.from_capture(segs, seed=3).batch(4)
    b = Pipeline.from_capture(segs, seed=3).batch(4)
    batches_a = [batch[0] for batch in a.train_batches(seed=0)]
    batches_b = [batch[0] for batch in b.train_batches(seed=0)]
    assert len(batches_a) == 4
    for xa, xb in zip(batches_a, batches_b):
        np.testing.assert_array_equal(xa, xb)


# ---------------------------------------------------------------------------
# quarantine + inspection tooling (satellite 3)
# ---------------------------------------------------------------------------


def test_quarantine_is_idempotent_and_filters_replay(tmp_path):
    (seg,) = _make_segments(tmp_path, counts=(4,))
    assert not is_quarantined(seg)
    quarantine_segment(seg, reason="rollback of candidate 9")
    quarantine_segment(seg, reason="again")
    assert is_quarantined(seg)
    assert committed_segments(str(tmp_path / "m")) == []
    with open(os.path.join(seg, "QUARANTINE")) as f:
        assert "again" in json.load(f)["reason"]


@pytest.fixture()
def inspect_mod():
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "ckpt_inspect", os.path.join(REPO, "scripts", "ckpt_inspect.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_ckpt_inspect_capture_mode(tmp_path, inspect_mod, capsys):
    (seg,) = _make_segments(tmp_path, counts=(6,))
    inspect_mod.main([seg, "--verify"])
    out = capsys.readouterr().out
    assert "versions" in out and "times" in out
    assert "capture segment for model 'm': COMMITTED" in out
    assert "1" in out  # the routed version column
    quarantine_segment(seg, reason="test")
    inspect_mod.main([seg])
    assert "QUARANTINED" in capsys.readouterr().out


def test_ckpt_inspect_capture_corrupt_exits_1(tmp_path, inspect_mod,
                                              capsys):
    (seg,) = _make_segments(tmp_path, counts=(6,))
    shard = os.path.join(seg, "shard_00000.jsonl")
    with open(shard, "ab") as f:
        f.write(b"garbage\n")
    with pytest.raises(SystemExit) as exc:
        inspect_mod.main([seg, "--verify"])
    assert exc.value.code == 1
    assert "CORRUPT" in capsys.readouterr().err


# ---------------------------------------------------------------------------
# trainer: warm start, high-water mark, rollback cleanup
# ---------------------------------------------------------------------------


def _seed_incumbent(ckpt_dir, in_dim=3, out_dim=2):
    import optax

    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    def build():
        return Estimator(
            Sequential([Dense(out_dim, input_shape=(in_dim,))]),
            optax.sgd(0.05))

    rng = np.random.default_rng(0)
    est = build()
    est.set_checkpoint(str(ckpt_dir), keep_last=8, asynchronous=False)
    est.train(ArrayFeatureSet(
        rng.normal(size=(16, in_dim)).astype(np.float32),
        rng.normal(size=(16, out_dim)).astype(np.float32)),
        objectives.mean_squared_error, batch_size=8)
    return build, objectives.mean_squared_error


def _trainer(tmp_path, build, criterion, **kw):
    base = dict(capture_dir=str(tmp_path / "m"),
                checkpoint_dir=str(tmp_path / "ckpts"),
                batch_size=8, checkpoint_every=2, keep_last=8, min_rows=4)
    base.update(kw)
    return FlywheelTrainer(build, criterion, RetrainConfig(**base))


def test_trainer_warm_starts_and_checkpoints_high_water_mark(tmp_path):
    build, crit = _seed_incumbent(tmp_path / "ckpts")
    _make_segments(tmp_path, counts=(10,))
    trainer = _trainer(tmp_path, build, crit)
    base = trainer.incumbent_step()
    step = trainer.run_once()
    assert step is not None and step > base
    # warm start: exactly one epoch over 10 rows (2 iterations)
    assert step == base + 2
    assert trainer.consumed_segments() == {"segment_00000"}
    assert trainer.pending_segments() == []
    # no new data -> no cycle, no state churn
    assert trainer.run_once() is None
    assert trainer.last_consumed == []
    # fresh data -> next incremental cycle from the new incumbent
    _make_segments(tmp_path, counts=(10,))  # writes segment_00001... via tap
    step2 = trainer.run_once()
    assert step2 == step + 2
    assert trainer.consumed_segments() == {"segment_00000",
                                           "segment_00001"}


def test_trainer_skips_below_min_rows(tmp_path):
    build, crit = _seed_incumbent(tmp_path / "ckpts")
    _make_segments(tmp_path, counts=(2,))
    trainer = _trainer(tmp_path, build, crit, min_rows=100)
    assert trainer.run_once() is None
    assert trainer.pending_segments() != []  # still pending, not consumed


def test_trainer_discard_candidates_after(tmp_path):
    build, crit = _seed_incumbent(tmp_path / "ckpts")
    _make_segments(tmp_path, counts=(10,))
    trainer = _trainer(tmp_path, build, crit)
    base = trainer.incumbent_step()
    step = trainer.run_once()
    removed = trainer.discard_candidates_after(base)
    assert any(p.endswith(f"ckpt_{step}") for p in removed)
    assert trainer.incumbent_step() == base


def test_trainer_mid_retrain_kill_in_process(tmp_path, chaos_raise):
    """In-process cousin of the subprocess matrix: the chaos point fires
    at a trigger evaluation, the partial cycle leaves NO high-water-mark
    advance, and the rerun completes the identical cycle."""
    build, crit = _seed_incumbent(tmp_path / "ckpts")
    _make_segments(tmp_path, counts=(16,))
    trainer = _trainer(tmp_path, build, crit)
    chaos_raise("flywheel_mid_retrain_kill", skip=1)
    with pytest.raises(_Boom):
        trainer.run_once()
    chaos.reset()
    for var in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP"):
        os.environ.pop(var, None)
    assert trainer.consumed_segments() == set()  # hwm never moved
    step = _trainer(tmp_path, build, crit).run_once()
    assert step is not None
    assert trainer.consumed_segments() == {"segment_00000"}


# ---------------------------------------------------------------------------
# estimator warm-start regression: epoch-boundary position on new data
# ---------------------------------------------------------------------------


def test_epoch_boundary_restore_accepts_different_stream(tmp_path):
    """A restored epoch-boundary pipeline position (position_batches=0)
    must not veto warm-starting on different data — that IS the flywheel
    cycle. A mid-epoch position on a different stream must still raise."""
    import optax

    from analytics_zoo_tpu.data.pipeline import Pipeline
    from analytics_zoo_tpu.data.sources import ArraySource
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    def pipe(n):
        rng = np.random.default_rng(n)
        return Pipeline(ArraySource(
            rng.normal(size=(n, 3)).astype(np.float32),
            rng.normal(size=(n, 2)).astype(np.float32)))

    def build():
        return Estimator(Sequential([Dense(2, input_shape=(3,))]),
                         optax.sgd(0.05))

    est = build()
    est.set_checkpoint(str(tmp_path), keep_last=4, asynchronous=False)
    est.train(pipe(16), objectives.mean_squared_error, batch_size=8)
    # warm start on a DIFFERENT-SIZED stream: epoch-boundary position
    est2 = build()
    est2.set_checkpoint(str(tmp_path), keep_last=4, asynchronous=False)
    est2.train(pipe(24), objectives.mean_squared_error, batch_size=8,
               auto_resume=True)
    assert est2.run_state.epoch == 2
    # a MID-EPOCH position on a mismatched stream stays loud
    est3 = build()
    est3.set_checkpoint(str(tmp_path), keep_last=4, asynchronous=False)
    est3._restored_data_state = {"version": 1, "position_batches": 2,
                                 "num_samples": 16, "batch_size": 8,
                                 "rng_seed": None, "epoch_seed": 1,
                                 "samples_seen": 16,
                                 "shuffle_buffer": None,
                                 "shuffle_seed": None}
    with pytest.raises(ValueError, match="different stream"):
        est3.train(pipe(24), objectives.mean_squared_error, batch_size=8)


# ---------------------------------------------------------------------------
# controller: the closed loop
# ---------------------------------------------------------------------------


def _closed_loop(tmp_path, ladder=(0.25, 1.0)):
    from analytics_zoo_tpu.serving import (
        BatcherConfig, RolloutConfig, ServingEngine,
    )

    build, crit = _seed_incumbent(tmp_path / "ckpts", in_dim=3)

    class Lin:
        def __init__(self, w, b):
            self.w, self.b = w, b

        def do_predict(self, x):
            return np.asarray(x, np.float32) @ self.w + self.b

    def build_model(path):
        flat, _ = atomic.read_checkpoint(path)
        d = dict(flat)
        w = next(v for v in d.values() if getattr(v, "ndim", 0) == 2)
        b = next(v for v in d.values() if getattr(v, "ndim", 0) == 1)
        return Lin(np.asarray(w), np.asarray(b))

    engine = ServingEngine(rollout=RolloutConfig(
        ladder=ladder, min_requests=4, auto_evaluate=False))
    tap = CaptureTap(CaptureConfig(directory=str(tmp_path / "cap"),
                                   fraction=1.0, rows_per_shard=16,
                                   roll_interval_s=0.1, idle_poll_s=0.02))
    engine.set_capture(tap)
    trainer = FlywheelTrainer(build, crit, RetrainConfig(
        capture_dir=str(tmp_path / "cap" / "m"),
        checkpoint_dir=str(tmp_path / "ckpts"),
        batch_size=8, checkpoint_every=2, min_rows=8))
    ctrl = FlywheelController(
        engine, "m", tap, trainer, build_model,
        example_input=np.ones((1, 3), np.float32),
        config=BatcherConfig(max_batch_size=8, max_wait_ms=1.0))
    return engine, tap, trainer, ctrl


def test_controller_closed_loop_promotes_with_zero_client_errors(tmp_path):
    """The acceptance scenario: serve, capture, retrain, auto-promote
    through the canary ladder — no client-visible errors anywhere."""
    engine, tap, trainer, ctrl = _closed_loop(tmp_path)
    try:
        incumbent = str(trainer.incumbent_step())
        assert engine.stats()["m"]["latest"] == incumbent
        x = np.ones((1, 3), np.float32)
        errors = [0]
        for _ in range(40):
            engine.predict("m", x)

        def traffic():
            for _ in range(8):
                try:
                    engine.predict("m", x)
                except Exception:  # noqa: BLE001 — counted, must be 0
                    errors[0] += 1

        report = ctrl.run_cycle(traffic_fn=traffic, timeout_s=60)
        assert report.outcome == "promoted", report
        assert errors[0] == 0
        assert engine.stats()["m"]["latest"] == str(report.candidate_step)
        assert report.quarantined == []
        # consumed data is recorded; nothing pending
        assert trainer.pending_segments() == []
        # observability: cycle + capture metric families rendered
        from analytics_zoo_tpu.common.observability import get_registry

        text = get_registry().render()
        assert "zoo_flywheel_cycles_total" in text
        assert "zoo_capture_shards_committed_total" in text
    finally:
        ctrl.close()
        tap.close()
        engine.shutdown()


def test_controller_no_data_cycle(tmp_path):
    engine, tap, trainer, ctrl = _closed_loop(tmp_path)
    try:
        report = ctrl.run_cycle(timeout_s=5)
        assert report.outcome == "no_data"
        assert report.candidate_step is None
    finally:
        ctrl.close()
        tap.close()
        engine.shutdown()


def test_controller_rollback_quarantines_capture_data(tmp_path):
    """A candidate the gates reject: incumbent keeps serving, the cycle's
    capture segments are quarantined, the candidate's checkpoints are
    deleted, and the next cycle sees no_data — poisoned data cannot
    re-enter through either door."""
    engine, tap, trainer, ctrl = _closed_loop(tmp_path)
    try:
        incumbent = str(trainer.incumbent_step())
        x = np.ones((1, 3), np.float32)
        for _ in range(40):
            engine.predict("m", x)
        armed = [False]

        def traffic():
            if not armed[0]:
                desc = engine.rollout_controller().describe("m")
                if desc is not None and desc.get("canary"):
                    chaos.arm_serving("canary_errors",
                                      tag=f"m@{desc['canary']}")
                    armed[0] = True
            for _ in range(8):
                try:
                    engine.predict("m", x)
                except Exception:  # noqa: BLE001 — canary-routed request
                    pass

        base = trainer.incumbent_step()
        report = ctrl.run_cycle(traffic_fn=traffic, timeout_s=60)
        assert armed[0], "canary never appeared"
        assert report.outcome == "rolled_back", report
        assert report.rollback_reason in ("breaker_open", "error_rate")
        # incumbent still serving, candidate gone
        assert engine.stats()["m"]["latest"] == incumbent
        assert trainer.incumbent_step() == base
        # the cycle's data is quarantined and will not replay
        assert report.quarantined and all(
            is_quarantined(s) for s in report.quarantined)
        assert trainer.pending_segments() == []
        chaos.reset()
        follow_up = ctrl.run_cycle(timeout_s=5)
        assert follow_up.outcome == "no_data"
        # clients see the incumbent, healthy
        np.testing.assert_array_equal(
            engine.predict("m", x).shape, (1, 2))
    finally:
        ctrl.close()
        tap.close()
        engine.shutdown()


# ---------------------------------------------------------------------------
# subprocess mid-retrain-kill matrix: bitwise-identical resumed candidate
# ---------------------------------------------------------------------------


def _worker_env(chaos_point=None, skip=0) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = ""
    env.pop("AZOO_FT_CHAOS", None)
    env.pop("AZOO_FT_CHAOS_SKIP", None)
    if chaos_point is not None:
        env["AZOO_FT_CHAOS"] = chaos_point
        env["AZOO_FT_CHAOS_SKIP"] = str(skip)
    return env


def _run_worker(mode, root, out, env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, WORKER, mode, str(root), str(out)],
        env=env, capture_output=True, text=True, timeout=240)


@pytest.fixture(scope="module")
def seeded_root(tmp_path_factory):
    """One seeded starting state (incumbent + committed capture segment)
    copied per cell so every retrain starts from identical bytes."""
    d = tmp_path_factory.mktemp("fly_seed")
    out = d / "seed.json"
    proc = _run_worker("seed", d / "root", out, _worker_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    return d / "root"


def _retrain_cell(tmp_path, seeded_root, kill_skip):
    ref_root = tmp_path / "ref"
    chaos_root = tmp_path / "chaos"
    shutil.copytree(seeded_root, ref_root)
    shutil.copytree(seeded_root, chaos_root)
    # reference: one uninterrupted retrain cycle
    ref_out = tmp_path / "ref.json"
    proc = _run_worker("retrain", ref_root, ref_out, _worker_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    # chaos: the same cycle hard-killed at a trigger evaluation...
    chaos_out = tmp_path / "chaos.json"
    proc = _run_worker("retrain", chaos_root, chaos_out,
                       _worker_env("flywheel_mid_retrain_kill",
                                   skip=kill_skip))
    assert proc.returncode == chaos.EXIT_CODE, (
        f"worker should have died (rc={proc.returncode})\n"
        + proc.stderr[-3000:])
    assert not chaos_out.exists(), "killed run must not have finished"
    # ...then resumed to completion
    proc = _run_worker("retrain", chaos_root, chaos_out, _worker_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    with open(ref_out) as f:
        ref = json.load(f)
    with open(chaos_out) as f:
        got = json.load(f)
    # the promoted candidate is the SAME step with BITWISE-identical
    # payload bytes, and the high-water mark consumed the same segments
    assert got["step"] == ref["step"]
    assert got["consumed"] == ref["consumed"]
    assert sorted(got["leaves"]) == sorted(ref["leaves"])
    for key, crc in ref["leaves"].items():
        assert got["leaves"][key] == crc, f"leaf {key} differs"


def test_mid_retrain_kill_resume_bitwise_canary(tmp_path, seeded_root):
    """The always-on cell: die at the first trigger evaluation (before
    any mid-epoch checkpoint), resume, promote identical bytes."""
    _retrain_cell(tmp_path, seeded_root, kill_skip=0)


@pytest.mark.slow
@pytest.mark.parametrize("kill_skip", [2, 4, 5])
def test_mid_retrain_kill_matrix_bitwise(tmp_path, seeded_root, kill_skip):
    """Deeper kill sites: after mid-epoch checkpoints have committed and
    at the epoch-end evaluation (2 subprocess boots per cell)."""
    _retrain_cell(tmp_path, seeded_root, kill_skip=kill_skip)


def test_flywheel_chaos_points_are_known():
    assert "capture_writer_torn" in chaos.FLYWHEEL_POINTS
    assert "flywheel_mid_retrain_kill" in chaos.FLYWHEEL_POINTS
    for point in chaos.FLYWHEEL_POINTS:
        os.environ["AZOO_FT_CHAOS"] = point
        try:
            assert chaos.active_point() == point
        finally:
            os.environ.pop("AZOO_FT_CHAOS", None)


def _leaf_crcs(path):
    flat, _ = atomic.read_checkpoint(path)
    return {k: zlib.crc32(np.ascontiguousarray(v).tobytes())
            for k, v in flat}
