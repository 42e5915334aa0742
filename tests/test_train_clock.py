"""The train loop's clock (ISSUE 26): ``Estimator.train`` splits every
call's host seconds into input wait, device wait and the host's own work,
counts its epochs and their fill, times the infeed thread, and, with the
tracer on, shows the same intervals as one tree of spans a call on a clock
that ``time.time_ns()`` can be tied to. CPU runs: the seconds here are host
clock readings checked against each other, never a device number."""

import threading
import time

import jax
import numpy as np
import optax
import pytest

import analytics_zoo_tpu as zoo
from analytics_zoo_tpu.common import observability as obs
from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
from analytics_zoo_tpu.data.pipeline import Pipeline
from analytics_zoo_tpu.data.sources import ArraySource
from analytics_zoo_tpu.engine import estimator as est_mod
from analytics_zoo_tpu.engine.estimator import Estimator
from analytics_zoo_tpu.engine.triggers import MaxEpoch
from analytics_zoo_tpu.keras import objectives
from analytics_zoo_tpu.keras.engine.base import reset_name_counts
from analytics_zoo_tpu.keras.engine.topology import Sequential
from analytics_zoo_tpu.keras.layers import Dense

N, DIM, CLASSES, BATCH = 64, 8, 3, 16
CRITERION = objectives.sparse_categorical_crossentropy_from_logits
CLOCK = ("zoo_train_call_seconds_total", "zoo_data_wait_seconds",
         "zoo_train_drain_seconds_total", "zoo_train_host_seconds_total",
         "zoo_train_fill_seconds_total", "zoo_train_epochs_total",
         "zoo_train_steps_total", "zoo_data_assemble_seconds_total",
         "zoo_data_transfer_seconds_total", "zoo_data_borrowed_batches_total")


def _data():
    rng = np.random.default_rng(3)
    return (rng.normal(size=(N, DIM)).astype(np.float32),
            rng.integers(0, CLASSES, N).astype(np.int32))


def _estimator():
    reset_name_counts()
    zoo.init_nncontext()
    model = Sequential([Dense(8, activation="relu", input_shape=(DIM,)),
                        Dense(CLASSES)])
    return Estimator(model, optax.sgd(0.05))


def _array_set():
    return ArrayFeatureSet(*_data())


def _native_set():
    from analytics_zoo_tpu import native
    from analytics_zoo_tpu.data.pmem import NativeCachedFeatureSet

    if not native.available():
        pytest.skip("native toolchain unavailable")
    return NativeCachedFeatureSet(*_data(), memory_type="DRAM")


def _stream_set():
    return Pipeline(ArraySource(*_data()), seed=7).batch(BATCH).prefetch(3)


def _device_set(device_shuffle):
    fs = ArrayFeatureSet(*_data()).cache_device()
    fs.device_shuffle = device_shuffle
    return fs


def _clock():
    """The clock's families, flat; a summary reads as its sum."""
    obs.training_metrics(), obs.data_metrics()      # registered on first use
    fams = obs.get_registry()._families
    out = {}
    for name in CLOCK:
        child = fams[name].child()
        out[name] = child.sum if fams[name].kind == "summary" else child.value
    return out


def _delta(before):
    now = _clock()
    return {k: now[k] - before[k] for k in now}


def _assert_parts_add_to_the_call(d):
    parts = (d["zoo_data_wait_seconds"] + d["zoo_train_drain_seconds_total"]
             + d["zoo_train_host_seconds_total"])
    assert parts == pytest.approx(d["zoo_train_call_seconds_total"],
                                  rel=1e-9, abs=1e-9)


@pytest.fixture
def tracer():
    t = obs.get_tracer()
    t.clear()
    t.enable()
    yield t
    t.disable()
    t.clear()


# ---------------------------------------------------------------------------
# counters, always on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("make_set", [_array_set, _native_set, _stream_set],
                         ids=["array", "native", "stream"])
def test_wait_drain_and_host_add_to_the_call(make_set):
    est, fs = _estimator(), make_set()
    before = _clock()
    t0 = time.perf_counter()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(2), batch_size=BATCH)
    outer = time.perf_counter() - t0
    d = _delta(before)
    assert 0 < d["zoo_train_call_seconds_total"] <= outer
    _assert_parts_add_to_the_call(d)
    assert min(d["zoo_data_wait_seconds"], d["zoo_train_drain_seconds_total"],
               d["zoo_train_host_seconds_total"]) > 0
    # the fill is a part of wait + host, one an epoch, never a fourth share
    assert 0 < d["zoo_train_fill_seconds_total"] <= (
        d["zoo_data_wait_seconds"] + d["zoo_train_host_seconds_total"])
    assert d["zoo_train_epochs_total"] == 2
    assert d["zoo_train_steps_total"] == 2 * N // BATCH
    # the infeed thread's work, and the gauges of every host-fed set
    assert d["zoo_data_assemble_seconds_total"] > 0
    assert d["zoo_data_transfer_seconds_total"] > 0
    # on the CPU a placed array can alias the host's: nothing is fed borrowed
    assert d["zoo_data_borrowed_batches_total"] == 0
    fams = obs.get_registry()._families
    assert 0 <= fams["zoo_data_starvation_ratio"].child().value <= 1


def _per_step(est):
    est.train(_array_set(), CRITERION, end_trigger=MaxEpoch(3),
              batch_size=BATCH)


def _scan(est):
    est.train(_device_set(False), CRITERION, end_trigger=MaxEpoch(3),
              batch_size=BATCH)
    assert any(t[0] == "train_scan" for t in est._jit_cache)


def _epoch(est):
    for k in (1, 2):        # one more epoch a call: an epoch a dispatch
        est.train(_device_set(True), CRITERION, end_trigger=MaxEpoch(k),
                  batch_size=BATCH)
    assert any(t[0] == "train_epoch" for t in est._jit_cache)


def _fused_fit(est):
    est.train(_device_set(True), CRITERION, end_trigger=MaxEpoch(3),
              batch_size=BATCH)
    assert any(t[0] == "train_fit" for t in est._jit_cache)


@pytest.mark.parametrize("path", [_per_step, _scan, _epoch, _fused_fit])
def test_epochs_counter_follows_run_state(path):
    est = _estimator()
    before = _clock()
    path(est)
    d = _delta(before)
    assert est.run_state.epoch > 0
    assert d["zoo_train_epochs_total"] == est.run_state.epoch
    assert d["zoo_train_steps_total"] == est.run_state.iteration
    _assert_parts_add_to_the_call(d)
    if path is _per_step:
        assert d["zoo_train_fill_seconds_total"] > 0
    else:           # no batch comes from the infeed thread: nothing to fill
        assert d["zoo_train_fill_seconds_total"] == 0


class SleepySet(ArrayFeatureSet):
    """Not a stream (no ``note_queue_depth``): every batch is late."""

    nap = 0.03

    def train_batches(self, batch_size, shuffle=True, seed=0, **kw):
        for item in super().train_batches(batch_size, shuffle, seed, **kw):
            time.sleep(self.nap)
            yield item


def test_a_slow_set_that_is_no_stream_reads_its_wait_and_starves():
    est, fs = _estimator(), SleepySet(*_data())
    assert not hasattr(fs, "note_queue_depth")
    est.train(fs, CRITERION, end_trigger=MaxEpoch(1), batch_size=BATCH)  # warm
    before = _clock()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(3), batch_size=BATCH)
    d = _delta(before)
    slept = 2 * (N // BATCH) * fs.nap
    assert d["zoo_data_wait_seconds"] >= 0.8 * slept
    assert d["zoo_data_assemble_seconds_total"] >= 0.8 * slept
    fams = obs.get_registry()._families
    assert fams["zoo_data_starvation_ratio"].child().value > 0.5
    assert (d["zoo_data_wait_seconds"]
            > 0.5 * d["zoo_train_call_seconds_total"])


# ---------------------------------------------------------------------------
# spans, when the tracer is on
# ---------------------------------------------------------------------------

CHILDREN = {
    "train.call": {None},
    "train.epoch": {"train.call"},
    "train.fill": {"train.epoch"},
    "train.infeed_wait": {"train.epoch", "train.fill"},
    "train.dispatch": {"train.epoch"},
    "train.drain": {"train.epoch"},
    "train.validation": {"train.epoch"},
    "infeed.assemble": {"train.epoch"},
    "infeed.transfer": {"train.epoch"},
}


class NamingSet(ArrayFeatureSet):
    """Keeps the (ident, name) of every thread that assembles its batches."""

    def __init__(self, *a):
        super().__init__(*a)
        self.threads = set()

    def train_batches(self, batch_size, shuffle=True, seed=0, **kw):
        for item in super().train_batches(batch_size, shuffle, seed, **kw):
            t = threading.current_thread()
            self.threads.add((t.ident, t.name))
            yield item


def test_traced_calls_are_one_tree_each(tracer):
    est, fs = _estimator(), NamingSet(*_data())
    x, y = _data()
    val = ArrayFeatureSet(x[:BATCH], y[:BATCH])
    from analytics_zoo_tpu.keras import metrics

    for k in (2, 3):
        est.train(fs, CRITERION, end_trigger=MaxEpoch(k), batch_size=BATCH,
                  validation_set=val, validation_method=[metrics.Accuracy()])
    spans = tracer.spans()
    by_id = {s.span_id: s for s in spans}
    calls = [s for s in spans if s.name == "train.call"]
    assert len(calls) == 2                         # one a call
    assert len({c.trace_id for c in calls}) == 2
    ours = [s for s in spans if s.name in CHILDREN]
    assert {s.name for s in ours} == set(CHILDREN)
    for s in ours:
        parent = by_id.get(s.parent_id)
        assert (parent.name if parent else None) in CHILDREN[s.name], s.name
        if parent is not None:
            assert s.trace_id == parent.trace_id
            assert s.start >= parent.start - 1e-6
            assert s.end <= parent.end + 1e-6
    per_call = [[s for s in ours if s.trace_id == c.trace_id] for c in calls]
    assert sum(s.name == "train.epoch" for s in per_call[0]) == 2
    assert sum(s.name == "train.epoch" for s in per_call[1]) == 1
    assert sum(s.name == "train.fill" for s in ours) == 3     # one an epoch
    assert sum(s.name == "train.dispatch" for s in ours) == 3 * N // BATCH
    # infeed.* come from the threads named zoo-infeed, the rest from here
    assert fs.threads and {name for _, name in fs.threads} == {"zoo-infeed"}
    infeed_threads = {ident for ident, _ in fs.threads}
    for s in ours:
        if s.name.startswith("infeed."):
            assert s.thread in infeed_threads
        else:
            assert s.thread == threading.get_ident()
    # siblings of one thread never overlap
    groups = {}
    for s in ours:
        groups.setdefault((s.thread, s.parent_id), []).append(s)
    for sibs in groups.values():
        sibs.sort(key=lambda s: s.start)
        for a, b in zip(sibs, sibs[1:]):
            assert a.end <= b.start + 1e-6, (a.name, b.name)


@pytest.mark.parametrize("make_set", [_array_set, _native_set],
                         ids=["array", "native"])
def test_spans_and_counters_time_the_same_intervals(tracer, make_set):
    est, fs = _estimator(), make_set()
    before = _clock()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(2), batch_size=BATCH)
    d = _delta(before)

    def total(name):
        return sum(s.duration for s in tracer.spans() if s.name == name)

    for name, family in (("train.call", "zoo_train_call_seconds_total"),
                         ("train.infeed_wait", "zoo_data_wait_seconds"),
                         ("train.drain", "zoo_train_drain_seconds_total"),
                         ("train.fill", "zoo_train_fill_seconds_total"),
                         ("infeed.assemble", "zoo_data_assemble_seconds_total"),
                         ("infeed.transfer", "zoo_data_transfer_seconds_total")):
        assert total(name) == pytest.approx(d[family], rel=1e-9), name


def test_tracer_off_keeps_no_span_and_the_counters_still_move():
    tracer = obs.get_tracer()
    tracer.disable()
    tracer.clear()
    est = _estimator()
    before = _clock()
    est.train(_array_set(), CRITERION, end_trigger=MaxEpoch(1),
              batch_size=BATCH)
    d = _delta(before)
    assert tracer.spans() == []
    assert tracer.current() is None
    assert d["zoo_train_call_seconds_total"] > 0
    assert d["zoo_train_drain_seconds_total"] > 0
    assert d["zoo_data_wait_seconds"] > 0
    assert d["zoo_train_epochs_total"] == 1


def test_a_call_that_raises_closes_its_spans_and_keeps_the_identity(tracer):
    class Broken(ArrayFeatureSet):
        def train_batches(self, batch_size, shuffle=True, seed=0, **kw):
            it = super().train_batches(batch_size, shuffle, seed, **kw)
            yield next(it)
            raise OSError("the disk went away")

    est = _estimator()
    before = _clock()
    with pytest.raises(OSError):
        est.train(Broken(*_data()), CRITERION, end_trigger=MaxEpoch(1),
                  batch_size=BATCH)
    d = _delta(before)
    assert tracer.current() is None            # nothing left open
    by_name = {s.name: s for s in tracer.spans()}
    assert by_name["train.call"].attrs["error"] == "OSError"
    assert by_name["train.epoch"].attrs["error"] == "OSError"
    _assert_parts_add_to_the_call(d)
    assert d["zoo_train_epochs_total"] == 0


def test_wall_clock_export_agrees_with_time_ns(tracer):
    before = time.time_ns()
    with tracer.span("outer"):
        time.sleep(0.01)
        inside_start = time.time_ns()
        with tracer.span("inner"):
            time.sleep(0.02)
        inside_end = time.time_ns()
    after = time.time_ns()
    spans = {n: (s, e) for n, s, e in tracer.wall_spans_ns()}
    ms = 1_000_000
    assert abs(spans["inner"][0] - inside_start) < ms
    assert abs(spans["inner"][1] - inside_end) < ms
    assert abs(spans["outer"][0] - before) < ms
    assert abs(spans["outer"][1] - after) < ms
    assert all(isinstance(v, int) for se in spans.values() for v in se)
    # the spacing is the monotonic clock's: one anchor an export
    inner = next(s for s in tracer.spans() if s.name == "inner")
    assert (spans["inner"][1] - spans["inner"][0]
            == pytest.approx(inner.duration * 1e9, abs=2))
    assert obs.wall_anchor() == pytest.approx(obs.wall_anchor_ns() / 1e9,
                                              abs=1e-3)


# ---------------------------------------------------------------------------
# batches lent from the native ring (ISSUE 27)
# ---------------------------------------------------------------------------


class Losses:
    """Stands in for a ``TrainSummary``: keeps every step's loss."""

    def __init__(self):
        self.loss = []

    def add_scalar(self, tag, value, step):
        if tag == "Loss":
            self.loss.append((step, value))


def _recycling_data():
    rng = np.random.default_rng(11)     # 203 rows: 26 batches of 8, a tail
    return (rng.normal(size=(203, DIM)).astype(np.float32),
            rng.integers(0, CLASSES, 203).astype(np.int32))


def _train_recorded(fs):
    zoo.init_nncontext()._rng_counter = 0   # the same keys for both trains
    est = _estimator()
    est.train_summary = Losses()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(3), batch_size=8)
    assert len(est.train_summary.loss) == 3 * 26
    leaves = jax.tree_util.tree_leaves(est.tstate.params)
    return [np.asarray(a) for a in leaves], est.train_summary.loss


def test_train_over_a_recycled_ring_is_bitwise_the_train_over_arrays():
    """A ring of two slots and 78 steps: every slot is refilled some forty
    times while steps are in flight. A slot reused under a live batch (on
    the CPU: a placed array that aliases it) changes a loss."""
    from analytics_zoo_tpu.data.pmem import cached_feature_set

    _native_set().close()       # skips without the native library
    fs = cached_feature_set(*_recycling_data(), memory_type="DRAM", n_slots=2)
    assert est_mod._names_parameter(fs.train_batches, "borrowed")
    params, losses = _train_recorded(fs)
    ref_params, ref_losses = _train_recorded(
        ArrayFeatureSet(*_recycling_data()))
    assert losses == ref_losses
    for a, b in zip(params, ref_params):
        np.testing.assert_array_equal(a, b)
    fs.close()


@pytest.mark.parametrize("make_set,lends", [(_native_set, True),
                                            (_array_set, False),
                                            (_stream_set, False)],
                         ids=["native", "array", "stream"])
def test_borrowed_counter_on_a_backend_that_copies(monkeypatch, make_set,
                                                   lends):
    """Steered here, not by an option: the CPU's placed arrays alias the
    slots, so this run's numbers mean nothing; its counters do."""
    monkeypatch.setattr(est_mod, "_aliases_host", lambda mesh: False)
    est, fs = _estimator(), make_set()
    before = _clock()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(2), batch_size=BATCH)
    d = _delta(before)
    steps = 2 * N // BATCH
    assert d["zoo_train_steps_total"] == steps
    assert d["zoo_data_borrowed_batches_total"] == (steps if lends else 0)
    _assert_parts_add_to_the_call(d)
    assert d["zoo_data_transfer_seconds_total"] > 0


def test_an_override_that_does_not_lend_is_fed_its_own_batches(monkeypatch):
    """A subclass's ``train_batches`` without the ``borrowed`` parameter is
    what the estimator iterates (it is not bypassed for the ring), as its
    own arrays: nothing borrowed, even on a backend that copies."""
    from analytics_zoo_tpu.data.pmem import NativeCachedFeatureSet

    _native_set().close()       # skips without the native library
    seen = []

    class Logged(NativeCachedFeatureSet):
        def train_batches(self, batch_size, shuffle=True, seed=0):
            for item in super().train_batches(batch_size, shuffle, seed):
                seen.append(item[0].flags.owndata)
                yield item

    monkeypatch.setattr(est_mod, "_aliases_host", lambda mesh: False)
    est, fs = _estimator(), Logged(*_data())
    before = _clock()
    est.train(fs, CRITERION, end_trigger=MaxEpoch(2), batch_size=BATCH)
    d = _delta(before)
    assert seen == [True] * (2 * N // BATCH)
    assert d["zoo_data_borrowed_batches_total"] == 0
    fs.close()


class Placed:
    """A fake transfer's result: ready once ``done`` is set."""

    def __init__(self, item, log):
        self.item, self.log, self.done = item, log, threading.Event()

    def block_until_ready(self):
        assert self.done.wait(10), "never became ready"
        self.log.append(("ready", self.item))
        return self


def _lender(log, n=4):
    for i in range(n):
        log.append(("lend", i))     # taking item i gives item i-1's slot back
        yield i
    log.append(("end",))


def test_a_lent_item_is_handed_on_at_once_and_held_until_its_copy_is_done():
    log = []
    for placed in est_mod._device_prefetch(
            _lender(log), lambda item: Placed(item, log), depth=4,
            borrowed=True):
        # the queue has room: ready only after the consumer has it, so a
        # thread that waited before it handed the batch on would hang here
        log.append(("got", placed.item))
        time.sleep(0.01)            # room for a thread that does not wait
        placed.done.set()
    order = [e for e in log if e[0] != "got"]
    assert order == [(k, i) for i in range(4) for k in ("lend", "ready")] + [
        ("end",)]
    assert [e[1] for e in log if e[0] == "got"] == [0, 1, 2, 3]


def test_with_the_queue_full_the_wait_comes_first_and_nothing_is_lost():
    log, made = [], []

    def transfer(item):
        made.append(Placed(item, log))
        if item > 0:
            made[-1].done.set()     # item 0's copy is the slow one
        return made[-1]

    gen = est_mod._device_prefetch(_lender(log, n=6), transfer, depth=1,
                                   borrowed=True)
    first = next(gen)               # the thread now holds item 0's slot
    time.sleep(0.05)
    assert [e for e in log if e[0] == "lend"] == [("lend", 0)]
    first.done.set()
    assert [p.item for p in gen] == [1, 2, 3, 4, 5]
    assert [e for e in log if e[0] != "got"] == [
        (k, i) for i in range(6) for k in ("lend", "ready")] + [("end",)]


def test_an_item_that_is_the_iterators_to_give_is_never_waited_for():
    log = []
    got = [p.item for p in est_mod._device_prefetch(
        _lender(log), lambda item: Placed(item, log))]
    assert got == [0, 1, 2, 3]
    assert [e for e in log if e[0] == "ready"] == []


def test_an_abandoned_epoch_still_waits_for_the_copies_it_started():
    log = []

    def transfer(item):
        placed = Placed(item, log)
        placed.done.set()
        return placed

    def names(kind):
        return [e[1] for e in log if e[0] == kind]

    gen = est_mod._device_prefetch(_lender(log, n=50), transfer, depth=1,
                                   borrowed=True)
    next(gen)
    gen.close()                     # the consumer leaves; copies in flight
    deadline = time.time() + 10
    while names("ready") != names("lend") and time.time() < deadline:
        time.sleep(0.01)
    assert names("ready") == names("lend")
    assert len(names("lend")) < 50 and ("end",) not in log


# ---------------------------------------------------------------------------
# Phase, the one place that times
# ---------------------------------------------------------------------------


def test_phase_adds_always_and_retires_a_span_only_when_on():
    seen = []
    t = obs.Tracer()
    phase = obs.Phase("p", seen.append, t, kind="unit")
    phase.stop()                       # not started: nothing
    assert seen == [] and phase.seconds == 0.0
    with phase:
        time.sleep(0.002)
    assert len(seen) == 1 and seen[0] >= 0.002 and t.spans() == []
    assert phase.last == seen[0] == phase.seconds
    t.enable()
    with phase:
        assert t.current().name == "p"
        with t.span("child"):
            pass
    (child, own) = t.spans()
    assert (own.name, own.attrs) == ("p", {"kind": "unit"})
    assert child.parent_id == own.span_id and child.trace_id == own.trace_id
    assert own.duration == seen[1] == phase.last     # the same interval
    assert phase.seconds == seen[0] + seen[1]
    assert t.current() is None
    with pytest.raises(KeyError):
        with phase:
            raise KeyError("x")
    assert t.spans()[-1].attrs["error"] == "KeyError" and len(seen) == 3


def test_phase_with_a_parent_joins_its_trace_from_another_thread():
    t = obs.Tracer().enable()
    seen = []
    with t.span("epoch") as epoch:
        phase = obs.Phase("infeed.x", seen.append, t, parent=epoch)

        def work():
            with phase:
                pass

        th = threading.Thread(target=work)
        th.start()
        th.join(5)
        assert not th.is_alive()
    x = next(s for s in t.spans() if s.name == "infeed.x")
    assert (x.trace_id, x.parent_id) == (epoch.trace_id, epoch.span_id)
    assert x.thread == th.ident and len(seen) == 1


def test_prefetch_runs_untimed_for_callers_that_pass_no_clock():
    got = list(est_mod._device_prefetch(iter(range(5)), lambda b: b * 2))
    assert got == [0, 2, 4, 6, 8]
