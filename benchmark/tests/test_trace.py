"""The trace reduction on a small recorded trace (small.xplane.pb, written
from small.xplane.txt, whose header says what it holds)."""

import os

import pytest

from benchmark import trace

HERE = os.path.dirname(os.path.abspath(__file__))


# the benchmark's own spans, as `harness.Spans` keeps them (the file's host
# plane holds the same, as a profiler with its host tracer on would write them)
HOST = [("bench.window", 1000, 2000), ("bench.submit", 1000, 1150),
        ("bench.wait_answer", 1450, 1750)]


def _reduce(min_gap_ns):
    return trace.reduce(trace.load(os.path.join(HERE, "small.xplane.pb")),
                        HOST, "^jit_train_", min_gap_ns=min_gap_ns)


@pytest.fixture(scope="module")
def reduced():
    return _reduce(50)


def test_recorded_file_matches_its_text(tmp_path):
    from jax.profiler import ProfileData

    with open(os.path.join(HERE, "small.xplane.txt")) as f:
        again = ProfileData.text_proto_to_serialized_xspace(f.read())
    (tmp_path / "again.xplane.pb").write_bytes(again)
    assert trace.load(str(tmp_path / "again.xplane.pb")) == trace.load(
        os.path.join(HERE, "small.xplane.pb"))


def test_busy_is_the_union_not_the_sum(reduced):
    assert reduced["window_s"] == pytest.approx(1000e-9)
    assert reduced["busy_s"] == pytest.approx(500e-9)   # sum would be 750


def test_gaps_go_to_the_host_span_they_fall_in(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["bench.submit"] == pytest.approx(100e-9)
    assert gaps["bench.wait_answer"] == pytest.approx(200e-9)
    assert gaps["_no_bench_span_"] == pytest.approx(200e-9)
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"])


def test_short_gaps_are_pooled():
    r = _reduce(150)
    assert dict(r["idle_gaps"])["_shorter_gaps_"] == pytest.approx(100e-9)


def test_module_time_and_counts(reduced):
    assert reduced["module_s"] == pytest.approx(400e-9)
    assert reduced["module_calls"] == 1
    assert reduced["modules_all"] == 2


def test_operations_are_exclusive(reduced):
    ops = dict(reduced["device_ops"])
    assert ops["while.8"] == pytest.approx(150e-9)      # 400 less its body
    assert ops["fusion.2"] == pytest.approx(150e-9)
    assert sum(ops.values()) == pytest.approx(reduced["busy_s"])


def test_busy_union_and_gaps_by_hand():
    merged = trace.busy_union([("a", 0, 10), ("b", 5, 20), ("c", 30, 40)])
    assert merged == [[0, 20], [30, 40]]
    assert trace.gaps(merged, 0, 50) == [(20, 30), (40, 50)]
    assert trace.gaps(merged, 10, 35) == [(20, 30)]


def test_clock_offset_is_the_quickest_dispatch():
    devices = {"/device:TPU:0": {"ops": [], "modules": [
        ("jit_bench_clock_mark(1)", 5300, 5310),
        ("jit_bench_clock_mark(1)", 7150, 7160), ("jit_forward(2)", 9000, 9500)]}}
    assert trace.clock_offset(devices, [10_000_200, 10_002_100]) == 7150 - 10_002_100
    with pytest.raises(ValueError):
        trace.clock_offset(devices, [1])
