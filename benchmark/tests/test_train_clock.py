"""The trainer's clock as the benchmark reads it (PR 26): the five per-layer
metrics of `benchmark/metrics/train_*.json` over the counters that
`Estimator.train` keeps, and `span_gaps.py`, which lays the program's spans
over a traced window. CPU runs at a tiny size: shares of the host's clock and
counts, no device number."""

import json
import os
import time

import pytest

from benchmark import cells, harness, span_gaps, trace
from benchmark.tests import tiny

SEED = 2 ** 31 + 26
NEW = ("train_input_starvation", "train_host_loop_share",
       "train_device_wait_share", "train_epoch_fill_ms",
       "train_infeed_busy_ms_per_step")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny.make_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(scope="module")
def traced(root):
    cell = cells.resolve("resnet-tiny.tiny-hostfed", root)
    run = cells.load(cell["traffic"]["driver"])(
        cell, SEED, 0.6, True, time.perf_counter(), any_platform=True)
    return cell, run, harness.result_line(cell, run["device"], run, True)


def test_a_traced_run_reports_the_five_readings(traced):
    _, _, line = traced
    got = {k: v["value"] for k, v in line["metrics"].items()}
    assert set(NEW) <= set(got)
    shares = (got["train_input_starvation"] + got["train_host_loop_share"]
              + got["train_device_wait_share"])
    assert shares == pytest.approx(100.0, abs=0.5)
    assert all(got[k] >= 0 for k in NEW)
    assert got["train_epoch_fill_ms"] > 0
    assert got["train_infeed_busy_ms_per_step"] > 0
    assert line["correct"] is True, line["compared"]


@pytest.mark.parametrize("name", NEW)
def test_a_new_metric_names_only_counters_the_run_holds(traced, name):
    _, run, _ = traced
    with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "benchmark.readers:counter_ratio"
    held = run["ctx"]["counters"]["window_end"]
    for counter in spec["args"]["num"] + spec["args"]["den"]:
        assert counter in held, counter
    entry = next(m for m in cells.manifest()["per_layer"]
                 if m["name"] == name)
    assert entry["source"] == "program_counter"
    assert entry["moves"] == "train_items_per_s_per_chip"


def test_every_counter_the_clock_adds_is_read_by_a_metric():
    read = set()
    for name in NEW:
        with open(os.path.join(cells.HERE, "metrics", name + ".json")) as f:
            args = json.load(f)["args"]
        read |= set(args["num"] + args["den"])
    assert read == {
        "zoo_train_call_seconds_total", "zoo_train_epochs_total",
        "zoo_data_wait_seconds_sum", "zoo_train_drain_seconds_total",
        "zoo_train_host_seconds_total", "zoo_train_fill_seconds_total",
        "zoo_data_assemble_seconds_total", "zoo_data_transfer_seconds_total",
        "zoo_train_steps_total"}


def test_idle_is_listed_by_the_programs_spans():
    """A device that stands idle while the loop is in `train.fill` and busy
    otherwise: the gap goes to that span, on the `time.time_ns()` clock that
    the benchmark's own spans keep."""
    from analytics_zoo_tpu.common.observability import get_tracer

    tracer = get_tracer()
    tracer.clear()
    tracer.enable()
    try:
        events = span_gaps._WithProgramSpans()
        t0 = time.time_ns()
        with tracer.span("train.call"):
            with tracer.span("train.fill"):
                with tracer.span("train.infeed_wait"):    # the fill's own
                    time.sleep(0.02)
            fill_end = time.time_ns()
            with tracer.span("train.infeed_wait"):
                time.sleep(0.01)
            with tracer.span("train.drain"):
                time.sleep(0.01)
            with tracer.span("infeed.assemble"):    # another thread's kind
                pass
        t1 = time.time_ns()
        events.append(("bench.window", t0, t1))
        host = list(events)
    finally:
        tracer.disable()
        tracer.clear()
    names = [n for n, _, _ in host]
    assert names[0] == "bench.window" and "train.fill" in names
    assert names.count("train.infeed_wait") == 1
    assert "infeed.assemble" not in names
    devices = {"/device:TPU:0": {"ops": [("fusion.1", fill_end, t1)],
                                 "modules": []}}
    gaps = dict(trace.reduce(devices, host, "^jit_")["idle_gaps"])
    assert gaps["train.fill"] == pytest.approx((fill_end - t0) / 1e9,
                                               abs=2e-3)
    assert "train.drain" not in gaps


@pytest.mark.parametrize("traced, tracer_on", [(0, 1), (0, 0), (1, 1)])
def test_span_gaps_runs_a_cell_with_the_tracer_on_or_off(root, capsys, traced,
                                                         tracer_on):
    rc = span_gaps.main(
        ["--workload", "resnet-tiny.tiny-hostfed", "--seed", str(SEED),
         "--seconds", "0.5", "--trace", str(traced), "--tracer",
         str(tracer_on)], root=root, any_platform=True)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True and line["tracer"] is bool(tracer_on)
    assert ("train_epoch_fill_ms" if traced else
            "train_items_per_s_per_chip") in line["metrics"]
    assert harness.Spans.__name__ == "Spans"          # put back
    if tracer_on:
        spans = line["program_spans"]
        calls = spans["train.call"]["count"]
        assert calls >= 1 and spans["train.epoch"]["count"] == calls
        assert spans["train.fill"]["count"] == calls
        assert spans["train.dispatch"]["count"] == 4 * calls
        assert spans["infeed.transfer"]["count"] == 4 * calls
        assert line["spans_per_s"] > 0
    else:
        assert line["program_spans"] == {} and line["spans_per_s"] == 0
