"""Subprocess crash-recovery matrix: REAL process kills at every commit-
protocol failure point (ft/chaos.py), then restart with auto_resume=True
and assert the final params are BITWISE-identical to an uninterrupted
run's — the ISSUE's acceptance bar.

The kill happens via ``os._exit(43)`` on the async writer thread while
the train loop is mid-flight (no finally blocks, no atexit — a
preemption's geometry). The full matrix is marked ``slow`` so tier-1
stays under its timeout (the fast in-process fault-injection equivalents
live in test_ft.py); one point runs unmarked as the always-on canary.
"""

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from analytics_zoo_tpu.ft import chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_ft_worker.py")


def _worker_env(chaos_point=None, skip=0) -> dict:
    env = dict(os.environ)
    env.pop("AZOO_FT_CHAOS", None)
    env.pop("AZOO_FT_CHAOS_SKIP", None)
    if chaos_point is not None:
        env["AZOO_FT_CHAOS"] = chaos_point
        env["AZOO_FT_CHAOS_SKIP"] = str(skip)
    return env


def _run_worker(ckpt_dir, out, env) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, WORKER, str(ckpt_dir), str(out)],
        env=env, capture_output=True, text=True, timeout=240)


def _params(out_path):
    with open(out_path) as f:
        doc = json.load(f)
    return {k: np.asarray(v) for k, v in doc["params"].items()}, doc


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """One uninterrupted run — the trajectory every kill/resume pair must
    reproduce bitwise."""
    d = tmp_path_factory.mktemp("ft_ref")
    out = d / "ref.json"
    proc = _run_worker(d / "ck", out, _worker_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    return _params(out)


def _kill_and_resume(tmp_path, reference, point):
    ck = tmp_path / "ck"
    out = tmp_path / "out.json"
    # run 1: hard kill at the SECOND checkpoint's failure point (the first
    # commit at iteration 4 survives, so resume starts from real state)
    proc = _run_worker(ck, out, _worker_env(point, skip=1))
    assert proc.returncode == chaos.EXIT_CODE, (
        f"worker should have died at '{point}' (rc={proc.returncode})\n"
        + proc.stderr[-3000:])
    assert not out.exists(), "killed run must not have finished"
    # the torn save is invisible: only committed checkpoints are readable
    from analytics_zoo_tpu.engine import checkpoint as ck_lib

    latest = ck_lib.latest_checkpoint(str(ck))
    assert latest is not None and latest.endswith("ckpt_4"), latest
    # run 2: process restart, auto_resume picks up the committed state
    proc = _run_worker(ck, out, _worker_env())
    assert proc.returncode == 0, proc.stderr[-3000:]
    got, doc = _params(out)
    want, ref_doc = reference
    assert doc["iteration"] == ref_doc["iteration"]
    assert doc["epoch"] == ref_doc["epoch"]
    assert sorted(got) == sorted(want)
    for key in want:
        np.testing.assert_array_equal(got[key], want[key], err_msg=key)


def test_kill_after_arrays_then_resume_bitwise(tmp_path, reference):
    """The always-on canary: die in the legacy corruption window (array
    file written, manifest not), restart, reproduce the uninterrupted
    trajectory bitwise."""
    _kill_and_resume(tmp_path, reference, "after_arrays")


@pytest.mark.slow
@pytest.mark.parametrize("point", [p for p in chaos.FAILURE_POINTS
                                   if p != "after_arrays"])
def test_kill_matrix_then_resume_bitwise(tmp_path, reference, point):
    """The rest of the failure-point matrix (slow: 2 subprocess boots per
    point)."""
    _kill_and_resume(tmp_path, reference, point)
