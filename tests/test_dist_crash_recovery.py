"""Multi-host kill matrix: REAL subprocess gangs, hard kills at every
two-phase-commit failure point × {participant, coordinator}, then a
full-gang restart with a fresh run id and ``auto_resume=True`` — the
final params must be BITWISE-identical to an uninterrupted 2-host run's,
and no kill may ever leave ``committed_checkpoints`` able to return a
torn checkpoint.

One combo runs unmarked as the always-on canary; the rest of the matrix
is ``slow``. The rendezvous root honors ``AZOO_DIST_RDV_ROOT`` so CI can
upload the exchange-round debris of a failed run.
"""

import json
import os
import subprocess
import sys
import uuid

import numpy as np
import pytest

from analytics_zoo_tpu.ft import atomic, chaos

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_dist_worker.py")
NHOSTS = 2


def _dirs(tmp_path):
    root = os.environ.get("AZOO_DIST_RDV_ROOT")
    rdv = (os.path.join(root, uuid.uuid4().hex[:12]) if root
           else str(tmp_path / "rdv"))
    os.makedirs(rdv, exist_ok=True)
    return str(tmp_path / "ck"), rdv


def _gang(ckpt_dir, rdv_dir, out_dir, *, chaos_host=None, chaos_point=None,
          skip=0, timeout_s=60, preempt_at=0, epochs=3):
    """Launch one NHOSTS-process gang; returns (returncodes, out_paths,
    stderrs). A fresh run id per gang — exactly how a restarted job
    avoids a dead run's rendezvous debris."""
    os.makedirs(out_dir, exist_ok=True)
    run_id = uuid.uuid4().hex[:12]
    procs, outs = [], []
    for h in range(NHOSTS):
        env = dict(os.environ)
        for k in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP", "DIST_PREEMPT_AT"):
            env.pop(k, None)
        env.update({"AZOO_DIST_HOST": str(h),
                    "AZOO_DIST_NHOSTS": str(NHOSTS),
                    "AZOO_DIST_RUN_ID": run_id,
                    "AZOO_DIST_TIMEOUT_S": str(timeout_s),
                    "DIST_EPOCHS": str(epochs)})
        if chaos_point is not None and h == chaos_host:
            env["AZOO_FT_CHAOS"] = chaos_point
            env["AZOO_FT_CHAOS_SKIP"] = str(skip)
        if preempt_at:
            env["DIST_PREEMPT_AT"] = str(preempt_at)
        out = os.path.join(out_dir, f"h{h}.json")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, WORKER, ckpt_dir, rdv_dir, out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
            text=True))
    rcs, errs = [], []
    for p in procs:
        try:
            _, err = p.communicate(timeout=240)
        except subprocess.TimeoutExpired:
            p.kill()
            _, err = p.communicate()
            err = (err or "") + "\n<gang member timed out>"
        rcs.append(p.returncode)
        errs.append(err)
    return rcs, outs, errs


def _params(out_path):
    with open(out_path) as f:
        doc = json.load(f)
    return {k: np.asarray(v) for k, v in doc["params"].items()}, doc


def _assert_no_torn_checkpoints(ckpt_dir):
    """Every checkpoint the reader API returns must restore and verify —
    the two-phase commit's whole point."""
    for _step, path in atomic.committed_checkpoints(ckpt_dir):
        flat, meta = atomic.read_checkpoint(path)  # verify=True
        assert flat and meta.get("dist"), path


@pytest.fixture(scope="module")
def reference(tmp_path_factory):
    """One uninterrupted 2-host run — the trajectory every kill/resume
    pair must reproduce bitwise."""
    d = tmp_path_factory.mktemp("dist_ref")
    ckpt, rdv = _dirs(d)
    rcs, outs, errs = _gang(ckpt, rdv, str(d / "out"))
    assert rcs == [0, 0], errs
    p0, doc0 = _params(outs[0])
    p1, _ = _params(outs[1])
    for k in p0:
        np.testing.assert_array_equal(p0[k], p1[k], err_msg=k)
    return p0, doc0


def _kill_and_resume(tmp_path, reference, point, victim):
    ckpt, rdv = _dirs(tmp_path)
    # run 1: hard kill at the SECOND save's failure point (the first
    # commit at iteration 4 survives, so resume starts from real state)
    rcs, _outs, errs = _gang(ckpt, rdv, str(tmp_path / "o1"),
                             chaos_host=victim, chaos_point=point,
                             skip=1, timeout_s=8)
    assert rcs[victim] == chaos.EXIT_CODE, (
        f"host {victim} should have died at '{point}' "
        f"(rc={rcs[victim]})\n" + errs[victim][-3000:])
    survivor = 1 - victim
    assert rcs[survivor] != 0, (
        "the surviving host cannot finish without its peer\n"
        + errs[survivor][-3000:])
    # the torn save is invisible: whatever committed, restores clean
    steps = [s for s, _ in atomic.committed_checkpoints(ckpt)]
    assert steps == [4], steps
    _assert_no_torn_checkpoints(ckpt)
    # run 2: full-gang restart (fresh run id), auto_resume picks up
    rcs, outs, errs = _gang(ckpt, rdv, str(tmp_path / "o2"))
    assert rcs == [0, 0], errs
    want, ref_doc = reference
    for out in outs:
        got, doc = _params(out)
        assert doc["iteration"] == ref_doc["iteration"]
        assert doc["epoch"] == ref_doc["epoch"]
        assert sorted(got) == sorted(want)
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)
    _assert_no_torn_checkpoints(ckpt)


def test_kill_torn_participant_then_resume_bitwise(tmp_path, reference):
    """The always-on canary: the non-coordinator dies mid-array-write
    (half the bytes staged), the gang dies with it, a restarted gang
    reproduces the uninterrupted trajectory bitwise."""
    _kill_and_resume(tmp_path, reference, "dist_participant_torn", victim=1)


_MATRIX = [
    ("dist_participant_torn", 0),
    ("dist_participant_before_manifest", 0),
    ("dist_participant_before_manifest", 1),
    ("dist_coordinator_before_merge", 0),
    ("dist_coordinator_before_commit", 0),
]


@pytest.mark.slow
@pytest.mark.parametrize("point,victim", _MATRIX)
def test_dist_kill_matrix_then_resume_bitwise(tmp_path, reference, point,
                                              victim):
    """The rest of the {failure point} × {participant, coordinator}
    matrix (coordinator points can only fire on host 0)."""
    _kill_and_resume(tmp_path, reference, point, victim)


@pytest.mark.slow
def test_preemption_propagates_and_resumes_bitwise(tmp_path, reference):
    """A preemption flagged on host 0 rides the gradient exchange: EVERY
    host saves coordinately (one committed checkpoint, same step) and
    exits 41; the restarted gang finishes bitwise."""
    ckpt, rdv = _dirs(tmp_path)
    rcs, outs, errs = _gang(ckpt, rdv, str(tmp_path / "o1"), preempt_at=5)
    assert rcs == [41, 41], (rcs, errs)
    docs = [_params(o)[1] for o in outs]
    assert all(d["preempted"] for d in docs)
    paths = {d["checkpoint_path"] for d in docs}
    assert len(paths) == 1 and None not in paths, paths
    assert atomic.is_committed(paths.pop())
    _assert_no_torn_checkpoints(ckpt)
    rcs, outs, errs = _gang(ckpt, rdv, str(tmp_path / "o2"))
    assert rcs == [0, 0], errs
    want, ref_doc = reference
    for out in outs:
        got, doc = _params(out)
        assert doc["iteration"] == ref_doc["iteration"]
        for key in want:
            np.testing.assert_array_equal(got[key], want[key], err_msg=key)


@pytest.mark.slow
def test_restore_on_different_host_count_is_deterministic(tmp_path):
    """A 2-host checkpoint restored by a 1-host run: resharding is a
    deterministic pure function of the checkpoint — two independent
    1-host resumes finish bitwise-identical to each other."""
    ckpt, rdv = _dirs(tmp_path)
    rcs, _outs, errs = _gang(ckpt, rdv, str(tmp_path / "o1"), epochs=2)
    assert rcs == [0, 0], errs
    steps = [s for s, _ in atomic.committed_checkpoints(ckpt)]
    assert steps == [4], steps

    def solo(tag):
        env = dict(os.environ)
        env["PYTHONPATH"] = ""
        for k in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP", "DIST_PREEMPT_AT"):
            env.pop(k, None)
        env.update({"AZOO_DIST_HOST": "0", "AZOO_DIST_NHOSTS": "1",
                    "AZOO_DIST_RUN_ID": uuid.uuid4().hex[:12],
                    "AZOO_DIST_TIMEOUT_S": "60", "DIST_EPOCHS": "3"})
        out = str(tmp_path / f"solo_{tag}.json")
        # copy the 2-host checkpoint dir so the two resumes are
        # independent (retention in one must not affect the other)
        import shutil

        ck = str(tmp_path / f"ck_{tag}")
        shutil.copytree(ckpt, ck)
        proc = subprocess.run(
            [sys.executable, WORKER, ck, rdv, out],
            env=env, capture_output=True, text=True, timeout=240)
        assert proc.returncode == 0, proc.stderr[-3000:]
        return _params(out)

    got_a, doc_a = solo("a")
    got_b, doc_b = solo("b")
    assert doc_a["iteration"] == doc_b["iteration"] == 9
    for key in got_a:
        np.testing.assert_array_equal(got_a[key], got_b[key], err_msg=key)
