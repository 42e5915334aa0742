"""Multi-host (multi-process) runtime tests.

The reference's defining capability is multi-node data-parallel training
(BigDL DistriOptimizer over a Spark cluster, wp-bigdl.md:113-160;
NNContext.scala:132-178 reads executor/node counts). The TPU-native analogue
is ``jax.distributed`` + a mesh spanning every process's devices, with each
process feeding only its local shard of the global batch.

Tested the way the reference tests clusters without one (SURVEY.md §4-4,
``local[N]``): spawn REAL OS processes on CPU devices, train the same model,
and assert the observable trajectory (losses, metrics, predictions, final
params) matches a single-process run to 1e-6 — the multi-process feed +
``make_array_from_process_local_data`` assembly must be numerically
invisible.
"""

import json
import os
import socket
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WORKER = os.path.join(REPO, "tests", "_mp_worker.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _clean_env(local_devices: int) -> dict:
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["MP_LOCAL_DEVICES"] = str(local_devices)
    env.pop("XLA_FLAGS", None)
    return env


def _spawn_cluster(nproc: int, out: str, mode: str, global_devices: int,
                   **env_knobs) -> list:
    coord = f"127.0.0.1:{_free_port()}"
    env = _clean_env(global_devices // nproc if nproc > 1 else global_devices)
    env["MP_MODE"] = mode
    for k, v in env_knobs.items():
        env[f"MP_{k.upper()}"] = str(v)
    return [
        subprocess.Popen(
            [sys.executable, WORKER, str(nproc), str(pid), coord, out],
            # nproc procs x (g/nproc) devices, or 1 proc x g: same mesh
            env=env,
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True)
        for pid in range(nproc)
    ]


def _run_cluster(nproc: int, out: str, timeout: int = 420,
                 mode: str = "stream", global_devices: int = 4,
                 **env_knobs) -> dict:
    """Launch nproc copies of the worker; return process-0's trajectory."""
    procs = _spawn_cluster(nproc, out, mode, global_devices, **env_knobs)
    logs = []
    try:
        for p in procs:
            stdout, _ = p.communicate(timeout=timeout)
            logs.append(stdout)
            assert p.returncode == 0, \
                f"worker rc={p.returncode}:\n{stdout[-3000:]}"
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    with open(out) as f:
        return json.load(f)


def _assert_trajectories_match(multi: dict, single: dict):
    np.testing.assert_allclose(multi["losses"], single["losses"], atol=1e-6)
    for k in single["metrics"]:
        np.testing.assert_allclose(multi["metrics"][k], single["metrics"][k],
                                   atol=1e-6, err_msg=k)
    assert multi["pred_shape"] == single["pred_shape"]
    np.testing.assert_allclose(multi["pred_head"], single["pred_head"],
                               atol=1e-6)
    for k in single["params"]:
        np.testing.assert_allclose(multi["params"][k], single["params"][k],
                                   atol=1e-6, err_msg=k)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["stream", "cached"])
def test_two_process_training_matches_single_process(tmp_path, mode):
    """stream: the local-shard streaming feed; cached: the row-sharded HBM
    device cache (in-step shard_map gather) — the flagship fit path at
    multi-host scale (VERDICT r3 #3)."""
    single = _run_cluster(1, str(tmp_path / "single.json"), mode=mode)
    multi = _run_cluster(2, str(tmp_path / "multi.json"), mode=mode)

    assert multi["process_count"] == 2
    assert multi["num_devices"] == 4 == single["num_devices"]
    _assert_trajectories_match(multi, single)


@pytest.mark.slow
@pytest.mark.parametrize("mode", ["stream", "cached"])
def test_three_process_uneven_tail_matches_single(tmp_path, mode):
    """3 processes x 2 devices vs 1 process x 6 devices, on a dataset size
    (50) that does NOT divide the batch (12): wrap-padded masked tails in
    both feeds — trajectories must still agree (VERDICT r3 #4)."""
    kn = dict(n=50, batch=12, global_devices=6, mode=mode)
    single = _run_cluster(1, str(tmp_path / "single.json"), **kn)
    multi = _run_cluster(3, str(tmp_path / "multi.json"), **kn)
    assert multi["process_count"] == 3
    assert multi["num_devices"] == 6 == single["num_devices"]
    _assert_trajectories_match(multi, single)


@pytest.mark.slow
def test_restart_resume_continues_trajectory(tmp_path):
    """Kill-and-restart resume at cluster scale: train 2 epochs, tear the
    cluster DOWN, boot a fresh one that resumes from the checkpoint and
    trains epoch 3 — its trajectory must equal an uninterrupted 3-epoch
    run (multi-host restore: allgathered ZeRO-1 moments re-placed)."""
    part = tmp_path / "part"
    full = tmp_path / "full"
    part.mkdir()
    full.mkdir()
    kn = dict(mode="cached", global_devices=4)
    _run_cluster(2, str(part / "a.json"), epochs=2, **kn)
    resumed = _run_cluster(2, str(part / "b.json"), epochs=3, resume=1, **kn)
    uninterrupted = _run_cluster(2, str(full / "c.json"), epochs=3, **kn)

    assert len(resumed["losses"]) == 1  # only epoch 3 ran after the restart
    np.testing.assert_allclose(resumed["losses"][-1],
                               uninterrupted["losses"][-1], atol=1e-6)
    for k in uninterrupted["params"]:
        np.testing.assert_allclose(resumed["params"][k],
                                   uninterrupted["params"][k],
                                   atol=1e-6, err_msg=k)
    for k in uninterrupted["metrics"]:
        np.testing.assert_allclose(resumed["metrics"][k],
                                   uninterrupted["metrics"][k],
                                   atol=1e-6, err_msg=k)


@pytest.mark.slow
def test_dead_worker_survivors_fail_fast(tmp_path):
    """Failure detection (SURVEY.md §5): worker 1 dies after epoch 1; the
    survivor's next collective stalls and the armed step watchdog must
    fail it FAST (on_stall -> exit) instead of hanging the cluster."""
    import time

    out = str(tmp_path / "dead.json")
    procs = _spawn_cluster(2, out, "stream", 4,
                           scenario="dead_worker", epochs=3)
    t0 = time.time()
    try:
        outs = []
        for p in procs:
            stdout, _ = p.communicate(timeout=180)
            outs.append(stdout)
    finally:
        for p in procs:
            if p.poll() is None:
                p.kill()
    elapsed = time.time() - t0
    assert procs[1].returncode == 7, outs[1][-2000:]  # the deliberate death
    # the survivor must NOT exit 0 (the run can't have completed) and must
    # exit quickly — watchdog path (rc 3 + marker) or a fast collective
    # error; either way "fail fast", not "hang forever"
    rc0 = procs[0].returncode
    assert rc0 != 0, outs[0][-2000:]
    assert elapsed < 150, f"survivor took {elapsed:.0f}s to fail"
    if rc0 == 3:
        assert os.path.exists(out + ".stall.0"), "watchdog marker missing"
    assert not os.path.exists(out), "dead run must not produce a trajectory"
