"""Multi-host data-parallel training bench → BENCH_DIST.json.

Three experiments over REAL subprocess gangs (each simulated host is one
process with 2 forced CPU devices, meeting its peers in a filesystem
rendezvous — docs/distributed-training.md has the execution model):

1. **Step-time scaling** (1/2/4 hosts): the same model and global batch
   trained end-to-end per host count. NOTE these are simulated hosts on
   one machine sharing a filesystem allreduce, so the number measures
   the *protocol overhead* of the rendezvous rounds (which dominates at
   this scale), not real-network scaling.

2. **Sharded-vs-replicated optimizer memory**: per-host bytes actually
   held by the sharded flat-vector optimizer state (each host owns a
   1/N slice) against the replicated per-leaf state every host would
   hold without sharding, plus each worker's ``ru_maxrss`` high-water
   mark.

3. **Kill → resume**: a 2-host gang hard-killed at the
   ``dist_participant_torn`` chaos site mid-commit of its second
   checkpoint; the torn attempt must stay invisible (only the first
   checkpoint committed), and a restarted gang must finish with final
   params bitwise-identical to an uninterrupted reference gang's.

CPU-only by construction: every simulated host is a CPU process with two
forced host devices, so the script pins ``JAX_PLATFORMS=cpu`` itself; its
step times measure the filesystem rendezvous, never a chip.

::

    python scripts/dist_train_bench.py
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
import uuid

# CPU-only by construction (see the module docstring): pinned before jax
# is imported, and inherited by every worker this script spawns.
os.environ["JAX_PLATFORMS"] = "cpu"

REPO = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..")
sys.path.insert(0, REPO)

from analytics_zoo_tpu.common.runtime import device_info  # noqa: E402

ROWS, FEATURES, CLASSES = 256, 32, 8
GLOBAL_BATCH, EPOCHS = 64, 3


# ---------------------------------------------------------------------------
# worker (one simulated host; re-exec'd by the orchestrator)
# ---------------------------------------------------------------------------


def worker(rdv_dir: str, out_path: str) -> None:
    import resource

    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np
    import optax

    from analytics_zoo_tpu.data.feature_set import ArrayFeatureSet
    from analytics_zoo_tpu.engine.estimator import Estimator
    from analytics_zoo_tpu.engine.triggers import MaxEpoch, SeveralIteration
    from analytics_zoo_tpu.ft.distributed import DistContext, ShardedUpdater
    from analytics_zoo_tpu.engine import checkpoint as ckpt_lib
    from analytics_zoo_tpu.keras import objectives
    from analytics_zoo_tpu.keras.engine.topology import Sequential
    from analytics_zoo_tpu.keras.layers import Dense

    host = int(os.environ["AZOO_DIST_HOST"])
    nhosts = int(os.environ["AZOO_DIST_NHOSTS"])
    ckpt_dir = os.environ.get("BENCH_CKPT_DIR") or None

    rng = np.random.default_rng(7)
    x = rng.normal(size=(ROWS, FEATURES)).astype(np.float32)
    y = rng.integers(0, CLASSES, ROWS).astype(np.int32)

    model = Sequential([
        Dense(256, activation="relu", input_shape=(FEATURES,)),
        Dense(64, activation="relu"),
        Dense(CLASSES),
    ])
    tx = optax.adam(0.01)
    est = Estimator(model, tx)
    if ckpt_dir:
        est.set_checkpoint(ckpt_dir, keep_last=2)
    dist = DistContext(host, nhosts, rdv_dir)

    t0 = time.perf_counter()
    est.train_distributed(
        ArrayFeatureSet(x, y),
        objectives.sparse_categorical_crossentropy_from_logits,
        end_trigger=MaxEpoch(EPOCHS),
        checkpoint_trigger=SeveralIteration(4) if ckpt_dir else None,
        batch_size=GLOBAL_BATCH,
        auto_resume=bool(ckpt_dir),
        dist=dist)
    wall = time.perf_counter() - t0

    params = est.tstate.params
    u = ShardedUpdater(tx, params, host, nhosts)
    sharded_bytes = sum(np.asarray(leaf).nbytes
                        for _k, leaf in u.opt_flat(u.init_opt(params)))
    replicated_bytes = sum(
        np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(tx.init(params)))
    digest = hashlib.sha256()
    for key, arr in ckpt_lib._flatten(params):
        digest.update(key.encode())
        digest.update(np.ascontiguousarray(np.asarray(arr)).tobytes())

    with open(out_path, "w") as f:
        json.dump({
            "host": host,
            "wall_s": round(wall, 3),
            "steps": est.run_state.iteration,
            "maxrss_mb": round(
                resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0,
                1),
            "flat_size": u.flat_size,
            "slice_len": u.slice_len,
            "opt_bytes_sharded": int(sharded_bytes),
            "opt_bytes_replicated": int(replicated_bytes),
            "params_sha256": digest.hexdigest(),
        }, f)


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------


def _gang(nhosts: int, workdir: str, tag: str, ckpt_dir=None,
          chaos=None, chaos_host=None, chaos_skip=0, timeout_s=60):
    """One gang of ``nhosts`` worker subprocesses; returns
    ``(returncodes, out-doc-or-None per host, stderr tails)``."""
    rdv = os.path.join(workdir, f"rdv_{tag}")
    os.makedirs(rdv, exist_ok=True)
    run_id = uuid.uuid4().hex[:12]
    procs, outs = [], []
    for h in range(nhosts):
        env = dict(os.environ)
        env["PYTHONPATH"] = ""
        for k in ("AZOO_FT_CHAOS", "AZOO_FT_CHAOS_SKIP"):
            env.pop(k, None)
        env.update({
            "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
            "JAX_PLATFORMS": "cpu",
            "AZOO_DIST_HOST": str(h),
            "AZOO_DIST_NHOSTS": str(nhosts),
            "AZOO_DIST_RUN_ID": run_id,
            "AZOO_DIST_TIMEOUT_S": str(timeout_s),
        })
        if ckpt_dir:
            env["BENCH_CKPT_DIR"] = ckpt_dir
        else:
            env.pop("BENCH_CKPT_DIR", None)
        if chaos is not None and h == chaos_host:
            env["AZOO_FT_CHAOS"] = chaos
            env["AZOO_FT_CHAOS_SKIP"] = str(chaos_skip)
        out = os.path.join(workdir, f"out_{tag}_h{h}.json")
        outs.append(out)
        procs.append(subprocess.Popen(
            [sys.executable, os.path.abspath(__file__), "--worker", rdv, out],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.PIPE,
            text=True))
    rcs, docs, errs = [], [], []
    for p, out in zip(procs, outs):
        _, err = p.communicate(timeout=300)
        rcs.append(p.returncode)
        errs.append((err or "")[-2000:])
        if os.path.isfile(out):
            with open(out) as f:
                docs.append(json.load(f))
        else:
            docs.append(None)
    return rcs, docs, errs


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--worker", nargs=2, metavar=("RDV", "OUT"),
                        help="internal: run as one gang member")
    parser.add_argument("--out", default=os.path.join(REPO,
                                                      "BENCH_DIST.json"))
    args = parser.parse_args(argv)
    if args.worker:
        worker(*args.worker)
        return

    from analytics_zoo_tpu.ft import atomic, chaos as chaos_mod

    report = {"bench": "dist_train",
              "rows": ROWS, "global_batch": GLOBAL_BATCH, "epochs": EPOCHS,
              "devices_per_host": 2}
    with tempfile.TemporaryDirectory(prefix="dist_bench_") as workdir:
        # 1 + 2: step-time scaling and optimizer memory
        scaling, memory = {}, {}
        for n in (1, 2, 4):
            rcs, docs, errs = _gang(n, workdir, f"scale{n}")
            assert rcs == [0] * n, (rcs, errs)
            steps = docs[0]["steps"]
            wall = sum(d["wall_s"] for d in docs) / n
            scaling[str(n)] = {
                "hosts": n,
                "steps": steps,
                "wall_s_mean": round(wall, 3),
                "step_ms": round(wall / steps * 1000.0, 2),
                "maxrss_mb_max": max(d["maxrss_mb"] for d in docs),
            }
            memory[str(n)] = {
                "flat_size": docs[0]["flat_size"],
                "slice_len": docs[0]["slice_len"],
                "opt_bytes_sharded_per_host": docs[0]["opt_bytes_sharded"],
                "opt_bytes_replicated": docs[0]["opt_bytes_replicated"],
                "sharded_fraction": round(
                    docs[0]["opt_bytes_sharded"]
                    / docs[0]["opt_bytes_replicated"], 3),
            }
            print(f"[scaling] {n} host(s): {steps} steps, "
                  f"{scaling[str(n)]['step_ms']} ms/step, opt "
                  f"{memory[str(n)]['opt_bytes_sharded_per_host']}B/host "
                  f"vs {memory[str(n)]['opt_bytes_replicated']}B replicated")
        report["scaling"] = scaling
        report["opt_memory"] = memory

        # 3: kill → resume bitwise record (2 hosts)
        ref_ck = os.path.join(workdir, "ck_ref")
        rcs, docs, errs = _gang(2, workdir, "ref", ckpt_dir=ref_ck)
        assert rcs == [0, 0], (rcs, errs)
        assert docs[0]["params_sha256"] == docs[1]["params_sha256"]
        ref_digest = docs[0]["params_sha256"]

        kill_ck = os.path.join(workdir, "ck_kill")
        point = "dist_participant_torn"
        rcs, _docs, errs = _gang(2, workdir, "kill", ckpt_dir=kill_ck,
                                 chaos=point, chaos_host=1, chaos_skip=1,
                                 timeout_s=8)
        assert rcs[1] == chaos_mod.EXIT_CODE and rcs[0] != 0, (rcs, errs)
        committed = [s for s, _ in atomic.committed_checkpoints(kill_ck)]
        for _s, p in atomic.committed_checkpoints(kill_ck):
            atomic.verify_checksums(p)

        rcs, docs, errs = _gang(2, workdir, "resume", ckpt_dir=kill_ck)
        assert rcs == [0, 0], (rcs, errs)
        report["kill_resume"] = {
            "chaos_point": point,
            "victim_rc": chaos_mod.EXIT_CODE,
            "committed_steps_after_kill": committed,
            "torn_attempt_visible": False,
            "bitwise_identical_to_reference":
                all(d["params_sha256"] == ref_digest for d in docs),
        }
        print(f"[kill_resume] committed after kill: {committed}, bitwise "
              f"ok: {report['kill_resume']['bitwise_identical_to_reference']}")
        assert report["kill_resume"]["bitwise_identical_to_reference"]

    report["device"] = device_info()
    with open(args.out, "w") as f:
        json.dump(report, f, indent=1)
        f.write("\n")
    print(f"wrote {args.out}")


if __name__ == "__main__":
    main()
