"""Multi-host data-parallel fault tolerance: rendezvous, sharded optimizer
updates, and the two-phase sharded checkpoint commit.

The multi-host trainer (``Estimator.train_distributed``) runs N host
processes in lockstep. Each host computes gradients on its slice of the
global batch with a real ``shard_map``/``psum`` step over its local
device mesh, the hosts exchange gradient sums through a filesystem
rendezvous (:class:`DistContext` — the stand-in for a collective fabric,
chosen so the kill matrix can murder any host at any point and the
survivors' view of the world stays inspectable on disk), and the
optimizer update itself is *sharded*: host k updates only the k-th
``1/N`` window of the flattened parameter vector
(:class:`ShardedUpdater`), then the updated slices are all-gathered.
Optimizer state is therefore ``1/N`` per host — the ZeRO-1 trick applied
across hosts.

Checkpoints extend the :mod:`analytics_zoo_tpu.ft.atomic` commit
protocol to many writers with a two-phase commit
(:func:`commit_sharded_checkpoint`):

1. **Stage** — every host writes ``ckpt_N.tmp/host_K/arrays.npz`` plus a
   fsynced per-host shard manifest (``shard.json``: leaf keys, shapes,
   dtypes, CRC32s, commit id).
2. **Commit** — exactly one coordinator (host 0) validates every shard
   manifest (leaf-set disjointness and union completeness against the
   expected key set), writes the merged ``manifest.json``, renames
   ``ckpt_N.tmp`` → ``ckpt_N`` and drops the ``COMMIT`` marker last.

``latest_checkpoint`` / ``committed_checkpoints`` / ``read_checkpoint``
therefore can never observe a torn multi-host checkpoint: a kill at any
point leaves either the previous committed checkpoint or sweepable
staging debris. Every kill site is a
:mod:`analytics_zoo_tpu.ft.chaos` ``dist_*`` failure point and the
crash matrix (tests/test_dist_crash_recovery.py) dies at each one on
each role.

Restore is host-count independent: a checkpoint written by N hosts
restores on M hosts by re-slicing the concatenated optimizer shards
deterministically (:meth:`ShardedUpdater.restore_opt`).
"""

from __future__ import annotations

import io
import json
import logging
import os
import shutil
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from analytics_zoo_tpu.ft import atomic, chaos

logger = logging.getLogger("analytics_zoo_tpu")

__all__ = [
    "DistTimeoutError",
    "DistCommitError",
    "DistContext",
    "ShardedUpdater",
    "commit_sharded_checkpoint",
    "opt_shard_key",
    "split_round_robin",
]

#: Default rendezvous/commit deadline in seconds; overridable per run via
#: ``AZOO_DIST_TIMEOUT_S`` (the kill matrix shortens it so a murdered
#: peer is detected in seconds, not minutes).
DEFAULT_TIMEOUT_S = 60.0


def _default_timeout() -> float:
    try:
        return float(os.environ.get("AZOO_DIST_TIMEOUT_S",
                                    str(DEFAULT_TIMEOUT_S)))
    except ValueError:  # pragma: no cover - malformed env
        return DEFAULT_TIMEOUT_S


class DistTimeoutError(RuntimeError):
    """A cross-host rendezvous or commit wait passed its deadline with
    peers still missing — the surviving host's signal that a peer died
    (or stalled) mid-round. The trainer surfaces it like an async
    checkpoint-writer failure: the save attempt is aborted and swept,
    training itself continues."""


class DistCommitError(atomic.CheckpointError):
    """A two-phase sharded commit was aborted: shard validation failed
    (overlapping or missing leaves), the coordinator swept the staging
    directory, or another run committed over the target path."""


def opt_shard_key(host: int, index: int) -> str:
    """Leaf key under which optimizer-shard leaf ``index`` of ``host`` is
    checkpointed (``optshard/00001/00003``) — zero-padded so key order is
    host-partition order."""
    return f"optshard/{int(host):05d}/{int(index):05d}"


def split_round_robin(flat: Sequence, host_id: int, num_hosts: int) -> list:
    """Deterministic ownership partition of a flat leaf list for the
    sharded commit: host ``k`` owns ``flat[k::num_hosts]``. Every host
    computes the same partition from the same list, so leaf-set
    disjointness and union completeness hold by construction when all
    hosts are healthy — the coordinator still verifies both."""
    return list(flat[int(host_id)::int(num_hosts)])


class DistContext:
    """Identity and rendezvous of one simulated host in an N-host run.

    Hosts are OS processes; the "collective" is a filesystem all-gather:
    each :meth:`exchange` round writes this host's payload to
    ``<rendezvous_dir>/x<seq>/h<k>.npz`` (atomically, via
    write-to-tmp + ``os.replace``) and polls until all N peers' files
    appear, then loads them **in fixed host order** — which makes the
    cross-host sum on every host bitwise identical. A peer missing past
    the deadline raises :class:`DistTimeoutError` naming the missing
    hosts. ``num_hosts == 1`` short-circuits without touching the
    filesystem.
    """

    def __init__(self, host_id: int, num_hosts: int,
                 rendezvous_dir: Optional[str] = None, *,
                 timeout_s: Optional[float] = None,
                 poll_s: float = 0.002,
                 run_id: Optional[str] = None):
        host_id, num_hosts = int(host_id), int(num_hosts)
        if num_hosts < 1:
            raise ValueError(f"num_hosts must be >= 1, got {num_hosts}")
        if not 0 <= host_id < num_hosts:
            raise ValueError(
                f"host_id {host_id} out of range for {num_hosts} host(s)")
        if num_hosts > 1 and not rendezvous_dir:
            raise ValueError("multi-host runs need a rendezvous_dir")
        self.host_id = host_id
        self.num_hosts = num_hosts
        self.timeout_s = (_default_timeout() if timeout_s is None
                          else float(timeout_s))
        self.poll_s = float(poll_s)
        self.run_id = (os.environ.get("AZOO_DIST_RUN_ID", "")
                       if run_id is None else str(run_id))
        # namespace rounds by run id: a restarted attempt must never read
        # the round files a dead run left behind in the same rendezvous dir
        if rendezvous_dir and self.run_id:
            rendezvous_dir = os.path.join(rendezvous_dir, self.run_id)
        self.rendezvous_dir = rendezvous_dir
        self._seq = 0
        if num_hosts > 1:
            os.makedirs(rendezvous_dir, exist_ok=True)

    @property
    def is_coordinator(self) -> bool:
        """True on host 0 — the single host that merges shard manifests
        and drops the COMMIT marker."""
        return self.host_id == 0

    def commit_id(self, step: int) -> str:
        """The commit identity ``"<run_id>:<step>"`` staged into every
        shard manifest — what lets the coordinator tell this attempt's
        shards from stale debris of an earlier aborted run at the same
        step."""
        return f"{self.run_id}:{int(step)}"

    def exchange(self, payload: Dict[str, np.ndarray]
                 ) -> List[Dict[str, np.ndarray]]:
        """All-gather ``payload`` (a dict of arrays) across the N hosts;
        returns the N payloads in host order (index = host id). Blocks
        until every peer's round file appears; raises
        :class:`DistTimeoutError` past the deadline. The previous
        round's own file is deleted once this round is visible from all
        peers (a peer writing round *s* has, by construction, finished
        reading round *s-1*), so the rendezvous dir stays O(1)."""
        seq = self._seq
        if self.num_hosts == 1:
            self._seq = seq + 1
            return [{k: np.asarray(v) for k, v in payload.items()}]
        round_dir = os.path.join(self.rendezvous_dir, f"x{seq:08d}")
        os.makedirs(round_dir, exist_ok=True)
        mine = os.path.join(round_dir, f"h{self.host_id}.npz")
        tmp = mine + ".tmp"
        with open(tmp, "wb") as f:
            np.savez(f, **{k: np.asarray(v) for k, v in payload.items()})
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, mine)

        paths = [os.path.join(round_dir, f"h{k}.npz")
                 for k in range(self.num_hosts)]
        deadline = time.monotonic() + self.timeout_s
        while True:
            missing = [k for k, p in enumerate(paths)
                       if not os.path.isfile(p)]
            if not missing:
                break
            if time.monotonic() > deadline:
                raise DistTimeoutError(
                    f"host {self.host_id}: rendezvous round {seq} — "
                    f"host(s) {missing} missing after "
                    f"{self.timeout_s:.1f}s ({round_dir})")
            time.sleep(self.poll_s)
        out = []
        for p in paths:
            with np.load(p) as z:
                out.append({k: z[k] for k in z.files})
        if seq > 0:
            prev_dir = os.path.join(self.rendezvous_dir, f"x{seq - 1:08d}")
            try:
                os.unlink(os.path.join(prev_dir, f"h{self.host_id}.npz"))
            except OSError:  # pragma: no cover - already gone
                pass
            try:
                os.rmdir(prev_dir)  # last deleter removes the round dir
            except OSError:
                pass
        self._seq = seq + 1
        return out

    def allreduce_sum(self, payload: Dict[str, np.ndarray]
                      ) -> Dict[str, np.ndarray]:
        """:meth:`exchange` then sum each key across hosts **in fixed
        host order** — float summation order is what makes the reduced
        value bitwise identical on every host."""
        parts = self.exchange(payload)
        out: Dict[str, np.ndarray] = {}
        for key in payload:
            acc = np.array(parts[0][key], copy=True)
            for part in parts[1:]:
                acc = acc + part[key]
            out[key] = acc
        return out

    def barrier(self) -> None:
        """A trivial :meth:`exchange` round — returns once every host has
        arrived here (or raises :class:`DistTimeoutError`)."""
        self.exchange({"b": np.zeros((), np.int8)})


class ShardedUpdater:
    """The sharded optimizer update: host ``k`` owns window ``k`` of the
    flattened parameter vector.

    The parameter pytree is raveled to a single vector of ``flat_size``
    elements, zero-padded to ``num_hosts * slice_len`` (``slice_len`` is
    itself a multiple of the local data-axis device count so the window
    subdivides evenly across devices). ``tx.init`` runs on this host's
    padded window only — optimizer state is ``1/num_hosts`` of the full
    model per host. :meth:`step` is a jitted ``shard_map`` over the local
    mesh: each device applies ``tx.update`` + ``optax.apply_updates`` to
    its sub-window elementwise, then ``jax.lax.all_gather(tiled=True)``
    reassembles the host's full updated window. Every transform in the
    supported chain is elementwise, so the updated *parameters* match the
    per-leaf pytree update — but XLA's per-shape codegen can wobble the
    STORED moments by 1 ulp between the flat and tree layouts, which is
    why the single-host training path keeps the plain per-leaf step and
    only converts layouts at checkpoint time (:meth:`tree_to_flat` /
    :meth:`to_tree_state` — pure data movement, bitwise).

    Checkpointing: :meth:`opt_flat` names this host's optimizer leaves
    ``optshard/<host>/<i>``; :meth:`restore_opt` reads them back from a
    checkpoint written by *any* host count, re-slicing deterministically.
    """

    def __init__(self, tx, params_template, host_id: int, num_hosts: int,
                 mesh_config=None):
        import jax
        from jax.flatten_util import ravel_pytree

        from analytics_zoo_tpu.mesh.config import MeshConfig

        self.tx = tx
        self.host_id = int(host_id)
        self.num_hosts = int(num_hosts)
        if not 0 <= self.host_id < self.num_hosts:
            raise ValueError(
                f"host_id {host_id} out of range for {num_hosts} host(s)")
        flat, unravel = ravel_pytree(params_template)
        self._unravel = unravel
        self.flat_size = int(flat.size)
        if self.flat_size == 0:
            raise ValueError("cannot shard an empty parameter pytree")
        self._flat_dtype = np.dtype(flat.dtype)
        if mesh_config is None:
            mesh_config = MeshConfig.host_local_data()
        self.mesh_config = mesh_config
        n_dev = int(mesh_config.axis_length("data"))
        per_dev = -(-self.flat_size // (self.num_hosts * n_dev))
        self.slice_len = per_dev * n_dev
        self.padded_size = self.num_hosts * self.slice_len
        self._mesh = mesh_config.build()
        self._opt_struct = jax.eval_shape(
            tx.init,
            jax.ShapeDtypeStruct((self.slice_len,), self._flat_dtype))
        self._step_fns: Dict[bool, Any] = {}

    @property
    def opt_leaf_count(self) -> int:
        """Number of optimizer-state leaves per host shard (identical on
        every host — same ``tx``, same ``slice_len``)."""
        import jax

        return len(jax.tree_util.tree_leaves(self._opt_struct))

    def padded_vector(self, tree) -> np.ndarray:
        """Ravel ``tree`` eagerly and zero-pad to ``padded_size``."""
        from jax.flatten_util import ravel_pytree

        flat, _ = ravel_pytree(tree)
        vec = np.zeros((self.padded_size,), dtype=self._flat_dtype)
        vec[: self.flat_size] = np.asarray(flat)
        return vec

    def slice_of(self, vec: np.ndarray, host: int) -> np.ndarray:
        """Window ``host`` of a padded flat vector."""
        lo = int(host) * self.slice_len
        return np.asarray(vec)[lo: lo + self.slice_len]

    def init_opt(self, params):
        """This host's optimizer shard: ``tx.init`` on the host's padded
        parameter window (mirrors what ``tx.init`` on the full pytree
        would hold for these elements)."""
        import jax.numpy as jnp

        return self.tx.init(
            jnp.asarray(self.slice_of(self.padded_vector(params),
                                      self.host_id)))

    def tree_to_flat(self, tree_state):
        """Convert a per-leaf (tree-layout) optimizer state — what
        ``tx.init(params)`` builds and the single-host training path
        updates — into the canonical flat-vector layout this class
        checkpoints. Single-host only (the tree state IS the whole
        model). Pure data movement: per-element subtrees are raveled in
        parameter order and zero-padded (the padded tail matches a fresh
        ``init_opt`` — zero grads keep zero moments), replicated leaves
        pass through. Bitwise inverse of :meth:`to_tree_state`."""
        if self.num_hosts != 1:
            raise ValueError(
                "tree_to_flat converts a whole-model optimizer state — "
                f"only valid with num_hosts == 1, not {self.num_hosts}")
        import jax
        from jax.flatten_util import ravel_pytree

        outer = jax.tree_util.tree_structure(self._opt_struct)
        struct_leaves = jax.tree_util.tree_leaves(self._opt_struct)
        parts = outer.flatten_up_to(tree_state)
        out = []
        for s, part in zip(struct_leaves, parts):
            if s.ndim == 1 and s.shape[0] == self.slice_len:
                rp, _ = ravel_pytree(part)
                vec = np.zeros((self.slice_len,), dtype=s.dtype)
                vec[: self.flat_size] = np.asarray(rp).astype(
                    s.dtype, copy=False)
                out.append(vec)
            else:
                out.append(np.asarray(part))
        return jax.tree_util.tree_unflatten(outer, out)

    def to_tree_state(self, flat_state):
        """Inverse of :meth:`tree_to_flat`: rebuild the per-leaf
        optimizer state from the canonical flat layout (e.g. what
        :meth:`restore_opt` returns), for the single-host training path.
        Bitwise: unraveling splits the vector back into the exact
        parameter-shaped leaves it was raveled from."""
        if self.num_hosts != 1:
            raise ValueError(
                "to_tree_state rebuilds a whole-model optimizer state — "
                f"only valid with num_hosts == 1, not {self.num_hosts}")
        import jax
        import jax.numpy as jnp

        outer = jax.tree_util.tree_structure(self._opt_struct)
        struct_leaves = jax.tree_util.tree_leaves(self._opt_struct)
        flat_leaves = jax.tree_util.tree_leaves(flat_state)
        if len(flat_leaves) != len(struct_leaves):
            raise ValueError(
                f"flat optimizer state has {len(flat_leaves)} leaves, "
                f"expected {len(struct_leaves)}")
        subtrees = []
        for s, leaf in zip(struct_leaves, flat_leaves):
            if s.ndim == 1 and s.shape[0] == self.slice_len:
                subtrees.append(self._unravel(
                    jnp.asarray(np.asarray(leaf)[: self.flat_size])))
            else:
                subtrees.append(jnp.asarray(np.asarray(leaf)))
        return jax.tree_util.tree_unflatten(outer, subtrees)

    def mask_vector(self, params, update_mask) -> Optional[np.ndarray]:
        """The boolean trainability mask as a padded flat vector (True =
        trainable), or None when ``update_mask`` is None (everything
        trainable). Padding is False so the padded tail can never be
        touched by an update."""
        if update_mask is None:
            return None
        import jax

        leaves_p = jax.tree_util.tree_leaves(params)
        leaves_m = jax.tree_util.tree_leaves(update_mask)
        parts = [np.full(np.shape(p), bool(m))
                 for p, m in zip(leaves_p, leaves_m)]
        vec = np.zeros((self.padded_size,), dtype=bool)
        flat = np.concatenate([p.ravel() for p in parts])
        vec[: self.flat_size] = flat
        return vec

    def _get_step_fn(self, with_mask: bool):
        if with_mask in self._step_fns:
            return self._step_fns[with_mask]
        import jax
        import jax.numpy as jnp
        import optax
        from jax import shard_map
        from jax.flatten_util import ravel_pytree
        from jax.sharding import PartitionSpec as P

        L, V, Vp = self.slice_len, self.flat_size, self.padded_size
        k, tx = self.host_id, self.tx
        opt_specs = jax.tree_util.tree_map(
            lambda s: P("data") if (len(s.shape) == 1 and s.shape[0] == L)
            else P(),
            self._opt_struct)

        if with_mask:
            def body(p, g, m, opt):
                # zero frozen grads BEFORE the transform (they must not
                # accumulate moments) and the updates after (decoupled decay
                # must not drift frozen params) — the plain train step's
                # exact masking discipline
                g = jnp.where(m, g, jnp.zeros_like(g))
                u, new_opt = tx.update(g, opt, p)
                u = jnp.where(m, u, jnp.zeros_like(u))
                new_p = optax.apply_updates(p, u)
                return jax.lax.all_gather(new_p, "data", tiled=True), new_opt

            wrapped = shard_map(
                body, mesh=self._mesh,
                in_specs=(P("data"), P("data"), P("data"), opt_specs),
                out_specs=(P(), opt_specs), check_vma=False)

            def run(params, grad_vec, opt_state, mask_vec):
                flat, _ = ravel_pytree(params)
                if Vp > V:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((Vp - V,), flat.dtype)])
                p = flat[k * L:(k + 1) * L]
                g = grad_vec.astype(flat.dtype)[k * L:(k + 1) * L]
                m = mask_vec[k * L:(k + 1) * L]
                return wrapped(p, g, m, opt_state)
        else:
            def body(p, g, opt):
                u, new_opt = tx.update(g, opt, p)
                new_p = optax.apply_updates(p, u)
                return jax.lax.all_gather(new_p, "data", tiled=True), new_opt

            wrapped = shard_map(
                body, mesh=self._mesh,
                in_specs=(P("data"), P("data"), opt_specs),
                out_specs=(P(), opt_specs), check_vma=False)

            def run(params, grad_vec, opt_state):
                flat, _ = ravel_pytree(params)
                if Vp > V:
                    flat = jnp.concatenate(
                        [flat, jnp.zeros((Vp - V,), flat.dtype)])
                p = flat[k * L:(k + 1) * L]
                g = grad_vec.astype(flat.dtype)[k * L:(k + 1) * L]
                return wrapped(p, g, opt_state)

        fn = jax.jit(run)
        self._step_fns[with_mask] = fn
        return fn

    def step(self, params, grad_vec, opt_state, mask_vec=None):
        """One sharded optimizer step. ``grad_vec`` is the globally
        combined padded gradient vector (identical on every host);
        returns ``(new_slice, new_opt_state)`` where ``new_slice`` is
        this host's updated ``(slice_len,)`` parameter window — what the
        next :meth:`DistContext.exchange` round circulates."""
        import jax.numpy as jnp

        fn = self._get_step_fn(mask_vec is not None)
        g = jnp.asarray(np.asarray(grad_vec))
        if mask_vec is None:
            return fn(params, g, opt_state)
        return fn(params, g, opt_state, jnp.asarray(np.asarray(mask_vec)))

    def assemble(self, slices: Sequence[np.ndarray]):
        """Rebuild the full parameter pytree from the N host windows (in
        host order) — truncates the zero padding and unravels."""
        if len(slices) != self.num_hosts:
            raise ValueError(
                f"assemble needs {self.num_hosts} slices, got {len(slices)}")
        full = np.concatenate([np.asarray(s) for s in slices])
        if full.size != self.padded_size:
            raise ValueError(
                f"assembled vector has {full.size} elements, expected "
                f"{self.padded_size}")
        return self._unravel(full[: self.flat_size])

    def opt_flat(self, opt_state) -> List[Tuple[str, np.ndarray]]:
        """This host's optimizer shard as named flat leaves for the
        sharded commit (``optshard/<host>/<i>`` in tree-flatten order)."""
        import jax

        leaves = jax.tree_util.tree_leaves(opt_state)
        return [(opt_shard_key(self.host_id, i), np.asarray(leaf))
                for i, leaf in enumerate(leaves)]

    def expected_opt_keys(self) -> set:
        """Every optimizer-shard key the N hosts will stage — part of the
        coordinator's union-completeness check."""
        return {opt_shard_key(h, i)
                for h in range(self.num_hosts)
                for i in range(self.opt_leaf_count)}

    def restore_opt(self, flat_map: Dict[str, np.ndarray],
                    dist_meta: Dict[str, Any]):
        """Rebuild this host's optimizer shard from a checkpoint written
        on ``dist_meta['num_hosts']`` hosts (possibly ≠ this run's count).

        Vector leaves (per-element state like Adam's ``mu``/``nu``) are
        concatenated across the old hosts' windows, truncated to the true
        flat size, re-padded and re-sliced for this host; replicated
        leaves (step counters) are taken from host 0. Deterministic: the
        same checkpoint restored on any host count yields bitwise the
        same optimizer state for any given parameter element."""
        import jax
        import jax.numpy as jnp

        n_old = int(dist_meta["num_hosts"])
        L_old = int(dist_meta["slice_len"])
        n_leaves = int(dist_meta["opt_leaves"])
        V = int(dist_meta["flat_size"])
        if V != self.flat_size:
            raise ValueError(
                f"checkpoint flattened {V} parameters, this model has "
                f"{self.flat_size} — not the same model")
        if n_leaves != self.opt_leaf_count:
            raise ValueError(
                f"checkpoint has {n_leaves} optimizer leaves per shard, "
                f"this optimizer has {self.opt_leaf_count} — not the same "
                "transform chain")
        struct_leaves, treedef = jax.tree_util.tree_flatten(self._opt_struct)
        new_leaves = []
        for i, s in enumerate(struct_leaves):
            parts = []
            for h in range(n_old):
                key = opt_shard_key(h, i)
                if key not in flat_map:
                    raise atomic.CheckpointCorruptError(
                        f"optimizer shard leaf {key!r} missing from "
                        "checkpoint")
                parts.append(np.asarray(flat_map[key]))
            if len(s.shape) == 1 and s.shape[0] == self.slice_len:
                for h, p in enumerate(parts):
                    if p.shape != (L_old,):
                        raise atomic.CheckpointCorruptError(
                            f"optimizer shard leaf {opt_shard_key(h, i)!r} "
                            f"has shape {p.shape}, expected ({L_old},)")
                full = np.concatenate(parts)[:V]
                mine = np.zeros((self.slice_len,), dtype=s.dtype)
                lo = self.host_id * self.slice_len
                seg = full[lo: lo + self.slice_len]
                mine[: seg.size] = seg
                new_leaves.append(jnp.asarray(mine))
            else:
                new_leaves.append(jnp.asarray(parts[0]).astype(s.dtype)
                                  .reshape(s.shape))
        return jax.tree_util.tree_unflatten(treedef, new_leaves)


def _abort_staging(tmp: str, outcome: str) -> None:
    from analytics_zoo_tpu.common.observability import (
        checkpoint_sweep_counters, distributed_metrics)

    shutil.rmtree(tmp, ignore_errors=True)
    checkpoint_sweep_counters()["dist_abort"].inc()
    distributed_metrics()["commits"].labels(outcome=outcome).inc()


def _read_committed_commit_id(path: str) -> Optional[str]:
    try:
        manifest = atomic.read_manifest(path)
    except atomic.CheckpointCorruptError:
        return None
    return (manifest.get("shards") or {}).get("commit_id")


def commit_sharded_checkpoint(path: str,
                              flat: List[Tuple[str, np.ndarray]], *,
                              host_id: int, num_hosts: int,
                              expected_keys: Optional[set] = None,
                              metadata: Optional[Dict] = None,
                              commit_id: str = "",
                              timeout_s: Optional[float] = None,
                              poll_s: float = 0.01,
                              overwrite: bool = True,
                              shard_meta: Optional[Dict] = None) -> str:
    """Two-phase multi-writer commit of a sharded checkpoint directory.

    Called by **every** host with its own ``flat`` leaf list. All hosts
    stage ``<path>.tmp/host_<k>/`` (``arrays.npz`` then a fsynced
    ``shard.json`` carrying keys/shapes/dtypes/CRC32s and ``commit_id``);
    host 0 then waits for all N shard manifests, validates leaf-set
    disjointness and (when ``expected_keys`` is given) union
    completeness, sweeps any stale ``host_K/`` debris whose commit id
    does not match, writes the merged ``manifest.json``, renames and
    drops ``COMMIT`` last. Participants block until the commit lands.

    Failure semantics: a coordinator-side timeout or validation failure
    sweeps the whole staging tree (counted in
    ``zoo_checkpoint_sweeps_total{kind="dist_abort"}``) and raises
    :class:`DistTimeoutError` / :class:`DistCommitError`; a
    participant-side wait past the deadline raises
    :class:`DistTimeoutError`. Either way no reader can ever observe a
    torn checkpoint. Returns ``path`` on success (on every host)."""
    from analytics_zoo_tpu.common.observability import (
        checkpoint_sweep_counters, distributed_metrics, get_tracer)

    host_id, num_hosts = int(host_id), int(num_hosts)
    if timeout_s is None:
        timeout_s = _default_timeout()
    parent = os.path.dirname(path) or "."
    os.makedirs(parent, exist_ok=True)
    if not overwrite and atomic.is_committed(path):
        raise FileExistsError(f"{path} exists and overwrite=False")

    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)  # hosts race; exist_ok makes it benign
    host_dir = os.path.join(tmp, f"host_{host_id}")
    if os.path.isdir(host_dir):
        shutil.rmtree(host_dir)  # own debris from an earlier aborted attempt
    os.makedirs(host_dir)

    arrays = {f"a{i}": np.asarray(a) for i, (_, a) in enumerate(flat)}
    buf = io.BytesIO()
    np.savez(buf, **arrays)
    data = buf.getvalue()
    with open(os.path.join(host_dir, atomic.ARRAYS), "wb") as f:
        if chaos.should_fail("dist_participant_torn"):
            f.write(data[: max(1, len(data) // 2)])
            atomic._fsync_file(f)
            chaos.fail("dist_participant_torn")
        f.write(data)
        atomic._fsync_file(f)
    chaos.maybe_fail("dist_participant_before_manifest")

    shard = {
        "format": atomic.FORMAT,
        "host": host_id,
        "num_hosts": num_hosts,
        "commit_id": commit_id,
        "keys": [k for k, _ in flat],
        "leaves": [atomic._leaf_record(k, np.asarray(a)) for k, a in flat],
    }
    if shard_meta:
        # writer-declared shard identity (e.g. the pipeline trainer's
        # {"stage": k}) — rides in shard.json and is copied into the
        # merged manifest's per-host entries for inspectors
        for mk, mv in shard_meta.items():
            shard.setdefault(str(mk), mv)
    with open(os.path.join(host_dir, atomic.SHARD_MANIFEST), "wb") as f:
        f.write(json.dumps(shard).encode())
        atomic._fsync_file(f)
    atomic._fsync_dir(host_dir)
    atomic._fsync_dir(tmp)

    if host_id != 0:
        # participant: staging done — wait for the coordinator's commit
        deadline = time.monotonic() + timeout_s
        while True:
            if atomic.is_committed(path):
                got = _read_committed_commit_id(path)
                if got == commit_id:
                    return path
                if not os.path.isdir(tmp):
                    raise DistCommitError(
                        f"host {host_id}: {path!r} was committed by a "
                        f"different attempt (commit id {got!r}, expected "
                        f"{commit_id!r})")
                # an OLDER committed checkpoint at the same step while our
                # staging still exists: the coordinator is mid-overwrite —
                # keep polling until it swaps in this attempt's commit
            if (not os.path.isdir(tmp)) and (not os.path.isdir(path)):
                raise DistCommitError(
                    f"host {host_id}: coordinator aborted commit "
                    f"{commit_id!r} of {path!r} (staging swept)")
            if time.monotonic() > deadline:
                raise DistTimeoutError(
                    f"host {host_id}: commit {commit_id!r} of {path!r} "
                    f"not finalized within {timeout_s:.1f}s")
            time.sleep(poll_s)

    # ------------------------------------------------------------------
    # coordinator
    # ------------------------------------------------------------------
    metrics = distributed_metrics()
    with get_tracer().span("dist.commit", path=path, hosts=num_hosts,
                           commit_id=commit_id):
        shard_manifests: Dict[int, Dict[str, Any]] = {}
        deadline = time.monotonic() + timeout_s
        while len(shard_manifests) < num_hosts:
            for k in range(num_hosts):
                if k in shard_manifests:
                    continue
                sp = os.path.join(tmp, f"host_{k}", atomic.SHARD_MANIFEST)
                try:
                    with open(sp) as f:
                        man = json.load(f)
                except (OSError, ValueError):
                    continue
                if man.get("commit_id") != commit_id:
                    continue  # stale debris — the live host will restage
                shard_manifests[k] = man
            if len(shard_manifests) == num_hosts:
                break
            if time.monotonic() > deadline:
                missing = sorted(set(range(num_hosts))
                                 - set(shard_manifests))
                _abort_staging(tmp, "timeout")
                raise DistTimeoutError(
                    f"coordinator: host(s) {missing} never staged commit "
                    f"{commit_id!r} within {timeout_s:.1f}s — staging "
                    "swept, training continues")
            time.sleep(poll_s)

        # validation: disjointness + (optionally) union completeness
        owner: Dict[str, int] = {}
        for k in range(num_hosts):
            for key in shard_manifests[k].get("keys", []):
                if key in owner:
                    _abort_staging(tmp, "aborted")
                    raise DistCommitError(
                        f"leaf {key!r} claimed by both host {owner[key]} "
                        f"and host {k} — shard sets must be disjoint")
                owner[key] = k
        if expected_keys is not None:
            missing_keys = set(expected_keys) - set(owner)
            extra_keys = set(owner) - set(expected_keys)
            if missing_keys or extra_keys:
                _abort_staging(tmp, "aborted")
                raise DistCommitError(
                    f"shard union mismatch: missing "
                    f"{sorted(missing_keys)[:5]}, unexpected "
                    f"{sorted(extra_keys)[:5]}")
        chaos.maybe_fail("dist_coordinator_before_merge")

        # sweep stale host dirs (wrong/absent commit id) before the rename
        # so the committed directory never carries undeclared payloads
        sweeps = checkpoint_sweep_counters()
        for fname in os.listdir(tmp):
            m = atomic._HOST_DIR_RE.match(fname)
            if m and int(m.group(1)) not in shard_manifests:
                shutil.rmtree(os.path.join(tmp, fname), ignore_errors=True)
                sweeps["orphan_shard"].inc()

        keys: List[str] = []
        recs: List[Dict[str, Any]] = []
        hosts_meta = []
        for k in range(num_hosts):
            man = shard_manifests[k]
            host_entry = {"host": k, "leaves": len(man["keys"])}
            if "stage" in man:
                host_entry["stage"] = man["stage"]
            hosts_meta.append(host_entry)
            for idx, (key, rec) in enumerate(zip(man["keys"],
                                                 man["leaves"])):
                merged_rec = dict(rec)
                merged_rec["host"] = k
                merged_rec["index"] = idx
                keys.append(key)
                recs.append(merged_rec)
        merged = {
            "format": atomic.FORMAT,
            "keys": keys,
            "leaves": recs,
            "metadata": metadata or {},
            "shards": {"num_hosts": num_hosts, "commit_id": commit_id,
                       "hosts": hosts_meta},
        }
        with open(os.path.join(tmp, atomic.MANIFEST), "wb") as f:
            f.write(json.dumps(merged).encode())
            atomic._fsync_file(f)
        atomic._fsync_dir(tmp)

        if os.path.isdir(path):
            shutil.rmtree(path)  # overwrite / husk replacement
        os.rename(tmp, path)
        atomic._fsync_dir(parent)
        chaos.maybe_fail("dist_coordinator_before_commit")

        with open(os.path.join(path, atomic.COMMIT), "w") as f:
            json.dump({"format": atomic.FORMAT, "commit_id": commit_id,
                       "bytes": len(data)}, f)
            atomic._fsync_file(f)
        atomic._fsync_dir(path)
        metrics["commits"].labels(outcome="committed").inc()
        logger.info("sharded checkpoint committed: %s (%d hosts, %d leaves)",
                    path, num_hosts, len(keys))
    return path
