"""Multi-process distributed context: one global mesh across worker
processes, with re-formation as the unit of elastic recovery.

Reference parity: the reference's allreduce mode ran one Horovod ring across
worker pods (NCCL/Gloo), re-built by a master-hosted rendezvous when
membership changed (SURVEY §3.4). The TPU-native rebuild uses
`jax.distributed` + ONE `jax.sharding.Mesh` over every process's devices;
gradient averaging is the `psum` XLA inserts over the `data` axis (ICI
in-slice, DCN across hosts). XLA's world is static per initialize(), so
elasticity = re-formation: tear the world down, re-initialize with the new
process set, restore from the latest checkpoint, resume at the exact task
boundary (the task queue makes this data-loss-free).

A worker cohort (elasticdl_tpu/worker/cohort.py) runs SPMD: every process
executes the same jitted steps; per-process data enters as process-local
shards of the global batch via `make_global_batch`.
"""

from __future__ import annotations

import os
from collections.abc import Mapping
from typing import Any, Dict, Optional, Sequence

import jax
import numpy as np

from elasticdl_tpu.common.log_utils import default_logger
from elasticdl_tpu.observability import tracing
from elasticdl_tpu.observability.registry import default_registry
from elasticdl_tpu.parallel import mesh as mesh_lib

logger = default_logger(__name__)

_reg = default_registry()
_HANDOFF_STAGED = _reg.counter(
    "edl_handoff_staged_leaves_total",
    "state leaves pulled to host because their owner devices vanish")
_HANDOFF_REPLICATED = _reg.counter(
    "edl_handoff_replicated_leaves_total",
    "leaves that lost their spec on the new mesh and fell back to "
    "replication (correct but larger — watch this on shrinks)")


class CohortContext:
    """The per-process handle on the distributed world."""

    def __init__(self, coordinator_addr: str, num_processes: int,
                 process_id: int, world_version: int = 0):
        self.coordinator_addr = coordinator_addr
        self.num_processes = num_processes
        self.process_id = process_id
        self.world_version = world_version
        self._initialized = False

    # ------------------------------------------------------------------ #

    def initialize(self) -> None:
        """jax.distributed.initialize — collective, blocks until every
        process of the world version has joined."""
        jax.distributed.initialize(
            coordinator_address=self.coordinator_addr,
            num_processes=self.num_processes,
            process_id=self.process_id,
        )
        self._initialized = True
        logger.info(
            "distributed world v%d up: process %d/%d, %d global devices",
            self.world_version, self.process_id, self.num_processes,
            len(jax.devices()),
        )

    def shutdown(self) -> None:
        if self._initialized:
            jax.distributed.shutdown()
            self._initialized = False

    @property
    def is_leader(self) -> bool:
        return self.process_id == 0

    # ------------------------------------------------------------------ #

    def global_mesh(self, axis_sizes: Optional[Dict[str, int]] = None):
        """Mesh over ALL processes' devices (default: 1-D data axis)."""
        return mesh_lib.build_mesh(axis_sizes, jax.devices())

    def broadcast_ints(self, values: Sequence[int]) -> np.ndarray:
        """Leader -> all: small int64 control vector (the cohort's task/
        checkpoint/LR protocol rides this).

        Shipped as int32 HALVES: with jax_enable_x64 off (the default,
        everywhere in this repo), an int64 array entering
        broadcast_one_to_all is canonicalized to int32 — silently wrapping
        anything past 2^31 (float64 LR bit-patterns; record spans of a
        Criteo-1TB-sized file). Splitting each value into two int32s keeps
        the full 64 bits across the wire."""
        from jax.experimental import multihost_utils

        arr = np.ascontiguousarray(np.asarray(values, np.int64))
        halves = arr.view(np.int32)            # (2n,), little-endian pairs
        out = np.asarray(
            multihost_utils.broadcast_one_to_all(
                halves, is_source=self.is_leader
            ),
            dtype=np.int32,
        )
        return np.ascontiguousarray(out).view(np.int64)

    def allgather_ints(self, values: Sequence[int]) -> np.ndarray:
        """All -> all: every process contributes a small int64 row, every
        process receives the (num_processes, len(values)) stack — the
        follower->leader telemetry channel (worker/cohort.py's member-
        stats exchange rides this at task boundaries). COLLECTIVE: every
        process of the world must call it with an equal-length row.

        Same int32-halving discipline as broadcast_ints: with
        jax_enable_x64 off an int64 array entering the collective would be
        silently canonicalized to int32, wrapping anything past 2^31."""
        arr = np.ascontiguousarray(np.asarray(values, np.int64))
        if jax.process_count() == 1:
            return arr[None, :]
        from jax.experimental import multihost_utils

        halves = arr.view(np.int32)            # (2n,), little-endian pairs
        out = np.asarray(
            multihost_utils.process_allgather(halves), dtype=np.int32
        )                                      # (P, 2n)
        return np.ascontiguousarray(out).view(np.int64)

    def barrier(self, name: str) -> None:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)


def make_global_batch(mesh, batch: Any, partition=None) -> Any:
    """Assemble a global sharded batch from each process's IDENTICAL host
    batch: every process holds the same full global batch (readers are
    deterministic), so each local device simply pulls its own slice via
    `make_array_from_callback` — correct for ANY partition spec (data, seq,
    or mixed axes across the process boundary), with no cross-process data
    motion.

    Single-process meshes fall through to the ordinary shard_batch path.
    """
    if jax.process_count() == 1:
        return mesh_lib.shard_batch(mesh, batch, partition)

    from jax.sharding import NamedSharding

    def put(x, sharding):
        x = np.asarray(x)
        return jax.make_array_from_callback(
            x.shape, sharding, lambda idx: x[idx]
        )

    if not isinstance(batch, Mapping):
        # non-dict host batch (bare array / tuple pytree): per-key partition
        # overrides can't apply, so the whole tree takes the default batch
        # spec — mirrors shard_batch's partition=None path
        sh = NamedSharding(mesh, mesh_lib.batch_key_spec(mesh, "", partition))
        return jax.tree_util.tree_map(lambda x: put(x, sh), batch)
    out = {}
    for key, value in batch.items():
        sh = NamedSharding(mesh, mesh_lib.batch_key_spec(mesh, key, partition))
        out[key] = jax.tree_util.tree_map(lambda x, s=sh: put(x, s), value)
    return out


def make_global_batch_stack(mesh, batches, partition=None) -> Any:
    """K identical-on-every-process host batches -> one global pytree with
    a leading step axis (leaves (K, B, ...), sharded P(None, <batch spec>))
    for `Trainer.train_many` — the multi-process twin of
    `mesh.shard_batch_stack`, assembled per-device like make_global_batch."""
    if jax.process_count() == 1:
        return mesh_lib.shard_batch_stack(mesh, batches, partition)

    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(leaves, spec):
        x = np.stack([np.asarray(l) for l in leaves])
        sh = NamedSharding(mesh, P(None, *spec))
        return jax.make_array_from_callback(x.shape, sh, lambda idx: x[idx])

    if not isinstance(batches[0], Mapping):
        # non-dict batches: default data spec on every leaf (matches
        # make_global_batch / mesh.shard_batch_stack fallbacks)
        spec = mesh_lib.batch_key_spec(mesh, "", partition)
        return jax.tree_util.tree_map(
            lambda *ls: put(ls, spec), *batches)
    out = {}
    for key in batches[0]:
        spec = mesh_lib.batch_key_spec(mesh, key, partition)
        out[key] = jax.tree_util.tree_map(
            lambda *ls, s=spec: put(ls, s), *(b[key] for b in batches))
    return out


def neighbor_world_sizes(
    current: int,
    pending: Optional[int] = None,
    min_size: int = 1,
    max_size: Optional[int] = None,
) -> list:
    """Candidate next world sizes for speculative compilation: the
    master's announced pending size (first — it is the one about to
    happen), then N-1 and N+1, clamped to [min_size, max_size]."""
    sizes = {current - 1, current + 1}
    if pending is not None:
        sizes.add(int(pending))
    sizes = {
        s for s in sizes
        if s >= min_size and (max_size is None or s <= max_size)
        and s != current
    }
    return sorted(sizes, key=lambda s: (s != pending, abs(s - current), s))


# ---------------------------------------------------------------------- #
# Live state handoff (rescale fast path, part 3)
#
# A PLANNED resize does not need the checkpoint-restore round trip: the
# donor arrays are still resident, and jax.device_put reshards them
# directly onto the new mesh. Only shards whose owner set changes move;
# a leaf already laid out identically passes through untouched.


class _HostStaged:
    """A state leaf pulled to host because its owner devices are about to
    disappear (cross-process teardown path); carries the PartitionSpec it
    had so `apply` can lay it back out on the new mesh."""

    __slots__ = ("array", "spec")

    def __init__(self, array, spec):
        self.array = array
        self.spec = spec


def _leaf_spec(x):
    from jax.sharding import PartitionSpec as P

    sharding = getattr(x, "sharding", None)
    spec = getattr(sharding, "spec", None)
    return spec if spec is not None else P()


def stage_leaf(x, spec=None) -> _HostStaged:
    """Wrap one array for the staged half of a handoff: the embedding
    tier's shard migrations (embedding/reshard.py) ride the same
    stage-then-reshard lane a TrainState leaf takes when its owner
    devices vanish. `spec` defaults to the array's own PartitionSpec
    (P() for host numpy arrays)."""
    import numpy as _np

    if isinstance(x, jax.Array):
        return _HostStaged(_np.asarray(jax.device_get(x)),
                           spec if spec is not None else _leaf_spec(x))
    return _HostStaged(_np.asarray(x), spec if spec is not None else _leaf_spec(x))


def reshard_state(state: Any, new_mesh) -> Any:
    """Reshard a TrainState (or any pytree of jax arrays) onto `new_mesh`,
    preserving each leaf's PartitionSpec (pruned to the new mesh's axes).
    Leaves whose layout is unchanged are untouched; a spec the new mesh
    cannot satisfy (row count not divisible by the shrunken axis) falls
    back to replication with a warning — correct, just larger."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def move(x):
        if isinstance(x, _HostStaged):
            value, spec = x.array, x.spec
        elif isinstance(x, jax.Array):
            value, spec = x, _leaf_spec(x)
        else:
            return x
        spec = mesh_lib.prune_spec(new_mesh, spec)
        try:
            return jax.device_put(value, NamedSharding(new_mesh, spec))
        except ValueError:
            _HANDOFF_REPLICATED.inc()
            logger.warning(
                "leaf %s cannot keep spec %s on the %s mesh; replicating",
                getattr(value, "shape", "?"), spec,
                dict(zip(new_mesh.axis_names, new_mesh.devices.shape)),
            )
            return jax.device_put(value, NamedSharding(new_mesh, P()))

    return jax.tree_util.tree_map(
        move, state, is_leaf=lambda x: isinstance(x, _HostStaged)
    )


class LiveStateHandoff:
    """One planned-resize handoff: capture on the old world, apply on the
    new — skipping the checkpoint-restore round trip.

    `capture` is zero-copy (device arrays are kept by reference) and
    records the step so the recipient can arbitrate against the newest
    durable checkpoint. `stage_to_host` exists for teardown paths where
    donor devices are about to vanish: ONLY leaves with at least one owner
    outside the surviving set are pulled to host (the snapshot is scoped
    to shards whose owner set changes; everything else stays on-device).
    `apply` reshards everything onto the new mesh via `reshard_state` and
    consumes the capture (one-shot)."""

    def __init__(self):
        self._state: Any = None
        self._step: Optional[int] = None

    @property
    def captured(self) -> bool:
        return self._state is not None

    @property
    def step(self) -> Optional[int]:
        return self._step

    def capture(self, state: Any) -> "LiveStateHandoff":
        self._state = state
        # host sync — callers sit at a task/step boundary by construction
        self._step = int(jax.device_get(state.step)) if hasattr(
            state, "step") else None
        return self

    def stage_to_host(self, surviving_device_ids) -> int:
        """Pull to host the leaves with any owner OUTSIDE the surviving
        device set; returns how many leaves were staged. In-process
        resizes never need this (device_put reads donors directly);
        teardown paths call it before the old world dies."""
        surviving = set(int(d) for d in surviving_device_ids)
        staged = 0

        def maybe_stage(x):
            nonlocal staged
            if not isinstance(x, jax.Array):
                return x
            owners = {int(d.id) for d in x.sharding.device_set}
            if owners <= surviving:
                return x
            staged += 1
            return _HostStaged(np.asarray(jax.device_get(x)), _leaf_spec(x))

        with tracing.span("handoff.stage_to_host") as sp:
            self._state = jax.tree_util.tree_map(maybe_stage, self._state)
            sp.set(staged_leaves=staged)
        _HANDOFF_STAGED.inc(staged)
        return staged

    def apply(self, new_mesh) -> Any:
        """Reshard the captured state onto `new_mesh`; consumes the
        capture so stale donors cannot be applied twice."""
        if self._state is None:
            raise RuntimeError("LiveStateHandoff.apply with nothing captured")
        state, self._state = self._state, None
        return reshard_state(state, new_mesh)

    def discard(self) -> None:
        self._state = None
        self._step = None


def context_from_env(cfg) -> Optional[CohortContext]:
    """Build the context for this process from config + env (the process
    manager exports EDL_PROCESS_ID per spawned cohort member).

    `EDL_NUM_PROCESSES` overrides `cfg.num_processes`: dynamic world
    resizing re-forms the cohort at a DIFFERENT size than the config's
    original — the manager tells each member the new world size through the
    environment so the argv (which is the job's immutable config) stays
    untouched. `EDL_WORLD_VERSION` carries the generation counter for logs
    and LR-rescale decisions. A resized-to-1 cohort is still a cohort
    (EDL_PROCESS_ID present), so the override may legitimately be 1.
    """
    n = int(os.environ.get("EDL_NUM_PROCESSES", "0") or 0) or cfg.num_processes
    pid = os.environ.get("EDL_PROCESS_ID")
    if n <= 1 and pid is None:
        return None
    if pid is None and os.environ.get("EDL_PROCESS_ID_FROM_HOSTNAME") == "1":
        # k8s StatefulSet flavor: pods are <name>-<ordinal>; the ordinal IS
        # the cohort process id (stable across pod restarts, which is what
        # makes a StatefulSet the right k8s shape for a jax.distributed
        # world — see client/k8s.py render_worker_statefulset)
        import socket

        host = socket.gethostname()
        pid = host.rsplit("-", 1)[-1]
        if not pid.isdigit():
            raise RuntimeError(
                f"EDL_PROCESS_ID_FROM_HOSTNAME=1 but hostname {host!r} has "
                "no trailing ordinal"
            )
    addr = (
        os.environ.get("EDL_COORDINATOR_ADDR")
        or cfg.coordinator_addr
        or "localhost:29400"
    )
    version = int(os.environ.get("EDL_WORLD_VERSION", "0") or 0)
    return CohortContext(addr, n, int(pid or 0), world_version=version)
