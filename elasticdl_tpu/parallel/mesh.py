"""Device mesh construction and canonical shardings.

TPU-native replacement for the reference's two communication fabrics:
- Horovod/NCCL allreduce rings (reference: elasticdl/python/worker/allreduce_trainer.py)
  become the `data` mesh axis — gradient averaging is XLA `psum` over ICI.
- Parameter-server placement of dense/embedding state
  (reference: elasticdl/pkg/ps/server.go) becomes `NamedSharding`s over the
  same mesh: dense params replicated, embedding rows sharded.

The mesh is the single source of truth for parallelism; everything downstream
(trainer, embedding engine, checkpointing) takes it as input.
"""

from __future__ import annotations

from collections.abc import Mapping
from typing import Dict, Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis


def build_mesh(
    axis_sizes: Optional[Dict[str, int]] = None,
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Build a Mesh over `devices` (default: all local+remote devices).

    `axis_sizes` maps axis name -> size; default puts every device on the
    `data` axis. A 2-D {"data": d, "model": m} mesh lays `model` innermost so
    embedding all-to-alls ride the fastest ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    if not axis_sizes:
        axis_sizes = {MeshAxis.DATA: len(devices)}
    names = tuple(axis_sizes.keys())
    sizes = tuple(axis_sizes.values())
    total = int(np.prod(sizes))
    if total != len(devices):
        raise ValueError(f"mesh {dict(axis_sizes)} needs {total} devices, have {len(devices)}")
    dev_array = np.asarray(devices).reshape(sizes)
    return Mesh(dev_array, names)


def build_hybrid_mesh(
    ici_axis_sizes: Dict[str, int],
    dcn_axis_sizes: Dict[str, int],
    devices: Optional[Sequence[jax.Device]] = None,
) -> Mesh:
    """Multi-slice mesh: each named axis is the product of its ICI (within-
    slice) and DCN (across-slice) factors, with the DCN factor slowest-
    varying — collectives on such an axis decompose hierarchically (XLA
    reduces within each slice over ICI first, then once across slices over
    DCN), which is the standard TPU multi-pod recipe: put data-parallel
    across slices ({"data": n_slices} in `dcn_axis_sizes`) and keep
    model/seq sharding inside a slice's ICI.

    Replaces the reference's flat NCCL/Gloo world (reference:
    elasticdl/python/collective_ops/ + Horovod ring over whatever network
    exists) with an explicitly two-tier fabric. On real multi-slice TPU the
    device order comes from `mesh_utils.create_hybrid_device_mesh` (honors
    slice_index); elsewhere (CPU meshes, single slice) the same layout is
    built by grouping `devices` into contiguous per-slice blocks.
    """
    names = tuple(
        dict.fromkeys(tuple(ici_axis_sizes) + tuple(dcn_axis_sizes))
    )
    ici = tuple(int(ici_axis_sizes.get(a, 1)) for a in names)
    dcn = tuple(int(dcn_axis_sizes.get(a, 1)) for a in names)
    devices = list(devices if devices is not None else jax.devices())
    total = int(np.prod(ici)) * int(np.prod(dcn))
    if total != len(devices):
        raise ValueError(
            f"hybrid mesh ici={dict(ici_axis_sizes)} x "
            f"dcn={dict(dcn_axis_sizes)} needs {total} devices, "
            f"have {len(devices)}"
        )
    try:
        from jax.experimental import mesh_utils

        dev_array = mesh_utils.create_hybrid_device_mesh(
            ici, dcn, devices=devices, allow_split_physical_axes=True,
        )
    except Exception:
        # virtual/CPU devices carry no slice topology: contiguous blocks of
        # prod(ici) devices act as slices, then per-axis (dcn_i, ici_i)
        # pairs collapse into one axis with dcn slowest-varying
        arr = np.asarray(devices).reshape(dcn + ici)
        n = len(names)
        perm = [k for i in range(n) for k in (i, n + i)]
        dev_array = arr.transpose(perm).reshape(
            tuple(d * s for d, s in zip(dcn, ici))
        )
    return Mesh(dev_array, names)


def build_job_mesh(cfg, devices: Optional[Sequence[jax.Device]] = None) -> Mesh:
    """The mesh a job's config asks for: flat (`--mesh_shape`) or hybrid
    multi-slice (`--dcn_mesh_shape` names the across-slice factors, and
    `--mesh_shape` then describes ONE slice's ICI layout). The single entry
    point used by the worker and cohort paths."""
    devices = list(devices if devices is not None else jax.devices())
    dcn = cfg.dcn_axes_sizes()
    if dcn:
        n_slices = int(np.prod(list(dcn.values())))
        if len(devices) % n_slices:
            raise ValueError(
                f"dcn_mesh_shape {cfg.dcn_mesh_shape!r} implies {n_slices} "
                f"slices, which does not divide {len(devices)} devices"
            )
        per_slice = len(devices) // n_slices
        ici = (
            cfg.mesh_axes_sizes(per_slice)
            if cfg.mesh_shape else {MeshAxis.DATA: per_slice}
        )
        return build_hybrid_mesh(ici, dcn, devices)
    return build_mesh(
        cfg.mesh_axes_sizes(len(devices)) if cfg.mesh_shape else None,
        devices,
    )


def data_axis(mesh: Mesh) -> str:
    return MeshAxis.DATA if MeshAxis.DATA in mesh.axis_names else mesh.axis_names[0]


def model_axis(mesh: Mesh) -> Optional[str]:
    return MeshAxis.MODEL if MeshAxis.MODEL in mesh.axis_names else None


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh) -> NamedSharding:
    """Shard the leading (batch) dim over the data axis."""
    return NamedSharding(mesh, P(data_axis(mesh)))


def table_sharding(mesh: Mesh) -> NamedSharding:
    """Embedding tables: rows sharded over every mesh axis.

    With a 1-D ("data",) mesh this is DLRM-style 'tables sharded across all
    chips, dense replicated'; with ("data", "model") rows shard over both.
    Replaces the reference's `id % ps_num` row placement
    (reference: elasticdl/python/worker/ps_client.py) with a contiguous
    row-range shard per device — contiguous ranges keep XLA gathers dense.
    """
    return NamedSharding(mesh, P(mesh.axis_names, None))


def batch_key_spec(mesh: Mesh, key: str, partition) -> P:
    """THE per-key batch sharding rule, shared by every batch-placement
    path (shard_batch, shard_batch_stack, elastic.make_global_batch and its
    stack twin): a `partition` override for the key (pruned to the mesh's
    axes) or the default P(data_axis)."""
    if partition and partition.get(key) is not None:
        return prune_spec(mesh, partition[key])
    return P(data_axis(mesh))


def shard_batch(mesh: Mesh, batch, partition=None):
    """Device-put a host batch (pytree of np arrays) with batch sharding.

    `partition` optionally overrides the sharding per TOP-LEVEL key with a
    PartitionSpec (models with a sequence-parallel axis shard tokens
    P('data','seq') — see the transformer zoo's batch_partition). Leaves
    already resident with the right sharding pass through untouched (the
    DevicePrefetcher hands the trainer pre-sharded batches)."""
    def put_with(sh):
        def put(x):
            if isinstance(x, jax.Array) and x.sharding == sh:
                return x
            return jax.device_put(x, sh)
        return put

    if not partition or not isinstance(batch, Mapping):
        # per-key overrides only apply to dict batches; a bare-array/tuple
        # batch takes the default data sharding on every leaf
        return jax.tree_util.tree_map(put_with(batch_sharding(mesh)), batch)
    out = {}
    for key, value in batch.items():
        sh = NamedSharding(mesh, batch_key_spec(mesh, key, partition))
        out[key] = jax.tree_util.tree_map(put_with(sh), value)
    return out


def shard_batch_stack(mesh: Mesh, batches, partition=None):
    """Stack K host batches into one pytree with a leading step axis —
    leaves (K, B, ...), device_put as P(None, <batch spec>) — for
    `Trainer.train_many` (one dispatch runs all K steps via lax.scan)."""
    if not isinstance(batches[0], Mapping):
        # non-dict batches: default data spec on every leaf (matches
        # shard_batch's fallback)
        sh = NamedSharding(mesh, P(None, *batch_key_spec(mesh, "", partition)))

        def put_all(*leaves):
            return jax.device_put(
                np.stack([np.asarray(l) for l in leaves]), sh
            )

        return jax.tree_util.tree_map(put_all, *batches)
    out = {}
    for key in batches[0]:
        spec = batch_key_spec(mesh, key, partition)
        sh = NamedSharding(mesh, P(None, *spec))

        def put(*leaves, _sh=sh):
            return jax.device_put(
                np.stack([np.asarray(l) for l in leaves]), _sh
            )

        out[key] = jax.tree_util.tree_map(put, *(b[key] for b in batches))
    return out


def abstract_batch(mesh: Mesh, batch, partition=None):
    """ShapeDtypeStruct mirror of `shard_batch(mesh, batch)`: same leaves,
    same NamedShardings, zero data movement. This is what execution-free
    AOT lowering consumes (rescale fast path: a speculative compile for a
    neighbor world must not device_put onto devices it cannot execute
    on)."""
    def sds_with(sh):
        def sds(x):
            x = x if hasattr(x, "shape") else np.asarray(x)
            return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)
        return sds

    if not partition or not isinstance(batch, Mapping):
        return jax.tree_util.tree_map(sds_with(batch_sharding(mesh)), batch)
    out = {}
    for key, value in batch.items():
        sh = NamedSharding(mesh, batch_key_spec(mesh, key, partition))
        out[key] = jax.tree_util.tree_map(sds_with(sh), value)
    return out


def abstract_batch_stack(mesh: Mesh, batch, k: int, partition=None):
    """ShapeDtypeStruct mirror of `shard_batch_stack(mesh, [batch]*k)`:
    leaves (K, B, ...) with P(None, <batch spec>) shardings, no data."""
    def sds_with(spec):
        sh = NamedSharding(mesh, P(None, *spec))

        def sds(x):
            x = x if hasattr(x, "shape") else np.asarray(x)
            return jax.ShapeDtypeStruct((k,) + tuple(x.shape), x.dtype,
                                        sharding=sh)
        return sds

    if not isinstance(batch, Mapping):
        return jax.tree_util.tree_map(
            sds_with(batch_key_spec(mesh, "", partition)), batch)
    out = {}
    for key, value in batch.items():
        out[key] = jax.tree_util.tree_map(
            sds_with(batch_key_spec(mesh, key, partition)), value)
    return out


def prune_spec(mesh: Mesh, spec: P) -> P:
    """Drop spec axes the mesh doesn't have: the same zoo config (e.g. tokens
    P('data','seq')) runs on a pure-data mesh without a seq axis."""
    entries = []
    for e in spec:
        if e is None:
            entries.append(None)
        else:
            axes = tuple(a for a in (e if isinstance(e, tuple) else (e,))
                         if a in mesh.axis_names)
            entries.append(axes if axes else None)
    return P(*entries)
