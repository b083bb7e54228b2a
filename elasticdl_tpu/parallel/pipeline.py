"""GPipe-style pipeline parallelism over a `pp` mesh axis.

Net-new relative to the reference (william-wang/elasticdl scales only by
data parallelism + the PS tier), completing the rebuild's parallelism
matrix: dp (psum over `data`), tp (GSPMD-partitioned kernels over
`model`), sp (ring/Ulysses over `seq`), and pp (this module).

TPU-first design — the scaling-book pipeline recipe, not a scheduler
thread pool: stage parameters are STACKED with a leading stage dim sharded
`P('pp')`, and the whole schedule runs inside ONE `shard_map` region as a
`lax.scan` over ticks. Each tick every device applies ITS resident stage
to the activation it holds, then the activations rotate one hop along the
ring with `lax.ppermute` — exactly the bounded, ICI-riding collective
pattern ring attention uses. Microbatch m enters stage 0 at tick m and
leaves stage S-1 at tick m+S-1; the scan runs M+S-1 ticks, so the classic
GPipe bubble is (S-1)/(M+S-1) of the ticks. Autodiff flows through
scan+ppermute (the same machinery ring attention differentiates through),
so `jax.grad` of a pipelined forward IS pipelined backprop — no hand
-written backward schedule.

The last stage's outputs are returned replicated via a `psum` over `pp`
(every other shard contributes zeros). That one output-sized collective
keeps the API shape-transparent: `gpipe(...)` is a drop-in for folding x
through the stages sequentially.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis

PIPE_AXIS = MeshAxis.PIPE


def stage_partition_specs(stage_params: Any, axis: str = PIPE_AXIS) -> Any:
    """P(axis, None, ...) for every leaf of a stacked stage-param tree."""
    return jax.tree_util.tree_map(
        lambda leaf: P(axis, *([None] * (leaf.ndim - 1))), stage_params
    )


def gpipe(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    x: jax.Array,
    *,
    num_microbatches: int,
    axis: str = PIPE_AXIS,
) -> jax.Array:
    """Fold `x` through S pipelined stages: equivalent to

        for s in range(S): x = stage_fn(params[s], x)

    but with stage s resident on pp-shard s and microbatches streaming
    through the ring.

    stage_fn: (per-stage params, (mb, ...) activation) -> same-shape
      activation. Must be shape-preserving (homogeneous stages — the
      transformer-block case).
    stage_params: pytree with leading stage dim S on every leaf, sharded
      P(axis) (see stage_partition_specs). S = the mesh's `axis` size.
    x: (B, ...) with B divisible by num_microbatches; replicated over
      `axis` (shard other mesh axes freely — they stay auto).
    """
    mesh = jax.sharding.get_abstract_mesh()
    if axis not in mesh.axis_names:
        # no pp axis: run the stages sequentially (single-chip fallback,
        # mirroring sequence_parallel_attention's no-seq-axis behavior)
        s_total = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
        for s in range(s_total):
            x = stage_fn(
                jax.tree_util.tree_map(lambda l: l[s], stage_params), x)
        return x
    n_stages = mesh.shape[axis]
    s_stacked = jax.tree_util.tree_leaves(stage_params)[0].shape[0]
    if s_stacked != n_stages:
        raise ValueError(
            f"stage_params stack {s_stacked} stages but mesh axis "
            f"{axis!r} has {n_stages} shards — they must match")
    batch = x.shape[0]
    if batch % num_microbatches:
        raise ValueError(
            f"batch {batch} not divisible by num_microbatches "
            f"{num_microbatches}")
    mb = batch // num_microbatches

    def shard_fn(params_local, x_full):
        # params_local leaves: (1, ...) — this device's stage
        params_one = jax.tree_util.tree_map(
            lambda l: jnp.squeeze(l, axis=0), params_local)
        idx = lax.axis_index(axis)
        m_total = num_microbatches
        x_micro = x_full.reshape((m_total, mb) + x_full.shape[1:])
        fwd_perm = [(i, i + 1) for i in range(n_stages - 1)]

        def tick(carry, t):
            act_in, outs = carry
            # stage 0 consumes the incoming stream (clamped index: ticks
            # past the last microbatch feed don't-cares that drain out of
            # the scan window before reaching the last stage)
            x_t = lax.dynamic_index_in_dim(
                x_micro, jnp.clip(t, 0, m_total - 1), axis=0,
                keepdims=False)
            inp = jnp.where(idx == 0, x_t, act_in)
            out = stage_fn(params_one, inp)
            # the LAST stage finished microbatch m = t - (S-1) this tick
            m = t - (n_stages - 1)
            store = (idx == n_stages - 1) & (m >= 0)
            outs = lax.dynamic_update_index_in_dim(
                outs,
                jnp.where(store, out, lax.dynamic_index_in_dim(
                    outs, jnp.clip(m, 0, m_total - 1), axis=0,
                    keepdims=False)),
                jnp.clip(m, 0, m_total - 1), axis=0)
            # rotate activations one hop down the ring; stage 0 receives
            # zeros (unused — it reads the stream)
            act_next = lax.ppermute(out, axis, fwd_perm)
            return (act_next, outs), None

        # carries become pp-varying after the first tick; mark the zero
        # initials varying up front or the scan rejects the type mismatch
        outs0 = lax.pcast(
            jnp.zeros((m_total, mb) + x_full.shape[1:], x_full.dtype),
            (axis,), to="varying")
        act0 = lax.pcast(
            jnp.zeros((mb,) + x_full.shape[1:], x_full.dtype),
            (axis,), to="varying")
        (_, outs), _ = lax.scan(
            tick, (act0, outs0), jnp.arange(m_total + n_stages - 1))
        # only the last shard holds real outputs; psum replicates them
        outs = lax.psum(
            jnp.where(idx == n_stages - 1, outs, 0.0), axis)
        return outs.reshape((batch,) + x_full.shape[1:])

    spec_params = stage_partition_specs(stage_params, axis)
    out = jax.shard_map(
        shard_fn,
        in_specs=(spec_params, P()),
        out_specs=P(),
        axis_names={axis},
    )(stage_params, x)
    return out
