"""Mixture-of-Experts with expert parallelism over an `expert` mesh axis.

Net-new relative to the reference (william-wang/elasticdl has no MoE),
completing the parallelism matrix alongside dp/tp/sp/pp: expert weights
are stacked (E, ...) and sharded one-expert-group-per-shard; tokens are
dispatched to experts through the GShard/Switch dense dispatch-mask
einsums, so XLA's SPMD partitioner lowers the token movement to
all_to_all over the expert axis — the rebuild never hand-writes the
collective (same philosophy as the tp/embedding paths).

Routing is Switch-style top-1 with a capacity bound: each expert accepts
at most `capacity_factor * tokens / E` tokens per batch; overflow tokens
pass through the residual untouched (their combine weight is zero), which
keeps every shape static — the XLA-friendly alternative to dynamic
per-expert buffers. The auxiliary load-balancing loss (Switch Transformer
eq. 4: E * Σ_e fraction_e · router_prob_e) is returned for the caller to
add to the task loss.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.constants import MeshAxis

EXPERT_AXIS = MeshAxis.EXPERT


def switch_moe(
    x: jax.Array,        # (N, C) tokens
    wg: jax.Array,       # (C, E) router
    w1: jax.Array,       # (E, C, H)
    b1: jax.Array,       # (E, H)
    w2: jax.Array,       # (E, H, C)
    b2: jax.Array,       # (E, C)
    capacity_factor: float = 1.25,
) -> Tuple[jax.Array, jax.Array]:
    """Top-1 MoE over flat tokens. Returns (out (N, C), aux_loss ()).

    Dense dispatch: a (N, E, Cap) one-hot mask routes tokens into the
    static (E, Cap, C) expert buffers and combines them back scaled by
    the router probability. Dropped (over-capacity) tokens contribute 0
    — callers add the residual so they pass through unchanged.
    """
    n, c = x.shape
    e = wg.shape[1]
    cap = max(1, int(capacity_factor * n / e))

    logits = (x.astype(jnp.float32)) @ wg.astype(jnp.float32)   # (N, E)
    probs = jax.nn.softmax(logits, axis=-1)
    expert_idx = jnp.argmax(probs, axis=-1)                     # (N,)
    gate = jnp.take_along_axis(
        probs, expert_idx[:, None], axis=-1)[:, 0]              # (N,)

    onehot_e = jax.nn.one_hot(expert_idx, e, dtype=jnp.float32)  # (N, E)
    # position of each token within its expert's buffer (arrival order)
    pos = jnp.cumsum(onehot_e, axis=0) * onehot_e - 1.0          # (N, E)
    pos_tok = jnp.sum(pos * onehot_e, axis=-1)                   # (N,)
    keep = (pos_tok >= 0) & (pos_tok < cap)
    pos_clamped = jnp.clip(pos_tok, 0, cap - 1).astype(jnp.int32)

    onehot_c = jax.nn.one_hot(pos_clamped, cap, dtype=jnp.float32)  # (N, Cap)
    dispatch = (
        onehot_e[:, :, None] * onehot_c[:, None, :]
        * keep[:, None, None].astype(jnp.float32)
    )                                                            # (N, E, Cap)

    expert_in = jnp.einsum(
        "nec,nd->ecd", dispatch, x.astype(jnp.float32))          # (E, Cap, C)
    h = jax.nn.gelu(
        jnp.einsum("ecd,edh->ech", expert_in, w1.astype(jnp.float32))
        + b1[:, None, :].astype(jnp.float32))
    expert_out = jnp.einsum(
        "ech,ehd->ecd", h, w2.astype(jnp.float32)
    ) + b2[:, None, :].astype(jnp.float32)                       # (E, Cap, C)

    combine = dispatch * gate[:, None, None]                     # (N, E, Cap)
    out = jnp.einsum("nec,ecd->nd", combine, expert_out)

    # Switch load-balancing loss: E * sum_e (token fraction_e * mean router
    # prob_e) — 1.0 at perfect balance
    frac = jnp.mean(onehot_e, axis=0)
    mean_prob = jnp.mean(probs, axis=0)
    aux = e * jnp.sum(frac * mean_prob)
    return out.astype(x.dtype), aux


def expert_partition_names(ndim: int) -> Tuple:
    """(expert, None, ...) partitioning names for a stacked expert leaf;
    the axis only binds when the ambient mesh has it (mesh-adaptive, like
    the Embedding layer / PipelinedBlocks)."""
    mesh = jax.sharding.get_abstract_mesh()
    lead = EXPERT_AXIS if EXPERT_AXIS in mesh.axis_names else None
    return (lead,) + (None,) * (ndim - 1)
