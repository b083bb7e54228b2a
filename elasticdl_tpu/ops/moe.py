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

`dropless_moe` is the other dispatch: top-k, no capacity, no drops — what
sparse-expert LMs are trained with today (OLMoE, `model_zoo/transformer/
olmoe.py`). The N·k (token, slot) pairs are sorted by expert, the rows
gathered into that order, each expert's contiguous group multiplied by its
own matrices (`jax.lax.ragged_dot`, which libtpu lowers to a Mosaic grouped
matmul), and the result gathered back and summed over the k slots. Shapes
are static: always N·k rows, whatever the routing.
"""

from __future__ import annotations

from functools import partial
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


# ------------------------------------------------------------------ #
# Dropless top-k dispatch


def topk_route(logits: jax.Array, k: int):
    """Softmax router over float32 logits (N, E): (probs (N, E), weights
    (N, k), expert_idx (N, k)). The weights are the k largest probabilities
    AS THEY ARE — not renormalised to sum to one (`norm_topk_prob: false`)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    weights, expert_idx = jax.lax.top_k(probs, k)
    return probs, weights, expert_idx


def router_aux_losses(logits: jax.Array, probs: jax.Array,
                      expert_idx: jax.Array):
    """(load balance, router z-loss), both unweighted. Load balance is
    E · Σ_e f_e · P_e with f_e the share of (token, slot) pairs sent to e
    (a count: no gradient) and P_e the mean router probability of e — 1.0
    at perfect balance. The z-loss is mean(logsumexp(logits)²)."""
    e = probs.shape[-1]
    f = pairs_per_expert(expert_idx, e).astype(jnp.float32) / expert_idx.size
    balance = e * jnp.sum(f * jnp.mean(probs, axis=0))
    z = jnp.mean(jax.nn.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2)
    return balance, z


def pairs_per_expert(expert_idx: jax.Array, num_experts: int) -> jax.Array:
    """How many (token, slot) pairs each expert gets: (E,) int32. A compare
    and a sum — a TPU scatters one element at a time."""
    hit = expert_idx.reshape(-1, 1) == jnp.arange(num_experts, dtype=expert_idx.dtype)
    return jnp.sum(hit, axis=0, dtype=jnp.int32)


def _take_rows(x, rows):
    """x[rows]; every index is in bounds by construction (a permutation, or
    a permutation // k), so the gather needs no clamp and no fill pass."""
    return x.at[rows].get(mode="promise_in_bounds")


@partial(jax.custom_vjp, nondiff_argnums=(3,))
def _rows_to_expert_order(x, order, inverse, k):
    """x (N, C) -> (N·k, C): row p of the result is the token of pair
    order[p]. A pair's token is pair // k."""
    return _take_rows(x, order // k)


def _rows_to_expert_order_fwd(x, order, inverse, k):
    return _rows_to_expert_order(x, order, inverse, k), (inverse, x.shape[0])


def _rows_to_expert_order_bwd(k, res, g):
    # the transpose of a gather is a scatter-add; `order` is a permutation,
    # so it is also the gather by the inverse permutation followed by a sum
    # over each token's k slots — which a TPU does at memory speed
    inverse, n = res
    back = _take_rows(g, inverse).reshape(n, k, g.shape[-1])
    return jnp.sum(back.astype(jnp.float32), axis=1).astype(g.dtype), None, None


_rows_to_expert_order.defvjp(_rows_to_expert_order_fwd, _rows_to_expert_order_bwd)


@jax.custom_vjp
def _rows_to_pair_order(ys, order, inverse):
    """ys (N·k, C) in expert order -> the same rows in pair order."""
    return _take_rows(ys, inverse)


def _rows_to_pair_order_fwd(ys, order, inverse):
    return _rows_to_pair_order(ys, order, inverse), order


def _rows_to_pair_order_bwd(order, g):
    return _take_rows(g, order), None, None


_rows_to_pair_order.defvjp(_rows_to_pair_order_fwd, _rows_to_pair_order_bwd)


def dropless_moe(
    x: jax.Array,            # (N, C) tokens
    expert_idx: jax.Array,   # (N, k) int32, the experts of each token
    weights: jax.Array,      # (N, k) float32, the weight of each slot
    w_gate: jax.Array,       # (E, C, H)
    w_up: jax.Array,         # (E, C, H)
    w_down: jax.Array,       # (E, H, C)
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """y_n = Σ_slot weights[n, slot] · W_down,e( silu(W_gate,e x_n) ⊙ W_up,e x_n )
    with e = expert_idx[n, slot]: every (token, slot) pair is computed, no
    capacity, no padding token. Returns (N, C) float32.

    Matmuls run in `compute_dtype` (float32 accumulation on the MXU), the
    weighted sum over slots in float32."""
    n, c = x.shape
    k = expert_idx.shape[1]
    e = w_gate.shape[0]
    dt = compute_dtype
    with jax.named_scope("dispatch"):
        flat = expert_idx.reshape(-1).astype(jnp.int32)      # pair p = (p // k, p % k)
        order = jnp.argsort(flat, stable=True)               # pairs by expert
        inverse = jnp.argsort(order)                         # a sort, not a scatter
        group_sizes = pairs_per_expert(flat, e)
        xs = _rows_to_expert_order(x.astype(dt), order, inverse, k)
    with jax.named_scope("experts"):
        gate = jax.lax.ragged_dot(xs, w_gate.astype(dt), group_sizes)
        up = jax.lax.ragged_dot(xs, w_up.astype(dt), group_sizes)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(dt)
        ys = jax.lax.ragged_dot(hidden, w_down.astype(dt), group_sizes)
    with jax.named_scope("combine"):
        pairs = _rows_to_pair_order(ys, order, inverse).reshape(n, k, c)
        return jnp.sum(pairs.astype(jnp.float32)
                       * weights.astype(jnp.float32)[:, :, None], axis=1)
