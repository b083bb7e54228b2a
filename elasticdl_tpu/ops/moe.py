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
olmoe.py`; Nemotron-H, `nemotron_h.py`). The N·k (token, slot) pairs are
sorted by expert, the rows gathered into that order, each expert's contiguous
group multiplied by its own matrices (the grouped matmul of `ops/pallas_gmm.py`
on a TPU: row tiles by group, the whole contraction in one block, tiles from
the shapes; `jax.lax.ragged_dot` elsewhere), and the result brought back and
summed over the k slots. The expert body is what the caller's matrices make it:
three of them a gated SiLU unit (`W_down(silu(W_gate x) ⊙ W_up x)`), two a
relu² unit (`W_down relu(W_up x)²`). Two routers come with it: `topk_route`
(softmax, weights as they are) and `sigmoid_topk_route` (sigmoid scores, a
selection bias, weights renormalised and scaled).

`held = (first, count)` tells the function WHICH experts it holds, the cut
that expert parallelism makes: the router still chooses among all experts,
the pairs of the `count` held ones are computed — every one of them — and
the others add nothing here (on their own chips they would). Then the work
follows the pairs held, not N·k: the held pairs sort to the front and are
taken in equal PASSES of `held_pass_rows` rows — twice the held experts' even
share of the pairs — each gathered, multiplied, weighted and scatter-added to
its tokens. The first pass is straight-line code, forward and backward, and
its cotangents go on as its kernels and scatter-adds wrote them; the pairs
past it are the OVERFLOW, as many further passes as they fill: a
`lax.while_loop` forward, and backward a `lax.cond` in which the first pass's
cotangents are widened to float32, the further passes' added in a loop and
the sums narrowed again. So nothing is ever dropped, nothing is sized for the
worst case, a step whose held pairs fit a pass pays for no loop of passes and
no accumulator, and a step on which more pairs land is slower by the passes it
adds, not wrong. Inside a pass the grouped matmul visits only the row tiles
that hold a held pair and returns the rows past the last one as zeros, so the
experts' time follows the pairs held. Of what XLA does around the kernels, the
combine's PULL-BACK follows them too: `_add_pass_rows`, a function with a rule
of its own (a loop of a run-time trip count has no reverse mode), gathers the
cotangent's rows in float32 a CHUNK of `held_row_chunk` rows at a time — whole
row tiles, a sixteenth of a pass — for as many chunks as hold a held pair, and
each leaves its chunk as the rows the kernels take and that chunk's dw: no
float32 (rows, C) array is made. The two scatter-adds (the combine's into the
tokens, and the one that transposes the dispatch's gather) are XLA's, one call
over the pass's rows — its sort-and-merge scatter costs a call 1–2 ms before
the first row and the rows past the last held pair next to nothing, so chunks
of it lose (`SCATTER_CHUNK_ROWS`) — but for the combine's in passes of at most
sixteen times 256 rows, whose chunks XLA walks row by row at the whole's price a
row. So a pass's rows
still size one call of each kernel, the dispatch's gather and its transpose,
the elementwise passes between the kernels and, in large passes, the combine's
float32 addends. Without `held` every expert is held and the shapes are
static at N·k rows, whatever the routing.
"""

from __future__ import annotations

from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.ops import pallas_gmm

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


def sigmoid_topk_route(logits: jax.Array, bias: jax.Array, k: int, scale: float,
                       eps: float = 1e-20):
    """Sigmoid router over float32 logits (N, E): (scores (N, E), weights
    (N, k), expert_idx (N, k)). The k experts with the largest `score + bias`
    are chosen — the bias selects and does not weigh — and their weights are
    the scores renormalised to sum to one, times `scale`
    (`norm_topk_prob: true`, `routed_scaling_factor`). `eps` is what the
    renormaliser adds to the chosen scores' sum: 1e-20 in DeepSeek-V3's
    family, 1e-6 in `lfm2_moe`'s."""
    scores = jax.nn.sigmoid(logits.astype(jnp.float32))
    _, expert_idx = jax.lax.top_k(scores + bias.astype(jnp.float32), k)
    chosen = jnp.take_along_axis(scores, expert_idx, axis=-1)
    weights = scale * chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + eps)
    return scores, weights, expert_idx


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


def _expert_body(xs, experts, group_sizes, dt):
    """Rows in expert order through their experts' matrices: three matrices
    are a gated SiLU unit, two a relu² unit. The grouped matmul is the repo's
    own kernel where it can run (a TPU; interpret mode in the CPU tests) and
    `jax.lax.ragged_dot` elsewhere. Rows past the last group: zeros from the
    kernel, undefined from `ragged_dot`."""
    gmm = pallas_gmm.grouped_matmul if pallas_gmm.runnable() else jax.lax.ragged_dot
    if len(experts) == 3:
        w_gate, w_up, w_down = experts
        gate = gmm(xs, w_gate.astype(dt), group_sizes)
        up = gmm(xs, w_up.astype(dt), group_sizes)
        hidden = (jax.nn.silu(gate.astype(jnp.float32))
                  * up.astype(jnp.float32)).astype(dt)
    else:
        w_up, w_down = experts
        up = gmm(xs, w_up.astype(dt), group_sizes)
        hidden = jnp.square(jax.nn.relu(up.astype(jnp.float32))).astype(dt)
    return gmm(hidden, w_down.astype(dt), group_sizes)


def held_pass_rows(pairs: int, num_experts: int, count: int) -> int:
    """The rows of one pass of a held dispatch: twice the held experts' even
    share of the pairs, in whole 512s, and never more than all pairs. It
    sizes the pass's gather, weights and scatter-add; the grouped matmuls'
    time follows the pairs the pass holds (`pallas_gmm`: the row tiles past
    the last held pair are skipped), where `ragged_dot`'s followed the rows."""
    return min(pairs, 512 * max(1, -(-2 * pairs * count // (512 * num_experts))))


def held_row_tiles(on_held, pairs: int, num_experts: int, count: int) -> jax.Array:
    """() int32: the row tiles that hold a held pair, over the passes a held
    dispatch of `pairs` pairs runs when `on_held` of them are on its `count`
    held experts (they sort first: a pass holds the next `held_pass_rows` of
    them) — what a grouped matmul of the pass visits of `passes x
    held_pass_rows / row tile`, by the kernel's own `pallas_gmm.row_tiles`
    at the kernel's own row tile."""
    rows = held_pass_rows(pairs, num_experts, count)
    tm = pallas_gmm.row_tile(rows)
    return sum(pallas_gmm.row_tiles(jnp.clip(on_held - lo, 0, rows), tm)
               for lo in range(0, pairs, rows))


def held_row_chunk(rows: int) -> int:
    """The rows of one CHUNK of a pass of `rows` rows: a whole number of the
    kernel's row tiles, a sixteenth of the pass where its tiles divide so —
    else the most equal parts under sixteen they do divide into, and the whole
    pass where it is no whole number of tiles. What walks a pass's rows one
    at a time and can choose (`_add_pass_rows` and its pull-back) walks the
    chunks that hold a held pair and no others."""
    tm = pallas_gmm.row_tile(rows)
    if rows % tm:
        return rows
    return rows // max(d for d in range(1, 17) if (rows // tm) % d == 0)


def held_row_chunks(on_held, pairs: int, num_experts: int, count: int) -> jax.Array:
    """() int32: the chunks that hold a held pair, over the passes of a held
    dispatch (as `held_row_tiles`, at `held_row_chunk` rows a chunk) — what
    the combine's pull-back (and, in small passes, its scatter-add) walks of
    `passes x held_pass_rows / chunk`."""
    rows = held_pass_rows(pairs, num_experts, count)
    chunk = held_row_chunk(rows)
    return sum(pallas_gmm.row_tiles(jnp.clip(on_held - lo, 0, rows), chunk)
               for lo in range(0, pairs, rows))


def _rows_at(x, at, chunk: int):
    """x[at:at + chunk] of a pass's (rows, ...) array."""
    return jax.lax.dynamic_slice_in_dim(x, at, chunk)


def _over_live_chunks(live, chunk: int, body, init):
    """body(at, carry) for at = 0, chunk, 2·chunk, … below `live`: a loop of
    as many trips as chunks hold a live row. It has no reverse-mode rule:
    `_add_pass_rows` brings its own."""
    return jax.lax.fori_loop(0, pallas_gmm.row_tiles(live, chunk),
                             lambda c, carry: body(c * chunk, carry), init)


# XLA's scatter-add on a TPU goes one of two ways (my chip runs, PR 53; into
# (16 384, 2304) float32): few update rows are walked one by one, ≈ 0.2 µs a
# row (256 rows 0.11 ms, 1024 0.34); many are sorted and merged, 1–2 ms a call
# whatever their number and 35–100 ns a row after that (4096 rows 2.07 ms,
# 16 384 2.99, 65 536 7.03) — and the rows past the last held pair, whose
# tokens come in order, cost it next to nothing. So the combine's scatter-add
# is walked in chunks only where a chunk goes the first way AT NO MORE A ROW
# than the whole: 256 rows into 4096 tokens, 0.045 ms = 176 ns a row against
# 167 for 4096 rows in one call, so a full pass costs what it did and a
# half-full one half (Xing's cell: `combine` 10.1 → 7.0 ms a step). Chunks of
# 512 rows into 8192 tokens still go the first way (0.087 ms, 170 ns a row)
# but the call over a pass of 8192 rows is cheaper a row than that in the
# cell (GLM's `combine` scatter-add read 3.89 ms a step whole, 4.58 in chunks),
# and 1024 rows into 4096 tokens or 2048 anywhere go the second way.
SCATTER_CHUNK_ROWS = 256


@partial(jax.custom_vjp, nondiff_argnums=(5,))
def _add_pass_rows(y, ys, w, tokens, live, chunk):
    """y (N, C) float32 plus row r of ys (rows, C) times w[r], added to token
    tokens[r]: over the chunks below `live` (chunks of `SCATTER_CHUNK_ROWS`
    at most; the rows past `live` in the last of them weigh nothing) or in
    one call over all rows (larger ones). Its pull-back walks the chunks
    below `live` whatever their size."""
    def add(at, size, y):
        addends = _rows_at(ys, at, size).astype(jnp.float32) * _rows_at(w, at, size)[:, None]
        return y.at[_rows_at(tokens, at, size)].add(addends, mode="promise_in_bounds")

    if chunk > SCATTER_CHUNK_ROWS:
        return add(0, tokens.shape[0], y)
    return _over_live_chunks(live, chunk, lambda at, y: add(at, chunk, y), y)


def _add_pass_rows_fwd(y, ys, w, tokens, live, chunk):
    return _add_pass_rows(y, ys, w, tokens, live, chunk), (ys, w, tokens, live)


def _add_pass_rows_bwd(chunk, res, g):
    """g's rows are gathered a chunk at a time, in float32, and leave the
    chunk as the rows the kernels take (ys' dtype) and the chunk's dw; the
    chunks past `live` stay the zeros they start as."""
    ys, w, tokens, live = res

    def pull(at, carry):
        dys, dw = carry
        rows = _take_rows(g, _rows_at(tokens, at, chunk))
        here = (rows * _rows_at(w, at, chunk)[:, None]).astype(ys.dtype)
        dw_here = jnp.sum(rows * _rows_at(ys, at, chunk).astype(jnp.float32), axis=1)
        return (jax.lax.dynamic_update_slice_in_dim(dys, here, at, 0),
                jax.lax.dynamic_update_slice_in_dim(dw, dw_here, at, 0))

    dys, dw = _over_live_chunks(live, chunk, pull, (jnp.zeros_like(ys), jnp.zeros_like(w)))
    return g, dys, dw, None, None


_add_pass_rows.defvjp(_add_pass_rows_fwd, _add_pass_rows_bwd)


def _held_pass(y, xd, flat_weights, experts, order, starts, ends, lo, k, rows):
    """y (N, C) float32 plus rows lo..lo+rows of the sorted order through
    their experts, each weighted and added to its token. The rows past the
    last held pair are in no group: the grouped matmul skips their row tiles
    and returns them as zeros, forward and backward, their weight is zero,
    and the combine's pull-back stops at the chunk that holds the last held
    pair (in a small pass its scatter-add too: `_add_pass_rows`)."""
    chunk = held_row_chunk(rows)
    live = jnp.clip(ends[-1] - lo, 0, rows)
    with jax.named_scope("dispatch"):
        pair = jax.lax.dynamic_slice(order, (lo,), (rows,))
        group_sizes = jnp.clip(ends, lo, lo + rows) - jnp.clip(starts, lo, lo + rows)
        tokens = pair // k
        xs = _take_rows(xd, tokens)
    with jax.named_scope("experts"):
        ys = _expert_body(xs, experts, group_sizes, xd.dtype)
    with jax.named_scope("combine"):
        w = jnp.where(jnp.arange(rows, dtype=jnp.int32) < live,
                      _take_rows(flat_weights, pair), 0.0)
        return _add_pass_rows(y, ys, w, tokens, live, chunk)


@partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _held_passes(xd, flat_weights, experts, order, starts, ends, k, rows):
    """Σ over the passes the held pairs fill of `_held_pass`: xd (N, C),
    flat_weights (N·k,) float32, `order` the pairs with the held ones first
    and `rows` entries of padding, starts / ends (count,) of the held experts'
    groups in it. (N, C) float32.

    The first pass is straight-line code, whatever the routing: with no held
    pair at all it adds nothing (every row is past `ends[-1]`: the kernel
    skips every tile and every weight is zero), at the price of one empty
    pass. The loop is the overflow, passes 1, 2, …: held pairs that fit one
    pass run none of it and the first pass's sum is handed through in place."""
    def one_more(carry):
        i, y = carry
        return i + 1, _held_pass(y, xd, flat_weights, experts, order, starts, ends,
                                 i * rows, k, rows)

    first = _held_pass(jnp.zeros(xd.shape, jnp.float32), xd, flat_weights, experts,
                       order, starts, ends, 0, k, rows)
    return jax.lax.while_loop(lambda c: c[0] * rows < ends[-1], one_more,
                              (jnp.int32(1), first))[1]


def _held_passes_fwd(xd, flat_weights, experts, order, starts, ends, k, rows):
    return (_held_passes(xd, flat_weights, experts, order, starts, ends, k, rows),
            (xd, flat_weights, experts, order, starts, ends))


def _held_passes_bwd(k, rows, res, g):
    """The same passes again, each recomputed and transposed: nothing is kept
    from the forward. The first pass's cotangents are the result as its
    kernels and scatter-adds wrote them, in the operands' dtypes. Only where
    the held pairs overflow a pass (`ends[-1] > rows`) are they widened to
    float32, the further passes' added to them in a loop and the sums
    narrowed again."""
    xd, flat_weights, experts, order, starts, ends = res
    zero = jnp.zeros_like(g)

    def pull_back(lo):
        _, transpose = jax.vjp(
            lambda a, b, c: _held_pass(zero, a, b, c, order, starts, ends, lo, k, rows),
            xd, flat_weights, experts)
        # the whole pull-back of a pass is billed to the experts' scope
        with jax.named_scope("experts"):
            return transpose(g)

    def overflow(first):
        def one_more(carry):
            i, sums = carry
            more = pull_back(i * rows)
            with jax.named_scope("experts"):
                return i + 1, jax.tree_util.tree_map(
                    lambda s, d: s + d.astype(jnp.float32), sums, more)

        _, sums = jax.lax.while_loop(
            lambda c: c[0] * rows < ends[-1], one_more,
            (jnp.int32(1), jax.tree_util.tree_map(
                lambda d: d.astype(jnp.float32), first)))
        return jax.tree_util.tree_map(lambda s, d: s.astype(d.dtype), sums, first)

    dx, dw, dexperts = jax.lax.cond(ends[-1] > rows, overflow, lambda first: first,
                                    pull_back(0))
    return dx, dw, dexperts, None, None, None


_held_passes.defvjp(_held_passes_fwd, _held_passes_bwd)


def _held_moe(x, expert_idx, weights, experts, num_experts, held, dt):
    k = expert_idx.shape[1]
    first, count = held
    rows = held_pass_rows(expert_idx.size, num_experts, count)
    with jax.named_scope("dispatch"):
        local = expert_idx.reshape(-1).astype(jnp.int32) - first
        is_held = (local >= 0) & (local < count)
        key = jnp.where(is_held, local, count)               # the others sort last
        # a pass reads `rows` entries wherever it starts
        order = jnp.pad(jnp.argsort(key, stable=True).astype(jnp.int32), (0, rows))
        sizes = pairs_per_expert(key, count)
        ends = jnp.cumsum(sizes)
    return _held_passes(
        x.astype(dt), weights.reshape(-1).astype(jnp.float32),
        tuple(w.astype(dt) for w in experts), order, ends - sizes, ends, k, rows)


def dropless_moe(
    x: jax.Array,            # (N, C) tokens
    expert_idx: jax.Array,   # (N, k) int32, the experts of each token
    weights: jax.Array,      # (N, k) float32, the weight of each slot
    experts,                 # (w_gate, w_up, w_down) or (w_up, w_down); up-type
                             # (E, C, H), down (E, H, C)
    held=None,               # (first, count) of `num_experts`; None: all held
    num_experts: int = 0,    # what the router chose among; needed with `held`
    compute_dtype=jnp.bfloat16,
) -> jax.Array:
    """y_n = Σ_slot weights[n, slot] · expert_e(x_n) with e = expert_idx[n,
    slot], over the experts held here: every (token, slot) pair of a held
    expert is computed, no capacity, no padding token. Returns (N, C) float32.

    Matmuls run in `compute_dtype` (float32 accumulation on the MXU), the
    weighted sum over slots in float32."""
    n, c = x.shape
    k = expert_idx.shape[1]
    e = experts[0].shape[0]
    dt = compute_dtype
    if held is not None and tuple(held) != (0, num_experts or e):
        if held[1] != e or not num_experts:
            raise ValueError(f"held={held} of num_experts={num_experts} with "
                             f"matrices of {e} experts")
        return _held_moe(x, expert_idx, weights, experts, num_experts, held, dt)
    with jax.named_scope("dispatch"):
        flat = expert_idx.reshape(-1).astype(jnp.int32)      # pair p = (p // k, p % k)
        order = jnp.argsort(flat, stable=True)               # pairs by expert
        inverse = jnp.argsort(order)                         # a sort, not a scatter
        group_sizes = pairs_per_expert(flat, e)
        xs = _rows_to_expert_order(x.astype(dt), order, inverse, k)
    with jax.named_scope("experts"):
        ys = _expert_body(xs, experts, group_sizes, dt)
    with jax.named_scope("combine"):
        pairs = _rows_to_pair_order(ys, order, inverse).reshape(n, k, c)
        return jnp.sum(pairs.astype(jnp.float32)
                       * weights.astype(jnp.float32)[:, :, None], axis=1)
