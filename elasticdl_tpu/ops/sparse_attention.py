"""Learned selection of keys for attention (the lightning indexer of
DeepSeek-V3.2-Exp's sparse attention): index scores, the exact selection of
each query's K best keys as a mask that is DATA, and the indexer's own loss.

Three functions, each over BLOCKS OF QUERY ROWS so that no (heads, T, T) array
exists, and of the (T, T) planes only `keep` (int8) — `select` and `index_kl`
each make the score plane's blocks as they need them (`_score_block`) and hold
no plane of scores or of their gradient. Blocked XLA, but for ONE kernel: the
pull-back of a score block to the indexer's operands (`index_score_bwd`),
where XLA's own form writes the 16 heads' float32 scores and their cotangent
to HBM. What each costs on the chip is in PERF.md sections 5 and 7.

- `index_scores(q_index, k_index, w)`: `I[t, s] = Σ_j w[t, j] ·
  relu(q_index[t, j] · k_index[s])` over the indexer's heads j against ONE key
  head, float32, the whole (T, T) plane (a query's future keys too: `select`
  masks them) — for whoever wants the plane; a training step does not call it.
- `select(q_index, k_index, w, k)`: for every query t the `min(t + 1, k)` keys
  of largest score among its causal prefix s ≤ t, as a row threshold and the plane
  `keep[t, s] = (s ≤ t) ∧ (I[t, s] ≥ threshold[t])`. The threshold is the EXACT
  k-th largest, found by bisection on the float's bit pattern (32 counting
  passes over the row block; a sort of 16 384 rows of 16 384 is what
  `lax.top_k` would run). TIES: where that plane would hold another count than
  `min(t + 1, k)` — several keys equal to the threshold — the row is counted
  (`tie_rows`) and the keys equal to the threshold are kept from the LOWER key
  index up until the count is right (`jax.lax.top_k`'s order, which is also why
  scores compare by bit pattern, −0.0 below +0.0, as a sort's total order
  has them). Not differentiated.
- `index_kl(q_index, k_index, w, q, k, lse, keep)`: the indexer's loss
  against the attention it serves. With P[h, t, s] = exp(q_h[t]·k[s]/√D − lse[h, t]) on the
  kept keys (the probabilities of the attention whose logsumexp `lse` is),
  p̂[t, s] = (1/H) Σ_h P and π[t, ·] the softmax of I[t, ·] over the kept keys:
  `L = (1/T) Σ_t Σ_s p̂ (log p̂ − log π)`, averaged over the batch. A custom
  rule: `∂L/∂I = (π − p̂) / T` on the kept keys, pulled back to the indexer's
  operands, and NOTHING for q, k or lse — p̂ is a target, not a path. The
  rule's cotangent enters as a scalar factor and nowhere else, so the loss is
  evaluated ONCE: the FORWARD rule makes the loss and its three gradients at a
  unit cotangent in one sweep over the blocks of 128 query rows (a block's
  scores, target and π once; neither the plane nor its gradient is ever held)
  and keeps the gradients — no operand — under `INDEX_GRADIENT_NAMES`; the
  backward rule multiplies them by its cotangent and evaluates nothing. The
  pull-back has two forms that share no logic, chosen by what the code can
  see (`pullback_keys`, as `pallas_attention.can_flash` chooses for
  attention): on a TPU (or inside `pallas_attention.interpret_mode()`) at
  shapes that tile, the Mosaic kernel `index_score_bwd` — a grid over key
  tiles; per tile and head the score block again in VMEM, `ds = dL/dI · w ·
  (s > 0)`, `dq += ds · k`, `dk += dsᵀ · q`, `dw += Σ dL/dI · relu(s)`; a tile
  in the rows' future skipped — and everywhere else, and under `EDL_FLASH=0`,
  `jax.vjp(_score_block)`. A caller that is not differentiated runs the loss
  alone.

`SELECTION_NAMES` are the `checkpoint_name`s of what `select` decides,
`INDEX_GRADIENT_NAMES` those of the index loss's three gradients, and
`KEEP_SELECTION` the `jax.checkpoint` policy that keeps both beside the flash
kernels' residuals: a layer recomputed in the backward pass then neither
searches again nor can select differently from its forward pass (on a TPU a
recomputed projection is not the forward's to the last bit, and a key at the
threshold would change sides), and holds nothing of the index loss (146 MB
kept over the cell's four layers, for a score block, a target and a pull-back
a layer not run a second time). Under a policy that saves none of the loss's
names the recomputation runs the forward rule again, to the same values.
"""

from __future__ import annotations

import functools
import os
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import pallas_attention

SELECTION_NAMES = ("dsa_threshold", "dsa_keep")
# the index loss's gradients for q_index, k_index and w at a unit cotangent
INDEX_GRADIENT_NAMES = ("dsa_index_kl_dq", "dsa_index_kl_dk", "dsa_index_kl_dw")
KEEP_SELECTION = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.RESIDUAL_NAMES, *SELECTION_NAMES, *INDEX_GRADIENT_NAMES)

# Rows a block: the score plane's block (the selection's too: it ranks a block
# as it makes it) holds (index heads, rows, T) float32 before its sum over
# heads — 268 MB at 16 heads, 256 rows and 16 384 keys; the index loss's holds
# that and the target's (heads, rows, T), 134 + 268 MB at 128 rows, and (where
# the pull-back is the vjp) the scores' cotangent beside them.
# `LIVE_BLOCK`: the square blocks `live_blocks` counts, the flash kernels' key
# block.
SCORE_ROWS = 256
KL_ROWS = 128
LIVE_BLOCK = 1024
# Keys a grid step of `index_score_bwd`: PERF.md section 6 (PR 39) has the sweep.
PULLBACK_KEYS = 1024


def _rows(t: int, target: int) -> int:
    """The largest power-of-two divisor of t up to `target` (1 if t is odd)."""
    return pallas_attention.pick_block(t, target, 1)


def _blocked(x, axis: int, rows: int):
    """x with `axis` (length T) split into (T / rows, rows) and the block
    index moved to the front, for `lax.map`."""
    shape = x.shape[:axis] + (x.shape[axis] // rows, rows) + x.shape[axis + 1:]
    return jnp.moveaxis(x.reshape(shape), axis, 0)


def _unblocked(x, axis: int):
    """`_blocked`'s inverse on a mapped result: (blocks, ..., rows, ...) with
    the rows at `axis + 1` -> (..., T, ...)."""
    x = jnp.moveaxis(x, 0, axis)
    return x.reshape(x.shape[:axis] + (-1,) + x.shape[axis + 2:])


def _score_block(q_rows, k_index, w_rows):
    """(B, R, Hi, Di) x (B, T, Di) x (B, R, Hi) -> (B, R, T) float32: a block
    of query rows of the score plane, under a scope of its own (`scores`)
    wherever it is computed."""
    with jax.named_scope("scores"):
        s = jnp.einsum("brhd,bsd->bhrs", q_rows, k_index,
                       preferred_element_type=jnp.float32)
        return jnp.sum(jax.nn.relu(s) * jnp.moveaxis(w_rows, 2, 1)[..., None], axis=1)


def index_scores(q_index: jax.Array, k_index: jax.Array, w: jax.Array) -> jax.Array:
    """q_index (B, T, Hi, Di), k_index (B, T, Di), w (B, T, Hi) float32 ->
    I (B, T, T) float32. The matmul takes its operands as they come (the
    caller rounds them to its compute dtype) and accumulates in float32; relu,
    the weights and the sum over heads are float32. NOT differentiated, and
    not what a training step calls: `select` and `index_kl` make the plane's
    blocks themselves, a block of query rows at a time, so that a step never
    holds the plane (1 GiB at 16 384 tokens). This is the plane as a whole, for
    whoever wants to look at it."""
    rows = _rows(q_index.shape[1], SCORE_ROWS)
    q_index, k_index, w = map(jax.lax.stop_gradient, (q_index, k_index, w))
    out = jax.lax.map(lambda a: _score_block(a[0], k_index, a[1]),
                      (_blocked(q_index, 1, rows), _blocked(w.astype(jnp.float32), 1, rows)))
    return _unblocked(out, 1)


_TOP = np.uint32(1 << 31)


def _ordered(x):
    """float32 -> uint32 in the floats' order (−inf lowest, +inf highest)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
    return jnp.where(bits >= _TOP, ~bits, bits | _TOP)


def _unordered(u):
    return jax.lax.bitcast_convert_type(jnp.where(u >= _TOP, u ^ _TOP, ~u), jnp.float32)


def _kth_largest(scores, causal, count):
    """The ordered bit pattern of the `count`[r]-th largest of row r of scores
    (B, R, T) among the keys `causal` (R, T) allows, exactly: the largest
    uint32 u such that at least `count` allowed keys have a pattern >= u,
    found bit by bit. -> (B, R) uint32."""
    def one_bit(i, prefix):
        candidate = prefix | (_TOP >> i.astype(jnp.uint32))
        # the pattern is rebuilt inside the pass: a fused compare-and-count,
        # no second plane
        at_least = jnp.sum(causal & (_ordered(scores) >= candidate[..., None]),
                           axis=-1, dtype=jnp.int32)
        return jnp.where(at_least >= count, candidate, prefix)

    return jax.lax.fori_loop(0, 32, one_bit, jnp.zeros(scores.shape[:2], jnp.uint32))


def _tie_rule(scores, causal, threshold, count):
    """keep (B, R, T) bool with exactly `count` keys a row: everything above
    the threshold (a bit pattern), and of the keys equal to it the lowest key
    indices."""
    pattern = _ordered(scores)
    above = causal & (pattern > threshold[..., None])
    equal = causal & (pattern == threshold[..., None])
    short = count - jnp.sum(above, axis=-1, dtype=jnp.int32)
    return above | (equal & (jnp.cumsum(equal, axis=-1, dtype=jnp.int32)
                             <= short[..., None]))


def select(q_index: jax.Array, k_index: jax.Array, w: jax.Array, k: int
           ) -> Tuple[jax.Array, jax.Array, Dict[str, jax.Array]]:
    """The indexer's operands (`index_scores`'s) -> (threshold (B, T) float32,
    keep (B, T, T) int8, counts): row t keeps `min(t + 1, k)` keys of its
    causal prefix — all of it while t < k; its own position, past that, only
    if its score is one of the k largest. Counts, int32 scalars over the
    batch: `tie_rows` (rows the tie rule had to settle), `selected_pairs`,
    `causal_pairs`, `live_blocks` (square blocks of `LIVE_BLOCK` that hold a
    kept key) and `causal_blocks` (those a causal grid visits). The first two
    results carry `SELECTION_NAMES`. Not differentiated."""
    b, t = q_index.shape[:2]
    rows = _rows(t, SCORE_ROWS)
    live_block = _rows(t, LIVE_BLOCK)
    q_index, k_index, w = map(jax.lax.stop_gradient, (q_index, k_index, w))

    def block(args):
        q_rows, w_rows, first_row = args
        scores = _score_block(q_rows, k_index, w_rows)
        position = first_row + jnp.arange(rows, dtype=jnp.int32)
        causal = jnp.arange(t, dtype=jnp.int32)[None, :] <= position[:, None]
        count = jnp.minimum(position + 1, k)
        threshold = _kth_largest(scores, causal, count)
        keep = causal & (_ordered(scores) >= threshold[..., None])
        ties = jnp.sum(keep, axis=-1, dtype=jnp.int32) != count
        keep = jax.lax.cond(
            jnp.any(ties),
            lambda: _tie_rule(scores, causal, threshold, count),
            lambda: keep)
        # which column blocks these rows keep a key in
        live = jnp.any(keep.reshape(b, rows, t // live_block, live_block), axis=(1, 3))
        return (_unordered(threshold), keep.astype(jnp.int8),
                jnp.sum(ties, dtype=jnp.int32), live)

    threshold, keep, ties, live = jax.lax.map(block, (
        _blocked(q_index, 1, rows), _blocked(w.astype(jnp.float32), 1, rows),
        jnp.arange(0, t, rows, dtype=jnp.int32)))
    # (row blocks of `rows`, B, column blocks) -> square blocks of `live_block`
    blocks = t // live_block
    live = jnp.any(live.reshape(blocks, max(live_block // rows, 1), b, blocks), axis=1)
    selected = t * (t + 1) // 2 if k >= t else k * (k + 1) // 2 + (t - k) * k
    counts = {"tie_rows": jnp.sum(ties), "live_blocks": jnp.sum(live, dtype=jnp.int32),
              "causal_blocks": jnp.int32(b * blocks * (blocks + 1) // 2),
              "selected_pairs": jnp.int32(b * selected),
              "causal_pairs": jnp.int32(b * t * (t + 1) // 2)}
    return (checkpoint_name(_unblocked(threshold, 1), SELECTION_NAMES[0]),
            checkpoint_name(_unblocked(keep, 1), SELECTION_NAMES[1]), counts)


def _target_and_log_pi(q_rows, k, lse_rows, keep_rows, score_rows):
    """One block of query rows: (p̂ (B, R, T), log π (B, R, T), which keys are
    kept)."""
    b, rows, heads, d = q_rows.shape
    kv_heads = k.shape[2]
    kept = keep_rows != 0
    s = jnp.einsum("brhgd,bshd->bhgrs",
                   q_rows.reshape(b, rows, kv_heads, heads // kv_heads, d), k,
                   preferred_element_type=jnp.float32) * d ** -0.5
    lse_g = lse_rows.reshape(b, kv_heads, heads // kv_heads, rows)
    p = jnp.where(kept[:, None, None], jnp.exp(s - lse_g[..., None]), 0.0)
    masked = jnp.where(kept, score_rows, -jnp.inf)
    return (jnp.sum(p, axis=(1, 2)) / heads,
            masked - jax.nn.logsumexp(masked, axis=-1, keepdims=True), kept)


def _kl_blocks(q_index, w, q, lse, keep, rows):
    """What a block of query rows of the index loss reads, block index first."""
    return (_blocked(q_index, 1, rows), _blocked(w.astype(jnp.float32), 1, rows),
            _blocked(q, 1, rows), _blocked(lse, 2, rows), _blocked(keep, 1, rows))


def _kl_block(score_rows, q_rows, k, lse_rows, keep_rows):
    """One block of query rows from its scores (B, R, T): (Σ p̂ (log p̂ − log π)
    over the block, and that sum's gradient for the scores, π − p̂ on the kept
    keys and zero elsewhere)."""
    target, log_pi, kept = _target_and_log_pi(q_rows, k, lse_rows, keep_rows, score_rows)
    return (jnp.sum(jax.scipy.special.xlogy(target, target)
                    - target * jnp.where(kept, log_pi, 0.0)),
            jnp.where(kept, jnp.exp(log_pi), 0.0) - target)


@jax.custom_vjp
def index_kl(q_index: jax.Array, k_index: jax.Array, w: jax.Array,
             q: jax.Array, k: jax.Array, lse: jax.Array, keep: jax.Array) -> jax.Array:
    """The indexer's operands (`index_scores`'s), q (B, T, H, D), k (B, T, Hkv,
    D) as the attention took them, lse (B, H, T) its logsumexp over the kept
    keys, keep (B, T, T) int8 -> the indexer's loss, a float32 scalar. Every
    row must keep a key. Gradient for q_index, k_index and w alone."""
    b, t = keep.shape[:2]
    rows = _rows(t, KL_ROWS)

    def block(args):
        q_index_rows, w_rows, q_rows, lse_rows, keep_rows = args
        return _kl_block(_score_block(q_index_rows, k_index, w_rows),
                         q_rows, k, lse_rows, keep_rows)[0]

    return jnp.sum(jax.lax.map(block, _kl_blocks(q_index, w, q, lse, keep, rows))) / (t * b)


def pullback_keys(rows: int, t: int, head_dim: int, q_dtype, k_dtype) -> Optional[int]:
    """The key tile `index_score_bwd` takes a block of `rows` query rows
    against `t` keys in, or None where the pull-back of a score block is
    `jax.vjp(_score_block)`: off a TPU (outside `pallas_attention.
    interpret_mode()`), under EDL_FLASH=0, and at shapes the kernel has no
    tiles for. From what the code can see; nothing a caller sets."""
    if os.environ.get("EDL_FLASH", "") == "0":
        return None
    if jax.default_backend() != "tpu" and not pallas_attention._interpret_active():
        return None
    if jnp.dtype(q_dtype) != jnp.dtype(k_dtype) or head_dim % 8:
        return None
    if rows % pallas_attention._min_block(q_dtype):
        return None
    return pallas_attention.pick_block(t, PULLBACK_KEYS, pallas_attention._LANE)


def _index_score_bwd_kernel(first_ref, q_ref, k_ref, w_ref, d_ref, dq_ref, dk_ref,
                            dw_ref, dq_acc, *, rows, block_k):
    """One key tile of a block of query rows: every head's score block again
    in VMEM, the relu's mask from it, and the three gradients it feeds. q_ref
    (1, Hi, R, Di), k_ref (1, bk, Di), w_ref (1, Hi, R, 128) the head weights
    along the lanes, d_ref (1, bk, R) the tile of dL/dI, keys first (the
    layout XLA makes a block of the plane in: turning a tile here costs less
    than XLA's copy of the block for a (R, bk) operand); dq_ref as q_ref,
    written at the last tile from `dq_acc` (float32); dk_ref (1, Di, bk)
    float32, this tile's own, the keys along the lanes (so that the operand
    transposed for it is the head's q, not its (R, bk) block); dw_ref (1, Hi,
    R, 128) float32 sums by lane, held across the tiles. A tile wholly in the
    rows' future holds no kept key (dL/dI is exactly zero there) and is
    skipped. The heads are unrolled: a `fori_loop` over them cost 2.9 ms a
    layer more (PERF.md section 6, PR 39)."""
    j = pl.program_id(1)
    lanes = pallas_attention._LANE

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)
        dw_ref[:] = jnp.zeros_like(dw_ref)

    dk_ref[:] = jnp.zeros_like(dk_ref)

    @pl.when(j * block_k < first_ref[0] + rows)
    def _tile():
        k = k_ref[0]
        d = d_ref[0].T
        for h in range(q_ref.shape[1]):
            q = q_ref[0, h]
            s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                    preferred_element_type=jnp.float32)    # (R, bk)
            # jax.nn.relu's gradient: nothing at exactly 0
            ds = jnp.where(s > 0, d * w_ref[0, h][:, :1], 0.0).astype(k.dtype)
            dq_acc[h] += jnp.dot(ds, k, preferred_element_type=jnp.float32)
            dk_ref[0] += jax.lax.dot_general(q, ds, (((0,), (0,)), ((), ())),
                                             preferred_element_type=jnp.float32)
            weighted = d * jnp.maximum(s, 0.0)
            dw_ref[0, h] += sum(weighted[:, c:c + lanes] for c in range(0, block_k, lanes))

    @pl.when(j == pl.num_programs(1) - 1)
    def _finalize():
        dq_ref[0] = dq_acc[:].astype(dq_ref.dtype)


def index_score_bwd(q_rows, k_index, w_rows, d_scores, first_row, *, block_k, interpret):
    """`_score_block`'s pull-back as ONE kernel: q_rows (B, R, Hi, Di) and
    k_index (B, T, Di) in one dtype, w_rows (B, R, Hi) float32, d_scores (B, R,
    T) float32 — zero on every key past the rows' own positions, the first of
    which is `first_row` (int32 scalar) — -> the three gradients in the
    KERNEL'S layouts, which a scan stacks and sums without a transpose a
    block: dq (B, Hi, R, Di) in q_rows' dtype, dk (B, Di, T) float32, dw (B,
    Hi, R) float32. The scores are float32 from the operands as they come, as
    the forward's; mask, head weights and dw are float32; the two matmuls that
    pull dL/dI back take it rounded to the operands' dtype, which is what the
    MXU does to a float32 operand."""
    b, rows, heads, d = q_rows.shape
    t = k_index.shape[1]
    lanes = pallas_attention._LANE
    # the last tile that holds a key of the rows' causal prefix: a later step
    # names it again, which fetches nothing
    tile_at = lambda j, first: jnp.minimum(j, (first[0] + rows - 1) // block_k)
    by_head = pl.BlockSpec((1, heads, rows, d), lambda i, j, first: (i, 0, 0, 0))
    by_lane = pl.BlockSpec((1, heads, rows, lanes), lambda i, j, first: (i, 0, 0, 0))
    with jax.named_scope("scores"):
        dq, dk, dw = pl.pallas_call(
            functools.partial(_index_score_bwd_kernel, rows=rows, block_k=block_k),
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=(b, t // block_k),
                in_specs=[
                    by_head,
                    pl.BlockSpec((1, block_k, d), lambda i, j, first: (i, tile_at(j, first), 0)),
                    by_lane,
                    pl.BlockSpec((1, block_k, rows),
                                 lambda i, j, first: (i, tile_at(j, first), 0)),
                ],
                out_specs=[
                    by_head,
                    pl.BlockSpec((1, d, block_k), lambda i, j, first: (i, 0, j)),
                    by_lane,
                ],
                scratch_shapes=[pltpu.VMEM((heads, rows, d), jnp.float32)],
            ),
            out_shape=[
                jax.ShapeDtypeStruct((b, heads, rows, d), q_rows.dtype),
                jax.ShapeDtypeStruct((b, d, t), jnp.float32),
                jax.ShapeDtypeStruct((b, heads, rows, lanes), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel", "arbitrary")),
            interpret=interpret,
            name="index_score_bwd",
        )(jnp.reshape(first_row, (1,)).astype(jnp.int32),
          jnp.moveaxis(q_rows, 2, 1), k_index,
          jnp.broadcast_to(jnp.moveaxis(w_rows, 2, 1)[..., None], (b, heads, rows, lanes)),
          jnp.swapaxes(d_scores, 1, 2))
        return dq, dk, jnp.sum(dw, axis=-1)


def _index_kl_fwd(q_index, k_index, w, q, k, lse, keep):
    """The loss and, in the SAME sweep over the blocks of query rows, its three
    gradients at a unit cotangent: a block's scores and target once, its KL
    sum, ∂L/∂I = (π − p̂) / T on the kept keys, and that pulled back to the
    indexer's operands — no (T, T) plane of scores or of their gradient. Two
    forms of the pull-back, chosen by `pullback_keys`: the kernel
    `index_score_bwd` beside ONE forward evaluation of the block (the heads'
    scores and their cotangent then live in VMEM alone), or
    `jax.vjp(_score_block)`, which writes both. The gradients, in the
    operands' layouts and dtypes under `INDEX_GRADIENT_NAMES`, are ALL the
    rule keeps: a recomputation under a policy that saves those names runs
    nothing of this."""
    b, t = keep.shape[:2]
    rows = _rows(t, KL_ROWS)
    block_k = pullback_keys(rows, t, q_index.shape[-1], q_index.dtype, k_index.dtype)
    blocks = _kl_blocks(q_index, w, q, lse, keep, rows)

    def block(dk_index, args):
        q_index_rows, w_rows, q_rows, lse_rows, keep_rows = args
        score_rows, pull = jax.vjp(_score_block, q_index_rows, k_index, w_rows)
        kl, d_scores = _kl_block(score_rows, q_rows, k, lse_rows, keep_rows)
        dq_rows, dk_rows, dw_rows = pull(d_scores * (1.0 / (t * b)))
        return dk_index + dk_rows.astype(jnp.float32), (kl, dq_rows, dw_rows)

    def kernel_block(dk_index, args):
        (q_index_rows, w_rows, q_rows, lse_rows, keep_rows), first_row = args
        kl, d_scores = _kl_block(_score_block(q_index_rows, k_index, w_rows),
                                 q_rows, k, lse_rows, keep_rows)
        dq_rows, dk_rows, dw_rows = index_score_bwd(
            q_index_rows, k_index, w_rows, d_scores * (1.0 / (t * b)), first_row,
            block_k=block_k, interpret=pallas_attention.kernel_interpret())
        return dk_index + dk_rows, (kl, dq_rows, dw_rows)

    if block_k is None:
        dk_index, (kl, dq_index, dw) = jax.lax.scan(
            block, jnp.zeros(k_index.shape, jnp.float32), blocks)
        dq_index, dw = _unblocked(dq_index, 1), _unblocked(dw, 1)
    else:
        # the kernel's layouts: heads before rows, dk's keys along the lanes
        dk_index, (kl, dq_index, dw) = jax.lax.scan(
            kernel_block, jnp.zeros((b, k_index.shape[2], t), jnp.float32),
            (blocks, jnp.arange(0, t, rows, dtype=jnp.int32)))
        dq_index, dw = (jnp.moveaxis(_unblocked(x, 2), 1, 2) for x in (dq_index, dw))
        dk_index = jnp.swapaxes(dk_index, 1, 2)
    gradients = (dq_index, dk_index.astype(k_index.dtype), dw.astype(w.dtype))
    return jnp.sum(kl) / (t * b), tuple(map(checkpoint_name, gradients, INDEX_GRADIENT_NAMES))


def _index_kl_bwd(gradients, g):
    """`g` times what the forward rule made (the product in float32, rounded
    to the operand's dtype); nothing for q, k, lse (p̂ is a target) or keep."""
    return (*((g * x.astype(jnp.float32)).astype(x.dtype) for x in gradients),
            None, None, None, None)


index_kl.defvjp(_index_kl_fwd, _index_kl_bwd)
