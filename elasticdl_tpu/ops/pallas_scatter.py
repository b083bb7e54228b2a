"""Pallas TPU kernel for the embedding-gradient placement — the MXU
replacement for XLA's row-serial scatter-add.

The embedding backward must place a batch's sorted gradient rows (213k a
step for DeepFM at batch 8192) into a dense table of millions of rows.
Every XLA formulation is bound by per-ROW transaction costs (tens of ns a
row: scatter-add, segment-sum, and the dynamic-slice plumbing of the
tiled schedule alike, ops/embedding.py). This kernel reformulates
placement as BLOCKED ONE-HOT MATMUL:

  grid over output row-blocks (bs rows); block b DMAs the contiguous
  window of the sorted stream that searchsorted assigned to it (scalar-
  prefetched first columns), then accumulates
      out_block += one_hot(ids - b*bs) @ grads        # (bs,C) @ (C,D)
  chunk by chunk on the MXU. Sorted-stream windows are CONTIGUOUS, so the
  "scatter" becomes a sequential read and dense compute.

What it costs (TPU v5e, `PERF.md` §6): the kernel's time follows the
one-hot, blocks x bs x the columns a block builds it for, not the ids.
With 512-column windows sent
through the MXU once per bf16 term it was 1.43-1.57 ps an element on all
three tables of the benchmark — 24.7 ms a step on 33.8M rows, a quarter
of the MXU's peak because only D of its rows carry values (ledger, PR 23).
So the window is sized in whole 128s from what the code sees (n, rows,
bs: `window_cols`), the columns before a block's first id are rotated out
of it, and both terms share one pass of the one-hot: 128 columns and
6.8 ms on that table — 0.41 us a block, which halving the one-hot once
more (256 -> 128 columns) moved by only 13%: what is left is paid per
block, not per element (PR 24).

Window coverage follows the tiled path's contract: the caller guarantees
(via the same lax.cond max-population guard) that no block's population
exceeds the window's room; ids beyond the caller's row range (manual-
shard sentinels, padding) simply never match the one-hot and drop out.

Reference parity note: the reference's Go PS applied sparse gradients
row-by-row in a hash map (elasticdl/pkg/ps/optimizer.go); this is that
component's hot loop, rebuilt as dense MXU math.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import _interpret_active, _sds

# Output rows per grid step, and sorted-stream columns per MXU pass. The
# one-hot's size is rows x window and the window shrinks with the block
# until it reaches 128 columns, so smaller blocks win until per-block
# costs bite. The block is a constant: a sweep on the benchmark's tables
# (ROADMAP A5) lands as a new constant or a value derived from the
# shapes, not as a variable. A window is a whole number of LANES: 128 is
# what Mosaic's DMA-offset proof and the MXU's contraction depth both
# want. The loop takes CHUNK columns at a time (4% faster than 128 at a
# time, kernel alone, PR 24) and a LANES-wide tail where an odd number of
# them is left; bs*CHUNK bf16 one-hot (1 MB at 2048x256) is the VMEM
# high-water mark.
BLOCK_ROWS = 2048
LANES = 128
CHUNK = 2 * LANES


def window_cols(n: int, num_rows: int, block_rows: int, slack: float) -> int:
    """Static window width, in sorted-stream columns, for `n` ids placed
    into `num_rows` rows by blocks of `block_rows`: room for `slack` x the
    mean block population (over the REAL row count: ceil-padding the block
    count would undersize the window for tables barely past the gate and
    land every step on the fallback branch), never more than the stream,
    in whole LANES — plus one LANES that the read needs and the placement
    does not: a window is read from its block's first id aligned DOWN to
    128, so it may begin up to 127 columns early. The kernel's time
    follows the window (module docstring), so it is derived from what the
    shapes say and rounded no further."""
    def lanes_up(x):
        return -(-x // LANES) * LANES

    per_block = math.ceil(slack * n * block_rows / num_rows)
    return min(lanes_up(n), max(LANES, lanes_up(per_block))) + LANES


def _kernel(firsts_ref, sf_ref, cf_ref, out_ref, ids_vmem, vec_vmem,
            sem_ids, sem_vec, *, bs, w, d, d_out):
    """One output block per grid step: DMA the block's window of both
    streams, one-hot its ids against the block's rows, matmul."""
    b = pl.program_id(0)
    # a window starts at its block's first id aligned DOWN to 128:
    # Mosaic must PROVE dynamic DMA offsets land on tile boundaries,
    # and both streams put the window dimension on LANES — ids as a
    # (1, N) row, gradients TRANSPOSED to (D, N) (slicing the
    # untransposed (N, D) would lane-slice a 128-padded memref, which
    # Mosaic rejects)
    start = pl.multiple_of(firsts_ref[b] // LANES * LANES, LANES)
    copies = (
        pltpu.make_async_copy(
            sf_ref.at[:, pl.ds(start, w)], ids_vmem, sem_ids),
        pltpu.make_async_copy(
            cf_ref.at[:, pl.ds(start, w)], vec_vmem, sem_vec),
    )
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    base = b * bs
    # the accumulator is built TRANSPOSED, (D, bs): the output's
    # row dimension must ride the 128-lane axis — a (bs, 17) block
    # lane-pads 17 -> 128 in VMEM, a 7.5x write-bandwidth tax that
    # was most of the kernel's cost (write-only floor 7.5 ms).
    # dot_general(vec, onehot) contracting the chunk gives (D, bs)
    # natively, no in-register transpose.
    #
    # The up-to-127 columns before the block's first id belong to the
    # block before: rotate them to the window's far end, so that the
    # one-hot is built for w - LANES columns and not for w.
    shift = (w - firsts_ref[b] % LANES) % w
    ids = pltpu.roll(
        jnp.broadcast_to(ids_vmem[...], (8, w)), shift, 1)[:1] - base
    vec = pltpu.roll(vec_vmem[...], shift, 1)
    # One pass of the one-hot per chunk: its bs x C elements are what
    # the MXU's time follows (only d of its rows carry values), so the
    # two bf16 terms of the split ride through it STACKED, (2d, C),
    # into one (2d, bs) float32 accumulator whose halves are added
    # before the write — the same products and float32 sums as two
    # passes, at one pass's price.
    acc = None
    for c0 in range(0, w - LANES, CHUNK):
        cw = min(CHUNK, w - LANES - c0)   # a LANES tail when odd
        vec_c = vec[:, c0:c0 + cw]                           # (D, cw)
        onehot = (jax.lax.broadcasted_iota(jnp.int32, (bs, cw), 0)
                  == ids[:, c0:c0 + cw]).astype(jnp.bfloat16)  # 0/1
        # Two-term bf16 split of the f32 gradient values: the MXU runs
        # bf16, and a single cast rounds the accumulated gradients to
        # ~8 mantissa bits (0.4% rel err measured, which the
        # benchmark's check refuses); hi+lo recovers ~16 bits (~4e-6
        # rel). Stacked in f32, where d (8-aligned) is whole sublane
        # tiles, then cast.
        hi_f = vec_c.astype(jnp.bfloat16).astype(jnp.float32)
        terms = jnp.concatenate(
            [hi_f, vec_c - hi_f], axis=0).astype(jnp.bfloat16)
        part = jax.lax.dot_general(
            terms, onehot, dimension_numbers=(((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc = part if acc is None else acc + part
    acc = acc[:d] + acc[d:]
    # d is the 8-aligned padded depth the DMA needs; the real
    # embedding width d_out is restored in-register before the write
    out_ref[...] = acc[:d_out, :]


@functools.partial(
    jax.jit,
    static_argnames=("num_rows", "block_rows", "w", "d_out", "interpret"))
def place_sorted_grads(cf, sf, firsts, *, num_rows, block_rows, w,
                       d_out=None, interpret=False):
    """Dense (D, num_rows) TRANSPOSED gradient from a SORTED stream
    (the row dimension rides the 128-lane axis so output writes aren't
    lane-padded; callers transpose once at the end).

    cf: (D, N_pad) float32 gradient rows TRANSPOSED into sorted-id order
    along lanes, padded by at least `w` columns; sf: (1, N_pad) the
    matching sorted int32 ids, padded with int32max; firsts:
    (num_rows/block_rows,) int32 — the column of each block's first id
    (searchsorted of the block's first row). A block reads the `w`
    columns from `firsts` aligned down to 128 and places the `w - 128`
    that follow its first id. Ids outside [block*bs, block*bs + bs)
    contribute nothing (the one-hot never matches), which also silently
    drops sentinel/padding ids and the columns of later blocks. The
    caller must guarantee that no block holds more than `w - 128` ids
    (lax.cond guard in ops.embedding) and that num_rows % block_rows == 0.
    """
    d, n_pad = cf.shape
    if d % 8:
        raise ValueError(
            f"cf depth {d} must be 8-aligned (Mosaic sublane tiling); pad "
            f"with zero rows and pass d_out")
    if w % LANES:
        # the kernel walks the window in whole LANES — a ragged tail
        # would be silently skipped (dropped gradient rows, caught only
        # by full-scale on-chip numerics in round 5); fail loudly instead
        raise ValueError(f"window {w} must be a multiple of {LANES}")
    d_out = d if d_out is None else d_out
    bs = block_rows
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(num_rows // bs,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec((d_out, bs), lambda b, firsts: (0, b)),
        scratch_shapes=[
            pltpu.VMEM((1, w), jnp.int32),
            pltpu.VMEM((d, w), jnp.float32),
            pltpu.SemaphoreType.DMA,
            pltpu.SemaphoreType.DMA,
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, bs=bs, w=w, d=d, d_out=d_out),
        grid_spec=grid_spec,
        # inside the manual (shard_map) lookup schedule the output must
        # declare the mesh axes it varies over, like the cotangents do
        out_shape=_sds((d_out, num_rows), jnp.float32, cf),
        interpret=interpret,
    )(firsts, sf, cf)


def runnable() -> bool:
    """The kernel needs a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()
