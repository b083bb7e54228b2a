"""Pallas TPU kernel for the embedding-gradient placement — the MXU
replacement for XLA's row-serial scatter-add.

Context (BASELINE.md round-5 pt 2): the embedding backward must place ~213k
sorted gradient rows into a 2.6M-row dense table. Every XLA formulation is
bound by per-ROW transaction costs — scatter-add ~14 ns/element in the
fast (<=256k-row output) zone, ~105 ns beyond it, and even dynamic-slice/
dynamic-update-slice window plumbing costs ~12-18 ns/row — so the best
XLA schedule (`EDL_EMB_SCATTER=tiled`, ops/embedding.py) still spends
~16 ms/step. This kernel reformulates placement as BLOCKED ONE-HOT MATMUL:

  grid over output row-blocks (bs rows); block b DMAs the contiguous
  window of the sorted stream that searchsorted assigned to it (scalar-
  prefetched starts), then accumulates
      out_block += one_hot(ids - b*bs) @ grads        # (bs,C) @ (C,D)
  chunk by chunk on the MXU. Sorted-stream windows are CONTIGUOUS, so the
  DMAs run at bandwidth, and the "scatter" itself becomes dense compute
  (~86 GFLOP for the DeepFM shape — ~0.5 ms of MXU time) instead of 280k
  row transactions.

Window coverage follows the tiled path's contract: the caller guarantees
(via the same lax.cond max-population guard) that no block's population
exceeds the static window W; ids beyond the caller's row range (manual-
shard sentinels, padding) simply never match the one-hot and drop out.

Reference parity note: the reference's Go PS applied sparse gradients
row-by-row in a hash map (elasticdl/pkg/ps/optimizer.go); this is that
component's hot loop, rebuilt as dense MXU math.
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import _interpret_active, _sds

# Output rows per grid step and sorted-stream rows per MXU chunk. bs*C
# bf16 one-hot (4 MB at 8192x256) is the VMEM high-water mark; C=256 keeps
# the contraction MXU-friendly (2x128 lanes). Total kernel work (compares
# AND matmul FLOPs) scales with vocab * window, and the window shrinks
# with the block, so smaller blocks win until grid/DMA overhead bites —
# block size is env-tunable for the bench sweep. Chip sweep (round 5,
# DeepFM shape, TRANSPOSED output): the standalone D=16/sgd update step
# measured 2048/4096/8192 -> 12.9/11.6/15.4 ms, but the FULL DeepFM
# step (D=17, adam, fwd gather in the same program) measured 589k
# samples/s at 2048 vs 560k at 4096 — the end-to-end metric wins, so
# 2048 stays the default.
DEFAULT_BLOCK_ROWS = 2048
CHUNK = 256


def block_rows() -> int:
    return int(os.environ.get(
        "EDL_EMB_PALLAS_BS", str(DEFAULT_BLOCK_ROWS)))


def _kernel(starts_ref, sf_ref, cf_ref, out_ref, ids_vmem, vec_vmem,
            sem_ids, sem_vec, *, bs, w, d, d_out, split, group):
    """`group` output blocks per grid step (default 1 — see the sweep
    note in place_sorted_grads). Sub-block indices are PYTHON ints
    (static scratch slots: the dynamic-slot double-buffer variant
    measured 5.5x SLOWER), and a step's DMAs all start before the first
    wait so multi-block groups overlap their transfers."""
    b = pl.program_id(0)

    def copies(g):
        # the caller aligns starts to 128: Mosaic must PROVE dynamic DMA
        # offsets land on tile boundaries, and both streams put the
        # window dimension on LANES — ids as a (1, N) row, gradients
        # TRANSPOSED to (D, N) (slicing the untransposed (N, D) would
        # lane-slice a 128-padded memref, which Mosaic rejects)
        start = pl.multiple_of(starts_ref[b * group + g], 128)
        return (
            pltpu.make_async_copy(
                sf_ref.at[:, pl.ds(start, w)], ids_vmem.at[g],
                sem_ids.at[g]),
            pltpu.make_async_copy(
                cf_ref.at[:, pl.ds(start, w)], vec_vmem.at[g],
                sem_vec.at[g]),
        )

    for g in range(group):
        for cp in copies(g):
            cp.start()

    for g in range(group):
        for cp in copies(g):
            cp.wait()
        base = (b * group + g) * bs
        # the accumulator is built TRANSPOSED, (D, bs): the output's
        # row dimension must ride the 128-lane axis — a (bs, 17) block
        # lane-pads 17 -> 128 in VMEM, a 7.5x write-bandwidth tax that
        # was most of the kernel's cost (write-only floor 7.5 ms) and
        # an OOM at group=8. dot_general(vec, onehot) contracting the
        # chunk gives (D, bs) natively, no in-register transpose.
        acc = jnp.zeros((d, bs), jnp.float32)
        row_ids = jax.lax.broadcasted_iota(
            jnp.int32, (bs, CHUNK), 0) + base
        for c in range(w // CHUNK):
            ids_c = ids_vmem[g, :, c * CHUNK:(c + 1) * CHUNK]    # (1, C)
            vec_c = vec_vmem[g, :, c * CHUNK:(c + 1) * CHUNK]    # (D, C)
            onehot = (row_ids == ids_c).astype(jnp.bfloat16)     # 0/1
            dims = (((1,), (1,)), ((), ()))
            if split:
                # Two-term bf16 split of the f32 gradient values: the
                # MXU runs bf16, and a single cast rounds the
                # accumulated gradients to ~8 mantissa bits (0.4% rel
                # err measured); hi+lo recovers ~16 bits (~4e-6 rel)
                # for a second matmul pass. EDL_EMB_PALLAS_PRECISION=
                # bf16 drops the second pass for models already
                # training in bf16 end to end.
                hi = vec_c.astype(jnp.bfloat16)
                lo = (vec_c - hi.astype(jnp.float32)).astype(jnp.bfloat16)
                acc = acc + jax.lax.dot_general(
                    hi, onehot, dimension_numbers=dims,
                    preferred_element_type=jnp.float32,
                ) + jax.lax.dot_general(
                    lo, onehot, dimension_numbers=dims,
                    preferred_element_type=jnp.float32,
                )
            else:
                acc = acc + jax.lax.dot_general(
                    vec_c.astype(jnp.bfloat16), onehot,
                    dimension_numbers=dims,
                    preferred_element_type=jnp.float32,
                )
        # d is the 8-aligned padded depth the DMA needs; the real
        # embedding width d_out is restored in-register before the write
        out_ref[:, g * bs:(g + 1) * bs] = acc[:d_out, :]


def group_blocks() -> int:
    g = int(os.environ.get("EDL_EMB_PALLAS_GROUP", "1"))
    if g < 1:
        raise ValueError(
            f"EDL_EMB_PALLAS_GROUP must be >= 1, got {g}")
    return g


@functools.partial(
    jax.jit,
    static_argnames=(
        "num_rows", "block_rows", "w", "d_out", "split", "group",
        "interpret"))
def place_sorted_grads(cf, sf, starts, *, num_rows, block_rows, w,
                       d_out=None, split=True, group=1, interpret=False):
    """Dense (D, num_rows) TRANSPOSED gradient from a SORTED stream
    (the row dimension rides the 128-lane axis so output writes aren't
    lane-padded; callers transpose once at the end).

    cf: (D, N_pad) float32 gradient rows TRANSPOSED into sorted-id order
    along lanes, padded by at least `w` columns; sf: (1, N_pad) the
    matching sorted int32 ids, padded with int32max; starts:
    (num_rows/block_rows,) int32 — each block's 128-ALIGNED window start.
    Ids outside [block*bs, block*bs + bs) contribute nothing (the one-hot
    never matches), which also silently drops sentinel/padding ids and
    the aligned-start leading slop. The caller must guarantee every
    block's window span fits in `w` (lax.cond guard in ops.embedding)
    and that num_rows % block_rows == 0.
    """
    d, n_pad = cf.shape
    if d % 8:
        raise ValueError(
            f"cf depth {d} must be 8-aligned (Mosaic sublane tiling); pad "
            f"with zero rows and pass d_out")
    if w % CHUNK:
        # the kernel iterates w // CHUNK WHOLE chunks — a ragged tail
        # would be silently skipped (dropped gradient rows, caught only
        # by full-scale on-chip numerics in round 5); fail loudly instead
        raise ValueError(f"window {w} must be a multiple of CHUNK={CHUNK}")
    d_out = d if d_out is None else d_out
    bs = block_rows
    nb = num_rows // bs
    # Chip sweep (round 5, DeepFM shape, transposed out): group 1/2/4
    # all ~8.3 ms, group 8 EXPLODES to ~60 ms (VMEM-pressure spill
    # signature). The write-only "7.5 ms grid floor" that motivated
    # grouping turned out to be the lane-padded (bs, 17) write tax the
    # transposed output already removed — per-step overhead is small.
    # `group` is a STATIC arg (callers read group_blocks()) so env
    # sweeps reach the jit cache key; legalize to a divisor of nb.
    while nb % group:
        group //= 2
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(nb // group,),
        in_specs=[
            pl.BlockSpec(memory_space=pl.ANY),
            pl.BlockSpec(memory_space=pl.ANY),
        ],
        out_specs=pl.BlockSpec(
            (d_out, bs * group), lambda b, starts: (0, b)),
        scratch_shapes=[
            pltpu.VMEM((group, 1, w), jnp.int32),
            pltpu.VMEM((group, d, w), jnp.float32),
            pltpu.SemaphoreType.DMA((group,)),
            pltpu.SemaphoreType.DMA((group,)),
        ],
    )
    return pl.pallas_call(
        functools.partial(
            _kernel, bs=bs, w=w, d=d, d_out=d_out, split=split,
            group=group),
        grid_spec=grid_spec,
        # inside the manual (shard_map) lookup schedule the output must
        # declare the mesh axes it varies over, like the cotangents do
        out_shape=_sds((d_out, num_rows), jnp.float32, cf),
        interpret=interpret,
    )(starts, sf, cf)


def runnable() -> bool:
    """The kernel needs a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()
