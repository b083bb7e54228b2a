"""Grouped matmul for the sparse experts: a Pallas (Mosaic) kernel of the
repo's own in place of `jax.lax.ragged_dot` (PR 31).

`grouped_matmul(lhs (M, K), rhs (G, K, N), group_sizes (G,))` multiplies the
rows of each group — contiguous, in group order, from row 0 — by that group's
matrix: what `ragged_dot` computes, with three differences the experts need.

- **Rows past the last group are defined and (almost) free.** They come back
  as zeros, take a zero cotangent in and add nothing to a matrix's gradient,
  and a row tile that holds none of a group's rows is never multiplied:
  the kernel's time follows the rows the groups hold, not M. (libtpu's
  `ragged_dot` leaves those rows undefined and pays for all M, PERF.md §6.)
- **Tiles from the shapes.** A grid step is one VISIT: (row tile, group) for
  every row tile that holds a row of the group, in row order
  (`row_tile_visits`, scalar-prefetched). The whole contraction is ONE block
  — no K loop, float32 accumulation over all of K inside the MXU's dot — so
  a group's matrix block keeps its index over the group's consecutive visits
  and is read from HBM once a group, and widths no power of two divides
  (2688 = 21·128, 1856 = 14.5·128) need no padded copy: a block that spans a
  whole dimension may have any size. The row tile and the column split come
  from M, K, N, the dtype and the chip's VMEM (`tiles`).
- **The backward is the same kernel**: dx = grouped_matmul(dy, rhs
  transposed in the block's index map, not in memory), and dW its
  transposed form — rows × rows → one (K, N) block a group, accumulated in a
  float32 scratch over the group's visits (`grouped_matmul_t`).

Operands are taken as they come (the caller casts to its compute dtype),
products accumulate in float32, the result is cast to the operands' dtype:
`ragged_dot`'s arithmetic. The `pallas_call`s are named `grouped_matmul` and
`grouped_matmul_t`, so a trace names them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import (
    _interpret_active, _vmem_bytes, kernel_interpret)

LANES = 128


def runnable() -> bool:
    """The kernel needs a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()


class Tiles(NamedTuple):
    rows: int        # row tile of lhs and out
    cols: int        # column tile of out (the whole of N where it fits)
    vmem_limit: int  # what Mosaic may use


def _cols_that_fit(n: int, fits) -> int:
    """The widest column tile `fits` accepts: all of n, else n split into the
    fewest equal parts of whole LANES."""
    if fits(n):
        return n
    for parts in range(2, -(-n // LANES) + 1):
        cols = -(-n // (parts * LANES)) * LANES
        if fits(cols):
            return cols
    return 0


ROW_TILE = 256


def row_tile(m: int) -> int:
    """A visit multiplies a whole row tile by its group's matrix whatever share
    of the tile the group owns, and the MXU loads a 128 x 128 piece of the
    matrix in the time it streams 128 rows past it: 256 rows keep it
    streaming two thirds of the time at worst, and a group boundary wastes
    half a tile on average. On the chip 128 ties at ~440 rows a group, 256
    wins at ~1024, 512 loses at both (PERF.md §6, PR 31)."""
    return min(ROW_TILE, -(-m // 16) * 16)


def tiles(m: int, k: int, n: int, dtype, transposed: bool = False) -> Tiles:
    """Row and column tile for (m, k) x (groups, k, n) — `transposed`: for the
    (groups, k, n) result of `grouped_matmul_t`: all of n if two buffers each
    of the row tile, the matrix block and the (m, n) block, and the float32
    form of what the kernel writes (a (rows, cols) result; transposed, the
    (k, cols) accumulator) fit half the chip's VMEM, else n in the fewest
    equal parts."""
    size = jnp.dtype(dtype).itemsize
    vmem = _vmem_bytes()
    rows = row_tile(m)

    def fits(cols):
        blocks = 2 * size * (rows * k + k * cols + rows * cols)
        return blocks + 4 * (k if transposed else rows) * cols <= vmem // 2

    return Tiles(rows, _cols_that_fit(n, fits), vmem * 3 // 4)


class Visits(NamedTuple):
    """One entry a grid step, `steps = row tiles + groups - 1` of them (the
    most there can be). The first `count` are visits; the rest change no
    block but the result's: they walk the row tiles no group has a row in,
    which the kernel zero-fills."""
    group: jax.Array      # (steps,) the group whose matrix the step takes
    tile: jax.Array       # (steps,) the row tile the step writes
    lhs_tile: jax.Array   # (steps,) the row tile it reads (the last visit's after `count`)
    lo: jax.Array         # (steps,) the rows lo <= r < hi of the tile are the group's
    hi: jax.Array
    count: jax.Array      # () visits
    row_tiles: jax.Array  # () row tiles that hold a row of a group


def row_tiles(rows_held, tm: int):
    """The row tiles of tm rows that hold a row of a group when the groups
    hold `rows_held` rows — they are contiguous from row 0, so the first
    ones. The rest are skipped."""
    return -(-rows_held // tm)


def row_tile_visits(group_sizes: jax.Array, m: int, tm: int,
                    visit_empty: bool = False) -> Visits:
    """The (row tile, group) visits of a grouped matmul over m rows in tiles
    of tm, from the groups' sizes alone. `visit_empty` gives an empty group one
    visit with no rows (the transposed form has to write its zero block)."""
    g = group_sizes.shape[0]
    tiles_m = -(-m // tm)
    steps = tiles_m + g - 1
    sizes = group_sizes.astype(jnp.int32)
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = jnp.minimum(starts // tm, tiles_m - 1)
    per_group = jnp.where(sizes > 0, (ends - 1) // tm - starts // tm + 1,
                          1 if visit_empty else 0)
    after = jnp.cumsum(per_group)                  # visits up to and with group i
    count = after[-1]
    with_rows = row_tiles(ends[-1], tm)

    s = jnp.arange(steps, dtype=jnp.int32)
    last = jnp.maximum(count - 1, 0)
    v = jnp.minimum(s, last)                       # the visit a step repeats
    group = jnp.minimum(
        jnp.sum(after[None, :] <= v[:, None], axis=1, dtype=jnp.int32), g - 1)
    tile = first[group] + v - (after - per_group)[group]
    live = s < count
    lhs_tile = jnp.where(count > 0, tile, 0)
    # past the visits: the row tiles after the last one with a row, in order
    tail = jnp.minimum(with_rows + s - count, tiles_m - 1)
    return Visits(group=jnp.where(count > 0, group, 0),
                  tile=jnp.where(live, tile, tail), lhs_tile=lhs_tile,
                  lo=jnp.where(live, starts[group], 0),
                  hi=jnp.where(live, ends[group], 0),
                  count=count, row_tiles=with_rows)


def _group_rows(lo, hi, row0, shape):
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, shape, 0)
    return (rows >= lo) & (rows < hi)


def _gmm_kernel(group, tile, lhs_tile, lo, hi, lhs_ref, rhs_ref, out_ref, *,
                tm, transpose_rhs):
    del group, lhs_tile
    s = pl.program_id(1)
    row0 = tile[s] * tm
    first = jnp.logical_or(s == 0, tile[s] != tile[jnp.maximum(s - 1, 0)])
    live = hi[s] > lo[s]
    whole = jnp.logical_and(lo[s] <= row0, hi[s] >= row0 + tm)

    @pl.when(live)
    def _visit():
        acc = jax.lax.dot_general(
            lhs_ref[...], rhs_ref[...],
            (((1,), (1 if transpose_rhs else 0,)), ((), ())),
            preferred_element_type=jnp.float32)

        @pl.when(whole)
        def _all_rows():
            out_ref[...] = acc.astype(out_ref.dtype)

        @pl.when(jnp.logical_not(whole))
        def _some_rows():
            # rows of another group keep what that group's visit wrote; on
            # the tile's first visit nothing is written yet: zeros, which the
            # rows past the last group keep
            kept = jnp.where(first, 0.0, out_ref[...].astype(jnp.float32))
            mine = _group_rows(lo[s], hi[s], row0, acc.shape)
            out_ref[...] = jnp.where(mine, acc, kept).astype(out_ref.dtype)

    @pl.when(jnp.logical_and(jnp.logical_not(live), first))
    def _no_rows():
        out_ref[...] = jnp.zeros_like(out_ref)


@functools.partial(jax.jit, static_argnames=("transpose_rhs", "tm", "tn", "interpret"))
def _gmm(lhs, rhs, group_sizes, *, transpose_rhs=False, tm=None, tn=None,
         interpret=False):
    """lhs (M, K) by rhs (G, K, N) — or (G, N, K) with `transpose_rhs` — by
    groups of rows: (M, N) in lhs's dtype, zeros past the last group."""
    m, k = lhs.shape
    g = rhs.shape[0]
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    if rhs.shape[2 if transpose_rhs else 1] != k or group_sizes.shape != (g,):
        raise ValueError(f"grouped matmul of {lhs.shape} by {rhs.shape} "
                         f"(transposed: {transpose_rhs}), groups {group_sizes.shape}")
    chosen = tiles(m, k, n, lhs.dtype)
    tm, tn = tm or chosen.rows, tn or chosen.cols
    if not tn:
        raise ValueError(f"no column tile of a ({k}, {n}) matrix fits VMEM")
    v = row_tile_visits(group_sizes, m, tm)
    steps = v.group.shape[0]

    if transpose_rhs:
        rhs_spec = pl.BlockSpec((None, tn, k), lambda j, s, gr, *_: (gr[s], j, 0))
    else:
        rhs_spec = pl.BlockSpec((None, k, tn), lambda j, s, gr, *_: (gr[s], 0, j))
    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_gmm_kernel, tm=tm, transpose_rhs=transpose_rhs),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-n // tn), steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, s, gr, ti, lt, *_: (lt[s], 0)),
                rhs_spec,
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, s, gr, ti, *_: (ti[s], j)),
        ),
        out_shape=jax.ShapeDtypeStruct((m, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=chosen.vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k * -(-n // tn) + g * k * n + m * n)),
        interpret=interpret,
        name="grouped_matmul",
    )(v.group, v.tile, v.lhs_tile, v.lo, v.hi, lhs, rhs)


def _tgmm_kernel(group, tile, lhs_tile, lo, hi, lhs_ref, dy_ref, out_ref, acc_ref, *,
                 tm):
    del lhs_tile
    s = pl.program_id(1)
    steps = pl.num_programs(1)
    row0 = tile[s] * tm
    opens = jnp.logical_or(s == 0, group[s] != group[jnp.maximum(s - 1, 0)])
    closes = jnp.logical_or(s == steps - 1,
                            group[s] != group[jnp.minimum(s + 1, steps - 1)])
    live = hi[s] > lo[s]

    @pl.when(opens)
    def _open():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _visit():
        # both operands keep the group's rows only: a row outside it may hold
        # anything, also what 0 x it is not 0 (the last tile's rows past M)
        x, dy = lhs_ref[...], dy_ref[...]
        x = jnp.where(_group_rows(lo[s], hi[s], row0, x.shape), x, jnp.zeros_like(x))
        dy = jnp.where(_group_rows(lo[s], hi[s], row0, dy.shape), dy, jnp.zeros_like(dy))
        acc_ref[...] += jax.lax.dot_general(
            x, dy, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32)

    @pl.when(closes)
    def _close():
        out_ref[...] = acc_ref[...].astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("tm", "tn", "interpret"))
def _tgmm(lhs, dy, group_sizes, *, tm=None, tn=None, interpret=False):
    """Σ over each group's rows of lhs (M, K) rowᵀ · dy (M, N) row: (G, K, N)
    in lhs's dtype; an empty group's block is zero, rows past the last group
    add nothing."""
    m, k = lhs.shape
    n = dy.shape[1]
    g = group_sizes.shape[0]
    if dy.shape[0] != m:
        raise ValueError(f"transposed grouped matmul of {lhs.shape} by {dy.shape}")
    chosen = tiles(m, k, n, lhs.dtype, transposed=True)
    tm, tn = tm or chosen.rows, tn or chosen.cols
    if not tn:
        raise ValueError(f"no column tile of a ({k}, {n}) result fits VMEM")
    v = row_tile_visits(group_sizes, m, tm, visit_empty=True)
    steps = v.group.shape[0]
    size = lhs.dtype.itemsize
    return pl.pallas_call(
        functools.partial(_tgmm_kernel, tm=tm),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-n // tn), steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, s, gr, ti, lt, *_: (lt[s], 0)),
                pl.BlockSpec((tm, tn), lambda j, s, gr, ti, lt, *_: (lt[s], j)),
            ],
            out_specs=pl.BlockSpec((None, k, tn), lambda j, s, gr, *_: (gr[s], 0, j)),
            scratch_shapes=[pltpu.VMEM((k, tn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((g, k, n), lhs.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=chosen.vmem_limit),
        cost_estimate=pl.CostEstimate(
            flops=2 * m * k * n, transcendentals=0,
            bytes_accessed=size * (m * k * -(-n // tn) + m * n + g * k * n)),
        interpret=interpret,
        name="grouped_matmul_t",
    )(v.group, v.tile, v.lhs_tile, v.lo, v.hi, lhs, dy)


@jax.custom_vjp
def grouped_matmul(lhs: jax.Array, rhs: jax.Array, group_sizes: jax.Array) -> jax.Array:
    """lhs (M, K) by rhs (G, K, N), row group i by matrix i: (M, N) in lhs's
    dtype, float32 accumulation over K. The groups are contiguous from row 0
    in the order of `group_sizes` (G,) int32; rows past the last group are
    zeros out and take no gradient in."""
    return _gmm(lhs, rhs, group_sizes, interpret=kernel_interpret())


def _grouped_matmul_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_matmul_bwd(res, dy):
    lhs, rhs, group_sizes = res
    interpret = kernel_interpret()
    dlhs = _gmm(dy, rhs, group_sizes, transpose_rhs=True, interpret=interpret)
    drhs = _tgmm(lhs, dy, group_sizes, interpret=interpret)
    return dlhs, drhs.astype(rhs.dtype), None


grouped_matmul.defvjp(_grouped_matmul_fwd, _grouped_matmul_bwd)
