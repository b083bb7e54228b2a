"""xDeepFM's Compressed Interaction Network as two Pallas (Mosaic) kernels
(PR 40): the layer `y[b,o,d] = sum_{h,f} W[o,h,f] * xk[b,h,d] * x0[b,f,d]`
with the outer-product plane `Z[(f h), (d b)] = x0[f] * xk[h]` — and, in the
backward, its pulled-back twin dZ — made a column tile at a time in VMEM and
never written to HBM.

CIN never mixes b or d, so with the D * B (coordinate, example) pairs
flattened onto the lanes every operand is a (rows, N) matrix and a grid step
is one column tile:

    y  (O, N)      = W (O, F*H) . Z (F*H, N)             `cin_fwd`
    dW (F*H, O)   += Z . g^T                              `cin_bwd`
    dZ (F*H, N)    = W^T (F*H, O) . g (O, N)              `cin_bwd`
    dxk[h] = sum_f dZ[f, h] * x0[f],  dx0[f] = sum_h dZ[f, h] * xk[h]

XLA runs the backward as four plane-sized items a layer — the two matmuls,
dZ written to HBM (5.75 GB in bfloat16 at the benchmark's shapes), and two
multiply-reduce passes over it (PERF.md section 6, PR 40); here dZ is the
float32 result of the MXU's dot, reduced both ways while it is in VMEM.

The layout, made by plain XLA outside the kernels (`cin`):

- **columns d-major** (column = d * Bp + b): the batch stays the minor
  dimension — a minor dimension of D = 10 would be padded to 128 lanes — and
  the grid is (example tiles, D) with d inside, so the block of a layer's
  sum over d, which is what the model reads, keeps its place over a tile's D
  steps: the forward kernel writes that sum beside y, the backward kernel
  adds its cotangent to g's tile. XLA does neither well: asked for the sum
  of a (O, D * B) array over d it first transposed all of it.
- **the plane's rows f-major** (row = f * Hp + h), so a chunk of rows is "all
  h for a few f": `x0[f]` is one row broadcast over the chunk's sublanes,
  the sum over f accumulates element-wise and the sum over h is a reduction
  over whole sublane tiles.
- **rows padded to whole packed tiles**: H, O and F are rounded up to
  `SUBLANES` (200 -> 208, 26 -> 32) with zero rows of W and of x0, so every
  slice of the plane starts on a tile of the packed bfloat16 layout, and B
  to the column tile with zero examples. A padded row or column is zero in
  y, takes a zero cotangent and gives a zero gradient; a layer's padded
  output is the next layer's input as it stands.
- W itself stays the model's (O, H * F) float32 parameter in (h, f) order:
  `w_rows` permutes it (differentiably: the gradient is permuted back by
  its transpose) and the kernels take the compute dtype's cast of that.

Operands go into the MXU in the compute dtype, products accumulate in
float32, dZ stays float32 up to its two reductions, a layer's sum over d is
taken of the float32 y, dW comes back float32: the einsum's arithmetic,
rounded no more often than it.
"""

from __future__ import annotations

import functools
import logging
from typing import NamedTuple, Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import (
    _interpret_active, _vmem_bytes, kernel_interpret)

logger = logging.getLogger(__name__)

LANES = 128
SUBLANES = 16     # rows of a packed bfloat16 tile; whole float32 tiles too
# column tiles tried, widest first. On a v5e 512 and 1024 columns tie with
# all 26 fields one chunk (forward 7.7, backward 16.0 ms at H = O = 200,
# N = 552 960), 2048 loses a fifth in the backward and takes three times as
# long to compile (PERF.md section 6, PR 40)
COLUMN_TILES = (512, 256, 128)


def runnable() -> bool:
    """The kernels need a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()


def ambient_devices() -> int:
    """Devices of the ambient `jax.set_mesh` context; 1 outside any."""
    mesh = jax.sharding.get_abstract_mesh()
    return 1 if mesh.empty else mesh.size


def _up(n: int, m: int) -> int:
    return -(-n // m) * m


def chunk_fields(h: int, o: int, f: int, cols: int, dtype) -> int:
    """The most fields a chunk of the plane's rows (a divisor of F) for which
    the backward's blocks at a column tile of `cols` — two buffers of each
    column block and of W, the float32 gradient of W, a chunk of Z and of
    float32 dZ, the float32 forms of a column block — fit half the chip's
    VMEM; 0: not even one field's."""
    size = jnp.dtype(dtype).itemsize
    hp, op, fp = _up(h, SUBLANES), _up(o, SUBLANES), _up(f, SUBLANES)
    held = f * hp * _up(op, LANES) * (2 * size + 2 * 4)     # W, dW
    blocks = 2 * size * cols * (2 * hp + 2 * fp + 3 * op)
    floats = 4 * cols * (3 * hp + 2 * fp + 2 * op)
    for fields in range(f, 0, -1):
        plane = fields * hp * cols * (size + 4)
        if f % fields == 0 and held + blocks + plane + floats <= _vmem_bytes() // 2:
            return fields
    return 0


def column_tile(h: int, o: int, f: int, b: int, dtype) -> int:
    """The column tile of one layer's kernels — H feature maps in, O out, F
    fields, B examples: the widest of `COLUMN_TILES` that pads B by under a
    32nd and has room for a chunk of the plane; 0: none has."""
    least = _up(b, LANES)
    for cols in COLUMN_TILES:
        if (cols <= least and _up(b, cols) - least <= b // 32
                and chunk_fields(h, o, f, cols, dtype)):
            return cols
    return 0


def network_tiles(x_shape: Sequence[int], layer_sizes: Sequence[int], dtype) -> int:
    """The one column tile a CIN over x0 (B, F, D) runs all its layers in —
    the narrowest of the layers' — or 0 where a layer has none."""
    b, f, _ = x_shape
    return min(column_tile(h, o, f, b, dtype)
               for h, o in zip((f,) + tuple(layer_sizes), layer_sizes))


def cin_route(x_shape: Sequence[int], layer_sizes: Sequence[int], dtype,
              kernel_runnable: bool, devices: int) -> str:
    """Which body a CIN over x0 (B, F, D) with `layer_sizes` feature maps
    takes — "kernel" or "einsum": a pure function of the shapes, the compute
    dtype, whether the kernels can run here (a TPU, or interpret mode in the
    CPU tests) and the devices of the ambient mesh. Across devices the
    kernel's gradient of W would need a `psum` no cell or test runs: the
    einsum, which XLA partitions, is the route there."""
    b, f, d = x_shape
    fit = (jnp.dtype(dtype) in (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
           and bool(network_tiles(x_shape, layer_sizes, dtype)))
    route = "kernel" if kernel_runnable and devices == 1 and fit else "einsum"
    # trace-time, once per compiled program: which route this shape took
    logger.info(
        "CIN %s over %d examples x %d fields x %d coordinates in %s takes the "
        "%s route (the Pallas kernels need a TPU or interpret mode: %s; one "
        "device, the ambient mesh has %d; bfloat16 or float32 and a layer's "
        "blocks inside VMEM: %s)", tuple(layer_sizes), b, f, d,
        jnp.dtype(dtype).name, route, kernel_runnable, devices, fit)
    return route


# --------------------------------------------------------------------- #
# the layouts

def to_columns(x: jax.Array, cols: int) -> jax.Array:
    """x (B, R, D) -> (Rp, D * Bp): a row a feature map, a column a (d, b)
    pair, d-major; rows padded to `SUBLANES`, examples to `cols`, with zeros."""
    b, r, d = x.shape
    rdb = jnp.pad(x.transpose(1, 2, 0),
                  ((0, _up(r, SUBLANES) - r), (0, 0), (0, _up(b, cols) - b)))
    return rdb.reshape(rdb.shape[0], -1)


def w_rows(w: jax.Array, h: int, f: int) -> jax.Array:
    """The parameter W (O, H * F), columns in (h, f) order, as the kernels
    take it: (F * Hp, Op), row f * Hp + h, the padded rows and columns zero."""
    o = w.shape[0]
    hp, op = _up(h, SUBLANES), _up(o, SUBLANES)
    rows = jnp.pad(w.reshape(o, h, f).transpose(2, 1, 0),
                   ((0, 0), (0, hp - h), (0, op - o)))
    return rows.reshape(f * hp, op)


# --------------------------------------------------------------------- #
# the kernels: a grid step is one column tile, `cols` examples at one
# coordinate d; the grid is (example tiles, D) with d the inner dimension, so
# the (Op, cols) block of a layer's sum over d keeps its place over a tile's
# D steps

def _plane(z_ref, xk, x0, first, fields, hp):
    """Z's rows for the fields first <= f < first + fields into z_ref
    (fields * Hp, cols): xk (Hp, cols) times the row x0[f], both float32,
    rounded to the operands' dtype as the MXU takes them."""
    for i in range(fields):
        f = first + i
        z_ref[i * hp:(i + 1) * hp, :] = (xk * x0[f:f + 1, :]).astype(z_ref.dtype)


def _fwd_kernel(w_ref, xk_ref, x0_ref, y_ref, s_ref, z_ref, s_acc, *, fields, hp):
    """w_ref (chunks, Op, fields * Hp), xk_ref (Hp, cols), x0_ref (Fp, cols)
    -> y_ref (Op, cols), and s_ref (Op, cols), the tile's sum of y over d,
    written at the last d from `s_acc` (float32)."""
    d = pl.program_id(1)
    xk = xk_ref[...].astype(jnp.float32)
    x0 = x0_ref[...].astype(jnp.float32)
    y = jnp.zeros(y_ref.shape, jnp.float32)
    for c in range(w_ref.shape[0]):
        _plane(z_ref, xk, x0, c * fields, fields, hp)
        y += jnp.dot(w_ref[c], z_ref[...], preferred_element_type=jnp.float32)
    y_ref[...] = y.astype(y_ref.dtype)

    @pl.when(d == 0)
    def _first():
        s_acc[...] = y

    @pl.when(d > 0)
    def _later():
        s_acc[...] += y

    @pl.when(d == pl.num_programs(1) - 1)
    def _last():
        s_ref[...] = s_acc[...].astype(s_ref.dtype)


def _bwd_kernel(*refs, fields, hp, with_g):
    """wt_ref (chunks, fields * Hp, Op), xk_ref (Hp, cols), x0_ref (Fp, cols),
    gs_ref (Op, cols) the cotangent of the sum over d, and `with_g` g_ref
    (Op, cols) that of y -> dxk_ref as xk_ref, dx0_ref as x0_ref, and dwt_ref
    as wt_ref in float32, one block held over the whole grid and added to by
    every step."""
    wt_ref, xk_ref, x0_ref, gs_ref = refs[:4]
    dxk_ref, dx0_ref, dwt_ref, z_ref, dz_ref, dx0_acc = refs[4 + with_g:]

    @pl.when(jnp.logical_and(pl.program_id(0) == 0, pl.program_id(1) == 0))
    def _init():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)
        dx0_acc[...] = jnp.zeros_like(dx0_acc)     # the rows past F stay zero

    xk = xk_ref[...].astype(jnp.float32)
    x0 = x0_ref[...].astype(jnp.float32)
    g = gs_ref[...]
    if with_g:
        g = (g.astype(jnp.float32) + refs[4][...].astype(jnp.float32)).astype(g.dtype)
    dxk = jnp.zeros(xk.shape, jnp.float32)
    for c in range(wt_ref.shape[0]):
        _plane(z_ref, xk, x0, c * fields, fields, hp)
        dwt_ref[c] += jax.lax.dot_general(
            z_ref[...], g, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        dz_ref[...] = jnp.dot(wt_ref[c], g, preferred_element_type=jnp.float32)
        for i in range(fields):
            f = c * fields + i
            dz = dz_ref[i * hp:(i + 1) * hp, :]
            dxk += dz * x0[f:f + 1, :]
            dx0_acc[f:f + 1, :] = jnp.sum(dz * xk, axis=0, keepdims=True)
    dxk_ref[...] = dxk.astype(dxk_ref.dtype)
    dx0_ref[...] = dx0_acc[...].astype(dx0_ref.dtype)


class _Layer(NamedTuple):
    hp: int
    op: int
    fp: int
    n: int
    cols: int
    fields: int
    rows: int     # of a chunk of the plane: fields * Hp
    chunks: int
    grid: tuple   # (example tiles, D)


def _layer(wt, xk, x0, d, cols, fields) -> _Layer:
    """A layer's sizes from its operands in the kernels' layout; `fields`
    given takes the rule's place (tests, sweeps)."""
    hp, n = xk.shape
    f_rows, op = wt.shape
    f = f_rows // hp
    if (f_rows != f * hp or x0.shape[1] != n or x0.shape[0] < f
            or n % (d * cols) or cols % LANES):
        raise ValueError(f"CIN layer of W {wt.shape}, xk {xk.shape}, x0 "
                         f"{x0.shape}, {d} coordinates in tiles of {cols}")
    fields = fields or chunk_fields(hp, op, f, cols, xk.dtype)
    if not fields or f % fields:
        raise ValueError(f"no chunk of {f} fields for a CIN layer of W "
                         f"{wt.shape} in tiles of {cols}: {fields}")
    return _Layer(hp, op, x0.shape[0], n, cols, fields, fields * hp, f // fields,
                  (n // (d * cols), d))


def _specs(la: _Layer):
    """A column block of r rows at grid step (example tile i, coordinate j),
    and the (Op, cols) block that keeps its place over a tile's D steps."""
    tiles_b = la.grid[0]
    col = lambda r: pl.BlockSpec((r, la.cols), lambda i, j: (0, j * tiles_b + i))
    return col, pl.BlockSpec((la.op, la.cols), lambda i, j: (0, i))


def _compiler_params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics,
                                vmem_limit_bytes=_vmem_bytes() * 3 // 4)


@functools.partial(jax.jit, static_argnames=("d", "cols", "fields", "interpret"))
def _cin_fwd(wt, xk, x0, *, d, cols, fields=None, interpret=False):
    """wt (F * Hp, Op), xk (Hp, N), x0 (Fp, N), all one dtype, N = D * Bp ->
    y (Op, N) and its sum over d (Op, Bp)."""
    la = _layer(wt, xk, x0, d, cols, fields)
    w = wt.reshape(la.chunks, la.rows, la.op).transpose(0, 2, 1)
    size = xk.dtype.itemsize
    col, over_d = _specs(la)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, fields=la.fields, hp=la.hp),
        grid=la.grid,
        in_specs=[pl.BlockSpec(w.shape, lambda i, j: (0, 0, 0)),
                  col(la.hp), col(la.fp)],
        out_specs=[col(la.op), over_d],
        out_shape=[jax.ShapeDtypeStruct((la.op, la.n), xk.dtype),
                   jax.ShapeDtypeStruct((la.op, la.n // d), xk.dtype)],
        scratch_shapes=[pltpu.VMEM((la.rows, cols), xk.dtype),
                        pltpu.VMEM((la.op, cols), jnp.float32)],
        compiler_params=_compiler_params("parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=2 * wt.size * la.n, transcendentals=0,
            bytes_accessed=size * (wt.size + la.n * (la.hp + la.fp + la.op))),
        interpret=interpret,
        name="cin_fwd",
    )(w, xk, x0)


@functools.partial(jax.jit, static_argnames=("d", "cols", "fields", "interpret"))
def _cin_bwd(wt, xk, x0, gs, g=None, *, d, cols, fields=None, interpret=False):
    """The layer's three gradients from gs (Op, Bp), the cotangent of the sum
    over d, and g (Op, N), that of y (None: zero): dxk as xk, dx0 as x0, and
    dW as wt in float32."""
    la = _layer(wt, xk, x0, d, cols, fields)
    size = xk.dtype.itemsize
    col, over_d = _specs(la)
    held = pl.BlockSpec((la.chunks, la.rows, la.op), lambda i, j: (0, 0, 0))
    with_g = g is not None
    dxk, dx0, dwt = pl.pallas_call(
        functools.partial(_bwd_kernel, fields=la.fields, hp=la.hp, with_g=with_g),
        grid=la.grid,
        in_specs=[held, col(la.hp), col(la.fp), over_d] + [col(la.op)] * with_g,
        out_specs=[col(la.hp), col(la.fp), held],
        out_shape=[jax.ShapeDtypeStruct(xk.shape, xk.dtype),
                   jax.ShapeDtypeStruct(x0.shape, x0.dtype),
                   jax.ShapeDtypeStruct(held.block_shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((la.rows, cols), xk.dtype),
                        pltpu.VMEM((la.rows, cols), jnp.float32),
                        pltpu.VMEM((la.fp, cols), jnp.float32)],
        compiler_params=_compiler_params("arbitrary", "arbitrary"),
        cost_estimate=pl.CostEstimate(
            flops=4 * wt.size * la.n, transcendentals=0,
            bytes_accessed=(size + 4) * wt.size
            + size * la.n * (2 * la.hp + 2 * la.fp + la.op * (1 + with_g))),
        interpret=interpret,
        name="cin_bwd",
    )(wt.reshape(held.block_shape), xk, x0, gs, *([g] * with_g))
    return dxk, dx0, dwt.reshape(wt.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3))
def cin_columns(wts, x0c, d: int, cols: int):
    """The network in the kernels' layout: wts[i] (F * Hp_i, Op_i) float32 as
    `w_rows` gives them, x0c (Fp, D * Bp) in the compute dtype as
    `to_columns` gives it -> every layer's sum over d, (Op_i, Bp) each. A
    layer's (Op, N) output is the next one's input as it stands. W is cast
    to the compute dtype for the MXU; its gradient comes back float32."""
    return _cin_columns_fwd(wts, x0c, d, cols)[0]


def _cin_columns_fwd(wts, x0c, d, cols):
    interpret = kernel_interpret()
    xk, xs, sums = x0c, [], []
    for wt in wts:
        xs.append(xk)
        xk, s = _cin_fwd(wt.astype(x0c.dtype), xk, x0c, d=d, cols=cols,
                         interpret=interpret)
        sums.append(s)
    return tuple(sums), (wts, tuple(xs), x0c)


def _cin_columns_bwd(d, cols, res, gsums):
    wts, xs, x0c = res
    interpret = kernel_interpret()
    g, dx0, dwts = None, 0.0, []
    for wt, xk, gs in reversed(list(zip(wts, xs, gsums))):
        g, dx0_f, dwt = _cin_bwd(wt.astype(x0c.dtype), xk, x0c, gs, g, d=d,
                                 cols=cols, interpret=interpret)
        dx0 = dx0 + dx0_f.astype(jnp.float32)
        dwts.append(dwt.astype(wt.dtype))
    # the first layer's xk is x0 itself
    dx0 = dx0 + g.astype(jnp.float32)
    return tuple(reversed(dwts)), dx0.astype(x0c.dtype)


cin_columns.defvjp(_cin_columns_fwd, _cin_columns_bwd)


def cin(ws: Sequence[jax.Array], x0: jax.Array) -> jax.Array:
    """The whole network on the kernels' route: ws[i] (O_i, H_i * F) float32
    in the model's own order, H_0 = F and H_i = O_{i-1}; x0 (B, F, D) in the
    compute dtype -> (B, sum of O_i), every layer's feature maps summed over
    d. Nothing of a layer's (rows, N) size is made outside the kernels but
    x0's own transpose."""
    b, f, d = x0.shape
    sizes = tuple(int(w.shape[0]) for w in ws)
    cols = network_tiles(x0.shape, sizes, x0.dtype)
    wts = tuple(w_rows(w, h, f) for w, h in zip(ws, (f,) + sizes))
    sums = cin_columns(wts, to_columns(x0, cols), d, cols)
    return jnp.concatenate([s[:o, :b] for s, o in zip(sums, sizes)], axis=0).T
