"""The chunked state-space scan (`ops/ssm.py::ssd_chunked`) as Pallas
(Mosaic) kernels, forward and backward, joined by a `jax.custom_vjp` (PR 33).

Same mathematics and the same rounding points as the plain body; what changes
is where the intermediates live. A grid step is one VISIT: (sequence, group,
chunk), the chunks of a (sequence, group) in order, the group's state —
(N, R·P) float32 for its R heads of P channels — in a VMEM scratch that
lives across the chunk axis. A visit reads one chunk of x for the group's
heads (L, R·P), of B and C (L, N) and the chunk's Δ and cumulative Δ·A, forms
`C Bᵀ` once for the group, and per head builds the decay matrix
`Λ_ts = exp(cum_t − cum_s)` under the lower-triangular mask,
`m = (C Bᵀ ⊙ Λ)` in the compute dtype and `y = m · Δx`; the state the chunk
starts from reaches y as ONE product for the group, `exp(cum_t) · (C · S)`,
and the state moves on as `S ← exp(cum_L) S + Bᵀ · (Δx ⊙ exp(cum_L − cum_s))`,
one product too. Nothing of shape (…, L, L) leaves VMEM. The backward is the
same grid with the chunks in reverse and dS carried in VMEM: it recomputes
`C Bᵀ`, Λ and m from the inputs and the state each chunk started from, and
returns dx, dB and dC (summed over a group's heads in the kernel) and the
gradients of the per-head vectors. The states it starts from come from a
sweep of its own just before it (`ssd_chunk_starts`: the forward's
recurrence alone, (T/L, N, H·P) float32, alive between the two calls): kept
by the forward they would ride through the rest of the layer's backward,
134 MB a layer that the benchmark's Nemotron cell has no room for (PERF.md
§6, PR 33).

What stays in XLA (`ssd_scan`): padding T to whole chunks, `cum = cumsum(Δ·A)`
inside a chunk, `cum_L − cum`, `exp(cum_L)` and the layouts the kernel reads
them in; JAX differentiates those, the `custom_vjp` covers the kernels alone.
A cumulative sum inside the kernel would be a triangular matmul that rounds
its float32 operand.

Lanes: heads narrower than a vreg's 128 lanes are taken `128 // P` at a time
— a lane tile of x holds that many heads whole, every slice of a block is
lane-aligned, and a head is picked out of its tile by a lane mask (a product
against the masked operand costs the MXU what the unmasked one would). Per-
head vectors come in two layouts, because Λ needs `cum` down the sublanes AND
along the lanes: `cols` (…, T, 3R) = [Δ | cum | cum_L − cum] by head, a token
a row, and `rows` (…, R, T) = cum, a head a row.

Precision: Δ, cum, every `exp`, the mask, the state and the recurrence over
chunks float32; matmul operands in `compute_dtype` exactly where the plain
body casts them (C, B, m, Δx, Δx ⊙ to_end, the starting states — and in the
backward the cotangents that take their places), float32 accumulation. The
`pallas_call`s are named `ssd_chunk_fwd`, `ssd_chunk_starts` and
`ssd_chunk_bwd`, so a trace names them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import (
    _interpret_active, _vmem_bytes, kernel_interpret)
from elasticdl_tpu.ops.pallas_gmm import LANES

_F32 = jnp.float32


def runnable() -> bool:
    """The kernels need a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()


class Blocks(NamedTuple):
    lane_tile: int   # lanes of x a step of the kernel's head loop takes
    heads_a_tile: int
    vmem_bytes: int  # what the backward's visit holds (the forward's is less)
    vmem_limit: int  # what Mosaic may use


def blocks(heads: int, head_dim: int, groups: int, state: int, chunk: int,
           dtype=_F32, compute_dtype=jnp.bfloat16) -> Optional[Blocks]:
    """The visit's blocks for `heads` heads of `head_dim` in `groups` groups
    of `state` columns at chunks of `chunk` tokens, or None where the kernels
    do not take the shape: chunk and state whole lanes, heads a multiple of
    groups, a head a whole number of lane tiles or a lane tile a whole number
    of a group's heads, and the backward's visit — two buffers of every block,
    its scratch and the float32 values it holds — inside half the chip's
    VMEM."""
    if chunk % LANES or state % LANES or heads % groups:
        return None
    r = heads // groups
    if head_dim % LANES == 0:
        tile, k = head_dim, 1
    elif LANES % head_dim == 0 and r % (LANES // head_dim) == 0:
        tile, k = LANES, LANES // head_dim
    else:
        return None
    size, csize = jnp.dtype(dtype).itemsize, jnp.dtype(compute_dtype).itemsize
    wide = r * head_dim
    # cols (L, 3R) pads its lanes, rows (R, L) its sublanes, keep (1, R·P) too
    vectors = 4 * (chunk * -(-3 * r // LANES) * LANES + -(-r // 8) * 8 * chunk + 8 * wide)
    moved = (3 * chunk * wide + 4 * chunk * state) * size + 2 * vectors + 4 * state * wide
    held = 4 * state * wide + 2 * csize * chunk * wide        # dS; Δx ⊙ to_end and dy ⊙ exp(cum)
    values = 4 * (3 * chunk * wide + 8 * chunk * chunk)
    need = 2 * moved + held + values
    vmem = _vmem_bytes()
    return Blocks(tile, k, need, vmem * 3 // 4) if need <= vmem // 2 else None


def _nt(a, b):
    """a (M, K) · b (N, K)ᵀ, float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _tn(a, b):
    """a (K, M)ᵀ · b (K, N), float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


class _Visit:
    """What both kernels read of a chunk the same way: the lane tiles of the
    group's heads and each head's Δ, cum and cum_L − cum as columns."""

    def __init__(self, cols_ref, rows_ref, r, p, plan: Blocks, l):
        self.cols, self.rows = cols_ref, rows_ref
        self.r, self.p, self.k, self.tile, self.l = r, p, plan.heads_a_tile, plan.lane_tile, l
        self.tiles = r * p // plan.lane_tile
        t = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
        s = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
        self.lower = t >= s
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, plan.lane_tile), 1)
        self.mine = [lane // p == i for i in range(self.k)]

    def lanes(self, j):
        return slice(j * self.tile, (j + 1) * self.tile)

    def column(self, which, h):
        """Δ (0), cum (1) or cum_L − cum (2) of head h: (L, 1) float32."""
        at = which * self.r + h
        return self.cols[:, at:at + 1]

    def spread(self, which, j):
        """The same for every head of lane tile j, each over its own lanes:
        (L, lane tile)."""
        h0 = j * self.k
        out = jnp.broadcast_to(self.column(which, h0), (self.l, self.tile))
        for i in range(1, self.k):
            out = jnp.where(self.mine[i], self.column(which, h0 + i), out)
        return out

    def only(self, i, v):
        """v on head i's lanes of its tile, zero elsewhere."""
        return v if self.k == 1 else jnp.where(self.mine[i], v, 0.0)

    def decay(self, h):
        """Λ of head h: exp(cum_t − cum_s) for s ≤ t, 0 above: (L, L)."""
        diff = self.column(1, h) - self.rows[h:h + 1, :]
        return jnp.exp(jnp.where(self.lower, diff, -jnp.inf))


def _carried(ref):
    """What a (sequence, group) carries across its chunks, zero at the first
    visit."""
    @pl.when(pl.program_id(2) == 0)
    def _first_visit():
        ref[...] = jnp.zeros_like(ref)

    return ref[...]


def _fwd_kernel(x_ref, b_ref, c_ref, cols_ref, rows_ref, keep_ref, y_ref,
                state_ref, w_ref, *, r, p, plan, dt_c):
    v = _Visit(cols_ref, rows_ref, r, p, plan, x_ref.shape[0])
    s0 = _carried(state_ref)
    bc, cc = b_ref[...].astype(dt_c), c_ref[...].astype(dt_c)
    cb = _nt(cc, bc)
    from_start = _nn(cc, s0.astype(dt_c))
    for j in range(v.tiles):
        at = v.lanes(j)
        xd = x_ref[:, at].astype(_F32) * v.spread(0, j)
        xdc = xd.astype(dt_c)
        y = from_start[:, at] * jnp.exp(v.spread(1, j))
        for i in range(v.k):
            m = (cb * v.decay(j * v.k + i)).astype(dt_c)
            y = y + _nn(m, v.only(i, xdc))
        y_ref[:, at] = y
        w_ref[:, at] = (xd * jnp.exp(v.spread(2, j))).astype(dt_c)
    state_ref[...] = keep_ref[...] * s0 + _tn(bc, w_ref[...])


def _starts_kernel(x_ref, b_ref, cols_ref, keep_ref, starts_ref, state_ref, w_ref, *,
                   r, p, plan, dt_c):
    """The forward's recurrence alone: writes the state each chunk starts
    from, for the backward."""
    v = _Visit(cols_ref, None, r, p, plan, x_ref.shape[0])
    s0 = _carried(state_ref)
    starts_ref[...] = s0
    for j in range(v.tiles):
        at = v.lanes(j)
        xd = x_ref[:, at].astype(_F32) * v.spread(0, j)
        w_ref[:, at] = (xd * jnp.exp(v.spread(2, j))).astype(dt_c)
    state_ref[...] = keep_ref[...] * s0 + _tn(b_ref[...].astype(dt_c), w_ref[...])


def _bwd_kernel(x_ref, dy_ref, b_ref, c_ref, cols_ref, rows_ref, keep_ref, starts_ref,
                dx_ref, db_ref, dc_ref, dcols_ref, drows_ref, dkeep_ref,
                dstate_ref, w_ref, df_ref, *, r, p, plan, dt_c):
    l = x_ref.shape[0]
    v = _Visit(cols_ref, rows_ref, r, p, plan, l)
    ds1, s0 = _carried(dstate_ref), starts_ref[...]
    ds1c, s0c = ds1.astype(dt_c), s0.astype(dt_c)
    bc, cc = b_ref[...].astype(dt_c), c_ref[...].astype(dt_c)
    cb = _nt(cc, bc)
    from_start = _nn(cc, s0c)
    dw = _nn(bc, ds1c)                         # cotangent of Δx ⊙ to_end
    dkeep_ref[...] = jnp.sum(ds1 * s0, axis=0, keepdims=True)
    dcb = jnp.zeros((l, l), _F32)
    of_head = lambda i, prod: jnp.sum(v.only(i, prod), axis=1, keepdims=True)
    for j in range(v.tiles):
        at = v.lanes(j)
        x, dy = x_ref[:, at].astype(_F32), dy_ref[:, at].astype(_F32)
        delta, e, to_end = v.spread(0, j), jnp.exp(v.spread(1, j)), jnp.exp(v.spread(2, j))
        xd = x * delta
        xdc, dyc = xd.astype(dt_c), dy.astype(dt_c)
        dxd = dw[:, at] * to_end
        dy_f = dy * from_start[:, at]     # y's share from the start, before exp(cum)
        for i in range(v.k):
            h = j * v.k + i
            decay = v.decay(h)
            mf = cb * decay
            dm = _nt(v.only(i, dyc), xdc)
            ddiff = dm * mf
            dcb = dcb + dm * decay
            dcols_ref[:, r + h:r + h + 1] = (
                jnp.sum(ddiff, axis=1, keepdims=True)
                + of_head(i, dy_f) * jnp.exp(v.column(1, h)))
            drows_ref[h:h + 1, :] = -jnp.sum(ddiff, axis=0, keepdims=True)
            dxd = dxd + v.only(i, _tn(mf.astype(dt_c), dyc))
        dx_ref[:, at] = (dxd * delta).astype(dx_ref.dtype)
        ddelta, drest = dxd * x, dw[:, at] * xd
        for i in range(v.k):
            h = j * v.k + i
            dcols_ref[:, h:h + 1] = of_head(i, ddelta)
            dcols_ref[:, 2 * r + h:2 * r + h + 1] = (
                of_head(i, drest) * jnp.exp(v.column(2, h)))
        df_ref[:, at] = (dy * e).astype(dt_c)
        w_ref[:, at] = (xd * to_end).astype(dt_c)
    dcbc = dcb.astype(dt_c)
    dc_ref[...] = (_nn(dcbc, bc) + _nt(df_ref[...], s0c)).astype(dc_ref.dtype)
    db_ref[...] = (_tn(dcbc, cc) + _nt(w_ref[...], ds1c)).astype(db_ref.dtype)
    dstate_ref[...] = keep_ref[...] * ds1 + _tn(cc, df_ref[...])


def _specs(k, l, reverse):
    """BlockSpecs of a visit's blocks by name; `reverse` walks the chunks
    from the last."""
    n, r, wide = k.n, k.r, k.wide
    at = (lambda c: k.nc - 1 - c) if reverse else (lambda c: c)
    return {
        "x": pl.BlockSpec((None, l, wide), lambda s, g, c: (s, at(c), g)),
        "bc": pl.BlockSpec((None, l, n), lambda s, g, c: (s, at(c), g)),
        "cols": pl.BlockSpec((None, None, l, 3 * r), lambda s, g, c: (s, g, at(c), 0)),
        "rows": pl.BlockSpec((None, None, r, l), lambda s, g, c: (s, g, 0, at(c))),
        "keep": pl.BlockSpec((None, None, None, 1, wide), lambda s, g, c: (s, g, at(c), 0, 0)),
        "starts": pl.BlockSpec((None, None, None, n, wide), lambda s, g, c: (s, g, at(c), 0, 0)),
    }


class _Call(NamedTuple):
    """What the three `pallas_call`s take from their operands' shapes."""
    bsz: int
    tp: int
    g: int
    r: int
    p: int
    n: int
    nc: int
    wide: int
    plan: Blocks


def _call(x, b, cols, l, dt_c) -> _Call:
    bsz, tp, hp = x.shape
    g, r = cols.shape[1], cols.shape[3] // 3
    p, n = hp // (g * r), b.shape[2] // g
    plan = blocks(g * r, p, g, n, l, x.dtype, dt_c)
    if plan is None:
        raise ValueError(f"the scan kernels do not take {g * r} heads of {p} in {g} "
                         f"groups of {n} at chunks of {l}")
    return _Call(bsz, tp, g, r, p, n, tp // l, r * p, plan)


def _params(plan):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=plan.vmem_limit)


@functools.partial(jax.jit, static_argnames=("l", "dt_c", "interpret"))
def _forward(x, b, c, cols, rows, keep, *, l, dt_c, interpret):
    k = _call(x, b, cols, l, dt_c)
    bsz, tp, g, r, p, n, nc, wide, plan = k
    sp = _specs(k, l, reverse=False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, r=r, p=p, plan=plan, dt_c=dt_c),
        grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["bc"], sp["bc"], sp["cols"], sp["rows"], sp["keep"]],
        out_specs=sp["x"],
        out_shape=jax.ShapeDtypeStruct((bsz, tp, g * wide), _F32),
        scratch_shapes=[pltpu.VMEM((n, wide), _F32), pltpu.VMEM((l, wide), dt_c)],
        compiler_params=_params(plan),
        cost_estimate=pl.CostEstimate(
            flops=2 * bsz * tp * g * (l * n + 2 * n * wide + l * wide * plan.lane_tile // p),
            transcendentals=bsz * tp * g * r * (l + 2 * p),
            bytes_accessed=bsz * tp * g * (x.dtype.itemsize * wide + 4 * wide
                                           + 2 * b.dtype.itemsize * n)),
        interpret=interpret,
        name="ssd_chunk_fwd",
    )(x, b, c, cols, rows, keep)


@functools.partial(jax.jit, static_argnames=("l", "dt_c", "interpret"))
def _chunk_starts(x, b, cols, keep, *, l, dt_c, interpret):
    k = _call(x, b, cols, l, dt_c)
    bsz, tp, g, r, p, n, nc, wide, plan = k
    sp = _specs(k, l, reverse=False)
    return pl.pallas_call(
        functools.partial(_starts_kernel, r=r, p=p, plan=plan, dt_c=dt_c),
        grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["bc"], sp["cols"], sp["keep"]],
        out_specs=sp["starts"],
        out_shape=jax.ShapeDtypeStruct((bsz, g, nc, n, wide), _F32),
        scratch_shapes=[pltpu.VMEM((n, wide), _F32), pltpu.VMEM((l, wide), dt_c)],
        compiler_params=_params(plan),
        cost_estimate=pl.CostEstimate(
            flops=2 * bsz * tp * g * n * wide, transcendentals=bsz * tp * g * wide,
            bytes_accessed=bsz * tp * g * (x.dtype.itemsize * wide + b.dtype.itemsize * n)
            + 4 * bsz * g * nc * n * wide),
        interpret=interpret,
        name="ssd_chunk_starts",
    )(x, b, cols, keep)


@functools.partial(jax.jit, static_argnames=("l", "dt_c", "interpret"))
def _backward(x, dy, b, c, cols, rows, keep, starts, *, l, dt_c, interpret):
    k = _call(x, b, cols, l, dt_c)
    bsz, tp, g, r, p, n, nc, wide, plan = k
    sp = _specs(k, l, reverse=True)
    like = lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, r=r, p=p, plan=plan, dt_c=dt_c),
        grid=(bsz, g, nc),
        in_specs=[sp["x"], sp["x"], sp["bc"], sp["bc"], sp["cols"], sp["rows"],
                  sp["keep"], sp["starts"]],
        out_specs=[sp["x"], sp["bc"], sp["bc"], sp["cols"], sp["rows"], sp["keep"]],
        out_shape=[like(x), like(b), like(c), like(cols), like(rows), like(keep)],
        scratch_shapes=[pltpu.VMEM((n, wide), _F32), pltpu.VMEM((l, wide), dt_c),
                        pltpu.VMEM((l, wide), dt_c)],
        compiler_params=_params(plan),
        cost_estimate=pl.CostEstimate(
            flops=2 * bsz * tp * g * (3 * l * n + 5 * n * wide
                                      + 2 * l * wide * plan.lane_tile // p),
            transcendentals=bsz * tp * g * r * (l + 3 * p),
            bytes_accessed=bsz * tp * g * (2 * x.dtype.itemsize * wide + 4 * wide
                                           + 4 * b.dtype.itemsize * n)
            + 4 * bsz * g * nc * n * wide),
        interpret=interpret,
        name="ssd_chunk_bwd",
    )(x, dy, b, c, cols, rows, keep, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def chunk_scan(x, b, c, cols, rows, keep, l, dt_c):
    """The scan over whole chunks of `l` tokens. x (B, T, H·P); b, c
    (B, T, G·N); cols (B, G, T, 3R) float32 = [Δ | cum | cum_L − cum] of the
    group's R heads; rows (B, G, R, T) = cum; keep (B, G, T/l, 1, R·P) =
    exp(cum_L), a head's value over its P lanes. Returns y (B, T, H·P)
    float32. Differentiable in all six; the residuals are the six."""
    return _forward(x, b, c, cols, rows, keep, l=l, dt_c=dt_c,
                    interpret=kernel_interpret())


def _chunk_scan_fwd(x, b, c, cols, rows, keep, l, dt_c):
    return chunk_scan(x, b, c, cols, rows, keep, l, dt_c), (x, b, c, cols, rows, keep)


def _chunk_scan_bwd(l, dt_c, res, dy):
    x, b, c, cols, rows, keep = res
    how = dict(l=l, dt_c=dt_c, interpret=kernel_interpret())
    # the forward call sits under the caller's scope; the backward is traced
    # apart from it and carries its own, so a trace read by scope finds both
    with jax.named_scope("ssd"):
        starts = _chunk_starts(x, b, cols, keep, **how)
        return tuple(_backward(x, dy, b, c, cols, rows, keep, starts, **how))


chunk_scan.defvjp(_chunk_scan_fwd, _chunk_scan_bwd)


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
             chunk: int, compute_dtype=jnp.bfloat16) -> jax.Array:
    """`ops/ssm.py::ssd_chunked` on the kernel route: same arguments, same
    result. The per-head vectors are made here, in float32, and laid out as
    the kernels read them."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r, l = h // g, chunk
    pad = -t % l
    if pad:
        widen = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    tp = t + pad
    nc = tp // l
    dt = dt.astype(_F32)
    # cum_t = Σ_{r≤t} Δ_r A inside the chunk: (B, nc, L, H), ≤ 0 and falling
    cum = jnp.cumsum((dt * a.astype(_F32)).reshape(bsz, nc, l, h), axis=2)
    last = cum[:, :, -1:, :]
    by_group = lambda v: v.reshape(bsz, tp, g, r).transpose(0, 2, 1, 3)
    cols = jnp.concatenate([by_group(dt), by_group(cum), by_group(last - cum)], axis=-1)
    rows = cum.reshape(bsz, tp, g, r).transpose(0, 2, 3, 1)
    keep = jnp.repeat(jnp.exp(last).reshape(bsz, nc, g, r), p, axis=-1)
    keep = keep.transpose(0, 2, 1, 3)[:, :, :, None, :]
    y = chunk_scan(x.reshape(bsz, tp, h * p), b.reshape(bsz, tp, g * n),
                   c.reshape(bsz, tp, g * n), cols, rows, keep, l,
                   jnp.dtype(compute_dtype))
    return y.reshape(bsz, tp, h, p)[:, :t]
