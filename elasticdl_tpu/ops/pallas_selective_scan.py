"""The selective scan (S6: Gu & Dao, arXiv:2312.00752, Algorithm 2) as Pallas
(Mosaic) kernels, forward and backward, joined by a `jax.custom_vjp`
(`ops/ssm.py::selective_scan` on its kernel route).

The recurrence, for every channel e of E and every state index n of N (each
channel has a step Δ of its own, so no two (e, n) share a decay and the
chunk-as-matmul form of `ops/pallas_ssd.py` does not apply):

    S_t[n, e] = exp(Δ_t[e] · A[n, e]) · S_{t-1}[n, e] + Δ_t[e] x_t[e] · B_t[n]
    y_t[e]    = Σ_n S_t[n, e] · C_t[n] + D[e] · x_t[e],          S_0 = 0

A grid step takes a (time block, channel block) of a sequence: grid
(sequence, channel block, time block), the time axis sequential, the channel
block's (N, channels) float32 state in a VMEM scratch across it — N on the
sublanes, channels on the lanes, the layout in which x, Δ and y lie as the
projection leaves them, (T, E) with a token a row. B and C come TRANSPOSED,
(N, T): a token's B is then a column, read out of its lane tile by a masked
lane sum and broadcast along the channels. A block is walked in strips of
`STRIP` tokens (one float32 tile of x, Δ and y), the tokens of a strip
unrolled. Nothing of (T, E, N) size exists in HBM on either pass.

The forward leaves the state each time block STARTS from, (T / `TIME_BLOCK`,
N, E) float32 (at 8192 tokens and 5120 channels of 16: 21 MB a sequence, an
eighth of y); the residuals are those and the operands. The backward visits a
channel block's time blocks in REVERSE with dS in VMEM across them: a visit
first walks its block forward again from the kept start state and holds the
block's `TIME_BLOCK` + 1 states in VMEM (8.4 MB at a channel block of 1024),
then walks it backward:

    G_t   = exp(Δ_{t+1} A) ⊙ G_{t+1} + C_t ⊗ dy_t                  (dS_t)
    dC_t  = Σ_e S_t ⊙ dy_t         dB_t = Σ_e G_t ⊙ Δ_t x_t
    g_t   = Σ_n G_t ⊙ B_t                                    (d(Δ_t x_t))
    W_t   = G_t ⊙ S_{t-1} ⊙ exp(Δ_t A)
    dΔ_t  = Σ_n W_t ⊙ A + g_t x_t     dA = Σ_t W_t Δ_t
    dx_t  = g_t Δ_t + D dy_t          dD = Σ_t dy_t x_t

dB and dC are sums over ALL channels: a channel block writes its partial,
(N, T), and XLA adds the E / channels partials and transposes them back; dA
and dD are summed over time in blocks that stay in VMEM across the time axis,
over sequences by XLA. Everything is float32. The `pallas_call`s are named
`selective_scan_fwd` and `selective_scan_bwd`, so a trace names them.
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.pallas_attention import kernel_interpret
from elasticdl_tpu.ops.pallas_gmm import LANES

_F32 = jnp.float32
SUBLANES = 8        # a float32 tile's rows: the state indices come in whole tiles
STRIP = SUBLANES    # tokens a step of the kernels' loops unrolls: one tile of x
TIME_BLOCK = LANES  # tokens a grid step walks: one lane tile of B's and C's transposes
_LANE_BLOCKS = (1024, 512, 256, 128)


class Blocks(NamedTuple):
    time: int      # tokens of a block
    lanes: int     # channels of a block


def blocks(t: int, channels: int, n: int) -> Optional[Blocks]:
    """The block a grid step takes of a (T, E) plane with N state indices a
    channel, or None where the kernels do not take the shape: channels whole
    lanes, tokens whole time blocks, state indices whole sublane tiles. The
    widest channel block that divides the plane, up to 1024: a token's B and C
    columns are read once a block however wide it is (at 8192 tokens, 5120
    channels of 16 on a v5e, forward / forward + backward ms a layer: 256
    channels 3.41 / 11.40, 512 2.38 / 8.68, 1024 2.05 / 7.81 — my chip run, PR
    59), and the backward's block of states is then 8.4 MB of VMEM."""
    if channels % LANES or t % TIME_BLOCK or n % SUBLANES or t == 0:
        return None
    return Blocks(TIME_BLOCK, next(b for b in _LANE_BLOCKS if channels % b == 0))


def _column(tile, lane, t):
    """Column t of a (N, tokens) tile as (N, 1): a masked sum along the lanes."""
    return jnp.sum(jnp.where(lane == t, tile, 0.0), axis=1, keepdims=True)


def _advance(s, x, dt, a, b_column):
    """S_t from S_{t-1}: x, dt (1, channels) a token's rows, a and s (N,
    channels), b_column (N, 1)."""
    return jnp.exp(dt * a) * s + (dt * x) * b_column


def _fwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, y_ref, start_ref, state, *, bt):
    @pl.when(pl.program_id(2) == 0)
    def _first_block():
        state[...] = jnp.zeros_like(state)

    start_ref[...] = state[...]
    a, d, b_tile, c_tile = a_ref[...], d_ref[...], b_ref[...], c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b_tile.shape, 1)

    def strip(g, s):
        r = pl.multiple_of(g * STRIP, STRIP)
        xs, dts = x_ref[pl.ds(r, STRIP), :], dt_ref[pl.ds(r, STRIP), :]
        rows = []
        for k in range(STRIP):
            s = _advance(s, xs[k:k + 1], dts[k:k + 1], a, _column(b_tile, lane, r + k))
            rows.append(jnp.sum(s * _column(c_tile, lane, r + k), axis=0, keepdims=True))
        y_ref[pl.ds(r, STRIP), :] = jnp.concatenate(rows, axis=0) + d * xs
        return s

    state[...] = jax.lax.fori_loop(0, bt // STRIP, strip, state[...])


def _bwd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, d_ref, dy_ref, start_ref,
                dx_ref, ddt_ref, da_ref, db_ref, dc_ref, dd_ref, states, grad, *, bt):
    @pl.when(pl.program_id(2) == 0)      # the sequence's LAST time block
    def _first_visit():
        grad[...] = jnp.zeros_like(grad)
        da_ref[...] = jnp.zeros_like(da_ref)
        dd_ref[...] = jnp.zeros_like(dd_ref)

    a, d, b_tile, c_tile = a_ref[...], d_ref[...], b_ref[...], c_ref[...]
    lane = jax.lax.broadcasted_iota(jnp.int32, b_tile.shape, 1)
    strips = bt // STRIP

    # the block's states again, from the state it started from: states[t + 1] = S_t
    states[0] = start_ref[...]

    def forward_strip(g, s):
        r = pl.multiple_of(g * STRIP, STRIP)
        xs, dts = x_ref[pl.ds(r, STRIP), :], dt_ref[pl.ds(r, STRIP), :]
        for k in range(STRIP):
            s = _advance(s, xs[k:k + 1], dts[k:k + 1], a, _column(b_tile, lane, r + k))
            states[r + k + 1] = s
        return s

    jax.lax.fori_loop(0, strips, forward_strip, start_ref[...])

    def backward_strip(i, carry):
        g, da, db, dc = carry
        r = pl.multiple_of((strips - 1 - i) * STRIP, STRIP)
        xs, dts, dys = (ref[pl.ds(r, STRIP), :] for ref in (x_ref, dt_ref, dy_ref))
        dx_rows, ddt_rows = [None] * STRIP, [None] * STRIP
        for k in reversed(range(STRIP)):
            t = r + k
            x, dt, dy = xs[k:k + 1], dts[k:k + 1], dys[k:k + 1]
            decay = jnp.exp(dt * a)
            g = g + _column(c_tile, lane, t) * dy
            dc = dc + jnp.where(
                lane == t, jnp.sum(states[t + 1] * dy, axis=1, keepdims=True), 0.0)
            db = db + jnp.where(
                lane == t, jnp.sum(g * (dt * x), axis=1, keepdims=True), 0.0)
            through = jnp.sum(g * _column(b_tile, lane, t), axis=0, keepdims=True)
            w = g * states[t] * decay
            ddt_rows[k] = jnp.sum(w * a, axis=0, keepdims=True) + through * x
            dx_rows[k] = through * dt + d * dy
            da = da + w * dt
            g = decay * g
        dx_ref[pl.ds(r, STRIP), :] = jnp.concatenate(dx_rows, axis=0)
        ddt_ref[pl.ds(r, STRIP), :] = jnp.concatenate(ddt_rows, axis=0)
        dd_ref[...] += dys * xs
        return g, da, db, dc

    zeros = jnp.zeros_like(b_tile)
    g, da, db, dc = jax.lax.fori_loop(
        0, strips, backward_strip, (grad[...], jnp.zeros_like(a), zeros, zeros))
    grad[...] = g
    da_ref[...] += da
    db_ref[...] = db
    dc_ref[...] = dc


def _specs(x_shape, n: int, plan: Blocks, reverse: bool):
    """(grid, the (time, channel) block's spec, a transposed B or C block's,
    a per-channel operand's of `rows` rows, the block-start state's): grid
    (sequence, channel block, time block); with `reverse` the time blocks of a
    (sequence, channel block) come last first."""
    bsz, t, e = x_shape
    bt, bc = plan
    steps = t // bt
    at = (lambda j: steps - 1 - j) if reverse else (lambda j: j)
    grid = (bsz, e // bc, steps)
    block = pl.BlockSpec((None, bt, bc), lambda b, i, j: (b, at(j), i))
    transposed = pl.BlockSpec((None, n, bt), lambda b, i, j: (b, 0, at(j)))
    of_channel = lambda rows: pl.BlockSpec((rows, bc), lambda b, i, j: (0, i))
    start = pl.BlockSpec((None, None, n, bc), lambda b, i, j: (b, at(j), 0, i))
    return grid, block, transposed, of_channel, start


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _forward(x, dt, a_t, b_t, c_t, d, *, plan: Blocks, interpret):
    """x, dt (B, T, E); a_t (N, E); b_t, c_t (B, N, T); d (1, E) -> (y (B, T,
    E), the state every time block starts from (B, T / time block, N, E))."""
    bsz, t, e = x.shape
    n = a_t.shape[0]
    grid, block, transposed, of_channel, start = _specs(x.shape, n, plan, False)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, bt=plan.time),
        grid=grid,
        in_specs=[block, block, of_channel(n), transposed, transposed, of_channel(1)],
        out_specs=[block, start],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, t // plan.time, n, e), _F32)],
        scratch_shapes=[pltpu.VMEM((n, plan.lanes), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=7 * n * x.size, transcendentals=n * x.size, bytes_accessed=12 * x.size),
        interpret=interpret,
        name="selective_scan_fwd",
    )(x, dt, a_t, b_t, c_t, d)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _backward(x, dt, a_t, b_t, c_t, d, dy, starts, *, plan: Blocks, interpret):
    """-> (dx, dΔ (B, T, E); dA's partials (B, N, E); dB's and dC's (B, E /
    channel block, N, T); dD's (B, 8, E))."""
    bsz, t, e = x.shape
    n = a_t.shape[0]
    bt, bc = plan
    grid, block, transposed, of_channel, start = _specs(x.shape, n, plan, True)
    steps = t // bt
    over_time = lambda rows: pl.BlockSpec((None, rows, bc), lambda b, i, j: (b, 0, i))
    partial = pl.BlockSpec((None, None, n, bt), lambda b, i, j: (b, i, 0, steps - 1 - j))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, bt=bt),
        grid=grid,
        in_specs=[block, block, of_channel(n), transposed, transposed, of_channel(1),
                  block, start],
        out_specs=[block, block, over_time(n), partial, partial, over_time(SUBLANES)],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32), jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, n, e), _F32),
                   jax.ShapeDtypeStruct((bsz, e // bc, n, t), _F32),
                   jax.ShapeDtypeStruct((bsz, e // bc, n, t), _F32),
                   jax.ShapeDtypeStruct((bsz, SUBLANES, e), _F32)],
        scratch_shapes=[pltpu.VMEM((bt + 1, n, bc), _F32), pltpu.VMEM((n, bc), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=25 * n * x.size, transcendentals=2 * n * x.size,
            bytes_accessed=20 * x.size),
        interpret=interpret,
        name="selective_scan_bwd",
    )(x, dt, a_t, b_t, c_t, d, dy, starts)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def scan(x, dt, a_t, b_t, c_t, d, plan: Blocks):
    """The kernels' own layouts, all float32: x, dt (B, T, E); a_t (N, E);
    b_t, c_t (B, N, T); d (1, E) -> y (B, T, E). Differentiable in the six."""
    return _forward(x, dt, a_t, b_t, c_t, d, plan=plan, interpret=kernel_interpret())[0]


def _scan_fwd(x, dt, a_t, b_t, c_t, d, plan):
    y, starts = _forward(x, dt, a_t, b_t, c_t, d, plan=plan, interpret=kernel_interpret())
    return y, (x, dt, a_t, b_t, c_t, d, starts)


def _scan_bwd(plan, kept, dy):
    dx, ddt, da, db, dc, dd = _backward(
        *kept[:6], dy, kept[6], plan=plan, interpret=kernel_interpret())
    return (dx, ddt, jnp.sum(da, axis=0), jnp.sum(db, axis=1), jnp.sum(dc, axis=1),
            jnp.sum(dd, axis=(0, 1))[None])


scan.defvjp(_scan_fwd, _scan_bwd)


def selective_scan_kernels(x, dt, a, b, c, d, plan: Blocks):
    """`ssm.selective_scan` on the kernel route: same arguments, same result."""
    f32 = lambda v: v.astype(_F32)
    transposed = lambda v: jnp.swapaxes(f32(v), 1, 2)
    return scan(f32(x), f32(dt), f32(a).T, transposed(b), transposed(c),
                f32(d).reshape(1, -1), plan)
