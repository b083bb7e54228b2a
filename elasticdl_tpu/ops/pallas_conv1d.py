"""The depthwise causal convolution (`ops/ssm.py::causal_conv1d`) as Pallas
(Mosaic) kernels, forward and pull-back, joined by a `jax.custom_vjp` (PR 57).

The same taps in the same order and float32 throughout; what changes is that
a direction is ONE pass over the (B, T, Ch) plane in the layout the projection
leaves it — time on the sublanes, channels on the lanes. XLA turns the plain
body's K shifted multiply-adds into a `convolution` in a layout of its own
with a relayout copy of the plane on either side (PERF.md §6, PR 57).

A grid step takes a (time block, channel block) of a sequence. The K − 1 rows
a block needs from its neighbour come through a second block spec on the same
array: the 8-row tile that ends where the block starts (forward, zeroed before
the sequence) or that starts where it ends (pull-back, zeroed past the end).
Inside, the block is walked in strips of `_STRIP` rows: a strip and the tile
beside it are rolled along the sublanes once a tap and cut back to the strip,
so a shifted row never leaves the registers.

    forward    u_t  = bias + Σ_j w_j · x_{t-K+1+j}
    pull-back  dx_t = Σ_j w_j · du_{t+K-1-j}
               dw_j = Σ_t x_t · du_{t+K-1-j},   db = Σ_t du_t

`dw` and `db` are summed over time in the kernel as 8-row partials, one
(K + 1, 8, channel block) float32 block a (sequence, channel block) that
stays in VMEM across the time axis; the last 8 → 1 and the sum over sequences
are XLA's, on (K + 1) · 8 · Ch numbers. The residuals are x and the weight:
x is the projection's output, which a checkpointed layer's recomputation
makes anyway, and u is not kept. The `pallas_call`s are named
`causal_conv1d_fwd` and `causal_conv1d_bwd`, so a trace names them.
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
SUBLANES = 8     # a float32 tile's rows: what a block borrows from its neighbour
_STRIP = 32      # rows a step of the kernels' inner loop holds in registers
_TIME_BLOCKS = (512, 256, 128, 64, 32)
_LANE_BLOCKS = (512, 256, 128)


class Blocks(NamedTuple):
    time: int      # rows of a block
    lanes: int     # channels of a block


def blocks(t: int, channels: int, k: int) -> Optional[Blocks]:
    """The block a grid step takes of a (T, Ch) plane under K taps, or None
    where the kernels do not take the shape: channels whole lanes, T whole
    time blocks (the smallest is `_STRIP` rows) and the K − 1 borrowed rows
    inside one 8-row tile. The largest blocks that divide the plane, up to
    (512, 512) — 1 MB: larger ones ran no faster on a v5e (PERF.md §6, PR 57)
    and the pull-back holds six of them."""
    if channels % LANES or not 1 <= k <= SUBLANES + 1:
        return None
    time = next((b for b in _TIME_BLOCKS if t % b == 0), None)
    lanes = next(b for b in _LANE_BLOCKS if channels % b == 0)
    return Blocks(time, lanes) if time else None


def _strips(bt: int, body):
    """`body(r, first)` for every strip start r of a block of bt rows: the
    first strip apart (its neighbour's tile is another ref), the others in a
    loop."""
    body(0, True)
    if bt > _STRIP:
        def step(i, carry):
            body(pl.multiple_of(i * _STRIP, _STRIP), False)
            return carry
        jax.lax.fori_loop(1, bt // _STRIP, step, 0)


def _fwd_kernel(x_ref, before_ref, w_ref, b_ref, u_ref, *, k, bt):
    first_block = pl.program_id(2) == 0
    w = w_ref[...]
    bias = b_ref[...]

    def strip(r, first):
        if first:       # the tile before the block: zeros before the sequence
            before = jnp.where(first_block, 0.0, before_ref[...])
            rows = jnp.concatenate([before, x_ref[pl.ds(0, _STRIP), :]], axis=0)
        else:
            rows = x_ref[pl.ds(pl.multiple_of(r - SUBLANES, SUBLANES), _STRIP + SUBLANES), :]
        u = jnp.broadcast_to(bias, (_STRIP, rows.shape[1]))
        for j in range(k):                      # the plain body's order
            back = k - 1 - j                    # x_{t - back}
            shifted = rows if back == 0 else pltpu.roll(rows, back, 0)
            u = u + shifted[SUBLANES:] * w[j:j + 1]
        u_ref[pl.ds(r, _STRIP), :] = u

    _strips(bt, strip)


def _bwd_kernel(du_ref, after_ref, x_ref, w_ref, dx_ref, dwb_ref, *, k, bt):
    c = pl.program_id(2)
    last_block = c == pl.num_programs(2) - 1
    w = w_ref[...]

    @pl.when(c == 0)
    def _first_visit():
        dwb_ref[...] = jnp.zeros_like(dwb_ref)

    def strip(r, first):
        # walked from the block's end: the first strip is its LAST rows, whose
        # neighbour is the tile after the block (zeros past the sequence)
        at = bt - _STRIP - r if first else pl.multiple_of(bt - _STRIP - r, _STRIP)
        if first:
            after = jnp.where(last_block, 0.0, after_ref[...])
            rows = jnp.concatenate([du_ref[pl.ds(at, _STRIP), :], after], axis=0)
        else:
            rows = du_ref[pl.ds(at, _STRIP + SUBLANES), :]
        x = x_ref[pl.ds(at, _STRIP), :]
        dx = jnp.zeros_like(x)
        n = _STRIP + SUBLANES
        for j in range(k):
            ahead = k - 1 - j                   # du_{t + ahead}
            shifted = (rows if ahead == 0 else pltpu.roll(rows, n - ahead, 0))[:_STRIP]
            dx = dx + shifted * w[j:j + 1]
            dwb_ref[j] += jnp.sum((shifted * x).reshape(-1, SUBLANES, x.shape[1]), axis=0)
        dx_ref[pl.ds(at, _STRIP), :] = dx
        dwb_ref[k] += jnp.sum(rows[:_STRIP].reshape(-1, SUBLANES, x.shape[1]), axis=0)

    _strips(bt, strip)


def _specs(x, plan: Blocks):
    """(grid, the block's spec, the tile before it, the tile after it, a
    per-channel operand's): grid (sequence, channel block, time block), the
    time blocks of a (sequence, channel block) in order."""
    bsz, t, ch = x.shape
    bt, bc = plan
    tiles, per = t // SUBLANES, bt // SUBLANES
    grid = (bsz, ch // bc, t // bt)
    block = pl.BlockSpec((None, bt, bc), lambda b, i, c: (b, c, i))
    before = pl.BlockSpec((None, SUBLANES, bc),
                          lambda b, i, c: (b, jnp.maximum(c * per - 1, 0), i))
    after = pl.BlockSpec((None, SUBLANES, bc),
                         lambda b, i, c: (b, jnp.minimum((c + 1) * per, tiles - 1), i))
    of_channel = lambda rows: pl.BlockSpec((rows, bc), lambda b, i, c: (0, i))
    return grid, block, before, after, of_channel


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _forward(x, weight, bias, *, plan: Blocks, interpret):
    k = weight.shape[0]
    grid, block, before, _, of_channel = _specs(x, plan)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, k=k, bt=plan.time),
        grid=grid,
        in_specs=[block, before, of_channel(k), of_channel(1)],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(x.shape, _F32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
        cost_estimate=pl.CostEstimate(
            flops=2 * k * x.size, transcendentals=0, bytes_accessed=8 * x.size),
        interpret=interpret,
        name="causal_conv1d_fwd",
    )(x, x, weight, bias)


@functools.partial(jax.jit, static_argnames=("plan", "interpret"))
def _backward(du, x, weight, *, plan: Blocks, interpret):
    k = weight.shape[0]
    bsz, _, ch = x.shape
    grid, block, _, after, of_channel = _specs(x, plan)
    partials = pl.BlockSpec((None, k + 1, SUBLANES, plan.lanes), lambda b, i, c: (b, 0, 0, i))
    return pl.pallas_call(
        functools.partial(_bwd_kernel, k=k, bt=plan.time),
        grid=grid,
        in_specs=[block, after, block, of_channel(k)],
        out_specs=[block, partials],
        out_shape=[jax.ShapeDtypeStruct(x.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, k + 1, SUBLANES, ch), _F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        cost_estimate=pl.CostEstimate(
            flops=(4 * k + 1) * x.size, transcendentals=0, bytes_accessed=12 * x.size),
        interpret=interpret,
        name="causal_conv1d_bwd",
    )(du, du, x, weight)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def conv(x, weight, bias, plan: Blocks):
    """x (B, T, Ch), weight (K, Ch), bias (1, Ch), all float32 -> u (B, T, Ch)
    float32. Differentiable in the three; the residuals are x and the weight."""
    return _forward(x, weight, bias, plan=plan, interpret=kernel_interpret())


def _conv_fwd(x, weight, bias, plan):
    return _forward(x, weight, bias, plan=plan, interpret=kernel_interpret()), (x, weight)


def _conv_bwd(plan, kept, du):
    x, weight = kept
    k = weight.shape[0]
    dx, partials = _backward(du, x, weight, plan=plan, interpret=kernel_interpret())
    sums = jnp.sum(partials, axis=(0, 2))
    return dx, sums[:k], sums[k:]


conv.defvjp(_conv_fwd, _conv_bwd)


def causal_conv1d_kernels(x, weight, bias, plan: Blocks):
    """`ssm.causal_conv1d` on the kernel route: same arguments, same result."""
    ch = x.shape[-1]
    bias = jnp.zeros((1, ch), _F32) if bias is None else bias.astype(_F32).reshape(1, ch)
    return conv(x.astype(_F32), weight.astype(_F32), bias, plan)
