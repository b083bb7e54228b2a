"""State-space mixer operations: the causal depthwise convolution, the two
scans and the gated group RMSNorm.

- `ssd_chunked` — Mamba-2's scan (SSD: Dao & Gu, arXiv:2405.21060): ONE decay
  a head, the state shared by a head's channels, computed chunk by chunk as
  matrix products. `model_zoo/transformer/nemotron_h.py` takes it.
- `selective_scan` — Mamba-1's (S6: Gu & Dao, arXiv:2312.00752, Algorithm 2):
  a step Δ for every CHANNEL, so a decay for every (channel, state index) and
  a state (channels, N) no two channels share; no chunk is a matrix product,
  the recurrence is walked token by token with the state on the chip.
  `model_zoo/transformer/phi4flash.py` takes it.
- `causal_conv1d` — both mixers' depthwise convolution (and the delta rule's:
  `model_zoo/transformer/kimi_linear.py`).

Plain `jax.numpy` / `lax`, but for the scans and the convolution: on a TPU
`ssd_chunked` runs as the Mosaic kernels of `ops/pallas_ssd.py`
(`scan_route`), `selective_scan` as those of `ops/pallas_selective_scan.py`
(`selective_scan_route`) and `causal_conv1d` as those of
`ops/pallas_conv1d.py` (`conv_route`); their plain bodies are the path
everywhere else and the tests' reference for the kernels.

The SSD recurrence, per head (P channels, N state columns; B and C shared by
the heads of a group):

    S_t = a_t · S_{t-1} + Δ_t · x_t ⊗ B_t,   a_t = exp(Δ_t · A),   S_0 = 0
    y_t = S_t · C_t

`ssd_chunked` computes it in chunks of L tokens. Inside a chunk the answer is
a masked matrix product: `y_t = Σ_{s≤t} (C_t·B_s) · Π_{s<r≤t} a_r · Δ_s x_s`,
the decay matrix `Λ_{ts} = exp(cum_t − cum_s)` from one cumulative sum of
`Δ·A`. Each chunk also leaves a state (`Σ_s exp(cum_L − cum_s) · Δ_s x_s ⊗
B_s`), a recurrence over the T/L chunks carries the state across, and the
state a chunk starts from reaches its outputs through C
(`exp(cum_t) · C_t · S`).

Precision: Δ, the decays, every cumulative sum and the recurrence over
chunks are float32; the matmuls inside a chunk take `compute_dtype` operands
(bfloat16 on the chip) and accumulate in float32. The (T/L, H, L, L) decay
matrices are the largest intermediates (268 MB in float32 at 8192 tokens, 64
heads). In the plain body they pass through HBM, and the body is a
`jax.checkpoint`, so the backward recomputes them from the inputs and nothing
of that shape is kept between the passes; in the kernels a chunk's matrix is
built and consumed in VMEM, forward and backward, and the backward first
sweeps the chunks once more for the state each starts from.

The selective scan, per channel e and state index n (B and C shared by every
channel, everything float32):

    S_t[e, n] = exp(Δ_t[e] · A[e, n]) · S_{t-1}[e, n] + Δ_t[e] x_t[e] · B_t[n]
    y_t[e]    = Σ_n S_t[e, n] · C_t[n] + D[e] · x_t[e],         S_0 = 0

(T, E, N) float32 is 2.68 GB at 8192 tokens, 5120 channels of 16: neither
route keeps anything of that size. The plain body is a `lax.scan` over time
inside `jax.checkpoint`ed blocks of `PLAIN_BLOCK` tokens (kept: a state a
block; recomputed: a block's states, one block at a time); the kernels keep
the state a time block of 128 tokens starts from.
"""

from __future__ import annotations

import logging
from functools import lru_cache, partial

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import pallas_conv1d, pallas_selective_scan, pallas_ssd

logger = logging.getLogger(__name__)


@lru_cache(maxsize=None)
def _log_conv_route(*said):
    """Once a process for each shape and route: a step's program traces the
    convolution a layer, a recomputation and a counter at a time."""
    logger.info(
        "causal convolution (%d tokens, %d channels, %d taps) takes the %s route "
        "(the Pallas kernels need a TPU or interpret mode: %s; channels whole "
        "lanes, whole time blocks, the taps' reach inside an 8-row tile: %s)", *said)


def conv_route(x_shape, k: int) -> str:
    """Which body a depthwise convolution of x (B, T, Ch) under k taps takes —
    "kernel" or "plain": a pure function of the shapes and of whether the
    kernels can run here (a TPU, or interpret mode in the CPU tests). Logged
    once for each answer."""
    _, t, ch = x_shape
    runnable = pallas_ssd.runnable()      # the same answer for every kernel here
    fits = pallas_conv1d.blocks(t, ch, k)
    route = "kernel" if runnable and fits else "plain"
    _log_conv_route(t, ch, k, route, runnable,
                    f"blocks of {fits.time} x {fits.lanes}" if fits else "no")
    return route


def causal_conv1d(x: jax.Array, weight: jax.Array, bias: jax.Array = None) -> jax.Array:
    """Depthwise causal convolution over time: x (B, T, Ch), weight (K, Ch),
    bias (Ch) or None -> y_t = Σ_{j<K} weight_j · x_{t-K+1+j} + bias, zeros
    before the sequence. float32. One algorithm on two routes (`conv_route`):
    the kernels of `ops/pallas_conv1d.py` take one pass over the plane a
    direction and keep x and the weight for their pull-back; the plain body
    is the path everywhere else and the tests' reference for the kernels."""
    k = weight.shape[0]
    if conv_route(x.shape, k) == "kernel":
        return pallas_conv1d.causal_conv1d_kernels(
            x, weight, bias, pallas_conv1d.blocks(x.shape[1], x.shape[2], k))
    return _causal_conv1d_plain(x, weight, bias)


def _causal_conv1d_plain(x, weight, bias=None):
    """`causal_conv1d` in `jax.numpy`: K shifted multiply-adds."""
    k, t = weight.shape[0], x.shape[1]
    x = x.astype(jnp.float32)
    padded = jnp.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    y = 0.0 if bias is None else bias.astype(jnp.float32)
    for j in range(k):
        y = y + padded[:, j:j + t] * weight[j].astype(jnp.float32)
    return y


def gated_short_conv(bgu: jax.Array, weight: jax.Array) -> jax.Array:
    """The double-gated short convolution of `lfm2`'s mixers on the three
    column blocks of ONE projection: bgu (B, T, 3C) = [B | G | u] float32,
    weight (K, C) -> G ⊙ conv_K(B ⊙ u) (B, T, C) float32, no bias, no
    activation. The convolution is `causal_conv1d` (and so on `conv_route`'s
    route); the two products are XLA's, each under a scope of its own so that
    a trace prices the three parts apart."""
    c = weight.shape[1]
    bgu = bgu.astype(jnp.float32)
    with jax.named_scope("gate_in"):
        v = bgu[..., :c] * bgu[..., 2 * c:]
    with jax.named_scope("conv"):
        y = causal_conv1d(v, weight)
    with jax.named_scope("gate_out"):
        return bgu[..., c:2 * c] * y


def gated_group_rmsnorm(y: jax.Array, z: jax.Array, weight: jax.Array,
                        groups: int, eps: float) -> jax.Array:
    """rmsnorm_grouped(y · silu(z)) · weight: the RMS is taken over each of
    `groups` equal runs of the last axis. float32."""
    g = y.astype(jnp.float32) * jax.nn.silu(z.astype(jnp.float32))
    shape = g.shape
    g = g.reshape(shape[:-1] + (groups, shape[-1] // groups))
    g = g * jax.lax.rsqrt(jnp.mean(g * g, axis=-1, keepdims=True) + eps)
    return g.reshape(shape) * weight


def scan_route(x_shape, bc_shape, chunk: int, kernel_runnable: bool,
               dtype=jnp.float32, compute_dtype=jnp.bfloat16) -> str:
    """Which body a scan of x (B, T, H, P) with b, c (B, T, G, N) takes —
    "kernel" or "plain": a pure function of the shapes and of whether the
    kernels can run here (a TPU, or interpret mode in the CPU tests)."""
    (_, t, h, p), (g, n) = x_shape, bc_shape[2:]
    fits = pallas_ssd.blocks(h, p, g, n, chunk, dtype, compute_dtype)
    route = "kernel" if kernel_runnable and fits else "plain"
    # trace-time, once per compiled program: which route this shape took
    logger.info(
        "state-space scan (%d tokens, %d heads of %d, %d groups of %d, chunks "
        "of %d) takes the %s route (the Pallas kernels need a TPU or interpret "
        "mode: %s; chunk and state whole lanes, whole heads a lane tile, a "
        "visit's blocks inside VMEM: %s)", t, h, p, g, n, chunk, route,
        kernel_runnable, f"{fits.vmem_bytes} bytes" if fits else "no")
    return route


def ssd_chunked(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                c: jax.Array, chunk: int, compute_dtype=jnp.bfloat16) -> jax.Array:
    """The state-space scan, chunked.

    x (B, T, H, P); dt (B, T, H) float32, already positive (Δ); a (H,)
    negative (A); b, c (B, T, G, N) with H a multiple of G (head i uses group
    i // (H/G)). Returns y (B, T, H, P) float32, `D·x` not included. T need
    not be a multiple of `chunk`: the tail is padded with Δ = 0, which leaves
    the state as it is. One algorithm on two routes (`scan_route`). Neither
    keeps anything of a chunk's (L, L) size for its backward: the plain body
    is a `jax.checkpoint` (its inputs), the kernels' `custom_vjp` keeps its
    operands (the inputs and the per-head vectors made from Δ and A)."""
    route = scan_route(x.shape, b.shape, chunk, pallas_ssd.runnable(), x.dtype,
                       compute_dtype)
    body = pallas_ssd.ssd_scan if route == "kernel" else _ssd_plain
    return body(x, dt, a, b, c, chunk, compute_dtype)


@partial(jax.checkpoint, static_argnums=(5, 6))
def _ssd_plain(x, dt, a, b, c, chunk, compute_dtype):
    """`ssd_chunked` in `jax.numpy`: every intermediate an array."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2], b.shape[3]
    r = h // g                                   # heads a group
    pad = -t % chunk
    if pad:
        widen = lambda v: jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        x, dt, b, c = widen(x), widen(dt), widen(b), widen(c)
    nc, l, dt_c = (t + pad) // chunk, chunk, compute_dtype
    dt = dt.astype(jnp.float32)
    xd = (x.astype(jnp.float32) * dt[..., None]).reshape(bsz, nc, l, g, r, p)
    bc = b.astype(dt_c).reshape(bsz, nc, l, g, n)
    cc = c.astype(dt_c).reshape(bsz, nc, l, g, n)
    # cum_t = Σ_{r≤t} Δ_r A inside the chunk: (B, nc, L, H), ≤ 0 and falling
    cum = jnp.cumsum((dt * a.astype(jnp.float32)).reshape(bsz, nc, l, h), axis=2)

    # inside a chunk: (C Bᵀ ⊙ Λ) · Δx, Λ_ts = exp(cum_t − cum_s) for s ≤ t
    cb = jnp.einsum("bclgn,bcsgn->bcgls", cc, bc,
                    preferred_element_type=jnp.float32)
    cum_h = cum.transpose(0, 1, 3, 2).reshape(bsz, nc, g, r, l)
    diff = cum_h[..., :, None] - cum_h[..., None, :]         # (B, nc, G, R, L, L)
    lower = jnp.tril(jnp.ones((l, l), bool))
    decay = jnp.exp(jnp.where(lower, diff, -jnp.inf))
    m = (cb[:, :, :, None] * decay).astype(dt_c)
    y = jnp.einsum("bcgrls,bcsgrp->bclgrp", m, xd.astype(dt_c),
                   preferred_element_type=jnp.float32)

    # what each chunk adds to the state: Σ_s exp(cum_L − cum_s) Δ_s x_s ⊗ B_s
    to_end = jnp.exp(cum[:, :, -1:, :] - cum).reshape(bsz, nc, l, g, r, 1)
    added = jnp.einsum("bclgn,bclgrp->bcgrpn", bc, (xd * to_end).astype(dt_c),
                       preferred_element_type=jnp.float32)

    # the recurrence over chunks, float32: S_c = exp(cum_L of chunk c) S_{c-1} + added_c
    through = jnp.exp(cum[:, :, -1, :]).reshape(bsz, nc, g, r, 1, 1)

    def carry_state(state, chunk_terms):
        keep, add = chunk_terms
        return keep * state + add, state         # emits the state a chunk STARTS from

    _, starts = jax.lax.scan(
        carry_state, jnp.zeros((bsz, g, r, p, n), jnp.float32),
        (jnp.moveaxis(through, 1, 0), jnp.moveaxis(added, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)                      # (B, nc, G, R, P, N)

    # the starting state reaches token t through exp(cum_t) · C_t
    from_start = jnp.einsum("bclgn,bcgrpn->bclgrp", cc, starts.astype(dt_c),
                            preferred_element_type=jnp.float32)
    y = y + from_start * jnp.exp(cum).reshape(bsz, nc, l, g, r, 1)
    return y.reshape(bsz, nc * l, h, p)[:, :t]


# ------------------------------------------------------------------ #
# The selective scan (S6)

PLAIN_BLOCK = 64     # tokens of a checkpointed block of the plain body: ≈ √T at 4k–8k


@lru_cache(maxsize=None)
def _log_selective_scan_route(*said):
    """Once a process for each shape and route, as `_log_conv_route`."""
    logger.info(
        "selective scan (%d tokens, %d channels of %d state indices) takes the %s "
        "route (the Pallas kernels need a TPU or interpret mode: %s; channels whole "
        "lanes, whole time blocks, state indices whole sublane tiles: %s)", *said)


def selective_scan_route(x_shape, n: int) -> str:
    """Which body a selective scan of x (B, T, E) with N state indices a
    channel takes — "kernel" or "plain": a pure function of the shapes and of
    whether the kernels can run here (a TPU, or interpret mode in the CPU
    tests). Logged once for each answer."""
    _, t, e = x_shape
    runnable = pallas_ssd.runnable()      # the same answer for every kernel here
    fits = pallas_selective_scan.blocks(t, e, n)
    route = "kernel" if runnable and fits else "plain"
    _log_selective_scan_route(t, e, n, route, runnable,
                              f"blocks of {fits.time} x {fits.lanes}" if fits else "no")
    return route


def selective_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array,
                   c: jax.Array, d: jax.Array) -> jax.Array:
    """The selective scan: x (B, T, E); dt (B, T, E), already positive (Δ); a
    (E, N) negative (A); b, c (B, T, N); d (E,). Returns y (B, T, E) float32,
    `D·x` INCLUDED. One algorithm on two routes (`selective_scan_route`),
    float32 on both; neither keeps anything of (T, E, N) size for its
    backward."""
    if selective_scan_route(x.shape, a.shape[1]) == "kernel":
        return pallas_selective_scan.selective_scan_kernels(
            x, dt, a, b, c, d, pallas_selective_scan.blocks(x.shape[1], x.shape[2], a.shape[1]))
    return _selective_scan_plain(x, dt, a, b, c, d)


def _selective_scan_plain(x, dt, a, b, c, d, block: int = PLAIN_BLOCK):
    """`selective_scan` in `jax.numpy`: a `lax.scan` over time inside
    checkpointed blocks of `block` tokens. A ragged tail is padded with Δ = 0,
    which leaves the state as it is."""
    bsz, t, e = x.shape
    x, dt, a, b, c = (v.astype(jnp.float32) for v in (x, dt, a, b, c))
    pad = -t % block
    # time first, in blocks: (T/block, block, B, ·)
    blocked = lambda v: jnp.moveaxis(
        jnp.pad(v, ((0, 0), (0, pad), (0, 0))), 1, 0).reshape(-1, block, bsz, v.shape[-1])

    def token(state, operands):
        x_t, dt_t, b_t, c_t = operands                   # (B, E), (B, E), (B, N), (B, N)
        state = (jnp.exp(dt_t[..., None] * a) * state
                 + (dt_t * x_t)[..., None] * b_t[:, None, :])
        return state, jnp.sum(state * c_t[:, None, :], axis=-1)

    @jax.checkpoint
    def tokens_of_a_block(state, operands):
        return jax.lax.scan(token, state, operands)

    _, y = jax.lax.scan(tokens_of_a_block, jnp.zeros((bsz,) + a.shape, jnp.float32),
                        tuple(blocked(v) for v in (x, dt, b, c)))
    y = jnp.moveaxis(y.reshape(-1, bsz, e), 0, 1)[:, :t]
    return y + d.astype(jnp.float32) * x
