"""The gated delta rule's chunk algebra (`ops/delta_rule.py::_block`) as Pallas
(Mosaic) kernels, forward and backward, joined by a `jax.custom_vjp` (PR 55).

Same mathematics and the same rounding points as the plain body; what changes
is where the intermediates live. A grid step is one VISIT: (sequence, head,
block of `chunks_per_block` chunks), the blocks of a head in order, the head's
state — TRANSPOSED, Sᵀ (d_v, d_k) float32, so that a chunk's decay `exp Γ_L`
multiplies it along the lanes as the row it is read as — in a VMEM scratch
that lives across the block axis. Operands are read where they lie: q, k, v, Γ
as (B, T, H·d), a visit's block (n·L, d) at lane offset h·d; nothing changes
layout. A visit first makes what does not read the state, chunk by chunk (M
and P — off-diagonal sub-blocks through the row sub-block's start on the MXU,
the (SUB, SUB, d) diagonal differences in float32 masked BEFORE the
exponential, a column of every sub-block at a time —, the triangular inverse by
the doubling product at the highest precision, A = X·Diag(β), W, U), then
sweeps the state over its chunks (`new = U − W·S`, `O = (Q ⊙ exp Γ)·S + P·new`,
`delta_rule.next_state`), writes O and — once a visit — the state the block
started from. Nothing of (L, L), (SUB, SUB, d) or (d, d) size reaches HBM but
those kept states.

The backward is the same grid with the blocks in reverse and dSᵀ carried in
VMEM: a visit makes the forward's terms of its block again from the kept
block-start state (they stay in VMEM: the chunk starts, X, M, P, W, U, new),
then walks the block's chunks backwards and pulls back through them
(`N̄ = Xᵀ X̄ Xᵀ`), and writes dq, dk, dv, dΓ and dβ.

What stays in XLA (`delta_rule_kernels`): padding T to whole blocks, Γ =
`delta_rule.cumulative_log_decay(g)` inside a chunk and its pull-back — since
PR 65 a triangular product at the highest precision, exact for a float32
operand against ones (at the DEFAULT precision it would round the operand to
bfloat16); it is XLA's and not the kernels' first lines because the
benchmark's rehearsal patches that function by name to round Γ, and a sum
made in here would pass the patch by — and β's layout (a chunk's strengths a
row: (B, H, blocks, n, L)); JAX differentiates those, the `custom_vjp` covers
the kernels alone.

Precision, as the plain body's: Γ, every exponential (every exponent a
difference ≤ 0), β, the diagonal sub-blocks, the inverse (float32 in and out,
its products at the highest precision), u, the state and what is added to it
float32; the other matmuls take `compute_dtype` operands rounded AFTER the
decay has been applied in float32 — in the backward the cotangents that take
their places — and accumulate in float32. The `pallas_call`s are named
`delta_rule_fwd` and `delta_rule_bwd`, so a trace names them.

THE SCALAR FORM (one decay a head, `delta_rule.py`'s second form; the second
half of this file) has a kernel pair of its own, `delta_rule_scalar_fwd` and
`delta_rule_scalar_bwd`: the same visit, with Γ a ROW of L numbers a chunk
(laid out as β is), M and P one (L, L) product each under ONE (L, L)
exponential, and every other decay a scale of rows or columns. The grid is
(sequence, KEY head, block, the r value heads that read the key head), the
value heads innermost: q's and k's blocks do not change between a key head's
visits, so the pipeline reads them once, and in the backward their dq and dk
blocks stay in VMEM and are ADDED to over the r visits — no H_v-head copy of q,
k, dq or dk exists. The r states live side by side in the VMEM scratch.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import delta_rule
from elasticdl_tpu.ops.pallas_attention import (
    _interpret_active, _vmem_bytes, kernel_interpret)
from elasticdl_tpu.ops.pallas_gmm import LANES

_F32 = jnp.float32
_HIGHEST = jax.lax.Precision.HIGHEST


def runnable() -> bool:
    """The kernels need a real TPU or interpret mode (CPU tests)."""
    return jax.default_backend() == "tpu" or _interpret_active()


class Blocks(NamedTuple):
    vmem_bytes: int  # what the backward's visit holds (the forward's is less)
    vmem_limit: int  # what Mosaic may use


def blocks(d_k: int, d_v: int, chunk: int, chunks_per_block: int) -> Optional[Blocks]:
    """A visit's blocks for heads of `d_k` key and `d_v` value channels at
    blocks of `chunks_per_block` chunks of `chunk` tokens, or None where the
    kernels do not take the shape: d_k = d_v whole lanes, a chunk whole
    sub-blocks of `SUB` (a power of two), and the backward's visit — two
    buffers of every block, its scratch and the float32 values it holds —
    inside half the chip's VMEM."""
    sub = delta_rule.SUB
    if d_k != d_v or d_k % LANES or chunk % sub or sub & (sub - 1):
        return None
    n = chunks_per_block
    plane, state, square = 4 * n * chunk * d_k, 4 * d_k * d_v, 4 * chunk * chunk
    # q, k, v, Γ, do read, four gradients written; three states; β and dβ
    moved = 9 * plane + 3 * state + 2 * 4 * 8 * max(chunk, LANES)
    # dSᵀ, the chunk starts, M, P and X of every chunk, W and new
    held = (1 + n) * state + 3 * n * square + 2 * plane
    values = 12 * 4 * chunk * d_k + 12 * square + 2 * state
    need = 2 * moved + held + values
    vmem = _vmem_bytes()
    return Blocks(need, vmem * 3 // 4) if need <= vmem // 2 else None


def _nt(a, b):
    """a (M, K) · b (N, K)ᵀ, float32 accumulation."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=_F32)


def _tn(a, b):
    """a (K, M)ᵀ · b (K, N), float32 accumulation."""
    return jax.lax.dot_general(a, b, (((0,), (0,)), ((), ())), preferred_element_type=_F32)


def _nn(a, b):
    return jnp.dot(a, b, preferred_element_type=_F32)


def _exact(a, b, dims=((1,), (0,))):
    """A float32 product at the highest precision: the inverse's."""
    return jax.lax.dot_general(a, b, (dims, ((), ())), precision=_HIGHEST,
                               preferred_element_type=_F32)


class _Masks:
    """The (L, L) index masks of a chunk of `l` tokens in sub-blocks of `sub`."""

    def __init__(self, l, sub):
        self.l, self.sub, self.s = l, sub, l // sub
        row = jax.lax.broadcasted_iota(jnp.int32, (l, l), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (l, l), 1)
        self.eye = row == col
        self.strict = row > col
        self.row_in = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0) & (sub - 1)
        # column j of every row's own diagonal sub-block
        at = col - (row - (row & (sub - 1)))
        self.at = [at == j for j in range(sub)]
        self.last = jax.lax.broadcasted_iota(jnp.int32, (l, 1), 0) == l - 1

    def of_sub(self, a, j):
        """Row j of every sub-block of a (L, d), over its sub-block's rows."""
        sub = self.sub
        return jnp.concatenate(
            [jnp.broadcast_to(a[i * sub + j:i * sub + j + 1, :], (sub, a.shape[1]))
             for i in range(self.s)], axis=0)

    def column(self, row):
        """A row vector (1, L) as a column (L, 1)."""
        return jnp.sum(jnp.where(self.eye, row, 0.0), axis=1, keepdims=True)

    def row(self, column):
        """A column vector (L, 1) as a row (1, L)."""
        return jnp.sum(jnp.where(self.eye, column, 0.0), axis=0, keepdims=True)


class _OffDiagonal(NamedTuple):
    """Row sub-block a's operands against the columns before it."""
    lo: int
    row: jax.Array      # exp(Γ_r − Γ_a) (SUB, d)
    col: jax.Array      # exp(Γ_a − Γ_i) for i before a, 0 from a on (L, d)
    both: jax.Array     # [K_a ⊙ row; Q_a ⊙ row] (2·SUB, d), compute dtype
    k_col: jax.Array    # K ⊙ col (L, d), compute dtype


def _off_operands(q, k, g, mk: _Masks, dt):
    """The operands of every row sub-block but the first."""
    l, d = k.shape
    sub = mk.sub
    for a in range(1, mk.s):
        lo = a * sub
        start = g[lo - 1:lo, :]
        row = jnp.exp(g[lo:lo + sub] - start)
        col = jnp.concatenate([jnp.exp(start - g[:lo]), jnp.zeros((l - lo, d), _F32)], axis=0)
        both = jnp.concatenate([k[lo:lo + sub] * row, q[lo:lo + sub] * row], axis=0).astype(dt)
        yield _OffDiagonal(lo, row, col, both, (k * col).astype(dt))


def _off_diagonal(q, k, g, mk: _Masks, dt):
    """M's and P's sub-blocks under the diagonal ones, (L, L) float32 each."""
    sub = mk.sub
    m_rows, p_rows = [jnp.zeros((sub, mk.l), _F32)], [jnp.zeros((sub, mk.l), _F32)]
    for term in _off_operands(q, k, g, mk, dt):
        mp = _nt(term.both, term.k_col)                                 # (2·SUB, L)
        m_rows.append(mp[:sub])
        p_rows.append(mp[sub:])
    return jnp.concatenate(m_rows, axis=0), jnp.concatenate(p_rows, axis=0)


def _diagonal_decays(k, g, mk: _Masks):
    """For column j of every diagonal sub-block: (j, E = exp(Γ_r − Γ_j) over
    the sub-block's rows r ≥ j and 0 above — masked before the exponential —,
    K_j ⊙ E), (L, d) float32 each."""
    for j in range(mk.sub):
        e = jnp.exp(jnp.where(mk.row_in >= j, g - mk.of_sub(g, j), -jnp.inf))
        yield j, e, mk.of_sub(k, j) * e


def _diagonal(q, k, g, mk: _Masks):
    """M's (strictly lower) and P's (lower) diagonal sub-blocks on the (L, L)
    matrix, float32."""
    m = jnp.zeros((mk.l, mk.l), _F32)
    p = jnp.zeros((mk.l, mk.l), _F32)
    for j, _, decayed in _diagonal_decays(k, g, mk):
        m = jnp.where(mk.at[j], jnp.sum(k * decayed, axis=1, keepdims=True), m)
        p = jnp.where(mk.at[j], jnp.sum(q * decayed, axis=1, keepdims=True), p)
    return jnp.where(mk.strict, m, 0.0), p


def _inverses(ns, mk: _Masks):
    """`delta_rule.unit_lower_inverse` on each of a visit's (L, L) matrices:
    the same products in the same order, the chunks' taken level by level
    side by side — each chunk's are a dependent chain, a visit's chunks are
    not — and a level's two (the power's square, x times the power) as ONE
    product of the two stacked over the power they share."""
    l = mk.l
    eye = jnp.where(mk.eye, 1.0, 0.0)
    levels = max(math.ceil(math.log2(l)) - 1, 0)
    xs = [eye + n for n in ns]
    powers = [_exact(n, n) for n in ns] if levels else ns
    for level in range(levels):
        if level + 1 < levels:
            both = [_exact(jnp.concatenate([power, x], axis=0), power)          # (2L, L)
                    for power, x in zip(powers, xs)]
            powers = [b[:l] for b in both]
            xs = [x + b[l:] for x, b in zip(xs, both)]
        else:
            xs = [x + _exact(x, power) for power, x in zip(powers, xs)]
    return xs


class _Chunk(NamedTuple):
    """What a chunk's algebra makes before it meets the state."""
    m: jax.Array        # (L, L)
    p: jax.Array        # (L, L)
    x: jax.Array        # (I + Diag(β) M)⁻¹ (L, L)
    w: jax.Array        # A (K ⊙ exp Γ) (L, d_k)
    u: jax.Array        # A V (L, d_v)


def _chunks(operands, mk: _Masks, dt):
    """The `_Chunk` of each of a visit's chunks, from its (q, k, v, Γ (L, d)
    each, β (1, L))."""
    made = []
    for q, k, _, g, _ in operands:
        m_off, p_off = _off_diagonal(q, k, g, mk, dt)
        m_in, p_in = _diagonal(q, k, g, mk)
        made.append((m_off + m_in, p_off + p_in))
    xs = _inverses([-mk.column(beta_row) * m
                    for (m, _), (*_, beta_row) in zip(made, operands)], mk)
    chunks = []
    for (m, p), x, (_, k, v, g, beta_row) in zip(made, xs, operands):
        solved = (x * beta_row).astype(dt)
        chunks.append(_Chunk(m, p, x, _nn(solved, (k * jnp.exp(g)).astype(dt)),
                             _nn(solved, v.astype(dt))))
    return chunks


def _to_end(g):
    """(exp(Γ_L − Γ) (L, d), exp Γ_L (1, d)): a token's and the state's decay
    to the chunk's end."""
    last = g[-1:, :]
    return jnp.exp(last - g), jnp.exp(last)


def _written(state, w, u, dt):
    """`new = U − W·S`: what a chunk writes, from the state Sᵀ (d_v, d_k) it
    starts from, (L, d_v)."""
    return u - _nt(w.astype(dt), state.astype(dt))


def _after(state, new, k, g, dt):
    """The state Sᵀ a chunk leaves."""
    f, through = _to_end(g)
    return delta_rule.next_state(through, state, _tn(new.astype(dt), (k * f).astype(dt)))


def _rows(c, l):
    return pl.ds(c * l, l)


def _read(ref, c, l):
    """Chunk c's (L, d) of a visit's block."""
    return ref[_rows(c, l), :]


def _operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l):
    """Chunk c's (q, k, v, Γ (L, d) each, β (1, L))."""
    return (_read(q_ref, c, l), _read(k_ref, c, l), _read(v_ref, c, l), _read(g_ref, c, l),
            beta_ref[c:c + 1, :])


def _fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, init_ref, o_ref, starts_ref, last_ref,
                state_ref, *, n, l, sub, dt):
    @pl.when(pl.program_id(2) == 0)
    def _first_visit():
        state_ref[...] = init_ref[...]

    mk = _Masks(l, sub)
    state = state_ref[...]
    starts_ref[...] = state
    chunks = _chunks([_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l)
                      for c in range(n)], mk, dt)
    for c, terms in enumerate(chunks):
        q, k, g = _read(q_ref, c, l), _read(k_ref, c, l), _read(g_ref, c, l)
        new = _written(state, terms.w, terms.u, dt)
        o_ref[_rows(c, l), :] = (_nt((q * jnp.exp(g)).astype(dt), state.astype(dt))
                                 + _nn(terms.p.astype(dt), new.astype(dt)))
        state = _after(state, new, k, g, dt)
    state_ref[...] = state
    last_ref[...] = state


def _pull(q, k, v, g, beta_row, m, p, x, w, new, state, d_o, d_state, mk: _Masks, dt):
    """The pull-back through one chunk: from the cotangents d_o (L, d_v) of
    its output and d_state (d_v, d_k) of the state it leaves, (dq, dk, dv, dΓ
    (L, d) each, dβ (1, L), the cotangent of the state it started from)."""
    sub = mk.sub
    sc, newc, doc, dsc = state.astype(dt), new.astype(dt), d_o.astype(dt), d_state.astype(dt)
    e = jnp.exp(g)
    kg, qg = k * e, q * e
    f, through = _to_end(g)
    to_end = k * f
    solved = (x * beta_row).astype(dt)
    # the state's sweep: new = U − W·S, O = (Q ⊙ exp Γ)·S + P·new, S' = ...
    d_new = _tn(p.astype(dt), doc) + _nt(to_end.astype(dt), dsc)        # (L, d_v)
    d_newc = d_new.astype(dt)
    d_to_end = _nn(newc, dsc)                                           # (L, d_k)
    d_p = _nt(doc, newc)                                                # (L, L)
    d_qg = _nn(doc, sc)
    d_wc = (-_nn(d_newc, sc)).astype(dt)
    d_start = through * d_state + _tn(doc, qg.astype(dt)) - _tn(d_newc, w.astype(dt))
    d_through = jnp.sum(d_state * state, axis=0, keepdims=True)         # (1, d_k)
    # W = A (K ⊙ exp Γ), U = A V, A = X Diag(β), X = (I − N)⁻¹, N = −Diag(β) M
    d_a = _nt(d_wc, kg.astype(dt)) + _nt(d_newc, v.astype(dt))          # (L, L)
    d_kg = _tn(solved, d_wc)
    d_v = _tn(solved, d_newc)
    d_n = _exact(_exact(x, d_a * beta_row, ((0,), (0,))), x, ((1,), (1,)))      # Xᵀ X̄ Xᵀ
    d_beta = (jnp.sum(d_a * x, axis=0, keepdims=True)
              - mk.row(jnp.sum(d_n * m, axis=1, keepdims=True)))
    d_m = jnp.where(mk.strict, -mk.column(beta_row) * d_n, 0.0)
    # M and P. A row's share with its own k or q factored out (r_k, r_q) and a
    # column's (c_k) are Γ's too: dΓ_r = k_r r_k + q_r r_q, dΓ_i = −k_i c_k
    r_k, r_q, c_k = [jnp.zeros((sub, k.shape[1]), _F32)], [jnp.zeros((sub, k.shape[1]), _F32)], 0.0
    for term in _off_operands(q, k, g, mk, dt):
        rows = slice(term.lo, term.lo + sub)
        d_mp = jnp.concatenate([d_m[rows], d_p[rows]], axis=0).astype(dt)       # (2·SUB, L)
        d_both = _nn(d_mp, term.k_col)
        r_k.append(term.row * d_both[:sub])
        r_q.append(term.row * d_both[sub:])
        c_k = c_k + term.col * _tn(d_mp, term.both)
    r_k, r_q = jnp.concatenate(r_k, axis=0), jnp.concatenate(r_q, axis=0)
    for j, e_j, decayed in _diagonal_decays(k, g, mk):
        dm_j = jnp.sum(jnp.where(mk.at[j], d_m, 0.0), axis=1, keepdims=True)    # (L, 1)
        dp_j = jnp.sum(jnp.where(mk.at[j], d_p, 0.0), axis=1, keepdims=True)
        r_k = r_k + dm_j * decayed
        r_q = r_q + dp_j * decayed
        share = (dm_j * k + dp_j * q) * e_j
        c_k = c_k + jnp.where(mk.row_in == j, jnp.concatenate(
            [jnp.broadcast_to(jnp.sum(share[i * sub:(i + 1) * sub], axis=0, keepdims=True),
                              (sub, k.shape[1])) for i in range(mk.s)], axis=0), 0.0)
    d_last = jnp.sum(d_to_end * to_end, axis=0, keepdims=True) + d_through * through
    d_g = (d_kg * kg + d_qg * qg - d_to_end * to_end + k * (r_k - c_k) + q * r_q
           + jnp.where(mk.last, d_last, 0.0))
    return d_qg * e + r_q, d_kg * e + d_to_end * f + r_k + c_k, d_v, d_g, d_beta, d_start


def _bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref, dlast_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dinit_ref,
                dstate_ref, states_ref, m_ref, p_ref, x_ref, w_ref, new_ref, *, n, l, sub, dt):
    @pl.when(pl.program_id(2) == 0)
    def _first_visit():
        dstate_ref[...] = dlast_ref[...]

    mk = _Masks(l, sub)
    # the block's forward again, its terms left in VMEM
    chunks = _chunks([_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l)
                      for c in range(n)], mk, dt)
    for c, terms in enumerate(chunks):
        m_ref[c], p_ref[c], x_ref[c], w_ref[c] = terms.m, terms.p, terms.x, terms.w
        new_ref[c] = terms.u                    # U until the sweep makes new of it
    state = starts_ref[...]
    for c in range(n):
        states_ref[c] = state
        new = _written(state, w_ref[c], new_ref[c], dt)
        new_ref[c] = new
        if c + 1 < n:
            state = _after(state, new, _read(k_ref, c, l), _read(g_ref, c, l), dt)
    # and the pull-back, the chunks from the last
    d_state = dstate_ref[...]
    for c in reversed(range(n)):
        d_q, d_k, d_v, d_g, d_beta, d_state = _pull(
            *_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l), m_ref[c], p_ref[c], x_ref[c], w_ref[c], new_ref[c],
            states_ref[c], _read(do_ref, c, l), d_state, mk, dt)
        rows = _rows(c, l)
        dq_ref[rows, :], dk_ref[rows, :], dv_ref[rows, :], dg_ref[rows, :] = d_q, d_k, d_v, d_g
        dbeta_ref[c:c + 1, :] = d_beta
    dstate_ref[...] = d_state
    dinit_ref[...] = d_state


class _Call(NamedTuple):
    """What the two `pallas_call`s take from their operands' shapes."""
    grid: tuple
    chunks: int
    plan: Blocks
    plane: pl.BlockSpec
    beta: pl.BlockSpec
    of_block: pl.BlockSpec
    of_head: pl.BlockSpec


def _call(q, beta, n, l, reverse) -> _Call:
    bsz, tp, _ = q.shape
    h, nb = beta.shape[1:3]
    d = q.shape[2] // h
    plan = blocks(d, d, l, n)
    if plan is None:
        raise ValueError(f"the delta-rule kernels do not take heads of {d} at blocks of "
                         f"{n} chunks of {l}")
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    return _Call(
        (bsz, h, nb), bsz * h * nb * n, plan,
        pl.BlockSpec((None, n * l, d), lambda b, i, c: (b, at(c), i)),
        pl.BlockSpec((None, None, None, n, l), lambda b, i, c: (b, i, at(c), 0, 0)),
        pl.BlockSpec((None, None, None, d, d), lambda b, i, c: (b, i, at(c), 0, 0)),
        pl.BlockSpec((None, None, d, d), lambda b, i, c: (b, i, 0, 0)))


def _params(plan, carried: int = 1):
    """`carried` trailing grid axes walk in order (the state lives across them)."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel") + ("arbitrary",) * carried,
        vmem_limit_bytes=plan.vmem_limit)


@functools.partial(jax.jit, static_argnames=("n", "l", "dt", "interpret"))
def _forward(q, k, v, g, beta, state, *, n, l, dt, interpret):
    bsz, tp, wide = q.shape
    grid, chunks, plan, plane, of_beta, of_block, of_head = _call(q, beta, n, l, reverse=False)
    (_, h, nb), d = grid, state.shape[-1]
    return pl.pallas_call(
        functools.partial(_fwd_kernel, n=n, l=l, sub=delta_rule.SUB, dt=dt),
        grid=grid,
        in_specs=[plane, plane, plane, plane, of_beta, of_head],
        out_specs=[plane, of_block, of_head],
        out_shape=[jax.ShapeDtypeStruct((bsz, tp, wide), _F32),
                   jax.ShapeDtypeStruct((bsz, h, nb, d, d), _F32),
                   jax.ShapeDtypeStruct((bsz, h, d, d), _F32)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32)],
        compiler_params=_params(plan),
        cost_estimate=pl.CostEstimate(
            flops=2 * chunks * (5 * l * l * d + 3 * l * d * d + 10 * l * l * l),
            transcendentals=chunks * (l * d * (3 + delta_rule.SUB) + l * l * d // 2),
            bytes_accessed=4 * (5 * bsz * tp * wide + bsz * h * (nb + 2) * d * d)),
        interpret=interpret,
        name="delta_rule_fwd",
    )(q, k, v, g, beta, state)



@functools.partial(jax.jit, static_argnames=("n", "l", "dt", "interpret"))
def _backward(q, k, v, g, beta, starts, d_o, d_last, *, n, l, dt, interpret):
    bsz, tp, wide = q.shape
    grid, chunks, plan, plane, of_beta, of_block, of_head = _call(q, beta, n, l, reverse=True)
    (_, h, nb), d = grid, starts.shape[-1]
    like = lambda a: jax.ShapeDtypeStruct(a.shape, _F32)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, n=n, l=l, sub=delta_rule.SUB, dt=dt),
        grid=grid,
        in_specs=[plane, plane, plane, plane, of_beta, of_block, plane, of_head],
        out_specs=[plane, plane, plane, plane, of_beta, of_head],
        out_shape=[like(q), like(k), like(v), like(g), like(beta), like(d_last)],
        scratch_shapes=[pltpu.VMEM((d, d), _F32), pltpu.VMEM((n, d, d), _F32),
                        pltpu.VMEM((n, l, l), _F32), pltpu.VMEM((n, l, l), _F32),
                        pltpu.VMEM((n, l, l), _F32), pltpu.VMEM((n, l, d), _F32),
                        pltpu.VMEM((n, l, d), _F32)],
        compiler_params=_params(plan),
        cost_estimate=pl.CostEstimate(
            flops=2 * chunks * (15 * l * l * d + 8 * l * d * d + 12 * l * l * l),
            transcendentals=chunks * (2 * l * d * (3 + delta_rule.SUB) + l * l * d),
            bytes_accessed=4 * (10 * bsz * tp * wide + bsz * h * (nb + 2) * d * d)),
        interpret=interpret,
        name="delta_rule_bwd",
    )(q, k, v, g, beta, starts, d_o, d_last)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def chunk_rule(q, k, v, g, beta, state, n, l, dt):
    """The rule over whole blocks of `n` chunks of `l` tokens. q, k, v and Γ
    = g (B, T, H·d) float32; beta (B, H, T/(n·l), n, l); state (B, H, d_v,
    d_k), transposed. Returns (o (B, T, H·d) float32, the last state as
    `state`). Differentiable in all six; the residuals are the first five and
    the block-start states."""
    o, _, last = _forward(q, k, v, g, beta, state, n=n, l=l, dt=dt,
                          interpret=kernel_interpret())
    return o, last


def _chunk_rule_fwd(q, k, v, g, beta, state, n, l, dt):
    o, starts, last = _forward(q, k, v, g, beta, state, n=n, l=l, dt=dt,
                               interpret=kernel_interpret())
    o = checkpoint_name(o, delta_rule.RESIDUAL_NAMES[0])
    starts = checkpoint_name(starts, delta_rule.RESIDUAL_NAMES[1])
    return (o, last), (q, k, v, g, beta, starts)


def _chunk_rule_bwd(n, l, dt, kept, cts):
    # the forward call sits under the caller's scope; the backward is traced
    # apart from it and carries its own, so a trace read by scope finds both
    with jax.named_scope("delta_rule"):
        return tuple(_backward(*kept, *cts, n=n, l=l, dt=dt, interpret=kernel_interpret()))


chunk_rule.defvjp(_chunk_rule_fwd, _chunk_rule_bwd)


def delta_rule_kernels(q, k, v, g, beta, chunk, chunks_per_block, compute_dtype,
                       initial_state=None):
    """`delta_rule.gated_delta_rule` on the kernel route: same arguments, same
    results. Γ and β's layout are made here, in float32."""
    b, t, h, d = k.shape
    l = chunk
    n = min(chunks_per_block, -(-t // l))
    pad = -t % (l * n)
    tp = t + pad
    f32 = lambda a: a.astype(_F32)
    widen = lambda a: jnp.pad(f32(a), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    plane = lambda a: widen(a).reshape(b, tp, h * d)
    cum = delta_rule.cumulative_log_decay(
        widen(g).reshape(b, tp // l, l, h * d)).reshape(b, tp, h * d)
    beta = widen(beta).reshape(b, tp // (l * n), n, l, h).transpose(0, 4, 1, 2, 3)
    state = (jnp.zeros((b, h, d, d), _F32) if initial_state is None
             else jnp.swapaxes(f32(initial_state), -1, -2))
    o, last = chunk_rule(plane(q), plane(k), plane(v), cum, beta, state, n, l,
                         jnp.dtype(compute_dtype))
    return o.reshape(b, tp, h, d)[:, :t], jnp.swapaxes(last, -1, -2)


# ------------------------------------------------------------------ #
# the scalar form: one decay a head, r value heads a key head


def scalar_blocks(d_k: int, d_v: int, chunk: int, chunks_per_block: int,
                  group: int = 1) -> Optional[Blocks]:
    """`blocks` for the scalar form at `group` value heads a key head: d_k =
    d_v whole lanes, a chunk of whole sublane tiles, the backward's visit
    inside half the chip's VMEM."""
    if d_k != d_v or d_k % LANES or chunk % 8:
        return None
    n = chunks_per_block
    plane, state, square = 4 * n * chunk * d_k, 4 * d_k * d_v, 4 * chunk * chunk
    rows = 4 * 8 * max(chunk, LANES)
    # q, k, v, do read, dq, dk, dv written; Γ, β, dΓ, dβ; the group's states thrice
    moved = 7 * plane + 4 * rows + 3 * group * state
    # dSᵀ of the group, the chunk starts, M, P and X of every chunk, W and new
    held = (group + n) * state + 3 * n * square + 2 * plane
    values = 12 * 4 * chunk * d_k + 16 * square + 2 * state
    need = 2 * moved + held + values
    vmem = _vmem_bytes()
    return Blocks(need, vmem * 3 // 4) if need <= vmem // 2 else None


class _Decay(NamedTuple):
    """A chunk's decays, from its Γ as a row."""
    row: jax.Array      # exp Γ (1, L)
    col: jax.Array      # exp Γ (L, 1)
    both: jax.Array     # exp(Γ_r − Γ_i) for i ≤ r, 0 above the diagonal (L, L)
    to_end: jax.Array   # exp(Γ_L − Γ) (L, 1)
    through: jax.Array  # exp Γ_L down a column of the state's height (d, 1)
    last: jax.Array     # exp Γ_L (1, 1)


def _decay(g_row, mk: _Masks, d: int) -> _Decay:
    """Every exponent a difference ≤ 0 (or Γ itself), masked BEFORE the
    exponential. Γ_L is laid down a column by a masked lane sum: Mosaic does
    not broadcast a (1, 1) value over sublanes and lanes at once."""
    l = mk.l
    g_col = mk.column(g_row)

    def last_down(rows):
        at_last = jax.lax.broadcasted_iota(jnp.int32, (rows, l), 1) == l - 1
        return jnp.sum(jnp.where(at_last, g_row, 0.0), axis=1, keepdims=True)

    both = jnp.exp(jnp.where(mk.strict | mk.eye, g_col - g_row, -jnp.inf))
    return _Decay(jnp.exp(g_row), jnp.exp(g_col), both, jnp.exp(last_down(l) - g_col),
                  jnp.exp(last_down(d)), jnp.exp(g_row[:, l - 1:l]))


def _scalar_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l):
    """Chunk c's (q, k, v (L, d) each, Γ (1, L), β (1, L))."""
    return (_read(q_ref, c, l), _read(k_ref, c, l), _read(v_ref, c, l),
            g_ref[c:c + 1, :], beta_ref[c:c + 1, :])


def _scalar_chunks(operands, mk: _Masks, dt):
    """(the `_Chunk` of each of a visit's chunks in the scalar form, its
    `_Decay`): M and P are ONE product each, decayed after it in float32."""
    decays = [_decay(g_row, mk, k.shape[1]) for _, k, _, g_row, _ in operands]
    made = []
    for (q, k, _, _, _), decay in zip(operands, decays):
        kc = k.astype(dt)
        made.append((jnp.where(mk.strict, _nt(kc, kc) * decay.both, 0.0),
                     _nt(q.astype(dt), kc) * decay.both))
    xs = _inverses([-mk.column(beta_row) * m
                    for (m, _), (*_, beta_row) in zip(made, operands)], mk)
    chunks = []
    for (m, p), x, decay, (_, k, v, _, beta_row) in zip(made, xs, decays, operands):
        solved = x * beta_row
        chunks.append(_Chunk(m, p, x, _nn((solved * decay.row).astype(dt), k.astype(dt)),
                             _nn(solved.astype(dt), v.astype(dt))))
    return chunks, decays


def _scalar_after(state, new, k, decay: _Decay, dt):
    """The state Sᵀ a chunk leaves: exp(Γ_L) Sᵀ + (exp(Γ_L − Γ) ⊙ new)ᵀ K."""
    return delta_rule.next_state(
        decay.through, state, _tn((new * decay.to_end).astype(dt), k.astype(dt)))


def _scalar_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, init_ref, o_ref, starts_ref,
                       last_ref, state_ref, *, n, l, dt):
    j = pl.program_id(3)                # which of the key head's value heads

    @pl.when(pl.program_id(2) == 0)
    def _first_visit():
        state_ref[j] = init_ref[j]

    mk = _Masks(l, delta_rule.SUB)
    state = state_ref[j]
    starts_ref[j] = state
    operands = [_scalar_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l) for c in range(n)]
    for c, (terms, decay, (q, k, *_)) in enumerate(zip(*_scalar_chunks(operands, mk, dt),
                                                       operands)):
        new = _written(state, terms.w, terms.u, dt)
        o_ref[_rows(c, l), :] = (decay.col * _nt(q.astype(dt), state.astype(dt))
                                 + _nn(terms.p.astype(dt), new.astype(dt)))
        state = _scalar_after(state, new, k, decay, dt)
    state_ref[j] = state
    last_ref[j] = state


def _scalar_pull(q, k, v, beta_row, decay: _Decay, m, p, x, w, new, state, d_o, d_state,
                 mk: _Masks, dt):
    """The pull-back through one chunk of the scalar form: from d_o (L, d_v)
    and d_state (d_v, d_k), (dq, dk, dv (L, d) each, dΓ (1, L), dβ (1, L), the
    cotangent of the state it started from). dΓ is row and column sums of
    (L, L) products."""
    l = mk.l
    qc, kc, vc = q.astype(dt), k.astype(dt), v.astype(dt)
    sc, newc, doc, dsc = state.astype(dt), new.astype(dt), d_o.astype(dt), d_state.astype(dt)
    solved = x * beta_row                                               # A
    solved_c = solved.astype(dt)
    decayed = solved * decay.row                                        # A ⊙ exp Γ_i
    written = new * decay.to_end                                        # exp(Γ_L − Γ) ⊙ new
    # the state's sweep: new = U − W·S, O = exp Γ ⊙ (Q·S) + P·new, S' = ...
    k_ds = _nt(kc, dsc)                                                 # K·dS' (L, d_v)
    d_new = _tn(p.astype(dt), doc) + decay.to_end * k_ds
    d_newc = d_new.astype(dt)
    d_p = _nt(doc, newc)                                                # (L, L)
    do_s = _nn(doc, sc)                                                 # dO·Sᵀ (L, d_k)
    gated = (decay.col * d_o).astype(dt)                                # exp Γ ⊙ dO
    d_wc = (-_nn(d_newc, sc)).astype(dt)
    d_start = decay.through * d_state + _tn(gated, qc) - _tn(d_newc, w.astype(dt))
    d_col = jnp.sum(q * do_s, axis=1, keepdims=True)                    # ∂/∂ exp Γ_r, of O
    d_to_end = jnp.sum(new * k_ds, axis=1, keepdims=True)               # ∂/∂ exp(Γ_L − Γ_r)
    d_through = jnp.sum(jnp.sum(d_state * state, axis=1, keepdims=True), axis=0, keepdims=True)
    # W = (A ⊙ exp Γ) K, U = A V, A = X Diag(β), X = (I − N)⁻¹, N = −Diag(β) M
    d_decayed = _nt(d_wc, kc)                                           # (L, L)
    d_a = d_decayed * decay.row + _nt(d_newc, vc)
    d_row = jnp.sum(d_decayed * solved, axis=0, keepdims=True)          # ∂/∂ exp Γ_i, of W
    d_v = _tn(solved_c, d_newc)
    d_n = _exact(_exact(x, d_a * beta_row, ((0,), (0,))), x, ((1,), (1,)))      # Xᵀ X̄ Xᵀ
    d_beta = (jnp.sum(d_a * x, axis=0, keepdims=True)
              - mk.row(jnp.sum(d_n * m, axis=1, keepdims=True)))
    d_m = jnp.where(mk.strict, -mk.column(beta_row) * d_n, 0.0)
    # M = tril(K Kᵀ, −1) ⊙ D, P = tril(Q Kᵀ) ⊙ D: the products' cotangents
    # stacked, one matmul a side
    d_products = jnp.concatenate([d_m * decay.both, d_p * decay.both], axis=0).astype(dt)
    by_k = _nn(d_products, kc)                                          # (2L, d_k)
    d_q = decay.col * do_s + by_k[l:]
    d_k = (_nn(written.astype(dt), dsc) + _tn(decayed.astype(dt), d_wc) + by_k[:l]
           + _tn(d_products, jnp.concatenate([kc, qc], axis=0)))
    # Γ: D_ri = exp(Γ_r − Γ_i) gives +row sums and −column sums of D̄ ⊙ D
    through_d = d_m * m + d_p * p
    d_last = (jnp.sum(d_to_end * decay.to_end, axis=0, keepdims=True)
              + d_through * decay.last)                                 # (1, 1)
    at_last = jax.lax.broadcasted_iota(jnp.int32, (1, l), 1) == l - 1
    d_g = (mk.row(jnp.sum(through_d, axis=1, keepdims=True) + d_col * decay.col
                  - d_to_end * decay.to_end)
           - jnp.sum(through_d, axis=0, keepdims=True) + d_row * decay.row
           + jnp.where(at_last, d_last, 0.0))
    return d_q, d_k, d_v, d_g, d_beta, d_start


def _scalar_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, starts_ref, do_ref, dlast_ref,
                       dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dinit_ref,
                       dstate_ref, states_ref, m_ref, p_ref, x_ref, w_ref, new_ref,
                       *, n, l, dt):
    j = pl.program_id(3)

    @pl.when(pl.program_id(2) == 0)
    def _first_visit():
        dstate_ref[j] = dlast_ref[j]

    mk = _Masks(l, delta_rule.SUB)
    operands = [_scalar_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, c, l) for c in range(n)]
    # the block's forward again, its terms left in VMEM
    chunks, decays = _scalar_chunks(operands, mk, dt)
    for c, terms in enumerate(chunks):
        m_ref[c], p_ref[c], x_ref[c], w_ref[c] = terms.m, terms.p, terms.x, terms.w
        new_ref[c] = terms.u                    # U until the sweep makes new of it
    state = starts_ref[j]
    for c in range(n):
        states_ref[c] = state
        new = _written(state, w_ref[c], new_ref[c], dt)
        new_ref[c] = new
        if c + 1 < n:
            state = _scalar_after(state, new, operands[c][1], decays[c], dt)
    # and the pull-back, the chunks from the last. dq and dk are the KEY
    # head's: its block stays in VMEM over the r visits, the first writes it
    # and the others add to it
    d_state = dstate_ref[j]
    for c in reversed(range(n)):
        q, k, v, _, beta_row = operands[c]
        d_q, d_k, d_v, d_g, d_beta, d_state = _scalar_pull(
            q, k, v, beta_row, decays[c], m_ref[c], p_ref[c], x_ref[c], w_ref[c], new_ref[c],
            states_ref[c], _read(do_ref, c, l), d_state, mk, dt)
        rows = _rows(c, l)
        dq_ref[rows, :] = jnp.where(j > 0, dq_ref[rows, :], 0.0) + d_q
        dk_ref[rows, :] = jnp.where(j > 0, dk_ref[rows, :], 0.0) + d_k
        dv_ref[rows, :] = d_v
        dg_ref[c:c + 1, :], dbeta_ref[c:c + 1, :] = d_g, d_beta
    dstate_ref[j] = d_state
    dinit_ref[j] = d_state


class _ScalarCall(NamedTuple):
    """What the scalar form's two `pallas_call`s take from their operands'
    shapes: the grid (sequence, key head, block, value head of the key head)."""
    grid: tuple
    chunks: int
    plan: Blocks
    of_key: pl.BlockSpec        # q, k, dq, dk: the KEY head's (n·L, d)
    of_value: pl.BlockSpec      # v, o, do, dv: the value head's
    rows: pl.BlockSpec          # Γ, β and their cotangents: (n, L)
    of_block: pl.BlockSpec      # the r block-start states of the key head
    of_head: pl.BlockSpec       # the r first or last states


def _scalar_call(q, v, beta, n, l, reverse) -> _ScalarCall:
    bsz, tp, _ = q.shape
    hv, nb = beta.shape[1:3]
    d = v.shape[2] // hv
    hk = q.shape[2] // d
    r = hv // hk
    plan = scalar_blocks(d, d, l, n, r)
    if plan is None:
        raise ValueError(f"the scalar delta-rule kernels do not take heads of {d} at "
                         f"blocks of {n} chunks of {l}, {r} value heads a key head")
    at = (lambda c: nb - 1 - c) if reverse else (lambda c: c)
    return _ScalarCall(
        (bsz, hk, nb, r), bsz * hv * nb * n, plan,
        pl.BlockSpec((None, n * l, d), lambda b, i, c, j: (b, at(c), i)),
        pl.BlockSpec((None, n * l, d), lambda b, i, c, j: (b, at(c), i * r + j)),
        pl.BlockSpec((None, None, None, n, l), lambda b, i, c, j: (b, i * r + j, at(c), 0, 0)),
        pl.BlockSpec((None, None, None, r, d, d), lambda b, i, c, j: (b, i, at(c), 0, 0, 0)),
        pl.BlockSpec((None, None, r, d, d), lambda b, i, c, j: (b, i, 0, 0, 0)))


@functools.partial(jax.jit, static_argnames=("n", "l", "dt", "interpret"))
def _scalar_forward(q, k, v, g, beta, state, *, n, l, dt, interpret):
    bsz, tp, _ = q.shape
    call = _scalar_call(q, v, beta, n, l, reverse=False)
    (_, hk, nb, r), d = call.grid, state.shape[-1]
    hv = hk * r
    return pl.pallas_call(
        functools.partial(_scalar_fwd_kernel, n=n, l=l, dt=dt),
        grid=call.grid,
        in_specs=[call.of_key, call.of_key, call.of_value, call.rows, call.rows, call.of_head],
        out_specs=[call.of_value, call.of_block, call.of_head],
        out_shape=[jax.ShapeDtypeStruct(v.shape, _F32),
                   jax.ShapeDtypeStruct((bsz, hk, nb, r, d, d), _F32),
                   jax.ShapeDtypeStruct((bsz, hk, r, d, d), _F32)],
        scratch_shapes=[pltpu.VMEM((r, d, d), _F32)],
        compiler_params=_params(call.plan, carried=2),
        cost_estimate=pl.CostEstimate(
            flops=2 * call.chunks * (5 * l * l * d + 3 * l * d * d + 10 * l * l * l),
            transcendentals=call.chunks * (l * l + 4 * l),
            bytes_accessed=4 * (2 * bsz * tp * (hk + hv) * d + bsz * hv * (nb + 2) * d * d)),
        interpret=interpret,
        name="delta_rule_scalar_fwd",
    )(q, k, v, g, beta, state)


@functools.partial(jax.jit, static_argnames=("n", "l", "dt", "interpret"))
def _scalar_backward(q, k, v, g, beta, starts, d_o, d_last, *, n, l, dt, interpret):
    bsz, tp, _ = q.shape
    call = _scalar_call(q, v, beta, n, l, reverse=True)
    (_, hk, nb, r), d = call.grid, starts.shape[-1]
    hv = hk * r
    like = lambda a: jax.ShapeDtypeStruct(a.shape, _F32)
    return pl.pallas_call(
        functools.partial(_scalar_bwd_kernel, n=n, l=l, dt=dt),
        grid=call.grid,
        in_specs=[call.of_key, call.of_key, call.of_value, call.rows, call.rows,
                  call.of_block, call.of_value, call.of_head],
        out_specs=[call.of_key, call.of_key, call.of_value, call.rows, call.rows,
                   call.of_head],
        out_shape=[like(q), like(k), like(v), like(g), like(beta), like(d_last)],
        scratch_shapes=[pltpu.VMEM((r, d, d), _F32), pltpu.VMEM((n, d, d), _F32),
                        pltpu.VMEM((n, l, l), _F32), pltpu.VMEM((n, l, l), _F32),
                        pltpu.VMEM((n, l, l), _F32), pltpu.VMEM((n, l, d), _F32),
                        pltpu.VMEM((n, l, d), _F32)],
        compiler_params=_params(call.plan, carried=2),
        cost_estimate=pl.CostEstimate(
            flops=2 * call.chunks * (17 * l * l * d + 9 * l * d * d + 12 * l * l * l),
            transcendentals=call.chunks * 2 * (l * l + 4 * l),
            bytes_accessed=4 * (4 * bsz * tp * (hk + hv) * d
                                + bsz * hv * (nb + 2) * d * d)),
        interpret=interpret,
        name="delta_rule_scalar_bwd",
    )(q, k, v, g, beta, starts, d_o, d_last)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def scalar_chunk_rule(q, k, v, g, beta, state, n, l, dt):
    """The scalar form over whole blocks of `n` chunks of `l` tokens. q, k
    (B, T, H_k·d), v (B, T, H_v·d) float32; Γ = g and beta (B, H_v, T/(n·l),
    n, l); state (B, H_k, r, d_v, d_k), transposed. Returns (o (B, T, H_v·d)
    float32, the last state as `state`). Differentiable in all six; the
    residuals are the first five and the block-start states."""
    o, _, last = _scalar_forward(q, k, v, g, beta, state, n=n, l=l, dt=dt,
                                 interpret=kernel_interpret())
    return o, last


def _scalar_chunk_rule_fwd(q, k, v, g, beta, state, n, l, dt):
    o, starts, last = _scalar_forward(q, k, v, g, beta, state, n=n, l=l, dt=dt,
                                      interpret=kernel_interpret())
    o = checkpoint_name(o, delta_rule.RESIDUAL_NAMES[0])
    starts = checkpoint_name(starts, delta_rule.RESIDUAL_NAMES[1])
    return (o, last), (q, k, v, g, beta, starts)


def _scalar_chunk_rule_bwd(n, l, dt, kept, cts):
    with jax.named_scope("delta_rule"):
        return tuple(_scalar_backward(*kept, *cts, n=n, l=l, dt=dt,
                                      interpret=kernel_interpret()))


scalar_chunk_rule.defvjp(_scalar_chunk_rule_fwd, _scalar_chunk_rule_bwd)


def delta_rule_scalar_kernels(q, k, v, g, beta, chunk, chunks_per_block, compute_dtype,
                              initial_state=None):
    """`delta_rule.gated_delta_rule` in the scalar form on the kernel route:
    q, k (B, T, H_k, d), v (B, T, H_v, d), g, beta (B, T, H_v). Γ — a (B, T,
    H_v) plane — and the rows' layout are made here, in float32."""
    b, t, hk, d = k.shape
    hv = v.shape[2]
    r = hv // hk
    l = chunk
    n = min(chunks_per_block, -(-t // l))
    pad = -t % (l * n)
    tp = t + pad
    f32 = lambda a: a.astype(_F32)
    widen = lambda a: jnp.pad(f32(a), ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
    plane = lambda a: widen(a).reshape(b, tp, -1)
    # (B, T, H_v) -> (B, H_v, blocks, n, L): a chunk's numbers a row
    rows = lambda a: a.reshape(b, tp // (l * n), n, l, hv).transpose(0, 4, 1, 2, 3)
    cum = delta_rule.cumulative_log_decay(
        widen(g).reshape(b, tp // l, l, hv)).reshape(b, tp, hv)
    state = (jnp.zeros((b, hv, d, d), _F32) if initial_state is None
             else jnp.swapaxes(f32(initial_state), -1, -2))
    o, last = scalar_chunk_rule(plane(q), plane(k), plane(v), rows(cum), rows(widen(beta)),
                                state.reshape(b, hk, r, d, d), n, l,
                                jnp.dtype(compute_dtype))
    return (o.reshape(b, tp, hv, d)[:, :t],
            jnp.swapaxes(last.reshape(b, hv, d, d), -1, -2))
