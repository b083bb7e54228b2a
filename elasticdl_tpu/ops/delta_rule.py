"""The gated delta rule: linear attention whose state is decayed, has the
key's direction erased and is then written, every token (the delta rule of
arXiv:2406.06484 under the gate of arXiv:2412.06464). Per head, with a state
S (d_k, d_v), S_0 = 0:

    S_t = (I − β_t k_t k_tᵀ) · Diag(exp(g_t)) · S_{t−1} + β_t k_t v_tᵀ
    o_t = S_tᵀ q_t

β_t the write strength of the head, g_t ≤ 0 its log-decay, in one of TWO FORMS
that the shape of g selects:

- "channel": g (B, T, H, d_k), one decay a key CHANNEL (Kimi Delta Attention,
  arXiv:2510.26692: `model_zoo/transformer/kimi_linear.py`); q, k, v all of H
  heads;
- "scalar": g (B, T, H_v), ONE decay a head (Gated DeltaNet, arXiv:2412.06464:
  `model_zoo/transformer/qwen3_next.py`), `Diag(exp g_t)` then `exp(g_t) · I`;
  q, k of H_k heads against v, g, β of H_v = r · H_k: value head h reads key
  head ⌊h / r⌋, and no H_v-head copy of q or k is ever made.

`delta_rule_recurrent` is the recurrence, token by token, in either form
(tests; the benchmark's references have their own). `gated_delta_rule` computes
it in chunks of L tokens.

The channel-wise form first. With u_t = β_t (v_t − S_{t−1}ᵀ (exp(g_t) ⊙ k_t)) the step is
`S_t = Diag(exp(g_t)) S_{t−1} + k_t u_tᵀ`, so inside a chunk that starts from
S, with Γ_r = Σ_{i≤r} g_i (per channel, ≤ 0 and falling):

    M_ri = Σ_c k_rc k_ic exp(Γ_rc − Γ_ic)   (i < r)     P_ri likewise with q_r (i ≤ r)
    (I + Diag(β) M) u = Diag(β) (V − (K ⊙ exp Γ) S)      — unit lower triangular
    O  = (Q ⊙ exp Γ) S + P u
    S' = Diag(exp Γ_L) S + (K ⊙ exp(Γ_L − Γ))ᵀ u

A decay a channel sits INSIDE the contraction over c and is carried on the
operands. Written as a quotient `(K ⊙ exp Γ)(K ⊘ exp Γ)ᵀ` it overflows (128
channels, each decaying over L steps): every exponent taken here is a
DIFFERENCE that is ≤ 0. The chunk is cut into
sub-blocks of `SUB` tokens; a row of sub-block a against a column of an
EARLIER sub-block goes through a's start, `exp(Γ_r − Γ_a) · exp(Γ_a − Γ_i)`,
both factors ≤ 1, the column factor made once for each (a, i); inside a
sub-block the (SUB, SUB, d) differences are taken one by one, masked BEFORE
the exponential.

The scalar form is the same chunk algebra with the decay OUTSIDE the
contraction: Γ_r = Σ_{i≤r} g_i is one number a head and token, so

    M = tril(K Kᵀ, −1) ⊙ exp(Γ_r − Γ_i)        P = tril(Q Kᵀ) ⊙ exp(Γ_r − Γ_i)
    (I + Diag(β) M) u = Diag(β) (V − exp(Γ) ⊙ (K S))
    O  = exp(Γ) ⊙ (Q S) + P u                  S' = exp(Γ_L) S + Kᵀ (exp(Γ_L − Γ) ⊙ u)

— ONE (L, L) product of the key head's q and k (shared by the r value heads
that read it) and ONE (L, L) exponential a value head, masked BEFORE the
exponential, where the channel-wise form takes sub-blocks of (SUB, SUB, d)
differences; the decays that are not in M and P scale rows and columns of
what is a value head's own already (A's columns, u's rows, the output's rows),
so nothing of (T, H_v, d_k) size — no plane of g, Γ or their exponentials, no
decayed copy of k — exists on either route. The pull-back gives dΓ as row and
column sums of (L, L) products, and dq, dk summed over the r value heads.

The triangular system is solved on the MXU without a sequential sweep: with
N = −Diag(β) M strictly lower, `(I − N)⁻¹ = (I + N)(I + N²)(I + N⁴)…`, log₂ L
factors, because N^L = 0 (`unit_lower_inverse`, whose pull-back is `Xᵀ X̄ Xᵀ`
and keeps X alone).

Precision: g, Γ, every exponential, β, M's and P's diagonal sub-blocks, the
inverse (matmuls at the highest precision), u, S and what is added to it are
float32; the chunk's other matmuls take `compute_dtype` operands (bfloat16 on
the chip), rounded AFTER the decay has been applied in float32, and
accumulate in float32 (the scalar form's K Kᵀ and Q Kᵀ take the operands as
they are and the decay multiplies the float32 product).

Memory: the sequence is walked in BLOCKS of `chunks_per_block` chunks. The
forward keeps the state each block starts from (B·H·d_k·d_v float32 a block:
2 MB at 32 heads of 128, 134 MB for the 64 blocks of 4 chunks of 16 384
tokens against 537 MB for every chunk's) and the operands; the backward (`custom_vjp`) walks the blocks in reverse,
recomputes one block's chunk algebra from its start state and pulls back
through it, so that nothing of a chunk's (L, L), (SUB, SUB, d) or (d, d) size
outlives its block. Both kept arrays carry `checkpoint_name`s
(`RESIDUAL_NAMES`: the output and the block-start states), so a layer
recomputed under `jax.checkpoint` with a policy that saves them runs the
forward sweep once.

There are TWO routes (`delta_rule_route`, which says in the log which one a
traced program took, in `ops/ssm.py::scan_route`'s manner). "kernel": the two
Pallas kernels of `ops/pallas_delta_rule.py` — a visit a (sequence, head,
block), the state in VMEM across a head's blocks, the operands read where they
lie, nothing of a chunk's size in HBM — where they can run (a TPU, or
interpret mode in the CPU tests) and the shapes fit (d_k = d_v whole lanes, a
chunk of whole sub-blocks, a visit's blocks inside VMEM). "plain": this file's
body, XLA's batched matmuls under two `lax.scan`s, everywhere else — the CPU's
route and the kernels' yardstick. Both take Γ from `cumulative_log_decay` — a
triangular product in XLA on both routes, never a sum inside the kernels —,
move the state through `next_state` (looked up in this module when a program
is traced: the benchmark's rehearsal patches them by name), keep the same two
named arrays and give the same values within rounding — in both forms: the
scalar form has a plain body (`_block_scalar`) and a kernel pair of its own
(`delta_rule_scalar_fwd` / `_bwd`), and shares `unit_lower_inverse`, the block
walk, the residuals, `cumulative_log_decay` (over a (…, L, H_v) plane on the
kernel route, a (…, L, 1) view on the plain one) and `next_state`.
"""

from __future__ import annotations

import functools
import logging
import math
from functools import partial

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from elasticdl_tpu.ops import pallas_delta_rule

logger = logging.getLogger(__name__)

SUB = 8                  # tokens of a sub-block: where the decay is referred to
RESIDUAL_NAMES = ("delta_rule_out", "delta_rule_states")
KEEP_RESIDUALS = jax.checkpoint_policies.save_only_these_names(*RESIDUAL_NAMES)
_HIGHEST = jax.lax.Precision.HIGHEST


def delta_rule_recurrent(q, k, v, g, beta, initial_state=None):
    """The recurrence as written, one token at a time, float32: q, k, g
    (B, T, H, d_k), v (B, T, H, d_v), beta (B, T, H) -> (o (B, T, H, d_v), the
    last state (B, H, d_k, d_v)). The scalar form too: g (B, T, H) is laid
    against every channel, q and k of fewer heads are repeated to v's."""
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    if g.ndim == 3:
        g = g[..., None]
    if k.shape[2] != v.shape[2]:
        q, k = (jnp.repeat(a, v.shape[2] // k.shape[2], axis=2) for a in (q, k))
    b, _, h, dk = k.shape
    state = (jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
             if initial_state is None else f32(initial_state))

    def step(s, token):
        q_t, k_t, v_t, g_t, beta_t = token
        s = jnp.exp(g_t)[..., None] * s
        seen = jnp.einsum("bhk,bhkv->bhv", k_t, s, precision=_HIGHEST)
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - seen),
                           precision=_HIGHEST)
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s, precision=_HIGHEST)

    by_token = lambda a: jnp.moveaxis(a, 1, 0)
    state, o = jax.lax.scan(step, state, tuple(map(by_token, (q, k, v, g, beta))))
    return jnp.moveaxis(o, 0, 1), state


# ------------------------------------------------------------------ #
# the triangular inverse


@jax.custom_vjp
def unit_lower_inverse(n: jax.Array) -> jax.Array:
    """(I − n)⁻¹ for n (..., L, L) STRICTLY lower triangular, float32, as the
    product (I + n)(I + n²)(I + n⁴)…: ⌈log₂ L⌉ factors, exact because n^L = 0."""
    x = jnp.eye(n.shape[-1], dtype=n.dtype) + n
    power = n
    for _ in range(max(math.ceil(math.log2(n.shape[-1])) - 1, 0)):
        power = jnp.matmul(power, power, precision=_HIGHEST)
        x = x + jnp.matmul(x, power, precision=_HIGHEST)
    return x


def _inverse_fwd(n):
    x = unit_lower_inverse(n)
    return x, x


def _inverse_bwd(x, ct):
    # X = (I − N)⁻¹, dX = X dN X: N̄ = Xᵀ X̄ Xᵀ
    xt = jnp.swapaxes(x, -1, -2)
    return (jnp.matmul(jnp.matmul(xt, ct, precision=_HIGHEST), xt, precision=_HIGHEST),)


unit_lower_inverse.defvjp(_inverse_fwd, _inverse_bwd)


# ------------------------------------------------------------------ #
# one block of chunks


def cumulative_log_decay(g):
    """Γ of chunks g (..., L, d): the inclusive sum over a chunk's tokens,
    float32 — as ONE product of the (L, L) lower triangle of ones with the
    chunk, at the highest precision: the float32 operand is split into
    bfloat16 parts that sum to it, every product with a 0 or a 1 is exact and
    the sums accumulate in float32, so this is the float32 cumulative sum in
    another order of additions (on the chip a product at the plane's rate of
    bytes, where a scan is a `reduce_window` between two relayouts; its
    pull-back is the transposed triangle's product)."""
    l = g.shape[-2]
    lower = jnp.tril(jnp.ones((l, l), g.dtype))
    return jnp.einsum("ij,...jd->...id", lower, g, precision=_HIGHEST)


def next_state(through, state, added):
    """`Diag(exp Γ_L) S + (K ⊙ exp(Γ_L − Γ))ᵀ u`: the state a chunk leaves,
    float32. `through` is exp Γ_L laid against the state's key axis (the
    kernels hold the state transposed)."""
    return through * state + added


def _mm(spec, a, b, dt):
    return jnp.einsum(spec, a.astype(dt), b.astype(dt),
                      preferred_element_type=jnp.float32)


def _chunk_matrices(q, k, cum, dt):
    """M (strictly lower) and P (lower, with its diagonal), (..., L, L)
    float32, of chunks q, k, cum (..., L, d): Σ_c (k or q)_rc k_ic
    exp(cum_rc − cum_ic). No exponent is positive."""
    l, d = k.shape[-2:]
    sub = min(SUB, l)
    s = l // sub
    lead = k.shape[:-2]
    in_subs = lambda a: a.reshape(lead + (s, sub, d))
    qs, ks, cs = in_subs(q), in_subs(k), in_subs(cum)
    # a sub-block's start: the cumulative decay up to the end of the one before
    start = jnp.concatenate(
        [jnp.zeros(lead + (1, d), cum.dtype), cs[..., :-1, -1, :]], axis=-2)
    row = jnp.exp(cs - start[..., None, :])                         # (.., s, sub, d) ≤ 1
    # the columns BEFORE sub-block a, as a's rows see them
    before = (jnp.arange(l)[None, :] < (jnp.arange(s) * sub)[:, None])[..., None]
    col = jnp.exp(jnp.where(before, start[..., :, None, :] - cum[..., None, :, :],
                            -jnp.inf))                              # (.., s, L, d) ≤ 1
    k_col = k[..., None, :, :] * col
    m = _mm("...ajc,...aic->...aji", ks * row, k_col, dt).reshape(lead + (l, l))
    p = _mm("...ajc,...aic->...aji", qs * row, k_col, dt).reshape(lead + (l, l))
    # inside a sub-block: every (row, column, channel) difference, float32
    r, i = jnp.arange(sub)[:, None], jnp.arange(sub)[None, :]
    diff = cs[..., :, None, :] - cs[..., None, :, :]                # (.., s, sub, sub, d)
    decayed_k = ks[..., None, :, :] * jnp.exp(
        jnp.where((r >= i)[..., None], diff, -jnp.inf))
    m_in = jnp.sum(ks[..., :, None, :] * decayed_k, axis=-1) * (r > i)
    p_in = jnp.sum(qs[..., :, None, :] * decayed_k, axis=-1)
    # the (s, sub, sub) diagonal sub-blocks onto the (L, L) matrix
    onto = jnp.eye(s, dtype=m_in.dtype)[:, None, :, None]
    diagonal = lambda a: (a[..., :, :, None, :] * onto).reshape(lead + (l, l))
    return m + diagonal(m_in), p + diagonal(p_in)


def _block(state, q, k, v, g, beta, dt, chunk):
    """One block of whole chunks from `state` (B, H, d_k, d_v): q, k, g
    (B, n·L, H, d_k), v (B, n·L, H, d_v), beta (B, n·L, H) -> (o like v, the
    state after them). The block's operands change layout HERE, to
    (B, H, n, L, d), so that nothing of the sequence's size is ever copied.
    Everything float32 but the operands `_mm` rounds."""
    b, tokens, h = beta.shape

    def by_chunk(a):
        """(B, n·L, H, ...) -> (B, H, n, L, ...)."""
        a = a.reshape((b, tokens // chunk, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    q, k, v, g, beta = map(by_chunk, (q, k, v, g, beta))
    cum = cumulative_log_decay(g)                                   # Γ, inclusive
    m, p = _chunk_matrices(q, k, cum, dt)
    solved = unit_lower_inverse(-beta[..., :, None] * m) * beta[..., None, :]
    w = _mm("...ri,...ic->...rc", solved, k * jnp.exp(cum), dt)     # A (K ⊙ exp Γ)
    u = _mm("...ri,...iv->...rv", solved, v, dt)                    # A V
    to_end = k * jnp.exp(cum[..., -1:, :] - cum)                    # K ⊙ exp(Γ_L − Γ)
    through = jnp.exp(cum[..., -1, :])                              # exp Γ_L

    def one_chunk(s, terms):
        w_n, u_n, to_end_n, through_n = terms
        new = u_n - _mm("bhrc,bhcv->bhrv", w_n, s, dt)              # u of the chunk
        return next_state(through_n[..., None], s, _mm("bhrc,bhrv->bhcv", to_end_n, new, dt)), (s, new)

    chunks_first = lambda a: jnp.moveaxis(a, 2, 0)
    state, (starts, new) = jax.lax.scan(
        one_chunk, state, tuple(map(chunks_first, (w, u, to_end, through))))
    starts, new = jnp.moveaxis(starts, 0, 2), jnp.moveaxis(new, 0, 2)
    o = (_mm("...rc,...cv->...rv", q * jnp.exp(cum), starts, dt)
         + _mm("...ri,...iv->...rv", p, new, dt))
    # (B, H, n, L, d_v) -> (B, n·L, H, d_v)
    return jnp.moveaxis(o, 1, 3).reshape(b, tokens, h, -1), state


def _block_scalar(state, q, k, v, g, beta, dt, chunk):
    """`_block` in the scalar form: q, k (B, n·L, H_k, d_k), v (B, n·L, H_v,
    d_v), g, beta (B, n·L, H_v), state (B, H_v, d_k, d_v) -> (o like v, the
    state after them). The r = H_v / H_k value heads of a key head are an
    axis of their own, (B, H_k, r, n, L, ·), against q and k's (B, H_k, n, L,
    d): K Kᵀ and Q Kᵀ are made once a key head, and every decay scales what is
    a value head's already."""
    b, tokens, hv = beta.shape
    hk = k.shape[2]
    r = hv // hk

    def by_chunk(a):
        """(B, n·L, H, ...) -> (B, H, n, L, ...)."""
        a = a.reshape((b, tokens // chunk, chunk) + a.shape[2:])
        return jnp.moveaxis(a, 3, 1)

    grouped = lambda a: a.reshape((b, hk, r) + a.shape[2:])           # H_v -> (H_k, r)
    q, k = by_chunk(q), by_chunk(k)                                   # (B, H_k, n, L, d)
    v, g, beta = (grouped(by_chunk(a)) for a in (v, g, beta))         # (B, H_k, r, n, L[, d])
    cum = cumulative_log_decay(g[..., None])[..., 0]                  # Γ, inclusive
    row, col = jnp.arange(chunk)[:, None], jnp.arange(chunk)[None, :]
    decay = jnp.exp(jnp.where(row >= col, cum[..., :, None] - cum[..., None, :], -jnp.inf))
    m = _mm("bhnrc,bhnic->bhnri", k, k, dt)[:, :, None] * decay * (row > col)
    p = _mm("bhnrc,bhnic->bhnri", q, k, dt)[:, :, None] * decay
    solved = unit_lower_inverse(-beta[..., :, None] * m) * beta[..., None, :]
    grown = jnp.exp(cum)                                              # exp Γ, ≤ 1
    w = _mm("bhgnri,bhnic->bhgnrc", solved * grown[..., None, :], k, dt)
    u = _mm("bhgnri,bhgniv->bhgnrv", solved, v, dt)                   # A V
    to_end = jnp.exp(cum[..., -1:] - cum)                             # exp(Γ_L − Γ)
    through = jnp.exp(cum[..., -1])                                   # exp Γ_L

    def one_chunk(s, terms):
        w_n, u_n, k_n, to_end_n, through_n = terms
        new = u_n - _mm("bhgrc,bhgcv->bhgrv", w_n, s, dt)             # u of the chunk
        added = _mm("bhrc,bhgrv->bhgcv", k_n, to_end_n[..., None] * new, dt)
        return next_state(through_n[..., None, None], s, added), (s, new)

    chunks_first = lambda a, axis: jnp.moveaxis(a, axis, 0)
    state, (starts, new) = jax.lax.scan(
        one_chunk, grouped(state),
        (chunks_first(w, 3), chunks_first(u, 3), chunks_first(k, 2),
         chunks_first(to_end, 3), chunks_first(through, 3)))
    starts, new = jnp.moveaxis(starts, 0, 3), jnp.moveaxis(new, 0, 3)
    o = (grown[..., None] * _mm("bhnrc,bhgncv->bhgnrv", q, starts, dt)
         + _mm("bhgnri,bhgniv->bhgnrv", p, new, dt))
    # (B, H_k, r, n, L, d_v) -> (B, n·L, H_v, d_v)
    o = jnp.moveaxis(o.reshape((b, hv) + o.shape[3:]), 1, 3).reshape(b, tokens, hv, -1)
    return o, state.reshape((b, hv) + state.shape[3:])


_BLOCK_OF = {"channel": _block, "scalar": _block_scalar}


# ------------------------------------------------------------------ #
# the blocks of a sequence, forward and backward


def _sweep(state, blocks, dt, chunk, form):
    """Every block in turn: (o of every block, the state each STARTED from,
    the last state)."""
    def one(s, block):
        o, after = _BLOCK_OF[form](s, *block, dt, chunk)
        return after, (o, s)

    last, (o, starts) = jax.lax.scan(one, state, blocks)
    return o, starts, last


@partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _blocks(state, blocks, dt, chunk, form="channel"):
    o, _, last = _sweep(state, blocks, dt, chunk, form)
    return o, last


def _blocks_fwd(state, blocks, dt, chunk, form):
    o, starts, last = _sweep(state, blocks, dt, chunk, form)
    o = checkpoint_name(o, RESIDUAL_NAMES[0])
    starts = checkpoint_name(starts, RESIDUAL_NAMES[1])
    return (o, last), (blocks, starts)


def _blocks_bwd(dt, chunk, form, kept, cts):
    blocks, starts = kept
    d_o, d_last = cts

    def one(d_state, block):
        start, operands, d_o_block = block
        _, pull = jax.vjp(lambda s, *ops: _BLOCK_OF[form](s, *ops, dt, chunk),
                          start, *operands)
        d_start, *d_operands = pull((d_o_block, d_state))
        return d_start, tuple(d_operands)

    d_state, d_blocks = jax.lax.scan(one, d_last, (starts, blocks, d_o), reverse=True)
    return d_state, d_blocks


_blocks.defvjp(_blocks_fwd, _blocks_bwd)


@functools.lru_cache(maxsize=None)
def _log_route(*said):
    """Once a process for each shape and route: a step's program traces the
    rule a layer, a recomputation and a counter at a time."""
    logger.info(
        "gated delta rule (%d tokens, %d heads of %d | %d, %s) takes the "
        "%s route: chunks of %d in sub-blocks of %d, blocks of %d chunks whose start "
        "states are kept (the Pallas kernels need a TPU or interpret mode: %s; d_k = "
        "d_v whole lanes, a chunk of whole sub-blocks, a visit's blocks inside VMEM: %s)",
        *said)


def delta_rule_route(shape, chunk: int, chunks_per_block: int, v_dim: int = None,
                     form: str = "channel", group: int = 1) -> str:
    """Which body the rule takes at q's shape (B, T, H, d_k) and values of
    `v_dim` channels (d_k if not given) — "kernel" or "plain": a pure function
    of the shapes, of the `form` ("channel", or "scalar" with `group` value
    heads reading each of the H key heads) and of whether the kernels can run
    here (a TPU, or interpret mode in the CPU tests). Logged once for each
    answer, form and route."""
    _, t, h, d = shape
    v_dim = v_dim or d
    runnable = pallas_delta_rule.runnable()
    n = min(chunks_per_block, -(-t // chunk))
    fits = (pallas_delta_rule.blocks(d, v_dim, chunk, n) if form == "channel"
            else pallas_delta_rule.scalar_blocks(d, v_dim, chunk, n, group))
    route = "kernel" if runnable and fits else "plain"
    said = ("a decay a channel" if form == "channel" else
            f"the SCALAR form, one decay a head and {group} value head(s) a key head")
    _log_route(t, h, d, v_dim, said, route, chunk, min(SUB, chunk), chunks_per_block,
               runnable, f"{fits.vmem_bytes} bytes" if fits else "no")
    return route


def gated_delta_rule(q, k, v, g, beta, chunk: int = 64, chunks_per_block: int = 8,
                     compute_dtype=jnp.bfloat16, initial_state=None):
    """The rule in chunks: q, k, g (B, T, H, d_k), v (B, T, H, d_v), beta
    (B, T, H) -> (o (B, T, H, d_v) float32, the last state (B, H, d_k, d_v)
    float32) — or, the SCALAR form, g (B, T, H_v) beside v (B, T, H_v, d_v)
    and beta (B, T, H_v), with q, k of H_k heads, H_v a multiple of H_k. g ≤ 0.
    T need not be a multiple of the chunk or of the block: the tail is padded
    with g = 0, β = 0, which leaves the state as it is. `chunk` is a multiple
    of `SUB` or at most `SUB`. Differentiable in all five operands and the
    initial state."""
    if chunk > SUB and chunk % SUB:
        raise ValueError(f"a chunk of {chunk} is not whole sub-blocks of {SUB}")
    form = "scalar" if g.ndim == 3 else "channel"
    h, group = v.shape[2], v.shape[2] // k.shape[2]
    if k.shape[2] * group != h or (form == "channel" and group != 1):
        raise ValueError(f"{k.shape[2]} key heads against {h} value heads in the "
                         f"{form} form")
    if delta_rule_route(q.shape, chunk, chunks_per_block, v.shape[-1], form,
                        group) == "kernel":
        kernels = (pallas_delta_rule.delta_rule_kernels if form == "channel"
                   else pallas_delta_rule.delta_rule_scalar_kernels)
        return kernels(q, k, v, g, beta, chunk, chunks_per_block, compute_dtype,
                       initial_state)
    f32 = lambda a: a.astype(jnp.float32)
    q, k, v, g, beta = map(f32, (q, k, v, g, beta))
    b, t, _, dk = k.shape
    n = min(chunks_per_block, -(-t // chunk))
    pad = -t % (chunk * n)
    nb = (t + pad) // (chunk * n)

    def blocked(a):
        """(B, T, H, ...) -> (blocks, B, n·L, H, ...): at B = 1 no copy."""
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        return jnp.moveaxis(a.reshape((b, nb, n * chunk) + a.shape[2:]), 1, 0)

    state = (jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
             if initial_state is None else f32(initial_state))
    o, last = _blocks(state, tuple(map(blocked, (q, k, v, g, beta))),
                      jnp.dtype(compute_dtype), chunk, form)
    # (blocks, B, n·L, H, d_v) -> (B, T, H, d_v)
    return jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, -1)[:, :t], last
