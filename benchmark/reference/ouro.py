"""The plain reference of configuration `ouro-2.6b` (and of any `ouro` zoo
model): forward pass, the entropy-regularised expected loss over the exits,
gradients by `jax.grad` and AdamW, in straightforward `jax.numpy`, float32. No
kernel, no `lax.scan` over the passes or the exits: the passes are a written
loop over a written loop of layers, attention is the score matrix of a block
of queries against ALL keys under a dense mask made from positions, an exit's
logits are made in blocks of rows (`lax.map` over the blocks of one call). The
caller runs it under `jax.default_matmul_precision("highest")`. Every matmul
goes through `product`, which rounds its operands to the configuration's
`compute_dtype` — float32: the plain einsum; bfloat16, as the cell's
configuration states: the operands of the product and of its backward's two
products rounded by `reduce_precision`, every sum float32 — so that the
reference computes in the precision the configuration states and no other.

Written from the published configuration (ByteDance/Ouro-2.6B `config.json`,
`model_type: ouro`) and the family's report (arXiv:2510.25741), not from the
zoo module. It shares one thing with the program: the names and shapes of the
parameters (`model_zoo/transformer/ouro.py` lists them), so that the program's
own initial parameters are the reference's starting point. What the
configuration file lists as `assumed` is assumed here alike.

- `h⁰ = E[tokens]`.
- layer l: `x ← x + rms(Attn(rms(x; n1_l)); n2_l)`, `x ← x + rms(MLP(rms(x;
  n3_l)); n4_l)`; `Attn`: `q = h W_q`, `k = h W_k`, `v = h W_v`, heads of D,
  `q ← R(q)`, `k ← R(k)` with R the rotary map (rotate-half: the dimension pair
  (i, i + D/2) of position t turned by the angle t · θ^(−2i/D)), `s_ij = q_i ·
  k_j / sqrt(D)`, key j visible to query i iff j ≤ i, softmax over the visible,
  `· v`, `W_o`; `MLP`: `W_down(silu(h W_gate) ⊙ h W_up)`.
- pass t = 1 … P: `hᵗ = rms(layer_N(… layer_1(hᵗ⁻¹)); n_f)`, the same
  parameters at every t.
- exit t: `Lᵗ = CE(hᵗ W_head, labels)` per position; `λᵗ = σ(hᵗ · w_g + b_g)`
  for t < P; `pᵗ = λᵗ Π_{s<t}(1 − λˢ)`, `pᴾ = Π_{s<P}(1 − λˢ)`.
- `loss = mean_positions(Σₜ pᵗ Lᵗ − β H(p))`, `H(p) = −Σₜ pᵗ ln pᵗ`.

Departures from a word-for-word transcription, values unchanged: each layer
application, each block of `QUERY_BLOCK` queries and each block of `ROW_BLOCK`
positions of an exit's logits with their cross entropy is recomputed in the
backward pass (`jax.checkpoint`), so that 32 layer applications over 4096
tokens and four 49 152-wide exits fit on one chip beside the parameters and
their gradient.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
EXIT_ENTROPY_COEF = 0.1
QUERY_BLOCK = 512
ROW_BLOCK = 1024
LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "attn_post_norm",
              "mlp_norm", "w_gate", "w_up", "w_down", "mlp_post_norm")

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch (my chip runs, PR
# 52 and its review session; all in PERF.md §6). THE REFERENCE COMPUTES IN THE
# PRECISION THE CONFIGURATION STATES: float32 everywhere but the operands of
# its matmuls, which `product` rounds to bfloat16 forward and backward, as the
# program's are. Against the same steps with unrounded operands the program
# reads two to ten times more (`mu_rel_l2` 0.016-0.032 for 0.007-0.012 in
# `wq`, 0.008 for 0.001-0.003 in the head; `loss_rel` up to 1.35e-4 for
# 5.9e-5): the operands' rounding, which is no error. What is left is the
# flash kernels' own roundings (probabilities before their normalisation, not
# after: `wq`, `wk` read highest) and 32 applications' amplification of both.
# SOUND: the largest reading of twenty runs at sixteen seeds (2147483777 and
# 2147483999 three times each, 2147485501, 2999990011, 1900000333,
# 2147481999, 3000001111, 412, 2147486003 — which the limits were first set
# from — and, under those limits, 2147487001, 2999991013, 1800000517,
# 2147480777, 3000002221, 97, 2147488001: they raised eleven of the largest
# readings by 0.4-49% and the limits with them by at most 22%, so that every
# verdict logged under the first limits stands). THE CONTROL every limit is
# read against: this reference's own two steps with everything the
# configuration states float32 computed in bfloat16, put in the program's place
# (`rehearse/departures_ouro.py::REFERENCE_CONTROLS`, seeds 2147483777 and
# 2147483999; float32 master weights and moments), and the nearest DEPARTURE
# there. A limit is the geometric middle of the largest sound reading and the
# nearest of those that reads 1.4 times it or more.
def _between(sound: float, nearest: float) -> float:
    assert nearest >= 1.4 * sound
    return (sound * nearest) ** 0.5


TOLERANCES = {
    # Sound at most 5.9e-5 (the sum and the expected loss alike). The norm
    # between the passes left out reads 1.27e-3 and 6.9e-4, the control 2.0e-3
    # and 7.0e-4 (3.7e-3, 3.5e-3 at the other seed), the entropy's coefficient
    # doubled 6.9e-3 in the sum, its sign flipped 1.4e-2
    "loss_rel": _between(5.9e-5, 1.27e-3),
    "loss_expected_rel": _between(5.8e-5, 6.9e-4),
    # − β H(p), a mean of a smooth function of the gate's float32 logits: sound
    # at most 3.9e-4; the control 3.7e-3 and 1.1e-2, the norm between the
    # passes left out 0.085, β doubled 1.0, the sign flipped 2.0
    "loss_entropy_rel": _between(3.9e-4, 3.7e-3),
    # the four exits' cross entropies, per-example means over 4096 positions:
    # a later exit has more applications behind it. (sound, the control's
    # smaller reading); the norm between the passes left out reads 3.1e-3,
    # 5.4e-3, 4.5e-3 in exits 2-4 and nothing in exit 1
    **{f"loss_exit_{t}_rel": _between(sound, control) for t, (sound, control) in enumerate(
        ((1.8e-5, 7.3e-4), (3.8e-5, 2.3e-3), (7.2e-5, 1.43e-3), (1.29e-4, 1.73e-3)), 1)},
    # the mean exit distribution the program counts itself against this
    # reference's, the largest difference of any exit at either step. Sound at
    # most 4.3e-4; the control 2.6e-3, the norm between the passes left out 0.036
    "exit_pmf_abs": _between(4.3e-4, 2.6e-3),
    # AdamW's first moment. Sound, largest over the runs: wq and wk 0.0130,
    # every other leaf of the layers and the final norm at most 0.0072, the
    # embedding 0.0069, the head 0.0028, the gate's weight 0.0090, its bias —
    # ONE number, a sum of 3 x 4096 positions' signed terms that nearly
    # cancel — 0.032. The second pass left out of the shared weights' gradient
    # reads 0.10 (0.13) in wq and wk and 0.18-0.25 in the layers' other leaves
    # and nothing anywhere else (no term of the loss moves: these limits alone
    # hold it); the norm between the passes left out 0.23 (head) - 0.48; β
    # doubled 0.30 and more in every leaf but the head, 1.6 in the gate; the
    # gate's bias left out 1.0 in `exit_gate_b` and nothing anywhere else.
    # The control reads 0.071 and 0.079 in the embedding (a scatter-add of
    # bfloat16 cotangents), which its limit is read against, and 0.010-0.023
    # in the layers: a first moment of the layers tells no precision apart
    "mu_rel_l2": {"default": _between(0.0130, 0.104),
                  "embed": _between(0.0069, 0.071),
                  "exit_gate_b": _between(0.032, 1.0)},
    # The parameters' update after the two steps: AdamW's first steps are ≈
    # lr · sign(g), so an element whose gradient is near zero changes sign
    # under any rounding and counts twice. The precision hardly moves it (the
    # control reads 1.4-1.9 times the sound readings), so each limit is the
    # geometric middle of the largest sound reading and 1, which is what a
    # state left unchanged reads. The second pass left out reads 0.43-0.59 in
    # every matrix of the layers. The norms' weights, of size one, do not move
    # at all in two steps at the warm-up's first step sizes (3.75e-9 and
    # 7.5e-9 against float32's 6e-8 at one) and read 0 on both sides: their
    # UPDATE is held by nothing, their moments are held above
    "update_rel_l2": {"default": _between(0.0945, 1.0),
                      **{leaf: _between(sound, 1.0) for leaf, sound in (
                          ("wq", 0.0758), ("wk", 0.0758), ("w_gate", 0.0603), ("w_up", 0.0603),
                          ("w_down", 0.0603), ("wo", 0.0529), ("wv", 0.0529),
                          ("exit_gate_w", 0.0599), ("head", 0.0280), ("exit_gate_b", 0.0089))}},
}


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "intermediate_size", "total_ut_steps")
    hp = {k: int(model_params[k]) for k in ints}
    hp["rope_theta"] = float(model_params.get("rope_theta", 1000000.0))
    hp["rms_norm_eps"] = float(model_params.get("rms_norm_eps", 1e-6))
    hp["exit_entropy_coef"] = float(model_params.get("exit_entropy_coef", EXIT_ENTROPY_COEF))
    # what every projection, the MLP, the head and attention's two products
    # round their operands to (`product`): the configuration's own statement,
    # under the program's key and default. And what everything else is
    # computed in: float32, but in the check's control
    # (`rehearse/departures_ouro.py::REFERENCE_CONTROLS`)
    hp["matmul_operands"] = model_params.get("compute_dtype", "bfloat16")
    hp["dtype"] = "float32"
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in ADAMW if k in model_params}}
    return hp


def _block(length: int, most: int) -> int:
    """The largest divisor of `length` that is at most `most`."""
    return next(n for n in range(min(most, length), 0, -1) if length % n == 0)


def _rounded(x, to):
    """x at dtype `to`'s bits, in its own dtype. An explicit
    `reduce_precision`: a cast to `to` and back is a pair XLA is free to drop
    (`xla_allow_excess_precision`), and on the chip it does."""
    info = jnp.finfo(to)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


@functools.cache
def _product_of_rounded(spec, to):
    """einsum `spec` whose three products — its own and the two of its
    backward pass — each read BOTH operands rounded to `to` (a, b; the
    cotangent and a rounded operand) and write what they accumulated."""
    plain = lambda a, b: jnp.einsum(spec, a, b)

    @jax.custom_vjp
    def product(a, b):
        return plain(_rounded(a, to), _rounded(b, to))

    def forward(a, b):
        a, b = _rounded(a, to), _rounded(b, to)
        return plain(a, b), (a, b)

    product.defvjp(forward, lambda kept, g: jax.vjp(plain, *kept)[1](_rounded(g, to)))
    return product


def product(spec, a, b, hp):
    """einsum `spec` of a and b as the configuration states a matmul: operands
    rounded to `hp["matmul_operands"]`, sums in the arrays' own precision,
    forward and backward. With float32 operands: the plain einsum."""
    to = jnp.dtype(hp["matmul_operands"])
    if to.itemsize >= a.dtype.itemsize:
        return jnp.einsum(spec, a, b)
    return _product_of_rounded(spec, to)(a, b)


def rms(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """x (T, heads, D): the pair (i, i + D/2) of position t turned by
    t · θ^(−2i/D)."""
    t, _, d = x.shape
    half = d // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None, None] * freq[None, None, :]
    cos, sin = jnp.cos(angle).astype(x.dtype), jnp.sin(angle).astype(x.dtype)   # the table
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(p, h, hp):
    """h (T, C) of ONE sequence -> (T, C)."""
    t = h.shape[0]
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    project = lambda w: product("tc,cn->tn", h, w, hp)
    q = rotary(project(p["wq"]).reshape(t, heads, d), hp["rope_theta"])
    k = rotary(project(p["wk"]).reshape(t, kv_heads, d), hp["rope_theta"])
    v = project(p["wv"]).reshape(t, kv_heads, d)
    k, v = (jnp.repeat(a, heads // kv_heads, axis=1) for a in (k, v))

    @jax.checkpoint
    def block(q_block, rows):
        scores = product("qhd,khd->hqk", q_block, k, hp) / jnp.sqrt(jnp.asarray(d, q.dtype))
        visible = jnp.arange(t)[None, :] <= rows[:, None]
        weights = jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1)
        return product("hqk,khd->qhd", weights, v, hp)

    size = _block(t, QUERY_BLOCK)
    out = jax.lax.map(lambda qr: block(*qr), (q.reshape(t // size, size, heads, d),
                                              jnp.arange(t).reshape(t // size, size)))
    return product("tn,nc->tc", out.reshape(t, heads * d), p["wo"], hp)


def layer(p, x, hp):
    eps = hp["rms_norm_eps"]
    x = x + rms(attention(p, rms(x, p["attn_norm"], eps), hp), p["attn_post_norm"], eps)
    h = rms(x, p["mlp_norm"], eps)
    m = product("tc,cf->tf", jax.nn.silu(product("tc,cf->tf", h, p["w_gate"], hp))
                * product("tc,cf->tf", h, p["w_up"], hp), p["w_down"], hp)
    return x + rms(m, p["mlp_post_norm"], eps)


def states_of(params, tokens, hp):
    """tokens (T,) of one sequence -> the list of the P normed states (T, C)."""
    x = params["embed"][tokens]
    states = []
    for _ in range(hp["total_ut_steps"]):
        for l in range(hp["num_hidden_layers"]):
            x = jax.checkpoint(lambda p, x: layer(p, x, hp))(
                {k: params[k][l] for k in LAYER_KEYS}, x)
        x = rms(x, params["final_norm"], hp["rms_norm_eps"])
        states.append(x)
    return states


def cross_entropy(state, head, labels, hp):
    """(T, C), (C, V), (T,) -> (T,), the logits made ROW_BLOCK rows at a time."""
    @jax.checkpoint
    def rows(h, y):
        logits = product("tc,cv->tv", h, head, hp)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]

    size = _block(state.shape[0], ROW_BLOCK)
    return jax.lax.map(lambda hy: rows(*hy), (state.reshape(-1, size, state.shape[-1]),
                                              labels.reshape(-1, size))).reshape(-1)


def exit_pmf(params, states):
    """The exit distribution of every position: a list of P arrays (T,)."""
    survive, pmf = jnp.ones(states[0].shape[:1], states[0].dtype), []
    for state in states[:-1]:
        gate = jax.nn.sigmoid(state @ params["exit_gate_w"] + params["exit_gate_b"])
        pmf.append(gate * survive)
        survive = survive * (1.0 - gate)
    return pmf + [survive]


def sequence_terms(params, tokens, labels, hp):
    """One sequence: ({term: scalar}, the mean exit distribution (P,))."""
    states = states_of(params, tokens, hp)
    pmf = exit_pmf(params, states)
    ces = [cross_entropy(state, params["head"], labels, hp) for state in states]
    expected = sum(jnp.mean(p * ce) for p, ce in zip(pmf, ces))
    plogp = sum(jnp.mean(jnp.where(p > 0, p * jnp.log(jnp.where(p > 0, p, 1.0)), 0.0))
                for p in pmf)                                   # −H(p)
    terms = {f"loss_exit_{t + 1}": jnp.mean(ce) for t, ce in enumerate(ces)}
    terms["loss_expected"] = expected
    terms["loss_entropy"] = hp["exit_entropy_coef"] * plogp
    return terms, jnp.stack([jnp.mean(p) for p in pmf])


def loss_terms(params, batch, hp):
    """batch {"tokens" (B, T), "labels" (B, T), "mask" (B,)} -> (the loss that
    is minimised, its terms by the names the program's step reports them
    under, the mean exit distribution (P,)); each the masked mean over the
    sequences."""
    params = {k: v.astype(hp["dtype"]) for k, v in params.items()}
    weight = (batch["mask"] / jnp.maximum(jnp.sum(batch["mask"]), 1.0)).astype(hp["dtype"])
    terms, pmf = None, 0.0
    for b in range(batch["tokens"].shape[0]):
        own, own_pmf = sequence_terms(params, batch["tokens"][b], batch["labels"][b], hp)
        own = {k: weight[b] * v for k, v in own.items()}
        terms = own if terms is None else {k: terms[k] + own[k] for k in own}
        pmf = pmf + weight[b] * own_pmf
    return terms["loss_expected"] + terms["loss_entropy"], terms, pmf


def adamw_step(params, grads, mu, nu, t, opt=ADAMW):
    """One AdamW step (decoupled weight decay on every parameter, bias-
    corrected moments, eps outside the root, linear warm-up of the step
    size), t counted from 1."""
    b1, b2 = opt["b1"], opt["b2"]
    lr = opt["learning_rate"] * jnp.minimum(1.0, t / opt["warmup_steps"])

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + opt["eps"])
        return p - lr * (step + opt["weight_decay"] * p), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)


def loss(params, batch, hp):
    """`loss_terms` as `jax.value_and_grad(..., has_aux=True)` takes it."""
    total, terms, pmf = loss_terms(params, batch, hp)
    return total, (terms, pmf)
