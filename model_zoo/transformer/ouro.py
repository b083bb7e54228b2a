"""Ouro: a looped decoder-only LM — ONE stack of layers run `total_ut_steps`
times over shared weights, an exit after every pass through one head and one
gate, trained on the entropy-regularised expected loss over the exits
(ByteDance/Ouro-2.6B, `model_type: ouro`; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741).

The mathematics is written ONCE, as pure functions over a dict of arrays —
`layer`, `stack`, `run_pass`, `passes`, `exit_distribution`, `exit_cross_entropies` —
like `olmoe.py`, whose `rmsnorm`, `rope`, optimizer and batch partition these
are; the flax module at the bottom declares the parameters and owns the
counters. Hidden C, H query heads and Hkv key-value heads of D, an MLP of
width F, N layers, P passes:

- `h⁰ = E[tokens]`.
- one layer, four RMSNorms (sandwich): `x ← x + N₂(Attn(N₁(x)))`, then
  `x ← x + N₄(MLP(N₃(x)))`. `Attn`: `q, k, v = h·Wq, h·Wk, h·Wv` (no bias, no
  norm of q or k), rotary positions (rotate-half, all D dimensions, θ =
  `rope_theta`) on q and k, causal softmax attention at scale D^-1/2
  (`ops.attention.full_attention`: the flash kernels on a TPU), `·Wo`. `MLP`:
  `W_down(silu(h·W_gate) ⊙ h·W_up)`.
- the loop: for t = 1 … P: `hᵗ = Norm_f(Stack(hᵗ⁻¹))` — all N layers in
  order, the SAME parameters at every t, the final RMSNorm at the end of EVERY
  pass, its output both the exit's input and the next pass's.
- the exits: `logitsᵗ = hᵗ·W_head` (one head), `Lᵗ_i` the next-token cross
  entropy of position i at exit t; the gate (one `Linear(C → 1)` with bias)
  `λᵗ_i = σ(hᵗ_i · w_g + b_g)` for t < P.
- the exit distribution, per position: `S⁰ = 1`, `Sᵗ = Sᵗ⁻¹(1 − λᵗ)`; `pᵗ =
  λᵗ Sᵗ⁻¹` for t < P and `pᴾ = Sᴾ⁻¹`: it sums to one by construction.
- the loss, per position: `Σₜ pᵗ Lᵗ − β H(p)`, `H(p) = − Σₜ pᵗ ln pᵗ`, β =
  `exit_entropy_coef` (a uniform prior over the exits); the mean over
  positions. With P = 1: `p¹ = 1`, `H = 0`, the plain cross entropy, and the
  gate has no gradient.

THE CONTRACT WITH THE LOSS. The four exits' logits never live at once (at
4096 tokens and 49 152 ids one plane is 805 MB float32, and as much again its
cotangent). The module's `outputs` are the P normed states as the head's
matmul reads them (`compute_dtype`), the head matrix itself, the exit
distribution and the entropy term; the zoo's `loss` owns the head matmul and
the cross entropy, ONE exit at a time (`lax.scan` over the exits, each
recomputed in the backward pass), and returns `loss` with its terms by name:
`loss_exit_1` … `loss_exit_P`, `loss_expected`, `loss_entropy`.

Precision: parameters, gradients, the residual stream, every RMSNorm, the
rotary table, the softmax's running sums, the gate, `p`, `H(p)`, every cross
entropy and the loss float32; projections, MLP and head take `compute_dtype`
operands (bfloat16 on the chip) and accumulate float32; q, k, v enter the
flash kernels in `compute_dtype`. A shared weight is cast to `compute_dtype`
where it is used, in every pass and at every exit, so its gradient is the
FLOAT32 sum of its uses' contributions.

Recomputation: every layer application is recomputed in the backward pass
(`jax.checkpoint`); of an application the stream it started from is kept, and
what the flash kernels' backward reads (`pallas_attention.KEEP_RESIDUALS`: q,
k, v, the output and the logsumexp, 100.7 MB an application at 4096 tokens).
The passes are UNROLLED, P x N checkpointed applications in one
program: as one `lax.scan` over the passes the window's program needs 16.4
GiB where this form needs 15.2 (the scan's backward holds a second
accumulator of the layers' gradients; PERF.md section 6, PR 52).

Counters, in collections the trainer threads through every step:
`loop/layer_applications` and `loop/passes` (summed over steps), `exit/pmf`
(P,) and `exit/entropy` (the last step's mean exit distribution and its mean
entropy) and `attn/kv_block_visits` beside `attn/kv_block_visits_causal`
(summed over steps, as `mellum.py` counts them).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops.attention import full_attention
from model_zoo.transformer.nemotron_h import matmul
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, optimizer, rmsnorm, rope)
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names. Two are this
    repo's: `exit_entropy_coef` (β) and `compute_dtype`."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    intermediate_size: int = 5632
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    exit_entropy_coef: float = 0.1
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.total_ut_steps < 1:
            raise ValueError("total_ut_steps is at least 1")


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)

LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "attn_post_norm",
              "mlp_norm", "w_gate", "w_up", "w_down", "mlp_post_norm")


def attention(p: Dict[str, jax.Array], h: jax.Array, cfg: Config) -> jax.Array:
    """Attention on the normed stream h (B, T, C): (B, T, C) float32."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = h.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    q = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, d)
    k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
    v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
    q, k = rope(q, cfg.rope_theta).astype(dt), rope(k, cfg.rope_theta).astype(dt)
    out = full_attention(q, k, v, causal=True)
    return matmul(out.reshape(b, t, heads * d), p["wo"], dt, jnp.float32)


def mlp(p: Dict[str, jax.Array], h: jax.Array, cfg: Config) -> jax.Array:
    dt = jnp.dtype(cfg.compute_dtype)
    gate = matmul(h, p["w_gate"], dt, jnp.float32)
    up = matmul(h, p["w_up"], dt, jnp.float32)
    return matmul(jax.nn.silu(gate) * up, p["w_down"], dt, jnp.float32)


def layer(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """One application of one layer (its parameters WITHOUT the layer axis)
    to the residual stream x (B, T, C) float32."""
    eps = cfg.rms_norm_eps
    with jax.named_scope("norm"):
        h = rmsnorm(x, p["attn_norm"], eps)
    with jax.named_scope("attn"):
        a = attention(p, h, cfg)
    with jax.named_scope("norm"):
        x = x + rmsnorm(a, p["attn_post_norm"], eps)
        h = rmsnorm(x, p["mlp_norm"], eps)
    with jax.named_scope("mlp"):
        m = mlp(p, h, cfg)
    with jax.named_scope("norm"):
        return x + rmsnorm(m, p["mlp_post_norm"], eps)


def stack(params: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """Every layer in order, once."""
    for l in range(cfg.num_hidden_layers):
        x = jax.checkpoint(lambda p, x: layer(p, x, cfg),
                           policy=pallas_attention.KEEP_RESIDUALS)(
            {k: params[k][l] for k in LAYER_KEYS}, x)
    return x


def run_pass(params: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """One pass: the stack, then the final norm."""
    x = stack(params, x, cfg)
    with jax.named_scope("final_norm"):
        return rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)


def passes(params: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The loop: x (B, T, C) -> the P normed states (P, B, T, C) float32, each
    the exit's input and the next pass's."""
    with jax.named_scope("pass"):
        states = []
        for _ in range(cfg.total_ut_steps):
            x = run_pass(params, x, cfg)
            states.append(x)
        return jnp.stack(states)


def exit_gates(params: Dict[str, jax.Array], states: jax.Array) -> jax.Array:
    """λ (P − 1, B, T) float32 of the first P − 1 states (P, B, T, C)."""
    logit = jnp.einsum("pbtc,c->pbt", states[:-1], params["exit_gate_w"],
                       precision=jax.lax.Precision.HIGHEST) + params["exit_gate_b"]
    return jax.nn.sigmoid(logit)


def exit_distribution(gates: jax.Array) -> jax.Array:
    """λ (P − 1, B, T) -> p (P, B, T): `pᵗ = λᵗ Πₛ₍ₛ₋ₜ₎(1 − λˢ)`, the last exit
    taking what survives."""
    survive = jnp.cumprod(1.0 - gates, axis=0)                        # Sᵗ
    ones = jnp.ones((1,) + gates.shape[1:], gates.dtype)
    before = jnp.concatenate([ones, survive])                         # Sᵗ⁻¹, t = 1 … P
    return jnp.concatenate([gates * before[:-1], before[-1:]])


def entropy(p: jax.Array) -> jax.Array:
    """H(p) over the leading axis; 0 · ln 0 = 0."""
    return -jnp.sum(p * jnp.log(jnp.maximum(p, 1e-30)), axis=0)


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config):
    """tokens (B, T) -> (states (P, B, T, C) float32, p (P, B, T))."""
    with jax.named_scope("ouro"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        states = passes(params, x, cfg)
        with jax.named_scope("exit"):
            gates = exit_gates(params, states)
        with jax.named_scope("exit_loss"):
            return states, exit_distribution(gates)


def head_logits(state: jax.Array, head: jax.Array) -> jax.Array:
    """(…, C) · (C, V) -> (…, V) float32, operands in `state`'s dtype."""
    return jnp.dot(state, head.astype(state.dtype), preferred_element_type=jnp.float32)


def exit_cross_entropies(states: jax.Array, head: jax.Array, labels: jax.Array):
    """Lᵗ (P, B, T) float32: the next-token cross entropy of every position at
    every exit. One exit's logits at a time, forward and backward."""
    labels = labels.astype(jnp.int32)

    @jax.checkpoint
    def one_exit(head, state):
        logits = head_logits(state, head)
        # the label's logit by a mask, not a gather: its backward is then
        # elementwise over the plane and fuses with the softmax's
        own = labels[..., None] == jnp.arange(logits.shape[-1], dtype=jnp.int32)
        picked = jnp.sum(jnp.where(own, logits, 0.0), axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - picked

    return jax.lax.scan(lambda head, state: (head, one_exit(head, state)),
                        head, states)[1]


def kv_block_visits(cfg: Config, seq_len: int):
    """((q block, kv block) pairs a head's forward grid computes in one step,
    over every layer application; what a causal grid would compute: the
    same, there is no window)."""
    visits, causal = pallas_attention.kv_block_visits(
        seq_len, seq_len, None, cfg.head_dim, jnp.dtype(cfg.compute_dtype))
    applications = cfg.num_hidden_layers * cfg.total_ut_steps
    return applications * visits, applications * causal


# ------------------------------------------------------------------ #
# The zoo contract


class Ouro(nn.Module):
    """Initialisation: normal(0.02) for every matrix, the embedding and the
    gate's weight, ones for every norm, zero for the gate's bias."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, C, V, F = (c.num_hidden_layers, c.hidden_size, c.vocab_size,
                      c.intermediate_size)
        H, Hkv, D, P = (c.num_attention_heads, c.num_key_value_heads, c.head_dim,
                        c.total_ut_steps)
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), ones),
            "head": ((C, V), normal),
            "exit_gate_w": ((C,), normal), "exit_gate_b": ((), nn.initializers.zeros),
            "attn_norm": ((L, C), ones), "attn_post_norm": ((L, C), ones),
            "mlp_norm": ((L, C), ones), "mlp_post_norm": ((L, C), ones),
            "wq": ((L, C, H * D), normal), "wk": ((L, C, Hkv * D), normal),
            "wv": ((L, C, Hkv * D), normal), "wo": ((L, H * D, C), normal),
            "w_gate": ((L, C, F), normal), "w_up": ((L, C, F), normal),
            "w_down": ((L, F, C), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape=(), dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        applications = counter("loop", "layer_applications")
        passes_run = counter("loop", "passes")
        pmf = counter("exit", "pmf", (P,), jnp.float32)
        mean_entropy = counter("exit", "entropy", (), jnp.float32)
        visits = counter("attn", "kv_block_visits")
        visits_causal = counter("attn", "kv_block_visits_causal")
        states, p = forward(params, features, c)
        with jax.named_scope("ouro/exit_loss"):
            h = entropy(p)                                           # (B, T)
        if training and not self.is_initializing():
            applications.value = applications.value + L * P
            passes_run.value = passes_run.value + P
            pmf.value = jnp.mean(p, axis=(1, 2))
            mean_entropy.value = jnp.mean(h)
            computed, causal = kv_block_visits(c, features.shape[1])
            visits.value = visits.value + computed
            visits_causal.value = visits_causal.value + causal
        return {"states": states.astype(jnp.dtype(c.compute_dtype)),
                "head": params["head"], "pmf": p,
                "entropy_term": -c.exit_entropy_coef * h.mean(axis=-1)}


def custom_model(**kwargs) -> Ouro:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Ouro(Config(**given))


def loss(labels, outputs):
    """Per-example means over the positions, float32 (B,): `loss` =
    `loss_expected` (Σₜ pᵗ Lᵗ) + `loss_entropy` (− β H(p)), and each exit's own
    cross entropy `loss_exit_t`, which only rides along."""
    with jax.named_scope("ouro/exit"):
        ce = exit_cross_entropies(outputs["states"], outputs["head"], labels)
    with jax.named_scope("ouro/exit_loss"):
        expected = jnp.sum(outputs["pmf"] * ce, axis=0).mean(axis=-1)
        terms = {f"loss_exit_{t + 1}": ce[t].mean(axis=-1) for t in range(ce.shape[0])}
        return {"loss": expected + outputs["entropy_term"], "loss_expected": expected,
                "loss_entropy": outputs["entropy_term"], **terms}


class LastExitAccuracy(TokenAccuracy):
    """`TokenAccuracy` of the LAST exit: its logits alone are made."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(
            state, labels, head_logits(outputs["states"][-1], outputs["head"]), mask)


class ExitShare(TokenAccuracy):
    """The mean of the exit distribution at one exit, over positions and
    examples (`TokenAccuracy`'s state: a sum and its weight)."""

    def __init__(self, exit_index: int):
        self.exit_index = exit_index

    def update(self, state, labels, outputs, mask=None):
        if self.exit_index >= outputs["pmf"].shape[0]:      # fewer passes
            return state
        each = outputs["pmf"][self.exit_index].mean(axis=-1)         # (B,)
        weight = jnp.ones_like(each) if mask is None else jnp.asarray(mask, jnp.float32)
        return state + jnp.stack([jnp.sum(each * weight), jnp.sum(weight)])


# The exits a job's evaluation reports the mean distribution over: the
# published model's four (`eval_metrics_fn` takes no configuration; an exit
# the model does not have reads 0, a fifth is not reported).
EVAL_EXITS = 4


def eval_metrics_fn():
    return {"token_accuracy": LastExitAccuracy(),
            **{f"exit_share_{t + 1}": ExitShare(t) for t in range(EVAL_EXITS)}}
