"""OLMoE: a decoder-only LM whose every feed-forward is a dropless top-k
mixture of SwiGLU experts (allenai/OLMoE-1B-7B-0125-Instruct, `model_type:
olmoe`; arXiv:2409.02060).

The block's mathematics is written ONCE, as pure functions over a dict of
arrays — `attention`, `moe`, `block`, `forward` — so that a pipelined model
can call `block` as its stage function (ROADMAP C6); the flax module at the
bottom only declares the parameters and sows the auxiliary losses. Layer
equations (hidden C, heads H of D = C / H, E experts of width F, k a token):

- `h = rmsnorm(x)`; `q, k, v = h·Wq, h·Wk, h·Wv` (no bias); `q = rmsnorm(q)`,
  `k = rmsnorm(k)` over the WHOLE width C, before the split into heads;
  rotary positions (rotate-half) on q and k; causal softmax attention at
  scale D^-1/2 (`ops.attention.full_attention`: the flash kernel on a TPU);
  `x = x + attn·Wo`.
- `h = rmsnorm(x)`; router logits `h·Wg` in float32; `p = softmax(logits)`;
  the k largest, weights unrenormalised; `y = Σ_e p_e · W_down,e(
  silu(W_gate,e h) ⊙ W_up,e h )`, every (token, slot) pair computed
  (`ops.moe.dropless_moe`); `x = x + y`.
- final rmsnorm, an untied head, per-example mean next-token cross entropy.
- auxiliary: load balance `E · Σ_e f_e · P_e` and router z-loss
  `mean(logsumexp(logits)²)`, each sown ALREADY multiplied by its own
  coefficient, so the module-level `aux_loss_weight` is 1.

Precision: parameters, norms, router, softmaxes, residual stream and loss
float32; the matmuls take `compute_dtype` operands (bfloat16 on the chip)
and accumulate in float32.

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.training import lr_modulation
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names."""

    vocab_size: int = 50304
    hidden_size: int = 2048
    num_hidden_layers: int = 16
    num_attention_heads: int = 16
    intermediate_size: int = 1024      # ONE expert's width
    num_experts: int = 64
    num_experts_per_tok: int = 8
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    load_balance_coef: float = 0.01
    router_z_coef: float = 0.001
    compute_dtype: str = "bfloat16"

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def rmsnorm(x, weight, eps):
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * weight


def rope(x, theta):
    """Rotary positions, rotate-half form, on x (B, T, H, D) float32:
    x·cos + rotate_half(x)·sin with angle t · theta^(-2i/D) on the pair of
    dimensions (i, i + D/2)."""
    t, d = x.shape[1], x.shape[-1]
    inv_freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    cos = jnp.concatenate([jnp.cos(angle)] * 2, axis=-1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(angle)] * 2, axis=-1)[None, :, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def _matmul(x, w, dt):
    """x·w with `dt` operands; the MXU accumulates in float32."""
    return jnp.dot(x.astype(dt), w.astype(dt))


def attention(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The attention branch's update of the residual stream x (B, T, C)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, c = x.shape
    heads = (b, t, cfg.num_attention_heads, cfg.head_dim)
    h = rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    q = rmsnorm(_matmul(h, p["wq"], dt), p["q_norm"], cfg.rms_norm_eps)
    k = rmsnorm(_matmul(h, p["wk"], dt), p["k_norm"], cfg.rms_norm_eps)
    v = _matmul(h, p["wv"], dt)
    q = rope(q.reshape(heads), cfg.rope_theta).astype(dt)
    k = rope(k.reshape(heads), cfg.rope_theta).astype(dt)
    out = full_attention(q, k, v.reshape(heads), causal=True)
    return _matmul(out.reshape(b, t, c), p["wo"], dt).astype(jnp.float32)


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The router of one block on the residual stream x (B, T, C): (the
    normed tokens (N, C), logits (N, E) float32, probs, weights (N, k),
    expert_idx (N, k))."""
    h = rmsnorm(x, p["ffn_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    return (h, logits) + moe_ops.topk_route(logits, cfg.num_experts_per_tok)


def moe(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The expert branch's update of x, and what the auxiliary losses and
    the counters need: {"load_balance", "router_z", "expert_idx", "weights",
    "router_input"}."""
    with jax.named_scope("router"):
        h, logits, probs, weights, expert_idx = route(p, x, cfg)
        balance, z = moe_ops.router_aux_losses(logits, probs, expert_idx)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_gate"], p["w_up"], p["w_down"]),
        compute_dtype=jnp.dtype(cfg.compute_dtype))
    return y.reshape(x.shape), {
        "load_balance": balance, "router_z": z, "expert_idx": expert_idx,
        "weights": weights, "router_input": x}


def block(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """One decoder block: (parameters of ONE layer, x (B, T, C) float32) ->
    (x, the expert branch's statistics)."""
    with jax.named_scope("attn"):
        x = x + attention(p, x, cfg)
    with jax.named_scope("moe"):
        y, stats = moe(p, x, cfg)
        return x + y, stats


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "ffn_norm", "router", "w_gate", "w_up", "w_down")


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config):
    """tokens (B, T) -> (logits (B, T, V) float32, per-layer statistics
    stacked on a leading layer axis). Per-layer parameters carry a leading
    layer axis (`LAYER_KEYS`), as `PipelinedBlocks` stacks its own."""
    with jax.named_scope("olmoe"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        stats = []
        for layer in range(cfg.num_hidden_layers):
            x, s = block({k: params[k][layer] for k in LAYER_KEYS}, x, cfg)
            stats.append(s)
        with jax.named_scope("head_loss"):
            h = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
            dt = jnp.dtype(cfg.compute_dtype)
            logits = jnp.dot(h.astype(dt), params["head"].astype(dt),
                             preferred_element_type=jnp.float32)
    return logits, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


def expert_assignments(params, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (layers, B·T, k), weights (layers, B·T, k), the residual
    stream each router saw (layers, B, T, C)). For counters (per-expert load)
    and for the benchmark's comparison of routing with its reference; the head
    is dead code here."""
    stats = forward(params, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


# ------------------------------------------------------------------ #
# The zoo contract


class OLMoE(nn.Module):
    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        del training                       # no dropout anywhere
        c = self.cfg
        L, C, F, E = (c.num_hidden_layers, c.hidden_size,
                      c.intermediate_size, c.num_experts)
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((c.vocab_size, C), normal),
            "attn_norm": ((L, C), ones), "q_norm": ((L, C), ones),
            "k_norm": ((L, C), ones), "ffn_norm": ((L, C), ones),
            "wq": ((L, C, C), normal), "wk": ((L, C, C), normal),
            "wv": ((L, C, C), normal), "wo": ((L, C, C), normal),
            "router": ((L, C, E), normal),
            "w_gate": ((L, E, C, F), normal), "w_up": ((L, E, C, F), normal),
            "w_down": ((L, E, F, C), normal),
            "final_norm": ((C,), ones),
            "head": ((C, c.vocab_size), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        logits, stats = forward(params, features, c)
        # overwrite, not flax's default append: the trainer threads mutable
        # collections through every step (see api.layers.MoE)
        for name, coef in (("load_balance", c.load_balance_coef),
                           ("router_z", c.router_z_coef)):
            self.sow("losses", name, coef * jnp.sum(stats[name]),
                     reduce_fn=lambda prev, new: new,
                     init_fn=lambda: jnp.float32(0.0))
        return logits


def custom_model(**kwargs) -> OLMoE:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return OLMoE(Config(**given))


# ModelSpec picks this up. The two auxiliary terms have different
# coefficients, so each is sown already multiplied by its own.
aux_loss_weight = 1.0


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B, T, V) +
    (B, T) -> (B,)."""
    with jax.named_scope("olmoe/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs.astype(jnp.float32), labels.astype(jnp.int32))
        return ce.mean(axis=-1)


def optimizer(**kwargs):
    """AdamW as the OLMoE paper trains with (decay on every parameter), with
    its linear warm-up: the step size is learning_rate · min(1, t / warmup).
    Without it the first AdamW steps, each ≈ lr · sign(g) on weights of size
    0.02, collapse the routers onto a few experts (loss 11.4 → 14–17 after
    one step on the chip, 17–29 of 64 experts without a token after eight)."""
    warmup = float(kwargs.get("warmup_steps", 2500))
    return lr_modulation.modulated(
        lambda learning_rate: optax.chain(
            optax.adamw(learning_rate, b1=0.9, b2=0.95, eps=1e-8,
                        weight_decay=float(kwargs.get("weight_decay", 0.1))),
            optax.scale_by_schedule(
                lambda count: jnp.minimum(1.0, (count + 1) / warmup))),
        learning_rate=float(kwargs.get("learning_rate", 4e-4)),
    )


def batch_partition() -> Dict[str, P]:
    return {"features": P(MeshAxis.DATA), "labels": P(MeshAxis.DATA),
            "mask": P(MeshAxis.DATA)}


def eval_metrics_fn():
    return {"token_accuracy": TokenAccuracy()}
