"""Mellum2: a decoder-only LM whose layers alternate between sliding-window
and full attention under TWO rotary tables, every feed-forward a dropless
top-k mixture of gated-SiLU experts (JetBrains/Mellum2-12B-A2.5B-Instruct,
`model_type: mellum`).

The mathematics is written ONCE, as pure functions over a dict of arrays —
`attention`, `moe`, `block`, `forward` — like `olmoe.py`; the flax module at
the bottom declares the parameters, sows the auxiliary loss and owns the
counters. Hidden C, H query heads and Hkv key-value heads of D, E experts of
width F, k a token, window W. Layer l is FULL if (l + 1) % `sliding_period`
== 0 and SLIDING otherwise (the published `layer_types`: three sliding
layers, then a full one), and its kind selects BOTH its mask and its rotary
table:

- `h = rmsnorm(x)`; `q = h·Wq` (C x H·D), `k = h·Wk`, `v = h·Wv` (C x Hkv·D),
  no bias, no norm of q or k; rotary positions (rotate-half, all D
  dimensions) from the table of the layer's kind (`rotary_tables`):
  - sliding: `inv_freq_i = theta^(-2i/D)`, cos and sin unscaled;
  - full (YaRN, `rope_parameters.full_attention`): with s = `rope_factor`,
    L0 = `original_max_position_embeddings`, `dim(r) = D·ln(L0 / (2 pi r)) /
    (2 ln theta)`, `low = max(floor(dim(beta_fast)), 0)`, `high =
    min(ceil(dim(beta_slow)), D - 1)`, `ramp_i = clip((i - low) / (high -
    low), 0, 1)`: `inv_freq_i = (1 - ramp_i)·theta^(-2i/D) + ramp_i·
    theta^(-2i/D) / s` — the fast dimensions keep their frequency, the slow
    ones are interpolated — and cos AND sin are multiplied by
    `attention_factor`, so a score carries its square. The blend is applied
    at every length, as a static YaRN table is.
- attention at scale D^-1/2, query head h reading key-value head h // (H /
  Hkv) (`ops.attention.full_attention`: the flash kernels on a TPU, with
  `window=W` their banded grids). Full: key j is visible to query i iff
  j <= i. Sliding: iff i - W < j <= i (W keys, the query's own position among
  them). `x = x + attn·Wo`.
- `h = rmsnorm(x)`; router logits `h·Wg` in float32; `p = softmax(logits)`
  over ALL `router_experts`; the k largest; weights `p_e / sum_chosen p`
  (`norm_topk_prob` true); an expert is `W_down(silu(W_gate h) * W_up h)`;
  this chip holds experts `first_expert ... first_expert + num_experts - 1`
  and computes every pair routed to them (`ops.moe.dropless_moe`, `held`);
  what the other experts would add is left out; `x = x + y`. No shared
  expert.
- final rmsnorm, an untied head, per-example mean next-token cross entropy.
- auxiliary: load balance `E · sum_e f_e · P_e` over all the experts the
  router chooses among, sown already multiplied by `load_balance_coef`. No
  z-loss.

Precision: parameters, norms, router, rotary tables, softmaxes, the residual
stream and the loss float32; the projections and the experts' matmuls take
`compute_dtype` operands (bfloat16 on the chip) and accumulate in float32;
what a sub-block adds to the residual stream is written in float32 as the
matmul accumulated it.

Every layer is recomputed in the backward pass (`jax.checkpoint`) under
`policy=pallas_attention.KEEP_RESIDUALS`: of a layer's activations the
residual stream it started from is kept and what the flash kernels' backward
reads — q, k, v, the output and the logsumexp, 0.77 GiB a layer at 16 384
tokens — so the recomputation runs no forward kernel again and rebuilds no q,
k, v. Fixed here, not a setting: the benchmark's window program (one
16 384-token sequence, 4 layers, 16 held experts) compiles to 15.10 GiB of
the chip's 15.75 with all four layers' kept and 12.04 with none (REHEARSAL,
PR 36; keeping the full layer's alone reads 15.93, the sliding layers' alone
15.68: XLA schedules the mixed forms worse than either), runs with them (step
502.7 ms against 552.6 without: my chip runs, PR 36), and a longer sequence or
a fifth layer would have to give them up.

Counters, in collections the trainer threads through every step:
`router_state/held_passes`, `held_row_tiles`, `held_row_chunks` (as
`glm4_moe_lite.py`'s),
`router_state/pairs_held_share` (per layer, of the last step) and
`attn/kv_block_visits` beside `attn/kv_block_visits_causal` (per kind
[sliding, full], summed over steps: the (q block, kv block) pairs a head's
forward grid computes in the layers of that kind, and what a causal grid
would).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition; the optimizer, the batch partition and `rmsnorm` are
`olmoe.py`'s. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops.attention import full_attention
from model_zoo.transformer.nemotron_h import (
    matmul, pairs_on_held, held_passes, held_row_chunks, held_row_tiles)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, eval_metrics_fn, optimizer, rmsnorm)
from model_zoo.transformer.transformer_lm import dataset_fn  # noqa: F401

KINDS = ("sliding", "full")


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names; the two
    groups of `rope_parameters` flattened (`rope_theta` is both kinds';
    `rope_factor`, `original_max_position_embeddings`, `beta_fast`,
    `beta_slow`, `attention_factor` the full layers' YaRN keys) and
    `layer_types` as its period (`sliding_period`: every fourth layer full).
    Five are this repo's: `router_experts` (how many experts the router
    chooses among; 0: `num_experts`, every expert held here), `first_expert`
    (the first of the `num_experts` held here), `load_balance_coef`,
    `embedding_initializer_range` and `residual_initializer_range`."""

    vocab_size: int = 98304
    hidden_size: int = 2304
    num_hidden_layers: int = 28
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 1024
    sliding_period: int = 4
    rope_theta: float = 500000.0
    rope_factor: float = 16.0
    original_max_position_embeddings: int = 8192
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    attention_factor: float = 1.2772588722239782
    num_experts: int = 64              # the experts HELD here
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 896
    load_balance_coef: float = 0.01
    rms_norm_eps: float = 1e-6
    # three standard deviations of the initialisation (see `Mellum`): every
    # matrix; the embedding; what a sub-block writes to the residual stream
    # (`wo`, `w_down`: smaller by sqrt(2 x the PUBLISHED 28 layers))
    initializer_range: float = 0.02
    embedding_initializer_range: float = 1.0
    residual_initializer_range: float = 0.02 / math.sqrt(2 * 28)
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.sliding_period < 1 or self.sliding_window < 1:
            raise ValueError("sliding_period and sliding_window are at least 1")

    @property
    def all_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.num_experts

    @property
    def held(self):
        return (self.first_expert, self.num_experts)

    @property
    def routing(self):
        """What `nemotron_h.py`'s counters of the held share read of a
        configuration: there `num_experts` is what the router chooses among."""
        return SimpleNamespace(held=self.held, num_experts=self.all_experts)

    def kind(self, layer: int) -> str:
        return "full" if (layer + 1) % self.sliding_period == 0 else "sliding"


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def yarn_inv_freq(cfg: Config) -> jax.Array:
    """(D/2,) float32: the full layers' blended inverse frequencies."""
    d, theta = cfg.head_dim, cfg.rope_theta
    plain = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def dim(rotations):
        return d * math.log(cfg.original_max_position_embeddings
                            / (2 * math.pi * rotations)) / (2 * math.log(theta))

    low = max(math.floor(dim(cfg.beta_fast)), 0)
    high = min(math.ceil(dim(cfg.beta_slow)), d - 1)
    ramp = jnp.clip((jnp.arange(d // 2, dtype=jnp.float32) - low)
                    / max(high - low, 1e-3), 0.0, 1.0)
    return (1.0 - ramp) * plain + ramp * plain / cfg.rope_factor


def rotary_tables(cfg: Config, seq_len: int) -> Dict[str, tuple]:
    """{"sliding", "full"}: (cos, sin), each (1, T, 1, D) float32 — the plain
    table, and the YaRN blend times `attention_factor`. Built once a step."""
    d = cfg.head_dim
    plain = cfg.rope_theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)

    def table(inv_freq, factor):
        angle = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
        both = lambda f: (factor * jnp.concatenate([f(angle)] * 2, axis=-1))[None, :, None, :]
        return both(jnp.cos), both(jnp.sin)

    return {"sliding": table(plain, 1.0),
            "full": table(yarn_inv_freq(cfg), cfg.attention_factor)}


def rotate(x, table):
    """Rotary positions, rotate-half form (`olmoe.py::rope`'s layout), on x
    (B, T, H, D) float32 from a (cos, sin) table."""
    cos, sin = table
    half = x.shape[-1] // 2
    return x * cos + jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1) * sin


def attention(p: Dict[str, jax.Array], x: jax.Array, table, window, cfg: Config):
    """The attention sub-block's update of the residual stream x (B, T, C):
    `table` the layer's (cos, sin), `window` its window or None."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("qkv"):
        q = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, d)
        k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
        v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
    with jax.named_scope("rope"):
        q, k = rotate(q, table).astype(dt), rotate(k, table).astype(dt)
    with jax.named_scope("attn"):
        out = full_attention(q, k, v, causal=True, window=window)
    with jax.named_scope("out"):
        return matmul(out.reshape(b, t, heads * d), p["wo"], dt, jnp.float32)


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The router of one layer on the residual stream x (B, T, C): (the
    normed tokens (N, C), logits (N, E) float32, probs, weights (N, k)
    renormalised to sum to one, expert_idx (N, k))."""
    h = rmsnorm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    probs, weights, expert_idx = moe_ops.topk_route(logits, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return h, logits, probs, weights, expert_idx


def moe(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The expert sub-block's update of x, and {"load_balance", "expert_idx",
    "weights", "router_input"} for the auxiliary loss, the counters and the
    benchmark's comparison of routing."""
    with jax.named_scope("router"):
        h, logits, probs, weights, expert_idx = route(p, x, cfg)
        balance, _ = moe_ops.router_aux_losses(logits, probs, expert_idx)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_gate"], p["w_up"], p["w_down"]),
        held=cfg.held, num_experts=cfg.all_experts,
        compute_dtype=jnp.dtype(cfg.compute_dtype))
    return y.reshape(x.shape), {
        "load_balance": balance, "expert_idx": expert_idx, "weights": weights,
        "router_input": x}


def block(p: Dict[str, jax.Array], x: jax.Array, table, kind: str, cfg: Config):
    """One layer of `kind` on x (B, T, C) float32: (x, the expert
    sub-block's statistics)."""
    with jax.named_scope(kind):
        x = x + attention(p, x, table,
                          cfg.sliding_window if kind == "sliding" else None, cfg)
    with jax.named_scope("moe"):
        y, stats = moe(p, x, cfg)
        return x + y, stats


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo",
              "moe_norm", "moe_router", "w_gate", "w_up", "w_down")


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config):
    """tokens (B, T) -> (logits (B, T, V) float32, per-layer statistics
    stacked on a leading layer axis). Per-layer parameters carry a leading
    layer axis (`LAYER_KEYS`)."""
    stats = []
    with jax.named_scope("mellum"):
        tables = rotary_tables(cfg, tokens.shape[1])
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for layer in range(cfg.num_hidden_layers):
            kind = cfg.kind(layer)
            x, s = jax.checkpoint(
                lambda p, x, table, kind=kind: block(p, x, table, kind, cfg),
                policy=pallas_attention.KEEP_RESIDUALS,
            )({k: params[k][layer] for k in LAYER_KEYS}, x, tables[kind])
            stats.append(s)
        with jax.named_scope("head_loss"):
            h = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
            logits = matmul(h, params["head"], jnp.dtype(cfg.compute_dtype), jnp.float32)
    return logits, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


def expert_assignments(params, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (layers, B·T, k), weights (the same), the residual stream
    each router saw (layers, B, T, C)). The head is dead code here."""
    stats = forward(params, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def kv_block_visits(cfg: Config, seq_len: int):
    """((2,) the (q block, kv block) pairs a head's forward grid computes in
    one step, summed over the layers of each kind [sliding, full]; (2,) what
    a causal grid would compute there)."""
    dt = jnp.dtype(cfg.compute_dtype)
    layers = [sum(cfg.kind(l) == kind for l in range(cfg.num_hidden_layers))
              for kind in KINDS]
    banded, causal = zip(*(
        pallas_attention.kv_block_visits(seq_len, seq_len, window, cfg.head_dim, dt)
        for window in (cfg.sliding_window, None)))
    return ([n * v for n, v in zip(layers, banded)],
            [n * v for n, v in zip(layers, causal)])


# ------------------------------------------------------------------ #
# The zoo contract


class Mellum(nn.Module):
    """Initialisation: normal(`initializer_range`) for every matrix, ones for
    every norm, normal(`embedding_initializer_range`) — one, as
    `torch.nn.Embedding` and T5 start — for the embedding, and
    normal(`residual_initializer_range`) — smaller by sqrt(2 x layers), the
    scaled initialisation of GPT-2 and Megatron-LM — for the two matrices that
    write to the residual stream, `wo` and `w_down`.

    With 0.02 everywhere the routers of a fresh model cannot tell tokens
    apart. Token ids follow Zipf's law, so a window's values average to nearly
    the SAME vector at every position (the frequent tokens' values, not
    noise); through `wo` at 0.02 that vector is several times a token's own
    embedding at 0.02, every router from the second layer on sees nearly one
    input, and one set of eight experts takes nearly every pair of a layer —
    another eight at every seed, so the share of the pairs that falls on the
    16 experts held here swings between a tenth and a half (PERF.md section 6,
    PR 36). With the embedding at one and the residual writes scaled, a
    token's embedding leads the stream, each token has its own experts, and
    the held share is a quarter at every seed, as a trained model's balanced
    routers give it."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, C, V, D = c.num_hidden_layers, c.hidden_size, c.vocab_size, c.head_dim
        H, Hkv, F, held = (c.num_attention_heads, c.num_key_value_heads,
                           c.moe_intermediate_size, c.num_experts)
        normal, ones = nn.initializers.normal(c.initializer_range), nn.initializers.ones
        residual = nn.initializers.normal(c.residual_initializer_range)
        shapes = {
            "embed": ((V, C), nn.initializers.normal(c.embedding_initializer_range)),
            "final_norm": ((C,), ones),
            "head": ((C, V), normal),
            "attn_norm": ((L, C), ones),
            "wq": ((L, C, H * D), normal), "wk": ((L, C, Hkv * D), normal),
            "wv": ((L, C, Hkv * D), normal), "wo": ((L, H * D, C), residual),
            "moe_norm": ((L, C), ones),
            "moe_router": ((L, C, c.all_experts), normal),
            "w_gate": ((L, held, C, F), normal), "w_up": ((L, held, C, F), normal),
            "w_down": ((L, held, F, C), residual),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape, dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        passes = counter("router_state", "held_passes", (L,))
        row_tiles = counter("router_state", "held_row_tiles", (L,))
        row_chunks = counter("router_state", "held_row_chunks", (L,))
        held_share = counter("router_state", "pairs_held_share", (L,), jnp.float32)
        visits = counter("attn", "kv_block_visits", (len(KINDS),))
        visits_causal = counter("attn", "kv_block_visits_causal", (len(KINDS),))
        logits, stats = forward(params, features, c)
        # overwrite, not flax's default append: the trainer threads mutable
        # collections through every step (see api.layers.MoE)
        self.sow("losses", "load_balance",
                 c.load_balance_coef * jnp.sum(stats["load_balance"]),
                 reduce_fn=lambda prev, new: new, init_fn=lambda: jnp.float32(0.0))
        if training and not self.is_initializing():
            idx, routing = stats["expert_idx"], c.routing
            passes.value = passes.value + held_passes(idx, routing)
            row_tiles.value = row_tiles.value + held_row_tiles(idx, routing)
            row_chunks.value = row_chunks.value + held_row_chunks(idx, routing)
            held_share.value = (pairs_on_held(idx, routing).astype(jnp.float32)
                                / (idx.shape[1] * idx.shape[2]))
            banded, causal = kv_block_visits(c, features.shape[1])
            visits.value = visits.value + jnp.asarray(banded, jnp.int32)
            visits_causal.value = visits_causal.value + jnp.asarray(causal, jnp.int32)
        return logits


def custom_model(**kwargs) -> Mellum:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Mellum(Config(**given))


# ModelSpec picks this up: the auxiliary term is sown already multiplied by
# its coefficient.
aux_loss_weight = 1.0


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B, T, V) +
    (B, T) -> (B,), as `loss` (to which the trainer adds the sown auxiliary
    term before it minimises) and again as `loss_ce`, which the step reports
    beside the sum."""
    with jax.named_scope("mellum/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs.astype(jnp.float32), labels.astype(jnp.int32)).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}
