"""LFM2-MoE (`model_type: lfm2_moe`, LiquidAI/LFM2-8B-A1B): a decoder-only LM
whose token mixer is, in three layers of four, a DOUBLE-GATED SHORT
CONVOLUTION — a depthwise causal convolution of K = 3 taps between two
elementwise gates, all three from one projection — and in the fourth
grouped-query attention with q/k head norms and rotary positions; the two
leading layers have a dense SwiGLU feed-forward, the others 32 SwiGLU experts
behind a sigmoid router with a selection bias, top-4, no shared expert. The
head is the embedding itself.

The mathematics is written ONCE, as pure functions over a dict of arrays —
`short_conv`, `attention`, `dense_mlp`, `moe`, `layer`, `forward`. What other
models own is called, not copied: `ops.ssm.gated_short_conv` (and through it
`causal_conv1d`'s two routes), `ops.moe.sigmoid_topk_route` /
`dropless_moe(held=)`, `glm4_moe_lite.gated_mlp`, `nemotron_h.matmul` /
`updated_bias` and the held share's counters, `olmoe.rmsnorm` / `rope`,
`phi4flash.head_logits`. C = `hidden_size`, H query heads on Hkv key-value
heads of D = C / H, no bias anywhere; RMSNorm(x; w) = x · rsqrt(mean(x²) +
`norm_eps`) ⊙ w in float32. The layer of PUBLISHED index i (`kept_layers` lists
those built; its mixer is `layer_types[i]`, its feed-forward dense iff i <
`num_dense_layers`), on the float32 residual stream x (B, T, C):

    x ← x + Mixer_i(RMSNorm(x; operator_norm))
    x ← x + FF_i(RMSNorm(x; ffn_norm))

- gated short convolution (`conv`): `(B, G, u) = split₃(h W_in)` (C → 3C, in
  that order); `v = B ⊙ u`; `c_t = Σ_{j<K} w_j ⊙ v_{t−K+1+j}` (depthwise, one
  tap vector of C a j, zeros before the sequence, NO activation, K =
  `conv_L_cache`); `out = (G ⊙ c) W_out` (C → C).
- attention (`full_attention`): `q = h W_q` (H·D), `k = h W_k`, `v = h W_v`
  (Hkv·D); `q ← RMSNorm_D(q; q_layernorm)`, `k ← RMSNorm_D(k; k_layernorm)`,
  one weight of D each shared by the heads, BEFORE the rotation; rotary
  positions on all D dimensions, rotate-half, θ = `rope_theta`; causal
  softmax(q kᵀ / √D) v, query head h on key-value head h // (H / Hkv); `W_o`.
- dense feed-forward: `(silu(h W_1) ⊙ h W_3) W_2`, C → `intermediate_size` → C.
- sparse feed-forward: `s = sigmoid(h W_r)` (C → `router_experts`, float32);
  the `num_experts_per_tok` experts with the largest `s + b` (`use_expert_bias`:
  b selects and does not weigh); weights `s_chosen / (Σ s_chosen + 1e-6)`
  (`norm_topk_prob`) times `routed_scaling_factor`; `Σ_slots w · E_e(h)`, E_e a
  SwiGLU of width `moe_intermediate_size`. b is no parameter
  (`router_state/expert_bias`): after every training step `b_e ← b_e +
  bias_update_speed · sign(mean load − load_e)`.
- `logits = RMSNorm(x; embedding_norm) · Eᵀ`, E the embedding (the config
  calls the final norm `embedding_norm`); the loss is the mean next-token
  cross entropy, no auxiliary term. `loss` owns the head's matmul and makes
  the logits `HEAD_ROWS` positions at a time (the module's outputs are the
  final norm's output and the matrix): whole, the float32 logits and their
  cotangent do not fit beside the step at 32 768 positions.

What is *assumed* — not a key of `config.json`, from memory of the Hugging
Face `lfm2_moe` modelling file or from the model card — is listed in
`benchmark/configs/lfm2-8b-a1b.json`, `assumed`: the order (B, G, u), no
activation on the convolution, the head norms before the rotation, the
renormaliser's 1e-6, the tied head, the bias's update, the initialisation.

Precision (the configuration's `precision`): parameters, gradients, the
residual stream, every RMSNorm, the router (scores, selection, weights),
rotary positions, the gates' products and the convolution, attention's
softmax sums and the loss float32; the projections (W_in, W_out, q, k, v, o),
the dense layer, the experts' grouped matmuls and the head take
`compute_dtype` operands (bfloat16 on the chip) and accumulate float32; q, k, v
enter the flash kernels in `compute_dtype`.

Parameters are stacked per KIND, flat names: `operator_norm`, `ffn_norm` over
every layer built; `conv_in`, `conv_w`, `conv_out` over the convolution
layers; `wq`, `wk`, `wv`, `wo`, `q_norm`, `k_norm` over the attention layers;
`mlp_gate`, `mlp_up`, `mlp_down` over the dense ones; `moe_router` and the held
routed experts `w_gate`, `w_up`, `w_down` ((sparse layers, experts, ., .), the
names `benchmark/check_lm.py` judges expert by expert) over the sparse ones.

Every layer is recomputed in the backward pass (`jax.checkpoint` around the
pair of sub-blocks); an attention layer keeps its flash kernels' five
residuals (`pallas_attention.KEEP_RESIDUALS`), so its forward kernel runs once
a step.

Counters (`TrainState.extra_vars`): `router_state/held_passes`,
`held_row_tiles`, `held_row_chunks` (GLM's), `router_state/pairs_held_share`
(the last step's share of a sparse layer's pairs that fell on held experts),
`conv/kernel_convs` (the convolutions of the forward passes that took
`ops/pallas_conv1d.py`'s kernels: one a convolution layer and step on
`conv_route`'s "kernel" route, 0 on the plain one), `attn/kv_block_visits`
(the (q block, kv block) pairs a head's forward grid computes, summed over
steps and attention layers).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.ops.ssm import conv_route, gated_short_conv
from model_zoo.transformer.glm4_moe_lite import gated_mlp
from model_zoo.transformer.nemotron_h import (
    held_passes, held_row_chunks, held_row_tiles, matmul, pairs_on_held, updated_bias)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, optimizer, rmsnorm, rope)
from model_zoo.transformer.phi4flash import head_logits
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401

KINDS = ("conv", "full_attention")
_PUBLISHED_LAYER_TYPES = ",".join(
    "full_attention" if i in (2, 6, 10, 14, 18, 21) else "conv" for i in range(24))
# what the renormaliser adds to the chosen scores' sum (`norm_topk_prob`)
ROUTE_EPS = 1e-6
# positions of a block of the head's matmul and its cross entropy: `loss` makes
# 4096 x V logits at a time, forward and backward
HEAD_ROWS = 4096


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names (`layer_types`
    the published list, comma-separated, whatever is built), but for one: the
    model_params key `num_experts` — how many experts are HELD here, what a
    benchmark configuration's `reduced` cuts — is the field `held_experts`,
    and `num_experts` is what the ROUTER chooses among (`router_experts`, or
    all held), the name `nemotron_h.py`'s counters and the benchmark's drivers
    read it by (`afmoe.py` does the same). This repo's own: `kept_layers`,
    `router_experts`, `first_expert`, `bias_update_speed`, `compute_dtype`."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24         # the layers BUILT here
    kept_layers: str = ""               # their published indices, "0,2,3,4,5"; "": 0, 1, 2, ...
    layer_types: str = _PUBLISHED_LAYER_TYPES
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    conv_L_cache: int = 3
    rope_theta: float = 1e6
    held_experts: int = 32
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    routed_scaling_factor: float = 1.0
    bias_update_speed: float = 1e-3
    norm_eps: float = 1e-5
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.hidden_size % self.num_attention_heads:
            raise ValueError(f"{self.num_attention_heads} heads do not divide a hidden "
                             f"size of {self.hidden_size}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        layers, types = self.layers, self.layer_types.split(",")
        if len(layers) != self.num_hidden_layers or list(layers) != sorted(set(layers)):
            raise ValueError(f"kept_layers {self.kept_layers!r} does not list "
                             f"{self.num_hidden_layers} published layers in order")
        if layers and layers[-1] >= len(types):
            raise ValueError(f"layer {layers[-1]} is beyond the {len(types)} entries of "
                             "layer_types")
        unknown = sorted(set(types) - set(KINDS))
        if unknown:
            raise ValueError(f"layer_types holds {unknown}: a layer is one of {KINDS}")

    @property
    def layers(self) -> tuple:
        """The published index of every layer built."""
        if not self.kept_layers:
            return tuple(range(self.num_hidden_layers))
        return tuple(int(l) for l in self.kept_layers.split(","))

    def kind(self, layer: int) -> str:
        """Of the layer of PUBLISHED index `layer`: looked up, not computed
        (the published pattern is not periodic at its end)."""
        return self.layer_types.split(",")[layer]

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    def layers_of(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in self.layers)

    @property
    def dense_layers(self) -> int:
        return sum(self.is_dense(l) for l in self.layers)

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.held_experts

    @property
    def held(self):
        return (self.first_expert, self.held_experts)


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def gated_conv(bgu: jax.Array, weight: jax.Array) -> jax.Array:
    """G ⊙ conv_K(B ⊙ u) of the projection's three column blocks: the name
    `benchmark/rehearse/departures_lfm2_moe.py` patches."""
    return gated_short_conv(bgu, weight)


def short_conv(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The convolution sub-block's update of the residual stream x (B, T, C)."""
    dt = jnp.dtype(cfg.compute_dtype)
    h = rmsnorm(x, p["operator_norm"], cfg.norm_eps)
    with jax.named_scope("in_proj"):
        bgu = matmul(h, p["conv_in"], dt, jnp.float32)
    y = gated_conv(bgu, p["conv_w"])
    with jax.named_scope("out_proj"):
        return matmul(y, p["conv_out"], dt, jnp.float32)


def qk_norm(p, q, k, cfg: Config):
    """q (B, T, H, D), k (B, T, Hkv, D) float32, each head normalised over D
    with one weight vector for all heads."""
    return rmsnorm(q, p["q_norm"], cfg.norm_eps), rmsnorm(k, p["k_norm"], cfg.norm_eps)


def attention(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The attention sub-block's update of the residual stream x (B, T, C)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = rmsnorm(x, p["operator_norm"], cfg.norm_eps)
    with jax.named_scope("qkv"):
        q = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, d)
        k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
        v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
    with jax.named_scope("qk_norm"):
        q, k = qk_norm(p, q, k, cfg)
    with jax.named_scope("rope"):
        q, k = rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)
    with jax.named_scope("attn"):
        out = full_attention(q.astype(dt), k.astype(dt), v, causal=True)
    with jax.named_scope("out"):
        return matmul(out.reshape(b, t, heads * d), p["wo"], dt, jnp.float32)


def dense_mlp(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps)
    return gated_mlp(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                     jnp.dtype(cfg.compute_dtype))


def route(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The router of one sparse layer on the residual stream x (B, T, C):
    (the normed tokens (N, C), weights (N, k), expert_idx (N, k))."""
    h = rmsnorm(x, p["ffn_norm"], cfg.norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, weights, expert_idx = moe_ops.sigmoid_topk_route(
        logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor, eps=ROUTE_EPS)
    return h, weights, expert_idx


def moe(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The sparse feed-forward's update of x, and {"expert_idx", "weights",
    "router_input"} for the bias update, the counters and the benchmark's
    comparison of routing."""
    with jax.named_scope("router"):
        h, weights, expert_idx = route(p, x, bias, cfg)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_gate"], p["w_up"], p["w_down"]),
        held=cfg.held, num_experts=cfg.num_experts,
        compute_dtype=jnp.dtype(cfg.compute_dtype))
    return y.reshape(x.shape), {
        "expert_idx": expert_idx, "weights": weights, "router_input": x}


NORM_KEYS = ("operator_norm", "ffn_norm")
MIXER_KEYS = {"conv": ("conv_in", "conv_w", "conv_out"),
              "full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}
DENSE_KEYS = ("mlp_gate", "mlp_up", "mlp_down")
SPARSE_KEYS = ("moe_router", "w_gate", "w_up", "w_down")
MIXER_SCOPE = {"conv": "conv", "full_attention": "attn"}


def layer(p: Dict[str, jax.Array], x: jax.Array, bias, kind: str, cfg: Config):
    """One layer of `kind` on x (B, T, C) float32: (x, the routing's
    statistics of a sparse layer or None). `bias` None makes its feed-forward
    the dense one."""
    with jax.named_scope(MIXER_SCOPE[kind]):
        x = x + (short_conv if kind == "conv" else attention)(p, x, cfg)
    if bias is None:
        with jax.named_scope("dense_mlp"):
            return x + dense_mlp(p, x, cfg), None
    with jax.named_scope("moe"):
        y, stats = moe(p, x, bias, cfg)
        return x + y, stats


def layer_parameters(params: Dict[str, jax.Array], cfg: Config):
    """[(kind, sparse index or None, the layer's own parameters)] of the
    layers built, each leaf taken from the stack of its kind."""
    seen = {stack: 0 for stack in KINDS + ("dense", "sparse")}
    out = []
    for i, published in enumerate(cfg.layers):
        kind = cfg.kind(published)
        feed_forward = "dense" if cfg.is_dense(published) else "sparse"
        p = {k: params[k][i] for k in NORM_KEYS}
        p.update({k: params[k][seen[kind]] for k in MIXER_KEYS[kind]})
        p.update({k: params[k][seen[feed_forward]]
                  for k in (DENSE_KEYS if feed_forward == "dense" else SPARSE_KEYS)})
        out.append((kind, None if feed_forward == "dense" else seen["sparse"], p))
        seen[kind] += 1
        seen[feed_forward] += 1
    return out


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (sparse layers, router_experts) -> ({"hidden" (B, T,
    C) in `compute_dtype`: the final norm's output, the head's operand;
    "embed" (V, C): the head}, the sparse layers' statistics stacked on a
    leading axis, or None where no layer is sparse). The logits are `loss`'s
    to make, a block of positions at a time."""
    stats = []
    with jax.named_scope("lfm2"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for kind, sparse, p in layer_parameters(params, cfg):
            x, s = jax.checkpoint(
                lambda p, x, b, kind=kind: layer(p, x, b, kind, cfg),
                policy=pallas_attention.KEEP_RESIDUALS if kind == "full_attention" else None,
            )(p, x, None if sparse is None else bias[sparse])
            if s is not None:
                stats.append(s)
        with jax.named_scope("head_loss"):
            h = rmsnorm(x, params["embedding_norm"], cfg.norm_eps)
            outputs = {"hidden": h.astype(jnp.dtype(cfg.compute_dtype)),
                       "embed": params["embed"]}
    return outputs, (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)
                     if stats else None)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (sparse layers, B·T, k), weights (the same), the residual
    stream each router saw (sparse layers, B, T, C)). The head is dead code."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def kernel_convs(cfg: Config, batch: int, seq_len: int) -> int:
    """The depthwise convolutions of one step's forward pass that take the
    Pallas kernels (`ops/pallas_conv1d.py`): one a convolution layer where
    `conv_route` says "kernel" at this shape, none elsewhere."""
    route = conv_route((batch, seq_len, cfg.hidden_size), cfg.conv_L_cache)
    return cfg.layers_of("conv") if route == "kernel" else 0


def kv_block_visits(cfg: Config, seq_len: int) -> int:
    """The (q block, kv block) pairs a head's forward grid computes in one
    step, summed over the attention layers."""
    return cfg.layers_of("full_attention") * pallas_attention.kv_block_visits(
        seq_len, seq_len, None, cfg.head_dim, jnp.dtype(cfg.compute_dtype))[1]


# ------------------------------------------------------------------ #
# The zoo contract


def _taps(key, shape, dtype):
    """U(−1/√K, 1/√K): the framework's default for a depthwise convolution of
    fan-in K (`shape` (layers, K, C))."""
    bound = shape[1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


class Lfm2Moe(nn.Module):
    """Initialisation (`config.json` names none; `assumed` in the benchmark's
    configuration): normal(0.02) for every matrix and the embedding, ones for
    every norm, U(−1/√K, 1/√K) for the taps, zeros for the selection bias."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, Dn, S = c.num_hidden_layers, c.dense_layers, c.sparse_layers
        Cv, A = c.layers_of("conv"), c.layers_of("full_attention")
        C, V, D = c.hidden_size, c.vocab_size, c.head_dim
        H, Hkv = c.num_attention_heads, c.num_key_value_heads
        F, held = c.moe_intermediate_size, c.held_experts
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((V, C), normal), "embedding_norm": ((C,), ones),
            "operator_norm": ((L, C), ones), "ffn_norm": ((L, C), ones),
            "conv_in": ((Cv, C, 3 * C), normal), "conv_w": ((Cv, c.conv_L_cache, C), _taps),
            "conv_out": ((Cv, C, C), normal),
            "wq": ((A, C, H * D), normal), "wk": ((A, C, Hkv * D), normal),
            "wv": ((A, C, Hkv * D), normal), "wo": ((A, H * D, C), normal),
            "q_norm": ((A, D), ones), "k_norm": ((A, D), ones),
            "mlp_gate": ((Dn, C, c.intermediate_size), normal),
            "mlp_up": ((Dn, C, c.intermediate_size), normal),
            "mlp_down": ((Dn, c.intermediate_size, C), normal),
            "moe_router": ((S, C, c.num_experts), normal),
            "w_gate": ((S, held, C, F), normal), "w_up": ((S, held, C, F), normal),
            "w_down": ((S, held, F, C), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape, dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        bias = counter("router_state", "expert_bias", (S, c.num_experts), jnp.float32)
        passes = counter("router_state", "held_passes", (S,))
        row_tiles = counter("router_state", "held_row_tiles", (S,))
        row_chunks = counter("router_state", "held_row_chunks", (S,))
        held_share = counter("router_state", "pairs_held_share", (S,), jnp.float32)
        convs = counter("conv", "kernel_convs", ())
        visits = counter("attn", "kv_block_visits", ())
        outputs, stats = forward(params, bias.value, features, c)
        if training and not self.is_initializing():
            if stats is not None:
                idx = stats["expert_idx"]
                bias.value = updated_bias(bias.value, idx, c)
                passes.value = passes.value + held_passes(idx, c)
                row_tiles.value = row_tiles.value + held_row_tiles(idx, c)
                row_chunks.value = row_chunks.value + held_row_chunks(idx, c)
                held_share.value = (pairs_on_held(idx, c).astype(jnp.float32)
                                    / (idx.shape[1] * idx.shape[2]))
            convs.value = convs.value + kernel_convs(c, *features.shape)
            visits.value = visits.value + kv_block_visits(c, features.shape[1])
        return outputs


def custom_model(**kwargs) -> Lfm2Moe:
    """Keys are the published config's (`num_experts`: the experts held here,
    `Config.held_experts`); unknown keys (the harness adds its own to every
    model) are ignored."""
    kwargs = {("held_experts" if k == "num_experts" else k): v for k, v in kwargs.items()}
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Lfm2Moe(Config(**given))


def logits_of(outputs) -> jax.Array:
    """(B, T, V) float32, whole: the tied head on the final norm's output."""
    return head_logits(outputs["hidden"], outputs["embed"], outputs["hidden"].dtype)


def cross_entropy(hidden: jax.Array, embed: jax.Array, labels: jax.Array) -> jax.Array:
    """(B, T) float32 next-token cross entropy under the tied head, `HEAD_ROWS`
    positions at a time, forward and backward: at 32 768 positions and 16 384
    ids the whole float32 logits and their cotangent are two planes of 2 GiB,
    and the step's program then needs 16.6 GiB of the chip's 15.75 (the
    rehearsal's compile, PR 62); a block's are 256 MiB."""
    b, t, c = hidden.shape
    rows = min(HEAD_ROWS, t)
    pad = -t % rows
    by_block = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2)).reshape(
            (b, -1, rows) + a.shape[2:]), 1, 0)

    @jax.checkpoint
    def block(embed, h, own):
        logits = head_logits(h, embed, h.dtype)
        # the label's logit by a mask, not a gather: its backward is then
        # elementwise over the plane and fuses with the softmax's
        mask = own[..., None] == jnp.arange(logits.shape[-1], dtype=jnp.int32)
        picked = jnp.sum(jnp.where(mask, logits, 0.0), axis=-1)
        return jax.nn.logsumexp(logits, axis=-1) - picked

    ce = jax.lax.scan(lambda embed, args: (embed, block(embed, *args)), embed,
                      (by_block(hidden), by_block(labels.astype(jnp.int32))))[1]
    return jnp.moveaxis(ce, 0, 1).reshape(b, t + pad)[:, :t]


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B,), as `loss`
    and again as `loss_ce`, the one term the step reports beside it. The
    head's matmul is here, in row blocks (`cross_entropy`)."""
    with jax.named_scope("lfm2/head_loss"):
        ce = cross_entropy(outputs["hidden"], outputs["embed"], labels).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}


class HeadAccuracy(TokenAccuracy):
    """`TokenAccuracy` of the tied head's logits, made whole: an evaluation
    step has no backward pass to share the chip with."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(state, labels, logits_of(outputs), mask)


def eval_metrics_fn():
    return {"token_accuracy": HeadAccuracy()}
