"""Qwen3-Next (`model_type: qwen3_next`, Qwen/Qwen3-Next-80B-A3B-Instruct): a
decoder-only LM whose token mixer is, in three layers of four, a GATED
DELTANET (arXiv:2412.06464) — a delta rule with ONE decay a head, whose 16 key
heads are each read by two of the 32 value heads — and in the fourth a softmax
attention with an output gate carried by the query projection, q/k head norms
and rotary positions on a quarter of each head; every layer's feed-forward is
512 gated-SiLU experts of width 512 behind a softmax router (top-10,
renormalised) beside ONE shared expert multiplied by a sigmoid of its own.

The mathematics is written ONCE, as pure functions over a dict of arrays —
`gated_deltanet`, `attention`, `moe`, `layer`, `forward`. What other models own
is called, not copied: `ops.delta_rule.gated_delta_rule` (its scalar form),
`ops.ssm.causal_conv1d` (and its two routes), `ops.moe.topk_route` /
`router_aux_losses` / `dropless_moe(held=)`, `glm4_moe_lite.gated_mlp`,
`nemotron_h.matmul` and the held share's counters, `olmoe.rmsnorm` / `rope`,
`kimi_linear.l2_normalised`, `lfm2_moe.cross_entropy` (the head and its loss
in row blocks). C = `hidden_size`; no bias anywhere; `norm(x; w) = x ·
rsqrt(mean(x²) + rms_norm_eps) ⊙ (1 + w)` in float32, w starting at ZERO (this
family's norm; the gated norm below is the one exception). The layer of
PUBLISHED index i (`kept_layers` lists those built; its mixer is
`layer_types[i]`, or by `full_attention_interval` — every fourth attends —
where no list is given), on the float32 residual stream x (B, T, C):

    x ← x + Mixer_i(norm(x; mixer_norm))
    x ← x + MoE(norm(x; moe_norm))

- Gated DeltaNet (`linear_attention`; H_k = `linear_num_key_heads` of d_k,
  H_v = `linear_num_value_heads` of d_v, value head h reads key head ⌊h / r⌋,
  r = H_v / H_k): `[q | k | v | z] = h W_qkvz` (C → H_k d_k | H_k d_k | H_v d_v
  | H_v d_v, the four blocks contiguous IN THAT ORDER), `[b | a] = h W_ba`
  (C → H_v | H_v); `[q | k | v] ← silu(conv_K([q | k | v]))`, ONE depthwise
  causal convolution of K = `linear_conv_kernel_dim` taps, no bias; `q ← q /
  sqrt(Σ q² + 1e-6) · d_k^-1/2`, `k ← k / sqrt(Σ k² + 1e-6)` per head; `β =
  sigmoid(b)`, `g = −exp(A_log) ⊙ softplus(a + dt_bias)`, one each a value head;
  per value head, S (d_k, d_v) float32, S_0 = 0: `S_t = exp(g_t) S_{t−1} + β_t
  k_t (v_t − (exp(g_t) S_{t−1})ᵀ k_t)ᵀ`, `o_t = S_tᵀ q_t`
  (`ops.delta_rule.gated_delta_rule`, g (B, T, H_v): chunks of `gdn_chunk`
  tokens, a program's constant that changes no value); `y = rmsnorm_dv(o) ⊙
  w_norm ⊙ silu(z)` — the norm FIRST, then the gate, w_norm (d_v) starting at
  ones and used as it is —; `y W_out` (H_v d_v → C).
- gated attention (`full_attention`; H heads on Hkv key-value heads of D =
  `head_dim`): `[q | γ] = h W_q` (C → H × (D | D), a head's gate beside its
  query), `k = h W_k`, `v = h W_v`; `q ← norm_D(q; q_norm)`, `k ← norm_D(k;
  k_norm)`, the (1 + w) norm over the head; rotate-half positions on the first
  `partial_rotary_factor` · D dimensions of q and k (θ = `rope_theta`), the
  rest pass; causal softmax(q kᵀ / √D) v; `(o ⊙ sigmoid(γ)) W_o` (`afmoe.py`'s
  gate, with the gate's columns in the query's matrix).
- sparse feed-forward: `p = softmax(h W_r)` over all `router_experts`,
  float32; the `num_experts_per_tok` largest; weights `p_chosen / Σ p_chosen`
  (`norm_topk_prob`); `Σ_slots w · E_e(h)` over the experts HELD here, E_e a
  gated-SiLU unit of width `moe_intermediate_size`; plus `sigmoid(h w_s) ·
  E_shared(h)`, width `shared_expert_intermediate_size`. The auxiliary loss is
  `router_aux_loss_coef` · Σ_layers E Σ_e f_e P_e over all E experts
  (`ops.moe.router_aux_losses`), sown for the trainer.
- `logits = norm(x; final_norm) · W_headᵀ`, W_head (V, C) untied from the
  embedding; the loss is the mean next-token cross entropy. `loss` owns the
  head's matmul and makes the logits `lfm2_moe.HEAD_ROWS` positions at a time.

NO multi-token-prediction module is built: the published configuration has no
key for it.

What is *assumed* — not a key of `config.json`, from memory of the Hugging
Face `qwen3_next` modelling file — is listed in `benchmark/configs/
qwen3-next-80b-a3b.json`, `assumed`.

Precision (the configuration's `precision`): parameters, gradients, the
residual stream, every norm, the convolution and SiLU, the L2 norms, b, a
(their projection C × 2 H_v at the highest matmul precision), β, softplus, g,
every cumulative sum and exponential of it, the triangular inverse, the state S
and what is added to it, the gated norm, the router (logits at the highest
precision, softmax, selection, renormalised weights), the shared expert's gate,
rotary positions, attention's softmax sums and the loss float32; the
projections, the chunk's matmuls (the decay applied in float32 AFTER the
product or before the rounding), the experts, the shared expert and the head
take `compute_dtype` operands (bfloat16 on the chip) and accumulate float32;
q, k, v enter the flash kernels in `compute_dtype`.

Parameters are stacked per KIND, flat names: `mixer_norm`, `moe_norm`,
`moe_router`, `shared_*`, `w_gate`, `w_up`, `w_down` over every layer built
(the held routed experts (layers, experts, ., .): the names
`benchmark/check_lm.py` judges expert by expert); `gdn_*` over the Gated
DeltaNet layers; `wq`, `wk`, `wv`, `wo`, `q_norm`, `k_norm` over the attention
layers.

Every layer is recomputed in the backward pass (`jax.checkpoint` around the
pair of sub-blocks) under the policy of its KIND (`KEEP`): an attention layer
keeps its flash kernels' five residuals (`pallas_attention.RESIDUAL_NAMES`), a
Gated DeltaNet layer its recurrence's output and block-start states
(`delta_rule.RESIDUAL_NAMES`), so each forward sweep runs once a step.

Counters (`TrainState.extra_vars`): `router_state/held_passes`,
`held_row_tiles`, `held_row_chunks` (GLM's), and of the last step, a layer
each, `router_state/pairs_held_share`, `held_pairs_max`, `held_pairs_mean`,
`held_experts_empty`; `gdn/chunks`, `gdn/kernel_chunks`, `gdn/kernel_convs`
(Kimi's: the chunks walked, those of them by the Pallas kernels, the
convolutions that took the kernels), `gdn/log_decay_min`, `gdn/beta_mean`,
`gdn/state_rms` (the last step's, a Gated DeltaNet layer each);
`attn/kv_block_visits`. The outputs carry `gdn_stats` (B, 3), which the
evaluation metrics read.

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import delta_rule, moe as moe_ops, pallas_attention
from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.ops.ssm import causal_conv1d, conv_route
from model_zoo.transformer.glm4_moe_lite import gated_mlp
from model_zoo.transformer.kimi_linear import l2_normalised
from model_zoo.transformer.lfm2_moe import cross_entropy
from model_zoo.transformer.nemotron_h import (
    held_passes, held_row_chunks, held_row_tiles, matmul, pairs_on_held)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, optimizer, rmsnorm, rope)
from model_zoo.transformer.phi4flash import head_logits
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401
from model_zoo.transformer.xing4 import HyperConnectionMean

KINDS = ("linear_attention", "full_attention")
MIXER_SCOPE = {"linear_attention": "gdn", "full_attention": "attn"}
# what a recomputed layer keeps from its forward pass, by the kind of its mixer
KEEP = {"linear_attention": delta_rule.KEEP_RESIDUALS,
        "full_attention": pallas_attention.KEEP_RESIDUALS}
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names, but for one:
    the model_params key `num_experts` — how many experts are HELD here, what a
    benchmark configuration's `reduced` cuts — is the field `held_experts`,
    and `num_experts` is what the ROUTER chooses among (`router_experts`, or
    all held), the name `nemotron_h.py`'s counters and the benchmark's drivers
    read it by (`lfm2_moe.py` does the same). This repo's own: `kept_layers`,
    `layer_types` (the published list where a caller gives one; else every
    `full_attention_interval`-th layer attends), `router_experts`,
    `first_expert`, `router_aux_loss_coef`, the
    recurrence's `gdn_chunk` / `gdn_chunks_per_block` and `compute_dtype`."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48         # the layers BUILT here
    kept_layers: str = ""               # their published indices, "0,1,2,3"; "": 0, 1, 2, ...
    layer_types: str = ""               # the published list, comma-separated; "": by interval
    full_attention_interval: int = 4
    linear_conv_kernel_dim: int = 4
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    held_experts: int = 512
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    router_aux_loss_coef: float = 1e-3
    rms_norm_eps: float = 1e-6
    gdn_chunk: int = 64
    gdn_chunks_per_block: int = 4
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.linear_num_value_heads % self.linear_num_key_heads:
            raise ValueError(f"{self.linear_num_value_heads} value heads do not divide "
                             f"over {self.linear_num_key_heads} key heads")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.rotary_dim % 2 or not 0 < self.rotary_dim <= self.head_dim:
            raise ValueError(f"partial_rotary_factor {self.partial_rotary_factor} rotates "
                             f"{self.rotary_dim} of {self.head_dim} dimensions")
        layers = self.layers
        if len(layers) != self.num_hidden_layers or list(layers) != sorted(set(layers)):
            raise ValueError(f"kept_layers {self.kept_layers!r} does not list "
                             f"{self.num_hidden_layers} published layers in order")
        if self.layer_types:
            types = self.layer_types.split(",")
            if layers and layers[-1] >= len(types):
                raise ValueError(f"layer {layers[-1]} is beyond the {len(types)} entries "
                                 "of layer_types")
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
        """Of the layer of PUBLISHED index `layer`: looked up where a list is
        given, else every `full_attention_interval`-th attends (3, 7, 11, …)."""
        if self.layer_types:
            return self.layer_types.split(",")[layer]
        attends = (layer + 1) % self.full_attention_interval == 0
        return "full_attention" if attends else "linear_attention"

    def layers_of(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in self.layers)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor)

    @property
    def value_group(self) -> int:
        """Value heads that read one key head."""
        return self.linear_num_value_heads // self.linear_num_key_heads

    @property
    def key_width(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_width(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.held_experts

    @property
    def held(self):
        return (self.first_expert, self.held_experts)


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def norm(x: jax.Array, weight: jax.Array, eps: float) -> jax.Array:
    """This family's RMSNorm: the weight is kept as its distance from one."""
    return rmsnorm(x, 1.0 + weight, eps)


def qk_normalised(q: jax.Array, k: jax.Array):
    """q, k (B, T, H_k, d_k) as the recurrence takes them: each L2-normalised
    over a head's channels, q times d_k^-1/2. The name
    `benchmark/rehearse/departures_qwen3_next.py` patches."""
    return l2_normalised(q) * q.shape[-1] ** -0.5, l2_normalised(k)


def decay_and_strength(p: Dict[str, jax.Array], ba: jax.Array):
    """[b | a] (B, T, 2 H_v) float32 -> (g = −exp(A_log) ⊙ softplus(a +
    dt_bias) ≤ 0, β = sigmoid(b)), (B, T, H_v) each: ONE of each a value head."""
    b, a = jnp.split(ba, 2, axis=-1)
    return -jnp.exp(p["gdn_A_log"]) * jax.nn.softplus(a + p["gdn_dt_bias"]), jax.nn.sigmoid(b)


def recurrence(q, k, v, g, beta, cfg: Config):
    """The mixer's state update, (o (B, T, H_v, d_v), the last state): the
    name `benchmark/rehearse/departures_qwen3_next.py` patches."""
    return delta_rule.gated_delta_rule(
        q, k, v, g, beta, chunk=cfg.gdn_chunk, chunks_per_block=cfg.gdn_chunks_per_block,
        compute_dtype=jnp.dtype(cfg.compute_dtype))


def gated_norm(p: Dict[str, jax.Array], o: jax.Array, z: jax.Array, cfg: Config) -> jax.Array:
    """rmsnorm_dv(o) ⊙ w_norm ⊙ silu(z): o, z (B, T, H_v, d_v); the norm FIRST,
    then the gate (`ops.ssm.gated_group_rmsnorm` is the other order, Mamba-2's);
    the weight of d_v is shared by the heads and used as it is."""
    return rmsnorm(o, p["gdn_onorm"], cfg.rms_norm_eps) * jax.nn.silu(z)


def gated_deltanet(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The Gated DeltaNet sub-block's update of the residual stream x (B, T,
    C), and per example (B, 3): the most negative in-chunk cumulative
    log-decay, the mean write strength, the RMS of the state after the last
    token."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    kw, vw = cfg.key_width, cfg.value_width
    h = norm(x, p["mixer_norm"], cfg.rms_norm_eps)
    with jax.named_scope("proj"):
        qkvz = matmul(h, p["gdn_qkvz"], dt, jnp.float32)
        ba = jnp.dot(h, p["gdn_ba"], precision=_HIGHEST)
    with jax.named_scope("conv"):
        qkv = jax.nn.silu(causal_conv1d(qkvz[..., :2 * kw + vw], p["gdn_conv"]))
    with jax.named_scope("qk_norm"):
        q, k = qk_normalised(qkv[..., :kw].reshape(b, t, hk, -1),
                             qkv[..., kw:2 * kw].reshape(b, t, hk, -1))
    with jax.named_scope("gates"):
        g, beta = decay_and_strength(p, ba)
    with jax.named_scope("delta_rule"):
        o, last = recurrence(q, k, qkv[..., 2 * kw:].reshape(b, t, hv, -1), g, beta, cfg)
    with jax.named_scope("gate_norm"):
        y = gated_norm(p, o, qkvz[..., 2 * kw + vw:].reshape(o.shape), cfg)
    with jax.named_scope("out"):
        update = matmul(y.reshape(b, t, vw), p["gdn_wo"], dt, jnp.float32)
    with jax.named_scope("counters"):
        pad = -t % cfg.gdn_chunk
        in_chunk = jnp.pad(g, ((0, 0), (0, pad), (0, 0))).reshape(
            b, -1, cfg.gdn_chunk, hv).sum(axis=2)
        stats = jnp.stack([jnp.min(in_chunk, axis=(1, 2)), jnp.mean(beta, axis=(1, 2)),
                           jnp.sqrt(jnp.mean(last * last, axis=(1, 2, 3)))], axis=-1)
    return update, jax.lax.stop_gradient(stats)


def qk_norm(p, q, k, cfg: Config):
    """q (B, T, H, D), k (B, T, Hkv, D) float32, each head normalised over D
    with one (1 + w) weight vector for all heads."""
    return norm(q, p["q_norm"], cfg.rms_norm_eps), norm(k, p["k_norm"], cfg.rms_norm_eps)


def partial_rope(x: jax.Array, cfg: Config) -> jax.Array:
    """Rotary positions on the first `rotary_dim` dimensions of every head of
    x (B, T, H, D) — rotate-half WITHIN them, pairs (i, i + rotary_dim / 2) —
    and the rest as they are."""
    rot = cfg.rotary_dim
    return jnp.concatenate([rope(x[..., :rot], cfg.rope_theta), x[..., rot:]], axis=-1)


def attention(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The attention sub-block's update of the residual stream x (B, T, C)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = norm(x, p["mixer_norm"], cfg.rms_norm_eps)
    with jax.named_scope("proj"):
        q_gate = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, 2 * d)
        q, gate = q_gate[..., :d], q_gate[..., d:]
        k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
        v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
    with jax.named_scope("qk_norm"):
        q, k = qk_norm(p, q, k, cfg)
    with jax.named_scope("rope"):
        q, k = partial_rope(q, cfg), partial_rope(k, cfg)
    with jax.named_scope("flash"):
        out = full_attention(q.astype(dt), k.astype(dt), v, causal=True)
    with jax.named_scope("gate"):
        gated = out.astype(jnp.float32) * jax.nn.sigmoid(gate)
    with jax.named_scope("out"):
        return matmul(gated.reshape(b, t, heads * d), p["wo"], dt, jnp.float32)


def route(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The router of one layer on the residual stream x (B, T, C): (the
    normed tokens (N, C), logits (N, E) float32, probs, weights (N, k)
    renormalised to sum to one, expert_idx (N, k))."""
    h = norm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32), precision=_HIGHEST)
    probs, weights, expert_idx = moe_ops.topk_route(logits, cfg.num_experts_per_tok)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return h, logits, probs, weights, expert_idx


def shared_expert(p: Dict[str, jax.Array], h: jax.Array, cfg: Config) -> jax.Array:
    """sigmoid(h w_s) · E_shared(h) on the normed tokens h (N, C): what every
    chip of the deployment computes alike."""
    gate = jax.nn.sigmoid(jnp.dot(h, p["shared_expert_gate"], precision=_HIGHEST))
    return gate * gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"],
                            jnp.dtype(cfg.compute_dtype))


def moe(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The feed-forward's update of x, and {"load_balance", "expert_idx",
    "weights", "router_input"} for the auxiliary loss, the counters and the
    benchmark's comparison of routing."""
    with jax.named_scope("router"):
        h, logits, probs, weights, expert_idx = route(p, x, cfg)
        balance, _ = moe_ops.router_aux_losses(logits, probs, expert_idx)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_gate"], p["w_up"], p["w_down"]),
        held=cfg.held, num_experts=cfg.num_experts,
        compute_dtype=jnp.dtype(cfg.compute_dtype))
    with jax.named_scope("shared"):
        y = y + shared_expert(p, h, cfg)
    return y.reshape(x.shape), {
        "load_balance": balance, "expert_idx": expert_idx, "weights": weights,
        "router_input": x}


LAYER_KEYS = ("mixer_norm", "moe_norm", "moe_router", "shared_gate", "shared_up",
              "shared_down", "shared_expert_gate", "w_gate", "w_up", "w_down")
MIXER_KEYS = {"linear_attention": ("gdn_qkvz", "gdn_ba", "gdn_conv", "gdn_A_log",
                                   "gdn_dt_bias", "gdn_onorm", "gdn_wo"),
              "full_attention": ("wq", "wk", "wv", "wo", "q_norm", "k_norm")}


def layer(p: Dict[str, jax.Array], x: jax.Array, kind: str, cfg: Config):
    """One layer of `kind` on x (B, T, C) float32: (x, the routing's
    statistics, `gated_deltanet`'s (B, 3) or None)."""
    gdn_stats = None
    with jax.named_scope(MIXER_SCOPE[kind]):
        if kind == "linear_attention":
            update, gdn_stats = gated_deltanet(p, x, cfg)
        else:
            update = attention(p, x, cfg)
        x = x + update
    with jax.named_scope("moe"):
        y, stats = moe(p, x, cfg)
        return x + y, stats, gdn_stats


def layer_parameters(params: Dict[str, jax.Array], cfg: Config):
    """[(kind, the layer's own parameters)] of the layers built, each leaf
    taken from the stack of its kind."""
    seen = {kind: 0 for kind in KINDS}
    out = []
    for i, published in enumerate(cfg.layers):
        kind = cfg.kind(published)
        p = {k: params[k][i] for k in LAYER_KEYS}
        p.update({k: params[k][seen[kind]] for k in MIXER_KEYS[kind]})
        seen[kind] += 1
        out.append((kind, p))
    return out


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config):
    """tokens (B, T) -> ({"hidden" (B, T, C) in `compute_dtype`: the final
    norm's output, the head's operand; "head" (V, C); "gdn_stats" (B, 3)}, the
    layers' statistics stacked on a leading axis, the Gated DeltaNet layers'
    (layers, B, 3)). The logits are `loss`'s to make, a block of positions at a
    time."""
    stats, gdn_stats = [], []
    with jax.named_scope("qwen3_next"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for kind, p in layer_parameters(params, cfg):
            x, s, gs = jax.checkpoint(
                lambda p, x, kind=kind: layer(p, x, kind, cfg), policy=KEEP[kind])(p, x)
            stats.append(s)
            if gs is not None:
                gdn_stats.append(gs)
        with jax.named_scope("head_loss"):
            h = norm(x, params["final_norm"], cfg.rms_norm_eps)
            outputs = {"hidden": h.astype(jnp.dtype(cfg.compute_dtype)),
                       "head": params["head"]}
    # (layers, B, 3); one row of zeros where no layer built is a Gated DeltaNet
    by_layer = (jnp.stack(gdn_stats) if gdn_stats
                else jnp.zeros((1, tokens.shape[0], 3), jnp.float32))
    outputs["gdn_stats"] = jnp.stack([jnp.min(by_layer[..., 0], axis=0),
                                      jnp.mean(by_layer[..., 1], axis=0),
                                      jnp.mean(by_layer[..., 2], axis=0)], axis=-1)
    return outputs, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats), by_layer


def expert_assignments(params, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (layers, B·T, k), weights (the same), the residual stream
    each router saw (layers, B, T, C)). The head is dead code here."""
    stats = forward(params, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def chunks_walked(cfg: Config, batch: int, seq_len: int) -> int:
    """The chunks the recurrence walks in one step: layers x batch x value
    heads x ceil(T / chunk)."""
    return (cfg.layers_of("linear_attention") * batch * cfg.linear_num_value_heads
            * -(-seq_len // cfg.gdn_chunk))


def kernel_chunks_walked(cfg: Config, batch: int, seq_len: int) -> int:
    """Those of them the Pallas kernels walk: all where the recurrence takes
    its kernel route at this shape, none where it takes the plain one."""
    shape = (batch, seq_len, cfg.linear_num_key_heads, cfg.linear_key_head_dim)
    route = delta_rule.delta_rule_route(
        shape, cfg.gdn_chunk, cfg.gdn_chunks_per_block, cfg.linear_value_head_dim,
        "scalar", cfg.value_group)
    return chunks_walked(cfg, batch, seq_len) if route == "kernel" else 0


def kernel_convs(cfg: Config, batch: int, seq_len: int) -> int:
    """The depthwise convolutions of one step's forward pass that take the
    Pallas kernels (`ops/pallas_conv1d.py`): ONE a Gated DeltaNet layer, over
    q, k and v's channels together, where `conv_route` says "kernel"."""
    shape = (batch, seq_len, 2 * cfg.key_width + cfg.value_width)
    route = conv_route(shape, cfg.linear_conv_kernel_dim)
    return cfg.layers_of("linear_attention") if route == "kernel" else 0


def kv_block_visits(cfg: Config, seq_len: int) -> int:
    """The (q block, kv block) pairs a head's forward grid computes in one
    step, summed over the attention layers."""
    return cfg.layers_of("full_attention") * pallas_attention.kv_block_visits(
        seq_len, seq_len, None, cfg.head_dim, jnp.dtype(cfg.compute_dtype))[1]


def held_pairs(expert_idx, cfg: Config):
    """(layers, held) int32: the (token, slot) pairs of each held expert."""
    first, count = cfg.held
    return jax.vmap(lambda idx: moe_ops.pairs_per_expert(idx - first, count))(expert_idx)


# ------------------------------------------------------------------ #
# The zoo contract


def _a_log_init(key, shape, dtype):
    """log of A drawn uniform in (0, 16]."""
    return jnp.log(jax.random.uniform(key, shape, dtype, jnp.finfo(dtype).tiny, 16.0))


class Qwen3Next(nn.Module):
    """Initialisation (`assumed` in the benchmark's configuration):
    normal(0.02) for every matrix, the taps and the embedding,
    ZEROS for every (1 + w) norm, ones for the gated norm's weight and
    `dt_bias`, `A_log` the logarithm of a uniform draw in (0, 16]."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, G, A = (c.num_hidden_layers, c.layers_of("linear_attention"),
                   c.layers_of("full_attention"))
        C, V, D = c.hidden_size, c.vocab_size, c.head_dim
        H, Hkv, Hv = c.num_attention_heads, c.num_key_value_heads, c.linear_num_value_heads
        kw, vw = c.key_width, c.value_width
        F, Fs, held = c.moe_intermediate_size, c.shared_expert_intermediate_size, c.held_experts
        normal = nn.initializers.normal(0.02)
        ones, zeros = nn.initializers.ones, nn.initializers.zeros
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), zeros), "head": ((V, C), normal),
            "mixer_norm": ((L, C), zeros), "moe_norm": ((L, C), zeros),
            "gdn_qkvz": ((G, C, 2 * kw + 2 * vw), normal), "gdn_ba": ((G, C, 2 * Hv), normal),
            "gdn_conv": ((G, c.linear_conv_kernel_dim, 2 * kw + vw), normal),
            "gdn_A_log": ((G, Hv), _a_log_init), "gdn_dt_bias": ((G, Hv), ones),
            "gdn_onorm": ((G, c.linear_value_head_dim), ones), "gdn_wo": ((G, vw, C), normal),
            "wq": ((A, C, H * 2 * D), normal), "wk": ((A, C, Hkv * D), normal),
            "wv": ((A, C, Hkv * D), normal), "wo": ((A, H * D, C), normal),
            "q_norm": ((A, D), zeros), "k_norm": ((A, D), zeros),
            "moe_router": ((L, C, c.num_experts), normal),
            "shared_gate": ((L, C, Fs), normal), "shared_up": ((L, C, Fs), normal),
            "shared_down": ((L, Fs, C), normal), "shared_expert_gate": ((L, C, 1), normal),
            "w_gate": ((L, held, C, F), normal), "w_up": ((L, held, C, F), normal),
            "w_down": ((L, held, F, C), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape, dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        passes = counter("router_state", "held_passes", (L,))
        row_tiles = counter("router_state", "held_row_tiles", (L,))
        row_chunks = counter("router_state", "held_row_chunks", (L,))
        last_routing = {name: counter("router_state", name, (L,), dtype) for name, dtype in (
            ("pairs_held_share", jnp.float32), ("held_pairs_max", jnp.int32),
            ("held_pairs_mean", jnp.float32), ("held_experts_empty", jnp.int32))}
        chunks = counter("gdn", "chunks", ())
        kernel_chunks = counter("gdn", "kernel_chunks", ())
        convs = counter("gdn", "kernel_convs", ())
        last_step = {name: counter("gdn", name, (G,), jnp.float32)
                     for name in ("log_decay_min", "beta_mean", "state_rms")}
        visits = counter("attn", "kv_block_visits", ())
        outputs, stats, gdn_stats = forward(params, features, c)
        # overwrite, not flax's default append: the trainer threads mutable
        # collections through every step (see api.layers.MoE)
        self.sow("losses", "load_balance",
                 c.router_aux_loss_coef * jnp.sum(stats["load_balance"]),
                 reduce_fn=lambda prev, new: new, init_fn=lambda: jnp.float32(0.0))
        if training and not self.is_initializing():
            idx = stats["expert_idx"]
            passes.value = passes.value + held_passes(idx, c)
            row_tiles.value = row_tiles.value + held_row_tiles(idx, c)
            row_chunks.value = row_chunks.value + held_row_chunks(idx, c)
            each = held_pairs(idx, c)
            last_routing["pairs_held_share"].value = (
                pairs_on_held(idx, c).astype(jnp.float32) / (idx.shape[1] * idx.shape[2]))
            last_routing["held_pairs_max"].value = jnp.max(each, axis=1)
            last_routing["held_pairs_mean"].value = jnp.mean(each.astype(jnp.float32), axis=1)
            last_routing["held_experts_empty"].value = jnp.sum(each == 0, axis=1, dtype=jnp.int32)
            chunks.value = chunks.value + chunks_walked(c, *features.shape)
            kernel_chunks.value = kernel_chunks.value + kernel_chunks_walked(c, *features.shape)
            convs.value = convs.value + kernel_convs(c, *features.shape)
            visits.value = visits.value + kv_block_visits(c, features.shape[1])
            if G:
                last_step["log_decay_min"].value = jnp.min(gdn_stats[..., 0], axis=1)
                last_step["beta_mean"].value = jnp.mean(gdn_stats[..., 1], axis=1)
                last_step["state_rms"].value = jnp.mean(gdn_stats[..., 2], axis=1)
        return outputs


def custom_model(**kwargs) -> Qwen3Next:
    """Keys are the published config's (`num_experts`: the experts held here,
    `Config.held_experts`); unknown keys (the harness adds its own to every
    model) are ignored."""
    kwargs = {("held_experts" if k == "num_experts" else k): v for k, v in kwargs.items()}
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Qwen3Next(Config(**given))


# ModelSpec picks this up: the auxiliary term is sown already multiplied by
# its coefficient.
aux_loss_weight = 1.0


def logits_of(outputs) -> jax.Array:
    """(B, T, V) float32, whole: the head on the final norm's output."""
    return head_logits(outputs["hidden"], outputs["head"], outputs["hidden"].dtype)


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B,), as `loss` (to
    which the trainer adds the sown auxiliary term before it minimises) and
    again as `loss_ce`, which the step reports beside the sum. The head's
    matmul is here, in row blocks (`lfm2_moe.cross_entropy`: 16 384 x 18 992
    float32 logits are 1.24 GB, and their cotangent beside them)."""
    with jax.named_scope("qwen3_next/head_loss"):
        ce = cross_entropy(outputs["hidden"], outputs["head"], labels).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}


class HeadAccuracy(TokenAccuracy):
    """`TokenAccuracy` of the head's logits, made whole: an evaluation step
    has no backward pass to share the chip with."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(state, labels, logits_of(outputs), mask)


class GdnStat(HyperConnectionMean):
    """The mean over examples of one column of `gdn_stats`."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(state, labels, {"mhc_stats": outputs["gdn_stats"]}, mask)


def eval_metrics_fn():
    return {"token_accuracy": HeadAccuracy(),
            "gdn_log_decay_min": GdnStat(0), "gdn_beta_mean": GdnStat(1),
            "gdn_state_rms": GdnStat(2)}
