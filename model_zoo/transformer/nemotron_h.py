"""Nemotron-H: a decoder-only LM whose layers are of three kinds — Mamba-2
state-space mixers, sparse-expert feed-forwards and grouped-query attention —
one mixer a layer, in the order its `hybrid_override_pattern` spells
(nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16, `model_type: nemotron_h`;
Nemotron-H, arXiv:2504.03624; Mamba-2, arXiv:2405.21060).

The mathematics is written ONCE, as pure functions over a dict of arrays —
`mamba`, `moe`, `attention`, `forward` — like `olmoe.py`; the flax module at
the bottom declares the parameters and owns the routers' selection bias.
Every block is `x ← x + mixer(rmsnorm(x))`, eps `layer_norm_epsilon`, no bias
anywhere except the convolution's (hidden C):

- `M`: `[z | xBC | dt] = h·W_in` (d_inner | d_inner + 2·G·N | H);
  `xBC ← silu(conv1d_causal(xBC) + b)` depthwise, kernel `conv_kernel`
  (`ops.ssm.causal_conv1d`: the kernels of `ops/pallas_conv1d.py` on a TPU;
  `router_state/kernel_convs` counts the convolutions that took them);
  `xBC → x (H heads × P), B, C (G groups × N)`, head i uses group i // (H/G);
  `Δ = softplus(dt + dt_bias)`, `a_t = exp(Δ_t·A)`, `A = −exp(A_log)`;
  `S_t = a_t S_{t-1} + Δ_t x_t ⊗ B_t`, `y_t = S_t C_t + D x_t`, computed in
  chunks of `chunk_size` (`ops.ssm.ssd_chunked`);
  `y ← rmsnorm_grouped(y · silu(z)) · w` over the G groups; output `y·W_out`.
- `E`: scores `sigmoid(h·W_r)` in float32 over ALL `router_experts`; the
  `num_experts_per_tok` with the largest `score + b` (b: the selection bias);
  weights `routed_scaling_factor · s_e / Σ_chosen s` (the bias does not
  weigh); expert e is `W_down,e relu(W_up,e h)²`; this chip holds experts
  `first_expert … first_expert + n_routed_experts − 1` and computes every
  pair routed to them (`ops.moe.dropless_moe`, `held`: one pass of
  `ops.moe.held_pass_rows` rows and a loop of more for what overflows it,
  through `ops.pallas_gmm`, which skips the row tiles past the last held pair;
  `router_state/held_passes` counts the passes,
  `router_state/held_row_tiles` the row tiles that held a pair and
  `router_state/held_row_chunks` the row chunks the combine's pull-back
  walked);
  what the other experts would add is left out; plus one shared expert of
  the same body on every token. b is no parameter: after each training step
  `b_e ← b_e + bias_update_speed · sign(mean load − load_e)` over this
  chip's tokens' choices among all experts (collection `router_state`, which
  the trainer threads through its steps as `extra_vars`). No auxiliary loss.
- `*`: `q = h·W_q` (H heads × D), `k, v = h·W_k, h·W_v` (Hkv heads × D),
  query head i attends with key-value head i // (H/Hkv), causal softmax at
  scale D^-1/2 (`ops.attention.full_attention`: the flash kernel on a TPU),
  `·W_o`. No rotary embedding, no QK-norm.
- embedding, final rmsnorm, an untied head, per-example mean next-token
  cross entropy.

Precision: parameters, gradients, norms, router, convolution, Δ, the decays
and the recurrence over chunks, softmaxes, residual stream and loss float32;
projections, expert matmuls and the scan's matmuls inside a chunk take
`compute_dtype` operands (bfloat16 on the chip) and accumulate in float32.
What a mixer adds to the residual stream is written in float32 as the matmul
accumulated it: a product rounded to `compute_dtype` and widened again is a
rounding XLA may skip in one consumer and keep in another
(`xla_allow_excess_precision`), and the residual stream a router saw would
then not be the one the step reports (PERF.md §6, PR 30).

Parameters are stacked per KIND of layer, flat names: `mamba_*` (number of M
layers, …), `attn_*` (number of * layers, …), and for the E layers `moe_norm`,
`moe_router`, `shared_up`, `shared_down`, `w_up`, `w_down` (the held routed
experts: (layers, experts, ., .), the names `benchmark/check_lm.py` judges
expert by expert).

Every block is recomputed in the backward pass (`jax.checkpoint` around each
mixer): of a block's activations only the residual stream it started from is
kept, so that one 8192-token sequence fits beside 667M parameters' optimizer
state on a 16 GB chip — and, by the policy `pallas_attention.KEEP_RESIDUALS`,
what the flash kernels' backward reads of an attention block: q, k, v, the
output and the logsumexp (277 MB at 8192 tokens), so that its recomputation
neither runs the forward kernel again nor rebuilds q, k and v (that module's
docstring says why it is all five or none); a Mamba block holds none of the
names and the policy is inert there. It is fixed here, not a setting.

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition; the optimizer (AdamW (0.9, 0.95), 1e-8, decay 0.1, linear
warm-up), the batch partition and the metric are `olmoe.py`'s, as is
`rmsnorm`. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_attention, ssm
from elasticdl_tpu.ops.attention import full_attention
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, eval_metrics_fn, optimizer, rmsnorm)
from model_zoo.transformer.transformer_lm import dataset_fn  # noqa: F401


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names. Three are
    this repo's: `router_experts` (how many experts the router chooses among;
    0: `n_routed_experts`, every expert held here), `first_expert` (the first
    of the `n_routed_experts` held here) and `bias_update_speed`."""

    vocab_size: int = 131072
    hidden_size: int = 2688
    num_hidden_layers: int = 52
    hybrid_override_pattern: str = (
        "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME")
    mamba_num_heads: int = 64
    mamba_head_dim: int = 64
    ssm_state_size: int = 128
    n_groups: int = 8
    conv_kernel: int = 4
    chunk_size: int = 128
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    n_routed_experts: int = 128        # the experts HELD here
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 6
    moe_intermediate_size: int = 1856
    moe_shared_expert_intermediate_size: int = 3712
    routed_scaling_factor: float = 2.5
    bias_update_speed: float = 1e-3
    layer_norm_epsilon: float = 1e-5
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        pattern = self.hybrid_override_pattern
        if len(pattern) != self.num_hidden_layers or set(pattern) - set("ME*"):
            raise ValueError(
                f"hybrid_override_pattern {pattern!r} does not spell "
                f"{self.num_hidden_layers} layers of kinds M, E, *")

    @property
    def d_inner(self) -> int:
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.n_routed_experts

    @property
    def held(self):
        return (self.first_expert, self.n_routed_experts)

    def layers_of(self, kind: str) -> int:
        return self.hybrid_override_pattern.count(kind)


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def matmul(x, w, dt, out=None):
    """x·w with `dt` operands; the MXU accumulates in float32, `out` is the
    dtype the product is written in (default: `dt`)."""
    return jnp.dot(x.astype(dt), w.astype(dt), preferred_element_type=out or dt)


def mamba(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The Mamba-2 mixer's update of the residual stream x (B, T, C)."""
    dt_c = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, hd, g, n = (cfg.mamba_num_heads, cfg.mamba_head_dim, cfg.n_groups,
                       cfg.ssm_state_size)
    h = rmsnorm(x, p["mamba_norm"], cfg.layer_norm_epsilon)
    with jax.named_scope("in_proj"):
        zxbcdt = matmul(h, p["mamba_in_proj"], dt_c, jnp.float32)
        z, xbc, dt = jnp.split(
            zxbcdt, [cfg.d_inner, cfg.d_inner + cfg.conv_dim], axis=-1)
    with jax.named_scope("conv"):
        xbc = jax.nn.silu(ssm.causal_conv1d(xbc, p["mamba_conv_w"], p["mamba_conv_b"]))
        xs, bmat, cmat = jnp.split(xbc, [cfg.d_inner, cfg.d_inner + g * n], axis=-1)
    with jax.named_scope("ssd"):
        xs = xs.reshape(b, t, heads, hd)
        delta = jax.nn.softplus(dt + p["mamba_dt_bias"])
        y = ssm.ssd_chunked(
            xs, delta, -jnp.exp(p["mamba_A_log"]), bmat.reshape(b, t, g, n),
            cmat.reshape(b, t, g, n), cfg.chunk_size, dt_c)
        y = (y + p["mamba_D"][:, None] * xs).reshape(b, t, cfg.d_inner)
    with jax.named_scope("gate_norm"):
        y = ssm.gated_group_rmsnorm(y, z, p["mamba_gate_norm"], g,
                                    cfg.layer_norm_epsilon)
    with jax.named_scope("out_proj"):
        return matmul(y, p["mamba_out_proj"], dt_c, jnp.float32)


def attention(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    """The grouped-query attention mixer's update of x (B, T, C)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    h = rmsnorm(x, p["attn_norm"], cfg.layer_norm_epsilon)
    q = matmul(h, p["attn_wq"], dt).reshape(b, t, cfg.num_attention_heads, cfg.head_dim)
    kv = (b, t, cfg.num_key_value_heads, cfg.head_dim)
    k = matmul(h, p["attn_wk"], dt).reshape(kv)
    v = matmul(h, p["attn_wv"], dt).reshape(kv)
    out = full_attention(q, k, v, causal=True)
    return matmul(out.reshape(b, t, -1), p["attn_wo"], dt, jnp.float32)


def route(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The router of one E layer on the residual stream x (B, T, C): (the
    normed tokens (N, C), weights (N, k), expert_idx (N, k))."""
    h = rmsnorm(x, p["moe_norm"], cfg.layer_norm_epsilon).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, weights, expert_idx = moe_ops.sigmoid_topk_route(
        logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    return h, weights, expert_idx


def relu2_expert(h, w_up, w_down, dt):
    """`W_down relu(W_up h)²`, the body of every expert, on all rows of h;
    float32 out."""
    up = matmul(h, w_up, dt, jnp.float32)
    return matmul(jnp.square(jax.nn.relu(up)), w_down, dt, jnp.float32)


def moe(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The expert mixer's update of x, and {"expert_idx", "weights",
    "router_input"} for the bias update, the counters and the benchmark's
    comparison of routing."""
    dt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("router"):
        h, weights, expert_idx = route(p, x, bias, cfg)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_up"], p["w_down"]), held=cfg.held,
        num_experts=cfg.num_experts, compute_dtype=dt)
    with jax.named_scope("shared"):
        y = y + relu2_expert(h, p["shared_up"], p["shared_down"], dt)
    return y.reshape(x.shape), {
        "expert_idx": expert_idx, "weights": weights, "router_input": x}


KEYS = {
    "M": ("mamba_norm", "mamba_in_proj", "mamba_conv_w", "mamba_conv_b",
          "mamba_dt_bias", "mamba_A_log", "mamba_D", "mamba_gate_norm",
          "mamba_out_proj"),
    "E": ("moe_norm", "moe_router", "shared_up", "shared_down", "w_up", "w_down"),
    "*": ("attn_norm", "attn_wq", "attn_wk", "attn_wv", "attn_wo"),
}
SCOPE = {"M": "mamba", "E": "moe", "*": "attn"}


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (E layers, router_experts) -> (logits (B, T, V)
    float32, the E layers' statistics stacked on a leading layer axis). The
    parameters of each kind carry a leading axis over that kind's layers; the
    pattern string says which kind comes when."""
    seen = {"M": 0, "E": 0, "*": 0}
    stats = []
    with jax.named_scope("nemotron_h"):
        x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for kind in cfg.hybrid_override_pattern:
            i = seen[kind]
            seen[kind] += 1
            p = {k: params[k][i] for k in KEYS[kind]}
            with jax.named_scope(SCOPE[kind]):
                if kind == "E":
                    y, s = jax.checkpoint(
                        lambda p, x, b: moe(p, x, b, cfg))(p, x, bias[i])
                    stats.append(s)
                else:
                    mixer = mamba if kind == "M" else attention
                    y = jax.checkpoint(lambda p, x: mixer(p, x, cfg),
                                       policy=pallas_attention.KEEP_RESIDUALS)(p, x)
                x = x + y
        with jax.named_scope("head_loss"):
            h = rmsnorm(x, params["final_norm"], cfg.layer_norm_epsilon)
            logits = matmul(h, params["head"], jnp.dtype(cfg.compute_dtype),
                            jnp.float32)
    return logits, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (E layers, B·T, k), weights (E layers, B·T, k), the residual
    stream each router saw (E layers, B, T, C)). The head is dead code here."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def updated_bias(bias, expert_idx, cfg: Config):
    """b_e + speed · sign(mean load − load_e): bias (E layers, experts),
    expert_idx (E layers, N, k), loads counted over all the experts the router
    chooses among."""
    load = jax.vmap(lambda idx: moe_ops.pairs_per_expert(idx, cfg.num_experts))(
        expert_idx).astype(jnp.float32)
    mean = jnp.mean(load, axis=-1, keepdims=True)
    return bias + cfg.bias_update_speed * jnp.sign(mean - load)


def pairs_on_held(expert_idx, cfg: Config):
    """(E layers,) int32: the (token, slot) pairs that fell on held experts."""
    first, count = cfg.held
    return jnp.sum((expert_idx >= first) & (expert_idx < first + count),
                   axis=(1, 2), dtype=jnp.int32)


def held_passes(expert_idx, cfg: Config):
    """(E layers,) int32: the passes the held dispatch ran at this routing,
    expert_idx (E layers, N, k) — one where the pairs on held experts fit a
    pass, none where every expert is held (no passes then) or no pair is."""
    first, count = cfg.held
    if count == cfg.num_experts:
        return jnp.zeros(expert_idx.shape[0], jnp.int32)
    pairs = expert_idx.shape[1] * expert_idx.shape[2]
    rows = moe_ops.held_pass_rows(pairs, cfg.num_experts, count)
    return -(-pairs_on_held(expert_idx, cfg) // rows)


def held_row_tiles(expert_idx, cfg: Config):
    """(E layers,) int32: the row tiles the held experts' grouped matmul
    visited at this routing (`ops.moe.held_row_tiles`), of `held_passes x
    held_pass_rows / row tile`: the rest were skipped. None where every
    expert is held."""
    if cfg.held[1] == cfg.num_experts:
        return jnp.zeros(expert_idx.shape[0], jnp.int32)
    return moe_ops.held_row_tiles(
        pairs_on_held(expert_idx, cfg), expert_idx.shape[1] * expert_idx.shape[2],
        cfg.num_experts, cfg.held[1])


def held_row_chunks(expert_idx, cfg: Config):
    """(E layers,) int32: the row chunks the held dispatch's combine walked
    at this routing — its pull-back, and in a small pass its scatter-add
    (`ops.moe.held_row_chunks`) — of `held_passes x held_pass_rows /
    held_row_chunk`: the share of a pass's rows that part of the XLA around
    the kernels still pays for. None where every expert is held."""
    if cfg.held[1] == cfg.num_experts:
        return jnp.zeros(expert_idx.shape[0], jnp.int32)
    return moe_ops.held_row_chunks(
        pairs_on_held(expert_idx, cfg), expert_idx.shape[1] * expert_idx.shape[2],
        cfg.num_experts, cfg.held[1])


def kernel_convs(cfg: Config, batch: int, seq_len: int) -> int:
    """The depthwise convolutions of one step's forward pass that take the
    Pallas kernels (`ops/pallas_conv1d.py`): one a Mamba layer where
    `ssm.conv_route` says "kernel" at this shape, none elsewhere."""
    route = ssm.conv_route((batch, seq_len, cfg.conv_dim), cfg.conv_kernel)
    return cfg.layers_of("M") if route == "kernel" else 0


# ------------------------------------------------------------------ #
# The zoo contract


def _dt_bias_init(cfg: Config):
    """Mamba-2's: Δ drawn log-uniform in [time_step_min, time_step_max],
    floored, and the bias its inverse softplus."""
    lo, hi = math.log(cfg.time_step_min), math.log(cfg.time_step_max)

    def init(key, shape, dtype):
        dt = jnp.exp(jax.random.uniform(key, shape, dtype) * (hi - lo) + lo)
        dt = jnp.maximum(dt, cfg.time_step_floor)
        return dt + jnp.log(-jnp.expm1(-dt))

    return init


def _a_log_init(key, shape, dtype):
    """Mamba-2's: A uniform in [1, 16], kept as its logarithm."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _uniform(bound):
    return lambda key, shape, dtype: jax.random.uniform(key, shape, dtype, -bound, bound)


class NemotronH(nn.Module):
    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        C, V = c.hidden_size, c.vocab_size
        M, E, A = c.layers_of("M"), c.layers_of("E"), c.layers_of("*")
        H = c.mamba_num_heads
        q_dim = c.num_attention_heads * c.head_dim
        kv_dim = c.num_key_value_heads * c.head_dim
        F, Fs, held = (c.moe_intermediate_size, c.moe_shared_expert_intermediate_size,
                       c.n_routed_experts)
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        conv = _uniform(c.conv_kernel ** -0.5)        # torch's Conv1d default
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), ones),
            "head": ((C, V), normal),
            "mamba_norm": ((M, C), ones),
            "mamba_in_proj": ((M, C, c.d_inner + c.conv_dim + H), normal),
            "mamba_conv_w": ((M, c.conv_kernel, c.conv_dim), conv),
            "mamba_conv_b": ((M, c.conv_dim), conv),
            "mamba_dt_bias": ((M, H), _dt_bias_init(c)),
            "mamba_A_log": ((M, H), _a_log_init),
            "mamba_D": ((M, H), ones),
            "mamba_gate_norm": ((M, c.d_inner), ones),
            "mamba_out_proj": ((M, c.d_inner, C), normal),
            "moe_norm": ((E, C), ones),
            "moe_router": ((E, C, c.num_experts), normal),
            "shared_up": ((E, C, Fs), normal), "shared_down": ((E, Fs, C), normal),
            "w_up": ((E, held, C, F), normal), "w_down": ((E, held, F, C), normal),
            "attn_norm": ((A, C), ones),
            "attn_wq": ((A, C, q_dim), normal), "attn_wk": ((A, C, kv_dim), normal),
            "attn_wv": ((A, C, kv_dim), normal), "attn_wo": ((A, q_dim, C), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        bias = self.variable("router_state", "e_score_correction_bias",
                             jnp.zeros, (E, c.num_experts), jnp.float32)
        passes = self.variable("router_state", "held_passes", jnp.zeros, (E,), jnp.int32)
        row_tiles = self.variable("router_state", "held_row_tiles", jnp.zeros, (E,), jnp.int32)
        row_chunks = self.variable("router_state", "held_row_chunks", jnp.zeros, (E,), jnp.int32)
        convs = self.variable("router_state", "kernel_convs", jnp.zeros, (), jnp.int32)
        logits, stats = forward(params, bias.value, features, c)
        if training and not self.is_initializing():
            bias.value = updated_bias(bias.value, stats["expert_idx"], c)
            passes.value = passes.value + held_passes(stats["expert_idx"], c)
            row_tiles.value = row_tiles.value + held_row_tiles(stats["expert_idx"], c)
            row_chunks.value = row_chunks.value + held_row_chunks(stats["expert_idx"], c)
            convs.value = convs.value + kernel_convs(c, *features.shape)
        return logits


def custom_model(**kwargs) -> NemotronH:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return NemotronH(Config(**given))


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B, T, V) +
    (B, T) -> (B,)."""
    with jax.named_scope("nemotron_h/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs.astype(jnp.float32), labels.astype(jnp.int32))
        return ce.mean(axis=-1)
