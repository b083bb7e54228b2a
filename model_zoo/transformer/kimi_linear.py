"""Kimi Linear (`model_type: kimi_linear`, moonshotai/Kimi-Linear-48B-A3B-
Instruct; "Kimi Linear: An Expressive, Efficient Attention Architecture",
arXiv:2510.26692): a decoder-only LM whose mixers are of two kinds — a
delta-rule linear attention with a decay for every CHANNEL (Kimi Delta
Attention, KDA) in three layers of four, latent attention WITHOUT positions in
the fourth — over DeepSeek-V3's feed-forwards: a leading dense layer, then
sparse-expert layers behind a sigmoid router with a selection bias, plus a
shared expert.

The mathematics is written ONCE, as pure functions over a dict of arrays —
`kda`, `dense_mlp`, `moe`, `layer`, `forward`. What is DeepSeek-V3's is
`glm4_moe_lite.py`'s and is called, not copied: `latent_attention` (given no
`q_a` — `q_lora_rank` null, the query ONE projection with no norm — and the
identity for its rotary map), `dense_mlp`, `moe` (with `route`, `gated_mlp`,
`ops.moe.dropless_moe(held=)`); the bias update and the held share's counters
are `nemotron_h.py`'s, as its initialisation of `A_log` and `dt_bias`; the
recurrence is `ops/delta_rule.py`'s. A layer is two residual sub-blocks, `x ←
x + Mixer(rmsnorm(x))`, `x ← x + FFN(rmsnorm(x))`, float32 stream, eps
`rms_norm_eps`, no bias anywhere. Layers are numbered from ONE, as the
published lists number them: layer l is a KDA layer if l is in `kda_layers`,
a latent one if in `full_attn_layers`; its feed-forward is dense if l ≤
`first_k_dense_replace`, sparse otherwise.

The KDA mixer (H = `linear_num_heads` heads of d = `linear_head_dim`, P = H·d;
h the normed input):

- `q~, k~, v~ = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))`:
  three depthwise causal convolutions of width `short_conv_kernel_size` over
  P channels, no bias (`ops.ssm.causal_conv1d`);
- `q = d^-1/2 · q~ / sqrt(Σ q~² + 1e-6)`, `k = k~ / sqrt(Σ k~² + 1e-6)`, the
  sums over a head's d channels;
- the decay, one number a CHANNEL: `a = (h W_f↓) W_f↑` (C → d → P), `g =
  −exp(A_log[head]) · softplus(a + dt_bias)`, the state's channels multiplied
  by `exp(g)` ∈ (0, 1) every token;
- the write strength `β = sigmoid(h W_β)` (C → H, one a head);
- per head, S_0 = 0, S (d, d) float32: `S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t)
  S_{t−1} + β_t k_t v_tᵀ`, `o_t = S_tᵀ q_t` (`ops.delta_rule.gated_delta_rule`:
  chunks of `kda_chunk` tokens, a program's constant that changes no value);
- the output gate `z = (h W_g↓) W_g↑` (C → d → P): `y = rmsnorm_d(o) ⊙
  sigmoid(z)`, the norm over each head's d channels with ONE weight of d
  shared by the heads; then `y W_o` (P → C).

Latent attention (GLM's function): `q = h W_q` → H × (nope | rope);
`[c_kv | k_r] = h W_kva`; `c_kv ← rmsnorm(c_kv)`; `[k_nope | v] = c_kv W_kvb`;
`k = [k_nope | k_r]`, k_r ONE head that all H use; NOTHING is rotated
(`mla_use_nope`: the `qk_rope_head_dim` channels stay, as plain channels);
causal softmax at scale (nope + rope)^-1/2; `W_o`.

What is *assumed* — not in `config.json`, from memory of the report and of the
published modelling file — is listed in `benchmark/configs/
kimi-linear-48b-a3b.json`, `assumed`: SiLU after the convolutions and no
convolution bias, the L2 norm's eps and the d^-1/2 on q, the rank d of both
low-rank gates, `A_log` a head and `dt_bias` a channel with Mamba's
initialisation, the output norm's one shared weight and the sigmoid gate, no
query norm in the latent layers, the selection bias and its update, no
auxiliary loss, the initialisation of the matrices.

Precision (the configuration's `precision`): parameters, gradients, the
residual stream, every RMSNorm (the per-head output norm and the latent one
among them), the L2 norms of q and k, the convolutions and SiLU, `a`,
softplus, `g`, every cumulative sum and exponential of it, β (its projection
too, at the highest matmul precision: C × H), the triangular inverse, the
state S and what is added to it, the router, the softmax's running sums and
the loss float32; the projections, the low-rank gates' matmuls, the chunk's
matmuls (operands rounded AFTER the decay has been applied in float32), the
experts, the shared expert, the dense layer and the head take `compute_dtype`
operands (bfloat16 on the chip) and accumulate float32; q, k, v enter the
flash kernels in `compute_dtype`.

Parameters are stacked per KIND, flat names: `kda_*` over the KDA layers,
`attn_norm`, `q_proj`, `kv_a`, `kv_a_norm`, `kv_b`, `wo` over the latent ones,
`mlp_*` over the dense layers, GLM's `SPARSE_KEYS` over the sparse ones (the
held routed experts `w_gate`, `w_up`, `w_down`: (sparse layers, experts, ., .),
the names `benchmark/check_lm.py` judges expert by expert).

Every layer is recomputed in the backward pass (`jax.checkpoint` around the
pair of sub-blocks) under `KEEP`: of a layer the residual stream it started
from is kept and, by name, what is dear to make twice — the flash kernels'
five residuals of a latent layer (`pallas_attention.RESIDUAL_NAMES`), a KDA
layer's recurrence output o (B, T, P float32: 268 MB at 16 384 tokens) and the
states its blocks of chunks start from (`delta_rule.RESIDUAL_NAMES`: 2 MB a
block, 134 MB a layer at blocks of 4 chunks) — so the recurrence's forward sweep runs once a step and its backward
recomputes one block's chunk algebra at a time.

Counters (`TrainState.extra_vars`): `router_state/held_passes`,
`held_row_tiles`, `held_row_chunks` (GLM's); `kda/chunks` (the chunks walked,
summed over steps, layers, batch and heads), `kda/kernel_chunks` (those of
them walked by the Pallas kernels: all of them on `delta_rule_route`'s
"kernel" route, 0 on the plain one), `kda/kernel_convs` (the depthwise
convolutions of the forward passes that took `ops/pallas_conv1d.py`'s kernels:
three a KDA layer and step on `ops.ssm.conv_route`'s "kernel" route, 0 on the
plain one), and of the last step, a KDA
layer each: `kda/log_decay_min` (the most negative in-chunk Γ: how near the
decay runs to underflow), `kda/beta_mean`, `kda/state_rms` (the state after the
last token). The outputs carry `kda_stats` (B, 3) — those three, the worst or
the mean over the layers — which the evaluation metrics read.

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
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

from elasticdl_tpu.ops import delta_rule, pallas_attention
from elasticdl_tpu.ops.ssm import causal_conv1d, conv_route
from model_zoo.transformer import glm4_moe_lite as glm
from model_zoo.transformer.afmoe import LogitAccuracy
from model_zoo.transformer.nemotron_h import (
    _a_log_init, _dt_bias_init, held_passes, held_row_chunks, held_row_tiles, matmul,
    updated_bias)
from model_zoo.transformer.olmoe import batch_partition, optimizer, rmsnorm  # noqa: F401
from model_zoo.transformer.transformer_lm import dataset_fn  # noqa: F401
from model_zoo.transformer.xing4 import HyperConnectionMean

KINDS = ("kda", "mla")
# what a recomputed layer keeps from its forward pass, by name
KEEP = jax.checkpoint_policies.save_only_these_names(
    *pallas_attention.RESIDUAL_NAMES, *delta_rule.RESIDUAL_NAMES)
_PUBLISHED_FULL = "4,8,12,16,20,24,27"
_PUBLISHED_KDA = ",".join(str(l) for l in range(1, 28)
                          if str(l) not in _PUBLISHED_FULL.split(","))
# Mamba's draw of the step the decay's bias starts from (`nemotron_h._dt_bias_init`)
_DT_INIT = SimpleNamespace(time_step_min=1e-3, time_step_max=0.1, time_step_floor=1e-4)
L2_EPS = 1e-6


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names, with
    `linear_attn_config` flattened (`linear_num_heads`, `linear_head_dim`,
    `short_conv_kernel_size`, and the two layer lists as comma-separated
    strings, numbered from one as published) — but for one: the model_params
    key `num_experts`, how many experts are HELD here, what a benchmark
    configuration's `reduced` cuts, is the field `held_experts`, and
    `num_experts` is what the ROUTER chooses among (`router_experts`, or all
    held), the name `nemotron_h.py`'s counters and the benchmark's drivers read
    it by (`afmoe.py` does the same). This repo's own: `router_experts`,
    `first_expert`, `bias_update_speed`, the three initialiser ranges, the
    recurrence's `kda_chunk` / `kda_chunks_per_block` and `compute_dtype`."""

    vocab_size: int = 163840
    hidden_size: int = 2304
    num_hidden_layers: int = 27
    first_k_dense_replace: int = 1
    intermediate_size: int = 9216
    kda_layers: str = _PUBLISHED_KDA
    full_attn_layers: str = _PUBLISHED_FULL
    linear_num_heads: int = 32
    linear_head_dim: int = 128
    short_conv_kernel_size: int = 4
    num_attention_heads: int = 32
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    held_experts: int = 256
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_token: int = 8
    num_shared_experts: int = 1
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.446
    bias_update_speed: float = 1e-3
    rms_norm_eps: float = 1e-5
    initializer_range: float = 0.02
    embedding_initializer_range: float = 1.0
    residual_initializer_range: float = 0.02 / math.sqrt(2 * 27)
    kda_chunk: int = 64
    kda_chunks_per_block: int = 4
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")
        kda, full = self._listed(self.kda_layers), self._listed(self.full_attn_layers)
        for l in range(1, self.num_hidden_layers + 1):
            if (l in kda) == (l in full):
                raise ValueError(
                    f"layer {l} of {self.num_hidden_layers} is in "
                    f"{'both' if l in kda else 'neither'} of kda_layers "
                    f"{self.kda_layers!r} and full_attn_layers {self.full_attn_layers!r}")

    @staticmethod
    def _listed(layers: str) -> frozenset:
        return frozenset(int(l) for l in layers.split(",") if l)

    def kind(self, layer: int) -> str:
        """Of the layer numbered `layer` (from ONE) in the published lists."""
        return "mla" if layer in self._listed(self.full_attn_layers) else "kda"

    def is_dense(self, layer: int) -> bool:
        return layer <= self.first_k_dense_replace

    def layers_of(self, kind: str) -> int:
        return sum(self.kind(l) == kind for l in range(1, self.num_hidden_layers + 1))

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.held_experts

    @property
    def held(self):
        return (self.first_expert, self.held_experts)

    @property
    def num_experts_per_tok(self) -> int:
        """`num_experts_per_token`, under the name GLM's `route` reads."""
        return self.num_experts_per_token


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def l2_normalised(x: jax.Array) -> jax.Array:
    """x (..., d) over its last axis, float32: x / sqrt(Σ x² + 1e-6)."""
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def log_decay(p: Dict[str, jax.Array], a: jax.Array, heads: int) -> jax.Array:
    """g = −exp(A_log[head]) · softplus(a + dt_bias): a (B, T, P) -> (B, T, H,
    d) float32, ≤ 0."""
    b, t, _ = a.shape
    g = jax.nn.softplus(a + p["kda_dt_bias"]).reshape(b, t, heads, -1)
    return -jnp.exp(p["kda_A_log"])[:, None] * g


def qk_normalised(q: jax.Array, k: jax.Array):
    """q, k (B, T, H, d) as the recurrence takes them: each L2-normalised over
    a head's channels, q times d^-1/2."""
    return l2_normalised(q) * q.shape[-1] ** -0.5, l2_normalised(k)


def gated_output(p: Dict[str, jax.Array], o: jax.Array, z: jax.Array, cfg) -> jax.Array:
    """rmsnorm_d(o) ⊙ sigmoid(z): o (B, T, H, d), z (B, T, H·d) -> (B, T, H·d);
    the norm's one weight of d is shared by the heads."""
    normed = rmsnorm(o, p["kda_onorm"], cfg.rms_norm_eps)
    return normed.reshape(z.shape) * jax.nn.sigmoid(z)


def recurrence(q, k, v, g, beta, cfg: Config):
    """The mixer's state update, (o (B, T, H, d), the last state): the name
    `benchmark/rehearse/departures_kimi_linear.py` patches."""
    return delta_rule.gated_delta_rule(
        q, k, v, g, beta, chunk=cfg.kda_chunk, chunks_per_block=cfg.kda_chunks_per_block,
        compute_dtype=jnp.dtype(cfg.compute_dtype))


def kda(p: Dict[str, jax.Array], x: jax.Array, cfg: Config):
    """The KDA sub-block's update of the residual stream x (B, T, C), and per
    example (B, 3): the most negative in-chunk cumulative log-decay, the mean
    write strength, the RMS of the state after the last token."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, d = cfg.linear_num_heads, cfg.linear_head_dim
    by_head = lambda a: a.reshape(b, t, heads, d)
    low_rank = lambda down, up: matmul(matmul(h, down, dt, jnp.float32), up, dt, jnp.float32)
    h = rmsnorm(x, p["kda_norm"], cfg.rms_norm_eps)
    with jax.named_scope("proj"):
        q, k, v = (matmul(h, p[w], dt, jnp.float32) for w in ("kda_wq", "kda_wk", "kda_wv"))
    with jax.named_scope("conv"):
        q, k, v = (jax.nn.silu(causal_conv1d(a, p[w]))
                   for a, w in ((q, "kda_conv_q"), (k, "kda_conv_k"), (v, "kda_conv_v")))
    with jax.named_scope("gates"):
        g = log_decay(p, low_rank(p["kda_f_a"], p["kda_f_b"]), heads)
        beta = jax.nn.sigmoid(jnp.dot(h, p["kda_beta"], precision=jax.lax.Precision.HIGHEST))
    with jax.named_scope("qk_norm"):
        q, k = qk_normalised(by_head(q), by_head(k))
    with jax.named_scope("delta_rule"):
        o, last = recurrence(q, k, by_head(v), g, beta, cfg)
    with jax.named_scope("out_gate"):
        y = gated_output(p, o, low_rank(p["kda_g_a"], p["kda_g_b"]), cfg)
    with jax.named_scope("out"):
        update = matmul(y, p["kda_wo"], dt, jnp.float32)
    with jax.named_scope("counters"):
        pad = -t % cfg.kda_chunk
        in_chunk = jnp.pad(g, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, -1, cfg.kda_chunk, heads, d).sum(axis=2)
        stats = jnp.stack([jnp.min(in_chunk, axis=(1, 2, 3)), jnp.mean(beta, axis=(1, 2)),
                           jnp.sqrt(jnp.mean(last * last, axis=(1, 2, 3)))], axis=-1)
    return update, jax.lax.stop_gradient(stats)


def no_positions(part: jax.Array) -> jax.Array:
    """The latent layers' rotary map: none (`mla_use_nope`)."""
    return part


KDA_KEYS = ("kda_norm", "kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k",
            "kda_conv_v", "kda_f_a", "kda_f_b", "kda_A_log", "kda_dt_bias", "kda_beta",
            "kda_g_a", "kda_g_b", "kda_onorm", "kda_wo")
MLA_KEYS = ("attn_norm", "q_proj", "kv_a", "kv_a_norm", "kv_b", "wo")
MIXER_KEYS = {"kda": KDA_KEYS, "mla": MLA_KEYS}


def layer(p: Dict[str, jax.Array], x: jax.Array, bias, kind: str, cfg: Config):
    """One layer on x (B, T, C) float32: (x, the routing's statistics of a
    sparse layer or None, `kda`'s (B, 3) of a KDA layer or None). `bias` None
    makes its feed-forward the dense one."""
    kda_stats = None
    if kind == "kda":
        with jax.named_scope("kda"):
            update, kda_stats = kda(p, x, cfg)
            x = x + update
    else:
        with jax.named_scope("mla"):
            x = x + glm.latent_attention(p, x, cfg, rotate=no_positions)
    if bias is None:
        with jax.named_scope("dense_mlp"):
            return x + glm.dense_mlp(p, x, cfg), None, kda_stats
    with jax.named_scope("moe"):
        y, stats = glm.moe(p, x, bias, cfg)
        return x + y, stats, kda_stats


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (sparse layers, router_experts) -> ({"logits"
    (B, T, V) float32, "kda_stats" (B, 3)}, the sparse layers' statistics
    stacked on a leading axis, the KDA layers' (layers, B, 3))."""
    seen = {kind: 0 for kind in KINDS}
    stats, kda_stats = [], []
    with jax.named_scope("kimi_linear"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for l in range(1, cfg.num_hidden_layers + 1):
            kind = cfg.kind(l)
            p = {k: params[k][seen[kind]] for k in MIXER_KEYS[kind]}
            seen[kind] += 1
            if cfg.is_dense(l):
                p.update({k: params[k][l - 1] for k in glm.DENSE_KEYS})
                x, s, ks = jax.checkpoint(
                    lambda p, x, kind=kind: layer(p, x, None, kind, cfg), policy=KEEP)(p, x)
            else:
                i = l - 1 - cfg.first_k_dense_replace
                p.update({k: params[k][i] for k in glm.SPARSE_KEYS})
                x, s, ks = jax.checkpoint(
                    lambda p, x, b, kind=kind: layer(p, x, b, kind, cfg),
                    policy=KEEP)(p, x, bias[i])
                stats.append(s)
            if ks is not None:
                kda_stats.append(ks)
        with jax.named_scope("head_loss"):
            logits = glm._head(x, params["final_norm"], params["head"], cfg)
    by_layer = jnp.stack(kda_stats)                                   # (layers, B, 3)
    outputs = {"logits": logits,
               "kda_stats": jnp.stack([jnp.min(by_layer[..., 0], axis=0),
                                       jnp.mean(by_layer[..., 1], axis=0),
                                       jnp.mean(by_layer[..., 2], axis=0)], axis=-1)}
    return (outputs, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats), by_layer)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (sparse layers, B·T, k), weights (the same), the residual
    stream each router saw (the same, B, T, C)). The head is dead code here."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def chunks_walked(cfg: Config, batch: int, seq_len: int) -> int:
    """The chunks the recurrence walks in one step: layers x batch x heads x
    ceil(T / chunk)."""
    return cfg.layers_of("kda") * batch * cfg.linear_num_heads * -(-seq_len // cfg.kda_chunk)


def kernel_chunks_walked(cfg: Config, batch: int, seq_len: int) -> int:
    """Those of them the Pallas kernels walk: all where the recurrence takes
    its kernel route at this shape, none where it takes the plain one."""
    shape = (batch, seq_len, cfg.linear_num_heads, cfg.linear_head_dim)
    route = delta_rule.delta_rule_route(shape, cfg.kda_chunk, cfg.kda_chunks_per_block)
    return chunks_walked(cfg, batch, seq_len) if route == "kernel" else 0


def kernel_convs(cfg: Config, batch: int, seq_len: int) -> int:
    """The depthwise convolutions of one step's forward pass that take the
    Pallas kernels (`ops/pallas_conv1d.py`): q's, k's and v's of every KDA
    layer where `conv_route` says "kernel" at this shape, none elsewhere."""
    shape = (batch, seq_len, cfg.linear_num_heads * cfg.linear_head_dim)
    route = conv_route(shape, cfg.short_conv_kernel_size)
    return 3 * cfg.layers_of("kda") if route == "kernel" else 0


# ------------------------------------------------------------------ #
# The zoo contract


class KimiLinear(nn.Module):
    """Initialisation (`assumed` in the benchmark's configuration):
    normal(`initializer_range`) for every matrix and convolution, ones for
    every norm, normal(`embedding_initializer_range`) for the embedding,
    normal(`residual_initializer_range`) for the matrices that write to the
    residual stream (`kda_wo`, `wo`, `mlp_down`, `shared_down`, `w_down`),
    Mamba's draws for `kda_A_log` and `kda_dt_bias`, zeros for the selection
    bias."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        C, V = c.hidden_size, c.vocab_size
        K, A = c.layers_of("kda"), c.layers_of("mla")
        D, S = c.first_k_dense_replace, c.sparse_layers
        Hl, d, H = c.linear_num_heads, c.linear_head_dim, c.num_attention_heads
        P, W = Hl * d, c.short_conv_kernel_size
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        F, held = c.moe_intermediate_size, c.held_experts
        Fs = F * c.num_shared_experts
        normal, ones = nn.initializers.normal(c.initializer_range), nn.initializers.ones
        residual = nn.initializers.normal(c.residual_initializer_range)
        shapes = {
            "embed": ((V, C), nn.initializers.normal(c.embedding_initializer_range)),
            "final_norm": ((C,), ones), "head": ((C, V), normal),
            "kda_norm": ((K, C), ones),
            "kda_wq": ((K, C, P), normal), "kda_wk": ((K, C, P), normal),
            "kda_wv": ((K, C, P), normal),
            "kda_conv_q": ((K, W, P), normal), "kda_conv_k": ((K, W, P), normal),
            "kda_conv_v": ((K, W, P), normal),
            "kda_f_a": ((K, C, d), normal), "kda_f_b": ((K, d, P), normal),
            "kda_A_log": ((K, Hl), _a_log_init),
            "kda_dt_bias": ((K, P), _dt_bias_init(_DT_INIT)),
            "kda_beta": ((K, C, Hl), normal),
            "kda_g_a": ((K, C, d), normal), "kda_g_b": ((K, d, P), normal),
            "kda_onorm": ((K, d), ones), "kda_wo": ((K, P, C), residual),
            "attn_norm": ((A, C), ones), "q_proj": ((A, C, H * qk), normal),
            "kv_a": ((A, C, c.kv_lora_rank + c.qk_rope_head_dim), normal),
            "kv_a_norm": ((A, c.kv_lora_rank), ones),
            "kv_b": ((A, c.kv_lora_rank, H * (c.qk_nope_head_dim + c.v_head_dim)), normal),
            "wo": ((A, H * c.v_head_dim, C), residual),
            "mlp_norm": ((D, C), ones),
            "mlp_gate": ((D, C, c.intermediate_size), normal),
            "mlp_up": ((D, C, c.intermediate_size), normal),
            "mlp_down": ((D, c.intermediate_size, C), residual),
            "moe_norm": ((S, C), ones),
            "moe_router": ((S, C, c.num_experts), normal),
            "shared_gate": ((S, C, Fs), normal), "shared_up": ((S, C, Fs), normal),
            "shared_down": ((S, Fs, C), residual),
            "w_gate": ((S, held, C, F), normal), "w_up": ((S, held, C, F), normal),
            "w_down": ((S, held, F, C), residual),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape, dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        bias = counter("router_state", "e_score_correction_bias", (S, c.num_experts),
                       jnp.float32)
        passes = counter("router_state", "held_passes", (S,))
        row_tiles = counter("router_state", "held_row_tiles", (S,))
        row_chunks = counter("router_state", "held_row_chunks", (S,))
        chunks = counter("kda", "chunks", ())
        kernel_chunks = counter("kda", "kernel_chunks", ())
        convs = counter("kda", "kernel_convs", ())
        last_step = {name: counter("kda", name, (K,), jnp.float32)
                     for name in ("log_decay_min", "beta_mean", "state_rms")}
        outputs, stats, kda_stats = forward(params, bias.value, features, c)
        if training and not self.is_initializing():
            idx = stats["expert_idx"]
            bias.value = updated_bias(bias.value, idx, c)
            passes.value = passes.value + held_passes(idx, c)
            row_tiles.value = row_tiles.value + held_row_tiles(idx, c)
            row_chunks.value = row_chunks.value + held_row_chunks(idx, c)
            chunks.value = chunks.value + chunks_walked(c, *features.shape)
            kernel_chunks.value = kernel_chunks.value + kernel_chunks_walked(c, *features.shape)
            convs.value = convs.value + kernel_convs(c, *features.shape)
            last_step["log_decay_min"].value = jnp.min(kda_stats[..., 0], axis=1)
            last_step["beta_mean"].value = jnp.mean(kda_stats[..., 1], axis=1)
            last_step["state_rms"].value = jnp.mean(kda_stats[..., 2], axis=1)
        return outputs


def custom_model(**kwargs) -> KimiLinear:
    """Keys are the published config's (`num_experts`: the experts held here,
    `Config.held_experts`); unknown keys (the harness adds its own to every
    model) are ignored."""
    kwargs = {("held_experts" if k == "num_experts" else k): v for k, v in kwargs.items()}
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return KimiLinear(Config(**given))


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B,), as `loss`
    and again as `loss_ce`, the one term the step reports beside it."""
    with jax.named_scope("kimi_linear/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs["logits"].astype(jnp.float32), labels.astype(jnp.int32)).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}


class KdaStat(HyperConnectionMean):
    """The mean over examples of one column of `kda_stats`."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(state, labels, {"mhc_stats": outputs["kda_stats"]}, mask)


def eval_metrics_fn():
    return {"token_accuracy": LogitAccuracy(),
            "kda_log_decay_min": KdaStat(0), "kda_beta_mean": KdaStat(1),
            "kda_state_rms": KdaStat(2)}
