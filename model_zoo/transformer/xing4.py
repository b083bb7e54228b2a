"""Xing4.0 (`model_type: xing4_0`, XingChen-AGI/Xing4.0-29B-A4B): a decoder-
only LM whose residual state is `hc_mult` STREAMS mixed at every sub-block by
learned, input-dependent maps of which the stream-to-stream one is doubly
stochastic (manifold-constrained hyper-connections, arXiv:2512.24880, over
hyper-connections, arXiv:2409.19606), around DeepSeek-V3's sub-blocks: latent
attention whose q and k heads (nope + rope) are WIDER than its v heads, under
YaRN; a leading dense layer; sparse-expert layers behind a sigmoid router
with a selection bias, plus a shared expert (arXiv:2412.19437 §2.1.1-2.1.2).

The mathematics is written ONCE, as pure functions over a dict of arrays.
What is DeepSeek-V3's is `glm4_moe_lite.py`'s and is called, not copied:
`latent_attention` (given this model's rotary map and softmax factor),
`dense_mlp`, `moe` (with `route`, `gated_mlp`, `ops.moe.dropless_moe(held=)`);
the YaRN frequencies and the rotation are `mellum.py`'s; the bias update and
the held share's counters `nemotron_h.py`'s. What is new is here: the streams.

n = `hc_mult`, C the hidden size, a token's state X (n, C); a layer is two
sub-blocks, f = attention then f = feed-forward, each with coefficients of
its own (phi (n·C, 2n + n²), three scalars alpha, 2n + n² biases b):

1. x~ = vec(X) / sqrt(mean(vec(X)²) + `hc_eps`) (no weight), float32;
   H~_pre = alpha_pre · (x~ phi_pre) + b_pre (n), H~_post likewise (n),
   H~_res = alpha_res · mat(x~ phi_res) + b_res (n x n, row i the stream
   written, column j the stream read; vec is stream-major, mat row-major).
2. H_pre = sigmoid(H~_pre); H_post = 2 · sigmoid(H~_post);
   H_res = SK(exp(clip(H~_res, `mhc_h_res_clamp_min`, `_max`))), SK
   `hc_sinkhorn_iters` rounds of: every row divided by (its sum + `hc_eps`),
   then every column by (its sum + `hc_eps`); differentiated through.
3. h = sum_i H_pre,i X_i; y = f(h) (f norms its input itself);
   X'_i = sum_j H_res,ij X_j + H_post,i · y.
4. Entry: every stream is the token's embedding. Exit: the final norm reads
   sum_i X_i. (Neither is in `config.json`: `benchmark/configs/
   xing4.0-29b-a4b.json`, `assumed`.)

Latent attention is GLM's with two changes, both arguments of its function:
the rotary table is YaRN's (`yarn_table`: the blend of `mellum.yarn_inv_freq`
over the `qk_rope_head_dim` rotary dimensions; `mscale == mscale_all_dim`, so
cos and sin are multiplied by ONE) and the scores carry, beside (nope +
rope)^-1/2, the factor m² with m = 0.1 · `mscale_all_dim` · ln(`rope_factor`)
+ 1 (`softmax_factor`), applied to q in float32. q and k are nope + rope
(192) wide and v `v_head_dim` (128): `ops.attention.full_attention` takes the
two widths, nothing is padded.

The multi-token-prediction module is NOT built: how it joins an n-stream
state cannot be written down from the published config, and a guess under the
model's name is worse than none. `num_nextn_predict_layers > 0` raises.

Layout: the state is held as (n, B, T, C) — stream-major, so that a stream is
a dense (B, T, C) array and n = 4 never sits in a tile's sublanes — and the
coefficients with the tokens minor, (n, B, T) and (n, n, B, T): the Sinkhorn
rounds are elementwise over whole lanes.

Precision (`benchmark/configs/xing4.0-29b-a4b.json`, `precision`): the
streams are stored in `compute_dtype` — bfloat16 on the chip, what
arXiv:2512.24880's own mixed-precision recipe stores, and their cotangents
likewise (the benchmark's check cannot tell float32 streams from these:
PERF.md §6, PR 48); the coefficients — the norm over n·C, phi's matmul (at
the highest matmul precision), sigmoids, exp, every Sinkhorn round — are
float32; both mixes read the streams up to float32, accumulate in float32,
and the write-back rounds once, for every reader alike (`stored`). h and y
are float32, as GLM's one stream is; inside f the precision is GLM's
(`compute_dtype` matmul operands, float32 accumulation, norms, router,
rotary, softmax).

Every layer is recomputed in the backward pass (`jax.checkpoint` around the
pair of sub-blocks) under `policy=pallas_attention.KEEP_RESIDUALS`: of a layer
the n-stream state it started from is kept (n times a one-stream model's) and
the flash kernels' five residuals.

The Sinkhorn rounds are ONE `lax.scan` of `hc_sinkhorn_iters` steps (PERF.md
§6, PR 48 has the set-up and step time of this form and of the unrolled one).

Counters: `router_state/held_passes`, `held_row_tiles`, `held_row_chunks` (GLM's),
`attn/kv_block_visits` (summed over steps: the (q block, kv block) pairs a
head's forward computes in the layers); the outputs carry `mhc_stats` (B, 2):
the largest |row or column sum - 1| of any H_res after its rounds, and the
mean mass off H_res's diagonal (0: the streams never mix), which the
evaluation metrics `mhc_sinkhorn_residual` and `mhc_h_res_offdiag` read.

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
import numpy as np
import optax

from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.training import metrics as metrics_lib
from model_zoo.transformer import glm4_moe_lite as glm
from model_zoo.transformer.afmoe import LogitAccuracy
from model_zoo.transformer.mellum import rotate, yarn_inv_freq
from model_zoo.transformer.nemotron_h import (
    held_passes, held_row_chunks, held_row_tiles, updated_bias)
from model_zoo.transformer.olmoe import batch_partition, optimizer  # noqa: F401
from model_zoo.transformer.transformer_lm import dataset_fn  # noqa: F401

@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names, `rope_scaling`
    flattened (`rope_factor`, `original_max_position_embeddings`, `beta_fast`,
    `beta_slow`, `mscale`, `mscale_all_dim`). This repo's: `router_experts`
    (how many experts the router chooses among; 0: `n_routed_experts`, every
    expert held here), `first_expert`, `bias_update_speed`, and the
    initialisation of the hyper-connections (`hc_alpha_init`: the
    three gates; `hc_res_init`: H~_res's diagonal bias, 4 puts 0.948 of H_res
    on its diagonal)."""

    vocab_size: int = 131072
    hidden_size: int = 3584
    num_hidden_layers: int = 40
    first_k_dense_replace: int = 2
    intermediate_size: int = 9216
    num_attention_heads: int = 32
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    rope_theta: float = 10000.0
    rope_factor: float = 64.0
    original_max_position_embeddings: int = 4096
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 1.0
    mscale_all_dim: float = 1.0
    n_routed_experts: int = 64         # the experts HELD here
    router_experts: int = 0
    first_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1024
    routed_scaling_factor: float = 2.0
    bias_update_speed: float = 1e-3
    num_nextn_predict_layers: int = 0  # published: 1; see the module docstring
    rms_norm_eps: float = 1e-6
    hc_mult: int = 4
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    mhc_h_res_clamp_min: float = -30.0
    mhc_h_res_clamp_max: float = 30.0
    hc_alpha_init: float = 0.01
    hc_res_init: float = 4.0
    compute_dtype: str = "bfloat16"    # the matmuls' operands AND the streams

    def __post_init__(self):
        if self.num_nextn_predict_layers:
            raise ValueError(
                f"num_nextn_predict_layers={self.num_nextn_predict_layers}: the "
                "multi-token-prediction module is not built here — how it joins "
                f"the {self.hc_mult} streams of the residual state is not in the "
                "published config; give 0")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")
        if self.hc_mult < 1 or self.hc_sinkhorn_iters < 0:
            raise ValueError("hc_mult is at least 1, hc_sinkhorn_iters at least 0")
        if self.mscale != self.mscale_all_dim:
            raise ValueError("mscale != mscale_all_dim would scale the rotary "
                             "table too: not what the published config has")

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.n_routed_experts

    @property
    def held(self):
        return (self.first_expert, self.n_routed_experts)

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.first_k_dense_replace

    @property
    def hc_coefficients(self) -> int:
        """H~_pre, H~_post and H~_res of one sub-block: 2n + n²."""
        return 2 * self.hc_mult + self.hc_mult ** 2

    @property
    def softmax_factor(self) -> float:
        """m², m = 0.1 · mscale_all_dim · ln(factor) + 1 (1 at factor <= 1)."""
        if self.rope_factor <= 1:
            return 1.0
        return (0.1 * self.mscale_all_dim * math.log(self.rope_factor) + 1.0) ** 2


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def yarn_table(cfg: Config, seq_len: int):
    """(cos, sin), each (1, T, 1, rot) float32: YaRN's blended frequencies
    over the `qk_rope_head_dim` rotary dimensions, times one."""
    inv_freq = yarn_inv_freq(SimpleNamespace(
        head_dim=cfg.qk_rope_head_dim, rope_theta=cfg.rope_theta,
        original_max_position_embeddings=cfg.original_max_position_embeddings,
        beta_fast=cfg.beta_fast, beta_slow=cfg.beta_slow, rope_factor=cfg.rope_factor))
    angle = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    both = lambda f: jnp.concatenate([f(angle)] * 2, axis=-1)[None, :, None, :]
    return both(jnp.cos), both(jnp.sin)


def sinkhorn(m: jax.Array, iters: int, eps: float) -> jax.Array:
    """`iters` rounds on m (n, n, ...) positive: rows (axis 1 summed) divided
    by their sums + eps, then columns (axis 0 summed) by theirs + eps."""
    def one_round(m, _):
        m = m / (jnp.sum(m, axis=1, keepdims=True) + eps)
        return m / (jnp.sum(m, axis=0, keepdims=True) + eps), None

    return jax.lax.scan(one_round, m, None, length=iters)[0]


def mhc_coefficients(p: Dict[str, jax.Array], streams: jax.Array, cfg: Config):
    """One sub-block's maps from the state `streams` (n, B, T, C): (H_pre
    (n, B, T), H_post (n, B, T), H_res (n, n, B, T)), float32. p: `hc_phi`
    (n·C, 2n + n²), `hc_alpha` (3,), `hc_b` (2n + n²,)."""
    n, b, t, c = streams.shape
    with jax.named_scope("coef"):
        x = streams.astype(jnp.float32)
        # the norm's factor is a token's own: it commutes with phi's matmul
        inv_rms = jax.lax.rsqrt(jnp.mean(x * x, axis=(0, 3)) + cfg.hc_eps)      # (B, T)
        raw = jnp.einsum("nbtc,nck->kbt", x, p["hc_phi"].reshape(n, c, -1),
                         precision=jax.lax.Precision.HIGHEST) * inv_rms
        alpha = jnp.repeat(p["hc_alpha"], np.array([n, n, n * n]),
                           total_repeat_length=cfg.hc_coefficients)
        raw = alpha[:, None, None] * raw + p["hc_b"][:, None, None]
        h_pre = jax.nn.sigmoid(raw[:n])
        h_post = 2.0 * jax.nn.sigmoid(raw[n:2 * n])
        positive = jnp.exp(jnp.clip(raw[2 * n:], cfg.mhc_h_res_clamp_min,
                                    cfg.mhc_h_res_clamp_max)).reshape(n, n, b, t)
    with jax.named_scope("sinkhorn"):
        h_res = sinkhorn(positive, cfg.hc_sinkhorn_iters, cfg.hc_eps)
    return h_pre, h_post, h_res


def stored(x: jax.Array, dtype) -> jax.Array:
    """x (float32) as the streams hold it: rounded to `dtype` ONCE and for
    every reader. The rounding is an operation of its own (`reduce_precision`)
    before the cast: a bare cast to bfloat16 and back is a pair XLA is free to
    drop inside a fusion (`xla_allow_excess_precision`), and on the chip it
    does — one reader of a stream then sees the rounded value and another the
    unrounded one (the router and the `router_input` the step reports; a
    layer's forward and its recomputation)."""
    dtype = jnp.dtype(dtype)
    if dtype == x.dtype:
        return x
    info = jnp.finfo(dtype)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp,
                                    mantissa_bits=info.nmant).astype(dtype)


def mhc_read(streams: jax.Array, h_pre: jax.Array) -> jax.Array:
    """h = sum_i H_pre,i X_i: (n, B, T, C) -> (B, T, C) float32."""
    with jax.named_scope("pre"):
        return sum(h_pre[i][..., None] * streams[i].astype(jnp.float32)
                   for i in range(streams.shape[0]))


def mhc_write(streams: jax.Array, y: jax.Array, h_post: jax.Array,
              h_res: jax.Array) -> jax.Array:
    """X'_i = sum_j H_res,ij X_j + H_post,i · y, accumulated in float32 and
    written in the streams' dtype."""
    n = streams.shape[0]
    with jax.named_scope("post_res"):
        x = [streams[j].astype(jnp.float32) for j in range(n)]
        return jnp.stack([
            stored(sum(h_res[i, j][..., None] * x[j] for j in range(n))
                   + h_post[i][..., None] * y, streams.dtype)
            for i in range(n)])


def mhc_stats(h_res: jax.Array):
    """((B,) the largest |row or column sum - 1| of a token's H_res, (B,) the
    mean mass off its diagonal), of one sub-block."""
    n = h_res.shape[0]
    off = jnp.maximum(jnp.max(jnp.abs(jnp.sum(h_res, axis=1) - 1.0), axis=0),
                      jnp.max(jnp.abs(jnp.sum(h_res, axis=0) - 1.0), axis=0))   # (B, T)
    diagonal = sum(h_res[i, i] for i in range(n))
    return jnp.max(off, axis=-1), jnp.mean(1.0 - diagonal / n, axis=-1)


def sub_block(p: Dict[str, jax.Array], streams: jax.Array, f, cfg: Config):
    """One hyper-connected sub-block around f: (B, T, C) -> (update (B, T, C)
    float32, anything). Returns (streams, f's second result, `mhc_stats`)."""
    with jax.named_scope("mhc"):
        h_pre, h_post, h_res = mhc_coefficients(p, streams, cfg)
        h = mhc_read(streams, h_pre)
    y, more = f(h)
    with jax.named_scope("mhc"):
        return mhc_write(streams, y, h_post, h_res), more, mhc_stats(h_res)


HC_KEYS = ("hc_phi", "hc_alpha", "hc_b")


def layer(p: Dict[str, jax.Array], streams: jax.Array, bias, table, cfg: Config):
    """One layer on the state (n, B, T, C): (streams, the routing's statistics
    of a sparse layer or None, `mhc_stats` of its two sub-blocks stacked).
    `bias` None makes it a dense layer; the `hc_*` entries of p carry the two
    sub-blocks' coefficients on a leading axis."""
    hc = lambda i: {k: p[k][i] for k in HC_KEYS}

    def attention(h):
        with jax.named_scope("mla"):
            return glm.latent_attention(p, h, cfg, rotate=lambda part: rotate(part, table),
                                        q_scale=cfg.softmax_factor), None

    def feed_forward(h):
        if bias is None:
            with jax.named_scope("dense_mlp"):
                return glm.dense_mlp(p, h, cfg), None
        with jax.named_scope("moe"):
            return glm.moe(p, h, bias, cfg)

    streams, _, first = sub_block(hc(0), streams, attention, cfg)
    streams, stats, second = sub_block(hc(1), streams, feed_forward, cfg)
    return streams, stats, jax.tree_util.tree_map(lambda *a: jnp.stack(a), first, second)


def _layer_params(params, index, keys, kind_index):
    p = {k: params[k][index] for k in glm.ATTN_KEYS}
    p.update({k: params[k][kind_index] for k in keys})
    p.update({k: params[k][2 * index:2 * index + 2] for k in HC_KEYS})
    return p


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (sparse layers, router_experts) -> ({"logits"
    (B, T, V) float32, "mhc_stats" (B, 2)}, the sparse layers' statistics
    stacked on a leading axis)."""
    dense = cfg.first_k_dense_replace
    stats, hc_stats = [], []
    checkpointed = lambda f: jax.checkpoint(f, policy=pallas_attention.KEEP_RESIDUALS)
    with jax.named_scope("xing4"):
        table = yarn_table(cfg, tokens.shape[1])
        with jax.named_scope("embed"):
            x = stored(jnp.take(params["embed"], tokens, axis=0), cfg.compute_dtype)
            streams = jnp.broadcast_to(x, (cfg.hc_mult,) + x.shape)
        for i in range(cfg.num_hidden_layers):
            if i < dense:
                p = _layer_params(params, i, glm.DENSE_KEYS, i)
                streams, _, hs = checkpointed(
                    lambda p, x, table: layer(p, x, None, table, cfg))(p, streams, table)
            else:
                p = _layer_params(params, i, glm.SPARSE_KEYS, i - dense)
                streams, s, hs = checkpointed(
                    lambda p, x, b, table: layer(p, x, b, table, cfg))(
                        p, streams, bias[i - dense], table)
                stats.append(s)
            hc_stats.append(hs)
        with jax.named_scope("head_loss"):
            x = sum(streams[i].astype(jnp.float32) for i in range(cfg.hc_mult))
            logits = glm._head(x, params["final_norm"], params["head"], cfg)
        residual, off_diagonal = jax.tree_util.tree_map(
            lambda *a: jnp.concatenate(a), *hc_stats)                 # (sub-blocks, B)
        outputs = {"logits": logits,
                   "mhc_stats": jnp.stack([jnp.max(residual, axis=0),
                                           jnp.mean(off_diagonal, axis=0)], axis=-1)}
    return outputs, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (sparse layers, B·T, k), weights (the same), the mixed stream
    each router saw (the same, B, T, C)). The head is dead code here."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def kv_block_visits(cfg: Config, seq_len: int) -> int:
    """The (q block, kv block) pairs a head's forward grid computes in one
    step, summed over the layers."""
    return cfg.num_hidden_layers * pallas_attention.kv_block_visits(
        seq_len, seq_len, None, cfg.qk_nope_head_dim + cfg.qk_rope_head_dim,
        jnp.dtype(cfg.compute_dtype))[1]


# ------------------------------------------------------------------ #
# The zoo contract


def _hc_b_init(cfg: Config):
    """H_pre = 1/n each (the n equal streams of the entry read as ONE), H_post
    = 1, H~_res = `hc_res_init` on the diagonal and 0 off it: at the seed the
    model is near a one-stream one and H_res near the identity."""
    n = cfg.hc_mult
    b = np.concatenate([np.full(n, -math.log(n - 1.0) if n > 1 else 30.0), np.zeros(n),
                        (cfg.hc_res_init * np.eye(n)).ravel()])
    return lambda key, shape, dtype: jnp.broadcast_to(jnp.asarray(b, dtype), shape)


class Xing4(nn.Module):
    """Initialisation (`assumed` in the benchmark's configuration): normal
    (0.02) for every matrix, the embedding and phi; ones for every norm;
    `hc_alpha_init` for the three gates; `_hc_b_init` for b; zeros for the
    selection bias."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        C, V, H, n = c.hidden_size, c.vocab_size, c.num_attention_heads, c.hc_mult
        A, D, S = c.num_hidden_layers, c.first_k_dense_replace, c.sparse_layers
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        F, held, K = c.moe_intermediate_size, c.n_routed_experts, c.hc_coefficients
        Fs = F * c.n_shared_experts
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), ones),
            "head": ((C, V), normal),
            "hc_phi": ((2 * A, n * C, K), normal),
            "hc_alpha": ((2 * A, 3), nn.initializers.constant(c.hc_alpha_init)),
            "hc_b": ((2 * A, K), _hc_b_init(c)),
            "attn_norm": ((A, C), ones),
            "q_a": ((A, C, c.q_lora_rank), normal), "q_a_norm": ((A, c.q_lora_rank), ones),
            "q_b": ((A, c.q_lora_rank, H * qk), normal),
            "kv_a": ((A, C, c.kv_lora_rank + c.qk_rope_head_dim), normal),
            "kv_a_norm": ((A, c.kv_lora_rank), ones),
            "kv_b": ((A, c.kv_lora_rank, H * (c.qk_nope_head_dim + c.v_head_dim)), normal),
            "wo": ((A, H * c.v_head_dim, C), normal),
            "mlp_norm": ((D, C), ones),
            "mlp_gate": ((D, C, c.intermediate_size), normal),
            "mlp_up": ((D, C, c.intermediate_size), normal),
            "mlp_down": ((D, c.intermediate_size, C), normal),
            "moe_norm": ((S, C), ones),
            "moe_router": ((S, C, c.num_experts), normal),
            "shared_gate": ((S, C, Fs), normal), "shared_up": ((S, C, Fs), normal),
            "shared_down": ((S, Fs, C), normal),
            "w_gate": ((S, held, C, F), normal), "w_up": ((S, held, C, F), normal),
            "w_down": ((S, held, F, C), normal),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        bias = self.variable("router_state", "e_score_correction_bias",
                             jnp.zeros, (S, c.num_experts), jnp.float32)
        passes = self.variable("router_state", "held_passes", jnp.zeros, (S,), jnp.int32)
        row_tiles = self.variable("router_state", "held_row_tiles", jnp.zeros, (S,), jnp.int32)
        row_chunks = self.variable("router_state", "held_row_chunks", jnp.zeros, (S,), jnp.int32)
        visits = self.variable("attn", "kv_block_visits", jnp.zeros, (), jnp.int32)
        outputs, stats = forward(params, bias.value, features, c)
        if training and not self.is_initializing():
            bias.value = updated_bias(bias.value, stats["expert_idx"], c)
            passes.value = passes.value + held_passes(stats["expert_idx"], c)
            row_tiles.value = row_tiles.value + held_row_tiles(stats["expert_idx"], c)
            row_chunks.value = row_chunks.value + held_row_chunks(stats["expert_idx"], c)
            visits.value = visits.value + kv_block_visits(c, features.shape[1])
        return outputs


def custom_model(**kwargs) -> Xing4:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Xing4(Config(**given))


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B,), as `loss`
    and again as `loss_ce`, its one term; `mhc_sinkhorn_residual` (the largest
    |row or column sum - 1| of any H_res of the example after its rounds) only
    rides along in the step's metrics: nothing of it is minimised."""
    with jax.named_scope("xing4/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs["logits"].astype(jnp.float32), labels.astype(jnp.int32)).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce,
            "mhc_sinkhorn_residual": outputs["mhc_stats"][:, 0]}


class HyperConnectionMean(metrics_lib.Metric):
    """The mean over examples of one column of `mhc_stats`."""

    def __init__(self, column: int):
        self.column = column

    def init_state(self) -> np.ndarray:
        return np.zeros((2,), np.float32)

    def update(self, state, labels, outputs, mask=None):
        each = outputs["mhc_stats"][:, self.column]
        weight = jnp.ones_like(each) if mask is None else jnp.asarray(mask, jnp.float32)
        return state + jnp.stack([jnp.sum(each * weight), jnp.sum(weight)])

    def result(self, state) -> float:
        return float(state[0] / max(float(state[1]), 1.0))


def eval_metrics_fn():
    return {"token_accuracy": LogitAccuracy(),
            "mhc_sinkhorn_residual": HyperConnectionMean(0),
            "mhc_h_res_offdiag": HyperConnectionMean(1)}
