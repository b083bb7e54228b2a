"""Keye-VL-2.0's language model: a decoder-only LM whose attention reads, for
every query, only the keys a second, LEARNED scorer selects — DeepSeek's sparse
attention (a "lightning indexer" and the K best keys of the causal prefix) over
grouped-query heads — and whose every feed-forward is a dropless top-k mixture
of gated-SiLU experts (Kwai-Keye/Keye-VL-2.0-30B-A3B, `model_type: KeyeVL2`).
The vision tower is not built: a text sequence gives M-RoPE three equal
position components, which is plain rotary.

The mathematics is written ONCE, as pure functions over a dict of arrays —
`indexer`, `attention`, `route`, `moe`, `block`, `forward` — like `mellum.py`;
the flax module at the bottom declares the parameters, sows the two auxiliary
losses and owns the counters. Hidden C, H query heads and Hkv key-value heads
of D, Hi index heads of Di against one index key head, K keys a query, E
experts of width F, k a token. Every layer, on the float32 residual stream x:

- `h = rmsnorm(x)`; `q = h·Wq` (C x H·D), `k = h·Wk`, `v = h·Wv` (C x Hkv·D),
  no bias; per-head `rmsnorm` of q and of k over D with a learned weight each;
  rotary positions (rotate-half, all D dimensions, `rope_theta`).
- index scores, with `hd = stop_gradient(h)`: `qI = hd·W_qI` (Hi heads of Di),
  `kI = layernorm(hd·W_kI)` (one head of Di, learned scale and bias), rotary on
  both, `w = (hd·W_w) · (Hi·Di)^-1/2`; `I[t, s] = Σ_j w[t, j] · relu(qI[t, j] ·
  kI[s])`, float32 (`ops.sparse_attention.index_scores`).
- selection: query t keeps its whole causal prefix while t < K, else the K
  keys of largest I[t, s] among s <= t, ties to the lower key index
  (`ops.sparse_attention.select`: a row threshold and a `keep` plane, with the
  rows the tie rule settled counted). Not differentiated.
- attention at scale D^-1/2 over the kept keys, query head h reading
  key-value head h // (H / Hkv) (`ops.attention.full_attention(keep=...)`: on a
  TPU the `flash_attention_sel_*` kernels); `x = x + attn·Wo`.
- the indexer's loss, with P the attention's probabilities, p̂ =
  stop_gradient(mean over heads of P), π = softmax of I over the kept keys:
  `L_I = (1/T) Σ_t Σ_s p̂ (log p̂ − log π)` (`ops.sparse_attention.index_kl`).
  TWO GRADIENT PATHS THAT DO NOT TOUCH: W_qI, W_kI, W_w and the key
  layernorm receive gradient from L_I alone (the indexer reads a detached h
  and the selection is not differentiated), and every other parameter
  receives none from it (p̂ is a target).
- `h = rmsnorm(x)`; router logits `h·Wg` in float32; softmax over ALL
  `router_experts`; the k largest, weights renormalised to sum to one; an
  expert is `W_down(silu(W_gate h) * W_up h)`; this chip holds experts
  `first_expert ... first_expert + num_experts − 1` and computes every pair
  routed to them (`ops.moe.dropless_moe`, `held`); `x = x + y`. No shared
  expert.
- final rmsnorm, an untied head, per-example mean next-token cross entropy.
- minimised: `loss_ce + loss_balance + loss_index`: the load balance
  `E · Σ_e f_e · P_e` over all the experts the router chooses among times
  `load_balance_coef`, and `Σ_layers L_I` times `index_loss_coef`, each sown
  under its own name and reported by the step under its own name
  (`aux_loss_terms`).

Precision: parameters, norms, router, rotary tables, softmaxes, the residual
stream, the index scores past their matmul (relu, weights, the sum over heads),
the selection, p̂, π and the losses float32; the projections, the index
scores' matmul, the experts' matmuls and the head take `compute_dtype`
operands (bfloat16 on the chip) and accumulate in float32.

Every layer is recomputed in the backward pass (`jax.checkpoint`) under
`policy=sparse_attention.KEEP_SELECTION`: kept are the residual stream the
layer started from, what the flash kernels' backward reads (q, k, v, output,
logsumexp), what the selection decided (thresholds and the `keep` plane) and
the index loss's gradients for the indexer's three operands, which its forward
rule makes with the loss, so the recomputation runs no forward kernel, no
search and nothing of the index loss again and cannot select differently from
the forward pass.

Counters, in collections the trainer threads through every step:
`router_state/held_passes`, `held_row_tiles`, `held_row_chunks`,
`pairs_held_share` (as
`mellum.py`'s) and, per layer, `dsa/selected_pairs` beside `dsa/causal_pairs`,
`dsa/live_blocks` beside `dsa/causal_blocks` (of the last step) and
`dsa/tie_rows` (summed over steps).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition; the optimizer, the batch partition and `rmsnorm` are
`olmoe.py`'s, `rotate` is `mellum.py`'s. Data: `synthetic://lm?vocab=V&seq=T`.
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
from elasticdl_tpu.ops import sparse_attention
from elasticdl_tpu.ops.attention import full_attention
from model_zoo.transformer.mellum import rotate
from model_zoo.transformer.nemotron_h import (
    matmul, pairs_on_held, held_passes, held_row_chunks, held_row_tiles)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, eval_metrics_fn, optimizer, rmsnorm)
from model_zoo.transformer.transformer_lm import dataset_fn  # noqa: F401


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names; `sa_config`
    flattened (`indexer_num_heads`, `indexer_head_dim`, `index_topk`). Seven
    are this repo's: `router_experts` (how many experts the router chooses
    among; 0: `num_experts`, every expert held here), `first_expert` (the
    first of the `num_experts` held here), `load_balance_coef`,
    `index_loss_coef`, `embedding_initializer_range`,
    `residual_initializer_range` and `compute_dtype`."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 10000000.0
    indexer_num_heads: int = 16
    indexer_head_dim: int = 64
    index_topk: int = 2048
    num_experts: int = 128             # the experts HELD here
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 768
    load_balance_coef: float = 0.001
    index_loss_coef: float = 1.0
    rms_norm_eps: float = 1e-6
    # as `mellum.Config`'s: every matrix; the embedding; what a sub-block
    # writes to the residual stream (smaller by sqrt(2 x the PUBLISHED 48))
    initializer_range: float = 0.02
    embedding_initializer_range: float = 1.0
    residual_initializer_range: float = 0.02 / math.sqrt(2 * 48)
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.index_topk < 1:
            raise ValueError("index_topk is at least 1: a query keeps a key")

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


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def rotary_table(theta: float, dim: int, seq_len: int):
    """(cos, sin), each (1, T, 1, dim) float32, for `mellum.rotate`."""
    inv_freq = theta ** (-jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    angle = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    both = lambda f: jnp.concatenate([f(angle)] * 2, axis=-1)[None, :, None, :]
    return both(jnp.cos), both(jnp.sin)


def rotary_tables(cfg: Config, seq_len: int) -> Dict[str, tuple]:
    """{"attn", "index"}: the heads' table and the indexer's, both plain."""
    return {"attn": rotary_table(cfg.rope_theta, cfg.head_dim, seq_len),
            "index": rotary_table(cfg.rope_theta, cfg.indexer_head_dim, seq_len)}


def layernorm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    centred = x - mean
    return centred * jax.lax.rsqrt(
        jnp.mean(centred * centred, axis=-1, keepdims=True) + eps) * scale + bias


# what cuts the indexer's gradient path from the main model's
detached = jax.lax.stop_gradient


def indexer(p: Dict[str, jax.Array], h: jax.Array, table, cfg: Config):
    """The indexer's operands of one layer from its normed residual stream h
    (B, T, C), which it reads DETACHED: (qI (B, T, Hi, Di) and kI (B, T, Di) in
    the compute dtype, the head weights w (B, T, Hi) float32) — what
    `sparse_attention.index_scores` makes the plane I (B, T, T) of."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = h.shape
    heads, d = cfg.indexer_num_heads, cfg.indexer_head_dim
    h = detached(h)
    q = matmul(h, p["index_wq"], dt, jnp.float32).reshape(b, t, heads, d)
    k = layernorm(matmul(h, p["index_wk"], dt, jnp.float32),
                  p["index_k_scale"], p["index_k_bias"], cfg.rms_norm_eps)
    q, k = rotate(q, table), rotate(k[:, :, None, :], table)[:, :, 0, :]
    w = matmul(h, p["index_w"], dt, jnp.float32) * (heads * d) ** -0.5
    return q.astype(dt), k.astype(dt), w


def attention(p: Dict[str, jax.Array], x: jax.Array, tables, cfg: Config):
    """The attention sub-block on the residual stream x (B, T, C): (its update
    of x, {"index_kl" the indexer's loss, "threshold" (B, T), "keep" (B, T, T)
    int8, the selection's counts})."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("qkv"):
        q = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, d)
        k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
        v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
        q = rmsnorm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rmsnorm(k, p["k_norm"], cfg.rms_norm_eps)
    with jax.named_scope("rope"):
        q, k = rotate(q, tables["attn"]).astype(dt), rotate(k, tables["attn"]).astype(dt)
    with jax.named_scope("index"):
        index = indexer(p, h, tables["index"], cfg)
    with jax.named_scope("select"):
        threshold, keep, counts = sparse_attention.select(*index, cfg.index_topk)
    with jax.named_scope("attn"):
        out, lse = full_attention(q, k, v, causal=True, keep=keep, with_lse=True)
    with jax.named_scope("index_loss"):
        # its rule gives q, k and lse no gradient: the target is detached there
        index_kl = sparse_attention.index_kl(*index, q, k, lse, keep)
    with jax.named_scope("out"):
        update = matmul(out.reshape(b, t, heads * d), p["wo"], dt, jnp.float32)
    return update, {"index_kl": index_kl, "threshold": threshold, "keep": keep, **counts}


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
    "weights", "router_input"}."""
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


def block(p: Dict[str, jax.Array], x: jax.Array, tables, cfg: Config,
          selection: bool = False):
    """One layer on x (B, T, C) float32: (x, the two sub-blocks' statistics).
    The `keep` plane and the residual stream the layer started from are among
    them only where `selection` asks (`selections`): a training step has no
    use for four layers' planes."""
    with jax.named_scope("attn"):
        update, chosen = attention(p, x, tables, cfg)
    stats = {name: value for name, value in chosen.items() if name != "keep"}
    if selection:
        stats.update(keep=chosen["keep"], layer_input=x)
    x = x + update
    with jax.named_scope("moe"):
        y, routed = moe(p, x, cfg)
        return x + y, {**stats, **routed}


LAYER_KEYS = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm",
              "index_wq", "index_wk", "index_k_scale", "index_k_bias", "index_w",
              "moe_norm", "moe_router", "w_gate", "w_up", "w_down")
# the indexer's own: gradient from the index loss alone
INDEX_KEYS = ("index_wq", "index_wk", "index_k_scale", "index_k_bias", "index_w")


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config,
            selection: bool = False):
    """tokens (B, T) -> (logits (B, T, V) float32, per-layer statistics
    stacked on a leading layer axis). Per-layer parameters carry a leading
    layer axis (`LAYER_KEYS`)."""
    stats = []
    with jax.named_scope("keye"):
        tables = rotary_tables(cfg, tokens.shape[1])
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for layer in range(cfg.num_hidden_layers):
            x, s = jax.checkpoint(
                lambda p, x, tables: block(p, x, tables, cfg, selection),
                policy=sparse_attention.KEEP_SELECTION,
            )({k: params[k][layer] for k in LAYER_KEYS}, x, tables)
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


def selections(params, tokens, cfg: Config):
    """What the program's own indexers decide in its forward pass: (the
    residual stream each layer started from (layers, B, T, C), the row
    thresholds (layers, B, T), the `keep` planes (layers, B, T, T) int8)."""
    stats = forward(params, tokens, cfg, selection=True)[1]
    return stats["layer_input"], stats["threshold"], stats["keep"]


def index_plane(layer_params, x, cfg: Config):
    """One layer's index scores (B, T, T) float32 on a GIVEN residual stream x
    (B, T, C): what that layer's `select` ranks."""
    h = rmsnorm(x, layer_params["attn_norm"], cfg.rms_norm_eps)
    return sparse_attention.index_scores(
        *indexer(layer_params, h, rotary_tables(cfg, x.shape[1])["index"], cfg))


# ------------------------------------------------------------------ #
# The zoo contract

_LAST_STEP = ("selected_pairs", "causal_pairs", "live_blocks", "causal_blocks")


class Keye(nn.Module):
    """Initialisation as `mellum.Mellum` has it and for its reason (Zipf token
    ids, routers that must tell tokens apart): normal(`initializer_range`) for
    every matrix — the indexer's three among them — ones for every norm's
    scale, zeros for the index keys' layernorm bias,
    normal(`embedding_initializer_range`) for the embedding and
    normal(`residual_initializer_range`) for `wo` and `w_down`."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, C, V, D = c.num_hidden_layers, c.hidden_size, c.vocab_size, c.head_dim
        H, Hkv, F, held = (c.num_attention_heads, c.num_key_value_heads,
                           c.moe_intermediate_size, c.num_experts)
        Hi, Di = c.indexer_num_heads, c.indexer_head_dim
        normal, ones = nn.initializers.normal(c.initializer_range), nn.initializers.ones
        residual = nn.initializers.normal(c.residual_initializer_range)
        shapes = {
            "embed": ((V, C), nn.initializers.normal(c.embedding_initializer_range)),
            "final_norm": ((C,), ones),
            "head": ((C, V), normal),
            "attn_norm": ((L, C), ones),
            "wq": ((L, C, H * D), normal), "wk": ((L, C, Hkv * D), normal),
            "wv": ((L, C, Hkv * D), normal), "wo": ((L, H * D, C), residual),
            "q_norm": ((L, D), ones), "k_norm": ((L, D), ones),
            "index_wq": ((L, C, Hi * Di), normal), "index_wk": ((L, C, Di), normal),
            "index_k_scale": ((L, Di), ones),
            "index_k_bias": ((L, Di), nn.initializers.zeros),
            "index_w": ((L, C, Hi), normal),
            "moe_norm": ((L, C), ones),
            "moe_router": ((L, C, c.all_experts), normal),
            "w_gate": ((L, held, C, F), normal), "w_up": ((L, held, C, F), normal),
            "w_down": ((L, held, F, C), residual),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, (L,), dtype)
        passes = counter("router_state", "held_passes")
        row_tiles = counter("router_state", "held_row_tiles")
        row_chunks = counter("router_state", "held_row_chunks")
        held_share = counter("router_state", "pairs_held_share", jnp.float32)
        tie_rows = counter("dsa", "tie_rows")
        last_step = {name: counter("dsa", name) for name in _LAST_STEP}
        logits, stats = forward(params, features, c)
        # overwrite, not flax's default append: the trainer threads mutable
        # collections through every step (see api.layers.MoE)
        for name, value in (("load_balance", c.load_balance_coef * jnp.sum(stats["load_balance"])),
                            ("index_kl", c.index_loss_coef * jnp.sum(stats["index_kl"]))):
            self.sow("losses", name, value, reduce_fn=lambda prev, new: new,
                     init_fn=lambda: jnp.float32(0.0))
        if training and not self.is_initializing():
            idx, routing = stats["expert_idx"], c.routing
            passes.value = passes.value + held_passes(idx, routing)
            row_tiles.value = row_tiles.value + held_row_tiles(idx, routing)
            row_chunks.value = row_chunks.value + held_row_chunks(idx, routing)
            held_share.value = (pairs_on_held(idx, routing).astype(jnp.float32)
                                / (idx.shape[1] * idx.shape[2]))
            tie_rows.value = tie_rows.value + stats["tie_rows"]
            for name, variable in last_step.items():
                variable.value = stats[name]
        return logits


def custom_model(**kwargs) -> Keye:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Keye(Config(**given))


# ModelSpec picks these up: each auxiliary term is sown already multiplied by
# its coefficient, and the step reports each under a name of its own.
aux_loss_weight = 1.0
aux_loss_terms = {"load_balance": "loss_balance", "index_kl": "loss_index"}


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B, T, V) +
    (B, T) -> (B,), as `loss` (to which the trainer adds the two sown terms
    before it minimises) and again as `loss_ce`."""
    with jax.named_scope("keye/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs.astype(jnp.float32), labels.astype(jnp.int32)).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}
