"""Trinity (`model_type: afmoe`, arcee-ai/Trinity-Mini): a decoder-only LM
whose layers vary in TWO ways at once — sliding-window or full attention by
the layer's published index, a dense or a sparse-expert feed-forward by
`num_dense_layers` — whose attention is GATED on its output, normalises its q
and k heads, rotates them in the sliding layers ONLY, and whose sub-blocks
write to the residual stream through a norm of their own: four norms a layer.

The mathematics is written ONCE, as pure functions over a dict of arrays —
`attention`, `dense_mlp`, `moe`, `block`, `forward` — like `olmoe.py`; the
flax module at the bottom declares the parameters and owns the routers' state
and the counters. Hidden C, H query heads and Hkv key-value heads of D, window
W, E experts of width F, k a token, eps `rms_norm_eps`, no bias anywhere.
A layer of PUBLISHED index l is FULL iff (l + 1) % `global_attn_every_n_layers`
== 0 and DENSE iff l < `num_dense_layers`; `kept_layers` lists the published
indices of the layers built here (a cut keeps, say, 0, 2, 3, 4, 5) and the
kinds follow the published index, not the position in the stack.

- `x0 = Emb[t] · sqrt(C)` (`mup_enabled` true: what the published model has).
- `x = x + rmsnorm_post_attn(Attn(rmsnorm_in(x)))`, then
  `x = x + rmsnorm_post_mlp(MLP(rmsnorm_pre_mlp(x)))`.
- attention, h the normed input: `q = h·Wq` (H x D), `k = h·Wk`, `v = h·Wv`
  (Hkv x D), `g = h·Wg` (H·D); `q, k = rmsnorm(q; w_qn), rmsnorm(k; w_kn)` over
  D, one weight vector each for all heads, BEFORE any rotation; in a sliding
  layer rotary positions (rotate-half, all D, the plain table at `rope_theta`),
  in a full layer NONE — positions reach it through the causal mask alone;
  softmax at scale D^-1/2, query head i reading key-value head i // (H / Hkv)
  (`ops.attention.full_attention`: the flash kernels on a TPU, `window=W`
  their banded grids); full: key j visible to query i iff j <= i; sliding: iff
  i - W < j <= i. `Attn = (o * sigmoid(g))·Wo`: the gate is elementwise on the
  (T, H·D) output, before the output projection.
- dense feed-forward: `W_down(silu(h·W_gate) * h·W_up)`, width
  `intermediate_size`.
- sparse feed-forward: scores `sigmoid(h·W_r)` in float32 over ALL
  `router_experts`; the k with the largest `score + b` (b selects and does not
  weigh); weights `route_scale · s_e / (sum_chosen s + 1e-20)`
  (`ops.moe.sigmoid_topk_route`); this chip holds experts `first_expert ...
  first_expert + num_experts - 1` and computes every pair routed to them
  (`ops.moe.dropless_moe`, `held`); what the other experts would add is left
  out; plus the one shared expert (`num_shared_experts` 1), as wide as a
  routed one, on every token.
- b is no parameter: after each training step `d_e = load_balance_coeff ·
  sign(mean load - load_e)`, `b = b + d - mean(d)`, from zero. The state kept
  (collection `router_state`, `expert_bias`) is the running sum `a` of the d,
  and a router adds `b = a - mean(a)` (`centred`), which IS that recurrence:
  sum(d - mean(d)) = sum(d) - mean(sum(d)). Kept so because one sign of d that
  falls the other way then moves ONE entry of the state, not a whole layer's
  through mean(d), and the benchmark's check compares the state entry by
  entry. The centring moves every entry of a layer alike, so it never changes
  a selection. No auxiliary loss.
- final rmsnorm, an untied head, per-example mean next-token cross entropy.

The gate's backward reads the kernel's UNGATED output (`d gate = d(o·g)·o`),
which is one of the five arrays `pallas_attention.KEEP_RESIDUALS` keeps across
a layer's recomputation: where a layer keeps them the one copy serves the
kernels' backward and the gate's alike; where it does not, the recomputation's
forward kernel rebuilds it for both.

Precision: parameters, every norm (the heads' among them), the router, rotary
positions, the gate's sigmoid and its product, softmaxes, the residual stream
and the loss float32; the projections, the experts' matmuls and the head take
`compute_dtype` operands (bfloat16 on the chip) and accumulate in float32; q
and k are written float32 (the head norms and the rotation read what the
matmul accumulated) and enter the kernels as `compute_dtype`.

Every layer is recomputed in the backward pass (`jax.checkpoint`), the layers
whose kind is in `KEEP_RESIDUALS_KINDS` under `policy=pallas_attention.
KEEP_RESIDUALS`. Fixed here, not a setting (`benchmark/configs/
trinity-mini.json`, `changed.recomputation`, has the bytes each choice needs).

Parameters are stacked per KIND of sub-block, flat names: the attention stacks
and the four norms (`attn_norm`, `post_attn_norm`, `mlp_norm`, `post_mlp_norm`)
carry every layer built, `mlp_*` the dense ones, `moe_router`, `shared_*`,
`w_gate`, `w_up`, `w_down` the sparse ones (the last three the held routed
experts, the names `benchmark/check_lm.py` judges expert by expert).

`outputs` is `{"logits" (B, T, V) float32, "gate_mean" (B, 2)}` — the mean of
`sigmoid(g)` over the layers of each kind [sliding, full], which only the
evaluation metrics read (a training step drops it as dead code).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition; the optimizer, the batch partition, `rmsnorm` and `rope`
are `olmoe.py`'s, `gated_mlp` is `glm4_moe_lite.py`'s, the held share's
counters `nemotron_h.py`'s. Data: `synthetic://lm?vocab=V&seq=T`.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops.attention import full_attention
from elasticdl_tpu.training import metrics as metrics_lib
from model_zoo.transformer.glm4_moe_lite import gated_mlp
from model_zoo.transformer.nemotron_h import (
    held_passes, held_row_chunks, held_row_tiles, matmul, pairs_on_held)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, optimizer, rmsnorm, rope)
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401

KINDS = ("sliding", "full")
# the kinds of layer whose flash residuals are kept across the recomputation
KEEP_RESIDUALS_KINDS = ("full",)


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names, but for one:
    the model_params key `num_experts` — how many experts are HELD here, what a
    benchmark configuration's `reduced` cuts — is the field `held_experts`, and
    `num_experts` is what the ROUTER chooses among (`router_experts`, or all
    held), the name `nemotron_h.py`'s counters and the benchmark's drivers read
    it by. This repo's own: `kept_layers`, `router_experts`, `first_expert`
    and `compute_dtype`."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_hidden_layers: int = 32         # the layers BUILT here
    kept_layers: str = ""               # their published indices, "0,2,3,4,5"; "": 0, 1, 2, ...
    num_dense_layers: int = 2
    global_attn_every_n_layers: int = 4
    intermediate_size: int = 6144
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    held_experts: int = 128
    router_experts: int = 0
    first_expert: int = 0
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    route_scale: float = 2.826
    load_balance_coeff: float = 1e-3
    rms_norm_eps: float = 1e-5
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.sliding_window < 1 or self.global_attn_every_n_layers < 1:
            raise ValueError("sliding_window and global_attn_every_n_layers are at least 1")
        layers = self.layers
        if len(layers) != self.num_hidden_layers or list(layers) != sorted(set(layers)):
            raise ValueError(f"kept_layers {self.kept_layers!r} does not list "
                             f"{self.num_hidden_layers} published layers in order")

    @property
    def layers(self) -> tuple:
        """The published index of every layer built."""
        if not self.kept_layers:
            return tuple(range(self.num_hidden_layers))
        return tuple(int(l) for l in self.kept_layers.split(","))

    def kind(self, layer: int) -> str:
        return "full" if (layer + 1) % self.global_attn_every_n_layers == 0 else "sliding"

    def is_dense(self, layer: int) -> bool:
        return layer < self.num_dense_layers

    @property
    def dense_layers(self) -> int:
        return sum(self.is_dense(l) for l in self.layers)

    @property
    def sparse_layers(self) -> int:
        return self.num_hidden_layers - self.dense_layers

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.held_experts

    @property
    def held(self):
        return (self.first_expert, self.held_experts)


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def embed(params, tokens, cfg: Config):
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    return x * math.sqrt(cfg.hidden_size)


def qk_norm(p, q, k, cfg: Config):
    """q (B, T, H, D), k (B, T, Hkv, D) float32, each head normalised over D
    with one weight vector for all heads."""
    return (rmsnorm(q, p["q_norm"], cfg.rms_norm_eps),
            rmsnorm(k, p["k_norm"], cfg.rms_norm_eps))


def positions(q, k, kind: str, cfg: Config):
    """Rotary positions on the sliding layers' q and k; a full layer's go on
    as they are."""
    if kind == "full":
        return q, k
    with jax.named_scope("rope"):
        return rope(q, cfg.rope_theta), rope(k, cfg.rope_theta)


def gate(p, h, out, cfg: Config):
    """`out * sigmoid(h·Wg)` on the kernels' output (B, T, H·D), float32, and
    the gate's mean a batch row (B,)."""
    g = jax.nn.sigmoid(matmul(h, p["wg"], jnp.dtype(cfg.compute_dtype), jnp.float32))
    return out.astype(jnp.float32) * g, jnp.mean(g, axis=(1, 2))


def attention(p: Dict[str, jax.Array], x: jax.Array, kind: str, cfg: Config):
    """The attention sub-block on the residual stream x (B, T, C) before its
    post-norm: (Attn (B, T, C) float32, the gate's mean (B,))."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    h = rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    with jax.named_scope("qkv"):
        q = matmul(h, p["wq"], dt, jnp.float32).reshape(b, t, heads, d)
        k = matmul(h, p["wk"], dt, jnp.float32).reshape(b, t, kv_heads, d)
        v = matmul(h, p["wv"], dt).reshape(b, t, kv_heads, d)
    with jax.named_scope("qk_norm"):
        q, k = qk_norm(p, q, k, cfg)
    q, k = positions(q, k, kind, cfg)
    with jax.named_scope("attn"):
        out = full_attention(q.astype(dt), k.astype(dt), v, causal=True,
                             window=cfg.sliding_window if kind == "sliding" else None)
    with jax.named_scope("gate"):
        gated, gate_mean = gate(p, h, out.reshape(b, t, heads * d), cfg)
    with jax.named_scope("out"):
        return matmul(gated, p["wo"], dt, jnp.float32), gate_mean


def post_attn_norm(p, y, cfg: Config):
    return rmsnorm(y, p["post_attn_norm"], cfg.rms_norm_eps)


def post_mlp_norm(p, y, cfg: Config):
    return rmsnorm(y, p["post_mlp_norm"], cfg.rms_norm_eps)


def dense_mlp(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    h = rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps)
    return gated_mlp(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                     jnp.dtype(cfg.compute_dtype))


def centred(bias_sum: jax.Array) -> jax.Array:
    """The selection bias b of a layer from the running sum a (..., experts)
    of its updates: a − mean(a), what `b ← b + d − mean(d)` from zero gives."""
    return bias_sum - jnp.mean(bias_sum, axis=-1, keepdims=True)


def route(p: Dict[str, jax.Array], x: jax.Array, bias_sum: jax.Array, cfg: Config):
    """The router of one sparse layer on the residual stream x (B, T, C), its
    selection bias given as the running sum of its updates (experts,): (the
    normed tokens (N, C), weights (N, k), expert_idx (N, k))."""
    h = rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, weights, expert_idx = moe_ops.sigmoid_topk_route(
        logits, centred(bias_sum), cfg.num_experts_per_tok, cfg.route_scale)
    return h, weights, expert_idx


def moe(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The sparse feed-forward on x before its post-norm, and {"expert_idx",
    "weights", "router_input"} for the bias update, the counters and the
    benchmark's comparison of routing."""
    dt = jnp.dtype(cfg.compute_dtype)
    with jax.named_scope("router"):
        h, weights, expert_idx = route(p, x, bias, cfg)
    y = moe_ops.dropless_moe(
        h, expert_idx, weights, (p["w_gate"], p["w_up"], p["w_down"]),
        held=cfg.held, num_experts=cfg.num_experts, compute_dtype=dt)
    with jax.named_scope("shared"):
        y = y + gated_mlp(h, p["shared_gate"], p["shared_up"], p["shared_down"], dt)
    return y.reshape(x.shape), {
        "expert_idx": expert_idx, "weights": weights, "router_input": x}


ATTN_KEYS = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo",
             "post_attn_norm", "mlp_norm", "post_mlp_norm")
DENSE_KEYS = ("mlp_gate", "mlp_up", "mlp_down")
SPARSE_KEYS = ("moe_router", "shared_gate", "shared_up", "shared_down",
               "w_gate", "w_up", "w_down")


def block(p: Dict[str, jax.Array], x: jax.Array, bias, kind: str, cfg: Config):
    """One layer of `kind` on x (B, T, C) float32: (x, the gate's mean (B,),
    the routing's statistics of a sparse layer or None). `bias` None makes its
    feed-forward the dense one."""
    with jax.named_scope(kind):
        y, gate_mean = attention(p, x, kind, cfg)
        x = x + post_attn_norm(p, y, cfg)
    if bias is None:
        with jax.named_scope("dense_mlp"):
            return x + post_mlp_norm(p, dense_mlp(p, x, cfg), cfg), gate_mean, None
    with jax.named_scope("moe"):
        y, stats = moe(p, x, bias, cfg)
        return x + post_mlp_norm(p, y, cfg), gate_mean, stats


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (sparse layers, router_experts), the running sums
    of the selection bias's updates (`route` centres them) -> ({"logits"
    (B, T, V) float32, "gate_mean" (B, 2) by kind [sliding, full]}, the sparse
    layers' statistics stacked on a leading axis)."""
    stats, gates = [], {kind: [] for kind in KINDS}
    dense = sparse = 0
    with jax.named_scope("afmoe"):
        with jax.named_scope("embed"):
            x = embed(params, tokens, cfg)
        for i, layer in enumerate(cfg.layers):
            kind = cfg.kind(layer)
            p = {k: params[k][i] for k in ATTN_KEYS}
            if cfg.is_dense(layer):
                p.update({k: params[k][dense] for k in DENSE_KEYS})
                layer_bias, dense = None, dense + 1
            else:
                p.update({k: params[k][sparse] for k in SPARSE_KEYS})
                layer_bias, sparse = bias[sparse], sparse + 1
            x, gate_mean, s = jax.checkpoint(
                lambda p, x, b, kind=kind: block(p, x, b, kind, cfg),
                policy=(pallas_attention.KEEP_RESIDUALS
                        if kind in KEEP_RESIDUALS_KINDS else None),
            )(p, x, layer_bias)
            gates[kind].append(gate_mean)
            if s is not None:
                stats.append(s)
        with jax.named_scope("head_loss"):
            h = rmsnorm(x, params["final_norm"], cfg.rms_norm_eps)
            logits = matmul(h, params["head"], jnp.dtype(cfg.compute_dtype), jnp.float32)
    mean = lambda each: (jnp.mean(jnp.stack(each), axis=0) if each
                         else jnp.zeros((tokens.shape[0],), jnp.float32))
    outputs = {"logits": logits,
               "gate_mean": jnp.stack([mean(gates[kind]) for kind in KINDS], axis=-1)}
    return outputs, (jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)
                     if stats else None)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (sparse layers, B·T, k), weights (the same), the residual
    stream each router saw (sparse layers, B, T, C)). The head is dead code."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


def updated_bias(bias_sum, expert_idx, cfg: Config):
    """a + d, d_e = load_balance_coeff · sign(mean load − load_e): the running
    sum (sparse layers, experts) whose `centred` form the routers add,
    expert_idx (sparse layers, N, k), loads counted over all the experts the
    router chooses among."""
    load = jax.vmap(lambda idx: moe_ops.pairs_per_expert(idx, cfg.num_experts))(
        expert_idx).astype(jnp.float32)
    return bias_sum + cfg.load_balance_coeff * jnp.sign(
        jnp.mean(load, axis=-1, keepdims=True) - load)


def kv_block_visits(cfg: Config, seq_len: int):
    """((2,) the (q block, kv block) pairs a head's forward grid computes in
    one step, summed over the layers of each kind [sliding, full]; (2,) what a
    causal grid would compute there)."""
    dt = jnp.dtype(cfg.compute_dtype)
    layers = [sum(cfg.kind(l) == kind for l in cfg.layers) for kind in KINDS]
    banded, causal = zip(*(
        pallas_attention.kv_block_visits(seq_len, seq_len, window, cfg.head_dim, dt)
        for window in (cfg.sliding_window, None)))
    return ([n * v for n, v in zip(layers, banded)],
            [n * v for n, v in zip(layers, causal)])


# ------------------------------------------------------------------ #
# The zoo contract


class Afmoe(nn.Module):
    """Initialisation (`config.json` names none): normal(0.02) for every
    matrix and the embedding — times sqrt(C) a token's own embedding is 0.9 a
    coordinate — ones for every norm, zeros for the selection bias."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, Dn, S = c.num_hidden_layers, c.dense_layers, c.sparse_layers
        C, V, D = c.hidden_size, c.vocab_size, c.head_dim
        H, Hkv = c.num_attention_heads, c.num_key_value_heads
        F, held = c.moe_intermediate_size, c.held_experts
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), ones), "head": ((C, V), normal),
            "attn_norm": ((L, C), ones), "post_attn_norm": ((L, C), ones),
            "mlp_norm": ((L, C), ones), "post_mlp_norm": ((L, C), ones),
            "wq": ((L, C, H * D), normal), "wk": ((L, C, Hkv * D), normal),
            "wv": ((L, C, Hkv * D), normal), "wg": ((L, C, H * D), normal),
            "q_norm": ((L, D), ones), "k_norm": ((L, D), ones),
            "wo": ((L, H * D, C), normal),
            "mlp_gate": ((Dn, C, c.intermediate_size), normal),
            "mlp_up": ((Dn, C, c.intermediate_size), normal),
            "mlp_down": ((Dn, c.intermediate_size, C), normal),
            "moe_router": ((S, C, c.num_experts), normal),
            "shared_gate": ((S, C, F), normal), "shared_up": ((S, C, F), normal),
            "shared_down": ((S, F, C), normal),
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
        visits = counter("attn", "kv_block_visits", (len(KINDS),))
        visits_causal = counter("attn", "kv_block_visits_causal", (len(KINDS),))
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
            banded, causal = kv_block_visits(c, features.shape[1])
            visits.value = visits.value + jnp.asarray(banded, jnp.int32)
            visits_causal.value = visits_causal.value + jnp.asarray(causal, jnp.int32)
        return outputs


def custom_model(**kwargs) -> Afmoe:
    """Keys are the published config's (`num_experts`: the experts held here,
    `Config.held_experts`); unknown keys (the harness adds its own to every
    model) are ignored."""
    kwargs = {("held_experts" if k == "num_experts" else k): v for k, v in kwargs.items()}
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Afmoe(Config(**given))


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B,), as `loss`
    and again as `loss_ce`, the one term the step reports beside it."""
    with jax.named_scope("afmoe/head_loss"):
        ce = optax.softmax_cross_entropy_with_integer_labels(
            outputs["logits"].astype(jnp.float32), labels.astype(jnp.int32)).mean(axis=-1)
    return {"loss": ce, "loss_ce": ce}


class LogitAccuracy(TokenAccuracy):
    """`TokenAccuracy` of the `logits` entry of the outputs."""

    def update(self, state, labels, outputs, mask=None):
        return super().update(state, labels, outputs["logits"], mask)


class GateMean(metrics_lib.Metric):
    """The mean of `sigmoid(g)` over the layers of one kind, the tokens and
    the H·D gated coordinates: half at the seed, and wherever training takes
    it."""

    def __init__(self, kind: str):
        self.column = KINDS.index(kind)

    def init_state(self) -> np.ndarray:
        return np.zeros((2,), np.float32)

    def update(self, state, labels, outputs, mask=None):
        each = outputs["gate_mean"][:, self.column]
        weight = jnp.ones_like(each) if mask is None else jnp.asarray(mask, jnp.float32)
        return state + jnp.stack([jnp.sum(each * weight), jnp.sum(weight)])

    def result(self, state) -> float:
        return float(state[0] / max(float(state[1]), 1.0))


def eval_metrics_fn():
    return {"token_accuracy": LogitAccuracy(),
            **{f"gate_mean_{kind}": GateMean(kind) for kind in KINDS}}
