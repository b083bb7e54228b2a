"""GLM-4.7-Flash: a decoder-only LM with latent (low-rank) attention, a leading
dense layer, sparse-expert layers behind a sigmoid router with a selection
bias, a shared expert and a multi-token-prediction module
(zai-org/GLM-4.7-Flash, `model_type: glm4_moe_lite`; every key is one of
DeepSeek-V2/V3's: arXiv:2405.04434 §2.1, arXiv:2412.19437 §2.1-2.2).

The mathematics is written ONCE, as pure functions over a dict of arrays —
`latent_attention`, `dense_mlp`, `moe`, `layer`, `forward` — like `olmoe.py`
and `nemotron_h.py`; the flax module at the bottom declares the parameters
and owns the routers' state. A layer is TWO residual sub-blocks,
`x ← x + attention(rmsnorm(x))` then `x ← x + feed_forward(rmsnorm(x))`, eps
`rms_norm_eps`, no bias anywhere (hidden C, H heads):

- latent attention (every layer): `c_q = rmsnorm(h·W_qa)` (`q_lora_rank`);
  `q = c_q·W_qb` → H heads × (`qk_nope_head_dim` | `qk_rope_head_dim`);
  `[c_kv | k_r] = h·W_kva` (`kv_lora_rank` | `qk_rope_head_dim`);
  `c_kv ← rmsnorm(c_kv)`; `[k_nope | v] = c_kv·W_kvb` → H heads ×
  (`qk_nope_head_dim` | `v_head_dim`); rotary positions (rotate-half, all of
  the rotary part) on q's rotary part and on `k_r`, which is ONE head that all
  H query heads use; `q = [q_nope | q_rope]`, `k = [k_nope | k_r]`; causal
  softmax at scale (nope + rope)^-1/2 (`ops.attention.full_attention`: the
  flash kernel on a TPU, k materialised at H heads); `·W_o`. The training
  form: `W_kvb` is applied, nothing is absorbed.
- dense feed-forward (the first `first_k_dense_replace` layers):
  `W_down(silu(h·W_gate) ⊙ h·W_up)`, width `intermediate_size`.
- sparse feed-forward (the others): scores `sigmoid(h·W_r)` in float32 over
  ALL `router_experts`; the `num_experts_per_tok` with the largest `score + b`
  (b: the selection bias, which selects and does not weigh); weights
  `routed_scaling_factor · s_e / Σ_chosen s`; an expert is the gated SiLU unit
  above at width `moe_intermediate_size`; this chip holds experts
  `first_expert … first_expert + n_routed_experts − 1` and computes every pair
  routed to them (`ops.moe.dropless_moe`, `held`); what the other experts
  would add is left out; plus the shared expert (`n_shared_experts` units
  wide) on every token. b is no parameter: after each training step
  `b_e ← b_e + bias_update_speed · sign(mean load − load_e)` (collection
  `router_state`, `nemotron_h.py`'s `updated_bias`). No auxiliary loss.
- multi-token prediction, depth `num_nextn_predict_layers` (0 or 1): with x
  the residual stream the last layer left (before `final_norm`) and e the
  embedding of the NEXT token, `h' = [rmsnorm_h(x) ; rmsnorm_e(e)]·W_eh`
  (2C → C); one more sparse layer of its own; its own final norm; the SAME
  head and the SAME embedding as the main stream; the target is the token
  after next. Shapes stay T: the module runs over all T positions on the
  features rolled by one, and the loss leaves its last position, which has no
  target, out of its mean.
- `loss = CE_main + MTP_LOSS_WEIGHT · CE_mtp`, per-example means in float32.

`outputs` is a dict of the two logit streams, `{"logits", "mtp_logits"}`
(B, T, V) float32; `loss` returns `{"loss", "loss_main", "loss_mtp"}`, of
which the trainer minimises `loss` and reports the others beside it.

Precision: parameters, gradients, every RMSNorm (the two latent ones among
them), the router, rotary positions, softmaxes, the residual stream and the
losses float32; the projections and the experts' matmuls take `compute_dtype`
operands (bfloat16 on the chip) and accumulate in float32. What a sub-block
adds to the residual stream is written in float32 as the matmul accumulated
it (`nemotron_h.py` says why).

Parameters are stacked per KIND, flat names. The module's own layer is the
LAST entry of the attention stacks (`attn_norm`, `q_a`, `q_a_norm`, `q_b`,
`kv_a`, `kv_a_norm`, `kv_b`, `wo`: layers + module) and of the sparse stacks
(`moe_norm`, `moe_router`, `shared_gate`, `shared_up`, `shared_down`, and the
held routed experts `w_gate`, `w_up`, `w_down`: (sparse layers + module,
experts, ., .), the names `benchmark/check_lm.py` judges expert by expert);
`mlp_*` the dense layers; `mtp_hnorm`, `mtp_enorm`, `mtp_eh_proj`,
`mtp_final_norm` the module's own.

Every layer, the module's among them, is recomputed in the backward pass
(`jax.checkpoint` around the pair of sub-blocks): of a layer's activations
only the residual stream it started from is kept — and, by the policy
`pallas_attention.KEEP_RESIDUALS`, what the flash kernels' backward reads: q,
k, v, the output and the logsumexp (5 × 84 MB a layer at 8192 tokens). The
recomputation then neither runs the forward kernel again nor rebuilds q, k
and v, and the attention's gradient is taken at the forward pass's own
operands (that module's docstring says why it is all five or none). It is
fixed here, not a setting. The layers are walked one by one, not scanned: a
`lax.scan` over the stacked sparse layers compiles in half the time (36 Mosaic
calls for 84) and costs 3.7 GiB of stacked gradients and sliced stacks
(REHEARSAL, PR 32).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition; the optimizer, the batch partition, `rmsnorm` and `rope`
are `olmoe.py`'s. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax

from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.ops import pallas_attention
from elasticdl_tpu.ops.attention import full_attention
# `_matmul`: the name `benchmark/rehearse/departures_glm4_moe_lite.py` patches here
from model_zoo.transformer.nemotron_h import matmul as _matmul
from model_zoo.transformer.nemotron_h import (
    held_passes, held_row_chunks, held_row_tiles, updated_bias)
from model_zoo.transformer.olmoe import (  # noqa: F401
    batch_partition, optimizer, rmsnorm, rope)
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401

# λ of DeepSeek-V3 §4.2 (its first 10T tokens): the module's weight in the loss
MTP_LOSS_WEIGHT = 0.3


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names. Three are
    this repo's: `router_experts` (how many experts the router chooses among;
    0: `n_routed_experts`, every expert held here), `first_expert` (the first
    of the `n_routed_experts` held here) and `bias_update_speed`."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    n_routed_experts: int = 64         # the experts HELD here
    router_experts: int = 0
    first_expert: int = 0
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.8
    bias_update_speed: float = 1e-3
    num_nextn_predict_layers: int = 1
    rms_norm_eps: float = 1e-5
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                f"queries and keys of {self.qk_nope_head_dim} + "
                f"{self.qk_rope_head_dim} beside values of {self.v_head_dim}: "
                "the attention kernels take one head size")
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError("num_nextn_predict_layers is 0 or 1 here")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError("first_k_dense_replace exceeds num_hidden_layers")

    @property
    def num_experts(self) -> int:
        """What the router chooses among."""
        return self.router_experts or self.n_routed_experts

    @property
    def held(self):
        return (self.first_expert, self.n_routed_experts)

    @property
    def sparse_layers(self) -> int:
        """Of the main stream; the module's own comes after them."""
        return self.num_hidden_layers - self.first_k_dense_replace


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)


def latent_attention(p: Dict[str, jax.Array], x: jax.Array, cfg, rotate=None,
                     q_scale=None) -> jax.Array:
    """The attention sub-block's update of the residual stream x (B, T, C).
    `rotate`: the rotary map of q's rotary part (B, T, H, rot) and of the one
    rotary key (B, T, 1, rot), where it is not the plain table at
    `cfg.rope_theta` (`xing4.py`: YaRN's; `kimi_linear.py`: the identity, no
    positions); p without `q_a` has the query as ONE projection `q_proj`
    (`q_lora_rank` null: no low rank, no query norm); `q_scale`: a factor on the scores
    beside (nope + rope)^-1/2, applied to q in float32 before it is rounded
    for the kernel. v may be narrower than q and k (`v_head_dim`)."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = x.shape
    heads, nope, rot = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    if rotate is None:
        rotate = lambda part: rope(part, cfg.rope_theta)
    h = rmsnorm(x, p["attn_norm"], cfg.rms_norm_eps)
    if "q_a" in p:
        with jax.named_scope("q_lora"):
            c_q = rmsnorm(_matmul(h, p["q_a"], dt, jnp.float32), p["q_a_norm"],
                          cfg.rms_norm_eps)
            q = _matmul(c_q, p["q_b"], dt, jnp.float32).reshape(b, t, heads, nope + rot)
    else:       # `q_lora_rank` null: one projection, no query norm
        with jax.named_scope("q_proj"):
            q = _matmul(h, p["q_proj"], dt, jnp.float32).reshape(b, t, heads, nope + rot)
    with jax.named_scope("kv_lora"):
        kv_a = _matmul(h, p["kv_a"], dt, jnp.float32)
        c_kv = rmsnorm(kv_a[..., :cfg.kv_lora_rank], p["kv_a_norm"], cfg.rms_norm_eps)
        k_r = kv_a[..., cfg.kv_lora_rank:].reshape(b, t, 1, rot)
        kv = _matmul(c_kv, p["kv_b"], dt).reshape(b, t, heads, nope + cfg.v_head_dim)
    with jax.named_scope("rope"):
        q = jnp.concatenate([q[..., :nope], rotate(q[..., nope:])], axis=-1)
        q = (q if q_scale is None else q * q_scale).astype(dt)
        # one rotary key head, used by every query head
        k_r = jnp.broadcast_to(rotate(k_r).astype(dt), (b, t, heads, rot))
        k = jnp.concatenate([kv[..., :nope], k_r], axis=-1)
    with jax.named_scope("attn"):
        out = full_attention(q, k, kv[..., nope:], causal=True)
    with jax.named_scope("out"):
        return _matmul(out.reshape(b, t, -1), p["wo"], dt, jnp.float32)


def gated_mlp(h, w_gate, w_up, w_down, dt):
    """`W_down(silu(W_gate h) ⊙ W_up h)` on all rows of h; float32 out."""
    gate = _matmul(h, w_gate, dt, jnp.float32)
    up = _matmul(h, w_up, dt, jnp.float32)
    return _matmul(jax.nn.silu(gate) * up, w_down, dt, jnp.float32)


def dense_mlp(p: Dict[str, jax.Array], x: jax.Array, cfg: Config) -> jax.Array:
    h = rmsnorm(x, p["mlp_norm"], cfg.rms_norm_eps)
    return gated_mlp(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"],
                     jnp.dtype(cfg.compute_dtype))


def route(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The router of one sparse layer on the residual stream x (B, T, C):
    (the normed tokens (N, C), weights (N, k), expert_idx (N, k))."""
    h = rmsnorm(x, p["moe_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
    logits = jnp.dot(h, p["moe_router"].astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    _, weights, expert_idx = moe_ops.sigmoid_topk_route(
        logits, bias, cfg.num_experts_per_tok, cfg.routed_scaling_factor)
    return h, weights, expert_idx


def moe(p: Dict[str, jax.Array], x: jax.Array, bias: jax.Array, cfg: Config):
    """The sparse feed-forward's update of x, and {"expert_idx", "weights",
    "router_input"} for the bias update, the counters and the benchmark's
    comparison of routing."""
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


ATTN_KEYS = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "wo")
DENSE_KEYS = ("mlp_norm", "mlp_gate", "mlp_up", "mlp_down")
SPARSE_KEYS = ("moe_norm", "moe_router", "shared_gate", "shared_up", "shared_down",
               "w_gate", "w_up", "w_down")


def layer(p: Dict[str, jax.Array], x: jax.Array, bias, cfg: Config):
    """One layer on x (B, T, C) float32: (x, the routing's statistics of a
    sparse layer or None). `bias` None makes it a dense layer."""
    with jax.named_scope("mla"):
        x = x + latent_attention(p, x, cfg)
    if bias is None:
        with jax.named_scope("dense_mlp"):
            return x + dense_mlp(p, x, cfg), None
    with jax.named_scope("moe"):
        y, stats = moe(p, x, bias, cfg)
        return x + y, stats


def _layer_params(params, attn_index, keys, index):
    p = {k: params[k][attn_index] for k in ATTN_KEYS}
    p.update({k: params[k][index] for k in keys})
    return p


def _head(x, norm, head, cfg: Config):
    h = rmsnorm(x, norm, cfg.rms_norm_eps)
    return _matmul(h, head, jnp.dtype(cfg.compute_dtype), jnp.float32)


def forward(params: Dict[str, jax.Array], bias: jax.Array, tokens: jax.Array,
            cfg: Config):
    """tokens (B, T), bias (sparse layers + module, router_experts) ->
    ({"logits" (B, T, V) float32, and with the module "mtp_logits"}, the
    sparse layers' statistics stacked on a leading axis, the module's last)."""
    dense, sparse = cfg.first_k_dense_replace, cfg.sparse_layers
    stats = []
    with jax.named_scope("glm4_moe_lite"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i in range(cfg.num_hidden_layers):
            if i < dense:
                p = _layer_params(params, i, DENSE_KEYS, i)
                x = jax.checkpoint(lambda p, x: layer(p, x, None, cfg)[0],
                                   policy=pallas_attention.KEEP_RESIDUALS)(p, x)
            else:
                p = _layer_params(params, i, SPARSE_KEYS, i - dense)
                x, s = jax.checkpoint(
                    lambda p, x, b: layer(p, x, b, cfg),
                    policy=pallas_attention.KEEP_RESIDUALS)(p, x, bias[i - dense])
                stats.append(s)
        outputs = {}
        with jax.named_scope("head_loss"):
            outputs["logits"] = _head(x, params["final_norm"], params["head"], cfg)
        if cfg.num_nextn_predict_layers:
            with jax.named_scope("mtp"):
                with jax.named_scope("join"):
                    # position i takes the embedding of token i + 1; the last
                    # position wraps around and is left out of the loss
                    nxt = jnp.take(params["embed"], jnp.roll(tokens, -1, axis=1),
                                   axis=0).astype(jnp.float32)
                    joined = jnp.concatenate(
                        [rmsnorm(x, params["mtp_hnorm"][0], cfg.rms_norm_eps),
                         rmsnorm(nxt, params["mtp_enorm"][0], cfg.rms_norm_eps)], axis=-1)
                    y = _matmul(joined, params["mtp_eh_proj"][0],
                                jnp.dtype(cfg.compute_dtype), jnp.float32)
                p = _layer_params(params, cfg.num_hidden_layers, SPARSE_KEYS, sparse)
                y, s = jax.checkpoint(
                    lambda p, x, b: layer(p, x, b, cfg),
                    policy=pallas_attention.KEEP_RESIDUALS)(p, y, bias[sparse])
                stats.append(s)
                with jax.named_scope("head_loss"):
                    outputs["mtp_logits"] = _head(
                        y, params["mtp_final_norm"][0], params["head"], cfg)
    return outputs, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *stats)


def expert_assignments(params, bias, tokens, cfg: Config):
    """What the program's own routers decide in its forward pass:
    (expert_idx (sparse layers + module, B·T, k), weights (the same), the
    residual stream each router saw (the same, B, T, C)). The heads are dead
    code here."""
    stats = forward(params, bias, tokens, cfg)[1]
    return stats["expert_idx"], stats["weights"], stats["router_input"]


# ------------------------------------------------------------------ #
# The zoo contract


class Glm4MoeLite(nn.Module):
    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        C, V, H = c.hidden_size, c.vocab_size, c.num_attention_heads
        M = c.num_nextn_predict_layers
        A, D, S = c.num_hidden_layers + M, c.first_k_dense_replace, c.sparse_layers + M
        qk = c.qk_nope_head_dim + c.qk_rope_head_dim
        F, held = c.moe_intermediate_size, c.n_routed_experts
        Fs = F * c.n_shared_experts
        normal, ones = nn.initializers.normal(0.02), nn.initializers.ones
        shapes = {
            "embed": ((V, C), normal), "final_norm": ((C,), ones),
            "head": ((C, V), normal),
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
            "mtp_hnorm": ((M, C), ones), "mtp_enorm": ((M, C), ones),
            "mtp_eh_proj": ((M, 2 * C, C), normal), "mtp_final_norm": ((M, C), ones),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        bias = self.variable("router_state", "e_score_correction_bias",
                             jnp.zeros, (S, c.num_experts), jnp.float32)
        passes = self.variable("router_state", "held_passes", jnp.zeros, (S,), jnp.int32)
        row_tiles = self.variable("router_state", "held_row_tiles", jnp.zeros, (S,), jnp.int32)
        row_chunks = self.variable("router_state", "held_row_chunks", jnp.zeros, (S,), jnp.int32)
        outputs, stats = forward(params, bias.value, features, c)
        if training and not self.is_initializing():
            bias.value = updated_bias(bias.value, stats["expert_idx"], c)
            passes.value = passes.value + held_passes(stats["expert_idx"], c)
            row_tiles.value = row_tiles.value + held_row_tiles(stats["expert_idx"], c)
            row_chunks.value = row_chunks.value + held_row_chunks(stats["expert_idx"], c)
        return outputs


def custom_model(**kwargs) -> Glm4MoeLite:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Glm4MoeLite(Config(**given))


def _cross_entropy(logits, labels):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits.astype(jnp.float32), labels.astype(jnp.int32))


def mtp_labels(labels):
    """The module's targets: position i predicts the token after next, the
    main stream's label of position i + 1; the last position has none."""
    return jnp.roll(labels, -1, axis=1)


def loss(labels, outputs):
    """Per-example means, float32, (B,) each: `loss_main` the next-token cross
    entropy over T positions, `loss_mtp` the module's over the T − 1 that
    have a target, `loss` their weighted sum, which is what is minimised."""
    with jax.named_scope("glm4_moe_lite/head_loss"):
        main = _cross_entropy(outputs["logits"], labels).mean(axis=-1)
    if "mtp_logits" not in outputs:
        return {"loss": main, "loss_main": main}
    with jax.named_scope("glm4_moe_lite/mtp/head_loss"):
        mtp = _cross_entropy(outputs["mtp_logits"], mtp_labels(labels))[:, :-1].mean(axis=-1)
    return {"loss": main + MTP_LOSS_WEIGHT * mtp, "loss_main": main, "loss_mtp": mtp}


class StreamAccuracy(TokenAccuracy):
    """`TokenAccuracy` of one of the two logit streams: the module's against
    its own targets, its last position not counted."""

    def __init__(self, stream: str):
        self.stream = stream

    def update(self, state, labels, outputs, mask=None):
        if self.stream not in outputs:
            return state
        if self.stream == "mtp_logits":
            return super().update(state, mtp_labels(labels)[:, :-1],
                                  outputs[self.stream][:, :-1], mask)
        return super().update(state, labels, outputs[self.stream], mask)


def eval_metrics_fn():
    return {"token_accuracy": StreamAccuracy("logits"),
            "mtp_token_accuracy": StreamAccuracy("mtp_logits")}
