"""Phi-4-mini-flash: a decoder-decoder hybrid LM (SambaY) — Mamba-1 mixers and
sliding-window differential attention in its first half, then ONE full
attention layer whose keys and values every later attention layer reads, and
Gated Memory Units that read ONE Mamba layer's scan output
(microsoft/Phi-4-mini-flash-reasoning, `model_type: phi4flash`; SambaY
arXiv:2507.06607, Samba arXiv:2406.07522, Mamba arXiv:2312.00752 Algorithm 2,
differential attention arXiv:2410.05258).

The mathematics is written ONCE, as pure functions over a dict of arrays —
`layernorm`, `mlp`, `mamba`, `gmu`, `diff_attention`, `layer`, `forward` — like
`olmoe.py`, whose optimizer and batch partition these are; the flax module at
the bottom declares the parameters and owns the counters. Hidden C, H query
and Hkv key-value heads of D = C / H, MLP width F, window W, N layers, eps
`layer_norm_eps`, NO positional encoding anywhere.

Every layer of PUBLISHED index i (0-based; `kept_layers` lists the published
indices of the layers built here, and the kinds follow the published index,
not the position in the stack): `x ← x + Mixer_i(LN(x))`, then
`x ← x + MLP(LN(x))`; LN is LayerNorm (mean removed, scale AND bias);
`MLP(h) = (up ⊙ silu(gate))·W_down`, `(gate, up) = split(h·W_gate_up)`, no
bias. After the last layer a final LayerNorm, then `logits = h·Eᵀ`, E the
embedding itself (`tie_word_embeddings`). `layer_kind(i)`, M = 16 the
published half (`num_hidden_layers` / 2 of the PUBLISHED 32):

    i < M, even    "mamba"
    i < M, odd     "sliding"  differential attention, key j visible to query t
                              iff t − W < j ≤ t
    i = M          "mamba"    its scan output m is THE MEMORY
    i = M + 1      "full"     differential attention, causal; its k, v are
                              THE SHARED KEYS AND VALUES
    i > M + 1, even "gmu"     reads m
    i > M + 1, odd  "cross"   differential attention, q from this layer's
                              input, k and v layer M + 1's, causal

- Mamba (S6), E = `mamba_expand`·C channels, N_s = `mamba_d_state`, K =
  `mamba_d_conv`, R = ⌈C / 16⌉: `(x, z) = split(h·W_in)`; `x =
  silu(conv(x))` (depthwise causal, with bias: `ops.ssm.causal_conv1d`);
  `(δ, B, Cm) = split(x·W_x)`; `Δ = softplus(δ·W_dt + b_dt)`; `A =
  −exp(A_log)`; `S_t = exp(Δ_t ⊗ A) ⊙ S_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t`, `y_t =
  S_t·Cm_t + D ⊙ x_t` (`ops.ssm.selective_scan`: the Pallas kernels on a TPU);
  `m = y`, BEFORE the gate; `out = (y ⊙ silu(z))·W_out`. No bias in a projection.
- GMU: `out = (m ⊙ silu(h·W_1))·W_2`, m layer M's, token for token.
- Differential attention: `q, k, v = split(h·W_qkv + b)`; heads 2j and 2j + 1
  of q (and of k) are a pair (q¹, q²), (k¹, k²): H / 2 query pairs over Hkv / 2
  key-value pairs; v's heads 2j, 2j + 1 joined are ONE head of 2D. `A¹ =
  softmax(q¹k¹ᵀ/√D + mask)`, `A² = softmax(q²k²ᵀ/√D + mask)`, `o = (A¹ −
  λ·A²)·v`, `λ = exp(λ_q1·λ_k1) − exp(λ_q2·λ_k2) + λ_init(i)`, `λ_init(i) =
  0.8 − 0.6·exp(−0.3·i)` with i the PUBLISHED index; per head `o ←
  RMSNorm_{2D}(o; γ)·(1 − λ_init(i))`; the H / 2 heads of 2D side by side
  `·W_o + b`. A cross layer has `W_q` (with bias) in place of `W_qkv`.
  BOTH softmaxes are ONE call of the shared `ops.attention.full_attention`
  (the flash kernels on a TPU): the first-of-pair heads, then the second, are
  stacked on the head axis (H query heads over Hkv key heads of D, v's Hkv / 2
  heads of 2D twice) — `A·v` is linear in A, so `A¹v − λ·A²v` is the two
  halves' difference. One call walks each key-value head's keys once for both
  maps and keeps one set of residuals; two calls would launch every kernel
  twice for the same arithmetic (not measured against each other).

VALUES THAT CROSS LAYERS. Every layer is recomputed in the backward pass
(`jax.checkpoint`, the flash kernels' residuals kept:
`pallas_attention.KEEP_RESIDUALS`). The memory m (float32, 168 MB at 8192
tokens) and layer M + 1's k and v (`compute_dtype`, 21 MB each) are OUTPUTS of
their layers' checkpointed functions and INPUTS of their readers': they are
kept from the forward pass as a layer's input stream is, no reader recomputes
its producer, and their cotangents are the sums over their readers' (7 GMUs;
7 cross layers and layer M + 1 itself in the published model).

THE TIED MATRIX is one parameter leaf, `embed` (V, C), read by `jnp.take` and
by the head's matmul; its gradient is the float32 sum of the two.

Precision: parameters, gradients, the residual stream, LayerNorms, Δ, A, the
scan's state and y, both softmaxes' running sums, λ, the sub-norm, the loss
float32; projections, MLP and head take `compute_dtype` operands (bfloat16 on
the chip) and accumulate float32; q, k, v enter the flash kernels in
`compute_dtype`.

Counters, in collections the trainer threads through every step:
`s6/scan_elements` (tokens x channels x state indices the scans walk, summed
over steps; float32: a step's 1.3e9 passes int32 in two), `memory/reads` and
`shared_kv/reads` (readers, summed over steps), `diff_attn/lambda` (one value
an attention layer built here, the last step's) and `attn/kv_block_visits`
beside `attn/kv_block_visits_causal` (as `mellum.py` counts them).

Zoo contract: custom_model / loss / optimizer / dataset_fn / eval_metrics_fn
/ batch_partition. Data: `synthetic://lm?vocab=V&seq=T` (uint16 tokens).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from types import SimpleNamespace
from typing import Dict, Optional, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from elasticdl_tpu.ops import pallas_attention, ssm
from elasticdl_tpu.ops.attention import full_attention
from model_zoo.transformer.keye_vl2 import layernorm
from model_zoo.transformer.nemotron_h import _dt_bias_init, _uniform, matmul
from model_zoo.transformer.olmoe import batch_partition, optimizer, rmsnorm  # noqa: F401
from model_zoo.transformer.transformer_lm import TokenAccuracy, dataset_fn  # noqa: F401

KINDS = ("mamba", "sliding", "full", "gmu", "cross")
ATTENTION_KINDS = ("sliding", "full", "cross")
PUBLISHED_LAYERS = 32        # what the halves and `lambda_init` are counted in
# Mamba's draw of the step Δ's bias starts from (`nemotron_h._dt_bias_init`)
_DT_INIT = SimpleNamespace(time_step_min=1e-3, time_step_max=0.1, time_step_floor=1e-4)


@dataclass(frozen=True)
class Config:
    """The published `config.json` keys, under their own names. This repo's
    own: `kept_layers`, `compute_dtype`, and the Mamba sizes `config.json`
    does not carry (`mamba_*`: the Mamba paper's defaults, `assumed` in the
    benchmark's configuration)."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32         # the layers BUILT here
    kept_layers: str = ""               # their published indices, "0,1,16,17,18,19"; "": all
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    compute_dtype: str = "bfloat16"

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(f"{self.num_attention_heads} query heads do not divide "
                             f"over {self.num_key_value_heads} key-value heads")
        if self.num_attention_heads % 2 or self.num_key_value_heads % 2:
            raise ValueError("differential attention pairs the heads: even counts")
        if self.hidden_size % self.num_attention_heads:
            raise ValueError("the head size is hidden_size / num_attention_heads")
        if self.mb_per_layer != 2:
            raise ValueError("a Mamba layer every second layer (mb_per_layer 2) is "
                             "the one arrangement written down here")
        layers = self.layers
        if (len(layers) != self.num_hidden_layers or list(layers) != sorted(set(layers))
                or layers[-1] >= PUBLISHED_LAYERS):
            raise ValueError(f"kept_layers {self.kept_layers!r} does not list "
                             f"{self.num_hidden_layers} published layers in order")
        kinds = [layer_kind(i) for i in layers]
        memory, shared = PUBLISHED_LAYERS // 2, PUBLISHED_LAYERS // 2 + 1
        if "gmu" in kinds and memory not in layers:
            raise ValueError(f"a GMU reads layer {memory}'s memory: keep that layer")
        if "cross" in kinds and shared not in layers:
            raise ValueError(f"a cross layer reads layer {shared}'s keys and values: "
                             "keep that layer")

    @property
    def layers(self) -> tuple:
        """The published index of every layer built."""
        if not self.kept_layers:
            return tuple(range(self.num_hidden_layers))
        return tuple(int(i) for i in self.kept_layers.split(","))

    def layers_of(self, *kinds: str) -> int:
        return sum(layer_kind(i) in kinds for i in self.layers)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return -(-self.hidden_size // 16)


def layer_kind(i: int) -> str:
    """The kind of the layer of PUBLISHED index i (the table above)."""
    half = PUBLISHED_LAYERS // 2
    if i <= half + 1:
        return "mamba" if i % 2 == 0 else ("full" if i == half + 1 else "sliding")
    return "gmu" if i % 2 == 0 else "cross"


def lambda_init(i: int) -> float:
    """λ_init of the layer of PUBLISHED index i."""
    return 0.8 - 0.6 * math.exp(-0.3 * i)


# ------------------------------------------------------------------ #
# The mathematics: pure functions of (parameters, activations)

COMMON_KEYS = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "mlp_gate_up", "mlp_down")
MAMBA_KEYS = ("mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x", "mamba_dt_w",
              "mamba_dt_b", "mamba_A_log", "mamba_D", "mamba_out")
GMU_KEYS = ("gmu_in", "gmu_out")
ATTENTION_KEYS = ("attn_wo", "attn_wo_b", "attn_lambda", "attn_subln")
SELF_KEYS = ("attn_qkv", "attn_qkv_b")
CROSS_KEYS = ("cross_q", "cross_q_b")
# {the stack a kind's layers are counted in: its parameters}, by kind
KEYS_OF = {
    "mamba": {"mamba": MAMBA_KEYS}, "gmu": {"gmu": GMU_KEYS},
    "sliding": {"attention": ATTENTION_KEYS, "self": SELF_KEYS},
    "full": {"attention": ATTENTION_KEYS, "self": SELF_KEYS},
    "cross": {"attention": ATTENTION_KEYS, "cross": CROSS_KEYS},
}


def mlp(p: Dict[str, jax.Array], h: jax.Array, cfg: Config) -> jax.Array:
    dt = jnp.dtype(cfg.compute_dtype)
    gate, up = jnp.split(matmul(h, p["mlp_gate_up"], dt, jnp.float32), 2, axis=-1)
    return matmul(up * jax.nn.silu(gate), p["mlp_down"], dt, jnp.float32)


def mamba(p: Dict[str, jax.Array], h: jax.Array, cfg: Config) -> Tuple[jax.Array, jax.Array]:
    """The Mamba mixer on the normed stream h (B, T, C): (out (B, T, C), the
    scan's output m (B, T, E) BEFORE the gate), float32."""
    dt_c = jnp.dtype(cfg.compute_dtype)
    r, n = cfg.dt_rank, cfg.mamba_d_state
    with jax.named_scope("proj"):
        x, z = jnp.split(matmul(h, p["mamba_in"], dt_c, jnp.float32), 2, axis=-1)
    with jax.named_scope("conv"):
        x = jax.nn.silu(ssm.causal_conv1d(x, p["mamba_conv_w"], p["mamba_conv_b"]))
    with jax.named_scope("dt"):
        delta, b, c = jnp.split(matmul(x, p["mamba_x"], dt_c, jnp.float32), [r, r + n], axis=-1)
        delta = jax.nn.softplus(matmul(delta, p["mamba_dt_w"], dt_c, jnp.float32)
                                + p["mamba_dt_b"])
    with jax.named_scope("scan"):
        y = ssm.selective_scan(x, delta, -jnp.exp(p["mamba_A_log"]), b, c, p["mamba_D"])
    with jax.named_scope("gate_out"):
        return matmul(y * jax.nn.silu(z), p["mamba_out"], dt_c, jnp.float32), y


def gmu(p: Dict[str, jax.Array], h: jax.Array, memory: jax.Array, cfg: Config) -> jax.Array:
    """The Gated Memory Unit: the memory (B, T, E), gated token for token by
    this layer's own stream."""
    dt = jnp.dtype(cfg.compute_dtype)
    gate = matmul(h, p["gmu_in"], dt, jnp.float32)
    return matmul(memory * jax.nn.silu(gate), p["gmu_out"], dt, jnp.float32)


def pairs_apart(x: jax.Array) -> jax.Array:
    """Heads (B, T, 2P, D) with 2j and 2j + 1 a pair -> the P first-of-pair
    heads, then the P second-of-pair heads."""
    b, t, heads, d = x.shape
    return x.reshape(b, t, heads // 2, 2, d).swapaxes(2, 3).reshape(b, t, heads, d)


def attention_lambda(lambdas: jax.Array, i: int) -> jax.Array:
    """λ of the layer of published index i from its four vectors (4, D):
    λ_q1, λ_k1, λ_q2, λ_k2."""
    return (jnp.exp(jnp.sum(lambdas[0] * lambdas[1])) - jnp.exp(jnp.sum(lambdas[2] * lambdas[3]))
            + lambda_init(i))


def diff_attention(p: Dict[str, jax.Array], h: jax.Array, cfg: Config, i: int,
                   window: Optional[int] = None, kv=None):
    """Differential attention of the layer of published index i on the normed
    stream h (B, T, C): (out (B, T, C) float32, (k, v)) — k (B, T, Hkv, D) with
    the first-of-pair heads first, v (B, T, Hkv / 2, 2D), in `compute_dtype`:
    this layer's own, or `kv` handed back where the layer reads another's."""
    dt = jnp.dtype(cfg.compute_dtype)
    b, t, _ = h.shape
    heads, kv_heads, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    with jax.named_scope("proj"):
        if kv is None:
            qkv = matmul(h, p["attn_qkv"], dt, jnp.float32) + p["attn_qkv_b"]
            q, k, v = jnp.split(qkv, [heads * d, (heads + kv_heads) * d], axis=-1)
            kv = (pairs_apart(k.reshape(b, t, kv_heads, d)).astype(dt),
                  v.reshape(b, t, kv_heads // 2, 2 * d).astype(dt))
        else:
            q = matmul(h, p["cross_q"], dt, jnp.float32) + p["cross_q_b"]
        q = pairs_apart(q.reshape(b, t, heads, d)).astype(dt)
    with jax.named_scope("flash"):
        k, v = kv
        both = full_attention(q, k, jnp.concatenate([v, v], axis=2), causal=True,
                              window=window).astype(jnp.float32)
    with jax.named_scope("combine"):
        first, second = jnp.split(both, 2, axis=2)            # (B, T, H / 2, 2D) each
        o = first - attention_lambda(p["attn_lambda"], i) * second
        o = rmsnorm(o, p["attn_subln"], cfg.layer_norm_eps) * (1.0 - lambda_init(i))
    with jax.named_scope("proj"):
        out = matmul(o.reshape(b, t, heads * d), p["attn_wo"], dt, jnp.float32) + p["attn_wo_b"]
    return out, kv


def layer(p: Dict[str, jax.Array], x: jax.Array, cfg: Config, i: int,
          memory=None, kv=None):
    """The layer of published index i on the residual stream x (B, T, C)
    float32: (x, what the layer hands to later layers — a "mamba" layer its
    scan output, a "full" layer its (k, v), the others None)."""
    kind, eps, made = layer_kind(i), cfg.layer_norm_eps, None
    with jax.named_scope("norm"):
        h = layernorm(x, p["ln1_scale"], p["ln1_bias"], eps)
    if kind == "mamba":
        with jax.named_scope("mamba"):
            update, made = mamba(p, h, cfg)
    elif kind == "gmu":
        with jax.named_scope("gmu"):
            update = gmu(p, h, memory, cfg)
    else:
        with jax.named_scope("diff_attn"):
            update, made = diff_attention(
                p, h, cfg, i, window=cfg.sliding_window if kind == "sliding" else None,
                kv=kv if kind == "cross" else None)
    with jax.named_scope("norm"):
        x = x + update
        h = layernorm(x, p["ln2_scale"], p["ln2_bias"], eps)
    with jax.named_scope("mlp"):
        return x + mlp(p, h, cfg), (made if kind in ("mamba", "full") else None)


def layer_parameters(params: Dict[str, jax.Array], cfg: Config):
    """[(published index, the layer's parameters WITHOUT the layer axis)]: a
    kind's own parameters are stacked over the layers that have them."""
    seen = {"mamba": 0, "gmu": 0, "attention": 0, "self": 0, "cross": 0}
    out = []
    for at, i in enumerate(cfg.layers):
        p = {k: params[k][at] for k in COMMON_KEYS}
        for stack, keys in KEYS_OF[layer_kind(i)].items():
            p.update({k: params[k][seen[stack]] for k in keys})
            seen[stack] += 1
        out.append((i, p))
    return out


def head_logits(h: jax.Array, embed: jax.Array, dt) -> jax.Array:
    """(…, C) · Eᵀ -> (…, V) float32: the tied head."""
    return jax.lax.dot_general(h.astype(dt), embed.astype(dt),
                               (((h.ndim - 1,), (1,)), ((), ())),
                               preferred_element_type=jnp.float32)


def forward(params: Dict[str, jax.Array], tokens: jax.Array, cfg: Config) -> jax.Array:
    """tokens (B, T) -> logits (B, T, V) float32."""
    memory_layer = PUBLISHED_LAYERS // 2
    memory = kv = None
    with jax.named_scope("phi4flash"):
        with jax.named_scope("embed"):
            x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
        for i, p in layer_parameters(params, cfg):
            # what crosses layers is an argument and a result of the
            # checkpointed function: kept, and its cotangent summed over readers
            x, made = jax.checkpoint(
                lambda p, x, memory, kv, i=i: layer(p, x, cfg, i, memory, kv),
                policy=pallas_attention.KEEP_RESIDUALS)(
                p, x, memory if layer_kind(i) == "gmu" else None,
                kv if layer_kind(i) == "cross" else None)
            if i == memory_layer:
                memory = made
            elif layer_kind(i) == "full":
                kv = made
        with jax.named_scope("head_loss"):
            h = layernorm(x, params["final_norm_scale"], params["final_norm_bias"],
                          cfg.layer_norm_eps)
            return head_logits(h, params["embed"], jnp.dtype(cfg.compute_dtype))


def kv_block_visits(cfg: Config, seq_len: int):
    """((q block, kv block) pairs a head's forward grid computes in one step,
    over every attention layer built; what a causal grid would compute)."""
    dt = jnp.dtype(cfg.compute_dtype)
    banded, causal = zip(*(
        pallas_attention.kv_block_visits(seq_len, seq_len, window, cfg.head_dim, dt)
        for window in (cfg.sliding_window, None)))
    sliding, other = cfg.layers_of("sliding"), cfg.layers_of("full", "cross")
    return sliding * banded[0] + other * banded[1], (sliding + other) * causal[1]


# ------------------------------------------------------------------ #
# The zoo contract


def _a_log_init(key, shape, dtype):
    """Mamba's (S4D-real): A[e, n] = −(n + 1), kept as its logarithm."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[-1] + 1, dtype=dtype)), shape)


class Phi4Flash(nn.Module):
    """Initialisation (`assumed` in the benchmark's configuration): normal(0.02)
    for every matrix and the embedding, ones and zeros for every LayerNorm's
    scale and bias and for the sub-norm's scale, zeros for the projections'
    biases, normal(0.1) for the four λ vectors, Mamba's draws for `mamba_A_log`,
    `mamba_dt_b`, `mamba_dt_w` (uniform ± R^-1/2) and the convolution (uniform
    ± K^-1/2), ones for `mamba_D`."""

    cfg: Config

    @nn.compact
    def __call__(self, features, training: bool = False):
        c = self.cfg
        L, C, V, F = c.num_hidden_layers, c.hidden_size, c.vocab_size, c.intermediate_size
        H, Hkv, D = c.num_attention_heads, c.num_key_value_heads, c.head_dim
        E, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, c.dt_rank
        M, G = c.layers_of("mamba"), c.layers_of("gmu")
        S, X = c.layers_of("sliding", "full"), c.layers_of("cross")
        A = S + X
        normal, ones, zeros = (nn.initializers.normal(0.02), nn.initializers.ones,
                               nn.initializers.zeros)
        shapes = {
            "embed": ((V, C), normal),
            "final_norm_scale": ((C,), ones), "final_norm_bias": ((C,), zeros),
            "ln1_scale": ((L, C), ones), "ln1_bias": ((L, C), zeros),
            "ln2_scale": ((L, C), ones), "ln2_bias": ((L, C), zeros),
            "mlp_gate_up": ((L, C, 2 * F), normal), "mlp_down": ((L, F, C), normal),
            "mamba_in": ((M, C, 2 * E), normal),
            "mamba_conv_w": ((M, K, E), _uniform(K ** -0.5)),
            "mamba_conv_b": ((M, E), _uniform(K ** -0.5)),
            "mamba_x": ((M, E, R + 2 * N), normal),
            "mamba_dt_w": ((M, R, E), _uniform(R ** -0.5)),
            "mamba_dt_b": ((M, E), _dt_bias_init(_DT_INIT)),
            "mamba_A_log": ((M, E, N), _a_log_init),
            "mamba_D": ((M, E), ones),
            "mamba_out": ((M, E, C), normal),
            "gmu_in": ((G, C, E), normal), "gmu_out": ((G, E, C), normal),
            "attn_qkv": ((S, C, (H + 2 * Hkv) * D), normal),
            "attn_qkv_b": ((S, (H + 2 * Hkv) * D), zeros),
            "cross_q": ((X, C, H * D), normal), "cross_q_b": ((X, H * D), zeros),
            "attn_wo": ((A, H * D, C), normal), "attn_wo_b": ((A, C), zeros),
            "attn_lambda": ((A, 4, D), nn.initializers.normal(0.1)),
            "attn_subln": ((A, 2 * D), ones),
        }
        params = {name: self.param(name, init, shape, jnp.float32)
                  for name, (shape, init) in shapes.items()}
        counter = lambda group, name, shape=(), dtype=jnp.int32: self.variable(
            group, name, jnp.zeros, shape, dtype)
        elements = counter("s6", "scan_elements", (), jnp.float32)
        memory_reads = counter("memory", "reads")
        kv_reads = counter("shared_kv", "reads")
        lambdas = counter("diff_attn", "lambda", (A,), jnp.float32)
        visits = counter("attn", "kv_block_visits")
        visits_causal = counter("attn", "kv_block_visits_causal")
        logits = forward(params, features, c)
        if training and not self.is_initializing():
            batch, seq_len = features.shape
            elements.value = elements.value + float(M * batch * seq_len * E * N)
            memory_reads.value = memory_reads.value + G
            # the layer that makes the keys and values reads them too
            kv_reads.value = kv_reads.value + X + c.layers_of("full")
            attention = [i for i in c.layers if layer_kind(i) in ATTENTION_KINDS]
            lambdas.value = jnp.stack([attention_lambda(params["attn_lambda"][at], i)
                                       for at, i in enumerate(attention)])
            computed, causal = kv_block_visits(c, seq_len)
            visits.value = visits.value + computed
            visits_causal.value = visits_causal.value + causal
        return logits


def custom_model(**kwargs) -> Phi4Flash:
    """Keys are the published config's; unknown keys (the harness adds its
    own to every model) are ignored."""
    given = {name: type(field.default)(kwargs[name])
             for name, field in Config.__dataclass_fields__.items()
             if name in kwargs}
    return Phi4Flash(Config(**given))


def loss(labels, outputs):
    """Per-example mean next-token cross entropy, float32: (B, T, V) + (B, T)
    -> (B,). The label's logit is picked by a mask, not a gather: its backward
    is then elementwise over the plane and fuses with the softmax's."""
    with jax.named_scope("phi4flash/head_loss"):
        logits = outputs.astype(jnp.float32)
        own = labels.astype(jnp.int32)[..., None] == jnp.arange(
            logits.shape[-1], dtype=jnp.int32)
        picked = jnp.sum(jnp.where(own, logits, 0.0), axis=-1)
        return (jax.nn.logsumexp(logits, axis=-1) - picked).mean(axis=-1)


def eval_metrics_fn():
    return {"token_accuracy": TokenAccuracy()}
