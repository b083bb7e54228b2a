"""Operations and bytes an LFM2-MoE (`lfm2_moe`) training step needs, from its
shapes alone, and the names its program gives its parts. A sample is one
sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward and
four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED —
the program recomputes every layer in its backward pass, and that work is its
own. What a token multiplies: a convolution mixer's two projections (C → 3C,
C → C), attention's four (q, k, v, o), the dense layer's three matrices, the
router, the routed experts it reaches HERE, the tied head — not the embedding
(a gather), the norms, the gates' products or the K taps (elementwise: priced
in bytes, `gated_conv_bytes_per_step`).

Attention is counted by VISIBLE (query, key) pairs only: a head sees
T(T + 1)/2 of them. A block the kernel computes and masks away is not in the
count, so it lowers the kernel's share of the roofline instead of hiding in
it.

`shape()` is the ONE dict the driver `resident_lm_model` asks of a
configuration's shape functions; the per-layer readers take their floors from
it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/lfm2_moe.py, ops/ssm.py,
# ops/moe.py, training/trainer.py), most specific first: an instruction
# belongs to the first whose name its `op_name` carries.
SCOPES = (
    tuple(f"lfm2/conv/{part}" for part in
          ("in_proj", "gate_in", "conv", "gate_out", "out_proj"))
    + ("lfm2/conv",)
    + tuple(f"lfm2/attn/{part}" for part in ("qkv", "qk_norm", "rope", "attn", "out"))
    + ("lfm2/attn", "lfm2/dense_mlp")
    + tuple(f"lfm2/moe/{part}" for part in ("router", "dispatch", "experts", "combine"))
    + ("lfm2/moe", "lfm2/embed", "lfm2/head_loss", "optimizer", "lfm2"))
# the routed experts' grouped matmuls are the program's only ragged dots where
# `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "lfm2/moe/experts"

_PUBLISHED_ATTENTION = (2, 6, 10, 14, 18, 21)


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "num_experts", "num_experts_per_tok",
        "moe_intermediate_size")}
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["num_experts"]
    p["taps"] = int(model_params.get("conv_L_cache", 3))
    p["head_dim"] = p["hidden_size"] // p["num_attention_heads"]
    kept = model_params.get("kept_layers", "")
    layers = ([int(l) for l in kept.split(",")] if kept
              else list(range(p["num_hidden_layers"])))
    types = model_params.get("layer_types")
    attention = ({i for i, kind in enumerate(types.split(",")) if kind == "full_attention"}
                 if types else set(_PUBLISHED_ATTENTION))
    dense_below = int(model_params.get("num_dense_layers", 2))
    p["dense"] = sum(l < dense_below for l in layers)
    p["sparse"] = len(layers) - p["dense"]
    p["attn_layers"] = sum(l in attention for l in layers)
    p["conv_layers"] = len(layers) - p["attn_layers"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies and the rest (norms, taps)."""
    c, d = p["hidden_size"], p["head_dim"]
    heads, kv = p["num_attention_heads"], p["num_key_value_heads"]
    return {
        "conv_matmul": c * 3 * c + c * c,           # W_in, W_out
        "conv_rest": p["taps"] * c,                 # the taps
        "attn_matmul": c * d * (heads + 2 * kv) + heads * d * c,    # q, k, v, o
        "attn_rest": 2 * d,                         # the q and k head norms
        "norms": 2 * c,                             # operator_norm, ffn_norm
        "dense_mlp": 3 * c * p["intermediate_size"],
        "router": c * p["router_experts"],
        "expert": 3 * c * p["moe_intermediate_size"],
    }


def parameter_count(model_params: dict) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, the embedding ONCE (it is the head), the final norm."""
    p = _sizes(model_params)
    n = _per_layer(p)
    mixers = (p["conv_layers"] * (n["conv_matmul"] + n["conv_rest"])
              + p["attn_layers"] * (n["attn_matmul"] + n["attn_rest"]))
    return (mixers + (p["dense"] + p["sparse"]) * n["norms"]
            + p["dense"] * n["dense_mlp"]
            + p["sparse"] * (n["router"] + p["num_experts"] * n["expert"])
            + p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def active_parameter_count(model_params: dict) -> int:
    """What one token's forward pass multiplies when every expert it chose is
    computed (the whole deployment's view of the token), outside the
    embedding — the mixers whole (their taps and head norms with their
    matrices), the feed-forwards' matrices, the router; not the layers' own
    norms — and so without the tied head, which `model_flops_per_sample`
    counts."""
    p = _sizes(model_params)
    n = _per_layer(p)
    return (p["conv_layers"] * (n["conv_matmul"] + n["conv_rest"])
            + p["attn_layers"] * (n["attn_matmul"] + n["attn_rest"])
            + p["dense"] * n["dense_mlp"]
            + p["sparse"] * (n["router"] + p["num_experts_per_tok"] * n["expert"]))


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs one head sees under a causal mask: T(T + 1)/2."""
    return seq_len * (seq_len + 1) // 2


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["num_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops(model_params: dict, seq_len: int) -> float:
    """The two matmuls (q·kᵀ and p·v) of every head over its visible pairs, in
    all the attention layers, forward + backward: 2 matmuls x D MACs a pair,
    6 FLOPs a MAC."""
    p = _sizes(model_params)
    return (6.0 * 2 * p["head_dim"] * p["num_attention_heads"] * p["attn_layers"]
            * visible_pairs(seq_len))


def gated_conv_bytes(model_params: dict, seq_len: int) -> float:
    """The least the mixers' elementwise part — G ⊙ conv_K(B ⊙ u), the scopes
    `gate_in`, `conv`, `gate_out` — has to move for one sequence, float32, as
    ONE pass a direction whatever implements it: forward B, G and u read and
    the gated result written (4 planes of T x C); backward the same three and
    the cotangent read and the three gradients written (7 planes). The taps
    and their gradient are K x C numbers and are left out."""
    p = _sizes(model_params)
    return (4 + 7) * 4.0 * seq_len * p["hidden_size"] * p["conv_layers"]


def conv_kernel_bytes(model_params: dict, seq_len: int) -> float:
    """What the depthwise convolution ALONE — the two kernels of
    `ops/pallas_conv1d.py` under `lfm2/conv/conv` — has to move for one
    sequence, float32: forward the plane read and written (2), backward the
    cotangent and the plane read and the plane's gradient written (3)."""
    p = _sizes(model_params)
    return (2 + 3) * 4.0 * seq_len * p["hidden_size"] * p["conv_layers"]


def model_flops_per_sample(model_params: dict, seq_len: int = 32768,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given) and attention's visible pairs."""
    p = _sizes(model_params)
    n = _per_layer(p)
    every_token = (p["conv_layers"] * n["conv_matmul"] + p["attn_layers"] * n["attn_matmul"]
                   + p["dense"] * n["dense_mlp"] + p["sparse"] * n["router"]
                   + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + attention_flops(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 32768) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, the float32 logits written and read forward and backward,
    and the mixers' elementwise planes. The other activations are left out (a
    lower bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return (optimizer_bytes(model_params) + n * (2 + 2 + 4)
            + 4.0 * batch * seq_len * p["vocab_size"] * 4
            + batch * gated_conv_bytes(model_params, seq_len))


def shape(model_params: dict, batch: int, seq_len: int, pairs_held: float = None) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip; `pairs_held` the (token, slot) pairs on held experts a sequence,
    summed over the sparse layers, as the run counted them."""
    p = _sizes(model_params)
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len, pairs_held),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "held_expert_matmul_flops_per_step":
            held_expert_matmul_flops(model_params, pairs_held) * batch,
        "attn_flops_per_step": attention_flops(model_params, seq_len) * batch,
        "gated_conv_bytes_per_step": gated_conv_bytes(model_params, seq_len) * batch,
        "conv_kernel_bytes_per_step": conv_kernel_bytes(model_params, seq_len) * batch,
        "visible_pairs_per_head": visible_pairs(seq_len),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
