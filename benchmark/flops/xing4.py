"""Operations and bytes a Xing4.0 (`xing4`) training step needs, from its
shapes alone, and the names its program gives its parts. A sample is one
sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward and
four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED —
the program recomputes every layer in its backward pass, and that work is its
own. What a token multiplies: the latent attention's five projections, the
dense layer's or the shared expert's three matrices, the router, the routed
experts it reaches, the hyper-connections' coefficient matrix (14 336 x 24,
twice a layer) and the head — not the embedding (a gather), the norms, or the
two stream mixes (24 multiply-adds a value of the state and sub-block on the
vector unit: they are bound by the bytes `mhc_bytes` counts, not by FLOPs).

`shape()` is the ONE dict the driver `resident_lm_model` asks of a
configuration's shape functions; the per-layer readers take their floors
from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/xing4.py, and inside
# its sub-blocks glm4_moe_lite.py's and ops/moe.py's; training/trainer.py),
# most specific first: an instruction belongs to the first whose name its
# `op_name` carries.
_MHC = ("mhc/coef", "mhc/sinkhorn", "mhc/pre", "mhc/post_res", "mhc")
_MLA = ("mla/q_lora", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out", "mla")
_MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "moe")
SCOPES = (tuple(f"xing4/{s}" for s in _MHC + _MLA) + ("xing4/dense_mlp",)
          + tuple(f"xing4/{s}" for s in _MOE)
          + ("xing4/embed", "xing4/head_loss", "optimizer", "xing4"))
# the routed experts' grouped matmuls are the program's only ragged dots
# where `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "xing4/moe/experts"

_ITEMSIZE = {"float32": 4, "bfloat16": 2}

# the published depth, experts and vocabulary, for `parameter_count(published)`
PUBLISHED = {"num_hidden_layers": 40, "first_k_dense_replace": 2, "n_routed_experts": 64,
             "router_experts": 0, "vocab_size": 131072}


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size")}
    p["dense"] = int(model_params.get("first_k_dense_replace", 2))
    p["shared"] = int(model_params.get("n_shared_experts", 1))
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["n_routed_experts"]
    p["streams"] = int(model_params.get("hc_mult", 4))
    # the streams are stored in the compute dtype (bfloat16 unless given)
    p["stream_bytes"] = _ITEMSIZE[model_params.get("compute_dtype", "bfloat16")]
    p["sparse"] = p["num_hidden_layers"] - p["dense"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies (`matmul`) and the rest (norms, gates, biases)."""
    c, heads, n = p["hidden_size"], p["num_attention_heads"], p["streams"]
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    coefficients = 2 * n + n * n
    return {
        "mla_matmul": (c * p["q_lora_rank"] + p["q_lora_rank"] * heads * qk
                       + c * (p["kv_lora_rank"] + p["qk_rope_head_dim"])
                       + p["kv_lora_rank"] * heads * (p["qk_nope_head_dim"] + p["v_head_dim"])
                       + heads * p["v_head_dim"] * c),
        "mla_rest": p["q_lora_rank"] + p["kv_lora_rank"] + c,   # two latent norms, the pre-norm
        "dense_mlp": 3 * c * p["intermediate_size"],
        "expert": 3 * c * p["moe_intermediate_size"],
        "shared": 3 * c * p["moe_intermediate_size"] * p["shared"],
        "router": c * p["router_experts"],
        "ff_rest": c,                                           # the pre-norm
        # of a LAYER: its two sub-blocks' phi, and their gates and biases
        "mhc_matmul": 2 * n * c * coefficients,
        "mhc_rest": 2 * (3 + coefficients),
    }


def _every_layer(n: dict) -> int:
    return n["mla_matmul"] + n["mla_rest"] + n["ff_rest"] + n["mhc_matmul"] + n["mhc_rest"]


def parameter_count(model_params: dict, published: bool = False) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head once; with `published` the uncut model's
    (40 layers of which 2 dense, all 64 experts, the whole vocabulary; the
    multi-token-prediction module not counted). The selection bias (64
    numbers a sparse layer) is router state, no parameter."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    sparse_layer = (_every_layer(n) + n["shared"] + n["router"]
                    + p["n_routed_experts"] * n["expert"])
    return (p["dense"] * (_every_layer(n) + n["dense_mlp"]) + p["sparse"] * sparse_layer
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def active_parameter_count(model_params: dict, published: bool = False) -> int:
    """What one token's forward pass multiplies when every expert it chose is
    computed (the whole deployment's view of the token)."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    return ((p["dense"] + p["sparse"]) * (n["mla_matmul"] + n["mhc_matmul"])
            + p["dense"] * n["dense_mlp"]
            + p["sparse"] * (n["shared"] + n["router"]
                             + p["num_experts_per_tok"] * n["expert"])
            + p["hidden_size"] * p["vocab_size"])


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["n_routed_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward: 6 x pairs x 3 x 3584 x 1024."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal attention's two matmuls at TWO head widths, every layer, forward
    + backward: per head and visible (query, key) pair (T²/2 of them) q·kᵀ is
    nope + rope = 192 MACs and p·v 128, 6 FLOPs a MAC."""
    p = _sizes(model_params)
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    return (seq_len * seq_len / 2) * p["num_attention_heads"] * (qk + p["v_head_dim"]) \
        * 2 * 3 * p["num_hidden_layers"]


def mhc_bytes(model_params: dict, batch: int, seq_len: int) -> float:
    """The least the two stream mixes move: per sub-block the state (n x C
    values a token, at the streams' stated dtype) read once and written once,
    forward and backward. The recomputation is not counted, as no attention
    roofline counts it; neither are h, y and the coefficients (a fifth of a
    stream each, or less)."""
    p = _sizes(model_params)
    state = batch * seq_len * p["streams"] * p["hidden_size"] * p["stream_bytes"]
    return 2.0 * p["num_hidden_layers"] * 4 * state


def model_flops_per_sample(model_params: dict, seq_len: int = 4096,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given) and causal attention."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["dense"] + p["sparse"]
    every_token = (layers * (n["mla_matmul"] + n["mhc_matmul"]) + p["dense"] * n["dense_mlp"]
                   + p["sparse"] * (n["shared"] + n["router"])
                   + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + attention_flops_per_sample(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 4096) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, the float32 logits written and read forward and backward,
    and the stream mixes' state. Other activations are left out (a lower
    bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return (optimizer_bytes(model_params) + n * (2 + 2 + 4)
            + 4.0 * batch * seq_len * p["vocab_size"] * 4
            + mhc_bytes(model_params, batch, seq_len))


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
        "mla_qk192_attention_flops_per_step":
            attention_flops_per_sample(model_params, seq_len) * batch,
        "mhc_bytes_per_step": mhc_bytes(model_params, batch, seq_len),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
