"""Operations and bytes a GLM-4.7-Flash (`glm4_moe_lite`) training step needs,
from its shapes alone, and the names its program gives its parts. A sample is
one sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward
and four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED
— the program recomputes every layer in its backward pass, and that work is
its own. What a token multiplies: the latent attention's five projections,
the dense layer's or the shared expert's three matrices, the router, the
routed experts it reaches, the module's joining projection, the head once for
each logit stream — not the embedding (a gather) or the norms.

`shape()` is the ONE dict the driver `resident_lm_model` asks of a
configuration's shape functions; the per-layer readers take their floors
from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/glm4_moe_lite.py,
# ops/moe.py, training/trainer.py), most specific first: an instruction
# belongs to the first whose name its `op_name` carries. The module's own
# layer repeats the layer's scopes under `glm4_moe_lite/mtp`.
_MLA = ("mla/q_lora", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out", "mla")
_MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "moe")
SCOPES = (
    tuple(f"glm4_moe_lite/mtp/{s}" for s in _MLA + _MOE)
    + ("glm4_moe_lite/mtp/join", "glm4_moe_lite/mtp/head_loss", "glm4_moe_lite/mtp")
    + tuple(f"glm4_moe_lite/{s}" for s in _MLA)
    + ("glm4_moe_lite/dense_mlp",)
    + tuple(f"glm4_moe_lite/{s}" for s in _MOE)
    + ("glm4_moe_lite/embed", "glm4_moe_lite/head_loss", "optimizer", "glm4_moe_lite"))
# the routed experts' grouped matmuls are the program's only ragged dots
# where `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "glm4_moe_lite/moe/experts"


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
        "moe_intermediate_size")}
    p["dense"] = int(model_params.get("first_k_dense_replace", 1))
    p["mtp"] = int(model_params.get("num_nextn_predict_layers", 1))
    p["shared"] = int(model_params.get("n_shared_experts", 1))
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["n_routed_experts"]
    p["sparse"] = p["num_hidden_layers"] - p["dense"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies (`matmul`) and the rest (norms)."""
    c, heads = p["hidden_size"], p["num_attention_heads"]
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
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
        "mtp_join": 2 * c * c,
        "mtp_rest": 3 * c,                                      # hnorm, enorm, its final norm
    }


def _sparse_layer(p: dict, n: dict, experts: int) -> int:
    return (n["mla_matmul"] + n["mla_rest"] + n["shared"] + n["router"] + n["ff_rest"]
            + experts * n["expert"])


def parameter_count(model_params: dict, with_mtp: bool = True) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head once. The selection bias (64 numbers a
    sparse layer) is router state, no parameter, and is not counted."""
    p = _sizes(model_params)
    n = _per_layer(p)
    held = p["n_routed_experts"]
    total = (p["dense"] * (n["mla_matmul"] + n["mla_rest"] + n["dense_mlp"] + n["ff_rest"])
             + p["sparse"] * _sparse_layer(p, n, held)
             + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])
    if with_mtp and p["mtp"]:
        total += _sparse_layer(p, n, held) + n["mtp_join"] + n["mtp_rest"]
    return total


def active_parameter_count(model_params: dict) -> int:
    """What one token's forward pass multiplies in the main stream when every
    expert it chose is computed (the whole deployment's view of the token)."""
    p = _sizes(model_params)
    n = _per_layer(p)
    return ((p["dense"] + p["sparse"]) * n["mla_matmul"] + p["dense"] * n["dense_mlp"]
            + p["sparse"] * (n["shared"] + n["router"]
                             + p["num_experts_per_tok"] * n["expert"])
            + p["hidden_size"] * p["vocab_size"])


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["n_routed_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal attention's two matmuls (q·kᵀ and p·v) in every layer and in the
    module's, forward + backward: per head 2 matmuls x T x T x D MACs, half of
    them under the causal mask, 6 FLOPs a MAC, D the 256 of `[nope | rope]`
    and of v."""
    p = _sizes(model_params)
    layers = p["num_hidden_layers"] + p["mtp"]
    return 6.0 * layers * 2 * seq_len * seq_len * p["num_attention_heads"] \
        * p["v_head_dim"] / 2


def model_flops_per_sample(model_params: dict, seq_len: int = 8192,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given) and causal attention."""
    p = _sizes(model_params)
    n = _per_layer(p)
    sparse = p["sparse"] + p["mtp"]
    every_token = ((p["dense"] + sparse) * n["mla_matmul"] + p["dense"] * n["dense_mlp"]
                   + sparse * (n["shared"] + n["router"]) + p["mtp"] * n["mtp_join"]
                   + (1 + p["mtp"]) * p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = sparse * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + attention_flops_per_sample(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 8192) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, and each stream's float32 logits written and read forward
    and backward. Activations of the layers are left out (a lower bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return optimizer_bytes(model_params) + n * (2 + 2 + 4) \
        + (1 + p["mtp"]) * 4.0 * batch * seq_len * p["vocab_size"] * 4


def shape(model_params: dict, batch: int, seq_len: int, pairs_held: float = None) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip; `pairs_held` the (token, slot) pairs on held experts a sequence,
    summed over the sparse layers, as the run counted them."""
    p = _sizes(model_params)
    if pairs_held is None:
        pairs_held = (p["sparse"] + p["mtp"]) * expected_held_pairs(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len, pairs_held),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "held_expert_matmul_flops_per_step":
            held_expert_matmul_flops(model_params, pairs_held) * batch,
        "mla_attention_flops_per_step":
            attention_flops_per_sample(model_params, seq_len) * batch,
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
