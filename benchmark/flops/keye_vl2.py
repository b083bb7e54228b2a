"""Operations and bytes a Keye-VL-2.0 language-model (`keye_vl2`) training step
needs, from its shapes alone, and the names its program gives its parts. A
sample is one sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs
forward and four backward (the gradient of each operand): 6 a MAC, NOTHING
RECOMPUTED — the program recomputes every layer in its backward pass, and that
work is its own. What a token multiplies: attention's four projections, the
indexer's three, the router, the routed experts it reaches, the head — not the
embedding (a gather) or the norms.

Attention is counted by SELECTED (query, key) pairs only: query t of a head
reads min(t + 1, K) keys, 31 458 304 a head at 16 384 tokens and K = 2048,
23.4% of the causal 134 225 920. A pair the masked kernel computes and masks
away is not in the count, so it lowers the kernel's share of the roofline
instead of hiding in it (`flops/mellum.py`'s rule for its band). The index
scores are counted over the CAUSAL pairs (every one has to be scored before
any can be left out), forward and backward; the index loss's target — the
heads' q·kᵀ over the selected pairs once more, forward only: a target has no
gradient — is counted too: both are what the mechanism costs, not recomputation.

`shape()` is the ONE dict the LM drivers ask of a configuration's shape
functions; the per-layer readers take their floors from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/keye_vl2.py, ops/moe.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
SCOPES = (
    # a block of the score plane, wherever it is made (`_score_block`)
    ("keye/attn/select/scores", "keye/attn/index_loss/scores")
    + tuple(f"keye/attn/{part}" for part in (
        "qkv", "rope", "index_loss", "index", "select", "attn", "out"))
    + ("keye/attn",)
    + tuple(f"keye/moe/{part}" for part in ("router", "dispatch", "experts", "combine"))
    + ("keye/moe", "keye/embed", "keye/head_loss", "optimizer", "keye"))
# the routed experts' grouped matmuls are the program's only ragged dots where
# `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "keye/moe/experts"


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "indexer_num_heads", "indexer_head_dim",
        "index_topk", "num_experts", "num_experts_per_tok", "moe_intermediate_size")}
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["num_experts"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one layer's parts, split into what a token multiplies
    and the rest (the two pre-norms, the heads' q and k norms, the index
    keys' layernorm)."""
    c, d = p["hidden_size"], p["head_dim"]
    hi, di = p["indexer_num_heads"], p["indexer_head_dim"]
    return {
        "attn_matmul": (c * d * (p["num_attention_heads"] + 2 * p["num_key_value_heads"])
                        + p["num_attention_heads"] * d * c),
        "index_matmul": c * hi * di + c * di + c * hi,
        "router": c * p["router_experts"],
        "expert": 3 * c * p["moe_intermediate_size"],
        "norms": 2 * c + 2 * d + 2 * di,
    }


def parameter_count(model_params: dict) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head once, the final norm."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layer = (n["attn_matmul"] + n["index_matmul"] + n["norms"] + n["router"]
             + p["num_experts"] * n["expert"])
    return (p["num_hidden_layers"] * layer + 2 * p["vocab_size"] * p["hidden_size"]
            + p["hidden_size"])


def active_parameter_count(model_params: dict) -> int:
    """What one token's forward pass multiplies when every expert it chose is
    computed (the whole deployment's view of the token)."""
    p = _sizes(model_params)
    n = _per_layer(p)
    return (p["num_hidden_layers"] * (n["attn_matmul"] + n["index_matmul"] + n["norms"]
                                      + n["router"] + p["num_experts_per_tok"] * n["expert"])
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def causal_pairs(seq_len: int) -> int:
    return seq_len * (seq_len + 1) // 2


def selected_pairs(seq_len: int, topk: int) -> int:
    """(query, key) pairs one head reads: Σ_t min(t + 1, K)."""
    if topk >= seq_len:
        return causal_pairs(seq_len)
    return topk * (topk + 1) // 2 + (seq_len - topk) * topk


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["num_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops(model_params: dict, seq_len: int) -> float:
    """The two matmuls (q·kᵀ and p·v) of every head over its SELECTED pairs,
    in all the layers, forward + backward: 2 matmuls x D MACs a pair, 6 FLOPs
    a MAC."""
    p = _sizes(model_params)
    return (6.0 * 2 * p["head_dim"] * p["num_attention_heads"] * p["num_hidden_layers"]
            * selected_pairs(seq_len, p["index_topk"]))


def index_score_flops(model_params: dict, seq_len: int) -> float:
    """The index heads' qI·kI over the causal pairs, all the layers, forward +
    backward: Hi x Di MACs a pair."""
    p = _sizes(model_params)
    return (6.0 * p["indexer_num_heads"] * p["indexer_head_dim"] * p["num_hidden_layers"]
            * causal_pairs(seq_len))


def index_target_flops(model_params: dict, seq_len: int) -> float:
    """The index loss's target: every head's q·kᵀ over its selected pairs once
    more, forward only (2 FLOPs a MAC)."""
    p = _sizes(model_params)
    return (2.0 * p["head_dim"] * p["num_attention_heads"] * p["num_hidden_layers"]
            * selected_pairs(seq_len, p["index_topk"]))


def model_flops_per_sample(model_params: dict, seq_len: int = 16384,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given), attention's selected pairs, the
    index scores and the index loss's target."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["num_hidden_layers"]
    every_token = layers * (n["attn_matmul"] + n["index_matmul"] + n["router"]) \
        + p["hidden_size"] * p["vocab_size"]
    if pairs_held is None:
        pairs_held = layers * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + attention_flops(model_params, seq_len)
            + index_score_flops(model_params, seq_len)
            + index_target_flops(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 16384) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, and the float32 logits written and read forward and
    backward. Activations of the layers — the (T, T) score and keep planes
    among them — are left out (a lower bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return optimizer_bytes(model_params) + n * (2 + 2 + 4) \
        + 4.0 * batch * seq_len * p["vocab_size"] * 4


def shape(model_params: dict, batch: int, seq_len: int, pairs_held: float = None) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip; `pairs_held` the (token, slot) pairs on held experts a sequence,
    summed over the layers, as the run counted them."""
    p = _sizes(model_params)
    if pairs_held is None:
        pairs_held = p["num_hidden_layers"] * expected_held_pairs(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len, pairs_held),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "held_expert_matmul_flops_per_step":
            held_expert_matmul_flops(model_params, pairs_held) * batch,
        "dsa_attention_flops_per_step": attention_flops(model_params, seq_len) * batch,
        "index_score_flops_per_step": index_score_flops(model_params, seq_len) * batch,
        "index_target_flops_per_step": index_target_flops(model_params, seq_len) * batch,
        "selected_pairs_per_head": selected_pairs(seq_len, p["index_topk"]),
        "causal_pairs_per_head": causal_pairs(seq_len),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
