"""Operations and bytes a Trinity (`afmoe`) training step needs, from its
shapes alone, and the names its program gives its parts. A sample is one
sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward and
four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED —
the program recomputes every layer in its backward pass, and that work is its
own. What a token multiplies: attention's FIVE projections (q, k, v, the
output gate, o), the dense layer's or the shared expert's three matrices, the
router, the routed experts it reaches, the head — not the embedding (a
gather), the norms or the gate's elementwise product.

Attention is counted by VISIBLE (query, key) pairs only: a head of a full
layer sees T(T + 1)/2 of them, a head of a sliding layer Σ_i min(i + 1, W). A
block the kernel computes and masks away is not in the count, so it lowers the
kernel's share of the roofline instead of hiding in it.

`shape()` is the ONE dict the driver `resident_lm_model` asks of a
configuration's shape functions; the per-layer readers take their floors from
it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/afmoe.py, ops/moe.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries. A full layer has NO `rope` scope.
SCOPES = (
    tuple(f"afmoe/sliding/{part}" for part in ("qkv", "qk_norm", "rope", "attn", "gate", "out"))
    + ("afmoe/sliding",)
    + tuple(f"afmoe/full/{part}" for part in ("qkv", "qk_norm", "attn", "gate", "out"))
    + ("afmoe/full", "afmoe/dense_mlp")
    + tuple(f"afmoe/moe/{part}" for part in
            ("router", "shared", "dispatch", "experts", "combine"))
    + ("afmoe/moe", "afmoe/embed", "afmoe/head_loss", "optimizer", "afmoe"))
# the routed experts' grouped matmuls are the program's only ragged dots where
# `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "afmoe/moe/experts"


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
        "num_experts", "num_experts_per_tok", "moe_intermediate_size")}
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["num_experts"]
    p["shared"] = int(model_params.get("num_shared_experts", 1))
    kept = model_params.get("kept_layers", "")
    layers = ([int(l) for l in kept.split(",")] if kept
              else list(range(p["num_hidden_layers"])))
    dense_below = int(model_params.get("num_dense_layers", 2))
    period = int(model_params.get("global_attn_every_n_layers", 4))
    p["dense"] = sum(l < dense_below for l in layers)
    p["sparse"] = len(layers) - p["dense"]
    p["full_layers"] = sum((l + 1) % period == 0 for l in layers)
    p["sliding_layers"] = len(layers) - p["full_layers"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies and the rest (norms)."""
    c, d = p["hidden_size"], p["head_dim"]
    heads, kv = p["num_attention_heads"], p["num_key_value_heads"]
    return {
        # q, k, v, the output gate, o
        "attn_matmul": c * d * (heads + 2 * kv) + c * heads * d + heads * d * c,
        "attn_rest": 2 * d,                 # the q and k head norms
        "norms": 4 * c,                     # in, post-attention, pre-MLP, post-MLP
        "dense_mlp": 3 * c * p["intermediate_size"],
        "router": c * p["router_experts"],
        "expert": 3 * c * p["moe_intermediate_size"],
    }


def parameter_count(model_params: dict) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head once, the final norm."""
    p = _sizes(model_params)
    n = _per_layer(p)
    every = n["attn_matmul"] + n["attn_rest"] + n["norms"]
    sparse = every + n["router"] + (p["shared"] + p["num_experts"]) * n["expert"]
    return (p["dense"] * (every + n["dense_mlp"]) + p["sparse"] * sparse
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def active_parameter_count(model_params: dict) -> int:
    """What one token's forward pass multiplies when every expert it chose is
    computed (the whole deployment's view of the token), outside the
    embedding: matrices only, the head once."""
    p = _sizes(model_params)
    n = _per_layer(p)
    sparse = n["attn_matmul"] + n["router"] \
        + (p["shared"] + p["num_experts_per_tok"]) * n["expert"]
    return (p["dense"] * (n["attn_matmul"] + n["dense_mlp"]) + p["sparse"] * sparse
            + p["vocab_size"] * p["hidden_size"])


def visible_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs one head sees: T(T + 1)/2 under a causal mask,
    Σ_i min(i + 1, W) under a window of W keys."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["num_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops(model_params: dict, seq_len: int) -> dict:
    """{"sliding", "full"}: the two matmuls (q·kᵀ and p·v) of every head over
    its visible pairs, in all the layers of that kind, forward + backward:
    2 matmuls x D MACs a pair, 6 FLOPs a MAC."""
    p = _sizes(model_params)
    per_pair = 6.0 * 2 * p["head_dim"] * p["num_attention_heads"]
    return {"sliding": per_pair * p["sliding_layers"]
            * visible_pairs(seq_len, p["sliding_window"]),
            "full": per_pair * p["full_layers"] * visible_pairs(seq_len)}


def model_flops_per_sample(model_params: dict, seq_len: int = 16384,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given) and attention's visible pairs."""
    p = _sizes(model_params)
    n = _per_layer(p)
    every_token = ((p["dense"] + p["sparse"]) * n["attn_matmul"]
                   + p["dense"] * n["dense_mlp"]
                   + p["sparse"] * (n["router"] + p["shared"] * n["expert"])
                   + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + sum(attention_flops(model_params, seq_len).values()))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 16384) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, and the float32 logits written and read forward and
    backward. Activations of the layers are left out (a lower bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return optimizer_bytes(model_params) + n * (2 + 2 + 4) \
        + 4.0 * batch * seq_len * p["vocab_size"] * 4


def shape(model_params: dict, batch: int, seq_len: int, pairs_held: float = None) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip; `pairs_held` the (token, slot) pairs on held experts a sequence,
    summed over the sparse layers, as the run counted them."""
    p = _sizes(model_params)
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    attn = attention_flops(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len, pairs_held),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "held_expert_matmul_flops_per_step":
            held_expert_matmul_flops(model_params, pairs_held) * batch,
        "gated_swa_attention_flops_per_step": attn["sliding"] * batch,
        "nope_attention_flops_per_step": attn["full"] * batch,
        "visible_pairs_per_head": {
            "sliding": visible_pairs(seq_len, p["sliding_window"]),
            "full": visible_pairs(seq_len)},
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
