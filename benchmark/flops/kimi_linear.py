"""Operations and bytes a Kimi Linear (`kimi_linear`) training step needs, from
its shapes alone, and the names its program gives its parts. A sample is one
sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward and
four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED —
the program recomputes every layer in its backward pass, and that work is its
own. What a token multiplies: a KDA mixer's three projections, both low-rank
gates, the write strength's projection and the output projection; the latent
layer's four projections; the dense layer's or the shared expert's three
matrices, the router, the routed experts it reaches, and the head — not the
embedding (a gather), the norms, the convolutions (4 multiply-adds a channel on
the vector unit) or the elementwise gates.

The delta rule is counted by the MODEL's arithmetic at a chunk of
`COUNT_CHUNK` = 64 tokens, a constant of the COUNT that is not read from the
program: whatever implements the scope `kimi_linear/kda/delta_rule` — today
`ops/delta_rule.py`'s XLA form — is held to the same floor
(`kda_delta_rule_roofline`), so a later change of the program's chunk, of its
sub-blocks or a kernel cannot make its own yardstick stale.

`shape()` is the ONE dict the driver `resident_lm_model` asks of a
configuration's shape functions; the per-layer readers take their floors
from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/kimi_linear.py, and
# inside its sub-blocks glm4_moe_lite.py's and ops/moe.py's;
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
_KDA = ("kda/proj", "kda/conv", "kda/gates", "kda/qk_norm", "kda/delta_rule",
        "kda/out_gate", "kda/out", "kda/counters", "kda")
_MLA = ("mla/q_proj", "mla/kv_lora", "mla/rope", "mla/attn", "mla/out", "mla")
_MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "moe")
SCOPES = (tuple(f"kimi_linear/{s}" for s in _KDA + _MLA) + ("kimi_linear/dense_mlp",)
          + tuple(f"kimi_linear/{s}" for s in _MOE)
          + ("kimi_linear/embed", "kimi_linear/head_loss", "optimizer", "kimi_linear"))
# the routed experts' grouped matmuls are the program's only ragged dots
# where `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "kimi_linear/moe/experts"

# the chunk the delta rule's work is COUNTED at (see the module docstring)
COUNT_CHUNK = 64

PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)
# the published depth, experts and vocabulary, for `parameter_count(published)`
PUBLISHED = {"num_hidden_layers": 27, "first_k_dense_replace": 1, "num_experts": 256,
             "router_experts": 0, "vocab_size": 163840}


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
        "linear_num_heads", "linear_head_dim", "num_attention_heads", "kv_lora_rank",
        "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "num_experts",
        "num_experts_per_token", "moe_intermediate_size")}
    p["dense"] = int(model_params.get("first_k_dense_replace", 1))
    p["conv"] = int(model_params.get("short_conv_kernel_size", 4))
    p["shared"] = int(model_params.get("num_shared_experts", 1))
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["num_experts"]
    full = model_params.get("full_attn_layers")
    full = PUBLISHED_FULL if full is None else tuple(int(l) for l in full.split(",") if l)
    layers = range(1, p["num_hidden_layers"] + 1)
    p["mla_layers"] = sum(l in full for l in layers)
    p["kda_layers"] = p["num_hidden_layers"] - p["mla_layers"]
    p["sparse"] = p["num_hidden_layers"] - p["dense"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies (`matmul`) and the rest (norms, convolutions, per-head and
    per-channel vectors)."""
    c, heads = p["hidden_size"], p["num_attention_heads"]
    lin_heads, d = p["linear_num_heads"], p["linear_head_dim"]
    wide = lin_heads * d
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    return {
        # q, k, v; the decay's and the output gate's low-rank pairs; β; W_o
        "kda_matmul": 3 * c * wide + 2 * (c * d + d * wide) + c * lin_heads + wide * c,
        # three convolutions, A_log, dt_bias, the output norm's one weight
        "kda_rest": 3 * p["conv"] * wide + lin_heads + wide + d,
        "mla_matmul": (c * heads * qk + c * (p["kv_lora_rank"] + p["qk_rope_head_dim"])
                       + p["kv_lora_rank"] * heads * (p["qk_nope_head_dim"] + p["v_head_dim"])
                       + heads * p["v_head_dim"] * c),
        "mla_rest": p["kv_lora_rank"],                          # the latent norm
        "dense_mlp": 3 * c * p["intermediate_size"],
        "expert": 3 * c * p["moe_intermediate_size"],
        "shared": 3 * c * p["moe_intermediate_size"] * p["shared"],
        "router": c * p["router_experts"],
        "norms": 2 * c,                                         # the two pre-norms
    }


def _mixers(p: dict, n: dict, part: str) -> int:
    """Σ over the layers of a mixer's `matmul` or `rest` parameters."""
    return p["kda_layers"] * n[f"kda_{part}"] + p["mla_layers"] * n[f"mla_{part}"]


def parameter_count(model_params: dict, published: bool = False) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head once; with `published` the uncut model's
    (27 layers of which one dense, all 256 experts, the whole vocabulary). The
    selection bias (256 numbers a sparse layer) is router state, no
    parameter."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    layers = p["num_hidden_layers"]
    return (_mixers(p, n, "matmul") + _mixers(p, n, "rest") + layers * n["norms"]
            + p["dense"] * n["dense_mlp"]
            + p["sparse"] * (n["shared"] + n["router"] + p["num_experts"] * n["expert"])
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def active_parameter_count(model_params: dict, published: bool = False) -> int:
    """The parameters one token's forward pass uses when every expert it
    chose is computed (the whole deployment's view of the token): everything
    but the routed experts it did not choose."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    idle = p["sparse"] * max(p["num_experts"] - p["num_experts_per_token"], 0) * n["expert"]
    return parameter_count(model_params, published) - idle


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_token"] * p["num_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward: 6 x pairs x 3 x 2304 x 1024."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal attention's two matmuls at TWO head widths, the latent layers,
    forward + backward: per head and visible (query, key) pair (T²/2 of them)
    q·kᵀ is nope + rope = 192 MACs and p·v 128, 6 FLOPs a MAC."""
    p = _sizes(model_params)
    qk = p["qk_nope_head_dim"] + p["qk_rope_head_dim"]
    return (seq_len * seq_len / 2) * p["num_attention_heads"] * (qk + p["v_head_dim"]) \
        * 2 * 3 * p["mla_layers"]


def delta_rule_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """The recurrence by the chunked form's arithmetic at a chunk of L =
    `COUNT_CHUNK`, per head and token 2 x (3 L d + 2 L d + 3 d d) forward — the
    three (L, L, d) products that build M, P and A (K ⊙ exp Γ); A V and P u;
    W S, (Q ⊙ exp Γ) S and the state's update — x 3 for the backward, the KDA
    layers. The inverse's log₂ L products and the recomputation are the
    program's own and not in the count."""
    p = _sizes(model_params)
    d, l = p["linear_head_dim"], COUNT_CHUNK
    per_head_token = 2.0 * (3 * l * d + 2 * l * d + 3 * d * d)
    return 3.0 * per_head_token * p["linear_num_heads"] * seq_len * p["kda_layers"]


def delta_rule_bytes_per_sample(model_params: dict, seq_len: int) -> float:
    """The least the recurrence moves: q, k, v, g read and o written forward;
    the same and do read, four gradients written backward; float32
    (`scan_bytes_per_sample` counts Mamba-2's so); β and its gradient (one a
    head) left out."""
    p = _sizes(model_params)
    plane = seq_len * p["linear_num_heads"] * p["linear_head_dim"] * 4.0
    return (5 + 5 + 1 + 4) * plane * p["kda_layers"]


def model_flops_per_sample(model_params: dict, seq_len: int = 16384,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given), causal attention and the delta
    rule."""
    p = _sizes(model_params)
    n = _per_layer(p)
    every_token = (_mixers(p, n, "matmul") + p["dense"] * n["dense_mlp"]
                   + p["sparse"] * (n["shared"] + n["router"])
                   + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = p["sparse"] * expected_held_pairs(model_params, seq_len)
    return (6.0 * every_token * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + attention_flops_per_sample(model_params, seq_len)
            + delta_rule_flops_per_sample(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 16384) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, the float32 logits written and read forward and backward,
    and the recurrence's operands. Other activations are left out (a lower
    bound)."""
    p = _sizes(model_params)
    n = parameter_count(model_params)
    return (optimizer_bytes(model_params) + n * (2 + 2 + 4)
            + 4.0 * batch * seq_len * p["vocab_size"] * 4
            + batch * delta_rule_bytes_per_sample(model_params, seq_len))


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
        "kda_mla_attention_flops_per_step":
            attention_flops_per_sample(model_params, seq_len) * batch,
        "delta_rule_flops_per_step":
            delta_rule_flops_per_sample(model_params, seq_len) * batch,
        "delta_rule_bytes_per_step":
            delta_rule_bytes_per_sample(model_params, seq_len) * batch,
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
