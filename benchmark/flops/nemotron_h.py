"""Operations and bytes a Nemotron-H training step needs, from its shapes
alone, and the names its program gives its parts. A sample is one sequence of
`seq_len` tokens. A multiply-accumulate is two FLOPs forward and four
backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED — the
program recomputes every block in its backward pass, and that work is its
own. Active parameters are those a token's forward pass multiplies: the
Mamba mixers' two projections, attention's four, the router, the shared
expert, k routed experts, the head — not the embedding (a gather), the
convolution, the norms or the per-head scalars."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/nemotron_h.py,
# ops/moe.py, training/trainer.py), most specific first: an instruction
# belongs to the first whose name its `op_name` carries.
SCOPES = (
    "nemotron_h/mamba/in_proj", "nemotron_h/mamba/conv", "nemotron_h/mamba/ssd",
    "nemotron_h/mamba/gate_norm", "nemotron_h/mamba/out_proj", "nemotron_h/mamba",
    "nemotron_h/moe/router", "nemotron_h/moe/dispatch", "nemotron_h/moe/experts",
    "nemotron_h/moe/combine", "nemotron_h/moe/shared", "nemotron_h/moe",
    "nemotron_h/attn", "nemotron_h/head_loss", "optimizer", "nemotron_h")
# libtpu writes the metadata of the Mosaic calls it lowers `ragged_dot` to
# itself (`op_name="ragged-dot-none"`); the program's only ragged dots are
# the routed experts' grouped matmuls
RAGGED_DOT_SCOPE = "nemotron_h/moe/experts"


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "mamba_num_heads", "mamba_head_dim",
        "ssm_state_size", "n_groups", "conv_kernel", "chunk_size",
        "num_attention_heads", "num_key_value_heads", "head_dim",
        "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
        "moe_shared_expert_intermediate_size")}
    pattern = model_params["hybrid_override_pattern"]
    p["layers"] = {kind: pattern.count(kind) for kind in "ME*"}
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["n_routed_experts"]
    p["d_inner"] = p["mamba_num_heads"] * p["mamba_head_dim"]
    p["conv_dim"] = p["d_inner"] + 2 * p["n_groups"] * p["ssm_state_size"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one layer of each kind, split into what a token
    multiplies (`matmul`) and the rest."""
    c, d, heads = p["hidden_size"], p["d_inner"], p["mamba_num_heads"]
    q = p["num_attention_heads"] * p["head_dim"]
    kv = p["num_key_value_heads"] * p["head_dim"]
    return {
        "mamba_matmul": c * (d + p["conv_dim"] + heads) + d * c,
        "mamba_rest": (p["conv_kernel"] + 1) * p["conv_dim"] + 3 * heads + c + d,
        "attn_matmul": 2 * c * q + 2 * c * kv,
        "attn_rest": c,
        "expert": 2 * c * p["moe_intermediate_size"],
        "shared": 2 * c * p["moe_shared_expert_intermediate_size"],
        "router": c * p["router_experts"],
        "moe_rest": c,
        "selection_bias": p["router_experts"],
    }


def optimizer_parameter_count(model_params: dict) -> int:
    """Every parameter AdamW sweeps on this chip: the held experts only."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["layers"]
    return (layers["M"] * (n["mamba_matmul"] + n["mamba_rest"])
            + layers["*"] * (n["attn_matmul"] + n["attn_rest"])
            + layers["E"] * (n["shared"] + n["router"] + n["moe_rest"]
                             + p["n_routed_experts"] * n["expert"])
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def parameter_count(model_params: dict) -> int:
    """As a checkpoint counts them: the optimizer's parameters and each
    sparse-expert layer's selection bias (no gradient, no AdamW)."""
    p = _sizes(model_params)
    return optimizer_parameter_count(model_params) \
        + p["layers"]["E"] * p["router_experts"]


def active_parameter_count(model_params: dict) -> int:
    """What one token's forward pass multiplies when every expert it chose is
    computed (the whole deployment's view of the token)."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["layers"]
    return (layers["M"] * n["mamba_matmul"] + layers["*"] * n["attn_matmul"]
            + layers["E"] * (n["shared"] + n["router"]
                             + p["num_experts_per_tok"] * n["expert"])
            + p["hidden_size"] * p["vocab_size"])


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["n_routed_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' two grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward."""
    p = _sizes(model_params)
    return 6.0 * pairs_held * _per_layer(p)["expert"]


def scan_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """The chunked scan's own matmuls, by shape, every chunk's L x L block
    counted whole: C Bᵀ (T·L·G·N MACs), the masked product with Δx
    (T·L·H·P), the chunk states (T·N·H·P) and the states' way to the
    outputs (T·N·H·P)."""
    p = _sizes(model_params)
    t, l = seq_len, p["chunk_size"]
    hp = p["d_inner"]
    macs = t * l * p["n_groups"] * p["ssm_state_size"] + t * l * hp \
        + 2 * t * p["ssm_state_size"] * hp
    return 6.0 * p["layers"]["M"] * macs


def scan_bytes_per_sample(model_params: dict, seq_len: int) -> float:
    """The least the scan moves, float32: forward reads x, B, C, Δ and writes
    y; backward reads them and dy again and writes dx, dB, dC, dΔ."""
    p = _sizes(model_params)
    inputs = p["conv_dim"] + p["mamba_num_heads"]             # x, B, C and Δ
    floats = (inputs + p["d_inner"]) + (inputs + p["d_inner"] + inputs)
    return 4.0 * p["layers"]["M"] * seq_len * floats


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal grouped-query attention's two matmuls (q·kᵀ and p·v), forward +
    backward: per QUERY head 2 matmuls x T x T x D MACs, half of them under
    the causal mask, 6 FLOPs a MAC. Fewer key-value heads save bytes, not
    FLOPs."""
    p = _sizes(model_params)
    return 6.0 * p["layers"]["*"] * 2 * seq_len * seq_len \
        * p["num_attention_heads"] * p["head_dim"] / 2


def model_flops_per_sample(model_params: dict, seq_len: int = 8192,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given), the scan and causal attention."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["layers"]
    dense = (layers["M"] * n["mamba_matmul"] + layers["*"] * n["attn_matmul"]
             + layers["E"] * (n["shared"] + n["router"])
             + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = layers["E"] * expected_held_pairs(model_params, seq_len)
    return (6.0 * dense * seq_len + held_expert_matmul_flops(model_params, pairs_held)
            + scan_flops_per_sample(model_params, seq_len)
            + attention_flops_per_sample(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * optimizer_parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 8192) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, and the float32 logits written and read forward and
    backward. Activations of the blocks are left out (a lower bound)."""
    n = optimizer_parameter_count(model_params)
    return optimizer_bytes(model_params) + n * (2 + 2 + 4) \
        + 4.0 * batch * seq_len * int(model_params["vocab_size"]) * 4
