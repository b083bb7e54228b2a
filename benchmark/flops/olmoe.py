"""Operations and bytes an OLMoE training step needs, from its shapes alone.
A sample is one sequence of `seq_len` tokens. A multiply-accumulate is two
FLOPs forward and four backward (the gradient of each operand): 6 a MAC,
nothing recomputed. Active parameters are those a token's forward pass
multiplies: attention's four projections, the router, k of the E experts, the
head — not the embedding (a gather) and not the norms."""

from __future__ import annotations


def _sizes(model_params: dict):
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "intermediate_size", "num_experts", "num_experts_per_tok")}
    return (p["vocab_size"], p["hidden_size"], p["num_hidden_layers"],
            p["num_attention_heads"], p["intermediate_size"], p["num_experts"],
            p["num_experts_per_tok"])


def parameter_count(model_params: dict) -> int:
    """Every parameter the optimizer sweeps."""
    v, c, layers, _, f, e, _ = _sizes(model_params)
    layer = 4 * c * c + 4 * c + c * e + e * 3 * c * f      # 4 norms: attn, q, k, ffn
    return layers * layer + 2 * v * c + c


def active_parameter_count(model_params: dict) -> int:
    v, c, layers, _, f, e, k = _sizes(model_params)
    return layers * (4 * c * c + c * e + k * 3 * c * f) + c * v


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal attention's two matmuls (q·kᵀ and p·v), forward + backward:
    per head 2 matmuls x T x T x D MACs, half of them under the causal mask,
    6 FLOPs a MAC."""
    _, c, layers, _, _, _, _ = _sizes(model_params)
    return 6.0 * layers * 2 * seq_len * seq_len * c / 2


def expert_matmul_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """The grouped matmuls alone: T x k pairs through three C x F matrices."""
    _, c, layers, _, f, _, k = _sizes(model_params)
    return 6.0 * layers * seq_len * k * 3 * c * f


def model_flops_per_sample(model_params: dict, seq_len: int = 4096) -> float:
    """6 x active parameters x tokens + causal attention."""
    return 6.0 * active_parameter_count(model_params) * seq_len \
        + attention_flops_per_sample(model_params, seq_len)


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def step_bytes(model_params: dict, batch: int, seq_len: int = 4096) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and its float32 gradient
    written once, and the float32 logits written and read forward and
    backward. Activations of the blocks are left out (a lower bound)."""
    v = int(model_params["vocab_size"])
    n = parameter_count(model_params)
    return optimizer_bytes(model_params) + n * (2 + 2 + 4) \
        + 4.0 * batch * seq_len * v * 4
