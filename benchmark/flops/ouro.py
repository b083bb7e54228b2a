"""Operations and bytes an Ouro (`ouro`) training step needs, from its shapes
alone, and the names its program gives its parts. A sample is one sequence of
`seq_len` tokens. A multiply-accumulate is two FLOPs forward and four backward
(the gradient of each operand): 6 a MAC, by the MODEL's arithmetic — every
layer APPLICATION counts (N layers x P passes: a shared weight multiplies P
times a step) and every exit's head, NOTHING RECOMPUTED and no form of the
loop assumed: the program recomputes every application in its backward pass,
and that work is its own. What a token multiplies: attention's four
projections and the MLP's three matrices in every application, the head at
every exit — not the embedding (a gather), the norms or the gate (a dot with
one vector).

Attention is counted by VISIBLE (query, key) pairs only: a head sees T(T + 1)/2
of them. A block the kernel computes and masks away is not in the count, and
neither is the scores' recomputation in the backward kernel: both lower the
kernels' share of the roofline instead of hiding in it.

`shape()` is the ONE dict the LM drivers ask of a configuration's shape
functions; the per-layer readers take their floors from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/ouro.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
SCOPES = (tuple(f"ouro/pass/{part}" for part in ("attn", "mlp", "norm", "final_norm"))
          + ("ouro/pass", "ouro/exit_loss", "ouro/exit", "ouro/embed", "optimizer", "ouro"))
# the program has no ragged dot
RAGGED_DOT_SCOPE = None


def _sizes(model_params: dict) -> dict:
    return {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "intermediate_size", "total_ut_steps")}


def _per_layer(p: dict) -> dict:
    """Parameters of one layer, split into what a token multiplies and the
    rest (the four sandwich norms)."""
    c, d = p["hidden_size"], p["head_dim"]
    return {
        "attn_matmul": (c * d * (p["num_attention_heads"] + 2 * p["num_key_value_heads"])
                        + p["num_attention_heads"] * d * c),
        "mlp_matmul": 3 * c * p["intermediate_size"],
        "norms": 4 * c,
    }


def parameter_count(model_params: dict) -> int:
    """Every parameter, all of them swept by AdamW, ONCE however many passes
    use them: the layers, embedding and head, the final norm, the exit gate
    (a vector and a bias). 612 438 017 at the benchmark's 8 layers,
    2 667 974 657 at the published 48."""
    p = _sizes(model_params)
    c = p["hidden_size"]
    return (p["num_hidden_layers"] * sum(_per_layer(p).values())
            + 2 * p["vocab_size"] * c + c + c + 1)


def applications(model_params: dict) -> int:
    """Layer applications a step: layers x passes."""
    p = _sizes(model_params)
    return p["num_hidden_layers"] * p["total_ut_steps"]


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs one head sees under a causal mask."""
    return seq_len * (seq_len + 1) // 2


def attention_flops(model_params: dict, seq_len: int) -> float:
    """The two matmuls (q·kᵀ and p·v) of every head over its visible pairs in
    EVERY layer application, forward + backward: 2 matmuls x D MACs a pair, 6
    FLOPs a MAC."""
    p = _sizes(model_params)
    return (6.0 * 2 * p["head_dim"] * p["num_attention_heads"] * visible_pairs(seq_len)
            * applications(model_params))


def layer_matmul_flops(model_params: dict, seq_len: int) -> dict:
    """{"attn", "mlp"}: the projections' and the MLP's matmuls over every
    token in every layer application, forward + backward."""
    n = _per_layer(_sizes(model_params))
    each = 6.0 * seq_len * applications(model_params)
    return {"attn": each * n["attn_matmul"], "mlp": each * n["mlp_matmul"]}


def exit_flops(model_params: dict, seq_len: int) -> float:
    """The head's matmul at every exit, forward + backward."""
    p = _sizes(model_params)
    return 6.0 * p["hidden_size"] * p["vocab_size"] * seq_len * p["total_ut_steps"]


def model_flops_per_sample(model_params: dict, seq_len: int = 4096) -> float:
    return (sum(layer_matmul_flops(model_params, seq_len).values())
            + attention_flops(model_params, seq_len) + exit_flops(model_params, seq_len))


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def exit_bytes(model_params: dict, batch: int, seq_len: int) -> float:
    """The least the exits move: each exit's float32 logits written and read
    forward and backward."""
    p = _sizes(model_params)
    return 4.0 * batch * seq_len * p["vocab_size"] * 4 * p["total_ut_steps"]


def step_bytes(model_params: dict, batch: int, seq_len: int = 4096) -> float:
    """The least a step has to move: the optimizer's sweep, every layer's
    parameters read once forward and once backward as bfloat16 IN EVERY PASS
    (the head at every exit) and every float32 gradient written once, and the
    exits' logits. Activations of the layers are left out (a lower bound)."""
    p = _sizes(model_params)
    n, passes = parameter_count(model_params), p["total_ut_steps"]
    used = n - p["vocab_size"] * p["hidden_size"]        # the embedding is gathered
    return (optimizer_bytes(model_params) + used * (2 + 2) * passes + n * 4
            + exit_bytes(model_params, batch, seq_len))


def shape(model_params: dict, batch: int, seq_len: int) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip."""
    matmul = layer_matmul_flops(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "ut_attention_flops_per_step": attention_flops(model_params, seq_len) * batch,
        "ut_attn_matmul_flops_per_step": matmul["attn"] * batch,
        "ut_mlp_matmul_flops_per_step": matmul["mlp"] * batch,
        "ut_exit_flops_per_step": exit_flops(model_params, seq_len) * batch,
        "ut_exit_bytes_per_step": exit_bytes(model_params, batch, seq_len),
        "visible_pairs_per_head": visible_pairs(seq_len),
        "layer_applications_per_step": applications(model_params),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "seq_len": seq_len,
    }
