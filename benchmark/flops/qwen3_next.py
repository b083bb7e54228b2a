"""Operations and bytes a Qwen3-Next (`qwen3_next`) training step needs, from
its shapes alone, and the names its program gives its parts. A sample is one
sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward and
four backward (the gradient of each operand): 6 a MAC, NOTHING RECOMPUTED —
the program recomputes every layer in its backward pass, and that work is its
own. What a token multiplies: a Gated DeltaNet mixer's fused q|k|v|z
projection, its (b, a) projection and its output projection; the attention
layer's q-and-gate, k, v and o projections; the router, the shared expert's
three matrices and its one-column gate, the routed experts it reaches, and the
head — not the embedding (a gather), the norms, the convolution (4
multiply-adds a channel on the vector unit) or the elementwise gates.

Attention is counted by VISIBLE (query, key) pairs only (T(T + 1)/2 a head).
The delta rule is counted by the MODEL's arithmetic in the SCALAR form at a
chunk of `COUNT_CHUNK` = 64 tokens, a constant of the COUNT that is not read
from the program: q and k at `linear_num_key_heads` heads, v and o at
`linear_num_value_heads`, g and β as (T, H_v) — whatever implements the scope
`qwen3_next/gdn/delta_rule` is held to the same floor
(`gdn_delta_rule_roofline`), so a repeated q or k, a widened g or a later
kernel cannot make its own yardstick stale.

`shape()` is the ONE dict the LM drivers ask of a configuration's shape
functions; the per-layer readers take their floors from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/qwen3_next.py, ops/moe.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
_GDN = ("gdn/proj", "gdn/conv", "gdn/qk_norm", "gdn/gates", "gdn/delta_rule",
        "gdn/gate_norm", "gdn/out", "gdn/counters", "gdn")
_ATTN = ("attn/proj", "attn/qk_norm", "attn/rope", "attn/flash", "attn/gate", "attn/out",
         "attn")
_MOE = ("moe/router", "moe/dispatch", "moe/experts", "moe/combine", "moe/shared", "moe")
SCOPES = (tuple(f"qwen3_next/{s}" for s in _GDN + _ATTN + _MOE)
          + ("qwen3_next/embed", "qwen3_next/head_loss", "optimizer", "qwen3_next"))
# the routed experts' grouped matmuls are the program's only ragged dots
# where `ops/pallas_gmm.py` cannot run
RAGGED_DOT_SCOPE = "qwen3_next/moe/experts"

# the chunk the delta rule's work is COUNTED at (see the module docstring)
COUNT_CHUNK = 64

# the published depth, experts and vocabulary, for `parameter_count(published)`
PUBLISHED = {"num_hidden_layers": 48, "kept_layers": "", "num_experts": 512,
             "router_experts": 0, "vocab_size": 151936}


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "linear_key_head_dim",
        "linear_value_head_dim", "linear_num_key_heads", "linear_num_value_heads",
        "num_attention_heads", "num_key_value_heads", "head_dim", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")}
    p["shared_width"] = int(model_params.get("shared_expert_intermediate_size",
                                             p["moe_intermediate_size"]))
    p["conv"] = int(model_params.get("linear_conv_kernel_dim", 4))
    p["router_experts"] = int(model_params.get("router_experts", 0)) or p["num_experts"]
    kept = model_params.get("kept_layers", "")
    layers = (tuple(int(l) for l in kept.split(",")) if kept
              else tuple(range(p["num_hidden_layers"])))
    types = model_params.get("layer_types", "")
    interval = int(model_params.get("full_attention_interval", 4))
    p["attn_layers"] = sum(
        (types.split(",")[l] == "full_attention") if types else (l + 1) % interval == 0
        for l in layers)
    p["gdn_layers"] = p["num_hidden_layers"] - p["attn_layers"]
    p["key_width"] = p["linear_num_key_heads"] * p["linear_key_head_dim"]
    p["value_width"] = p["linear_num_value_heads"] * p["linear_value_head_dim"]
    return p


def _per_layer(p: dict) -> dict:
    """Parameters of one sub-block of each kind, split into what a token
    multiplies (`matmul`) and the rest (norms, taps, per-head vectors)."""
    c, heads, d = p["hidden_size"], p["num_attention_heads"], p["head_dim"]
    kw, vw, hv = p["key_width"], p["value_width"], p["linear_num_value_heads"]
    return {
        # the fused q|k|v|z projection, (b, a), W_out
        "gdn_matmul": c * (2 * kw + 2 * vw) + c * 2 * hv + vw * c,
        # the taps over q, k and v's channels, A_log, dt_bias, the gated norm's weight
        "gdn_rest": p["conv"] * (2 * kw + vw) + 2 * hv + p["linear_value_head_dim"],
        # q and its gate, k, v, o
        "attn_matmul": (c * heads * 2 * d + 2 * c * p["num_key_value_heads"] * d
                        + heads * d * c),
        "attn_rest": 2 * d,                                     # the two head norms
        "expert": 3 * c * p["moe_intermediate_size"],
        "shared": 3 * c * p["shared_width"] + c,                # and its one-column gate
        "router": c * p["router_experts"],
        "norms": 2 * c,                                         # the two pre-norms
    }


def _mixers(p: dict, n: dict, part: str) -> int:
    """Σ over the layers of a mixer's `matmul` or `rest` parameters."""
    return p["gdn_layers"] * n[f"gdn_{part}"] + p["attn_layers"] * n[f"attn_{part}"]


def parameter_count(model_params: dict, published: bool = False) -> int:
    """Every parameter this chip holds, all of them swept by AdamW: the held
    experts only, embedding and head apart (untied), the final norm; with
    `published` the uncut model's (48 layers, all 512 experts, the whole
    vocabulary): 625 667 136 at the cell's cut, 79 674 391 296 uncut."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    layers = p["num_hidden_layers"]
    return (_mixers(p, n, "matmul") + _mixers(p, n, "rest")
            + layers * (n["norms"] + n["router"] + n["shared"] + p["num_experts"] * n["expert"])
            + 2 * p["vocab_size"] * p["hidden_size"] + p["hidden_size"])


def active_parameter_count(model_params: dict, published: bool = False) -> int:
    """The parameters one token's forward pass multiplies when every expert it
    chose is computed (the whole deployment's view of the token), the
    embedding's gather left out as the card's A3B leaves it: 3.56B uncut."""
    p = _sizes({**model_params, **PUBLISHED} if published else model_params)
    n = _per_layer(p)
    idle = (p["num_hidden_layers"] * max(p["num_experts"] - p["num_experts_per_tok"], 0)
            * n["expert"])
    return (parameter_count(model_params, published) - idle
            - p["vocab_size"] * p["hidden_size"])


def visible_pairs(seq_len: int) -> int:
    """(query, key) pairs one head sees under a causal mask."""
    return seq_len * (seq_len + 1) // 2


def expected_held_pairs(model_params: dict, seq_len: int) -> float:
    """(token, slot) pairs on the held experts of ONE layer at even routing."""
    p = _sizes(model_params)
    return seq_len * p["num_experts_per_tok"] * p["num_experts"] / p["router_experts"]


def held_expert_matmul_flops(model_params: dict, pairs_held: float) -> float:
    """The routed experts' three grouped matmuls for `pairs_held` pairs (summed
    over the layers), forward + backward: 6 x pairs x 3 x 2048 x 512."""
    return 6.0 * pairs_held * _per_layer(_sizes(model_params))["expert"]


def attention_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """Causal attention's two matmuls (q·kᵀ and p·v, `head_dim` MACs a pair
    each) of every query head over its visible pairs, the attention layers,
    forward + backward at 6 FLOPs a MAC."""
    p = _sizes(model_params)
    return (6.0 * 2 * p["head_dim"] * p["num_attention_heads"] * p["attn_layers"]
            * visible_pairs(seq_len))


def delta_rule_flops_per_sample(model_params: dict, seq_len: int) -> float:
    """The recurrence by the scalar chunked form's arithmetic at a chunk of L
    = `COUNT_CHUNK`, a token: per KEY head the two (L, L) products K Kᵀ and Q
    Kᵀ, 2 L d_k MACs; per VALUE head A K, A V and P u, L (d_k + 2 d_v), and W S,
    Q S and the state's update, 3 d_k d_v; 2 FLOPs a MAC forward, x 3 with the
    backward; the Gated DeltaNet layers. The inverse's log₂ L products and the
    recomputation are the program's own and not in the count."""
    p = _sizes(model_params)
    dk, dv, l = p["linear_key_head_dim"], p["linear_value_head_dim"], COUNT_CHUNK
    macs = (p["linear_num_key_heads"] * 2 * l * dk
            + p["linear_num_value_heads"] * (l * (dk + 2 * dv) + 3 * dk * dv))
    return 3.0 * 2.0 * macs * seq_len * p["gdn_layers"]


def delta_rule_bytes_per_sample(model_params: dict, seq_len: int) -> float:
    """The least the recurrence moves, float32: forward q, k (H_k heads) and v
    read, o written (H_v), g and β (T, H_v) read; backward q, k, v and do
    read, dq, dk, dv, dg and dβ written, g and β read again — 6 key planes, 5
    value planes, 6 planes of one number a value head."""
    p = _sizes(model_params)
    return 4.0 * seq_len * p["gdn_layers"] * (
        6 * p["key_width"] + 5 * p["value_width"] + 6 * p["linear_num_value_heads"])


def model_flops_per_sample(model_params: dict, seq_len: int = 16384,
                           pairs_held: float = None) -> float:
    """What this chip's step computes for one sequence: 6 x (what every token
    multiplies here) x tokens, the held experts' matmuls for the pairs that
    reached them (even routing if not given), causal attention over visible
    pairs and the delta rule."""
    p = _sizes(model_params)
    n = _per_layer(p)
    layers = p["num_hidden_layers"]
    every_token = (_mixers(p, n, "matmul") + layers * (n["shared"] + n["router"])
                   + p["hidden_size"] * p["vocab_size"])
    if pairs_held is None:
        pairs_held = layers * expected_held_pairs(model_params, seq_len)
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
    summed over the layers, as the run counted them."""
    p = _sizes(model_params)
    if pairs_held is None:
        pairs_held = p["num_hidden_layers"] * expected_held_pairs(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len, pairs_held),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "held_expert_matmul_flops_per_step":
            held_expert_matmul_flops(model_params, pairs_held) * batch,
        "gdn_attention_flops_per_step":
            attention_flops_per_sample(model_params, seq_len) * batch,
        "delta_rule_flops_per_step":
            delta_rule_flops_per_sample(model_params, seq_len) * batch,
        "delta_rule_bytes_per_step":
            delta_rule_bytes_per_sample(model_params, seq_len) * batch,
        "visible_pairs_per_head": visible_pairs(seq_len),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "active_parameters": active_parameter_count(model_params),
        "seq_len": seq_len,
    }
