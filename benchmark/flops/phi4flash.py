"""Operations and bytes a Phi-4-mini-flash (`phi4flash`) training step needs,
from its shapes alone, and the names its program gives its parts. A sample is
one sequence of `seq_len` tokens. A multiply-accumulate is two FLOPs forward
and four backward (the gradient of each operand): 6 a MAC, by the MODEL's
arithmetic — NOTHING RECOMPUTED: the program recomputes every layer in its
backward pass, and that work is its own. What a token multiplies: every
projection of its layer's mixer, the MLP's two matrices, the tied head — not
the embedding's gather, the LayerNorms, the convolution (K multiply-adds a
channel) or the selective scan, which is no matmul at all and is counted in
state updates and bytes of its own.

Differential attention is counted by VISIBLE (query, key) pairs only (T(T +
1)/2 a head under the causal mask, Σ_t min(t + 1, W) under a window): both
maps' q·kᵀ at the head size D and both maps' p·v at the value size 2D, for
every query PAIR. A block the kernel computes and masks away is not in the
count, and neither is the scores' recomputation in the backward kernel: both
lower the kernels' share of the roofline instead of hiding in it.

`shape()` is the ONE dict the LM drivers ask of a configuration's shape
functions; the per-layer readers take their floors from it."""

from __future__ import annotations

# The scopes the program names (model_zoo/transformer/phi4flash.py,
# training/trainer.py), most specific first: an instruction belongs to the
# first whose name its `op_name` carries.
SCOPES = (tuple(f"phi4flash/mamba/{part}" for part in ("proj", "conv", "dt", "scan", "gate_out"))
          + tuple(f"phi4flash/diff_attn/{part}" for part in ("proj", "flash", "combine"))
          + ("phi4flash/mamba", "phi4flash/gmu", "phi4flash/diff_attn", "phi4flash/mlp",
             "phi4flash/norm", "phi4flash/head_loss", "phi4flash/embed", "optimizer",
             "phi4flash"))
# the program has no ragged dot
RAGGED_DOT_SCOPE = None

PUBLISHED_LAYERS = 32
# the published model's own sizes, for `parameter_count("published")`
PUBLISHED = {"vocab_size": 200064, "hidden_size": 2560, "num_hidden_layers": 32,
             "num_attention_heads": 40, "num_key_value_heads": 20,
             "intermediate_size": 10240, "sliding_window": 512}
KINDS = ("mamba", "sliding", "full", "gmu", "cross")


def _sizes(model_params: dict) -> dict:
    p = {k: int(model_params[k]) for k in PUBLISHED}
    p["d_state"] = int(model_params.get("mamba_d_state", 16))
    p["d_conv"] = int(model_params.get("mamba_d_conv", 4))
    p["d_inner"] = int(model_params.get("mamba_expand", 2)) * p["hidden_size"]
    p["dt_rank"] = -(-p["hidden_size"] // 16)
    p["head_dim"] = p["hidden_size"] // p["num_attention_heads"]
    kept = model_params.get("kept_layers")
    p["layers"] = (tuple(int(i) for i in str(kept).split(",")) if kept
                   else tuple(range(p["num_hidden_layers"])))
    return p


def kind_of(i: int) -> str:
    """The mixer of the layer of published index i."""
    half = PUBLISHED_LAYERS // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    return "sliding" if i < half else ("full" if i == half + 1 else "cross")


def layers_by_kind(model_params: dict) -> dict:
    kinds = [kind_of(i) for i in _sizes(model_params)["layers"]]
    return {kind: kinds.count(kind) for kind in KINDS}


def _mixer(p: dict) -> dict:
    """{kind: (parameters a token multiplies, the mixer's other parameters)}."""
    c, e, n, r, k = p["hidden_size"], p["d_inner"], p["d_state"], p["dt_rank"], p["d_conv"]
    q = p["num_attention_heads"] * p["head_dim"]
    kv = 2 * p["num_key_value_heads"] * p["head_dim"]
    other = q + c + 4 * p["head_dim"] + 2 * p["head_dim"]     # wo, its bias, λ, the sub-norm
    attention = lambda width: (c * width + q * c, width + other - q)
    return {
        "mamba": (c * 2 * e + e * (r + 2 * n) + r * e + e * c, k * e + e + e + e * n + e),
        "gmu": (2 * c * e, 0),
        "sliding": attention(q + kv), "full": attention(q + kv), "cross": attention(q),
    }


def _mlp(p: dict) -> int:
    return 3 * p["hidden_size"] * p["intermediate_size"]


def parameter_count(model_params) -> int:
    """Every parameter, all of them swept by AdamW; the tied matrix ONCE.
    `"published"` (or a dict of the published sizes): 3 852 562 944, the
    card's 3.8B; at the benchmark's six layers and 25 008 ids: 697 094 272."""
    p = _sizes(PUBLISHED if model_params == "published" else model_params)
    mixers, c = _mixer(p), p["hidden_size"]
    layers = sum(sum(mixers[kind_of(i)]) + _mlp(p) + 4 * c for i in p["layers"])
    return layers + p["vocab_size"] * c + 2 * c


def visible_pairs(seq_len: int, window=None) -> int:
    """(query, key) pairs one head sees: T(T + 1)/2 under the causal mask,
    Σ_t min(t + 1, W) under a window."""
    if window is None or window >= seq_len:
        return seq_len * (seq_len + 1) // 2
    return window * (window + 1) // 2 + (seq_len - window) * window


def diff_flash_flops(model_params: dict, seq_len: int) -> float:
    """Both maps of every query pair of every attention layer over their
    VISIBLE pairs, forward + backward: q·kᵀ at D and p·v at 2D MACs a pair and
    map, 6 FLOPs a MAC."""
    p = _sizes(model_params)
    n = layers_by_kind(model_params)
    pairs = (n["sliding"] * visible_pairs(seq_len, p["sliding_window"])
             + (n["full"] + n["cross"]) * visible_pairs(seq_len))
    d = p["head_dim"]
    return 6.0 * 2 * (d + 2 * d) * (p["num_attention_heads"] // 2) * pairs


def matmul_flops(model_params: dict, seq_len: int) -> dict:
    """{kind of layer: its mixer's projections, "mlp", "head"}: the matmuls
    over every token, forward + backward."""
    p = _sizes(model_params)
    n, mixers = layers_by_kind(model_params), _mixer(p)
    out = {kind: 6.0 * seq_len * n[kind] * mixers[kind][0] for kind in KINDS}
    out["mlp"] = 6.0 * seq_len * len(p["layers"]) * _mlp(p)
    out["head"] = 6.0 * seq_len * p["hidden_size"] * p["vocab_size"]
    return out


def scan_updates(model_params: dict, seq_len: int) -> int:
    """(token, channel, state index) updates the scans of one sequence walk
    in ONE pass over it."""
    p = _sizes(model_params)
    return layers_by_kind(model_params)["mamba"] * seq_len * p["d_inner"] * p["d_state"]


def scan_bytes(model_params: dict, batch: int, seq_len: int) -> float:
    """The least the selective scans move, whatever implements the scope: x,
    Δ, B, C read and y written forward; the same and dy read and five
    gradients (dx, dΔ, dB, dC, and dA + dD, which are small) written backward;
    float32."""
    p = _sizes(model_params)
    plane, small = seq_len * p["d_inner"], seq_len * p["d_state"]
    forward = 3 * plane + 2 * small
    backward = 3 * plane + 2 * small + plane + (2 * plane + 2 * small
                                                + p["d_inner"] * (p["d_state"] + 1))
    return 4.0 * batch * layers_by_kind(model_params)["mamba"] * (forward + backward)


def model_flops_per_sample(model_params: dict, seq_len: int = 8192) -> float:
    return sum(matmul_flops(model_params, seq_len).values()) + diff_flash_flops(
        model_params, seq_len)


def optimizer_bytes(model_params: dict) -> float:
    """AdamW's sweep: gradient, parameter and both moments read, parameter
    and both moments written, float32."""
    return 7.0 * 4 * parameter_count(model_params)


def head_bytes(model_params: dict, batch: int, seq_len: int) -> float:
    """The least the head and loss move: the float32 logits written and read
    forward and backward."""
    return 4.0 * batch * seq_len * _sizes(model_params)["vocab_size"] * 4


def step_bytes(model_params: dict, batch: int, seq_len: int = 8192) -> float:
    """The least a step has to move: the optimizer's sweep, every parameter
    read once forward and once backward as bfloat16 and every float32 gradient
    written once, the logits, the scans' planes. The layers' other activations
    are left out (a lower bound)."""
    n = parameter_count(model_params)
    return (optimizer_bytes(model_params) + n * (2 + 2) + n * 4
            + head_bytes(model_params, batch, seq_len)
            + scan_bytes(model_params, batch, seq_len))


def shape(model_params: dict, batch: int, seq_len: int) -> dict:
    """Everything shape-derived a run reports, for `batch` sequences a step on
    one chip."""
    matmul = matmul_flops(model_params, seq_len)
    return {
        "model_flops_per_sample": model_flops_per_sample(model_params, seq_len),
        "step_bytes_per_chip": step_bytes(model_params, batch, seq_len),
        "diff_flash_flops_per_step": diff_flash_flops(model_params, seq_len) * batch,
        "s6_scan_updates_per_step": scan_updates(model_params, seq_len) * batch,
        "s6_scan_bytes_per_step": scan_bytes(model_params, batch, seq_len),
        **{f"{kind}_matmul_flops_per_step": flops * batch for kind, flops in matmul.items()},
        "head_bytes_per_step": head_bytes(model_params, batch, seq_len),
        "layers_by_kind": layers_by_kind(model_params),
        "optimizer_bytes_per_chip": optimizer_bytes(model_params),
        "parameters": parameter_count(model_params),
        "seq_len": seq_len,
    }
