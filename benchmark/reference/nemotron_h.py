"""The plain reference of configuration `nemotron-3-nano-30b-a3b` (and of any
`nemotron_h` zoo model): forward pass, loss, gradients by `jax.grad`, AdamW
and the routers' bias update, in straightforward `jax.numpy`, float32. No
kernel, no chunked scan, no sort-by-expert, no grouped matmul: the state-space
recurrence is a `lax.scan` over tokens, the convolution four shifted sums,
every held expert is applied to ALL tokens and masked, attention is the
materialised score matrix. The caller runs it under
`jax.default_matmul_precision("highest")`.

Written from the published layer equations (nvidia/NVIDIA-Nemotron-3-Nano-
30B-A3B-BF16 `config.json`, `model_type: nemotron_h`; Nemotron-H,
arXiv:2504.03624; Mamba-2, arXiv:2405.21060; the sigmoid router with a
selection bias: DeepSeek-V3, arXiv:2412.19437 §2.1.2), not from the zoo
module. It shares one thing with the program: the names and shapes of the
parameters, stacked per KIND of layer (`mamba_*` (M, …), `attn_*` (A, …),
`moe_norm`, `moe_router`, `shared_up`, `shared_down`, `w_up`, `w_down`
(E, …); `embed`, `final_norm`, `head`), so that the program's own initial
parameters are the reference's starting point, and the same share of the
deployment: the routed experts `first_expert … first_expert +
n_routed_experts − 1` and the vocabulary slice.

Every block is `x ← x + mixer(rmsnorm(x))`:
- `M`: `[z | xBC | dt] = h W_in`; `xBC ← silu(conv(xBC) + b)`; `x, B, C`;
  `Δ = softplus(dt + dt_bias)`; `S_t = exp(Δ_t A) S_{t−1} + Δ_t x_t ⊗ B_t`,
  `A = −exp(A_log)`; `y_t = S_t C_t + D x_t`; `y ← rmsnorm over each of the G
  groups of (y · silu(z)) · w`; `y W_out`.
- `E`: `s = sigmoid(h W_r)`; the k experts with the largest `s + b`;
  `w_e = scale · s_e / (Σ_chosen s + 1e-20)`; `Σ_{chosen, held} w_e W_down,e
  relu(W_up,e h)² + W_down,s relu(W_up,s h)²`; after the step
  `b_e ← b_e + u · sign(mean load − load_e)`.
- `*`: 32 query heads on 2 key-value heads (query head i, key-value head
  i // 16), causal softmax at scale D^-1/2, no rotary, no QK-norm.

Departures from a word-for-word transcription, values unchanged: each layer,
each block of `SCAN_BLOCK` tokens of the recurrence, each block of
`QUERY_BLOCK` queries and each expert's body is recomputed in the backward
pass (`jax.checkpoint`), so that 8192 tokens fit on one chip.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
BIAS_UPDATE_SPEED = 1e-3
# where the program keeps the routers' selection bias (TrainState.extra_vars)
BIAS = ("router_state", "e_score_correction_bias")
# and where it counts the passes its held dispatch ran, per E layer, since the
# state was made: one a layer and step while the pairs on held experts fit
# `held_pass_rows`
PASSES = ("router_state", "held_passes")
SCAN_BLOCK = 128
QUERY_BLOCK = 1024

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch. A limit sits
# between two readings (my chip runs, PR 30; all in PERF.md §6): the largest
# the program gave over its seeds (SOUND: 2147484101-2147484108, 11,
# 2147487001-2147487013, 2147491000, 2147491001, 2147492001-2147492009: 32
# runs, the last eleven at the configuration as committed) and what a CONTROL
# gives — the program with what the configuration
# states float32 computed or kept in bfloat16 (a bfloat16 router; the
# state-space path's convolution, Δ, scan output and gated norm kept in
# bfloat16: `rehearse/departures_nemotron_h.py::CONTROLS`), which has to read
# `correct: false` by one of these limits, not by each. Where a control
# hardly moves a figure (under 1.4 times the largest sound reading) the limit
# is three times that reading, and never wider than it stood.
def _between(sound: float, control: float, no_wider_than: float) -> float:
    if control >= 1.4 * sound:
        return (sound * control) ** 0.5
    return min(3.0 * sound, no_wider_than)


# AdamW's first moment is linear in the gradients, and every matmul of the
# program rounds its operands to bfloat16: per leaf (the largest sound
# reading, the reading with the state-space path kept in bfloat16, which
# about doubles the noise of every leaf upstream of attention's output).
# `mamba_dt_bias`, `mamba_A_log` (256 numbers a leaf) vary 1.8 and 1.5 times
# over the seeds and the control moves them less than that. Δ ALONE in
# bfloat16 moves no leaf by more than 8%: this check cannot see it.
_MU_READINGS = {
    "embed": (0.0209, 0.0348), "head": (0.00367, 0.00523), "final_norm": (0.00380, 0.00500),
    "mamba_norm": (0.0155, 0.0254), "mamba_in_proj": (0.0158, 0.0259),
    "mamba_conv_w": (0.0150, 0.0238), "mamba_conv_b": (0.00828, 0.0127),
    "mamba_dt_bias": (0.0261, 0.0327), "mamba_A_log": (0.0114, 0.0155),
    "mamba_D": (0.0115, 0.0239), "mamba_gate_norm": (0.0109, 0.0177),
    "mamba_out_proj": (0.0109, 0.0173),
    "moe_norm": (0.0115, 0.0185), "shared_up": (0.0105, 0.0165),
    "shared_down": (0.00555, 0.00798),
    "attn_norm": (0.00460, 0.00533), "attn_wq": (0.0133, 0.0195), "attn_wk": (0.0129, 0.0196),
    "attn_wv": (0.00419, 0.00449), "attn_wo": (0.00408, 0.00440),
    # the router's gradient comes through the renormalised weights alone and
    # is small beside its noise
    "moe_router": (0.0840, 0.1593),
}
TOLERANCES = {
    # the loss at seeded weights, a per-example mean over 8192 tokens: the
    # bfloat16 matmul errors of the single tokens average out and no control
    # moves it (2.4e-5-5.0e-5 under them), so three times the largest sound
    # reading: 7.1e-5 once in 32 runs, the others at most 5.0e-5. The
    # convolution's bias, D·x or silu(z) left out and weights not
    # renormalised read 2.0e-4, 2.5e-4, 4.1e-4 and 2.0e-4
    "loss_rel": 2.1e-4,
    # The program's router against this one ON THE SAME INPUT (the residual
    # stream the program's router saw), both float32 at the highest matmul
    # precision, at BOTH steps (the bias is zero at the first). Since the
    # mixers write their output in float32 the stream a router saw IS the one
    # the step reports, and only a near-tie closer than float32 rounding flips
    # (sound: 0.99992-1.0 agree; the weights' median error is 0, largest
    # 1.5e-7 under a departure). A bfloat16 router reads 0.99733 and 1.6e-4,
    # the bias used as a weight 7.7e-4 (at the second step, after one update
    # of 1e-3), the 2.5 left out 0.6, weights not renormalised 4.3. Limits at
    # the geometric middle of the disagreeing shares (6e-5, 2.7e-3) and well
    # under the smallest control's weight error
    "router_same_input_agreement_min": 0.9996,
    "router_weight_rel_median": 1e-5,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a residual stream without the program's bfloat16 rounding
    # upstream: a pair in 130 flips at a near-tie (sound: 0.9918-0.9927,
    # steady to 2e-4 at a seed). The reference then computes with the
    # program's choice. The state-space path kept in bfloat16 reads 0.9906
    # and passes THIS limit (it fails by the first moments); rotary positions
    # applied to q and k 0.9842 (attention at seeded weights is close to a
    # running mean, so positions move little); key-value head i % 2 0.918,
    # one RMS over 4096 channels 0.794, the convolution's bias, D·x or
    # silu(z) left out 0.45, 0.20, 0.14
    "routing_agreement_min": 0.988,
    # per leaf from `_MU_READINGS`; the experts' below. `default` is for a
    # leaf the table does not name
    "mu_rel_l2": {"default": 6e-2, "experts": 1.1e-1,
                  **{leaf: _between(sound, control, 6e-2)
                     for leaf, (sound, control) in _MU_READINGS.items()}},
    # the parameter update after the steps: AdamW's first steps are
    # ≈ lr · sign(g), so an element whose gradient is near zero changes sign
    # under rounding and counts twice, and at the warm-up's first step sizes
    # (5e-9, 1e-8) a float32 weight moves by a few ulps: the leaves of size
    # one (norms, D, A_log, dt_bias) do not move at all in the two steps and
    # read 0 on both sides. Sound: at most 0.136 (embed, mamba_conv_w), the
    # router 0.20-0.25, an expert 0.16-0.21; with the state-space path in
    # bfloat16 0.185 (mamba_conv_w), 0.375, 0.299: the precision hardly moves
    # them (1.2-1.5 times), three times the sound reading would be wider
    # than these stood, and they are not widened
    "update_rel_l2": {"default": 2.5e-1, "moe_router": 3.5e-1, "experts": 3.2e-1},
    # the share of the selection bias's entries that differ from the
    # reference's after the steps (sound: at most 6 of 512, experts whose load
    # is within a pair or two of the mean; the controls 2-4 of 512, key-value
    # head i % 2 36, the convolution's bias left out 115)
    "bias_entries_off_share": 0.05,
}
# The experts' leaves (`w_up`, `w_down`), expert by expert, all its layers
# together: an expert is judged apart only if it got at least this many
# (token, slot) pairs over the compared steps and layers; those with fewer
# are POOLED and judged as one unit (their squared errors and norms added).
# Derivation, from the 288 (layer, expert) slices of the nine seeds, by the
# pairs a slice got (first-moment error of `w_up`, the noisier leaf; largest
# of the bin): 32-64 pairs 0.171, 64-128 0.199, 128-256 0.179, 256-512 0.169,
# 512-1024 0.126, 1024 and more 0.079 (medians 0.135, 0.105, 0.094, 0.061,
# 0.048, 0.049): under about a thousand pairs a slice averages too little
# rounding noise out to be held to one number over seeds — OLMoE's
# worst-expert limit fails at one seed for that reason (PERF.md §7). At the
# cell's size an expert sees about 3072 pairs over two steps and four layers
# (fewest seen over the seeds: see PERF.md §6) and reads at most 0.078
# (`w_up`) and 0.067 (`w_down`); with the state-space path kept in bfloat16
# the worst judged expert reads 0.128 and 0.104. The limit 0.11 lies between
# `w_up`'s two readings; `w_down`'s control reading sits under it.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `num_experts` is what the router
    chooses among."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "mamba_num_heads",
            "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "n_routed_experts", "num_experts_per_tok", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    hp["hybrid_override_pattern"] = model_params["hybrid_override_pattern"]
    hp["first_expert"] = int(model_params.get("first_expert", 0))
    hp["num_experts"] = int(model_params.get("router_experts", 0)) or hp["n_routed_experts"]
    hp["routed_scaling_factor"] = float(model_params.get("routed_scaling_factor", 2.5))
    hp["eps"] = float(model_params.get("layer_norm_epsilon", 1e-5))
    hp["moe_layers"] = hp["hybrid_override_pattern"].count("E")
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def conv_causal(x, w, b):
    """x (B, T, Ch), w (K, Ch): y_t = Σ_j w_j x_{t−(K−1)+j} + b, zeros before
    the sequence — K shifted copies, summed."""
    k, t = w.shape[0], x.shape[1]
    y = jnp.zeros_like(x) + b
    for j in range(k):
        back = k - 1 - j
        shifted = jnp.concatenate(
            [jnp.zeros_like(x[:, :back]), x[:, :t - back]], axis=1)
        y = y + w[j] * shifted
    return y


def recurrence(x, delta, a, bmat, cmat):
    """S_t = exp(Δ_t a) S_{t−1} + Δ_t x_t ⊗ B_t; y_t = S_t C_t, token by
    token. x (B, T, H, P), delta (B, T, H), a (H,), bmat, cmat (B, T, H, N)
    (already one per head). Returns (B, T, H, P)."""
    b, t, h, p = x.shape
    n = bmat.shape[-1]

    def token(state, inputs):
        x_t, d_t, b_t, c_t = inputs
        state = (jnp.exp(d_t * a)[..., None, None] * state
                 + (d_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def block(state, inputs):
        return jax.lax.scan(token, state, inputs)

    pad = -t % SCAN_BLOCK               # Δ = 0 leaves the state as it is
    seq = [jnp.moveaxis(jnp.pad(v, ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2)), 1, 0)
           for v in (x, delta, bmat, cmat)]
    seq = [v.reshape((-1, SCAN_BLOCK) + v.shape[1:]) for v in seq]
    _, ys = jax.lax.scan(block, jnp.zeros((b, h, p, n), jnp.float32), tuple(seq))
    return jnp.moveaxis(ys.reshape((-1,) + ys.shape[2:]), 0, 1)[:, :t]


def mamba(p, x, hp):
    b, t, _ = x.shape
    heads, hd, g, n = (hp["mamba_num_heads"], hp["mamba_head_dim"], hp["n_groups"],
                       hp["ssm_state_size"])
    d_inner = heads * hd
    h = rms_norm(x, p["mamba_norm"], hp["eps"])
    proj = h @ p["mamba_in_proj"]
    z, xbc, dt = (proj[..., :d_inner], proj[..., d_inner:2 * d_inner + 2 * g * n],
                  proj[..., 2 * d_inner + 2 * g * n:])
    xbc = jax.nn.silu(conv_causal(xbc, p["mamba_conv_w"], p["mamba_conv_b"]))
    xs = xbc[..., :d_inner].reshape(b, t, heads, hd)
    bmat = xbc[..., d_inner:d_inner + g * n].reshape(b, t, g, n)
    cmat = xbc[..., d_inner + g * n:].reshape(b, t, g, n)
    per_head = lambda m: jnp.repeat(m, heads // g, axis=2)     # head i: group i // (H/G)
    delta = jax.nn.softplus(dt + p["mamba_dt_bias"])
    y = recurrence(xs, delta, -jnp.exp(p["mamba_A_log"]), per_head(bmat), per_head(cmat))
    y = y + p["mamba_D"][:, None] * xs
    gated = (y.reshape(b, t, d_inner) * jax.nn.silu(z)).reshape(b, t, g, d_inner // g)
    normed = gated / jnp.sqrt(jnp.mean(jnp.square(gated), axis=-1, keepdims=True) + hp["eps"])
    return (normed.reshape(b, t, d_inner) * p["mamba_gate_norm"]) @ p["mamba_out_proj"]


def attention(p, x, hp):
    b, t, _ = x.shape
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    q = (h @ p["attn_wq"]).reshape(b, t, heads, d)
    k = (h @ p["attn_wk"]).reshape(b, t, kv_heads, d)
    v = (h @ p["attn_wv"]).reshape(b, t, kv_heads, d)
    # query head i attends with key-value head i // (heads / kv_heads)
    k = jnp.repeat(k, heads // kv_heads, axis=2)
    v = jnp.repeat(v, heads // kv_heads, axis=2)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    positions = jnp.arange(t + pad).reshape(-1, block)
    q_blocks = jnp.moveaxis(
        jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(b, -1, block, heads, d), 1, 0)

    @jax.checkpoint
    def queries(q_block, q_pos):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_block, k) / jnp.sqrt(jnp.float32(d))
        causal = jnp.arange(t)[None, :] <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(lambda args: queries(*args), (q_blocks, positions))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * d)[:, :t]
    return out @ p["attn_wo"]


def router(p, x, bias, hp):
    """(h (N, C), scores (N, E), chosen (N, E) bool): the k experts with the
    largest score + bias among all E."""
    h = rms_norm(x, p["moe_norm"], hp["eps"]).reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(h @ p["moe_router"])
    biased = scores + bias
    # exactly k a token: of equal values the lower expert id first, as a
    # sort breaks ties (two sigmoids do come out equal in float32)
    by_rank = jnp.argsort(-biased, axis=-1, stable=True)
    rank = jnp.argsort(by_rank, axis=-1)
    return h, scores, rank < hp["num_experts_per_tok"]


def slot_weights(scores, use, hp):
    """(N, E): for every expert the weight it has if it is one of the token's
    experts `use` — the scores renormalised over the chosen, times the scale;
    the bias is not in it."""
    total = jnp.sum(jnp.where(use, scores, 0.0), axis=-1, keepdims=True)
    return hp["routed_scaling_factor"] * scores / (total + 1e-20)


def experts(p, h, weight, hp):
    """Σ_{e held} weight[:, e] · W_down,e relu(W_up,e h)², every held expert
    on every token; `weight` (N, E) is zero where the expert was not chosen,
    and only the held experts' columns are read."""
    first, held = hp["first_expert"], hp["n_routed_experts"]

    @jax.checkpoint
    def one(w_up, w_down, w_col):
        return w_col[:, None] * (jnp.square(jax.nn.relu(h @ w_up)) @ w_down)

    def add(total, per_expert):
        return total + one(*per_expert), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (p["w_up"], p["w_down"], weight[:, first:first + held].T))
    return total


def moe(p, x, bias, use, hp):
    """(the mixer's output, own choice (N, E), the weights of every expert
    under the reference's own choice (N, E)). `use` (N, E) bool, where given,
    takes the place of the router's own choice."""
    h, scores, own = router(p, x, bias, hp)
    weight = jnp.where(own if use is None else use,
                       slot_weights(scores, own if use is None else use, hp), 0.0)
    shared = jnp.square(jax.nn.relu(h @ p["shared_up"])) @ p["shared_down"]
    return ((experts(p, h, weight, hp) + shared).reshape(x.shape), own,
            slot_weights(scores, own, hp))


_KEYS = {"M": "mamba_", "*": "attn_"}
_MOE_KEYS = ("moe_norm", "moe_router", "shared_up", "shared_down", "w_up", "w_down")


def _layer(params, kind, index):
    if kind == "E":
        return {k: params[k][index] for k in _MOE_KEYS}
    return {k: v[index] for k, v in params.items() if k.startswith(_KEYS[kind])}


def forward(params, tokens, hp, chosen=None, bias=None):
    """tokens (B, T) -> (logits (B, T, V), per E layer the router's OWN
    choice (L, N, E) bool and the weights under it (L, N, E)).

    `chosen` (L, N, E) bool, where given, takes the place of the routers' own
    choice of experts — the scores stay the reference's. The check passes the
    program's choice: a pair that flips at a near-tie is rounding, and is
    judged by the share of agreeing pairs, not by the gradients of an expert
    that got another token. `bias` (L, E): the selection bias, zero if not
    given."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    x = params["embed"][tokens]
    seen = {"M": 0, "E": 0, "*": 0}
    own_all, weights_all = [], []
    for kind in hp["hybrid_override_pattern"]:
        i = seen[kind]
        seen[kind] += 1
        p = _layer(params, kind, i)
        if kind == "E":
            use = None if chosen is None else chosen[i]
            y, own, weights = jax.checkpoint(
                lambda p, x, b, use: moe(p, x, b, use, hp))(p, x, bias[i], use)
            own_all.append(own)
            weights_all.append(weights)
        else:
            mixer = mamba if kind == "M" else attention
            y = jax.checkpoint(lambda p, x: mixer(p, x, hp))(p, x)
        x = x + y
    logits = rms_norm(x, params["final_norm"], hp["eps"]) @ params["head"]
    return logits, jnp.stack(own_all), jnp.stack(weights_all)


def routers_on(params, router_inputs, hp, bias=None):
    """Every E layer's router on GIVEN residual streams (L, B, T, C): (chosen
    (L, N, E) bool, the weights under that choice (L, N, E))."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    chosen, weights = [], []
    for layer in range(hp["moe_layers"]):
        _, scores, own = router(_layer(params, "E", layer), router_inputs[layer],
                                bias[layer], hp)
        chosen.append(own)
        weights.append(slot_weights(scores, own, hp))
    return jnp.stack(chosen), jnp.stack(weights)


def loss(params, batch, hp, chosen=None, bias=None):
    """batch {"tokens" (B, T), "labels" (B, T), "mask" (B,)} -> (the scalar
    the optimizer minimises — the cross entropy alone, there is no auxiliary
    term — and (chosen, weights) of every E layer's own router)."""
    logits, own, weights = forward(params, batch["tokens"], hp, chosen, bias)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    per_example = jnp.mean(nll, axis=-1)
    mask = batch["mask"].astype(jnp.float32)
    return jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0), (own, weights)


def bias_update(bias, chosen, u=BIAS_UPDATE_SPEED):
    """b_e + u · sign(mean load − load_e): bias (L, E), chosen (L, N, E) bool
    — the choice the step was computed with, over all E experts."""
    load = jnp.sum(chosen, axis=1).astype(jnp.float32)
    return bias + u * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)


def adamw_step(params, grads, mu, nu, t, opt=ADAMW):
    """One AdamW step (decoupled weight decay on every parameter, bias-
    corrected moments, eps outside the root, linear warm-up of the step
    size), t counted from 1."""
    b1, b2 = opt["b1"], opt["b2"]
    lr = opt["learning_rate"] * jnp.minimum(1.0, t / opt["warmup_steps"])

    def leaf(p, g, m, v):
        m = b1 * m + (1.0 - b1) * g
        v = b2 * v + (1.0 - b2) * jnp.square(g)
        step = (m / (1.0 - b1 ** t)) / (jnp.sqrt(v / (1.0 - b2 ** t)) + opt["eps"])
        return p - lr * (step + opt["weight_decay"] * p), m, v

    out = jax.tree_util.tree_map(leaf, params, grads, mu, nu)
    pick = lambda i: jax.tree_util.tree_map(lambda _, o: o[i], params, out)
    return pick(0), pick(1), pick(2)
