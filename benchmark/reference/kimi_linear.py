"""The plain reference of configuration `kimi-linear-48b-a3b` (and of any
`kimi_linear` zoo model): forward pass, loss, gradients by `jax.grad`, AdamW
and the routers' bias update, in straightforward `jax.numpy`, float32. No
kernel, no chunk algebra, no triangular inverse, no WY form, no sort-by-expert,
no grouped matmul, no key widened to every head: the delta rule is the
recurrence as published, ONE TOKEN AT A TIME; the convolution is four shifted
sums; the scores are the sum of a per-head product over the first
`qk_nope_head_dim` channels and a product with the ONE shared key over the
last `qk_rope_head_dim`; every held expert is applied to ALL tokens and
masked; attention is the score matrix of a block of queries against all keys.
The caller runs it under `jax.default_matmul_precision("highest")`.

Written from the published equations, not from the zoo module: "Kimi Linear:
An Expressive, Efficient Attention Architecture" (arXiv:2510.26692) for the
mixers; moonshotai/Kimi-Linear-48B-A3B-Instruct `config.json` (`model_type:
kimi_linear`), whose feed-forward keys are DeepSeek-V3's (arXiv:2412.19437
§2.1.2). It shares one thing with the program: the names and shapes of the
parameters (`model_zoo/transformer/kimi_linear.py` lists them), so that the
program's own initial parameters are the reference's starting point, and the
same share of the deployment: the routed experts `first_expert … first_expert
+ num_experts − 1` and the vocabulary slice. Every *assumed* item is the
configuration file's (`benchmark/configs/kimi-linear-48b-a3b.json`,
`assumed`).

Layers are numbered from one. A layer: `x ← x + Mixer(rms_norm(x))`, `x ← x +
FFN(rms_norm(x))`.
- KDA (layers in `kda_layers`; H heads of d, h the normed input): `q~, k~, v~
  = silu(conv4(h W_q)), silu(conv4(h W_k)), silu(conv4(h W_v))`, the
  convolutions depthwise and causal; `q = d^-1/2 q~ / sqrt(Σ q~² + 1e-6)`, `k
  = k~ / sqrt(Σ k~² + 1e-6)` per head; `g = −exp(A_log[head]) softplus((h
  W_f↓) W_f↑ + dt_bias)`, one a channel; `β = sigmoid(h W_β)`, one a head; per
  head `S_0 = 0`, `S_t = (I − β_t k_t k_tᵀ) Diag(exp g_t) S_{t−1} + β_t k_t
  v_tᵀ`, `o_t = S_tᵀ q_t`; `y = rms_norm_d(o) ⊙ sigmoid((h W_g↓) W_g↑)`, the
  norm over each head's d channels with one shared weight; `y W_o`.
- latent attention (layers in `full_attn_layers`): `q = h W_q` per head (no
  low rank, no norm); `[c_kv | k_r] = h W_kva`, `c_kv ← rms_norm(c_kv)`, `[k_n
  | v] = c_kv W_kvb` per head; `s = (q_n · k_n + q_r · k_r) / sqrt(d_n + d_r)`,
  NOTHING rotated; causal softmax; `· v`; `W_o`.
- dense ff (layers ≤ `first_k_dense_replace`): `W_down(silu(h W_gate) ⊙ h
  W_up)`.
- sparse ff: `s = sigmoid(h W_r)`; the k experts with the largest `s + b`;
  `w_e = scale · s_e / (Σ_chosen s + 1e-20)`; `Σ_{chosen, held} w_e ff_e(h) +
  ff_shared(h)`; after the step `b_e ← b_e + u · sign(mean load − load_e)`.

Departures from a word-for-word transcription, values unchanged — MEMORY
SHAPING ONLY: the recurrence is a `lax.scan` over tokens in TWO levels, an
outer one over blocks of `KDA_BLOCK` tokens under `jax.checkpoint` and an
inner one over a block's tokens (32 heads x 64 KB of state a token is 34 GB
kept flat at 16 384 tokens; 128 blocks of 128 keep 2 x 268 MB); each of a
layer's two sub-blocks, each block of `QUERY_BLOCK` queries, each expert's
body and each block of `HEAD_BLOCK` positions of the head with its cross
entropy is recomputed in the backward pass (`jax.checkpoint`), so that 16 384
tokens fit on one chip beside the float32 parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
BIAS_UPDATE_SPEED = 1e-3
# where the program keeps the routers' selection bias (TrainState.extra_vars)
BIAS = ("router_state", "e_score_correction_bias")
# and where it counts the passes its held dispatch ran, per sparse layer
PASSES = ("router_state", "held_passes")
QUERY_BLOCK = 512
HEAD_BLOCK = 1024
KDA_BLOCK = 128
L2_EPS = 1e-6
PUBLISHED_FULL = (4, 8, 12, 16, 20, 24, 27)


# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch (my chip runs, PR
# 54; every reading in PERF.md §6). SOUND: the largest the program gave over
# its seeds from settled routers at 16 384 tokens (2147483659, 2147481013,
# 1900000129, 2147483777, and the seeds of PERF.md §6's later runs). CONTROL:
# the nearest of `rehearse/departures_kimi_linear.py`'s cases — a departure of
# the model, or a part the configuration states float32 computed in bfloat16 —
# which has to read `correct: false` by one of these limits, not by each. The
# sound readings lie close together (a dense leaf's first moment 0.003–0.008 at
# every seed) and the departures far off (0.6–0.7 in every KDA leaf with the
# L2 norm of k left out, at 16 384 tokens), so a limit is THREE TIMES the
# largest sound reading of its group: room for a fresh seed, and a tenth or
# less of what a departure reads.
TOLERANCES = {
    # the loss at seeded weights, a per-example mean over 16 384 tokens: sound
    # 1.8e-6 – 4.0e-6; the L2 norm of k left out reads 1.6e-5. Mellum2's limit
    # (the same hidden size, sequence and initialisation), four times the
    # largest sound reading
    "loss_rel": 1.6e-5,
    "loss_ce_rel": 1.6e-5,
    # The program's router against this one ON THE SAME INPUT, both float32 at
    # the highest matmul precision, at both steps: sound 0.9988–0.9994 agree
    # (the disagreeing share at most 1.2e-3), the weights' median error 0
    "router_same_input_agreement_min": 1.0 - 3 * 1.2e-3,
    "router_weight_rel_median": 5e-6,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a stream without the program's bfloat16 rounding upstream:
    # sound 0.9940–0.9948 (a pair in 170 flips at a near-tie); the L2 norm of k
    # left out reads 0.929
    "routing_agreement_min": 1.0 - 3 * 6.0e-3,
    # AdamW's first moment, linear in the gradients. Sound: every dense leaf
    # 0.0009 (head) – 0.0076 (`kda_f_a`), the KDA leaves 0.0046–0.0076, the
    # latent ones 0.0029–0.0062
    "mu_rel_l2": {"default": 3 * 0.0076,
                  # WHAT HOLDS THE DECAY'S PRECISION: the cumulative log-decay
                  # kept in bfloat16 moves the decay's own low-rank pair from
                  # 0.0074 to 0.0231 / 0.0229 (8192 tokens, seed 2147483777;
                  # sound at 16 384: 0.0069–0.0076 in five runs) and every
                  # other leaf by under 1.3 times: the geometric middle
                  "kda_f_a": (0.0076 * 0.0231) ** 0.5,
                  "kda_f_b": (0.0076 * 0.0229) ** 0.5,
                  # the router's gradient comes through the renormalised
                  # weights alone: sound 0.018–0.042
                  "moe_router": 3 * 0.042,
                  # the worst judged expert (every one of the eight got
                  # 3645–3912 pairs over the two steps and four layers): sound
                  # 0.035–0.045
                  "experts": 3 * 0.045},
    # the parameter update after the steps: AdamW's first steps are
    # ≈ lr · sign(g), so an element whose gradient is near zero changes sign
    # under rounding and counts twice (PR 44's law: ≈ 1.13 √mu_rel_l2). Sound:
    # the matrices 0.010 (head) – 0.041, the norms 0.044–0.078 (or 0, or one
    # float32 ulp: at the warm-up's first steps their updates, 2e-8 and 4e-8,
    # are under float32's resolution at 1.0, as `kda_A_log`'s and
    # `kda_dt_bias`'s are), the router 0.068–0.092, the worst judged expert
    # 0.091–0.116
    "update_rel_l2": {"default": 3 * 0.078,
                      # 128 and 4096 numbers of size 1–3 and 2–7 whose two
                      # updates (2e-8, 4e-8 under the warm-up) are UNDER
                      # float32's resolution there: the figure counts the few
                      # entries that move one ulp — 0, 0.0011 and, at 8192
                      # tokens, 0.3333 in sound runs — and holds nothing;
                      # their first moment (above) holds these leaves
                      "kda_A_log": 1.5, "kda_dt_bias": 1.5,
                      "moe_router": 3 * 0.092,
                      "experts": 3 * 0.116},
    # the share of the selection bias's entries that differ from the
    # reference's after the steps: an expert within a pair or two of the mean
    # load turns its sign on one flipped pair (sound: 0.012–0.020 of 1024). A
    # missing or mis-signed update reads 0.5–1 (GLM's cell, the same rule)
    "bias_entries_off_share": 3 * 0.020,
}
# The experts' leaves, expert by expert, all its layers together: an expert is
# judged apart only if it got at least this many (token, slot) pairs over the
# compared steps and layers; those with fewer are POOLED and judged as one.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `num_experts` is what the router
    chooses among, `n_routed_experts` how many are held here (the names the
    driver reads them by)."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
            "linear_num_heads", "linear_head_dim", "num_attention_heads", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim", "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    given = lambda key, default, kind=float: kind(model_params.get(key, default))
    hp["n_routed_experts"] = int(model_params["num_experts"])
    hp["num_experts_per_tok"] = int(model_params["num_experts_per_token"])
    hp["first_k_dense_replace"] = given("first_k_dense_replace", 1, int)
    hp["conv"] = given("short_conv_kernel_size", 4, int)
    hp["first_expert"] = given("first_expert", 0, int)
    hp["num_experts"] = given("router_experts", 0, int) or hp["n_routed_experts"]
    hp["routed_scaling_factor"] = given("routed_scaling_factor", 2.446)
    hp["eps"] = given("rms_norm_eps", 1e-5)
    full = model_params.get("full_attn_layers")
    hp["full_attn_layers"] = (PUBLISHED_FULL if full is None
                              else tuple(int(l) for l in full.split(",") if l))
    hp["moe_layers"] = hp["num_hidden_layers"] - hp["first_k_dense_replace"]
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


# ------------------------------------------------------------------ #
# the delta-rule mixer


def causal_conv(x, weight):
    """x (B, T, P), weight (W, P): y_t = Σ_j weight_j x_{t − (W−1) + j}, zeros
    before the sequence — W shifted sums."""
    width, t = weight.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(width):
        shift = width - 1 - j
        shifted = jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :t]
        y = y + shifted * weight[j]
    return y


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k, g (B, T, H, d), v (B, T, H, d),
    beta (B, T, H) -> o (B, T, H, d). S (B, H, d_k, d_v) starts at zero."""
    b, t, h, d = k.shape
    block = min(KDA_BLOCK, t)
    pad = -t % block             # padded tokens: no decay, nothing written

    def blocks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, -1, block) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 2, 0), 2, 0)            # (blocks, block, B, ...)

    def token(s, at):
        q_t, k_t, v_t, g_t, beta_t = at
        s = jnp.exp(g_t)[..., None] * s                              # Diag(α) S
        erased = jnp.einsum("bhk,bhkv->bhv", k_t, s)                 # (k ᵀ S): what k reads
        s = s + jnp.einsum("bhk,bhv->bhkv", k_t, beta_t[..., None] * (v_t - erased))
        return s, jnp.einsum("bhk,bhkv->bhv", q_t, s)

    @jax.checkpoint
    def block_of_tokens(s, operands):
        return jax.lax.scan(token, s, operands)

    _, o = jax.lax.scan(block_of_tokens, jnp.zeros((b, h, d, v.shape[-1]), jnp.float32),
                        tuple(map(blocks, (q, k, v, g, beta))))
    # (blocks, block, B, H, d) -> (B, T, H, d)
    return jnp.moveaxis(o.reshape((-1,) + o.shape[2:]), 0, 1)[:, :t]


def kda(p, x, hp):
    b, t, _ = x.shape
    heads, d = hp["linear_num_heads"], hp["linear_head_dim"]
    by_head = lambda a: a.reshape(b, t, heads, d)
    unit = lambda a: a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + L2_EPS)
    h = rms_norm(x, p["kda_norm"], hp["eps"])
    q = by_head(jax.nn.silu(causal_conv(h @ p["kda_wq"], p["kda_conv_q"])))
    k = by_head(jax.nn.silu(causal_conv(h @ p["kda_wk"], p["kda_conv_k"])))
    v = by_head(jax.nn.silu(causal_conv(h @ p["kda_wv"], p["kda_conv_v"])))
    q, k = unit(q) / math.sqrt(d), unit(k)
    a = (h @ p["kda_f_a"]) @ p["kda_f_b"]
    g = -jnp.exp(p["kda_A_log"])[:, None] * by_head(jax.nn.softplus(a + p["kda_dt_bias"]))
    beta = jax.nn.sigmoid(h @ p["kda_beta"])
    o = delta_rule(q, k, v, g, beta)
    gate = jax.nn.sigmoid((h @ p["kda_g_a"]) @ p["kda_g_b"])
    y = rms_norm(o, p["kda_onorm"], hp["eps"]).reshape(b, t, heads * d) * gate
    return y @ p["kda_wo"]


# ------------------------------------------------------------------ #
# latent attention, no positions


def attention(p, x, hp):
    b, t, _ = x.shape
    heads, d_n, d_r, d_v = (hp["num_attention_heads"], hp["qk_nope_head_dim"],
                            hp["qk_rope_head_dim"], hp["v_head_dim"])
    rank = hp["kv_lora_rank"]
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    q = (h @ p["q_proj"]).reshape(b, t, heads, d_n + d_r)
    q_n, q_r = q[..., :d_n], q[..., d_n:]
    down = h @ p["kv_a"]
    c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], hp["eps"])
    k_r = down[..., rank:]                                     # (B, T, d_r): one head
    kv = (c_kv @ p["kv_b"]).reshape(b, t, heads, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = 1.0 / math.sqrt(d_n + d_r)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    positions = jnp.arange(t + pad).reshape(-1, block)
    blocks = lambda a: jnp.moveaxis(
        jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
            b, -1, block, heads, a.shape[-1]), 1, 0)

    @jax.checkpoint
    def queries(qn_block, qr_block, q_pos):
        scores = (jnp.einsum("bqhd,bkhd->bhqk", qn_block, k_n)
                  + jnp.einsum("bqhd,bkd->bhqk", qr_block, k_r)) * scale
        causal = jnp.arange(t)[None, :] <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(causal[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", probs, v)

    out = jax.lax.map(lambda args: queries(*args), (blocks(q_n), blocks(q_r), positions))
    out = jnp.moveaxis(out, 0, 1).reshape(b, t + pad, heads * d_v)[:, :t]
    return out @ p["wo"]


# ------------------------------------------------------------------ #
# the feed-forwards


def gated_unit(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router(p, x, bias, hp):
    """(h (N, C), scores (N, E), chosen (N, E) bool): the k experts with the
    largest score + bias among all E."""
    h = rms_norm(x, p["moe_norm"], hp["eps"]).reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(h @ p["moe_router"])
    # exactly k a token: of equal values the lower expert id first, as a
    # sort breaks ties (two sigmoids do come out equal in float32)
    by_rank = jnp.argsort(-(scores + bias), axis=-1, stable=True)
    rank = jnp.argsort(by_rank, axis=-1)
    return h, scores, rank < hp["num_experts_per_tok"]


def slot_weights(scores, use, hp):
    """(N, E): for every expert the weight it has if it is one of the token's
    experts `use` — the scores renormalised over the chosen, times the scale;
    the bias is not in it."""
    total = jnp.sum(jnp.where(use, scores, 0.0), axis=-1, keepdims=True)
    return hp["routed_scaling_factor"] * scores / (total + 1e-20)


def experts(p, h, weight, hp):
    """Σ_{e held} weight[:, e] · ff_e(h), every held expert on every token;
    `weight` (N, E) is zero where the expert was not chosen, and only the
    held experts' columns are read."""
    first, held = hp["first_expert"], hp["n_routed_experts"]

    @jax.checkpoint
    def one(w_gate, w_up, w_down, w_col):
        return w_col[:, None] * gated_unit(h, w_gate, w_up, w_down)

    def add(total, per_expert):
        return total + one(*per_expert), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], weight[:, first:first + held].T))
    return total


def moe(p, x, bias, use, hp):
    """(the feed-forward's output, (own choice (N, E), the weights of every
    expert under the reference's own choice (N, E))). `use` (N, E) bool, where
    given, takes the place of the router's own choice."""
    h, scores, own = router(p, x, bias, hp)
    taken = own if use is None else use
    weight = jnp.where(taken, slot_weights(scores, taken, hp), 0.0)
    shared = gated_unit(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return ((experts(p, h, weight, hp) + shared).reshape(x.shape),
            (own, slot_weights(scores, own, hp)))


_KDA = ("kda_norm", "kda_wq", "kda_wk", "kda_wv", "kda_conv_q", "kda_conv_k",
        "kda_conv_v", "kda_f_a", "kda_f_b", "kda_A_log", "kda_dt_bias", "kda_beta",
        "kda_g_a", "kda_g_b", "kda_onorm", "kda_wo")
_MLA = ("attn_norm", "q_proj", "kv_a", "kv_a_norm", "kv_b", "wo")
_DENSE = ("mlp_norm", "mlp_gate", "mlp_up", "mlp_down")
_SPARSE = ("moe_norm", "moe_router", "shared_gate", "shared_up", "shared_down",
           "w_gate", "w_up", "w_down")


def _sparse_params(params, index):
    return {k: params[k][index] for k in _SPARSE}


def _layer(p, latent: bool, x, bias, use, hp):
    """One layer: (x, the sparse router's (own, weights) or None). `bias`
    None: its feed-forward is the dense one."""
    def mix(p, x):
        return x + (attention(p, x, hp) if latent else kda(p, x, hp))

    def feed_forward(p, x, bias, use):
        if bias is None:
            h = rms_norm(x, p["mlp_norm"], hp["eps"])
            return x + gated_unit(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), None
        y, more = moe(p, x, bias, use, hp)
        return x + y, more

    # the two sub-blocks recomputed apart: a mixer's and a 9216-wide dense
    # layer's activations do not fit side by side at 16 384 tokens
    return jax.checkpoint(feed_forward)(p, jax.checkpoint(mix)(p, x), bias, use)


def _cross_entropy(x, norm, head, targets, eps):
    """(B, T) negative log likelihood of `targets` under the head on x, in
    blocks of `HEAD_BLOCK` positions so that T x V logits never exist at
    once."""
    b, t, c = x.shape
    block = min(HEAD_BLOCK, t)
    pad = -t % block
    x_blocks = jnp.moveaxis(
        jnp.pad(x, ((0, 0), (0, pad), (0, 0))).reshape(b, -1, block, c), 1, 0)
    target_blocks = jnp.moveaxis(
        jnp.pad(targets, ((0, 0), (0, pad))).reshape(b, -1, block), 1, 0)

    @jax.checkpoint
    def positions(x_block, target_block):
        logp = jax.nn.log_softmax(rms_norm(x_block, norm, eps) @ head, axis=-1)
        return -jnp.take_along_axis(logp, target_block[..., None], axis=-1)[..., 0]

    nll = jax.lax.map(lambda args: positions(*args), (x_blocks, target_blocks))
    return jnp.moveaxis(nll, 0, 1).reshape(b, t + pad)[:, :t]


def forward(params, batch, hp, chosen=None, bias=None):
    """batch {"tokens" (B, T), "labels" (B, T)} -> (per-example loss (B,), per
    sparse layer the router's OWN choice (L, N, E) bool and the weights under
    it).

    `chosen` (L, N, E) bool, where given, takes the place of the routers' own
    choice of experts — the scores stay the reference's. `bias` (L, E): the
    selection bias, zero if not given."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    tokens, labels = batch["tokens"], batch["labels"]
    dense = hp["first_k_dense_replace"]
    x = params["embed"][tokens]                                  # (B, T, C)
    seen = {True: 0, False: 0}
    own_all, weights_all = [], []
    for number in range(1, hp["num_hidden_layers"] + 1):
        latent = number in hp["full_attn_layers"]
        p = {k: params[k][seen[latent]] for k in (_MLA if latent else _KDA)}
        seen[latent] += 1
        if number <= dense:
            p.update({k: params[k][number - 1] for k in _DENSE})
            x, _ = _layer(p, latent, x, None, None, hp)
        else:
            s = number - 1 - dense
            p.update(_sparse_params(params, s))
            x, (own, weights) = _layer(p, latent, x, bias[s],
                                       None if chosen is None else chosen[s], hp)
            own_all.append(own)
            weights_all.append(weights)
    nll = _cross_entropy(x, params["final_norm"], params["head"], labels, hp["eps"])
    return jnp.mean(nll, axis=-1), jnp.stack(own_all), jnp.stack(weights_all)


def routers_on(params, router_inputs, hp, bias=None):
    """Every sparse layer's router on GIVEN inputs (L, B, T, C): (chosen
    (L, N, E) bool, the weights under that choice (L, N, E))."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    chosen, weights = [], []
    for layer in range(hp["moe_layers"]):
        _, scores, own = router(_sparse_params(params, layer), router_inputs[layer],
                                bias[layer], hp)
        chosen.append(own)
        weights.append(slot_weights(scores, own, hp))
    return jnp.stack(chosen), jnp.stack(weights)


def loss_terms(params, batch, hp, chosen=None, bias=None):
    """(the scalar the optimizer minimises, what the program's step reports
    beside it — `loss_ce`, its one term —, (chosen, weights) of every sparse
    layer's own router)."""
    per_example, own, weights = forward(params, batch, hp, chosen, bias)
    mask = batch["mask"].astype(jnp.float32)
    total = jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return total, {"loss_ce": total}, (own, weights)


def loss(params, batch, hp, chosen=None, bias=None):
    total, _, own = loss_terms(params, batch, hp, chosen, bias)
    return total, own


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
