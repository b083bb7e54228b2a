"""The plain reference of configuration `xing4.0-29b-a4b` (and of any `xing4`
zoo model): forward pass, loss, gradients by `jax.grad`, AdamW and the
routers' bias update, in straightforward `jax.numpy`, float32. No kernel, no
recomputation policy, no sort-by-expert, no grouped matmul, no key widened to
every head, no stream-major layout: a token's state is an (n, C) matrix, the
Sinkhorn rounds are a Python loop, the scores are the sum of a per-head
product over the non-rotary part and a product with the ONE rotary key, every
held expert is applied to ALL tokens and masked, attention is the score matrix
of a block of queries against all keys. The caller runs it under
`jax.default_matmul_precision("highest")`.

Written from the published equations, not from the zoo module:
manifold-constrained hyper-connections arXiv:2512.24880 (over hyper-
connections arXiv:2409.19606) for the streams; XingChen-AGI/Xing4.0-29B-A4B
`config.json` (`model_type: xing4_0`), whose other keys are DeepSeek-V3's:
latent attention arXiv:2405.04434 §2.1 / arXiv:2412.19437 §2.1.1, the sigmoid
router with a selection bias §2.1.2, YaRN arXiv:2309.00071 §3 as DeepSeek's
code applies it. It shares one thing with the program: the names and shapes of
the parameters (`model_zoo/transformer/xing4.py` lists them; `hc_phi`,
`hc_alpha`, `hc_b` carry sub-block 2·layer for attention and 2·layer + 1 for
the feed-forward), so that the program's own initial parameters are the
reference's starting point, and the same share of the deployment: the routed
experts `first_expert … first_expert + n_routed_experts − 1` and the
vocabulary slice.

With X (n, C) a token's state, n = `hc_mult`, per sub-block f:
- x~ = vec(X) / sqrt(mean(vec(X)²) + hc_eps), vec stream by stream;
  [H~_pre | H~_post | vec(H~_res)] = alpha ⊙ (x~ phi) + b, alpha one scalar
  for each of the three groups, H~_res (n, n) row-major;
- H_pre = sigmoid(H~_pre), H_post = 2 sigmoid(H~_post), H_res = 20 rounds on
  exp(clip(H~_res, −30, 30)) of: each row / (its sum + hc_eps), then each
  column / (its sum + hc_eps);
- h = H_pre X (C); y = f(h); X' = H_res X + H_post ⊗ y.
Entry X = n copies of the embedding; exit rms_norm(sum of the n streams).
- attention, on rms_norm(h): `c_q = rms_norm(h W_qa)`, `[q_n | q_r] = c_q W_qb`
  per head; `[c_kv | k_r] = h W_kva`, `c_kv ← rms_norm(c_kv)`, `[k_n | v] =
  c_kv W_kvb` per head (v the LAST `v_head_dim` columns of a head's slice);
  `s = m² (q_n · k_n + R(q_r) · R(k_r)) / sqrt(d_n + d_r)`, R the rotary map
  (rotate-half) at YaRN's blended frequencies, m = 0.1 ln(factor) + 1; causal
  softmax; `· v`; `W_o`.
- dense ff: `W_down(silu(h W_gate) ⊙ h W_up)` on rms_norm(h).
- sparse ff: `s = sigmoid(h W_r)`; the k experts with the largest `s + b`;
  `w_e = scale · s_e / (Σ_chosen s + 1e-20)`; `Σ_{chosen, held} w_e ff_e(h) +
  ff_shared(h)`; after the step `b_e ← b_e + u · sign(mean load − load_e)`.

Departures from a word-for-word transcription, values unchanged: each layer,
each block of `QUERY_BLOCK` queries, each expert's body and each block of
`HEAD_BLOCK` positions of the head with its cross entropy is recomputed in the
backward pass (`jax.checkpoint`), so that 4096 tokens of four streams fit on
one chip beside the float32 parameters.
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


# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch. A limit sits
# between two readings (my chip runs, PR 48; all in PERF.md §6): the largest
# the program gave over its seeds (SOUND, from settled routers) and what the
# CONTROL gives — the program with a part the configuration states float32
# computed in bfloat16 (`rehearse/departures_xing4.py::CONTROLS`: the Sinkhorn
# rounds, the router), which has to read `correct: false` by one of these
# limits, not by each. Where the control hardly moves a figure (under 1.4
# times the largest sound reading) the limit is three times that reading, and
# never wider than `no_wider_than`. The streams are stored bfloat16 (the
# configuration's `precision` says why): the sound readings are that
# program's — six runs (seeds 2147480021, 2147480023, 2147480035,
# 2147481201, 2147481211, 2147481219), the three last of them with the
# residual's figure.
def _between(sound: float, control: float, no_wider_than: float) -> float:
    if control >= 1.4 * sound:
        return (sound * control) ** 0.5
    return min(3.0 * sound, no_wider_than)


TOLERANCES = {
    # the loss at seeded weights, a per-example mean over 4096 tokens: the
    # bfloat16 errors of the single tokens average out (sound at most 1.83e-4);
    # the weights not renormalised read 2.0e-3, H_post without its 2 2.4e-3,
    # the softmax factor left out 7.5e-3, v at the wrong columns 9.8e-3: the
    # geometric middle of the largest sound reading and the nearest departure
    "loss_rel": _between(1.83e-4, 2.0e-3, 1e-3),
    "loss_ce_rel": _between(1.83e-4, 2.0e-3, 1e-3),
    # WHAT HOLDS THE SINKHORN ROUNDS, their number and their precision: the
    # largest |row or column sum − 1| the rounds leave in any H_res of the
    # step, as the program's step reports it, against this reference's. From
    # H~_res = 4 on the diagonal the rounds converge slowly (the second
    # singular value of a nearly diagonal H_res is near one), so after twenty
    # the residual is 4.1-4.7e-4 — a smooth function of the coefficients, not
    # rounding noise — and the program's is the reference's to 5.6e-3 (sound:
    # 1.5e-3, 4.5e-3, 5.6e-3); ten rounds leave 1.8-2.0e-3 and
    # read 3.33, 3.41; the rounds in bfloat16 cannot get under bfloat16's own
    # 2^-8 (3.78e-3) and read 7.76, 8.20; H_res the identity reads exactly
    # one. The limit was written before those readings (PERF.md §6, PR 48):
    # 18 times the sound one, a thirty-third of the nearest departure's
    "mhc_sinkhorn_residual_rel": 0.1,
    # The program's router against this one ON THE SAME INPUT (the mixed
    # stream the program's router saw), both float32 at the highest matmul
    # precision, at BOTH steps. Sound: 0.99947-0.99995 agree, the weights'
    # median error 0. A bfloat16 router reads 0.99629 and 2.5e-4; the weights
    # not renormalised 2.3 in the weights. Limits at the geometric middle of
    # the disagreeing shares (5.3e-4, 3.7e-3) and well under the weights' error
    "router_same_input_agreement_min": 1.0 - _between(5.3e-4, 3.7e-3, 1.0),
    "router_weight_rel_median": 5e-6,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a stream without the program's bfloat16 rounding upstream:
    # from settled routers a pair in 60-70 flips at a near-tie (sound:
    # 0.98335-0.98569 in six runs; no control moves it). Limit at a little over twice the
    # sound disagreeing share (0.0167): the weights not renormalised read
    # 0.806, H_post without its 2 0.649
    "routing_agreement_min": 0.96,
    # AdamW's first moment, linear in the gradients. Sound: every matrix and
    # norm at most 0.0207 (q_a_norm), hc_b 0.0212, hc_phi 0.0271; no control
    # moves any of them (at most 1.1 times), so three times the largest
    # matrix's. H_post without its 2 reads 0.16-0.36 in every leaf
    "mu_rel_l2": {"default": 3.0 * 0.0207,
                  # 30 gates: sound up to 0.037, H_post without its 2 0.31
                  "hc_alpha": _between(0.037, 0.31, 0.2),
                  # the router's gradient comes through the renormalised
                  # weights alone: sound 0.043-0.081, controls at most 0.095
                  "moe_router": _between(0.081, 0.095, 0.2),
                  # the worst judged expert: sound at most 0.0726, no control
                  # over it; the weights not renormalised 1.9
                  "experts": _between(0.0726, 0.0726, 0.15)},
    # the parameter update after the steps: AdamW's first steps are
    # ≈ lr · sign(g), so an element whose gradient is near zero changes sign
    # under rounding and counts twice. Sound: the matrices 0.058-0.132 (embed),
    # the router 0.237-0.281, the worst judged expert 0.209-0.241: for those
    # the geometric middle of the sound reading and the nearest departure's,
    # 0.25 for the rest.
    "update_rel_l2": {
        "default": 2.5e-1,
        "moe_router": _between(0.281, 0.797, 1.0),      # H_post without its 2
        "experts": _between(0.241, 0.707, 1.0),         # the weights not renormalised
        # The hyper-connections' leaves: 240 biases, 30 gates and 3 440 640
        # entries of phi whose H_res parts move by the sign of gradients that
        # cancel almost wholly between four nearly equal streams — a few
        # entries' signs turn from run to run (sound: hc_b 0.10-0.33, hc_phi
        # 0.24-0.28), and a precision control moves them no further than
        # another seed does (the rounds in bfloat16 0.56-0.67 and 0.27-0.31,
        # ten rounds 0.24-0.37 and 0.35-0.38). These limits hold the GROSS
        # departures only (H_res the identity 0.80 in hc_b, v at the wrong
        # columns over 1 in all three); what holds the rounds is
        # `mhc_sinkhorn_residual_rel`. About twice the largest sound reading
        "hc_b": 7.5e-1,
        "hc_phi": 5.0e-1,
        # 30 gates, each moved by about lr · sign(g): ONE turned sign reads
        # 0.37, two 0.52, and sound runs read 0.21-0.50. The figure can hold
        # no more than "most gates move the right way": a quarter of them
        # turned reads 1.0 (v at the wrong columns 1.31)
        "hc_alpha": 1.0},
    # the share of the selection bias's entries that differ from the
    # reference's after the steps: an expert within a pair or two of the mean
    # load turns its sign on one flipped pair (sound: 2-12 of 256). Three
    # times the largest sound reading; a missing or mis-signed update reads
    # 0.5-1 (GLM's cell, the same rule)
    "bias_entries_off_share": 0.15,
}
# The experts' leaves, expert by expert, all its layers together: an expert is
# judged apart only if it got at least this many (token, slot) pairs over the
# compared steps and layers; those with fewer are POOLED and judged as one.
# From settled routers every held expert got 1389-2380 pairs over the two
# steps and four sparse layers at every seed: all eight are judged apart.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `num_experts` is what the router
    chooses among."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
            "qk_rope_head_dim", "v_head_dim", "n_routed_experts", "num_experts_per_tok",
            "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    given = lambda key, default, kind=float: kind(model_params.get(key, default))
    if given("num_nextn_predict_layers", 0, int):
        raise ValueError("the multi-token-prediction module has no reference: how it "
                         "joins the streams is not in the published config")
    hp["first_k_dense_replace"] = given("first_k_dense_replace", 2, int)
    hp["first_expert"] = given("first_expert", 0, int)
    hp["num_experts"] = given("router_experts", 0, int) or hp["n_routed_experts"]
    hp["routed_scaling_factor"] = given("routed_scaling_factor", 2.0)
    hp["eps"] = given("rms_norm_eps", 1e-6)
    hp["rope_theta"] = given("rope_theta", 1e4)
    hp["rope_factor"] = given("rope_factor", 64.0)
    hp["original_max_position_embeddings"] = given(
        "original_max_position_embeddings", 4096, int)
    hp["beta_fast"], hp["beta_slow"] = given("beta_fast", 32.0), given("beta_slow", 1.0)
    hp["mscale_all_dim"] = given("mscale_all_dim", 1.0)
    hp["hc_mult"] = given("hc_mult", 4, int)
    hp["hc_sinkhorn_iters"] = given("hc_sinkhorn_iters", 20, int)
    hp["hc_eps"] = given("hc_eps", 1e-6)
    hp["clamp"] = (given("mhc_h_res_clamp_min", -30.0), given("mhc_h_res_clamp_max", 30.0))
    hp["moe_layers"] = hp["num_hidden_layers"] - hp["first_k_dense_replace"]
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


# ------------------------------------------------------------------ #
# YaRN


def yarn_frequencies(hp):
    """(d_r / 2,) the rotary frequencies: dimension i keeps theta^(−2i/d) if it
    turns more than `beta_fast` times over the original context, takes
    theta^(−2i/d) / factor if fewer than `beta_slow`, and a linear blend
    between the two dimensions where those counts fall."""
    d, theta = hp["qk_rope_head_dim"], hp["rope_theta"]
    plain = [theta ** (-2.0 * i / d) for i in range(d // 2)]

    def dimension_turning(times):
        return d * math.log(hp["original_max_position_embeddings"]
                            / (times * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(dimension_turning(hp["beta_fast"])), 0)
    high = min(math.ceil(dimension_turning(hp["beta_slow"])), d - 1)
    span = max(high - low, 1e-3)
    out = []
    for i, f in enumerate(plain):
        slow = min(max((i - low) / span, 0.0), 1.0)       # 0: keeps its frequency
        out.append((1.0 - slow) * f + slow * f / hp["rope_factor"])
    return jnp.asarray(out, jnp.float32)


def softmax_factor(hp) -> float:
    """m², m = 0.1 · mscale_all_dim · ln(factor) + 1."""
    if hp["rope_factor"] <= 1:
        return 1.0
    return (0.1 * hp["mscale_all_dim"] * math.log(hp["rope_factor"]) + 1.0) ** 2


def rotary(x, hp):
    """x (B, T, ..., d_r): dimension pair (i, i + d_r/2) of position t turned
    by the angle t · frequency_i."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * yarn_frequencies(hp)[None, :]
    angle = angle.reshape((1, t) + (1,) * (x.ndim - 3) + (d // 2,))
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


# ------------------------------------------------------------------ #
# the streams


def sinkhorn(m, rounds: int, eps: float):
    """m (..., n, n) positive."""
    for _ in range(rounds):
        m = m / (jnp.sum(m, axis=-1, keepdims=True) + eps)      # rows
        m = m / (jnp.sum(m, axis=-2, keepdims=True) + eps)      # columns
    return m


def connections(p, state, hp):
    """state (B, T, n, C) -> (H_pre (B, T, n), H_post (B, T, n), H_res
    (B, T, n, n)). p: `hc_phi` (n·C, 2n + n²), `hc_alpha` (3,), `hc_b`."""
    b, t, n, c = state.shape
    flat = state.reshape(b, t, n * c)
    normed = flat / jnp.sqrt(jnp.mean(jnp.square(flat), axis=-1, keepdims=True)
                             + hp["hc_eps"])
    raw = normed @ p["hc_phi"]
    pre = p["hc_alpha"][0] * raw[..., :n] + p["hc_b"][:n]
    post = p["hc_alpha"][1] * raw[..., n:2 * n] + p["hc_b"][n:2 * n]
    res = (p["hc_alpha"][2] * raw[..., 2 * n:] + p["hc_b"][2 * n:]).reshape(b, t, n, n)
    return (jax.nn.sigmoid(pre), 2.0 * jax.nn.sigmoid(post),
            sinkhorn(jnp.exp(jnp.clip(res, *hp["clamp"])), hp["hc_sinkhorn_iters"],
                     hp["hc_eps"]))


def sinkhorn_residual(h_res):
    """h_res (B, T, n, n) -> (B,): how far from doubly stochastic the rounds
    left the worst token's matrix — the largest |row or column sum − 1|."""
    rows = jnp.abs(jnp.sum(h_res, axis=-1) - 1.0)
    columns = jnp.abs(jnp.sum(h_res, axis=-2) - 1.0)
    return jnp.max(jnp.maximum(rows, columns), axis=(1, 2))


def connected(p, state, f, hp):
    """One sub-block: (the new state, whatever f returns beside its output,
    `sinkhorn_residual` of its H_res)."""
    h_pre, h_post, h_res = connections(p, state, hp)
    y, more = f(jnp.einsum("btn,btnc->btc", h_pre, state))
    return (jnp.einsum("btij,btjc->btic", h_res, state)
            + h_post[..., None] * y[:, :, None, :]), more, sinkhorn_residual(h_res)


# ------------------------------------------------------------------ #
# the sub-blocks


def attention(p, x, hp):
    b, t, _ = x.shape
    heads, d_n, d_r, d_v = (hp["num_attention_heads"], hp["qk_nope_head_dim"],
                            hp["qk_rope_head_dim"], hp["v_head_dim"])
    rank = hp["kv_lora_rank"]
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    c_q = rms_norm(h @ p["q_a"], p["q_a_norm"], hp["eps"])
    q = (c_q @ p["q_b"]).reshape(b, t, heads, d_n + d_r)
    q_n, q_r = q[..., :d_n], rotary(q[..., d_n:], hp)
    down = h @ p["kv_a"]
    c_kv = rms_norm(down[..., :rank], p["kv_a_norm"], hp["eps"])
    k_r = rotary(down[..., rank:], hp)                         # (B, T, d_r): one head
    kv = (c_kv @ p["kv_b"]).reshape(b, t, heads, d_n + d_v)
    k_n, v = kv[..., :d_n], kv[..., d_n:]
    scale = softmax_factor(hp) / math.sqrt(d_n + d_r)
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


_ATTN = ("attn_norm", "q_a", "q_a_norm", "q_b", "kv_a", "kv_a_norm", "kv_b", "wo")
_DENSE = ("mlp_norm", "mlp_gate", "mlp_up", "mlp_down")
_SPARSE = ("moe_norm", "moe_router", "shared_gate", "shared_up", "shared_down",
           "w_gate", "w_up", "w_down")
_HC = ("hc_phi", "hc_alpha", "hc_b")


def _sparse_params(params, index):
    return {k: params[k][index] for k in _SPARSE}


def _layer(params, index, ff_keys, ff_index, state, bias, use, hp):
    """Layer `index`: (the new state, the sparse router's (own, weights) or
    None, the larger `sinkhorn_residual` of its two sub-blocks). `bias` None:
    a dense layer."""
    p = {**{k: params[k][index] for k in _ATTN},
         **{k: params[k][ff_index] for k in ff_keys}}
    hc = lambda sub: {k: params[k][2 * index + sub] for k in _HC}

    def run(p, hc_attention, hc_ff, state, bias, use):
        state, _, first = connected(hc_attention, state,
                                    lambda h: (attention(p, h, hp), None), hp)
        if bias is None:
            ff = lambda h: (gated_unit(rms_norm(h, p["mlp_norm"], hp["eps"]),
                                       p["mlp_gate"], p["mlp_up"], p["mlp_down"]), None)
        else:
            ff = lambda h: moe(p, h, bias, use, hp)
        state, more, second = connected(hc_ff, state, ff, hp)
        return state, more, jnp.maximum(first, second)

    return jax.checkpoint(run)(p, hc(0), hc(1), state, bias, use)


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
    it, per example the largest `sinkhorn_residual` of any sub-block).

    `chosen` (L, N, E) bool, where given, takes the place of the routers' own
    choice of experts — the scores stay the reference's. `bias` (L, E): the
    selection bias, zero if not given."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    tokens, labels = batch["tokens"], batch["labels"]
    dense = hp["first_k_dense_replace"]
    embedded = params["embed"][tokens]                                  # (B, T, C)
    state = jnp.repeat(embedded[:, :, None, :], hp["hc_mult"], axis=2)  # every stream
    own_all, weights_all, residuals = [], [], []
    for i in range(hp["num_hidden_layers"]):
        if i < dense:
            state, _, residual = _layer(params, i, _DENSE, i, state, None, None, hp)
        else:
            s = i - dense
            state, (own, weights), residual = _layer(
                params, i, _SPARSE, s, state, bias[s],
                None if chosen is None else chosen[s], hp)
            own_all.append(own)
            weights_all.append(weights)
        residuals.append(residual)
    nll = _cross_entropy(jnp.sum(state, axis=2), params["final_norm"], params["head"],
                         labels, hp["eps"])
    return (jnp.mean(nll, axis=-1), jnp.stack(own_all), jnp.stack(weights_all),
            jnp.max(jnp.stack(residuals), axis=0))


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
    beside it — `loss_ce`, its one term, and `mhc_sinkhorn_residual`, which is
    no term of it: the examples' mean of the largest |row or column sum − 1|
    the twenty rounds left in any sub-block's H_res —, (chosen, weights) of
    every sparse layer's own router)."""
    per_example, own, weights, residual = forward(params, batch, hp, chosen, bias)
    mask = batch["mask"].astype(jnp.float32)
    mean = lambda each: jnp.sum(each * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = mean(per_example)
    return (total, {"loss_ce": total,
                    "mhc_sinkhorn_residual": jax.lax.stop_gradient(mean(residual))},
            (own, weights))


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
