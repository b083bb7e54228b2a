"""The plain reference of configuration `mellum2-12b-a2.5b` (and of any
`mellum` zoo model): forward pass, loss, gradients by `jax.grad` and AdamW, in
straightforward `jax.numpy`, float32. No kernel, no band, no sort-by-expert,
no grouped matmul: attention is the score matrix of a block of queries of a
group of heads against ALL keys under a dense mask made from positions, every
held expert is applied to ALL tokens and masked. The caller runs it under
`jax.default_matmul_precision("highest")`.

Written from the published configuration
(JetBrains/Mellum2-12B-A2.5B-Instruct `config.json`, `model_type: mellum`),
not from the zoo module. It shares one thing with the program: the names and
shapes of the parameters (`model_zoo/transformer/mellum.py` lists them), so
that the program's own initial parameters are the reference's starting point,
and the same share of the deployment: the routed experts `first_expert …
first_expert + num_experts − 1` and the vocabulary slice.

Layer l (kind FULL if (l + 1) % `sliding_period` == 0, else SLIDING) is
`x ← x + attention(rms_norm(x))`, `x ← x + ff(rms_norm(x))`:
- attention: `q = h W_q` (H heads of D), `k = h W_k`, `v = h W_v` (Hkv heads);
  `q ← R_kind(q)`, `k ← R_kind(k)` with R the rotary map (rotate-half: the
  dimension pair (i, i + D/2) of position t turned by the angle t · f_i and
  scaled by a). SLIDING: `f_i = θ^(−2i/D)`, a = 1. FULL (YaRN): with
  `dim(r) = D ln(L0 / (2π r)) / (2 ln θ)`, `low = max(⌊dim(β_fast)⌋, 0)`,
  `high = min(⌈dim(β_slow)⌉, D − 1)`, `ramp_i = clip((i − low) / (high − low),
  0, 1)`: `f_i = θ^(−2i/D) · ((1 − ramp_i) + ramp_i / s)`, a =
  `attention_factor`. `s_ij = q_i · k_j / sqrt(D)`, query head h with
  key-value head h // (H / Hkv); key j is visible to query i iff j ≤ i, and in
  a SLIDING layer iff also j > i − W; softmax over the visible; `· v`; `W_o`.
- ff: `p = softmax(h W_r)` over all E; the k largest; `w_e = p_e / Σ_chosen
  p`; `Σ_{chosen, held} w_e W_down,e(silu(h W_gate,e) ⊙ h W_up,e)`.
- `loss = CE + c · Σ_layers E Σ_e f_e P_e` with f_e the share of the pairs
  sent to e (the choice the step is computed with) and P_e the mean router
  probability of e, over all E.

Departures from a word-for-word transcription, values unchanged: each layer,
each block of `QUERY_BLOCK` queries of one key-value head's group, each
expert's body and each block of `HEAD_BLOCK` positions of the head with its
cross entropy is recomputed in the backward pass (`jax.checkpoint`), so that
16 384 tokens fit on one chip (32 heads' 16 384² float32 scores are 34 GB).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
LOAD_BALANCE_COEF = 0.01
# where the program counts the passes its held dispatch ran, per layer
PASSES = ("router_state", "held_passes")
QUERY_BLOCK = 512
HEAD_BLOCK = 1024

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch. A limit sits
# between two readings (my chip runs, PR 36; all in PERF.md §6): the largest
# the program gave over its seeds (SOUND: eight runs, seeds 2147484301-306,
# 3000000304, 2147484307, 2147484501) and what a CONTROL gives — the program
# with a part the configuration states float32 computed in bfloat16
# (`rehearse/departures_mellum.py::CONTROLS`: the router's logits; the
# residual stream), which has to read `correct: false` by one of these limits,
# not by each. Where a control moves a figure by 1.4 times the largest sound
# reading or more, the limit is the geometric middle of the two; where it
# moves it less, three times the sound reading, and never wider than
# `no_wider_than`.
def _between(sound: float, control: float, no_wider_than: float) -> float:
    if control >= 1.4 * sound:
        return (sound * control) ** 0.5
    return min(3.0 * sound, no_wider_than)


# AdamW's first moment is linear in the gradients, and every matmul of the
# program rounds its operands to bfloat16: per leaf (the largest sound
# reading, the control that moves it most — for `moe_norm`, the router and
# the experts the one that moves it LEAST, so that either control fails
# there; the router's own leaf spreads 0.0040-0.0059 over the seeds and both
# controls move it under 1.4 times that). The residual
# stream in bfloat16 reads 0.0032 in the head (sound 0.00095-0.00101), 0.0023
# in the final norm and 0.020-0.021 in the worst judged expert (sound
# 0.0067-0.0097); the bfloat16 router 0.0228-0.0234 there. What the departures
# read: the YaRN table on the sliding layers 0.74 (wk), 0.72 (wq), 0.18
# (attn_norm); the plain table on the full layer 0.50 / 0.53 / 0.11;
# `attention_factor` left out 0.43 / 0.39 / 0.09; weights not renormalised
# 0.44 (router), 0.53 (moe_norm), 0.64 (every expert)
_MU_READINGS = {
    "attn_norm": (0.00327, 0.00374), "embed": (0.00401, 0.00558),
    "final_norm": (0.00122, 0.00226), "head": (0.00101, 0.00322),
    "moe_norm": (0.00563, 0.00955), "moe_router": (0.00586, 0.00795),
    "wk": (0.00576, 0.00759), "wo": (0.00314, 0.00391), "wq": (0.00607, 0.00824),
    "wv": (0.00305, 0.00384),
}
# The parameter update after the steps: AdamW's first steps are ≈ lr · sign(g),
# so an element whose gradient is near zero changes sign under rounding and
# counts twice; the norms' weights, of size one, do not move at all in two
# steps at the warm-up's first step sizes and read 0 on both sides. Sound
# readings hardly vary here (wq 0.0386-0.0396 over eight seeds), which is what
# lets `wq` see THE WINDOW OFF BY ONE KEY: 1023 or 1025 keys in place of 1024
# read 0.0597 and 0.0598 there, 0.0585 and 0.0586 at a second seed (wk 0.047
# against a sound 0.033-0.036) and move nothing else. The YaRN table on the sliding layers reads 0.68 (wq), 0.55
# (wk); the plain table on the full layer 0.44 / 0.31
_UPDATE_READINGS = {
    "embed": (0.0251, 0.0324), "head": (0.0145, 0.0243),
    "moe_router": (0.0355, 0.0592), "wk": (0.0364, 0.0473),
    "wo": (0.0256, 0.0301), "wq": (0.0396, 0.0597), "wv": (0.0287, 0.0315),
}
TOLERANCES = {
    # the losses at seeded weights, per-example means over 16 384 tokens: the
    # bfloat16 matmul errors of the single tokens average out. Sound: the sum
    # and the cross entropy 1.3e-6 - 5.3e-6, the auxiliary term 3.7e-6 -
    # 1.5e-5: four-fold over the seeds, so a limit between that and the
    # controls' 9.8e-6 / 2.3e-5 (the residual stream in bfloat16; the router
    # 2.3e-6 / 6e-6) would fail a sound run: three times the largest sound
    # reading. `attention_factor` left out reads 5.4e-5, the plain table on
    # the full layer 4.8e-5, the YaRN table on the sliding layers 4.4e-5 (and
    # 2.5e-4 in the auxiliary term)
    "loss_rel": 1.6e-5,
    "loss_ce_rel": 1.6e-5,
    "loss_aux_rel": 4.6e-5,
    # The program's router against this one ON THE SAME INPUT (the residual
    # stream the program's router saw), both float32 at the highest matmul
    # precision, at BOTH steps. Sound: every pair agrees (1.0 in all eight
    # runs), the weights' median error at most 9.0e-8. A bfloat16 router reads
    # 0.99656 and 2.3e-3; weights not renormalised 0.59 in the weights
    "router_same_input_agreement_min": 0.9995,
    "router_weight_rel_median": 1e-5,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a residual stream without the program's bfloat16 rounding
    # upstream: a pair in 7000 flips at a near-tie (sound: 0.99984-0.99987).
    # The reference then computes with the program's choice. The bfloat16
    # router reads 0.99654, the residual stream in bfloat16 0.99737, the YaRN
    # table on the sliding layers 0.98015: the geometric middle of the
    # disagreeing shares (1.6e-4, 2.6e-3)
    "routing_agreement_min": 0.99935,
    # `default` is for a leaf the tables do not name (a norm's weight in
    # `update_rel_l2`: 0 on both sides)
    "mu_rel_l2": {"default": 3e-2,
                  # the worst judged expert of `w_gate`, `w_up`, `w_down`
                  # (sound at most 0.00966; the weaker control 0.0200)
                  "experts": _between(0.00966, 0.0200, 3e-2),
                  **{leaf: _between(sound, control, 3e-2)
                     for leaf, (sound, control) in _MU_READINGS.items()}},
    "update_rel_l2": {"default": 2.5e-1,
                      # sound at most 0.0495, the weaker control 0.0641
                      "experts": _between(0.0495, 0.0641, 2.5e-1),
                      **{leaf: _between(sound, control, 2.5e-1)
                         for leaf, (sound, control) in _UPDATE_READINGS.items()}},
}
# An expert's slice of the experts' leaves is judged apart only if it got at
# least this many (token, slot) pairs over the compared steps and layers;
# those with fewer are pooled and judged as one (PR 30's derivation). Here
# every held expert got 11 300-13 100 pairs over the two steps and four
# layers at all eight seeds, so all sixteen are judged apart.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `n_routed_experts` is what this
    chip holds (`num_experts` of the configuration), `num_experts` what the
    router chooses among, as the check and the drivers read them."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "sliding_window", "num_experts_per_tok",
            "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    hp["n_routed_experts"] = int(model_params["num_experts"])
    hp["num_experts"] = int(model_params.get("router_experts", 0)) or hp["n_routed_experts"]
    hp["first_expert"] = int(model_params.get("first_expert", 0))
    hp["sliding_period"] = int(model_params.get("sliding_period", 4))
    hp["rope_theta"] = float(model_params.get("rope_theta", 500000.0))
    hp["rope_factor"] = float(model_params.get("rope_factor", 16.0))
    hp["original_max_position_embeddings"] = int(
        model_params.get("original_max_position_embeddings", 8192))
    hp["beta_fast"] = float(model_params.get("beta_fast", 32.0))
    hp["beta_slow"] = float(model_params.get("beta_slow", 1.0))
    hp["attention_factor"] = float(model_params.get("attention_factor", 1.2772588722239782))
    hp["load_balance_coef"] = float(model_params.get("load_balance_coef", LOAD_BALANCE_COEF))
    hp["eps"] = float(model_params.get("rms_norm_eps", 1e-6))
    hp["moe_layers"] = hp["num_hidden_layers"]
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def is_full(layer: int, hp) -> bool:
    return (layer + 1) % hp["sliding_period"] == 0


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def frequencies(full: bool, hp):
    """((D/2,) the angle a position turns dimension pair i by, the factor on
    cos and sin) of a layer's kind."""
    d, theta = hp["head_dim"], hp["rope_theta"]
    i = jnp.arange(d // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * i / d)
    if not full:
        return plain, 1.0
    dim = lambda turns: d * math.log(hp["original_max_position_embeddings"]
                                     / (2.0 * math.pi * turns)) / (2.0 * math.log(theta))
    low = max(math.floor(dim(hp["beta_fast"])), 0)
    high = min(math.ceil(dim(hp["beta_slow"])), d - 1)
    ramp = jnp.clip((i - low) / (high - low), 0.0, 1.0)
    return plain * ((1.0 - ramp) + ramp / hp["rope_factor"]), hp["attention_factor"]


def rotary(x, full: bool, hp):
    """x (B, T, heads, D) with its positions' rotation of the layer's kind."""
    t, d = x.shape[1], x.shape[-1]
    freq, factor = frequencies(full, hp)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :])[None, :, None, :]
    cos, sin = factor * jnp.cos(angle), factor * jnp.sin(angle)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def attention(p, x, full: bool, hp):
    b, t, _ = x.shape
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    group = heads // kv_heads
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    q = rotary((h @ p["wq"]).reshape(b, t, heads, d), full, hp)
    k = rotary((h @ p["wk"]).reshape(b, t, kv_heads, d), full, hp)
    v = (h @ p["wv"]).reshape(b, t, kv_heads, d)
    block = min(QUERY_BLOCK, t)
    pad = -t % block
    positions = jnp.arange(t + pad).reshape(-1, block)
    # (kv head, query block, B, block, group, D): one key-value head's group
    # of query heads, one block of queries at a time
    q_blocks = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0))).reshape(
        b, -1, block, kv_heads, group, d).transpose(3, 1, 0, 2, 4, 5)

    @jax.checkpoint
    def queries(q_block, q_pos, k_head, v_head):
        """q_block (B, block, group, D) against k_head, v_head (B, T, D)."""
        scores = jnp.einsum("bqgd,bkd->bgqk", q_block, k_head) / math.sqrt(d)
        key_pos = jnp.arange(t)[None, :]
        visible = key_pos <= q_pos[:, None]
        if not full:
            visible &= key_pos > q_pos[:, None] - hp["sliding_window"]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", probs, v_head)

    def one_head(args):
        q_head, k_head, v_head = args       # (blocks, B, block, group, D), (B, T, D) x 2
        return jax.lax.map(lambda qp: queries(qp[0], qp[1], k_head, v_head),
                           (q_head, positions))

    out = jax.lax.map(one_head, (q_blocks, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    # (kv head, blocks, B, block, group, D) -> (B, T, heads · D)
    out = out.transpose(2, 1, 3, 0, 4, 5).reshape(b, t + pad, heads * d)[:, :t]
    return out @ p["wo"]


def router(p, x, hp):
    """(h (N, C), probs (N, E), chosen (N, E) bool): the k experts with the
    largest probability among all E."""
    h = rms_norm(x, p["moe_norm"], hp["eps"]).reshape(-1, x.shape[-1])
    probs = jax.nn.softmax(h @ p["moe_router"], axis=-1)
    # exactly k a token: of equal values the lower expert id first
    by_rank = jnp.argsort(-probs, axis=-1, stable=True)
    rank = jnp.argsort(by_rank, axis=-1)
    return h, probs, rank < hp["num_experts_per_tok"]


def slot_weights(probs, use):
    """(N, E): for every expert the weight it has if it is one of the token's
    experts `use` — the probabilities renormalised over the chosen."""
    return probs / jnp.sum(jnp.where(use, probs, 0.0), axis=-1, keepdims=True)


def experts(p, h, weight, hp):
    """Σ_{e held} weight[:, e] · ff_e(h), every held expert on every token;
    `weight` (N, E) is zero where the expert was not chosen, and only the
    held experts' columns are read."""
    first, held = hp["first_expert"], hp["n_routed_experts"]

    @jax.checkpoint
    def one(w_gate, w_up, w_down, w_col):
        return w_col[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

    def add(total, per_expert):
        return total + one(*per_expert), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], weight[:, first:first + held].T))
    return total


def moe(p, x, use, hp):
    """(the feed-forward's output, the load-balance term, own choice (N, E),
    the weights of every expert under the reference's own choice (N, E)).
    `use` (N, E) bool, where given, takes the place of the router's own
    choice, in the weights and in the load-balance term's counts alike."""
    h, probs, own = router(p, x, hp)
    taken = own if use is None else use
    weight = jnp.where(taken, slot_weights(probs, taken), 0.0)
    share = jnp.mean(taken.astype(jnp.float32), axis=0) / hp["num_experts_per_tok"]
    balance = hp["num_experts"] * jnp.sum(share * jnp.mean(probs, axis=0))
    return (experts(p, h, weight, hp).reshape(x.shape), balance, own,
            slot_weights(probs, own))


_LAYER = ("attn_norm", "wq", "wk", "wv", "wo",
          "moe_norm", "moe_router", "w_gate", "w_up", "w_down")


def _layer(params, index, x, use, hp):
    p = {k: params[k][index] for k in _LAYER}
    full = is_full(index, hp)

    def run(p, x, use):
        x = x + attention(p, x, full, hp)
        y, balance, own, weights = moe(p, x, use, hp)
        return x + y, balance, own, weights

    return jax.checkpoint(run)(p, x, use)


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


def forward(params, batch, hp, chosen=None):
    """batch {"tokens" (B, T), "labels" (B, T)} -> (per-example cross entropy
    (B,), the load-balance terms' sum, per layer the router's OWN choice
    (L, N, E) bool and the weights under it).

    `chosen` (L, N, E) bool, where given, takes the place of the routers' own
    choice of experts — the probabilities stay the reference's."""
    x = params["embed"][batch["tokens"]]
    balance_all, own_all, weights_all = [], [], []
    for i in range(hp["num_hidden_layers"]):
        x, balance, own, weights = _layer(
            params, i, x, None if chosen is None else chosen[i], hp)
        balance_all.append(balance)
        own_all.append(own)
        weights_all.append(weights)
    ce = jnp.mean(_cross_entropy(x, params["final_norm"], params["head"],
                                 batch["labels"], hp["eps"]), axis=-1)
    return ce, sum(balance_all), jnp.stack(own_all), jnp.stack(weights_all)


def routers_on(params, router_inputs, hp):
    """Every layer's router on GIVEN residual streams (L, B, T, C): (chosen
    (L, N, E) bool, the weights under that choice (L, N, E))."""
    chosen, weights = [], []
    for layer in range(hp["num_hidden_layers"]):
        _, probs, own = router({k: params[k][layer] for k in ("moe_norm", "moe_router")},
                               router_inputs[layer], hp)
        chosen.append(own)
        weights.append(slot_weights(probs, own))
    return jnp.stack(chosen), jnp.stack(weights)


def loss_terms(params, batch, hp, chosen=None):
    """(the scalar the optimizer minimises, {"loss_ce", "loss_aux"} apart,
    (chosen, weights) of every layer's own router)."""
    ce, balance, own, weights = forward(params, batch, hp, chosen)
    mask = batch["mask"].astype(jnp.float32)
    terms = {"loss_ce": jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0),
             "loss_aux": hp["load_balance_coef"] * balance}
    return terms["loss_ce"] + terms["loss_aux"], terms, (own, weights)


def loss(params, batch, hp, chosen=None):
    """(the scalar the optimizer minimises, (chosen, weights) of every layer's
    own router)."""
    total, _, own = loss_terms(params, batch, hp, chosen)
    return total, own


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
