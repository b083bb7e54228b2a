"""The plain reference of configuration `qwen3-next-80b-a3b` (and of any
`qwen3_next` zoo model): forward pass, loss, gradients by `jax.grad` and AdamW,
in straightforward `jax.numpy`, float32. No kernel, no chunk algebra, no
triangular inverse, no sort-by-expert, no grouped matmul: the delta rule is
the recurrence as published, ONE TOKEN AT A TIME, every value head with the
key head it reads; the convolution is four shifted sums; every held expert is
applied to ALL tokens and masked; attention is the score matrix of a block of
queries of a group of heads against all keys. The caller runs it under
`jax.default_matmul_precision("highest")`.

Written from the equations of ISSUE 64 (Gated DeltaNet, arXiv:2412.06464, as
Qwen/Qwen3-Next-80B-A3B-Instruct `config.json`, `model_type: qwen3_next`,
sizes it), not from the zoo module. It shares one thing with the program: the
names and shapes of the parameters (`model_zoo/transformer/qwen3_next.py`
lists them), so that the program's own initial parameters are the reference's
starting point, and the same share of the deployment: the routed experts
`first_expert … first_expert + num_experts − 1` and the vocabulary slice.
Every *assumed* item is the configuration file's
(`benchmark/configs/qwen3-next-80b-a3b.json`, `assumed`).

`norm(x; w) = x / sqrt(mean(x²) + eps) ⊙ (1 + w)`. The layer of published
index i (attention iff (i + 1) % `full_attention_interval` == 0, or as
`layer_types` lists): `x ← x + mixer(norm(x))`, `x ← x + ff(norm(x))`.
- Gated DeltaNet (H_k key heads, H_v = r H_k value heads, value head h with key
  head ⌊h / r⌋): `[q | k | v | z] = h W_qkvz`, `[b | a] = h W_ba`; `[q | k | v]
  ← silu(conv4([q | k | v]))`, depthwise and causal; `q ← q / sqrt(Σ q² + 1e-6)
  / sqrt(d_k)`, `k ← k / sqrt(Σ k² + 1e-6)` per head; `β = sigmoid(b)`, `g =
  −exp(A_log) softplus(a + dt_bias)`; `S_0 = 0`, `S_t = exp(g_t) S_{t−1} + β_t
  k_t (v_t − (exp(g_t) S_{t−1})ᵀ k_t)ᵀ`, `o_t = S_tᵀ q_t`; `y = o / sqrt(mean(o²)
  + eps) ⊙ w_norm ⊙ silu(z)` per head, the norm first; `y W_out`.
- gated attention: `[q | γ] = h W_q` per head, `k = h W_k`, `v = h W_v`; `q ←
  norm(q)`, `k ← norm(k)` over the head; the first `rotary` dimensions turned
  (pair (i, i + rotary/2) of position t by the angle t θ^(−2i/rotary)); `s_ij
  = q_i · k_j / sqrt(D)`, query head h with key-value head h // (H / Hkv),
  causal softmax, `· v`; `(o ⊙ sigmoid(γ)) W_o`.
- ff: `p = softmax(h W_r)` over all E; the k largest; `w_e = p_e / Σ_chosen p`;
  `Σ_{chosen, held} w_e ff_e(h) + sigmoid(h w_s) ff_shared(h)`.
- `loss = CE + c · Σ_layers E Σ_e f_e P_e`, f_e the share of the pairs sent to e
  (the choice the step is computed with), P_e the mean router probability.

Departures from a word-for-word transcription, values unchanged — MEMORY
SHAPING ONLY: the recurrence is a `lax.scan` over tokens in TWO levels, an
outer one over blocks of `GDN_BLOCK` tokens under `jax.checkpoint` and an inner
one over a block's tokens; each of a layer's two sub-blocks, each block of
`QUERY_BLOCK` queries, each expert's body and each block of `HEAD_BLOCK`
positions of the head with its cross entropy is recomputed in the backward
pass (`jax.checkpoint`), so that 16 384 tokens fit on one chip beside the
float32 parameters.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
ROUTER_AUX_LOSS_COEF = 1e-3
# where the program counts the passes its held dispatch ran, per layer
PASSES = ("router_state", "held_passes")
QUERY_BLOCK = 512
HEAD_BLOCK = 1024
GDN_BLOCK = 128
L2_EPS = 1e-6

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch (my chip runs, PR
# 64; PERF.md §6). SOUND: the largest the program gave over TWO SETS of seeds
# at 16 384 tokens — 0 and 2147483777 (the first set, the limits' first
# draft), then 2147483659, 2147481013, 1900000129, 3000000019, 2040000011 and
# 1234567891 (the second, the cell's six spread runs). CONTROL: each part the
# configuration states float32 computed in bfloat16 ALONE
# (`rehearse/departures_qwen3_next.py::CONTROLS`, seed 2147483777, the program
# as it is beside them), which has to read `correct: false` by one of these
# limits, not by each. Two of the seven do: g and Γ in bfloat16 moves every
# leaf's first moment five-fold and more (below), a bfloat16 router fails the
# same-input figures. FIVE ARE BELOW THE NOISE of this cell's sound readings
# (the file's `BELOW_THE_NOISE`, with their figures): the state, the L2 norms,
# the residual stream, the q/k head norms and the gated norm in bfloat16 move
# no first moment by more than 1.1 times — every product that reads them
# rounds its operands to bfloat16 anyway, and with normal(0.02) everywhere
# the sound readings are ten times Kimi-Linear's (0.034–0.043 on the matrices;
# LFM2, the other configuration initialised so, reads 0.014–0.025). A limit is
# the geometric middle of the two readings where the control moves the figure
# by 1.4 times the sound reading or more, else three times the sound reading,
# and never wider than `no_wider_than`.
def _between(sound: float, control: float, no_wider_than: float) -> float:
    if control >= 1.4 * sound:
        return (sound * control) ** 0.5
    return min(3.0 * sound, no_wider_than)


# AdamW's first moment is linear in the gradients, and every matmul of the
# program rounds its operands to bfloat16: per leaf (the largest sound reading
# of the eight runs, g and Γ in bfloat16). The sound readings hardly vary over
# the seeds (`embed` 0.0340–0.0367, the largest at the final tree's fresh seed
# 2147484311, `moe_router` 0.0396–0.0427, the worst
# judged expert 0.0411–0.0482); `gdn_A_log` and `gdn_dt_bias`, 96 numbers each,
# do (0.017–0.058), and their limits are three times their largest.
_MU_READINGS = {
    "embed": (0.03675, 0.2084), "final_norm": (0.006559, 0.03488),
    "gdn_A_log": (0.05759, 0.6154), "gdn_ba": (0.03762, 0.4019),
    "gdn_conv": (0.03746, 0.2042), "gdn_dt_bias": (0.05513, 0.8577),
    "gdn_onorm": (0.041, 0.2237), "gdn_qkvz": (0.03703, 0.2048),
    "gdn_wo": (0.03564, 0.1924), "head": (0.01295, 0.06483), "k_norm": (0.04053, 0.1937),
    "mixer_norm": (0.03597, 0.2086), "moe_norm": (0.02948, 0.1495),
    "moe_router": (0.04271, 0.2194), "q_norm": (0.03987, 0.1861),
    "shared_down": (0.02725, 0.142), "shared_expert_gate": (0.03672, 0.1936),
    "shared_gate": (0.03097, 0.165), "shared_up": (0.02951, 0.1568),
    "wk": (0.03828, 0.191), "wo": (0.007496, 0.03063), "wq": (0.03771, 0.1878),
    "wv": (0.007314, 0.03087),
}
# The parameter update after the steps: AdamW's first steps are ≈ lr · sign(g),
# so an element whose gradient is near zero changes sign under rounding and
# counts twice: the figure goes as the ROOT of the first moment's. The
# matrices' sound readings vary by a thirtieth over the seeds (`embed`
# 0.182–0.188, `gdn_qkvz` 0.121–0.125), which is what lets a limit half again
# as wide hold them. (sound, g and Γ in bfloat16):
_UPDATE_READINGS = {
    "embed": (0.188, 0.4315), "gdn_ba": (0.1298, 0.8056), "gdn_conv": (0.1257, 0.3872),
    "gdn_qkvz": (0.1253, 0.3891), "gdn_wo": (0.1237, 0.3842), "head": (0.05974, 0.1596),
    "moe_router": (0.1667, 0.4003), "shared_down": (0.09899, 0.2934),
    "shared_expert_gate": (0.1231, 0.3463), "shared_gate": (0.1072, 0.321),
    "shared_up": (0.1031, 0.3069), "wk": (0.1253, 0.3641), "wo": (0.05846, 0.1566),
    "wq": (0.1177, 0.3544), "wv": (0.05958, 0.151),
}
# the (1 + w) norms' weights, 256 to 2048 numbers that start at ZERO: under the
# warm-up's first steps (1e-8, 2e-8) their updates swing with the seed
# (`q_norm` 0.033–0.147, `k_norm` 0.062–0.171, `final_norm` 0.005–0.049) and
# hold nothing their first moment does not: three times the largest sound reading
_NORM_UPDATES = {"final_norm": 0.04869, "k_norm": 0.1706, "q_norm": 0.147,
                 "mixer_norm": 0.1206, "moe_norm": 0.1063}
TOLERANCES = {
    # the losses at seeded weights, per-example means over 16 384 tokens. Sound:
    # the sum and the cross entropy 7.3e-6 – 2.4e-5, three-fold over the eight
    # seeds, so a limit between that and g and Γ in bfloat16's 6.0e-5 (the L2
    # norms in bfloat16 3.96e-5, the others 1.1e-5 – 2.6e-5) would fail a sound
    # run: three times the largest sound reading. What the departures read at
    # the tiny preset is `tests/test_qwen3_next_check.py`'s
    "loss_rel": 7.5e-5,
    "loss_ce_rel": 7.5e-5,
    # the auxiliary term is what the trainer ADDED: the step's float32 loss
    # minus its float32 cross entropy, 0.00404 of 10.27 — ONE unit of float32's
    # resolution at the loss is 2.4e-4 of the term. Sound 2.8e-5 – 9.0e-5 (no
    # control moves it): two units
    "loss_aux_rel": 5e-4,
    # The program's router against this one ON THE SAME INPUT (the residual
    # stream the program's router saw), both float32 at the highest matmul
    # precision, at BOTH steps. Sound: every one of 655 360 pairs agrees in all
    # eight runs, the weights' median error 1.12e-7 – 1.16e-7. A bfloat16
    # router reads 0.9937 and 3.25e-3: the agreement a sixth of the control's
    # disagreeing share, the weights' error the geometric middle
    "router_same_input_agreement_min": 0.999,
    "router_weight_rel_median": 2e-5,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a residual stream without the program's bfloat16 rounding
    # upstream: a softmax top-10 of 512 has many near-ties, a pair in 58 flips
    # (sound 0.9824–0.9830 in all eight runs). The reference then computes
    # with the program's choice. g and Γ in bfloat16 reads 0.9416: the
    # geometric middle of the disagreeing shares (1.76e-2, 5.84e-2)
    "routing_agreement_min": 0.968,
    # `default` is for a leaf the table does not name
    "mu_rel_l2": {"default": 1e-1,
                  # the worst judged expert of `w_gate`, `w_up`, `w_down` (all 32
                  # held experts judged apart at every seed: the fewest got
                  # 1969–2245 pairs over the two steps and four layers). Sound
                  # at most 0.0482; g and Γ in bfloat16 0.2454
                  "experts": _between(0.04824, 0.2454, 1.5e-1),
                  **{leaf: _between(sound, control, 1.5e-1)
                     for leaf, (sound, control) in _MU_READINGS.items()},
                  "gdn_A_log": 3 * 0.05759, "gdn_dt_bias": 3 * 0.05513},
    "update_rel_l2": {"default": 4e-1,
                      # sound at most 0.1581, g and Γ in bfloat16 0.3791
                      "experts": _between(0.1581, 0.3791, 4e-1),
                      **{leaf: _between(sound, control, 4e-1)
                         for leaf, (sound, control) in _UPDATE_READINGS.items()},
                      **{leaf: 3 * sound for leaf, sound in _NORM_UPDATES.items()},
                      # 96 numbers each whose two updates are UNDER float32's
                      # resolution at their size: the figure counts the entries
                      # that move one unit — 0, 0.25, 0.44 in sound runs, 0.75
                      # and 0.97 under controls — and holds nothing (Kimi's
                      # `kda_A_log`); their first moment (above) holds them
                      "gdn_A_log": 1.5, "gdn_dt_bias": 1.5},
}
# An expert's slice of the experts' leaves is judged apart only if it got at
# least this many (token, slot) pairs over the compared steps and layers;
# those with fewer are pooled and judged as one (PR 30's derivation). Here a
# held expert sees ≈ 320 pairs a layer and step, ≈ 2560 over the check, and the
# emptiest of any run 1969: all 32 are judged apart.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `n_routed_experts` is what this
    chip holds (`num_experts` of the configuration), `num_experts` what the
    router chooses among, as the check and the drivers read them."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "linear_key_head_dim",
            "linear_value_head_dim", "linear_num_key_heads", "linear_num_value_heads",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "num_experts_per_tok", "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    given = lambda key, default, kind=float: kind(model_params.get(key, default))
    hp["n_routed_experts"] = int(model_params["num_experts"])
    hp["num_experts"] = given("router_experts", 0, int) or hp["n_routed_experts"]
    hp["first_expert"] = given("first_expert", 0, int)
    hp["conv"] = given("linear_conv_kernel_dim", 4, int)
    hp["rotary"] = int(hp["head_dim"] * given("partial_rotary_factor", 0.25))
    hp["rope_theta"] = given("rope_theta", 1e7)
    hp["aux_coef"] = given("router_aux_loss_coef", ROUTER_AUX_LOSS_COEF)
    hp["eps"] = given("rms_norm_eps", 1e-6)
    kept = model_params.get("kept_layers", "")
    layers = (tuple(int(l) for l in kept.split(",")) if kept
              else tuple(range(hp["num_hidden_layers"])))
    types = model_params.get("layer_types", "")
    interval = given("full_attention_interval", 4, int)
    hp["attends"] = tuple(
        (types.split(",")[l] == "full_attention") if types else (l + 1) % interval == 0
        for l in layers)
    hp["moe_layers"] = hp["num_hidden_layers"]
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def rms(x, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps)


def norm(x, weight, eps):
    """This family's RMSNorm: the stored weight is the scale's distance from one."""
    return rms(x, eps) * (1.0 + weight)


# ------------------------------------------------------------------ #
# the Gated DeltaNet mixer


def causal_conv(x, weight):
    """x (B, T, P), weight (W, P): y_t = Σ_j weight_j x_{t − (W−1) + j}, zeros
    before the sequence — W shifted sums."""
    width, t = weight.shape[0], x.shape[1]
    y = jnp.zeros_like(x)
    for j in range(width):
        shift = width - 1 - j
        y = y + jnp.pad(x, ((0, 0), (shift, 0), (0, 0)))[:, :t] * weight[j]
    return y


def delta_rule(q, k, v, g, beta):
    """The recurrence, token by token: q, k (B, T, H_k, d_k), v (B, T, H_v,
    d_v), g, beta (B, T, H_v) -> o (B, T, H_v, d_v). The state S (B, H_k, r,
    d_k, d_v), one a value head beside the key head it reads, starts at zero."""
    b, t, hk, dk = k.shape
    hv, dv = v.shape[2:]
    r = hv // hk
    block = min(GDN_BLOCK, t)
    pad = -t % block             # padded tokens: no decay, nothing written
    grouped = lambda a: a.reshape((b, t, hk, r) + a.shape[3:])

    def blocks(a):
        a = jnp.pad(a, ((0, 0), (0, pad)) + ((0, 0),) * (a.ndim - 2))
        a = a.reshape((b, -1, block) + a.shape[2:])
        return jnp.moveaxis(jnp.moveaxis(a, 2, 0), 2, 0)            # (blocks, block, B, ...)

    def token(s, at):
        q_t, k_t, v_t, g_t, beta_t = at                              # k (B, H_k, d), v (B, H_k, r, d)
        s = jnp.exp(g_t)[..., None, None] * s                        # one decay a value head
        read = jnp.einsum("bhk,bhrkv->bhrv", k_t, s)                 # what k reads of each state
        s = s + jnp.einsum("bhk,bhrv->bhrkv", k_t, beta_t[..., None] * (v_t - read))
        return s, jnp.einsum("bhk,bhrkv->bhrv", q_t, s)

    @jax.checkpoint
    def block_of_tokens(s, operands):
        return jax.lax.scan(token, s, operands)

    _, o = jax.lax.scan(
        block_of_tokens, jnp.zeros((b, hk, r, dk, dv), jnp.float32),
        (blocks(q), blocks(k), blocks(grouped(v)), blocks(grouped(g)), blocks(grouped(beta))))
    # (blocks, block, B, H_k, r, d_v) -> (B, T, H_v, d_v)
    return jnp.moveaxis(o.reshape((-1, b, hv, dv)), 0, 1)[:, :t]


def gated_deltanet(p, x, hp):
    b, t, _ = x.shape
    hk, hv = hp["linear_num_key_heads"], hp["linear_num_value_heads"]
    dk, dv = hp["linear_key_head_dim"], hp["linear_value_head_dim"]
    kw, vw = hk * dk, hv * dv
    unit = lambda a: a / jnp.sqrt(jnp.sum(jnp.square(a), axis=-1, keepdims=True) + L2_EPS)
    h = norm(x, p["mixer_norm"], hp["eps"])
    qkvz = h @ p["gdn_qkvz"]
    ba = h @ p["gdn_ba"]
    qkv = jax.nn.silu(causal_conv(qkvz[..., :2 * kw + vw], p["gdn_conv"]))
    z = qkvz[..., 2 * kw + vw:].reshape(b, t, hv, dv)
    q = unit(qkv[..., :kw].reshape(b, t, hk, dk)) / math.sqrt(dk)
    k = unit(qkv[..., kw:2 * kw].reshape(b, t, hk, dk))
    v = qkv[..., 2 * kw:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["gdn_A_log"]) * jax.nn.softplus(ba[..., hv:] + p["gdn_dt_bias"])
    o = delta_rule(q, k, v, g, beta)
    y = rms(o, hp["eps"]) * p["gdn_onorm"] * jax.nn.silu(z)          # the norm first, then the gate
    return y.reshape(b, t, vw) @ p["gdn_wo"]


# ------------------------------------------------------------------ #
# gated attention


def rotary(x, hp):
    """x (B, T, heads, D): the first `rotary` dimensions turned by their
    positions, rotate-half within them; the rest as they are."""
    t, rot = x.shape[1], hp["rotary"]
    freq = hp["rope_theta"] ** (-2.0 * jnp.arange(rot // 2, dtype=jnp.float32) / rot)
    angle = (jnp.arange(t, dtype=jnp.float32)[:, None] * freq[None, :])[None, :, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    first, second, rest = x[..., :rot // 2], x[..., rot // 2:rot], x[..., rot:]
    return jnp.concatenate(
        [first * cos - second * sin, second * cos + first * sin, rest], axis=-1)


def attention(p, x, hp):
    b, t, _ = x.shape
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    group = heads // kv_heads
    h = norm(x, p["mixer_norm"], hp["eps"])
    q_gate = (h @ p["wq"]).reshape(b, t, heads, 2 * d)
    q, gate = q_gate[..., :d], q_gate[..., d:]
    q = rotary(norm(q, p["q_norm"], hp["eps"]), hp)
    k = rotary(norm((h @ p["wk"]).reshape(b, t, kv_heads, d), p["k_norm"], hp["eps"]), hp)
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
        visible = jnp.arange(t)[None, :] <= q_pos[:, None]
        probs = jax.nn.softmax(jnp.where(visible[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bgqk,bkd->bqgd", probs, v_head)

    def one_head(args):
        q_head, k_head, v_head = args
        return jax.lax.map(lambda qp: queries(qp[0], qp[1], k_head, v_head),
                           (q_head, positions))

    out = jax.lax.map(one_head, (q_blocks, jnp.moveaxis(k, 2, 0), jnp.moveaxis(v, 2, 0)))
    # (kv head, blocks, B, block, group, D) -> (B, T, heads, D)
    out = out.transpose(2, 1, 3, 0, 4, 5).reshape(b, t + pad, heads, d)[:, :t]
    return (out * jax.nn.sigmoid(gate)).reshape(b, t, heads * d) @ p["wo"]


# ------------------------------------------------------------------ #
# the feed-forward


def gated_unit(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router(p, x, hp):
    """(h (N, C), probs (N, E), chosen (N, E) bool): the k experts with the
    largest probability among all E."""
    h = norm(x, p["moe_norm"], hp["eps"]).reshape(-1, x.shape[-1])
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
        return w_col[:, None] * gated_unit(h, w_gate, w_up, w_down)

    def add(total, per_expert):
        return total + one(*per_expert), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(h),
        (p["w_gate"], p["w_up"], p["w_down"], weight[:, first:first + held].T))
    return total


def shared(p, h):
    """sigmoid(h w_s) · ff_shared(h): computed alike on every chip."""
    return jax.nn.sigmoid(h @ p["shared_expert_gate"]) * gated_unit(
        h, p["shared_gate"], p["shared_up"], p["shared_down"])


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
    return ((experts(p, h, weight, hp) + shared(p, h)).reshape(x.shape), balance, own,
            slot_weights(probs, own))


_EVERY = ("mixer_norm", "moe_norm", "moe_router", "shared_gate", "shared_up", "shared_down",
          "shared_expert_gate", "w_gate", "w_up", "w_down")
_GDN = ("gdn_qkvz", "gdn_ba", "gdn_conv", "gdn_A_log", "gdn_dt_bias", "gdn_onorm", "gdn_wo")
_ATTN = ("wq", "wk", "wv", "wo", "q_norm", "k_norm")


def _layer(p, attends: bool, x, use, hp):
    def mix(p, x):
        return x + (attention(p, x, hp) if attends else gated_deltanet(p, x, hp))

    def feed_forward(p, x, use):
        y, balance, own, weights = moe(p, x, use, hp)
        return x + y, balance, own, weights

    # the two sub-blocks recomputed apart
    return jax.checkpoint(feed_forward)(p, jax.checkpoint(mix)(p, x), use)


def _cross_entropy(x, final_norm, head, targets, eps):
    """(B, T) negative log likelihood of `targets` under the head (V, C) on x,
    in blocks of `HEAD_BLOCK` positions so that T x V logits never exist at
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
        logp = jax.nn.log_softmax(norm(x_block, final_norm, eps) @ head.T, axis=-1)
        return -jnp.take_along_axis(logp, target_block[..., None], axis=-1)[..., 0]

    nll = jax.lax.map(lambda args: positions(*args), (x_blocks, target_blocks))
    return jnp.moveaxis(nll, 0, 1).reshape(b, t + pad)[:, :t]


def layer_params(params, hp):
    """[(attends, the layer's own parameters)] of the layers built."""
    seen = {True: 0, False: 0}
    out = []
    for i, attends in enumerate(hp["attends"]):
        p = {k: params[k][i] for k in _EVERY}
        p.update({k: params[k][seen[attends]] for k in (_ATTN if attends else _GDN)})
        seen[attends] += 1
        out.append((attends, p))
    return out


def forward(params, batch, hp, chosen=None):
    """batch {"tokens" (B, T), "labels" (B, T)} -> (per-example cross entropy
    (B,), the load-balance terms' sum, per layer the router's OWN choice
    (L, N, E) bool and the weights under it).

    `chosen` (L, N, E) bool, where given, takes the place of the routers' own
    choice of experts — the probabilities stay the reference's."""
    x = params["embed"][batch["tokens"]]
    balance_all, own_all, weights_all = [], [], []
    for i, (attends, p) in enumerate(layer_params(params, hp)):
        x, balance, own, weights = _layer(
            p, attends, x, None if chosen is None else chosen[i], hp)
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
             "loss_aux": hp["aux_coef"] * balance}
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
