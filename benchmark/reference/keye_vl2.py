"""The plain reference of configuration `keye-vl-2.0-30b-a3b` (and of any
`keye_vl2` zoo model): forward pass, the three terms of the loss, gradients by
`jax.grad` and AdamW, in straightforward `jax.numpy`, float32. No kernel, no
threshold, no bisection, no sort-by-expert, no grouped matmul: the index
scores are a matmul, a relu and a weighted sum; a query's keys are the K
largest of its masked prefix by `jax.lax.top_k`; attention is the score matrix
of a block of queries against ALL keys under a dense mask made from index
sets; every held expert is applied to ALL tokens and masked. The caller runs
it under `jax.default_matmul_precision("highest")`.

Written from the published configuration (Kwai-Keye/Keye-VL-2.0-30B-A3B
`config.json`, `model_type: KeyeVL2`, `sa_config`) and the published equations
of DeepSeek-V3.2-Exp's lightning indexer, not from the zoo module. It shares
with the program the names and shapes of the parameters
(`model_zoo/transformer/keye_vl2.py` lists them) and the same share of the
deployment. What a Mellum2 layer and this one have in common — the router, the
held experts, the blocked cross entropy, AdamW — is `reference/mellum.py`'s,
loaded from there.

Layer: `x ← x + attention(rms_norm(x))`, `x ← x + ff(rms_norm(x))`, and a loss
term of its own:
- `q = h W_q` (H heads of D), `k = h W_k`, `v = h W_v` (Hkv heads); q and k
  each `rms_norm`ed per head over D with a learned weight; `q ← R(q)`,
  `k ← R(k)`, R the rotary map built BY `mrope_section` from a (3, T) position
  array (temporal, height, width; a text sequence gives three equal rows).
- with `hd = stop_gradient(h)`: `qI = hd W_qI` (Hi heads of Di), `kI =
  layer_norm(hd W_kI)` (one head; scale and bias), `R` on both, `w = hd W_w /
  sqrt(Hi Di)`; `I[t, s] = Σ_j w[t, j] relu(qI[t, j] · kI[s])`.
- `S_t` = every s ≤ t while t < K, else the K keys of largest I[t, s] among
  s ≤ t, ties to the lower key index.
- `s_ts = q_t · k_s / sqrt(D)`, query head h with key-value head
  h // (H / Hkv); softmax over S_t; `· v`; `W_o`.
- `p̂[t, s] = stop_gradient(mean_h P[h, t, s])`, `π[t, ·] = softmax_{S_t}
  I[t, ·]`, `L_I = (1/T) Σ_t Σ_{s ∈ S_t} p̂ (log p̂ − log π)`.
- ff: `reference/mellum.py`'s (softmax over all E, top-k, renormalised, the
  held experts' part).
- `loss = CE + c_b Σ_layers E Σ_e f_e P_e + c_I Σ_layers L_I`.

`keep` (L, B, T, T) bool, where given, takes the place of the reference's own
selection (as `chosen` takes the place of its routers' choice): top-k is
discontinuous, so the comparison computes the reference ON THE PROGRAM'S
SELECTIONS and compares the indexers' decisions on their own (`index_plane`,
`own_selection`).

Departures from a word-for-word transcription, values unchanged: each layer,
each block of `QUERY_BLOCK` queries (all heads), each expert's body and each
block of positions of the head is recomputed in the backward pass
(`jax.checkpoint`), so that 16 384 tokens fit on one chip.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from benchmark import common

_mellum = common.load_module("reference", "mellum")
rms_norm, moe, routers_on, adamw_step = (
    _mellum.rms_norm, _mellum.moe, _mellum.routers_on, _mellum.adamw_step)
_cross_entropy, _between = _mellum._cross_entropy, _mellum._between

ADAMW = dict(_mellum.ADAMW)
LOAD_BALANCE_COEF = 0.001
INDEX_LOSS_COEF = 1.0
MROPE_SECTION = (16, 24, 24)
# where the program counts the passes its held dispatch ran, per layer
PASSES = ("router_state", "held_passes")
QUERY_BLOCK = 128

INDEX_LEAVES = ("index_wq", "index_wk", "index_k_scale", "index_k_bias", "index_w")

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch. A limit sits
# between two readings (my chip runs, PR 38; all in PERF.md section 6): the
# largest the program gave over its seeds (SOUND: five runs, seeds
# 2147484401-403, 3000000404, 2147484405) and what a CONTROL gives — the program
# with a part the configuration states float32 computed in bfloat16
# (`rehearse/departures_keye_vl2.py::CONTROLS`: the index scores end to end,
# matmul output, relu, head weights and the sum over heads; the residual
# stream), which has to read `correct: false` by one of these limits, not by
# each. `reference/mellum.py::_between`'s rule: where a control moves a figure
# by 1.4 times the largest sound reading or more, the geometric middle of the
# two; where it moves it less, three times the sound reading, and never wider
# than `no_wider_than`.
#
# AdamW's first moment, per leaf: (the largest sound reading, the control that
# moves it most — for the indexer's five leaves the control of THEIR precision,
# the index scores in bfloat16, and for the router the control that moves it
# LEAST: both spread by a third over the seeds, index_w 0.0041-0.0053, the
# router's update 0.035-0.047, and the residual stream in bfloat16, which moves
# them by 1.5 times, is caught elsewhere). The residual stream in bfloat16 reads 0.0030 in the head
# (sound 0.00076-0.00083) and 0.0021 in the final norm (0.0011); the index
# scores in bfloat16 move NO first moment by 1.4 times (the indexer's own
# leaves read 0.0058-0.0075 under it, inside their sound spread: the loss that
# trains them is an average over 2048 keys a row) — that control is caught by
# the selection, below. The experts' worst judged slice is wider here than in
# Mellum2's cell (0.0253 against 0.0097): a held expert sees ≈ 1000 pairs a
# step, half of Mellum2's.
_MU_READINGS = {
    "attn_norm": (0.00334, 0.00358), "embed": (0.00407, 0.00486),
    "final_norm": (0.00112, 0.00206), "head": (0.00083, 0.00296),
    "index_k_bias": (0.00644, 0.00584), "index_k_scale": (0.0018, 0.00151),
    "index_w": (0.00528, 0.00555), "index_wk": (0.01052, 0.00753),
    "index_wq": (0.00797, 0.00679), "k_norm": (0.00671, 0.00728),
    "moe_norm": (0.00831, 0.01053), "moe_router": (0.01078, 0.00578),
    "q_norm": (0.0068, 0.00728), "wk": (0.00775, 0.00837), "wo": (0.00315, 0.00348),
    "wq": (0.00773, 0.00835), "wv": (0.00304, 0.0035),
}
# The parameter update after the steps (≈ lr · sign(g) at the warm-up's first
# step sizes: an element whose gradient is near zero counts twice; the norms'
# scales do not move and read 0 on both sides). The index keys' layernorm bias
# starts at zero and moves by 1e-8: its reading swings 0.008-0.066 over the
# seeds.
_UPDATE_READINGS = {
    "embed": (0.02902, 0.03678), "head": (0.01141, 0.01942),
    "index_k_bias": (0.06594, 0.03679), "index_w": (0.03708, 0.03661),
    "index_wk": (0.06642, 0.05743), "index_wq": (0.042, 0.04227),
    "moe_router": (0.04694, 0.04321), "wk": (0.05449, 0.05847),
    "wo": (0.02728, 0.02921), "wq": (0.05088, 0.05535), "wv": (0.02737, 0.02869),
}
_MU_CAP, _UPDATE_CAP = 1e-1, 2.5e-1
TOLERANCES = {
    # the losses at seeded weights, per-example means over 16 384 tokens.
    # Sound: the sum 6.3e-7 - 4.1e-6, the cross entropy 1.7e-6 - 3.3e-6, the
    # load balance 1.5e-6 - 9.6e-6, the index loss 1.1e-5 - 8.7e-5: several-fold
    # over the seeds and the controls inside that (5.2e-6 / 6.6e-6 / 1.5e-5 /
    # 1.3e-4), so three times the largest sound reading each. The index loss
    # left out reads 1 there, p-hat not divided by the heads 8.3, no relu 0.75
    "loss_rel": 1.3e-5,
    "loss_ce_rel": 1.0e-5,
    "loss_balance_rel": 2.9e-5,
    "loss_index_rel": 2.6e-4,
    # The program's router against this one ON THE SAME INPUT, both float32 at
    # the highest matmul precision, at both steps: `reference/mellum.py`'s
    # limits and reasons (sound here: 0.999998-1.0, weights' median error
    # 8.7e-8 - 9.1e-8; weights not renormalised read 0.59 in the weights there)
    "router_same_input_agreement_min": 0.9995,
    "router_weight_rel_median": 1e-5,
    # The program's choice against the reference's OWN forward pass (sound:
    # 0.99983-0.99989). The residual stream in bfloat16 reads 0.99686: the
    # geometric middle of the disagreeing shares (1.7e-4, 3.1e-3)
    "routing_agreement_min": 0.99927,
    # The indexers' decisions, layer by layer and step by step, ON THE SAME
    # INPUT (the residual stream the program's layer started from), the worst
    # of the eight. The program's matmuls round their operands to bfloat16, so
    # its plane is off by 0.0045-0.0051 of the reference's (relative L2 over
    # the causal pairs) and of a row's 2048 keys a few at the threshold change
    # sides. `index_score_rel`: sound at most 0.00509, the scores in bfloat16
    # end to end 0.00556, under 1.4 times: three times the sound reading (no
    # relu and the head weights left out read 1.0 and 1.2).
    "index_score_rel": 1.53e-2,
    # The least share, over rows, of a row's selected keys on which the two
    # agree: sound 0.9907-0.9912, the scores in bfloat16 0.9888 (disagreeing
    # shares 0.0093 and 0.0112: under 1.4 times; three times the sound one)
    "selection_agreement_min": 0.972,
    # The MEAN of that share over a layer's 16 384 rows, the least of the
    # eight: what catches the index scores in bfloat16. An average over 31M
    # selected pairs hardly moves with the seed — sound 0.997474-0.997566 over
    # all 40 (seed, step, layer) readings, that control 0.997224-0.997270 over
    # its eight — so the limit lies between them though they are close:
    # the middle of the sound's least and the control's largest
    "selection_agreement_mean_min": 0.99737,
    # Pairs on which the selections differ although the reference's score lies
    # further from the row's threshold than twice the row's largest score
    # error: none (a key changes sides only if rounding can carry it across;
    # sound and both controls read 0)
    "selection_outside_error_max": 0,
    # `default` is for a leaf the tables do not name (a norm's scale in
    # `update_rel_l2`: 0 on both sides)
    "mu_rel_l2": {"default": 3e-2,
                  # the worst judged expert of `w_gate`, `w_up`, `w_down`
                  # (sound at most 0.0253; the residual stream in bfloat16 0.0283)
                  "experts": _between(0.0253, 0.0283, _MU_CAP),
                  **{leaf: _between(sound, control, _MU_CAP)
                     for leaf, (sound, control) in _MU_READINGS.items()}},
    "update_rel_l2": {"default": 2.5e-1,
                      # sound at most 0.0630, the residual stream in bfloat16 0.0800
                      "experts": _between(0.0630, 0.0800, _UPDATE_CAP),
                      **{leaf: _between(sound, control, _UPDATE_CAP)
                         for leaf, (sound, control) in _UPDATE_READINGS.items()}},
}
# as `reference/mellum.py`'s: an expert with fewer pairs over the compared
# steps and layers is pooled with the others below the floor
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `n_routed_experts` is what this
    chip holds (`num_experts` of the configuration), `num_experts` what the
    router chooses among, as the check and the drivers read them."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "head_dim", "indexer_num_heads", "indexer_head_dim",
            "index_topk", "num_experts_per_tok", "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    hp["n_routed_experts"] = int(model_params["num_experts"])
    hp["num_experts"] = int(model_params.get("router_experts", 0)) or hp["n_routed_experts"]
    hp["first_expert"] = int(model_params.get("first_expert", 0))
    hp["rope_theta"] = float(model_params.get("rope_theta", 10000000.0))
    hp["mrope_section"] = MROPE_SECTION
    hp["load_balance_coef"] = float(model_params.get("load_balance_coef", LOAD_BALANCE_COEF))
    hp["index_loss_coef"] = float(model_params.get("index_loss_coef", INDEX_LOSS_COEF))
    hp["eps"] = float(model_params.get("rms_norm_eps", 1e-6))
    hp["moe_layers"] = hp["num_hidden_layers"]
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def text_positions(t: int):
    """(3, T): the temporal, height and width positions of a text sequence."""
    return jnp.stack([jnp.arange(t, dtype=jnp.float32)] * 3)


def mrope_angles(positions, dim: int, hp):
    """(T, dim/2): the angle each pair of dimensions (i, i + dim/2) turns by.
    Pair i takes its position from the component whose section holds it: the
    first `mrope_section[0]` pairs of every `sum(mrope_section)` the temporal
    one, the next the height, the last the width (boundaries in proportion
    where a head has another number of pairs than the sections name)."""
    half = dim // 2
    freq = hp["rope_theta"] ** (-2.0 * jnp.arange(half, dtype=jnp.float32) / dim)
    total, edges, edge = sum(hp["mrope_section"]), [], 0
    for size in hp["mrope_section"]:
        edge += size
        edges.append(round(edge * half / total))
    component = jnp.asarray([sum(i >= e for e in edges[:-1]) for i in range(half)])
    return positions[component, :].T * freq[None, :]


def rotary(x, positions, hp):
    """x (B, T, heads, D) turned by its positions (3, T)."""
    d = x.shape[-1]
    angle = mrope_angles(positions, d, hp)[None, :, None, :]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * cos - second * sin, second * cos + first * sin], axis=-1)


def _block(t: int) -> int:
    """Queries a block: the largest divisor of t up to `QUERY_BLOCK`."""
    return next(b for b in range(min(QUERY_BLOCK, t), 0, -1) if t % b == 0)


def _query_blocks(x):
    """x (B, T, ...) -> (blocks, B, block, ...)."""
    block = _block(x.shape[1])
    return jnp.moveaxis(x.reshape(x.shape[0], -1, block, *x.shape[2:]), 1, 0)


def _from_blocks(y):
    """(blocks, B, block, ...) -> (B, T, ...)."""
    y = jnp.moveaxis(y, 0, 1)
    return y.reshape(y.shape[0], -1, *y.shape[3:])


def index_scores(p, h, hp):
    """I (B, T, T) from the normed stream h (B, T, C), read detached."""
    b, t, _ = h.shape
    heads, d = hp["indexer_num_heads"], hp["indexer_head_dim"]
    h = jax.lax.stop_gradient(h)
    positions = text_positions(t)
    q = rotary((h @ p["index_wq"]).reshape(b, t, heads, d), positions, hp)
    k = layer_norm(h @ p["index_wk"], p["index_k_scale"], p["index_k_bias"], hp["eps"])
    k = rotary(k[:, :, None, :], positions, hp)[:, :, 0, :]
    w = (h @ p["index_w"]) / math.sqrt(heads * d)

    @jax.checkpoint
    def rows(q_rows, w_rows):
        per_head = jax.nn.relu(jnp.einsum("brhd,bsd->brhs", q_rows, k))
        return jnp.einsum("brhs,brh->brs", per_head, w_rows)

    return _from_blocks(jax.lax.map(lambda a: rows(*a), (_query_blocks(q), _query_blocks(w))))


def own_selection(scores, k: int):
    """(B, T, T) bool: for query t the `min(t + 1, k)` keys of largest score
    among s ≤ t, by `jax.lax.top_k` over the masked prefix (of equal scores the
    lower key index first)."""
    b, t, _ = scores.shape
    block = _block(t)

    def rows(args):
        score_rows, position = args
        causal = jnp.arange(t)[None, :] <= position[:, None]
        _, idx = jax.lax.top_k(jnp.where(causal, score_rows, -jnp.inf), min(k, t))
        chosen = jnp.zeros(score_rows.shape, bool)
        chosen = chosen.at[jnp.arange(b)[:, None, None],
                           jnp.arange(block)[None, :, None], idx].set(True)
        return chosen & causal

    positions = jnp.arange(t).reshape(-1, block)
    return _from_blocks(jax.lax.map(rows, (_query_blocks(scores), positions)))


def attention(p, x, keep, hp):
    """(the attention sub-block's update of x, L_I of this layer, the scores
    I, the selection used): `keep` (B, T, T) bool, or None for the
    reference's own selection."""
    b, t, _ = x.shape
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    group = heads // kv_heads
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    positions = text_positions(t)
    q = rms_norm((h @ p["wq"]).reshape(b, t, heads, d), p["q_norm"], hp["eps"])
    k = rms_norm((h @ p["wk"]).reshape(b, t, kv_heads, d), p["k_norm"], hp["eps"])
    q, k = rotary(q, positions, hp), rotary(k, positions, hp)
    v = (h @ p["wv"]).reshape(b, t, kv_heads, d)
    scores = index_scores(p, h, hp)
    if keep is None:
        keep = own_selection(jax.lax.stop_gradient(scores), hp["index_topk"])

    @jax.checkpoint
    def rows(q_rows, keep_rows, score_rows):
        """q_rows (B, R, H, D) against every key under the rows' mask."""
        s = jnp.einsum("brhgd,bshd->bhgrs",
                       q_rows.reshape(b, -1, kv_heads, group, d), k) / math.sqrt(d)
        probs = jax.nn.softmax(jnp.where(keep_rows[:, None, None], s, -jnp.inf), axis=-1)
        out = jnp.einsum("bhgrs,bshd->brhgd", probs, v).reshape(b, -1, heads * d)
        target = jax.lax.stop_gradient(jnp.mean(probs, axis=(1, 2)))
        log_pi = jax.nn.log_softmax(jnp.where(keep_rows, score_rows, -jnp.inf), axis=-1)
        log_target = jnp.log(jnp.where(target > 0, target, 1.0))
        kl = jnp.sum(jnp.where(keep_rows & (target > 0),
                               target * (log_target - log_pi), 0.0))
        return out, kl

    out, kl = jax.lax.map(lambda a: rows(*a), (
        _query_blocks(q), _query_blocks(keep), _query_blocks(scores)))
    return _from_blocks(out) @ p["wo"], jnp.sum(kl) / (t * b), scores, keep


_LAYER = ("attn_norm", "wq", "wk", "wv", "wo", "q_norm", "k_norm") + INDEX_LEAVES + (
    "moe_norm", "moe_router", "w_gate", "w_up", "w_down")


def _layer(params, index, x, use, keep, hp):
    p = {k: params[k][index] for k in _LAYER}

    def run(p, x, use, keep):
        update, index_kl, _, _ = attention(p, x, keep, hp)
        x = x + update
        y, balance, own, weights = moe(p, x, use, hp)
        return x + y, balance, index_kl, own, weights

    return jax.checkpoint(run)(p, x, use, keep)


def forward(params, batch, hp, chosen=None, keep=None):
    """batch {"tokens" (B, T), "labels" (B, T)} -> (per-example cross entropy
    (B,), the load-balance terms' sum, the index losses' sum, per layer the
    router's OWN choice (L, N, E) bool and the weights under it). `chosen`
    (L, N, E) and `keep` (L, B, T, T) bool, where given, take the place of the
    routers' own choice of experts and of the indexers' own selection."""
    x = params["embed"][batch["tokens"]]
    balance_all, index_all, own_all, weights_all = [], [], [], []
    for i in range(hp["num_hidden_layers"]):
        x, balance, index_kl, own, weights = _layer(
            params, i, x, None if chosen is None else chosen[i],
            None if keep is None else keep[i], hp)
        balance_all.append(balance)
        index_all.append(index_kl)
        own_all.append(own)
        weights_all.append(weights)
    ce = jnp.mean(_cross_entropy(x, params["final_norm"], params["head"],
                                 batch["labels"], hp["eps"]), axis=-1)
    return ce, sum(balance_all), sum(index_all), jnp.stack(own_all), jnp.stack(weights_all)


def index_plane(layer_params, x, hp):
    """One layer's index scores (B, T, T) on a GIVEN residual stream x."""
    return index_scores(layer_params, rms_norm(x, layer_params["attn_norm"], hp["eps"]), hp)


def loss_terms(params, batch, hp, chosen=None, keep=None):
    """(the scalar the optimizer minimises, {"loss_ce", "loss_balance",
    "loss_index"} apart, (chosen, weights) of every layer's own router)."""
    ce, balance, index_kl, own, weights = forward(params, batch, hp, chosen, keep)
    mask = batch["mask"].astype(jnp.float32)
    terms = {"loss_ce": jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0),
             "loss_balance": hp["load_balance_coef"] * balance,
             "loss_index": hp["index_loss_coef"] * index_kl}
    return sum(terms.values()), terms, (own, weights)


def loss(params, batch, hp, chosen=None, keep=None):
    total, _, own = loss_terms(params, batch, hp, chosen, keep)
    return total, own
