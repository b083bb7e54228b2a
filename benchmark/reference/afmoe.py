"""The plain reference of configuration `trinity-mini` (and of any `afmoe` zoo
model): forward pass, loss, gradients by `jax.grad`, the routers' bias update
and AdamW, in straightforward `jax.numpy`, float32. No kernel, no band, no
sort-by-expert, no grouped matmul: attention is the score matrix of a block of
queries of one key-value head's group against ALL keys under a dense mask made
from positions, every held expert is applied to ALL tokens and masked. The
caller runs it under `jax.default_matmul_precision("highest")`.

Written from the published configuration (arcee-ai/Trinity-Mini `config.json`,
`model_type: afmoe`) and ISSUE 44's layer equations, not from the zoo module.
It shares one thing with the program: the names and shapes of the parameters
(`model_zoo/transformer/afmoe.py` lists them), so that the program's own
initial parameters are the reference's starting point, and the same share of
the deployment: the routed experts `first_expert … first_expert + num_experts −
1` and the vocabulary slice.

`x_0 = Emb[t] · sqrt(C)`. The layer of PUBLISHED index l (`kept_layers` lists
those built; FULL iff (l + 1) % 4 == 0, DENSE iff l < `num_dense_layers`) is
`x ← x + rms(Attn(rms(x; w_in)); w_post_attn)`, then
`x ← x + rms(MLP(rms(x; w_pre_mlp)); w_post_mlp)`:
- attention, h the normed input: `q = h W_q` (H heads of D), `k = h W_k`,
  `v = h W_v` (Hkv heads), `g = h W_g` (H·D); `q ← rms(q; w_qn)`, `k ← rms(k;
  w_kn)` over D; in a SLIDING layer `q ← R(q)`, `k ← R(k)` with R the rotary
  map (the dimension pair (i, i + D/2) of position t turned by t · θ^(−2i/D)),
  in a FULL layer nothing; `s_ij = q_i · k_j / sqrt(D)`, query head h with
  key-value head h // (H / Hkv); key j is visible to query i iff j ≤ i, and in
  a SLIDING layer iff also j > i − W; softmax over the visible; `o = · v`;
  `Attn = (o ⊙ sigmoid(g)) W_o`.
- dense ff: `W_down(silu(h W_gate) ⊙ h W_up)`.
- sparse ff: `s = sigmoid(h W_r)`; the k experts with the largest `s + b`;
  `w_e = scale · s_e / (Σ_chosen s + 1e-20)`; `Σ_{chosen, held} w_e ff_e(h) +
  ff_shared(h)`; after the step `d_e = u · sign(mean load − load_e)`,
  `b ← b + d − mean(d)` from zero. What passes between the steps, and between
  the program and this file, is the running sum `a ← a + d`; the router adds
  `b = a − mean(a)`, the same numbers (Σ(d − mean d) = Σd − mean Σd).
- `loss = mean CE(rms(x; w_final) W_head)`; no auxiliary term.

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
LOAD_BALANCE_COEFF = 1e-3
# where the program keeps the routers' selection bias, as the running sum of
# its updates (TrainState.extra_vars)
BIAS = ("router_state", "expert_bias")
# and where it counts the passes its held dispatch ran, per sparse layer
PASSES = ("router_state", "held_passes")
QUERY_BLOCK = 512
HEAD_BLOCK = 1024

# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch. A limit sits
# between two readings (my chip runs, PR 44; the table in PERF.md §6): the
# largest the program gave over its seeds (SOUND: fifteen runs at fourteen
# seeds, 2147484501, 2147484601, 2147484801-813, and after the review
# 2147485001 and 2147485101-107, every one from settled routers) and what the
# CONTROL gives — the program with a part the configuration states float32
# computed in bfloat16 (a bfloat16 router: `rehearse/departures_afmoe.py::
# CONTROLS`), which has to read `correct: false` by one of these limits, not
# by each. Where the control hardly moves a figure the limit is about three
# times the largest sound reading. The other two controls — the residual
# stream rounded to bfloat16, and the attention block's activations (q, k, the
# gate's logits, the head norms' output, the sigmoid and its product) — move
# the router's first moment by 1.42 times the seed's reading and nothing else
# by more than 1.32, and no figure by more than 1.1 times: every array they
# round is read next by a matmul or a kernel that rounds its operand to
# bfloat16 anyway. This check cannot see them, nor one key more or fewer of a
# window's 2048 (at most 1.08 times), and `BELOW_THE_NOISE_ON_THE_CHIP` names
# the four.
TOLERANCES = {
    # the loss at seeded weights, a per-example mean over 16 384 tokens: the
    # bfloat16 matmul errors of the single tokens average out and no control
    # moves it (sound 1.3e-5-8.0e-5; the bfloat16 router 5.3e-5): three times
    # the largest sound reading. The gate from the un-normed input reads
    # 2.1e-3. The step reports its one term again as `loss_ce`, held to the
    # same limit
    "loss_rel": 2.5e-4,
    "loss_ce_rel": 2.5e-4,
    # The program's router against this one ON THE SAME INPUT (the residual
    # stream the program's router saw), both float32 at the highest matmul
    # precision, at BOTH steps. Sound: 0.99960-0.99995 agree (after the
    # settling many experts sit within a float32 rounding of the threshold),
    # the weights' median error at most 1e-7. A bfloat16 router reads 0.99574
    # and 2.9e-4, the bias used as a weight 0.084 in the weights. Limits near
    # the geometric middle of the disagreeing shares (4.0e-4, 4.3e-3) and of
    # the weights' errors (1e-7, 2.9e-4)
    "router_same_input_agreement_min": 0.9988,
    "router_weight_rel_median": 5e-6,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a residual stream without the program's bfloat16 rounding
    # upstream: from settled routers a pair in a hundred flips at a near-tie
    # (sound 0.98998-0.99061; no control moves it: 0.9892, 0.9884). The
    # reference then computes with the program's choice. Limit at twice the
    # sound disagreeing share: the gate from the un-normed input reads 0.844,
    # the bias used as a weight 0.975
    "routing_agreement_min": 0.98,
    # AdamW's first moment is linear in the gradients, and every matmul of the
    # program rounds its operands to bfloat16. `default` is for every leaf but
    # the router's and the experts': sound at most 0.0141 (the q and k head
    # norms' weights; the matrices 0.006-0.009), the controls at most 0.0143:
    # three times the sound reading; the head norms behind the rotation read 0.645 in `q_norm` and `k_norm`
    # (the loss does not move: at weights of one the output is the same, the
    # gradient of the weights is not), the gate from the un-normed input
    # 0.14-0.46 in every leaf
    "mu_rel_l2": {"default": 4.2e-2,
                  # the router's gradient comes through the renormalised
                  # weights alone and is small beside its noise: sound
                  # 0.036-0.062, the controls 0.063 and 0.078; the bias
                  # used as a weight 0.100, the gate from the un-normed input
                  # 0.27. Three times the sound reading
                  "moe_router": 1.85e-1,
                  # the worst judged expert of `w_gate`, `w_up`, `w_down`:
                  # sound at most 0.047, the controls 0.049 and 0.052: three
                  # times the sound reading; the bias used as a weight 0.18,
                  # the gate from the un-normed input 0.25
                  "experts": 1.4e-1},
    # the parameter update after the steps. AdamW's first steps are
    # lr · g/|g| an element: one whose gradient is smaller than its error
    # takes the other sign and counts twice, so this figure goes as the ROOT
    # of the first moment's (≈ 0.6-1.6 x 1.13 √mu_rel_l2 on every leaf). It is
    # not float32 quantisation, as this comment once argued: with program and
    # reference at `warmup_steps` 1 (a step of 4e-4, 200 000 ulps of a weight
    # of 0.02, where the warm-up's 5e-9 is 2.7) no leaf's figure fell — embed
    # 0.121 -> 0.126, the matrices 0.045-0.080 -> 0.072-0.110, the router
    # 0.247 -> 0.343, the worst expert 0.183 -> 0.295 (seed 2147485001). The
    # norms' weights, of size one, do not move at all under the warm-up's
    # steps and read 0 on both sides. Sound: at most 0.127 (embed; the
    # matrices 0.045-0.080), the router 0.20-0.28, the worst judged expert
    # 0.17-0.20; no control moves them (at most 1.2 times), and three times
    # the sound reading would mean nothing: half again as wide as the sound
    # reading for the router and the experts, 0.25 for the rest. The gate from
    # the un-normed input reads 0.515 (embed), 0.50 and 0.46. Two steps cannot
    # see a wrong weight decay: its term is 0.1 · |p| = 0.002 of a unit step
    "update_rel_l2": {"default": 2.5e-1, "moe_router": 4.2e-1, "experts": 3.0e-1},
    # the share of the entries of the routers' state — the running sum of the
    # bias's updates, 4 x 128 entries — that differ from the reference's after
    # the steps: an expert whose load sits within a pair of the mean takes the
    # other sign when one pair flips between the step's own forward pass and
    # the routing read beside it, and moves ITS entry (under the centred form
    # it moved its layer's 128). Sound: 2-10 of 512 over ten runs at eight
    # seeds (0.0039-0.0195). The update left out reads 0.150 and mis-signed
    # 0.287 (seed 2147485001; of the two check steps' updates most cancel, the
    # settling having alternated between the same two batches), and both read
    # false by the routers on the same input as well (0.9910, 0.9818: at step
    # 2 the reference's bias is a step away from the program's). Near the
    # geometric middle of the largest sound reading and the update left out
    "bias_entries_off_share": 0.05,
}
# The experts' leaves (`w_gate`, `w_up`, `w_down`), expert by expert, all its
# layers together: an expert is judged apart only if it got at least this
# many (token, slot) pairs over the compared steps and layers; those with
# fewer are POOLED and judged as one unit (PR 30's derivation). From settled
# routers every held expert got at least 5734 pairs over the two steps and
# four sparse layers at all fourteen seeds (in one layer as few as 99 of a
# mean of 2048), so all sixteen are judged apart.
EXPERT_PAIRS_FLOOR = 1024


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names. `n_routed_experts` is what this
    chip holds (`num_experts` of the configuration), `num_experts` what the
    router chooses among, `moe_layers` the sparse layers built, as the check
    and the drivers read them."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "head_dim", "sliding_window",
            "num_experts_per_tok", "moe_intermediate_size")
    hp = {k: int(model_params[k]) for k in ints}
    hp["n_routed_experts"] = int(model_params["num_experts"])
    hp["num_experts"] = int(model_params.get("router_experts", 0)) or hp["n_routed_experts"]
    hp["first_expert"] = int(model_params.get("first_expert", 0))
    kept = model_params.get("kept_layers", "")
    hp["layers"] = (tuple(int(l) for l in kept.split(",")) if kept
                    else tuple(range(hp["num_hidden_layers"])))
    hp["num_dense_layers"] = int(model_params.get("num_dense_layers", 2))
    hp["global_attn_every_n_layers"] = int(model_params.get("global_attn_every_n_layers", 4))
    hp["rope_theta"] = float(model_params.get("rope_theta", 10000.0))
    hp["route_scale"] = float(model_params.get("route_scale", 2.826))
    hp["mup_enabled"] = bool(int(model_params.get("mup_enabled", 1)))
    hp["eps"] = float(model_params.get("rms_norm_eps", 1e-5))
    hp["moe_layers"] = sum(not is_dense(l, hp) for l in hp["layers"])
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in (
        "learning_rate", "weight_decay", "warmup_steps") if k in model_params}}
    return hp


def is_full(layer: int, hp) -> bool:
    return (layer + 1) % hp["global_attn_every_n_layers"] == 0


def is_dense(layer: int, hp) -> bool:
    return layer < hp["num_dense_layers"]


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """x (B, T, heads, D): dimension pair (i, i + D/2) of position t turned by
    the angle t · theta^(−2i/D)."""
    t, d = x.shape[1], x.shape[-1]
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] \
        * theta ** (-2.0 * jnp.arange(d // 2, dtype=jnp.float32) / d)[None, :]
    angle = angle[None, :, None, :]
    first, second = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([first * jnp.cos(angle) - second * jnp.sin(angle),
                            second * jnp.cos(angle) + first * jnp.sin(angle)], axis=-1)


def attention(p, x, full: bool, hp):
    """The attention sub-block on x (B, T, C) before its post-norm."""
    b, t, _ = x.shape
    heads, kv_heads, d = hp["num_attention_heads"], hp["num_key_value_heads"], hp["head_dim"]
    group = heads // kv_heads
    h = rms_norm(x, p["attn_norm"], hp["eps"])
    q = rms_norm((h @ p["wq"]).reshape(b, t, heads, d), p["q_norm"], hp["eps"])
    k = rms_norm((h @ p["wk"]).reshape(b, t, kv_heads, d), p["k_norm"], hp["eps"])
    v = (h @ p["wv"]).reshape(b, t, kv_heads, d)
    if not full:
        q, k = rotary(q, hp["rope_theta"]), rotary(k, hp["rope_theta"])
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
    return (out * jax.nn.sigmoid(h @ p["wg"])) @ p["wo"]


def gated_unit(h, w_gate, w_up, w_down):
    return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down


def router(p, x, bias_sum, hp):
    """(h (N, C), scores (N, E), chosen (N, E) bool): the k experts with the
    largest score + b among all E, b = a − mean(a) of the running sum a (E,)
    of the bias's updates."""
    h = rms_norm(x, p["mlp_norm"], hp["eps"]).reshape(-1, x.shape[-1])
    scores = jax.nn.sigmoid(h @ p["moe_router"])
    bias = bias_sum - jnp.mean(bias_sum)
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
    return hp["route_scale"] * scores / (total + 1e-20)


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
    """(the feed-forward's output before its post-norm, own choice (N, E), the
    weights of every expert under the reference's own choice (N, E)). `use`
    (N, E) bool, where given, takes the place of the router's own choice."""
    h, scores, own = router(p, x, bias, hp)
    taken = own if use is None else use
    weight = jnp.where(taken, slot_weights(scores, taken, hp), 0.0)
    shared = gated_unit(h, p["shared_gate"], p["shared_up"], p["shared_down"])
    return ((experts(p, h, weight, hp) + shared).reshape(x.shape), own,
            slot_weights(scores, own, hp))


_ATTN = ("attn_norm", "wq", "wk", "wv", "wg", "q_norm", "k_norm", "wo",
         "post_attn_norm", "mlp_norm", "post_mlp_norm")
_DENSE = ("mlp_gate", "mlp_up", "mlp_down")
_SPARSE = ("moe_router", "shared_gate", "shared_up", "shared_down",
           "w_gate", "w_up", "w_down")


def _layer(params, index, kind_index, layer, x, bias, use, hp):
    """The layer of published index `layer`, the `index`-th built and the
    `kind_index`-th of its feed-forward's kind: (x, own choice, weights), the
    last two None in a dense layer."""
    dense = is_dense(layer, hp)
    p = {**{k: params[k][index] for k in _ATTN},
         **{k: params[k][kind_index] for k in (_DENSE if dense else _SPARSE)}}

    def run(p, x, b, use):
        y = attention(p, x, is_full(layer, hp), hp)
        x = x + rms_norm(y, p["post_attn_norm"], hp["eps"])
        if dense:
            h = rms_norm(x, p["mlp_norm"], hp["eps"])
            y, own, weights = gated_unit(h, p["mlp_gate"], p["mlp_up"], p["mlp_down"]), None, None
        else:
            y, own, weights = moe(p, x, b, use, hp)
        return x + rms_norm(y, p["post_mlp_norm"], hp["eps"]), own, weights

    return jax.checkpoint(run)(p, x, bias, use)


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
    selection bias as the running sum of its updates, zero if not given."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    x = params["embed"][batch["tokens"]]
    if hp["mup_enabled"]:
        x = x * math.sqrt(hp["hidden_size"])
    own_all, weights_all = [], []
    dense = sparse = 0
    for index, layer in enumerate(hp["layers"]):
        if is_dense(layer, hp):
            x, _, _ = _layer(params, index, dense, layer, x, None, None, hp)
            dense += 1
        else:
            x, own, weights = _layer(params, index, sparse, layer, x, bias[sparse],
                                     None if chosen is None else chosen[sparse], hp)
            own_all.append(own)
            weights_all.append(weights)
            sparse += 1
    ce = jnp.mean(_cross_entropy(x, params["final_norm"], params["head"],
                                 batch["labels"], hp["eps"]), axis=-1)
    return ce, jnp.stack(own_all), jnp.stack(weights_all)


def routers_on(params, router_inputs, hp, bias=None):
    """Every sparse layer's router on GIVEN residual streams (L, B, T, C):
    (chosen (L, N, E) bool, the weights under that choice (L, N, E))."""
    if bias is None:
        bias = jnp.zeros((hp["moe_layers"], hp["num_experts"]), jnp.float32)
    built = [i for i, l in enumerate(hp["layers"]) if not is_dense(l, hp)]
    chosen, weights = [], []
    for sparse, index in enumerate(built):
        p = {"mlp_norm": params["mlp_norm"][index], "moe_router": params["moe_router"][sparse]}
        _, scores, own = router(p, router_inputs[sparse], bias[sparse], hp)
        chosen.append(own)
        weights.append(slot_weights(scores, own, hp))
    return jnp.stack(chosen), jnp.stack(weights)


def loss_terms(params, batch, hp, chosen=None, bias=None):
    """(the scalar the optimizer minimises, {"loss_ce"} — its one term, as the
    program's step reports it — (chosen, weights) of every sparse layer's own
    router)."""
    ce, own, weights = forward(params, batch, hp, chosen, bias)
    mask = batch["mask"].astype(jnp.float32)
    total = jnp.sum(ce * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return total, {"loss_ce": total}, (own, weights)


def loss(params, batch, hp, chosen=None, bias=None):
    total, _, own = loss_terms(params, batch, hp, chosen, bias)
    return total, own


def bias_update(bias_sum, chosen, u=LOAD_BALANCE_COEFF):
    """a + d with d_e = u · sign(mean load − load_e): the running sum (L, E)
    whose centred form `router` adds, chosen (L, N, E) bool — the choice the
    step was computed with, over all E experts."""
    load = jnp.sum(chosen, axis=1).astype(jnp.float32)
    return bias_sum + u * jnp.sign(jnp.mean(load, axis=-1, keepdims=True) - load)


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
