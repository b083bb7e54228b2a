"""The plain reference of configuration `olmoe-1b-7b` (and of any `olmoe`
zoo model): forward pass, loss with both auxiliary terms, gradients by
`jax.grad`, and AdamW, in straightforward `jax.numpy`, float32. No kernel, no
sort-by-expert, no grouped matmul: every expert is applied to ALL tokens, one
after another, and the top-k mask is applied to its output. The caller runs
it under `jax.default_matmul_precision("highest")`.

Written from the published layer equations (allenai/OLMoE-1B-7B-0125-Instruct
`config.json`, `model_type: olmoe`; OLMoE, arXiv:2409.02060), not from the
zoo module. It shares one thing with the program: the names and shapes of the
parameters (`embed` (V, C); per layer, stacked on a leading layer axis,
`attn_norm`, `q_norm`, `k_norm`, `ffn_norm` (L, C), `wq`, `wk`, `wv`, `wo`
(L, C, C), `router` (L, C, E), `w_gate`, `w_up` (L, E, C, F), `w_down`
(L, E, F, C); `final_norm` (C,), `head` (C, V)), so that the program's own
initial parameters are the reference's starting point.

Departures from the source, also in the configuration file: the load-balance
term is E · Σ_e f_e · P_e with f_e the share of (token, slot) PAIRS sent to e
(the Hugging Face implementation sums the k slots' shares, k times this);
one expert's body is recomputed in the backward pass (`jax.checkpoint`) so
that 64 experts on 4096 tokens fit beside the parameters on one chip — the
values are the same.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
LOAD_BALANCE_COEF = 0.01
ROUTER_Z_COEF = 0.001

# Errors of the program against this reference after the cell's check steps
# on the chip at full width, and what each must catch. Measured figures are
# in PERF.md §6 (PR 25); the reasons below. Read again at PR 66 (fourteen
# seeds of the program; the reference with float8 matmul operands in its
# place on three: `rehearse/departures_olmoe.py`) and left as they were:
# every limit lies over the program's largest reading, the routers', the
# experts' first moments (0.019 | 0.08 | 0.122) and seven more under the
# control's smallest (PERF.md §6, PR 66, has the table).
TOLERANCES = {
    # per-example means over 4096 tokens: the bfloat16 matmul errors of the
    # single tokens average out
    "loss_rel": 2e-3,
    # The program's router against this one ON THE SAME INPUT, both float32
    # at the highest matmul precision: only a near-tie closer than float32
    # rounding flips, and a weight differs in its last bits. A bfloat16
    # router is off by 2^-9 of logits of order one (weights ≈2e-3 apart,
    # one pair in two hundred flipped); renormalised weights are five times
    # too large; a missing slot shows in the pair count
    "router_same_input_agreement_min": 0.9995,
    "router_weight_rel_median": 1e-4,
    # The program's choice against the reference's OWN forward pass, whose
    # router sees a residual stream without the program's bfloat16 rounding
    # upstream (attention's output is most of it): a pair in two hundred
    # flips at a near-tie (0.9938–0.9958 seen). The reference then computes
    # with the program's choice, so this share is the only place where a
    # flip is judged; a missing slot reads 0.875
    "routing_agreement_min": 0.98,
    # AdamW's first moment is linear in the gradients. Every matmul of the
    # program rounds its operands to bfloat16 (2^-9 relative, independent
    # per element): gradients agree to 1–2e-2, the router's and the norm's
    # before it (small leaves fed by the sum over all experts) to 7e-2 in
    # one seed of fourteen. An expert's leaf is judged
    # expert by expert, the worst held to the tolerance: an expert with a
    # handful of tokens averages less noise out. A capacity bound drops
    # pairs (an eighth of them is an error of 0.1–0.35), renormalised
    # weights scale the expert branch fivefold
    "mu_rel_l2": {"default": 6e-2, "experts": 8e-2, "router": 1.2e-1},
    # the parameter update after the check steps: AdamW's first steps are
    # ≈ lr · sign(g), so an element whose gradient is near zero changes sign
    # under rounding and counts twice; the update agrees less closely than
    # the moment, and still fails a wrong learning rate, decay or moment
    "update_rel_l2": {"default": 2.5e-1, "experts": 3e-1},
}


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "intermediate_size", "num_experts",
            "num_experts_per_tok")
    hp = {k: int(model_params[k]) for k in ints}
    hp["rms_norm_eps"] = float(model_params.get("rms_norm_eps", 1e-5))
    hp["rope_theta"] = float(model_params.get("rope_theta", 10000.0))
    return hp


def rms_norm(x, weight, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """x (B, T, H, D): out[..., i] = x_i cos(a_i) - x_{i+D/2} sin(a_i) and
    out[..., i+D/2] = x_{i+D/2} cos(a_i) + x_i sin(a_i), a_i = t · theta^(-2i/D)."""
    half = x.shape[-1] // 2
    freq = 1.0 / theta ** (jnp.arange(half, dtype=jnp.float32) / half)
    a = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freq        # (T, D/2)
    cos, sin = jnp.cos(a)[None, :, None, :], jnp.sin(a)[None, :, None, :]
    lo, hi = x[..., :half], x[..., half:]
    return jnp.concatenate([lo * cos - hi * sin, hi * cos + lo * sin], axis=-1)


def attention(p, x, hp):
    b, t, c = x.shape
    heads = hp["num_attention_heads"]
    d = c // heads
    h = rms_norm(x, p["attn_norm"], hp["rms_norm_eps"])
    q = rms_norm(h @ p["wq"], p["q_norm"], hp["rms_norm_eps"])
    k = rms_norm(h @ p["wk"], p["k_norm"], hp["rms_norm_eps"])
    v = h @ p["wv"]
    q = rotary(q.reshape(b, t, heads, d), hp["rope_theta"])
    k = rotary(k.reshape(b, t, heads, d), hp["rope_theta"])
    v = v.reshape(b, t, heads, d)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((t, t), bool))
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, t, c)
    return out @ p["wo"]


def router(p, x, hp):
    """(h (N, C), logits (N, E), probs (N, E), chosen (N, E) bool): the k
    largest probabilities of each token."""
    h = rms_norm(x, p["ffn_norm"], hp["rms_norm_eps"]).reshape(-1, x.shape[-1])
    logits = h @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    # exactly k a token: of equal probabilities the lower expert id first, as
    # a sort breaks ties. Two of a token's probabilities do come out equal in
    # float32: at seed 157194244 the eighth and ninth of one token of 8192
    # did, `probs >= the eighth largest` chose nine, and the pair count read
    # 65 537 against the program's 65 536 (PERF.md §6, PR 66)
    by_rank = jnp.argsort(-probs, axis=-1, stable=True)
    rank = jnp.argsort(by_rank, axis=-1)
    return h, logits, probs, rank < hp["num_experts_per_tok"]


def experts(p, h, weight):
    """Σ_e weight[:, e] · W_down,e( silu(W_gate,e h) ⊙ W_up,e h ), every
    expert on every token; `weight` is zero where the expert was not chosen."""

    @jax.checkpoint
    def one(w_gate, w_up, w_down, w_col):
        return w_col[:, None] * ((jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down)

    def add(total, per_expert):
        return total + one(*per_expert), None

    total, _ = jax.lax.scan(
        add, jnp.zeros_like(h), (p["w_gate"], p["w_up"], p["w_down"], weight.T))
    return total


def forward(params, tokens, hp, chosen=None):
    """tokens (B, T) -> (logits (B, T, V), {"load_balance", "router_z"}
    summed over layers, and per layer the router's OWN choice (L, N, E) bool
    and its probabilities (L, N, E)).

    `chosen` (L, N, E) bool, where given, takes the place of the router's own
    choice of experts — the probabilities stay the reference's. The check
    passes the program's choice: a pair that flips at a near-tie is rounding,
    and is judged by the share of agreeing pairs, not by the gradients of an
    expert that got another token."""
    x = params["embed"][tokens]
    balance, z, chosen_all, probs_all = 0.0, 0.0, [], []
    for layer in range(hp["num_hidden_layers"]):
        p = _layer(params, layer)
        x = x + attention(p, x, hp)
        h, logits, probs, own = router(p, x, hp)
        use = own if chosen is None else chosen[layer]
        # the weights are the probabilities as they are: norm_topk_prob false
        weight = jnp.where(use, probs, 0.0)
        x = x + experts(p, h, weight).reshape(x.shape)
        share = jnp.sum(use, axis=0) / jnp.sum(use).astype(jnp.float32)
        balance += hp["num_experts"] * jnp.sum(share * jnp.mean(probs, axis=0))
        z += jnp.mean(jnp.square(jax.nn.logsumexp(logits, axis=-1)))
        chosen_all.append(own)
        probs_all.append(probs)
    logits = rms_norm(x, params["final_norm"], hp["rms_norm_eps"]) @ params["head"]
    aux = {"load_balance": balance, "router_z": z}
    return logits, aux, jnp.stack(chosen_all), jnp.stack(probs_all)


def _layer(params, layer):
    return {k: v[layer] for k, v in params.items()
            if k not in ("embed", "final_norm", "head")}


def routers_on(params, router_inputs, hp):
    """Every layer's router on GIVEN residual streams (L, B, T, C): (chosen
    (L, N, E) bool, probs (L, N, E)). For the comparison of the program's
    router with this one on the same input."""
    chosen, probs = [], []
    for layer in range(hp["num_hidden_layers"]):
        _, _, p, c = router(_layer(params, layer), router_inputs[layer], hp)
        chosen.append(c)
        probs.append(p)
    return jnp.stack(chosen), jnp.stack(probs)


def loss(params, batch, hp, chosen=None):
    """batch {"tokens" (B, T), "labels" (B, T), "mask" (B,)} -> (the scalar
    the optimizer minimises, (chosen, probs) of every layer's own router)."""
    logits, aux, own, probs = forward(params, batch["tokens"], hp, chosen)
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, batch["labels"][..., None], axis=-1)[..., 0]
    per_example = jnp.mean(nll, axis=-1)
    mask = batch["mask"].astype(jnp.float32)
    ce = jnp.sum(per_example * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    total = (ce + LOAD_BALANCE_COEF * aux["load_balance"]
             + ROUTER_Z_COEF * aux["router_z"])
    return total, (own, probs)


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
