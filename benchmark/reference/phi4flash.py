"""The plain reference of configuration `phi-4-mini-flash` (and of any
`phi4flash` zoo model): forward pass, next-token cross entropy, gradients by
`jax.grad` and AdamW, in straightforward `jax.numpy`, float32. No kernel: the
selective scan is a `lax.scan` over time, one token a step (in checkpointed
blocks of `SCAN_BLOCK` tokens, so that its backward fits: (T, E, N_s) float32
is 2.68 GB a layer at 8192 tokens), attention is two explicit softmaxes of a
block of queries against ALL keys under a dense mask made from positions, the
logits are made in blocks of rows. The caller runs it under
`jax.default_matmul_precision("highest")`. Every matmul goes through
`product`, which rounds its operands to the configuration's `compute_dtype` —
float32: the plain einsum; bfloat16, as the cell's configuration states: the
operands of the product and of its backward's two products rounded by
`reduce_precision`, every sum float32 — so that the reference computes in the
precision the configuration states and no other.

Written from the published configuration (microsoft/Phi-4-mini-flash-reasoning
`config.json`, `model_type: phi4flash`) and the papers (SambaY
arXiv:2507.06607, Samba arXiv:2406.07522, Mamba arXiv:2312.00752 Algorithm 2,
differential attention arXiv:2410.05258), not from the zoo module. It shares
one thing with the program: the names and shapes of the parameters
(`model_zoo/transformer/phi4flash.py` lists them), so that the program's own
initial parameters are the reference's starting point. What the configuration
file lists as `assumed` is assumed here alike.

Hidden C, H query and Hkv key-value heads of D = C / H, MLP width F, window W,
eps `layer_norm_eps`; no positional encoding. `h⁰ = E[tokens]`. Layer of
PUBLISHED index i (`kept_layers` names the ones built): `x ← x + Mixer_i(LN(x;
s1, b1))`, `x ← x + MLP(LN(x; s2, b2))`, `LN(x; s, b) = (x − mean) / sqrt(var +
eps) · s + b`, `MLP(h) = (up ⊙ silu(gate)) W_down`, `(gate, up) = split(h
W_gate_up)`. After the last layer `LN(x; s_f, b_f)`, `logits = h Eᵀ`.

Mixer by published index, M = 16 (half of the published 32 layers):
- i ≤ M, even: Mamba. `(x, z) = split(h W_in)`; `x = silu(b_c + Σ_j w_j ⊙
  x_{t−K+1+j})` (zeros before the sequence); `(δ, B, Cm) = split(x W_x)` at R,
  N_s, N_s; `Δ = softplus(δ W_dt + b_dt)`; `A = −exp(A_log)`; `S_t = exp(Δ_t ⊗
  A) ⊙ S_{t−1} + (Δ_t ⊙ x_t) ⊗ B_t`, `S_0 = 0`; `y_t = S_t Cm_t + D ⊙ x_t`;
  `out = (y ⊙ silu(z)) W_out`. Layer M's y is the memory m.
- i < M odd (window W), i = M + 1 (causal; its k, v are the shared ones), i >
  M + 1 odd (cross: `q = h W_q + b_q`, k and v layer M + 1's): differential
  attention. `q, k, v = split(h W_qkv + b)`; heads 2j, 2j + 1 of q and of k
  are a pair, v's heads 2j, 2j + 1 joined one head of 2D; query pair j reads
  key-value pair j // (H / Hkv). `A¹ = softmax(q¹ k¹ᵀ / √D)`, `A² = softmax(q²
  k²ᵀ / √D)` over the visible keys (j ≤ t; under a window also j > t − W), `o
  = (A¹ − λ A²) v`, `λ = exp(λ_q1 · λ_k1) − exp(λ_q2 · λ_k2) + λ_init`,
  `λ_init = 0.8 − 0.6 exp(−0.3 i)`; `o ← o / sqrt(mean(o²) + eps) · γ · (1 −
  λ_init)` per head of 2D; the heads side by side `W_o + b_o`.
- i > M + 1 even: GMU. `out = (m ⊙ silu(h W_1)) W_2`.

Departures from a word-for-word transcription, values unchanged: each layer,
each block of `SCAN_BLOCK` tokens of a scan, each block of `QUERY_BLOCK`
queries and each block of `ROW_BLOCK` rows of the logits with their cross
entropy is recomputed in the backward pass (`jax.checkpoint`), so that 8192
tokens fit on one chip beside the parameters and their gradient.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

ADAMW = {"learning_rate": 4e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8,
         "weight_decay": 0.1, "warmup_steps": 2500}
PUBLISHED_LAYERS = 32
SCAN_BLOCK = 64
QUERY_BLOCK = 512
ROW_BLOCK = 1024


def _between(sound: float, nearest: float) -> float:
    """The geometric middle of the largest sound reading and the nearest
    reading that must fail, which is at least 1.4 times it."""
    assert nearest >= 1.4 * sound
    return (sound * nearest) ** 0.5


# Errors of the program against this reference after the cell's two check
# steps on the chip at full width, and what each must catch (my chip runs, PR
# 59; all in PERF.md §6). THE REFERENCE COMPUTES IN THE PRECISION THE
# CONFIGURATION STATES: float32 everywhere but the operands of its matmuls,
# which `product` rounds to bfloat16 forward and backward, as the program's
# are. SOUND: the largest reading of the program as it is over the seeds
# 2147483777, 2999990011, 1900000333 and 412, which the limits were first set
# from (a leaf's readings differ by at most a fifth between them), and the
# cell's own runs under those limits since. THE CONTROL every limit is read
# against: this reference's own two steps with everything the configuration
# states float32 computed in bfloat16, put in the program's place
# (`rehearse/departures_phi4flash.py::REFERENCE_CONTROLS`, seed 2147483777;
# float32 master weights and moments), and the nearest DEPARTURE or program
# control there. A limit is the geometric middle of the largest sound reading
# and the nearest of those that reads 1.4 times it or more.
TOLERANCES = {
    # The per-example mean cross entropy over 8192 positions. Sound at most
    # 1.9e-5. The control reads 2.48e-3; the memory taken after the gate
    # 2.66e-4, the second map left out 5.6e-4, the window dropped 6.2e-4, the
    # sub-norm left out 9.1e-4. (The scan's state in bfloat16 reads 4.4e-5 and
    # the dropped cotangents and biases nothing here: the moments hold those)
    "loss_rel": _between(1.9e-5, 2.66e-4),
    # AdamW's first moment after the second step. Sound, largest over the
    # seeds: 0.0119 (`mamba_in`) in every leaf but the four the scan's
    # gradient reaches alone — `mamba_x` 0.0149, `mamba_dt_w` 0.0133,
    # `mamba_dt_b` 0.0130, `mamba_A_log` 0.0113 — the biases 0.0017-0.0042.
    # THE SCAN'S STATE IN BFLOAT16 reads 0.098, 0.060, 0.124 and 0.087 in those
    # four and 0.032 in `gmu_out` (sound 0.0055), and nothing in the loss: these
    # limits alone hold the state's precision. The control reads 0.79 in
    # `mamba_x`, 0.52 in `mamba_A_log`, 0.22 in `mamba_dt_b` and `mamba_dt_w`.
    # The nearest departure in the other leaves is the GMUs' share of the
    # memory's cotangent dropped: 0.045 in `attn_wo_b`, 0.066 in `attn_qkv_b`,
    # 0.071 in `ln1_bias` (0.32 in `mamba_A_log`); the cross layers' share of
    # the shared keys' and values' dropped reads 0.17-0.28 in the attention
    # leaves, the head's share of the tied gradient dropped 0.52 in `embed`
    # alone, the LayerNorm bias dropped 1.0 in the three bias leaves alone
    "mu_rel_l2": {"default": _between(0.0119, 0.0445),
                  "mamba_x": _between(0.0149, 0.0982),
                  "mamba_dt_w": _between(0.0133, 0.0602),
                  "mamba_dt_b": _between(0.0130, 0.1235),
                  "mamba_A_log": _between(0.0113, 0.0868)},
    # The parameters' update after the two steps: AdamW's first steps are ≈
    # lr · sign(g) at step sizes of 2.5e-9 and 5e-9 (the warm-up's first two),
    # so an element whose gradient is near zero changes sign under any rounding
    # and counts twice, and a parameter of size 0.1-1 does not move at all or
    # by one unit in the last place (float32's spacing at 0.1 is 7.5e-9: the
    # norms' scales, `mamba_D` and `mamba_dt_b` read 0 on both sides, the λ
    # vectors 0 to 0.13). The precision hardly moves it, so each limit is the
    # geometric middle of the largest sound reading and 1, which is what a
    # state left unchanged reads. `attn_qkv_b` reads 0.564-0.576 at EVERY seed,
    # and that is no rounding of a gradient: the KEYS' third of the bias has no
    # gradient at all — a constant added to every key moves every score of a
    # query alike and the softmax does not see it — so its moment is rounding
    # noise on both sides (`mu_rel_l2.attn_qkv_b` 0.0027 is the other two
    # thirds'), AdamW moves each of its 1280 elements by lr along a random
    # sign, and half of them differ: sqrt(1/3 · 1/2 · 4) of a leaf whose every
    # element moved by lr reads 0.58-0.82
    "update_rel_l2": {"default": _between(0.1064, 1.0),
                      "attn_lambda": _between(0.1294, 1.0),
                      "attn_qkv_b": _between(0.576, 1.0)},
}


def hyper(model_params: dict) -> dict:
    """The sizes the reference needs, from a configuration's `model_params`
    (strings) under the published key names."""
    ints = ("vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
            "num_key_value_heads", "intermediate_size", "sliding_window")
    hp = {k: int(model_params[k]) for k in ints}
    hp["layers"] = tuple(int(i) for i in model_params["kept_layers"].split(",")) \
        if model_params.get("kept_layers") else tuple(range(hp["num_hidden_layers"]))
    hp["layer_norm_eps"] = float(model_params.get("layer_norm_eps", 1e-5))
    hp["d_state"] = int(model_params.get("mamba_d_state", 16))
    hp["dt_rank"] = -(-hp["hidden_size"] // 16)
    # what every projection, the MLP, the head and attention's products round
    # their operands to (`product`): the configuration's own statement, under
    # the program's key and default. And what everything else is computed in:
    # float32, but in the check's control
    # (`rehearse/departures_phi4flash.py::REFERENCE_CONTROLS`)
    hp["matmul_operands"] = model_params.get("compute_dtype", "bfloat16")
    hp["dtype"] = "float32"
    hp["adamw"] = {**ADAMW, **{k: float(model_params[k]) for k in ADAMW if k in model_params}}
    return hp


def _block(length: int, most: int) -> int:
    """The largest divisor of `length` that is at most `most`."""
    return next(n for n in range(min(most, length), 0, -1) if length % n == 0)


def _rounded(x, to):
    """x at dtype `to`'s bits, in its own dtype. An explicit
    `reduce_precision`: a cast to `to` and back is a pair XLA is free to drop
    (`xla_allow_excess_precision`), and on the chip it does."""
    info = jnp.finfo(to)
    return jax.lax.reduce_precision(x, exponent_bits=info.nexp, mantissa_bits=info.nmant)


@functools.cache
def _product_of_rounded(spec, to):
    """einsum `spec` whose three products — its own and the two of its
    backward pass — each read BOTH operands rounded to `to` (a, b; the
    cotangent and a rounded operand) and write what they accumulated."""
    plain = lambda a, b: jnp.einsum(spec, a, b)

    @jax.custom_vjp
    def product(a, b):
        return plain(_rounded(a, to), _rounded(b, to))

    def forward(a, b):
        a, b = _rounded(a, to), _rounded(b, to)
        return plain(a, b), (a, b)

    product.defvjp(forward, lambda kept, g: jax.vjp(plain, *kept)[1](_rounded(g, to)))
    return product


def product(spec, a, b, hp):
    """einsum `spec` of a and b as the configuration states a matmul: operands
    rounded to `hp["matmul_operands"]`, sums in the arrays' own precision,
    forward and backward. With float32 operands: the plain einsum."""
    to = jnp.dtype(hp["matmul_operands"])
    if to.itemsize >= a.dtype.itemsize:
        return jnp.einsum(spec, a, b)
    return _product_of_rounded(spec, to)(a, b)


def kind_of(i: int) -> str:
    """The mixer of the layer of published index i."""
    half = PUBLISHED_LAYERS // 2
    if i % 2 == 0:
        return "mamba" if i <= half else "gmu"
    return "sliding" if i < half else ("full" if i == half + 1 else "cross")


def layer_norm(x, scale, bias, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def mlp(p, h, hp):
    gate, up = jnp.split(product("tc,cf->tf", h, p["mlp_gate_up"], hp), 2, axis=-1)
    return product("tf,fc->tc", up * jax.nn.silu(gate), p["mlp_down"], hp)


def selective_scan(x, delta, a, b, c, d):
    """x, Δ (T, E); a (E, N); b, c (T, N); d (E,) -> y (T, E): the recurrence,
    one token a step."""
    t, e = x.shape

    def token(state, operands):
        x_t, delta_t, b_t, c_t = operands
        state = jnp.exp(delta_t[:, None] * a) * state + (delta_t * x_t)[:, None] * b_t[None, :]
        return state, state @ c_t + d * x_t

    @jax.checkpoint
    def tokens(state, operands):
        return jax.lax.scan(token, state, operands)

    size = _block(t, SCAN_BLOCK)
    blocked = lambda v: v.reshape(t // size, size, v.shape[-1])
    _, y = jax.lax.scan(tokens, jnp.zeros(a.shape, x.dtype),
                        (blocked(x), blocked(delta), blocked(b), blocked(c)))
    return y.reshape(t, e)


def mamba(p, h, hp):
    """h (T, C) of ONE sequence -> (out (T, C), the scan's output y (T, E))."""
    t = h.shape[0]
    r, n = hp["dt_rank"], hp["d_state"]
    x, z = jnp.split(product("tc,ce->te", h, p["mamba_in"], hp), 2, axis=-1)
    taps = p["mamba_conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((taps - 1, x.shape[1]), x.dtype), x])
    x = jax.nn.silu(p["mamba_conv_b"]
                    + sum(p["mamba_conv_w"][j] * padded[j:j + t] for j in range(taps)))
    delta, b, c = jnp.split(product("te,en->tn", x, p["mamba_x"], hp), [r, r + n], axis=-1)
    delta = jax.nn.softplus(product("tr,re->te", delta, p["mamba_dt_w"], hp) + p["mamba_dt_b"])
    y = selective_scan(x, delta, -jnp.exp(p["mamba_A_log"]), b, c, p["mamba_D"])
    return product("te,ec->tc", y * jax.nn.silu(z), p["mamba_out"], hp), y


def gmu(p, h, memory, hp):
    return product("te,ec->tc", memory * jax.nn.silu(product("tc,ce->te", h, p["gmu_in"], hp)),
                   p["gmu_out"], hp)


def keys_and_values(p, h, hp):
    """A self-attention layer's (k (T, Hkv, D), v (T, Hkv / 2, 2D)) from its
    normed stream h (T, C), heads in the published order."""
    t = h.shape[0]
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    d = hp["hidden_size"] // heads
    kv = (product("tc,cn->tn", h, p["attn_qkv"][:, heads * d:], hp)
          + p["attn_qkv_b"][heads * d:])
    k, v = jnp.split(kv, 2, axis=-1)
    return k.reshape(t, kv_heads, d), v.reshape(t, kv_heads // 2, 2 * d)


def diff_attention(p, h, kv, i, window, hp):
    """Differential attention of the layer of published index i: h (T, C) ->
    (T, C); `kv` the keys and values it reads (its own or layer M + 1's)."""
    t = h.shape[0]
    heads, kv_heads = hp["num_attention_heads"], hp["num_key_value_heads"]
    d = hp["hidden_size"] // heads
    if "cross_q" in p:
        q = product("tc,cn->tn", h, p["cross_q"], hp) + p["cross_q_b"]
    else:
        q = (product("tc,cn->tn", h, p["attn_qkv"][:, :heads * d], hp)
             + p["attn_qkv_b"][:heads * d])
    k, v = kv
    group = heads // kv_heads
    q = q.reshape(t, heads // 2, 2, d)                          # (T, pair, 1st | 2nd, D)
    k = jnp.repeat(k.reshape(t, kv_heads // 2, 2, d), group, axis=1)
    v = jnp.repeat(v, group, axis=1)                            # (T, pair, 2D)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * i)
    lq1, lk1, lq2, lk2 = p["attn_lambda"]
    lam = jnp.exp(jnp.sum(lq1 * lk1)) - jnp.exp(jnp.sum(lq2 * lk2)) + lam_init

    @jax.checkpoint
    def block(q_block, rows):
        keys = jnp.arange(t)[None, :]
        visible = keys <= rows[:, None]
        if window is not None:
            visible &= keys > rows[:, None] - window
        maps = []
        for which in (0, 1):
            scores = product("qhd,khd->hqk", q_block[:, :, which], k[:, :, which], hp) \
                / jnp.sqrt(jnp.asarray(d, q.dtype))
            maps.append(jax.nn.softmax(jnp.where(visible[None], scores, -jnp.inf), axis=-1))
        return product("hqk,khd->qhd", maps[0] - lam * maps[1], v, hp)

    size = _block(t, QUERY_BLOCK)
    o = jax.lax.map(lambda qr: block(*qr), (q.reshape(t // size, size, heads // 2, 2, d),
                                            jnp.arange(t).reshape(t // size, size)))
    o = o.reshape(t, heads // 2, 2 * d)
    o = o / jnp.sqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                     + hp["layer_norm_eps"]) * p["attn_subln"] * (1.0 - lam_init)
    return product("tn,nc->tc", o.reshape(t, heads * d), p["attn_wo"], hp) + p["attn_wo_b"]


_COMMON = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "mlp_gate_up", "mlp_down")
_ATTENTION = ("attn_wo", "attn_wo_b", "attn_lambda", "attn_subln")
_OWN = {
    "mamba": {"mamba": ("mamba_in", "mamba_conv_w", "mamba_conv_b", "mamba_x", "mamba_dt_w",
                        "mamba_dt_b", "mamba_A_log", "mamba_D", "mamba_out")},
    "gmu": {"gmu": ("gmu_in", "gmu_out")},
    "sliding": {"attention": _ATTENTION, "self": ("attn_qkv", "attn_qkv_b")},
    "full": {"attention": _ATTENTION, "self": ("attn_qkv", "attn_qkv_b")},
    "cross": {"attention": _ATTENTION, "cross": ("cross_q", "cross_q_b")},
}


def layers_of(params, hp):
    """[(published index, kind, the layer's own parameters)]: a kind's
    parameters are stacked over the layers that have them, in order."""
    seen, out = {}, []
    for at, i in enumerate(hp["layers"]):
        kind = kind_of(i)
        p = {k: params[k][at] for k in _COMMON}
        for stack, keys in _OWN[kind].items():
            n = seen.get(stack, 0)
            p.update({k: params[k][n] for k in keys})
            seen[stack] = n + 1
        out.append((i, kind, p))
    return out


def layer(p, x, memory, kv, i, kind, hp):
    """-> (x, the memory this layer makes or None, the (k, v) or None)."""
    eps = hp["layer_norm_eps"]
    h = layer_norm(x, p["ln1_scale"], p["ln1_bias"], eps)
    made_memory = made_kv = None
    if kind == "mamba":
        update, made_memory = mamba(p, h, hp)
    elif kind == "gmu":
        update = gmu(p, h, memory, hp)
    elif kind == "cross":
        update = diff_attention(p, h, kv, i, None, hp)
    else:
        made_kv = keys_and_values(p, h, hp)
        update = diff_attention(p, h, made_kv, i,
                                hp["sliding_window"] if kind == "sliding" else None, hp)
    x = x + update
    return x + mlp(p, layer_norm(x, p["ln2_scale"], p["ln2_bias"], eps), hp), made_memory, made_kv


def final_state(params, tokens, hp):
    """tokens (T,) of one sequence -> the normed last state (T, C)."""
    x = params["embed"][tokens]
    memory = kv = None
    for i, kind, p in layers_of(params, hp):
        x, made_memory, made_kv = jax.checkpoint(
            lambda p, x, memory, kv, i=i, kind=kind: layer(p, x, memory, kv, i, kind, hp))(
            p, x, memory if kind == "gmu" else None, kv if kind == "cross" else None)
        if i == PUBLISHED_LAYERS // 2:
            memory = made_memory
        if kind == "full":
            kv = made_kv
    return layer_norm(x, params["final_norm_scale"], params["final_norm_bias"],
                      hp["layer_norm_eps"])


def cross_entropy(state, embed, labels, hp):
    """(T, C), (V, C), (T,) -> (T,), the logits made ROW_BLOCK rows at a time
    against the embedding itself."""
    @jax.checkpoint
    def rows(h, y):
        logits = product("tc,vc->tv", h, embed, hp)
        return jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(
            logits, y[:, None], axis=-1)[:, 0]

    size = _block(state.shape[0], ROW_BLOCK)
    return jax.lax.map(lambda hy: rows(*hy), (state.reshape(-1, size, state.shape[-1]),
                                              labels.reshape(-1, size))).reshape(-1)


def loss_terms(params, batch, hp):
    """batch {"tokens" (B, T), "labels" (B, T), "mask" (B,)} -> (the loss that
    is minimised, its terms by the names the program's step reports them
    under: none beside the loss itself); the masked mean over the sequences."""
    params = {k: v.astype(hp["dtype"]) for k, v in params.items()}
    weight = (batch["mask"] / jnp.maximum(jnp.sum(batch["mask"]), 1.0)).astype(hp["dtype"])
    total = 0.0
    for b in range(batch["tokens"].shape[0]):
        state = final_state(params, batch["tokens"][b], hp)
        total = total + weight[b] * jnp.mean(
            cross_entropy(state, params["embed"], batch["labels"][b], hp))
    return total, {}


def logits(params, tokens, hp):
    """tokens (T,) -> (T, V): for the tests, at sizes where the plane fits."""
    params = {k: v.astype(hp["dtype"]) for k, v in params.items()}
    return product("tc,vc->tv", final_state(params, tokens, hp), params["embed"], hp)


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


def loss(params, batch, hp):
    """`loss_terms` as `jax.value_and_grad(..., has_aux=True)` takes it."""
    return loss_terms(params, batch, hp)
