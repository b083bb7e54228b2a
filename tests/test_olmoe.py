"""OLMoE (model_zoo/transformer/olmoe.py, ops/moe.py dropless dispatch)
against its plain reference (benchmark/reference/olmoe.py) on seeded weights,
at a tiny size on the CPU: dim 64, 4 heads of 16, 8 experts top-2 of width 32,
2 layers, 32 tokens, vocabulary 256, float32.

The comparison is the benchmark's own (`benchmark/check_lm.py`), so the
cases at the bottom hold it to its purpose: each departure the cell's check
must catch on the chip — a bfloat16 router, renormalised top-k weights,
top-(k-1), a capacity bound — is patched into the program here and the
comparison must FAIL, under the chip's own tolerances.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from elasticdl_tpu.ops import moe as moe_ops
from elasticdl_tpu.parallel.mesh import shard_batch_stack
from tests import zoo_lm

TINY = {"vocab_size": 256, "hidden_size": 64, "num_hidden_layers": 2,
        "num_attention_heads": 4, "intermediate_size": 32, "num_experts": 8,
        "num_experts_per_tok": 2, "compute_dtype": "float32"}
LEAVES = ("embed", "attn_norm", "q_norm", "k_norm", "ffn_norm", "wq", "wk",
          "wv", "wo", "router", "w_gate", "w_up", "w_down", "final_norm", "head")
# float32 against float32: the only differences are the order of sums
TIGHT = {"loss_rel": 1e-5, "routing_agreement_min": 1.0,
         "router_same_input_agreement_min": 1.0, "router_weight_rel_median": 1e-5,
         "mu_rel_l2": {"default": 1e-4}, "update_rel_l2": {"default": 2e-3}}

reference = common.load_module("reference", "olmoe")

# the seed's own parameters (no rule of `lively`), and no driver: this cell's
# check is older than `program_check` and `departures_*.py`, so the file keeps
# its own `two_steps_compared` and its own patches
lm = zoo_lm.ZooLM(
    "olmoe", tiny=TINY, reference=reference, seq=32, mutable=("losses",), training=True,
    sown={"loss_balance": "load_balance", "loss_z": "router_z"})


def zoo():
    return lm.zoo


def two_steps_compared(tolerances, router_scale=1.0, trainer_of=lm.trainer):
    """The benchmark's check, as `drivers/resident_lm.py` drives it; a case
    that has patched the program hands in `lm.fresh_trainer`."""
    spec, trainer = trainer_of()
    data = lm.batches()
    state = lm.state()
    # router logits of order one, as at the published width (2048-wide tokens
    # against normal(0.02) weights), where rounding them matters
    state = state.replace(params={
        **state.params, "router": state.params["router"] * router_scale})
    checker = check_lm.LMStepCheck(reference, lm.tiny_params(), data)
    checker.before(state)
    assignments = jax.jit(lambda p, t: zoo().expert_assignments(p, t, spec.model.cfg))
    losses, routings = [], []
    for batch in data:
        routings.append(jax.device_get(assignments(state.params, batch["features"])))
        state, m = trainer.train_many(state, shard_batch_stack(
            trainer.mesh, [batch], spec.batch_partition))
        losses.append(np.asarray(m["loss"]))
    checker.read_program(state, np.concatenate(losses), routings)
    return check_lm.compare(checker.got, checker.reference_steps(),
                            checker.params0, tolerances)


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss and gradients of one batch from the
    same seeded parameters."""
    ((got, _), got_grads), ((want, _), want_grads) = lm.gradients(
        lambda p, batch, hp: (reference.loss(p, batch, hp)[0], {}))
    return (got, got_grads), (want, want_grads)


def test_loss_matches_reference(gradients):
    (got, _), (want, _) = gradients
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    assert float(want) > np.log(TINY["vocab_size"]) - 0.5     # untrained, + aux


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert set(got) == set(LEAVES)
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_two_adamw_steps_match_reference():
    verdict = two_steps_compared(TIGHT)
    assert verdict["ok"], verdict["failures"]
    assert verdict["figures"]["experts_compared"] == TINY["num_experts"]
    assert verdict["figures"]["leaves_compared"] == len(LEAVES)


# ------------------------------------------------------------------ #
# the dispatch alone, against the loop-over-experts form


def loop_over_experts(x, expert_idx, weights, w_gate, w_up, w_down):
    """y_n = Σ_slot weights[n, slot] · expert_{idx[n, slot]}(x_n): every
    expert applied to every token, a mask on its output."""
    y = jnp.zeros_like(x)
    for e in range(w_gate.shape[0]):
        out = (jax.nn.silu(x @ w_gate[e]) * (x @ w_up[e])) @ w_down[e]
        y = y + jnp.sum(jnp.where(expert_idx == e, weights, 0.0), axis=1)[:, None] * out
    return y


def routings(n, k, e):
    even = np.stack([(np.arange(n) * k + s) % e for s in range(k)], axis=1)
    one = np.full((n, k), 3)                    # both slots of every token
    empty = np.where(even == 5, 6, even)        # expert 5 gets no token
    return {"even": even, "all_on_one_expert": one, "an_expert_without_tokens": empty}


@pytest.mark.parametrize("direction", ["forward", "backward"])
@pytest.mark.parametrize("routing", ["even", "all_on_one_expert",
                                     "an_expert_without_tokens"])
def test_dropless_dispatch_matches_loop_over_experts(routing, direction):
    n, c, f, e, k = 24, 16, 8, 8, 2
    r = np.random.default_rng(0)
    x = jnp.asarray(r.normal(size=(n, c)), jnp.float32)
    w = [jnp.asarray(r.normal(size=s) * 0.3, jnp.float32)
         for s in ((e, c, f), (e, c, f), (e, f, c))]
    weights = jnp.asarray(r.uniform(0.1, 1.0, size=(n, k)), jnp.float32)
    idx = jnp.asarray(routings(n, k, e)[routing], jnp.int32)
    if direction == "forward":
        got = moe_ops.dropless_moe(x, idx, weights, tuple(w), compute_dtype=jnp.float32)
        want = loop_over_experts(x, idx, weights, *w)
        np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
        return
    probe = jnp.asarray(r.normal(size=(n, c)), jnp.float32)

    def scalar(fn):
        return lambda x, weights, *w: jnp.sum(probe * fn(x, idx, weights, *w))

    got = jax.grad(scalar(lambda x, idx, weights, *w: moe_ops.dropless_moe(
        x, idx, weights, w, compute_dtype=jnp.float32)), argnums=(0, 1, 2, 3, 4))(x, weights, *w)
    want = jax.grad(scalar(loop_over_experts), argnums=(0, 1, 2, 3, 4))(x, weights, *w)
    for g, h in zip(got, want):
        np.testing.assert_allclose(g, h, rtol=1e-4, atol=1e-5)
    if routing == "an_expert_without_tokens":
        assert not np.any(np.asarray(got[2][5]))        # its weights get no gradient


def test_topk_weights_are_not_renormalised_and_aux_losses_by_hand():
    logits = jnp.asarray(np.random.default_rng(2).normal(size=(16, 8)), jnp.float32)
    probs, weights, idx = moe_ops.topk_route(logits, 2)
    p = np.exp(logits) / np.exp(logits).sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)[:, :2]
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(order, -1))
    np.testing.assert_allclose(weights, np.take_along_axis(p, order, -1), rtol=1e-6)
    assert np.all(weights.sum(-1) < 1.0)                # norm_topk_prob: false
    balance, z = moe_ops.router_aux_losses(logits, probs, idx)
    share = np.bincount(np.asarray(idx).ravel(), minlength=8) / idx.size
    np.testing.assert_allclose(balance, 8 * np.sum(share * p.mean(0)), rtol=1e-6)
    np.testing.assert_allclose(
        z, np.mean(np.log(np.exp(logits).sum(-1)) ** 2), rtol=1e-5)


# ------------------------------------------------------------------ #
# rotary positions and the full-width QK-norm, each against a hand formula


def test_rope_against_hand_formula():
    b, t, h, d = 1, 5, 2, 8
    x = np.random.default_rng(3).normal(size=(b, t, h, d)).astype(np.float32)
    want = np.zeros_like(x)
    for pos in range(t):
        for i in range(d // 2):
            a = pos * 10000.0 ** (-2.0 * i / d)
            lo, hi = x[:, pos, :, i], x[:, pos, :, i + d // 2]
            want[:, pos, :, i] = lo * np.cos(a) - hi * np.sin(a)
            want[:, pos, :, i + d // 2] = hi * np.cos(a) + lo * np.sin(a)
    np.testing.assert_allclose(zoo().rope(jnp.asarray(x), 10000.0), want,
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("norm_over", ["the_whole_width", "each_head"])
def test_qk_norm_is_over_the_whole_width(norm_over):
    """`attention` against a numpy twin whose q and k are normalised over all
    C = 64 columns before the split into heads; the per-head twin must NOT
    agree."""
    m = zoo()
    cfg = m.Config(**TINY)
    r = np.random.default_rng(4)
    c, heads, t = 64, 4, 6
    p = {k: r.normal(size=(c, c)).astype(np.float32) * 0.2 for k in ("wq", "wk", "wv", "wo")}
    p.update({k: r.uniform(0.5, 1.5, size=(c,)).astype(np.float32)
              for k in ("attn_norm", "q_norm", "k_norm")})
    x = r.normal(size=(1, t, c)).astype(np.float32)

    def rms(v, w, axis_size):
        v = v.reshape(v.shape[:-1] + (-1, axis_size))
        v = v / np.sqrt(np.mean(v * v, -1, keepdims=True) + 1e-5)
        return v.reshape(v.shape[:-2] + (-1,)) * w

    width = c if norm_over == "the_whole_width" else c // heads
    hidden = rms(x, p["attn_norm"], c)
    q = rms(hidden @ p["wq"], p["q_norm"], width).reshape(1, t, heads, -1)
    k = rms(hidden @ p["wk"], p["k_norm"], width).reshape(1, t, heads, -1)
    v = (hidden @ p["wv"]).reshape(1, t, heads, -1)
    q, k = np.asarray(m.rope(jnp.asarray(q), 10000.0)), np.asarray(m.rope(jnp.asarray(k), 10000.0))
    s = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(c // heads)
    s = np.where(np.tril(np.ones((t, t), bool)), s, -np.inf)
    prob = np.exp(s - s.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    want = np.einsum("bhqk,bkhd->bqhd", prob, v).reshape(1, t, c) @ p["wo"]
    got = np.asarray(m.attention({k: jnp.asarray(a) for k, a in p.items()},
                                 jnp.asarray(x), cfg))
    close = np.allclose(got, want, rtol=1e-4, atol=1e-5)
    assert close == (norm_over == "the_whole_width")


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1)        # the full step size at once
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params())
    assert model.cfg == spec.model.cfg
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert losses[-1] < losses[0] - 0.3


# ------------------------------------------------------------------ #
# what the cell's check must catch, under the chip's tolerances


def _bf16_router(monkeypatch):
    m = zoo()

    def route(p, x, cfg):
        h = m.rmsnorm(x, p["ffn_norm"], cfg.rms_norm_eps).reshape(-1, x.shape[-1])
        logits = jnp.dot(h.astype(jnp.bfloat16),
                         p["router"].astype(jnp.bfloat16)).astype(jnp.float32)
        return (h, logits) + moe_ops.topk_route(logits, cfg.num_experts_per_tok)

    monkeypatch.setattr(m, "route", route)


def _renormalised(monkeypatch):
    plain = moe_ops.topk_route

    def topk_route(logits, k):
        probs, weights, idx = plain(logits, k)
        return probs, weights / jnp.sum(weights, axis=-1, keepdims=True), idx

    monkeypatch.setattr(moe_ops, "topk_route", topk_route)


def _one_slot_short(monkeypatch):
    plain = moe_ops.topk_route
    monkeypatch.setattr(moe_ops, "topk_route", lambda logits, k: plain(logits, k - 1))


def _capacity_bound(monkeypatch):
    plain = moe_ops.dropless_moe

    def capped(x, expert_idx, weights, *rest, **kw):
        n, k = expert_idx.shape
        e = rest[0][0].shape[0]
        cap = n * k // e                                # capacity factor 1.0
        hit = jax.nn.one_hot(expert_idx.reshape(-1), e, dtype=jnp.int32)
        place = jnp.sum((jnp.cumsum(hit, axis=0) - 1) * hit, axis=-1).reshape(n, k)
        return plain(x, expert_idx, jnp.where(place < cap, weights, 0.0), *rest, **kw)

    monkeypatch.setattr(moe_ops, "dropless_moe", capped)


DEPARTURES = {"a_bfloat16_router": _bf16_router,
              "renormalised_weights": _renormalised,
              "top_k_minus_one": _one_slot_short,
              "a_capacity_bound": _capacity_bound}


@pytest.mark.parametrize("departure", [None] + sorted(DEPARTURES))
def test_the_check_fails_on(departure, monkeypatch):
    if departure:
        DEPARTURES[departure](monkeypatch)
    verdict = two_steps_compared(reference.TOLERANCES, router_scale=6.0,
                                 trainer_of=lm.fresh_trainer if departure else lm.trainer)
    assert verdict["ok"] == (departure is None), (verdict["failures"], verdict["figures"])
