"""Phi-4-mini-flash (model_zoo/transformer/phi4flash.py: Mamba-1 mixers,
differential attention under a window, full and across layers, Gated Memory
Units, one matrix that is embedding and head) against its plain reference
(benchmark/reference/phi4flash.py) on seeded weights, at a tiny size on the
CPU: hidden 64, 4 query heads over 2 key-value heads of 16, 128 channels of 16
state indices, an MLP of 96, vocabulary 256, window 8, 40 tokens, float32 —
the benchmark's six kept layers (published 0, 1, 16, 17, 18, 19).

ALL 32 published layers at narrower widths (the memory has 7 readers there and
the shared keys and values 8), the zoo contract, the counters, the scopes and
the departures the check must catch are in `tests/test_phi4flash_contract.py`:
a file of its own so that two xdist workers share the model's cases.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tests import zoo_lm

TINY = zoo_lm.preset("tiny-lm-sambay.json")
NORMS = ("ln1_scale", "ln1_bias", "ln2_scale", "ln2_bias", "final_norm_scale",
         "final_norm_bias", "attn_subln")
BIASES = ("attn_qkv_b", "attn_wo_b", "cross_q_b", "mamba_conv_b")
MATRICES = ("mlp_gate_up", "mlp_down", "mamba_in", "mamba_x", "mamba_out", "gmu_in",
            "gmu_out", "attn_qkv", "cross_q", "attn_wo")
LEAVES = NORMS + BIASES + MATRICES + (
    "embed", "attn_lambda", "mamba_conv_w", "mamba_dt_w", "mamba_dt_b", "mamba_A_log",
    "mamba_D")
COUNTERS = ("s6", "memory", "shared_kv", "diff_attn", "attn")
# every published layer, at widths a CPU compiles in seconds
ALL_LAYERS = dict(num_hidden_layers=32, kept_layers="", hidden_size=32, intermediate_size=48,
                  vocab_size=64, mamba_d_state=8)

reference = common.load_module("reference", "phi4flash")
flops = common.load_module("flops", "phi4flash")
driver = common.load_module("drivers", "resident_lm_plain")
departures = common.load_module("rehearse", "departures_phi4flash")

lm = zoo_lm.ZooLM(
    "phi4flash", tiny=TINY, reference=reference, driver=driver, departures=departures, seq=40,
    mutable=COUNTERS, training=True,
    # LayerNorm scales away from one and biases away from zero, projections'
    # biases that are not the zeros they start as, mixers whose output is not
    # a rounding of the stream, λ vectors large enough for λ to leave λ_init
    lively=[(tuple(n for n in NORMS if "bias" not in n), zoo_lm.jittered),
            (tuple(n for n in NORMS if "bias" in n) + BIASES[:3], zoo_lm.drawn(0.1)),
            (MATRICES, zoo_lm.scaled(4.0)),
            (("attn_lambda",), zoo_lm.scaled(2.0))])


def zoo():
    return lm.zoo


def reference_loss(p, batch, hp):
    total, terms = reference.loss_terms(p, batch, hp)
    return total, {"loss": total, **terms}


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss and gradients of one batch from the same
    lively parameters, the six kept layers."""
    return lm.gradients(reference_loss)


def test_loss_matches_reference(gradients):
    ((_, got), _), ((_, want), _) = gradients
    np.testing.assert_allclose(float(got["loss"]), float(want["loss"]), rtol=2e-6)


def assert_leaf_matches(got, want, leaf, limit=2e-5):
    want_leaf = np.asarray(want[leaf])
    assert np.linalg.norm(want_leaf) > 0                 # every leaf is reached
    error = np.linalg.norm(np.asarray(got[leaf]) - want_leaf) / np.linalg.norm(want_leaf)
    assert error < limit, (leaf, error)


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradients_match_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert_leaf_matches(got, want, leaf)


# ------------------------------------------------------------------ #
# two AdamW steps through the benchmark's own check


@pytest.fixture(scope="module")
def verdict():
    return lm.run_check()["figures"]


def test_two_adamw_steps_match_the_reference_s_losses(verdict):
    assert verdict["loss_rel"] < 5e-6 and verdict["leaves_compared"] == len(LEAVES)


@pytest.mark.parametrize("leaf", LEAVES)
def test_two_adamw_steps_match_the_reference_s_moments_and_updates(verdict, leaf):
    assert verdict[f"mu_rel_l2.{leaf}"] < 5e-5
    # AdamW's first steps are ≈ lr · sign(g): an element whose gradient is
    # near zero flips under float32's own rounding and counts twice
    assert verdict[f"update_rel_l2.{leaf}"] < 0.05


# ------------------------------------------------------------------ #
# each kind of layer alone


def one_layer(kind):
    """(cfg, hp, the parameters of the first layer of `kind` among the kept
    six, its published index)."""
    cfg = lm.trainer()[0].model.cfg
    i, p = next((i, p) for i, p in zoo().layer_parameters(lm.params(), cfg)
                if zoo().layer_kind(i) == kind)
    return cfg, reference.hyper(lm.tiny_params()), p, i


@pytest.mark.parametrize("kind", ("mamba", "sliding", "full", "gmu", "cross"))
def test_a_layer_of_each_kind_matches_the_reference_s(kind):
    cfg, hp, p, i = one_layer(kind)
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    t, heads, d = 24, cfg.num_key_value_heads, cfg.head_dim
    x = jax.random.normal(keys[0], (1, t, cfg.hidden_size))
    memory = jax.random.normal(keys[1], (1, t, cfg.d_inner))
    k = jax.random.normal(keys[2], (1, t, heads, d))            # published head order
    v = jax.random.normal(keys[3], (1, t, heads // 2, 2 * d))
    with jax.default_matmul_precision("highest"):
        got, made = zoo().layer(p, x, cfg, i, memory, (zoo().pairs_apart(k), v))
        want, want_memory, want_kv = reference.layer(p, x[0], memory[0], (k[0], v[0]), i, kind, hp)
    np.testing.assert_allclose(got[0], want, rtol=2e-5, atol=2e-5)
    if kind == "mamba":
        np.testing.assert_allclose(made[0], want_memory, rtol=2e-5, atol=2e-5)
    elif kind == "full":
        # the program hands on k with the first-of-pair heads first
        np.testing.assert_allclose(made[0][0], zoo().pairs_apart(want_kv[0][None])[0],
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(made[1][0], want_kv[1], rtol=2e-5, atol=2e-5)
    else:
        assert made is None and want_memory is None


# ------------------------------------------------------------------ #
# the tied matrix


def test_the_tied_gradient_is_the_sum_of_an_untied_pair_s(monkeypatch):
    spec, _ = lm.trainer()
    batch, params, cfg = lm.batches(steps=1)[0], lm.params(), spec.model.cfg
    head = zoo().head_logits

    def loss_of(take, head_matrix):
        monkeypatch.setattr(zoo(), "head_logits", lambda h, _, dt: head(h, head_matrix, dt))
        logits = zoo().forward({**params, "embed": take}, batch["features"], cfg)
        return jnp.mean(zoo().loss(batch["labels"], logits))

    embed = params["embed"]
    of_take, of_head = jax.grad(loss_of, argnums=(0, 1))(embed, embed)
    monkeypatch.setattr(zoo(), "head_logits", head)
    tied = jax.grad(lambda e: jnp.mean(zoo().loss(
        batch["labels"], zoo().forward({**params, "embed": e}, batch["features"], cfg))))(embed)
    assert float(jnp.linalg.norm(of_take)) > 0 and float(jnp.linalg.norm(of_head)) > 0
    np.testing.assert_allclose(tied, of_take + of_head, rtol=1e-5,
                               atol=1e-6 * float(jnp.max(jnp.abs(tied))))


# ------------------------------------------------------------------ #
# what follows the PUBLISHED index


KINDS_PUBLISHED = (["mamba", "sliding"] * 8 + ["mamba", "full"] + ["gmu", "cross"] * 7)


@pytest.mark.parametrize("i", range(32))
def test_the_kind_and_lambda_init_follow_the_published_index(i):
    assert zoo().layer_kind(i) == KINDS_PUBLISHED[i] == reference.kind_of(i) == flops.kind_of(i)
    assert zoo().lambda_init(i) == pytest.approx(0.8 - 0.6 * math.exp(-0.3 * i))


def test_a_cut_that_drops_a_producer_is_refused():
    for kept in ("0,1,18", "0,1,16,19"):           # a GMU without 16, a cross layer without 17
        with pytest.raises(ValueError, match="keep that layer"):
            zoo().custom_model(**lm.tiny_params(num_hidden_layers=len(kept.split(",")),
                                                kept_layers=kept))


# ------------------------------------------------------------------ #
# the window's edges


@pytest.mark.parametrize("back,sees", [(TINY["sliding_window"] - 1, True),
                                       (TINY["sliding_window"], False),
                                       (TINY["sliding_window"] + 1, False)],
                         ids=["W-1", "W", "W+1"])
def test_a_query_sees_the_key_w_minus_one_back_and_not_the_one_w_back(back, sees):
    """Key j is visible to query t iff t − W < j ≤ t: a change of the stream
    at position j reaches the attention's output at t = j + back through k
    and v iff back < W (and no position before j, whatever the window)."""
    cfg, _, p, i = one_layer("sliding")
    h = jax.random.normal(jax.random.PRNGKey(11), (1, 32, cfg.hidden_size))
    j = 5
    moved = h.at[0, j].add(1.0)
    attend = lambda h: zoo().diff_attention(p, h, cfg, i, window=cfg.sliding_window)[0]
    change = np.abs(np.asarray(attend(moved) - attend(h)))[0].max(axis=-1)      # (T,)
    assert np.all(change[:j] == 0) and change[j] > 1e-4
    assert (change[j + back] > 1e-6) == sees
