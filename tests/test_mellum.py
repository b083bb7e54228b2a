"""Mellum2 (model_zoo/transformer/mellum.py: sliding-window and full layers
under two rotary tables, grouped-query heads, a held share of softmax-routed
gated-SiLU experts with renormalised weights) against its plain reference
(benchmark/reference/mellum.py) on seeded weights, at a tiny size on the CPU:
hidden 48, four layers (three sliding with a window of 8, one full), 4/2
heads of 16, 16 experts top-3 of which experts 4-7 are held, vocabulary 256,
36 tokens, float32.

The benchmark's own comparison, and the departures it must catch, are in
`tests/test_mellum_check.py`.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import check_lm, common
from elasticdl_tpu.ops import pallas_attention
from tests import zoo_lm
from tests.conftest import pallas_calls

TINY = zoo_lm.preset("tiny-lm-mellum.json")
LEAVES = ("embed", "final_norm", "head", "attn_norm", "wq", "wk", "wv", "wo",
          "moe_norm", "moe_router", "w_gate", "w_up", "w_down")

reference = common.load_module("reference", "mellum")
flops = common.load_module("flops", "mellum")
driver = common.load_module("drivers", "resident_lm_stateless")
departures = common.load_module("rehearse", "departures_mellum")

lm = zoo_lm.ZooLM(
    "mellum", tiny=TINY, reference=reference, driver=driver, departures=departures,
    seq=36, mutable=("losses", "router_state", "attn"), sown={"loss_aux": "load_balance"},
    # router logits of order one, every norm's weight away from one,
    # projections large enough that attention's softmax is far from a running
    # mean, so that positions — the window and the two tables — matter; what a
    # sub-block writes is as large as the stream it writes to (embedding: one)
    lively=[(("moe_router",), zoo_lm.scaled(8.0)),
            (("final_norm", "attn_norm", "moe_norm"), zoo_lm.jittered),
            (("wq", "wk", "wv", "w_gate", "w_up"), zoo_lm.scaled(6.0)),
            (("wo", "w_down"), zoo_lm.scaled(45.0))],
    # the check's cases run ONE period of two layers (a sliding one, a full
    # one): both kinds, and half the compile time of the tiny preset's four
    short={"num_hidden_layers": 2, "sliding_period": 2})


def zoo():
    return lm.zoo


@pytest.fixture(scope="module")
def gradients():
    """(program's, reference's) loss terms and gradients of one batch from
    the same lively parameters."""
    return lm.gradients(lambda p, batch, hp: reference.loss_terms(p, batch, hp)[:2])


# ------------------------------------------------------------------ #
# the two rotary tables, by hand


def published():
    return zoo().Config()


def test_the_yarn_ramp_runs_from_dimension_18_to_35():
    cfg = published()
    d, theta = cfg.head_dim, cfg.rope_theta
    dim = lambda turns: d * math.log(8192 / (2 * math.pi * turns)) / (2 * math.log(theta))
    assert (round(dim(32), 2), round(dim(1), 2)) == (18.08, 34.98)
    assert (math.floor(dim(32)), math.ceil(dim(1))) == (18, 35)
    ratio = np.asarray(zoo().yarn_inv_freq(cfg)) / (theta ** (-np.arange(64) * 2.0 / d))
    np.testing.assert_allclose(ratio[:19], 1.0, rtol=1e-6)        # i <= 18 unscaled
    np.testing.assert_allclose(ratio[35:], 1 / 16, rtol=1e-6)     # i >= 35 divided by 16
    assert np.all(np.diff(ratio[18:36]) < 0)                      # the blend in between
    np.testing.assert_allclose(ratio[20], 1 - (2 / 17) * (15 / 16), rtol=1e-6)


def test_the_attention_factor_is_a_tenth_of_ln_16_plus_one():
    assert published().attention_factor == pytest.approx(0.1 * math.log(16) + 1, abs=1e-15)


@pytest.mark.parametrize("kind", ["sliding", "full"])
def test_rotary_table_by_hand(kind):
    cfg = published()
    cos, sin = zoo().rotary_tables(cfg, 40)[kind]
    assert cos.shape == sin.shape == (1, 40, 1, 128)
    t, i = 37, 50                                  # a slow dimension: i >= 35
    freq = 500000.0 ** (-2 * i / 128)
    factor = 1.0
    if kind == "full":
        freq, factor = freq / 16, 1.2772588722239782
    for column in (i, i + 64):                     # rotate-half: both halves alike
        np.testing.assert_allclose(cos[0, t, 0, column], factor * math.cos(t * freq), rtol=1e-5)
        np.testing.assert_allclose(sin[0, t, 0, column], factor * math.sin(t * freq), rtol=1e-5)
    # a fast dimension keeps its frequency in both tables; the full one scales
    np.testing.assert_allclose(cos[0, 3, 0, 2], factor * math.cos(3 * 500000.0 ** (-4 / 128)),
                               rtol=1e-5)


def test_the_plain_table_is_olmoe_s_rope():
    from model_zoo.transformer.olmoe import rope

    cfg = published()
    x = jnp.asarray(np.random.default_rng(0).normal(size=(1, 24, 2, 128)), jnp.float32)
    np.testing.assert_allclose(zoo().rotate(x, zoo().rotary_tables(cfg, 24)["sliding"]),
                               rope(x, cfg.rope_theta), rtol=1e-5, atol=1e-6)


def test_reference_tables_are_the_program_s():
    cfg = published()
    hp = reference.hyper({k: str(getattr(cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")})
    x = jnp.asarray(np.random.default_rng(1).normal(size=(1, 33, 2, 128)), jnp.float32)
    tables = zoo().rotary_tables(cfg, 33)
    for kind in ("sliding", "full"):
        np.testing.assert_allclose(zoo().rotate(x, tables[kind]),
                                   reference.rotary(x, kind == "full", hp),
                                   rtol=2e-5, atol=2e-5)


def test_layers_are_three_sliding_to_one_full():
    cfg = published()
    kinds = [cfg.kind(l) for l in range(28)]
    assert kinds == ["sliding", "sliding", "sliding", "full"] * 7
    hp = {"sliding_period": 4}
    assert [reference.is_full(l, hp) for l in range(28)] == [k == "full" for k in kinds]


# ------------------------------------------------------------------ #
# the model against the reference


@pytest.mark.parametrize("term", ["loss", "loss_ce", "loss_aux"])
def test_loss_terms_match_reference(gradients, term):
    ((total, got), _), ((ref_total, want), _) = gradients
    got, want = ({**got, "loss": total}[term], {**want, "loss": ref_total}[term])
    assert abs(float(got) - float(want)) < 1e-5 * abs(float(want))
    if term != "loss_aux":
        assert float(want) > np.log(TINY["vocab_size"]) - 0.5          # untrained


@pytest.mark.parametrize("leaf", LEAVES)
def test_gradient_leaf_matches_reference(gradients, leaf):
    (_, got), (_, want) = gradients
    assert got[leaf].shape == want[leaf].shape
    assert np.linalg.norm(want[leaf]) > 0
    assert check_lm._rel_l2(np.asarray(got[leaf]), np.asarray(want[leaf])) < 1e-4


def test_the_window_and_the_tables_matter_at_the_tiny_size(gradients):
    """The comparison above would prove little if a full causal mask gave the
    same loss: with the window taken away the program's loss moves."""
    batch, params = lm.batches(steps=1)[0], lm.params()
    plain = float(lm.program_terms()(params, batch)["loss_ce"])
    spec, _ = lm.fresh_trainer()
    with departures.applied("window_one_key_long", zoo()):
        longer = float(lm.terms(spec, params, batch)["loss_ce"])
    assert abs(plain - longer) > 1e-4 * plain
    ((_, got), _), _ = gradients
    assert abs(plain - float(got["loss_ce"])) < 1e-6 * plain


def test_renormalised_top_k_weights_sum_to_one():
    idx, weights, _ = lm.assignments()(lm.params(), lm.batches(steps=1)[0]["features"])
    assert idx.shape == weights.shape == (4, 2 * 36, 3)
    np.testing.assert_allclose(np.asarray(weights).sum(-1), 1.0, rtol=1e-6)
    assert float(np.asarray(weights).min()) > 0


@pytest.mark.parametrize("route", ["fallback", "kernel"])
def test_keeping_the_flash_residuals_changes_no_value_on_the_cpu(route, monkeypatch):
    """`forward` checkpoints each layer with `KEEP_RESIDUALS`; on the kernel
    route (sequence 64, window 16: the banded kernels) a step's jaxpr holds
    ONE forward kernel a layer — three windowed, one full — and the plain
    policy two (counted in the jaxpr, not run); on the fallback route the
    gradients are equal either way to the bit."""
    if route == "kernel":
        monkeypatch.setenv(pallas_attention._INTERPRET_ENV, "1")
        monkeypatch.setenv("EDL_FLASH", "1")
    spec, _ = lm.fresh_trainer(sliding_window=16)
    batch, params = lm.batches(steps=1, seq=64)[0], lm.params()

    kept_policy = pallas_attention.KEEP_RESIDUALS

    def loss(keep):
        monkeypatch.setattr(pallas_attention, "KEEP_RESIDUALS",
                            kept_policy if keep else None)
        return lambda p: lm.terms(spec, p, batch)["loss"]     # a new closure each time

    jaxpr = lambda keep: jax.make_jaxpr(jax.grad(loss(keep)))(params).jaxpr
    grads = lambda keep: jax.jit(jax.grad(loss(keep)))(params)

    if route == "kernel":       # the calls are counted, not run: interpreted
        counts = lambda keep: [     # kernels under a gradient take minutes
            pallas_calls(jaxpr(keep), name) for name in (
                "flash_attention_swa_fwd", "flash_attention_fwd",
                "flash_attention_swa_bwd", "flash_attention_bwd")]
        assert counts(True) == [3, 1, 3, 1]
        assert counts(False) == [6, 2, 3, 1]
        return
    assert pallas_calls(jaxpr(True), "flash_attention_fwd") == 0
    kept, plain = grads(True), grads(False)
    for leaf in LEAVES:
        np.testing.assert_array_equal(np.asarray(kept[leaf]), np.asarray(plain[leaf]))


def test_the_program_counts_its_kernels_grid_steps(monkeypatch):
    """`attn/kv_block_visits` beside `attn/kv_block_visits_causal`, per kind
    [sliding, full], and the share of the pairs held, per layer (blocks of 16
    here, so that 64 tokens are four of them)."""
    for name in ("DEFAULT_BLOCK_Q", "DEFAULT_BLOCK_K"):
        monkeypatch.setattr(pallas_attention, name, 16)
    spec, trainer = lm.fresh_trainer(sliding_window=16)
    batch = lm.batches(steps=1, seq=64)[0]
    state = trainer.init_state(batch)
    for _ in range(2):
        state, _ = trainer.train_step(state, batch)
    banded, causal = pallas_attention.kv_block_visits(64, 64, 16, 16, jnp.float32)
    assert (banded, causal) == (7, 10)
    counted = jax.device_get(state.extra_vars)
    assert counted["attn"]["kv_block_visits"].tolist() == [2 * 3 * banded, 2 * causal]
    assert counted["attn"]["kv_block_visits_causal"].tolist() == [2 * 3 * causal, 2 * causal]
    share = counted["router_state"]["pairs_held_share"]
    assert share.shape == (4,) and np.all((share > 0) & (share < 1))
    assert counted["router_state"]["held_passes"].tolist() == [2, 2, 2, 2]


def test_custom_model_ignores_the_harness_keys_and_trains():
    spec, trainer = lm.trainer(warmup_steps=1)
    model = zoo().custom_model(field_vocab="512", **lm.tiny_params())
    assert model.cfg == spec.model.cfg
    data = lm.batches(steps=1)[0]
    state = trainer.init_state(data)
    losses = []
    for _ in range(8):
        state, m = trainer.train_step(state, data)
        losses.append(float(m["loss"]))
    assert set(m) == {"loss", "loss_ce"} and float(m["loss"]) > float(m["loss_ce"])
    assert losses[-1] < losses[0] - 0.1     # an embedding of size one moves slowly


# ------------------------------------------------------------------ #
# parameter counts: the card's, and the cut's


def _published_params(**more):
    cfg = published()
    return {k: str(getattr(cfg, k)) for k in (
        "vocab_size", "hidden_size", "num_hidden_layers", "num_attention_heads",
        "num_key_value_heads", "head_dim", "sliding_window", "num_experts",
        "num_experts_per_tok", "moe_intermediate_size")} | {k: str(v) for k, v in more.items()}


@pytest.mark.parametrize("more,count", [
    ({}, 12_149_915_904),
    ({"num_hidden_layers": 4, "num_experts": 16, "router_experts": 64,
      "vocab_size": 24576}, 595_153_152)])
def test_parameter_count_uncut_and_at_the_cut(more, count):
    params = _published_params(**more)
    assert flops.parameter_count(params) == count
    model = zoo().custom_model(**params)
    shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))
    assert sum(int(np.prod(leaf.shape))
               for leaf in jax.tree_util.tree_leaves(shapes["params"])) == count


def test_the_card_s_active_parameters():
    assert flops.active_parameter_count(_published_params()) == 2_439_053_568


# ------------------------------------------------------------------ #
# the share of a deployment, tied to the whole (model-configs guide §4)


def test_four_held_shares_make_the_uncut_layer():
    """One feed-forward at 16 experts top-3: the parts that 4 shares of 4
    experts compute (the program's held dispatch) add up to what the
    reference gives for the layer with every expert held — as the cell's
    four shares of 16 make its 64 — and the reference, given a share, gives
    that share's part."""
    m = zoo()
    r = np.random.default_rng(3)
    c, f, e = 48, 24, 16
    normal = lambda *shape: r.normal(size=shape) * 0.2
    whole = {"moe_norm": r.uniform(0.5, 1.5, (c,)), "moe_router": r.normal(size=(c, e)),
             "w_gate": normal(e, c, f), "w_up": normal(e, c, f), "w_down": normal(e, f, c)}
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(r.normal(size=(2, 9, c)), jnp.float32)
    hp_whole = reference.hyper(lm.tiny_params(num_experts=16, first_expert=0))
    with jax.default_matmul_precision("highest"):
        want = jax.jit(lambda p, x: reference.moe(p, x, None, hp_whole)[0])(whole, x)
        total = jnp.zeros_like(x)
        for share in range(4):
            cfg = m.Config(**{**TINY, "first_expert": 4 * share})
            held = slice(4 * share, 4 * share + 4)
            part = {**whole, "w_gate": whole["w_gate"][held], "w_up": whole["w_up"][held],
                    "w_down": whole["w_down"][held]}
            y, _ = jax.jit(lambda p, x: m.moe(p, x, cfg))(part, x)
            total = total + y
            hp = reference.hyper(lm.tiny_params(first_expert=4 * share))
            ref_part = jax.jit(lambda p, x: reference.moe(p, x, None, hp)[0])(part, x)
            np.testing.assert_allclose(y, ref_part, rtol=1e-4, atol=1e-5)
    assert float(jnp.abs(want).max()) > 0.1
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)
